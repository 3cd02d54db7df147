"""Paged KV-cache pool: block-paged KV memory with a copy-on-write
shared-prefix radix cache.

Layout:
  * pool.py   — ``PagePool``: refcounted fixed-size page allocator,
                ``PoolExhausted``, COW primitives, telemetry.
  * radix.py  — ``RadixCache``: token-prefix tree mapping page-grid
                chunks of prompts to shared pages (LSTM nodes also carry
                recurrent-state snapshots), LRU leaf reclamation.
  * store.py  — ``PagedKVStore``: device tensors holding attention K/V
                pages, join-time prompt scatter, physical COW copy.
  * stream.py — ``PagedDecodeStream``: the continuous-batching decode
                stream the scheduler serves on, over pool pages.
"""
from repro.serving.kvpool.pool import TRASH_PAGE, PagePool, PoolExhausted
from repro.serving.kvpool.radix import PrefixMatch, RadixCache
from repro.serving.kvpool.store import PagedKVStore
from repro.serving.kvpool.stream import PagedDecodeStream

__all__ = [
    "TRASH_PAGE",
    "PagePool",
    "PoolExhausted",
    "PrefixMatch",
    "RadixCache",
    "PagedKVStore",
    "PagedDecodeStream",
]
