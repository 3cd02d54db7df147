"""Device-side paged storage for attention-family models.

One set of pool arrays per engine, shared by every paged stream, each with
a leading layer axis and then the pages (``arrays``):

    dense / moe :  k, v    (L, N_pages, P, KV, head_dim)
    mla_moe     :  latent  (L, N_pages, P, kv_lora_rank + qk_rope_head_dim)

in the engine's cache dtype. The MLA family caches one normalised latent
and one rotary key per token, shared by every head, instead of per-head K
and V.

The paged decode step (``attn_decode_paged`` / ``mla_decode_paged``)
scatter-writes each row's new token at ``(page_table[row, pos // P],
pos % P)`` and gathers the row's pages back into a dense
``(B, n_pages * P, ...)`` view — shaped EXACTLY like the contiguous cache
when ``page_size`` divides ``max_len``, which is what keeps paged greedy
decode bit-identical to the dense path (stale rows beyond ``pos`` are
masked to exact zeros either way; see layers/attention.py). A join writes
its prompt's rows inside its own executable (``engine._attn_join``).

The paged step and the join take the pool arrays donated and write their
rows in place. A COW copy (``copy_page``) still goes through a functional
``.at[].set`` that replaces the whole pool array — XLA copies the buffer,
which is fine for one page per join.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig


class PagedKVStore:
    """Physical page storage (+ a per-page copy helper) for one engine's
    dense, moe or mla_moe attention stack."""

    def __init__(self, cfg: ModelConfig, num_pages: int, page_size: int,
                 dtype=jnp.float32):
        lead = (cfg.num_layers, num_pages, page_size)
        if cfg.family == "mla_moe":
            self.arrays = {"latent": jnp.zeros(lead + (cfg.mla_latent_dim,),
                                               dtype)}
        elif cfg.family in ("dense", "moe"):
            shape = lead + (cfg.num_kv_heads, cfg.head_dim)
            self.arrays = {"k": jnp.zeros(shape, dtype),
                           "v": jnp.zeros(shape, dtype)}
        else:
            raise NotImplementedError(
                f"PagedKVStore supports dense/moe/mla_moe stacks, not "
                f"{cfg.family}")
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self._sharding = None

    @property
    def nbytes(self) -> int:
        return int(sum(a.nbytes for a in self.arrays.values()))

    @property
    def bytes_per_page(self) -> int:
        return self.nbytes // self.num_pages

    def place(self, sharding) -> None:
        """Pin the pool arrays to a mesh sharding (replicated) so
        mesh-aware paged steps and host-side updates stay on one device
        set. Idempotent per sharding."""
        if sharding is not None and self._sharding is not sharding:
            self.arrays = jax.device_put(self.arrays, sharding)
            self._sharding = sharding

    def copy_page(self, src: int, dst: int) -> None:
        """COW substance: duplicate every layer's rows of ``src`` into
        ``dst`` (the new sole-holder page)."""
        self.arrays = {k: a.at[:, dst].set(a[:, src])
                       for k, a in self.arrays.items()}
