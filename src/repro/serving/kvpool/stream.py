"""Paged continuous-batching decode stream: page tables instead of padding.

``PagedDecodeStream`` is ``DecodeStream``'s drop-in sibling (same
``join``/``step``/``evict``/``pop_finished`` surface, same fixed width and
compile discipline) with per-stream contiguous caches replaced by a chain
of pool pages per slot:

  * ATTENTION families (dense/moe/mla_moe): K/V rows (or MLA latents)
    live in the engine-wide ``PagedKVStore``; each slot owns a page chain
    and the batched step runs ``decode_step_paged`` over (pool arrays, page
    table, positions). The gathered paged view has the dense cache's exact
    shape (``page_size`` divides ``max_len``), identical values at every
    unmasked position, and the identical keep-mask. Prefix reuse is
    STORAGE sharing: fully-covered prompt pages are shared by reference;
    the join still prefills the whole prompt solo, padded to a bucket (a
    power of two of pages), and writes only its real positions on its
    private pages, in one dispatch (``engine._attn_join``), so a stream
    compiles one join per bucket, not one per prompt length. The ``moe``
    family's capacity follows the prefill's length, so its joins prefill
    at the prompt's own length (``bucket``).

  * LSTM family (the paper's architecture): decode carries no per-token
    KV, so pages are LOGICAL accounting (uniform admission / telemetry /
    pressure semantics) and the radix cache's node payloads are recurrent
    state snapshots. A prefix hit is a true COMPUTE skip: prefill resumes
    from the deepest snapshot and runs only the suffix, bit-exactly (a
    restarted scan is the same cell sequence). The whole join — suffix
    prefill padded to the page grid, the state at every page boundary,
    the splice into the slot's row and, on a jittable head, the first
    token — is ONE device dispatch (``engine._lstm_join``, composed with
    the head by ``DecodeEngine._lstm_join_step``) and one fetch; each new
    node's payload is a ``SnapshotHandle`` into that dispatch's stacked
    snapshots.

Sharing is copy-on-write: a slot's first write into a page with other
holders (a cache-pinned prompt tail, a sibling slot's shared prefix)
re-allocates it privately — physically copied for attention families,
pure accounting for LSTM — before the batched step runs, so the jitted
step only ever scatter-writes sole-owner pages (or the trash page, for
idle rows).

Slots grow page-by-page on demand between steps; ``PoolExhausted``
propagates to the scheduler as the pool-pressure signal (nothing is
consumed or advanced when it fires, so the tick can simply retry after
eviction/preemption frees pages).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.serving.engine import _StreamSlot, _tag_id
from repro.serving.kvpool.pool import PagePool, PoolExhausted
from repro.serving.request import ServeRequest
from repro.serving.observe.trace import NULL_TRACER
from repro.serving.resilience.faults import HeadFault, guard_tokens


@dataclass(frozen=True)
class SnapshotHandle:
    """An LSTM radix payload: the recurrent state after one page, as row
    ``index`` of the stacked snapshots (per layer ``{"h", "c"}`` of
    (S, 1, d)) that one join's dispatch returned. Pages of a join share
    its arrays, so storing them costs no device work; the join executable
    slices the row it resumes from."""
    states: Any
    index: int

    def resolve(self) -> list:
        """The state itself, per layer ``{"h", "c"}`` of (1, d)."""
        return [{k: a[self.index] for k, a in layer.items()}
                for layer in self.states]


class PagedDecodeStream:
    """Fixed-width continuous decode over pool pages. See module docstring;
    ``DecodeStream`` documents the shared join/step/evict contract."""

    def __init__(self, engine, head, width: int, pool: PagePool,
                 temperature: Optional[float] = None, top_p: float = 1.0,
                 seed: int = 0, head_name: str = "custom"):
        if width < 1:
            raise ValueError(f"stream width must be >= 1: {width}")
        pool.bind(engine)
        self.engine = engine
        self.head = engine.resolve_head(head)
        self.head_name = head_name
        self.width = int(width)
        self.pool = pool
        self.temperature = temperature
        self.top_p = float(top_p)
        self.seed = int(seed)
        self.sampled = temperature is not None
        if self.sampled:
            self._key = jax.random.key(self.seed)
        # resilience hooks: the scheduler arms the injector; the vocab
        # bound makes the output guards honest-failure detectors too
        self.fault_injector = None
        self.tracer = NULL_TRACER
        self.vocab = int(engine.W.shape[0])
        self.family = engine.model.cfg.family
        # experts this chip holds per expert layer (MoE families), for the
        # step spans
        self.experts_held = engine.model.cfg.experts_held
        self.max_pages = engine.max_len // pool.page_size
        self._repl = None
        if self.head.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            self._repl = NamedSharding(self.head.mesh, PartitionSpec())
        if self.family == "lstm":
            self.cache = engine.model.init_cache(self.width, engine.max_len,
                                                 dtype=engine.cache_dtype)
            # a cold join resumes from row 0 of all-zero snapshots: same
            # shapes as a handle's, so cold and resumed joins share one
            # executable per bucket
            self._zero_snaps = jax.tree.map(
                lambda a: jnp.zeros((self.max_pages, 1, *a.shape[1:]),
                                    a.dtype), self.cache["lstm"])
            if self._repl is not None:
                self.cache, self._zero_snaps = jax.device_put(
                    (self.cache, self._zero_snaps), self._repl)
            self.table = None
        else:
            # per-slot sequence-page -> pool-page map; 0 = trash page, so
            # idle rows gather junk that their mask/discard guarantees
            # never surfaces (see attn_decode_paged)
            self.table = np.zeros((self.width, self.max_pages), np.int32)
            if self._repl is not None and pool.store is not None:
                pool.store.place(self._repl)
        self.tok = np.zeros((self.width,), np.int32)
        self.pos = np.zeros((self.width,), np.int32)
        self.slots: List[Optional[_StreamSlot]] = [None] * self.width
        self._pages: List[List[int]] = [[] for _ in range(self.width)]
        self._finished: List[tuple] = []

    # -- capacity (DecodeStream contract) ------------------------------------
    @property
    def n_active(self) -> int:
        return sum(s is not None for s in self.slots)

    @property
    def free_slots(self) -> int:
        return self.width - self.n_active

    @property
    def idle(self) -> bool:
        return self.n_active == 0 and not self._finished

    @property
    def pages_held(self) -> int:
        return sum(len(c) for c in self._pages)

    def occupied(self) -> List[tuple]:
        return [(i, s.tag) for i, s in enumerate(self.slots) if s is not None]

    def _first_free(self) -> int:
        for i, s in enumerate(self.slots):
            if s is None:
                return i
        raise RuntimeError("PagedDecodeStream is full — check free_slots")

    def _first_token(self, h_last) -> int:
        """The first token by an eager head call: an attention join's, or
        an LSTM join's on a head its executable cannot compose."""
        hd = self.head
        key = None
        with self.tracer.scope("serve.join.first", "join"):
            h_in = h_last if hd.is_jittable else np.asarray(h_last)
            if self.sampled:
                key, k0 = jax.random.split(self._key)
                first = hd.sample(k0, h_in, self.temperature, self.top_p)
            else:
                first = hd.next(h_in)
        return self._commit_first(first, key)

    def _commit_first(self, first, key) -> int:
        """Fetch and guard the first token, then commit the PRNG key the
        draw advanced to (``key``; None on a greedy stream)."""
        # the join's one device sync: everything before it only dispatched
        with self.tracer.scope("serve.join.wait", "join"):
            first = np.asarray(first)
        # guard before the PRNG key (or any stream state) commits — join's
        # page rollback plus an unconsumed key make the retry bit-identical
        first = int(guard_tokens(self.fault_injector, "join", self.head_name,
                                 first, self.vocab).ravel()[0])
        if self.sampled:
            self._key = key
        return first

    # -- join -----------------------------------------------------------------
    def join(self, request: ServeRequest, tag: object = None) -> int:
        """Admit one request: radix-match its prompt, share/COW/allocate its
        page chain, prefill (resumed for LSTM, solo for attention), splice.
        Raises ``PoolExhausted`` — with every page reference this join took
        rolled back — when the pool cannot back the prompt."""
        eng = self.engine
        Tp = int(request.prompt.shape[0])
        if Tp + request.max_new > eng.max_len:
            raise ValueError(
                f"request needs {Tp + request.max_new} cache slots, stream "
                f"max_len is {eng.max_len}")
        slot = self._first_free()
        with self.tracer.scope("serve.join.match", "join"):
            toks = [int(t) for t in request.prompt]
            match = self.pool.radix.match(toks)
        held: List[int] = []                      # page refs this join owns
        try:
            if self.family == "lstm":
                first = self._join_lstm(slot, request, toks, match, held)
            else:
                first = self._join_attn(request, toks, match, held)
        except (PoolExhausted, HeadFault):
            # same rollback either way: the pool cannot back the prompt OR
            # the head faulted mid-join — every page ref this join took is
            # released and the stream is exactly as it was
            for pg in held:
                self.pool.release(pg)
            raise
        self._pages[slot] = held
        if self.table is not None:
            self.table[slot, :] = 0
            self.table[slot, :len(held)] = held
        self.tok[slot] = first
        self.pos[slot] = Tp
        entry = _StreamSlot(tag=tag, request=request, tokens=[first],
                            remaining=request.max_new - 1)
        if entry.remaining == 0:
            self._finished.append(
                (entry.tag, entry.request, np.asarray(entry.tokens, np.int32)))
            self._release_chain(slot)
        else:
            self.slots[slot] = entry
        return slot

    def _join_lstm(self, slot, request, toks, match, held) -> int:
        """Resume prefill from the deepest cached snapshot over the suffix,
        padded up to the page grid, snapshotting at every page boundary so
        the whole prompt inserts as radix nodes, splice the final state
        into ``slot`` and, on a jittable head, compute the first token: one
        dispatch. The new cache (and a sampled stream's key) commits only
        after the first token's guard and the page bookkeeping pass, so a
        fault leaves the stream as it was. Returns the first token."""
        eng, pool, tr = self.engine, self.pool, self.tracer
        P, Tp, S = pool.page_size, len(toks), self.max_pages
        t = match.n_full                      # snapshot exists exactly here
        for pg, _ in match.chain:
            held.append(pool.retain(pg))
        n = Tp - t
        # the page grid buckets the suffix; a whole-prompt hit (n == 0)
        # runs no step and rides the smallest bucket, already compiled
        bucket = max(P, -(-n // P) * P)
        # page-grid ends in (t, Tp]: each closes a node that gets a snapshot
        ends = [*range((t // P + 1) * P, Tp, P), Tp] if n else []
        with tr.scope("serve.join.prefill", "join"):
            src = match.payload
            states, k = (self._zero_snaps, 0) if src is None \
                else (src.states, src.index)
            ints = np.zeros(3 + S + bucket, np.int32)
            ints[:3] = n, k, slot
            ints[3:3 + len(ends)] = np.asarray(ends, np.int32) - t - 1
            ints[3 + S:3 + S + n] = request.prompt[t:]
            args = (ints, states, self.cache)
            # a jittable head's first token comes out of the join's own
            # executable, as the decode step composes its head
            folded, key = self.head.is_jittable, None
            if not folded:
                h_last, snaps, cache = eng._jit_lstm_join(eng.params, *args)
            elif self.sampled:
                fn = eng._lstm_join_step(self.head, self.temperature,
                                         self.top_p)
                first, key, snaps, cache = fn(eng.params, self._key, *args)
            else:
                fn = eng._lstm_join_step(self.head)
                first, snaps, cache = fn(eng.params, *args)
            if tr.enabled:
                tr.note(bucket=bucket, snapshots=len(ends),
                        first_in_join=int(folded))
        if tr.enabled:
            tr.note(cached_tokens=t, chunks=int(n > 0))
        first = self._commit_first(first, key) if folded \
            else self._first_token(h_last)
        with tr.scope("serve.join.splice", "join"):
            # page chain: a partially-covered grid slot being EXTENDED must
            # go private now (logical COW — its node's snapshot stops at t,
            # ours will stop deeper); fresh pages back the remaining slots
            n_prompt = (Tp + P - 1) // P
            if t < Tp and t % P:
                # in-place swap: if cow's alloc raises, held[-1] still names
                # the shared ref so join's rollback releases it — no leak
                held[-1] = pool.cow(held[-1])
            while len(held) < n_prompt:
                held.append(pool.alloc())
            payloads: List[object] = [None] * n_prompt
            for j, end in enumerate(ends):
                payloads[(end - 1) // P] = SnapshotHandle(snaps, j)
            pool.radix.insert(toks, held[:n_prompt], payloads)
            pool.radix.record(t, Tp)
            self.cache = cache
        return first

    def bucket(self, n: int) -> int:
        """The padded length a join of an ``n``-token prompt prefills at:
        its pages rounded up to a power of two, at most ``max_len``. A
        capacity-bounded expert layer (``moe`` family) sizes its capacity
        from the prefill's length, so padding would keep tokens that the
        contiguous prefill drops: its joins stay at the prompt's length."""
        if self.family == "moe":
            return n
        P = self.pool.page_size
        pages = 1 << max(0, -(-n // P) - 1).bit_length()
        return min(pages * P, self.engine.max_len)

    def _join_attn(self, request, toks, match, held) -> int:
        """Storage-shared full prefix pages, private pages for the rest,
        then one dispatch: the prompt prefilled solo at its bucket, its
        real positions past the shared pages written into the private
        pages, in place (the pool is donated, so the returned one is kept at
        once; a join that faults later wrote only pages its rollback frees,
        and freed pages' rows are never read). Returns the first token."""
        eng, pool = self.engine, self.pool
        P, Tp = pool.page_size, len(toks)
        n_prompt = (Tp + P - 1) // P
        # share only FULLY-covered grid slots; a partial slot is rewritten
        # from our own prefill on a private page (counted as a COW when it
        # displaces a matched partial node's page)
        for pg, nv in match.chain:
            if nv == P:
                held.append(pool.retain(pg))
        j0 = len(held)
        if match.chain and match.chain[-1][1] < P:
            # displace the matched partial node's page with a private one
            # (two steps so a cow failure leaves the retained ref in held
            # for join's rollback)
            held.append(pool.retain(match.chain[-1][0]))
            held[-1] = pool.cow(held[-1])
        while len(held) < n_prompt:
            held.append(pool.alloc())
        store, tr = pool.store, self.tracer
        with tr.scope("serve.join.prefill", "join"):
            T = self.bucket(Tp)
            t = np.arange(T)
            own = (t >= j0 * P) & (t < Tp)
            dest = np.stack([np.full(T, store.num_pages), t % P]).astype(
                np.int32)
            dest[0, own] = np.asarray(held, np.int32)[t[own] // P]
            tokens = np.zeros((1, T), np.int32)
            tokens[0, :Tp] = request.prompt
            args = (eng.params, tokens, np.int32(Tp), store.arrays, dest)
            if self._repl is not None:
                args = jax.device_put(args, self._repl)
            h_last, store.arrays = eng._jit_attn_join(*args)
            if tr.enabled:
                tr.note(prompt_len=Tp, bucket=T)
        if tr.enabled:
            tr.note(cached_tokens=j0 * P, chunks=1)
        first = self._first_token(h_last)
        with tr.scope("serve.join.splice", "join"):
            pool.radix.insert(toks, held[:n_prompt])
            pool.radix.record(j0 * P, Tp)
        return first

    # -- step -----------------------------------------------------------------
    def _ensure_pages(self, idx) -> None:
        """Every active row must own a WRITABLE page at its write position
        before the batched step scatters into the pool: grow chains page-by
        -page, COW pages with other holders. Raises ``PoolExhausted`` with
        nothing consumed (completed allocations stay in their chains and
        are reused on retry)."""
        P = self.pool.page_size
        for i in idx:
            j = int(self.pos[i]) // P
            chain = self._pages[i]
            if j == len(chain):
                chain.append(self.pool.alloc())
            else:
                chain[j] = self.pool.ensure_writable(chain[j])
            if self.table is not None:
                self.table[i, j] = chain[j]

    def step(self) -> List[tuple]:
        """One batched decode tick; same contract as ``DecodeStream.step``.
        May raise ``PoolExhausted`` BEFORE any state advances — the
        scheduler frees pages (cache eviction / preemption) and re-ticks."""
        idx = [i for i, s in enumerate(self.slots) if s is not None]
        if not idx:
            return self.pop_finished()
        tr = self.tracer
        with tr.scope("serve.step", "step"):
            if tr.enabled:
                tr.note(head=self.head_name, active=len(idx),
                        rids=[_tag_id(self.slots[i].tag) for i in idx])
                if self.experts_held:
                    tr.note(experts_held=self.experts_held)
            with tr.scope("serve.step.pages", "step"):
                self._ensure_pages(idx)
            out = self._finished
            self._finished = []
            eng = self.engine
            tok = jnp.asarray(self.tok)
            pos = jnp.asarray(self.pos)
            # compute into locals and commit (cache, PRNG) only after the
            # guard — a step fault advances nothing, so a retry re-runs the
            # identical step (pages grown by _ensure_pages stay in their
            # chains and are simply reused, same as the PoolExhausted retry
            # contract). The page pool is donated and kept at once: the
            # step wrote each row's entry at its own position, which a
            # retry rewrites with the same values
            key = cache = None
            if self.family == "lstm":
                # the SAME cached dense step DecodeStream uses — the paged
                # LSTM path adds zero step executables by construction
                if self.sampled:
                    fn = eng._sample_step(self.head, self.temperature,
                                          self.top_p)
                    key, ki = jax.random.split(self._key)
                    nxt, _, cache = fn(eng.params, ki, tok, self.cache, pos)
                else:
                    fn = eng._greedy_step(self.head)
                    nxt, _, cache = fn(eng.params, tok, self.cache, pos)
            else:
                store = self.pool.store
                table = jnp.asarray(self.table)
                if self.sampled:
                    fn = eng._paged_sample_step(self.head, self.temperature,
                                                self.top_p)
                    key, ki = jax.random.split(self._key)
                    nxt, _, store.arrays = fn(eng.params, ki, tok,
                                              store.arrays, table, pos)
                else:
                    fn = eng._paged_greedy_step(self.head)
                    nxt, _, store.arrays = fn(eng.params, tok, store.arrays,
                                              table, pos)
            with tr.scope("serve.step.wait", "step"):
                nxt = np.asarray(nxt)
            nxt = guard_tokens(self.fault_injector, "step", self.head_name,
                               nxt, self.vocab, rows=idx)
            if self.sampled:
                self._key = key
            if self.family == "lstm":
                self.cache = cache
            for i in idx:
                s = self.slots[i]
                t = int(nxt[i])
                s.tokens.append(t)
                s.remaining -= 1
                self.tok[i] = t
                self.pos[i] += 1
                if s.remaining == 0:
                    out.append((s.tag, s.request,
                                np.asarray(s.tokens, np.int32)))
                    self.slots[i] = None
                    self._release_chain(i)
        return out

    def pop_finished(self) -> List[tuple]:
        out = self._finished
        self._finished = []
        return out

    # -- evict / release -------------------------------------------------------
    def _release_chain(self, slot: int) -> None:
        for pg in self._pages[slot]:
            self.pool.release(pg)
        self._pages[slot] = []
        if self.table is not None:
            self.table[slot, :] = 0
        self.pos[slot] = 0               # park: trash-page writes, discarded
        self.tok[slot] = 0

    def evict(self, slot: int) -> tuple:
        """Preemption hook: retire a slot, RELEASING its page chain (shared
        prefix pages just drop one holder; sole-owner pages free)."""
        s = self.slots[slot]
        if s is None:
            raise ValueError(f"slot {slot} is not occupied")
        self.slots[slot] = None
        self._release_chain(slot)
        return (s.tag, s.request, np.asarray(s.tokens, np.int32))
