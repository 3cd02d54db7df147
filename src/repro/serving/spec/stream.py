"""SpecDecodeStream — continuous-batching speculative decode.

One round = n DRAFT trunk steps through the engine's cached vector-pos
decode step composed with a cheap draft head, then ONE batched VERIFY call
of the target head over the stacked draft hidden states. The draft and
verify heads share the model trunk, so the hidden state each draft step
produces IS the exact trunk state the verify head needs — verification
never runs a second forward. The win is the memory wall: plain exact decode
streams the (V, d) softmax weights from HBM once per token; batched verify
streams them once per ROUND of up to n tokens.

Round anatomy (per slot, 0-indexed; T0 = the slot's pending token at round
start, pos0 its position):

  draft step i consumes token d_{i-1} (d_{-1} = T0) at pos0 + i, yields
  hidden h_i, and the draft head picks d_i from h_i. After n steps the
  verify head scores every h_i in one call:

  greedy   e_i = verify.next(h_i); accept a = longest prefix d_i == e_i.
           Emit d_0..d_{a-1} (+ correction e_a when a < n): every emitted
           token is the exact head's greedy choice — BIT-identical to solo
           exact decode (tests pin this).
  sampled  standard rejection rule over (q_i, p_i) = nucleus/temperature-
           adjusted dist_logits of draft and verify heads — emitted tokens
           follow the TARGET law exactly (spec/acceptance.py). Requires an
           UNSHARDED verify head with ``supports_dist``.

Rollback of rejected draft positions:
  * attention caches need NONE — the ``arange(S) <= pos`` keep-mask of
    ``attn_decode`` hides slots beyond the resumed position exactly
    (NEG_INF → exp 0.0), and decode overwrites them when it re-reaches
    those positions.
  * recurrent state (lstm / ssm / hybrid — and ring-buffer sliding-window
    attention, whose overwritten old slots cannot be masked back) is
    SNAPSHOT per draft step. jax arrays are immutable, so a snapshot is a
    pytree reference — no copy; restore stacks the n snapshots and
    fancy-indexes one per row.

Compile discipline: drafts ride the engine's cached ``_greedy_step`` /
``_sample_step`` (the SAME executables plain streams use); verify rides
``_spec_verify_step`` / ``_spec_dist_step``, padded to a FIXED n_max so the
adaptive ``DraftLenController`` shrinking n never re-traces. Zero new
executables after warmup (``compiled_step_counts`` is the audit).

KV paging: each slot takes a LOGICAL page reservation from the stream's
``kv_pool`` — ``ceil((Tp + max_new + n_max − 1) / page_size)`` pages, the
``n_max − 1`` slack being the rejected-token positions a round can
transiently write past the request's final token. Reservations give the
pool's admission/pressure machinery real numbers (``PoolExhausted``
propagates from ``join``); the decode itself stays in the stream's private
contiguous cache, and spec slots never dedupe prefixes through the radix
cache.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.serving.request import ServeRequest
from repro.serving.observe.trace import NULL_TRACER
from repro.serving.resilience.faults import guard_tokens
from repro.serving.spec.acceptance import accept_draft, greedy_accept_lengths
from repro.serving.spec.policy import DraftLenController


@dataclass
class _SpecSlot:
    """One occupied slot of a SpecDecodeStream."""
    tag: object
    request: ServeRequest
    tokens: list
    remaining: int
    pages: list = field(default_factory=list)   # kv_pool reservation


def _needs_snapshot(cfg) -> bool:
    """Families whose decode state cannot be rolled back by position
    masking alone: recurrent state advances destructively, and ring-buffer
    sliding windows overwrite the oldest slots during the draft run."""
    return cfg.family in ("lstm", "ssm", "hybrid") or \
        getattr(cfg, "sliding_window", None) is not None


def _splice_cache(group, solo, slot, cfg):
    """Write a solo (B=1) prefilled cache into row ``slot`` of a width-W
    stream cache. LSTM state lists carry batch at axis 0; stacked
    attention/ssm caches at axis 1."""
    if cfg.family == "lstm":
        return {"lstm": [{k: g[k].at[slot].set(s[k][0]) for k in g}
                         for g, s in zip(group["lstm"], solo["lstm"])]}
    return jax.tree_util.tree_map(lambda g, s: g.at[:, slot].set(s[:, 0]),
                                  group, solo)


def _select_snapshots(snaps, sel, cfg):
    """Per-row snapshot restore: ``snaps[j]`` is the cache pytree after
    draft step j; row i resumes from ``snaps[sel[i]]``. Batch-axis split
    mirrors ``_splice_cache``: LSTM state lists carry batch at axis 0,
    stacked caches at axis 1."""
    sel = jnp.asarray(np.asarray(sel, np.int32))
    rows = jnp.arange(sel.shape[0])
    if cfg.family == "lstm":
        out = []
        for li in range(len(snaps[0]["lstm"])):
            layer = {}
            for k in snaps[0]["lstm"][li]:
                stacked = jnp.stack([s["lstm"][li][k] for s in snaps])
                layer[k] = stacked[sel, rows]          # (W, hidden)
            out.append(layer)
        return {"lstm": out}

    def pick(*leaves):
        stacked = jnp.stack(leaves)                    # (n, L, W, ...)
        return jnp.moveaxis(stacked[sel, :, rows], 0, 1)
    return jax.tree_util.tree_map(pick, *snaps)


class SpecDecodeStream:
    """A scheduler lane (the join/step/evict/pop_finished/occupied contract
    of ``repro.serving.kvpool.PagedDecodeStream``) that decodes
    speculatively over its own dense cache.

    One ``step()`` is one whole draft/verify ROUND, emitting 1..n tokens
    per active slot (a plain stream emits exactly 1). The first token after
    a join comes from the VERIFY head (the prefill's last hidden state is
    free), so output starts exact from token one.
    """

    def __init__(self, engine, draft_head, verify_head, width: int = 4,
                 draft_len: int = 4, temperature: Optional[float] = None,
                 top_p: float = 1.0, seed: int = 0,
                 draft_name: str = "draft", verify_name: str = "verify",
                 controller: Optional[DraftLenController] = None, *,
                 kv_pool):
        if width < 1:
            raise ValueError(f"stream width must be >= 1: {width}")
        if draft_len < 1:
            raise ValueError(f"draft_len must be >= 1: {draft_len}")
        self.engine = engine
        self.draft_head = engine.resolve_head(draft_head)
        self.verify_head = engine.resolve_head(verify_head)
        if self.draft_head.step_key() == self.verify_head.step_key():
            raise ValueError(
                "speculative decode needs DISTINCT draft and verify heads "
                f"(both resolved to {verify_name!r})")
        self.width = int(width)
        self.n_max = int(draft_len)
        self.draft_name = draft_name
        self.verify_name = verify_name
        self.head_name = f"{verify_name}+spec[{draft_name}]"
        self.temperature = temperature
        self.top_p = float(top_p)
        self.seed = int(seed)
        # temperature <= 0 is argmax — decode through the greedy machinery
        self.sampled = temperature is not None and float(temperature) > 0
        if self.sampled:
            if (self.verify_head.n_shards or 1) > 1:
                raise ValueError(
                    "sampled speculative decode needs an unsharded verify "
                    "head (sharded verify is greedy-only: full-vocab "
                    "distribution rows are never gathered)")
            for role, hd in (("draft", self.draft_head),
                             ("verify", self.verify_head)):
                if not getattr(hd, "supports_dist", False):
                    raise ValueError(
                        f"sampled speculative decode needs dist_logits on "
                        f"the {role} head ({getattr(hd, 'name', role)!r} "
                        f"has supports_dist=False)")
            self._key = jax.random.key(self.seed)
            # rejection/residual draws: own deterministic host chain,
            # consumed in slot order each round
            self._nprng = np.random.default_rng(self.seed + 0x5bec)
        self.controller = controller
        self.kv_pool = kv_pool
        # resilience hooks: the scheduler arms the injector; draft and
        # verify boundaries guard under their OWN head names so a breaker
        # can trip the draft alone (degrade to plain decode)
        self.fault_injector = None
        self.tracer = NULL_TRACER
        self.vocab = int(engine.W.shape[0])
        self._snapshot = _needs_snapshot(engine.model.cfg)
        self.cache = engine.model.init_cache(self.width, engine.max_len,
                                             dtype=engine.cache_dtype)
        self._repl = None
        if self.draft_head.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            self._repl = NamedSharding(self.draft_head.mesh, PartitionSpec())
            self.cache = jax.device_put(self.cache, self._repl)
        self.tok = np.zeros((self.width,), np.int32)
        self.pos = np.zeros((self.width,), np.int32)
        self.slots: List[Optional[_SpecSlot]] = [None] * self.width
        self._finished: List[tuple] = []
        # telemetry (cumulative; the scheduler diffs spec_counters()).
        # ``rounds`` counts PER-SLOT verify rounds (one per active slot per
        # tick), so emitted/rounds is the per-sequence accepted-tokens-per-
        # step — a plain stream scores exactly 1.0 on the same metric.
        self.rounds = 0
        self.draft_steps = 0
        self.drafted = 0
        self.accepted = 0
        self.emitted = 0
        self.verify_queries = 0
        self.verify_flops = 0.0

    # -- capacity ------------------------------------------------------------
    @property
    def n_active(self) -> int:
        return sum(s is not None for s in self.slots)

    @property
    def free_slots(self) -> int:
        return self.width - self.n_active

    @property
    def idle(self) -> bool:
        return self.n_active == 0 and not self._finished

    def occupied(self) -> List[tuple]:
        return [(i, s.tag) for i, s in enumerate(self.slots) if s is not None]

    def _first_free(self) -> int:
        for i, s in enumerate(self.slots):
            if s is None:
                return i
        raise RuntimeError("SpecDecodeStream is full — check free_slots")

    def spec_counters(self) -> dict:
        """Cumulative round telemetry (the scheduler diffs consecutive
        snapshots into ``ServerStats.record_spec``)."""
        return {"rounds": self.rounds, "draft_steps": self.draft_steps,
                "drafted": self.drafted, "accepted": self.accepted,
                "emitted": self.emitted,
                "verify_queries": self.verify_queries,
                "verify_flops": self.verify_flops}

    # -- join ----------------------------------------------------------------
    def join(self, request: ServeRequest, tag: object = None) -> int:
        """Solo prefill + cache splice, first token from the VERIFY head.
        Needs ``Tp + max_new + n_max − 1 <= max_len`` — rejected draft
        positions can transiently write up to n_max − 1 slots past the
        request's final token."""
        eng = self.engine
        Tp = int(request.prompt.shape[0])
        need = Tp + request.max_new + self.n_max - 1
        if need > eng.max_len:
            raise ValueError(
                f"spec request needs {need} cache slots (prompt {Tp} + "
                f"max_new {request.max_new} + draft overshoot "
                f"{self.n_max - 1}), stream max_len is {eng.max_len}")
        slot = self._first_free()
        pages = []
        # ANY failure between here and the guard — pool exhaustion OR a
        # head fault mid-prefill — releases the reservation and leaves the
        # stream untouched (splice/PRNG commit only after the guard passes)
        try:
            P = self.kv_pool.page_size
            for _ in range(-(-need // P)):
                pages.append(self.kv_pool.alloc())
            cache1 = eng.model.init_cache(1, eng.max_len,
                                          dtype=eng.cache_dtype)
            h, cache1 = eng._jit_prefill(
                eng.params, {"tokens": jnp.asarray(request.prompt[None])},
                cache1)
            h_last = h[:, -1]
            vh = self.verify_head
            h_in = h_last if vh.is_jittable else np.asarray(h_last)
            if self.sampled:
                key, k0 = jax.random.split(self._key)
                first = vh.sample(k0, h_in, self.temperature, self.top_p)
            else:
                first = vh.next(h_in)
            first = int(guard_tokens(self.fault_injector, "join",
                                     self.verify_name, first,
                                     self.vocab).ravel()[0])
        except Exception:
            for pg in pages:
                self.kv_pool.release(pg)
            raise
        if self.sampled:
            self._key = key
        if self._repl is not None:
            cache1 = jax.device_put(cache1, self._repl)
        self.cache = _splice_cache(self.cache, cache1, slot, eng.model.cfg)
        self.tok[slot] = first
        self.pos[slot] = Tp
        entry = _SpecSlot(tag=tag, request=request, tokens=[first],
                          remaining=request.max_new - 1, pages=pages)
        if entry.remaining == 0:
            self._release_pages(entry)
            self._finished.append(
                (entry.tag, entry.request, np.asarray(entry.tokens,
                                                      np.int32)))
        else:
            self.slots[slot] = entry
        return slot

    def _release_pages(self, entry: _SpecSlot) -> None:
        for pg in entry.pages:
            self.kv_pool.release(pg)
        entry.pages = []

    # -- the round -----------------------------------------------------------
    def step(self) -> List[tuple]:
        """One draft/verify round. Returns retired (tag, request, tokens)
        triples, like ``PagedDecodeStream.step``."""
        out = self._finished
        self._finished = []
        idx = [i for i, s in enumerate(self.slots) if s is not None]
        if not idx:
            return out
        eng = self.engine
        n = self.n_max if self.controller is None else \
            min(max(self.controller.n, 1), self.n_max)
        start_pos = self.pos.copy()
        if self.sampled:
            draft_fn = eng._sample_step(self.draft_head, self.temperature,
                                        self.top_p)
        else:
            draft_fn = eng._greedy_step(self.draft_head)
        tok = jnp.asarray(self.tok)
        pos = self.pos.copy()
        cache = self.cache
        tr = self.tracer
        draft_t0 = tr.now() if tr.enabled else 0.0
        hs, drafts, snaps = [], [], []
        for _ in range(n):
            pvec = jnp.asarray(pos)
            if self.sampled:
                self._key, ki = jax.random.split(self._key)
                tok, h, cache = draft_fn(eng.params, ki, tok, cache, pvec)
            else:
                tok, h, cache = draft_fn(eng.params, tok, cache, pvec)
            hs.append(h)
            drafts.append(np.asarray(tok))
            if self._snapshot:
                snaps.append(cache)
            pos += 1
        drafts = np.stack(drafts, axis=1)                    # (W, n)
        if tr.enabled:
            tr.span("spec.draft", "kernel", draft_t0,
                    args={"head": self.draft_name, "n": n,
                          "active": len(idx)})
        verify_t0 = tr.now() if tr.enabled else 0.0
        hs = hs + [hs[-1]] * (self.n_max - n)                # pad to n_max
        if self.sampled:
            fn = eng._spec_dist_step(self.draft_head, self.verify_head,
                                     self.n_max, self.temperature,
                                     self.top_p)
            q, p = fn(*hs)
            q = np.asarray(q)                                # (n_max, W, V)
            p = np.asarray(p)
        else:
            fn = eng._spec_verify_step(self.verify_head, self.n_max)
            exact_ids = np.asarray(fn(*hs))                  # (n_max, W)
            acc_len = greedy_accept_lengths(
                drafts, exact_ids[:n].T)                     # (W,)

        # guard BEFORE the apply loop: every commit (tok/pos/slots/cache)
        # lives below, so a draft- or verify-boundary fault rolls the whole
        # round back and a greedy retry replays it bit-identically. Draft
        # and verify guard under their own head names — the scheduler can
        # strip a faulting draft and keep decoding plain on the verify head
        guard_tokens(self.fault_injector, "draft", self.draft_name,
                     drafts[idx], self.vocab)
        if self.sampled:
            if self.fault_injector is not None:
                self.fault_injector.raise_for("verify", self.verify_name)
        else:
            guard_tokens(self.fault_injector, "verify", self.verify_name,
                         exact_ids[:n][:, idx], self.vocab)
        if tr.enabled:
            tr.span("spec.verify", "kernel", verify_t0,
                    args={"head": self.verify_name, "n_max": self.n_max,
                          "active": len(idx)})

        sel = np.full((self.width,), n - 1, np.int32)        # snapshot index
        round_accepted = round_emitted = 0
        for i in idx:
            s = self.slots[i]
            if self.sampled:
                emitted, a = accept_draft(self._nprng, drafts[i],
                                          q[:n, i], p[:n, i])
            else:
                a = int(acc_len[i])
                emitted = [int(t) for t in drafts[i, :a]]
                if a < n:
                    emitted.append(int(exact_ids[a, i]))
            round_accepted += a
            take = min(len(emitted), s.remaining)
            s.tokens.extend(emitted[:take])
            s.remaining -= take
            round_emitted += take
            if a == n:
                self.tok[i] = int(drafts[i, n - 1])
                self.pos[i] = int(start_pos[i]) + n
                sel[i] = n - 1
            else:
                self.tok[i] = int(emitted[a])
                self.pos[i] = int(start_pos[i]) + a + 1
                sel[i] = a
            if s.remaining == 0:
                self._release_pages(s)
                out.append((s.tag, s.request, np.asarray(s.tokens,
                                                         np.int32)))
                self.slots[i] = None
        if self._snapshot and any(sel[i] != n - 1 for i in idx):
            cache = _select_snapshots(snaps, sel, eng.model.cfg)
        self.cache = cache
        # telemetry + adaptive draft length
        self.rounds += len(idx)
        self.draft_steps += n
        self.drafted += n * len(idx)
        self.accepted += round_accepted
        self.emitted += round_emitted
        self.verify_queries += self.n_max * self.width
        vfl = self.verify_head.flops_per_query
        if vfl == vfl:                                        # NaN-safe
            self.verify_flops += float(vfl) * self.n_max * self.width
        if self.controller is not None and idx:
            self.controller.observe(round_accepted / float(n * len(idx)))
        return out

    def pop_finished(self) -> List[tuple]:
        out = self._finished
        self._finished = []
        return out

    def evict(self, slot: int) -> tuple:
        s = self.slots[slot]
        if s is None:
            raise ValueError(f"slot {slot} is not occupied")
        self._release_pages(s)
        self.slots[slot] = None
        return (s.tag, s.request, np.asarray(s.tokens, np.int32))
