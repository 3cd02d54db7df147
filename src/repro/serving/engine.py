"""Batched decode engine: prefill → token-by-token generation through a
pluggable ``SoftmaxHead``.

The head is the ONE seam: greedy decode, temperature/nucleus sampling, and
beam search all route next-token selection through ``head.next`` /
``head.sample`` / ``head.topk_logprobs``. A head is a registry name
("exact", "screened", "screened-pallas", "svd", ...) resolved against the
engine's (W, b, screen) context, or a ready ``SoftmaxHead`` instance — and
is switchable PER REQUEST: every public method takes ``head=`` overriding
the engine default.

Compilation discipline: the model prefill/decode step is jitted once at
engine init; per-head composed steps (decode + head.next) are jitted once
per head and cached, and head-side top-k/log-prob functions are
module-level jits with static k — nothing re-wraps ``jax.jit`` per
invocation. Non-jittable heads (the numpy §4.1 baselines) run on the host
side of the jitted decode step. Vocab-SHARDED heads (``head.mesh`` set)
get a mesh-aware composed step: inputs are pinned replicated over the
head's mesh via ``in_shardings`` so the decode step and the head's
shard_map share one device set — still one compilation per head.

Beam search follows the paper's §4.2 protocol: log-softmax over the head's
reduced candidate space, probability 0 (−inf log-prob) elsewhere.

Request-centric serving: ``serve_batch(requests, policy=...)`` takes
``ServeRequest``s (repro.serving.request), resolves each to a head name
through a ``RoutingPolicy`` (repro.serving.router), groups requests by
(resolved head, prompt length, sampling statics), pads each group to one
batched decode over the SAME cached jitted steps ``generate`` uses — so a
mixed batch causes zero new step compilations after warmup — and scatters
``ServeResult``s back in request order.

Continuous batching: ``open_stream(head, width)`` returns a
``DecodeStream`` — a FIXED-width batched decode whose pad slots are live
capacity. ``join`` prefills one request solo and splices its cache rows
into a free slot mid-decode (per-row positions ride the vector-``pos``
branch of ``attn_decode``); ``step`` advances every active slot one token
through the same cached jitted steps; finished slots retire and free
their pad slot for the next join. The ``repro.serving.scheduler``
subsystem builds its tick loop on exactly these three hooks.
"""
from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import heads as heads_registry
from repro.core.screening import ScreenParams
from repro.heads.base import MissingScreenError, SoftmaxHead
from repro.models.model import Model
from repro.serving.observe.trace import NULL_TRACER
from repro.serving.request import ServeRequest, ServeResult
from repro.serving.resilience.faults import guard_tokens

HeadLike = Union[str, SoftmaxHead]

# serve_batch sentinel: "route to the engine's default head instance" —
# never a valid registry name, never resolved through the registry
_ENGINE_DEFAULT = "__engine-default__"


@dataclass
class GenerationResult:
    tokens: np.ndarray              # (B, T_new) generated ids
    scores: Optional[np.ndarray] = None
    steps: int = 0


class DecodeEngine:
    def __init__(self, model: Model, params, head: HeadLike = "exact",
                 screen: Optional[ScreenParams] = None, max_len: int = 512,
                 cache_dtype=jnp.float32, head_kwargs: Optional[dict] = None):
        """``head``: default decode head — a registry name or an instance.
        ``screen``: L2S screen handed to screening heads resolved by name.
        ``head_kwargs``: extra construction kwargs for name resolution
        (e.g. ``{"rho": 32}``, ``{"local": "pallas"}``)."""
        self.model = model
        self.params = params
        self.screen = screen
        self.max_len = max_len
        self.cache_dtype = cache_dtype
        W, b = model.softmax_weights(params)
        self.W, self.b = W, b
        self._head_kwargs = dict(head_kwargs or {})
        self._head_cache: Dict[str, SoftmaxHead] = {}
        # bounded LRU: steps are cheap to rebuild but hold compiled
        # executables; per-request temperatures must not accumulate entries
        # forever. Keys use head.step_key() — a stable identity over the
        # head's underlying arrays — so transient instances of the same
        # prepared head hit (and refresh) the hot entry instead of filling
        # the cache and evicting it. Least-recently-USED is evicted.
        self._step_cache: "OrderedDict[tuple, callable]" = OrderedDict()
        self._step_cache_max = 32
        self._jit_prefill = jax.jit(
            lambda p, batch, cache: model.prefill(p, batch, cache))
        self._jit_decode = jax.jit(
            lambda p, tok, cache, pos: model.decode_step(p, tok, cache, pos))
        # paged-serving companions (repro.serving.kvpool): prefill resumed
        # from a cached recurrent state (LSTM prefix-cache compute skip) and
        # the page-table decode step for non-jittable heads
        self._jit_resume_prefill = jax.jit(
            lambda p, batch, cache: model.prefill(p, batch, cache,
                                                  resume=True))
        # the paged LSTM join as one executable (see _lstm_join): suffix
        # prefill padded to the page grid, its page snapshots, and the splice
        # of its final state into the stream's cache. A jittable head's join
        # also computes the first token (_lstm_join_step); this one serves
        # heads that run on the host
        self._jit_lstm_join = jax.jit(
            lambda p, ints, src, group: _lstm_join(model, p, ints, src,
                                                   group))
        # the paged attention join as one executable (see _attn_join): the
        # prompt prefilled at its bucket, its rows written into the pages
        self._jit_attn_join = jax.jit(
            lambda p, tokens, n, pool, dest: _attn_join(model, p, tokens, n,
                                                        pool, dest),
            donate_argnums=(3,))
        self._jit_decode_paged = jax.jit(
            lambda p, tok, pool, table, pos: model.decode_step_paged(
                p, tok, pool, table, pos))
        self.head = self.resolve_head("exact" if head is None else head)

    # -- head resolution ----------------------------------------------------
    def resolve_head(self, head: Optional[HeadLike]) -> SoftmaxHead:
        """name | instance | None (engine default) → prepared SoftmaxHead."""
        if head is None:
            return self.head
        if isinstance(head, str):
            if head not in self._head_cache:
                self._head_cache[head] = heads_registry.get(
                    head, W=self.W, b=self.b, screen=self.screen,
                    **self._head_kwargs)
            return self._head_cache[head]
        return head.prepare()

    # -- per-head jitted steps (built once, cached) --------------------------
    def _head_jit(self, heads: Sequence[SoftmaxHead], step, n_placed: int,
                  donate: Optional[int] = None):
        """jit a composed step ``step(*heads, *args)`` → ``fn(*args)``.

        The heads' arrays (``device_state()``) enter the executable as
        arguments: a head closed over by the step would have its weights
        baked into every compiled step as constants — a second copy of W in
        device memory per step, the full W on every device of a sharded
        head's mesh, and executables too large for a compile cache.

        A vocab-SHARDED head (``head.mesh`` set) keeps its arrays where they
        live, and the step's other inputs (params, token, cache — the first
        ``n_placed`` args) join the mesh: ``in_shardings`` pins them
        replicated, and ``fn`` device_puts each call so committed
        single-device arrays (e.g. the prefill cache) reshard instead of
        erroring; once outputs come back mesh-placed, the device_put is a
        no-op. The jitted callable is built ONCE here and cached like every
        other step — no per-step re-jitting. ``fn.lower(*args)`` lowers it.

        ``donate``: the index of the one arg whose buffers the step
        consumes (a paged step's pool, updated in place instead of
        copied); the caller uses the step's output in its place."""
        heads = tuple(h.prepare() for h in heads)
        state = tuple(h.device_state() for h in heads)

        def traced(state, args, donated):
            if donate is not None:
                args = (*args[:donate], donated, *args[donate + 1:])
            return step(*(h.with_state(s) for h, s in zip(heads, state)),
                        *args)

        donate_argnums = () if donate is None else (2,)
        mesh = next((h.mesh for h in heads if h.mesh is not None), None)
        if mesh is None:
            jitted = jax.jit(traced, donate_argnums=donate_argnums)

            def place(args):
                return args
        else:
            from jax.sharding import NamedSharding, PartitionSpec
            repl = NamedSharding(mesh, PartitionSpec())
            # arrays off the mesh (a host-built screen) are replicated once
            state = jax.tree.map(
                lambda a: a if a.sharding.device_set == repl.device_set
                else jax.device_put(a, repl), state)
            jitted = jax.jit(traced, donate_argnums=donate_argnums,
                             in_shardings=(jax.tree.map(lambda a: a.sharding,
                                                        state), repl, repl))

            def place(args):
                return (*jax.device_put(args[:n_placed], repl),
                        *args[n_placed:])

        def split(args):
            args = place(args)
            if donate is None:
                return args, ()
            return (*args[:donate], None, *args[donate + 1:]), args[donate]

        def fn(*args):
            return jitted(state, *split(args))
        fn.lower = lambda *args: jitted.lower(state, *split(args))
        fn._inner_jit = jitted
        return fn

    def _greedy_step(self, head: SoftmaxHead):
        key = (head.step_key(), "greedy")
        if key not in self._step_cache:
            if head.is_jittable:
                def step(hd, params, tok, cache, pos):
                    h, cache = self.model.decode_step(params, tok, cache, pos)
                    return hd.next(h), h, cache
                fn = self._head_jit((head,), step, n_placed=3)
            else:
                def fn(params, tok, cache, pos):
                    h, cache = self._jit_decode(params, tok, cache, pos)
                    nxt = jnp.asarray(np.asarray(head.next(np.asarray(h))),
                                      jnp.int32)
                    return nxt, h, cache
            self._put_step(key, fn)
        else:
            self._step_cache.move_to_end(key)       # LRU hit → most recent
        return self._step_cache[key]

    def _put_step(self, key, fn):
        while len(self._step_cache) >= self._step_cache_max:
            self._step_cache.popitem(last=False)    # least-recently-used
        self._step_cache[key] = fn

    def _cache_size(self) -> int:
        """Cached compiled steps — at most one per (head, step-kind)."""
        return len(self._step_cache)

    def compiled_step_counts(self) -> Dict[tuple, int]:
        """{(head name, step kind): XLA executables held} across the step
        cache — the recompile telemetry benchmarks/serve_mixed.py reports.
        A count above 1 for one key means the same step was re-traced (e.g.
        for a new batch shape), which is exactly what serve_batch's
        group-and-pad exists to avoid."""
        out: Dict[tuple, int] = {}
        for (skey, kind, *_), fn in self._step_cache.items():
            inner = getattr(fn, "_inner_jit", fn)
            n = inner._cache_size() if hasattr(inner, "_cache_size") else 0
            k = (skey[0], kind)
            out[k] = out.get(k, 0) + n
        return out

    def _sample_step(self, head: SoftmaxHead, temperature: float,
                     top_p: float):
        key = (head.step_key(), "sample", float(temperature), float(top_p))
        if key in self._step_cache:
            self._step_cache.move_to_end(key)       # LRU hit → most recent
        if key not in self._step_cache:
            if head.is_jittable:
                def step(hd, params, rkey, tok, cache, pos):
                    h, cache = self.model.decode_step(params, tok, cache, pos)
                    return hd.sample(rkey, h, temperature, top_p), h, cache
                fn = self._head_jit((head,), step, n_placed=4)
            else:
                def fn(params, rkey, tok, cache, pos):
                    h, cache = self._jit_decode(params, tok, cache, pos)
                    nxt = jnp.asarray(
                        np.asarray(head.sample(rkey, np.asarray(h),
                                               temperature, top_p)),
                        jnp.int32)
                    return nxt, h, cache
            self._put_step(key, fn)
        return self._step_cache[key]

    def _lstm_join_step(self, head: SoftmaxHead,
                        temperature: Optional[float] = None,
                        top_p: float = 1.0):
        """The paged LSTM join with its first token, for a jittable head:
        ``_lstm_join`` then the head on its ``h_last``, one executable per
        page-grid bucket, as ``_greedy_step`` composes the decode step and
        ``hd.next``. Greedy: ``fn(params, ints, src, group) -> (first (1,),
        snapshots, group)``. Sampled (``temperature`` set): ``fn(params,
        rkey, ints, src, group) -> (first, rkey', snapshots, group)``,
        drawing with the second half of ``rkey``'s split and returning the
        first, as the stream splits its key for an eager first token.
        Cached under ``(head.step_key(), "join", ...)`` with the same
        LRU/mesh discipline as every other composed step; the caller
        commits the key and the cache only after the token's guard."""
        if temperature is None:
            key = (head.step_key(), "join", "greedy")
        else:
            key = (head.step_key(), "join", "sample", float(temperature),
                   float(top_p))
        if key in self._step_cache:
            self._step_cache.move_to_end(key)       # LRU hit → most recent
            return self._step_cache[key]
        model = self.model
        if temperature is None:
            def step(hd, params, ints, src, group):
                h, snaps, group = _lstm_join(model, params, ints, src, group)
                return hd.next(h), snaps, group
            fn = self._head_jit((head,), step, n_placed=4)
        else:
            def step(hd, params, rkey, ints, src, group):
                h, snaps, group = _lstm_join(model, params, ints, src, group)
                rkey, k0 = jax.random.split(rkey)
                return (hd.sample(k0, h, temperature, top_p), rkey, snaps,
                        group)
            fn = self._head_jit((head,), step, n_placed=5)
        self._put_step(key, fn)
        return fn

    # -- speculative decode steps (repro.serving.spec) -----------------------
    def _spec_verify_step(self, head: SoftmaxHead, n_max: int):
        """Batched multi-position VERIFY: greedy ids of ``head`` over n_max
        stacked draft hidden states in ONE head call — the (V, d) softmax
        weights stream from HBM once per round instead of once per token.
        Signature ``fn(h_0, ..., h_{n_max-1}) -> (n_max, W) int32`` with each
        ``h_i`` of fixed shape (W, d): the adaptive controller shrinking the
        LIVE draft length (callers pad the tail by repeating the last hidden)
        never changes shapes, so nothing re-traces. Cached under
        ``(head.step_key(), "spec-verify", n_max)`` with the same LRU/mesh
        discipline as every other composed step."""
        key = (head.step_key(), "spec-verify", int(n_max))
        if key not in self._step_cache:
            if head.is_jittable:
                def step(hd, *hs):
                    H = jnp.concatenate(hs, axis=0)        # (n_max·W, d)
                    return hd.next(H).reshape(len(hs), hs[0].shape[0])
                # exact-SHARDED verify: every hidden joins the mesh
                fn = self._head_jit((head,), step, n_placed=n_max)
            else:
                def fn(*hs):
                    H = np.concatenate([np.asarray(h) for h in hs], axis=0)
                    return jnp.asarray(np.asarray(head.next(H)),
                                       jnp.int32).reshape(len(hs),
                                                          hs[0].shape[0])
            self._put_step(key, fn)
        else:
            self._step_cache.move_to_end(key)       # LRU hit → most recent
        return self._step_cache[key]

    def _spec_dist_step(self, draft: SoftmaxHead, verify: SoftmaxHead,
                        n_max: int, temperature: float, top_p: float):
        """Sampled-verify companion: one call yields BOTH heads'
        temperature/nucleus-adjusted full-vocab distribution logits over the
        stacked draft hiddens — q (draft law) and p (target law) as
        (n_max, W, V) — for the host-side rejection rule
        (repro.serving.spec.acceptance). Never mesh-aware: sampled spec is
        restricted to UNSHARDED verify heads (full-vocab rows are never
        gathered across shards)."""
        from repro.heads.base import adjust_logits
        key = (draft.step_key(), "spec-dist", verify.step_key(), int(n_max),
               float(temperature), float(top_p))
        if key in self._step_cache:
            self._step_cache.move_to_end(key)       # LRU hit → most recent
        if key not in self._step_cache:
            def step(dr, ve, *hs):
                H = jnp.concatenate(hs, axis=0)            # (n_max·W, d)
                W = hs[0].shape[0]
                q = adjust_logits(dr.dist_logits(H), temperature, top_p)
                p = adjust_logits(ve.dist_logits(H), temperature, top_p)
                return (q.reshape(len(hs), W, -1),
                        p.reshape(len(hs), W, -1))
            self._put_step(key, self._head_jit((draft, verify), step,
                                               n_placed=0))
        return self._step_cache[key]

    # -- paged decode steps (attention families; see repro.serving.kvpool) ---
    def _paged_greedy_step(self, head: SoftmaxHead):
        """Composed (decode over pool pages + head.next) step, cached under
        ``(head.step_key(), "greedy-paged")`` with the same LRU/meshing
        discipline as ``_greedy_step``. ``pool`` is the store's arrays
        (``PagedKVStore.arrays``), donated: the step writes its rows in
        place and the caller keeps the returned pool. Signature:
        ``fn(params, tok, pool, table, pos) -> (next, h, pool)``."""
        key = (head.step_key(), "greedy-paged")
        if key not in self._step_cache:
            if head.is_jittable:
                def step(hd, params, tok, pool, table, pos):
                    h, pool = self.model.decode_step_paged(
                        params, tok, pool, table, pos)
                    return hd.next(h), h, pool
                fn = self._head_jit((head,), step, n_placed=3, donate=2)
            else:
                def fn(params, tok, pool, table, pos):
                    h, pool = self._jit_decode_paged(params, tok, pool,
                                                     table, pos)
                    nxt = jnp.asarray(np.asarray(head.next(np.asarray(h))),
                                      jnp.int32)
                    return nxt, h, pool
            self._put_step(key, fn)
        else:
            self._step_cache.move_to_end(key)       # LRU hit → most recent
        return self._step_cache[key]

    def _paged_sample_step(self, head: SoftmaxHead, temperature: float,
                           top_p: float):
        """Sampled twin of ``_paged_greedy_step``; key carries the sampling
        statics like ``_sample_step``'s."""
        key = (head.step_key(), "sample-paged", float(temperature),
               float(top_p))
        if key in self._step_cache:
            self._step_cache.move_to_end(key)       # LRU hit → most recent
        if key not in self._step_cache:
            if head.is_jittable:
                def step(hd, params, rkey, tok, pool, table, pos):
                    h, pool = self.model.decode_step_paged(
                        params, tok, pool, table, pos)
                    return hd.sample(rkey, h, temperature, top_p), h, pool
                fn = self._head_jit((head,), step, n_placed=4, donate=3)
            else:
                def fn(params, rkey, tok, pool, table, pos):
                    h, pool = self._jit_decode_paged(params, tok, pool,
                                                     table, pos)
                    nxt = jnp.asarray(
                        np.asarray(head.sample(rkey, np.asarray(h),
                                               temperature, top_p)),
                        jnp.int32)
                    return nxt, h, pool
            self._put_step(key, fn)
        return self._step_cache[key]

    # -- generation (greedy or sampled, head-routed) -------------------------
    def generate(self, prompts: np.ndarray, max_new: int,
                 head: Optional[HeadLike] = None,
                 temperature: Optional[float] = None, top_p: float = 1.0,
                 key=None) -> GenerationResult:
        """prompts: (B, Tp) int32. Decode ``max_new`` tokens.

        ``temperature=None`` (default) is greedy; otherwise temperature /
        nucleus sampling through ``head.sample`` (``key`` required unless
        temperature ≤ 0)."""
        hd = self.resolve_head(head)
        B, Tp = prompts.shape
        cache = self.model.init_cache(B, self.max_len, dtype=self.cache_dtype)
        h, cache = self._jit_prefill(self.params,
                                     {"tokens": jnp.asarray(prompts)}, cache)
        h_last = h[:, -1]
        if temperature is None:
            step = self._greedy_step(hd)
            first = hd.next(h_last if hd.is_jittable else np.asarray(h_last))
            tok = jnp.asarray(np.asarray(first), jnp.int32)
            out = [np.asarray(tok)]
            for i in range(max_new - 1):
                tok, _, cache = step(self.params, tok, cache, Tp + i)
                out.append(np.asarray(tok))
            return GenerationResult(tokens=np.stack(out, axis=1),
                                    steps=max_new)
        if key is None:
            if temperature > 0:
                raise ValueError("sampling with temperature > 0 needs a PRNG "
                                 "key (generate(..., key=jax.random.key(..)))")
            key = jax.random.key(0)
        step = self._sample_step(hd, temperature, top_p)
        key, k0 = jax.random.split(key)
        first = hd.sample(k0, h_last if hd.is_jittable else np.asarray(h_last),
                          temperature, top_p)
        tok = jnp.asarray(np.asarray(first), jnp.int32)
        out = [np.asarray(tok)]
        for i in range(max_new - 1):
            key, ki = jax.random.split(key)
            tok, _, cache = step(self.params, ki, tok, cache, Tp + i)
            out.append(np.asarray(tok))
        return GenerationResult(tokens=np.stack(out, axis=1), steps=max_new)

    # -- request-centric serving ---------------------------------------------
    def head_catalog(self, names: Sequence[str]) -> Dict[str, dict]:
        """{name: head.describe()} for every resolvable name — the metadata
        routing policies weigh. Names whose head cannot be built in THIS
        engine — a screening head with no fitted screen, or a kernel head
        whose screen has the wrong block size (those factories assert) —
        are omitted, so a policy listing them simply never routes there;
        unknown registry names still raise KeyError."""
        catalog = {}
        for name in dict.fromkeys(names):
            try:
                catalog[name] = self.resolve_head(name).describe()
            except (MissingScreenError, AssertionError):
                continue
        return catalog

    def serve_batch(self, requests: Sequence[ServeRequest],
                    policy=None) -> List[ServeResult]:
        """Serve a mixed batch of ``ServeRequest``s through routed heads.

        Each request resolves to a head name — its explicit ``head`` field,
        else ``policy.route`` over ``head_catalog(policy.candidates)``;
        ``policy=None`` keeps everything on the engine's default head.
        Requests sharing (head, prompt length, sampling statics) run as ONE
        batched decode padded to the group's longest ``max_new`` through
        the same cached jitted steps ``generate`` uses — a mixed batch adds
        zero step compilations after warmup. Results come back in request
        order; greedy results are bit-identical to solo ``generate`` calls
        (see repro.serving.request for the sampling determinism
        contract)."""
        from repro.serving.router import StaticPolicy, route_requests
        requests = list(requests)
        if not requests:
            return []
        # policy=None serves through the engine's default head INSTANCE (a
        # custom instance may not be re-resolvable by name); the sentinel
        # groups those requests together and maps back to self.head below
        if policy is None:
            policy = StaticPolicy(_ENGINE_DEFAULT)
        catalog = self.head_catalog(
            tuple(n for n in getattr(policy, "candidates", ())
                  if n != _ENGINE_DEFAULT))
        names = route_requests(requests, policy, catalog)

        groups: "OrderedDict[tuple, List[int]]" = OrderedDict()
        for i, (req, name) in enumerate(zip(requests, names)):
            groups.setdefault(req.group_key(name), []).append(i)

        results: List[Optional[ServeResult]] = [None] * len(requests)
        for key, idxs in groups.items():
            name = key[0]
            head = self.head if name == _ENGINE_DEFAULT else name
            reqs = [requests[i] for i in idxs]
            prompts = np.stack([r.prompt for r in reqs])
            max_new = max(r.max_new for r in reqs)
            proto = reqs[0]                  # sampling statics shared by key
            if proto.sampled:
                out = self.generate(prompts, max_new, head=head,
                                    temperature=proto.temperature,
                                    top_p=proto.top_p,
                                    key=jax.random.key(proto.seed))
            else:
                out = self.generate(prompts, max_new, head=head)
            served = getattr(self.head, "name", _ENGINE_DEFAULT) \
                if name == _ENGINE_DEFAULT else name
            for row, i in enumerate(idxs):
                results[i] = ServeResult(
                    tokens=out.tokens[row, :requests[i].max_new],
                    head=served, request=requests[i], group_size=len(idxs))
        return results

    # -- continuous batching: fixed-width streams ---------------------------
    def open_stream(self, head: Optional[HeadLike] = None, width: int = 4,
                    temperature: Optional[float] = None, top_p: float = 1.0,
                    seed: int = 0) -> "DecodeStream":
        """Open a fixed-width continuous decode stream on this engine.

        The stream shares the engine's cached jitted steps (one vector-pos
        executable per (head, step kind, width) — compiled at the stream's
        first step, reused forever after) and its compiled prefill. Greedy
        tokens produced through a stream are bit-identical to solo
        ``generate`` calls; see ``DecodeStream``."""
        name = head if isinstance(head, str) else None
        hd = self.resolve_head(head)
        if name is None:
            name = getattr(hd, "name", "custom")
        return DecodeStream(self, hd, width, temperature=temperature,
                            top_p=top_p, seed=seed, head_name=name)

    def open_paged_stream(self, pool, head: Optional[HeadLike] = None,
                          width: int = 4,
                          temperature: Optional[float] = None,
                          top_p: float = 1.0, seed: int = 0):
        """Open a continuous decode stream backed by a ``PagePool`` instead
        of a private contiguous cache: per-slot KV (or logical LSTM) pages
        with shared-prefix radix reuse and copy-on-write. Same contract as
        ``open_stream`` — greedy tokens stay bit-identical and attention
        streams add at most one paged executable per (head, kind, width);
        LSTM streams reuse the dense steps outright. See
        ``repro.serving.kvpool.PagedDecodeStream``."""
        from repro.serving.kvpool.stream import PagedDecodeStream
        name = head if isinstance(head, str) else None
        hd = self.resolve_head(head)
        if name is None:
            name = getattr(hd, "name", "custom")
        return PagedDecodeStream(self, hd, width, pool,
                                 temperature=temperature, top_p=top_p,
                                 seed=seed, head_name=name)

    def open_spec_stream(self, draft_head: HeadLike,
                         verify_head: Optional[HeadLike] = None,
                         width: int = 4, draft_len: int = 4,
                         temperature: Optional[float] = None,
                         top_p: float = 1.0, seed: int = 0,
                         kv_pool=None, adaptive: bool = True):
        """Open a continuous SPECULATIVE decode stream: ``draft_head``
        drafts up to ``draft_len`` tokens per round through the engine's
        cached decode steps, ``verify_head`` (default: the engine's default
        head) verifies the whole draft in one batched call, and only tokens
        the verify head would itself have produced are emitted — greedy
        output is bit-identical to a plain ``verify_head`` stream. With
        ``adaptive`` a per-stream ``DraftLenController`` shrinks the live
        draft length when measured acceptance drops (shapes stay padded to
        ``draft_len``; nothing re-traces). See
        ``repro.serving.spec.SpecDecodeStream``."""
        from repro.serving.spec.policy import DraftLenController
        from repro.serving.spec.stream import SpecDecodeStream
        draft_name = draft_head if isinstance(draft_head, str) else \
            getattr(draft_head, "name", "custom")
        if verify_head is None:
            verify_name = getattr(self.head, "name", "custom")
        else:
            verify_name = verify_head if isinstance(verify_head, str) else \
                getattr(verify_head, "name", "custom")
        controller = DraftLenController(draft_len) if adaptive else None
        return SpecDecodeStream(self, draft_head, verify_head, width=width,
                                draft_len=draft_len, temperature=temperature,
                                top_p=top_p, seed=seed,
                                draft_name=draft_name,
                                verify_name=verify_name,
                                controller=controller, kv_pool=kv_pool)

    # -- beam search (batch of 1 prompt, beam B_w) ---------------------------
    def beam_search(self, prompt: np.ndarray, beam: int, max_new: int,
                    head: Optional[HeadLike] = None) -> GenerationResult:
        """prompt: (Tp,) int32. Returns the top beam's tokens and score.

        ``head.topk_logprobs`` supplies the per-step (ids, log-probs); its
        jit (static k) lives at head-module level, so repeated calls — and
        repeated ``beam_search`` invocations — reuse one compilation."""
        hd = self.resolve_head(head)
        Tp = len(prompt)
        prompts = np.broadcast_to(prompt[None], (beam, Tp)).copy()
        cache = self.model.init_cache(beam, self.max_len,
                                      dtype=self.cache_dtype)
        h, cache = self._jit_prefill(self.params,
                                     {"tokens": jnp.asarray(prompts)}, cache)
        h_last = h[:, -1]                                  # (beam, d)

        def lp_fn(h_step, k):
            if not hd.is_jittable:
                h_step = np.asarray(h_step)
            return hd.topk_logprobs(h_step, k)

        ids, lps = lp_fn(h_last[:1], beam)                 # expand from beam 0
        ids, lps = np.asarray(ids), np.asarray(lps)
        beam_tokens = [[int(ids[0, j])] for j in range(beam)]
        beam_scores = np.asarray(lps[0], np.float64).copy()
        tok = jnp.asarray(ids[0], jnp.int32)

        for i in range(max_new - 1):
            h1, cache = self._jit_decode(self.params, tok, cache, Tp + i)
            ids, lps = lp_fn(h1, beam)                     # (beam, beam)
            ids = np.asarray(ids)
            total = beam_scores[:, None] + np.asarray(lps, np.float64)
            flat = total.reshape(-1)
            top = np.argsort(-flat)[:beam]
            src, choice = np.unravel_index(top, total.shape)
            beam_tokens = [beam_tokens[s] + [int(ids[s, c])]
                           for s, c in zip(src, choice)]
            beam_scores = flat[top]
            tok = jnp.asarray([int(ids[s, c]) for s, c in zip(src, choice)],
                              jnp.int32)
            # reorder caches to follow the surviving beams
            src_idx = jnp.asarray(src, jnp.int32)
            cache = _reorder_cache(cache, src_idx, self.model.cfg)

        best = int(np.argmax(beam_scores))
        return GenerationResult(tokens=np.asarray(beam_tokens[best])[None],
                                scores=beam_scores[best:best + 1],
                                steps=max_new)


@dataclass
class _StreamSlot:
    """One occupied pad slot of a DecodeStream."""
    tag: object                      # opaque caller handle (scheduler bookkeeping)
    request: ServeRequest
    tokens: list                     # generated ids so far (python ints)
    remaining: int                   # tokens still to decode


class DecodeStream:
    """A fixed-width continuously-batched decode: join-at-step over pad slots.

    The stream owns one width-W decode cache plus per-slot (token, position)
    state. ``join(request)`` prefills the request SOLO (B=1 — the compiled
    prefill for its prompt length), computes its first token exactly the way
    ``generate`` does, and splices the prefilled cache rows into a free
    slot; ``step()`` advances every occupied slot one token through the SAME
    cached jitted step ``generate``/``serve_batch`` use, passing a (W,)
    vector of per-row positions (the vector-``pos`` branch of
    ``attn_decode`` — LSTM/SSM states ignore position entirely). Because
    every row of a batched decode step is computed independently, a greedy
    request's tokens are bit-identical to a solo ``generate`` call no matter
    when it joined or who shares the stream.

    Compile discipline: the batch width is FIXED at ``width`` — empty slots
    are padding, so join/retire churn never changes the step's shapes. One
    vector-pos executable per (head, step kind, width) is traced at the
    stream's first step and reused for the stream's whole life; repeated
    streams of the same shape add zero executables
    (``engine.compiled_step_counts()`` is the audit).

    Sampling: one stream carries ONE sampling static tuple (temperature,
    top_p, seed) — the scheduler keys streams so this holds. The stream
    advances a single PRNG chain exactly like ``generate`` (split per step,
    one batch-wide draw), so an isolated width-1 sampled stream reproduces
    solo ``generate``; at width > 1 draws depend on stream width and join
    composition, the same contract ``serve_batch`` documents for group
    composition.
    """

    def __init__(self, engine: DecodeEngine, head: SoftmaxHead, width: int,
                 temperature: Optional[float] = None, top_p: float = 1.0,
                 seed: int = 0, head_name: str = "custom"):
        if width < 1:
            raise ValueError(f"stream width must be >= 1: {width}")
        self.engine = engine
        self.head = engine.resolve_head(head)
        self.head_name = head_name
        # resilience hooks: the scheduler arms an injector on streams it
        # opens; the vocab bound backs the always-on output guard (a head
        # emitting sentinel/NaN ids raises a typed HeadFault instead of
        # feeding garbage back into the decode)
        self.fault_injector = None
        # observability: the scheduler arms its tracer here too; join and
        # step scopes split host work from the one explicit device fetch
        self.tracer = NULL_TRACER
        self.vocab = int(engine.W.shape[0])
        self.width = int(width)
        self.temperature = temperature
        self.top_p = float(top_p)
        self.seed = int(seed)
        self.sampled = temperature is not None
        if self.sampled:
            self._key = jax.random.key(self.seed)
        self.cache = engine.model.init_cache(self.width, engine.max_len,
                                             dtype=engine.cache_dtype)
        self._repl = None
        if self.head.mesh is not None:
            # mesh-placed stream: splices must not mix device-0-committed
            # solo rows into a mesh-replicated cache (same discipline as
            # the engine's mesh-aware step wrapper)
            from jax.sharding import NamedSharding, PartitionSpec
            self._repl = NamedSharding(self.head.mesh, PartitionSpec())
            self.cache = jax.device_put(self.cache, self._repl)
        self.tok = np.zeros((self.width,), np.int32)
        self.pos = np.zeros((self.width,), np.int32)
        self.slots: List[Optional[_StreamSlot]] = [None] * self.width
        self._finished: List[tuple] = []

    # -- capacity ------------------------------------------------------------
    @property
    def n_active(self) -> int:
        return sum(s is not None for s in self.slots)

    @property
    def free_slots(self) -> int:
        return self.width - self.n_active

    @property
    def idle(self) -> bool:
        """No occupied slots and no completions waiting to be drained —
        safe for a scheduler to close and replace."""
        return self.n_active == 0 and not self._finished

    def occupied(self) -> List[tuple]:
        """[(slot index, tag)] for every occupied slot — what a scheduler
        scans when deciding whom to preempt."""
        return [(i, s.tag) for i, s in enumerate(self.slots) if s is not None]

    def _first_free(self) -> int:
        for i, s in enumerate(self.slots):
            if s is None:
                return i
        raise RuntimeError("DecodeStream is full — check free_slots first")

    # -- join: solo prefill + cache splice into a pad slot -------------------
    def join(self, request: ServeRequest, tag: object = None) -> int:
        """Admit one request into a free slot mid-decode. Returns the slot.

        The request's first token comes from its own solo prefill (identical
        to ``generate``'s first-token path); subsequent tokens come from the
        shared batched ``step``. A ``max_new == 1`` request completes here
        and surfaces from the next ``step()``/``pop_finished()``."""
        eng = self.engine
        Tp = int(request.prompt.shape[0])
        if Tp + request.max_new > eng.max_len:
            raise ValueError(
                f"request needs {Tp + request.max_new} cache slots, stream "
                f"max_len is {eng.max_len}")
        slot = self._first_free()
        tr = self.tracer
        with tr.scope("serve.join.prefill", "join"):
            cache1 = eng.model.init_cache(1, eng.max_len,
                                          dtype=eng.cache_dtype)
            h, cache1 = eng._jit_prefill(
                eng.params, {"tokens": jnp.asarray(request.prompt[None])},
                cache1)
            h_last = h[:, -1]
        if tr.enabled:
            tr.note(cached_tokens=0, chunks=1)
        hd = self.head
        with tr.scope("serve.join.first", "join"):
            h_in = h_last if hd.is_jittable else np.asarray(h_last)
            if self.sampled:
                self._key, k0 = jax.random.split(self._key)
                first = hd.sample(k0, h_in, self.temperature, self.top_p)
            else:
                first = hd.next(h_in)
        with tr.scope("serve.join.wait", "join"):
            first = np.asarray(first)
        # guard BEFORE any stream state mutates: a join-boundary fault
        # (injected or an honestly degenerate first token) leaves the
        # stream exactly as it was, so the scheduler can retry or re-route
        first = int(guard_tokens(self.fault_injector, "join",
                                 self.head_name, first,
                                 self.vocab).ravel()[0])
        with tr.scope("serve.join.splice", "join"):
            if self._repl is not None:
                cache1 = jax.device_put(cache1, self._repl)
            self.cache = _splice_cache(self.cache, cache1, slot,
                                       eng.model.cfg)
        self.tok[slot] = first
        self.pos[slot] = Tp
        entry = _StreamSlot(tag=tag, request=request, tokens=[first],
                            remaining=request.max_new - 1)
        if entry.remaining == 0:
            self._finished.append(
                (entry.tag, entry.request,
                 np.asarray(entry.tokens, np.int32)))
        else:
            self.slots[slot] = entry
        return slot

    # -- step: advance every occupied slot one token -------------------------
    def step(self) -> List[tuple]:
        """One batched decode tick. Returns retired ``(tag, request,
        tokens)`` triples — requests that hit their ``max_new`` this tick
        (plus any that completed at join). Idle slots decode padding that is
        never read and is overwritten by the next join's splice."""
        out = self._finished
        self._finished = []
        idx = [i for i, s in enumerate(self.slots) if s is not None]
        if not idx:
            return out
        tr = self.tracer
        with tr.scope("serve.step", "step"):
            if tr.enabled:
                tr.note(head=self.head_name, active=len(idx),
                        rids=[_tag_id(self.slots[i].tag) for i in idx])
            eng = self.engine
            tok = jnp.asarray(self.tok)
            pos = jnp.asarray(self.pos)
            # compute into locals and commit (cache, PRNG) only after the
            # guard: a step-boundary fault leaves the stream untouched, so
            # the scheduler's retry re-runs the identical step bit-for-bit
            # (jax caches are immutable pytrees — holding the old reference
            # IS the rollback, recurrent LSTM state included)
            if self.sampled:
                fn = eng._sample_step(self.head, self.temperature,
                                      self.top_p)
                key, ki = jax.random.split(self._key)
                nxt, _, cache = fn(eng.params, ki, tok, self.cache, pos)
            else:
                fn = eng._greedy_step(self.head)
                nxt, _, cache = fn(eng.params, tok, self.cache, pos)
            with tr.scope("serve.step.wait", "step"):
                nxt = np.asarray(nxt)
            nxt = guard_tokens(self.fault_injector, "step", self.head_name,
                               nxt, self.vocab, rows=idx)
            if self.sampled:
                self._key = key
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
        return out

    def pop_finished(self) -> List[tuple]:
        """Drain completions that happened outside ``step`` (max_new == 1
        joins) without advancing the decode."""
        out = self._finished
        self._finished = []
        return out

    # -- evict: preemption hook ----------------------------------------------
    def evict(self, slot: int) -> tuple:
        """Forcibly retire a slot (scheduler preemption). Returns ``(tag,
        request, partial_tokens)``; the slot is free for the next join."""
        s = self.slots[slot]
        if s is None:
            raise ValueError(f"slot {slot} is not occupied")
        self.slots[slot] = None
        return (s.tag, s.request, np.asarray(s.tokens, np.int32))


def _tag_id(tag):
    """The request id of a slot's tag: a scheduler tags each slot with its
    queued request, whose ``id`` is the result id; None for other tags."""
    return getattr(tag, "id", None)


def _splice_cache(group, solo, slot, cfg):
    """Write a solo (B=1) prefilled cache into row ``slot`` of a width-W
    stream cache. Batch axis mirrors ``_reorder_cache``: LSTM state lists
    carry batch at axis 0; stacked attention/ssm caches at axis 1."""
    if cfg.family == "lstm":
        return {"lstm": [{k: g[k].at[slot].set(s[k][0]) for k in g}
                         for g, s in zip(group["lstm"], solo["lstm"])]}
    return jax.tree_util.tree_map(lambda g, s: g.at[:, slot].set(s[:, 0]),
                                  group, solo)


def _lstm_join(model, params, ints, src, group):
    """One paged LSTM join on the device.

    ``ints`` packs the host's int32 inputs into one upload: ``[n, k, slot,
    snap_idx (S), tokens (T)]`` — the real suffix length (0 for a
    whole-prompt hit), the index of the resume state in ``src``, the stream
    row to write, the step after which each page snapshot is taken
    (padding entries read step 0), and the suffix padded to its bucket T,
    a multiple of the page size. ``src`` holds S stacked states per layer
    (a page snapshot handle's arrays, or zeros); the prefill starts from
    ``src[k]`` and runs exactly ``n`` steps.

    Returns (h_last (1, d): the top layer's h after the last real step;
    snapshots: S states per layer, ``src``'s layout; the stream cache with
    row ``slot`` replaced by the final state). ``group`` is not donated:
    the caller commits the new cache only once the join can no longer
    fail."""
    S = src[0]["h"].shape[0]
    n, k, slot = ints[0], ints[1], ints[2]
    snap_idx, tokens = ints[3:3 + S], ints[3 + S:]
    state0 = [{key: a[k] for key, a in layer.items()} for layer in src]
    final, steps = model.prefill_steps(params, tokens[None], state0, n)
    snaps = [{key: a[snap_idx] for key, a in layer.items()}
             for layer in steps]
    group = {"lstm": [{key: g[key].at[slot].set(f[key][0]) for key in g}
                      for g, f in zip(group["lstm"], final)]}
    return final[-1]["h"], snaps, group


def _attn_join(model, params, tokens, n, pool, dest):
    """One paged attention join on the device: the prompt prefilled solo at
    its bucket and its rows written into the pool's pages.

    ``tokens`` (1, T): the prompt padded to its bucket T; ``n``: its real
    length. Padding lies after the prompt, so the causal mask keeps it out
    of every real position. ``pool``: the store's arrays (a leading layer
    axis, then pages); ``dest`` (2, T) int32: the page and the in-page row
    that position t is written to, the page out of range (the pool's page
    count) for a position not to be written (the padding, and the prompt's
    shared prefix pages). Returns (h_last (1, d): the top layer's state at
    the prompt's last position; the pool with those rows written). ``pool``
    is donated, its rows written in place: the caller keeps the returned
    pool whatever becomes of the join (a join that fails later wrote only
    its own private pages, which its rollback frees)."""
    dtype = jax.tree.leaves(pool)[0].dtype
    cache = model.init_cache(1, tokens.shape[1], dtype=dtype)
    h, cache = model.prefill(params, {"tokens": tokens}, cache)
    solo = cache.get("attn", cache)
    page, row = dest[0], dest[1]
    pool = {k: a.at[:, page, row].set(solo[k][:, 0].astype(a.dtype),
                                      mode="drop")
            for k, a in pool.items()}
    return jax.lax.dynamic_slice_in_dim(h[0], n - 1, 1), pool


def _reorder_cache(cache, src_idx, cfg):
    """Gather beam rows. Batch axis position differs per cache kind:
    attention/ssm caches are stacked per layer → batch is axis 1; LSTM state
    lists carry batch at axis 0."""
    if cfg.family == "lstm":
        return {"lstm": [{k: v[src_idx] for k, v in layer.items()}
                         for layer in cache["lstm"]]}
    return jax.tree_util.tree_map(lambda a: a[:, src_idx], cache)
