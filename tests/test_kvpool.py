"""Paged KV pool subsystem: allocator invariants, radix prefix cache,
COW, paged-stream bit-parity, stale-KV masking, and scheduler pressure.

The load-bearing guarantees pinned here:

  * greedy tokens through a ``PagedDecodeStream`` are BIT-IDENTICAL to solo
    ``engine.generate`` for the LSTM family (resume prefill from radix
    snapshots), attention families (paged scatter/gather decode), and the
    vocab-sharded head path — regardless of prefix sharing, COW, or page
    reuse;
  * freed pages full of stale (poisoned) KV rows never leak into another
    request's decode — the paged attention mask zeroes them exactly;
  * ``PoolExhausted`` is rollback-safe at join, non-consuming at step, and
    surfaces through the scheduler as typed preemption/rejection.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import L2SConfig, get_config
from repro.core import fit_l2s
from repro.models import build_model
from repro.serving import (BudgetAdmission, ContinuousScheduler,
                           DecodeEngine, PagePool, PoolExhausted,
                           ServeRequest, ServeResult, Tracer)
from repro.serving.kvpool import RadixCache
from repro.serving.scheduler import AdmissionRejected


@pytest.fixture(scope="module")
def lstm_engine():
    cfg = get_config("ptb-small-lstm").reduced()
    m = build_model(cfg)
    params = m.init(jax.random.key(0), dtype=jnp.float32)
    return cfg, DecodeEngine(m, params, max_len=24)


@pytest.fixture(scope="module")
def pallas_engine(lstm_engine):
    """``lstm_engine``'s model and weights behind a screen fit in 128-word
    blocks, so both ``exact`` and ``screened-pallas`` resolve."""
    cfg, eng = lstm_engine
    H = np.random.default_rng(0).standard_normal(
        (64, cfg.d_model)).astype(np.float32)
    y = np.asarray(jnp.argsort(-(H @ eng.W.T + eng.b), axis=-1)[:, :5])
    screen = fit_l2s(H, y, cfg.vocab_size,
                     L2SConfig(num_clusters=4, budget=256, outer_iters=1,
                               sgd_steps=5, vocab_block=128)).screen
    return cfg, DecodeEngine(eng.model, eng.params, screen=screen,
                             max_len=eng.max_len)


@pytest.fixture(scope="module")
def dense_engine():
    cfg = get_config("smollm-360m").reduced()
    m = build_model(cfg)
    params = m.init(jax.random.key(1), dtype=jnp.float32)
    return cfg, DecodeEngine(m, params, max_len=24)


def _prefix_requests(cfg, n, template_len=10, suffix_len=3, max_new=5,
                     seed=0):
    rng = np.random.default_rng(seed)
    tmpl = rng.integers(0, cfg.vocab_size, size=template_len)
    return [ServeRequest(
        prompt=np.concatenate(
            [tmpl, rng.integers(0, cfg.vocab_size, size=suffix_len)]
        ).astype(np.int32), max_new=max_new) for _ in range(n)]


def _run_stream(stream, requests):
    got, pending = {}, list(enumerate(requests))
    while pending or stream.n_active or stream._finished:
        while pending and stream.free_slots:
            i, r = pending.pop(0)
            stream.join(r, tag=i)
        for tag, _, toks in stream.step():
            got[tag] = toks
    return got


# -- PagePool unit ------------------------------------------------------------

def test_pool_alloc_release_refcounts():
    pool = PagePool(6, 4)
    assert pool.pages_free == 5 and pool.pages_in_use == 0
    a, b = pool.alloc(), pool.alloc()
    assert a != 0 and b != 0 and a != b     # page 0 reserved (trash)
    assert pool.pages_in_use == 2 and pool.writable(a)
    pool.retain(a)
    assert pool.ref(a) == 2 and not pool.writable(a)
    pool.release(a)
    assert pool.ref(a) == 1
    pool.release(a)
    assert pool.ref(a) == 0 and pool.pages_free == 4
    with pytest.raises(ValueError, match="double free"):
        pool.release(a)
    with pytest.raises(ValueError):
        pool.retain(a)                      # non-live
    pool.release(b)
    assert pool.pages_in_use == 0 and pool.peak_in_use == 2


def test_pool_cow_and_ensure_writable():
    pool = PagePool(6, 4)
    a = pool.alloc()
    assert pool.ensure_writable(a) == a     # sole holder: no copy
    pool.retain(a)
    c = pool.ensure_writable(a)
    assert c != a and pool.ref(a) == 1 and pool.ref(c) == 1
    assert pool.cow_copies == 1


def test_pool_exhaustion_typed():
    pool = PagePool(3, 4)                   # 2 allocatable
    pool.alloc(), pool.alloc()
    with pytest.raises(PoolExhausted) as ei:
        pool.alloc()
    assert ei.value.needed == 1 and ei.value.free == 0 and ei.value.total == 2
    assert "exhausted" in str(ei.value)


def test_pool_validation():
    with pytest.raises(ValueError):
        PagePool(1, 4)                      # page 0 alone is not a pool
    with pytest.raises(ValueError):
        PagePool(4, 0)


# -- RadixCache unit ----------------------------------------------------------

def test_radix_insert_match_roundtrip():
    pool = PagePool(32, 4)
    radix = RadixCache(pool)
    toks = list(range(10))                  # 2 full chunks + 1 partial
    pages = [pool.alloc() for _ in range(3)]
    created = radix.insert(toks, pages, payloads=["s0", "s1", "s2"])
    assert created == 3 and radix.nodes == 3
    for pg in pages:                        # cache pinned each page
        assert pool.ref(pg) == 2
    m = radix.match(toks)
    assert m.n_full == 10 and m.n_tokens == 10
    assert m.payload == "s2"
    assert [n for _, n in m.chain] == [4, 4, 2]
    # partial hit inside the tail node
    m2 = radix.match(toks[:9])
    assert m2.n_full == 8 and m2.n_tokens == 9 and m2.tail == (pages[2], 1)
    # divergent suffix: full chunks still shared
    m3 = radix.match(list(range(8)) + [99, 98])
    assert m3.n_full == 8 and m3.payload == "s1"


def test_radix_reclaim_skips_shared_pages():
    pool = PagePool(32, 4)
    radix = RadixCache(pool)
    toks = list(range(8))
    pages = [pool.alloc(), pool.alloc()]
    radix.insert(toks, pages)
    for pg in pages:                        # simulate the stream dropping out
        pool.release(pg)
    pool.retain(pages[1])                   # another stream still shares p1
    freed = radix.reclaim(2)
    # only the leaf whose page is sole-held by the cache can free; p1's node
    # is also the remaining leaf's parent, so one LRU pass frees nothing
    # until the shared holder lets go
    assert freed == 0                       # leaf p1 is shared; p0 is inner
    pool.release(pages[1])
    assert radix.reclaim(2) == 2 and radix.nodes == 0
    assert pool.pages_in_use == 0


def test_radix_partials_lru_capped():
    from repro.serving.kvpool.radix import MAX_PARTIALS
    pool = PagePool(64, 4)
    radix = RadixCache(pool)
    for i in range(MAX_PARTIALS + 3):
        pages = [pool.alloc()]
        radix.insert([100 + i, 200 + i], pages)
        pool.release(pages[0])
    assert radix.nodes == MAX_PARTIALS
    assert radix.evictions == 3


def test_bind_requires_page_alignment(lstm_engine):
    _, eng = lstm_engine
    pool = PagePool(8, 7)                   # 7 does not divide max_len 24
    with pytest.raises(ValueError, match="must divide"):
        eng.open_paged_stream(pool)


# -- paged stream bit-parity --------------------------------------------------

def test_lstm_paged_stream_parity_and_hits(lstm_engine):
    cfg, eng = lstm_engine
    reqs = _prefix_requests(cfg, 6, max_new=5, seed=2)
    pool = PagePool(64, 4)
    stream = eng.open_paged_stream(pool, width=3)
    got = _run_stream(stream, reqs)
    for i, r in enumerate(reqs):
        ref = eng.generate(r.prompt[None], r.max_new).tokens[0]
        assert np.array_equal(got[i], ref), f"request {i} diverged"
    # template is 10 tokens of 13 → later joins resume from snapshots
    assert pool.radix.hit_rate > 0.3
    assert pool.cow_copies > 0              # partial-tail extension COWs
    # all stream chains released; only radix pins remain
    assert stream.pages_held == 0
    assert pool.pages_in_use == pool.radix.nodes
    # LSTM paged streams reuse the DENSE greedy step — no paged step
    # kinds; the join executable with its first token is the only other
    assert {kind for _, kind in eng.compiled_step_counts()} == {"greedy",
                                                                "join"}


def test_lstm_mixed_prompt_lengths_parity(lstm_engine):
    """Mixed-length prompts sharing partial prefixes: grid realignment,
    COW of extended partial tails, and whole-prompt cache hits (a prompt
    that IS a cached prefix decodes its first token with no forward pass)."""
    cfg, eng = lstm_engine
    rng = np.random.default_rng(5)
    base = rng.integers(0, cfg.vocab_size, size=11).astype(np.int32)
    reqs = [ServeRequest(prompt=base[:n], max_new=4)
            for n in (11, 7, 11, 5, 9, 11)]
    pool = PagePool(64, 4)
    got = _run_stream(eng.open_paged_stream(pool, width=2), reqs)
    for i, r in enumerate(reqs):
        ref = eng.generate(r.prompt[None], r.max_new).tokens[0]
        assert np.array_equal(got[i], ref), f"len {len(r.prompt)} diverged"


def test_dense_paged_stream_parity(dense_engine):
    cfg, eng = dense_engine
    reqs = _prefix_requests(cfg, 4, template_len=8, suffix_len=4,
                            max_new=4, seed=3)
    pool = PagePool(64, 4)
    got = _run_stream(eng.open_paged_stream(pool, width=2), reqs)
    for i, r in enumerate(reqs):
        ref = eng.generate(r.prompt[None], r.max_new).tokens[0]
        assert np.array_equal(got[i], ref), f"request {i} diverged"
    assert pool.radix.hit_rate > 0.3        # full prompt pages deduped
    cts = eng.compiled_step_counts()
    assert cts.get(("exact", "greedy-paged"), 0) >= 1


def test_capacity_moe_paged_join_parity():
    """A capacity-bounded expert layer (phi3.5-moe) drops the tokens over
    its capacity, which the prefill's length sets: a paged join must
    prefill at the prompt's own length, so it keeps exactly the tokens the
    contiguous prefill keeps. A zero router sends every token to experts 0
    and 1, so a 20-token prompt overflows the capacity of 16 that its own
    length gives (a 32-token bucket's would be 24, keeping all 20)."""
    cfg = get_config("phi3.5-moe-42b-a6.6b").reduced()
    m = build_model(cfg)
    params = m.init(jax.random.key(2), dtype=jnp.float32)
    moe = params["stack"]["blocks"]["moe"]
    moe["w_router"] = jnp.zeros_like(moe["w_router"])
    eng = DecodeEngine(m, params, max_len=32)
    stream = eng.open_paged_stream(PagePool(32, 4), width=2)
    assert stream.bucket(20) == 20
    rng = np.random.default_rng(4)
    reqs = [ServeRequest(prompt=rng.integers(0, cfg.vocab_size, n).astype(
        np.int32), max_new=4) for n in (20, 13)]
    got = _run_stream(stream, reqs)
    for i, r in enumerate(reqs):
        ref = eng.generate(r.prompt[None], r.max_new).tokens[0]
        assert np.array_equal(got[i], ref), f"request {i} diverged"
    # the join's state at the last position is the contiguous prefill's
    prompt = reqs[0].prompt
    h, _ = eng._jit_prefill(
        params, {"tokens": jnp.asarray(prompt[None])},
        m.init_cache(1, eng.max_len, dtype=eng.cache_dtype))
    store = stream.pool.store
    dest = np.stack([np.full(20, store.num_pages),
                     np.arange(20) % 4]).astype(np.int32)
    h_join, store.arrays = eng._jit_attn_join(
        params, prompt[None], np.int32(20), store.arrays, dest)
    np.testing.assert_allclose(h_join, h[:, -1], atol=1e-5, rtol=1e-5)


def test_dense_stale_page_rows_never_leak(dense_engine):
    """Satellite audit: POISON every pool page with large finite garbage,
    then decode on freshly-allocated pages. The paged attention mask must
    zero stale rows exactly (score −1e30 → exp underflows to 0.0), so
    tokens stay bit-identical to the solo path. The pool's LIFO free list
    maximizes reuse of just-freed (poisoned) pages."""
    cfg, eng = dense_engine
    pool = PagePool(16, 4)
    stream = eng.open_paged_stream(pool, width=2)
    # round 1 dirties pages; then drop the radix pins so pages free up
    reqs1 = _prefix_requests(cfg, 2, template_len=8, suffix_len=4,
                             max_new=4, seed=7)
    _run_stream(stream, reqs1)
    pool.radix.clear()
    assert pool.pages_in_use == 0
    # poison EVERY non-trash page with large-but-finite junk
    pool.store.arrays = {k: a.at[:, 1:].set(1e3)
                         for k, a in pool.store.arrays.items()}
    reqs2 = _prefix_requests(cfg, 3, template_len=8, suffix_len=4,
                             max_new=5, seed=11)
    got = _run_stream(stream, reqs2)
    for i, r in enumerate(reqs2):
        ref = eng.generate(r.prompt[None], r.max_new).tokens[0]
        assert np.array_equal(got[i], ref), \
            f"stale KV rows leaked into request {i}"


def test_join_rolls_back_on_exhaustion(lstm_engine):
    cfg, eng = lstm_engine
    pool = PagePool(3, 4)                   # 2 allocatable pages
    stream = eng.open_paged_stream(pool, width=2)
    rng = np.random.default_rng(9)
    big = ServeRequest(
        prompt=rng.integers(0, cfg.vocab_size, size=14).astype(np.int32),
        max_new=4)                          # needs 4 prompt pages
    with pytest.raises(PoolExhausted):
        stream.join(big)
    assert pool.pages_in_use == 0           # every ref rolled back
    assert stream.n_active == 0 and stream.pages_held == 0
    # a request that fits still serves afterwards
    small = ServeRequest(
        prompt=rng.integers(0, cfg.vocab_size, size=4).astype(np.int32),
        max_new=3)
    got = _run_stream(stream, [small])
    ref = eng.generate(small.prompt[None], small.max_new).tokens[0]
    assert np.array_equal(got[0], ref)


# -- scheduler integration ----------------------------------------------------

def test_scheduler_paged_drain_parity(lstm_engine):
    cfg, eng = lstm_engine
    reqs = _prefix_requests(cfg, 6, max_new=5, seed=17)
    pool = PagePool(64, 4)
    sched = ContinuousScheduler(eng, max_slots=3, kv_pool=pool)
    res = sched.serve(reqs)
    assert all(isinstance(r, ServeResult) for r in res)
    for r, req in zip(res, reqs):
        ref = eng.generate(req.prompt[None], req.max_new).tokens[0]
        assert np.array_equal(r.tokens, ref)
    snap = sched.stats.snapshot()
    assert snap["pool"] is not None
    assert snap["pool"]["prefix"]["hit_rate"] > 0.3
    assert snap["pool"]["pages_in_use"] == pool.pages_in_use


def test_scheduler_pool_pressure_preempts(lstm_engine):
    """A pool too small for concurrent requests serializes them through
    typed preemption/placement results instead of stalling drain()."""
    cfg, eng = lstm_engine
    rng = np.random.default_rng(19)
    reqs = [ServeRequest(
        prompt=rng.integers(0, cfg.vocab_size, size=12).astype(np.int32),
        max_new=6, latency_tier="batch") for _ in range(3)]
    pool = PagePool(6, 4)                   # 5 pages; each request needs 5
    sched = ContinuousScheduler(eng, max_slots=2, kv_pool=pool)
    res = sched.serve(reqs)                 # must terminate, not stall
    assert len(res) == 3
    assert any(isinstance(r, ServeResult) for r in res)
    kinds = {type(r).__name__ for r in res}
    assert "AdmissionRejected" in kinds     # pool pressure surfaced typed
    assert sched.stats.pool_stalled_ticks > 0


def test_admission_prices_marginal_pages(lstm_engine):
    cfg, eng = lstm_engine
    rng = np.random.default_rng(23)
    pool = PagePool(4, 4)                   # 3 allocatable pages
    sched = ContinuousScheduler(eng, admission=BudgetAdmission(),
                                kv_pool=pool)
    big = ServeRequest(
        prompt=rng.integers(0, cfg.vocab_size, size=12).astype(np.int32),
        max_new=6)                          # ceil(18/4) = 5 marginal pages
    res = sched.serve([big])
    assert isinstance(res[0], AdmissionRejected)
    assert res[0].stage == "admission" and "pool exhausted" in res[0].reason
    # a fitting request is admitted and served
    small = ServeRequest(
        prompt=rng.integers(0, cfg.vocab_size, size=6).astype(np.int32),
        max_new=4)
    res2 = sched.serve([small])              # results span BOTH serve calls
    assert isinstance(res2[-1], ServeResult)


def test_admission_discounts_resident_prefix(lstm_engine):
    """Marginal-page pricing: a request whose prefix is radix-resident is
    charged only its new pages — it fits a pool its cold twin would not."""
    cfg, eng = lstm_engine
    rng = np.random.default_rng(29)
    tmpl = rng.integers(0, cfg.vocab_size, size=8).astype(np.int32)
    mk = lambda: ServeRequest(prompt=np.concatenate(
        [tmpl, rng.integers(0, cfg.vocab_size, size=4).astype(np.int32)]),
        max_new=4)                          # 16 slots = 4 pages total
    pool = PagePool(8, 4)                   # 7 allocatable
    sched = ContinuousScheduler(eng, admission=BudgetAdmission(),
                                kv_pool=pool)
    first = sched.serve([mk()])             # primes radix: 2 full pages
    assert isinstance(first[0], ServeResult)
    load_pages = sched._marginal_pages(mk())
    assert load_pages == 2                  # 4 total - 2 shared


def test_scheduler_paged_zero_recompiles(lstm_engine):
    """Warm paged serving adds no step executables: LSTM paged streams ride
    the dense greedy step, so a second scheduler (same widths) compiles
    nothing new."""
    cfg, eng = lstm_engine
    pool = PagePool(64, 4)
    warm = _prefix_requests(cfg, 3, max_new=3, seed=31)
    ContinuousScheduler(eng, max_slots=3, kv_pool=pool).serve(warm)
    counts0 = eng.compiled_step_counts()
    meas = _prefix_requests(cfg, 5, max_new=4, seed=37)
    sched = ContinuousScheduler(eng, max_slots=3, kv_pool=pool)
    res = sched.serve(meas)
    assert all(isinstance(r, ServeResult) for r in res)
    counts1 = eng.compiled_step_counts()
    assert sum(counts1.values()) == sum(counts0.values()), (counts0, counts1)


def test_scheduler_paged_join_zero_recompiles(lstm_engine):
    """The fused LSTM join compiles one executable per page-grid bucket: a
    second scheduler over prompt lengths a first one already served
    compiles nothing at all — no join bucket, no step, no eager op."""
    cfg, eng = lstm_engine
    lengths = (1, 3, 4, 6, 9, 12, 13)

    def batch(seed):
        rng = np.random.default_rng(seed)
        return [ServeRequest(prompt=rng.integers(0, cfg.vocab_size, size=n)
                             .astype(np.int32), max_new=3) for n in lengths]

    ContinuousScheduler(eng, max_slots=3,
                        kv_pool=PagePool(64, 4)).serve(batch(43))
    join = eng._lstm_join_step(eng.resolve_head(None))._inner_jit
    n_join = join._cache_size()
    assert n_join > 0
    compiles = []

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        res = ContinuousScheduler(eng, max_slots=3,
                                  kv_pool=PagePool(64, 4)).serve(batch(47))
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert all(isinstance(r, ServeResult) for r in res)
    assert compiles == []
    assert join._cache_size() == n_join


# -- fused LSTM join: one dispatch, page-grid buckets, lazy snapshots ---------

P_FUSED = 4
# (kind, cached tokens t, prompt length): every prompt length 1..3P+1, so
# every bucket and every pad amount; resumes from a partial tail
# (t % P != 0), a suffix longer than a page among them; whole-prompt hits
# from an identical prompt and from a page-aligned prefix of a longer one
_FUSED_CASES = (
    [("cold", 0, n) for n in range(1, 3 * P_FUSED + 2)]
    + [("resume", n - 1 if (n - 1) % P_FUSED else n - 2, n)
       for n in range(2, 3 * P_FUSED + 2)]
    + [("resume", 1, 13), ("resume", 3, 13), ("resume", 5, 11)]
    + [("whole", n, n) for n in range(1, 3 * P_FUSED + 2)]
    + [("prefix", n, n) for n in (4, 8, 12)])


def _resume_prefill_state(eng, prompt):
    """Reference snapshot: ``_jit_resume_prefill`` over ``prompt`` from
    zeros, per layer ``{"h", "c"}``."""
    cache = eng.model.init_cache(1, eng.max_len, dtype=eng.cache_dtype)
    _, cache = eng._jit_resume_prefill(
        eng.params, {"tokens": jnp.asarray(prompt[None])}, cache)
    return cache["lstm"]


@pytest.mark.parametrize("kind,t,n", _FUSED_CASES)
def test_lstm_fused_join_parity(lstm_engine, kind, t, n):
    """One fused join per case: tokens bit-identical to solo generate,
    the join resumed from exactly ``t`` cached tokens, and every page of
    the prompt carries a snapshot handle that resolves to the state
    ``_jit_resume_prefill`` reaches from zeros over the page's prefix."""
    cfg, eng = lstm_engine
    P = P_FUSED
    rng = np.random.default_rng(1000 + 31 * t + n)
    full = rng.integers(0, cfg.vocab_size, size=13).astype(np.int32)
    prompt = full[:n]
    pool = PagePool(64, P)
    stream = eng.open_paged_stream(pool, width=2)
    first = {"resume": full[:t], "whole": prompt, "prefix": full}.get(kind)
    if first is not None:
        _run_stream(stream, [ServeRequest(prompt=first, max_new=2)])
    hit0 = pool.radix.tokens_hit
    req = ServeRequest(prompt=prompt, max_new=4)
    got = _run_stream(stream, [req])[0]
    ref = eng.generate(prompt[None], req.max_new).tokens[0]
    np.testing.assert_array_equal(got, ref)
    assert pool.radix.tokens_hit - hit0 == t
    for end in [*range(P, n, P), n]:
        handle = pool.radix.match(prompt[:end], peek=True).payload
        want = _resume_prefill_state(eng, prompt[:end])
        for layer, (g, w) in enumerate(zip(handle.resolve(), want)):
            for k in ("h", "c"):
                np.testing.assert_array_equal(
                    np.asarray(g[k]), np.asarray(w[k]),
                    err_msg=f"end {end} layer {layer} {k}")


def _count_calls(calls, name, fn):
    def wrapped(*args, **kw):
        calls[name] += 1
        return fn(*args, **kw)
    return wrapped


def test_lstm_join_is_one_dispatch(lstm_engine, monkeypatch):
    """Through the scheduler, every paged LSTM join is exactly one call of
    the head's join executable — cold, resumed or whole-prompt hit — and
    neither the head-less ``_jit_lstm_join`` nor the chunked
    ``_jit_resume_prefill`` is ever called."""
    cfg, eng = lstm_engine
    calls = {"join": 0, "headless": 0, "resume": 0}
    join_step = eng._lstm_join_step
    monkeypatch.setattr(eng, "_lstm_join_step", lambda *a: _count_calls(
        calls, "join", join_step(*a)))
    monkeypatch.setattr(eng, "_jit_lstm_join", _count_calls(
        calls, "headless", eng._jit_lstm_join))
    monkeypatch.setattr(eng, "_jit_resume_prefill", _count_calls(
        calls, "resume", eng._jit_resume_prefill))
    rng = np.random.default_rng(53)
    base = rng.integers(0, cfg.vocab_size, size=13).astype(np.int32)
    prompts = [base, base[:6], base[:8]]            # resumed, whole hits
    prompts += [rng.integers(0, cfg.vocab_size, size=m).astype(np.int32)
                for m in (1, 2, 5, 7, 10, 13)]
    tr = Tracer()
    res = ContinuousScheduler(eng, max_slots=3, kv_pool=PagePool(64, 4),
                              tracer=tr).serve(
        [ServeRequest(prompt=p, max_new=3) for p in prompts])
    assert all(isinstance(r, ServeResult) for r in res)
    joins = [e for e in tr.events() if e["name"] == "serve.join"]
    assert len(joins) == len(prompts)
    assert calls == {"join": len(joins), "headless": 0, "resume": 0}
    forward = [j for j in joins if j["args"]["chunks"] == 1]
    whole = [j for j in joins if j["args"]["chunks"] == 0]
    assert len(forward) + len(whole) == len(joins)
    assert whole and all(j["args"]["cached_tokens"] == j["args"]["prompt_len"]
                         for j in whole)


# -- the first token inside the join executable --------------------------------

def _join_prompts(cfg):
    """Cold, whole-prompt hits (page-aligned and not), a resume from a
    page boundary inside a shared chain, and a short cold prompt."""
    rng = np.random.default_rng(59)
    base = rng.integers(0, cfg.vocab_size, size=13).astype(np.int32)
    other = rng.integers(0, cfg.vocab_size, size=5).astype(np.int32)
    return [base, base[:8], base, np.concatenate([base[:6], other]),
            other[:3]]


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
@pytest.mark.parametrize("head", ["exact", "screened-pallas"])
def test_lstm_join_first_token_parity(pallas_engine, monkeypatch, head,
                                      sampled):
    """The head's join executable gives the first token, the page
    snapshots and the new cache bit-identical to the head-less
    ``_jit_lstm_join`` followed by the eager ``_first_token``, on cold,
    resumed and whole-prompt joins at P=4; a sampled join leaves the
    stream's key where the eager draw leaves it."""
    cfg, eng = pallas_engine
    kw = dict(temperature=0.8, seed=5) if sampled else {}
    stream = eng.open_paged_stream(PagePool(64, P_FUSED), head=head,
                                   width=2, **kw)
    join_step, suffixes, keys = eng._lstm_join_step, [], []

    def checked(*a):
        fn = join_step(*a)

        def run(params, *args):
            out = fn(params, *args)
            ints, src, group = args[-3:]
            h_last, snaps, cache = eng._jit_lstm_join(params, ints, src,
                                                      group)
            key = stream._key if sampled else None
            first = stream._first_token(h_last)
            if sampled:
                np.testing.assert_array_equal(
                    jax.random.key_data(out[1]),
                    jax.random.key_data(stream._key))
                keys.append(stream._key)
                stream._key = key        # the join itself commits it
            assert int(np.asarray(out[0]).ravel()[0]) == first
            for g, w in zip(jax.tree.leaves(out[-2:]),
                            jax.tree.leaves((snaps, cache))):
                np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
            suffixes.append(int(ints[0]))
            return out
        return run

    monkeypatch.setattr(eng, "_lstm_join_step", checked)
    prompts = _join_prompts(cfg)
    for p in prompts:
        stream.join(ServeRequest(prompt=p, max_new=2))
        if sampled:
            np.testing.assert_array_equal(jax.random.key_data(stream._key),
                                          jax.random.key_data(keys[-1]))
        stream.step()
    assert len(suffixes) == len(prompts)
    kinds = {"cold" if n == len(p) else "whole" if n == 0 else "resumed"
             for n, p in zip(suffixes, prompts)}
    assert kinds == {"cold", "whole", "resumed"}


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
def test_lstm_join_head_runs_inside_join(lstm_engine, monkeypatch, sampled):
    """Through the scheduler, a paged LSTM join on a jittable head is one
    call of the head's join executable and nothing else: no head-less
    join, no ``head.next`` or ``head.sample`` outside an executable, no
    ``serve.join.first`` span, and its prefill notes ``first_in_join``."""
    cfg, eng = lstm_engine
    head = eng.resolve_head(None)
    assert head.is_jittable
    calls = {"join": 0, "headless": 0, "eager_head": 0}
    join_step = eng._lstm_join_step
    monkeypatch.setattr(eng, "_lstm_join_step", lambda *a: _count_calls(
        calls, "join", join_step(*a)))
    monkeypatch.setattr(eng, "_jit_lstm_join", _count_calls(
        calls, "headless", eng._jit_lstm_join))
    for name, argnum in (("next", 1), ("sample", 2)):
        method = getattr(type(head), name)

        def eager_counted(*a, _method=method, _argnum=argnum, **kw):
            if not isinstance(a[_argnum], jax.core.Tracer):
                calls["eager_head"] += 1
            return _method(*a, **kw)
        monkeypatch.setattr(type(head), name, eager_counted)
    rng = np.random.default_rng(61)
    base = rng.integers(0, cfg.vocab_size, size=10).astype(np.int32)
    prompts = [base, base[:8], base[:5]]
    prompts += [rng.integers(0, cfg.vocab_size, size=m).astype(np.int32)
                for m in (3, 7)]
    sampling = dict(temperature=0.7, seed=3) if sampled else {}
    tr = Tracer()
    res = ContinuousScheduler(eng, max_slots=2, kv_pool=PagePool(64, 4),
                              tracer=tr).serve(
        [ServeRequest(prompt=p, max_new=3, **sampling) for p in prompts])
    assert all(isinstance(r, ServeResult) for r in res)
    joins = [e for e in tr.events() if e["name"] == "serve.join"]
    assert len(joins) == len(prompts)
    assert calls == {"join": len(joins), "headless": 0, "eager_head": 0}
    prefills = [e for e in tr.events() if e["name"] == "serve.join.prefill"]
    assert [e["args"]["first_in_join"] for e in prefills] == [1] * len(joins)
    assert not [e for e in tr.events() if e["name"] == "serve.join.first"]


@pytest.mark.parametrize("head", ["exact", "screened-pallas"])
def test_lstm_join_step_takes_head_weights_as_arguments(pallas_engine, head):
    """The head's join executable lowers with the head's arrays as
    arguments, never as captured constants."""
    cfg, eng = pallas_engine
    S = eng.max_len // P_FUSED
    cache = eng.model.init_cache(2, eng.max_len, dtype=eng.cache_dtype)
    src = jax.tree.map(lambda a: jnp.zeros((S, 1, *a.shape[1:]), a.dtype),
                       cache["lstm"])
    ints = np.zeros(3 + S + P_FUSED, np.int32)
    fn = eng._lstm_join_step(eng.resolve_head(head))
    was = jax.config.jax_captured_constants_warn_bytes
    jax.config.update("jax_captured_constants_warn_bytes", 1024)
    try:
        with warnings.catch_warnings():
            warnings.filterwarnings("error", message=".*constants were "
                                    "captured")
            fn.lower(eng.params, ints, src, cache)
    finally:
        jax.config.update("jax_captured_constants_warn_bytes", was)


# -- multidevice: paged decode under a vocab-sharded head ---------------------

@pytest.mark.multidevice
def test_paged_stream_parity_sharded_head(lstm_engine, multidevice):
    """The sharded-matrix acceptance case: paged streams through an
    8-device vocab-sharded exact head stay bit-identical to solo
    generate on the same head."""
    cfg, eng = lstm_engine
    reqs = _prefix_requests(cfg, 4, max_new=4, seed=41)
    pool = PagePool(64, 4)
    stream = eng.open_paged_stream(pool, head="exact-sharded", width=2)
    got = _run_stream(stream, reqs)
    for i, r in enumerate(reqs):
        ref = eng.generate(r.prompt[None], r.max_new,
                           head="exact-sharded").tokens[0]
        assert np.array_equal(got[i], ref), f"request {i} diverged"
