"""Resilience layer: deterministic fault injection (seeded replay, count /
after windows), the always-on token guards (honest degeneration detection),
per-head circuit-breaker lifecycle, the stream watchdog, per-request
timeouts, typed ``SchedulerStalled`` drains, crash-safe benchmark JSON —
and the chaos acceptance test: 54 requests over three heads under
transient + permanent + NaN + stall fire, where drain() terminates, every
request resolves typed, fault-free survivors stay bit-identical to solo
generate, breaker transitions land in ``ServerStats``, and the recompile
count after warmup is zero."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import L2SConfig, TrainConfig, get_config
from repro.core import collect_contexts, fit_l2s
from repro.data import ZipfMarkovCorpus, make_lm_batches
from repro.launch.steps import make_train_step
from repro.models import build_model
from repro.optim import adamw_init
from repro.serving import (AdmissionRejected, CircuitBreaker,
                           ContinuousScheduler, DecodeEngine, FaultInjector,
                           FaultSpec, HeadFault, LogicalClock, PagePool,
                           PoolExhausted, SchedulerStalled, ServeRequest,
                           ServeResult, StaticPolicy, StreamWatchdog,
                           TierPolicy)
from repro.serving.resilience.breaker import CLOSED, HALF_OPEN, OPEN
from repro.serving.resilience.faults import guard_tokens, invalid_token_rows
from repro.serving.scheduler import TIER_DEADLINES


# -- unit: LogicalClock / FaultSpec / FaultInjector ---------------------------

def test_logical_clock_reads_and_advances():
    clk = LogicalClock(10.0, dt_per_read=0.5)
    assert clk() == 10.5 and clk() == 11.0
    assert clk.advance(2.0) == 13.0
    frozen = LogicalClock(3.0)              # dt_per_read=0: reads are free
    assert frozen() == 3.0 and frozen() == 3.0


def test_fault_spec_validation():
    with pytest.raises(ValueError, match="site"):
        FaultSpec(site="decode", kind="transient")
    with pytest.raises(ValueError, match="kind"):
        FaultSpec(site="step", kind="explode")
    with pytest.raises(ValueError, match="rate"):
        FaultSpec(site="step", kind="transient", rate=1.5)


def test_injector_count_and_after_window():
    """rate=1 spec with after=3, count=2 fires on opportunities 4 and 5
    exactly — never earlier, never again."""
    inj = FaultInjector()
    inj.arm("step", "transient", head="h", count=2, after=3)
    outcomes = []
    for _ in range(8):
        try:
            inj.raise_for("step", "h")
            outcomes.append(False)
        except HeadFault as e:
            assert e.transient and e.injected and e.head == "h"
            outcomes.append(True)
    assert outcomes == [False] * 3 + [True] * 2 + [False] * 3
    assert inj.telemetry()["fired_total"] == 2


def test_injector_head_filter_and_permanent():
    inj = FaultInjector()
    inj.arm("step", "permanent", head="svd", count=1)
    inj.raise_for("step", "screened")       # other heads unaffected
    with pytest.raises(HeadFault) as ei:
        inj.raise_for("step", "svd")
    assert not ei.value.transient and ei.value.kind == "permanent"


def test_injector_deterministic_replay():
    """Same seed + specs + call sequence → the identical fault schedule
    (every matching spec consumes one rng draw whether or not it fires)."""
    def drive(inj):
        trace = []
        for i in range(40):
            head = ("screened", "svd", "exact")[i % 3]
            try:
                inj.raise_for("step", head)
                trace.append("ok")
            except HeadFault as e:
                trace.append(e.kind)
            trace.append(inj.stalled(head))
            toks = inj.corrupt("step", head, np.array([1, 2, 3]))
            trace.append(toks.dtype.kind)
            trace.append(inj.on_tick())
        return trace, inj.telemetry()

    def build():
        inj = FaultInjector(seed=123)
        inj.arm("step", "transient", rate=0.3)
        inj.arm("step", "stall", head="exact", rate=0.5)
        inj.arm("step", "nan", head="screened", rate=0.2)
        inj.arm("tick", "delay", rate=0.25, delay_s=1e-3)
        return inj

    t1, tel1 = drive(build())
    t2, tel2 = drive(build())
    assert t1 == t2 and tel1 == tel2
    assert tel1["fired_total"] > 0          # the schedule is non-trivial


# -- unit: token guards (always on) -------------------------------------------

def test_invalid_token_rows_flags_nan_and_out_of_range():
    assert invalid_token_rows(np.array([0, 7, 8]), vocab=8) == [2]
    assert invalid_token_rows(np.array([1.0, np.nan]), vocab=8) == [1]
    assert invalid_token_rows(np.array([-1, 3]), vocab=8) == [0]
    # rows restricts to ACTIVE slots: pad rows legally decode garbage
    assert invalid_token_rows(np.array([9, 3, 9]), vocab=8, rows=[1]) == []


def test_guard_tokens_honest_detection_without_injector():
    """No injector at all: a head that emits sentinel/out-of-range ids
    still surfaces as a typed, retryable HeadFault — the guard is the
    honest-degeneration detector, not just the chaos hook."""
    ok = guard_tokens(None, "step", "h", np.array([0, 5]), vocab=8)
    np.testing.assert_array_equal(ok, [0, 5])
    with pytest.raises(HeadFault) as ei:
        guard_tokens(None, "step", "h", np.array([0, -1]), vocab=8)
    e = ei.value
    assert e.kind == "corrupt" and e.transient and not e.injected


def test_guard_tokens_injected_corruption():
    inj = FaultInjector()
    inj.arm("step", "nan", head="h", count=1)
    inj.arm("step", "sentinel", head="h", count=1)
    for _ in range(2):                      # one NaN fire, one sentinel fire
        with pytest.raises(HeadFault) as ei:
            guard_tokens(inj, "step", "h", np.array([1, 2]), vocab=8)
        assert ei.value.kind == "corrupt" and ei.value.injected
    np.testing.assert_array_equal(          # specs exhausted: clean again
        guard_tokens(inj, "step", "h", np.array([1, 2]), vocab=8), [1, 2])


# -- unit: circuit breaker ----------------------------------------------------

def test_breaker_full_lifecycle():
    """closed → (threshold soft failures) open → cooldown → half-open
    probe → success closes; every transition hits on_transition."""
    clk = LogicalClock(0.0)
    seen = []
    br = CircuitBreaker(failure_threshold=3, cooldown_s=1.0, clock=clk,
                        on_transition=lambda h, o, n: seen.append((h, o, n)))
    br.record_failure("svd")
    br.record_failure("svd")
    assert br.state("svd") == CLOSED and br.allow("svd")
    br.record_success("svd")                # resets the consecutive counter
    br.record_failure("svd")
    br.record_failure("svd")
    br.record_failure("svd")                # third consecutive: trip
    assert br.state("svd") == OPEN and not br.allow("svd")
    clk.advance(1.5)                        # past cooldown
    assert br.allow("svd")                  # the probe transitions
    assert br.state("svd") == HALF_OPEN
    br.record_success("svd")
    assert br.state("svd") == CLOSED
    assert seen == [("svd", CLOSED, OPEN), ("svd", OPEN, HALF_OPEN),
                    ("svd", HALF_OPEN, CLOSED)]


def test_breaker_hard_fault_trips_instantly_and_half_open_reopens():
    clk = LogicalClock(0.0)
    br = CircuitBreaker(failure_threshold=99, cooldown_s=1.0, clock=clk)
    br.record_failure("exact", kind="permanent", hard=True)
    assert br.state("exact") == OPEN
    clk.advance(2.0)
    assert br.allow("exact") and br.state("exact") == HALF_OPEN
    br.record_failure("exact")              # probe failed: re-open
    assert br.state("exact") == OPEN and not br.allow("exact")
    assert br.telemetry()["exact"]["failures"] == 2
    assert br.open_heads() == ("exact",)


def test_breaker_latency_spikes_count_as_soft_failures():
    br = CircuitBreaker(failure_threshold=2, latency_spike_s=0.1,
                        clock=LogicalClock(0.0))
    br.record_latency("h", 0.05)            # under threshold: ignored
    br.record_latency("h", 0.2)
    br.record_latency("h", 0.3)
    assert br.state("h") == OPEN
    with pytest.raises(ValueError):
        CircuitBreaker(failure_threshold=0)


# -- unit: watchdog / request timeout / SchedulerStalled ----------------------

def test_watchdog_stall_detection_and_forget():
    wd = StreamWatchdog(stall_timeout_s=1.0)
    assert wd.armed
    wd.observe(1, 0, now=0.0)
    wd.observe(2, 0, now=0.0)
    wd.observe(1, 3, now=1.0)               # rid 1 progressed; rid 2 did not
    assert wd.stalled(now=1.5) == [2]
    wd.forget(2)
    assert wd.stalled(now=9.0) == [1]       # rid 1 idle since t=1.0 now too
    assert StreamWatchdog().armed is False and StreamWatchdog().stalled(5) == []
    with pytest.raises(ValueError):
        StreamWatchdog(stall_timeout_s=0)


def test_request_timeout_s_validation():
    p = np.array([1, 2, 3], np.int32)
    assert ServeRequest(prompt=p, max_new=2).timeout_s is None
    assert ServeRequest(prompt=p, max_new=2, timeout_s=0.5).timeout_s == 0.5
    for bad in (0, -1.0):
        with pytest.raises(ValueError, match="timeout_s"):
            ServeRequest(prompt=p, max_new=2, timeout_s=bad)


def test_scheduler_stalled_carries_rids_and_stats():
    e = SchedulerStalled("stuck", rids=[3, 5], stats={"ticks": 7})
    assert isinstance(e, RuntimeError)      # existing catch-alls still work
    assert e.rids == (3, 5) and e.stats == {"ticks": 7}


# -- unit: crash-safe benchmark JSON (satellite: atomic update_bench_json) ----

def test_update_bench_json_atomic_and_corruption_tolerant(tmp_path, capsys):
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                    "benchmarks"))
    try:
        from common import update_bench_json
    finally:
        sys.path.pop(0)
    path = str(tmp_path / "BENCH.json")
    update_bench_json("a", {"x": 1, "bad": float("nan")}, path=path)
    update_bench_json("b", {"y": 2}, path=path)
    with open(path) as f:
        data = json.load(f)                 # strict JSON: NaN became null
    # every section carries the schema stamp (satellite: versioned bench
    # sections); the payload fields survive unchanged beside it
    for sec in data.values():
        assert sec.pop("schema_version") >= 2
        assert "T" in sec.pop("generated_at")
    assert data == {"a": {"x": 1, "bad": None}, "b": {"y": 2}}
    # a corrupt existing file is loudly rebuilt, never crashes the merge
    with open(path, "w") as f:
        f.write('{"a": {truncated')
    update_bench_json("c", {"z": 3}, path=path)
    assert "WARNING" in capsys.readouterr().out
    with open(path) as f:
        got = json.load(f)
    assert list(got) == ["c"] and got["c"]["z"] == 3
    # no temp siblings left behind
    assert os.listdir(tmp_path) == ["BENCH.json"]


# -- integration: scheduler under fire ----------------------------------------

@pytest.fixture(scope="module")
def trained():
    """Small trained LSTM + fitted screen shared by the resilience tests
    (the scheduler-test recipe: screened / svd / exact all cataloged)."""
    cfg = get_config("ptb-small-lstm").reduced()
    m = build_model(cfg)
    params = m.init(jax.random.key(0), dtype=jnp.float32)
    corpus = ZipfMarkovCorpus(cfg.vocab_size, branching=32, seed=3)
    tcfg = TrainConfig(lr=2e-3, total_steps=60, warmup_steps=5,
                       remat="none", loss_chunk=None)
    step = jax.jit(make_train_step(m, tcfg))
    opt = adamw_init(params)
    for batch in make_lm_batches(corpus, 60, 8, 32, seed=1):
        params, opt, _ = step(params, opt,
                              {k: jnp.asarray(v) for k, v in batch.items()})
    H, y = collect_contexts(
        m, params, [jnp.asarray(b["tokens"])
                    for b in make_lm_batches(corpus, 8, 8, 32, seed=9)],
        max_vectors=2000)
    st = fit_l2s(H, y, cfg.vocab_size,
                 L2SConfig(num_clusters=16, budget=64, outer_iters=1,
                           sgd_steps=50))
    return cfg, m, params, corpus, st


def _engine(trained, max_len=36):
    cfg, m, params, _, st = trained
    return DecodeEngine(m, params, screen=st.screen, max_len=max_len,
                        head_kwargs=dict(rho=cfg.d_model,
                                         n_top=cfg.vocab_size))


def test_transient_fault_retries_bit_identical(trained, page_pool):
    """One injected transient step fault: the scheduler retries the SAME
    stream after backoff and — because the streams commit key/cache only
    after the guard passes — the greedy decode is bit-identical to the
    fault-free run."""
    cfg, _, _, corpus, _ = trained
    eng = _engine(trained)
    req = ServeRequest(prompt=corpus.sample_batch(1, 6, seed=41)[0],
                       max_new=6)
    ref = ContinuousScheduler(
        eng, policy=StaticPolicy("screened"), max_slots=2,
        kv_pool=page_pool(eng)).serve([req])[0]
    assert isinstance(ref, ServeResult) and ref.head == "screened"

    inj = FaultInjector(seed=0)
    inj.arm("step", "transient", head="screened", count=2)
    sched = ContinuousScheduler(eng, policy=StaticPolicy("screened"),
                                max_slots=2, fault_injector=inj,
                                breaker=CircuitBreaker(failure_threshold=5,
                                                       clock=LogicalClock()),
                                max_retries=3, kv_pool=page_pool(eng))
    out = sched.serve([req])[0]
    assert isinstance(out, ServeResult) and out.head == "screened"
    np.testing.assert_array_equal(out.tokens, ref.tokens)
    rz = sched.stats.snapshot()["resilience"]
    assert rz["faults_transient"] == 2 and rz["retries"] == 2
    assert rz["fallbacks"] == 0 and rz["faulted"] == 0
    assert 0 in sched.fault_rids            # parity excludes touched rids


def test_permanent_fault_trips_breaker_and_falls_back(trained, page_pool):
    """A hard fault on the routed head: instant breaker trip, the running
    request re-routes to a healthy head (exact is the universal last
    resort) and completes there — output equals exact's solo decode."""
    cfg, _, _, corpus, _ = trained
    eng = _engine(trained)
    req = ServeRequest(prompt=corpus.sample_batch(1, 6, seed=43)[0],
                       max_new=6)
    inj = FaultInjector(seed=0)
    inj.arm("step", "permanent", head="svd", count=1)
    br = CircuitBreaker(failure_threshold=3, cooldown_s=100.0,
                        clock=LogicalClock())
    sched = ContinuousScheduler(eng, policy=StaticPolicy("svd"), max_slots=2,
                                fault_injector=inj, breaker=br,
                                kv_pool=page_pool(eng))
    out = sched.serve([req])[0]
    assert isinstance(out, ServeResult) and out.head == "exact"
    ref = eng.generate(req.prompt[None], req.max_new).tokens[0]
    np.testing.assert_array_equal(out.tokens, ref)
    assert br.state("svd") == OPEN
    rz = sched.stats.snapshot()["resilience"]
    assert rz["faults_permanent"] == 1 and rz["fallbacks"] >= 1
    assert rz["breaker_trips"] == 1
    assert rz["breaker_states"]["svd"] == OPEN
    assert any(h == "svd" and n == OPEN
               for _, h, _, n in rz["breaker_transitions"])


def test_breaker_open_vetoes_placement_until_half_open(trained, page_pool):
    """While a head's breaker is open, NEW requests route around it (the
    ``breaker_open`` stamp in head_eligible); after cooldown the half-open
    probe lets traffic place again and a success closes the breaker."""
    cfg, _, _, corpus, _ = trained
    eng = _engine(trained)
    clk = LogicalClock(0.0, dt_per_read=1e-3)
    br = CircuitBreaker(failure_threshold=1, cooldown_s=0.5, clock=clk)
    policy = TierPolicy({"realtime": "screened"}, default="screened")
    sched = ContinuousScheduler(eng, policy=policy, max_slots=2, clock=clk,
                                breaker=br, kv_pool=page_pool(eng))
    br.record_failure("screened", hard=True)        # trip it out-of-band
    p = corpus.sample_batch(2, 6, seed=47)
    out = sched.serve([ServeRequest(prompt=p[0], max_new=4)])[0]
    assert isinstance(out, ServeResult) and out.head != "screened"
    clk.advance(1.0)                                # past cooldown
    # results() is non-consuming: the second drain returns BOTH requests
    out2 = sched.serve([ServeRequest(prompt=p[1], max_new=4)])[-1]
    assert isinstance(out2, ServeResult) and out2.head == "screened"
    assert br.state("screened") == CLOSED           # probe succeeded
    rz = sched.stats.snapshot()["resilience"]
    assert rz["breaker_half_opens"] >= 1 and rz["breaker_closes"] >= 1


def test_request_timeout_returns_typed_partial(trained, page_pool):
    """timeout_s elapses mid-decode on the scheduler's clock: the request
    terminates as AdmissionRejected(stage="timeout") carrying the partial
    tokens; everything else completes untouched."""
    cfg, _, _, corpus, _ = trained
    eng = _engine(trained)
    clk = LogicalClock(0.0, dt_per_read=1e-3)
    p = corpus.sample_batch(2, 6, seed=53)
    slow = ServeRequest(prompt=p[0], max_new=24, timeout_s=0.02)
    fine = ServeRequest(prompt=p[1], max_new=4)
    sched = ContinuousScheduler(eng, max_slots=2, clock=clk,
                                kv_pool=page_pool(eng))
    res = sched.serve([slow, fine])
    assert isinstance(res[0], AdmissionRejected)
    assert res[0].stage == "timeout" and "timeout" in res[0].reason
    assert res[0].tokens is not None
    assert 0 < len(res[0].tokens) < slow.max_new    # a genuine partial
    assert isinstance(res[1], ServeResult)
    assert sched.stats.snapshot()["resilience"]["timed_out"] == 1


def test_watchdog_evicts_stalled_request_to_fallback(trained, page_pool):
    """An endless injected stall on the routed head: the watchdog notices
    zero token progress, evicts the request, and the fallback path serves
    it to completion on a healthy head."""
    cfg, _, _, corpus, _ = trained
    eng = _engine(trained)
    clk = LogicalClock(0.0, dt_per_read=1e-3)
    inj = FaultInjector(seed=0, clock=clk)
    inj.arm("step", "stall", head="screened")       # no count: forever
    sched = ContinuousScheduler(
        eng, policy=StaticPolicy("screened"), max_slots=2, clock=clk,
        fault_injector=inj, breaker=CircuitBreaker(clock=clk),
        watchdog=StreamWatchdog(stall_timeout_s=5e-3), kv_pool=page_pool(eng))
    req = ServeRequest(prompt=corpus.sample_batch(1, 6, seed=59)[0],
                       max_new=5)
    out = sched.serve([req])[0]
    assert isinstance(out, ServeResult) and out.head != "screened"
    rz = sched.stats.snapshot()["resilience"]
    assert rz["watchdog_stalls"] >= 1 and rz["fallbacks"] >= 1
    assert 0 in sched.fault_rids


def test_drain_stall_raises_typed_scheduler_stalled(trained, page_pool):
    """With NO watchdog and every head stalled, drain() cannot progress —
    it must raise the typed SchedulerStalled naming the stuck rids, not
    spin forever or return a short result list."""
    cfg, _, _, corpus, _ = trained
    eng = _engine(trained)
    inj = FaultInjector(seed=0)
    inj.arm("step", "stall")                        # any head, forever
    sched = ContinuousScheduler(eng, policy=StaticPolicy("exact"),
                                max_slots=2, fault_injector=inj,
                                kv_pool=page_pool(eng))
    sched.submit(ServeRequest(prompt=corpus.sample_batch(1, 6, seed=61)[0],
                              max_new=4))
    with pytest.raises(SchedulerStalled) as ei:
        sched.drain()
    assert ei.value.rids and ei.value.stats["ticks"] > 0


def test_drain_max_ticks_exhaustion_raises(trained, page_pool):
    cfg, _, _, corpus, _ = trained
    eng = _engine(trained)
    sched = ContinuousScheduler(eng, max_slots=2, kv_pool=page_pool(eng))
    sched.submit(ServeRequest(prompt=corpus.sample_batch(1, 6, seed=67)[0],
                              max_new=20))
    with pytest.raises(SchedulerStalled, match="max_ticks"):
        sched.drain(max_ticks=3)


def _paged_stream_state(stream):
    """Everything a paged LSTM join may touch, as host values: the cache,
    tokens, positions, slots, page chains, pool refcounts, and the radix
    tree (path, page, payload identity) with its counters. LRU stamps are
    left out: the radix match that opens a join refreshes them whether or
    not the join goes on to succeed."""
    radix, nodes = stream.pool.radix, []
    stack = [(radix.root, ())]
    while stack:
        node, path = stack.pop()
        for child in [*node.children.values(), *node.partials]:
            key = path + child.tokens
            nodes.append((key, child.page, id(child.payload)))
            stack.append((child, key))
    return {"cache": [np.asarray(a) for a in jax.tree.leaves(stream.cache)],
            "tok": stream.tok.tolist(), "pos": stream.pos.tolist(),
            "slots": list(stream.slots),
            "pages": [list(c) for c in stream._pages],
            "refs": stream.pool.live_pages(), "nodes": sorted(nodes),
            "counts": (radix.nodes, radix.evictions, radix.lookups,
                       radix.lookup_hits, radix.tokens_hit,
                       radix.tokens_total)}


def _assert_same_state(a, b):
    assert len(a["cache"]) == len(b["cache"])
    for x, y in zip(a["cache"], b["cache"]):
        np.testing.assert_array_equal(x, y)
    for key in a:
        if key != "cache":
            assert a[key] == b[key], key


@pytest.mark.parametrize("fault", ["head", "pool"])
def test_paged_lstm_join_rolls_back_bit_identical(trained, fault):
    """A join that fails after its device dispatch — a ``HeadFault`` at the
    first-token guard, or ``PoolExhausted`` at the COW of the partial tail
    it extends — leaves the stream's cache, tokens, positions, page chains
    and radix exactly as they were; the retried join then matches a clean
    run and solo generate."""
    cfg, _, _, corpus, _ = trained
    eng = _engine(trained)
    a, b, c = corpus.sample_batch(3, 9, seed=71)
    a = a[:6]                         # a partial tail at 6 (P = 4)
    req_b = ServeRequest(prompt=np.concatenate([a, b[:3]]), max_new=4)

    def drain(stream):
        out = {}
        while stream.n_active:
            out.update((tag, t) for tag, _, t in stream.step())
        return out

    clean_stream = eng.open_paged_stream(PagePool(64, 4), head="exact",
                                         width=3)
    clean_stream.join(ServeRequest(prompt=a, max_new=4), tag="a")
    clean_stream.join(req_b, tag="b")
    clean = drain(clean_stream)["b"]
    # "pool": 4 allocatable pages, two held by a and two by c, so b's COW
    # of a's shared tail page finds nothing free and nothing reclaimable
    pool = PagePool(64 if fault == "head" else 5, 4)
    stream = eng.open_paged_stream(pool, head="exact", width=3)
    stream.join(ServeRequest(prompt=a, max_new=4), tag="a")
    stream.join(ServeRequest(prompt=c[:8], max_new=4), tag="c")
    if fault == "head":
        stream.step()
        inj = FaultInjector(seed=0)
        inj.arm("join", "transient", count=1)
        stream.fault_injector = inj
    before = _paged_stream_state(stream)
    with pytest.raises(HeadFault if fault == "head" else PoolExhausted):
        stream.join(req_b, tag="b")
    _assert_same_state(_paged_stream_state(stream), before)
    if fault == "pool":
        for i, _ in stream.occupied():
            stream.evict(i)
    stream.join(req_b, tag="b")
    got = drain(stream)["b"]
    np.testing.assert_array_equal(got, clean)
    np.testing.assert_array_equal(
        got, eng.generate(req_b.prompt[None], 4, head="exact").tokens[0])


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_fault_paths_leak_no_kv_pages(trained, seed):
    """Property-style (satellite): under a paged KV pool, every fault /
    retry / fallback / stall path releases exactly the pages it held —
    after drain the pool returns to empty with exact refcounts and
    in_use + free == total."""
    cfg, _, _, corpus, _ = trained
    eng = _engine(trained)
    clk = LogicalClock(0.0, dt_per_read=1e-3)
    inj = FaultInjector(seed=seed, clock=clk)
    inj.arm("step", "transient", head="screened", rate=0.4, count=3)
    inj.arm("step", "permanent", head="svd", count=1, after=2)
    inj.arm("join", "transient", head="screened", count=1, after=1)
    inj.arm("step", "stall", head="exact", rate=0.5, count=4)
    pool = PagePool(64, 4)
    sched = ContinuousScheduler(
        eng, policy=TierPolicy({"realtime": "screened", "standard": "svd",
                                "batch": "exact"}, default="screened"),
        max_slots=3, clock=clk, kv_pool=pool, fault_injector=inj,
        breaker=CircuitBreaker(failure_threshold=2, cooldown_s=0.05,
                               clock=clk),
        watchdog=StreamWatchdog(stall_timeout_s=5e-3))
    prompts = corpus.sample_batch(9, 6, seed=100 + seed)
    tiers = ["realtime", "standard", "batch"]
    res = sched.serve([ServeRequest(prompt=p, max_new=4 + (i % 3),
                                    latency_tier=tiers[i % 3])
                       for i, p in enumerate(prompts)])
    assert len(res) == 9
    assert all(isinstance(r, (ServeResult, AdmissionRejected)) for r in res)
    assert pool.pages_free + pool.pages_in_use == 64 - 1    # conservation
    pool.radix.clear()                      # drop cached prefixes...
    assert pool.pages_in_use == 0           # ...and NOTHING else holds pages
    assert pool.live_pages() == {}


def test_chaos_54_requests_funnel_parity_breakers_recompiles(trained,
                                                             page_pool):
    """THE acceptance test: 54 requests across screened/svd/exact on one
    LogicalClock, under transient + permanent + NaN + stall + tick-delay
    fire with breaker, watchdog, retries and timeouts all armed. drain()
    terminates; every request resolves to ServeResult or a typed
    AdmissionRejected; fault-free survivors are bit-identical to solo
    generate; trip/half-open/close transitions are observable in the
    stats snapshot; and chaos adds ZERO step executables after warmup."""
    cfg, _, _, corpus, _ = trained
    eng = _engine(trained)
    policy = TierPolicy({"realtime": "screened", "standard": "svd",
                         "batch": "exact"}, default="screened")
    catalog = eng.head_catalog(tuple(policy.candidates))
    n_req, max_new = 54, 4
    prompts = corpus.sample_batch(n_req, 6, seed=71)
    tiers = ["realtime", "standard", "batch"]
    requests = [ServeRequest(prompt=p, max_new=max_new,
                             latency_tier=tiers[i % 3],
                             timeout_s=0.004 if i in (5, 11) else None)
                for i, p in enumerate(prompts)]

    # warmup compiles every greedy stream chaos could touch (same widths)
    warm = [ServeRequest(prompt=prompts[0], max_new=2, head=name)
            for name in catalog]
    ContinuousScheduler(eng, policy=policy, max_slots=3,
                        max_streams=len(catalog) + 1,
                        kv_pool=page_pool(eng)).serve(warm)
    counts0 = eng.compiled_step_counts()

    clock = LogicalClock(0.0, dt_per_read=1e-3)
    inj = FaultInjector(seed=7, clock=clock)
    inj.arm("step", "transient", head="screened", count=3, after=2)
    inj.arm("step", "permanent", head="svd", count=1, after=4)
    inj.arm("step", "nan", head="screened", count=2, after=12)
    inj.arm("step", "stall", head="exact", count=8, after=3)
    inj.arm("join", "transient", head="svd", count=1, after=8)
    inj.arm("tick", "delay", delay_s=2e-3, rate=0.1, count=5)
    sched = ContinuousScheduler(
        eng, policy=policy, max_slots=3, max_streams=8,
        deadlines={t: s * 10 for t, s in TIER_DEADLINES.items()},
        clock=clock, fault_injector=inj,
        breaker=CircuitBreaker(failure_threshold=3, cooldown_s=0.05,
                               clock=clock),
        watchdog=StreamWatchdog(stall_timeout_s=5e-3), max_retries=2,
        kv_pool=page_pool(eng))
    for r in requests:
        sched.submit(r)
    results = sched.drain(max_ticks=5000)   # terminates cleanly or raises
    counts1 = eng.compiled_step_counts()    # BEFORE the parity generates

    # funnel closure: every arrival resolves to exactly one typed result
    assert len(results) == n_req
    completed = [(i, r) for i, r in enumerate(results)
                 if isinstance(r, ServeResult)]
    rejects = [r for r in results if isinstance(r, AdmissionRejected)]
    assert len(completed) + len(rejects) == n_req
    assert all(r.stage in ("admission", "preempt", "fault", "timeout")
               for r in rejects)
    assert len(completed) >= n_req // 3     # chaos degrades, not destroys

    # fault-free survivors decode bit-identical to solo generate
    clean = [(i, r) for i, r in completed
             if i not in sched.fault_rids and r.head == "exact"]
    assert clean
    for i, r in clean[:8]:
        ref = eng.generate(requests[i].prompt[None], max_new).tokens[0]
        np.testing.assert_array_equal(r.tokens, ref)

    rz = sched.stats.snapshot()["resilience"]
    assert rz["faults_transient"] >= 1 and rz["faults_permanent"] >= 1
    assert rz["fault_kinds"].get("corrupt", 0) >= 1     # the NaN guard fired
    assert rz["watchdog_stalls"] >= 1                   # stalls were caught
    assert rz["retries"] >= 1 and rz["fallbacks"] >= 1
    assert rz["breaker_trips"] >= 1                     # trips observable...
    assert rz["breaker_half_opens"] >= 1                # ...and recovery too
    assert rz["breaker_transitions"]
    assert set(rz["breaker_states"]) <= set(catalog)
    assert inj.telemetry()["fired_total"] >= 10

    # chaos is host-side only: zero step executables after warmup
    assert sum(counts1.values()) == sum(counts0.values()), (counts0, counts1)
