"""Observability layer: Tracer ring buffer + Chrome trace-event export,
nested scopes (ids, parents, the profiler mirror), NullTracer no-op
contract, typed Counter/Gauge/Histogram + registry (monotonic mirroring,
label validation, Prometheus text exposition), ServerStats -> registry
mirroring, defensive snapshot copies, request spans submit->retire through
the scheduler (plus retry/fallback instants under injected faults, with
zero recompiles while traced), the ``serve.*`` span tree of a scheduler
run, schema-stamped bench JSON with loud old-schema upgrades, and
compiled_step_counts under paged / speculative / resilience step kinds."""
import contextlib
import json
import time
import tracemalloc

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
from repro.serving import (NULL_TRACER, CircuitBreaker, ContinuousScheduler,
                           DecodeEngine, FaultInjector, LogicalClock,
                           MetricsRegistry, NullTracer, PagePool,
                           ServeRequest, ServeResult, StaticPolicy,
                           TierPolicy, Tracer)
from repro.serving.observe.trace import SCHED_TID
from repro.serving.scheduler import ServerStats


class FakeClock:
    """Deterministic monotonic clock: advances ``dt`` per read."""

    def __init__(self, dt=0.0):
        self.t = 0.0
        self.dt = dt

    def __call__(self):
        self.t += self.dt
        return self.t


# -- unit: Tracer -------------------------------------------------------------

def test_tracer_ring_buffer_bounds_and_dropped():
    clk = FakeClock()
    tr = Tracer(clock=clk, capacity=4)
    for i in range(6):
        tr.instant(f"ev{i}", "test")
    assert tr.emitted == 6 and tr.dropped == 2
    assert [e["name"] for e in tr.events()] == ["ev2", "ev3", "ev4", "ev5"]
    tr.clear()
    assert tr.emitted == 0 and tr.events() == []


def test_tracer_chrome_trace_shape_and_export(tmp_path):
    clk = FakeClock()
    tr = Tracer(clock=clk)
    clk.t = 1.0
    tr.instant("submit", "request", tid=7, args={"tier": "realtime"})
    tr.span("request", "request", 1.0, 3.5, tid=7, args={"outcome": "ok"})
    tr.span("tick", "scheduler", 0.5, 2.0)          # scheduler lane
    doc = tr.chrome_trace()
    evs = [e for e in doc["traceEvents"] if e["ph"] != "M"]
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    # µs scaling, per-request lanes, labeled threads
    span = next(e for e in evs if e["name"] == "request")
    assert span["ts"] == pytest.approx(1.0e6)
    assert span["dur"] == pytest.approx(2.5e6)
    assert span["tid"] == 7 and span["pid"] == 1
    assert span["args"]["outcome"] == "ok"
    names = {m["tid"]: m["args"]["name"] for m in meta}
    assert names[SCHED_TID] == "scheduler" and names[7] == "request 7"
    assert doc["otherData"] == {"emitted": 3, "dropped": 0}
    # negative durations are clamped, not exported
    tr.span("bad", "test", 5.0, 4.0)
    assert tr.events()[-1]["dur"] == 0.0
    # both exports round-trip through strict JSON
    p = tr.export_chrome(str(tmp_path / "trace.json"))
    with open(p) as f:
        assert json.load(f)["displayTimeUnit"] == "ms"
    pl = tr.export_jsonl(str(tmp_path / "trace.jsonl"))
    with open(pl) as f:
        lines = [json.loads(ln) for ln in f]
    assert len(lines) == 4 and all("pid" in ln for ln in lines)


def _no_annotation(name):
    return contextlib.nullcontext()


def test_tracer_scope_nesting_ids_and_parents():
    """Each scope records one "X" span carrying its id and the id of the
    innermost scope open when it began; lanes default to the parent's;
    ``note`` fills the innermost open scope; a scope left by an exception
    still records and unwinds."""
    tr = Tracer(clock=FakeClock(dt=1.0), annotate=_no_annotation)
    with tr.scope("a", "t"):
        with tr.scope("b", "t", tid=7, k=1):
            tr.note(extra=2)
        with tr.scope("c", "t"):
            with tr.scope("d", "t"):
                pass
    with tr.scope("e", "t"):
        pass
    ev = {e["name"]: e for e in tr.events()}
    assert all(e["ph"] == "X" for e in ev.values())
    ids = {n: e["args"]["id"] for n, e in ev.items()}
    assert len(set(ids.values())) == 5
    parent = {n: e["args"]["parent"] for n, e in ev.items()}
    assert parent == {"a": None, "b": ids["a"], "c": ids["a"],
                      "d": ids["c"], "e": None}
    assert ev["b"]["tid"] == 7 and ev["b"]["args"]["k"] == 1 \
        and ev["b"]["args"]["extra"] == 2
    assert ev["a"]["tid"] == ev["c"]["tid"] == ev["d"]["tid"] == SCHED_TID
    # a parent's interval covers its children's on the tracer's clock
    end = {n: e["ts"] + e["dur"] for n, e in ev.items()}
    assert ev["a"]["ts"] < ev["b"]["ts"] and end["d"] < end["c"] < end["a"]
    with pytest.raises(ValueError):
        with tr.scope("f", "t"):
            raise ValueError("boom")
    with tr.scope("g", "t"):
        pass
    ev = {e["name"]: e for e in tr.events()}
    assert ev["f"]["args"]["error"] == "ValueError"
    assert ev["g"]["args"]["parent"] is None        # the stack unwound
    out = next(e for e in tr.chrome_trace()["traceEvents"]
               if e.get("name") == "d")
    assert out["args"]["parent"] == ids["c"] and out["dur"] == \
        pytest.approx(1e6)


def test_scope_mirrors_into_profiler_annotations():
    """While enabled, every scope enters and leaves exactly one profiler
    annotation of its own name, nested as the scopes are; plain spans and
    instants do not annotate."""
    log = []

    class Ann:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("enter", self.name))

        def __exit__(self, *exc):
            log.append(("exit", self.name))

    tr = Tracer(clock=FakeClock(), annotate=Ann)
    with tr.scope("serve.tick", "scheduler"):
        with tr.scope("serve.join", "join", tid=3):
            tr.instant("join", "queue", tid=3)
    tr.span("queue.wait", "queue", 0.0)
    assert log == [("enter", "serve.tick"), ("enter", "serve.join"),
                   ("exit", "serve.join"), ("exit", "serve.tick")]


def test_scope_lands_in_a_profiler_trace(tmp_path):
    """The default mirror is ``jax.profiler.TraceAnnotation``: a profiler
    trace taken meanwhile holds each scope as a host event of its name."""
    from jax.profiler import ProfileData
    tr = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.scope("serve.tick", "scheduler"):
            with tr.scope("serve.step", "step"):
                jnp.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    names = {ev.name for plane in ProfileData.from_file(str(path)).planes
             for line in plane.lines for ev in line.events}
    assert {"serve.tick", "serve.step"} <= names


def test_null_tracer_scope_allocates_and_records_nothing():
    """Tracing off: ``scope`` hands back one preallocated no-op context,
    and ten thousand of them allocate nothing that outlives them."""
    s = NULL_TRACER.scope("serve.tick", "scheduler")
    assert s is NULL_TRACER.scope("serve.join", "join", tid=3)

    def run(n):
        for _ in range(n):
            with NULL_TRACER.scope("serve.tick", "scheduler"):
                with NULL_TRACER.scope("serve.join", "join", tid=3):
                    pass

    run(10)
    tracemalloc.start()
    try:
        before = tracemalloc.get_traced_memory()[0]
        tracemalloc.reset_peak()
        run(10_000)
        now, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert now == before and peak - before < 1024
    assert NULL_TRACER.events() == [] and NULL_TRACER.emitted == 0


def test_null_tracer_is_inert_but_exports_empty(tmp_path):
    assert NULL_TRACER.enabled is False
    assert isinstance(NULL_TRACER, NullTracer)
    NULL_TRACER.span("x", "y", 0.0)
    NULL_TRACER.instant("x", "y")
    assert NULL_TRACER.events() == [] and NULL_TRACER.dropped == 0
    p = NULL_TRACER.export_chrome(str(tmp_path / "empty.json"))
    with open(p) as f:
        assert json.load(f)["traceEvents"] == []


# -- unit: metrics ------------------------------------------------------------

def test_counter_rejects_negative_and_regression():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total", "requests", ("event",))
    c.inc(2, event="ok")
    c.inc(event="ok")
    assert c.value(event="ok") == 3.0
    with pytest.raises(ValueError):
        c.inc(-1, event="ok")
    c.set_monotonic(7, event="ok")
    with pytest.raises(ValueError):                 # mirrored source ran back
        c.set_monotonic(5, event="ok")
    with pytest.raises(ValueError):                 # label set must match
        c.inc(1, evnt="typo")
    with pytest.raises(ValueError):
        c.inc(1)


def test_histogram_buckets_sum_count_and_exposition():
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 0.5, 5.0):
        h.observe(v)
    h.observe(float("nan"))                         # dropped, not counted
    assert h.count() == 4 and h.sum() == pytest.approx(6.05)
    g = reg.gauge("depth")
    g.set(3)
    g.inc()
    g.dec(2)
    assert g.value() == 2.0
    text = reg.prometheus_text()
    assert '# TYPE lat_seconds histogram' in text
    assert 'lat_seconds_bucket{le="0.1"} 1' in text
    assert 'lat_seconds_bucket{le="1"} 3' in text   # cumulative
    assert 'lat_seconds_bucket{le="+Inf"} 4' in text
    assert 'lat_seconds_count 4' in text
    assert '# TYPE depth gauge' in text and 'depth 2' in text


def test_registry_get_or_create_and_shape_conflicts():
    reg = MetricsRegistry()
    a = reg.counter("x_total", labelnames=("head",))
    assert reg.counter("x_total", labelnames=("head",)) is a
    with pytest.raises(ValueError):                 # kind mismatch
        reg.gauge("x_total", labelnames=("head",))
    with pytest.raises(ValueError):                 # labelnames mismatch
        reg.counter("x_total", labelnames=("event",))
    with pytest.raises(ValueError):                 # empty histogram buckets
        reg.histogram("h", buckets=())
    assert reg.get("x_total") is a and reg.get("nope") is None
    # collectors run before every exposition
    calls = []
    reg.register_collector(lambda: calls.append(1))
    reg.prometheus_text()
    reg.snapshot()
    assert len(calls) == 2


def test_server_stats_mirror_into_registry():
    st = ServerStats()
    st.submitted += 3
    st.admitted += 2
    st.rejected += 1
    st.record_decode("exact", 5, 0.25)
    st.record_completion("exact", latency_s=0.2, on_time=True)
    st.record_queue_wait(0.01)
    st.record_fault("transient", transient=True)
    st.record_retry()
    st.record_breaker("exact", "closed", "open")
    snap = st.metrics.snapshot()
    assert snap["serve_requests_total"]["values"]["event=submitted"] == 3
    assert snap["serve_requests_total"]["values"]["event=completed"] == 1
    assert snap["serve_head_tokens_total"]["values"]["head=exact"] == 5
    assert snap["serve_breaker_state"]["values"]["head=exact"] == 2  # open
    assert snap["serve_resilience_total"]["values"]["event=retries"] == 1
    lat = snap["serve_request_latency_seconds"]["values"]["_"]
    assert lat["count"] == 1 and lat["sum"] == pytest.approx(0.2)
    assert st.metrics.histogram("serve_queue_wait_seconds").count() == 1
    text = st.metrics.prometheus_text()
    assert 'serve_requests_total{event="submitted"} 3' in text
    assert 'serve_faults_total{kind="transient"} 1' in text


def test_snapshot_returns_defensive_copies():
    """Regression: snapshots are stashed and diffed across ticks, so a
    caller mutating one (including the NESTED pool/prefix dicts, which
    used to be live references) must never corrupt the stats or a
    previously-taken snapshot."""
    st = ServerStats()
    st.record_decode("exact", 4, 0.1)
    st.observe_pool({"pages_in_use": 2, "cow_copies": 1,
                     "prefix": {"tokens_hit": 10, "tokens_total": 12}})
    s1 = st.snapshot()
    s1["per_head"]["exact"]["tokens"] = 999
    s1["pool"]["prefix"]["tokens_hit"] = 999
    s1["pool"]["pages_in_use"] = 999
    s2 = st.snapshot()
    assert s2["per_head"]["exact"]["tokens"] == 4
    assert s2["pool"]["prefix"]["tokens_hit"] == 10
    assert s2["pool"]["pages_in_use"] == 2
    # and the live source was never touched either
    assert st.pool["prefix"]["tokens_hit"] == 10


# -- bench JSON schema stamps -------------------------------------------------

def test_update_bench_json_upgrades_old_schema_loudly(tmp_path, capsys):
    from benchmarks.common import SCHEMA_VERSION, update_bench_json
    path = str(tmp_path / "BENCH.json")
    # a pre-versioning (v1) file left by an older benchmark run
    with open(path, "w") as f:
        json.dump({"old_bench": {"tokens_per_s": 123.0}}, f)
    update_bench_json("new_bench", {"x": 1}, path=path)
    out = capsys.readouterr().out
    assert "WARNING" in out and "old_bench" in out and "schema v1" in out
    with open(path) as f:
        data = json.load(f)
    old = data["old_bench"]
    assert old["schema_version"] == SCHEMA_VERSION
    assert old["schema_upgraded_from"] == 1
    assert old["tokens_per_s"] == 123.0             # fields kept verbatim
    new = data["new_bench"]
    assert new["schema_version"] == SCHEMA_VERSION
    assert "schema_upgraded_from" not in new
    assert "generated_at" in new
    # re-merging is quiet: everything already stamped at current version
    update_bench_json("new_bench", {"x": 2}, path=path)
    assert "WARNING" not in capsys.readouterr().out


def test_serve_launcher_log_jsonl_requires_scheduler(capsys):
    """--log-jsonl without --scheduler fails with exit 2 BEFORE training."""
    from repro.launch import serve as serve_mod
    rc = serve_mod.main(["--arch", "ptb-small-lstm", "--reduced",
                         "--log-jsonl", "ticks.jsonl"])
    assert rc == 2
    out = capsys.readouterr().out
    assert "--log-jsonl needs --scheduler" in out
    assert "trained" not in out                     # guard beat the train loop


# -- integration: traced scheduler --------------------------------------------

@pytest.fixture(scope="module")
def trained():
    """Small trained LSTM + fitted screen (the scheduler-test recipe)."""
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


def _by_name(tr, name):
    return [e for e in tr.events() if e["name"] == name]


def test_scheduler_traces_request_lifecycle(trained):
    """Every completed request leaves one submit->retire "request" span on
    its own lane plus submit/admit/join instants and a queue.wait span;
    the scheduler lane carries tick spans; kernel dispatch windows are
    spanned — and tracing itself adds ZERO compiled steps."""
    cfg, _, _, corpus, _ = trained
    eng = _engine(trained)
    policy = TierPolicy({"realtime": "screened"}, default="exact")
    reqs = [ServeRequest(prompt=p, max_new=3,
                         latency_tier=("realtime", "standard")[i % 2])
            for i, p in enumerate(corpus.sample_batch(4, 6, seed=31))]
    # warmup so the traced drain is compile-free
    ContinuousScheduler(eng, policy=policy, max_slots=2).serve(reqs)
    counts0 = eng.compiled_step_counts()

    tr = Tracer(clock=FakeClock(dt=1e-4))
    sched = ContinuousScheduler(eng, policy=policy, max_slots=2, tracer=tr)
    out = sched.serve(reqs)
    assert all(isinstance(r, ServeResult) for r in out)
    assert eng.compiled_step_counts() == counts0    # tracing is host-side

    spans = _by_name(tr, "request")
    assert len(spans) == len(reqs)                  # one terminal per request
    assert {s["args"]["outcome"] for s in spans} == {"completed"}
    assert {s["args"]["head"] for s in spans} == {"screened", "exact"}
    assert all(s["dur"] > 0 and s["tid"] != SCHED_TID for s in spans)
    per_req = {s["tid"] for s in spans}
    assert {e["tid"] for e in _by_name(tr, "submit")} == per_req
    assert {e["tid"] for e in _by_name(tr, "admit")} == per_req
    assert {e["tid"] for e in _by_name(tr, "join")} == per_req
    assert {e["tid"] for e in _by_name(tr, "queue.wait")} == per_req
    ticks = _by_name(tr, "serve.tick")
    assert ticks and all(e["tid"] == SCHED_TID for e in ticks)
    assert ticks[-1]["args"]["tick"] == sched.stats.ticks
    kern = _by_name(tr, "serve.step")
    assert kern and {e["args"]["head"] for e in kern} == {"screened", "exact"}
    # the request span COVERS its kernel work on the shared timeline
    t0 = min(s["ts"] for s in spans)
    assert all(k["ts"] >= t0 for k in kern)
    # live-source gauges flow through the same registry
    snap = sched.stats.metrics.snapshot()
    assert snap["serve_requests_total"]["values"]["event=completed"] == 4


JOIN_KIDS = ["serve.join.first", "serve.join.match", "serve.join.prefill",
             "serve.join.splice", "serve.join.wait"]


def _kids(tr):
    out = {}
    for e in tr.events():
        if e["ph"] == "X" and e.get("args", {}).get("parent") is not None:
            out.setdefault(e["args"]["parent"], []).append(e)
    return out


def _paged_run(trained, tracer=None, pool_pages=64, n=6, eng=None,
               head="screened"):
    """Greedy requests through a paged scheduler (the served path), some
    sharing a two-page prefix so the radix cache hits."""
    corpus = trained[3]
    eng = eng if eng is not None else _engine(trained)
    base = corpus.sample_batch(n, 10, seed=61)
    shared = base[0][:8]
    reqs = [ServeRequest(prompt=np.concatenate([shared, p[:2 + i % 3]])
                         if i % 2 else p[:5 + i], max_new=3 + i % 3)
            for i, p in enumerate(base)]
    sched = ContinuousScheduler(eng, policy=StaticPolicy(head),
                                max_slots=2, kv_pool=PagePool(pool_pages, 4),
                                tracer=tracer)
    return eng, sched, sched.serve(reqs)


def _join_kids(kids, join):
    """A join's child spans, and the ones it should have: a first token
    that came out of the join's executable (``first_in_join`` 1 on its
    prefill) has no ``serve.join.first`` head call."""
    got = kids[join["args"]["id"]]
    folded = [c["args"].get("first_in_join", 0) for c in got
              if c["name"] == "serve.join.prefill"] == [1]
    want = [n for n in JOIN_KIDS if not (folded and n == "serve.join.first")]
    return sorted(c["name"] for c in got), want, folded


def test_scheduler_span_tree_paged(trained):
    """On the paged LSTM path every ``serve.join`` sits in a ``serve.tick``
    and has exactly one each of match, prefill, first-token wait and
    splice, and a first-token head call unless the prefill's executable
    computed the token (``first_in_join`` 1, as on this jittable head);
    every ``serve.step`` has its page growth and its wait; ticks count
    their joins and steps; submissions carry the admission load as a
    child."""
    tr = Tracer(clock=FakeClock(dt=1e-4))
    _, sched, out = _paged_run(trained, tr)
    assert all(isinstance(r, ServeResult) for r in out)
    kids = _kids(tr)
    by_id = {e["args"]["id"]: e for e in tr.events()
             if e["ph"] == "X" and "id" in e.get("args", {})}
    joins = _by_name(tr, "serve.join")
    assert len(joins) == len(out)
    for j in joins:
        got, want, folded = _join_kids(kids, j)
        assert got == want and folded
        assert by_id[j["args"]["parent"]]["name"] == "serve.tick"
        assert j["tid"] >= 0                    # the request's own lane
        a = j["args"]
        assert 0 <= a["cached_tokens"] < a["prompt_len"] and a["chunks"] >= 1
    assert any(j["args"]["cached_tokens"] > 0 for j in joins)
    assert {j["tid"] for j in joins} == {e["tid"] for e in
                                         _by_name(tr, "join")}
    steps = _by_name(tr, "serve.step")
    assert steps
    for st in steps:
        assert sorted(c["name"] for c in kids[st["args"]["id"]]) == [
            "serve.step.pages", "serve.step.wait"]
        assert by_id[st["args"]["parent"]]["name"] == "serve.tick"
        assert st["args"]["active"] == len(st["args"]["rids"]) >= 1
    ticks = _by_name(tr, "serve.tick")
    assert len(ticks) == sched.stats.ticks
    for t in ticks:
        assert t["args"]["parent"] is None
        names = [c["name"] for c in kids.get(t["args"]["id"], [])]
        assert names.count("serve.join") == t["args"]["joins"]
        assert names.count("serve.step") == t["args"]["steps"]
        assert names.count("serve.tick.observe") == 1
    subs = _by_name(tr, "serve.submit")
    assert len(subs) == len(out)
    for s in subs:
        assert [c["name"] for c in kids[s["args"]["id"]]] == [
            "serve.submit.load"]


def test_scheduler_span_tree_paged_host_head(trained):
    """A head that runs on the host keeps the eager first-token call: each
    paged join notes ``first_in_join`` 0 and has its ``serve.join.first``
    beside match, prefill, wait and splice."""
    tr = Tracer(clock=FakeClock(dt=1e-4))
    _, _, out = _paged_run(trained, tr, n=3, head="svd")
    assert all(isinstance(r, ServeResult) for r in out)
    kids = _kids(tr)
    joins = _by_name(tr, "serve.join")
    assert len(joins) == len(out)
    for j in joins:
        got, want, folded = _join_kids(kids, j)
        assert got == want == JOIN_KIDS and not folded


def test_paged_lstm_prefill_span_args(trained):
    """On the paged LSTM path a join's ``serve.join.prefill`` carries the
    padded scan length ``bucket`` and the page ``snapshots`` it wrote: a
    forward pass is one dispatch (``chunks`` 1 on the join) over the
    smallest page-grid bucket that holds the suffix, with one snapshot per
    page the suffix reaches."""
    P = 4
    tr = Tracer(clock=FakeClock(dt=1e-4))
    _, _, out = _paged_run(trained, tr)
    assert all(isinstance(r, ServeResult) for r in out)
    kids = _kids(tr)
    joins = _by_name(tr, "serve.join")
    assert joins and any(j["args"]["cached_tokens"] > 0 for j in joins)
    for j in joins:
        a = j["args"]
        (pre,) = [c for c in kids[a["id"]]
                  if c["name"] == "serve.join.prefill"]
        suffix = a["prompt_len"] - a["cached_tokens"]
        assert suffix > 0 and a["chunks"] == 1
        bucket = pre["args"]["bucket"]
        assert bucket % P == 0 and suffix <= bucket < suffix + P
        assert pre["args"]["snapshots"] == \
            -(-a["prompt_len"] // P) - a["cached_tokens"] // P


def test_pool_reclaim_span_nests_under_its_allocation(trained):
    """A pool small enough that the radix cache must give pages back:
    each ``serve.pool.reclaim`` nests under the join splice or step page
    growth that allocated, with the pages freed and leaves scanned."""
    tr = Tracer(clock=FakeClock(dt=1e-4))
    _, sched, out = _paged_run(trained, tr, pool_pages=12, n=8)
    assert all(isinstance(r, ServeResult) for r in out)
    rec = _by_name(tr, "serve.pool.reclaim")
    assert rec
    by_id = {e["args"]["id"]: e for e in tr.events()
             if e["ph"] == "X" and "id" in e.get("args", {})}
    for r in rec:
        assert by_id[r["args"]["parent"]]["name"] in (
            "serve.join.splice", "serve.step.pages")
        assert r["args"]["scanned"] >= r["args"]["freed"] >= 0
    assert sum(r["args"]["freed"] for r in rec) == \
        sched.kv_pool.radix.evictions


def test_decode_stream_span_tree(trained):
    """The unpaged ``DecodeStream`` has the same tree minus what it has no
    pool for: no radix match, no page growth."""
    cfg, _, _, corpus, _ = trained
    eng = _engine(trained)
    tr = Tracer(clock=FakeClock(dt=1e-4))
    reqs = [ServeRequest(prompt=p, max_new=3)
            for p in corpus.sample_batch(3, 6, seed=67)]
    out = ContinuousScheduler(eng, policy=StaticPolicy("screened"),
                              max_slots=2, tracer=tr).serve(reqs)
    assert all(isinstance(r, ServeResult) for r in out)
    kids = _kids(tr)
    joins = _by_name(tr, "serve.join")
    assert len(joins) == len(reqs)
    for j in joins:
        assert sorted(c["name"] for c in kids[j["args"]["id"]]) == [
            n for n in JOIN_KIDS if n != "serve.join.match"]
        assert j["args"]["cached_tokens"] == 0 and j["args"]["chunks"] == 1
    for st in _by_name(tr, "serve.step"):
        assert [c["name"] for c in kids[st["args"]["id"]]] == [
            "serve.step.wait"]


def test_join_instant_unchanged(trained):
    """The ``join`` instant keeps its shape and place: on the request's
    lane, stamped after its ``serve.join`` scope closes, with ``join_s``
    timing the whole join around that scope."""
    tr = Tracer(clock=time.perf_counter)
    _paged_run(trained, tr)
    spans = {e["tid"]: e for e in _by_name(tr, "serve.join")}
    instants = _by_name(tr, "join")
    assert instants and len(instants) == len(spans)
    for ev in instants:
        assert set(ev) == {"name", "cat", "ph", "s", "ts", "tid", "args"}
        assert (ev["cat"], ev["ph"], ev["s"]) == ("queue", "i", "t")
        assert set(ev["args"]) == {"head", "join_s"}
        sp = spans[ev["tid"]]
        assert ev["ts"] >= sp["ts"] + sp["dur"]
        assert ev["args"]["join_s"] >= sp["dur"]


def test_tracing_compiles_nothing_and_keeps_tokens(trained):
    """With the tracer on, a warm paged run compiles nothing (steps or
    eager ops) and emits the same greedy tokens as with it off."""
    eng, _, plain = _paged_run(trained)
    compiles = []

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    counts0 = eng.compiled_step_counts()
    tr = Tracer(clock=time.perf_counter)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        _, _, traced = _paged_run(trained, tr, eng=eng)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert _by_name(tr, "serve.step")
    assert compiles == []
    assert eng.compiled_step_counts() == counts0
    for a, b in zip(plain, traced):
        np.testing.assert_array_equal(a.tokens, b.tokens)


def test_scheduler_traces_reject_and_fault_paths(trained):
    """Terminal spans cover the non-happy outcomes too: an admission
    reject retires on its own lane, and injected faults leave fault +
    retry instants (transient) or a fallback instant (permanent) with the
    request still completing."""
    cfg, _, _, corpus, _ = trained
    eng = _engine(trained)
    p = corpus.sample_batch(3, 6, seed=37)
    # queue_limit=0-style reject: oversize budget path via breaker-free
    # admission is covered elsewhere; here use fault injection.
    inj = FaultInjector(seed=0)
    inj.arm("step", "transient", head="screened", count=2)
    inj.arm("step", "permanent", head="svd", count=1)
    clk = LogicalClock(0.0, dt_per_read=1e-3)
    tr = Tracer(clock=lambda: clk.t)                # peek, don't advance
    sched = ContinuousScheduler(
        eng, policy=StaticPolicy("screened"), max_slots=2, clock=clk,
        fault_injector=inj, max_retries=3, tracer=tr,
        breaker=CircuitBreaker(failure_threshold=5, clock=clk))
    out = sched.serve([ServeRequest(prompt=p[0], max_new=4)])
    assert isinstance(out[0], ServeResult)
    faults = _by_name(tr, "fault")
    retries = _by_name(tr, "retry")
    assert len(faults) == 2 and len(retries) == 2
    assert all(e["args"]["kind"] == "transient" for e in faults)
    span = _by_name(tr, "request")[0]
    assert span["args"]["outcome"] == "completed"

    clk2 = LogicalClock(0.0, dt_per_read=1e-3)
    tr2 = Tracer(clock=lambda: clk2.t)
    sched2 = ContinuousScheduler(
        eng, policy=StaticPolicy("svd"), max_slots=2, clock=clk2,
        fault_injector=inj, tracer=tr2,
        breaker=CircuitBreaker(failure_threshold=3, cooldown_s=100.0,
                               clock=clk2))
    out2 = sched2.serve([ServeRequest(prompt=p[1], max_new=4)])
    assert isinstance(out2[0], ServeResult) and out2[0].head == "exact"
    fb = _by_name(tr2, "fallback")
    assert fb and fb[0]["args"]["from"] == "svd"
    assert _by_name(tr2, "request")[0]["args"]["outcome"] == "completed"


# -- compiled_step_counts / _cache_size across step kinds ---------------------

def test_compiled_step_counts_paged_kind_and_redrain_flat():
    """Attention + PagePool traffic surfaces the "greedy-paged" step kind
    in compiled_step_counts, _cache_size tracks distinct cache keys, and a
    second drain through a fresh pool adds zero executables."""
    cfg = get_config("smollm-360m").reduced()
    m = build_model(cfg)
    params = m.init(jax.random.key(0), dtype=jnp.float32)
    eng = DecodeEngine(m, params, max_len=32)
    rng = np.random.default_rng(5)
    reqs = [ServeRequest(prompt=rng.integers(
                0, cfg.vocab_size, 6).astype(np.int32), max_new=3)
            for _ in range(3)]
    pool = PagePool(64, 8)
    out = ContinuousScheduler(eng, max_slots=2, kv_pool=pool).serve(reqs)
    assert all(isinstance(r, ServeResult) for r in out)
    counts = eng.compiled_step_counts()
    assert ("exact", "greedy-paged") in counts
    assert all(n >= 1 for n in counts.values())
    assert eng._cache_size() >= 1
    size0 = eng._cache_size()
    out2 = ContinuousScheduler(eng, max_slots=2,
                               kv_pool=PagePool(64, 8)).serve(reqs)
    assert eng.compiled_step_counts() == counts     # zero recompiles
    assert eng._cache_size() == size0
    for a, b in zip(out, out2):                     # paged redrain is stable
        np.testing.assert_array_equal(a.tokens, b.tokens)


def test_compiled_step_counts_spec_verify_kind(trained):
    """A speculative stream adds the draft's "greedy" step AND the
    verifier's "spec-verify" step to the cache, both flat on re-drain."""
    cfg, _, _, corpus, _ = trained
    eng = _engine(trained)
    from repro.serving import SpecPolicy
    pol = SpecPolicy(drafts=("screened",), min_ratio=1.0)
    reqs = [ServeRequest(prompt=p, max_new=4)
            for p in corpus.sample_batch(2, 6, seed=51)]
    out = ContinuousScheduler(eng, policy=StaticPolicy("exact"),
                              max_slots=2, spec=pol).serve(reqs)
    assert all(isinstance(r, ServeResult) for r in out)
    counts = eng.compiled_step_counts()
    assert ("exact", "spec-verify") in counts
    assert ("screened", "greedy") in counts
    ContinuousScheduler(eng, policy=StaticPolicy("exact"), max_slots=2,
                        spec=pol).serve(reqs)
    assert eng.compiled_step_counts() == counts


def test_compiled_step_counts_flat_under_retries(trained):
    """The resilience path reuses the identical compiled step on retry: a
    faulted-and-retried drain adds zero executables over a clean one."""
    cfg, _, _, corpus, _ = trained
    eng = _engine(trained)
    req = ServeRequest(prompt=corpus.sample_batch(1, 6, seed=53)[0],
                       max_new=4)
    ContinuousScheduler(eng, policy=StaticPolicy("screened"),
                        max_slots=2).serve([req])
    counts0 = eng.compiled_step_counts()
    inj = FaultInjector(seed=0)
    inj.arm("step", "transient", head="screened", count=2)
    sched = ContinuousScheduler(
        eng, policy=StaticPolicy("screened"), max_slots=2,
        fault_injector=inj, max_retries=3,
        breaker=CircuitBreaker(failure_threshold=5, clock=LogicalClock()))
    out = sched.serve([req])
    assert isinstance(out[0], ServeResult)
    assert sched.stats.retries == 2
    assert eng.compiled_step_counts() == counts0
