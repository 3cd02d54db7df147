"""The readers of the program's own ``serve.*`` spans, on hand-made tracer
events with known self times and token gaps, and the trace reduction with
and without program spans on the host's timeline."""
from __future__ import annotations

import pytest

from bench import harness, spans, trace_reduce
from bench.tests.test_bench_trace import MS, TICKS, toy_trace

READERS = ["join_host_ms", "step_host_ms", "tick_self_ms", "itl_p95_ms"]
WINDOW = type("Book", (), {"t0": 0.0, "t1": 1.0})()


def span(name, sid, parent, ts, dur, tid=-1, **args):
    return {"name": name, "cat": "t", "ph": "X", "ts": ts, "dur": dur,
            "tid": tid, "args": {"id": sid, "parent": parent, **args}}


def events():
    """Two ticks: the first joins request 5 (30 ms, 8 of them waiting for
    the first token) and steps it (10 ms, 4 waiting); the second steps it
    again (10 ms, 6 waiting). Outside the window, or cut short by an
    exception, a span does not count."""
    return [
        span("serve.join.match", 3, 2, 0.100, 0.002, tid=5),
        span("serve.join.prefill", 4, 2, 0.102, 0.010, tid=5),
        span("serve.join.first", 5, 2, 0.112, 0.003, tid=5),
        span("serve.join.wait", 6, 2, 0.115, 0.008, tid=5),
        span("serve.join.splice", 7, 2, 0.123, 0.005, tid=5),
        span("serve.join", 2, 1, 0.100, 0.030, tid=5, prompt_len=20,
             cached_tokens=0, chunks=3),
        {"name": "join", "cat": "queue", "ph": "i", "s": "t", "ts": 0.131,
         "tid": 5, "args": {"head": "h", "join_s": 0.031}},
        span("serve.step.pages", 10, 8, 0.135, 0.001),
        span("serve.step.wait", 9, 8, 0.138, 0.004),
        span("serve.step", 8, 1, 0.135, 0.010, active=1, rids=[5]),
        span("serve.tick", 1, None, 0.100, 0.050, joins=1, steps=1),
        span("serve.step.wait", 13, 12, 0.206, 0.006),
        span("serve.step", 12, 11, 0.205, 0.010, active=1, rids=[5]),
        span("serve.tick", 11, None, 0.200, 0.020, joins=0, steps=1),
        span("serve.join", 14, 11, 0.210, 0.001, tid=6,
             error="PoolExhausted"),
        span("serve.tick", 15, None, 1.5, 0.9),
        span("serve.join", 16, 15, 1.5, 0.5, tid=7),
    ]


def art(evs, trace=True):
    return {"tracer_events": evs, "book": WINDOW,
            "trace": {"busy_s": 0.001} if trace else None}


def read(name, a):
    return harness.load_reader(name)(a)


def test_join_host_ms_leaves_out_the_wait():
    assert read("join_host_ms", art(events())) == pytest.approx(30 - 8)


def test_step_host_ms_leaves_out_the_wait():
    assert read("step_host_ms", art(events())) == pytest.approx(
        ((10 - 4) + (10 - 6)) / 2)


def test_tick_self_ms_leaves_out_joins_and_steps():
    # 50 - 30 - 10 and 20 - 10 - 1: a join that failed was still a join
    assert read("tick_self_ms", art(events())) == pytest.approx(
        ((50 - 30 - 10) + (20 - 10 - 1)) / 2)


def test_itl_p95_ms_from_join_then_step_ends():
    # request 5's tokens at 131, 145 and 215 ms: gaps 14 and 70 ms
    gaps = spans.token_gaps_s(art(events()))
    assert gaps == pytest.approx([0.014, 0.070])
    assert read("itl_p95_ms", art(events())) == pytest.approx(
        14 + 0.95 * (70 - 14))
    # a gap counts where its later token falls in the window
    late = dict(art(events()), book=type("B", (), {"t0": 0.2, "t1": 1})())
    assert spans.token_gaps_s(late) == pytest.approx([0.070])


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_to_read(name):
    outside = [e for e in events() if e["ts"] >= 1.0]
    assert read(name, art(outside)) is None
    assert read(name, art(events(), trace=False)) is None
    assert read(name, art(None)) is None
    # a program without the scopes: only the join instant, as before
    assert read(name, art([e for e in events() if e["ph"] == "i"])) is None


def with_program_spans():
    """The toy trace with the program's scopes inside the harness's ticks
    on the host's timeline, as a trace that kept them would hold them."""
    tr = toy_trace()
    tr["host"] += [["serve.tick", 20 * MS + 1, 25 * MS - 1],
                   ["serve.join", 21 * MS, 23 * MS],
                   ["serve.tick", 30 * MS + 1, 40 * MS - 1],
                   ["serve.step", 31 * MS, 39 * MS],
                   ["serve.step.wait", 38 * MS, 39 * MS]]
    return tr


def test_reduce_unmoved_by_program_spans():
    plain = trace_reduce.reduce(toy_trace(), 0.0, 0.05, TICKS, TICKS)
    more = trace_reduce.reduce(with_program_spans(), 0.0, 0.05, TICKS, TICKS)
    for key in ("busy_s", "window_s", "tick_s", "tick_idle_s", "op_s",
                "op_n", "op_label"):
        assert more[key] == plain[key], key
    assert trace_reduce.clock_offset_ns(with_program_spans(), TICKS) == \
        trace_reduce.clock_offset_ns(toy_trace(), TICKS)
    assert more["breakdown"]["device_ops"] == plain["breakdown"]["device_ops"]
    assert [s for _, s in more["breakdown"]["idle_gaps"]] == \
        [s for _, s in plain["breakdown"]["idle_gaps"]]


def test_idle_gaps_name_the_innermost_program_span():
    out = trace_reduce.reduce(with_program_spans(), 0.0, 0.05, TICKS, TICKS)
    # the gap 24..45 ms is centred at 34.5 ms: in bench.step, serve.tick
    # and serve.step, of which serve.step is the innermost
    assert [label for label, _ in out["breakdown"]["idle_gaps"]] == [
        "serve.step", "none", "none", "bench.wait"]
