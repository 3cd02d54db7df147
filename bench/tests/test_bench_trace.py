"""The reduction from a trace's plain form to busy, idle and kernel time."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from bench import readout, system, trace_reduce

TESTDATA = Path(__file__).resolve().parent.parent / "testdata"

MS = 1_000_000          # ns
# the fused top-k kernel and the route kernel as a TPU trace names them
FUSED = ("%screened_fused_topk_tpu.3 = (f32[16,1,128]{2,1,0:T(1,128)}, "
         "s32[16,1,128]{2,1,0:T(1,128)S(1)}, f32[16,1,128]{2,1,0:T(1,128)}) "
         "custom-call(s32[16,8]{1,0:T(8,128)S(1)} %fusion.1, "
         "f32[196,128,500]{2,1,0:T(8,128)S(1)} %copy.7), "
         'custom_call_target="tpu_custom_call"')
ROUTE = ("%screened_fused_topk_tpu.2 = s32[128]{0:T(128)S(1)} custom-call("
         "f32[128,500]{1,0:T(8,128)S(1)} %pad.4, f32[500,128]{1,0:T(8,128)S(1)}"
         ' %pad.5), custom_call_target="tpu_custom_call"')


def toy_trace():
    """Harness ticks at 10-12 ms, 20-25 ms and 30-40 ms of trace time (the
    same instants in harness seconds); device ops in and between them."""
    return {
        "devices": [0],
        "ops": [[0, "fusion.1", 10 * MS, 11 * MS, "jit(step)/dot"],
                [0, FUSED, 21 * MS, 23 * MS, ""],
                [0, ROUTE, 20 * MS, 21 * MS, ""],
                [0, "fusion.1", 22 * MS, 24 * MS, "jit(step)/dot"],
                [0, "fusion.1", 45 * MS, 46 * MS, "jit(step)/dot"]],
        "host": [["bench.run", 0, 60 * MS],
                 ["bench.step", 10 * MS, 12 * MS],
                 ["bench.step", 20 * MS, 25 * MS],
                 ["bench.step", 30 * MS, 40 * MS],
                 ["bench.wait", 41 * MS, 50 * MS]],
    }


TICKS = [(0.010, 0.012), (0.020, 0.025), (0.030, 0.040)]
WINDOW = type("Book", (), {"t0": 0.0, "t1": 0.05})()
JOIN = {"name": "join", "ph": "i", "tid": 0, "ts": 0.01,
        "args": {"join_s": 0.001}}


def test_union_and_covered():
    m = trace_reduce.union([[5, 7], [1, 3], [2, 4], [7, 8]])
    assert m == [[1, 4], [5, 8]]
    assert trace_reduce.covered(m, 0, 10) == 6
    assert trace_reduce.covered(m, 3, 6) == 2


def test_reduce_toy_trace():
    out = trace_reduce.reduce(toy_trace(), 0.0, 0.05, TICKS, TICKS)
    # busy: [10,11] + [20,24] + [45,46] = 6 ms of the 50 ms window
    assert out["busy_s"] == pytest.approx(0.006)
    assert out["window_s"] == pytest.approx(0.05)
    # ticks: 17 ms, of which busy 1 + 4 + 0 = 5 ms
    assert out["tick_s"] == pytest.approx(0.017)
    assert out["tick_idle_s"] == pytest.approx(0.012)
    assert out["op_s"]["fusion.1"] == pytest.approx(0.004)
    assert out["op_n"][FUSED] == 1
    # the breakdown names an op by its text without layouts
    names = [n for n, _ in out["breakdown"]["device_ops"]]
    assert "%screened_fused_topk_tpu.2 = s32[128] custom-call(" \
        "f32[128,500] %pad.4, f32[500,128] %pad.5)" in " ".join(names)
    # idle 24..45 ms (mid in the third tick), 0..10 and 11..20 ms (the
    # host in neither), 46..50 ms (waiting for an arrival)
    assert [(label, round(s, 6)) for label, s in
            out["breakdown"]["idle_gaps"]] == [
        ("bench.step", 0.021), ("none", 0.01), ("none", 0.009),
        ("bench.wait", 0.004)]
    # 17 tokens in the window, one of them a join's first token
    art = {"trace": out, "win": {"tokens0": 0, "tokens1": 17}, "lbar": 1024.0,
           "conf": {"d_model": 500}, "kind": "TPU v5 lite",
           "work": {"width": 16}, "book": WINDOW, "tracer_events": [JOIN]}
    # the fused kernel alone, not the route kernel that shares its name
    assert readout.fused_topk_calls(art) == {16: (1, pytest.approx(0.002))}
    assert readout.device_idle_share(art) == pytest.approx(100 * 12 / 17)
    # 16 decode rows of 1,024 words × 500 float32 at 819 GB/s, over 2 ms
    share = readout.fused_topk_roofline(art)
    assert share == pytest.approx(100 * 16 * 1024 * 500 * 4 / 819e9 / 0.002)
    # no tracer, or no call at the stream's width: nothing to read
    assert readout.fused_topk_roofline(dict(art, tracer_events=None)) is None
    assert readout.fused_topk_roofline(
        dict(art, work={"width": 8})) is None


def test_clock_offset_from_ticks():
    tr = toy_trace()
    shifted = [(a + 2.0, b + 2.0) for a, b in TICKS]
    assert trace_reduce.clock_offset_ns(tr, shifted) == pytest.approx(-2e9)
    assert trace_reduce.reduce({"devices": [], "ops": [], "host": []},
                               0, 1, TICKS, TICKS) is None


def test_step_mfu_over_device_busy_time():
    out = trace_reduce.reduce(toy_trace(), 0.0, 0.05, TICKS, TICKS)

    class Rec:
        class planned:
            prompt = [0] * 10

    steps = [{"name": "serve.step", "ph": "X", "tid": -1, "ts": ts,
              "dur": 0.001, "args": {"id": i, "parent": None, "rids": [0]}}
             for i, ts in enumerate((0.02, 0.03, 0.04))]
    art = {"trace": out, "win": {"tokens0": 0, "tokens1": 4},
           "tracer_events": [{"name": "join", "ph": "i", "tid": 0, "ts": 0.01,
                              "args": {"join_s": 0.001}}] + steps,
           "book": type("B", (), {"t0": 0.0, "t1": 0.05})(),
           "records": {0: Rec}, "lbar": 1024.0, "screen_r": 50,
           "work": {"head": "screened-pallas"}, "chips": 1,
           "conf": {"d_model": 500, "num_layers": 2, "vocab_size": 25000},
           "kind": "TPU v5 lite", "family": system.load_family("lstm")}
    # 10 prompt tokens + 3 decode steps of the trunk, 4 head queries, over
    # the 6 ms the device was busy
    prompt, decode = readout.trunk_positions(art)
    assert prompt.tolist() == list(range(10))
    assert decode.tolist() == [10, 11, 12]
    flops = 13 * 2 * 8 * 500 ** 2 * 2 + 4 * 2 * (50 + 1024) * 500
    assert readout.step_mfu(art) == pytest.approx(
        100 * flops / (0.006 * 197e12))
    assert readout.step_mfu(dict(art, trace=None)) is None


def test_recorded_tpu_trace_slice():
    """Five decode ticks of a ``deen-l2s-poisson`` run on one TPU v5e, in
    the plain form ``trace_reduce.load`` makes of the profiler's file."""
    tr = json.loads((TESTDATA / "deen_trace_slice.json").read_text())
    a, b = tr["window_ns"]
    ticks = [(s / 1e9, e / 1e9) for n, s, e in tr["host"] if n == "bench.step"]
    out = trace_reduce.reduce(tr, a / 1e9, b / 1e9, ticks, ticks)
    assert 0 < out["busy_s"] < out["window_s"]
    assert 0 < out["tick_idle_s"] < out["tick_s"]
    # the fused kernel runs once a tick; the route kernel, which the trace
    # names after the same jitted function, is not counted with it
    fused = [op for op in out["op_n"]
             if readout.FUSED_TOPK.search(trace_reduce.op_text(op))]
    assert sum(out["op_n"][op] for op in fused) == len(ticks) == 5
    assert any("screened_fused_topk_tpu" in op and op not in fused
               for op in out["op_n"])
    # at most 16 rows a tick: the share of the roofline stays under 100 %
    art = {"trace": out, "win": {"tokens0": 0, "tokens1": 16 * len(ticks)},
           "lbar": 1024.0, "conf": {"d_model": 500}, "kind": "TPU v5 lite",
           "work": {"width": 16}, "tracer_events": [],
           "book": type("Book", (), {"t0": a / 1e9, "t1": b / 1e9})()}
    assert readout.fused_topk_calls(art)[16][0] == 5
    assert 0 < readout.fused_topk_roofline(art) < 100
