"""What the per-layer readers under ``bench/metrics/`` share: window
counts, the model's operations over the window, and kernel time by name.

Each reader is ``read(art) -> float | None``; ``art`` is the run's
artefacts (see ``harness.run``): the due-time book, the window's ticks on
the harness clock, the scheduler's window counters, the program tracer's
events, the reduced device trace, the configuration and the screen's L̄.
"""
from __future__ import annotations

import re
from typing import Optional

import numpy as np

from bench import peaks, work
from bench.trace_reduce import op_text

# The fused subset top-k kernel (src/repro/kernels/fused_topk.py) as the
# device trace shows it. The trace names a Pallas kernel after the jitted
# function around it (``%screened_fused_topk_tpu.3``, a name the route
# kernel shares), so the kernel is found by its signature, with the
# layouts taken out of the op's text: a custom call on the (B, K) block
# ids that returns the (B, 1, k) values and ids and the (B, 1, 128)
# log-sum-exp row, e.g.
#   %x.3 = (f32[16,1,128], s32[16,1,128], f32[16,1,128]) custom-call(s32[16,8] ...
FUSED_TOPK = re.compile(
    r"= \(f32\[(\d+),1,(\d+)\], s32\[\1,1,\2\], f32\[\1,1,\d+\]\) "
    r"custom-call\(s32\[\1,\d+\]")


def latency_p95_s(art) -> Optional[float]:
    """95th percentile of due-to-last-token latency over every request due
    in the window, in s; one that failed or never finished counts as
    finishing at the end of the drain. Past the knee the queue grows all
    through the window, so this tail follows the smallest change in
    capacity."""
    v = art["book"].latency_quantile(0.95, art["t_drained"])
    return None if v != v else v


def tick_ms(art) -> Optional[float]:
    """Mean host time inside ``ContinuousScheduler.step`` per tick of the
    window, on the harness's clock, in ms."""
    if not art["ticks"]:
        return None
    return 1e3 * sum(e - s for s, e in art["ticks"]) / len(art["ticks"])


def joins_in_window(art) -> Optional[list]:
    """[(rid, join seconds)] of the program tracer's ``join`` instants in
    the window; None without a tracer."""
    evs = art.get("tracer_events")
    if evs is None:
        return None
    b = art["book"]
    return [(ev["tid"], ev["args"]["join_s"]) for ev in evs
            if ev["name"] == "join" and ev.get("ph") == "i"
            and b.t0 <= ev["ts"] < b.t1]


def output_tokens(art) -> int:
    w = art["win"]
    return int(w["tokens1"] - w["tokens0"])


def head_macs(art) -> float:
    c = art["conf"]
    return work.head_macs(art["work"]["head"], c["vocab_size"], c["d_model"],
                          art["screen_r"] or 0, art["lbar"] or 0.0)


def trunk_positions(art) -> Optional[tuple]:
    """(prompt positions, decode positions) of the trunk's tokens in the
    window: every prompt token of the window's joins, and the input token
    of every row of the window's decode steps (the k-th step of a request
    with a prompt of p tokens runs its token k−1 at position p + k − 1);
    None without the program's join instants and step spans."""
    joins = joins_in_window(art)
    evs = art.get("tracer_events")
    if joins is None or evs is None:
        return None
    recs = art["records"]
    prompt = [np.arange(len(recs[rid].planned.prompt))
              for rid, _ in joins if rid in recs]
    b = art["book"]
    steps: dict = {}
    decode = []
    for ev in sorted((e for e in evs if e["name"] == "serve.step"
                      and e.get("ph") == "X"
                      and "error" not in e.get("args", {})),
                     key=lambda e: e["ts"]):
        inside = b.t0 <= ev["ts"] < b.t1
        for rid in ev["args"].get("rids", ()):
            steps[rid] = k = steps.get(rid, 0) + 1
            if inside and rid in recs:
                decode.append(len(recs[rid].planned.prompt) + k - 1)
    return (np.concatenate(prompt) if prompt else np.zeros(0, np.int64),
            np.asarray(decode, np.int64))


def step_mfu(art) -> Optional[float]:
    """Model operations the window's work required over (device-busy time
    in the window, from the trace, × chips × peak), in %. The peak is the
    MXU's bfloat16 rate: the configuration's float32 matmuls run at the
    TPU's default precision, one bfloat16-operand pass, so no faster rate
    exists for them."""
    tr = art.get("trace")
    pos = trunk_positions(art)
    if tr is None or pos is None or tr["busy_s"] <= 0:
        return None
    flops = work.model_flops(art["family"], art["conf"], head_macs(art),
                             pos[0], pos[1], output_tokens(art))
    peak = peaks.chip_peaks(art["kind"])["flops_bf16"]
    return 100.0 * flops / (tr["busy_s"] * art["chips"] * peak)


def fused_topk_calls(art) -> dict:
    """{batch rows: (calls, device seconds)} of the fused kernel in the
    window: the decode step's calls at the stream's width, and the joins'
    calls on one row, which the trace may show under another signature."""
    tr = art.get("trace")
    out: dict = {}
    if tr is None:
        return out
    for op, s in tr["op_s"].items():
        m = FUSED_TOPK.search(op_text(op))
        if m:
            n, t = out.get(int(m.group(1)), (0, 0.0))
            out[int(m.group(1))] = (n + tr["op_n"][op], t + s)
    return out


def fused_topk_roofline(art) -> Optional[float]:
    """Least time of the decode step's useful head work over the device time
    of the fused kernel's calls at the stream's width, in %. Useful work:
    every token a decode step emits is one active query row scoring L̄
    candidate words; a join's first token is left out with the join's own
    calls (its one-row call need not carry the same signature)."""
    if not art.get("lbar"):
        return None
    calls = fused_topk_calls(art).get(int(art["work"]["width"]))
    joins = joins_in_window(art)
    if calls is None or joins is None or calls[1] <= 0:
        return None
    rows = output_tokens(art) - len(joins)
    if rows <= 0:
        return None
    flops, nbytes = work.fused_topk_work(rows, art["lbar"],
                                         art["conf"]["d_model"])
    least, _ = peaks.least_time_s(flops, nbytes, art["kind"])
    return 100.0 * least / calls[1]


def device_idle_share(art) -> Optional[float]:
    """Share of the time inside the window's ticks in which no device op
    runs, in %."""
    tr = art.get("trace")
    if tr is None or tr["tick_s"] <= 0:
        return None
    return 100.0 * tr["tick_idle_s"] / tr["tick_s"]
