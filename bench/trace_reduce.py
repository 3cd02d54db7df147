"""From a profiler trace to device busy and idle time, kernel time, and the
breakdown the result line carries.

``load(path)`` reads one ``.xplane.pb`` with nothing but JAX
(``jax.profiler.ProfileData``) into a small plain form:

    {"devices": [...], "ops": [[device, name, start_ns, end_ns, label]],
     "host": [[name, start_ns, end_ns]]}

``ops`` are the events of each device plane's "XLA Ops" line (``label``
holds the event's string stats, where the framework-level op name lives);
``host`` are the harness's own ``TraceAnnotation`` spans (``bench.*``) and
the program's (``serve.*``).
``reduce(trace, ...)`` works on that form only, so a trace in that form,
recorded or made by hand, tests it without the chip.

The harness clock and the trace's clock are tied by the ``bench.step``
spans: the k-th one is the k-th tick the harness timed.

    python -m bench.trace_reduce <trace dir or .xplane.pb>

prints the planes, lines and most frequent op names of a trace, for
reading one by hand.
"""
from __future__ import annotations

import glob
import os
import re
import sys
from collections import Counter, defaultdict
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"
# the harness's own spans and the program's (``serve.*``): an idle gap is
# labelled with the innermost of them at its middle
HOST_PREFIX = ("bench.", "serve.")
LABEL_STATS = ("tf_op", "long_name", "hlo_op", "name", "kernel_details",
               "source")
LAYOUT = re.compile(r"\{[^{}]*\}")
# an op's name as the breakdown gives it: its HLO text, cut to this length
BREAKDOWN_NAME = 200


def op_text(name: str) -> str:
    """A device op's HLO text (the trace's name for it) with the layouts
    taken out: ``f32[16,500]{1,0:T(8,128)}`` reads ``f32[16,500]``."""
    return LAYOUT.sub("", name)


def find_xplane(path: str) -> Optional[str]:
    if os.path.isfile(path):
        return path
    hits = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                            recursive=True))
    return hits[-1] if hits else None


def _stats(ev) -> Dict[str, str]:
    out = {}
    try:
        for k, v in ev.stats:
            out[str(k)] = str(v)
    except (AttributeError, TypeError, ValueError):
        pass
    return out


def load(path: str) -> dict:
    """The plain form of one trace (see the module docstring)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, ops, host = [], [], []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(2))
            devices.append(dev)
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    st = _stats(ev)
                    label = " ".join(st[k] for k in LABEL_STATS if k in st)
                    s = int(ev.start_ns)
                    ops.append([dev, ev.name, s, s + int(ev.duration_ns),
                                label])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        s = int(ev.start_ns)
                        host.append([ev.name, s, s + int(ev.duration_ns)])
    return {"devices": sorted(devices), "ops": ops, "host": host}


def union(intervals: Sequence[Sequence[float]]) -> List[List[float]]:
    """Merged, sorted [start, end] intervals."""
    out: List[List[float]] = []
    for s, e in sorted((float(a), float(b)) for a, b in intervals if b > a):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def covered(merged: List[List[float]], a: float, b: float) -> float:
    """Length of [a, b] covered by ``merged``."""
    tot = 0.0
    for s, e in merged:
        if e <= a:
            continue
        if s >= b:
            break
        tot += min(e, b) - max(s, a)
    return tot


def clock_offset_ns(trace: dict, ticks: Sequence[Sequence[float]]
                    ) -> Optional[float]:
    """Trace ns minus harness seconds·1e9, from the k-th ``bench.step``
    span against the k-th timed tick (median over all pairs)."""
    steps = sorted(h[1] for h in trace["host"] if h[0] == "bench.step")
    n = min(len(steps), len(ticks))
    if n == 0:
        return None
    d = np.array(steps[:n], np.float64) - np.array(
        [t[0] for t in ticks[:n]], np.float64) * 1e9
    return float(np.median(d))


def reduce(trace: dict, t0: float, t1: float,
           ticks: Sequence[Sequence[float]],
           window_ticks: Sequence[Sequence[float]]) -> Optional[dict]:
    """Busy and idle time of the devices over the window [t0, t1) and
    inside the window's ticks (harness seconds), device seconds and event
    counts per op name (averaged over devices) with each name's label, and
    the breakdown. None when the trace has no device op or cannot be tied
    to the ticks."""
    off = clock_offset_ns(trace, ticks)
    if off is None or not trace["ops"]:
        return None
    w0, w1 = t0 * 1e9 + off, t1 * 1e9 + off
    devices = trace["devices"] or sorted({o[0] for o in trace["ops"]})
    by_dev = defaultdict(list)
    for dev, name, s, e, label in trace["ops"]:
        if e > w0 and s < w1:
            by_dev[dev].append((max(s, w0), min(e, w1)))
    merged = {d: union(by_dev[d]) for d in devices}
    busy = [covered(merged[d], w0, w1) for d in devices]
    tick_ns = [(a * 1e9 + off, b * 1e9 + off) for a, b in window_ticks]
    tick_total = sum(b - a for a, b in tick_ns)
    tick_busy = [sum(covered(merged[d], a, b) for a, b in tick_ns)
                 for d in devices]
    per_op: Counter = Counter()
    op_n: Counter = Counter()
    op_label: Dict[str, str] = {}
    for dev, name, s, e, label in trace["ops"]:
        if e <= w0 or s >= w1:
            continue
        per_op[name] += (min(e, w1) - max(s, w0)) / 1e9 / len(devices)
        op_n[name] += 1
        op_label.setdefault(name, label)
    # idle gaps of device 0 inside the window, labelled by what the host
    # harness was doing at the gap's middle
    host = sorted((h for h in trace["host"] if h[0] != "bench.run"),
                  key=lambda h: h[1])
    gaps = []
    m0 = merged[devices[0]]
    edges = [w0] + [x for iv in m0 for x in iv] + [w1]
    for i in range(0, len(edges) - 1, 2):
        a, b = edges[i], edges[i + 1]
        if b > a:
            gaps.append((b - a, a, b))
    gaps.sort(reverse=True)
    idle_gaps = []
    for g, a, b in gaps[:10]:
        mid = (a + b) / 2
        label = "none"
        for name, s, e in host:
            if s <= mid < e:
                label = name
        idle_gaps.append([label, g / 1e9])
    return {"busy_s": float(np.mean(busy)) / 1e9, "window_s": t1 - t0,
            "tick_s": tick_total / 1e9,
            "tick_idle_s": (tick_total - float(np.mean(tick_busy))) / 1e9,
            "op_s": dict(per_op), "op_n": dict(op_n), "op_label": op_label,
            "breakdown": {"device_ops": [[op_text(n)[:BREAKDOWN_NAME], s]
                                         for n, s in per_op.most_common(10)],
                          "idle_gaps": idle_gaps}}


def reduce_dir(trace_dir: str, t0: float, t1: float, ticks, window_ticks,
               log: Callable = lambda *a: None) -> Optional[dict]:
    path = find_xplane(trace_dir)
    if path is None:
        log(f"no .xplane.pb under {trace_dir}")
        return None
    trace = load(path)
    log(f"trace: {len(trace['ops'])} device ops on devices "
        f"{trace['devices']}, {len(trace['host'])} harness spans "
        f"({os.path.getsize(path)} bytes)")
    out = reduce(trace, t0, t1, ticks, window_ticks)
    if out is None:
        log("trace: no device op, or no tick to tie the clocks")
    return out


def describe(path: str, top: int = 40) -> None:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(find_xplane(path))
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            print(f"  line {line.name!r}: {len(evs)} events")
            if DEVICE_PLANE.match(plane.name) or line.name == OPS_LINE:
                names = Counter(ev.name for ev in evs)
                for n, c in names.most_common(top):
                    ev = next(e for e in evs if e.name == n)
                    print(f"    {c:6d} {n!r} start {ev.start_ns} dur "
                          f"{ev.duration_ns} stats {_stats(ev)}")


if __name__ == "__main__":
    describe(sys.argv[1])
