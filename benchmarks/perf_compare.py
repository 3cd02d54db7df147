"""§Perf companion: baseline vs optimized bound-time comparison across the
full single-pod matrix. Reads results/baselines_16x16.jsonl and
results/opt_16x16.jsonl, writes results/perf_compare.md."""
from __future__ import annotations

import json
import os

import numpy as np

from benchmarks.common import csv_row
from repro.utils.compile_cache import enable_compile_cache


def _load(f):
    out = {}
    if not os.path.exists(f):
        return out
    for line in open(f):
        r = json.loads(line)
        if "roofline" in r and "error" not in r.get("roofline", {}):
            rl = r["roofline"]
            out[(r["arch"], r["shape"])] = (
                max(rl["compute_s"], rl["memory_s"], rl["collective_s"]),
                rl["dominant"])
    return out


def run():
    base = _load("results/baselines_16x16.jsonl")
    opt = _load("results/opt_16x16.jsonl")
    rows = []
    for k in sorted(base):
        if k in opt and opt[k][0] > 0:
            rows.append((base[k][0] / opt[k][0], k[0], k[1],
                         base[k][0], opt[k][0], base[k][1], opt[k][1]))
    rows.sort(reverse=True)
    lines = ["# Baseline vs §Perf-optimized roofline bound (16×16 mesh)", "",
             "| arch | shape | baseline bound_s (dom) | optimized bound_s "
             "(dom) | × |", "|---|---|---|---|---|"]
    for sp, a, s, b, o, bd, od in rows:
        lines.append(f"| {a} | {s} | {b:.3e} ({bd}) | {o:.3e} ({od}) "
                     f"| {sp:.1f}× |")
        csv_row(f"perf/{a}/{s}", o * 1e6, f"baseline_s={b:.3e},speedup={sp:.2f}x")
    if rows:
        geo = float(np.exp(np.mean([np.log(r[0]) for r in rows])))
        lines.append(f"\ngeomean speedup: **{geo:.2f}×** over {len(rows)} combos")
    with open("results/perf_compare.md", "w") as f:
        f.write("\n".join(lines) + "\n")
    print(f"[perf_compare] wrote results/perf_compare.md ({len(rows)} rows)")


if __name__ == "__main__":
    enable_compile_cache()
    run()
