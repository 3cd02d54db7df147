"""§Roofline deliverable: per (arch × shape × mesh) three-term roofline from
the dry-run artifacts (results/*.jsonl), with MODEL_FLOPS/HLO_FLOPs ratio and
the dominant bottleneck. Emits CSV + a markdown table to results/roofline.md.
"""
from __future__ import annotations

import json
import os

import numpy as np

from benchmarks.common import csv_row
from repro.configs import INPUT_SHAPES, get_config
from repro.utils.compile_cache import enable_compile_cache

FILES = {
    "16x16": "results/baselines_16x16.jsonl",
    "16x16-l2s": "results/l2s_16x16.jsonl",
    "2x16x16": "results/baselines_2x16x16.jsonl",
    # §Perf-optimized reruns (seq-parallel attention, seq-sharded caches,
    # 2D weight-stationary serving, sharded MoE dispatch buffers)
    "16x16-opt": "results/opt_16x16.jsonl",
    "16x16-opt-l2s": "results/opt_l2s_16x16.jsonl",
    "2x16x16-opt": "results/opt_2x16x16.jsonl",
}


def model_flops_per_dev(arch: str, shape_name: str, n_chips: int) -> float:
    cfg = get_config(arch)
    sc = INPUT_SHAPES[shape_name]
    n_active = cfg.active_param_count()
    if sc.kind == "train":
        tokens = sc.global_batch * sc.seq_len
        return 6.0 * n_active * tokens / n_chips
    if sc.kind == "prefill":
        tokens = sc.global_batch * sc.seq_len
        return 2.0 * n_active * tokens / n_chips
    # decode: one token per sequence
    return 2.0 * n_active * sc.global_batch / n_chips


def load(fname):
    if not os.path.exists(fname):
        return []
    return [json.loads(l) for l in open(fname)]


def run():
    lines = ["# Roofline table (per-device terms, TPU v5e constants)", "",
             "| arch | shape | mesh | head | compute_s | memory_s | "
             "collective_s | dominant | MODEL/HLO flops | note |",
             "|---|---|---|---|---|---|---|---|---|---|"]
    for mesh_name, fname in FILES.items():
        head = "l2s" if mesh_name.endswith("l2s") else "full"
        n_chips = 512 if mesh_name.startswith("2x") else 256
        if not os.path.exists(fname):
            continue
        for r in load(fname):
            if "skipped" in r:
                lines.append(f"| {r['arch']} | {r['shape']} | {mesh_name} | "
                             f"{head} | — | — | — | — | — | SKIP: "
                             f"{r['skipped'][:40]} |")
                continue
            if "error" in r or "roofline" not in r or \
                    "error" in r.get("roofline", {}):
                lines.append(f"| {r['arch']} | {r['shape']} | {mesh_name} | "
                             f"{head} | ERR | | | | | |")
                continue
            rl = r["roofline"]
            mf = model_flops_per_dev(r["arch"], r["shape"], n_chips)
            ratio = mf / max(rl["flops_per_dev"], 1.0)
            note = r.get("variant", "")
            lines.append(
                f"| {r['arch']} | {r['shape']} | {mesh_name} | {head} "
                f"| {rl['compute_s']:.3e} | {rl['memory_s']:.3e} "
                f"| {rl['collective_s']:.3e} | {rl['dominant']} "
                f"| {ratio:.2f} | {note} |")
            csv_row(f"roofline/{r['arch']}/{r['shape']}/{mesh_name}/{head}",
                    rl["memory_s"] * 1e6,
                    f"dominant={rl['dominant']},compute_s={rl['compute_s']:.3e},"
                    f"collective_s={rl['collective_s']:.3e},"
                    f"model_hlo_ratio={ratio:.2f}")
    os.makedirs("results", exist_ok=True)
    with open("results/roofline.md", "w") as f:
        f.write("\n".join(lines) + "\n")
    print(f"[roofline] wrote results/roofline.md ({len(lines) - 4} rows)")


if __name__ == "__main__":
    enable_compile_cache()
    run()
