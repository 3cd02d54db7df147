"""Small stand-ins for the benchmark's cells, sized for a CPU test run: the
program's own reduced variant of a configuration (d=128, vocabulary 512)
and a cell's traffic shrunk to a few requests."""
from __future__ import annotations

import argparse
import copy
import io
import json
import time

from bench import harness, peaks, system

# Limit for the reduced cells on the CPU: the program and the reference
# both compute in float32 there, so sound runs read 0 up to reassociation
# (~1e-7 of these logits); the controls read ~1e-4 and up.
CPU_LIMIT = 1e-5


def small_conf(name: str = "nmt-deen-lstm") -> dict:
    conf = system.load_config(name)
    conf.update(registry_reduced=True, d_model=128, vocab_size=512,
                num_layers=2)
    conf["screen"].update(clusters=8, budget_blocks=2, kmeans_iters=3,
                          ctx_batches=2)
    return conf


def small_work(cell: str, limit: float = CPU_LIMIT) -> dict:
    work = copy.deepcopy(system.load_workload(cell))
    work.update(width=4, pool_pages=128, ramp_s=0.3, drain_s=30.0)
    if work["arrival"]["law"] == "closed":
        work["arrival"]["clients"] = work["width"]
    else:
        work["arrival"]["rate"] = 20.0
    work["prompt_len"].update(min=4, max=12, median=6)
    work["output_len"].update(min=2, max=12, median=6)
    work["check"].update(requests=32, block_rows=8)
    work["check"]["limits"] = {k: limit for k in work["check"]["limits"]}
    return work


def run_small(cell: str, seed: int = 2 ** 31 + 77, seconds: float = 1.5,
              trace: int = 0, work: dict = None) -> dict:
    """One run of the reduced ``cell`` on the CPU, past the harness's look
    for a chip; returns the result line as a dict."""
    bench = harness.load_benchmark()
    entry = harness.cell_entry(bench, cell)
    args = argparse.Namespace(workload=cell, seed=seed, seconds=seconds,
                              trace=trace, trace_dir=None)
    out = io.StringIO()
    saved = dict(peaks.CHIP_PEAKS)
    peaks.CHIP_PEAKS["cpu"] = peaks.CHIP_PEAKS["TPU v5 lite"]
    try:
        rc = harness.run(args, time.perf_counter(), require_accelerator=False,
                         bench=bench, conf=small_conf(entry["config"]),
                         work=work or small_work(cell), out=out)
    finally:
        peaks.CHIP_PEAKS.clear()
        peaks.CHIP_PEAKS.update(saved)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])
