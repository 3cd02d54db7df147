#!/usr/bin/env python3
"""Readings for a cell's limits: the program's and the control's.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 3

In one process, for each seed: the cell is set up as ``bench/run.py`` sets
it up, its own traffic runs for ``--seconds`` at the cell's load, and the
finished requests are sampled and checked exactly as a run checks them
(``check.sample``, ``check.readings``, ``check.judge``). On the same
prompts and served tokens the control, the reference computed at a lower
precision (``--control``: float8 weights, the default, or bfloat16
weights, activations and state; ``reference.CONTROLS``) and put in the
program's place, picks its own token at every position, and
``check.judge`` holds those picks to the same limits. One JSON line
per seed gives each number's widest reading and the verdict, for the
program and for the control.

The lower reading of a limit is the largest the program gives over a dozen
seeds or more; the upper is the smallest the control gives (PERF.md).
Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent


def one_seed(conf, work, seed: int, seconds: float, log,
             control: str = "float8") -> dict:
    from bench import check, harness, system, traffic
    sysm = system.System(conf, work, seed, log=log)
    sysm.warm()
    planned = traffic.plan(work, sysm.corpus, seed, seconds)
    drv = harness.Driver(sysm, sysm.scheduler(), planned, seconds,
                         work["ramp_s"], work["drain_s"])
    book = drv.run()
    screen = sysm.screen_host
    sysm.free()
    drv = None
    recs = check.sample(book, work["check"]["requests"], seed)
    r = check.readings(check.reference_for(conf, work, seed, screen), recs,
                       control=control)
    limits = work["check"]["limits"]
    prog, ctl = check.judge(r, "served", limits), check.judge(r, "control",
                                                               limits)
    return {"seed": seed, "control_precision": control,
            "positions": prog.positions,
            "p_at_1": float(np.mean(r["top1"])),
            "program": {k: d["value"] for k, d in prog.compared.items()},
            "program_correct": prog.correct,
            "control": {k: d["value"] for k, d in ctl.compared.items()},
            "control_correct": ctl.correct,
            "spread": {k: {"mean": float(np.mean(x[np.isfinite(x)])),
                           "share_above_0": float(np.mean(x > 0))}
                       for k, x in r.items() if k != "top1"}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--control", default="float8",
                    help="the control's precision: float8 or bfloat16")
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    from bench import harness, system
    work = system.load_workload(args.workload)
    conf = system.load_config(work["config"])
    if harness.device_info(1, True) is None:
        return 1
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        out = one_seed(conf, work, seed, args.seconds, harness.log,
                       args.control)
        out["wall_s"] = time.perf_counter() - t
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
