#!/usr/bin/env python3
"""Find a cell's knee: one process, one set-up, a window per offered rate.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 50,100,200,400

For each rate the cell's own traffic (its lengths, its head, its width) is
offered open-loop for ``--seconds`` after a ramp, on one scheduler per rate,
and one line reports the rate offered, the tokens/s completed, the due-time
latency p50/p95, the generator's lateness and the failures. The knee is the
highest rate whose completed rate keeps up with the offered one without a
growing backlog; a cell then offers a fixed rate below it. Not part of a
benchmark run: cells are measured by ``bench/run.py`` alone.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    from bench import harness, system, traffic
    work = system.load_workload(args.workload)
    conf = system.load_config(work["config"])
    if harness.device_info(1, True) is None:
        return 1
    sysm = system.System(conf, work, args.seed, log=harness.log)
    sysm.warm()
    for rate in (float(r) for r in args.rates.split(",")):
        w = dict(work, arrival={"law": "poisson", "rate": rate},
                 drain_s=min(work["drain_s"], 10.0))
        planned = traffic.plan(w, sysm.corpus, args.seed, args.seconds)
        drv = harness.Driver(sysm, sysm.scheduler(), planned, args.seconds,
                             w["ramp_s"], w["drain_s"])
        t = time.perf_counter()
        book = drv.run()
        due = book.due_in_window()
        print(json.dumps({
            "rate": rate, "due": len(due),
            "failed": sum(1 for r in due if r.failed),
            "tokens_per_s": book.tokens_per_s(),
            "latency_p50_s": book.latency_quantile(0.5, time.perf_counter()),
            "latency_p95_s": book.latency_quantile(0.95, time.perf_counter()),
            "lateness": book.lateness(),
            "tick_ms": 1e3 * sum(e - s for s, e in drv.window_ticks())
            / max(len(drv.window_ticks()), 1),
            "drain_s": time.perf_counter() - t - w["ramp_s"] - args.seconds}),
            flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
