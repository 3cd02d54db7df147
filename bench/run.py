#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's traffic, configuration and per-layer readers are found by name
under ``bench/`` (see ``BENCHMARK.json``). Progress goes to standard error;
the last line of standard output is the result as one JSON object. With no
TPU, or fewer chips than the cell asks for, the run exits 1 and prints no
result. JAX's compilation cache lives in ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace in this directory "
                         "(default: a temporary one, removed after reading)")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.seconds <= 0:
        print("[bench] --seconds must be positive", file=sys.stderr)
        return 2
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"[bench] the program (src/repro) is not in this checkout: {e}",
              file=sys.stderr)
        return 2
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    from bench import harness
    return harness.run(args, T_START)


if __name__ == "__main__":
    raise SystemExit(main())
