"""Fused vs unfused L2S kernel-path microbenchmark.

Compares ``screened_fused_topk_tpu`` (in-VMEM subset softmax + top-k, only
(B, k) results reach HBM) against ``screened_topk_tpu`` (candidate-logit
tile written back, XLA-side masking + top-k) on synthetic packed heads:

  * wall time per call (median of timed reps, post-warmup)
  * XLA bytes-accessed from HLO cost analysis, plus a structural check that
    the fused executable contains NO (B, K·V_BLK) f32 buffer

The kernels run compiled on a TPU and interpreted elsewhere
(repro.kernels.mode); off the chip the wall times measure the EMULATED
kernels and only the bytes/buffer columns reflect the TPU story.

    PYTHONPATH=src python benchmarks/kernel_fused.py              # full
    PYTHONPATH=src python benchmarks/kernel_fused.py --reduced    # CI smoke
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ops import (pack_head_blocks, screened_fused_topk_tpu,
                               screened_topk_tpu)
from repro.launch.hlo_cost import materializes_f32_buffer, xla_bytes_accessed
from repro.utils.compile_cache import enable_compile_cache

try:
    from benchmarks.common import csv_row
except ModuleNotFoundError:    # run as `python benchmarks/kernel_fused.py`:
    from common import csv_row  # the script's own dir is sys.path[0]


def _has_candidate_tile(hlo: str, B: int, K: int) -> bool:
    return materializes_f32_buffer(hlo, B, K, 128)


def _time(fn, *args, reps: int, **kw) -> float:
    jax.block_until_ready(fn(*args, **kw))          # compile + warm
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args, **kw))
        times.append(time.perf_counter() - t0)
    return float(np.median(times)) * 1e6            # µs


def run(reduced: bool = False):
    if reduced:
        cases = [(16, 8, 128, 5, 1500)]             # (B, K, d, k, L)
        reps = 3
    else:
        cases = [(32, 16, 512, 5, 4000),
                 (32, 16, 512, 64, 4000),
                 (8, 8, 256, 5, 2000)]
        reps = 10
    rng = np.random.default_rng(0)
    print(f"{'B':>4} {'K':>3} {'d':>4} {'k':>3} | {'unfused µs':>11} "
          f"{'fused µs':>11} | {'unfused MB':>10} {'fused MB':>9} "
          f"{'tile?':>11}")
    for B, K, d, k, L in cases:
        W = jnp.asarray(rng.standard_normal((L, d)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((L,)), jnp.float32)
        Wb, bb = pack_head_blocks(W, b)
        r = 8
        v = jnp.asarray(rng.standard_normal((r, d)), jnp.float32)
        cand = jnp.asarray(rng.integers(0, Wb.shape[0] + 1, (r, K)),
                           jnp.int32)
        h = jnp.asarray(rng.standard_normal((B, d)), jnp.float32)
        args = (Wb, bb, v, cand, h)
        kw = dict(k=k)

        iu, vu = screened_topk_tpu(*args, **kw)
        if_, vf, _ = screened_fused_topk_tpu(*args, **kw)
        assert np.array_equal(np.asarray(iu), np.asarray(if_)), \
            "fused/unfused id mismatch"
        assert np.array_equal(np.asarray(vu), np.asarray(vf)), \
            "fused/unfused val mismatch"

        t_u = _time(screened_topk_tpu, *args, reps=reps, **kw)
        t_f = _time(screened_fused_topk_tpu, *args, reps=reps, **kw)
        cu = screened_topk_tpu.lower(*args, **kw).compile()
        cf = screened_fused_topk_tpu.lower(*args, **kw).compile()
        b_u, b_f = xla_bytes_accessed(cu), xla_bytes_accessed(cf)
        tiles = (f"{'Y' if _has_candidate_tile(cu.as_text(), B, K) else 'N'}"
                 f"/{'Y' if _has_candidate_tile(cf.as_text(), B, K) else 'N'}")
        assert not _has_candidate_tile(cf.as_text(), B, K), \
            "fused executable materialized the candidate-logit tile"
        assert b_f < b_u, "fused path should access strictly fewer bytes"
        print(f"{B:>4} {K:>3} {d:>4} {k:>3} | {t_u:>11.1f} {t_f:>11.1f} | "
              f"{b_u / 1e6:>10.2f} {b_f / 1e6:>9.2f} {tiles:>11}")
        csv_row(f"kernel_fused/B{B}_K{K}_d{d}_k{k}", t_f,
                f"unfused_us={t_u:.1f},bytes_fused={b_f:.0f},"
                f"bytes_unfused={b_u:.0f}")
    print("\n(tile? = unfused/fused executables containing the "
          "(B, K·V_BLK) f32 candidate-logit buffer — the fused column "
          "must be N)")


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--reduced", action="store_true",
                    help="one small case, few reps (CI smoke)")
    a = ap.parse_args()
    run(reduced=a.reduced)
