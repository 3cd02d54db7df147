"""Paper Table 5 / §7.3: full-distribution perplexity with the low-rank
fallback for out-of-candidate logits (Shim et al. style), vs exact softmax
and vs pure SVD-softmax at the same rank."""
from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv_row, get_artifacts
from repro.configs import L2SConfig
from repro.core import fit_l2s
from repro.core.lowrank import (build_lowrank, exact_perplexity, perplexity)
from repro.utils.compile_cache import enable_compile_cache

RANK = 32          # paper uses 20 for PTB-Small; 32 here — the
                   # synthetic corpus has fatter tails (see notes)


def run():
    cfg, model, params, W, b, Htr, ytr, Hte, yte, targets = get_artifacts()
    Hppl, tgt = targets
    Hppl, tgt = Hppl[:4096], tgt[:4096]

    t0 = time.perf_counter()
    ppl_exact = exact_perplexity(W, b, Hppl, tgt)
    t_exact = time.perf_counter() - t0
    csv_row("table5/exact", t_exact / len(Hppl) * 1e6,
            f"ppl={ppl_exact:.2f},speedup=1.00x")

    U, Vt = build_lowrank(W, RANK)

    state = fit_l2s(Htr, ytr, cfg.vocab_size,
                    L2SConfig(num_clusters=100, budget=400, outer_iters=2,
                              sgd_steps=200))
    t0 = time.perf_counter()
    ppl_l2s = perplexity(W, b, U, Vt, state.screen, Hppl, tgt)
    t_l2s = time.perf_counter() - t0
    # analytic softmax-cost speedup: (r + L̄ + rank·fallback) vs L, d-dim ops
    csv_row("table5/L2S+lowrank", t_l2s / len(Hppl) * 1e6,
            f"ppl={ppl_l2s:.2f},ppl_delta={(ppl_l2s-ppl_exact)/ppl_exact*100:.2f}%")

    # pure low-rank (SVD-softmax style preview used for ALL logits)
    t0 = time.perf_counter()
    ppl_svd = perplexity(W, b, U, Vt,
                         _empty_screen(state.screen), Hppl, tgt)
    t_svd = time.perf_counter() - t0
    csv_row("table5/svd-only", t_svd / len(Hppl) * 1e6,
            f"ppl={ppl_svd:.2f},ppl_delta={(ppl_svd-ppl_exact)/ppl_exact*100:.2f}%")


def _empty_screen(screen):
    """Screen with empty candidate sets → every logit is low-rank."""
    import dataclasses
    return dataclasses.replace(
        screen,
        cand_idx=jnp.full_like(screen.cand_idx, screen.vocab_size),
        cand_len=jnp.zeros_like(screen.cand_len))


if __name__ == "__main__":
    enable_compile_cache()
    run()
