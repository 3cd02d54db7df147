"""Ablation of the paper's loss design (Eq.(6)/(8) hyper-parameters):

  λ  — false-positive weight (paper: 3e-4; λ=0 removes the compute penalty,
       large λ suppresses candidate growth)
  γ  — Lagrange weight on the L̄ ≤ B budget (paper: 10; γ=0 drops the
       budget constraint from the v-step)

Reported per setting: P@5 on held-out contexts and realized L̄ — validates
the paper's intuition that (a) missing a true candidate costs much more than
a wasted inner product (λ ≪ 1) and (b) the budget term keeps L̄ near B.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv_row, get_artifacts
from repro.configs import L2SConfig
from repro.core import fit_l2s, precision_at_k
from repro.core.evaluate import (PerQueryScreen, avg_candidate_size,
                                 exact_topk)
from repro.utils.compile_cache import enable_compile_cache


def run(k: int = 5):
    cfg, model, params, W, b, Htr, ytr, Hte, yte, _ = get_artifacts()
    Wd, bd = jnp.asarray(W), jnp.asarray(b)
    Hq = Hte[:1024]
    exact = np.asarray(exact_topk(Wd, bd, jnp.asarray(Hq), k))

    base = L2SConfig(num_clusters=100, budget=40, outer_iters=2,
                     sgd_steps=150)
    settings = [
        ("paper", base),                                        # λ=3e-4, γ=10
        ("lambda0", dataclasses.replace(base, lamb=0.0)),
        ("lambda-big", dataclasses.replace(base, lamb=0.05)),
        ("gamma0", dataclasses.replace(base, gamma=0.0)),
    ]
    for name, l2s_cfg in settings:
        state = fit_l2s(Htr, ytr, cfg.vocab_size, l2s_cfg)
        pq = PerQueryScreen(W, b, state.screen)
        pred = np.stack([pq.topk(Hq[i], k) for i in range(len(Hq))])
        p5 = precision_at_k(pred, exact)
        lbar = avg_candidate_size(state.screen, Hte)
        csv_row(f"ablation/{name}", lbar,
                f"lamb={l2s_cfg.lamb},gamma={l2s_cfg.gamma},"
                f"p5={p5:.3f},lbar={lbar:.1f}")


if __name__ == "__main__":
    enable_compile_cache()
    run()
