"""Paper Table 3: L2S robustness to the number of clusters r. The budget B is
co-varied (paper protocol: keep total prediction cost ~constant)."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv_row, get_artifacts, time_fn
from repro.configs import L2SConfig
from repro.core import fit_l2s, precision_at_k
from repro.core.evaluate import (PerQueryScreen, avg_candidate_size,
                                 exact_topk)
from repro.utils.compile_cache import enable_compile_cache
import time


def run(k: int = 5):
    cfg, model, params, W, b, Htr, ytr, Hte, yte, _ = get_artifacts()
    Wd, bd = jnp.asarray(W), jnp.asarray(b)
    Hq = Hte[:1536]
    exact = np.asarray(exact_topk(Wd, bd, jnp.asarray(Hq), k))

    # paper protocol: co-vary (r, B) so r + L̄ stays ~constant
    for r, budget in ((50, 250), (100, 200), (200, 100), (250, 50)):
        state = fit_l2s(Htr, ytr, cfg.vocab_size,
                        L2SConfig(num_clusters=r, budget=budget,
                                  outer_iters=2, sgd_steps=200))
        pq = PerQueryScreen(W, b, state.screen)
        pred = np.stack([pq.topk(Hq[i], k) for i in range(len(Hq))])
        p1 = precision_at_k(pred[:, :1], exact[:, :1])
        p5 = precision_at_k(pred, exact)
        t0 = time.perf_counter()
        for i in range(400):
            pq.topk(Hq[i], k)
        us = (time.perf_counter() - t0) / 400 * 1e6
        lbar = avg_candidate_size(state.screen, Hte)
        csv_row(f"table3/r{r}", us,
                f"budget={budget},p1={p1:.3f},p5={p5:.3f},lbar={lbar:.0f}")


if __name__ == "__main__":
    enable_compile_cache()
    run()
