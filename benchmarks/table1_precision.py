"""Paper Table 1 / Figs 2-4: precision@1/@5 vs speedup of L2S against every
competing method — every row is a registered ``SoftmaxHead``, enumerated
from ``repro.heads`` over one shared (W, b, screen) context instead of
hand-calling five baseline classes.

Timing protocol = the paper's: ONE query at a time on a single CPU thread
(numpy-backed heads throughout, so per-op overheads are identical; the L2S
rows use the "screened-cpu" per-query adapter). Precision is evaluated over
a 2048-query held-out set against the exact softmax top-k. Each row also
reports the head's analytic cost model (``flops_per_query``).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import (csv_row, get_artifacts, head_context,
                               time_head_per_query)
from repro import heads
from repro.configs import L2SConfig
from repro.core import fit_l2s, precision_at_k
from repro.core.evaluate import (avg_candidate_size, exact_topk,
                                 full_softmax_topk_numpy)
from repro.core.train_l2s import kmeans_only_screen
from repro.utils.compile_cache import enable_compile_cache

N_EVAL = 2048
N_TIME = 400


def run(k: int = 5):
    cfg, model, params, W, b, Htr, ytr, Hte, yte, _ = get_artifacts()
    Hq = Hte[:N_EVAL]
    exact = np.asarray(exact_topk(jnp.asarray(W), jnp.asarray(b),
                                  jnp.asarray(Hq), k))

    t0 = time.perf_counter()
    for i in range(N_TIME):
        full_softmax_topk_numpy(W, b, Hq[i], k)
    t_full = (time.perf_counter() - t0) / N_TIME
    csv_row("table1/full-softmax", t_full * 1e6,
            "speedup=1.00x,p1=1.000,p5=1.000")

    def report(label, head, extra=""):
        pred = np.stack([np.asarray(head.topk(Hq[i:i + 1], k)[0][0])
                         for i in range(N_EVAL)])
        p1 = precision_at_k(pred[:, :1], exact[:, :1])
        p5 = precision_at_k(pred, exact)
        t = time_head_per_query(head, Hq, k, n_time=N_TIME)
        csv_row(f"table1/{label}", t * 1e6,
                f"speedup={t_full / t:.2f}x,p1={p1:.3f},p5={p5:.3f},"
                f"flops={head.flops_per_query:.0f}{extra}")

    # --- L2S (the paper) at two budgets (time/accuracy tradeoff) ---
    for budget in (100, 300):
        t0 = time.perf_counter()
        state = fit_l2s(Htr, ytr, cfg.vocab_size,
                        L2SConfig(num_clusters=100, budget=budget,
                                  outer_iters=3, sgd_steps=250))
        fit_s = time.perf_counter() - t0
        lbar = avg_candidate_size(state.screen, Hte)
        head = heads.get("screened-cpu",
                         **head_context(W, b, screen=state.screen))
        report(f"L2S-B{budget}", head,
               extra=f",lbar={lbar:.0f},fit_s={fit_s:.0f}")

    # --- spherical k-means ablation (Table 4 row) ---
    km = kmeans_only_screen(Htr, ytr, cfg.vocab_size,
                            L2SConfig(num_clusters=100, budget=100))
    report("kmeans-screen",
           heads.get("screened-cpu", **head_context(W, b, screen=km.screen)))

    # --- §4.1 competitors: enumerate the head registry ---
    freq = np.bincount(ytr[:, 0], minlength=cfg.vocab_size)
    competitor_rows = [
        ("svd-softmax-r16", "svd", dict(rho=16, n_top=400)),
        ("svd-softmax-r32", "svd", dict(rho=32, n_top=800)),
        ("adaptive-softmax", "shortlist",
         dict(freq_order=np.argsort(-freq), n_head=800, n_tails=4)),
        ("greedy-mips", "greedy-mips", dict(budget=512)),
        ("lsh-mips", "lsh-mips", dict(bands=8, bits=10)),
        ("pca-mips", "pca-mips", dict(depth=5)),
    ]
    registered = set(heads.names())
    for label, name, kw in competitor_rows:
        assert name in registered, f"{name} missing from head registry"
        report(label, heads.get(name, **head_context(W, b, **kw)))

    # --- adaptive frequency-tiered head (trained-unigram tiers; the flops
    #     column is the TIER-WEIGHTED expected cost — short-list + gates +
    #     p_descend × expected tail width, see benchmarks/README.md). The
    #     time column is jax per-query dispatch, not the numpy protocol:
    #     compare it to the other rows via flops, not speedup. ---
    ad = heads.get("adaptive", **head_context(W, b, counts=freq,
                                              shortlist=800, n_tails=4))
    report("adaptive-tiered", ad,
           extra=f",p_descend={ad._lay.p_descend:.3f}")

    # --- vocab-sharded heads (multi-device only; flops are PER SHARD —
    #     see benchmarks/README.md for how to read them) ---
    if jax.device_count() > 1:
        for name in ("exact-sharded", "screened-sharded"):
            head = heads.get(name, **head_context(W, b, screen=state.screen))
            csv_row(f"table1/{name}", float("nan"),
                    f"shards={head.n_shards},"
                    f"flops_per_shard={head.flops_per_query:.0f}")
        ads = heads.get("adaptive-sharded",
                        **head_context(W, b, counts=freq, shortlist=800,
                                       n_tails=4))
        csv_row("table1/adaptive-sharded", float("nan"),
                f"shards={ads.n_shards},"
                f"flops_per_shard={ads.flops_per_query:.0f},"
                f"p_descend={ads._lay.p_descend:.3f}")


if __name__ == "__main__":
    enable_compile_cache()
    run()
