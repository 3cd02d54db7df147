"""Paper Table 2: beam-search decode quality vs speedup. BLEU is replaced by
DECODE AGREEMENT with the exact-softmax beam (token-level + exact-match), per
DESIGN §6 — the quantity BLEU-delta proxies on real NMT data.
"""
from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv_row, get_artifacts
from repro.configs import L2SConfig
from repro.core import fit_l2s
from repro.data import ZipfMarkovCorpus
from repro.serving import DecodeEngine
from repro.utils.compile_cache import enable_compile_cache

N_PROMPTS = 12
PROMPT_LEN = 12
MAX_NEW = 24


def run():
    cfg, model, params, W, b, Htr, ytr, *_ = get_artifacts()
    state = fit_l2s(Htr[:40_000], ytr[:40_000], cfg.vocab_size,
                    L2SConfig(num_clusters=100, budget=200, outer_iters=2,
                              sgd_steps=200))
    engine = DecodeEngine(model, params, screen=state.screen,
                          max_len=PROMPT_LEN + MAX_NEW)
    c = ZipfMarkovCorpus(cfg.vocab_size, branching=96, seed=0)
    prompts = c.sample_batch(N_PROMPTS, PROMPT_LEN, seed=1234)

    for beam in (1, 5):
        tok_agree, exact_match, t_full, t_l2s = [], [], 0.0, 0.0
        for i in range(N_PROMPTS):
            t0 = time.perf_counter()
            ref = engine.beam_search(prompts[i], beam, MAX_NEW,
                                     head="exact")
            t_full += time.perf_counter() - t0
            t0 = time.perf_counter()
            got = engine.beam_search(prompts[i], beam, MAX_NEW,
                                     head="screened")
            t_l2s += time.perf_counter() - t0
            agree = float((ref.tokens[0] == got.tokens[0]).mean())
            tok_agree.append(agree)
            exact_match.append(float(agree == 1.0))
        us = t_l2s / (N_PROMPTS * MAX_NEW) * 1e6
        csv_row(f"table2/beam{beam}", us,
                f"speedup={t_full / t_l2s:.2f}x,"
                f"token_agreement={np.mean(tok_agree):.3f},"
                f"exact_match={np.mean(exact_match):.2f}")


if __name__ == "__main__":
    enable_compile_cache()
    run()
