"""Paper Table 6 analog: qualitative side-by-side decodes — full softmax vs
L2S-screened beam search on the same prompts (the paper shows DE→EN
translations; here token-id sequences from the synthetic corpus with
agreement markers)."""
from __future__ import annotations

import numpy as np

from benchmarks.common import csv_row, get_artifacts
from repro.configs import L2SConfig
from repro.core import fit_l2s
from repro.data import ZipfMarkovCorpus
from repro.serving import DecodeEngine
from repro.utils.compile_cache import enable_compile_cache

N_SAMPLES = 6
MAX_NEW = 16


def run():
    cfg, model, params, W, b, Htr, ytr, *_ = get_artifacts()
    state = fit_l2s(Htr[:40_000], ytr[:40_000], cfg.vocab_size,
                    L2SConfig(num_clusters=100, budget=200, outer_iters=2,
                              sgd_steps=200))
    engine = DecodeEngine(model, params, screen=state.screen,
                          max_len=12 + MAX_NEW)
    c = ZipfMarkovCorpus(cfg.vocab_size, branching=96, seed=0)
    prompts = c.sample_batch(N_SAMPLES, 12, seed=4242)

    same = 0
    for i in range(N_SAMPLES):
        ref = engine.beam_search(prompts[i], beam=5, max_new=MAX_NEW,
                                 head="exact")
        got = engine.beam_search(prompts[i], beam=5, max_new=MAX_NEW,
                                 head="screened")
        a, bseq = ref.tokens[0], got.tokens[0]
        marks = "".join("·" if x == y else "X" for x, y in zip(a, bseq))
        agree = float((a == bseq).mean())
        same += agree == 1.0
        csv_row(f"table6/sample{i}", agree * 100,
                f"full={' '.join(map(str, a[:8]))}...,"
                f"l2s={' '.join(map(str, bseq[:8]))}...,marks={marks}")
    csv_row("table6/summary", same / N_SAMPLES * 100,
            f"identical_decodes={same}/{N_SAMPLES}")


if __name__ == "__main__":
    enable_compile_cache()
    run()
