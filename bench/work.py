"""Operations and bytes the served model needs, from shapes and the screen.

Counted from the configuration and the fitted screen, never from the
implementation, so a PR that does the same work in fewer operations shows
as a gain and one that skips work cannot hide it.

Trunk: the family's ``trunk_flops(conf, positions)`` (``bench/families/``),
for a token at each position the trunk runs. Head: the exact softmax scores every word, L·d multiply-adds; the screened
head scores the r cluster centres and the L̄ words of the routed candidate
set, (r + L̄)·d.
"""
from __future__ import annotations

import numpy as np


def head_macs(head: str, vocab: int, d: int, r: int = 0,
              lbar: float = 0.0) -> float:
    """Multiply-adds per query of ``head``: L·d exact, (r + L̄)·d screened."""
    if head == "exact":
        return float(vocab) * d
    return (float(r) + float(lbar)) * d


def mean_candidate_words(cand_len, block: int) -> float:
    """L̄: candidate words per cluster, averaged over the r clusters."""
    return float(np.mean(np.asarray(cand_len, np.float64))) * block


def model_flops(family, conf: dict, head_macs_per_query: float,
                prompt_positions, decode_positions, output_tokens: int
                ) -> float:
    """Operations the served work required: the trunk once for every prompt
    token and for every decode step's input token, each at its position
    (``family.trunk_flops``); the head once for every output token. A
    request's first output token comes straight from the prefill's last
    state, so it has no decode step of its own."""
    return family.trunk_flops(conf, prompt_positions) \
        + family.trunk_flops(conf, decode_positions) \
        + output_tokens * 2.0 * head_macs_per_query


def fused_topk_work(rows: int, lbar: float, d: int) -> tuple:
    """(FLOPs, bytes) the fused subset top-k needs for ``rows`` active query
    rows: each scores its L̄ candidate words (2·L̄·d operations) from their
    float32 weight rows (4·L̄·d bytes)."""
    return 2.0 * rows * lbar * d, 4.0 * rows * lbar * d
