"""Inference-side screening: cluster routing + screened softmax (paper Fig. 1).

Representation: the learned candidate mask (r, n_items) is converted once to
padded index arrays for fixed-shape execution:

  cand_idx (r, C_max) int32  — word (or block) ids, padded with sentinel L
  cand_len (r,)       int32  — true candidate count per cluster

Prediction (paper "The Prediction Process"):
  z(h) = argmax_t v_t·h                      O(r·d)
  logits over W[cand_idx[z]] + b             O(L̄·d)
  top-k within the candidate set             (padded entries = −inf)

The serving engine and benchmarks consume this through the ``SoftmaxHead``
protocol (repro.heads: "screened" wraps these functions, "screened-pallas"
the Pallas kernels, which implement the same contract with explicit VMEM
tiling for TPU). ``make_screen_fn`` remains as a standalone jit-compiled
batched closure for direct use.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


@dataclass
class ScreenParams:
    """Learned screening model (paper: {v_t}, {c_t}).

    Registered as a JAX pytree (arrays are children, ``vocab_size``/``block``
    static aux data), so a screen passes through jit boundaries as a real
    argument — heads take it as a parameter instead of baking it in as a
    closure constant, and swapping same-shaped screens never recompiles."""
    v: jnp.ndarray          # (r, d) cluster weights
    cand_idx: jnp.ndarray   # (r, C_max) padded candidate ids (word or block)
    cand_len: jnp.ndarray   # (r,)
    vocab_size: int
    block: int = 1          # item granularity in words (TPU adaptation)

    @property
    def r(self) -> int:
        return self.v.shape[0]

    @property
    def c_max(self) -> int:
        return self.cand_idx.shape[1]

    def avg_candidate_words(self, cluster_sizes) -> float:
        """L̄ under a cluster-usage distribution."""
        w = np.asarray(cluster_sizes, np.float64)
        lens = np.asarray(self.cand_len, np.float64) * self.block
        return float((w * lens).sum() / max(w.sum(), 1.0))


jax.tree_util.register_pytree_node(
    ScreenParams,
    lambda s: ((s.v, s.cand_idx, s.cand_len), (s.vocab_size, s.block)),
    lambda aux, ch: ScreenParams(v=ch[0], cand_idx=ch[1], cand_len=ch[2],
                                 vocab_size=aux[0], block=aux[1]),
)


def candidates_to_padded(mask: np.ndarray, vocab_size: int, block: int = 1,
                         pad_to_multiple: int = 8) -> Tuple[np.ndarray, np.ndarray]:
    """(r, n_items) bool → (cand_idx (r, C_max), cand_len (r,)). Sentinel = n_items.

    Vectorized scatter: np.nonzero walks the mask row-major, so subtracting
    each row's cumulative offset turns flat positions into within-row slots.
    """
    r, n_items = mask.shape
    mask = np.asarray(mask, bool)
    lens = mask.sum(axis=1)
    c_max = int(max(int(lens.max(initial=1)), 1))
    c_max = -(-c_max // pad_to_multiple) * pad_to_multiple
    idx = np.full((r, c_max), n_items, np.int32)
    rows, cols = np.nonzero(mask)
    slots = np.arange(rows.size) - np.repeat(np.cumsum(lens) - lens, lens)
    idx[rows, slots] = cols
    return idx, lens.astype(np.int32)


def assign_clusters(v: jnp.ndarray, h: jnp.ndarray) -> jnp.ndarray:
    """z(h) = argmax_t v_t·h. h: (..., d) → (...,) int32. Paper Eq.(2).

    Scored at full float32 precision: the argmax is a discrete choice, and
    the TPU's default (bfloat16-operand) matmul flips it between clusters
    whose scores are that close — so heads that route here and the route
    kernel (kernels/route.py) would send the same h to different candidate
    sets. r·d is small next to the candidate matmul it selects."""
    scores = jnp.einsum("...d,rd->...r", h, v,
                        precision=jax.lax.Precision.HIGHEST)
    return jnp.argmax(scores, axis=-1).astype(jnp.int32)


def screened_logits(W: jnp.ndarray, b: jnp.ndarray, screen: ScreenParams,
                    h: jnp.ndarray, cluster: jnp.ndarray
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Exact logits over the routed candidate set.

    W (L, d), b (L,), h (B, d), cluster (B,) →
      (logits (B, C_max·block) with −inf padding, word_ids (B, C_max·block)).
    """
    L, d = W.shape
    items = screen.cand_idx[cluster]                     # (B, C_max)
    n_items = -(-L // screen.block)
    valid = items < n_items                              # (B, C_max); sentinel = n_items
    if screen.block == 1:
        safe = jnp.where(valid, items, 0)
        w = W[safe]                                      # (B, C_max, d)
        logits = jnp.einsum("bcd,bd->bc", w, h) + b[safe]
        logits = jnp.where(valid, logits, NEG_INF)
        word_ids = jnp.where(valid, items, L)
        return logits, word_ids
    # block variant: gather (C_max, block, d) tiles
    blk = screen.block
    safe = jnp.where(valid, items, 0)
    Wp = W.reshape(n_items, blk, d) if L % blk == 0 else _pad_rows(W, n_items, blk)
    bp = b if L % blk == 0 else jnp.pad(b, (0, n_items * blk - L), constant_values=NEG_INF)
    bp = bp.reshape(n_items, blk)
    w = Wp[safe]                                         # (B, C_max, blk, d)
    logits = jnp.einsum("bckd,bd->bck", w, h) + bp[safe]
    logits = jnp.where(valid[..., None], logits, NEG_INF)
    word_ids = jnp.where(valid[..., None], safe[..., None] * blk +
                         jnp.arange(blk)[None, None, :], L)
    return logits.reshape(h.shape[0], -1), word_ids.reshape(h.shape[0], -1)


def _pad_rows(W, n_items, blk):
    L, d = W.shape
    return jnp.pad(W, ((0, n_items * blk - L), (0, 0))).reshape(n_items, blk, d)


def screened_topk(W, b, screen: ScreenParams, h, k: int
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full prediction: route → screened logits → top-k word ids.

    Returns (topk_ids (B, k) int32 — sentinel L where fewer than k candidates,
    topk_logits (B, k)).
    """
    cluster = assign_clusters(screen.v, h)
    logits, word_ids = screened_logits(W, b, screen, h, cluster)
    vals, pos = jax.lax.top_k(logits, k)
    ids = jnp.take_along_axis(word_ids, pos, axis=-1)
    return ids, vals


def make_screen_fn(W, b, screen: ScreenParams, k: int = 5):
    """jit-compiled batched top-k screening closure."""
    @jax.jit
    def fn(h):
        return screened_topk(W, b, screen, h, k)
    return fn
