"""jit'd public wrappers around the Pallas kernels.

Two kernelized L2S decode hot paths:

``screened_topk_tpu`` — the UNFUSED reference pipeline:
  route (cluster_route kernel) → gather-matmul (screened_logits kernel) →
  sentinel masking → ``jax.lax.top_k`` over the candidate union. The
  (B, K·V_BLK) candidate-logit tile round-trips through HBM between the
  kernel and the top-k.

``screened_fused_topk_tpu`` — the FUSED pipeline (kernels/fused_topk.py):
  route → per-row on-chip reduction over candidate slots. Top-k, sentinel
  masking, and the §4.2 log-sum-exp all happen in VMEM; only (B, k)
  ids/vals and (B,) logZ ever reach HBM. ids/vals are bit-identical to the
  unfused path. ``screened_fused_sample_tpu`` rides the same kernel with
  temperature-scaled Gumbel noise (Gumbel-max ≡ categorical sampling).

Composition is flat: the inner pieces (``cluster_route``,
``screened_logits``, ``fused_screened_topk``) are plain traceable
functions; only the public entry points here (and the standalone
per-kernel wrappers they re-export) are jitted — no jit-inside-jit.

``interpret`` defaults to None: the kernels run compiled on a TPU backend
and interpreted elsewhere (repro.kernels.mode); an explicit bool is a test
override. The wrappers handle all padding/masking so callers see the same
contract as the pure-jnp reference path in repro.core.screening.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.fused_topk import fused_screened_topk
from repro.kernels.ref import NEG_INF
from repro.kernels.route import cluster_route
from repro.kernels.screen import V_BLK, screened_logits


def pack_head_blocks(W: jnp.ndarray, b: jnp.ndarray, v_blk: int = V_BLK):
    """(L, d) softmax weights → MXU-tiled (n_blk, v_blk, d) + (n_blk, v_blk).

    Rows past L are zero-padded with −inf bias so they never win top-k."""
    L, d = W.shape
    n_blk = -(-L // v_blk)
    Wp = jnp.pad(W, ((0, n_blk * v_blk - L), (0, 0)))
    bp = jnp.pad(b, (0, n_blk * v_blk - L), constant_values=NEG_INF)
    return Wp.reshape(n_blk, v_blk, d), bp.reshape(n_blk, v_blk)


def _route_block_ids(v, cand_blocks, h,
                     interpret: Optional[bool]) -> jnp.ndarray:
    """Kernelized routing → per-row candidate block ids (B, K)."""
    cluster = cluster_route(h, v, interpret=interpret)               # (B,)
    return cand_blocks[cluster]


def _candidate_logits(W_blocks, b_blocks, v, cand_blocks, h,
                      interpret: Optional[bool]
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Plain body of ``screened_candidate_logits_tpu``."""
    n_blk, v_blk, d = W_blocks.shape
    block_ids = _route_block_ids(v, cand_blocks, h, interpret)       # (B, K)
    raw = screened_logits(W_blocks, b_blocks, h, block_ids,
                          interpret=interpret)                       # (B, K, V)
    valid = (block_ids < n_blk)[..., None]
    logits = jnp.where(valid, raw, NEG_INF).reshape(h.shape[0], -1)
    word_ids = jnp.where(
        valid, block_ids[..., None] * v_blk +
        jnp.arange(v_blk, dtype=jnp.int32)[None, None, :],
        n_blk * v_blk).reshape(h.shape[0], -1)
    return logits, word_ids


@functools.partial(jax.jit, static_argnames=("interpret",))
def screened_candidate_logits_tpu(W_blocks, b_blocks, v, cand_blocks, h,
                                  interpret: Optional[bool] = None
                                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Kernelized route + gather-matmul over the routed candidate blocks.

    W_blocks (n_blk, V_BLK, d), b_blocks (n_blk, V_BLK): packed softmax head.
    v (r, d): cluster weights. cand_blocks (r, K) int32, sentinel ≥ n_blk.
    h (B, d): context vectors. → (logits (B, K·V_BLK) with −inf at sentinel
    slots, word ids (B, K·V_BLK) with sentinel n_blk·V_BLK) — the flattened
    candidate union, ready for top-k, log-softmax, or sampling.
    """
    return _candidate_logits(W_blocks, b_blocks, v, cand_blocks, h,
                             interpret)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def screened_topk_tpu(W_blocks, b_blocks, v, cand_blocks, h, k: int = 5,
                      interpret: Optional[bool] = None
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Unfused kernelized L2S prediction: candidate logits → top-k.

    Same inputs as ``screened_candidate_logits_tpu``;
    → (word ids (B, k), logits (B, k)).
    """
    logits, word_ids = _candidate_logits(W_blocks, b_blocks, v, cand_blocks,
                                         h, interpret)
    vals, pos = jax.lax.top_k(logits, k)
    ids = jnp.take_along_axis(word_ids, pos, axis=-1)
    return ids, vals


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def screened_fused_topk_tpu(W_blocks, b_blocks, v, cand_blocks, h,
                            k: int = 5, interpret: Optional[bool] = None
                            ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fully fused L2S prediction: route → in-VMEM subset softmax + top-k.

    Same inputs as ``screened_candidate_logits_tpu``;
    → (word ids (B, k) int32, logits (B, k) f32, logZ (B,) f32). ids/vals
    bit-identical to ``screened_topk_tpu``; logZ is the §4.2 log-sum-exp
    over the candidate union (−∞, never NaN, for all-sentinel rows).
    """
    block_ids = _route_block_ids(v, cand_blocks, h, interpret)
    return fused_screened_topk(W_blocks, b_blocks, h, block_ids, k=k,
                               interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def screened_fused_sample_tpu(W_blocks, b_blocks, v, cand_blocks, h, key,
                              temperature: float = 1.0,
                              interpret: Optional[bool] = None
                              ) -> jnp.ndarray:
    """Fused categorical draw from the candidate softmax (Gumbel-max).

    argmax(logits/T + G) ≡ argmax(logits + T·G) for T > 0, so the fused
    top-1 over Gumbel-perturbed tiles IS a temperature-T sample — the
    candidate-logit tile still never leaves VMEM (only the (B, K, V_BLK)
    noise, which is independent of d, is generated off-chip).
    → (B,) int32 word ids (sentinel n_blk·V_BLK on all-sentinel rows).
    """
    block_ids = _route_block_ids(v, cand_blocks, h, interpret)
    B, K = block_ids.shape
    v_blk = W_blocks.shape[1]
    noise = temperature * jax.random.gumbel(key, (B, K, v_blk), jnp.float32)
    ids, _, _ = fused_screened_topk(W_blocks, b_blocks, h, block_ids, k=1,
                                    noise=noise, interpret=interpret)
    return ids[:, 0]


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def tier_fused_topk_tpu(W_blocks, b_blocks, h, block_ids, k: int = 5,
                        interpret: Optional[bool] = None
                        ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Per-TIER fused entry for the adaptive softmax heads
    (repro.heads.adaptive): the same in-VMEM subset reduction as
    ``screened_fused_topk_tpu`` with the candidate blocks given DIRECTLY —
    the frequency-tier layout IS the routing, so there is no cluster_route
    step. ``block_ids`` (B, K) int32 with sentinel ≥ n_blk; a fully-sentinel
    row (a query whose tail-gate lost) yields NEG_INF vals, sentinel ids and
    logZ = −∞, never NaN.
    → (packed-row ids (B, k) int32, logits (B, k) f32, logZ (B,) f32);
    callers translate packed rows to vocab ids through their tier id map.
    """
    return fused_screened_topk(W_blocks, b_blocks, h,
                               block_ids.astype(jnp.int32), k=k,
                               interpret=interpret)
