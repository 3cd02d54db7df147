"""Pallas TPU kernel: fused subset softmax + top-k over screened candidates.

The full-fusion form of the L2S decode hot path (ROADMAP "On-TPU top-k /
fused subset softmax"): where ``screened_logits_pallas`` writes the whole
(B, K·V_BLK) candidate-logit tile back to HBM and leaves sentinel masking /
``jax.lax.top_k`` / the §4.2 log-softmax to separate XLA ops, this kernel
reduces each query row's candidates ON-CHIP and emits only

  top-k word ids (B, k) · top-k raw logits (B, k) · log Z (B,)

so per-query HBM traffic drops from O(K·V_BLK) floats to O(k) — the
device-resident reduction trick of FGD (Zhang et al., 2018) and adaptive
softmax (Grave et al., 2017), applied to the paper's screened candidate
sets.

Grid: (B, K) with the candidate slot j as the INNER, sequential dimension.
TPU grids iterate row-major, so for a fixed row i the K slot programs run
back-to-back and VMEM scratch carries state across them:

  vals/ids scratch (1, k_pad)  running top-k, sorted descending, ties at
                               the earliest flattened position (slot-major,
                               lane-minor) — exactly ``jax.lax.top_k``'s
                               convention over the unfused (B, K·V_BLK) row,
                               so ids AND vals are bit-identical to the
                               unfused path
  max/sum scratch  (1, V_BLK)  running (max, sum-exp) for the §4.2 log-Z,
                               online-softmax style, every lane holding the
                               same value (the TPU keeps vectors, not
                               scalars, in VMEM)

Each slot program DMAs its (V_BLK, d) weight tile (scalar-prefetch gather,
same as kernels/screen.py), computes the V_BLK tile logits on the MXU,
masks sentinel slots to −inf IN-KERNEL (``@pl.when`` guards the LSE update
so empty slots contribute nothing), reconstructs word ids from
``block_id · V_BLK + lane``, merges into the running top-k, and emits on
the last slot. The running accumulators are initialized to (−∞, sentinel)
so a row with fewer than k real candidates pads with NEG_INF/sentinel —
matching the unfused sentinel convention bit-for-bit — and an all-sentinel
row yields logZ = −∞ (callers map it to "probability 0", never NaN).

Sampling rides the same reduction: with ``noise`` (temperature-scaled
Gumbel, (B, K, V_BLK)) the perturbed top-1 IS a categorical draw over the
candidate softmax (the Gumbel-max trick), so sampling also never
materializes the logit tile.

TPU tiling follows kernels/screen.py: single rows (h, bias, noise, and the
per-row outputs) carry a unit axis of their own so each block's trailing
pair spans its array's. Outputs are lane-dense — ids/vals (B, 1, k_pad) and
logZ (B, 1, V_BLK) broadcast across lanes — and the wrapper slices them.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mode import interpret_mode
from repro.kernels.ref import NEG_INF
from repro.kernels.screen import V_BLK, tile_logits


def _merge_topk(vals, ids, k: int, width: int, fill_id: int):
    """Top-k of a (1, C) pool by (value desc, position asc), written into
    lanes 0..k-1 of a (1, width) row padded with (−inf, ``fill_id``).

    Selection by iterated first-position argmax reproduces
    ``jax.lax.top_k``'s lowest-index tie-break as long as the pool is laid
    out in flattened-position order — which the caller guarantees by
    concatenating [running list (earlier positions), new tile (lane
    order)]. Results are placed by lane select, not sliced stores, so every
    store stays lane-dense."""
    C = vals.shape[-1]
    pos = jax.lax.broadcasted_iota(jnp.int32, vals.shape, 1)
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    out_v = jnp.full((1, width), -jnp.inf, jnp.float32)
    out_i = jnp.full((1, width), fill_id, jnp.int32)
    for t in range(k):
        m = jnp.max(vals, axis=-1, keepdims=True)               # (1, 1)
        first = jnp.min(jnp.where(vals == m, pos, C), axis=-1,
                        keepdims=True)                          # first max
        take = pos == first
        pick = jnp.sum(jnp.where(take, ids, 0), axis=-1, keepdims=True)
        out_v = jnp.where(slot == t, m, out_v)
        out_i = jnp.where(slot == t, pick, out_i)
        vals = jnp.where(take, -jnp.inf, vals)
    return out_v, out_i


def _fused_topk_kernel(ids_ref, w_ref, h_ref, b_ref, *rest,
                       k: int, n_blk: int, v_blk: int, with_noise: bool):
    if with_noise:
        (noise_ref, vals_out, ids_out, logz_out,
         vals_scr, ids_scr, m_scr, s_scr) = rest
    else:
        noise_ref = None
        (vals_out, ids_out, logz_out,
         vals_scr, ids_scr, m_scr, s_scr) = rest
    i, j = pl.program_id(0), pl.program_id(1)
    sentinel = n_blk * v_blk

    @pl.when(j == 0)
    def _init():
        vals_scr[...] = jnp.full(vals_scr.shape, -jnp.inf, jnp.float32)
        ids_scr[...] = jnp.full(ids_scr.shape, sentinel, jnp.int32)
        m_scr[...] = jnp.full(m_scr.shape, -jnp.inf, jnp.float32)
        s_scr[...] = jnp.zeros(s_scr.shape, jnp.float32)

    blk = ids_ref[i, j]
    valid = blk < n_blk
    tile = jnp.where(valid, tile_logits(w_ref, h_ref, b_ref),
                     NEG_INF)                                   # (1, V_BLK)
    lane = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)
    tile_ids = jnp.where(valid, blk * v_blk + lane, sentinel)

    # §4.2 logZ: online (max, sum-exp); sentinel slots contribute nothing.
    # Padded vocab rows carry exactly NEG_INF bias, so exp underflows to 0.
    @pl.when(valid)
    def _lse():
        m_old = m_scr[...]
        m_new = jnp.maximum(m_old, jnp.max(tile, axis=-1, keepdims=True))
        m_scr[...] = m_new
        s_scr[...] = (s_scr[...] * jnp.exp(m_old - m_new) +
                      jnp.sum(jnp.exp(tile - m_new), axis=-1, keepdims=True))

    if with_noise:
        # Gumbel-max sampling: perturb AFTER the LSE so logZ stays exact;
        # sentinel slots keep NEG_INF (never drawn vs any real candidate)
        tile = jnp.where(valid, tile + noise_ref[0, 0], NEG_INF)

    # running top-k merge: scratch first (earlier flattened positions win
    # ties), tile second — scratch lanes past k hold −inf and never win
    pool_v = jnp.concatenate([vals_scr[...], tile], axis=-1)
    pool_i = jnp.concatenate([ids_scr[...], tile_ids], axis=-1)
    new_v, new_i = _merge_topk(pool_v, pool_i, k, vals_scr.shape[-1],
                               sentinel)
    vals_scr[...] = new_v
    ids_scr[...] = new_i

    @pl.when(j == pl.num_programs(1) - 1)
    def _emit():
        vals_out[0] = vals_scr[...]
        ids_out[0] = ids_scr[...]
        logz_out[0] = m_scr[...] + jnp.log(s_scr[...])


def fused_screened_topk(W_blocks: jnp.ndarray, b_blocks: jnp.ndarray,
                        h: jnp.ndarray, block_ids: jnp.ndarray, k: int,
                        noise: Optional[jnp.ndarray] = None,
                        interpret: Optional[bool] = None
                        ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """In-VMEM screened softmax reduction (plain/traceable; jitted entry
    points live in kernels/ops.py).

    W_blocks (n_blk, V_BLK, d); b_blocks (n_blk, V_BLK); h (B, d);
    block_ids (B, K) int32, sentinel ≥ n_blk; optional noise (B, K, V_BLK)
    added to valid candidate logits (Gumbel-max sampling).
    → (ids (B, k) int32, vals (B, k) f32, logZ (B,) f32). ids/vals are
    bit-identical to sentinel-masking + ``jax.lax.top_k`` over the unfused
    (B, K·V_BLK) candidate row; logZ is −∞ (not NaN) for all-sentinel rows.
    """
    n_blk, v_blk, d = W_blocks.shape
    B, K = block_ids.shape
    k_pad = -(-k // v_blk) * v_blk
    block_ids = block_ids.astype(jnp.int32)

    def tile_idx(i, j, ids):
        return (jnp.where(ids[i, j] < n_blk, ids[i, j], 0), 0, 0)

    in_specs = [
        pl.BlockSpec((1, v_blk, d), tile_idx),                  # W tile
        pl.BlockSpec((1, 1, d), lambda i, j, ids: (i, 0, 0)),   # h row
        pl.BlockSpec((1, 1, v_blk), tile_idx),                  # bias tile
    ]
    inputs = [block_ids, W_blocks, h.reshape(B, 1, d),
              b_blocks.reshape(n_blk, 1, v_blk)]
    if noise is not None:
        in_specs.append(pl.BlockSpec((1, 1, 1, v_blk),
                                     lambda i, j, ids: (i, j, 0, 0)))
        inputs.append(noise.astype(jnp.float32).reshape(B, K, 1, v_blk))

    def row_out(width):
        return pl.BlockSpec((1, 1, width), lambda i, j, ids: (i, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, K),
        in_specs=in_specs,
        out_specs=[row_out(k_pad), row_out(k_pad), row_out(v_blk)],
        scratch_shapes=[
            pltpu.VMEM((1, k_pad), jnp.float32),
            pltpu.VMEM((1, k_pad), jnp.int32),
            pltpu.VMEM((1, v_blk), jnp.float32),
            pltpu.VMEM((1, v_blk), jnp.float32),
        ],
    )
    with jax.named_scope("fused_topk"):
        vals, ids, logz = pl.pallas_call(
            functools.partial(_fused_topk_kernel, k=k, n_blk=n_blk,
                              v_blk=v_blk, with_noise=noise is not None),
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((B, 1, k_pad), jnp.float32),
                jax.ShapeDtypeStruct((B, 1, k_pad), jnp.int32),
                jax.ShapeDtypeStruct((B, 1, v_blk), jnp.float32),
            ],
            interpret=interpret_mode(interpret),
            name="fused_topk",
        )(*inputs)
        return ids[:, 0, :k], vals[:, 0, :k], logz[:, 0, 0]
