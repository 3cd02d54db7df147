"""Pallas TPU kernel: screened-logits gather-matmul (the L2S hot path).

The TPU adaptation of the paper's candidate-set softmax (DESIGN §3): candidate
sets are sets of V_BLK-row vocab blocks, so the "gather" is a blocked DMA that
streams exactly the candidate tiles of W from HBM into VMEM.

Mechanism: ``PrefetchScalarGridSpec(num_scalar_prefetch=1)`` — the per-(row,
slot) block ids are prefetched into SMEM before the grid runs, and W's
BlockSpec ``index_map`` reads them to choose WHICH (V_BLK, d) tile of W each
program instance DMAs. This is the canonical Pallas sparse-gather pattern.

Grid: (B, K) — one program per (query row, candidate slot).
VMEM per step: V_BLK·d (W tile) + d (h row) + V_BLK (bias+out) ≈ 2·128·d bytes
(bf16) — ≤ 4 MB at d = 8192, well inside the ~16 MB v5e VMEM budget; the
matmul dims (V_BLK=128 rows × d cols) are MXU-aligned.

TPU tiling: a block's last two dims must be (8, 128)-divisible or span the
whole array. One query row, one bias tile and one output tile are each a
single row, so the wrapper gives them a unit axis of their own — h as
(B, 1, d), bias as (n_blk, 1, V_BLK), logits as (B, K, 1, V_BLK) — and
every block's trailing pair then spans its array's trailing pair, for any
B, K and d (d = 500 included).

Sentinel block ids (≥ n_blocks) are mapped to tile 0 by the index_map and
masked to −inf in the wrapper (ops.py) — the kernel itself stays branch-free.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mode import interpret_mode

V_BLK = 128


def tile_logits(w_ref, h_ref, b_ref) -> jnp.ndarray:
    """(1, V_BLK) f32 logits of one gathered tile: h (1, d) · W_tileᵀ + b.
    Shared by this kernel and the fused top-k kernel, so both compute the
    same bits for the same tile."""
    acc = jax.lax.dot_general(
        h_ref[0], w_ref[0],                           # (1, d) · (V_BLK, d)ᵀ
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    return acc + b_ref[0].astype(jnp.float32)


def _screened_logits_kernel(block_ids_ref, w_ref, h_ref, b_ref, out_ref):
    """One (row, slot): out[V_BLK] = W_tile (V_BLK, d) · h (d,) + bias."""
    out_ref[0, 0] = tile_logits(w_ref, h_ref, b_ref)


def screened_logits(W_blocks: jnp.ndarray, b_blocks: jnp.ndarray,
                    h: jnp.ndarray, block_ids: jnp.ndarray,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """W_blocks (n_blk, V_BLK, d); b_blocks (n_blk, V_BLK); h (B, d);
    block_ids (B, K) int32 (sentinel ≥ n_blk). → raw logits (B, K, V_BLK) f32
    (sentinel tiles NOT yet masked — ops.py applies the −inf mask).

    Plain/traceable — compose inside an outer jit (kernels/ops.py does);
    ``screened_logits_pallas`` is the jitted public entry point."""
    n_blk, v_blk, d = W_blocks.shape
    B, K = block_ids.shape
    safe_ids = jnp.where(block_ids < n_blk, block_ids, 0).astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, K),
        in_specs=[
            # W: tile selected by the prefetched id for (row i, slot j)
            pl.BlockSpec((1, v_blk, d), lambda i, j, ids: (ids[i, j], 0, 0)),
            # h: row i
            pl.BlockSpec((1, 1, d), lambda i, j, ids: (i, 0, 0)),
            # bias: same tile as W
            pl.BlockSpec((1, 1, v_blk), lambda i, j, ids: (ids[i, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, v_blk),
                               lambda i, j, ids: (i, j, 0, 0)),
    )
    out = pl.pallas_call(
        _screened_logits_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, 1, v_blk), jnp.float32),
        interpret=interpret_mode(interpret),
    )(safe_ids, W_blocks, h.reshape(B, 1, d),
      b_blocks.reshape(n_blk, 1, v_blk))
    return out.reshape(B, K, v_blk)


screened_logits_pallas = jax.jit(screened_logits,
                                 static_argnames=("interpret",))
