"""Pallas TPU kernel: fused cluster scoring + top-1 routing (paper Eq.(2)).

scores = h (B, d) · vᵀ (d, r); cluster = argmax over r — fused so the (B, r)
score matrix never round-trips to HBM. The screening overhead O(r·d) must
stay negligible next to the O(L̄·d) candidate matmul; fusing removes its
memory traffic entirely.

Grid: (B / B_TILE,). Each step: (B_TILE, d) × (d, r_pad) MXU matmul + row
argmax in VREGs. r is padded to a lane multiple (128) with −inf columns.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.mode import interpret_mode

NEG_INF = -1e30
B_TILE = 128
LANE = 128


def _route_kernel(h_ref, vt_ref, out_ref, *, r_true: int):
    h = h_ref[...]                      # (B_TILE, d)
    vt = vt_ref[...]                    # (d, r_pad)
    # full f32: the argmax must match core.screening.assign_clusters
    scores = jax.lax.dot_general(
        h, vt, dimension_numbers=(((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)          # (B_TILE, r_pad)
    r_pad = scores.shape[-1]
    col = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    scores = jnp.where(col < r_true, scores, NEG_INF)
    # argmax with jnp.argmax's tie-break (lowest index), spelled out so the
    # compiled kernel cannot break ties between equal clusters differently
    best = jnp.max(scores, axis=-1, keepdims=True)
    out_ref[...] = jnp.min(jnp.where(scores == best, col, r_pad), axis=-1)


def cluster_route(h: jnp.ndarray, v: jnp.ndarray,
                  interpret: Optional[bool] = None) -> jnp.ndarray:
    """h (B, d); v (r, d) → (B,) int32 cluster ids.

    Plain/traceable — compose inside an outer jit (kernels/ops.py does);
    ``cluster_route_pallas`` is the jitted public entry point."""
    B, d = h.shape
    r = v.shape[0]
    r_pad = -(-r // LANE) * LANE
    b_pad = -(-B // B_TILE) * B_TILE
    vt = jnp.zeros((d, r_pad), v.dtype).at[:, :r].set(v.T)
    hp = jnp.zeros((b_pad, d), h.dtype).at[:B].set(h)

    out = pl.pallas_call(
        functools.partial(_route_kernel, r_true=r),
        grid=(b_pad // B_TILE,),
        in_specs=[
            pl.BlockSpec((B_TILE, d), lambda i: (i, 0)),
            pl.BlockSpec((d, r_pad), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((B_TILE,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((b_pad,), jnp.int32),
        interpret=interpret_mode(interpret),
    )(hp, vt)
    return out[:B]


cluster_route_pallas = jax.jit(cluster_route, static_argnames=("interpret",))
