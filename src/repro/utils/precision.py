"""float32 activations against narrower weights, at float32's accuracy.

The mla_moe family stores its weights as published (bfloat16) and keeps its
activations in float32. A float32 ``x`` meets such weights as three terms
of the weights' dtype whose sum is x to float32's accuracy (bfloat16:
3 × 8 bits): each term is the rounding of what the terms before it leave.
One rounding of x would keep 8 bits, enough to move expert choices at
near-ties away from those of a float32 computation. The price is three
products per weight read instead of one. The layers (``attention.wdot``,
``moe._ragged_wdot``) and the screened-logits kernel (``screen.tile_logits``)
all split through ``split_terms``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

N_TERMS = 3


def split_terms(x, dtype, *, in_kernel: bool = False):
    """float32 ``x`` as ``N_TERMS`` terms of the narrower ``dtype``.

    In XLA the roundings go through ``reduce_precision``, which no compiler
    may skip as excess precision (XLA may drop a float32 → bfloat16 →
    float32 round trip). Mosaic, which compiles Pallas kernels, has no
    lowering for ``reduce_precision``; a kernel (``in_kernel``) rounds with
    ``astype``, whose converts it keeps as written. Both round to nearest
    even, so both give the same terms."""
    bits = {jnp.bfloat16: 7, jnp.float16: 10}[jnp.dtype(dtype).type]
    terms, rest = [], x
    for _ in range(N_TERMS):
        if in_kernel:
            t = rest.astype(dtype)
            rest = rest - t.astype(rest.dtype)
        else:
            r = jax.lax.reduce_precision(rest, exponent_bits=8,
                                         mantissa_bits=bits)
            t = r.astype(dtype)
            rest = rest - r
        terms.append(t)
    return terms
