"""Attention unit tests: causality, GQA, sliding window, chunked==full,
decode==forward, ring-buffer semantics."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.layers.attention import (_sdpa, _sdpa_chunked, attn_decode,
                                    attn_forward, attn_init, init_cache,
                                    make_mask)

CFG = get_config("smollm-360m").reduced()   # 4 heads, kv 1..4


def _setup(cfg=CFG, B=2, T=16, seed=0):
    key = jax.random.key(seed)
    p = attn_init(key, cfg, jnp.float32)
    x = jax.random.normal(jax.random.key(seed + 1), (B, T, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    return p, x, pos


def test_causality():
    """Changing future tokens must not change past outputs."""
    p, x, pos = _setup()
    out1 = attn_forward(p, x, CFG, pos)
    x2 = x.at[:, 10:].set(x[:, 10:] * 3.0 + 1.0)
    out2 = attn_forward(p, x2, CFG, pos)
    np.testing.assert_allclose(np.asarray(out1[:, :10]),
                               np.asarray(out2[:, :10]), atol=1e-5)
    assert not np.allclose(np.asarray(out1[:, 10:]), np.asarray(out2[:, 10:]))


def test_sliding_window_masks_far_context():
    p, x, pos = _setup(T=32)
    full = attn_forward(p, x, CFG, pos)
    win = attn_forward(p, x, CFG, pos, window=4)
    # early positions (inside the window) identical, late positions differ
    np.testing.assert_allclose(np.asarray(full[:, :4]), np.asarray(win[:, :4]),
                               atol=1e-5)
    assert not np.allclose(np.asarray(full[:, -1]), np.asarray(win[:, -1]))


def test_make_mask_window():
    m = make_mask(8, 8, causal=True, window=3)
    assert bool(m[5, 5]) and bool(m[5, 3]) and not bool(m[5, 2])
    assert not bool(m[3, 4])   # causal


def test_chunked_matches_full():
    cfg = CFG
    p, x, pos = _setup(T=64)
    from repro.layers.attention import _project_qkv
    q, k, v = _project_qkv(p, x, cfg, pos)
    mask = make_mask(64, 64, causal=True)
    ref = _sdpa(q, k, v, mask, cfg)
    for qc in (16, 32, 64):
        out = _sdpa_chunked(q, k, v, cfg, causal=True, window=None, q_chunk=qc)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=1e-4)


def test_chunked_matches_full_window():
    cfg = CFG
    p, x, pos = _setup(T=64)
    from repro.layers.attention import _project_qkv
    q, k, v = _project_qkv(p, x, cfg, pos)
    mask = make_mask(64, 64, causal=True, window=7)
    ref = _sdpa(q, k, v, mask, cfg)
    out = _sdpa_chunked(q, k, v, cfg, causal=True, window=7, q_chunk=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)


def test_decode_matches_forward():
    p, x, pos = _setup(T=8)
    full = attn_forward(p, x, CFG, pos)
    cache = init_cache(CFG, 2, 8, dtype=jnp.float32)
    outs = []
    for t in range(8):
        o, cache = attn_decode(p, x[:, t:t + 1], cache, t, CFG)
        outs.append(o)
    dec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(full), np.asarray(dec), atol=1e-5)


# The vector-position branch builds its mask and cache writes per row, so
# XLA fuses and orders the softmax·V reduction differently from the scalar
# branch: outputs agree to float32 rounding of a reduction over the 8 cached
# positions (a few ulps; 8·2⁻²³ relative bounds it), not bit for bit. The
# caches themselves are written from identical projections and stay
# bit-identical.
_VEC_POS_TOL = dict(rtol=8 * 2.0 ** -23, atol=8 * 2.0 ** -23)


def test_vector_pos_decode_bit_identical_to_scalar():
    """The per-row position branch (continuous batching): aligned rows give
    outputs equal to float32 rounding (see ``_VEC_POS_TOL``) and
    BIT-identical caches to the scalar path, and rows at DIFFERENT depths
    each match their own scalar-pos decode."""
    p, x, pos = _setup(T=8)
    cache_s = init_cache(CFG, 2, 8, dtype=jnp.float32)
    cache_v = init_cache(CFG, 2, 8, dtype=jnp.float32)
    for t in range(8):
        o_s, cache_s = attn_decode(p, x[:, t:t + 1], cache_s, t, CFG)
        o_v, cache_v = attn_decode(p, x[:, t:t + 1], cache_v,
                                   jnp.full((2,), t, jnp.int32), CFG)
        np.testing.assert_allclose(np.asarray(o_v), np.asarray(o_s),
                                   **_VEC_POS_TOL)
    np.testing.assert_array_equal(np.asarray(cache_s["k"]),
                                  np.asarray(cache_v["k"]))
    # divergent depths: row 0 at t, row 1 at t+3 — each row equals a solo
    # scalar decode of the same (input, position) sequence
    B1 = 1
    c0 = init_cache(CFG, B1, 8, dtype=jnp.float32)
    c1 = init_cache(CFG, B1, 8, dtype=jnp.float32)
    cv = init_cache(CFG, 2, 8, dtype=jnp.float32)
    # pre-load row 1 three steps ahead (on both the solo and vector caches)
    for t in range(3):
        _, c1 = attn_decode(p, x[1:2, t:t + 1], c1, t, CFG)
        cv = {k: v.at[1].set(c1[k][0]) for k, v in cv.items()}
    for t in range(4):
        o0, c0 = attn_decode(p, x[0:1, t:t + 1], c0, t, CFG)
        o1, c1 = attn_decode(p, x[1:2, t + 3:t + 4], c1, t + 3, CFG)
        ov, cv = attn_decode(p, x[jnp.asarray([0, 1]),
                               jnp.asarray([t, t + 3])][:, None], cv,
                             jnp.asarray([t, t + 3], jnp.int32), CFG)
        np.testing.assert_allclose(np.asarray(ov[0]), np.asarray(o0[0]),
                                   **_VEC_POS_TOL)
        np.testing.assert_allclose(np.asarray(ov[1]), np.asarray(o1[0]),
                                   **_VEC_POS_TOL)


def test_vector_pos_ring_buffer_decode():
    """Vector-pos path with a ring cache: per-row slots wrap mod window and
    match the scalar ring decode row-for-row when aligned."""
    W = 4
    cfg = dataclasses.replace(CFG, sliding_window=W)
    p, x, pos = _setup(cfg, T=12)
    cache_s = init_cache(cfg, 2, 12, dtype=jnp.float32, window=W)
    cache_v = init_cache(cfg, 2, 12, dtype=jnp.float32, window=W)
    for t in range(12):
        o_s, cache_s = attn_decode(p, x[:, t:t + 1], cache_s, t, cfg,
                                   window=W)
        o_v, cache_v = attn_decode(p, x[:, t:t + 1], cache_v,
                                   jnp.full((2,), t, jnp.int32), cfg,
                                   window=W)
        np.testing.assert_array_equal(np.asarray(o_s), np.asarray(o_v))


def test_ring_buffer_decode_matches_windowed_forward():
    W = 4
    cfg = dataclasses.replace(CFG, sliding_window=W)
    p, x, pos = _setup(cfg, T=12)
    full = attn_forward(p, x, cfg, pos, window=W)
    cache = init_cache(cfg, 2, 12, dtype=jnp.float32, window=W)
    assert cache["k"].shape[1] == W
    outs = []
    for t in range(12):
        o, cache = attn_decode(p, x[:, t:t + 1], cache, t, cfg, window=W)
        outs.append(o)
    dec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(full), np.asarray(dec), atol=1e-5)


@pytest.mark.parametrize("arch", ["gemma-2b", "qwen1.5-110b"])
def test_gqa_and_bias_variants(arch):
    cfg = get_config(arch).reduced()
    p, x, pos = _setup(cfg)
    out = attn_forward(p, x, cfg, pos)
    assert out.shape == x.shape
    assert not bool(jnp.any(jnp.isnan(out)))
    if cfg.qkv_bias:
        assert "bq" in p
