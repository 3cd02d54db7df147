"""The mla_moe family at its reduced size (Moonlight-16B-A3B's mechanisms):
latent attention in its two forms, the held-expert layer, one chip's share
against the uncut layer, and bucketed paged joins.

Everything is float32 on the CPU, so the forms compared here differ only
by the order of float32 sums; each tolerance says how far that reaches and
why a bfloat16 computation (errors near 2⁻⁹ of each value) would fail it.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.layers.attention import (mla_decode, mla_forward, mla_init,
                                    mla_latent)
from repro.layers.moe import gated_ffn, moe_held_apply, moe_held_init
from repro.models import build_model
from repro.serving import DecodeEngine, PagePool, ServeRequest

CFG = get_config("moonlight-16b-a3b-ep8").reduced()
# float32 reassociation: a few ulps of values of order 1 (~1e-6); one
# bfloat16 rounding moves them by ~4e-3
TOL = 2e-5


def _with_moe(cfg, **kw):
    return dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, **kw))


def test_reduced_keeps_the_mechanisms():
    assert CFG.family == "mla_moe" and CFG.first_k_dense == 1
    assert CFG.kv_lora_rank and CFG.qk_rope_head_dim and CFG.v_head_dim
    m = CFG.moe
    assert m.routed_scale == 2.446 and m.num_shared == 2 and m.held == 1
    assert m.num_experts % m.held == 0 and m.held < m.num_experts


def test_absorbed_decode_equals_decompressed():
    """Decode over the cached latent with W_kvb absorbed into the query and
    the output gives what the decompressed prefill gives at each
    position, and the cache holds the prefill's latents."""
    p = mla_init(jax.random.key(0), CFG)
    T = 12
    x = jax.random.normal(jax.random.key(1), (2, T, CFG.d_model))
    pos = jnp.broadcast_to(jnp.arange(T)[None], (2, T))
    full, lat = mla_forward(p, x, CFG, pos)
    cache = jnp.zeros((3, 2, 16, CFG.mla_latent_dim))
    outs = []
    for t in range(T):
        o, cache = mla_decode(p, x[:, t:t + 1], cache, 1,
                              jnp.full((2,), t, jnp.int32), CFG)
        outs.append(o)
    np.testing.assert_allclose(np.concatenate(outs, 1), full, atol=TOL,
                               rtol=TOL)
    np.testing.assert_allclose(cache[1, :, :T], lat, atol=TOL, rtol=TOL)
    assert not np.any(np.asarray(cache)[[0, 2]])


def _dense_held(p, x, cfg):
    """Every held expert over every token, weighted by its gate (zero where
    not chosen), plus the shared experts: the layer without grouping."""
    from repro.layers.moe import route_held
    gates, idx = route_held(p, x, cfg)
    m = cfg.moe
    out = gated_ffn(p["shared"], x) if m.num_shared else 0.0
    for e in range(cfg.experts_held):
        w = jnp.sum(jnp.where(idx == m.held_offset + e, gates, 0.0), -1)
        ex = {k: p[k][e] for k in ("w_gate", "w_up", "w_down")}
        out = out + w[:, None] * gated_ffn(ex, x)
    return out


def test_held_experts_drop_no_token():
    """All 64 tokens route to the same two experts, both held: a capacity
    of any size below 64 would drop most of them; this layer computes every
    one."""
    cfg = _with_moe(CFG, held=2, held_offset=0)
    p = moe_held_init(jax.random.key(2), cfg)
    bias = jnp.full((cfg.num_experts,), -100.0).at[:2].set(100.0)
    p = dict(p, bias=bias)
    x = jax.random.normal(jax.random.key(3), (64, cfg.d_model))
    got = moe_held_apply(p, x, cfg)
    np.testing.assert_allclose(got, _dense_held(p, x, cfg), atol=TOL,
                               rtol=TOL)
    no_shared = moe_held_apply(p, x, _with_moe(cfg, num_shared=0))
    assert np.all(np.linalg.norm(np.asarray(no_shared), axis=-1) > 0.1)


def test_chip_shares_add_up_to_the_uncut_layer():
    """Eight experts, top-3, in four shares of two: the shares' routed
    parts, with the shared experts counted once, add up to the uncut
    layer's output; each share routes over all eight."""
    full_cfg = _with_moe(CFG, num_experts=8, top_k=3, held=8)
    p = moe_held_init(jax.random.key(4), full_cfg)
    x = jax.random.normal(jax.random.key(5), (40, CFG.d_model))
    whole = moe_held_apply(p, x, full_cfg)
    shared = gated_ffn(p["shared"], x)
    total = shared
    for chip in range(4):
        cfg = _with_moe(full_cfg, held=2, held_offset=2 * chip)
        share = dict(p, **{k: p[k][2 * chip:2 * chip + 2]
                           for k in ("w_gate", "w_up", "w_down")})
        total = total + moe_held_apply(share, x, cfg) - shared
    np.testing.assert_allclose(total, whole, atol=TOL, rtol=TOL)
    np.testing.assert_allclose(whole, _dense_held(p, x, full_cfg), atol=TOL,
                               rtol=TOL)


@pytest.fixture(scope="module")
def engine():
    m = build_model(CFG)
    params = m.init(jax.random.key(6), dtype=jnp.float32)
    return DecodeEngine(m, params, max_len=64)


def test_bucketed_join_matches_an_unpadded_one(engine):
    """A 21-token prompt prefilled at its bucket (32) gives the state, the
    first token and the page rows of the same prompt prefilled at its own
    length, and writes nothing past its last position."""
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, CFG.vocab_size, 21).astype(np.int32)
    pool = PagePool(num_pages=20, page_size=8)
    stream = engine.open_paged_stream(pool, width=2)
    assert stream.bucket(21) == 32 and stream.bucket(8) == 8
    assert stream.bucket(64) == 64 and stream.bucket(33) == 64
    arrays = pool.store.arrays
    pages = np.array([3, 7, 5], np.int32)

    def join(T):
        tokens = np.zeros((1, T), np.int32)
        tokens[0, :21] = prompt
        t = np.arange(T)
        dest = np.stack([np.where(t < 21, pages[np.minimum(t // 8, 2)], 20),
                         t % 8]).astype(np.int32)
        # the join consumes (donates) the pool it is given
        return engine._jit_attn_join(engine.params, tokens, np.int32(21),
                                     jax.tree.map(jnp.copy, arrays), dest)

    h32, pool32 = join(32)
    h21, pool21 = join(21)
    np.testing.assert_allclose(h32, h21, atol=TOL, rtol=TOL)
    head = engine.resolve_head("exact")
    assert int(head.next(h32)[0]) == int(head.next(h21)[0])
    np.testing.assert_allclose(pool32["latent"], pool21["latent"], atol=TOL,
                               rtol=TOL)
    lat = np.asarray(pool32["latent"])
    assert not np.any(lat[:, 5, 5:])        # past the prompt: untouched
    assert np.any(lat[:, 5, :5]) and not np.any(lat[:, [0, 1, 2, 4]])


def test_paged_stream_matches_dense_decode(engine):
    """Requests through the paged stream (latent pages, bucketed joins)
    decode the tokens that the contiguous-cache generate decodes."""
    rng = np.random.default_rng(1)
    reqs = [ServeRequest(prompt=rng.integers(0, CFG.vocab_size, n).astype(
        np.int32), max_new=k) for n, k in ((5, 6), (17, 4), (9, 7))]
    stream = engine.open_paged_stream(PagePool(num_pages=24, page_size=8),
                                      width=2)
    got, pending = {}, list(enumerate(reqs))
    while pending or stream.n_active or stream._finished:
        while pending and stream.free_slots:
            i, r = pending.pop(0)
            stream.join(r, tag=i)
        for tag, _, toks in stream.step():
            got[tag] = toks
    for i, r in enumerate(reqs):
        ref = engine.generate(r.prompt[None], r.max_new).tokens[0]
        np.testing.assert_array_equal(got[i], ref)


def test_latent_is_normalised_and_rotated():
    """The cached latent is RMSNorm(c) (unit mean square before its scale)
    beside the rotary key, whose norm the rotation keeps."""
    p = mla_init(jax.random.key(7), CFG)
    x = jax.random.normal(jax.random.key(8), (1, 4, CFG.d_model))
    lat = mla_latent(p, x, CFG, jnp.arange(4)[None])
    r = CFG.kv_lora_rank
    np.testing.assert_allclose(np.mean(np.square(lat[..., :r]), -1), 1.0,
                               rtol=1e-4)
    lat0 = mla_latent(p, x, CFG, jnp.zeros((1, 4), jnp.int32))
    np.testing.assert_allclose(np.linalg.norm(lat[..., r:], axis=-1),
                               np.linalg.norm(lat0[..., r:], axis=-1),
                               rtol=1e-5)
