"""Pallas kernel validation (interpret=True on CPU): shape/dtype sweeps
against the pure-jnp oracles in repro.kernels.ref."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.screening import ScreenParams, screened_topk
from repro.kernels.ops import pack_head_blocks, screened_topk_tpu
from repro.kernels.ref import (cluster_route_ref, screened_logits_ref,
                               subset_softmax_topk_ref)
from repro.kernels.route import cluster_route_pallas
from repro.kernels.screen import screened_logits_pallas


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n_blk,d,B,K", [
    (4, 128, 2, 2),
    (10, 256, 4, 3),
    (7, 512, 1, 5),
    (16, 64, 8, 8),
])
def test_screened_logits_sweep(n_blk, d, B, K, dtype):
    rng = np.random.default_rng(n_blk + d + B + K)
    v_blk = 128
    W = jnp.asarray(rng.standard_normal((n_blk, v_blk, d)), dtype)
    bb = jnp.asarray(rng.standard_normal((n_blk, v_blk)), dtype)
    h = jnp.asarray(rng.standard_normal((B, d)), dtype)
    ids = jnp.asarray(rng.integers(0, n_blk + 2, (B, K)), jnp.int32)
    out = screened_logits_pallas(W, bb, h, ids)
    ref = screened_logits_ref(W, bb, h, ids)
    valid = (ids < n_blk)[..., None]
    out = jnp.where(valid, out, ref)     # kernel leaves sentinels unmasked
    tol = 1e-4 if dtype == jnp.float32 else 0.3
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol * np.sqrt(d), rtol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,d,r", [(1, 64, 3), (37, 64, 50), (128, 256, 100),
                                   (130, 128, 129)])
def test_cluster_route_sweep(B, d, r, dtype):
    rng = np.random.default_rng(B + d + r)
    h = jnp.asarray(rng.standard_normal((B, d)), dtype)
    v = jnp.asarray(rng.standard_normal((r, d)), dtype)
    got = cluster_route_pallas(h, v)
    ref = cluster_route_ref(h, v)
    # bf16 ties can legitimately differ; require ≥ 99% agreement for bf16
    agree = float(jnp.mean((got == ref).astype(jnp.float32)))
    assert agree == 1.0 if dtype == jnp.float32 else agree > 0.97


def test_full_kernel_path_matches_core():
    """screened_topk_tpu (kernels) ≡ screened_topk (core, block granularity)."""
    rng = np.random.default_rng(0)
    L, d, r, K = 1500, 128, 6, 4
    W = jnp.asarray(rng.standard_normal((L, d)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((L,)), jnp.float32)
    Wb, bb = pack_head_blocks(W, b)
    n_blk = Wb.shape[0]
    v = jnp.asarray(rng.standard_normal((r, d)), jnp.float32)
    cand = jnp.asarray(rng.integers(0, n_blk + 1, (r, K)), jnp.int32)
    h = jnp.asarray(rng.standard_normal((9, d)), jnp.float32)

    ids_k, vals_k = screened_topk_tpu(Wb, bb, v, cand, h, k=5)
    lens = np.asarray((cand < n_blk).sum(axis=1), np.int32)
    sp = ScreenParams(v=v, cand_idx=cand, cand_len=jnp.asarray(lens),
                      vocab_size=L, block=128)
    ids_r, vals_r = screened_topk(W, b, sp, h, 5)
    np.testing.assert_array_equal(np.asarray(ids_k), np.asarray(ids_r))
    np.testing.assert_allclose(np.asarray(vals_k), np.asarray(vals_r),
                               atol=1e-3, rtol=1e-4)


def test_pack_head_blocks_padding():
    W = jnp.ones((100, 16))
    b = jnp.zeros((100,))
    Wb, bb = pack_head_blocks(W, b)
    assert Wb.shape == (1, 128, 16)
    assert float(bb[0, 99]) == 0.0
    assert float(bb[0, 100]) < -1e29      # padded rows can never win


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("S,KV,hd", [(128, 1, 64), (256, 8, 32), (512, 4, 128)])
def test_cache_slot_update_sweep(S, KV, hd, dtype):
    """Predicated in-place cache update (§Perf HC1 structural fix) vs the
    dynamic_update_slice oracle across slot positions incl. boundaries."""
    from repro.kernels.cache_update import (cache_slot_update,
                                            cache_slot_update_ref)
    rng = np.random.default_rng(S + KV + hd)
    cache = jnp.asarray(rng.standard_normal((S, KV, hd)), dtype)
    upd = jnp.asarray(rng.standard_normal((KV, hd)), dtype)
    for slot in (0, 127, S // 2, S - 1, S + 5):   # incl. out-of-range clamp
        got = cache_slot_update(cache.copy(), upd, slot)
        ref = cache_slot_update_ref(cache, upd, min(slot, S - 1))
        assert bool(jnp.array_equal(got, ref)), slot


def test_subset_softmax_ref():
    logits = jnp.asarray([[1.0, 2.0, -1e30, 0.0]])
    ids, lp = subset_softmax_topk_ref(logits, 2)
    assert ids[0, 0] == 1 and ids[0, 1] == 0
    # normalized over the valid subset only
    np.testing.assert_allclose(float(jnp.exp(lp).sum()),
                               np.exp(lp[0, 0]).item() + np.exp(lp[0, 1]).item(),
                               atol=1e-6)


@pytest.mark.parametrize("B,nc,Q,H,P,G,N", [
    (2, 3, 16, 4, 8, 1, 16),
    (1, 2, 32, 4, 16, 2, 8),
    (1, 1, 64, 2, 32, 1, 32),
])
def test_ssd_intra_kernel_sweep(B, nc, Q, H, P, G, N):
    """SSD intra-chunk dual kernel vs oracle across shapes/groups."""
    from repro.kernels.ssd import ssd_intra_pallas, ssd_intra_ref
    rng = np.random.default_rng(B * nc * Q + H)
    xw = jnp.asarray(rng.standard_normal((B, nc, Q, H, P)), jnp.float32)
    Bm = jnp.asarray(rng.standard_normal((B, nc, Q, G, N)), jnp.float32)
    Cm = jnp.asarray(rng.standard_normal((B, nc, Q, G, N)), jnp.float32)
    l = jnp.asarray(-np.abs(np.cumsum(
        rng.uniform(0.01, 0.2, (B, nc, Q, H)), axis=2)), jnp.float32)
    y, S = ssd_intra_pallas(xw, Bm, Cm, l, n_groups=G)
    yr, Sr = ssd_intra_ref(xw, Bm, Cm, l)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(S), np.asarray(Sr),
                               atol=1e-4, rtol=1e-4)


def test_ssd_kernel_plus_scan_equals_ssd_chunked():
    """Kernel intra terms + the inter-chunk lax.scan must reproduce the
    full ssd_chunked output (the layer's oracle)."""
    from repro.kernels.ssd import ssd_intra_pallas
    from repro.layers.ssm import ssd_chunked
    rng = np.random.default_rng(0)
    B, T, H, P, G, N, chunk = 2, 48, 4, 8, 1, 16, 16
    x = jnp.asarray(rng.standard_normal((B, T, H, P)), jnp.float32)
    Bm = jnp.asarray(rng.standard_normal((B, T, G, N)), jnp.float32)
    Cm = jnp.asarray(rng.standard_normal((B, T, G, N)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (B, T, H)), jnp.float32)
    A_log = jnp.asarray(np.log(rng.uniform(0.5, 4.0, (H,))), jnp.float32)
    D = jnp.asarray(rng.standard_normal((H,)), jnp.float32)
    y_ref, h_ref = ssd_chunked(x, Bm, Cm, dt, A_log, D, chunk)

    # recompose: kernel intra + python inter-chunk recurrence
    nc = T // chunk
    A = -jnp.exp(A_log)
    dA = (dt * A).reshape(B, nc, chunk, H)
    l = jnp.cumsum(dA, axis=2)
    xw = (x * dt[..., None]).reshape(B, nc, chunk, H, P)
    Bc = Bm.reshape(B, nc, chunk, G, N)
    Cc = Cm.reshape(B, nc, chunk, G, N)
    y_intra, S = ssd_intra_pallas(xw, Bc, Cc, l, n_groups=G)
    Ch = jnp.repeat(Cc, H // G, axis=3)
    a_chunk = jnp.exp(l[:, :, -1, :])
    Hst = jnp.zeros((B, H, P, N))
    y = np.asarray(y_intra).copy()
    for c in range(nc):
        y[:, c] += np.asarray(jnp.einsum(
            "bqh,bqhn,bhpn->bqhp", jnp.exp(l[:, c]), Ch[:, c], Hst))
        Hst = Hst * a_chunk[:, c][:, :, None, None] + jnp.moveaxis(
            S[:, c], -2, -1)
    y = y.reshape(B, T, H, P) + np.asarray(x * D[None, None, :, None])
    np.testing.assert_allclose(y, np.asarray(y_ref), atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(Hst), np.asarray(h_ref),
                               atol=1e-3, rtol=1e-3)


def test_interpret_mode_follows_backend(monkeypatch):
    """One place picks the Pallas mode: interpreted off the TPU, compiled
    on it, and an explicit override wins either way."""
    from repro.kernels import mode
    assert mode.interpret_mode() is (jax.default_backend() != "tpu")
    monkeypatch.setattr(mode.jax, "default_backend", lambda: "tpu")
    assert mode.interpret_mode() is False
    assert mode.interpret_mode(True) is True
    monkeypatch.setattr(mode.jax, "default_backend", lambda: "cpu")
    assert mode.interpret_mode() is True
    assert mode.interpret_mode(False) is False


def test_bfloat16_tiles_keep_float32_accuracy():
    """A float32 query against bfloat16 tiles: the kernel meets the tile
    with the query's three bfloat16 terms, so its logits are those of the
    exact product with the rounded weights to float32's accuracy, and the
    layers' product (``wdot``), which splits through the same helper,
    gives the same logits. One bfloat16 rounding of the query would be off
    by ~2⁻⁹ of each product's terms, far outside the tolerance."""
    from repro.layers.attention import wdot
    rng = np.random.default_rng(5)
    n_blk, d, B, K = 6, 256, 3, 4
    W = jnp.asarray(rng.standard_normal((n_blk, 128, d)), jnp.bfloat16)
    bb = jnp.zeros((n_blk, 128), jnp.float32)
    h = jnp.asarray(rng.standard_normal((B, d)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, n_blk, (B, K)), jnp.int32)
    out = np.asarray(screened_logits_pallas(W, bb, h, ids), np.float64)
    w64 = np.asarray(W, np.float64)[np.asarray(ids)]          # (B, K, 128, d)
    exact = np.einsum("bkvd,bd->bkv", w64, np.asarray(h, np.float64))
    scale = np.sqrt(d)
    np.testing.assert_allclose(out, exact, atol=1e-5 * scale, rtol=0)
    rounded = np.einsum("bkvd,bd->bkv", w64,
                        np.asarray(h.astype(jnp.bfloat16), np.float64))
    assert np.max(np.abs(rounded - exact)) > 1e-3 * scale
    layers = np.stack([np.asarray(wdot("d,vd->v", h[i], W[ids[i, k]]))
                       for i in range(B) for k in range(K)])
    np.testing.assert_allclose(out.reshape(B * K, 128), layers,
                               atol=1e-5 * scale, rtol=0)
