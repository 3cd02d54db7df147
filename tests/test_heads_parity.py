"""Parametrized parity suite: every registered head must return the exact
softmax top-k on a fixture where its candidate set provably contains the
true top-k.

Exactness configs per backend (candidate pool = full vocabulary):
  screened / screened-cpu  all-ones candidate mask
  screened-pallas          all-blocks mask, L % 128 != 0 (padding path)
  exact-sharded            vocab-sharded exact (default mesh = all devices)
  screened-sharded         vocab-sharded L2S, same all-ones mask
  svd                      full rank + rerank pool = L
  shortlist                n_head = L (head covers the vocab, no tails)
  greedy-mips              budget = L · min(d, 32) → per-dim lists cover L
  lsh-mips                 bits = 0 → one bucket holding the whole database
  pca-mips                 depth = 0 → a single leaf holding the database

The SHARDED parity matrix below additionally pins the sharded heads to
{1, 2, 8} shards (2/8 need the 8-device harness from conftest) on a vocab
NOT divisible by the shard count (padding path), with k both below and above
L/n_shards, asserting ids bit-identical to the unsharded counterparts.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import heads
from repro.core.screening import ScreenParams, candidates_to_padded

L, D, R, N, K = 200, 32, 4, 16, 5


@pytest.fixture(scope="module")
def fixture():
    rng = np.random.default_rng(0)
    W = jnp.asarray(rng.standard_normal((L, D)), jnp.float32)
    b = jnp.asarray(rng.standard_normal(L) * 0.1, jnp.float32)
    h = jnp.asarray(rng.standard_normal((N, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((R, D)), jnp.float32)

    mask = np.ones((R, L), bool)                       # full-coverage screen
    idx, lens = candidates_to_padded(mask, L)
    screen = ScreenParams(v=v, cand_idx=jnp.asarray(idx),
                          cand_len=jnp.asarray(lens), vocab_size=L)

    n_blk = -(-L // 128)                               # block screen, L%128≠0
    assert L % 128 != 0
    maskb = np.ones((R, n_blk), bool)
    idxb, lensb = candidates_to_padded(maskb, L, block=128)
    screen_blk = ScreenParams(v=v, cand_idx=jnp.asarray(idxb),
                              cand_len=jnp.asarray(lensb), vocab_size=L,
                              block=128)

    exact_ids, exact_vals = heads.get("exact", W=W, b=b).topk(h, K)
    return dict(W=W, b=b, h=h, screen=screen, screen_blk=screen_blk,
                exact_ids=np.asarray(exact_ids))


# (registry name, exactness kwargs, which screen the head needs)
CASES = [
    ("exact", {}, None),
    ("exact-sharded", {}, None),
    ("screened", {}, "screen"),
    ("screened-sharded", {}, "screen"),
    ("screened-cpu", {}, "screen"),
    ("screened-pallas", {}, "screen_blk"),
    ("adaptive", dict(shortlist=L), None),            # no tails → exact
    ("adaptive-sharded", dict(shortlist=L), None),
    ("svd", dict(rho=D, n_top=L), None),
    ("shortlist", dict(n_head=L), None),
    ("greedy-mips", dict(budget=L * 32), None),
    ("lsh-mips", dict(bands=2, bits=0), None),
    ("pca-mips", dict(depth=0), None),
]

# shard counts for the sharded parity matrix; >1 needs the 8-device harness
SHARD_COUNTS = [1,
                pytest.param(2, marks=pytest.mark.multidevice),
                pytest.param(8, marks=pytest.mark.multidevice)]


def _require_devices(n):
    if jax.device_count() < n:
        pytest.skip(f"needs {n} devices (have {jax.device_count()})")


def _build(fixture, name, kw, screen_key):
    ctx = dict(W=fixture["W"], b=fixture["b"], **kw)
    if screen_key is not None:
        ctx["screen"] = fixture[screen_key]
    return heads.get(name, **ctx)


def test_registry_covers_required_backends():
    names = heads.names()
    for required in ["exact", "exact-sharded", "screened",
                     "screened-sharded", "screened-pallas", "adaptive",
                     "adaptive-sharded", "svd", "shortlist", "greedy-mips",
                     "lsh-mips", "pca-mips"]:
        assert required in names, names
    assert len(names) >= 6
    assert {name for name, _, _ in CASES} == set(names), \
        "parity suite must cover every registered head"


@pytest.mark.parametrize("name,kw,screen_key", CASES,
                         ids=[c[0] for c in CASES])
def test_topk_parity_with_exact(fixture, name, kw, screen_key):
    head = _build(fixture, name, kw, screen_key)
    ids, vals = head.topk(fixture["h"], K)
    ids = np.asarray(ids)
    exact = fixture["exact_ids"]
    assert ids.shape == (N, K)
    # identical top-k sets, identical argmax
    for i in range(N):
        assert set(ids[i].tolist()) == set(exact[i].tolist()), (name, i)
    np.testing.assert_array_equal(ids[:, 0], exact[:, 0])
    # scores finite (no sentinel −inf leaked into a full-coverage top-k)
    assert np.all(np.asarray(vals, np.float32) > -1e29)


@pytest.mark.parametrize("name,kw,screen_key", CASES,
                         ids=[c[0] for c in CASES])
def test_next_and_logprobs_consistent(fixture, name, kw, screen_key):
    head = _build(fixture, name, kw, screen_key)
    nxt = np.asarray(head.next(fixture["h"]))
    np.testing.assert_array_equal(nxt, fixture["exact_ids"][:, 0])
    ids, lp = head.topk_logprobs(fixture["h"], K)
    lp = np.asarray(lp, np.float32)
    assert np.all(lp <= 1e-6)                      # log-probs
    assert np.all(np.diff(lp, axis=1) <= 1e-6)     # sorted descending
    np.testing.assert_array_equal(np.asarray(ids)[:, 0],
                                  fixture["exact_ids"][:, 0])


@pytest.mark.parametrize("name,kw,screen_key",
                         [c for c in CASES if c[0] != "exact"],
                         ids=[c[0] for c in CASES if c[0] != "exact"])
def test_sample_stays_in_vocab_and_greedy_at_t0(fixture, name, kw, screen_key):
    head = _build(fixture, name, kw, screen_key)
    s = np.asarray(head.sample(jax.random.key(0), fixture["h"],
                               temperature=1.0))
    assert s.shape == (N,) and s.min() >= 0 and s.max() < L
    g = np.asarray(head.sample(jax.random.key(1), fixture["h"],
                               temperature=0.0))
    np.testing.assert_array_equal(g, fixture["exact_ids"][:, 0])


def test_sample_nucleus_truncation_and_variance(fixture):
    """sample_from_logits contract through a head: a vanishing nucleus
    (top_p → 0) degenerates to argmax at any temperature, and high
    temperature actually varies across keys."""
    head = heads.get("exact", W=fixture["W"], b=fixture["b"])
    h = fixture["h"]
    tight = np.asarray(head.sample(jax.random.key(0), h, temperature=1.0,
                                   top_p=1e-6))
    np.testing.assert_array_equal(tight, fixture["exact_ids"][:, 0])
    a = np.asarray(head.sample(jax.random.key(3), h, temperature=5.0))
    c = np.asarray(head.sample(jax.random.key(4), h, temperature=5.0))
    assert not np.array_equal(a, c)
    # top_p keeps high-probability tokens reachable: with top_p=0.9 every
    # draw is inside the full vocab and argmax is still drawable
    s = np.asarray(head.sample(jax.random.key(5), h, temperature=1.0,
                               top_p=0.9))
    assert s.min() >= 0 and s.max() < L


def test_baseline_small_vocab_pool_and_empty_bucket():
    """Regression: norm_pool > n_head must not crash shortlist logprobs /
    sampling, and an empty LSH bucket must not leak the sentinel id."""
    rng = np.random.default_rng(5)
    W = jnp.asarray(rng.standard_normal((L, D)), jnp.float32)
    b = jnp.zeros((L,), jnp.float32)
    h = jnp.asarray(rng.standard_normal((N, D)), jnp.float32)
    short = heads.get("shortlist", W=W, b=b)       # default n_head = L//10
    ids, lp = short.topk_logprobs(h, K)            # pool(64) > n_head(20)
    assert ids.shape == (N, K)
    s = short.sample(jax.random.key(0), h, temperature=1.0)
    assert s.min() >= 0 and s.max() < L
    # many-bit LSH on a tiny vocab → most buckets empty
    lsh = heads.get("lsh-mips", W=W, b=b, bands=2, bits=10)
    nxt = np.asarray(lsh.next(h))
    assert nxt.min() >= 0 and nxt.max() < L        # never the sentinel
    ids, lp = lsh.topk_logprobs(h, K)
    lp = np.asarray(lp)
    sentinel = np.asarray(ids) >= L
    assert np.all(lp[sentinel] <= -1e29)           # no mass on missing words


def test_metadata_present():
    fix_rng = np.random.default_rng(1)
    W = jnp.asarray(fix_rng.standard_normal((64, 8)), jnp.float32)
    b = jnp.zeros((64,), jnp.float32)
    head = heads.get("exact", W=W, b=b)
    d = head.describe()
    assert d["name"] == "exact" and d["is_jittable"] is True
    assert d["flops_per_query"] == 64 * 8
    svd = heads.get("svd", W=W, b=b, rho=4, n_top=16)
    assert svd.device_kind == "numpy" and svd.is_jittable is False
    assert np.isfinite(svd.flops_per_query)


# -- sharded parity matrix ---------------------------------------------------
# vocab 203 is NOT divisible by 2 or 8 (padding path); k=40 exceeds
# L/8 = 26 (local top-k truncation + merge padding path)

LS = 203


@pytest.fixture(scope="module")
def sharded_fixture():
    rng = np.random.default_rng(7)
    W = jnp.asarray(rng.standard_normal((LS, D)), jnp.float32)
    b = jnp.asarray(rng.standard_normal(LS) * 0.1, jnp.float32)
    h = jnp.asarray(rng.standard_normal((N, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((R, D)), jnp.float32)
    mask = rng.random((R, LS)) < 0.5            # non-trivial candidate sets
    mask[:, 0] = True
    idx, lens = candidates_to_padded(mask, LS)
    screen = ScreenParams(v=v, cand_idx=jnp.asarray(idx),
                          cand_len=jnp.asarray(lens), vocab_size=LS)
    return dict(W=W, b=b, h=h, screen=screen,
                exact=heads.get("exact", W=W, b=b),
                screened=heads.get("screened", W=W, b=b, screen=screen))


@pytest.mark.parametrize("n_shards", SHARD_COUNTS)
@pytest.mark.parametrize("k", [K, 40, 120])
def test_exact_sharded_bit_identical(sharded_fixture, n_shards, k):
    """exact-sharded == exact: ids bit-identical, scores/logprobs equal to
    float tolerance, at every shard count, k above and below L/n_shards.

    Scores are not bit-identical: each shard's (B, L/n)·(L/n, D) matmul may
    be blocked differently by XLA than the one (B, L)·(L, D) matmul, so
    the D-term dot products round in a different order. The bound on that
    is float32's summation error, D·2⁻²⁴·Σ|hᵢ||wᵢ| per logit (twice it
    between two orders); the ids stay exactly equal."""
    _require_devices(n_shards)
    fx = sharded_fixture
    head = heads.get("exact-sharded", W=fx["W"], b=fx["b"],
                     n_shards=n_shards)
    assert head.n_shards == n_shards
    eids, evals = fx["exact"].topk(fx["h"], k)
    ids, vals = head.topk(fx["h"], k)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(eids))
    mag = np.abs(np.asarray(fx["h"])) @ np.abs(np.asarray(fx["W"])).T
    bound = 2 * D * 2.0 ** -24 * np.take_along_axis(mag, np.asarray(eids),
                                                    axis=-1)
    assert np.all(np.abs(np.asarray(vals) - np.asarray(evals)) <= bound)
    elids, elp = fx["exact"].topk_logprobs(fx["h"], k)
    lids, lp = head.topk_logprobs(fx["h"], k)
    np.testing.assert_array_equal(np.asarray(lids), np.asarray(elids))
    np.testing.assert_allclose(np.asarray(lp), np.asarray(elp), atol=1e-5)
    # greedy + temperature-0 sampling agree with exact argmax
    np.testing.assert_array_equal(np.asarray(head.next(fx["h"])),
                                  np.asarray(eids)[:, 0])
    t0 = head.sample(jax.random.key(0), fx["h"], temperature=0.0)
    np.testing.assert_array_equal(np.asarray(t0), np.asarray(eids)[:, 0])


@pytest.mark.parametrize("n_shards", SHARD_COUNTS)
@pytest.mark.parametrize("k", [K, 40])
def test_screened_sharded_matches_screened(sharded_fixture, n_shards, k):
    """screened-sharded == screened on ids AND logprobs with a non-trivial
    screen: candidate slabs split by owning shard, including k larger than
    any single shard's candidate count (gather shorter than k → sentinel
    padding, exactly like the unsharded candidate-set sentinel)."""
    _require_devices(n_shards)
    fx = sharded_fixture
    head = heads.get("screened-sharded", W=fx["W"], b=fx["b"],
                     screen=fx["screen"], n_shards=n_shards)
    assert head.n_shards == n_shards
    sids, svals = fx["screened"].topk(fx["h"], k)
    ids, vals = head.topk(fx["h"], k)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(sids))
    np.testing.assert_allclose(np.asarray(vals), np.asarray(svals),
                               rtol=1e-5, atol=1e-5)
    slids, slp = fx["screened"].topk_logprobs(fx["h"], k)
    lids, lp = head.topk_logprobs(fx["h"], k)
    np.testing.assert_array_equal(np.asarray(lids), np.asarray(slids))
    np.testing.assert_allclose(np.asarray(lp), np.asarray(slp), atol=1e-5)
    # sampling stays inside the routed candidate set
    s = np.asarray(head.sample(jax.random.key(1), fx["h"], temperature=1.0))
    assert s.min() >= 0 and s.max() < LS
    t0 = head.sample(jax.random.key(2), fx["h"], temperature=0.0)
    np.testing.assert_array_equal(np.asarray(t0), np.asarray(sids)[:, 0])


@pytest.mark.parametrize("n_shards", SHARD_COUNTS)
@pytest.mark.parametrize("k", [K, 40, 120])
def test_adaptive_sharded_matches_adaptive(sharded_fixture, n_shards, k):
    """adaptive-sharded == adaptive bit-for-bit on ids at every shard count:
    shortlist=50 splits 203 words into a 1-block short tier + 3 tail
    clusters whose widths (51) are NOT V_BLK- or shard-divisible (padding
    path), counts=None exercises the deterministic weight-norm fallback,
    and k=120 exceeds the short-list capacity (every query must descend)
    AND any single tier's valid words (sentinel-padding path)."""
    _require_devices(n_shards)
    fx = sharded_fixture
    ad = heads.get("adaptive", W=fx["W"], b=fx["b"], shortlist=50, n_tails=3)
    sh = heads.get("adaptive-sharded", W=fx["W"], b=fx["b"], shortlist=50,
                   n_tails=3, n_shards=n_shards)
    aids, avals = ad.topk(fx["h"], k)
    ids, vals = sh.topk(fx["h"], k)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(aids))
    np.testing.assert_allclose(np.asarray(vals), np.asarray(avals),
                               rtol=1e-6, atol=1e-6)
    alids, alp = ad.topk_logprobs(fx["h"], k)
    lids, lp = sh.topk_logprobs(fx["h"], k)
    lp = np.asarray(lp, np.float32)
    np.testing.assert_array_equal(np.asarray(lids), np.asarray(alids))
    np.testing.assert_allclose(lp, np.asarray(alp, np.float32), atol=1e-5)
    assert not np.any(np.isnan(lp))                # sentinel rows stay −inf
    # greedy + temperature-0 sampling agree across the shard counts
    np.testing.assert_array_equal(np.asarray(sh.next(fx["h"])),
                                  np.asarray(ad.next(fx["h"])))
    t0 = sh.sample(jax.random.key(0), fx["h"], temperature=0.0)
    np.testing.assert_array_equal(np.asarray(t0),
                                  np.asarray(ad.next(fx["h"])))


def test_adaptive_short_tier_materializes_no_full_vocab_buffer(
        sharded_fixture):
    """ISSUE 7 HLO-cost satellite: the fused adaptive path must never
    materialize a full-vocab (or full packed-tier) f32 logit buffer — only
    the per-tier O(k) results reach HBM. The unfused escape hatch DOES
    materialize its packed short-tier row, which keeps this probe from
    being vacuously true."""
    from repro.heads.adaptive import (_fused_short_topk, _fused_tiered_topk,
                                      _unfused_short_topk)
    from repro.launch.hlo_cost import materializes_f32_buffer
    fx = sharded_fixture
    ad = heads.get("adaptive", W=fx["W"], b=fx["b"], shortlist=50, n_tails=3)
    args = (ad._Wb, ad._bb, ad._gid, ad._short_blocks, ad._tail_tab,
            ad._g, ad._gb, fx["h"])
    text = _fused_tiered_topk.lower(*args, k=K, L=LS) \
        .compile().as_text()
    n_blk = ad._Wb.shape[0]
    assert not materializes_f32_buffer(text, N, LS)
    assert not materializes_f32_buffer(text, N, n_blk * 128)
    # anti-vacuity pair on the no-tails geometry: unfused materializes the
    # (N, n_blk·V_BLK) packed logit row, fused must not
    full = heads.get("adaptive", W=fx["W"], b=fx["b"], shortlist=LS)
    fargs = (full._Wb, full._bb, full._gid, full._short_blocks, fx["h"])
    utext = _unfused_short_topk.lower(*fargs, k=K, L=LS) \
        .compile().as_text()
    ftext = _fused_short_topk.lower(*fargs, k=K, L=LS) \
        .compile().as_text()
    nb = full._Wb.shape[0]
    assert materializes_f32_buffer(utext, N, nb * 128)
    assert not materializes_f32_buffer(ftext, N, nb * 128)


# -- empty-candidate-row convention (ISSUE 7 satellite) ----------------------
# Heads that can route a query to an EMPTY candidate set must report
# log-probability NEG_INF (probability 0) with sentinel ids — never NaN and
# never a fake uniform distribution from log-softmax'ing an all-−inf row.

EMPTY_ROW_CAPABLE = {"screened", "screened-cpu", "screened-sharded",
                     "screened-pallas"}


def _empty_row_fixture():
    """2-cluster screen where cluster 0 has NO candidates; queries with
    h[:, 0] = +5 route there, queries with h[:, 0] = −5 route to the
    full-coverage cluster 1."""
    rng = np.random.default_rng(11)
    Le, d, n = 96, 16, 8
    W = jnp.asarray(rng.standard_normal((Le, d)), jnp.float32)
    b = jnp.asarray(rng.standard_normal(Le) * 0.1, jnp.float32)
    h = np.asarray(rng.standard_normal((n, d)), np.float32)
    h[:n // 2, 0] = 5.0
    h[n // 2:, 0] = -5.0
    v = np.zeros((2, d), np.float32)
    v[0, 0], v[1, 0] = 1.0, -1.0
    mask = np.zeros((2, Le), bool)
    mask[1] = True                                 # cluster 0 stays EMPTY
    idx, lens = candidates_to_padded(mask, Le)
    screen = ScreenParams(v=jnp.asarray(v), cand_idx=jnp.asarray(idx),
                          cand_len=jnp.asarray(lens), vocab_size=Le)
    maskb = np.zeros((2, 1), bool)                 # 96 words → 1 block
    maskb[1] = True
    idxb, lensb = candidates_to_padded(maskb, Le, block=128)
    screen_blk = ScreenParams(v=jnp.asarray(v), cand_idx=jnp.asarray(idxb),
                              cand_len=jnp.asarray(lensb), vocab_size=Le,
                              block=128)
    return Le, W, b, jnp.asarray(h), screen, screen_blk


def _empty_row_head(name, Le, W, b, screen, screen_blk, **extra):
    kw = dict(W=W, b=b, **extra)
    if name == "screened-pallas":
        kw["screen"] = screen_blk
    elif name.startswith("screened"):
        kw["screen"] = screen
    if name.endswith("-sharded"):
        kw["n_shards"] = 1
    if name.startswith("adaptive"):
        kw.update(shortlist=32, n_tails=2)
    return heads.get(name, **kw)


@pytest.mark.parametrize("name", sorted(heads.names()))
def test_empty_candidate_rows_are_neg_inf_never_nan(name):
    """EVERY registered head: topk_logprobs yields finite-or-NEG_INF
    log-probs (no NaN, nothing > 0); heads that can produce an empty
    candidate row additionally report NEG_INF + sentinel ids on exactly
    the rows routed to the empty cluster. Pre-fix, `screened` handed
    empty rows a fake uniform distribution (log_softmax of all-−inf)."""
    from repro.heads.base import NEG_INF
    Le, W, b, h, screen, screen_blk = _empty_row_fixture()
    head = _empty_row_head(name, Le, W, b, screen, screen_blk)
    ids, lp = head.topk_logprobs(h, 5)
    ids, lp = np.asarray(ids), np.asarray(lp, np.float32)
    assert not np.any(np.isnan(lp)), name
    assert np.all(lp <= 1e-6), name
    if name in EMPTY_ROW_CAPABLE:
        assert np.all(lp[:4] <= NEG_INF / 2), (name, lp[:4])
        assert np.all(ids[:4] >= Le), (name, ids[:4])
        assert np.all(lp[4:, 0] > NEG_INF / 2), name   # full cluster is live


def test_empty_candidate_rows_unfused_pallas_variant():
    """The screened-pallas jnp escape hatch (fused=False) shares the fused
    kernel's empty-row contract."""
    from repro.heads.base import NEG_INF
    Le, W, b, h, screen, screen_blk = _empty_row_fixture()
    head = heads.get("screened-pallas", W=W, b=b, screen=screen_blk,
                     fused=False)
    ids, lp = head.topk_logprobs(h, 5)
    lp = np.asarray(lp, np.float32)
    assert not np.any(np.isnan(lp))
    assert np.all(lp[:4] <= NEG_INF / 2)
    assert np.all(np.asarray(ids)[:4] >= Le)


@pytest.mark.multidevice
def test_sharded_weights_actually_partitioned(sharded_fixture, multidevice):
    """prepare() placement: each device holds 1/n of the padded vocab rows,
    not a replica — the memory-scaling claim the head exists for."""
    fx = sharded_fixture
    head = heads.get("exact-sharded", W=fx["W"], b=fx["b"], n_shards=8)
    Lp = head.Wp.shape[0]
    assert Lp % 8 == 0 and Lp >= LS
    shard_rows = {s.data.shape[0] for s in head.Wp.addressable_shards}
    assert shard_rows == {Lp // 8}
    assert len(head.Wp.sharding.device_set) == 8
    scr = heads.get("screened-sharded", W=fx["W"], b=fx["b"],
                    screen=fx["screen"], n_shards=8)
    assert {s.data.shape[0] for s in scr.cand_local.addressable_shards} == {1}


def test_top_p_tie_regression():
    """Nucleus sampling with duplicated logits must not keep every position
    tied with the cutoff: logits [2,2,2,-10,...] at top_p=0.5 keep exactly
    the first TWO duplicates (rank mask), never the third."""
    logits = np.full((1, 8), -10.0, np.float32)
    logits[0, :3] = 2.0
    from repro.heads.base import sample_from_logits
    seen = set()
    for i in range(64):
        s = sample_from_logits(jax.random.key(i), jnp.asarray(logits),
                               temperature=1.0, top_p=0.5)
        seen.add(int(s[0]))
    assert seen == {0, 1}, seen


def test_screen_params_is_pytree():
    """ScreenParams flattens/unflattens and crosses jit boundaries as an
    argument (not a closure constant)."""
    rng = np.random.default_rng(2)
    v = jnp.asarray(rng.standard_normal((3, 8)), jnp.float32)
    sp = ScreenParams(v=v, cand_idx=jnp.zeros((3, 8), jnp.int32),
                      cand_len=jnp.ones((3,), jnp.int32), vocab_size=40,
                      block=1)
    leaves, treedef = jax.tree_util.tree_flatten(sp)
    assert len(leaves) == 3
    sp2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert sp2.vocab_size == 40 and sp2.block == 1

    @jax.jit
    def through_jit(screen, h):
        return jnp.einsum("bd,rd->br", h, screen.v)

    h = jnp.asarray(rng.standard_normal((2, 8)), jnp.float32)
    out = through_jit(sp, h)
    assert out.shape == (2, 3)
