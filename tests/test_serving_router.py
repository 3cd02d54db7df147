"""Request-centric serving API: routing policies, mixed-traffic serve_batch
bit-parity against solo generate, the LRU step cache, the LSTM branch of
beam-search cache reordering, and the launcher's typed missing-screen exit.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import heads
from repro.configs import L2SConfig, TrainConfig, get_config
from repro.core import collect_contexts, fit_l2s
from repro.data import ZipfMarkovCorpus, make_lm_batches
from repro.heads import MissingScreenError
from repro.heads.screened import ScreenedHead
from repro.launch.steps import make_train_step
from repro.models import build_model
from repro.optim import adamw_init
from repro.serving import (CostAwarePolicy, DecodeEngine, ServeRequest,
                           StaticPolicy, TierPolicy, route_requests)


@pytest.fixture(scope="module")
def trained():
    """Small trained LSTM + fitted screen shared by the serving tests."""
    cfg = get_config("ptb-small-lstm").reduced()
    m = build_model(cfg)
    params = m.init(jax.random.key(0), dtype=jnp.float32)
    corpus = ZipfMarkovCorpus(cfg.vocab_size, branching=32, seed=3)
    tcfg = TrainConfig(lr=2e-3, total_steps=60, warmup_steps=5,
                       remat="none", loss_chunk=None)
    step = jax.jit(make_train_step(m, tcfg))
    opt = adamw_init(params)
    for batch in make_lm_batches(corpus, 60, 8, 32, seed=1):
        params, opt, _ = step(params, opt,
                              {k: jnp.asarray(v) for k, v in batch.items()})
    H, y = collect_contexts(
        m, params, [jnp.asarray(b["tokens"])
                    for b in make_lm_batches(corpus, 8, 8, 32, seed=9)],
        max_vectors=2000)
    st = fit_l2s(H, y, cfg.vocab_size,
                 L2SConfig(num_clusters=16, budget=64, outer_iters=1,
                           sgd_steps=50))
    return cfg, m, params, corpus, st


def _req(prompt_len=6, **kw):
    rng = np.random.default_rng(kw.pop("rng_seed", 0))
    return ServeRequest(prompt=rng.integers(0, 50, prompt_len), max_new=4,
                        **kw)


# -- policies (pure request→name logic over a synthetic catalog) -------------

CATALOG = {
    "exact": {"flops_per_query": 1e6, "memory_bytes": 4_000_000,
              "n_shards": None, "supports_sampling": True},
    "screened": {"flops_per_query": 5e4, "memory_bytes": 4_400_000,
                 "n_shards": None, "supports_sampling": True},
    "screened-sharded": {"flops_per_query": 2e4, "memory_bytes": 4_400_000,
                         "n_shards": 8, "supports_sampling": True},
    "svd": {"flops_per_query": 3e5, "memory_bytes": 5_000_000,
            "n_shards": None, "supports_sampling": False},
}


def test_static_and_tier_policies():
    assert StaticPolicy("svd").route(_req(), CATALOG) == "svd"
    tp = TierPolicy({"realtime": "screened", "batch": "exact"},
                    default="svd")
    assert tp.route(_req(latency_tier="realtime"), CATALOG) == "screened"
    assert tp.route(_req(latency_tier="batch"), CATALOG) == "exact"
    assert tp.route(_req(latency_tier="unheard-of"), CATALOG) == "svd"
    assert set(tp.candidates) == {"screened", "exact", "svd"}


def test_cost_aware_policy_constraints():
    pol = CostAwarePolicy(["screened-sharded", "screened", "svd", "exact"])
    # cheapest eligible head wins
    assert pol.route(_req(), CATALOG) == "screened-sharded"
    # accuracy floor 1.0 → only exact-accuracy heads survive
    assert pol.route(_req(accuracy_floor=1.0), CATALOG) == "exact"
    # wide k demands exact accuracy too (approximate candidate lists may
    # not contain k valid words)
    assert pol.route(_req(k=64), CATALOG) == "exact"
    # sampled requests never route to a non-sampling head
    pol_svd = CostAwarePolicy(["svd"], fallback="exact")
    assert pol_svd.route(_req(), CATALOG) == "svd"
    assert pol_svd.route(_req(temperature=0.8), CATALOG) == "exact"
    # "batch" tier is quality-first among eligible heads
    assert pol.route(_req(latency_tier="batch"), CATALOG) == "exact"


def test_cost_aware_memory_budget_prefers_sharded():
    """A per-device memory budget below the full table size leaves only the
    sharded variant standing — the routing move that sends big-vocab /
    memory-pressured traffic multi-device."""
    pol = CostAwarePolicy(["screened", "screened-sharded"],
                          memory_budget_bytes=1_000_000)
    assert pol.route(_req(), CATALOG) == "screened-sharded"
    roomy = CostAwarePolicy(["screened", "screened-sharded"],
                            memory_budget_bytes=10_000_000)
    # with room everywhere, plain cost ordering resumes
    assert roomy.route(_req(), CATALOG) == "screened-sharded"
    # candidates missing from the catalog are skipped, fallback otherwise
    none_fit = CostAwarePolicy(["screened"], memory_budget_bytes=1)
    assert none_fit.route(_req(), CATALOG) == "exact"


def test_cost_aware_nan_cost_is_ineligible_for_ranking():
    """ISSUE 7 NaN-cost regression: a head whose flops_per_query is NaN
    (documented "unmodeled") must not participate in cost ranking at all.
    Pre-fix, NaN mapped to inf and the decision fell through to the BYTES
    tie-break — an unmodeled head could win or lose on a number that is
    meaningless without a flops model to tie on."""
    cat = dict(CATALOG)
    cat["stub-a"] = {"flops_per_query": float("nan"), "bytes_per_query": 9e9,
                     "memory_bytes": 1, "n_shards": None,
                     "supports_sampling": True}
    cat["stub-b"] = {"flops_per_query": float("nan"), "bytes_per_query": 1.0,
                     "memory_bytes": 1, "n_shards": None,
                     "supports_sampling": True}
    # a modeled head beats ANY unmodeled head, even one with tiny bytes
    pol = CostAwarePolicy(["stub-a", "stub-b", "screened"],
                          accuracy={"stub-a": 0.99, "stub-b": 0.99})
    assert pol.route(_req(), cat) == "screened"
    # every eligible head unmodeled → candidate (tier) order decides;
    # pre-fix the bytes tie-break picked stub-b
    pol2 = CostAwarePolicy(["stub-a", "stub-b"], fallback="stub-b",
                           accuracy={"stub-a": 0.99, "stub-b": 0.99})
    assert pol2.route(_req(), cat) == "stub-a"


def test_accuracy_floor_one_requires_provably_exact_head():
    """ISSUE 7 floor-1.0 regression: accuracy_floor == 1.0 is satisfiable
    ONLY by the exact-by-construction heads (EXACT_HEADS membership), never
    by a MEASURED agreement estimate that rounds to float 1.0."""
    # a measured 1.0 for an approximate head must not promote it
    pol = CostAwarePolicy(["screened", "exact"], accuracy={"screened": 1.0})
    assert pol.route(_req(accuracy_floor=1.0), CATALOG) == "exact"
    # a floor computed as 1.0 − ε rounds back to exactly 1.0 in float —
    # the sentinel has to catch that too
    eps_floor = 1.0 - 1e-17
    assert eps_floor == 1.0
    assert pol.route(_req(accuracy_floor=eps_floor), CATALOG) == "exact"
    # the wide-k promotion raises the floor through the same sentinel
    assert pol.route(_req(k=64), CATALOG) == "exact"
    # both exact-by-construction heads satisfy the floor
    cat = dict(CATALOG)
    cat["exact-sharded"] = {"flops_per_query": 2e5,
                            "memory_bytes": 4_000_000, "n_shards": 8,
                            "supports_sampling": True}
    shard_pol = CostAwarePolicy(["exact-sharded"], fallback="exact")
    assert shard_pol.route(_req(accuracy_floor=1.0), cat) == "exact-sharded"


def test_cost_aware_routes_zipfian_traffic_to_adaptive():
    """ISSUE 7 acceptance: on a Zipfian unigram the adaptive head's
    tier-weighted cost model undercuts a dense 0.5-density screen, so
    CostAwarePolicy routes standard traffic onto it — and an accuracy floor
    above its nominal 0.98 falls back to the screened head."""
    from repro.core.screening import ScreenParams, candidates_to_padded
    rng = np.random.default_rng(13)
    Lz, d, r = 600, 32, 4
    W = jnp.asarray(rng.standard_normal((Lz, d)), jnp.float32)
    b = jnp.zeros((Lz,), jnp.float32)
    v = jnp.asarray(rng.standard_normal((r, d)), jnp.float32)
    mask = rng.random((r, Lz)) < 0.5            # dense screen: ~300 words
    idx, lens = candidates_to_padded(mask, Lz)
    screen = ScreenParams(v=v, cand_idx=jnp.asarray(idx),
                          cand_len=jnp.asarray(lens), vocab_size=Lz)
    counts = rng.permutation(1e6 / np.arange(1, Lz + 1) ** 1.5)
    scr = heads.get("screened", W=W, b=b, screen=screen)
    ad = heads.get("adaptive", W=W, b=b, counts=counts, shortlist=64,
                   n_tails=2)
    assert ad.flops_per_query < scr.flops_per_query
    catalog = {"screened": scr.describe(), "adaptive": ad.describe(),
               "exact": heads.get("exact", W=W, b=b).describe()}
    pol = CostAwarePolicy(["adaptive", "screened", "exact"])
    assert pol.route(_req(), catalog) == "adaptive"
    assert pol.route(_req(accuracy_floor=0.99), catalog) == "screened"
    assert pol.route(_req(accuracy_floor=1.0), catalog) == "exact"


def test_serve_request_validates_fields_upfront():
    """Bad k / max_new / top_p must raise typed ValueErrors at construction
    — not as shape/NaN failures deep inside a jitted decode step."""
    ok = dict(prompt=np.arange(4), max_new=2)
    assert ServeRequest(**ok).k == 1
    for bad in (dict(ok, k=0), dict(ok, k=-3)):
        with pytest.raises(ValueError, match="k must be >= 1"):
            ServeRequest(**bad)
    for bad in (dict(ok, max_new=0), dict(ok, max_new=-1)):
        with pytest.raises(ValueError, match="max_new must be >= 1"):
            ServeRequest(**bad)
    for bad_p in (0.0, -0.2, 1.5):
        with pytest.raises(ValueError, match=r"top_p must be in \(0, 1\]"):
            ServeRequest(**ok, top_p=bad_p)
    # boundary values stay legal
    assert ServeRequest(**ok, top_p=1.0).top_p == 1.0
    assert ServeRequest(**ok, top_p=0.5, k=64).k == 64
    with pytest.raises(ValueError, match="1-D"):
        ServeRequest(prompt=np.zeros((2, 3)), max_new=2)


def test_route_requests_explicit_head_wins():
    pol = StaticPolicy("screened")
    reqs = [_req(), _req(head="exact"), _req()]
    assert route_requests(reqs, pol, CATALOG) == \
        ["screened", "exact", "screened"]


def test_missing_screen_error_is_typed():
    W = np.zeros((24, 4), np.float32)
    b = np.zeros((24,), np.float32)
    assert issubclass(MissingScreenError, ValueError)
    for name in ("screened", "screened-sharded", "screened-cpu",
                 "screened-pallas"):
        with pytest.raises(MissingScreenError):
            heads.get(name, W=W, b=b, screen=None)


# -- serve_batch: mixed traffic, bit-parity, compile discipline --------------

def _mixed_requests(corpus, tiers, n, sampled_idx=()):
    prompts = corpus.sample_batch(n, 6, seed=21)
    reqs = []
    for i in range(n):
        sampled = i in sampled_idx
        reqs.append(ServeRequest(
            prompt=prompts[i], max_new=4 + (i % 3),
            latency_tier=tiers[i % len(tiers)],
            temperature=0.9 if sampled else None,
            top_p=0.95 if sampled else 1.0, seed=7))
    return reqs


def _assert_parity(eng, reqs, results):
    """Every result bit-identical to a solo generate(head=...) call, in
    request order."""
    for req, res in zip(reqs, results):
        assert res.request is req
        if req.temperature is None:
            solo = eng.generate(req.prompt[None], req.max_new, head=res.head)
        else:
            solo = eng.generate(req.prompt[None], req.max_new, head=res.head,
                                temperature=req.temperature,
                                top_p=req.top_p, key=jax.random.key(req.seed))
        np.testing.assert_array_equal(solo.tokens[0], res.tokens)


def test_mixed_batch_parity_single_device(trained):
    """≥6 requests across 3 heads on one engine: request-order results
    bit-identical to solo generate, one cached step per (head, kind)."""
    cfg, m, params, corpus, st = trained
    eng = DecodeEngine(m, params, screen=st.screen, max_len=30,
                       head_kwargs=dict(rho=cfg.d_model,
                                        n_top=cfg.vocab_size))
    policy = TierPolicy({"realtime": "screened", "standard": "svd",
                         "batch": "exact"}, default="exact")
    reqs = _mixed_requests(corpus, ["realtime", "standard", "batch"], 7)
    eng.serve_batch(reqs, policy=policy)            # warmup
    warm = eng._cache_size()
    results = eng.serve_batch(reqs, policy=policy)
    assert {r.head for r in results} == {"screened", "svd", "exact"}
    # one compiled step per (head, step-kind): 3 heads × greedy only
    assert warm == eng._cache_size() == 3
    _assert_parity(eng, reqs, results)
    # repeat runs stay warm
    eng.serve_batch(reqs, policy=policy)
    assert eng._cache_size() == 3


@pytest.mark.multidevice
def test_mixed_batch_parity_with_sharded(trained, multidevice):
    """The acceptance matrix: ≥6 requests resolving to ≥3 heads including a
    vocab-SHARDED head on the 8-simulated-device fixture, plus one sampled
    request riding the same batch — all bit-identical to solo calls."""
    cfg, m, params, corpus, st = trained
    eng = DecodeEngine(m, params, screen=st.screen, max_len=30,
                       head_kwargs=dict(n_shards=8))
    policy = TierPolicy({"realtime": "screened",
                         "standard": "screened-sharded",
                         "batch": "exact"}, default="exact")
    reqs = _mixed_requests(corpus, ["realtime", "standard", "batch"], 8,
                           sampled_idx=(6,))
    eng.serve_batch(reqs, policy=policy)            # warmup
    warm = eng._cache_size()
    results = eng.serve_batch(reqs, policy=policy)
    used = {r.head for r in results}
    assert used == {"screened", "screened-sharded", "exact"}
    sharded = eng.resolve_head("screened-sharded")
    assert sharded.n_shards == 8
    # at most one compiled step per (head, step-kind): 3 greedy + 1 sample
    assert warm == eng._cache_size() == 4
    _assert_parity(eng, reqs, results)


def test_serve_batch_defaults_and_groups(trained):
    """No policy → engine default head; same-key requests share one padded
    batched decode (group_size), trimmed back per request."""
    cfg, m, params, corpus, st = trained
    eng = DecodeEngine(m, params, screen=st.screen, max_len=30)
    prompts = corpus.sample_batch(4, 6, seed=5)
    reqs = [ServeRequest(prompt=p, max_new=3 + i % 2)
            for i, p in enumerate(prompts)]
    results = eng.serve_batch(reqs)
    assert all(r.head == "exact" for r in results)
    assert all(r.group_size == 4 for r in results)
    assert [len(r.tokens) for r in results] == [3, 4, 3, 4]
    _assert_parity(eng, reqs, results)
    assert eng.serve_batch([]) == []
    # different prompt lengths split groups (prefill shapes differ) but
    # still come back in request order
    mixed_len = [ServeRequest(prompt=prompts[0], max_new=3),
                 ServeRequest(prompt=prompts[1][:4], max_new=3)]
    out = eng.serve_batch(mixed_len)
    assert [r.group_size for r in out] == [1, 1]
    _assert_parity(eng, mixed_len, out)


def test_serve_batch_default_uses_engine_head_instance(trained):
    """policy=None serves the engine's default head INSTANCE — including a
    custom one whose name isn't re-resolvable from the registry."""
    cfg, m, params, corpus, st = trained
    custom = ScreenedHead(np.asarray(m.softmax_weights(params)[0]),
                          np.asarray(m.softmax_weights(params)[1]),
                          st.screen)
    custom.name = "custom-screened"          # not a registry name
    eng = DecodeEngine(m, params, head=custom, max_len=30)
    reqs = [ServeRequest(prompt=p, max_new=3)
            for p in corpus.sample_batch(2, 6, seed=5)]
    out = eng.serve_batch(reqs)
    assert [r.head for r in out] == ["custom-screened", "custom-screened"]
    ref = eng.generate(np.stack([r.prompt for r in reqs]), 3)
    np.testing.assert_array_equal(np.stack([r.tokens for r in out]),
                                  ref.tokens)


def test_head_catalog_skips_unbuildable_heads(trained):
    """Catalog omits heads this engine can't build — no screen, or a screen
    whose block size the kernel head rejects — without killing the batch."""
    cfg, m, params, corpus, st = trained
    assert st.screen.block == 1              # pallas head demands block=128
    eng = DecodeEngine(m, params, screen=st.screen, max_len=20)
    cat = eng.head_catalog(["exact", "screened", "screened-pallas"])
    assert set(cat) == {"exact", "screened"}
    pol = CostAwarePolicy(["screened-pallas", "screened"])
    out = eng.serve_batch(
        [ServeRequest(prompt=corpus.sample_batch(1, 6, seed=2)[0],
                      max_new=2)], policy=pol)
    assert out[0].head == "screened"


def test_sharded_memory_bytes_counts_device_tables(trained):
    """memory_bytes for the sharded screened head is the device-resident
    tables, not those PLUS the retained host screen (double count)."""
    cfg, m, params, corpus, st = trained
    eng = DecodeEngine(m, params, screen=st.screen, max_len=20)
    hd = eng.resolve_head("screened-sharded")
    expect = int(hd.Wp.nbytes + hd.bp.nbytes +
                 hd.cand_local.nbytes + hd.v.nbytes)
    assert hd.memory_bytes == expect
    assert hd.describe()["memory_bytes"] == expect


# -- engine step cache: true LRU keyed by stable head identity ---------------

def test_step_cache_stays_at_one_across_resolve_generate_cycles(trained):
    """Regression: repeated resolve_head("screened") + generate cycles reuse
    ONE cached step — including when callers hand in transient prepared
    instances over the same arrays (stable step_key identity)."""
    cfg, m, params, corpus, st = trained
    eng = DecodeEngine(m, params, screen=st.screen, max_len=30)
    prompts = corpus.sample_batch(1, 6, seed=3)
    for _ in range(4):
        eng.resolve_head("screened")
        eng.generate(prompts, 2, head="screened")
        assert eng._cache_size() == 1
    for _ in range(3):
        transient = ScreenedHead(eng.W, eng.b, st.screen).prepare()
        eng.generate(prompts, 2, head=transient)
        assert eng._cache_size() == 1
    counts = eng.compiled_step_counts()
    assert counts == {("screened", "greedy"): 1}


def test_step_key_distinguishes_adapter_knobs(trained):
    """Two adapter heads over the SAME arrays but different method knobs
    must not share a step key — the knobs change the decode behavior."""
    from repro.heads.adapters import SVDHead
    cfg, m, params, corpus, st = trained
    W, b = (np.asarray(a) for a in m.softmax_weights(params))
    a = SVDHead(W, b, rho=4).prepare()
    c = SVDHead(W, b, rho=8).prepare()
    assert a.step_key() != c.step_key()


def test_step_cache_lru_evicts_least_recently_used(trained):
    """Move-to-end on hit: the oldest-INSERTED entry survives if it was
    recently used; the least-recently-USED entry is evicted."""
    cfg, m, params, corpus, st = trained
    eng = DecodeEngine(m, params, screen=st.screen, max_len=30)
    eng._step_cache_max = 3
    hd = eng.resolve_head("exact")
    eng._greedy_step(hd)                       # A (oldest inserted)
    eng._sample_step(hd, 1.0, 1.0)             # B
    eng._sample_step(hd, 0.5, 1.0)             # C — cache full
    eng._greedy_step(hd)                       # hit A → most recent
    eng._sample_step(hd, 0.7, 1.0)             # D → must evict B, not A
    assert (hd.step_key(), "greedy") in eng._step_cache
    assert (hd.step_key(), "sample", 1.0, 1.0) not in eng._step_cache
    assert (hd.step_key(), "sample", 0.5, 1.0) in eng._step_cache
    assert (hd.step_key(), "sample", 0.7, 1.0) in eng._step_cache


# -- beam-search cache reordering: the LSTM branch ---------------------------

def test_reorder_cache_lstm_rows_follow_src_idx():
    from repro.serving.engine import _reorder_cache
    cfg = get_config("ptb-small-lstm").reduced()
    m = build_model(cfg)
    cache = m.init_cache(4, 8, dtype=jnp.float32)
    tagged = {"lstm": [{k: v + jnp.arange(4.0)[:, None]
                        for k, v in layer.items()}
                       for layer in cache["lstm"]]}
    src = jnp.asarray([2, 2, 0, 1], jnp.int32)
    re = _reorder_cache(tagged, src, cfg)
    assert len(re["lstm"]) == cfg.num_layers
    for layer in re["lstm"]:
        for v in layer.values():
            np.testing.assert_array_equal(np.asarray(v[:, 0]),
                                          [2.0, 2.0, 0.0, 1.0])


def test_reorder_cache_transformer_kv_rows_follow_src_idx():
    """The transformer KV-cache branch (stacked (L, B, S, KV, hd) leaves,
    batch at axis 1): rows must gather along the BATCH axis, untouched
    elsewhere — the branch PR 3 left uncovered."""
    from repro.serving.engine import _reorder_cache
    cfg = get_config("smollm-360m").reduced()
    m = build_model(cfg)
    cache = m.init_cache(4, 8, dtype=jnp.float32)
    assert set(cache) == {"attn"}
    tagged = jax.tree_util.tree_map(
        lambda a: a + jnp.arange(4.0).reshape(
            (1, 4) + (1,) * (a.ndim - 2)), cache)
    src = jnp.asarray([2, 2, 0, 1], jnp.int32)
    re = _reorder_cache(tagged, src, cfg)
    for leaf, ref in zip(jax.tree_util.tree_leaves(re),
                         jax.tree_util.tree_leaves(tagged)):
        assert leaf.shape == ref.shape            # (L, B, S, KV, hd) intact
        np.testing.assert_array_equal(
            np.asarray(leaf[:, :, 0, 0, 0]),
            np.broadcast_to(np.asarray([2.0, 2.0, 0.0, 1.0]),
                            (leaf.shape[0], 4)))
        # gathered rows carry their source rows' full contents
        np.testing.assert_array_equal(np.asarray(leaf),
                                      np.asarray(ref[:, src]))


def test_beam_search_transformer_state_follows_surviving_beams():
    """Beam search on a KV-cache arch: best-beam score == teacher-forced
    log-prob of the returned sequence, which requires _reorder_cache's
    stacked-cache branch to move K/V with the beams."""
    cfg = get_config("smollm-360m").reduced()
    m = build_model(cfg)
    params = m.init(jax.random.key(0), dtype=jnp.float32)
    eng = DecodeEngine(m, params, max_len=24)
    prompt = np.random.default_rng(3).integers(
        0, cfg.vocab_size, 6).astype(np.int32)
    bm = eng.beam_search(prompt, beam=3, max_new=5)
    full = np.concatenate([prompt, bm.tokens[0]])
    h, _ = m.forward(params, {"tokens": jnp.asarray(full[None])})
    lp = jax.nn.log_softmax(m.logits(params, h).astype(jnp.float32), -1)
    ref = sum(float(lp[0, len(prompt) - 1 + i, t])
              for i, t in enumerate(bm.tokens[0]))
    np.testing.assert_allclose(bm.scores[0], ref, atol=1e-3)


def test_beam_search_lstm_state_follows_surviving_beams(trained):
    """Beam search on the LSTM family: the reported best-beam score must
    equal the teacher-forced log-prob of the returned sequence — which only
    holds if _reorder_cache's LSTM branch moved (h, c) with the beams."""
    cfg, m, params, corpus, st = trained
    eng = DecodeEngine(m, params, max_len=30)
    prompt = corpus.sample_batch(1, 6, seed=17)[0]
    bm = eng.beam_search(prompt, beam=4, max_new=6)

    full = np.concatenate([prompt, bm.tokens[0]])
    h, _ = m.forward(params, {"tokens": jnp.asarray(full[None])})
    lp = jax.nn.log_softmax(m.logits(params, h).astype(jnp.float32), -1)
    ref = sum(float(lp[0, len(prompt) - 1 + i, t])
              for i, t in enumerate(bm.tokens[0]))
    np.testing.assert_allclose(bm.scores[0], ref, atol=1e-3)


# -- launcher: typed missing-screen probe ------------------------------------

def test_serve_launcher_exits_2_without_screen(capsys):
    from repro.launch import serve as serve_mod
    rc = serve_mod.main(["--arch", "ptb-small-lstm", "--reduced",
                         "--head", "screened", "--train-steps", "1"])
    assert rc == 2
    out = capsys.readouterr().out
    assert "cannot build head 'screened'" in out
    assert "--l2s" in out and "fit_l2s" in out


def test_serve_launcher_refuses_unbuildable_named_head(
        capsys, monkeypatch, isolated_compile_cache):
    """A --head that cannot be built once the screen is fit exits 2 instead
    of serving its tier on exact. Forced here by fitting screened-pallas a
    word-granular screen, which its constructor refuses."""
    from repro.launch import serve as serve_mod
    monkeypatch.setattr(serve_mod, "screen_block", lambda *names: 1)
    rc = serve_mod.main(["--arch", "ptb-small-lstm", "--reduced", "--l2s",
                         "--head", "screened-pallas", "--scheduler",
                         "--train-steps", "2", "--requests", "2",
                         "--max-new", "2", "--clusters", "4",
                         "--budget", "40"])
    out = capsys.readouterr().out
    assert rc == 2
    assert "cannot build head 'screened-pallas'" in out
    assert "vocab_block=128" in out
    assert "scheduler:" not in out                 # nothing was served


def test_serve_launcher_serves_named_pallas_head(capsys,
                                                 isolated_compile_cache):
    """--head screened-pallas fits its 128-word block screen, at a default
    budget of whole blocks, and serves the realtime tier on it — not on
    exact, and not through a fault's fallback."""
    from repro.launch import serve as serve_mod
    rc = serve_mod.main(["--arch", "ptb-small-lstm", "--reduced", "--l2s",
                         "--head", "screened-pallas", "--scheduler",
                         "--train-steps", "2", "--requests", "3",
                         "--max-new", "2", "--clusters", "4"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "budget=1024" in out and "block=128" in out
    assert "screened-pallas: 1 req" in out
    assert "resilience" not in out
