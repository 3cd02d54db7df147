"""Speculative decoding subsystem (repro.serving.spec): the rejection-rule
emission identity (analytic + hypothesis property + Monte Carlo), greedy
prefix acceptance, SpecPolicy draft selection and the adaptive draft-length
controller, ServeRequest spec-field validation, SpecDecodeStream greedy
bit-parity with solo exact decode on LSTM (snapshot rollback) and
transformer (mask rollback) families with zero step recompiles after
warmup, KV-pool page reservations, scheduler integration (parity, spec
telemetry, draft-before-head admission shedding), exact-SHARDED verify on
simulated multidevice meshes, and the serve launcher's --draft-head
fail-fast paths."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import heads as heads_registry
from repro.configs import L2SConfig, TrainConfig, get_config
from repro.core import collect_contexts, fit_l2s
from repro.data import ZipfMarkovCorpus, make_lm_batches
from repro.heads.base import NEG_INF
from repro.launch.steps import make_train_step
from repro.models import build_model
from repro.optim import adamw_init
from repro.serving import (ContinuousScheduler, DecodeEngine, PagePool,
                           ServeRequest, ServeResult, SpecPolicy,
                           StaticPolicy)
from repro.serving.scheduler import BudgetAdmission
from repro.serving.scheduler.queue import head_flops
from repro.serving.spec import (DraftLenController, accept_draft,
                                accept_step, emission_distribution,
                                greedy_accept_lengths, row_probs,
                                spec_step_flops)


@pytest.fixture(scope="module")
def trained():
    """Small trained LSTM + fitted screen: the screened head agrees with
    exact often, so speculation actually pays here."""
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


@pytest.fixture(scope="module")
def transformer_engine():
    """UNTRAINED transformer + a screen fitted on random contexts: the
    draft disagrees with exact constantly, exercising rejection + the
    attention-mask rollback path hard."""
    cfg = get_config("smollm-360m").reduced()
    m = build_model(cfg)
    params = m.init(jax.random.key(0), dtype=jnp.float32)
    rng = np.random.default_rng(0)
    H = jnp.asarray(rng.standard_normal((1500, cfg.d_model)), jnp.float32)
    y = jnp.asarray(rng.integers(0, cfg.vocab_size, (1500, 1)))
    st = fit_l2s(H, y, cfg.vocab_size,
                 L2SConfig(num_clusters=8, budget=40, outer_iters=1,
                           sgd_steps=20))
    return cfg, DecodeEngine(m, params, screen=st.screen, max_len=40)


def _engine(trained, **kw):
    cfg, m, params, corpus, st = trained
    kw.setdefault("max_len", 32)
    return DecodeEngine(m, params, screen=st.screen, **kw)


def _run_stream(stream, requests):
    done = {}
    for i, r in enumerate(requests):
        stream.join(r, tag=i)
    for _ in range(200):
        for tag, _, toks in stream.step():
            done[tag] = toks
        if stream.idle:
            return done
    raise AssertionError("stream never drained")


# -- acceptance math ----------------------------------------------------------

def test_row_probs_empty_convention():
    full = row_probs(np.array([0.0, math.log(3.0)]))
    np.testing.assert_allclose(full, [0.25, 0.75])
    empty = row_probs(np.full(4, NEG_INF))
    np.testing.assert_array_equal(empty, np.zeros(4))
    # one live entry among NEG_INF sentinels: all mass there, no NaN
    one = np.full(4, NEG_INF)
    one[2] = 1.5
    np.testing.assert_allclose(row_probs(one), [0, 0, 1, 0])


def test_greedy_accept_lengths():
    draft = np.array([[1, 2, 3], [1, 9, 3], [9, 2, 3]])
    exact = np.array([[1, 2, 3], [1, 2, 3], [1, 2, 3]])
    np.testing.assert_array_equal(greedy_accept_lengths(draft, exact),
                                  [3, 1, 0])


def test_emission_identity_property():
    """Satellite: the rejection rule's analytic per-position emitted law
    equals the TARGET distribution for random draft/target logit pairs,
    including −inf-masked entries and fully-empty draft rows (the PR-7
    empty-candidate convention)."""
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as hst

    @settings(max_examples=200, deadline=None)
    @given(hst.integers(0, 2**32 - 1), hst.integers(2, 12),
           hst.floats(0.0, 1.0), hst.booleans())
    def check(seed, V, mask_frac, empty_draft):
        rng = np.random.default_rng(seed)
        q = rng.standard_normal(V) * 3.0
        p = rng.standard_normal(V) * 3.0
        q[rng.random(V) < mask_frac] = NEG_INF      # screened-out words
        if empty_draft:
            q[:] = NEG_INF                          # empty candidate set
        p[rng.random(V) < mask_frac * 0.5] = NEG_INF
        if np.all(p <= NEG_INF / 2):
            p[rng.integers(V)] = 0.0                # target is never empty
        emitted = emission_distribution(q, p)
        np.testing.assert_allclose(emitted, row_probs(p), atol=1e-12)

    check()


def test_emission_identity_numpy_sweep():
    """The same property as above, pure-numpy and always-on: 300 seeded
    random (q, p) pairs sweeping mask density from 0 to ~1, plus the
    empty-draft row, must all emit exactly the target law."""
    rng = np.random.default_rng(0)
    for trial in range(300):
        V = int(rng.integers(2, 16))
        q = rng.standard_normal(V) * 3.0
        p = rng.standard_normal(V) * 3.0
        frac = trial / 300.0
        q[rng.random(V) < frac] = NEG_INF
        if trial % 7 == 0:
            q[:] = NEG_INF                          # empty candidate set
        p[rng.random(V) < frac * 0.5] = NEG_INF
        if np.all(p <= NEG_INF / 2):
            p[rng.integers(V)] = 0.0
        np.testing.assert_allclose(emission_distribution(q, p),
                                   row_probs(p), atol=1e-12)


def test_accept_step_monte_carlo():
    """The sampled rule empirically reproduces p — including when the draft
    row is masked far from the target."""
    rng = np.random.default_rng(7)
    q = np.array([2.0, NEG_INF, 0.0, 1.0])
    p = np.array([0.0, 1.0, 1.0, NEG_INF])
    counts = np.zeros(4)
    n = 20_000
    for _ in range(n):
        d = rng.choice(4, p=row_probs(q))
        _, tok = accept_step(rng, int(d), q, p)
        counts[tok] += 1
    np.testing.assert_allclose(counts / n, row_probs(p), atol=0.02)


def test_accept_step_empty_draft_row():
    """Empty draft distribution (all-NEG_INF q): auto-reject, replacement
    drawn from p itself — emission still follows the target."""
    rng = np.random.default_rng(0)
    q = np.full(3, NEG_INF)
    p = np.array([NEG_INF, 0.0, NEG_INF])
    ok, tok = accept_step(rng, 0, q, p)
    assert not ok and tok == 1


def test_accept_step_empty_target_raises():
    rng = np.random.default_rng(0)
    with pytest.raises(ValueError, match="EMPTY target"):
        accept_step(rng, 0, np.full(3, NEG_INF), np.full(3, NEG_INF))


def test_accept_draft_stops_at_first_rejection():
    rng = np.random.default_rng(1)
    n, V = 4, 5
    q = np.zeros((n, V))
    p = np.full((n, V), NEG_INF)
    p[:, 2] = 0.0                    # target is a point mass on token 2
    emitted, a = accept_draft(rng, np.array([2, 2, 0, 0]), q, p)
    assert a == 2                    # first two drafts match the point mass
    assert emitted == [2, 2, 2]      # + the replacement, drawn from p
    emitted, a = accept_draft(rng, np.array([2, 2, 2, 2]), q, p)
    assert a == 4 and emitted == [2, 2, 2, 2]


# -- policy + controller ------------------------------------------------------

def _cat(**heads):
    return {n: {"flops_per_query": f, "bytes_per_query": float(f),
                "supports_sampling": True, "supports_dist": True,
                "n_shards": None, **extra}
            for n, (f, extra) in heads.items()}


def test_draft_len_controller():
    c = DraftLenController(4, low=0.45, high=0.75, ema=1.0)
    assert c.n == 4
    assert c.observe(0.1) == 3       # below low → shrink
    assert c.observe(0.0) == 2
    assert c.observe(0.0) == 1
    assert c.observe(0.0) == 1       # floor at 1
    for _ in range(5):
        c.observe(1.0)
    assert c.n == 4                  # recovers to n_max, never past it
    with pytest.raises(ValueError):
        DraftLenController(0)


def test_spec_policy_picks_cheapest_modeled_draft():
    cat = _cat(**{"exact": (100.0, {}), "screened": (10.0, {}),
                  "screened-pallas": (10.0, {"bytes_per_query": 1.0}),
                  "adaptive": (40.0, {})})
    pol = SpecPolicy(drafts=("screened-pallas", "screened", "adaptive"),
                     min_ratio=2.0)
    r = ServeRequest(prompt=np.zeros(4, np.int32), max_new=8)
    # flops tie between the two screened variants → bytes break it
    assert pol.draft_for(r, "exact", cat) == "screened-pallas"
    # min_ratio excludes a draft that is not cheap enough
    assert SpecPolicy(drafts=("adaptive",), min_ratio=4.0) \
        .draft_for(r, "exact", cat) is None
    # NaN-cost drafts never win
    cat_nan = _cat(**{"exact": (100.0, {}),
                      "screened": (math.nan, {"bytes_per_query": 1.0})})
    assert SpecPolicy(drafts=("screened",)).draft_for(r, "exact",
                                                      cat_nan) is None
    # non-exact verify heads are not speculated for by default
    assert pol.draft_for(r, "screened", cat) is None
    # unknown verify → None
    assert pol.draft_for(r, "nope", cat) is None


def test_spec_policy_sampled_constraints():
    cat = _cat(**{"exact": (100.0, {}),
                  "exact-sharded": (50.0, {"n_shards": 4}),
                  "screened": (10.0, {}),
                  "nodist": (5.0, {"supports_dist": False})})
    pol = SpecPolicy(drafts=("nodist", "screened"))
    sampled = ServeRequest(prompt=np.zeros(4, np.int32), max_new=8,
                           temperature=0.8, seed=1)
    greedy = ServeRequest(prompt=np.zeros(4, np.int32), max_new=8)
    # sampled: a draft without dist_logits is skipped, screened still wins
    assert pol.draft_for(sampled, "exact", cat) == "screened"
    # greedy id-compare has no dist requirement — nodist is cheapest
    assert pol.draft_for(greedy, "exact", cat) == "nodist"
    # sampled on a SHARDED verify head: greedy-only → no spec
    assert pol.draft_for(sampled, "exact-sharded", cat) is None
    assert pol.draft_for(greedy, "exact-sharded", cat) == "nodist"


def test_spec_policy_explicit_draft_and_headroom():
    cat = _cat(**{"exact": (100.0, {}), "screened": (10.0, {}),
                  "adaptive": (90.0, {})})
    pol = SpecPolicy(drafts=("screened",))
    # explicit draft_head is honored even when the ranked pick differs
    # (and even though "adaptive" fails min_ratio)
    r = ServeRequest(prompt=np.zeros(4, np.int32), max_new=8,
                     draft_head="adaptive")
    assert pol.draft_for(r, "exact", cat) == "adaptive"
    # ... but not when it IS the verify head or unknown
    assert pol.draft_for(
        ServeRequest(prompt=np.zeros(4, np.int32), max_new=8,
                     draft_head="nope"), "exact", cat) is None
    # cache headroom: no room for even a 2-token draft → no spec
    tight = ServeRequest(prompt=np.zeros(10, np.int32), max_new=10)
    assert pol.draft_len_for(tight, max_len=20) == 1
    assert pol.draft_for(tight, "exact", cat, max_len=20) is None
    assert pol.draft_for(tight, "exact", cat, max_len=25) == "screened"
    # request-level draft_len override
    r8 = ServeRequest(prompt=np.zeros(4, np.int32), max_new=8, draft_len=8)
    assert pol.draft_len_for(r8, max_len=100) == 8


def test_spec_step_flops_charges_both_heads():
    cat = _cat(**{"exact": (100.0, {}), "screened": (10.0, {})})
    assert spec_step_flops(cat, "screened", "exact") == 110.0
    assert spec_step_flops(cat, "screened", "exact") > \
        head_flops(cat, "exact")     # flops-honest: spec charges MORE


def test_request_spec_field_validation():
    ok = ServeRequest(prompt=np.zeros(4, np.int32), max_new=4,
                      draft_head="screened", draft_len=4)
    assert ok.draft_head == "screened" and ok.draft_len == 4
    with pytest.raises(ValueError, match="draft_len"):
        ServeRequest(prompt=np.zeros(4, np.int32), max_new=4, draft_len=0)
    with pytest.raises(ValueError, match="draft_head"):
        ServeRequest(prompt=np.zeros(4, np.int32), max_new=4,
                     head="screened", draft_head="screened")


# -- dist_logits head protocol ------------------------------------------------

def test_dist_logits_matches_sampling_support(trained):
    """screened.dist_logits scatters candidate logits to vocab coordinates:
    NEG_INF exactly off the routed candidate set, raw logits on it, and the
    exact head's rows are the raw full-vocab logits."""
    cfg, m, params, corpus, st = trained
    eng = _engine(trained)
    h = jnp.asarray(np.random.default_rng(3).standard_normal(
        (5, cfg.d_model)), jnp.float32)
    exact = eng.resolve_head("exact")
    screened = eng.resolve_head("screened")
    assert exact.supports_dist and screened.supports_dist
    assert exact.describe()["supports_dist"]
    pe = np.asarray(exact.dist_logits(h))
    np.testing.assert_allclose(
        pe, np.asarray(h @ eng.W.T + eng.b), rtol=1e-5, atol=1e-5)
    ps = np.asarray(screened.dist_logits(h))
    assert ps.shape == (5, cfg.vocab_size)
    on = ps > NEG_INF / 2
    assert on.any(axis=1).all() and (~on).any()     # real support, masked rest
    np.testing.assert_allclose(np.where(on, ps, 0.0),
                               np.where(on, pe, 0.0), rtol=1e-4, atol=1e-4)
    # argmax over dist_logits IS the head's greedy choice
    np.testing.assert_array_equal(ps.argmax(1), np.asarray(screened.next(h)))


# -- SpecDecodeStream ---------------------------------------------------------

def test_spec_stream_greedy_parity_lstm(trained, page_pool):
    """Tentpole acceptance: greedy spec tokens are BIT-identical to solo
    exact-head generate on the LSTM (snapshot-restore rollback), with zero
    new step executables once warm."""
    cfg, m, params, corpus, st = trained
    eng = _engine(trained)
    prompts = corpus.sample_batch(3, 6, seed=42)
    reqs = [ServeRequest(prompt=p, max_new=10) for p in prompts]
    base = eng.generate(prompts, 10, head="exact")

    s1 = eng.open_spec_stream("screened", "exact", width=4, draft_len=4,
                              kv_pool=page_pool(eng))
    done = _run_stream(s1, reqs)
    for i in range(3):
        np.testing.assert_array_equal(done[i], base.tokens[i])
    c = s1.spec_counters()
    # the first token per request comes from the join prefill, not a round
    assert c["emitted"] == 27 and c["rounds"] >= 3
    assert c["emitted"] / c["rounds"] > 1.0      # speculation paid
    warm = eng.compiled_step_counts()

    # a second stream of the same shape adds ZERO executables
    s2 = eng.open_spec_stream("screened", "exact", width=4, draft_len=4,
                              kv_pool=page_pool(eng))
    done = _run_stream(s2, [ServeRequest(prompt=p, max_new=8)
                            for p in corpus.sample_batch(2, 6, seed=9)])
    assert eng.compiled_step_counts() == warm


def test_spec_stream_transformer_rollback_parity(transformer_engine,
                                                 page_pool):
    """Attention-family rollback is pure position masking — parity must
    hold under HEAVY rejection (untrained model, junk screen)."""
    cfg, eng = transformer_engine
    rng = np.random.default_rng(5)
    prompts = rng.integers(0, cfg.vocab_size, (3, 6)).astype(np.int32)
    base = eng.generate(prompts, 10, head="exact")
    st = eng.open_spec_stream("screened", "exact", width=4, draft_len=3,
                              kv_pool=page_pool(eng))
    done = _run_stream(st, [ServeRequest(prompt=p, max_new=10)
                            for p in prompts])
    for i in range(3):
        np.testing.assert_array_equal(done[i], base.tokens[i])
    c = st.spec_counters()
    assert c["accepted"] < c["drafted"]          # rejections really happened


def test_spec_stream_adaptive_controller_shrinks(transformer_engine,
                                                 page_pool):
    """Junk-screen acceptance collapses → the controller walks the live
    draft length down to 1 without re-tracing (counted via draft_steps)."""
    cfg, eng = transformer_engine
    rng = np.random.default_rng(6)
    st = eng.open_spec_stream("screened", "exact", width=2, draft_len=4,
                              kv_pool=page_pool(eng))
    _run_stream(st, [ServeRequest(prompt=p, max_new=12) for p in
                     rng.integers(0, cfg.vocab_size, (2, 5)).astype(np.int32)])
    assert st.controller is not None and st.controller.n < 4


def test_spec_stream_sampled_smoke(trained, page_pool):
    """Sampled spec: runs to completion, emits in-vocab tokens, and the
    guards reject configurations the rejection rule cannot serve."""
    cfg, m, params, corpus, st = trained
    eng = _engine(trained)
    prompts = corpus.sample_batch(2, 6, seed=11)
    stream = eng.open_spec_stream("screened", "exact", width=2, draft_len=3,
                                  temperature=0.8, top_p=0.9, seed=3,
                                  kv_pool=page_pool(eng))
    done = _run_stream(stream, [
        ServeRequest(prompt=p, max_new=8, temperature=0.8, top_p=0.9,
                     seed=3) for p in prompts])
    for i in range(2):
        assert done[i].shape == (8,)
        assert 0 <= done[i].min() and done[i].max() < cfg.vocab_size
    # guard: draft == verify
    with pytest.raises(ValueError, match="DISTINCT"):
        eng.open_spec_stream("exact", "exact", kv_pool=page_pool(eng))
    # guard: sampled needs dist_logits on both heads (svd has none)
    svd = heads_registry.get("svd", W=eng.W, b=eng.b, screen=None,
                             rho=cfg.d_model, n_top=cfg.vocab_size)
    with pytest.raises(ValueError, match="dist_logits"):
        eng.open_spec_stream(svd, "exact", temperature=0.8,
                             kv_pool=page_pool(eng))


def test_spec_stream_join_headroom_and_width(page_pool):
    cfg = get_config("ptb-small-lstm").reduced()
    m = build_model(cfg)
    params = m.init(jax.random.key(0), dtype=jnp.float32)
    rng = np.random.default_rng(0)
    H = jnp.asarray(rng.standard_normal((500, cfg.d_model)), jnp.float32)
    y = jnp.asarray(rng.integers(0, cfg.vocab_size, (500, 1)))
    st = fit_l2s(H, y, cfg.vocab_size,
                 L2SConfig(num_clusters=4, budget=32, outer_iters=1,
                           sgd_steps=10))
    eng = DecodeEngine(m, params, screen=st.screen, max_len=16)
    stream = eng.open_spec_stream("screened", "exact", width=2, draft_len=4,
                                  kv_pool=page_pool(eng))
    # 8 + 6 + (4-1) = 17 > 16: the draft overshoot must be priced in
    with pytest.raises(ValueError, match="overshoot"):
        stream.join(ServeRequest(prompt=np.zeros(8, np.int32), max_new=6))
    stream.join(ServeRequest(prompt=np.zeros(8, np.int32), max_new=5))
    with pytest.raises(ValueError, match="width"):
        eng.open_spec_stream("screened", "exact", width=0,
                             kv_pool=page_pool(eng))


def test_spec_stream_kv_pool_reservations(trained):
    """With a kv_pool the stream takes logical page reservations covering
    prompt + max_new + draft overshoot, and releases them at retire."""
    cfg, m, params, corpus, st = trained
    eng = _engine(trained)
    pool = PagePool(num_pages=32, page_size=4)
    stream = eng.open_spec_stream("screened", "exact", width=2, draft_len=4,
                                  kv_pool=pool)
    req = ServeRequest(prompt=corpus.sample_batch(1, 6, seed=1)[0],
                       max_new=6)
    stream.join(req, tag=0)
    # ceil((6 + 6 + 3) / 4) = 4 pages
    assert pool.pages_in_use == 4
    while not stream.idle:
        stream.step()
    assert pool.pages_in_use == 0
    # exhaustion at join rolls back every page it took
    tiny = PagePool(num_pages=2, page_size=4)
    s2 = eng.open_spec_stream("screened", "exact", width=2, draft_len=4,
                              kv_pool=tiny)
    from repro.serving import PoolExhausted
    with pytest.raises(PoolExhausted):
        s2.join(req, tag=0)
    assert tiny.pages_in_use == 0


# -- scheduler integration ----------------------------------------------------

def test_scheduler_spec_parity_and_stats(trained, page_pool):
    """ContinuousScheduler(spec=...) serves exact-routed traffic on spec
    lanes: results bit-match plain serve_batch, the composite head name is
    reported, and ServerStats grows a populated "spec" section."""
    cfg, m, params, corpus, st = trained
    eng = _engine(trained)
    prompts = corpus.sample_batch(6, 6, seed=21)
    reqs = [ServeRequest(prompt=p, max_new=6 + (i % 3))
            for i, p in enumerate(prompts)]
    base = eng.serve_batch(reqs, policy=StaticPolicy("exact"))
    sched = ContinuousScheduler(
        eng, policy=StaticPolicy("exact"),
        spec=SpecPolicy(drafts=("screened",), draft_len=4),
        kv_pool=page_pool(eng))
    res = sched.serve(reqs)
    for r, b in zip(res, base):
        assert isinstance(r, ServeResult)
        np.testing.assert_array_equal(r.tokens, b.tokens)
        assert r.head == "exact+spec[screened]"
    snap = sched.stats.snapshot()["spec"]
    assert snap is not None and snap["rounds"] > 0
    assert snap["accepted_tokens_per_step"] > 1.0
    assert 0.0 <= snap["draft_acceptance"] <= 1.0
    assert snap["verify_queries"] > 0 and snap["verify_flops"] > 0
    # token accounting: joins credit 1 first token, rounds credit EMITTED
    assert sched.stats.tokens == sum(len(b.tokens) for b in base)


def test_scheduler_drops_draft_before_head(trained, page_pool):
    """Admission prices the draft's extra flops; when the routed head fits
    only WITHOUT it, the spec assignment is dropped — never the head."""
    cfg, m, params, corpus, st = trained
    eng = _engine(trained)
    cat = eng.head_catalog(("exact", "screened"))
    tight = head_flops(cat, "exact") + 0.5 * head_flops(cat, "screened")
    sched = ContinuousScheduler(
        eng, policy=StaticPolicy("exact"),
        admission=BudgetAdmission(flops_budget=tight),
        spec=SpecPolicy(drafts=("screened",)), kv_pool=page_pool(eng))
    sched.submit(ServeRequest(prompt=corpus.sample_batch(1, 6, seed=2)[0],
                              max_new=4))
    qr = next(iter(sched.queue))
    assert qr.head == "exact" and qr.draft is None
    assert sched.stats.downgraded == 0
    # with budget headroom the same submission keeps its draft (and the
    # queue entry carries the spec cost of BOTH heads)
    roomy = ContinuousScheduler(
        eng, policy=StaticPolicy("exact"),
        admission=BudgetAdmission(flops_budget=10 * tight),
        spec=SpecPolicy(drafts=("screened",), draft_len=4),
        kv_pool=page_pool(eng))
    roomy.submit(ServeRequest(prompt=corpus.sample_batch(1, 6, seed=2)[0],
                              max_new=4))
    qr = next(iter(roomy.queue))
    assert qr.draft == "screened" and qr.draft_len == 4
    assert qr.cost == pytest.approx(spec_step_flops(cat, "screened",
                                                    "exact"))


def test_scheduler_spec_lane_signature(trained, page_pool):
    """Spec and plain requests never share a stream lane: the draft rides
    the stream signature."""
    cfg, m, params, corpus, st = trained
    eng = _engine(trained)
    sched = ContinuousScheduler(
        eng, policy=StaticPolicy("exact"),
        spec=SpecPolicy(drafts=("screened",), draft_len=4),
        kv_pool=page_pool(eng))
    p = corpus.sample_batch(2, 6, seed=5)
    sched.submit(ServeRequest(prompt=p[0], max_new=4))
    # draft_len=1 → draft_len_for < 2 → plain lane for this request
    sched.submit(ServeRequest(prompt=p[1], max_new=4, draft_len=1))
    sigs = {sched._sig(qr) for qr in sched.queue}
    assert len(sigs) == 2
    res = sched.drain()
    heads = sorted(r.head for r in res)
    assert heads == ["exact", "exact+spec[screened]"]


# -- exact-sharded verify (multidevice) ---------------------------------------

@pytest.mark.multidevice
@pytest.mark.parametrize("n_shards", [2, 8])
def test_sharded_verify_greedy_parity(trained, multidevice, n_shards,
                                      page_pool):
    """Greedy spec with an exact-SHARDED verify head: one mesh-aware
    batched verify executable, tokens bit-identical to unsharded exact."""
    cfg, m, params, corpus, st = trained
    eng = _engine(trained)
    prompts = corpus.sample_batch(3, 6, seed=42)
    base = eng.generate(prompts, 8, head="exact")
    sharded = heads_registry.get("exact-sharded", W=eng.W, b=eng.b,
                                 n_shards=n_shards)
    stream = eng.open_spec_stream("screened", sharded, width=4, draft_len=4,
                                  kv_pool=page_pool(eng))
    done = _run_stream(stream, [ServeRequest(prompt=p, max_new=8)
                                for p in prompts])
    for i in range(3):
        np.testing.assert_array_equal(done[i], base.tokens[i])
    counts = eng.compiled_step_counts()
    assert counts[("exact-sharded", "spec-verify")] == 1


@pytest.mark.multidevice
def test_sharded_verify_refuses_sampled(trained, multidevice, page_pool):
    cfg, m, params, corpus, st = trained
    eng = _engine(trained)
    sharded = heads_registry.get("exact-sharded", W=eng.W, b=eng.b,
                                 n_shards=2)
    with pytest.raises(ValueError, match="unsharded"):
        eng.open_spec_stream("screened", sharded, temperature=0.8,
                             kv_pool=page_pool(eng))


# -- launcher fail-fast -------------------------------------------------------

def test_serve_launcher_draft_head_validation():
    """--draft-head combos fail with exit 2 BEFORE any training."""
    from repro.launch import serve as serve_mod
    base = ["--arch", "ptb-small-lstm", "--reduced"]
    # unknown draft head name
    assert serve_mod.main(base + ["--scheduler", "--draft-head", "nope"]) == 2
    # spec without the scheduler's stream lanes
    assert serve_mod.main(base + ["--draft-head", "screened",
                                  "--l2s"]) == 2
    # drafting with the verify head itself
    assert serve_mod.main(base + ["--scheduler", "--draft-head",
                                  "exact"]) == 2
    # screening draft without a screen to fit
    assert serve_mod.main(base + ["--scheduler", "--draft-head",
                                  "screened"]) == 2
