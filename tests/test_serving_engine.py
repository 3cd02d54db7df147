"""Serving engine on the SoftmaxHead API: greedy == teacher-forced argmax;
beam ≥ greedy score; screened decode; kernel-head decode; per-request head
switching; cache reordering under beam search; deprecated sampling shims."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import heads
from repro.configs import L2SConfig, get_config
from repro.core import fit_l2s
from repro.core.screening import ScreenParams, candidates_to_padded
from repro.models import build_model
from repro.serving import DecodeEngine


@pytest.mark.parametrize("arch", ["ptb-small-lstm", "smollm-360m",
                                  "mamba2-1.3b"])
def test_greedy_matches_teacher_forcing(arch):
    cfg = get_config(arch).reduced()
    m = build_model(cfg)
    params = m.init(jax.random.key(0), dtype=jnp.float32)
    eng = DecodeEngine(m, params, max_len=24)
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 6)).astype(np.int32)
    r = eng.generate(prompts, 5)
    full = np.concatenate([prompts, r.tokens], axis=1)
    h, _ = m.forward(params, {"tokens": jnp.asarray(full)})
    logits = m.logits(params, h)
    ref = np.asarray(jnp.argmax(logits, -1))[:, 5:-1]
    np.testing.assert_array_equal(ref, r.tokens)


@pytest.mark.parametrize("head", ["exact", "screened-pallas"])
def test_composed_step_takes_head_weights_as_arguments(head):
    """The cached greedy step lowers with W as an executable argument: a
    head closed over by the step would embed its weights as constants (a
    second copy of W per compiled step)."""
    cfg = get_config("ptb-small-lstm").reduced()
    m = build_model(cfg)
    params = m.init(jax.random.key(0), dtype=jnp.float32)
    W, b = m.softmax_weights(params)
    H = np.random.default_rng(0).standard_normal((64, cfg.d_model))
    y = np.asarray(jnp.argsort(-(jnp.asarray(H, jnp.float32) @ W.T + b),
                               axis=-1)[:, :5])
    screen = fit_l2s(H.astype(np.float32), y, cfg.vocab_size,
                     L2SConfig(num_clusters=4, budget=256, outer_iters=1,
                               sgd_steps=5, vocab_block=128)).screen
    eng = DecodeEngine(m, params, screen=screen, max_len=16)
    fn = eng._greedy_step(eng.resolve_head(head))
    tok = jnp.zeros((2,), jnp.int32)
    cache = m.init_cache(2, 16, dtype=jnp.float32)
    was = jax.config.jax_captured_constants_warn_bytes
    jax.config.update("jax_captured_constants_warn_bytes", 1024)
    try:
        with warnings.catch_warnings():
            warnings.filterwarnings("error", message=".*constants were "
                                    "captured")
            fn.lower(params, tok, cache, tok)
    finally:
        jax.config.update("jax_captured_constants_warn_bytes", was)


def test_beam_score_at_least_greedy():
    cfg = get_config("ptb-small-lstm").reduced()
    m = build_model(cfg)
    params = m.init(jax.random.key(0), dtype=jnp.float32)
    eng = DecodeEngine(m, params, max_len=24)
    prompt = np.asarray([1, 2, 3, 4], np.int32)

    def seq_logprob(tokens):
        full = np.concatenate([prompt, tokens])
        h, _ = m.forward(params, {"tokens": jnp.asarray(full[None])})
        lp = jax.nn.log_softmax(m.logits(params, h).astype(jnp.float32), -1)
        return sum(float(lp[0, len(prompt) - 1 + i, t])
                   for i, t in enumerate(tokens))

    g = eng.generate(prompt[None], 5)
    bm = eng.beam_search(prompt, beam=4, max_new=5)
    assert seq_logprob(bm.tokens[0]) >= seq_logprob(g.tokens[0]) - 1e-4
    np.testing.assert_allclose(bm.scores[0], seq_logprob(bm.tokens[0]),
                               atol=1e-3)


def test_screened_logprobs_subset_normalization():
    rng = np.random.default_rng(0)
    L, d, r = 40, 8, 3
    W = jnp.asarray(rng.standard_normal((L, d)), jnp.float32)
    b = jnp.zeros((L,), jnp.float32)
    mask = np.zeros((r, L), bool)
    mask[:, :10] = True
    idx, lens = candidates_to_padded(mask, L)
    sp = ScreenParams(v=jnp.asarray(rng.standard_normal((r, d)), jnp.float32),
                      cand_idx=jnp.asarray(idx), cand_len=jnp.asarray(lens),
                      vocab_size=L)
    h = jnp.asarray(rng.standard_normal((2, d)), jnp.float32)
    screened = heads.get("screened", W=W, b=b, screen=sp)
    ids, lp = screened.topk_logprobs(h, 10)
    # probabilities over the 10-word candidate set sum to 1
    np.testing.assert_allclose(np.asarray(jnp.exp(lp).sum(-1)), 1.0, atol=1e-4)
    # and differ from full-vocab normalization
    _, lp_full = heads.get("exact", W=W, b=b).topk_logprobs(h, 10)
    assert float(jnp.exp(lp_full).sum()) < 2.0


def _trained_screen_setup(vocab_block=None, steps=60, budget=64, clusters=16,
                          sgd_steps=50):
    from repro.core import collect_contexts
    from repro.data import ZipfMarkovCorpus, make_lm_batches
    from repro.launch.steps import make_train_step
    from repro.configs import TrainConfig
    from repro.optim import adamw_init

    cfg = get_config("ptb-small-lstm").reduced()
    m = build_model(cfg)
    params = m.init(jax.random.key(0), dtype=jnp.float32)
    corpus = ZipfMarkovCorpus(cfg.vocab_size, branching=32, seed=3)
    tcfg = TrainConfig(lr=2e-3, total_steps=steps, warmup_steps=5,
                      remat="none", loss_chunk=None)
    step = jax.jit(make_train_step(m, tcfg))
    opt = adamw_init(params)
    for batch in make_lm_batches(corpus, steps, 8, 32, seed=1):
        params, opt, _ = step(params, opt,
                              {k: jnp.asarray(v) for k, v in batch.items()})
    H, y = collect_contexts(
        m, params, [jnp.asarray(b["tokens"])
                    for b in make_lm_batches(corpus, 8, 8, 32, seed=9)],
        max_vectors=2000)
    l2s_kwargs = dict(num_clusters=clusters, budget=budget, outer_iters=1,
                      sgd_steps=sgd_steps)
    if vocab_block is not None:
        l2s_kwargs["vocab_block"] = vocab_block
    st = fit_l2s(H, y, cfg.vocab_size, L2SConfig(**l2s_kwargs))
    return cfg, m, params, corpus, st


def test_screened_decode_end_to_end():
    """With a screen trained on the model's own behavior, screened greedy
    decode agrees with exact decode on most tokens — heads switched per
    request on ONE engine."""
    cfg, m, params, corpus, st = _trained_screen_setup()
    eng = DecodeEngine(m, params, screen=st.screen, max_len=40)
    prompts = corpus.sample_batch(4, 8, seed=5)
    exact = eng.generate(prompts, 12, head="exact")
    fast = eng.generate(prompts, 12, head="screened")
    agree = float((exact.tokens == fast.tokens).mean())
    assert agree > 0.7, agree


def test_kernel_screened_decode_matches_jnp_path():
    """The Pallas block-candidate head must produce the same tokens as the
    jnp screened head given the same block screen — resolved by name from
    the same engine, no use_kernel flag."""
    cfg, m, params, corpus, st = _trained_screen_setup(
        vocab_block=128, steps=40, budget=256, clusters=8, sgd_steps=30)
    assert st.screen.block == 128
    prompts = corpus.sample_batch(2, 6, seed=5)
    eng = DecodeEngine(m, params, screen=st.screen, max_len=20)
    out_jnp = eng.generate(prompts, 8, head="screened")
    out_krn = eng.generate(prompts, 8, head="screened-pallas")
    np.testing.assert_array_equal(out_jnp.tokens, out_krn.tokens)


def test_engine_rejects_legacy_flags():
    """The use_screen/use_kernel calling convention is gone."""
    cfg = get_config("ptb-small-lstm").reduced()
    m = build_model(cfg)
    params = m.init(jax.random.key(0), dtype=jnp.float32)
    eng = DecodeEngine(m, params, max_len=16)
    prompts = np.zeros((1, 4), np.int32)
    with pytest.raises(TypeError):
        eng.generate(prompts, 2, use_screen=True)
    with pytest.raises(TypeError):
        DecodeEngine(m, params, use_kernel=True)


def test_engine_sampling_routes_through_head():
    """Sampling decode: temperature 0 reproduces greedy; screened sampling
    stays inside the routed candidate sets."""
    cfg, m, params, corpus, st = _trained_screen_setup()
    eng = DecodeEngine(m, params, screen=st.screen, max_len=30)
    prompts = corpus.sample_batch(2, 6, seed=11)
    greedy = eng.generate(prompts, 6)
    t0 = eng.generate(prompts, 6, temperature=0.0)
    np.testing.assert_array_equal(greedy.tokens, t0.tokens)
    s = eng.generate(prompts, 6, temperature=1.2, top_p=0.9,
                     key=jax.random.key(2))
    assert s.tokens.shape == (2, 6)
    assert s.tokens.max() < cfg.vocab_size
    with pytest.raises(ValueError):
        eng.generate(prompts, 2, temperature=1.0)     # key required
    # screened sampling: every sampled token ∈ its step's candidate union
    allowed = set()
    cand = np.asarray(st.screen.cand_idx)
    for t in range(cand.shape[0]):
        allowed |= set((cand[t][cand[t] < cfg.vocab_size]).tolist())
    ss = eng.generate(prompts, 6, head="screened", temperature=1.0,
                      key=jax.random.key(3))
    assert set(ss.tokens.reshape(-1).tolist()) <= allowed


@pytest.mark.parametrize(
    "n_shards",
    [None,                                       # default mesh: all devices
     pytest.param(8, marks=pytest.mark.multidevice)])
def test_sharded_heads_decode_end_to_end(n_shards):
    """DecodeEngine(head="screened-sharded" / "exact-sharded"): greedy,
    sampled, and beam decode all run through the mesh-aware jitted step and
    produce the same tokens as their unsharded counterparts — with exactly
    ONE compilation per cached step (no per-step re-jitting). The pinned
    8-shard variant keeps the multi-device engine path in the multidevice
    CI job; the default variant covers whatever platform runs tier-1."""
    if jax.device_count() < (n_shards or 1):
        pytest.skip(f"needs {n_shards} devices")
    cfg, m, params, corpus, st = _trained_screen_setup()
    eng = DecodeEngine(m, params, screen=st.screen, max_len=40,
                       head_kwargs=dict(n_shards=n_shards))
    prompts = corpus.sample_batch(2, 6, seed=13)

    exact = eng.generate(prompts, 8, head="exact")
    exact_sh = eng.generate(prompts, 8, head="exact-sharded")
    np.testing.assert_array_equal(exact.tokens, exact_sh.tokens)
    scr = eng.generate(prompts, 8, head="screened")
    scr_sh = eng.generate(prompts, 8, head="screened-sharded")
    np.testing.assert_array_equal(scr.tokens, scr_sh.tokens)

    # sampling: temperature 0 reproduces greedy; t>0 stays in-vocab and in
    # the routed candidate sets (same invariant as the unsharded head)
    t0 = eng.generate(prompts, 6, head="screened-sharded", temperature=0.0)
    np.testing.assert_array_equal(t0.tokens, scr.tokens[:, :6])
    s = eng.generate(prompts, 6, head="screened-sharded", temperature=1.0,
                     key=jax.random.key(5))
    assert s.tokens.max() < cfg.vocab_size and s.tokens.min() >= 0

    # beam search routes through topk_logprobs on the sharded candidate space
    bm = eng.beam_search(prompts[0], beam=3, max_new=5,
                         head="screened-sharded")
    bm_ref = eng.beam_search(prompts[0], beam=3, max_new=5, head="screened")
    np.testing.assert_array_equal(bm.tokens, bm_ref.tokens)
    np.testing.assert_allclose(bm.scores, bm_ref.scores, atol=1e-4)

    # no per-step re-jitting: each cached mesh-aware step compiled once
    for name in ("exact-sharded", "screened-sharded"):
        hd = eng.resolve_head(name)
        assert hd.mesh is not None
        if n_shards is not None:
            assert hd.n_shards == n_shards
        step = eng._step_cache[(hd.step_key(), "greedy")]
        inner = getattr(step, "_inner_jit", step)
        if hasattr(inner, "_cache_size"):
            assert inner._cache_size() == 1, name


def test_numpy_baseline_head_decodes():
    """A non-jittable (numpy) head runs on the host side of the jitted
    decode step — greedy and beam both work, and an exact-config SVD head
    matches the exact head token-for-token."""
    cfg = get_config("ptb-small-lstm").reduced()
    m = build_model(cfg)
    params = m.init(jax.random.key(0), dtype=jnp.float32)
    eng = DecodeEngine(m, params, max_len=20,
                       head_kwargs=dict(rho=cfg.d_model,
                                        n_top=cfg.vocab_size))
    prompts = np.random.default_rng(1).integers(
        0, cfg.vocab_size, (2, 5)).astype(np.int32)
    exact = eng.generate(prompts, 6, head="exact")
    svd = eng.generate(prompts, 6, head="svd")
    np.testing.assert_array_equal(exact.tokens, svd.tokens)
    bm = eng.beam_search(prompts[0], beam=3, max_new=4, head="svd")
    assert bm.tokens.shape == (1, 4)


def test_sampling_module_removed():
    """The deprecated ``repro.serving.sampling`` shims completed their
    deprecation cycle: the module is GONE from the package and from the
    public serving surface — heads are the one next-token API."""
    import importlib
    import repro.serving as serving
    with pytest.raises(ModuleNotFoundError):
        importlib.import_module("repro.serving.sampling")
    for name in ("greedy_next", "screened_greedy_next", "sample_next",
                 "topk_logprobs"):
        assert not hasattr(serving, name)
        assert name not in serving.__all__


def test_train_launcher_checkpoint_resume(tmp_path):
    """train.py round trip: train → checkpoint → resume continues from step."""
    from repro.launch import train as train_mod
    ck = str(tmp_path / "ck")
    rc = train_mod.main(["--arch", "ptb-small-lstm", "--reduced",
                         "--steps", "6", "--batch", "4", "--seq", "16",
                         "--ckpt-dir", ck, "--log-every", "3"])
    assert rc == 0
    from repro.checkpoint import latest_step
    assert latest_step(ck) == 6
    # resume: runs the remaining steps without error
    rc = train_mod.main(["--arch", "ptb-small-lstm", "--reduced",
                         "--steps", "8", "--batch", "4", "--seq", "16",
                         "--ckpt-dir", ck, "--log-every", "2"])
    assert rc == 0
    assert latest_step(ck) == 8
