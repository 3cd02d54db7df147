"""The plain reference against the program at a reduced size on the CPU,
and the head semantics it holds the served tokens to."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, system
from bench.tests.helpers import small_conf

SEED = 2 ** 31 + 99


LSTM = system.load_family("lstm")


def trunk(w, tokens, conf):
    return LSTM.trunk(w, tokens, conf)


def ref_of(w, conf, screen, **kw):
    return reference.Reference(LSTM, conf, w, screen, conf["vocab_size"],
                               **kw)


@pytest.fixture(scope="module")
def model_and_weights():
    from repro.models import build_model
    conf = small_conf()
    w = system.make_weights(conf, SEED)
    return build_model(system.model_config(conf)), w, conf


def test_reference_matches_the_program(model_and_weights):
    model, w, conf = model_and_weights
    params = LSTM.program_params(w, conf)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, conf["vocab_size"], (3, 20)), jnp.int32)
    h_prog, _ = model.forward(params, {"tokens": tokens})
    z_prog = model.logits(params, h_prog)
    h_ref = trunk(w, tokens, conf)
    z_ref = h_ref @ w["w_out"].T + w["b_out"]
    # float32 on both sides; they differ by summation order alone
    tol = 1e-5
    assert float(jnp.max(jnp.abs(h_prog - h_ref))) < tol
    assert float(jnp.max(jnp.abs(z_prog - z_ref))) < tol
    # a bfloat16 trunk misses the same tolerance by orders of magnitude
    low = jax.tree.map(lambda a: a.astype(jnp.bfloat16), w)
    h_low = trunk(low, tokens, conf).astype(jnp.float32)
    assert float(jnp.max(jnp.abs(h_low - h_ref))) > 100 * tol


def _brute_l2s(z, s, words):
    """Screened-head semantics by hand: the best-scoring cluster, then the
    best logit among its candidate words."""
    t = int(np.argmax(s))
    cand = [int(x) for x in words[t] if x < z.shape[0]]
    return cand[int(np.argmax(z[cand]))]


def test_screened_gaps(model_and_weights):
    model, w, conf = model_and_weights
    V, d = conf["vocab_size"], conf["d_model"]
    rng = np.random.default_rng(1)
    v = rng.normal(size=(4, d)).astype(np.float32)
    cand_idx = np.array([[0, 1], [2, 4], [1, 4], [1, 4]], np.int32)
    cand_len = np.array([2, 1, 1, 1], np.int32)
    screen = {"v": v, "cand_idx": cand_idx, "cand_len": cand_len,
              "block": 128}
    ref = ref_of(w, conf, screen, block_rows=2)
    prompts = [rng.integers(0, V, 6), rng.integers(0, V, 9)]
    served = [rng.integers(0, V, 4), rng.integers(0, V, 2)]
    out = ref.run(prompts, served)
    assert out["s"].shape == (6, 4)
    # the token the screened semantics picks has gap 0 on both numbers
    words = reference.candidate_words(cand_idx, cand_len, 128, V)
    h = np.asarray(trunk(w, jnp.asarray(
        np.concatenate([prompts[0], served[0][:-1]])[None]), conf))[0, 5:]
    z = h @ np.asarray(w["w_out"]).T + np.asarray(w["b_out"])
    s = h @ v.T
    picks = np.array([_brute_l2s(z[i], s[i], words) for i in range(4)])
    zp = z[np.arange(4), picks]
    g = ref.gaps({k: a[:4] for k, a in out.items()}, picks, zp)
    assert np.all(g["route_gap"] == 0) and np.allclose(g["token_gap"], 0,
                                                       atol=1e-5)
    # a word no cluster holds (block 3: words 384..511) cannot be served
    outside = np.full(4, 500)
    g = ref.gaps({k: a[:4] for k, a in out.items()}, outside, zp)
    assert np.all(np.isinf(g["route_gap"]))


def test_routing_near_tie_reads_as_a_route_gap(model_and_weights):
    """A context whose two best clusters nearly tie, routed to the second:
    the served token is that cluster's best, and the first cluster holds it
    too but has a better word. The token reads as the small route gap, not
    as the distance to the other cluster's best word."""
    _, w, conf = model_and_weights
    screen = {"v": np.zeros((2, conf["d_model"]), np.float32),
              "cand_idx": np.array([[0, 0], [0, 1]], np.int32),
              "cand_len": np.array([1, 2], np.int32), "block": 128}
    ref = ref_of(w, conf, screen)
    z_tok = np.array([2.0])
    out = {"s": np.array([[1.0, 1.001]]), "best_c": np.array([[2.0, 2.5]])}
    g = ref.gaps(out, np.array([5]), z_tok)
    assert g["route_gap"][0] == pytest.approx(0.001)
    assert g["token_gap"][0] == 0.0
    # a word only the second cluster holds keeps its token gap
    g = ref.gaps(out, np.array([200]), z_tok)
    assert g["route_gap"][0] == 0.0 and g["token_gap"][0] == 0.5


def test_float8_control_rounds_like_e4m3():
    a = jnp.asarray(np.random.default_rng(3).normal(size=(64, 64)),
                    jnp.float32)
    q = np.asarray(reference.float8_weights(a))
    a = np.asarray(a)
    big = np.abs(a) >= np.abs(a).max() / 16
    rel = np.abs(q - a)[big] / np.abs(a)[big]
    # 3 mantissa bits: at most half a step of 2^-3, far coarser than the
    # 2^-9 of bfloat16
    assert rel.max() <= 2.0 ** -4 * 1.001
    assert rel.max() > 2.0 ** -6
    assert len(np.unique(q[big])) <= 2 * 16 * 8


def test_exact_gap_is_distance_to_the_best(model_and_weights):
    model, w, conf = model_and_weights
    ref = ref_of(w, conf, None, block_rows=1)
    out = ref.run([np.arange(5)], [np.array([7, 8, 9])])
    g = ref.gaps(out, out["zarg"], out["zmax"])
    assert np.all(g["token_gap"] == 0)
    g = ref.gaps(out, out["served"], out["z_served"])
    assert np.allclose(g["token_gap"], out["zmax"] - out["z_served"])
