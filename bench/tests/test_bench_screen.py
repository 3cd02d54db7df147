"""The benchmark's own screen: built from the seed with no program code,
the same shape for every seed, each cluster holding its contexts' labels."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from bench import screen, system, traffic
from bench.tests.helpers import small_conf

LSTM = system.load_family("lstm")


@pytest.fixture(scope="module")
def conf():
    return small_conf()


@pytest.mark.parametrize("seed", [2 ** 31 + 5, 2 ** 40 + 17])
def test_screen_shape_is_fixed(conf, seed):
    w = system.make_weights(conf, seed)
    s = screen.fit(LSTM, w, conf, traffic.Corpus(conf["vocab_size"], seed),
                   seed)
    sc = conf["screen"]
    n_blk = -(-conf["vocab_size"] // sc["block"])
    assert s["v"].shape == (sc["clusters"], conf["d_model"])
    assert s["cand_idx"].shape == (sc["clusters"], sc["budget_blocks"])
    assert np.all(s["cand_len"] == sc["budget_blocks"])
    assert np.all(np.diff(s["cand_idx"], axis=1) > 0)
    assert s["cand_idx"].min() >= 0 and s["cand_idx"].max() < n_blk
    p = screen.program_screen(s, conf["vocab_size"])
    assert p.c_max == sc["budget_blocks"] and p.block == sc["block"]


def test_screen_covers_the_reference_top1(conf):
    """On the contexts it was built from, the routed cluster holds the
    reference's top-1 far more often than a random set of blocks would."""
    seed = 2 ** 31 + 11
    w = system.make_weights(conf, seed)
    corpus = traffic.Corpus(conf["vocab_size"], seed)
    s = screen.fit(LSTM, w, conf, corpus, seed)
    toks = corpus.tokens(traffic.rng_for(seed, 999), 8, 32)
    h = np.asarray(LSTM.trunk(w, jnp.asarray(toks), conf)).reshape(
        -1, conf["d_model"])
    z = h @ np.asarray(w["w_out"]).T + np.asarray(w["b_out"])
    t = np.argmax(h @ s["v"].T, axis=1)
    top_blk = np.argmax(z, axis=1) // s["block"]
    hit = np.mean([b in s["cand_idx"][c] for b, c in zip(top_blk, t)])
    n_blk = -(-conf["vocab_size"] // s["block"])
    assert hit > s["cand_idx"].shape[1] / n_blk + 0.1
