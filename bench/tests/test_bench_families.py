"""Model families, found by the configuration's ``family``: the LSTM's
weights are those the benchmark has always served, a family that is one
new file runs through the screen, the check and the work count, and the
check's head runs in chunks of rows under its cap."""
from __future__ import annotations

import hashlib
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, screen, system, traffic, work
from bench.tests.helpers import small_conf

INTERFACE = ("init_weights", "program_params", "trunk", "lower",
             "trunk_flops", "checked_keys")


def digest(tree) -> str:
    m = hashlib.sha256()
    for a in jax.tree.leaves(tree):
        a = np.asarray(a)
        m.update(str(a.dtype).encode())
        m.update(str(a.shape).encode())
        m.update(np.ascontiguousarray(a).tobytes())
    return m.hexdigest()[:16]


def test_loader_finds_the_family_by_name():
    for name in ("nmt-deen-lstm", "ptb-large-lstm"):
        conf = system.load_config(name)
        fam = system.load_family(conf["family"])
        assert Path(fam.__file__) == system.FAMILY_DIR / "lstm.py"
        assert all(hasattr(fam, k) for k in INTERFACE)


def test_unknown_family_is_refused_by_name():
    with pytest.raises(ValueError, match="no_such_family"):
        system.load_family("no_such_family")
    conf = dict(small_conf(), family="no_such_family")
    with pytest.raises(ValueError, match="no_such_family"):
        system.make_weights(conf, 1)


# digests of the weights ``reference.init_weights`` made before the LSTM
# moved into its family module, at the reduced DE-EN size
@pytest.mark.parametrize("seed,want", [(2 ** 31 + 99, "a81046698a58d6e4"),
                                       (2 ** 40 + 17, "42faed08155e45a7")])
def test_lstm_weights_are_unchanged(seed, want):
    assert digest(system.make_weights(small_conf(), seed)) == want


@pytest.fixture
def rnn(monkeypatch):
    """The test-only ``tanh_rnn`` family, found by name like any other."""
    monkeypatch.setattr(system, "FAMILY_DIR",
                        Path(__file__).resolve().parent / "families")
    conf = small_conf()
    conf.update(family="tanh_rnn", d_model=64)
    return system.load_family("tanh_rnn"), conf


def test_a_new_family_is_one_file(rnn):
    fam, conf = rnn
    seed = 2 ** 31 + 21
    w = system.make_weights(conf, seed)
    assert "b_out" not in w and w["w_out"].shape == (512, 64)
    s = screen.fit(fam, w, conf, traffic.Corpus(512, seed), seed)
    assert s["v"].shape == (conf["screen"]["clusters"], 64)
    ref = reference.Reference(fam, conf, w, s, 512, block_rows=2)
    prompts, served = [np.arange(3, 9), np.arange(20, 24)], \
        [np.array([5, 6, 7]), np.array([1, 2])]
    out = ref.run(prompts, served, control="float8")
    # the screened semantics by hand: route, then the best candidate
    h = np.asarray(fam.trunk(w, jnp.asarray(
        np.concatenate([prompts[0], served[0][:-1]])[None]), conf))[0, 5:]
    z = h @ np.asarray(w["w_out"]).T
    words = reference.candidate_words(s["cand_idx"], s["cand_len"],
                                      s["block"], 512)
    t = np.argmax(h @ s["v"].T, axis=1)
    picks = np.array([words[c][np.argmax(z[i, words[c]])]
                      for i, c in enumerate(t)])
    g = ref.gaps({k: a[:3] for k, a in out.items()}, picks,
                 z[np.arange(3), picks])
    assert np.all(g["route_gap"] == 0)
    assert np.allclose(g["token_gap"], 0, atol=1e-5)
    assert np.all(np.isfinite(ref.gaps(out, out["ctl_tok"],
                                       out["z_ctl"])["token_gap"]))
    f = work.model_flops(fam, conf, 10.0, np.arange(6), np.array([6, 7]), 3)
    assert f == 8 * 2 * 2 * 64 ** 2 + 3 * 2 * 10.0


@pytest.mark.parametrize("control", [None, "float8"])
def test_chunked_head_reads_as_one_chunk(control):
    conf = small_conf()
    seed = 2 ** 31 + 3
    w = system.make_weights(conf, seed)
    fam = system.load_family("lstm")
    s = screen.fit(fam, w, conf, traffic.Corpus(512, seed), seed)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 512, n) for n in (5, 9, 12)]
    served = [rng.integers(0, 512, n) for n in (3, 6, 2)]
    one = reference.Reference(fam, conf, w, s, 512, block_rows=2,
                              seq_len=16)
    # 7 rows a chunk: 32 rows a block in 5 chunks, the last one padded
    cut = reference.Reference(fam, conf, w, s, 512, block_rows=2,
                              seq_len=16, chunk_bytes=7 * 4 * 513)
    assert one.rows >= 32 and cut.rows == 7
    a, b = one.run(prompts, served, control), cut.run(prompts, served,
                                                      control)
    assert a.keys() == b.keys()
    for k in a:
        np.testing.assert_allclose(a[k], b[k], rtol=1e-6, atol=0, err_msg=k)


@pytest.mark.parametrize("vocab,rows_a_block", [
    (25_000, 32 * 96),        # DE-EN: 32 sequences of 96 positions
    (10_000, 32 * 72),        # PTB-Large: 32 of 72
    (200_192, 0),             # a vocabulary of 2·10^5
])
def test_chunk_respects_the_cap(vocab, rows_a_block):
    rows = reference.chunk_rows(vocab)
    assert 0 < rows * 4 * (vocab + 1) <= reference.HEAD_CHUNK_BYTES
    assert (rows + 1) * 4 * (vocab + 1) > reference.HEAD_CHUNK_BYTES
    # both LSTM cells' blocks fit in one chunk: their arithmetic is unchunked
    assert rows >= rows_a_block
