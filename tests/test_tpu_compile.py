"""The served Pallas kernels compile for a TPU v5e at nmt-deen-lstm widths,
the paged LSTM join at ptb-large-lstm widths, and the join with its first
token on the Pallas head at nmt-deen-lstm widths.

The TPU compiler runs without a chip: each test lowers a kernel entry point
with shapes placed on a described ``v5e:2x2`` device and compiles it, which
refuses what interpret mode cannot see (block tiling, VMEM limits, scalar
stores into vector memory). Kernel widths are the paper's DE→EN model: d=500
and a 25,000-word vocabulary, i.e. 196 blocks of 128; batches are the
scheduler's decode width and a single query. Every kernel compile must
contain a Mosaic kernel (``tpu_custom_call``): interpreted kernels would
lower to plain XLA. The head-less join is plain XLA and has none; the join
with its first token on the Pallas head carries the kernel.

The topology is described inside a fixture, never at import, so every test
worker collects the same tests and only the one running this file loads the
TPU compiler.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.launch.serve import MAX_SLOTS

D, VOCAB, R, K_SLOTS, TOPK = 500, 25_000, 50, 8, 5
N_BLK = -(-VOCAB // 128)


@pytest.fixture(scope="module")
def no_persistent_cache():
    """Compiles for a described chip are written to the persistent cache
    but can never be read back without one; keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo(no_persistent_cache):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _head_args(sharding, B):
    """(W_blocks, b_blocks, v, cand_blocks, h) shapes of the packed head."""
    return (_spec((N_BLK, 128, D), sharding), _spec((N_BLK, 128), sharding),
            _spec((R, D), sharding),
            _spec((R, K_SLOTS), sharding, jnp.int32),
            _spec((B, D), sharding))


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("B", [MAX_SLOTS, 1])
def test_fused_topk_compiles(one_chip, B):
    """cluster_route + fused in-VMEM top-k: the screened-pallas decode path."""
    from repro.kernels.ops import screened_fused_topk_tpu
    _assert_kernel(screened_fused_topk_tpu.lower(
        *_head_args(one_chip, B), k=TOPK, interpret=False).compile())


@pytest.mark.parametrize("B", [MAX_SLOTS, 1])
def test_fused_gumbel_sample_compiles(one_chip, B):
    """The same kernel with Gumbel noise: the screened-pallas sampling path."""
    from repro.kernels.ops import screened_fused_sample_tpu
    key = _spec((2,), one_chip, jnp.uint32)
    _assert_kernel(screened_fused_sample_tpu.lower(
        *_head_args(one_chip, B), key, temperature=0.7,
        interpret=False).compile())


@pytest.mark.parametrize("B", [MAX_SLOTS, 1])
def test_screened_logits_compiles(one_chip, B):
    """cluster_route + the unfused gather-matmul (nucleus sampling path)."""
    from repro.kernels.ops import screened_topk_tpu
    _assert_kernel(screened_topk_tpu.lower(
        *_head_args(one_chip, B), k=TOPK, interpret=False).compile())


def test_sharded_fused_local_compiles(topo, monkeypatch):
    """screened-sharded (local="pallas") over the 4-way "model" mesh: each
    shard runs the fused kernel over its 49 blocks, then all-gathers."""
    import repro.kernels.fused_topk as fused_topk
    from repro.heads.sharded import _screened_pallas_impl
    # the shard_map body resolves the kernel mode from this process's CPU
    # backend; the target here is the chip
    monkeypatch.setattr(fused_topk, "interpret_mode",
                        lambda interpret=None: False)
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4), ("data", "model"))
    Ls = -(-(-(-VOCAB // 4)) // 128) * 128

    def on(*spec):
        return NamedSharding(mesh, P(*spec))

    fns = _screened_pallas_impl(mesh, VOCAB, Ls)
    compiled = fns.topk.lower(
        _spec((4 * Ls, D), on("model", None)), _spec((4 * Ls,), on("model")),
        _spec((R, D), on(None, None)),
        _spec((4, R, K_SLOTS), on("model", None, None), jnp.int32),
        _spec((MAX_SLOTS, D), on(None, None)), k=TOPK).compile()
    _assert_kernel(compiled)
    assert "all-gather" in compiled.as_text()


@pytest.mark.parametrize("bucket", [8, 64])
def test_lstm_join_compiles(one_chip, bucket):
    """The paged LSTM join — suffix prefill over one page-grid bucket, the
    page snapshots, the splice into a stream cache of the scheduler's
    width — compiles as one executable at the PTB-Large widths (d=1500,
    V=10,000), with pages of 8 and a 72-slot cache (9 snapshot rows), and
    runs its steps in one device loop per layer."""
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving.engine import _lstm_join
    model = build_model(get_config("ptb-large-lstm"))
    params = jax.tree.map(lambda s: _spec(s.shape, one_chip, s.dtype),
                          model.init_shapes(jnp.float32))
    layers = model.cfg.num_layers
    d = model.cfg.d_model
    S = 72 // 8
    src = [{k: _spec((S, 1, d), one_chip) for k in "hc"}] * layers
    group = {"lstm": [{k: _spec((MAX_SLOTS, d), one_chip) for k in "hc"}]
             * layers}
    ints = _spec((3 + S + bucket,), one_chip, jnp.int32)
    compiled = jax.jit(
        lambda p, i, s, g: _lstm_join(model, p, i, s, g)).lower(
        params, ints, src, group).compile()
    assert compiled.as_text().count(" while(") >= layers


def test_lstm_join_with_pallas_head_compiles(one_chip, monkeypatch):
    """The paged LSTM join with its first token on ``screened-pallas``, as
    ``DecodeEngine._lstm_join_step`` composes it, compiles as one
    executable at the DE→EN widths (d=500, V=25,000; r=50, 8 blocks a
    cluster) with pages of 8, a 96-slot cache (12 snapshot rows) of the
    closed-loop cell's width 64 and the largest bucket (48): the Mosaic
    kernel lowers inside the join, and the prefill keeps one device loop
    per layer, none of which copies into fast memory on every step. The
    head is built small and traced over full-size state, as the engine
    passes it in."""
    from collections import OrderedDict
    import repro.kernels.fused_topk as fused_topk
    import repro.kernels.route as route
    from repro.configs import get_config
    from repro.core.screening import ScreenParams
    from repro.heads.pallas import ScreenedPallasHead
    from repro.models import build_model
    from repro.serving.engine import DecodeEngine
    # the kernels resolve their mode from this process's CPU backend; the
    # target here is the chip
    for mod in (fused_topk, route):
        monkeypatch.setattr(mod, "interpret_mode",
                            lambda interpret=None: False)
    model = build_model(get_config("nmt-deen-lstm"))
    layers, d = model.cfg.num_layers, model.cfg.d_model
    assert (d, model.cfg.vocab_size) == (D, VOCAB)
    params = jax.tree.map(lambda s: _spec(s.shape, one_chip, s.dtype),
                          model.init_shapes(jnp.float32))
    small = ScreenParams(v=jnp.zeros((R, D)),
                         cand_idx=jnp.zeros((R, K_SLOTS), jnp.int32),
                         cand_len=jnp.zeros((R,), jnp.int32),
                         vocab_size=VOCAB, block=128)
    head = ScreenedPallasHead(jnp.zeros((128, D)), jnp.zeros((128,)),
                              small).prepare()
    state = {"W": _spec((VOCAB, D), one_chip), "b": _spec((VOCAB,), one_chip),
             "_Wb": _spec((N_BLK, 128, D), one_chip),
             "_bb": _spec((N_BLK, 128), one_chip),
             "screen": ScreenParams(
                 v=_spec((R, D), one_chip),
                 cand_idx=_spec((R, K_SLOTS), one_chip, jnp.int32),
                 cand_len=_spec((R,), one_chip, jnp.int32),
                 vocab_size=VOCAB, block=128)}
    assert set(state) == set(head.device_state())
    eng = DecodeEngine.__new__(DecodeEngine)
    eng.model, eng._step_cache, eng._step_cache_max = model, OrderedDict(), 4
    fn = eng._lstm_join_step(head)
    S, width, bucket = 96 // 8, 64, 48
    src = [{k: _spec((S, 1, d), one_chip) for k in "hc"}] * layers
    group = {"lstm": [{k: _spec((width, d), one_chip) for k in "hc"}]
             * layers}
    ints = _spec((3 + S + bucket,), one_chip, jnp.int32)
    compiled = fn._inner_jit.lower((state,), (params, ints, src, group),
                                   ()).compile()
    _assert_kernel(compiled)
    text = compiled.as_text()
    bodies = re.findall(r"body=%([\w.\-]+)", text)
    assert len(bodies) >= layers
    for name in bodies:
        body = re.search(rf"^%{re.escape(name)} .*?^}}", text,
                         re.S | re.M).group(0)
        assert "copy-start" not in body, f"{name} copies on every step"


def _moonlight_stage():
    """moonlight-16b-a3b-ep8 at its published widths with the dense layer
    and one scanned expert layer (every kind of layer, compiled once)."""
    import dataclasses
    from repro.configs import get_config
    from repro.models import build_model
    return build_model(dataclasses.replace(
        get_config("moonlight-16b-a3b-ep8"), num_layers=2))


@pytest.fixture(scope="module")
def moonlight(one_chip):
    model = _moonlight_stage()
    params = jax.tree.map(lambda s: _spec(s.shape, one_chip, s.dtype),
                          model.init_shapes(jnp.bfloat16))
    pool = {"latent": _spec((2, 6400, 16, model.cfg.mla_latent_dim),
                            one_chip)}
    return model, params, pool


def test_mla_moe_decode_step_compiles(one_chip, moonlight):
    """The moonlight cell's decode step — latent pages of 16 at 128 rows
    of 768 positions, the held experts' grouped products, then the fused
    screened head over bfloat16 tiles (V=163,840, 16 blocks a cluster) —
    compiles as one executable with the Mosaic kernel in it."""
    from repro.kernels.ops import screened_fused_topk_tpu
    model, params, pool = moonlight
    B, d, n_blk = 128, model.cfg.d_model, 163_840 // 128

    def step(p, tok, pool, table, pos, wb, bb, v, cand):
        h, pool = model.decode_step_paged(p, tok, pool, table, pos)
        ids, _, _ = screened_fused_topk_tpu(wb, bb, v, cand, h, k=1,
                                            interpret=False)
        return ids, pool

    compiled = jax.jit(step).lower(
        params, _spec((B,), one_chip, jnp.int32), pool,
        _spec((B, 48), one_chip, jnp.int32), _spec((B,), one_chip, jnp.int32),
        _spec((n_blk, 128, d), one_chip, jnp.bfloat16),
        _spec((n_blk, 128), one_chip, jnp.bfloat16),
        _spec((100, d), one_chip), _spec((100, 16), one_chip, jnp.int32),
    ).compile()
    _assert_kernel(compiled)


def test_mla_moe_bucketed_join_compiles(one_chip, moonlight):
    """One bucketed join of the moonlight cell (512 positions): prefill of
    the padded prompt and the scatter of its rows into the latent pages."""
    from repro.serving.engine import _attn_join
    model, params, pool = moonlight
    compiled = jax.jit(
        lambda p, t, n, pool, dest: _attn_join(model, p, t, n, pool, dest)
    ).lower(params, _spec((1, 512), one_chip, jnp.int32),
            _spec((), one_chip, jnp.int32), pool,
            _spec((2, 512), one_chip, jnp.int32)).compile()
    assert compiled.as_text().count(" while(") >= 1
