"""The served Pallas kernels compile for a TPU v5e at nmt-deen-lstm widths.

The TPU compiler runs without a chip: each test lowers a kernel entry point
with shapes placed on a described ``v5e:2x2`` device and compiles it, which
refuses what interpret mode cannot see (block tiling, VMEM limits, scalar
stores into vector memory). Widths are the paper's DE→EN model: d=500 and a
25,000-word vocabulary, i.e. 196 blocks of 128; batches are the scheduler's
decode width and a single query. Every compile must contain a Mosaic kernel
(``tpu_custom_call``): interpreted kernels would lower to plain XLA.

The topology is described inside a fixture, never at import, so every test
worker collects the same tests and only the one running this file loads the
TPU compiler.
"""
import os

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
