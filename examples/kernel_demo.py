"""Pallas kernel demo: the TPU-adapted screened softmax hot path
(cluster_route → scalar-prefetch block gather-matmul → subset top-k) behind
the ``SoftmaxHead`` protocol, validated against the pure-jnp reference head
(kernels compiled on a TPU, interpreted elsewhere).

Run: PYTHONPATH=src python examples/kernel_demo.py
"""
import jax.numpy as jnp
import numpy as np

from repro import heads
from repro.core.screening import ScreenParams
from repro.kernels.ref import cluster_route_ref
from repro.kernels.route import cluster_route_pallas

rng = np.random.default_rng(0)
L, d, r, K, B = 16_384, 512, 64, 8, 32          # vocab, dim, clusters, blocks
print(f"softmax head: vocab={L}, d={d} | screen: r={r}, {K} blocks/cluster")

W = jnp.asarray(rng.standard_normal((L, d)), jnp.float32)
b = jnp.asarray(rng.standard_normal((L,)) * 0.1, jnp.float32)
v = jnp.asarray(rng.standard_normal((r, d)), jnp.float32)
cand = jnp.asarray(rng.integers(0, -(-L // 128), (r, K)), jnp.int32)
h = jnp.asarray(rng.standard_normal((B, d)), jnp.float32)
sp = ScreenParams(v=v, cand_idx=cand,
                  cand_len=jnp.full((r,), K, jnp.int32), vocab_size=L,
                  block=128)

# one registry, two backends over the same screen
kern = heads.get("screened-pallas", W=W, b=b, screen=sp)   # compiled on TPU
ref = heads.get("screened", W=W, b=b, screen=sp)           # pure jnp
print(f"packed head: {kern.packed_shape} — {kern.packed_nbytes/1e6:.0f} MB "
      "in MXU vocab blocks (prepare() ran once)")

ids, vals = kern.topk(h, 5)
route = cluster_route_pallas(h, v)
assert bool(jnp.all(route == cluster_route_ref(h, v)))

ids_ref, vals_ref = ref.topk(h, 5)
assert bool(jnp.all(ids == ids_ref)), "kernel head != reference head"
print("kernel head == jnp reference head on all", B, "queries  ✓")
print("per-query compute (flops_per_query): full softmax",
      f"{heads.get('exact', W=W, b=b).flops_per_query:.0f}",
      "vs screened", f"{kern.flops_per_query:.0f}",
      f"({heads.get('exact', W=W, b=b).flops_per_query / kern.flops_per_query:.1f}x fewer)")
