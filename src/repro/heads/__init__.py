"""repro.heads — the pluggable decode-head API.

One protocol (``SoftmaxHead``), one registry, every backend:

    from repro import heads
    head = heads.get("screened", W=W, b=b, screen=screen)
    ids, logprobs = head.topk_logprobs(h, k=5)

Registered backends (see each class for the cost model):

  exact           full-vocab softmax                      O(L·d)
  exact-sharded   vocab-sharded exact: per-shard top-k    O(L/n·d) per shard
                  + all-gather merge over a "model" mesh
  screened        L2S route + candidate softmax (jnp)     O((r+L̄)·d)
  screened-sharded L2S with candidate blocks placed on    O((r+L̄/n)·d) per shard
                  the shard owning their vocab range
  screened-pallas L2S on the Pallas TPU kernels           O((r+L̄)·d)
  screened-cpu    L2S per-query numpy (paper timing)      O((r+L̄)·d)
  adaptive        frequency-tiered adaptive softmax       O((F+C+p·T̄)·d)
                  (short-list + lazily-gated rare tails,
                  fused per-tier Pallas top-k)
  adaptive-sharded adaptive with the rare-tail region     O((F+C+p·T̄/n)·d)
                  vocab-range-sharded, short-list         per shard
                  replicated on every shard
  svd             SVD-softmax preview + rerank            O(d·ρ + L·ρ + Ñ·d)
  shortlist       adaptive-softmax frequent shortlist     O((n_head+τ)·d)
  greedy-mips     budgeted per-dimension screening        O(B·d)
  lsh-mips        SimHash bands + bucket rerank           O(bands·bits·d + pool·d)
  pca-mips        PCA-tree leaf + rerank                  O(depth·d + leaf·d)

New heads register with ``heads.register(name, factory)`` where the factory
takes the construction context as kwargs (``W``, ``b``, ``screen``, ...) and
tolerates extras — that single seam is how new approximation methods,
kernels, and per-request policies plug into the engine and benchmarks."""
from repro.heads.base import (NEG_INF, MissingScreenError, SoftmaxHead,
                              adjust_logits, sample_from_logits,
                              screened_flops_per_query,
                              tiered_flops_per_query)
from repro.heads.registry import get, names, register
from repro.heads.exact import ExactHead
from repro.heads.screened import ScreenedHead
from repro.heads.pallas import ScreenedPallasHead
from repro.heads.sharded import ExactShardedHead, ScreenedShardedHead
from repro.heads.adaptive import AdaptiveHead, AdaptiveShardedHead
from repro.heads.adapters import (BaselineHead, GreedyMIPSHead, LSHHead,
                                  PCAHead, ScreenedNumpyHead, ShortlistHead,
                                  SVDHead)

register("exact", lambda W, b, **_: ExactHead(W, b))
register("exact-sharded",
         lambda W, b, mesh=None, n_shards=None, **_:
         ExactShardedHead(W, b, mesh=mesh, n_shards=n_shards))
register("screened", lambda W, b, screen, **_: ScreenedHead(W, b, screen))
register("screened-sharded",
         lambda W, b, screen, mesh=None, n_shards=None, local="jnp", **_:
         ScreenedShardedHead(W, b, screen, mesh=mesh, n_shards=n_shards,
                             local=local))
register("screened-pallas",
         lambda W, b, screen, fused=True, **_:
         ScreenedPallasHead(W, b, screen, fused=fused))
register("screened-cpu",
         lambda W, b, screen, **_: ScreenedNumpyHead(W, b, screen))
register("adaptive",
         lambda W, b, counts=None, shortlist=None, n_tails=4, fused=True,
         **_:
         AdaptiveHead(W, b, counts=counts, shortlist=shortlist,
                      n_tails=n_tails, fused=fused))
register("adaptive-sharded",
         lambda W, b, counts=None, shortlist=None, n_tails=4, mesh=None,
         n_shards=None, **_:
         AdaptiveShardedHead(W, b, counts=counts, shortlist=shortlist,
                             n_tails=n_tails, mesh=mesh, n_shards=n_shards))
register("svd", lambda W, b, rho=16, n_top=None, **_:
         SVDHead(W, b, rho=rho, n_top=n_top))
register("shortlist",
         lambda W, b, freq_order=None, n_head=None, n_tails=4, **_:
         ShortlistHead(W, b, freq_order=freq_order, n_head=n_head,
                       n_tails=n_tails))
register("greedy-mips", lambda W, b, budget=512, **_:
         GreedyMIPSHead(W, b, budget=budget))
register("lsh-mips", lambda W, b, bands=8, bits=10, seed=0, **_:
         LSHHead(W, b, bands=bands, bits=bits, seed=seed))
register("pca-mips", lambda W, b, depth=6, **_: PCAHead(W, b, depth=depth))
