"""Vocab-sharded decode heads: (W, b) row-partitioned over the "model" axis.

``exact-sharded`` — the exact softmax with the weight matrix sharded over the
vocabulary (Grave et al.'s memory/latency pressure, the standard scaling move
for large-vocab softmax): each shard computes logits for its L/n rows, takes
a shard-LOCAL ``jax.lax.top_k`` of size min(k, L_shard), translates local row
indices to global vocab ids with its shard offset, all-gathers the
k·n_shards candidate (value, id) pairs, and re-top-ks globally. The gather is
shard-major and each shard block arrives sorted descending with ties at
lowest index, so the merged top-k reproduces the single-device
``jax.lax.top_k`` tie convention (lowest global index) bit-for-bit on ids —
the parity suite asserts exactly that.

``screened-sharded`` — the paper's L2S head with each cluster's packed
candidate list split by owning vocab range: ``prepare()`` rebuilds the
candidate tables per shard (LOCAL row indices, sentinel-padded) and places
slab s on the device owning rows [s·L_shard, (s+1)·L_shard). At query time
every shard routes z(h) = argmax_t v_t·h (r·d, replicated — the routing
weights are tiny) but computes candidate logits ONLY for the candidates it
owns; the same local-top-k → all-gather → re-top-k merge then runs over
candidate ids. Block screens (block > 1) are expanded to word granularity at
prepare() time, which preserves the screened head's semantics exactly.

``prepare()`` owns placement — ``jax.device_put`` with the NamedShardings
from ``repro.launch.sharding.head_shardings`` — and pads the vocab up to a
multiple of the shard count with −inf-bias rows that can never win top-k.
``flops_per_query`` reports PER-SHARD cost (the wall-clock-relevant number
once shards run in parallel; see benchmarks/README.md for how to compare it
against unsharded heads).
"""
from __future__ import annotations

from functools import lru_cache, partial
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.screening import ScreenParams, assign_clusters
from repro.heads.base import (NEG_INF, SoftmaxHead, require_screen,
                              sample_from_logits)
from repro.kernels.fused_topk import fused_screened_topk
from repro.kernels.screen import V_BLK
from repro.launch.mesh import make_test_mesh
from repro.launch.sharding import head_shardings


# -- merge primitives (pure jnp, shared by the shard_map bodies and the
#    hypothesis property test) ----------------------------------------------

def merge_shard_topk(vals, ids, k: int, sentinel: int):
    """Global top-k over gathered per-shard candidates.

    ``vals``/``ids`` are (B, n_shards·kk), SHARD-MAJOR: shard 0's local top-kk
    block first, each block sorted descending with ties at lowest local index.
    Because shard s owns a strictly lower vocab range than shard s+1, position
    order in the concatenation equals global-index order among equal values,
    so ``jax.lax.top_k``'s position tie-break reproduces the global
    lowest-index convention. Pads with (−inf, sentinel) when fewer than k
    candidates were gathered."""
    short = k - vals.shape[-1]
    if short > 0:
        vals = jnp.pad(vals, ((0, 0), (0, short)), constant_values=NEG_INF)
        ids = jnp.pad(ids, ((0, 0), (0, short)), constant_values=sentinel)
    mvals, pos = jax.lax.top_k(vals, k)
    mids = jnp.take_along_axis(ids, pos, axis=-1)
    return mids.astype(jnp.int32), mvals


def simulate_sharded_topk(logits, n_shards: int, k: int):
    """Single-host reference of the sharded pipeline: chunk the vocab axis,
    per-chunk local top-min(k, L_shard), offset-translate, shard-major
    concat, merge. Must equal ``jax.lax.top_k(logits, k)`` for every
    (logits, n_shards, k ≤ L) — the hypothesis property test asserts it."""
    B, L = logits.shape
    Ls = -(-L // n_shards)
    pad = n_shards * Ls - L
    lp = jnp.pad(logits, ((0, 0), (0, pad)), constant_values=NEG_INF)
    kk = min(k, Ls)
    vals_blocks, ids_blocks = [], []
    for s in range(n_shards):
        v, i = jax.lax.top_k(lp[:, s * Ls:(s + 1) * Ls], kk)
        vals_blocks.append(v)
        ids_blocks.append(i + s * Ls)
    return merge_shard_topk(jnp.concatenate(vals_blocks, axis=-1),
                            jnp.concatenate(ids_blocks, axis=-1),
                            k, sentinel=L)


def _resharded(x, sharding):
    """Place x under ``sharding`` — device_put outside jit, a sharding
    constraint when tracing inside the engine's composed decode step."""
    if isinstance(x, jax.core.Tracer):
        return jax.lax.with_sharding_constraint(x, sharding)
    return jax.device_put(x, sharding)


# -- shard_map-internal collectives shared by both head impls ---------------

def _global_lse(logits):
    """logsumexp over vocab sharded on "model": local max/sum-exp, pmax/psum.
    Padding contributes exp(−inf − m) = 0."""
    m = jax.lax.pmax(jnp.max(logits, axis=1), "model")
    s = jax.lax.psum(jnp.sum(jnp.exp(logits - m[:, None]), axis=1), "model")
    return m + jnp.log(s)


def _local_topk_gather(logits, gids, k: int, L: int):
    """Shard-local top-min(k, width) over (logits, global ids), all-gather
    shard-major, global re-top-k — the one merge both heads run."""
    vals, pos = jax.lax.top_k(logits, min(k, logits.shape[-1]))
    ids = jnp.take_along_axis(gids, pos, axis=-1)
    vals = jax.lax.all_gather(vals, "model", axis=1, tiled=True)
    ids = jax.lax.all_gather(ids, "model", axis=1, tiled=True)
    return merge_shard_topk(vals, ids, k, sentinel=L)


def _combine_shard_logz(lz):
    """(B,) per-shard candidate logZ → global log Σ_s exp(lz_s), −inf-safe:
    a shard with no candidates reports −∞ and contributes nothing; ALL
    shards empty yields −∞ (probability 0), never NaN."""
    m = jax.lax.pmax(lz, "model")
    sub = jnp.where(jnp.isfinite(m), lz - m, -jnp.inf)
    return m + jnp.log(jax.lax.psum(jnp.exp(sub), "model"))


# -- exact-sharded -----------------------------------------------------------

@lru_cache(maxsize=None)
def _exact_impl(mesh, L: int):
    """Jitted shard_map closures for one (mesh, vocab) geometry — cached at
    module level so head instances sharing a mesh share compilations."""
    wspec, bspec, rspec = P("model", None), P("model"), P(None, None)

    def local_logits(W, b, h):
        return (jnp.einsum("bd,vd->bv", h, W) + b).astype(jnp.float32)

    def global_row_ids(logits):
        Ls = logits.shape[-1]
        offset = jax.lax.axis_index("model") * Ls
        return jnp.broadcast_to(jnp.arange(Ls) + offset, logits.shape)

    def topk_body(W, b, h, k):
        logits = local_logits(W, b, h)                       # (B, Ls)
        return _local_topk_gather(logits, global_row_ids(logits), k, L)

    def topk_logprobs_body(W, b, h, k):
        logits = local_logits(W, b, h)
        z = _global_lse(logits)
        ids, mvals = _local_topk_gather(logits, global_row_ids(logits), k, L)
        return ids, mvals - z[:, None]

    def full_logits_body(W, b, h):
        return jax.lax.all_gather(local_logits(W, b, h), "model", axis=1,
                                  tiled=True)                # (B, Lp)

    def smap(body, n_out=2):
        outs = tuple([rspec] * n_out) if n_out > 1 else rspec
        return jax.shard_map(body, mesh=mesh, in_specs=(wspec, bspec, rspec),
                             out_specs=outs, check_vma=False)

    @partial(jax.jit, static_argnames="k")
    def topk(W, b, h, k):
        return smap(partial(topk_body, k=k))(W, b, h)

    @partial(jax.jit, static_argnames="k")
    def topk_logprobs(W, b, h, k):
        return smap(partial(topk_logprobs_body, k=k))(W, b, h)

    @jax.jit
    def full_logits(W, b, h):
        return smap(full_logits_body, n_out=1)(W, b, h)[:, :L]

    return SimpleNamespace(topk=topk, topk_logprobs=topk_logprobs,
                           full_logits=full_logits)


class ExactShardedHead(SoftmaxHead):
    """Exact softmax over a vocab-partitioned (W, b): per-shard local top-k,
    shard-offset id translation, all-gather, global re-top-k."""
    name = "exact-sharded"

    def __init__(self, W, b, mesh=None, n_shards: int = None):
        self._W0 = np.asarray(W, np.float32)
        self._b0 = np.asarray(b, np.float32)
        self._shape = self._W0.shape
        self._mesh_arg, self._n_shards_arg = mesh, n_shards
        self.mesh = None

    def prepare(self) -> "ExactShardedHead":
        if self.mesh is not None:
            return self
        mesh = self._mesh_arg if self._mesh_arg is not None else \
            make_test_mesh(self._n_shards_arg)
        n = mesh.shape["model"]
        L, d = self._shape
        Ls = -(-L // n)
        pad = n * Ls - L
        # padded rows: zero weights + −inf bias — unreachable by top-k/sample
        Wp = np.pad(self._W0, ((0, pad), (0, 0)))
        bp = np.pad(self._b0, (0, pad), constant_values=NEG_INF)
        sh = head_shardings(mesh)
        self.Wp = jax.device_put(jnp.asarray(Wp), sh["W"])
        self.bp = jax.device_put(jnp.asarray(bp), sh["b"])
        self._W0 = self._b0 = None      # only the sharded copy stays resident
        self._repl = sh["replicated"]
        self.mesh, self.L = mesh, L
        self._fns = _exact_impl(mesh, L)
        return self

    def topk(self, h, k: int):
        self.prepare()
        h = _resharded(jnp.asarray(h), self._repl)
        return self._fns.topk(self.Wp, self.bp, h, k=k)

    def topk_logprobs(self, h, k: int):
        self.prepare()
        h = _resharded(jnp.asarray(h), self._repl)
        return self._fns.topk_logprobs(self.Wp, self.bp, h, k=k)

    def sample(self, key, h, temperature: float = 1.0, top_p: float = 1.0):
        self.prepare()
        h = _resharded(jnp.asarray(h), self._repl)
        logits = self._fns.full_logits(self.Wp, self.bp, h)
        return sample_from_logits(key, logits, temperature, top_p)

    @property
    def flops_per_query(self) -> float:
        """PER-SHARD MACs: each shard multiplies its L/n rows; the k·n merge
        is O(k·n·log) comparisons, not MACs."""
        L, d = self._shape
        n = self.mesh.shape["model"] if self.mesh is not None else \
            (self._n_shards_arg or 1)
        return float(-(-L // n) * d)

    @property
    def bytes_per_query(self) -> float:
        """PER-SHARD HBM bytes: this shard's L/n weight rows streamed once
        plus its local logit row written back for the local top-k."""
        L, d = self._shape
        n = self.mesh.shape["model"] if self.mesh is not None else \
            (self._n_shards_arg or 1)
        Ls = -(-L // n)
        return float((Ls * d + 2 * Ls) * 4)

    @property
    def memory_bytes(self) -> int:
        """Device-resident shard tables only (the host staging copy is
        dropped at prepare()); total across shards."""
        if self.mesh is None:
            return int(self._W0.nbytes + self._b0.nbytes)
        return int(self.Wp.nbytes + self.bp.nbytes)


# -- screened-sharded --------------------------------------------------------

@lru_cache(maxsize=None)
def _screened_impl(mesh, L: int):
    """Jitted shard_map closures for the routed candidate pipeline."""
    wspec, bspec = P("model", None), P("model")
    cspec, rspec = P("model", None, None), P(None, None)

    def local_candidate_logits(W, b, v, cand, h):
        """Each shard scores only the candidates it OWNS: (logits, global
        word ids) over its (r, Cs) local candidate slab, −inf/sentinel-L at
        padding."""
        Ls = W.shape[0]
        cluster = assign_clusters(v, h)                      # (B,) replicated
        items = cand[0][cluster]                             # (B, Cs) local ids
        valid = items < Ls
        safe = jnp.where(valid, items, 0)
        logits = (jnp.einsum("bcd,bd->bc", W[safe], h) +
                  b[safe]).astype(jnp.float32)
        logits = jnp.where(valid, logits, NEG_INF)
        offset = jax.lax.axis_index("model") * Ls
        gids = jnp.where(valid, items + offset, L)
        return logits, gids

    def topk_body(W, b, v, cand, h, k):
        logits, gids = local_candidate_logits(W, b, v, cand, h)
        return _local_topk_gather(logits, gids, k, L)

    def topk_logprobs_body(W, b, v, cand, h, k):
        logits, gids = local_candidate_logits(W, b, v, cand, h)
        # log-softmax over the cluster's ENTIRE candidate set (paper §4.2),
        # assembled from per-shard pieces; an all-empty candidate union is
        # probability 0 (NEG_INF), matching the local="pallas" path's
        # −inf-safe contract so the backend knob never changes semantics
        z = _global_lse(logits)
        mids, mvals = _local_topk_gather(logits, gids, k, L)
        lp = jnp.where((z <= NEG_INF / 2)[:, None], NEG_INF,
                       mvals - z[:, None])
        return mids, lp

    def gather_body(W, b, v, cand, h):
        logits, gids = local_candidate_logits(W, b, v, cand, h)
        return (jax.lax.all_gather(logits, "model", axis=1, tiled=True),
                jax.lax.all_gather(gids, "model", axis=1, tiled=True))

    def smap(body):
        return jax.shard_map(body, mesh=mesh,
                             in_specs=(wspec, bspec, rspec, cspec, rspec),
                             out_specs=(rspec, rspec), check_vma=False)

    @partial(jax.jit, static_argnames="k")
    def topk(W, b, v, cand, h, k):
        return smap(partial(topk_body, k=k))(W, b, v, cand, h)

    @partial(jax.jit, static_argnames="k")
    def topk_logprobs(W, b, v, cand, h, k):
        return smap(partial(topk_logprobs_body, k=k))(W, b, v, cand, h)

    @jax.jit
    def candidate_logits(W, b, v, cand, h):
        return smap(gather_body)(W, b, v, cand, h)

    return SimpleNamespace(topk=topk, topk_logprobs=topk_logprobs,
                           candidate_logits=candidate_logits)


@lru_cache(maxsize=None)
def _screened_pallas_impl(mesh, L: int, Ls: int):
    """Jitted shard_map closures for the FUSED-Pallas local candidate path
    (``local="pallas"``): each shard reshapes its (Ls, d) weight rows into
    MXU tiles — zero-copy, Ls is a V_BLK multiple by construction — and
    runs the fused in-VMEM subset-softmax kernel over exactly the candidate
    BLOCKS it owns, so the shard-local §4.2 reduction (sentinel masking,
    top-k, log-sum-exp) happens on-chip and only (B, k) + (B,) cross the
    collective. The merge is the same shard-major all-gather → re-top-k as
    the word path, so ids keep the global lowest-index tie convention."""
    wspec, bspec = P("model", None), P("model")
    cspec, rspec = P("model", None, None), P(None, None)
    nb = Ls // V_BLK

    def local_fused(W, b, v, candb, h, k):
        """(per-shard) fused kernel over the local block slab → shard-local
        top-k (global word ids) + shard-local candidate logZ."""
        d = W.shape[1]
        cluster = assign_clusters(v, h)                  # (B,) replicated
        block_ids = candb[0][cluster]                    # (B, Kb) local blocks
        kk = min(k, block_ids.shape[-1] * V_BLK)
        lids, vals, logz = fused_screened_topk(
            W.reshape(nb, V_BLK, d), b.reshape(nb, V_BLK), h, block_ids,
            k=kk)
        offset = jax.lax.axis_index("model") * Ls
        gids = jnp.where(lids < Ls, lids + offset, L)    # kernel sentinel = Ls
        return vals, gids, logz

    def gather_merge(vals, gids, k):
        vals = jax.lax.all_gather(vals, "model", axis=1, tiled=True)
        gids = jax.lax.all_gather(gids, "model", axis=1, tiled=True)
        return merge_shard_topk(vals, gids, k, sentinel=L)

    def topk_body(W, b, v, candb, h, k):
        vals, gids, _ = local_fused(W, b, v, candb, h, k)
        return gather_merge(vals, gids, k)

    def topk_logprobs_body(W, b, v, candb, h, k):
        vals, gids, logz = local_fused(W, b, v, candb, h, k)
        z = _combine_shard_logz(logz)
        mids, mvals = gather_merge(vals, gids, k)
        lp = jnp.where(jnp.isfinite(z)[:, None], mvals - z[:, None], NEG_INF)
        return mids, lp

    def smap(body):
        return jax.shard_map(body, mesh=mesh,
                             in_specs=(wspec, bspec, rspec, cspec, rspec),
                             out_specs=(rspec, rspec), check_vma=False)

    @partial(jax.jit, static_argnames="k")
    def topk(W, b, v, candb, h, k):
        return smap(partial(topk_body, k=k))(W, b, v, candb, h)

    @partial(jax.jit, static_argnames="k")
    def topk_logprobs(W, b, v, candb, h, k):
        return smap(partial(topk_logprobs_body, k=k))(W, b, v, candb, h)

    return SimpleNamespace(topk=topk, topk_logprobs=topk_logprobs)


class ScreenedShardedHead(SoftmaxHead):
    """L2S screening with vocab-partitioned weights AND candidate tables:
    cluster candidates live on the shard owning their vocab range, so each
    shard's gather-matmul touches only local rows.

    ``local`` selects the shard-local scoring backend:
      "jnp"     (default) word-granular gather-einsum + local top-k
      "pallas"  the fused in-VMEM subset-softmax kernel over the candidate
                BLOCKS each shard owns (requires a block == V_BLK screen;
                shards pad their vocab range up to a V_BLK multiple so
                global blocks never straddle shards). topk/topk_logprobs
                reduce on-chip per shard; sampling keeps the word-granular
                gather path (it needs the full local distribution)."""
    name = "screened-sharded"

    def __init__(self, W, b, screen: ScreenParams, mesh=None,
                 n_shards: int = None, local: str = "jnp"):
        require_screen(screen, "ScreenedShardedHead")
        if local not in ("jnp", "pallas"):
            raise ValueError(f"local must be 'jnp' or 'pallas', got {local!r}")
        if local == "pallas":
            assert screen.block == V_BLK, (
                f"local='pallas' needs a {V_BLK}-word block-candidate screen "
                f"(got block={getattr(screen, 'block', None)}); fit with "
                f"L2SConfig(vocab_block={V_BLK})")
        self._W0 = np.asarray(W, np.float32)
        self._b0 = np.asarray(b, np.float32)
        self._shape = self._W0.shape
        self.screen = screen
        self.local = local
        self._mesh_arg, self._n_shards_arg = mesh, n_shards
        self.mesh = None

    def prepare(self) -> "ScreenedShardedHead":
        if self.mesh is not None:
            return self
        mesh = self._mesh_arg if self._mesh_arg is not None else \
            make_test_mesh(self._n_shards_arg)
        n = mesh.shape["model"]
        L, d = self._shape
        Ls = -(-L // n)
        if self.local == "pallas":
            # shard width up to a V_BLK multiple: global candidate blocks
            # then land wholly on one shard and the per-shard (Ls, d) rows
            # reshape zero-copy into (Ls/V_BLK, V_BLK, d) MXU tiles
            Ls = -(-Ls // V_BLK) * V_BLK
        pad = n * Ls - L
        Wp = np.pad(self._W0, ((0, pad), (0, 0)))
        bp = np.pad(self._b0, (0, pad), constant_values=NEG_INF)

        # split each cluster's candidate words by owning shard; store LOCAL
        # row indices, sentinel Ls past the end. Block screens expand to word
        # granularity (same candidate word set → same semantics).
        cand = np.asarray(self.screen.cand_idx)
        lens = np.asarray(self.screen.cand_len)
        blk = self.screen.block
        r = cand.shape[0]
        per_cluster = []
        for t in range(r):
            items = cand[t, :lens[t]].astype(np.int64)
            words = items if blk == 1 else \
                (items[:, None] * blk + np.arange(blk)).reshape(-1)
            words = np.sort(words[words < L])
            per_cluster.append(words)
        counts = [[int(((w >= s * Ls) & (w < (s + 1) * Ls)).sum())
                   for w in per_cluster] for s in range(n)]
        Cs = max(1, max(max(c) for c in counts))
        Cs = -(-Cs // 8) * 8
        table = np.full((n, r, Cs), Ls, np.int32)
        for s in range(n):
            for t, w in enumerate(per_cluster):
                local = w[(w >= s * Ls) & (w < (s + 1) * Ls)] - s * Ls
                table[s, t, :len(local)] = local

        sh = head_shardings(mesh)
        self.Wp = jax.device_put(jnp.asarray(Wp), sh["W"])
        self.bp = jax.device_put(jnp.asarray(bp), sh["b"])
        self.cand_local = jax.device_put(jnp.asarray(table), sh["cand"])
        self.v = jax.device_put(jnp.asarray(self.screen.v), sh["replicated"])
        self._W0 = self._b0 = None      # only the sharded copy stays resident
        self._repl = sh["replicated"]
        self.mesh, self.L, self.Ls, self.c_shard_max = mesh, L, Ls, Cs
        self._fns = _screened_impl(mesh, L)

        if self.local == "pallas":
            # per-shard candidate BLOCK slabs: cand_idx already holds global
            # block ids (block == V_BLK) and Ls % V_BLK == 0, so block g
            # belongs wholly to shard g // (Ls/V_BLK); store LOCAL block
            # ids ascending (preserves the global tie order through the
            # shard-major merge), sentinel nbs past the end
            nbs = Ls // V_BLK
            blocks_per_cluster = [np.sort(cand[t, :lens[t]].astype(np.int64))
                                  for t in range(r)]
            kb = max(1, max((int(((g >= s * nbs) & (g < (s + 1) * nbs)).sum())
                             for g in blocks_per_cluster
                             for s in range(n)), default=1))
            btab = np.full((n, r, kb), nbs, np.int32)
            for s in range(n):
                for t, g in enumerate(blocks_per_cluster):
                    loc = g[(g >= s * nbs) & (g < (s + 1) * nbs)] - s * nbs
                    btab[s, t, :len(loc)] = loc
            self.cand_blocks = jax.device_put(jnp.asarray(btab), sh["cand"])
            self.kb_shard_max = kb
            self._pallas_fns = _screened_pallas_impl(mesh, L, Ls)
        return self

    def topk(self, h, k: int):
        self.prepare()
        h = _resharded(jnp.asarray(h), self._repl)
        if self.local == "pallas":
            return self._pallas_fns.topk(self.Wp, self.bp, self.v,
                                         self.cand_blocks, h, k=k)
        return self._fns.topk(self.Wp, self.bp, self.v, self.cand_local, h,
                              k=k)

    def topk_logprobs(self, h, k: int):
        self.prepare()
        h = _resharded(jnp.asarray(h), self._repl)
        if self.local == "pallas":
            return self._pallas_fns.topk_logprobs(self.Wp, self.bp, self.v,
                                                  self.cand_blocks, h, k=k)
        return self._fns.topk_logprobs(self.Wp, self.bp, self.v,
                                       self.cand_local, h, k=k)

    def sample(self, key, h, temperature: float = 1.0, top_p: float = 1.0):
        """Sample within the routed candidate set (probability 0 elsewhere):
        gather the per-shard candidate logits, then temperature/nucleus."""
        self.prepare()
        h = _resharded(jnp.asarray(h), self._repl)
        logits, gids = self._fns.candidate_logits(self.Wp, self.bp, self.v,
                                                  self.cand_local, h)
        choice = sample_from_logits(key, logits, temperature, top_p)
        return jnp.take_along_axis(gids, choice[:, None],
                                   axis=-1)[:, 0].astype(jnp.int32)

    @property
    def flops_per_query(self) -> float:
        """PER-SHARD MACs: routing is replicated (every shard pays r·d); the
        mean candidate matmul splits 1/n_shards per shard."""
        d = self._shape[1]
        lbar = float(np.mean(np.asarray(self.screen.cand_len))) * \
            self.screen.block
        n = self.mesh.shape["model"] if self.mesh is not None else \
            (self._n_shards_arg or 1)
        return float((self.screen.r + lbar / n) * d)

    @property
    def bytes_per_query(self) -> float:
        """PER-SHARD HBM bytes (mirrors ``flops_per_query``): the replicated
        router plus this shard's 1/n slice of the mean candidate tiles,
        plus the local writeback — the (Cs) candidate-logit slab for the
        jnp path, only the O(V_BLK) fused-kernel results for ``pallas``."""
        d = self._shape[1]
        lbar = float(np.mean(np.asarray(self.screen.cand_len))) * \
            self.screen.block
        n = self.mesh.shape["model"] if self.mesh is not None else \
            (self._n_shards_arg or 1)
        if self.local == "pallas":
            writeback = float(V_BLK)
        else:
            writeback = float(getattr(self, "c_shard_max",
                                      self.screen.c_max * self.screen.block))
        return float(((self.screen.r + lbar / n) * d + 2 * writeback) * 4)

    @property
    def memory_bytes(self) -> int:
        """Device-resident shard tables (weights + per-shard candidate
        slabs + replicated router), total across shards — NOT the retained
        host screen, which would double-count the candidate structure."""
        if self.mesh is None:
            return int(self._W0.nbytes + self._b0.nbytes)
        total = int(self.Wp.nbytes + self.bp.nbytes +
                    self.cand_local.nbytes + self.v.nbytes)
        if self.local == "pallas":
            total += int(self.cand_blocks.nbytes)
        return total
