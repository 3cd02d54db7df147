"""Adaptive frequency-tiered softmax heads (Grave et al.'s adaptive softmax
applied to the serving head catalog).

``adaptive`` — the vocabulary is split by unigram frequency into a SHORT-LIST
tier (the top-F words, packed into VMEM-friendly V_BLK tiles and scored for
every query) plus C rare-TAIL clusters, each represented in the short-list
competition by one gate vector (the mean tail weight/bias — a tail cluster's
gate logit upper-bounds nothing but tracks its mass, the standard adaptive-
softmax construction). A query descends into its argmax tail cluster ONLY
when the best gate logit beats the k-th short-list logit — Zipfian traffic
therefore pays O((F + C)·d) almost always and the tail matmul only in
expectation, which is the cost model ``tiered_flops_per_query`` exports for
routing. Both tiers reduce through the existing fused in-VMEM Pallas top-k
kernel (``kernels/fused_topk.py``) over their packed tiles; results merge
with the same (value desc, position asc) convention as the sharded heads and
the per-tier logZ recombines −inf-safely (``combine_tier_logz``), so a
non-descending query's absent tail contributes probability 0, never NaN.

``adaptive-sharded`` — the short-list tier is REPLICATED (every shard scores
the frequent words locally; it is small by construction) while the rare-tail
region row-partitions over the "model" mesh axis by packed vocab range,
reusing the placement machinery from ``heads/sharded.py``
(``adaptive_head_shardings``, per-shard local block tables, shard-major
all-gather → re-top-k merge, ``_combine_shard_logz``). Ids are bit-identical
to the unsharded ``adaptive`` head: the tie order (short tier first, then
tail candidates in packed-row order) survives the shard-major merge exactly
as it does for the screened heads.

Exactness caveat: within ONE tier the reduction is exact, but the tier-gate
is an approximation — a rare word whose cluster gate loses the short-list
competition is simply not scored. ``shortlist=L`` (no tails) degenerates to
the exact head over a frequency-permuted vocabulary.

``prepare()`` owns tier construction from token frequency ``counts``; when
no counts are given the deterministic fallback orders words by weight-row
norm (the same proxy the shortlist baseline adapter uses) and weights the
cost model by a Zipf(1) unigram.
"""
from __future__ import annotations

from functools import lru_cache, partial
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.heads.base import (NEG_INF, SoftmaxHead, sample_from_logits,
                              tiered_bytes_per_query, tiered_flops_per_query)
from repro.heads.sharded import (_combine_shard_logz, _resharded,
                                 merge_shard_topk)
from repro.kernels.fused_topk import fused_screened_topk
from repro.kernels.screen import V_BLK
from repro.launch.mesh import make_test_mesh
from repro.launch.sharding import adaptive_head_shardings


# -- tier layout -------------------------------------------------------------

def _build_tiers(W, b, counts, shortlist, n_tails):
    """Frequency-tiered packed layout.

    Words sort by descending unigram count (stable — ties keep vocab order;
    weight-row norm when ``counts`` is None). The top-F words form the
    short-list tier; the remainder splits into ≤ n_tails contiguous-by-rank
    tail clusters. Each tier pads independently to a V_BLK multiple with
    zero-weight / NEG_INF-bias rows, so packed blocks NEVER straddle tiers
    and each tier's block set feeds the fused kernel directly.

    Returns the packed tiles, the packed-row → vocab-id map (sentinel row =
    L), the per-tier block tables, the tail gate vectors (mean tail
    weight/bias), and the unigram-weighted cost-model statistics
    (``p_descend`` = unigram mass beyond the short-list,
    ``exp_tail_words`` = unigram-weighted mean tail-cluster width)."""
    W = np.asarray(W, np.float32)
    b = np.asarray(b, np.float32)
    L, d = W.shape
    if counts is not None:
        c = np.asarray(counts, np.float64).reshape(-1)
        if c.shape[0] != L:
            raise ValueError(f"counts has {c.shape[0]} entries for a "
                             f"{L}-word vocabulary")
        order = np.argsort(-c, kind="stable")
        mass = c[order]
        unigram = mass / mass.sum() if mass.sum() > 0 else None
    else:
        order = np.argsort(-np.linalg.norm(W, axis=1), kind="stable")
        unigram = None
    if unigram is None:
        # deterministic fallback: Zipf(1) over frequency rank
        z = 1.0 / np.arange(1, L + 1, dtype=np.float64)
        unigram = z / z.sum()

    F = L if shortlist is None else int(shortlist)
    F = max(1, min(L, F))
    tails = [t for t in np.array_split(order[F:], max(1, int(n_tails)))
             if len(t)]
    tiers = [order[:F]] + tails

    rows_w, rows_b, rows_g, tier_nb = [], [], [], []
    for words in tiers:
        nbt = -(-len(words) // V_BLK)
        padn = nbt * V_BLK - len(words)
        rows_w.append(np.pad(W[words], ((0, padn), (0, 0))))
        rows_b.append(np.pad(b[words], (0, padn), constant_values=NEG_INF))
        rows_g.append(np.pad(words.astype(np.int64), (0, padn),
                             constant_values=L))
        tier_nb.append(nbt)
    packed_w = np.concatenate(rows_w, axis=0)
    n_blk = packed_w.shape[0] // V_BLK
    Wblk = packed_w.reshape(n_blk, V_BLK, d)
    bblk = np.concatenate(rows_b).reshape(n_blk, V_BLK)
    # +1: the fused kernel's all-sentinel id n_blk·V_BLK maps to vocab L
    gid = np.append(np.concatenate(rows_g), L).astype(np.int32)

    nb0, C = tier_nb[0], len(tails)
    tail_tab = g = gb = None
    if C:
        kb = max(tier_nb[1:])
        tail_tab = np.full((C, kb), n_blk, np.int32)
        off = nb0
        for ci, nbt in enumerate(tier_nb[1:]):
            tail_tab[ci, :nbt] = np.arange(off, off + nbt)
            off += nbt
        g = np.stack([W[t].mean(axis=0) for t in tails]).astype(np.float32)
        gb = np.asarray([b[t].mean() for t in tails], np.float32)

    # cost-model statistics over the unigram (which lives in RANK space:
    # unigram[i] is the mass of the i-th most frequent word)
    p_descend = float(unigram[F:].sum()) if C else 0.0
    if C and p_descend > 0:
        off, exp_tail = F, 0.0
        for t in tails:
            exp_tail += unigram[off:off + len(t)].sum() / p_descend * len(t)
            off += len(t)
        exp_tail_words = float(exp_tail)
    elif C:
        exp_tail_words = float(np.mean([len(t) for t in tails]))
    else:
        exp_tail_words = 0.0

    return SimpleNamespace(order=order, F=F, C=C, nb0=nb0, n_blk=n_blk,
                           kb=0 if not C else tail_tab.shape[1],
                           Wblk=Wblk, bblk=bblk, gid=gid, tail_tab=tail_tab,
                           g=g, gb=gb, tail_sizes=[len(t) for t in tails],
                           p_descend=p_descend,
                           exp_tail_words=exp_tail_words)


# -- −inf-safe cross-tier recombination --------------------------------------

def combine_tier_logz(a, b):
    """Elementwise log(eᵃ + eᵇ) — the cross-tier §4.2 logZ recombine, with
    the same −inf contract as the shards' ``_combine_shard_logz``: a tier
    that scored no candidates (a non-descending query's tail) reports −∞
    and contributes nothing; BOTH tiers empty yields −∞ (probability 0),
    never NaN."""
    m = jnp.maximum(a, b)
    safe = jnp.isfinite(m)
    m0 = jnp.where(safe, m, 0.0)
    s = jnp.exp(a - m0) + jnp.exp(b - m0)
    return jnp.where(safe, m0 + jnp.log(s), -jnp.inf)


def _masked_lse(logits):
    """Row log-sum-exp treating ≤ NEG_INF/2 entries as ABSENT — the unfused
    escape hatch's twin of the fused kernel's online logZ: an all-masked row
    yields −∞ (probability 0), never NaN and never the fake uniform mass a
    bare log_softmax would assign."""
    m = jnp.max(logits, axis=-1)
    live = m > NEG_INF / 2
    m0 = jnp.where(live, m, 0.0)
    s = jnp.sum(jnp.where(logits > NEG_INF / 2,
                          jnp.exp(logits - m0[:, None]), 0.0), axis=-1)
    return jnp.where(live, m0 + jnp.log(s), -jnp.inf)


# -- shared tier bodies (plain/traceable; jitted entries below and in the
#    shard_map closures — composition stays flat, kernels/ops.py idiom) ------

def _short_topk_body(Wb, bb, gid, short_blocks, h, k, L):
    """Fused short-list tier: kernel over the short blocks, packed rows →
    vocab ids, pad to k. Works on the full packed tiles (unsharded) or the
    replicated short slice (sharded) — the kernel sentinel is
    ``Wb.shape[0]·V_BLK`` and ``gid``'s last entry maps it to L either way."""
    B = h.shape[0]
    nb0 = short_blocks.shape[0]
    ks = min(k, nb0 * V_BLK)
    sb = jnp.broadcast_to(short_blocks[None, :], (B, nb0))
    srows, svals, logz = fused_screened_topk(Wb, bb, h, sb, k=ks)
    return gid[srows], svals, logz


def _short_row_body(Wb, bb, gid, short_blocks, h):
    """Short-list candidate row (word-granular, for sampling): logits and
    vocab ids over the packed short tier; pad rows carry NEG_INF bias."""
    B = h.shape[0]
    nb0 = short_blocks.shape[0]
    slog = (jnp.einsum("nvd,bd->bnv", Wb[:nb0], h) +
            bb[:nb0][None]).astype(jnp.float32).reshape(B, nb0 * V_BLK)
    sids = jnp.broadcast_to(gid[None, :nb0 * V_BLK], slog.shape)
    return slog, sids


def _gate(g, gb, h):
    return (h @ g.T + gb[None]).astype(jnp.float32)


def _descend_mask(gate, svals, ks, k):
    """Descend iff the best tail gate beats the k-th short-list logit; when
    k exceeds the short-list capacity every query must descend (the
    satellite "k larger than the short-list" case)."""
    if ks < k:
        return jnp.ones(gate.shape[:1], bool)
    return jnp.max(gate, axis=-1) >= svals[:, -1]


@partial(jax.jit, static_argnames=("k", "L"))
def _fused_short_topk(Wb, bb, gid, short_blocks, h, *, k, L):
    """No-tails (shortlist = L) fused path: the short tier IS the head."""
    sgids, svals, logz = _short_topk_body(Wb, bb, gid, short_blocks, h,
                                          k, L)
    ids, vals = merge_shard_topk(svals, sgids, k, sentinel=L)
    return ids, vals, logz


@partial(jax.jit, static_argnames=("k", "L"))
def _fused_tiered_topk(Wb, bb, gid, short_blocks, tail_tab, g, gb, h, *,
                       k, L):
    """Fused two-tier top-k: short-list kernel for every query, tail kernel
    with the non-descending rows' block ids MASKED TO THE SENTINEL — those
    rows ride the kernel's proven all-sentinel path (NEG_INF vals, sentinel
    ids, logZ = −∞) so laziness costs no separate launch and the merge needs
    no special cases. Only (B, k) results per tier ever reach HBM; no
    full-vocab (or full-tier) logit buffer is materialized — the parity
    suite asserts that on the lowered HLO."""
    nb0 = short_blocks.shape[0]
    n_blk = Wb.shape[0]
    ks = min(k, nb0 * V_BLK)
    sgids, svals, slogz = _short_topk_body(Wb, bb, gid, short_blocks, h,
                                           k, L)
    gate = _gate(g, gb, h)
    cluster = jnp.argmax(gate, axis=-1)
    descend = _descend_mask(gate, svals, ks, k)
    tb = jnp.where(descend[:, None], tail_tab[cluster], n_blk)
    kt = min(k, tail_tab.shape[-1] * V_BLK)
    trows, tvals, tlogz = fused_screened_topk(Wb, bb, h, tb, k=kt)
    ids, vals = merge_shard_topk(
        jnp.concatenate([svals, tvals], axis=-1),
        jnp.concatenate([sgids, gid[trows]], axis=-1), k, sentinel=L)
    return ids, vals, combine_tier_logz(slogz, tlogz)


@partial(jax.jit, static_argnames=("k", "L"))
def _unfused_short_topk(Wb, bb, gid, short_blocks, h, *, k, L):
    slog, sids = _short_row_body(Wb, bb, gid, short_blocks, h)
    ks = min(k, slog.shape[-1])
    svals, pos = jax.lax.top_k(slog, ks)
    ids, vals = merge_shard_topk(svals, jnp.take_along_axis(sids, pos, -1),
                                 k, sentinel=L)
    return ids, vals, _masked_lse(slog)


def _tail_row_body(Wb, bb, gid, tail_tab, cluster, descend):
    """Tail candidate rows (word-granular): each query's argmax cluster's
    blocks gathered from the packed tiles, NEG_INF / sentinel-L at
    non-descending rows and block padding. Returns a closure-free pair of
    (B, kb·V_BLK) logit/ids builders shared by the unfused top-k and the
    sampling row."""
    n_blk = Wb.shape[0]
    tb = jnp.where(descend[:, None], tail_tab[cluster], n_blk)
    valid = tb < n_blk
    safe = jnp.where(valid, tb, 0)
    lane = jnp.arange(V_BLK, dtype=jnp.int32)
    rows = jnp.where(valid[..., None],
                     safe[..., None] * V_BLK + lane[None, None, :],
                     n_blk * V_BLK)
    B = tb.shape[0]

    def logits(h):
        tl = (jnp.einsum("bkvd,bd->bkv", Wb[safe], h) +
              bb[safe]).astype(jnp.float32)
        return jnp.where(valid[..., None], tl, NEG_INF).reshape(B, -1)

    return logits, gid[rows].reshape(B, -1)


@partial(jax.jit, static_argnames=("k", "L"))
def _unfused_tiered_topk(Wb, bb, gid, short_blocks, tail_tab, g, gb, h, *,
                         k, L):
    """jnp escape hatch for the two-tier path — identical ids/vals to the
    fused kernel (same flattened-position tie order), identical empty-row
    convention (NEG_INF, never NaN) via ``_masked_lse``."""
    slog, sids = _short_row_body(Wb, bb, gid, short_blocks, h)
    ks = min(k, slog.shape[-1])
    svals, pos = jax.lax.top_k(slog, ks)
    sgids = jnp.take_along_axis(sids, pos, axis=-1)
    gate = _gate(g, gb, h)
    cluster = jnp.argmax(gate, axis=-1)
    descend = _descend_mask(gate, svals, ks, k)
    tl_fn, tgids = _tail_row_body(Wb, bb, gid, tail_tab, cluster, descend)
    tlog = tl_fn(h)
    kt = min(k, tlog.shape[-1])
    tvals, tpos = jax.lax.top_k(tlog, kt)
    ids, vals = merge_shard_topk(
        jnp.concatenate([svals, tvals], axis=-1),
        jnp.concatenate([sgids, jnp.take_along_axis(tgids, tpos, -1)],
                        axis=-1), k, sentinel=L)
    return ids, vals, combine_tier_logz(_masked_lse(slog), _masked_lse(tlog))


@jax.jit
def _short_row(Wb, bb, gid, short_blocks, h):
    return _short_row_body(Wb, bb, gid, short_blocks, h)


@jax.jit
def _tiered_row(Wb, bb, gid, short_blocks, tail_tab, g, gb, h):
    """Word-granular candidate row across both tiers (sampling needs the
    full distribution). Sampling uses the k=1 gate rule: descend iff the
    best gate beats the best short-list logit — consistent with greedy
    (t=0) decode through ``next()``."""
    slog, sids = _short_row_body(Wb, bb, gid, short_blocks, h)
    gate = _gate(g, gb, h)
    cluster = jnp.argmax(gate, axis=-1)
    descend = jnp.max(gate, axis=-1) >= jnp.max(slog, axis=-1)
    tl_fn, tgids = _tail_row_body(Wb, bb, gid, tail_tab, cluster, descend)
    return (jnp.concatenate([slog, tl_fn(h)], axis=-1),
            jnp.concatenate([sids, tgids], axis=-1))


# -- adaptive (single-device) ------------------------------------------------

class AdaptiveHead(SoftmaxHead):
    """Frequency-tiered adaptive softmax over packed V_BLK tiles; see the
    module docstring for the tier/gate semantics. ``fused=True`` (default)
    reduces each tier through the in-VMEM Pallas kernel; ``fused=False`` is
    the word-granular jnp escape hatch with identical ids/tie order."""
    name = "adaptive"

    def __init__(self, W, b, counts=None, shortlist=None, n_tails: int = 4,
                 fused: bool = True):
        if n_tails < 1:
            raise ValueError(f"n_tails must be >= 1, got {n_tails}")
        self.W = jnp.asarray(W)
        self.b = jnp.asarray(b)
        self.counts = None if counts is None else np.asarray(counts)
        self.shortlist = shortlist
        self.n_tails = int(n_tails)
        self.fused = bool(fused)
        self._Wb = None

    def prepare(self) -> "AdaptiveHead":
        if self._Wb is not None:
            return self
        lay = _build_tiers(np.asarray(self.W), np.asarray(self.b),
                           self.counts, self.shortlist, self.n_tails)
        self._lay = lay
        self.L = int(self.W.shape[0])
        self._Wb = jnp.asarray(lay.Wblk)
        self._bb = jnp.asarray(lay.bblk)
        self._gid = jnp.asarray(lay.gid)
        self._short_blocks = jnp.arange(lay.nb0, dtype=jnp.int32)
        self._tail_tab = None if lay.C == 0 else jnp.asarray(lay.tail_tab)
        self._g = None if lay.C == 0 else jnp.asarray(lay.g)
        self._gb = None if lay.C == 0 else jnp.asarray(lay.gb)
        return self

    def _run(self, h, k: int):
        self.prepare()
        h = jnp.asarray(h)
        if self._tail_tab is None:
            fn = _fused_short_topk if self.fused else _unfused_short_topk
            return fn(self._Wb, self._bb, self._gid, self._short_blocks, h,
                      k=k, L=self.L)
        fn = _fused_tiered_topk if self.fused else _unfused_tiered_topk
        return fn(self._Wb, self._bb, self._gid, self._short_blocks,
                  self._tail_tab, self._g, self._gb, h, k=k, L=self.L)

    def topk(self, h, k: int):
        ids, vals, _ = self._run(h, k)
        return ids, vals

    def topk_logprobs(self, h, k: int):
        """Log-softmax over the tiers the query actually scored (short-list
        ∪ descended tail), probability 0 elsewhere — the paper's §4.2
        reduced-search-space convention with the tier union as the space."""
        ids, vals, logz = self._run(h, k)
        lp = jnp.where(jnp.isfinite(logz)[:, None], vals - logz[:, None],
                       NEG_INF)
        return ids, jnp.where(vals <= NEG_INF / 2, NEG_INF, lp)

    def sample(self, key, h, temperature: float = 1.0, top_p: float = 1.0):
        self.prepare()
        h = jnp.asarray(h)
        if self._tail_tab is None:
            logits, gids = _short_row(self._Wb, self._bb, self._gid,
                                      self._short_blocks, h)
        else:
            logits, gids = _tiered_row(self._Wb, self._bb, self._gid,
                                       self._short_blocks, self._tail_tab,
                                       self._g, self._gb, h)
        choice = sample_from_logits(key, logits, temperature, top_p)
        return jnp.take_along_axis(gids, choice[:, None],
                                   axis=-1)[:, 0].astype(jnp.int32)

    @property
    def flops_per_query(self) -> float:
        self.prepare()
        lay = self._lay
        return tiered_flops_per_query(lay.F, lay.C, lay.p_descend,
                                      lay.exp_tail_words,
                                      int(self.W.shape[1]))

    @property
    def bytes_per_query(self) -> float:
        self.prepare()
        lay = self._lay
        if self.fused:
            writeback = 2.0 * V_BLK          # O(k)+logZ per tier kernel
        else:
            writeback = float((lay.nb0 + lay.kb) * V_BLK)
        return tiered_bytes_per_query(lay.F, lay.C, lay.p_descend,
                                      lay.exp_tail_words,
                                      int(self.W.shape[1]),
                                      writeback_floats=writeback)

    @property
    def memory_bytes(self) -> int:
        self.prepare()
        total = SoftmaxHead.memory_bytes.fget(self)
        for a in (self._gid, self._short_blocks, self._tail_tab, self._g,
                  self._gb):
            if a is not None:
                total += int(a.nbytes)
        return total


# -- adaptive-sharded --------------------------------------------------------

@lru_cache(maxsize=None)
def _sharded_short_impl(mesh, L: int):
    """shard_map closures for the degenerate no-tails geometry: the
    replicated short tier is the whole head, every shard computes it
    locally (no collective) — kept inside shard_map so the Pallas call
    always runs under manual SPMD like every other sharded head."""
    r1, r2, r3 = P(None), P(None, None), P(None, None, None)

    def run_body(Wb, bb, gid_s, short_blocks, h, k):
        sgids, svals, logz = _short_topk_body(Wb, bb, gid_s, short_blocks,
                                              h, k, L)
        ids, vals = merge_shard_topk(svals, sgids, k, sentinel=L)
        return ids, vals, logz

    def smap(body, outs):
        return jax.shard_map(body, mesh=mesh, in_specs=(r3, r2, r1, r1, r2),
                             out_specs=outs, check_vma=False)

    @partial(jax.jit, static_argnames="k")
    def run(Wb, bb, gid_s, short_blocks, h, k):
        return smap(partial(run_body, k=k), (r2, r2, r1))(
            Wb, bb, gid_s, short_blocks, h)

    @jax.jit
    def row(Wb, bb, gid_s, short_blocks, h):
        return smap(_short_row_body, (r2, r2))(Wb, bb, gid_s, short_blocks,
                                               h)

    return SimpleNamespace(run=run, row=row)


@lru_cache(maxsize=None)
def _adaptive_sharded_impl(mesh, L: int, Ls_t: int):
    """shard_map closures for one (mesh, vocab, tail-shard-width) geometry —
    cached at module level so instances sharing a mesh share compilations.

    The short tier, gates and descend decision are replicated compute (the
    code path is LITERALLY the unsharded tier bodies, so ids stay
    bit-identical); each shard then runs the fused kernel over only the tail
    blocks IT owns, translates local packed rows through the replicated
    ``gid_t`` map, and the shard-major all-gather → re-top-k merge plus
    ``_combine_shard_logz`` reassemble the tail tier before the cross-tier
    recombine."""
    wspec, bspec = P("model", None), P("model")
    cspec = P("model", None, None)
    r1, r2, r3 = P(None), P(None, None), P(None, None, None)
    nbs = Ls_t // V_BLK

    def run_body(Wb, bb, gid_s, short_blocks, Wt, bt, btab, gid_t, g, gb,
                 h, k):
        nb0 = short_blocks.shape[0]
        ks = min(k, nb0 * V_BLK)
        sgids, svals, slogz = _short_topk_body(Wb, bb, gid_s, short_blocks,
                                               h, k, L)
        gate = _gate(g, gb, h)
        cluster = jnp.argmax(gate, axis=-1)
        descend = _descend_mask(gate, svals, ks, k)
        d = Wt.shape[1]
        tb = jnp.where(descend[:, None], btab[0][cluster], nbs)
        kt = min(k, tb.shape[-1] * V_BLK)
        lrows, tvals, tlz = fused_screened_topk(
            Wt.reshape(nbs, V_BLK, d), bt.reshape(nbs, V_BLK), h, tb,
            k=kt)
        offset = jax.lax.axis_index("model") * Ls_t
        safe = jnp.where(lrows < Ls_t, lrows + offset, 0)
        tg = jnp.where(lrows < Ls_t, gid_t[safe], L)
        tvals = jax.lax.all_gather(tvals, "model", axis=1, tiled=True)
        tg = jax.lax.all_gather(tg, "model", axis=1, tiled=True)
        tids, tvals = merge_shard_topk(tvals, tg, k, sentinel=L)
        ids, vals = merge_shard_topk(
            jnp.concatenate([svals, tvals], axis=-1),
            jnp.concatenate([sgids, tids], axis=-1), k, sentinel=L)
        return ids, vals, combine_tier_logz(slogz, _combine_shard_logz(tlz))

    def row_body(Wb, bb, gid_s, short_blocks, Wt, bt, btab, gid_t, g, gb,
                 h):
        B = h.shape[0]
        slog, sids = _short_row_body(Wb, bb, gid_s, short_blocks, h)
        gate = _gate(g, gb, h)
        cluster = jnp.argmax(gate, axis=-1)
        descend = jnp.max(gate, axis=-1) >= jnp.max(slog, axis=-1)
        tb = jnp.where(descend[:, None], btab[0][cluster], nbs)
        valid = tb < nbs
        safe = jnp.where(valid, tb, 0)
        d = Wt.shape[1]
        Wtb = Wt.reshape(nbs, V_BLK, d)
        btb = bt.reshape(nbs, V_BLK)
        tlog = (jnp.einsum("bkvd,bd->bkv", Wtb[safe], h) +
                btb[safe]).astype(jnp.float32)
        tlog = jnp.where(valid[..., None], tlog, NEG_INF).reshape(B, -1)
        lane = jnp.arange(V_BLK, dtype=jnp.int32)
        offset = jax.lax.axis_index("model") * Ls_t
        rows = safe[..., None] * V_BLK + lane[None, None, :] + offset
        tg = jnp.where(valid[..., None], gid_t[rows], L).reshape(B, -1)
        tlog = jax.lax.all_gather(tlog, "model", axis=1, tiled=True)
        tg = jax.lax.all_gather(tg, "model", axis=1, tiled=True)
        return (jnp.concatenate([slog, tlog], axis=-1),
                jnp.concatenate([sids, tg], axis=-1))

    def smap(body, outs):
        return jax.shard_map(body, mesh=mesh,
                             in_specs=(r3, r2, r1, r1, wspec, bspec, cspec, r1,
                                       r2, r1, r2),
                             out_specs=outs, check_vma=False)

    @partial(jax.jit, static_argnames="k")
    def run(Wb, bb, gid_s, short_blocks, Wt, bt, btab, gid_t, g, gb, h, k):
        return smap(partial(run_body, k=k), (r2, r2, r1))(
            Wb, bb, gid_s, short_blocks, Wt, bt, btab, gid_t, g, gb, h)

    @jax.jit
    def row(Wb, bb, gid_s, short_blocks, Wt, bt, btab, gid_t, g, gb, h):
        return smap(row_body, (r2, r2))(
            Wb, bb, gid_s, short_blocks, Wt, bt, btab, gid_t, g, gb, h)

    return SimpleNamespace(run=run, row=row)


class AdaptiveShardedHead(SoftmaxHead):
    """Adaptive softmax with the rare-tail region vocab-range-sharded over
    the "model" mesh and the short-list tier replicated on every shard —
    the Zipfian placement: the tiles almost every query touches live
    everywhere, the tiles almost no query touches split 1/n per device.
    Ids are bit-identical to the unsharded ``adaptive`` head."""
    name = "adaptive-sharded"

    def __init__(self, W, b, counts=None, shortlist=None, n_tails: int = 4,
                 mesh=None, n_shards: int = None):
        if n_tails < 1:
            raise ValueError(f"n_tails must be >= 1, got {n_tails}")
        self._W0 = np.asarray(W, np.float32)
        self._b0 = np.asarray(b, np.float32)
        self._shape = self._W0.shape
        self.counts = None if counts is None else np.asarray(counts)
        self.shortlist = shortlist
        self.n_tails = int(n_tails)
        self._mesh_arg, self._n_shards_arg = mesh, n_shards
        self.mesh = None

    def prepare(self) -> "AdaptiveShardedHead":
        if self.mesh is not None:
            return self
        mesh = self._mesh_arg if self._mesh_arg is not None else \
            make_test_mesh(self._n_shards_arg)
        n = mesh.shape["model"]
        L, d = self._shape
        lay = _build_tiers(self._W0, self._b0, self.counts, self.shortlist,
                           self.n_tails)
        self._lay = lay
        sh = adaptive_head_shardings(mesh)
        repl = sh["replicated"]
        # replicated short tier: its own slice of the packed tiles plus a
        # short gid map whose last entry absorbs the kernel sentinel
        gid_s = np.append(lay.gid[:lay.nb0 * V_BLK], L).astype(np.int32)
        self._Wb = jax.device_put(jnp.asarray(lay.Wblk[:lay.nb0]), repl)
        self._bb = jax.device_put(jnp.asarray(lay.bblk[:lay.nb0]), repl)
        self._gid_s = jax.device_put(jnp.asarray(gid_s), repl)
        self._short_blocks = jax.device_put(
            jnp.arange(lay.nb0, dtype=jnp.int32), repl)
        self._repl = repl
        self.mesh, self.L = mesh, L

        if lay.C == 0:
            self.Wp = self.bp = self.cand_blocks = None
            self._g = self._gb = self._gid_t = None
            self._fns = _sharded_short_impl(mesh, L)
            self._W0 = self._b0 = None
            return self

        # tail region: the packed rows after the short tier, padded so each
        # shard owns a V_BLK-multiple slab (blocks never straddle shards)
        tail_rows = (lay.n_blk - lay.nb0) * V_BLK
        Ls_t = -(-tail_rows // (n * V_BLK)) * V_BLK
        padn = n * Ls_t - tail_rows
        Wt = np.pad(lay.Wblk[lay.nb0:].reshape(tail_rows, d),
                    ((0, padn), (0, 0)))
        bt = np.pad(lay.bblk[lay.nb0:].reshape(tail_rows), (0, padn),
                    constant_values=NEG_INF)
        gid_t = np.pad(lay.gid[lay.nb0 * V_BLK: lay.n_blk * V_BLK],
                       (0, padn), constant_values=L).astype(np.int32)
        # per-shard local block tables: cluster c's blocks in tail-REGION
        # coordinates, split by owning shard, local ids ascending (preserves
        # the global tie order through the shard-major merge), sentinel nbs
        nbs = Ls_t // V_BLK
        region = [lay.tail_tab[c][lay.tail_tab[c] < lay.n_blk] - lay.nb0
                  for c in range(lay.C)]
        kb = max(1, max((int(((gblk >= s * nbs) &
                              (gblk < (s + 1) * nbs)).sum())
                         for gblk in region for s in range(n)), default=1))
        btab = np.full((n, lay.C, kb), nbs, np.int32)
        for s in range(n):
            for c, gblk in enumerate(region):
                loc = gblk[(gblk >= s * nbs) & (gblk < (s + 1) * nbs)] \
                    - s * nbs
                btab[s, c, :len(loc)] = loc
        self.Wp = jax.device_put(jnp.asarray(Wt), sh["tail_W"])
        self.bp = jax.device_put(jnp.asarray(bt), sh["tail_b"])
        self.cand_blocks = jax.device_put(jnp.asarray(btab), sh["tail_cand"])
        self._gid_t = jax.device_put(jnp.asarray(gid_t), repl)
        self._g = jax.device_put(jnp.asarray(lay.g), repl)
        self._gb = jax.device_put(jnp.asarray(lay.gb), repl)
        self.Ls_t = Ls_t
        self._fns = _adaptive_sharded_impl(mesh, L, Ls_t)
        self._W0 = self._b0 = None      # only the placed copies stay resident
        return self

    def _run(self, h, k: int):
        self.prepare()
        h = _resharded(jnp.asarray(h), self._repl)
        if self.Wp is None:
            return self._fns.run(self._Wb, self._bb, self._gid_s,
                                 self._short_blocks, h, k=k)
        return self._fns.run(self._Wb, self._bb, self._gid_s,
                             self._short_blocks, self.Wp, self.bp,
                             self.cand_blocks, self._gid_t, self._g,
                             self._gb, h, k=k)

    def topk(self, h, k: int):
        ids, vals, _ = self._run(h, k)
        return ids, vals

    def topk_logprobs(self, h, k: int):
        ids, vals, logz = self._run(h, k)
        lp = jnp.where(jnp.isfinite(logz)[:, None], vals - logz[:, None],
                       NEG_INF)
        return ids, jnp.where(vals <= NEG_INF / 2, NEG_INF, lp)

    def sample(self, key, h, temperature: float = 1.0, top_p: float = 1.0):
        self.prepare()
        h = _resharded(jnp.asarray(h), self._repl)
        if self.Wp is None:
            logits, gids = self._fns.row(self._Wb, self._bb, self._gid_s,
                                         self._short_blocks, h)
        else:
            logits, gids = self._fns.row(self._Wb, self._bb, self._gid_s,
                                         self._short_blocks, self.Wp,
                                         self.bp, self.cand_blocks,
                                         self._gid_t, self._g, self._gb, h)
        choice = sample_from_logits(key, logits, temperature, top_p)
        return jnp.take_along_axis(gids, choice[:, None],
                                   axis=-1)[:, 0].astype(jnp.int32)

    @property
    def flops_per_query(self) -> float:
        """PER-SHARD MACs (mirrors the other sharded heads): the replicated
        short tier and gates are paid on every shard; the expected tail
        matmul splits 1/n per shard."""
        self.prepare()
        lay = self._lay
        n = self.mesh.shape["model"]
        return tiered_flops_per_query(lay.F, lay.C, lay.p_descend,
                                      lay.exp_tail_words / n,
                                      self._shape[1])

    @property
    def bytes_per_query(self) -> float:
        """PER-SHARD HBM bytes: replicated short tiles + gates stream per
        shard, this shard's expected tail slice, and only the two fused
        kernels' O(k) results write back."""
        self.prepare()
        lay = self._lay
        n = self.mesh.shape["model"]
        return tiered_bytes_per_query(lay.F, lay.C, lay.p_descend,
                                      lay.exp_tail_words / n,
                                      self._shape[1],
                                      writeback_floats=2.0 * V_BLK)

    @property
    def memory_bytes(self) -> int:
        """Device-resident serving tables, TOTAL across shards: replicated
        structures (short tier, gates, gid maps) count once PER SHARD —
        that is the real footprint the per-device budget divides by
        n_shards — plus the sharded tail region once."""
        if self.mesh is None:
            return int(self._W0.nbytes + self._b0.nbytes)
        n = self.mesh.shape["model"]
        repl = (self._Wb, self._bb, self._gid_s, self._short_blocks,
                self._g, self._gb, self._gid_t)
        total = n * sum(int(a.nbytes) for a in repl if a is not None)
        for a in (self.Wp, self.bp, self.cand_blocks):
            if a is not None:
                total += int(a.nbytes)
        return total
