"""ScreenedPallasHead — the L2S head on the Pallas TPU kernel path.

Default (``fused=True``): cluster_route kernel → the fused in-VMEM subset
softmax + top-k kernel (kernels/fused_topk.py). Each query row's candidate
logits are reduced on-chip — sentinel masking, top-k, and the §4.2
log-sum-exp never leave VMEM, so HBM sees only (B, k) ids/vals and (B,)
logZ instead of the (B, K·V_BLK) candidate-logit tile. Top-k ids/vals are
bit-identical to the unfused path. Sampling uses the same kernel with
temperature-scaled Gumbel noise (Gumbel-max ≡ categorical); nucleus
sampling (top_p < 1) needs the full candidate distribution and takes the
unfused path.

``fused=False`` is the escape hatch: scalar-prefetch block gather-matmul →
(B, K·V_BLK) logits in HBM → XLA-side masking + ``jax.lax.top_k`` — the
pre-fusion pipeline, kept for A/B timing (benchmarks/kernel_fused.py) and
as a fallback while bringing the fused kernel up on new hardware.

This head OWNS the block-candidate invariant: the screen must have been fit
at ``block == V_BLK`` (= 128, the MXU tile height) so candidate sets are sets
of vocab blocks and the "gather" is a blocked DMA of exactly the candidate
tiles. ``prepare()`` does the one-time MXU packing of (W, b) into
(n_blk, V_BLK, d) tiles; rows past the vocab are padded with −inf bias so
they can never win top-k.

The kernels run compiled on a TPU backend and interpreted elsewhere; the
choice is made once, in repro.kernels.mode, never by the head."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.screening import ScreenParams
from repro.heads.base import (NEG_INF, SoftmaxHead, require_screen,
                              sample_from_logits, screened_bytes_per_query,
                              screened_flops_per_query)
from repro.kernels.screen import V_BLK


class ScreenedPallasHead(SoftmaxHead):
    name = "screened-pallas"
    supports_dist = True

    def __init__(self, W, b, screen: ScreenParams, fused: bool = True):
        require_screen(screen, "ScreenedPallasHead")
        assert screen.block == V_BLK, (
            f"Pallas head needs a {V_BLK}-word block-candidate screen "
            f"(got block={getattr(screen, 'block', None)}); fit with "
            f"L2SConfig(vocab_block={V_BLK})")
        self.W = jnp.asarray(W)
        self.b = jnp.asarray(b)
        self.screen = screen
        self.fused = fused
        self._Wb = None
        self._bb = None

    def prepare(self) -> "ScreenedPallasHead":
        if self._Wb is None:
            from repro.kernels.ops import pack_head_blocks
            self._Wb, self._bb = pack_head_blocks(self.W, self.b)
        return self

    @property
    def packed_shape(self):
        """(n_blk, V_BLK, d) of the MXU-tiled weights (after prepare())."""
        self.prepare()
        return tuple(self._Wb.shape)

    @property
    def packed_nbytes(self) -> int:
        self.prepare()
        return int(self._Wb.nbytes + self._bb.nbytes)

    def _candidate_logits(self, h):
        from repro.kernels.ops import screened_candidate_logits_tpu
        self.prepare()
        return screened_candidate_logits_tpu(
            self._Wb, self._bb, self.screen.v, self.screen.cand_idx, h)

    def _fused_topk(self, h, k: int):
        from repro.kernels.ops import screened_fused_topk_tpu
        self.prepare()
        return screened_fused_topk_tpu(
            self._Wb, self._bb, self.screen.v, self.screen.cand_idx, h,
            k=k)

    def topk(self, h, k: int):
        if self.fused:
            ids, vals, _ = self._fused_topk(h, k)
            return ids.astype(jnp.int32), vals
        from repro.kernels.ops import screened_topk_tpu
        self.prepare()
        ids, vals = screened_topk_tpu(self._Wb, self._bb, self.screen.v,
                                      self.screen.cand_idx, h, k=k)
        return ids.astype(jnp.int32), vals

    def topk_logprobs(self, h, k: int):
        """§4.2 log-softmax over the routed candidate set. Fused path:
        top-k raw logits minus the kernel's on-chip logZ, with an explicit
        −inf-safe guard — a row whose candidate union is all-sentinel has
        logZ = −∞ and gets NEG_INF log-probs (probability 0 everywhere),
        never NaN."""
        if self.fused:
            ids, vals, logz = self._fused_topk(h, k)
            lp = jnp.where(jnp.isfinite(logz)[:, None],
                           vals - logz[:, None], NEG_INF)
            return ids.astype(jnp.int32), lp
        logits, word_ids = self._candidate_logits(h)
        logits = logits.astype(jnp.float32)
        lp = jax.nn.log_softmax(logits, axis=-1)
        # same empty-row convention as the fused kernel (the escape hatch
        # must not change semantics): an all-sentinel candidate union is
        # probability 0 everywhere, not log-uniform over the padding
        empty = jnp.all(logits <= NEG_INF / 2, axis=-1)
        lp = jnp.where(empty[:, None], NEG_INF, lp)
        vals, pos = jax.lax.top_k(lp, k)
        ids = jnp.take_along_axis(word_ids, pos, axis=-1)
        return ids.astype(jnp.int32), vals

    def sample(self, key, h, temperature: float = 1.0, top_p: float = 1.0):
        if self.fused and top_p >= 1.0:
            if temperature <= 0:
                ids, _, _ = self._fused_topk(h, 1)
                return ids[:, 0].astype(jnp.int32)
            from repro.kernels.ops import screened_fused_sample_tpu
            self.prepare()
            return screened_fused_sample_tpu(
                self._Wb, self._bb, self.screen.v, self.screen.cand_idx, h,
                key, temperature=temperature).astype(jnp.int32)
        # nucleus sampling (and fused=False) needs the full candidate
        # distribution — unfused gather path
        logits, word_ids = self._candidate_logits(h)
        choice = sample_from_logits(key, logits.astype(jnp.float32),
                                    temperature, top_p)
        return jnp.take_along_axis(word_ids, choice[:, None],
                                   axis=-1)[:, 0].astype(jnp.int32)

    def dist_logits(self, h):
        """Same sampling law as the jnp screened head (the fused Gumbel-max
        path is an exact categorical over the candidate set), so the scatter
        to vocab coordinates is shared with it."""
        from repro.heads.screened import _dist_logits
        return _dist_logits(self.W, self.b, self.screen, h)

    @property
    def flops_per_query(self) -> float:
        # identical cost model to the jnp screened head — the kernel
        # changes the constant (and the memory profile), not the count
        return screened_flops_per_query(self.screen, self.W.shape[1])

    @property
    def bytes_per_query(self) -> float:
        """Fused: router + candidate tiles stream once, only O(k ≤ V_BLK)
        results reach HBM. Unfused: the full K·V_BLK candidate-logit row is
        written back and re-read by masking + top-k.

        Models the topk/topk_logprobs decode hot path. Fused SAMPLING
        streams a (K·V_BLK,) Gumbel-noise row per query (generated
        off-chip), so its writeback is comparable to the unfused path —
        only the d-proportional logit traffic stays fused there."""
        writeback = (float(V_BLK) if self.fused
                     else float(self.screen.c_max * V_BLK))
        return screened_bytes_per_query(self.screen, self.W.shape[1],
                                        writeback_floats=writeback)
