"""The ``SoftmaxHead`` protocol — the one seam every decode head plugs into.

A head owns the softmax layer (W (L, d), b (L,)) plus whatever auxiliary
structure its approximation needs (a learned screen, SVD factors, hash
tables, ...) and answers four queries over context vectors h (B, d):

  topk(h, k)          → (ids (B, k) int32, scores (B, k))   raw logits
  topk_logprobs(h, k) → (ids (B, k) int32, logprobs (B, k)) paper §4.2
                        convention: log-softmax over the head's OWN
                        candidate space, probability 0 elsewhere
  next(h)             → (B,) int32 greedy argmax
  sample(key, h, temperature, top_p) → (B,) int32

``prepare()`` performs any one-time packing (e.g. MXU block tiling) and
returns the head; it is idempotent and is called by the registry and the
serving engine, so constructors stay cheap.

Metadata drives engine behavior and benchmark reporting:

  flops_per_query — analytic multiply-accumulate count per query, the
                    hardware-independent speedup column of paper Table 1
  bytes_per_query — estimated decode-step HBM traffic; separates the
                    equal-flops fused/unfused kernel paths for routing
  device_kind     — "jax" or "numpy" (numpy heads run per-query on host,
                    the paper's single-thread CPU timing protocol)
  is_jittable     — True iff the head's methods are jnp-traceable, so the
                    engine may fuse them into its jitted decode step
"""
from __future__ import annotations

import copy
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


class MissingScreenError(ValueError):
    """A screening head was requested without a fitted ``ScreenParams``.

    Raised by the screening head factories (screened, screened-sharded,
    screened-pallas, screened-cpu) so callers — the serving launcher, the
    router's catalog builder — can distinguish "this head needs an L2S
    screen" from a programming error and surface the fix
    (``fit_l2s(...)`` → pass ``screen=``) instead of a bare assertion."""


def require_screen(screen, head_name: str):
    if screen is None:
        raise MissingScreenError(
            f"{head_name} needs a fitted ScreenParams — fit one with "
            f"fit_l2s(...) and pass screen= to the engine or heads.get")
    return screen


def screened_flops_per_query(screen, d: int) -> float:
    """Shared L2S cost model O((r + L̄)·d): routing plus the mean candidate
    matmul, with L̄ the uniform-over-clusters mean candidate words. One
    definition for every screened backend so Table-1 flops columns agree."""
    lbar = float(np.mean(np.asarray(screen.cand_len))) * screen.block
    return float((screen.r + lbar) * d)


def tiered_flops_per_query(short_words: int, n_gates: int, p_descend: float,
                           expected_tail_words: float, d: int) -> float:
    """Adaptive-softmax cost model (Grave et al.): every query pays the
    short-list matmul plus the tail gates, O((F + C)·d); the tail cluster
    matmul is paid only when the gate wins, so it enters in EXPECTATION
    under the configured unigram distribution — ``p_descend`` is the
    unigram mass beyond the short-list and ``expected_tail_words`` the
    unigram-weighted mean tail-cluster width. One definition for both
    adaptive heads so routing compares like against like."""
    return float((short_words + n_gates +
                  p_descend * expected_tail_words) * d)


def tiered_bytes_per_query(short_words: int, n_gates: int, p_descend: float,
                           expected_tail_words: float, d: int,
                           writeback_floats: float = 0.0,
                           itemsize: int = 4) -> float:
    """HBM-traffic twin of ``tiered_flops_per_query``: short-list tiles and
    gates stream once per query, tail tiles stream in expectation, and
    ``writeback_floats`` intermediates are written back and re-read
    (counted twice) — O(k) results for the fused per-tier kernel, the full
    candidate row for the unfused escape hatch."""
    return float(((short_words + n_gates +
                   p_descend * expected_tail_words) * d +
                  2.0 * writeback_floats) * itemsize)


def screened_bytes_per_query(screen, d: int, writeback_floats: float = 0.0,
                             itemsize: int = 4) -> float:
    """Shared L2S HBM-traffic model for one decode step: the router and the
    mean candidate weight tiles stream HBM→VMEM once, O((r + L̄)·d), plus
    ``writeback_floats`` intermediate values written back and re-read
    (counted twice). The fused Pallas path's whole point is driving the
    writeback term from O(K·V_BLK) candidate logits down to O(k) results —
    this is the number ``CostAwarePolicy`` compares across screened
    backends."""
    lbar = float(np.mean(np.asarray(screen.cand_len))) * screen.block
    return float(((screen.r + lbar) * d + 2.0 * writeback_floats) * itemsize)


class SoftmaxHead:
    """Base class / protocol for decode heads. Subclasses must implement
    ``topk`` and ``topk_logprobs``; ``next`` and ``sample`` have generic
    defaults in terms of those."""

    name: str = "abstract"
    device_kind: str = "jax"
    is_jittable: bool = True
    # every shipped head implements ``sample``; a future head that cannot
    # (e.g. a pure-ranking retrieval index) sets False and routing policies
    # keep sampled requests off it
    supports_sampling: bool = True
    # True iff the head implements ``dist_logits`` — its full-vocabulary
    # sampling law in vocab coordinates. Speculative decoding's rejection
    # rule needs the draft (q) and target (p) distributions over ONE
    # coordinate system; spec policies keep sampled traffic off heads
    # that can't produce it
    supports_dist: bool = False
    # vocab-sharded heads set this to their jax.sharding.Mesh in prepare();
    # the serving engine uses it to build mesh-aware jitted decode steps
    # (inputs replicated over the head's device set instead of device 0)
    mesh = None

    def prepare(self) -> "SoftmaxHead":
        """One-time packing / table builds. Idempotent."""
        return self

    def device_state(self) -> dict:
        """The head's device arrays by attribute name: every attribute that
        is a jax Array or a pytree of them (a ScreenParams). The serving
        engine passes this dict into its compiled steps as an argument, so
        the weights are never baked into an executable as constants."""
        def on_device(v):
            leaves = jax.tree_util.tree_leaves(v)
            return bool(leaves) and all(isinstance(x, jax.Array)
                                        for x in leaves)
        return {k: v for k, v in vars(self).items() if on_device(v)}

    def with_state(self, state: dict) -> "SoftmaxHead":
        """A shallow copy of this head over ``state`` (the traced
        ``device_state()`` inside a compiled step); the head is unchanged."""
        head = copy.copy(self)
        head.__dict__.update(state)
        return head

    # -- core queries -------------------------------------------------------
    def topk(self, h, k: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
        raise NotImplementedError

    def topk_logprobs(self, h, k: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
        raise NotImplementedError

    def next(self, h) -> jnp.ndarray:
        ids, _ = self.topk(h, 1)
        return ids[:, 0].astype(jnp.int32)

    def sample(self, key, h, temperature: float = 1.0,
               top_p: float = 1.0) -> jnp.ndarray:
        raise NotImplementedError

    def dist_logits(self, h) -> jnp.ndarray:
        """(B, V) distribution logits over the FULL vocabulary: softmax of a
        row is exactly the law ``sample(key, h, 1.0, 1.0)`` draws from, with
        ``NEG_INF`` at every word outside the head's own candidate space
        (the §4.2 probability-0 convention). Temperature / nucleus
        adjustments are applied downstream via ``adjust_logits`` — the same
        transform ``sample_from_logits`` draws through — so speculative
        rejection sampling can score ANY sampling configuration. Heads that
        implement it set ``supports_dist = True``."""
        raise NotImplementedError(
            f"{type(self).__name__} does not expose a full-vocab "
            f"distribution (supports_dist is False)")

    # -- metadata -----------------------------------------------------------
    @property
    def flops_per_query(self) -> float:
        """Analytic MACs per query (paper's hardware-independent cost)."""
        return float("nan")

    @property
    def bytes_per_query(self) -> float:
        """Estimated HBM bytes one decode-step query moves: weights/tables
        streamed on-chip plus intermediates written back and re-read.
        Distinguishes memory profiles the FLOP count can't — e.g. the fused
        Pallas head does the same MACs as the unfused one but never writes
        the (B, K·V_BLK) candidate-logit tile to HBM. Per-shard for sharded
        heads (mirroring ``flops_per_query``); NaN when unmodeled. Routing
        policies use it as the memory-profile tie-break between heads with
        equal flops."""
        return float("nan")

    _MEMORY_ATTRS = ("W", "b", "_Wb", "_bb")

    @property
    def memory_bytes(self) -> int:
        """Resident bytes of the head's serving tables: weights (plus the
        MXU-packed copy when the head keeps one) and any screen structure.
        Sharded heads override this with their device-resident shard tables.
        For a sharded head the number is the TOTAL across shards; divide by
        ``n_shards`` for the per-device footprint routing policies care
        about."""
        seen, total = set(), 0
        for attr in self._MEMORY_ATTRS:
            a = getattr(self, attr, None)
            if a is not None and hasattr(a, "nbytes") and id(a) not in seen:
                seen.add(id(a))
                total += int(a.nbytes)
        screen = getattr(self, "screen", None)
        if screen is not None:
            for leaf in jax.tree_util.tree_leaves(screen):
                if hasattr(leaf, "nbytes") and id(leaf) not in seen:
                    seen.add(id(leaf))
                    total += int(leaf.nbytes)
        return total

    @property
    def n_shards(self):
        """Vocab shards this head spans (None when unsharded). Sharded heads
        overwrite the attribute in ``prepare()``."""
        return None if self.mesh is None else int(self.mesh.shape["model"])

    def step_key(self) -> tuple:
        """Stable identity for the serving engine's compiled-step cache.

        Two prepared instances of the same head class over the same
        underlying arrays hash equal, so a transient instance (rebuilt per
        request) reuses — instead of evicting — the hot compiled step of its
        registry-cached twin. Arrays are identified by ``id`` (jnp.asarray
        is a no-copy on jnp inputs, so wrapping the same weights yields the
        same ids); heads holding distinct arrays never collide. ``impl``
        (the baseline adapters' configured method object) is part of the
        identity because it carries behavior-defining knobs (rho, budget,
        bands, ...) that the arrays alone don't."""
        parts = [self.name, type(self)]
        for attr in ("W", "b", "Wp", "bp", "_Wb", "_bb", "screen", "mesh",
                     "impl", "fused", "local"):
            v = getattr(self, attr, None)
            if v is not None:
                parts.append(v if isinstance(v, (str, int, float, bool))
                             else id(v))
        return tuple(parts)

    def describe(self) -> dict:
        """Routing metadata: everything a ``RoutingPolicy`` may weigh — the
        analytic cost model, device placement, memory footprint, and which
        query kinds the head can serve."""
        return {"name": self.name, "device_kind": self.device_kind,
                "is_jittable": self.is_jittable,
                "supports_sampling": self.supports_sampling,
                "supports_dist": self.supports_dist,
                "flops_per_query": self.flops_per_query,
                "bytes_per_query": self.bytes_per_query,
                "memory_bytes": self.memory_bytes,
                "n_shards": self.n_shards}

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        return (f"{type(self).__name__}(name={self.name!r}, "
                f"flops_per_query={self.flops_per_query:.3g})")


def adjust_logits(logits, temperature: float, top_p: float):
    """The temperature / nucleus transform ``sample_from_logits`` draws
    through, exposed on its own so speculative decoding can compute the
    EXACT proposal law of a sampled head: ``categorical(adjust_logits(
    dist_logits(h), T, p))`` is distributed as ``sample(key, h, T, p)``.

    Entries already masked to ``NEG_INF`` stay exactly ``NEG_INF`` (dividing
    the sentinel by a temperature > 1 would shrink its magnitude and could
    promote an empty row past the ``<= NEG_INF / 2`` emptiness test
    downstream consumers share). Requires temperature > 0.
    """
    masked = logits <= NEG_INF / 2
    logits = jnp.where(masked, NEG_INF, logits / temperature)
    if top_p < 1.0:
        # Mask by sorted RANK, not by value: a `logits >= cutoff` test keeps
        # every position tied with the cutoff logit, which can exceed the
        # nucleus when duplicates exist. Stable argsort of -logits gives the
        # descending order with ties broken by lowest index (the top-k
        # convention); rank < k_keep keeps exactly the smallest prefix.
        order = jnp.argsort(-logits, axis=-1)
        sorted_logits = jnp.take_along_axis(logits, order, axis=-1)
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # smallest prefix with mass ≥ top_p
        k_keep = jnp.sum(cum < top_p, axis=-1) + 1
        rank = jnp.argsort(order, axis=-1)
        logits = jnp.where(rank < k_keep[:, None], logits, NEG_INF)
    return logits


def sample_from_logits(key, logits, temperature: float, top_p: float):
    """Temperature + nucleus sampling over a (B, C) logit matrix.

    temperature ≤ 0 degenerates to argmax; top_p < 1 keeps the smallest
    prefix of the sorted distribution with mass ≥ top_p.
    """
    if temperature <= 0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = adjust_logits(logits, temperature, top_p)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)
