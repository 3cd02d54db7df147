"""Algorithm 1 — end-to-end training of the screening model.

Alternating minimization of Eq.(7):
  v-step: SGD on Eq.(8) through the Gumbel-ST relaxation. With candidate
          masks fixed and binary, the per-sample per-cluster loss is
            loss_{i,t} = (k − hits_{i,t}) + λ·(|c_t|·block − hits_{i,t})
          where hits_{i,t} = |y_i ∩ c_t|; the sample's loss is Σ_t p̄_t·loss_t
          (p̄ = straight-through one-hot), plus γ·max(0, L̄_mov − B) with a
          moving-average L̄ (paper: mini-batch moving average).
  c-step: greedy knapsack (repro.core.knapsack).

``collect_contexts`` runs the trained LM over a corpus to harvest (h, y):
y = exact-softmax top-k ids — the paper trains the screen to mimic the full
softmax, not the data labels.
"""
from __future__ import annotations

from dataclasses import dataclass, replace as dc_replace
from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import L2SConfig
from repro.core.gumbel import gumbel_softmax_st
from repro.core.kmeans import spherical_kmeans
from repro.core.knapsack import candidate_stats, greedy_knapsack
from repro.core.screening import ScreenParams, assign_clusters, candidates_to_padded


@dataclass
class L2SState:
    screen: ScreenParams
    mask: np.ndarray            # (r, n_items) bool — current candidate sets
    history: list               # per-round dicts: losses, L̄, precision


def collect_contexts(model, params, token_batches, max_vectors: int = 200_000,
                     k: int = 5) -> Tuple[np.ndarray, np.ndarray]:
    """Harvest (H (N, d), y (N, k)) from an LM over token batches.

    y_i = exact softmax top-k at each position (paper Algorithm 1 line 2).
    """
    # params enter as an argument: closed over, the whole model would be
    # baked into the executable as constants
    @jax.jit
    def fwd(params, tokens):
        h, _ = model.forward(params, {"tokens": tokens})
        W, b = model.softmax_weights(params)
        logits = jnp.einsum("btd,vd->btv", h, W) + b
        _, top = jax.lax.top_k(logits, k)
        return h, top

    Hs, ys = [], []
    n = 0
    for tokens in token_batches:
        h, top = fwd(params, tokens)
        d = h.shape[-1]
        Hs.append(np.asarray(h.reshape(-1, d), np.float32))
        ys.append(np.asarray(top.reshape(-1, k), np.int32))
        n += Hs[-1].shape[0]
        if n >= max_vectors:
            break
    H = np.concatenate(Hs)[:max_vectors]
    y = np.concatenate(ys)[:max_vectors]
    return H, y


# -- v-step -------------------------------------------------------------------

@partial(jax.jit, static_argnames=("cfg_budget", "cfg_lamb", "cfg_gamma",
                                   "cfg_temp", "cfg_k", "cfg_block"))
def _vstep_batch(v, key, h, hits_per_cluster, cand_words, lbar_mov,
                 cfg_budget: float, cfg_lamb: float, cfg_gamma: float,
                 cfg_temp: float, cfg_k: int, cfg_block: int, lr):
    """One SGD step on Eq.(8).

    h: (B, d); hits_per_cluster: (B, r) — |y_i ∩ c_t| (precomputed, c fixed);
    cand_words: (r,) — candidate set sizes |c_t| in words.
    """
    def loss_fn(v):
        logits = jnp.einsum("bd,rd->br", h, v)              # log P(t|h) ∝ v_t·h
        p_bar, p_soft = gumbel_softmax_st(key, logits, cfg_temp)
        miss = cfg_k - hits_per_cluster                     # (B, r)
        fp = cfg_lamb * (cand_words[None, :] - hits_per_cluster)
        per_cluster = miss + fp
        sample_loss = jnp.sum(p_bar * per_cluster, axis=-1)
        # moving-average label size constraint (Lagrangian, Eq.(8))
        lbar_batch = jnp.mean(jnp.sum(p_bar * cand_words[None, :], axis=-1))
        lbar = 0.9 * lbar_mov + 0.1 * lbar_batch
        penalty = cfg_gamma * jnp.maximum(0.0, lbar - cfg_budget)
        return jnp.mean(sample_loss) + penalty, lbar

    (loss, lbar), grad = jax.value_and_grad(loss_fn, has_aux=True)(v)
    return v - lr * grad, loss, lbar


def _hits_matrix(mask_dev: jnp.ndarray, y: jnp.ndarray, block: int) -> jnp.ndarray:
    """hits_{i,t} = |y_i ∩ c_t|. mask_dev (r, n_items) float; y (B, k) word ids."""
    items = y // block if block > 1 else y               # (B, k)
    sel = mask_dev[:, items]                             # (r, B, k)
    return jnp.sum(sel, axis=-1).T                       # (B, r)


# -- full Algorithm 1 ----------------------------------------------------------

def fit_l2s(H: np.ndarray, y: np.ndarray, vocab_size: int, cfg: L2SConfig,
            verbose: bool = False,
            eval_fn: Optional[Callable] = None) -> L2SState:
    """Train the screening model on harvested (H, y)."""
    N, d = H.shape
    k = y.shape[1]
    r = cfg.num_clusters
    block = cfg.vocab_block
    n_items = -(-vocab_size // block)
    key = jax.random.key(cfg.seed)

    # line 3: spherical k-means init
    key, sk = jax.random.split(key)
    sub = H[np.random.default_rng(cfg.seed).choice(N, min(N, 50_000), replace=False)]
    v = spherical_kmeans(sk, jnp.asarray(sub), r)
    Hd = jnp.asarray(H)
    yd = jnp.asarray(y)

    history = []
    lbar_mov = jnp.float32(0.0)

    def cstep(v_cur):
        """Knapsack under the current assignments → (mask, coverage).
        coverage = mean fraction of true top-k captured — the quantity P@k
        tracks; used for best-round selection."""
        assign = np.asarray(assign_clusters(v_cur, Hd))
        counts, csizes = candidate_stats(assign, y, r, vocab_size, block)
        m = greedy_knapsack(counts, csizes, N, cfg.budget, cfg.lamb,
                            vocab_size, block)
        hits = (m[assign][np.arange(N)[:, None],
                          (y // block if block > 1 else y)]).sum()
        return m, float(hits) / (N * k)

    # round 0's (v, c) is exactly the spherical-kmeans screen; keep the BEST
    # round overall so the end-to-end refinement can never underperform its
    # own init (observed on near-separable context distributions, where the
    # Lagrange pressure at tight budgets can degrade the kmeans optimum).
    best = {"v": v, "mask": None, "cov": -1.0}
    mask = np.zeros((r, n_items), bool)

    for round_i in range(cfg.outer_iters):
        # ---- c-step: knapsack under the CURRENT assignments ----
        mask, cov = cstep(v)
        if cov > best["cov"]:
            best = {"v": v, "mask": mask, "cov": cov}
        mask_dev = jnp.asarray(mask, jnp.float32)
        cand_words = jnp.asarray(mask.sum(axis=1) * block, jnp.float32)

        # ---- v-step: SGD with Gumbel-ST ----
        losses = []
        for step in range(cfg.sgd_steps):
            key, kb, kg = jax.random.split(key, 3)
            idx = jax.random.randint(kb, (cfg.batch_size,), 0, N)
            hb = Hd[idx]
            hits = _hits_matrix(mask_dev, yd[idx], block)
            v, loss, lbar_mov = _vstep_batch(
                v, kg, hb, hits, cand_words, lbar_mov,
                float(cfg.budget), cfg.lamb, cfg.gamma, cfg.gumbel_temp,
                k, block, cfg.lr)
            losses.append(float(loss))

        rec = {"round": round_i, "loss": float(np.mean(losses[-20:])),
               "lbar": float(lbar_mov), "coverage": cov}
        if eval_fn is not None:
            rec.update(eval_fn(v, mask))
        history.append(rec)
        if verbose:
            print(f"[l2s] round {round_i}: {rec}")

    # final c-step on converged assignments; select the best round
    mask, cov = cstep(v)
    if cov > best["cov"]:
        best = {"v": v, "mask": mask, "cov": cov}
    v, mask = best["v"], best["mask"]
    history.append({"round": "final", "coverage_best": best["cov"]})
    cand_idx, cand_len = candidates_to_padded(mask, vocab_size, block)
    screen = ScreenParams(v=jnp.asarray(v), cand_idx=jnp.asarray(cand_idx),
                          cand_len=jnp.asarray(cand_len),
                          vocab_size=vocab_size, block=block)
    return L2SState(screen=screen, mask=mask, history=history)


def kmeans_only_screen(H: np.ndarray, y: np.ndarray, vocab_size: int,
                       cfg: L2SConfig) -> L2SState:
    """Table-4 ablation: spherical k-means clusters + one knapsack c-step
    (no Gumbel end-to-end refinement)."""
    N, d = H.shape
    r, block = cfg.num_clusters, cfg.vocab_block
    key = jax.random.key(cfg.seed)
    sub = H[np.random.default_rng(cfg.seed).choice(N, min(N, 50_000), replace=False)]
    v = spherical_kmeans(key, jnp.asarray(sub), r)
    assign = np.asarray(assign_clusters(v, jnp.asarray(H)))
    counts, csizes = candidate_stats(assign, y, r, vocab_size, block)
    mask = greedy_knapsack(counts, csizes, N, cfg.budget, cfg.lamb,
                           vocab_size, block)
    cand_idx, cand_len = candidates_to_padded(mask, vocab_size, block)
    screen = ScreenParams(v=jnp.asarray(v), cand_idx=jnp.asarray(cand_idx),
                          cand_len=jnp.asarray(cand_len),
                          vocab_size=vocab_size, block=block)
    return L2SState(screen=screen, mask=mask, history=[])
