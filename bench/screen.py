"""The L2S screen the benchmark hands the program, built by the benchmark's
own code from the seed, so that nothing the program computes defines what
the check calls correct.

A screen (paper §3) is r cluster centres v_t and a candidate set c_t per
cluster; a context h routes to argmax_t v_t·h and is answered from c_t.
Here it is made in one jitted call:

1. contexts: the plain reference trunk (the family's ``trunk``, float32
   at HIGHEST) over ``ctx_batches × ctx_batch`` sequences of ``ctx_len``
   tokens of the seeded corpus, every position's hidden state;
2. labels: each context's ``label_topk`` best words under the reference's
   full softmax (the paper fits the screen to hold the full softmax's
   top-k);
3. clusters: ``kmeans_iters`` rounds of spherical k-means over the unit
   contexts, from ``clusters`` distinct contexts drawn from the seed;
4. candidates: for each cluster, the ``budget_blocks`` blocks of ``block``
   words that hold the most labels of its contexts (ties to the lower
   block), listed in ascending order.

Every cluster holds exactly ``budget_blocks`` blocks, so the screen has the
same shape for every seed, and the program compiles the same executables.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference, traffic


def _mm(a, b):
    return jnp.matmul(a, b, precision=reference.HIGHEST)


def _fit(trunk, w, tokens, key, r: int, iters: int, budget: int, block: int,
         topk: int):
    """tokens (n_batches, B, T) → (v (r, d) float32, blocks (r, budget))."""
    f32 = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    d = f32["w_out"].shape[1]
    vocab = f32["w_out"].shape[0]
    h = jax.lax.map(lambda t: trunk(f32, t).reshape(-1, d), tokens)
    labels = jax.lax.map(
        lambda hc: jax.lax.top_k(reference.output_logits(f32, hc), topk)[1],
        h)
    h = h.reshape(-1, d)
    labels = labels.reshape(-1, topk)
    u = h / jnp.maximum(jnp.linalg.norm(h, axis=1, keepdims=True), 1e-30)
    v0 = u[jax.random.choice(key, u.shape[0], (r,), replace=False)]

    def round_(_, v):
        a = jnp.argmax(_mm(u, v.T), axis=1)
        s = jax.ops.segment_sum(u, a, num_segments=r)
        n = jnp.linalg.norm(s, axis=1, keepdims=True)
        return jnp.where(n > 0, s / jnp.maximum(n, 1e-30), v)

    v = jax.lax.fori_loop(0, iters, round_, v0)
    a = jnp.argmax(_mm(h, v.T), axis=1)
    n_blk = -(-vocab // block)
    counts = jnp.zeros((r, n_blk), jnp.float32).at[
        a[:, None], labels // block].add(1.0)
    _, blocks = jax.lax.top_k(counts, budget)
    return v, jnp.sort(blocks, axis=1).astype(jnp.int32)


def fit(family, weights, conf: dict, corpus: traffic.Corpus, seed: int
        ) -> dict:
    """The screen of ``conf``'s model (``family``, its family module) as
    host arrays: {"v" (r, d) float32, "cand_idx" (r, C) int32 block ids,
    "cand_len" (r,) int32, "block"}."""
    sc = conf["screen"]
    rng = traffic.rng_for(seed, traffic.SALT_CONTEXTS)
    tokens = corpus.tokens(rng, sc["ctx_batches"] * sc["ctx_batch"],
                           sc["ctx_len"]).reshape(
        sc["ctx_batches"], sc["ctx_batch"], sc["ctx_len"])
    key = jax.random.key(traffic.seed32(seed, 101))
    fit_jit = jax.jit(partial(_fit, partial(family.trunk, conf=conf)),
                      static_argnames=("r", "iters", "budget", "block",
                                       "topk"))
    v, blocks = fit_jit(weights, jnp.asarray(tokens), key, r=sc["clusters"],
                        iters=sc["kmeans_iters"], budget=sc["budget_blocks"],
                        block=sc["block"], topk=sc["label_topk"])
    blocks = np.array(blocks)
    return {"v": np.array(v, np.float32), "cand_idx": blocks,
            "cand_len": np.full(len(blocks), blocks.shape[1], np.int32),
            "block": int(sc["block"])}


def program_screen(screen: dict, vocab: int):
    """The program's ``ScreenParams`` holding ``screen``'s arrays."""
    from repro.core.screening import ScreenParams
    return ScreenParams(v=jnp.asarray(screen["v"]),
                        cand_idx=jnp.asarray(screen["cand_idx"]),
                        cand_len=jnp.asarray(screen["cand_len"]),
                        vocab_size=int(vocab), block=int(screen["block"]))
