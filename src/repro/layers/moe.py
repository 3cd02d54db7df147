"""Mixture-of-Experts layer: top-k router + capacity-bounded dispatch.

Design (TPU-minded, fixed shapes):
  * router logits (T, E); top-k gates renormalized over the selected experts
    (Mixtral convention).
  * GROUPED dispatch: each batch row is a routing group (standard "group-wise
    expert capacity", cf. GShard/Flaxformer). Capacity C = ceil(cf · T · k / E)
    per group. The per-group dispatch uses cumulative-count positions +
    scatter-add into (E, C, d) buffers — O(T·E) bookkeeping instead of the
    O(T·E·C) one-hot dispatch matmul, infeasible at train_4k token counts.
    Groups vmap over the batch axis, so dispatch shards over `data` with no
    cross-device cumsum.
  * expert FFNs are stacked weights (E, d, ff) applied with one batched
    einsum — shardable over the model axis (ff) or an expert axis (E).
  * tokens over capacity are dropped (their combine weight is 0) — standard
    capacity-factor semantics.
  * aux load-balance loss (Switch-style): E · Σ_e f_e · p_e.

Held experts (``moe.held`` set; ``moe_held_apply``): the layer of one chip
of an expert-parallel deployment. It routes every token over all
``num_experts`` (DeepSeek-V3 sigmoid routing with a correction bias), then
computes only the assignments that fall on the experts it holds, grouped
by expert with ``jax.lax.ragged_dot``: no capacity, so no token is ever
dropped, whatever the load. The part the absent experts would add is left
out (their chips add it in the deployment's exchange); shared experts run
on every token.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.layers.attention import wdot
from repro.layers.initializers import dense_init
from repro.layers.mlp import GATED
from repro.utils.precision import split_terms


def moe_init(key, cfg: ModelConfig, dtype=jnp.float32):
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.moe.num_experts
    ks = jax.random.split(key, 4)
    p = {"w_router": dense_init(ks[0], (d, E), dtype)}
    if cfg.mlp_activation in GATED:
        p["w_gate"] = dense_init(ks[1], (E, d, ff), dtype)
        p["w_up"] = dense_init(ks[2], (E, d, ff), dtype)
        p["w_down"] = dense_init(ks[3], (E, ff, d), dtype)
    else:
        p["w_up"] = dense_init(ks[1], (E, d, ff), dtype)
        p["w_down"] = dense_init(ks[2], (E, ff, d), dtype)
    return p


def capacity(tokens_per_group: int, cfg: ModelConfig) -> int:
    m = cfg.moe
    c = int(math.ceil(m.capacity_factor * tokens_per_group * m.top_k / m.num_experts))
    return max(8, -(-c // 8) * 8)  # round up to 8 for lane friendliness


def _route(params, xt, cfg: ModelConfig):
    """xt: (T, d) → gates (T, K), experts (T, K), probs (T, E)."""
    m = cfg.moe
    logits = jnp.einsum("nd,de->ne", xt, params["w_router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, m.top_k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)
    return gate_vals, expert_idx, probs


def _dispatch_combine(params, xt, gate_vals, expert_idx, buf0,
                      cfg: ModelConfig):
    """One routing group. xt (T, d) → (T, d). ``buf0``: zeroed (E, C, d)
    dispatch buffer — allocated OUTSIDE the vmap with an explicit batch
    sharding constraint; scattering into a vmap-internal zeros() lets GSPMD
    replicate the batched buffer and all-reduce every scatter (measured
    1.8 TB/step on mixtral train — EXPERIMENTS.md §Perf HC4)."""
    T, d = xt.shape
    C = buf0.shape[1]
    E, K = cfg.moe.num_experts, cfg.moe.top_k
    flat_e = expert_idx.reshape(-1)                               # (T·K,)
    flat_g = gate_vals.reshape(-1)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)           # (T·K, E)
    pos_in_e = jnp.cumsum(onehot, axis=0) - onehot
    flat_p = jnp.sum(pos_in_e * onehot, axis=-1)                  # (T·K,)
    keep = flat_p < C
    flat_g = jnp.where(keep, flat_g, 0.0)
    safe_p = jnp.where(keep, flat_p, 0)

    token_of_slot = jnp.repeat(jnp.arange(T), K)                  # (T·K,)
    contrib = xt[token_of_slot] * keep[:, None].astype(xt.dtype)
    buf = buf0.at[flat_e, safe_p].add(contrib)

    if cfg.mlp_activation in GATED:
        g = jnp.einsum("ecd,edf->ecf", buf, params["w_gate"])
        u = jnp.einsum("ecd,edf->ecf", buf, params["w_up"])
        g = jax.nn.silu(g) if cfg.mlp_activation == "swiglu" else jax.nn.gelu(g, approximate=True)
        h = g * u
    else:
        u = jnp.einsum("ecd,edf->ecf", buf, params["w_up"])
        h = jax.nn.gelu(u, approximate=True)
    out_buf = jnp.einsum("ecf,efd->ecd", h, params["w_down"])     # (E, C, d)

    slot_out = out_buf[flat_e, safe_p] * flat_g[:, None].astype(xt.dtype)
    out = jnp.zeros((T, d), xt.dtype).at[token_of_slot].add(slot_out.astype(xt.dtype))
    return out


def moe_apply(params, x, cfg: ModelConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, T, d) → (out (B, T, d), aux_loss scalar). Groups = batch rows."""
    from repro.utils.shard import shard_batch

    B, T, d = x.shape
    E = cfg.moe.num_experts
    C = capacity(T, cfg)
    gate_vals, expert_idx, probs = _route(params, x.reshape(B * T, d), cfg)
    gv = gate_vals.reshape(B, T, -1)
    ei = expert_idx.reshape(B, T, -1)
    buf0 = jnp.zeros((B, E, C, d), x.dtype)
    if T > 1:
        # training/prefill: pin the dispatch buffers to the data axis (HC4).
        # decode (T == 1) buffers are tiny and the activations may be
        # deliberately replicated (weight-stationary serving) — constraining
        # them would force a reshard.
        buf0 = shard_batch(buf0)
    out = jax.vmap(lambda xi, g, e, bf: _dispatch_combine(params, xi, g, e,
                                                          bf, cfg))(
        x, gv, ei, buf0)
    if T > 1:
        out = shard_batch(out)
    frac = jnp.mean(jax.nn.one_hot(expert_idx[:, 0], E, dtype=jnp.float32), axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux = cfg.moe.aux_loss_weight * E * jnp.sum(frac * mean_prob)
    return out, aux


# -- held experts (one chip's share of an expert-parallel layer) -------------

def gated_ffn(params, x):
    """SwiGLU x → (silu(x·W_gate) ⊙ x·W_up)·W_down, each product through
    ``wdot`` (float32 result whatever the weights' dtype)."""
    g = wdot("...d,df->...f", x, params["w_gate"])
    u = wdot("...d,df->...f", x, params["w_up"])
    return wdot("...f,fd->...d", jax.nn.silu(g) * u, params["w_down"])


def moe_held_init(key, cfg: ModelConfig, dtype=jnp.float32):
    m = cfg.moe
    d, ff, H, E = cfg.d_model, cfg.expert_d_ff, cfg.experts_held, m.num_experts
    ks = jax.random.split(key, 8)

    def init(k, shape, fan_in):
        return dense_init(k, shape, dtype, scale=fan_in ** -0.5)

    p = {"w_router": init(ks[0], (d, E), d),
         # the correction bias only chooses experts; seeded, non-zero
         "bias": (0.1 * jax.random.normal(ks[1], (E,))).astype(dtype),
         "w_gate": init(ks[2], (H, d, ff), d),
         "w_up": init(ks[3], (H, d, ff), d),
         "w_down": init(ks[4], (H, ff, d), ff)}
    if m.num_shared:
        fs = ff * m.num_shared
        p["shared"] = {"w_gate": init(ks[5], (d, fs), d),
                       "w_up": init(ks[6], (d, fs), d),
                       "w_down": init(ks[7], (fs, d), fs)}
    return p


def route_held(params, xt, cfg: ModelConfig):
    """DeepSeek-V3 routing over all experts, in float32. xt (T, d) →
    (gates (T, K), experts (T, K) int32): sigmoid scores; the experts with
    the largest score + correction bias chosen; the gates the unbiased
    scores of the chosen, normalised over them and scaled by
    ``routed_scale``."""
    m = cfg.moe
    logits = jnp.matmul(xt.astype(jnp.float32),
                        params["w_router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + params["bias"].astype(jnp.float32),
                           m.top_k)
    gates = jnp.take_along_axis(scores, idx, axis=-1)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return gates * m.routed_scale, idx


def _ragged_wdot(x, w, sizes):
    """``ragged_dot(x, w, sizes)`` in float32, with ``wdot``'s terms for
    narrower weights: each group's rows once per term, one after the
    other, so one pass over each expert's weights serves every term. Rows
    past the groups read anything; the caller masks them."""
    x = x.astype(jnp.float32)
    if w.dtype == jnp.float32:
        return jax.lax.ragged_dot(x, w, sizes,
                                  preferred_element_type=jnp.float32)
    terms = split_terms(x, w.dtype)
    M, n = x.shape[0], len(terms)
    group = jnp.searchsorted(jnp.cumsum(sizes), jnp.arange(M), side="right")
    order = jnp.argsort(jnp.tile(group, n), stable=True)
    y = jax.lax.ragged_dot(jnp.concatenate(terms)[order], w, n * sizes,
                           preferred_element_type=jnp.float32)
    return y[jnp.argsort(order)].reshape(n, M, -1).sum(axis=0)


def moe_held_apply(params, x, cfg: ModelConfig):
    """x (..., d) → (..., d) float32: the held experts' weighted outputs
    for the tokens routed to them, plus the shared experts."""
    m = cfg.moe
    shape = x.shape
    xt = x.reshape(-1, shape[-1])
    T, K, H = xt.shape[0], m.top_k, cfg.experts_held
    with jax.named_scope("moe.route"):
        gates, idx = route_held(params, xt, cfg)
        local = idx - m.held_offset
        held = (local >= 0) & (local < H)
        # every assignment to a held expert, grouped by expert; the others
        # sort last, past the groups, and are computed by no group
        key = jnp.where(held, local, H).reshape(-1)              # (T·K,)
        order = jnp.argsort(key, stable=True)
        sizes = jnp.sum(jax.nn.one_hot(key, H, dtype=jnp.int32), axis=0)
        gate = jnp.where(held, gates, 0.0).reshape(-1)[order]
    with jax.named_scope("moe.experts"):
        xs = xt[order // K]
        g = _ragged_wdot(xs, params["w_gate"], sizes)
        u = _ragged_wdot(xs, params["w_up"], sizes)
        y = _ragged_wdot(jax.nn.silu(g) * u, params["w_down"], sizes)
        y = jnp.where((key[order] < H)[:, None], y * gate[:, None], 0.0)
        # back to (token, choice) order and summed over a token's choices
        out = y[jnp.argsort(order)].reshape(T, K, -1).sum(axis=1)
    if m.num_shared:
        with jax.named_scope("moe.shared"):
            out = out + gated_ffn(params["shared"], xt)
    return out.reshape(shape[:-1] + (out.shape[-1],))
