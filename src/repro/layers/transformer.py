"""Transformer decoder/encoder stacks with ``lax.scan`` over layers.

Scan-over-layers keeps the HLO size O(1) in depth (80-layer qwen1.5-110b
lowers as one loop) — essential for multi-arch dry-run compile times and the
standard production pattern. Per-layer params are stacked on a leading L axis.

Block families:
  * dense/vlm/audio: pre-norm attention + pre-norm MLP
  * moe: pre-norm attention + pre-norm MoE
  * mla_moe: pre-norm latent attention (MLA) + pre-norm MLP for the
    leading ``first_k_dense`` layers, run one by one, then pre-norm MLA +
    the held-expert MoE for the rest, scanned (``_mla_run``)
  * ssm (mamba2): pre-norm SSD block only
  * hybrid (zamba2): SSD layers with ONE weight-shared attention+MLP block
    applied every ``hybrid_shared_period`` layers (scan over super-blocks)
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.layers.attention import (attn_decode, attn_decode_paged,
                                    attn_forward, attn_forward_kv, attn_init,
                                    init_cache as attn_init_cache,
                                    mla_decode, mla_decode_paged, mla_forward,
                                    mla_init)
from repro.layers.mlp import mlp_apply, mlp_init
from repro.layers.moe import (gated_ffn, moe_apply, moe_held_apply,
                              moe_held_init, moe_init)
from repro.layers.norms import norm_apply, norm_init, rmsnorm
from repro.layers.ssm import (ssm_decode_step, ssm_forward, ssm_init,
                              ssm_init_cache)
from repro.utils.shard import shard_batch


def _stack_layers(per_layer_params):
    """List of identical pytrees → single pytree with leading layer axis."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_layer_params)


# -- block init ---------------------------------------------------------------

def block_init(key, cfg: ModelConfig, dtype=jnp.float32) -> Dict[str, Any]:
    """One layer's params for the cfg's family."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    if cfg.family in ("ssm",):
        return {"norm": norm_init(cfg.d_model, cfg.norm, dtype),
                "ssm": ssm_init(k1, cfg, dtype)}
    if cfg.family == "hybrid":
        return {"norm": norm_init(cfg.d_model, cfg.norm, dtype),
                "ssm": ssm_init(k1, cfg, dtype)}
    p = {
        "norm1": norm_init(cfg.d_model, cfg.norm, dtype),
        "attn": attn_init(k1, cfg, dtype),
        "norm2": norm_init(cfg.d_model, cfg.norm, dtype),
    }
    if cfg.family == "moe":
        p["moe"] = moe_init(k2, cfg, dtype)
    else:
        p["mlp"] = mlp_init(k2, cfg, dtype)
    return p


def shared_block_init(key, cfg: ModelConfig, dtype=jnp.float32):
    """Zamba2's single shared attention+MLP block."""
    k1, k2 = jax.random.split(key)
    return {
        "norm1": norm_init(cfg.d_model, cfg.norm, dtype),
        "attn": attn_init(k1, cfg, dtype),
        "norm2": norm_init(cfg.d_model, cfg.norm, dtype),
        "mlp": mlp_init(k2, cfg, dtype),
    }


def stack_init(key, cfg: ModelConfig, dtype=jnp.float32):
    if cfg.family == "mla_moe":
        return _mla_stack_init(key, cfg, dtype)
    keys = jax.random.split(key, cfg.num_layers + 1)
    blocks = _stack_layers([block_init(keys[i], cfg, dtype)
                            for i in range(cfg.num_layers)])
    p = {"blocks": blocks, "final_norm": norm_init(cfg.d_model, cfg.norm, dtype)}
    if cfg.family == "hybrid":
        p["shared"] = shared_block_init(keys[-1], cfg, dtype)
    return p


# -- block apply (full sequence) ----------------------------------------------

def _attn_mlp_block(p, x, cfg: ModelConfig, positions, window=None):
    h = x + attn_forward(p["attn"], norm_apply(p["norm1"], x, cfg.norm), cfg,
                         positions, causal=not cfg.is_encoder, window=window)
    if cfg.family == "moe":
        y, aux = moe_apply(p["moe"], norm_apply(p["norm2"], h, cfg.norm), cfg)
        return h + y, aux
    y = mlp_apply(p["mlp"], norm_apply(p["norm2"], h, cfg.norm), cfg)
    return h + y, jnp.float32(0.0)


def stack_forward(params, x, cfg: ModelConfig, positions,
                  window: Optional[int] = None,
                  remat: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence stack. x: (B, T, d) → (h (B, T, d), aux loss).

    ``remat=True`` checkpoints each layer (scan body): backward recomputes
    the block instead of saving per-layer attention/MoE intermediates as
    scan residuals — mandatory at production shapes (a 4k×4k score tensor
    saved for 32 layers is petabytes; see EXPERIMENTS.md §Dry-run).
    """
    if cfg.family in ("ssm", "hybrid"):
        return _ssm_stack_forward(params, x, cfg, remat=remat)
    if cfg.family == "mla_moe":
        h, _ = _mla_run(params, x, cfg, lambda p, x, st, l: (
            mla_forward(p, x, cfg, positions)[0], st), None)
        return h, jnp.float32(0.0)

    def body(carry, p):
        x, aux = carry
        x = shard_batch(x)
        x, a = _attn_mlp_block(p, x, cfg, positions, window)
        return (shard_batch(x), aux + a), None

    if remat:
        body = jax.checkpoint(body)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0.0)), params["blocks"])
    x = norm_apply(params["final_norm"], x, cfg.norm)
    return x, aux


def _ssm_block(p, x, cfg: ModelConfig):
    y, _ = ssm_forward(p["ssm"], norm_apply(p["norm"], x, cfg.norm), cfg)
    return x + y


def _ssm_stack_forward(params, x, cfg: ModelConfig, remat: bool = False):
    period = cfg.hybrid_shared_period if cfg.family == "hybrid" else cfg.num_layers
    L = cfg.num_layers
    assert L % period == 0, (L, period)
    n_super = L // period
    blocks = jax.tree_util.tree_map(
        lambda a: a.reshape((n_super, period) + a.shape[1:]), params["blocks"])
    positions = jnp.broadcast_to(
        jnp.arange(x.shape[1], dtype=jnp.int32)[None], x.shape[:2])

    def super_body(x, p_super):
        def inner(x, p):
            return shard_batch(_ssm_block(p, shard_batch(x), cfg)), None
        if remat:
            inner = jax.checkpoint(inner)
        x, _ = jax.lax.scan(inner, x, p_super)
        if cfg.family == "hybrid":
            x, _ = _attn_mlp_block(params["shared"], x, cfg, positions,
                                   window=cfg.sliding_window)
        return x, None

    if remat:
        super_body = jax.checkpoint(super_body)
    x, _ = jax.lax.scan(super_body, x, blocks)
    x = norm_apply(params["final_norm"], x, cfg.norm)
    return x, jnp.float32(0.0)


# -- prefill (forward + cache priming) ----------------------------------------

def stack_prefill(params, x, cfg: ModelConfig, positions, cache,
                  window: Optional[int] = None):
    """Forward pass that also fills the decode cache with the prompt's K/V
    (attention) or final SSM states. x: (B, T, d). Returns (h, new_cache).

    Assumes the prompt occupies cache slots [0, T) (standard non-ring prefill;
    for ring caches T must be ≤ window)."""
    T = x.shape[1]
    if cfg.family in ("ssm", "hybrid"):
        return _ssm_stack_prefill(params, x, cfg, cache, window)
    if cfg.family == "mla_moe":
        def attend(p, x, c, layer):
            out, lat = mla_forward(p, x, cfg, positions)
            return out, jax.lax.dynamic_update_slice(
                c, lat[None].astype(c.dtype), (layer, 0, 0, 0))
        h, lat = _mla_run(params, x, cfg, attend, cache["latent"])
        return h, {"latent": lat}

    w = window if window is not None else cfg.sliding_window

    def body(carry, xs):
        x, aux = carry
        p, c = xs
        a_out, k, v = attn_forward_kv(p["attn"], norm_apply(p["norm1"], x, cfg.norm),
                                      cfg, positions, causal=not cfg.is_encoder,
                                      window=w)
        S = c["k"].shape[1]
        kk = k[:, -S:].astype(c["k"].dtype)
        vv = v[:, -S:].astype(c["v"].dtype)
        newc = {
            "k": jax.lax.dynamic_update_slice(c["k"], kk, (0, 0, 0, 0)),
            "v": jax.lax.dynamic_update_slice(c["v"], vv, (0, 0, 0, 0)),
        }
        h = x + a_out
        if cfg.family == "moe":
            y, a = moe_apply(p["moe"], norm_apply(p["norm2"], h, cfg.norm), cfg)
        else:
            y, a = mlp_apply(p["mlp"], norm_apply(p["norm2"], h, cfg.norm), cfg), 0.0
        return (h + y, aux + a), newc

    (x, aux), new_attn = jax.lax.scan(body, (x, jnp.float32(0.0)),
                                      (params["blocks"], cache["attn"]))
    x = norm_apply(params["final_norm"], x, cfg.norm)
    return x, {"attn": new_attn}


def _ssm_stack_prefill(params, x, cfg: ModelConfig, cache, window):
    period = cfg.hybrid_shared_period if cfg.family == "hybrid" else cfg.num_layers
    L = cfg.num_layers
    n_super = L // period
    blocks = jax.tree_util.tree_map(
        lambda a: a.reshape((n_super, period) + a.shape[1:]), params["blocks"])
    positions = jnp.broadcast_to(
        jnp.arange(x.shape[1], dtype=jnp.int32)[None], x.shape[:2])

    def super_body(x, xs):
        if cfg.family == "hybrid":
            p_super, attn_c = xs
        else:
            (p_super,) = xs
            attn_c = None

        def inner(x, p):
            y, c = ssm_forward(p["ssm"], norm_apply(p["norm"], x, cfg.norm), cfg)
            return x + y, c
        x, new_ssm = jax.lax.scan(inner, x, p_super)
        new_attn = None
        if cfg.family == "hybrid":
            sp = params["shared"]
            w = window if window is not None else cfg.sliding_window
            a_out, k, v = attn_forward_kv(
                sp["attn"], norm_apply(sp["norm1"], x, cfg.norm), cfg, positions,
                causal=True, window=w)
            S = attn_c["k"].shape[1]
            new_attn = {
                "k": jax.lax.dynamic_update_slice(
                    attn_c["k"], k[:, -S:].astype(attn_c["k"].dtype), (0, 0, 0, 0)),
                "v": jax.lax.dynamic_update_slice(
                    attn_c["v"], v[:, -S:].astype(attn_c["v"].dtype), (0, 0, 0, 0)),
            }
            h = x + a_out
            x = h + mlp_apply(sp["mlp"], norm_apply(sp["norm2"], h, cfg.norm), cfg)
        return x, (new_ssm, new_attn)

    if cfg.family == "hybrid":
        x, (new_ssm, new_attn) = jax.lax.scan(super_body, x, (blocks, cache["shared_attn"]))
    else:
        x, (new_ssm, _) = jax.lax.scan(super_body, x, (blocks,))
    x = norm_apply(params["final_norm"], x, cfg.norm)
    new_cache = {"ssm": jax.tree_util.tree_map(
        lambda a: a.reshape((L,) + a.shape[2:]), new_ssm)}
    if cfg.family == "hybrid":
        new_cache["shared_attn"] = new_attn
    return x, new_cache


# -- caches & decode ----------------------------------------------------------

def stack_init_cache(cfg: ModelConfig, batch: int, max_len: int,
                     dtype=jnp.bfloat16, window: Optional[int] = None):
    """Stacked per-layer caches (leading L axis) + shared-block caches."""
    L = cfg.num_layers
    if cfg.family == "mla_moe":
        return {"latent": jnp.zeros((L, batch, max_len, cfg.mla_latent_dim),
                                    dtype)}
    if cfg.family in ("ssm", "hybrid"):
        one = ssm_init_cache(cfg, batch, dtype)
        cache = {"ssm": jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (L,) + a.shape).copy(), one)}
        if cfg.family == "hybrid":
            n_super = L // cfg.hybrid_shared_period
            w = window if window is not None else cfg.sliding_window
            one_attn = attn_init_cache(cfg, batch, max_len, dtype, window=w)
            cache["shared_attn"] = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a[None], (n_super,) + a.shape).copy(),
                one_attn)
        return cache
    w = window if window is not None else cfg.sliding_window
    one = attn_init_cache(cfg, batch, max_len, dtype, window=w)
    return {"attn": jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (L,) + a.shape).copy(), one)}


def stack_decode(params, x1, cache, pos, cfg: ModelConfig,
                 window: Optional[int] = None):
    """One-token decode through the stack. x1: (B, 1, d)."""
    if cfg.family in ("ssm", "hybrid"):
        return _ssm_stack_decode(params, x1, cache, pos, cfg, window)
    if cfg.family == "mla_moe":
        h, lat = _mla_run(params, x1, cfg, lambda p, x, c, layer: mla_decode(
            p, x, c, layer, pos, cfg), cache["latent"])
        return h, {"latent": lat}

    def body2(x, xs):
        p, c = xs
        a_out, newc = _decode_attn(p, x, c, pos, cfg, window)
        h = x + a_out
        if cfg.family == "moe":
            y, _ = moe_apply(p["moe"], norm_apply(p["norm2"], h, cfg.norm), cfg)
        else:
            y = mlp_apply(p["mlp"], norm_apply(p["norm2"], h, cfg.norm), cfg)
        return h + y, newc

    x, new_attn = jax.lax.scan(body2, x1, (params["blocks"], cache["attn"]))
    x = norm_apply(params["final_norm"], x, cfg.norm)
    return x, {"attn": new_attn}


def _decode_attn(p, x, c, pos, cfg, window):
    return attn_decode(p["attn"], norm_apply(p["norm1"], x, cfg.norm), c, pos,
                       cfg, window=window)


def stack_decode_paged(params, x1, pool, page_table, pos, cfg: ModelConfig):
    """One-token decode through the stack against block-paged KV storage.

    ``pool``: {"k", "v"} with a leading layer axis — (L, N_pages, P, KV,
    hd); ``page_table``: (B, n_pages) int32 shared by every layer (page
    identity is per-(layer, page): layer l of sequence page j lives at
    pool[l, page_table[:, j]]). Returns (h (B, 1, d), new_pool).

    Mirrors ``stack_decode``'s scan-over-layers exactly — same block body,
    same op order — with ``attn_decode_paged`` swapped in for the cache
    update, which is what keeps paged greedy tokens bit-identical to the
    contiguous path (see attn_decode_paged)."""
    if cfg.family == "mla_moe":
        h, lat = _mla_run(params, x1, cfg, lambda p, x, c, layer:
                          mla_decode_paged(p, x, c, layer, page_table, pos,
                                           cfg), pool["latent"])
        return h, {"latent": lat}
    if cfg.family not in ("dense", "moe"):
        raise NotImplementedError(
            f"paged decode supports dense/moe/mla_moe stacks, not "
            f"{cfg.family}")

    def body(x, xs):
        p, pk, pv = xs
        a_out, npk, npv = attn_decode_paged(
            p["attn"], norm_apply(p["norm1"], x, cfg.norm), pk, pv,
            page_table, pos, cfg)
        h = x + a_out
        if cfg.family == "moe":
            y, _ = moe_apply(p["moe"], norm_apply(p["norm2"], h, cfg.norm), cfg)
        else:
            y = mlp_apply(p["mlp"], norm_apply(p["norm2"], h, cfg.norm), cfg)
        return h + y, (npk, npv)

    x, (nk, nv) = jax.lax.scan(body, x1,
                               (params["blocks"], pool["k"], pool["v"]))
    x = norm_apply(params["final_norm"], x, cfg.norm)
    return x, {"k": nk, "v": nv}


# -- latent attention over held experts (mla_moe) -----------------------------
#
# params: {"dense": [block] * first_k_dense, "blocks": the expert layers'
# blocks stacked on a leading axis, "final_norm"}; a block is {"norm1",
# "attn" (MLA), "norm2", "mlp" | "moe"}. Cache and pages hold one latent
# array with a leading axis over all layers, dense ones first:
# {"latent": (L, B, S, r + rope)} or {"latent": (L, N_pages, P, r + rope)}.

def _mla_block_init(key, cfg: ModelConfig, dtype, expert: bool):
    k1, k2 = jax.random.split(key)
    p = {"norm1": norm_init(cfg.d_model, "rmsnorm", dtype),
         "attn": mla_init(k1, cfg, dtype),
         "norm2": norm_init(cfg.d_model, "rmsnorm", dtype)}
    if expert:
        p["moe"] = moe_held_init(k2, cfg, dtype)
    else:
        p["mlp"] = mlp_init(k2, cfg, dtype)
    return p


def _mla_stack_init(key, cfg: ModelConfig, dtype):
    nd, L = cfg.first_k_dense, cfg.num_layers
    keys = jax.random.split(key, L)
    return {"dense": [_mla_block_init(keys[i], cfg, dtype, False)
                      for i in range(nd)],
            "blocks": _stack_layers([_mla_block_init(keys[i], cfg, dtype,
                                                     True)
                                     for i in range(nd, L)]),
            "final_norm": norm_init(cfg.d_model, "rmsnorm", dtype)}


def _mla_run(params, x, cfg: ModelConfig, attend, state):
    """The mla_moe layers over x (B, T, d), in a float32 residual stream:
    each layer is ``x + attend(p["attn"], norm(x), state, layer)`` then the
    MLP or the held experts; ``attend`` returns (out, state) and ``state``
    (the latent cache or pages of every layer, or None) is carried through
    every layer, so each layer writes its rows in place.
    → (final-normed h, state)."""
    def norm(p, x):
        return rmsnorm(p, x, cfg.norm_eps)

    def layer(p, x, state, l):
        a, state = attend(p["attn"], norm(p["norm1"], x), state, l)
        h = x + a
        y = moe_held_apply(p["moe"], norm(p["norm2"], h), cfg) \
            if "moe" in p else gated_ffn(p["mlp"], norm(p["norm2"], h))
        return h + y, state

    x = x.astype(jnp.float32)
    for l, p in enumerate(params["dense"]):
        x, state = layer(p, x, state, l)

    def body(carry, xs):
        x, state = layer(xs[0], *carry, xs[1])
        return (x, state), None

    nd = len(params["dense"])
    (x, state), _ = jax.lax.scan(
        body, (x, state),
        (params["blocks"], nd + jnp.arange(cfg.num_layers - nd)))
    return norm(params["final_norm"], x), state


def _ssm_stack_decode(params, x1, cache, pos, cfg: ModelConfig, window):
    period = cfg.hybrid_shared_period if cfg.family == "hybrid" else cfg.num_layers
    L = cfg.num_layers
    n_super = L // period
    blocks = jax.tree_util.tree_map(
        lambda a: a.reshape((n_super, period) + a.shape[1:]), params["blocks"])
    ssm_cache = jax.tree_util.tree_map(
        lambda a: a.reshape((n_super, period) + a.shape[1:]), cache["ssm"])

    def super_body(x, xs):
        if cfg.family == "hybrid":
            p_super, c_super, attn_c = xs
        else:
            p_super, c_super = xs
            attn_c = None

        def inner(x, pc):
            p, c = pc
            y, newc = ssm_decode_step(p["ssm"],
                                      norm_apply(p["norm"], x, cfg.norm), c, cfg)
            return x + y, newc
        x, new_c = jax.lax.scan(inner, x, (p_super, c_super))
        new_attn = None
        if cfg.family == "hybrid":
            sp = params["shared"]
            a_out, new_attn = attn_decode(
                sp["attn"], norm_apply(sp["norm1"], x, cfg.norm), attn_c, pos,
                cfg, window=window if window is not None else cfg.sliding_window)
            h = x + a_out
            x = h + mlp_apply(sp["mlp"], norm_apply(sp["norm2"], h, cfg.norm), cfg)
        return x, (new_c, new_attn)

    if cfg.family == "hybrid":
        x, (new_ssm, new_attn) = jax.lax.scan(
            super_body, x1, (blocks, ssm_cache, cache["shared_attn"]))
    else:
        x, (new_ssm, _) = jax.lax.scan(super_body, x1, (blocks, ssm_cache))
    x = norm_apply(params["final_norm"], x, cfg.norm)
    new_cache = {"ssm": jax.tree_util.tree_map(
        lambda a: a.reshape((L,) + a.shape[2:]), new_ssm)}
    if cfg.family == "hybrid":
        new_cache["shared_attn"] = new_attn
    return x, new_cache
