"""Attention: MHA / GQA / MQA with RoPE or M-RoPE, causal or bidirectional
masks, sliding-window variants, and one-token KV-cache decode (standard and
ring-buffer window caches).

Conventions:
  x                (B, T, d_model)
  q                (B, T, H, hd)      grouped as (B, T, KV, Q_PER_KV, hd)
  k, v             (B, S, KV, hd)
  cache            dict(k, v)         k/v (B, S_max, KV, hd); RoPE applied at
                                      write time (absolute positions).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.layers.initializers import dense_init
from repro.layers.norms import rmsnorm
from repro.layers.rope import apply_mrope, apply_rope
from repro.utils.precision import split_terms

NEG_INF = -1e30


def attn_init(key, cfg: ModelConfig, dtype=jnp.float32):
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, h, hd), dtype),
        "wk": dense_init(ks[1], (d, kv, hd), dtype),
        "wv": dense_init(ks[2], (d, kv, hd), dtype),
        "wo": dense_init(ks[3], (h, hd, d), dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h, hd), dtype)
        p["bk"] = jnp.zeros((kv, hd), dtype)
        p["bv"] = jnp.zeros((kv, hd), dtype)
    return p


def _project_qkv(params, x, cfg: ModelConfig, positions):
    """positions: (B, T) int32 for rope | (B, T, 3) for mrope | None."""
    q = jnp.einsum("btd,dhk->bthk", x, params["wq"])
    k = jnp.einsum("btd,dhk->bthk", x, params["wk"])
    v = jnp.einsum("btd,dhk->bthk", x, params["wv"])
    if cfg.qkv_bias:
        q = q + params["bq"].astype(q.dtype)
        k = k + params["bk"].astype(k.dtype)
        v = v + params["bv"].astype(v.dtype)
    if cfg.positional == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    elif cfg.positional == "mrope":
        q = apply_mrope(q, positions, cfg.rope_theta)
        k = apply_mrope(k, positions, cfg.rope_theta)
    return q, k, v


def _sdpa(q, k, v, mask, cfg: ModelConfig):
    """q (B,T,H,hd), k/v (B,S,KV,hd), mask (B,T,S) or (T,S) bool (True=keep).

    Matmuls run in the storage dtype with f32 accumulation
    (preferred_element_type) — casting the cache itself to f32 would force a
    full-cache f32 materialization every decode step."""
    B, T, H, hd = q.shape
    KV = k.shape[2]
    g = H // KV
    qg = q.reshape(B, T, KV, g, hd)
    scores = jnp.einsum("btkgh,bskh->bkgts", qg, k.astype(qg.dtype),
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(hd).astype(jnp.float32)
    if mask is not None:
        if mask.ndim == 2:
            mask = mask[None]
        scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgts,bskh->btkgh", probs.astype(v.dtype),
                     v, preferred_element_type=jnp.float32)
    return out.reshape(B, T, H, hd).astype(q.dtype)


# Above this many query positions, full-sequence attention switches to the
# chunked online-softmax path so the (T, S) score matrix never materializes.
CHUNKED_ATTN_THRESHOLD = 2048
Q_CHUNK = 512


def _sdpa_chunked(q, k, v, cfg: ModelConfig, causal: bool,
                  window: Optional[int], q_chunk: int = Q_CHUNK):
    """Memory-bounded attention: sequential scan over query chunks.

    Only one (B, KV, g, q_chunk, S) score tile is live at a time (softmax is
    taken over the full key axis per chunk, so no online-softmax carry is
    needed). Exact — tested allclose vs _sdpa. This is the flash-attention
    memory discipline expressed in pure JAX; on real TPU the same tiling
    would live in a Pallas kernel.

    SEQUENCE-PARALLEL path (EXPERIMENTS.md §Perf HC2): when the head count
    does not divide the model axis (smollm 15H, gemma 8H, starcoder2 24H,
    qwen2-vl 12H on a 16-way axis), head sharding is impossible and the
    baseline replicates attention over `model` — per-device attention cost
    ×msize. Instead we shard each chunk's QUERY dim over `model`: every
    device computes q_chunk/msize query rows against the full (replicated)
    K/V. Score/prob tiles, flops, and HBM traffic all divide by msize; the
    only new collective is the output re-gather, O(B·T·H·hd) ≪ scores.
    """
    from repro.utils.shard import model_axis_size, shard_axis

    B, T, H, hd = q.shape
    S, KV = k.shape[1], k.shape[2]
    g = H // KV
    pad = (-T) % q_chunk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nq = (T + pad) // q_chunk
    qc = q.reshape(B, nq, q_chunk, KV, g, hd)
    kf = k.astype(q.dtype)
    vf = v.astype(q.dtype)
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    kpos = jnp.arange(S)
    import os
    msize = model_axis_size()
    # REPRO_SEQ_PARALLEL=0 reproduces the paper-faithful replicated baseline
    seq_parallel = (os.environ.get("REPRO_SEQ_PARALLEL", "1") == "1"
                    and msize > 1 and H % msize != 0
                    and q_chunk % msize == 0)

    def chunk_body(_, qi_i):
        qi, i = qi_i                                  # (B, qc, KV, g, hd)
        if seq_parallel:
            qi = shard_axis(qi, 1, "model")
        qpos = i * q_chunk + jnp.arange(q_chunk)
        scores = jnp.einsum("bqkgh,bskh->bkgqs", qi, kf,
                            preferred_element_type=jnp.float32) * scale
        m = jnp.ones((q_chunk, S), bool)
        if causal:
            m &= kpos[None, :] <= qpos[:, None]
        if window is not None:
            m &= kpos[None, :] > qpos[:, None] - window
        scores = jnp.where(m[None, None, None], scores, NEG_INF)
        if seq_parallel:
            scores = shard_axis(scores, 3, "model")
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bkgqs,bskh->bqkgh", probs.astype(vf.dtype), vf,
                         preferred_element_type=jnp.float32)
        return None, out

    _, outs = jax.lax.scan(chunk_body, None,
                           (jnp.moveaxis(qc, 1, 0), jnp.arange(nq)))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, T + pad, H, hd)
    return out[:, :T].astype(q.dtype)


def make_mask(T: int, S: int, causal: bool, window: Optional[int] = None,
              q_offset: int = 0) -> jnp.ndarray:
    """(T, S) bool keep-mask. ``q_offset``: absolute position of query row 0."""
    qpos = jnp.arange(T)[:, None] + q_offset
    kpos = jnp.arange(S)[None, :]
    m = jnp.ones((T, S), bool)
    if causal:
        m &= kpos <= qpos
    if window is not None:
        m &= kpos > qpos - window
    return m


def attn_forward(params, x, cfg: ModelConfig, positions,
                 causal: bool = True, window: Optional[int] = None):
    """Full-sequence attention (training / prefill). Returns (B, T, d)."""
    return attn_forward_kv(params, x, cfg, positions, causal, window)[0]


def attn_forward_kv(params, x, cfg: ModelConfig, positions,
                    causal: bool = True, window: Optional[int] = None):
    """Like attn_forward but also returns (k, v) for cache priming."""
    q, k, v = _project_qkv(params, x, cfg, positions)
    T = x.shape[1]
    w = window if window is not None else cfg.sliding_window
    is_causal = causal and not cfg.is_encoder
    if T >= CHUNKED_ATTN_THRESHOLD:
        out = _sdpa_chunked(q, k, v, cfg, is_causal, w)
    else:
        mask = make_mask(T, T, causal=is_causal, window=w)
        out = _sdpa(q, k, v, mask, cfg)
    return jnp.einsum("bthk,hkd->btd", out, params["wo"]), k, v


# -- KV-cache decode ---------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16,
               window: Optional[int] = None):
    """Standard cache of ``max_len`` slots, or ring buffer of ``window``."""
    S = window if window is not None else max_len
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, S, kv, hd), dtype),
        "v": jnp.zeros((batch, S, kv, hd), dtype),
    }


def attn_decode(params, x1, cache, pos, cfg: ModelConfig,
                window: Optional[int] = None):
    """One-token decode. x1: (B, 1, d); pos: scalar int32 absolute position,
    or a (B,) int32 vector of PER-ROW positions (continuous batching: rows
    of one batched decode step may sit at different depths after a request
    joined mid-stream — see ``SpecDecodeStream`` in repro.serving.spec).

    Returns (out (B, 1, d), new_cache). Ring-buffer semantics when ``window``
    (or cfg.sliding_window) is set and the cache S equals that window.
    The scalar and vector paths write identical K/V values and build
    identical masks for rows at equal positions, so per-row results are
    bit-identical across the two.

    Slot-reuse audit: when a stream evicts a slot and a later request
    reuses it, the old occupant's K/V rows persist in the cache until the
    new join's splice overwrites the ENTIRE row (spec.stream._splice_cache
    replaces all S slots). Between eviction and reuse the idle row keeps
    decoding parked at pos 0 — its write lands in slot 0 of its own row
    and its output is discarded, so the ``arange(S) <= pos`` mask plus
    finite stale values guarantee no leakage into live rows (the same
    argument attn_decode_paged makes for recycled pages).
    """
    B = x1.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    per_row = pos.ndim == 1
    pvec = pos if per_row else jnp.broadcast_to(pos, (B,))
    if cfg.positional == "mrope":
        p3 = jnp.broadcast_to(pvec[:, None, None], (B, 1, 3))
        q, k, v = _project_qkv(params, x1, cfg, p3)
    else:
        q, k, v = _project_qkv(params, x1, cfg, pvec[:, None])
    S = cache["k"].shape[1]
    w = window if window is not None else cfg.sliding_window
    is_ring = w is not None and S == w
    if per_row:
        # each row writes its own cache slot: scatter instead of a shared
        # dynamic_update_slice. Out-of-range positions (an idle stream slot
        # parked at 0 past its end) clamp like dynamic_update_slice would.
        slot = (pvec % S) if is_ring else jnp.minimum(pvec, S - 1)
        rows = jnp.arange(B)
        ck = cache["k"].at[rows, slot].set(k[:, 0].astype(cache["k"].dtype))
        cv = cache["v"].at[rows, slot].set(v[:, 0].astype(cache["v"].dtype))
        if is_ring:
            valid = jnp.arange(S)[None, :] < jnp.minimum(pvec + 1, S)[:, None]
        else:
            valid = jnp.arange(S)[None, :] <= pvec[:, None]      # (B, S)
        mask = valid[:, None, :]
    else:
        slot = (pos % S) if is_ring else pos
        ck = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0))
        cv = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0))
        if is_ring:
            valid = jnp.arange(S) < jnp.minimum(pos + 1, S)      # (S,)
        else:
            valid = jnp.arange(S) <= pos
        mask = jnp.broadcast_to(valid[None, None, :], (B, 1, S))
    out = _sdpa(q, ck, cv, mask, cfg)
    out = jnp.einsum("bthk,hkd->btd", out, params["wo"])
    return out, {"k": ck, "v": cv}


def attn_decode_paged(params, x1, pk, pv, page_table, pos, cfg: ModelConfig):
    """One-token decode against block-paged KV storage (one layer's pool).

    pk/pv: (N_pages, P, KV, hd) page pool; page_table: (B, n_pages) int32
    mapping each row's sequence pages to pool pages; pos: (B,) int32
    per-row positions. Returns (out (B, 1, d), new_pk, new_pv).

    Each row scatter-writes its new K/V at (page_table[row, pos // P],
    pos % P), then attends over the gathered view pk[page_table] reshaped
    to a dense (B, n_pages * P, KV, hd) — the contiguous cache's exact
    shape when n_pages * P == max_len, with identical values at every
    position <= pos and the identical ``arange(S) <= pos`` keep-mask. That
    makes greedy paged decode bit-identical to ``attn_decode``'s vector-pos
    branch: masked scores are NEG_INF exactly, their probabilities exp to
    exact 0.0, and 0.0 times a finite stale row contributes exact zeros.

    Stale-content discipline (the reuse audit): a freed page keeps its old
    occupant's rows until someone writes it, and page 0 (the TRASH page)
    accumulates junk from every idle slot's parked write at (0, 0). Neither
    can leak: positions beyond a row's ``pos`` are masked out exactly, a
    fresh join overwrites every in-range row of its pages from its own solo
    prefill before they become visible, and idle rows (parked at pos 0 over
    an all-trash page table) have their outputs discarded. The ONLY
    invariant this rests on is that stale contents stay FINITE — previous
    K/V values and zero-init are; nothing ever writes inf/NaN into a page.
    tests/test_kvpool.py poisons freed pages with large values to pin this.
    """
    B = x1.shape[0]
    pvec = jnp.asarray(pos, jnp.int32)
    if pvec.ndim == 0:
        pvec = jnp.broadcast_to(pvec, (B,))
    q, k, v = _project_qkv(params, x1, cfg, pvec[:, None])
    P = pk.shape[1]
    n_pages = page_table.shape[1]
    S = n_pages * P
    rows = jnp.arange(B)
    # clamp like the dense path's out-of-range write: an idle slot parked
    # at 0 lands on the trash page its table points at anyway
    page = page_table[rows, jnp.minimum(pvec // P, n_pages - 1)]
    off = pvec % P
    pk = pk.at[page, off].set(k[:, 0].astype(pk.dtype))
    pv = pv.at[page, off].set(v[:, 0].astype(pv.dtype))
    ck = pk[page_table].reshape(B, S, pk.shape[2], pk.shape[3])
    cv = pv[page_table].reshape(B, S, pv.shape[2], pv.shape[3])
    valid = jnp.arange(S)[None, :] <= pvec[:, None]          # (B, S)
    out = _sdpa(q, ck, cv, valid[:, None, :], cfg)
    out = jnp.einsum("bthk,hkd->btd", out, params["wo"])
    return out, pk, pv


# -- latent attention (MLA) ---------------------------------------------------
#
# DeepSeek-V2 (arXiv:2405.04434 §2.1) without query compression:
#   q = x·W_q, split per head into q_nope and q_rope;
#   [c, k_rope] = x·W_kva;  c′ = RMSNorm(c);  [k_nope, v] = c′·W_kvb;
#   RoPE on q_rope and on k_rope, one rotary key shared by every head;
#   score = (q_nope·k_nope + q_rope·k_rope) / √(nope + rope).
# RoPE layout: split halves (``rope.apply_rope``). The published code
# de-interleaves the rotary channels first; with random weights that is a
# fixed permutation of W_q's and W_kva's rotary columns.
# The cache holds [c′, k_rope] per token (``cfg.mla_latent_dim`` wide).
# Prefill decompresses keys and values from c′; decode absorbs W_kvb's key
# half into the query and its value half into the output and attends over
# the cached latent itself. Activations and the cache are float32; the
# products between activations run at HIGHEST and those with weights
# through ``wdot``.


HIGHEST = jax.lax.Precision.HIGHEST


def wdot(spec: str, x, w):
    """``einsum(spec, x, w)`` in float32 with weights of any dtype. A
    float32 ``x`` meets narrower (bfloat16) weights as its terms
    (``utils.precision.split_terms``) in one product, so the weights are
    read once and the result keeps float32's accuracy. One expert choice
    that differs from a float32 computation's changes a token's state in
    every later layer and every later token's attention."""
    x = x.astype(jnp.float32)
    if w.dtype == jnp.float32:
        return jnp.einsum(spec, x, w)
    xs, rest = spec.split(",", 1)
    out = jnp.einsum("z" + xs + "," + rest.replace("->", "->z"),
                     jnp.stack(split_terms(x, w.dtype)), w,
                     preferred_element_type=jnp.float32)
    return out.sum(axis=0)


def mla_init(key, cfg: ModelConfig, dtype=jnp.float32):
    d, H, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 4)

    def init(k, shape, fan_in):
        return dense_init(k, shape, dtype, scale=fan_in ** -0.5)

    return {"wq": init(ks[0], (d, H, nope + rope), d),
            "wkv_a": init(ks[1], (d, r + rope), d),
            "kv_norm": {"scale": jnp.ones((r,), dtype)},
            "wkv_b": init(ks[2], (r, H, nope + vd), r),
            "wo": init(ks[3], (H, vd, d), H * vd)}


def _mla_query(params, x, cfg: ModelConfig, positions):
    """(q_nope (B, T, H, nope), rotated q_rope (B, T, H, rope)), float32."""
    q = wdot("btd,dhk->bthk", x, params["wq"])
    nope = cfg.qk_nope_head_dim
    return q[..., :nope], apply_rope(q[..., nope:], positions, cfg.rope_theta)


def mla_latent(params, x, cfg: ModelConfig, positions):
    """What the cache holds for each token of ``x``: [c′, rotated k_rope],
    (B, T, kv_lora_rank + rope) float32."""
    kv = wdot("btd,dr->btr", x, params["wkv_a"])
    r = cfg.kv_lora_rank
    c = rmsnorm(params["kv_norm"], kv[..., :r], cfg.norm_eps)
    k_rope = apply_rope(kv[..., None, r:], positions, cfg.rope_theta)
    k_rope = k_rope[..., 0, :]
    return jnp.concatenate([c, k_rope], axis=-1)


def _mla_scale(cfg: ModelConfig) -> float:
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5


def mla_forward(params, x, cfg: ModelConfig, positions):
    """Causal MLA over a whole sequence in the decompressed form.
    x (B, T, d) → (out (B, T, d) float32, latent (B, T, r + rope))."""
    with jax.named_scope("mla"):
        q_nope, q_rope = _mla_query(params, x, cfg, positions)
        lat = mla_latent(params, x, cfg, positions)
        r, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
        kv = wdot("bsr,rhk->bshk", lat[..., :r], params["wkv_b"])
        k_nope, v = kv[..., :nope], kv[..., nope:]
        scores = (jnp.einsum("bthk,bshk->bhts", q_nope, k_nope,
                             precision=HIGHEST)
                  + jnp.einsum("bthk,bsk->bhts", q_rope, lat[..., r:],
                               precision=HIGHEST)) * _mla_scale(cfg)
        T = x.shape[1]
        mask = make_mask(T, T, causal=True)
        probs = jax.nn.softmax(jnp.where(mask, scores, NEG_INF), axis=-1)
        o = jnp.einsum("bhts,bshk->bthk", probs, v, precision=HIGHEST)
        return wdot("bthk,hkd->btd", o, params["wo"]), lat


def mla_attend_latent(params, x1, latent, valid, pos, cfg: ModelConfig):
    """One query token per row attending over cached latents in the
    absorbed form. x1 (B, 1, d); latent (B, S, r + rope) holding every
    position the row may see, the row's own included; valid (B, S) bool;
    pos (B,) int32. → (B, 1, d) float32."""
    with jax.named_scope("mla"):
        q_nope, q_rope = _mla_query(params, x1, cfg, pos[:, None])
        r, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
        w_k = params["wkv_b"][..., :nope]                    # (r, H, nope)
        w_v = params["wkv_b"][..., nope:]                    # (r, H, v)
        q_c = wdot("bthk,rhk->bthr", q_nope, w_k)
        lat = latent.astype(jnp.float32)
        scores = (jnp.einsum("bthr,bsr->bhts", q_c, lat[..., :r],
                             precision=HIGHEST)
                  + jnp.einsum("bthk,bsk->bhts", q_rope, lat[..., r:],
                               precision=HIGHEST)) * _mla_scale(cfg)
        scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        o_c = jnp.einsum("bhts,bsr->bthr", probs, lat[..., :r],
                         precision=HIGHEST)
        o = wdot("bthr,rhk->bthk", o_c, w_v)
        return wdot("bthk,hkd->btd", o, params["wo"])


def mla_decode(params, x1, cache, layer, pos, cfg: ModelConfig):
    """One-token decode against the contiguous latent cache of every layer,
    (L, B, S, r + rope); ``layer`` selects this layer's; pos (B,) int32
    per-row positions (an idle row parked past the end clamps onto the last
    slot, like ``attn_decode``). → (out (B, 1, d), new cache)."""
    B, S = cache.shape[1:3]
    pvec = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    lat1 = mla_latent(params, x1, cfg, pvec[:, None])
    cache = cache.at[layer, jnp.arange(B), jnp.minimum(pvec, S - 1)].set(
        lat1[:, 0].astype(cache.dtype))
    valid = jnp.arange(S)[None, :] <= pvec[:, None]
    return mla_attend_latent(params, x1, cache[layer], valid, pvec,
                             cfg), cache


def mla_decode_paged(params, x1, pool, layer, page_table, pos,
                     cfg: ModelConfig):
    """One-token decode against the latent pages of every layer, (L,
    N_pages, P, r + rope), addressed as ``attn_decode_paged`` addresses K/V
    pages: this ``layer``'s new latent of each row is written at
    (page_table[row, pos // P], pos % P), and the row attends over its
    gathered pages, positions past ``pos`` masked. Writing into and reading
    from the whole pool, never a copy of one layer's slice, keeps the
    step's pool traffic to the rows it touches. → (out (B, 1, d), pool)."""
    B = x1.shape[0]
    pvec = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    P = pool.shape[2]
    n_pages = page_table.shape[1]
    S = n_pages * P
    lat1 = mla_latent(params, x1, cfg, pvec[:, None])
    page = page_table[jnp.arange(B), jnp.minimum(pvec // P, n_pages - 1)]
    pool = pool.at[layer, page, pvec % P].set(lat1[:, 0].astype(pool.dtype))
    view = pool[layer, page_table].reshape(B, S, pool.shape[-1])
    valid = jnp.arange(S)[None, :] <= pvec[:, None]
    return mla_attend_latent(params, x1, view, valid, pvec, cfg), pool
