"""The mla_moe family: DeepSeek-V3-style decoders (Moonlight-16B-A3B) as one
chip's share of an expert-parallel deployment, written from the equations
alone.

Each layer is pre-norm: x += MLA(RMSNorm(x)); x += FFN(RMSNorm(x)); a final
RMSNorm gives the head's input. The first ``first_k_dense`` layers' FFN is
a SwiGLU of width ``d_ff``; the others' is the expert layer.

Latent attention (arXiv:2405.04434 §2.1, no query compression), for head
i, in the decompressed form:

    q_i = x·W_q,i = [q_nope,i ; q_rope,i]
    [c ; k_rope] = x·W_kva,    c′ = RMSNorm(c)
    [k_nope,i ; v_i] = c′·W_kvb,i
    q_rope,i, k_rope rotated by RoPE at their positions (θ = rope_theta)
    o_i = softmax_causal((q_nope,i·k_nope,i + q_rope,i·k_rope) / √(nope + rope))·v_i
    out = Σ_i o_i·W_o,i

Expert layer (arXiv:2412.19437 §2.1.2, one group): s = σ(x·W_router) over
all ``num_experts``; the ``experts_per_token`` experts with the largest
s + b (b the correction bias) are chosen; gate_e = s_e / Σ_chosen s ×
``routed_scaling_factor``; out = Σ_e gate_e·SwiGLU_e(x) + SwiGLU_shared(x).
This chip holds experts [expert_offset, expert_offset + experts_held): the
sum runs over those alone, every one computed densely over every token and
weighted by its gate (zero where not chosen); what the absent experts add
is left out, as in the program: one chip's share of the layer. The
shared experts are one SwiGLU of ``num_shared_experts`` × ``expert_d_ff``.

Departures from the published description, all written here:
  * RoPE pairs channels as split halves (the first half of the rotary
    channels with the second); the published code pairs neighbours. With
    random weights that is a fixed permutation of W_q's and W_kva's rotary
    columns.
  * every RMSNorm, c's included, uses ``norm_eps`` (rms_norm_eps, 1e-5);
    the published code leaves c's norm at its class default, 1e-6 (against
    a mean square near 1: a relative difference of 5e-6).
  * the weights are random from the seed (``init_weights``), the
    correction bias included.

Nothing here imports the program. ``trunk`` computes in the dtype of the
weights it is given (float32 for the reference), every product at HIGHEST.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import HIGHEST, float8_weights

# keys of the configuration the program's registry entry must also hold,
# beside those every family checks (``system.CHECKED_KEYS``)
checked_keys = ("num_heads", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "rope_theta", "norm_eps", "first_k_dense", "d_ff",
                "num_experts", "experts_per_token", "expert_d_ff",
                "num_shared_experts", "routed_scaling_factor",
                "experts_held", "expert_offset")

# bytes of one cached latent value: the program keeps state in float32
CACHE_BYTES = 4


def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _dims(conf: dict) -> dict:
    if conf.get("q_lora_rank") is not None or \
            conf.get("scoring_func", "sigmoid") != "sigmoid" or \
            not conf.get("norm_topk_prob", True):
        raise ValueError(f"{conf['name']}: the mla_moe family needs no query "
                         f"compression, sigmoid scores and normalised gates")
    return dict(d=conf["d_model"], H=conf["num_heads"],
                r=conf["kv_lora_rank"], nope=conf["qk_nope_head_dim"],
                rope=conf["qk_rope_head_dim"], v=conf["v_head_dim"],
                ff=conf["d_ff"], ffe=conf["expert_d_ff"],
                E=conf["num_experts"], K=conf["experts_per_token"],
                held=conf["experts_held"], off=conf["expert_offset"],
                shared=conf["num_shared_experts"], V=conf["vocab_size"],
                L=conf["num_layers"], dense=conf["first_k_dense"])


def init_weights(key, conf: dict) -> dict:
    """Random weights from one key, in the configuration's dtype: the
    embedding N(0, 1); every matrix N(0, 1/fan-in); RMSNorm scales 1; the
    correction bias N(0, 0.1²). → {"emb" (V, d), "w_out" (V, d),
    "final_norm" (d,), "dense": [layer], "blocks": expert layers stacked
    on a leading axis}; a layer holds "norm1", "wq" (d, H, nope + rope),
    "wkv_a" (d, r + rope), "kv_norm" (r,), "wkv_b" (r, H, nope + v), "wo"
    (H, v, d), "norm2", and "w_gate", "w_up" (d, ff), "w_down" (ff, d)
    (dense) or "w_router" (d, E), "bias" (E,), "e_gate", "e_up" (held, d,
    ffe), "e_down" (held, ffe, d), "s_gate", "s_up" (d, shared·ffe),
    "s_down" (shared·ffe, d) (expert)."""
    m = _dims(conf)
    dtype = jnp.dtype(conf["dtype"])
    d, H, r, ff, ffe = m["d"], m["H"], m["r"], m["ff"], m["ffe"]
    fs = m["shared"] * ffe

    def mat(k, shape, fan_in, lead=()):
        return (jax.random.normal(k, lead + shape, jnp.float32)
                * fan_in ** -0.5).astype(dtype)

    def layer(k, expert: bool, lead=()):
        ks = jax.random.split(k, 12)
        one = lambda n: jnp.ones(lead + (n,), dtype)  # noqa: E731
        p = {"norm1": one(d), "norm2": one(d), "kv_norm": one(r),
             "wq": mat(ks[0], (d, H, m["nope"] + m["rope"]), d, lead),
             "wkv_a": mat(ks[1], (d, r + m["rope"]), d, lead),
             "wkv_b": mat(ks[2], (r, H, m["nope"] + m["v"]), r, lead),
             "wo": mat(ks[3], (H, m["v"], d), H * m["v"], lead)}
        if not expert:
            p.update(w_gate=mat(ks[4], (d, ff), d, lead),
                     w_up=mat(ks[5], (d, ff), d, lead),
                     w_down=mat(ks[6], (ff, d), ff, lead))
            return p
        p.update(w_router=mat(ks[4], (d, m["E"]), d, lead),
                 bias=(0.1 * jax.random.normal(ks[5], lead + (m["E"],))
                       ).astype(dtype),
                 e_gate=mat(ks[6], (m["held"], d, ffe), d, lead),
                 e_up=mat(ks[7], (m["held"], d, ffe), d, lead),
                 e_down=mat(ks[8], (m["held"], ffe, d), ffe, lead),
                 s_gate=mat(ks[9], (d, fs), d, lead),
                 s_up=mat(ks[10], (d, fs), d, lead),
                 s_down=mat(ks[11], (fs, d), fs, lead))
        return p

    ks = jax.random.split(key, 3 + m["dense"])
    return {"emb": jax.random.normal(ks[0], (m["V"], d)).astype(dtype),
            "w_out": mat(ks[1], (m["V"], d), d),
            "final_norm": jnp.ones((d,), dtype),
            "dense": [layer(ks[3 + i], False) for i in range(m["dense"])],
            "blocks": layer(ks[2], True, (m["L"] - m["dense"],))}


def program_params(w, conf: dict) -> dict:
    """``init_weights``' arrays in the program's parameter tree (the same
    arrays, no copy), with the program's zero output bias."""
    def attn(p):
        return {"norm1": {"scale": p["norm1"]},
                "norm2": {"scale": p["norm2"]},
                "attn": {"wq": p["wq"], "wkv_a": p["wkv_a"],
                         "kv_norm": {"scale": p["kv_norm"]},
                         "wkv_b": p["wkv_b"], "wo": p["wo"]}}

    b = w["blocks"]
    blocks = dict(attn(b), moe={
        "w_router": b["w_router"], "bias": b["bias"],
        "w_gate": b["e_gate"], "w_up": b["e_up"], "w_down": b["e_down"],
        "shared": {"w_gate": b["s_gate"], "w_up": b["s_up"],
                   "w_down": b["s_down"]}})
    dense = [dict(attn(p), mlp={k: p[k] for k in ("w_gate", "w_up",
                                                   "w_down")})
             for p in w["dense"]]
    return {"embed": {"embedding": w["emb"], "lm_head": w["w_out"],
                      "lm_bias": jnp.zeros(w["w_out"].shape[:1],
                                           w["w_out"].dtype)},
            "stack": {"dense": dense, "blocks": blocks,
                      "final_norm": {"scale": w["final_norm"]}}}


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """Split-halves RoPE: x (..., T, n, D) at positions pos (T,)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / theta ** (np.arange(half, dtype=np.float32) / half)
    ang = (pos[:, None] * freqs)[:, None, :].astype(x.dtype)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _mla(p, x, m, conf):
    """Causal latent attention over x (B, T, d), decompressed."""
    T = x.shape[1]
    pos = jnp.arange(T, dtype=jnp.float32)
    nope, r = m["nope"], m["r"]
    q = _mm("btd,dhk->bthk", x, p["wq"])
    kv = _mm("btd,dr->btr", x, p["wkv_a"])
    c = _rms(kv[..., :r], p["kv_norm"], conf["norm_eps"])
    k_rope = _rope(kv[..., None, r:], pos, conf["rope_theta"])[..., 0, :]
    q_rope = _rope(q[..., nope:], pos, conf["rope_theta"])
    kvb = _mm("bsr,rhk->bshk", c, p["wkv_b"])
    s = (_mm("bthk,bshk->bhts", q[..., :nope], kvb[..., :nope])
         + _mm("bthk,bsk->bhts", q_rope, k_rope)) / np.sqrt(
        nope + m["rope"]).astype(np.float32)
    causal = np.tril(np.ones((T, T), bool))
    a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = _mm("bhts,bshk->bthk", a, kvb[..., nope:])
    return _mm("bthk,hkd->btd", o, p["wo"])


def _swiglu(x, wg, wu, wd):
    return _mm("...f,fd->...d", jax.nn.silu(_mm("...d,df->...f", x, wg))
               * _mm("...d,df->...f", x, wu), wd)


def _experts(p, x, m, conf):
    """The held experts' part of the expert layer plus the shared experts,
    every held expert over every token, weighted by its gate."""
    logits = _mm("btd,de->bte", x.astype(jnp.float32),
                 p["w_router"].astype(jnp.float32))
    s = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(s + p["bias"].astype(jnp.float32), m["K"])
    g = jnp.take_along_axis(s, chosen, -1)
    g = g / jnp.sum(g, -1, keepdims=True) * conf["routed_scaling_factor"]
    held = m["off"] + jnp.arange(m["held"])
    gate = jnp.sum(jnp.where(chosen[..., None] == held, g[..., None], 0.0),
                   axis=-2).astype(x.dtype)                  # (B, T, held)
    h = jax.nn.silu(_mm("btd,edf->ebtf", x, p["e_gate"])) \
        * _mm("btd,edf->ebtf", x, p["e_up"])
    y = _mm("ebtf,efd->ebtd", h, p["e_down"])
    return jnp.einsum("ebtd,bte->btd", y, gate, precision=HIGHEST) \
        + _swiglu(x, p["s_gate"], p["s_up"], p["s_down"])


def trunk(w, tokens, conf: dict):
    """Teacher-forced final hidden states: tokens (B, T) → (B, T, d), in
    the weights' dtype (float32 for the reference), every product at
    HIGHEST, the final norm applied."""
    m = _dims(conf)
    eps = conf["norm_eps"]
    x = w["emb"][tokens]
    n_exp = m["L"] - m["dense"]
    layers = list(w["dense"]) + [jax.tree.map(lambda a, i=i: a[i],
                                              w["blocks"])
                                 for i in range(n_exp)]
    for p in layers:
        x = x + _mla(p, _rms(x, p["norm1"], eps), m, conf)
        h = _rms(x, p["norm2"], eps)
        x = x + (_experts(p, h, m, conf) if "w_router" in p else
                 _swiglu(h, p["w_gate"], p["w_up"], p["w_down"]))
    return _rms(x, w["final_norm"], eps)


# weights that stay as they are under the float8 control
_KEPT = ("norm1", "norm2", "kv_norm", "bias", "final_norm")


def lower(w, control: str):
    """The weights at a control's precision (``reference.CONTROLS``):
    ``float8``, every weight matrix of every layer rounded to e4m3 with a
    per-tensor scale (the embedding and head included), norms and the
    correction bias kept; ``bfloat16``, every array in bfloat16."""
    if control == "bfloat16":
        return jax.tree.map(lambda a: a.astype(jnp.bfloat16), w)
    if control != "float8":
        raise ValueError(f"unknown control {control!r}")

    def layer(p, stacked: bool):
        f8 = jax.vmap(float8_weights) if stacked else float8_weights
        return {k: a if k in _KEPT else f8(a) for k, a in p.items()}

    return {"emb": float8_weights(w["emb"]),
            "w_out": float8_weights(w["w_out"]),
            "final_norm": w["final_norm"],
            "dense": [layer(p, False) for p in w["dense"]],
            "blocks": layer(w["blocks"], True)}


def _layers(m: dict, layers: str) -> tuple:
    """(dense layers, expert layers) that ``layers`` counts: "all" the
    trunk's, "experts" the expert layers alone (the program's scanned
    layer loop)."""
    if layers not in ("all", "experts"):
        raise ValueError(f"unknown layers {layers!r}")
    return (m["dense"] if layers == "all" else 0), m["L"] - m["dense"]


def _layer_macs(m: dict) -> tuple:
    """(multiply-adds a token needs in one dense layer, in one expert layer,
    the attention's per visible position): MLA's projections with each
    token's keys and values decompressed once, its scores and weighted sum
    over the visible positions; the expert layer's router, the chip's share
    of the routed experts (experts_per_token × held/num_experts of them on
    average) and the shared experts."""
    d, H = m["d"], m["H"]
    attn = (d * H * (m["nope"] + m["rope"]) + d * (m["r"] + m["rope"])
            + m["r"] * H * (m["nope"] + m["v"]) + H * m["v"] * d)
    per_pos = H * (m["nope"] + m["rope"] + m["v"])
    expert = 3 * d * m["ffe"]
    moe = d * m["E"] + expert * (m["K"] * m["held"] / m["E"] + m["shared"])
    return attn + 3 * d * m["ff"], attn + moe, per_pos


def trunk_flops(conf: dict, positions, layers: str = "all") -> float:
    """Operations of the trunk (or of its expert layers alone, ``layers``
    "experts") for one token at each of ``positions`` (0-based: a token at
    position p sees p + 1 positions)."""
    m = _dims(conf)
    n_dense, n_exp = _layers(m, layers)
    dense, expert, per_pos = _layer_macs(m)
    n = len(positions)
    seen = float(np.sum(np.asarray(positions, np.float64) + 1))
    return 2.0 * (n * (n_dense * dense + n_exp * expert)
                  + (n_dense + n_exp) * per_pos * seen)


def decode_bytes(conf: dict, positions, layers: str = "all") -> float:
    """Bytes one decode step must read from memory for busy rows at
    ``positions``: every weight of the trunk once (all held experts: a
    step of many rows routes to each of them), the cached latents each row
    attends over (p + 1 positions of every layer, float32), and the
    embedding rows of the step's tokens; the head is counted apart. With
    ``layers`` "experts": the expert layers' weights and latents alone."""
    m = _dims(conf)
    n_dense, n_exp = _layers(m, layers)
    item = jnp.dtype(conf["dtype"]).itemsize
    d, H, r, E = m["d"], m["H"], m["r"], m["E"]
    attn = (d * H * (m["nope"] + m["rope"]) + d * (r + m["rope"]) + r
            + r * H * (m["nope"] + m["v"]) + H * m["v"] * d + 2 * d)
    moe = (d + 1) * E + 3 * d * m["ffe"] * (m["held"] + m["shared"])
    weights = (n_dense + n_exp) * attn + n_dense * 3 * d * m["ff"] \
        + n_exp * moe
    seen = float(np.sum(np.asarray(positions, np.float64) + 1))
    latents = seen * (n_dense + n_exp) * (r + m["rope"]) * CACHE_BYTES
    if layers == "all":          # the final norm and the embedding rows
        weights += d + len(positions) * d
    return float(weights * item + latents)
