"""Config dataclasses: model architecture, input shapes, mesh, L2S, training.

Every assigned architecture gets one ``ModelConfig`` in ``repro/configs/<id>.py``
registered under its ``--arch`` id. ``ModelConfig.reduced()`` produces the
small CPU-smoke-test variant of the same family (≤2 layers, d_model ≤ 512,
≤4 experts) mandated by the brief. It keeps every mechanism the family has:
latent attention (MLA) keeps its split of query and key into a position-free
and a rotary part and its normalised latent, at small widths; a leading
dense layer stays first; an expert layer told which experts it holds keeps
holding a share of them (one of the four), its routing's scaling and its
shared experts.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field, replace
from typing import Optional, Tuple

# Vocab block size used by the TPU-adapted L2S candidate sets (see DESIGN §3).
V_BLK = 128


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8
    top_k: int = 2
    # capacity factor for fixed-shape dispatch (tokens per expert =
    # capacity_factor * tokens * top_k / num_experts)
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    router_jitter: float = 0.0
    # gates of the held-expert layer (below) are scaled by this after their
    # normalisation (DeepSeek-V3's routed_scaling_factor)
    routed_scale: float = 1.0
    # width of one routed expert (None: the model's d_ff) and the number of
    # shared experts every token passes through (together one SwiGLU of
    # num_shared × expert width)
    d_ff: Optional[int] = None
    num_shared: int = 0
    # this chip's share of an expert-parallel deployment: it holds experts
    # [held_offset, held_offset + held) of num_experts (num_experts // held
    # chips share each expert layer). Set for the mla_moe stack, whose
    # expert layer is the dropless held-expert layer with DeepSeek-V3
    # routing (layers/moe.py); None: every expert, the capacity-bounded
    # layer
    held: Optional[int] = None
    held_offset: int = 0


@dataclass(frozen=True)
class SSMConfig:
    """Mamba2 / SSD settings."""
    state_dim: int = 128          # N: per-channel SSM state size
    head_dim: int = 64            # P: channels per SSD head
    expand: int = 2               # inner dim = expand * d_model
    chunk: int = 256              # SSD chunk length (intra-chunk dual form)
    conv_width: int = 4           # causal depthwise conv width
    n_groups: int = 1             # B/C groups (GVA-style)


@dataclass(frozen=True)
class ModelConfig:
    name: str
    # arch family: dense | moe | mla_moe | ssm | hybrid | vlm | audio | lstm
    family: str
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None          # default d_model // num_heads
    # activations: geglu | swiglu | gelu | relu
    mlp_activation: str = "swiglu"
    # positional scheme: rope | mrope | learned | none
    positional: str = "rope"
    rope_theta: float = 10000.0
    qkv_bias: bool = False
    tie_embeddings: bool = True
    norm: str = "rmsnorm"                   # rmsnorm | layernorm
    sliding_window: Optional[int] = None    # SWA window (mixtral: 4096)
    # MoE / SSM / hybrid extras
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    # hybrid (zamba2): one shared attention block applied every k mamba layers
    hybrid_shared_period: int = 6
    # encoder-only (audio): no causal mask, no decode
    is_encoder: bool = False
    # vlm: number of vision patch embeddings prepended to text (stub frontend)
    num_patch_tokens: int = 0
    # citation for the config (paper / model card)
    source: str = ""
    # dtype for params/activations in dry-runs
    dtype: str = "bfloat16"
    # latent attention (MLA, arXiv:2405.04434 §2.1; mla_moe family): keys
    # and values come from a normalised kv_lora_rank latent; query and key
    # heads are a position-free part (qk_nope_head_dim) and a rotary part
    # (qk_rope_head_dim, one key head shared by all). The query is
    # projected directly from x (no query compression: q_lora_rank null)
    kv_lora_rank: Optional[int] = None
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # leading dense layers (MLP of width d_ff) before the expert layers
    first_k_dense: int = 0
    # RMSNorm epsilon of the mla_moe stack (other families use the norm's
    # default)
    norm_eps: float = 1e-6

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(self, "head_dim", self.d_model // max(self.num_heads, 1))
        assert self.family in ("dense", "moe", "mla_moe", "ssm", "hybrid",
                               "vlm", "audio", "lstm"), self.family
        if self.num_heads:
            assert self.num_heads % max(self.num_kv_heads, 1) == 0, (
                f"{self.name}: heads {self.num_heads} not divisible by kv {self.num_kv_heads}")

    # -- derived -----------------------------------------------------------
    @property
    def mla_latent_dim(self) -> int:
        """Width of one token's cached latent: normalised c plus the rotary
        key, shared by every head."""
        return (self.kv_lora_rank or 0) + self.qk_rope_head_dim

    # the expert settings by the names a configuration file gives them
    num_experts = property(lambda s: s.moe.num_experts if s.moe else 0)
    experts_per_token = property(lambda s: s.moe.top_k if s.moe else 0)
    expert_d_ff = property(
        lambda s: (s.moe.d_ff or s.d_ff) if s.moe else 0)
    num_shared_experts = property(lambda s: s.moe.num_shared if s.moe else 0)
    routed_scaling_factor = property(
        lambda s: s.moe.routed_scale if s.moe else 1.0)
    experts_held = property(
        lambda s: (s.moe.held or s.moe.num_experts) if s.moe else 0)
    expert_offset = property(lambda s: s.moe.held_offset if s.moe else 0)

    @property
    def q_per_kv(self) -> int:
        return self.num_heads // max(self.num_kv_heads, 1)

    @property
    def attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def supports_decode(self) -> bool:
        return not self.is_encoder

    def supports_long_context(self) -> bool:
        """True if decode over 500k context is sub-quadratic / bounded-state."""
        if self.family in ("ssm", "hybrid"):
            return True
        return self.sliding_window is not None

    def param_count(self) -> int:
        """Analytic parameter count (used for roofline MODEL_FLOPS = 6·N·D)."""
        d, ff, v, nl = self.d_model, self.d_ff, self.vocab_size, self.num_layers
        hd = self.head_dim
        n = v * d  # embedding
        if not self.tie_embeddings:
            n += v * d
        if self.family == "lstm":
            # 2-layer LSTM: per layer 4 * (in + hidden + 1) * hidden
            for li in range(nl):
                n += 4 * (d + d + 1) * d
            return n
        if self.family == "mla_moe":
            # parameters held on this chip: every layer's MLA and norms, the
            # leading dense MLPs, and per expert layer the router, its
            # correction bias, the held experts and the shared experts
            H, r = self.num_heads, self.kv_lora_rank
            attn = (d * H * (self.qk_nope_head_dim + self.qk_rope_head_dim)
                    + d * self.mla_latent_dim + r
                    + r * H * (self.qk_nope_head_dim + self.v_head_dim)
                    + H * self.v_head_dim * d + 2 * d)
            expert = 3 * d * self.expert_d_ff
            n_moe = nl - self.first_k_dense
            n += nl * attn + self.first_k_dense * 3 * d * ff + d
            n += n_moe * ((d + 1) * self.num_experts + expert * (
                self.experts_held + self.num_shared_experts))
            return n
        per_layer_attn = (
            d * self.num_heads * hd            # Wq
            + 2 * d * self.num_kv_heads * hd   # Wk, Wv
            + self.num_heads * hd * d          # Wo
        )
        act_mult = 3 if self.mlp_activation in ("swiglu", "geglu") else 2
        per_layer_mlp = act_mult * d * ff
        if self.family == "moe":
            per_layer_mlp *= self.moe.num_experts
            per_layer_mlp += d * self.moe.num_experts  # router
        if self.family in ("ssm", "hybrid"):
            s = self.ssm
            dinner = s.expand * d
            nh = dinner // s.head_dim
            per_layer_ssm = (
                d * (2 * dinner + 2 * s.n_groups * s.state_dim + nh)  # in_proj
                + dinner * d                                          # out_proj
                + s.conv_width * (dinner + 2 * s.n_groups * s.state_dim)
                + 2 * nh                                              # A_log, D
            )
            n += nl * (per_layer_ssm + 2 * d)
            if self.family == "hybrid":
                # ONE shared attention+MLP block (weights reused; Zamba trick)
                n += per_layer_attn + per_layer_mlp + 2 * d
            return n
        for _ in range(nl):
            n += per_layer_attn + per_layer_mlp + 2 * d  # + norms
        return n

    def active_param_count(self) -> int:
        """Active params per token (MoE: only top_k experts count)."""
        if self.family != "moe":
            return self.param_count()
        full = self.param_count()
        act_mult = 3 if self.mlp_activation in ("swiglu", "geglu") else 2
        expert_p = act_mult * self.d_model * self.d_ff
        inactive = (self.moe.num_experts - self.moe.top_k) * expert_p * self.num_layers
        return full - inactive

    # -- reduced smoke variant ---------------------------------------------
    def reduced(self) -> "ModelConfig":
        """Same family, tiny: ≤2 layers, d_model ≤ 512, ≤4 experts (per brief)."""
        d = min(self.d_model, 128)
        heads = min(self.num_heads, 4)
        kv = max(1, min(self.num_kv_heads, heads))
        while heads and heads % kv:
            kv -= 1
        kw = dict(
            name=self.name + "-reduced",
            num_layers=2,
            d_model=d,
            num_heads=heads,
            num_kv_heads=kv if heads else 0,
            head_dim=(d // heads) if heads else 16,
            d_ff=min(self.d_ff, 256) if self.d_ff else 0,
            vocab_size=min(self.vocab_size, 512),
            sliding_window=min(self.sliding_window, 64) if self.sliding_window else None,
            num_patch_tokens=min(self.num_patch_tokens, 8) if self.num_patch_tokens else 0,
            dtype="float32",
        )
        if self.moe is not None:
            m = self.moe
            E = min(m.num_experts, 4)
            mk = dict(num_experts=E)
            if m.held is not None:
                held = max(1, E * m.held // m.num_experts)
                mk.update(top_k=min(m.top_k, 2), held=held,
                          held_offset=min(m.held_offset, E - held))
            if m.d_ff is not None:
                mk["d_ff"] = min(m.d_ff, 64)
            kw["moe"] = replace(m, **mk)
        if self.kv_lora_rank is not None:
            kw.update(kv_lora_rank=min(self.kv_lora_rank, 32),
                      qk_nope_head_dim=min(self.qk_nope_head_dim, 16),
                      qk_rope_head_dim=min(self.qk_rope_head_dim, 8),
                      v_head_dim=min(self.v_head_dim, 16))
        if self.first_k_dense:
            kw["first_k_dense"] = 1
        if self.ssm is not None:
            kw["ssm"] = replace(self.ssm, state_dim=min(self.ssm.state_dim, 16),
                                head_dim=16, chunk=16, expand=2)
        if self.family == "hybrid":
            kw["hybrid_shared_period"] = 1
        return replace(self, **kw)


@dataclass(frozen=True)
class ShapeConfig:
    """One of the 4 assigned input shapes."""
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


INPUT_SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


@dataclass(frozen=True)
class L2SConfig:
    """Hyper-parameters of the paper's technique (Algorithm 1)."""
    num_clusters: int = 100          # r
    budget: int = 512                # B: average candidate size (words)
    top_k: int = 5                   # k used to build ground-truth label sets y
    lamb: float = 3e-4               # λ in Eq.(6) — paper value
    gamma: float = 10.0              # γ Lagrange weight — paper value
    outer_iters: int = 4             # T alternating rounds
    sgd_steps: int = 200             # SGD steps per v-update round
    lr: float = 0.05
    gumbel_temp: float = 1.0
    batch_size: int = 512
    # TPU-adapted block-candidate variant (DESIGN §3); block=1 → paper-faithful
    vocab_block: int = 1
    seed: int = 0


@dataclass(frozen=True)
class TrainConfig:
    lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1000
    weight_decay: float = 0.1
    b1: float = 0.9
    b2: float = 0.95
    grad_clip: float = 1.0
    microbatch: Optional[int] = None   # gradient accumulation (None = off)
    remat: str = "block"               # none | block  (activation checkpointing)
    loss_chunk: Optional[int] = 512    # chunked xent (avoid full B,T,V logits)


def shapes_for(cfg: ModelConfig) -> Tuple[str, ...]:
    """Which of the 4 input shapes apply to an architecture (DESIGN §5)."""
    out = ["train_4k", "prefill_32k"]
    if cfg.supports_decode:
        out.append("decode_32k")
        out.append("long_500k")  # dense archs use the swa-variant (see dryrun)
    return tuple(out)
