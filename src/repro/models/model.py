"""Model builder: family dispatch over the shared layer substrate."""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.layers.embeddings import embed_init, embed_tokens, head_matrix, lm_logits
from repro.layers.initializers import dense_init
from repro.layers.lstm import (lstm_decode_step, lstm_forward,
                               lstm_forward_steps, lstm_init, lstm_init_state)
from repro.layers.rope import mrope_positions
from repro.layers.transformer import (stack_decode, stack_decode_paged,
                                      stack_forward, stack_init,
                                      stack_init_cache, stack_prefill)


class Model:
    """Functional model wrapper (params are plain pytrees)."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # -- init -----------------------------------------------------------------
    def init(self, rng, dtype=None) -> Dict[str, Any]:
        cfg = self.cfg
        dtype = dtype or jnp.dtype(cfg.dtype)
        k_embed, k_stack, k_extra = jax.random.split(rng, 3)
        params = {"embed": embed_init(k_embed, cfg, dtype)}
        if cfg.family == "lstm":
            params["lstm"] = lstm_init(k_stack, cfg, dtype)
        else:
            params["stack"] = stack_init(k_stack, cfg, dtype)
        if cfg.family == "vlm":
            # projector from (stub) vision embeddings to the LM width
            params["vision_proj"] = dense_init(k_extra, (cfg.d_model, cfg.d_model), dtype)
        if cfg.family == "audio":
            params["frame_proj"] = dense_init(k_extra, (cfg.d_model, cfg.d_model), dtype)
        return params

    def init_shapes(self, dtype=None):
        """Abstract params (ShapeDtypeStruct pytree) — used by the dry-run."""
        return jax.eval_shape(lambda k: self.init(k, dtype), jax.random.key(0))

    # -- forward (train / prefill) ---------------------------------------------
    def forward(self, params, batch: Dict[str, jnp.ndarray],
                window: Optional[int] = None,
                remat: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
        cfg = self.cfg
        if cfg.family == "lstm":
            x = embed_tokens(params["embed"], batch["tokens"], cfg)
            h, _ = lstm_forward(params["lstm"], x, cfg)
            return h, jnp.float32(0.0)
        if cfg.family == "audio":
            x = jnp.einsum("btd,de->bte", batch["frames"], params["frame_proj"])
            x = x + _sinusoidal(x.shape[1], cfg.d_model, x.dtype)
            positions = _text_positions(x)
            return stack_forward(params["stack"], x, cfg, positions, window,
                                 remat=remat)
        if cfg.family == "vlm":
            tok = embed_tokens(params["embed"], batch["tokens"], cfg)
            pat = jnp.einsum("bpd,de->bpe", batch["patches"], params["vision_proj"])
            x = jnp.concatenate([pat.astype(tok.dtype), tok], axis=1)
            positions = mrope_positions(x.shape[0], pat.shape[1], tok.shape[1])
            return stack_forward(params["stack"], x, cfg, positions, window,
                                 remat=remat)
        x = embed_tokens(params["embed"], batch["tokens"], cfg)
        positions = _text_positions(x)
        return stack_forward(params["stack"], x, cfg, positions, window,
                             remat=remat)

    # -- head -------------------------------------------------------------------
    def logits(self, params, h) -> jnp.ndarray:
        return lm_logits(params["embed"], h, self.cfg)

    def head_matrix(self) -> str:
        return "embedding" if self.cfg.tie_embeddings else "lm_head"

    def softmax_weights(self, params):
        """(W (V, d), b (V,)) — the matrix/bias the paper's screening targets."""
        return head_matrix(params["embed"], self.cfg), params["embed"]["lm_bias"]

    # -- decode ------------------------------------------------------------------
    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16,
                   window: Optional[int] = None):
        cfg = self.cfg
        if not cfg.supports_decode:
            raise ValueError(f"{cfg.name} is encoder-only: no decode")
        if cfg.family == "lstm":
            return {"lstm": lstm_init_state(cfg, batch, dtype)}
        return stack_init_cache(cfg, batch, max_len, dtype, window)

    def prefill(self, params, batch, cache, window: Optional[int] = None,
                resume: bool = False):
        """Forward over the prompt AND prime the decode cache.

        Returns (h (B, T, d), cache). Prompt must fit the cache (slots [0, T)).

        ``resume=True`` (LSTM family only) continues from ``cache``'s
        recurrent state instead of zeros — the paged serving path's
        prefix-cache compute skip: a scan restarted from a snapshot runs
        the identical cell sequence, so resumed prefill over a suffix is
        bit-identical to one-shot prefill over the full prompt."""
        with jax.named_scope("prefill"):
            return self._prefill(params, batch, cache, window, resume)

    def prefill_steps(self, params, tokens, state, n):
        """LSTM prefill of ``tokens`` (B, T) from recurrent ``state``, of
        which only the first ``n`` steps are real (T may be a padded
        bucket). → (final state, per-step states); see
        ``lstm_forward_steps``. The paged serving path's join reads its
        page snapshots from the per-step states."""
        if self.cfg.family != "lstm":
            raise NotImplementedError("prefill_steps is LSTM-only")
        with jax.named_scope("prefill"):
            x = embed_tokens(params["embed"], tokens, self.cfg)
            return lstm_forward_steps(params["lstm"], x, state, n)

    def _prefill(self, params, batch, cache, window, resume):
        cfg = self.cfg
        if cfg.family == "lstm":
            x = embed_tokens(params["embed"], batch["tokens"], cfg)
            h, state = lstm_forward(params["lstm"], x, cfg,
                                    state=cache["lstm"] if resume else None)
            return h, {"lstm": state}
        if resume:
            raise NotImplementedError(
                "resume prefill is LSTM-only: attention-family prefix reuse "
                "shares KV pages for storage, not prefill compute (chunked "
                "cross-attention resume is future work — see README)")
        if cfg.family == "vlm":
            tok = embed_tokens(params["embed"], batch["tokens"], cfg)
            pat = jnp.einsum("bpd,de->bpe", batch["patches"], params["vision_proj"])
            x = jnp.concatenate([pat.astype(tok.dtype), tok], axis=1)
            positions = mrope_positions(x.shape[0], pat.shape[1], tok.shape[1])
        else:
            x = embed_tokens(params["embed"], batch["tokens"], cfg)
            positions = _text_positions(x)
        return stack_prefill(params["stack"], x, cfg, positions, cache, window)

    def decode_step(self, params, token, cache, pos,
                    window: Optional[int] = None):
        """token: (B,) int32; pos: scalar absolute position, or a (B,) int32
        vector of per-row positions (continuous batching — rows decoding at
        different depths; see attn_decode). → (h (B, d), cache)."""
        cfg = self.cfg
        with jax.named_scope("decode_step"):
            x1 = embed_tokens(params["embed"], token[:, None], cfg)  # (B,1,d)
            if cfg.family == "lstm":
                h, new_state = lstm_decode_step(params["lstm"], x1[:, 0],
                                                cache["lstm"], cfg)
                return h, {"lstm": new_state}
            h, new_cache = stack_decode(params["stack"], x1, cache, pos, cfg,
                                        window)
            return h[:, 0], new_cache

    def decode_step_paged(self, params, token, pool, page_table, pos):
        """Paged decode step (attention families): K/V live in a shared
        page pool addressed through ``page_table`` instead of a per-stream
        contiguous cache. → (h (B, d), new_pool). See stack_decode_paged."""
        cfg = self.cfg
        if cfg.family == "lstm":
            raise NotImplementedError(
                "LSTM decode carries no per-token KV — paged LSTM streams "
                "use the ordinary decode_step with logical page accounting")
        with jax.named_scope("decode_step"):
            x1 = embed_tokens(params["embed"], token[:, None], cfg)  # (B,1,d)
            h, new_pool = stack_decode_paged(params["stack"], x1, pool,
                                             page_table, pos, cfg)
            return h[:, 0], new_pool


def _text_positions(x):
    return jnp.broadcast_to(
        jnp.arange(x.shape[1], dtype=jnp.int32)[None], x.shape[:2])


def _sinusoidal(T: int, d: int, dtype):
    pos = jnp.arange(T, dtype=jnp.float32)[:, None]
    i = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, 2 * i / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1).astype(dtype)[None]


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
