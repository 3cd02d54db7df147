"""Multi-layer LSTM language model — the paper's own architecture (§4:
2-layer LSTM, hidden = embedding = 200 (PTB-Small) / 1500 (PTB-Large) /
500 (NMT DE-EN decoder)).

The LSTM produces the context vectors h that L2S screens. Layout follows the
standard fused-gate formulation: gates = x·Wx + h·Wh + b, split into
(i, f, g, o).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.layers.initializers import dense_init


def lstm_init(key, cfg: ModelConfig, dtype=jnp.float32):
    d = cfg.d_model
    layers = []
    for li in range(cfg.num_layers):
        k1, k2, key = jax.random.split(key, 3)
        layers.append({
            "wx": dense_init(k1, (d, 4 * d), dtype),
            "wh": dense_init(k2, (d, 4 * d), dtype),
            "b": jnp.zeros((4 * d,), dtype)
                 .at[d:2 * d].set(1.0),  # forget-gate bias 1
        })
    return {"layers": layers}


def _cell(p, x, h, c):
    gates = x @ p["wx"] + h @ p["wh"] + p["b"]
    i, f, g, o = jnp.split(gates, 4, axis=-1)
    c_new = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
    h_new = jax.nn.sigmoid(o) * jnp.tanh(c_new)
    return h_new, c_new


def lstm_init_state(cfg: ModelConfig, batch: int, dtype=jnp.float32):
    d = cfg.d_model
    return [{"h": jnp.zeros((batch, d), dtype), "c": jnp.zeros((batch, d), dtype)}
            for _ in range(cfg.num_layers)]


def lstm_forward(params, x, cfg: ModelConfig, state=None) -> Tuple[jnp.ndarray, list]:
    """x: (B, T, d) embedded inputs → (hidden (B, T, d), final state)."""
    B, T, d = x.shape
    if state is None:
        state = lstm_init_state(cfg, B, x.dtype)
    out = x
    new_state = []
    for li, p in enumerate(params["layers"]):
        def step(carry, xt, p=p):
            h, c = carry
            h, c = _cell(p, xt, h, c)
            return (h, c), h
        (hT, cT), ys = jax.lax.scan(
            step, (state[li]["h"], state[li]["c"]), jnp.moveaxis(out, 0, 1))
        out = jnp.moveaxis(ys, 0, 1)
        new_state.append({"h": hT, "c": cT})
    return out, new_state


def lstm_forward_steps(params, x, state, n) -> Tuple[list, list]:
    """``lstm_forward`` over the first ``n`` of x's T steps, keeping every
    step's state. x: (B, T, d) embedded inputs; ``n`` may be traced, so T
    can be a padded bucket: the loop runs exactly ``n`` steps.

    Returns (final state, step states): per layer ``{"h", "c"}`` of
    (T, B, d), where entry k is the state after step k (entries from ``n``
    on are zeros). Each step is the same ``_cell`` as ``lstm_forward``'s,
    and the two agree bit for bit; a select on a step mask inside the
    loop would not, since it changes how the compiler fuses the cell."""
    out = jnp.moveaxis(x, 0, 1)
    final, steps = [], []
    for li, p in enumerate(params["layers"]):
        def body(i, carry, p=p, xs=out):
            h, c, hs, cs = carry
            h, c = _cell(p, xs[i], h, c)
            return h, c, hs.at[i].set(h), cs.at[i].set(c)
        h0 = state[li]["h"]
        zeros = jnp.zeros((x.shape[1], *h0.shape), h0.dtype)
        hT, cT, hs, cs = jax.lax.fori_loop(
            0, n, body, (h0, state[li]["c"], zeros, zeros))
        out = hs
        final.append({"h": hT, "c": cT})
        steps.append({"h": hs, "c": cs})
    return final, steps


def lstm_decode_step(params, x1, state, cfg: ModelConfig):
    """x1: (B, d) one embedded token → (h_top (B, d), new state)."""
    out = x1
    new_state = []
    for li, p in enumerate(params["layers"]):
        h, c = _cell(p, out, state[li]["h"], state[li]["c"])
        new_state.append({"h": h, "c": c})
        out = h
    return out, new_state
