"""The LSTM family: the paper's decoders (DE→EN, PTB-Large), written from
the equations alone.

    gates = x·Wx + h·Wh + b,  (i, f, g, o) = split(gates, 4)
    c' = σ(f)·c + σ(i)·tanh(g),  h' = σ(o)·tanh(c')

A family module gives the benchmark everything it needs to know of a
trunk: its weights from a key, their place in the program's parameter
tree, the teacher-forced trunk of the plain reference, the weights at a
control's precision, and the trunk's operations per token. It imports
nothing of the program; the harness finds it by the configuration's
``family`` key (``system.load_family``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import HIGHEST, float8_weights

# keys of the configuration the program's registry entry must also hold,
# beside those every family checks (``system.CHECKED_KEYS``)
checked_keys = ()


def init_weights(key, conf: dict) -> dict:
    """Random weights from one key, at PyTorch's default initialisation:
    embedding N(0, 1); every LSTM and output weight and bias uniform in
    ±1/√d; the forget-gate bias raised by 1 (Jozefowicz et al. 2015, and
    the program's own LSTM initialiser). → {"emb" (V, d), "layers":
    [{"wx" (d, 4d), "wh" (d, 4d), "b" (4d,)}], "w_out" (V, d), "b_out" (V,)}.
    """
    vocab, d, layers = conf["vocab_size"], conf["d_model"], conf["num_layers"]
    dtype = jnp.dtype(conf["dtype"])
    ks = jax.random.split(key, 3 + 3 * layers)
    a = 1.0 / float(np.sqrt(d))

    def u(k, shape):
        return jax.random.uniform(k, shape, dtype, -a, a)

    return {"emb": jax.random.normal(ks[0], (vocab, d), dtype),
            "layers": [{"wx": u(ks[3 + 3 * i], (d, 4 * d)),
                        "wh": u(ks[4 + 3 * i], (d, 4 * d)),
                        "b": u(ks[5 + 3 * i], (4 * d,)).at[d:2 * d].add(1.0)}
                       for i in range(layers)],
            "w_out": u(ks[1], (vocab, d)),
            "b_out": u(ks[2], (vocab,))}


def program_params(w, conf: dict) -> dict:
    """``init_weights``' arrays in the program's parameter layout."""
    return {"embed": {"embedding": w["emb"], "lm_head": w["w_out"],
                      "lm_bias": w["b_out"]},
            "lstm": {"layers": [dict(p) for p in w["layers"]]}}


def trunk(w, tokens, conf: dict):
    """Teacher-forced hidden states: tokens (B, T) → h (B, T, d), in the
    weights' dtype (float32 for the reference), every product at HIGHEST."""
    x = w["emb"][tokens]
    B = tokens.shape[0]
    for p in w["layers"]:
        d = p["wh"].shape[0]

        def cell(carry, xt, p=p):
            h, c = carry
            gates = jnp.matmul(xt, p["wx"], precision=HIGHEST) \
                + jnp.matmul(h, p["wh"], precision=HIGHEST) + p["b"]
            i, f, g, o = jnp.split(gates, 4, axis=-1)
            c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
            h = jax.nn.sigmoid(o) * jnp.tanh(c)
            return (h, c), h

        zero = jnp.zeros((B, d), x.dtype)
        _, ys = jax.lax.scan(cell, (zero, zero), jnp.moveaxis(x, 0, 1))
        x = jnp.moveaxis(ys, 0, 1)
    return x


def lower(w, control: str):
    """The weights at a control's precision (``reference.CONTROLS``):
    ``float8``, every weight matrix rounded to e4m3 with a per-tensor
    scale, biases kept; ``bfloat16``, every array in bfloat16."""
    if control == "bfloat16":
        return jax.tree.map(lambda a: a.astype(jnp.bfloat16), w)
    if control == "float8":
        return {"emb": float8_weights(w["emb"]),
                "layers": [{"wx": float8_weights(p["wx"]),
                            "wh": float8_weights(p["wh"]), "b": p["b"]}
                           for p in w["layers"]],
                "w_out": float8_weights(w["w_out"]), "b_out": w["b_out"]}
    raise ValueError(f"unknown control {control!r}")


def trunk_flops(conf: dict, positions) -> float:
    """Operations of the trunk for one token at each of ``positions``: each
    layer computes x·Wx + h·Wh with Wx, Wh of shape (d, 4d), 2·4d²
    multiply-adds, whatever the position."""
    d = conf["d_model"]
    return 2.0 * 8 * d * d * conf["num_layers"] * len(positions)
