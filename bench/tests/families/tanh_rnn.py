"""A one-layer tanh RNN with no output bias, h' = tanh(x·W + h·U): a
family that only the tests have, to show that a new family is one file."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import HIGHEST, float8_weights

checked_keys = ()


def init_weights(key, conf: dict) -> dict:
    vocab, d = conf["vocab_size"], conf["d_model"]
    ks = jax.random.split(key, 4)
    a = 1.0 / float(np.sqrt(d))
    return {"emb": jax.random.normal(ks[0], (vocab, d), jnp.float32),
            "w": jax.random.uniform(ks[1], (d, d), jnp.float32, -a, a),
            "u": jax.random.uniform(ks[2], (d, d), jnp.float32, -a, a),
            "w_out": jax.random.uniform(ks[3], (vocab, d), jnp.float32, -a, a)}


def program_params(w, conf: dict) -> dict:
    return {"rnn": dict(w)}


def trunk(w, tokens, conf: dict):
    x = w["emb"][tokens]

    def cell(h, xt):
        h = jnp.tanh(jnp.matmul(xt, w["w"], precision=HIGHEST)
                     + jnp.matmul(h, w["u"], precision=HIGHEST))
        return h, h

    h0 = jnp.zeros((tokens.shape[0], w["u"].shape[0]), x.dtype)
    _, ys = jax.lax.scan(cell, h0, jnp.moveaxis(x, 0, 1))
    return jnp.moveaxis(ys, 0, 1)


def lower(w, control: str):
    if control == "bfloat16":
        return jax.tree.map(lambda a: a.astype(jnp.bfloat16), w)
    return jax.tree.map(float8_weights, w)


def trunk_flops(conf: dict, positions) -> float:
    return 2.0 * 2 * conf["d_model"] ** 2 * len(positions)
