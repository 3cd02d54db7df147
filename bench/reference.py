"""The plain reference: a model's trunk from its family module
(``bench/families/<family>.py``) and both head semantics, written from the
equations alone.

    logits = h·Woutᵀ + bout

Exact head: the served token is the arg-max of the logits over the whole
vocabulary. Screened head (L2S, paper Eq. 2): the context routes to the
cluster t = argmax_t v_t·h, and the served token is the arg-max of the
logits over that cluster's candidate words.

Nothing here imports the program. The weights are regenerated from the
seed by the family's ``init_weights`` (the same function the harness used
to make the weights it handed the program); the fitted screen (v,
candidate blocks) is the served model's own parameter set and is copied
to the host at set-up. Every matrix product runs at ``Precision.HIGHEST``
so that a TPU does not round its float32 operands to bfloat16.

The controls (``CONTROLS``) are the same model at a lower precision, put in
the program's place: ``float8``, every weight matrix rounded to float8
e4m3 (3 mantissa bits) with a per-tensor scale, activations and state in
float32; ``bfloat16``, weights, activations and state in bfloat16. The
family's ``lower`` rounds its weights; the route vectors are rounded here.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST

CONTROLS = ("float8", "bfloat16")

# A chunk of the head's rows holds at most this many bytes of float32
# logits, (rows, V+1). Both LSTM cells' blocks fit in one chunk (DE→EN 307
# MB, PTB-Large 92 MB), so their arithmetic is the unchunked one; at a
# vocabulary of 2·10⁵ a chunk is 670 rows, and a chunk's logits, the
# control's beside them and the candidate gathers stay a few GB, inside
# one chip's 16 GB however long the block's sequences are.
HEAD_CHUNK_BYTES = 512 * 2 ** 20


def chunk_rows(vocab: int, chunk_bytes: int = HEAD_CHUNK_BYTES) -> int:
    """Rows of (V+1) float32 logits that fit in ``chunk_bytes``."""
    return max(1, chunk_bytes // (4 * (int(vocab) + 1)))


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


# float8 e4m3 with a per-tensor scale: the largest magnitude maps to the
# largest finite value of a 4-bit exponent, 3-bit mantissa format
E4M3_MAX = 240.0


def float8_weights(a):
    """``a`` rounded as float8 e4m3 with a per-tensor scale (float32)."""
    scale = jnp.max(jnp.abs(a)) / E4M3_MAX
    q = jax.lax.reduce_precision(a / scale, exponent_bits=4, mantissa_bits=3)
    return q * scale


def lower_route(v, control: str):
    """The route vectors at the control's precision."""
    if control == "bfloat16":
        return v.astype(jnp.bfloat16)
    if control == "float8":
        return float8_weights(v)
    raise ValueError(f"unknown control {control!r} (known: {CONTROLS})")


def output_logits(w, h):
    """h·Woutᵀ, plus bout where the model has an output bias."""
    z = _mm(h, w["w_out"].T)
    return z + w["b_out"] if "b_out" in w else z


def _head(w, v, cand_words, h):
    """Logits (N, V+1) with a −inf column for padded candidate slots, route
    scores (N, r), and each cluster's best candidate logit (N, r)."""
    z = output_logits(w, h)
    z = jnp.concatenate([z, jnp.full((z.shape[0], 1), -jnp.inf, z.dtype)], 1)
    s = _mm(h, v.T)
    best_c = jax.lax.map(lambda words: jnp.max(z[:, words], axis=-1),
                         cand_words).T
    return z, s, best_c


def _rows(fn, xs, rows: int):
    """``fn`` over chunks of at most ``rows`` leading rows of the arrays
    ``xs``, its outputs joined again; one call when they fit in one."""
    n = xs[0].shape[0]
    if n <= rows:
        return fn(*xs)
    k = -(-n // rows)
    pad = [jnp.pad(x, [(0, k * rows - n)] + [(0, 0)] * (x.ndim - 1))
           .reshape((k, rows) + x.shape[1:]) for x in xs]
    out = jax.lax.map(lambda c: fn(*c), pad)
    return jax.tree.map(lambda a: a.reshape((k * rows,) + a.shape[2:])[:n],
                        out)


def _block(trunk, lower, w, v, cand_words, tokens, targets, screened: bool,
           control: Optional[str], rows: int):
    """One block of sequences. → dict of per-position arrays (B·T,)."""
    f32 = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    h = trunk(f32, tokens).reshape(-1, f32["w_out"].shape[1])
    v32 = v.astype(jnp.float32)
    tgt = targets.reshape(-1)
    if control:
        low, vl = lower(w, control), lower_route(v, control)
        hl = trunk(low, tokens).reshape(h.shape)

    def head(h, tgt, *hl_chunk):
        z, s, best_c = _head(f32, v32, cand_words, h)
        out = {"zmax": jnp.max(z, -1), "zarg": jnp.argmax(z, -1),
               "z_served": jnp.take_along_axis(z, tgt[:, None], 1)[:, 0],
               "s": s, "best_c": best_c}
        if control:
            zl, sl, _ = _head(low, vl, cand_words, hl_chunk[0])
            if screened:
                t = jnp.argmax(sl, -1)
                words = cand_words[t]                          # (N, Cw)
                pick = jnp.argmax(jnp.take_along_axis(zl, words, 1), -1)
                tok = jnp.take_along_axis(words, pick[:, None], 1)[:, 0]
            else:
                tok = jnp.argmax(zl[:, :-1], -1)
            out["ctl_tok"] = tok
            out["z_ctl"] = jnp.take_along_axis(z, tok[:, None], 1)[:, 0]
        return out

    return _rows(head, (h, tgt) + ((hl,) if control else ()), rows)


def candidate_words(cand_idx: np.ndarray, cand_len: np.ndarray, block: int,
                    vocab: int) -> np.ndarray:
    """(r, C_max·block) word ids of each cluster's candidate blocks; padded
    slots and words past the vocabulary point at ``vocab`` (the −inf
    column)."""
    r, c_max = cand_idx.shape
    lane = np.arange(block)
    words = cand_idx[:, :, None].astype(np.int64) * block + lane
    valid = np.arange(c_max)[None, :, None] < cand_len[:, None, None]
    words = np.where(valid & (words < vocab), words, vocab)
    return words.reshape(r, -1).astype(np.int32)


class Reference:
    """Teacher-forced reference over served requests.

    ``family``: the configuration's family module; ``weights``: its
    ``init_weights``' dict; ``screen``: None for the exact head, else host
    copies {"v", "cand_idx", "cand_len", "block"}. The trunk runs over a
    block of ``block_rows`` whole sequences at a time, the head over chunks
    of at most ``chunk_rows(vocab, chunk_bytes)`` of its positions."""

    def __init__(self, family, conf: dict, weights, screen, vocab: int,
                 block_rows: int = 32, seq_len: int = 0,
                 chunk_bytes: int = HEAD_CHUNK_BYTES):
        self.w = weights
        self.vocab = int(vocab)
        self.block_rows = int(block_rows)
        self.seq_len = int(seq_len)
        self.rows = chunk_rows(self.vocab, chunk_bytes)
        self._block = jax.jit(
            partial(_block, partial(family.trunk, conf=conf), family.lower),
            static_argnames=("screened", "control", "rows"))
        self.screened = screen is not None
        if self.screened:
            self.v = jnp.asarray(screen["v"], jnp.float32)
            self.words = candidate_words(np.asarray(screen["cand_idx"]),
                                         np.asarray(screen["cand_len"]),
                                         int(screen["block"]), self.vocab)
        else:
            d = weights["w_out"].shape[1]
            self.v = jnp.zeros((1, d), jnp.float32)
            self.words = np.full((1, 1), self.vocab, np.int32)
        self.member = np.zeros((self.words.shape[0], self.vocab + 1), bool)
        self.member[np.arange(self.words.shape[0])[:, None], self.words] = True
        self.member[:, self.vocab] = False

    def run(self, prompts: Sequence[np.ndarray], served: Sequence[np.ndarray],
            control: Optional[str] = None) -> Dict[str, np.ndarray]:
        """Per checked position (every served token of every request):
        reference quantities, and the pick of the control named by
        ``control`` (one of ``CONTROLS``)."""
        seqs, tgts, masks = [], [], []
        for p, t in zip(prompts, served):
            p, t = np.asarray(p), np.asarray(t)
            x = np.concatenate([p, t[:-1]])
            y = np.zeros_like(x)
            m = np.zeros(len(x), bool)
            y[len(p) - 1:] = t
            m[len(p) - 1:] = True
            seqs.append(x), tgts.append(y), masks.append(m)
        T = max(self.seq_len, max(len(s) for s in seqs))
        cw = jnp.asarray(self.words)
        parts: List[Dict[str, np.ndarray]] = []
        for i in range(0, len(seqs), self.block_rows):
            blk = range(i, min(i + self.block_rows, len(seqs)))
            tok = np.zeros((self.block_rows, T), np.int32)
            tgt = np.zeros((self.block_rows, T), np.int32)
            msk = np.zeros((self.block_rows, T), bool)
            for j, k in enumerate(blk):
                n = len(seqs[k])
                tok[j, :n], tgt[j, :n], msk[j, :n] = seqs[k], tgts[k], masks[k]
            out = self._block(self.w, self.v, cw, jnp.asarray(tok),
                              jnp.asarray(tgt), screened=self.screened,
                              control=control, rows=self.rows)
            keep = msk.reshape(-1)
            part = {k: np.asarray(a)[keep] for k, a in out.items()}
            part["served"] = tgt.reshape(-1)[keep]
            parts.append(part)
        return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}

    def gaps(self, ref: Dict[str, np.ndarray], tokens: np.ndarray,
             z_tok: np.ndarray) -> Dict[str, np.ndarray]:
        """For chosen ``tokens`` (with their reference logits ``z_tok``):
        ``token_gap``, how far each token's logit lies below the best the
        reference allows. Screened head: a token is the head's answer for
        a cluster c that holds it when c routes first and the token is
        c's best candidate; c's ``route_gap`` is the reference's best route
        score minus c's, its ``token_gap`` c's best candidate logit minus
        the token's. Both are read at the cluster holding the token whose
        larger gap is least, so a near-tie in routing that sends the
        context to another cluster holding the token reads as the small
        route gap it is (inf on both when no cluster holds the token)."""
        if not self.screened:
            return {"token_gap": ref["zmax"] - z_tok}
        s, best_c = ref["s"], ref["best_c"]
        n = len(tokens)
        member = self.member[:, tokens].T                  # (n, r)
        rg = s.max(axis=1, keepdims=True) - s              # (n, r)
        tg = best_c - z_tok[:, None]
        worst = np.where(member, np.maximum(rg, tg), np.inf)
        c = np.argmin(worst, axis=1)
        rows = np.arange(n)
        has = member.any(axis=1)
        route = np.where(has, rg[rows, c], np.inf)
        token = np.where(has, tg[rows, c], np.inf)
        return {"token_gap": token, "route_gap": route}
