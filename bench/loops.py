"""Device time of the program's scanned layer loops, found in the reduced
trace by their signature, as ``readout.FUSED_TOPK`` finds the fused kernel.

The trace's op labels do not name the program's scopes on the chip (they
read empty), but a ``lax.scan`` over a stack's layers is one ``while`` op
whose duration spans its body, and its text names its carry: the loop
counter, then the activations and the cache or pool the layers write.
For the mla_moe stack (``layers/transformer._mla_run``), layouts taken out:

  decode step at width W:  %while.66 = (s32[], f32[W,1,d], f32[L,N,P,c], ...
  join at bucket T:        %while.48 = (s32[], f32[1,T,d], f32[L,1,T,c], ...

so the loops cover the expert layers; the leading dense layers run
outside them.
"""
from __future__ import annotations

import re

from bench.trace_reduce import op_text


def _seconds(art, pattern: str) -> float:
    tr = art.get("trace")
    if tr is None:
        return 0.0
    sig = re.compile(pattern)
    return sum(s for op, s in tr["op_s"].items() if sig.search(op_text(op)))


def decode_loop_seconds(art) -> float:
    """Device seconds in the window of the decode step's layer loop at the
    stream's width."""
    c = art["conf"]
    return _seconds(art, rf"^%while[\w.]* = \(s32\[\], "
                         rf"f32\[{int(art['work']['width'])},1,"
                         rf"{c['d_model']}\], f32\[{c['num_layers']},"
                         rf"\d+,\d+,\d+\]")


def join_loop_seconds(art) -> float:
    """Device seconds in the window of the joins' layer loops, every
    bucket."""
    c = art["conf"]
    return _seconds(art, rf"^%while[\w.]* = \(s32\[\], f32\[1,(\d+),"
                         rf"{c['d_model']}\], f32\[{c['num_layers']},1,"
                         rf"\1,\d+\]")
