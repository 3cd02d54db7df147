"""``prefill_mfu``: how much of a join's prefill is work, in %.

The operations of the expert layers on the real prompt tokens of the
window's joins — the family's ``trunk_flops`` at positions 0 … prompt_len
− 1 of each ``serve.join.prefill`` span that starts in the window and
carries its ``prompt_len`` — over the device time of the joins' layer
loops in the window (``loops.join_loop_seconds``) × the chip's bfloat16
peak. Padding a prompt to its bucket costs time and adds no operation
here, so a later change that pads less shows as a gain. Nothing to read
without the spans' ``prompt_len`` or the loops in the trace.
"""
import numpy as np

from bench import loops, peaks, spans


def read(art):
    joins = spans.in_window(art, "serve.join.prefill")
    if not joins:
        return None
    flops = sum(art["family"].trunk_flops(
        art["conf"], np.arange(ev["args"]["prompt_len"]), layers="experts")
        for ev in joins if "prompt_len" in ev["args"])
    secs = loops.join_loop_seconds(art)
    if flops <= 0 or secs <= 0:
        return None
    return 100.0 * flops / (secs * peaks.chip_peaks(art["kind"])["flops_bf16"])
