"""``decode_hbm_roofline``: how near the decode step's expert layers run to
the chip's memory bandwidth, in %.

The bytes the window's decode steps had to read in their expert layers —
for each ``serve.step`` span that starts in the window, the family's
``decode_bytes`` of the expert layers at its busy rows' positions (their
weights, each row's cached latents) — over the device time of the decode
step's layer loop in the window (``loops.decode_loop_seconds``) × the
chip's HBM bandwidth. It counts what the served work needs, not what the
program reads: skipping the dense gather of a row's pages shows as a
gain. Nothing to read without the family's ``decode_bytes``, the
program's step spans, or the loop in the trace.
"""
import numpy as np

from bench import loops, peaks, spans


def read(art):
    fam, evs = art["family"], spans.events(art)
    if evs is None or not hasattr(fam, "decode_bytes"):
        return None
    recs, b = art["records"], art["book"]
    steps: dict = {}
    total = 0.0
    for ev in sorted((e for e in evs if e["name"] == "serve.step"
                      and e.get("ph") == "X"
                      and "error" not in e.get("args", {})),
                     key=lambda e: e["ts"]):
        pos = []
        for rid in ev["args"].get("rids", ()):
            steps[rid] = k = steps.get(rid, 0) + 1
            if rid in recs:
                pos.append(len(recs[rid].planned.prompt) + k - 1)
        if pos and b.t0 <= ev["ts"] < b.t1:
            total += fam.decode_bytes(art["conf"], np.asarray(pos),
                                      layers="experts")
    secs = loops.decode_loop_seconds(art)
    if total <= 0 or secs <= 0:
        return None
    return 100.0 * total / (secs * peaks.chip_peaks(art["kind"])["hbm_bw"])
