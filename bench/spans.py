"""What the readers of the program's own spans share: the spans of the
window, parent and child, self time, and the gaps between one request's
tokens.

The program's tracer (``repro.serving.observe.Tracer``, on the harness's
clock) records each ``serve.*`` scope as one "X" event, in seconds, whose
``args`` carry its ``id`` and its ``parent``'s id; a scope cut short by an
exception carries ``args["error"]``. The harness hands every event over
in ``art["tracer_events"]``. A program without these scopes leaves the
readers nothing to read, and they return None.

The readers report only beside a device trace (``art["trace"]``): they
split the host's time from the device's, and a run without a device's
trace (on the CPU) gives neither a device nor a time worth reporting.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, Iterable, List, Optional

import numpy as np


def events(art) -> Optional[list]:
    """The program tracer's events; None without them or a device trace."""
    if art.get("trace") is None:
        return None
    return art.get("tracer_events")


def in_window(art, name: str) -> Optional[List[dict]]:
    """Complete ``name`` spans that start in the window."""
    evs = events(art)
    if evs is None:
        return None
    b = art["book"]
    return [ev for ev in evs if ev["name"] == name and ev.get("ph") == "X"
            and "id" in ev.get("args", {}) and "error" not in ev["args"]
            and b.t0 <= ev["ts"] < b.t1]


def children(evs: Iterable[dict]) -> Dict[int, List[dict]]:
    """{span id: its direct child spans}."""
    out: Dict[int, List[dict]] = defaultdict(list)
    for ev in evs:
        parent = ev.get("args", {}).get("parent")
        if ev.get("ph") == "X" and parent is not None:
            out[parent].append(ev)
    return out


def mean_less_children_ms(art, name: str, less: Iterable[str]
                          ) -> Optional[float]:
    """Mean over the window's ``name`` spans of the duration less that of
    their direct children named in ``less``, in ms."""
    spans = in_window(art, name)
    if not spans:
        return None
    less = set(less)
    kids = children(events(art))
    own = [ev["dur"] - sum(c["dur"] for c in kids[ev["args"]["id"]]
                           if c["name"] in less) for ev in spans]
    return 1e3 * float(np.mean(own))


def token_gaps_s(art) -> Optional[List[float]]:
    """Gaps between consecutive tokens of one request whose later token
    falls in the window, in s. A request's first token is its ``join``
    instant; each later one is the end of a ``serve.step`` whose ``rids``
    hold it."""
    evs = events(art)
    if evs is None:
        return None
    times: Dict[int, List[float]] = defaultdict(list)
    for ev in evs:
        if ev["name"] == "join" and ev.get("ph") == "i":
            times[ev["tid"]].append(ev["ts"])
        elif ev["name"] == "serve.step" and ev.get("ph") == "X" \
                and "error" not in ev.get("args", {}):
            end = ev["ts"] + ev["dur"]
            for rid in ev["args"].get("rids", ()):
                times[rid].append(end)
    b = art["book"]
    gaps = []
    for ts in times.values():
        ts.sort()
        gaps += [t - s for s, t in zip(ts, ts[1:]) if b.t0 <= t < b.t1]
    return gaps
