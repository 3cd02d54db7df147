"""Span tracing for the serving stack.

A ``Tracer`` records per-request spans (submit -> admit/queue/join ->
decode ticks -> retire), nested ``serve.*`` scopes at every layer
boundary of the serving program (tick, join, stream step, pool reclaim)
and instants into a bounded ring buffer, exportable as Chrome trace-event
JSON (loadable in ``chrome://tracing`` / Perfetto) or as JSONL.

Design constraints, in order:

* **Zero recompiles.** Everything here is host-side Python; nothing the
  tracer does may feed a traced value into jit.
* **One timeline.** The tracer reads the scheduler's injectable clock
  (``LogicalClock`` under chaos tests, ``time.perf_counter`` in real
  runs), so spans, deadlines and watchdog decisions share an axis.
* **The profiler's timeline too.** While the tracer is enabled, each
  ``scope`` also enters a ``jax.profiler.TraceAnnotation`` of the same
  name, so a profiler trace taken at the same time shows the program's
  host spans beside the device's ops, on the profiler's own clock: the
  device's idle gaps can be put down to the host code running in them.
* **Cheap when off.** ``NullTracer`` no-ops every method and advertises
  ``enabled = False`` so hot paths can skip argument construction with
  ``if tracer.enabled:``; its ``scope`` hands back one preallocated
  no-op context. The module-level ``NULL_TRACER`` singleton is the
  default everywhere a tracer is threaded through.

Scopes nest: each records one "X" span whose ``args`` carry its ``id``
and the ``parent`` id of the innermost scope open when it began (None at
the top), so a span's self time is its duration less its children's.
The tracer is single-threaded, like the scheduler that drives it.

Chrome trace-event mapping: request rows use ``tid = rid`` so every
request gets its own lane under one process; the scheduler's scopes
live on ``tid = SCHED_TID`` (-1 — request ids start at 0, so the
scheduler lane must sit outside the rid space). Durations/timestamps
are exported in microseconds as the format requires.
"""
from __future__ import annotations

import json
import time
from collections import deque
from typing import Callable, Dict, List, Optional

# Trace lane for scheduler-level (non-request) spans. Negative so it can
# never collide with a request id (rids count up from 0).
SCHED_TID = -1


def _profiler_annotation(name: str):
    import jax.profiler
    return jax.profiler.TraceAnnotation(name)


class _Scope:
    """One ``Tracer.scope``: records its span on exit (see the module
    docstring). ``args`` may grow while it is open (``Tracer.note``)."""

    __slots__ = ("_tr", "name", "cat", "tid", "args", "id", "parent",
                 "_t0", "_ann")

    def __init__(self, tracer: "Tracer", name: str, cat: str,
                 tid: Optional[int], args: dict):
        self._tr = tracer
        self.name, self.cat, self.tid, self.args = name, cat, tid, args

    def __enter__(self) -> "_Scope":
        tr = self._tr
        outer = tr._open[-1] if tr._open else None
        self.parent = None if outer is None else outer.id
        if self.tid is None:
            self.tid = SCHED_TID if outer is None else outer.tid
        tr._scopes += 1
        self.id = tr._scopes
        tr._open.append(self)
        self._ann = tr._annotate(self.name)
        self._ann.__enter__()
        self._t0 = tr.now()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        tr = self._tr
        t1 = tr.now()
        self._ann.__exit__(exc_type, exc, tb)
        tr._open.pop()
        self.args["id"] = self.id
        self.args["parent"] = self.parent
        if exc_type is not None:
            self.args["error"] = exc_type.__name__
        tr.span(self.name, self.cat, self._t0, t1, tid=self.tid,
                args=self.args)
        return False


class _NullScope:
    __slots__ = ()

    def __enter__(self) -> "_NullScope":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False


_NULL_SCOPE = _NullScope()


class Tracer:
    """Bounded ring buffer of trace events on an injectable clock.

    Events are stored as small dicts in trace-event shape (seconds
    internally; scaled to microseconds at export). When the buffer
    overflows, the oldest events are dropped — ``dropped`` reports how
    many, so exports can say so instead of silently truncating.

    ``annotate(name)`` returns the context each scope also enters: by
    default a ``jax.profiler.TraceAnnotation``, which costs about a
    microsecond and records only while a profiler trace is running."""

    enabled = True

    def __init__(self, clock: Optional[Callable[[], float]] = None,
                 capacity: int = 65536,
                 annotate: Callable[[str], object] = _profiler_annotation):
        self._clock = clock if clock is not None else time.perf_counter
        self._buf: deque = deque(maxlen=int(capacity))
        self.capacity = int(capacity)
        self.emitted = 0
        self._annotate = annotate
        self._open: List[_Scope] = []      # scopes entered, not yet exited
        self._scopes = 0                   # ids handed out so far

    # -- recording -----------------------------------------------------------
    def now(self) -> float:
        """Current time on the tracer's clock (seconds)."""
        return float(self._clock())

    def span(self, name: str, cat: str, t0: float,
             t1: Optional[float] = None, tid: int = SCHED_TID,
             args: Optional[dict] = None) -> None:
        """A complete ("X") span from ``t0`` to ``t1`` (default: now)."""
        if t1 is None:
            t1 = self.now()
        ev = {"name": name, "cat": cat, "ph": "X", "ts": float(t0),
              "dur": max(0.0, float(t1) - float(t0)), "tid": int(tid)}
        if args:
            ev["args"] = dict(args)
        self._buf.append(ev)
        self.emitted += 1

    def instant(self, name: str, cat: str, tid: int = SCHED_TID,
                args: Optional[dict] = None,
                t: Optional[float] = None) -> None:
        """A point-in-time ("i") marker (admit/reject/fault/retry...)."""
        ev = {"name": name, "cat": cat, "ph": "i", "s": "t",
              "ts": self.now() if t is None else float(t), "tid": int(tid)}
        if args:
            ev["args"] = dict(args)
        self._buf.append(ev)
        self.emitted += 1

    def scope(self, name: str, cat: str, tid: Optional[int] = None,
              **args) -> _Scope:
        """Context manager: one "X" span from entry to exit, child of the
        innermost scope open at entry, on lane ``tid`` (default: the
        enclosing scope's lane, else ``SCHED_TID``). A scope left by an
        exception still records, with the exception's type as
        ``args["error"]``."""
        return _Scope(self, name, cat, tid, args)

    def note(self, **args) -> None:
        """Add ``args`` to the innermost open scope (for values known only
        once its work is under way: a radix hit, a chunk count)."""
        if self._open:
            self._open[-1].args.update(args)

    # -- inspection / export -------------------------------------------------
    @property
    def dropped(self) -> int:
        """Events lost to ring-buffer overflow."""
        return self.emitted - len(self._buf)

    def events(self) -> List[dict]:
        """The retained events, oldest first (internal units: seconds)."""
        return list(self._buf)

    def clear(self) -> None:
        self._buf.clear()
        self.emitted = 0

    def chrome_trace(self) -> dict:
        """The full Chrome trace-event JSON object (timestamps in µs)."""
        pid = 1
        events = []
        tids: Dict[int, bool] = {}
        for ev in self._buf:
            out = dict(ev)
            out["pid"] = pid
            out["ts"] = ev["ts"] * 1e6
            if "dur" in out:
                out["dur"] = ev["dur"] * 1e6
            events.append(out)
            tids[ev["tid"]] = True
        # thread_name metadata makes Perfetto label the lanes usefully
        meta = []
        for tid in sorted(tids):
            name = "scheduler" if tid == SCHED_TID else f"request {tid}"
            meta.append({"name": "thread_name", "ph": "M", "pid": pid,
                         "tid": tid, "args": {"name": name}})
        return {
            "traceEvents": meta + events,
            "displayTimeUnit": "ms",
            "otherData": {"emitted": self.emitted, "dropped": self.dropped},
        }

    def export_chrome(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
            f.write("\n")
        return path

    def export_jsonl(self, path: str) -> str:
        """One retained event per line (µs timestamps, same shape as the
        ``traceEvents`` entries, no metadata rows)."""
        with open(path, "w") as f:
            for ev in self._buf:
                out = dict(ev)
                out["pid"] = 1
                out["ts"] = ev["ts"] * 1e6
                if "dur" in out:
                    out["dur"] = ev["dur"] * 1e6
                f.write(json.dumps(out) + "\n")
        return path

    def __repr__(self) -> str:      # pragma: no cover - debug aid
        return (f"Tracer(events={len(self._buf)}, emitted={self.emitted}, "
                f"dropped={self.dropped})")


class NullTracer:
    """Disabled tracer: every method is a no-op and ``enabled`` is False,
    so instrumented hot paths cost one attribute read when tracing is
    off. Export methods still work (they write an empty trace)."""

    enabled = False
    emitted = 0
    dropped = 0
    capacity = 0

    def now(self) -> float:
        return 0.0

    def span(self, *a, **k) -> None:
        pass

    def instant(self, *a, **k) -> None:
        pass

    def scope(self, name: str, cat: str, tid: Optional[int] = None,
              **args) -> _NullScope:
        return _NULL_SCOPE

    def note(self, **args) -> None:
        pass

    def events(self) -> List[dict]:
        return []

    def clear(self) -> None:
        pass

    def chrome_trace(self) -> dict:
        return {"traceEvents": [], "displayTimeUnit": "ms",
                "otherData": {"emitted": 0, "dropped": 0}}

    def export_chrome(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
        return path

    def export_jsonl(self, path: str) -> str:
        open(path, "w").close()
        return path


#: Shared disabled tracer — the default for every instrumented surface.
NULL_TRACER = NullTracer()
