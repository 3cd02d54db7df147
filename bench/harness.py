"""One run of one cell: set-up, a measured window, the drain, the check.

``run(args)`` is the whole of ``bench/run.py`` after the argument parse:

1. set-up (``setup_s``): weights from the seed, the screen fit, a warm-up
   that compiles every shape the cell uses, then the cell's own traffic
   for ``ramp_s`` seconds;
2. the window: ``--seconds`` of the cell's traffic through
   ``ContinuousScheduler.submit`` / ``.step``, with compiles counted;
3. the drain: no new requests; those due in the window finish or fail;
4. device memory is read, the program's state freed, and the reference
   checks a seeded sample of the finished requests (``check.py``);
5. one JSON line: end-to-end metrics (``--trace 0``) or the cell's
   per-layer metrics from ``bench/metrics/<name>.py`` (``--trace 1``).
"""
from __future__ import annotations

import importlib.util
import json
import math
import sys
import tempfile
import time
from contextlib import contextmanager, nullcontext
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from bench.traffic import ClosedPlan, LatencyBook, Record

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
METRICS_DIR = BENCH / "metrics"


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class CompileClock:
    """Seconds JAX spends producing executables (compiling, or loading one
    from the persistent cache), and how many came from that cache."""
    BACKEND = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.secs, self.n, self.hits = 0.0, 0, 0

    def on_duration(self, event, duration, **_):
        if event == self.BACKEND:
            self.secs += duration
            self.n += 1

    def on_event(self, event, **_):
        if event == self.HIT:
            self.hits += 1

    @contextmanager
    def listening(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)
        try:
            yield self
        finally:
            jax.monitoring.unregister_event_duration_listener(self.on_duration)
            jax.monitoring.unregister_event_listener(self.on_event)


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell_entry(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                   f"(known: {[w['name'] for w in bench['workloads']]})")


def reports(metric: dict, cell: str, e2e_of_cell: Optional[set] = None) -> bool:
    """Whether ``cell`` reports ``metric``: listed in its ``workloads``, or
    with no such key, every cell (for a per-layer metric: every cell that
    reports the end-to-end metric it moves)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    if e2e_of_cell is not None and "moves" in metric:
        return metric["moves"] in e2e_of_cell
    return True


def load_reader(name: str) -> Callable:
    """``bench/metrics/<name>.py``'s ``read(artefacts)``."""
    path = METRICS_DIR / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def window_tokens_per_s(win: dict) -> float:
    """Tokens the server emitted inside the window (joins' first tokens and
    decode steps' tokens, of every request) over the window's length, both
    read between the same two ticks: all the work and all the time of the
    window, so a request that straddles an edge counts only for what it
    emitted inside."""
    return (win["tokens1"] - win["tokens0"]) / (win["t_close"] - win["t_open"])


# -- the measured loop ------------------------------------------------------

class Driver:
    """Offers the cell's planned traffic to a scheduler on the harness's
    clock and keeps the due-time book. Single-threaded: submissions happen
    between ticks, so a long tick makes the generator late, and the
    latency of every request timed from its due time shows it.

    Open loop (a list of planned requests): each is submitted once its due
    time has come. Closed loop (a ``traffic.ClosedPlan``): every client
    keeps one request outstanding, submitting its next in the tick after
    its last one finished, with no think time; a request is due when it is
    submitted. Either way nothing is submitted once the window has closed.
    """

    def __init__(self, system, sched, planned, seconds: float, ramp_s: float,
                 drain_s: float, clock=time.perf_counter,
                 sleep=time.sleep, annotate: Optional[Callable] = None,
                 on_open: Optional[Callable] = None,
                 on_close: Optional[Callable] = None):
        self.system, self.sched = system, sched
        self.clock, self.sleep = clock, sleep
        self.planned = planned
        self.seconds, self.ramp_s, self.drain_s = seconds, ramp_s, drain_s
        self.ann = annotate or (lambda name: nullcontext())
        self.on_open, self.on_close = on_open, on_close
        self.records: Dict[int, object] = {}
        self.by_req: Dict[int, int] = {}
        self.ticks: List[tuple] = []
        self.submits: List[tuple] = []
        self.book: Optional[LatencyBook] = None
        # closed loop: clients ready to submit, and each one's next request
        self.ready: Optional[List[int]] = None
        self.sent: List[int] = []

    def _submit(self, p, due: float):
        req = self.system.request(p)
        t = self.clock()
        with self.ann("bench.submit"):
            rid = self.sched.submit(req)
        rec = Record(planned=p, due=due, submitted=self.clock())
        self.submits.append((t, rec.submitted))
        self.records[rid] = rec
        self.by_req[id(req)] = rid
        self.book.add(rec)
        return rid

    def _finish(self, res, t: float):
        from repro.serving import ServeResult
        rid = self.by_req.pop(id(res.request))
        rec = self.records[rid]
        rec.done = t
        if self.ready is not None:
            self.ready.append(self.planned.client_of(rec.planned))
        if not isinstance(res, ServeResult):
            rec.failed = f"refused ({res.stage})"
        elif res.head != self.system.head:
            rec.failed = f"fell back to {res.head}"
        elif rid in self.sched.fault_rids:
            rec.failed = "faulted"
        else:
            rec.tokens = np.asarray(res.tokens)

    def run(self):
        clock, sched = self.clock, self.sched
        t_base = clock()
        t0 = t_base + self.ramp_s
        t1 = t0 + self.seconds
        self.book = LatencyBook(t0, t1)
        opened = closed_window = False
        closed_loop = isinstance(self.planned, ClosedPlan)
        if closed_loop:
            self.ready = list(range(self.planned.clients))
            self.sent = [0] * self.planned.clients
            todo = []
        else:
            todo = sorted(self.planned, key=lambda p: p.due)
        ptr = 0
        while True:
            now = clock()
            if not opened and now >= t0:
                opened = True
                if self.on_open:
                    self.on_open()
            if opened and not closed_window and now >= t1:
                closed_window = True
                if self.on_close:
                    self.on_close()
            if closed_loop and not closed_window:
                ready, self.ready = self.ready, []
                for c in ready:
                    p = self.planned.request(c, self.sent[c])
                    self.sent[c] += 1
                    self._submit(p, clock())
            while ptr < len(todo) and t_base + todo[ptr].due <= now \
                    and t_base + todo[ptr].due < t1:
                self._submit(todo[ptr], t_base + todo[ptr].due)
                ptr += 1
            if sched.busy:
                with self.ann("bench.step"):
                    ts = clock()
                    sched.step()
                    te = clock()
                self.ticks.append((ts, te))
                for res in sched.pop_results():
                    self._finish(res, te)
                if now >= t1 + self.drain_s:
                    break
                continue
            if closed_window:
                break
            wake = min(t_base + todo[ptr].due, t1) if ptr < len(todo) else t1
            with self.ann("bench.wait"):
                while clock() < wake:
                    self.sleep(min(max(wake - clock(), 0.0), 1e-3))
        for rid, rec in self.records.items():
            if rec.done is None:
                rec.failed = "unfinished"
        return self.book

    def window_ticks(self):
        b = self.book
        return [(s, e) for s, e in self.ticks if b.t0 <= s < b.t1]

    def longest(self, n: int = 5) -> Dict[str, list]:
        """The ``n`` longest ticks and submissions of the window, each as
        (seconds after the window opened, seconds it took)."""
        b = self.book
        out = {}
        for name, spans in (("ticks", self.ticks), ("submits", self.submits)):
            inside = [(s - b.t0, e - s) for s, e in spans if b.t0 <= s < b.t1]
            out[name] = [(round(a, 3), round(d, 4)) for a, d in
                         sorted(inside, key=lambda x: -x[1])[:n]]
        return out


class GcClock:
    """Pauses of Python's garbage collector, as (generation, seconds)."""

    def __init__(self):
        self.t: Optional[float] = None
        self.pauses: List[tuple] = []

    def __call__(self, phase, info):
        if phase == "start":
            self.t = time.perf_counter()
        elif self.t is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self.t))
            self.t = None

    @contextmanager
    def listening(self):
        import gc
        gc.callbacks.append(self)
        try:
            yield self
        finally:
            gc.callbacks.remove(self)

    def summary(self) -> str:
        if not self.pauses:
            return "none"
        longest = max(self.pauses, key=lambda p: p[1])
        return (f"{len(self.pauses)} pauses, {sum(d for _, d in self.pauses):.3f}"
                f" s in all, longest {longest[1]:.3f} s (generation "
                f"{longest[0]})")


# -- the run ------------------------------------------------------------------

def device_info(chips: int, require_accelerator: bool):
    """(platform, kind, count); None when the run must not proceed."""
    import jax
    devs = jax.devices()
    dev = devs[0]
    if require_accelerator and dev.platform != "tpu":
        log(f"no TPU: JAX reports platform {dev.platform!r}; this benchmark "
            f"measures the chip and never falls back to the CPU")
        return None
    if require_accelerator and len(devs) < chips:
        log(f"the cell needs {chips} chips, JAX reports {len(devs)}")
        return None
    return dev.platform, dev.device_kind, len(devs)


def memory_peak_bytes() -> Optional[int]:
    import jax
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run(args, t_start: float, require_accelerator: bool = True,
        bench: Optional[dict] = None, conf: Optional[dict] = None,
        work: Optional[dict] = None, out=sys.stdout) -> int:
    """Run one cell once. ``bench``/``conf``/``work`` override the files
    (tests); returns the exit code."""
    from bench import check, system, traffic

    bench = bench or load_benchmark()
    entry = cell_entry(bench, args.workload)
    work = work or system.load_workload(args.workload)
    conf = conf or system.load_config(entry["config"])
    if work["config"] != entry["config"]:
        raise ValueError(f"{args.workload}: workload file names config "
                         f"{work['config']!r}, BENCHMARK.json "
                         f"{entry['config']!r}")
    info = device_info(int(entry["chips"]), require_accelerator)
    if info is None:
        return 1
    import jax
    from repro.serving import Tracer
    from repro.utils.timing import LatencyTracker
    platform, kind, count = info
    log(f"device: {kind} ({platform}, {count} devices); cell "
        f"{args.workload}: {conf['name']} on {work['head']}, seed {args.seed}")

    clock = CompileClock()
    with clock.listening():
        sysm = system.System(conf, work, args.seed, log=log)
        t = time.perf_counter()
        n_warm = sysm.warm()
        log(f"warm-up: {n_warm} requests in {time.perf_counter() - t:.2f} s; "
            f"{clock.n} executables so far ({clock.hits} from the cache, "
            f"{clock.secs:.2f} s)")
        planned = traffic.plan(work, sysm.corpus, args.seed, args.seconds)
        tracer = Tracer(clock=time.perf_counter, capacity=1 << 20) \
            if args.trace else None
        sched = sysm.scheduler(tracer=tracer)
        win: Dict[str, object] = {}

        def on_open():
            win["t_open"] = time.perf_counter()
            win["setup_s"] = win["t_open"] - t_start
            win["compiles0"] = clock.n
            win["tokens0"] = sched.stats.tokens
            sched.stats.queue_wait = LatencyTracker(1 << 20)

        def on_close():
            win["t_close"] = time.perf_counter()
            win["compiles1"] = clock.n
            win["tokens1"] = sched.stats.tokens
            win["queue_wait_p95_s"] = sched.stats.queue_wait.p95
            win["queue_wait_n"] = len(sched.stats.queue_wait)
            sched.stats.queue_wait = LatencyTracker()

        annotate = None
        trace_dir = None
        if args.trace:
            import jax.profiler
            annotate = jax.profiler.TraceAnnotation
            trace_dir = args.trace_dir or tempfile.mkdtemp(prefix="bench-trace-")
        drv = Driver(sysm, sched, planned, args.seconds, work["ramp_s"],
                     work["drain_s"], annotate=annotate, on_open=on_open,
                     on_close=on_close)
        gc_clock = GcClock()
        if args.trace:
            # device ops and the harness's own annotations; no runtime or
            # Python host events and no HLO protos (they make the file large)
            opts = jax.profiler.ProfileOptions()
            opts.host_tracer_level = 1
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation("bench.run"), \
                        gc_clock.listening():
                    book = drv.run()
            finally:
                t = time.perf_counter()
                jax.profiler.stop_trace()
                log(f"profiler stopped in {time.perf_counter() - t:.1f} s")
        else:
            with gc_clock.listening():
                book = drv.run()
    t_drained = time.perf_counter()
    due = book.due_in_window()
    failed = [r for r in due if r.failed]
    log(f"window: {len(due)} requests due, {len(book.completed_in_window())} "
        f"completed in it, {len(failed)} failed; drain ended "
        f"{t_drained - book.t1:.2f} s after the window")
    reasons: Dict[str, int] = {}
    for r in failed:
        reasons[r.failed] = reasons.get(r.failed, 0) + 1
    if reasons:
        log(f"failed: {reasons}")
    log(f"compiles inside the window: "
        f"{win.get('compiles1', clock.n) - win['compiles0']}")
    log(f"generator lateness: {book.lateness()}")
    log(f"longest in the window: {drv.longest()}")
    log(f"garbage collector while driving: {gc_clock.summary()}")
    mem = memory_peak_bytes()

    lbar = None
    if sysm.screen_host is not None:
        from bench import work as work_mod
        lbar = work_mod.mean_candidate_words(sysm.screen_host["cand_len"],
                                             sysm.screen_host["block"])
    art = {"args": args, "work": work, "conf": conf, "kind": kind,
           "chips": count, "book": book, "t_drained": t_drained,
           "ticks": drv.window_ticks(),
           "all_ticks": list(drv.ticks),
           "records": drv.records, "win": win, "lbar": lbar,
           "family": sysm.family,
           "screen_r": None if sysm.screen is None else int(sysm.screen.r),
           "tracer_events": tracer.events() if tracer is not None else None,
           "trace": None}
    sched = drv = None
    sysm.free()

    verdict = check.run_check(conf, work, args.seed, book, sysm.screen_host,
                              log=log)
    metrics: Dict[str, dict] = {}
    device = {"platform": platform, "kind": kind, "count": count,
              "memory_peak_bytes": mem}
    breakdown = None
    cell = args.workload
    e2e = [m for m in bench["end_to_end"] if reports(m, cell)]
    if not args.trace:
        values = {"setup_s": win["setup_s"],
                  "tokens_per_s": window_tokens_per_s(win)}
        for m in e2e:
            v = values.get(m["name"])
            if v is None or not math.isfinite(v):
                log(f"end-to-end metric {m['name']} has no value")
                continue
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        from bench import trace_reduce
        t = time.perf_counter()
        tr = trace_reduce.reduce_dir(trace_dir, book.t0, book.t1,
                                     art["all_ticks"], art["ticks"], log=log)
        log(f"trace read in {time.perf_counter() - t:.1f} s")
        art["trace"] = tr
        if tr is not None:
            device["busy_s"] = tr["busy_s"]
            device["window_s"] = tr["window_s"]
            breakdown = tr["breakdown"]
            from bench import readout
            log(f"fused top-k kernel calls by batch rows (calls, device s): "
                f"{readout.fused_topk_calls(art)}; ticks in the window: "
                f"{len(art['ticks'])}; tokens: {readout.output_tokens(art)}; "
                f"joins: {len(readout.joins_in_window(art) or [])}")
        names = {m["name"] for m in e2e}
        for m in bench["per_layer"]:
            if not reports(m, cell, names):
                continue
            v = load_reader(m["name"])(art)
            if v is None:
                log(f"per-layer metric {m['name']}: nothing to read")
                continue
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        if not args.trace_dir:
            import shutil
            shutil.rmtree(trace_dir, ignore_errors=True)
    result = {"correct": verdict.correct, "attempted": len(due),
              "failed": len(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = verdict.compared
    log(f"run took {time.perf_counter() - t_start:.1f} s")
    for line in verdict.lines():
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), file=out, flush=True)
    return 0
