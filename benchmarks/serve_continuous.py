"""Continuous-batching heavy-traffic harness: Poisson arrivals, mixed
heads and tiers, admission control — the open-loop load test the paper's
deployment story needs.

Unlike serve_mixed.py (one pre-assembled batch through ``serve_batch``),
this drives ``ContinuousScheduler`` the way live traffic would: request
arrival times are drawn from a Poisson process (exponential inter-arrival
gaps at ``--rate`` requests/s), each request is submitted when the wall
clock reaches its arrival time, and the scheduler ticks continuously —
requests JOIN running decode streams at sequence boundaries, finish at
different times, and over-budget arrivals are rejected or downgraded by a
``BudgetAdmission`` policy wired to the head catalog's
``flops_per_query``.

Reported: sustained tokens/s, reject/downgrade rates, per-head tokens/s,
p50/p95 request latency (submission → last token), max queue depth, and
the recompile count between warmup and the measured run (expected 0 — the
whole point of fixed-width streams over the LRU step cache). A
machine-readable section is merged into ``BENCH_serving.json``.

    PYTHONPATH=src python benchmarks/serve_continuous.py            # full
    PYTHONPATH=src python benchmarks/serve_continuous.py --reduced  # CI

With >1 jax device the standard tier rides "screened-sharded", putting the
mesh-aware stream path under load.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

try:
    from benchmarks.common import update_bench_json
    from benchmarks.serve_mixed import build_engine
except ImportError:                        # script's own dir is sys.path[0]
    from common import update_bench_json
    from serve_mixed import build_engine

from repro.serving import (BudgetAdmission, CircuitBreaker,
                           ContinuousScheduler, FaultInjector, LogicalClock,
                           PagePool, ServeRequest, ServeResult,
                           StreamWatchdog, TierPolicy, Tracer)
from repro.serving.scheduler import TIER_DEADLINES, AdmissionRejected
from repro.utils.compile_cache import enable_compile_cache


def _export_trace(tracer, path, label):
    """Write the Chrome trace-event file + a one-line summary; returns the
    JSON-ready trace telemetry for the bench section."""
    if tracer is None:
        return None
    tracer.export_chrome(path)
    evs = tracer.events()
    n_req = sum(1 for e in evs if e["ph"] == "X" and e["name"] == "request")
    print(f"[{label}] trace: {len(evs)} events ({n_req} request spans, "
          f"{tracer.dropped} dropped) -> {path} "
          f"(load in chrome://tracing or ui.perfetto.dev)")
    return {"path": path, "events": len(evs), "request_spans": n_req,
            "dropped": tracer.dropped}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--chaos", action="store_true",
                    help="resilience workload: deterministic FaultInjector "
                         "(transient/permanent/NaN/stall faults) + circuit "
                         "breakers + watchdog on a simulated clock; "
                         "reports the fault funnel, breaker transitions, "
                         "greedy parity of fault-free survivors, and "
                         "recompiles (expected 0 — chaos is host-side)")
    ap.add_argument("--shared-prefix", action="store_true",
                    help="paged-KV workload: every prompt = one templated "
                         "system prompt + a short unique suffix, served "
                         "over a shared PagePool with a prefix radix "
                         "cache; reports prefix hit rate, pages in use, "
                         "HBM residency, COW rate, and greedy parity")
    ap.add_argument("--page-size", type=int, default=8,
                    help="[shared-prefix] KV page size in token slots "
                         "(must divide the engine max_len, 80)")
    ap.add_argument("--pool-pages", type=int, default=256,
                    help="[shared-prefix] total pool pages (page 0 is the "
                         "reserved trash page)")
    ap.add_argument("--template-len", type=int, default=48,
                    help="[shared-prefix] shared system-prompt tokens")
    ap.add_argument("--suffix-len", type=int, default=4,
                    help="[shared-prefix] unique per-request suffix tokens")
    ap.add_argument("--parity-checks", type=int, default=4,
                    help="[shared-prefix] completed requests to replay "
                         "through solo engine.generate for bit-identity")
    ap.add_argument("--requests", type=int, default=None,
                    help="total arrivals (default 16 reduced / 64)")
    ap.add_argument("--rate", type=float, default=None,
                    help="Poisson arrival rate, requests/s "
                         "(default 200 reduced / 50)")
    ap.add_argument("--max-new", type=int, default=None)
    ap.add_argument("--max-slots", type=int, default=4)
    ap.add_argument("--budget-x", type=float, default=3.0,
                    help="flops budget as a multiple of the priciest "
                         "candidate head's flops_per_query (drives a "
                         "nonzero reject/downgrade rate under burst)")
    ap.add_argument("--deadline-scale", type=float, default=10.0,
                    help="multiply TIER_DEADLINES by this (default 10: "
                         "CPU/interpret decode is orders slower than the "
                         "TPU the sub-second tiers assume; set 1.0 to "
                         "measure preemption churn)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="export the measured run's span timeline as a "
                         "Chrome trace-event JSON file (chrome://tracing / "
                         "Perfetto); works with the standard, --chaos and "
                         "--shared-prefix workloads")
    ap.add_argument("--json", default="BENCH_serving.json",
                    help="machine-readable output file ('' disables)")
    args = ap.parse_args(argv)
    n_req = args.requests or (16 if args.reduced else 64)
    rate = args.rate or (200.0 if args.reduced else 50.0)
    max_new = args.max_new or (8 if args.reduced else 32)

    cfg, corpus, engine = build_engine(args.reduced, args.seed)

    if args.chaos:
        return _chaos(args, cfg, corpus, engine,
                      args.requests or (24 if args.reduced else 64))
    if args.shared_prefix:
        return _shared_prefix(args, cfg, corpus, engine, n_req, rate)

    standard = "screened-sharded" if jax.device_count() > 1 else "svd"
    policy = TierPolicy({"realtime": "screened", "standard": standard,
                         "batch": "exact"}, default="screened")
    tiers = ["realtime", "standard", "batch"]
    prompts = corpus.sample_batch(n_req, 16, seed=42)
    requests = []
    for i, p in enumerate(prompts):
        sampled = (i % 6 == 5)
        requests.append(ServeRequest(
            prompt=p, max_new=max_new, latency_tier=tiers[i % 3],
            temperature=0.8 if sampled else None,
            top_p=0.95 if sampled else 1.0, seed=7))

    # flops budget off the same catalog admission reads; generous enough
    # that steady-state traffic flows, tight enough that a Poisson burst
    # sheds load (the reject path must be exercised, not just compiled)
    catalog = engine.head_catalog(tuple(policy.candidates))
    top_flops = max(m["flops_per_query"] for m in catalog.values())
    budget = args.budget_x * top_flops

    # warmup: compile every (candidate head × greedy/sample) stream combo
    # the measured run could touch. Routing alone does not bound this —
    # admission may DOWNGRADE any request (greedy or sampled) onto any
    # cheaper cataloged head, so the warmup pins each combo explicitly via
    # the request.head escape hatch instead of trusting the policy's map.
    warm_p = corpus.sample_batch(2, 16, seed=7)
    warmup = []
    for name in catalog:
        warmup.append(ServeRequest(prompt=warm_p[0], max_new=2, head=name))
        warmup.append(ServeRequest(prompt=warm_p[1], max_new=2, head=name,
                                   temperature=0.8, top_p=0.95, seed=7))
    ContinuousScheduler(engine, policy=policy, max_slots=args.max_slots,
                        max_streams=2 * len(catalog),
                        kv_pool=_pool(engine, len(warmup))).serve(warmup)
    counts0 = engine.compiled_step_counts()

    deadlines = {t: s * args.deadline_scale for t, s in TIER_DEADLINES.items()}
    # tracer on the SAME clock the scheduler reads, so request spans and
    # deadline bookkeeping share one timeline
    tracer = Tracer(clock=time.monotonic) if args.trace else None
    sched = ContinuousScheduler(
        engine, policy=policy,
        admission=BudgetAdmission(flops_budget=budget),
        max_slots=args.max_slots, max_streams=8, deadlines=deadlines,
        kv_pool=_pool(engine, len(requests)), tracer=tracer)
    wall = _drive(sched, requests, rate, args.seed)
    counts1 = engine.compiled_step_counts()
    recompiles = sum(counts1.values()) - sum(counts0.values())
    trace_info = _export_trace(tracer, args.trace, "serve_continuous")

    stats = sched.stats
    snap = stats.snapshot()
    results = sched.results()
    completed_tokens = sum(len(r.tokens) for r in results
                           if isinstance(r, ServeResult))
    print(f"\n[serve_continuous] vocab={cfg.vocab_size} arrivals={n_req} "
          f"rate={rate:.0f}/s max_new={max_new} "
          f"devices={jax.device_count()} flops_budget={budget:.3g}")
    print(f"[serve_continuous] {completed_tokens} tokens in {wall:.2f}s = "
          f"{completed_tokens / wall:.0f} tok/s sustained | admitted "
          f"{stats.admitted}/{stats.submitted} (rejected {stats.rejected}, "
          f"downgraded {stats.downgraded}, preempted {stats.preempted})")
    print(f"[serve_continuous] latency p50 {snap['latency']['p50_s']:.3f}s "
          f"p95 {snap['latency']['p95_s']:.3f}s | max queue depth "
          f"{stats.max_queue_depth} | recompiles after warmup {recompiles} "
          f"(expected 0)")
    print(f"{'head':<18}{'requests':>9}{'tokens':>8}{'tok/s':>10}")
    for head, d in snap["per_head"].items():
        print(f"{head:<18}{d['requests']:>9}{d['tokens']:>8}"
              f"{d['tokens_per_s']:>10.0f}")
    if args.json:
        path = update_bench_json("serve_continuous", {
            "devices": jax.device_count(), "vocab": cfg.vocab_size,
            "arrivals": n_req, "rate": rate, "max_new": max_new,
            "reduced": args.reduced, "flops_budget": budget,
            "wall_s": wall, "completed_tokens": completed_tokens,
            "tokens_per_s": completed_tokens / wall,
            "recompiles": recompiles, "trace": trace_info, **snap,
        }, path=args.json)
        print(f"[serve_continuous] wrote {path}")
    return 0


def _drive(sched, requests, rate, seed):
    """Open-loop Poisson arrivals at ``rate`` req/s; returns wall seconds."""
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / rate, size=len(requests)))
    t0 = time.perf_counter()
    nxt = 0
    while nxt < len(requests) or sched.busy:
        now = time.perf_counter() - t0
        while nxt < len(requests) and arrivals[nxt] <= now:
            sched.submit(requests[nxt])
            nxt += 1
        if sched.busy:
            sched.step()
        elif nxt < len(requests):         # idle until the next arrival
            time.sleep(max(0.0, arrivals[nxt] - (time.perf_counter() - t0)))
    return time.perf_counter() - t0


def _pool(engine, sequences):
    """A page pool of ``max_len`` pages with room for every sequence at
    once, so the pool adds no pressure of its own: each sequence holds one
    page (a dense cache's footprint) plus the private copy its first decode
    write makes of the prompt page the radix cache keeps."""
    return PagePool(num_pages=1 + 2 * sequences, page_size=engine.max_len)


def _chaos(args, cfg, corpus, engine, n_req):
    """--chaos: the resilience layer under deterministic fire.

    All-greedy traffic over three heads on a simulated ``LogicalClock``
    shared by scheduler, breaker and injector (the whole run replays
    bit-identically from the seed). The armed fault schedule exercises
    every degradation path: transient step faults (bounded retry),
    a permanent fault (hard breaker trip → fallback re-route → cooldown →
    half-open probe → close), NaN output corruption (guard detection),
    injected stalls (watchdog eviction), and per-request timeouts.

    Invariants printed and serialized: ZERO unhandled exceptions, the
    funnel closes (arrivals == completed + typed rejects), fault-free
    survivors decode bit-identical to solo ``engine.generate``, and the
    recompile count after warmup is 0 — fault injection and detection are
    entirely host-side, so chaos runs compile exactly what healthy runs
    compile."""
    max_new = args.max_new or 8
    policy = TierPolicy({"realtime": "screened", "standard": "svd",
                         "batch": "exact"}, default="screened")
    catalog = engine.head_catalog(tuple(policy.candidates))
    tiers = ["realtime", "standard", "batch"]
    prompts = corpus.sample_batch(n_req, 16, seed=42)
    requests = []
    for i, p in enumerate(prompts):
        # two late timeouts for coverage; everything else unbounded
        requests.append(ServeRequest(
            prompt=p, max_new=max_new, latency_tier=tiers[i % 3],
            timeout_s=0.004 if i in (5, 11) else None))

    # warmup compiles every greedy stream the run (or a fallback) could
    # touch; chaos itself is host-side and adds zero executables
    warm_p = corpus.sample_batch(1, 16, seed=7)
    warmup = [ServeRequest(prompt=warm_p[0], max_new=2, head=name)
              for name in catalog]
    ContinuousScheduler(engine, policy=policy, max_slots=args.max_slots,
                        max_streams=len(catalog) + 1,
                        kv_pool=_pool(engine, len(warmup))).serve(warmup)
    counts0 = engine.compiled_step_counts()

    clock = LogicalClock(0.0, dt_per_read=1e-3)
    injector = FaultInjector(seed=args.seed, clock=clock)
    injector.arm("step", "transient", head="screened", count=3, after=2)
    injector.arm("step", "permanent", head="svd", count=1, after=4)
    injector.arm("step", "nan", head="screened", count=2, after=12)
    injector.arm("step", "stall", head="exact", count=8, after=3)
    injector.arm("join", "transient", head="svd", count=1, after=8)
    injector.arm("tick", "delay", delay_s=2e-3, rate=0.1, count=5)
    breaker = CircuitBreaker(failure_threshold=3, cooldown_s=0.05,
                             clock=clock)
    watchdog = StreamWatchdog(stall_timeout_s=5e-3)
    deadlines = {t: s * args.deadline_scale
                 for t, s in TIER_DEADLINES.items()}
    # PEEK the logical clock (clock.t, not clock()): reads auto-advance
    # the shared simulated timeline, so a tracing read would perturb the
    # deterministic fault/deadline schedule the run replays
    tracer = Tracer(clock=lambda: clock.t) if args.trace else None
    sched = ContinuousScheduler(
        engine, policy=policy, max_slots=args.max_slots, max_streams=8,
        deadlines=deadlines, clock=clock, kv_pool=_pool(engine, len(requests)),
        fault_injector=injector, breaker=breaker, watchdog=watchdog,
        max_retries=2, tracer=tracer)
    t0 = time.perf_counter()
    unhandled = None
    try:
        for r in requests:
            sched.submit(r)
        results = sched.drain(max_ticks=5000)
    except Exception as e:                     # noqa: BLE001 — the headline
        unhandled = f"{type(e).__name__}: {e}"
        results = sched.results()
    wall = time.perf_counter() - t0
    counts1 = engine.compiled_step_counts()
    recompiles = sum(counts1.values()) - sum(counts0.values())
    trace_info = _export_trace(tracer, args.trace, "serve_chaos")

    completed = [(i, r) for i, r in enumerate(results)
                 if isinstance(r, ServeResult)]
    rejects = [r for r in results if isinstance(r, AdmissionRejected)]
    funnel_closed = len(completed) + len(rejects) == n_req
    clean = [(i, r) for i, r in completed if i not in sched.fault_rids]
    parity = True
    for i, r in clean[:8]:
        ref = engine.generate(requests[i].prompt[None],
                              requests[i].max_new).tokens[0]
        parity = parity and bool(np.array_equal(r.tokens, ref))

    snap = sched.stats.snapshot()
    rz = snap["resilience"] or {}
    print(f"\n[serve_chaos] arrivals={n_req} max_new={max_new} heads="
          f"{list(catalog)} devices={jax.device_count()} wall={wall:.2f}s")
    print(f"[serve_chaos] unhandled exceptions: "
          f"{unhandled or 0} (expected 0)")
    print(f"[serve_chaos] funnel: {len(completed)} completed + "
          f"{len(rejects)} typed rejects == {n_req} arrivals: "
          f"{funnel_closed}")
    print(f"[serve_chaos] faults {injector.telemetry()['fired_total']} "
          f"fired ({rz.get('faults_transient', 0)} transient, "
          f"{rz.get('faults_permanent', 0)} permanent) | retries "
          f"{rz.get('retries', 0)} fallbacks {rz.get('fallbacks', 0)} "
          f"faulted {rz.get('faulted', 0)} timed_out "
          f"{rz.get('timed_out', 0)} stalls "
          f"{rz.get('watchdog_stalls', 0)}")
    print(f"[serve_chaos] breakers: trips {rz.get('breaker_trips', 0)} "
          f"half-opens {rz.get('breaker_half_opens', 0)} closes "
          f"{rz.get('breaker_closes', 0)} | states "
          f"{rz.get('breaker_states', {})}")
    print(f"[serve_chaos] greedy parity of {len(clean[:8])} fault-free "
          f"survivors: {parity} | recompiles after warmup {recompiles} "
          f"(expected 0)")
    ok = unhandled is None and funnel_closed and parity and recompiles == 0
    if args.json:
        path = update_bench_json("serve_chaos", {
            "devices": jax.device_count(), "vocab": cfg.vocab_size,
            "arrivals": n_req, "max_new": max_new,
            "reduced": args.reduced, "wall_s": wall,
            "unhandled": unhandled, "funnel_closed": funnel_closed,
            "completed": len(completed), "typed_rejects": len(rejects),
            "fault_rids": len(sched.fault_rids),
            "faults_fired": injector.telemetry(),
            "greedy_parity": parity, "parity_checked": len(clean[:8]),
            "recompiles": recompiles, "ok": ok, "trace": trace_info, **snap,
        }, path=args.json)
        print(f"[serve_chaos] wrote {path}")
    return 0 if ok else 1


def _shared_prefix(args, cfg, corpus, engine, n_req, rate):
    """--shared-prefix: templated prompts over one shared ``PagePool``.

    Every request is the SAME template prompt (a long "system prompt")
    plus a short unique suffix — the agent-serving shape paged KV exists
    for. A warmup scheduler shares the pool, so it both compiles every
    stream step the measured run touches AND primes the radix cache with
    the template's pages; the measured window then sees a per-request
    prefix hit of template/(template+suffix) tokens, zero step recompiles,
    and greedy tokens bit-identical to solo ``engine.generate``."""
    max_new = args.max_new or 8
    Tp = args.template_len + args.suffix_len
    if Tp + max_new > engine.max_len:
        raise SystemExit(f"template+suffix+max_new = {Tp + max_new} exceeds "
                         f"engine max_len {engine.max_len}")
    template = corpus.sample_batch(1, args.template_len, seed=5)[0]
    suffixes = corpus.sample_batch(n_req + 2, args.suffix_len, seed=43)
    tiers = ["realtime", "standard", "batch"]
    requests = [ServeRequest(
        prompt=np.concatenate([template, suffixes[i]]).astype(np.int32),
        max_new=max_new, latency_tier=tiers[i % 3]) for i in range(n_req)]

    standard = "screened-sharded" if jax.device_count() > 1 else "svd"
    policy = TierPolicy({"realtime": "screened", "standard": standard,
                         "batch": "exact"}, default="screened")
    catalog = engine.head_catalog(tuple(policy.candidates))
    pool = PagePool(num_pages=args.pool_pages, page_size=args.page_size)

    # warmup shares the POOL: compiles per-head streams + chunked resume
    # prefill for the template grid AND pins the template's pages in the
    # radix cache, so the measured window starts hot on both axes
    warmup = [ServeRequest(
        prompt=np.concatenate([template, suffixes[n_req + i % 2]])
        .astype(np.int32), max_new=2, head=name)
        for i, name in enumerate(catalog)]
    ContinuousScheduler(engine, policy=policy, max_slots=args.max_slots,
                        max_streams=len(catalog) + 1,
                        kv_pool=pool).serve(warmup)
    counts0 = engine.compiled_step_counts()
    radix = pool.radix
    hit0, tot0 = radix.tokens_hit, radix.tokens_total

    deadlines = {t: s * args.deadline_scale
                 for t, s in TIER_DEADLINES.items()}
    tracer = Tracer(clock=time.monotonic) if args.trace else None
    sched = ContinuousScheduler(engine, policy=policy,
                                max_slots=args.max_slots, max_streams=8,
                                deadlines=deadlines, kv_pool=pool,
                                tracer=tracer)
    wall = _drive(sched, requests, rate, args.seed)
    counts1 = engine.compiled_step_counts()
    recompiles = sum(counts1.values()) - sum(counts0.values())
    trace_info = _export_trace(tracer, args.trace, "serve_shared_prefix")
    hit_rate = (radix.tokens_hit - hit0) / max(1, radix.tokens_total - tot0)

    results = sched.results()
    served = [(req, r) for req, r in zip(requests, results)
              if isinstance(r, ServeResult)]
    parity = True
    for req, r in served[:args.parity_checks]:
        ref = engine.generate(req.prompt[None], req.max_new).tokens[0]
        parity = parity and bool(np.array_equal(r.tokens, ref))

    snap = sched.stats.snapshot()
    ptel = snap["pool"]
    tokens = sum(len(r.tokens) for _, r in served)
    print(f"\n[serve_shared_prefix] arrivals={n_req} template="
          f"{args.template_len} suffix={args.suffix_len} page={args.page_size} "
          f"pool={args.pool_pages} devices={jax.device_count()}")
    print(f"[serve_shared_prefix] {tokens} tokens in {wall:.2f}s = "
          f"{tokens / wall:.0f} tok/s | completed {len(served)}/{n_req} "
          f"(preempted {sched.stats.preempted})")
    print(f"[serve_shared_prefix] prefix hit rate {hit_rate:.3f} (measured "
          f"window; cumulative {radix.hit_rate:.3f}) | pages in use "
          f"{ptel['pages_in_use']}/{ptel['pages_total']} (peak "
          f"{ptel['peak_pages_in_use']}) | cow {ptel['cow_copies']} | "
          f"hbm resident {ptel['hbm_resident_bytes']} B")
    print(f"[serve_shared_prefix] greedy parity {parity} | recompiles after "
          f"warmup {recompiles} (expected 0)")
    if args.json:
        path = update_bench_json("serve_shared_prefix", {
            "devices": jax.device_count(), "vocab": cfg.vocab_size,
            "arrivals": n_req, "rate": rate, "max_new": max_new,
            "reduced": args.reduced, "template_len": args.template_len,
            "suffix_len": args.suffix_len, "page_size": args.page_size,
            "pool_pages": args.pool_pages,
            "wall_s": wall, "completed_tokens": tokens,
            "tokens_per_s": tokens / wall,
            "prefix_hit_rate": hit_rate,
            "greedy_parity": parity, "recompiles": recompiles,
            "trace": trace_info, **snap,
        }, path=args.json)
        print(f"[serve_shared_prefix] wrote {path}")
    return 0


if __name__ == "__main__":
    enable_compile_cache()
    raise SystemExit(main())
