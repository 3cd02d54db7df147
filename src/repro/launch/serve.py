"""Serving launcher: batched generation with optional L2S screened softmax.

``python -m repro.launch.serve --arch ptb-small-lstm --reduced --l2s``
trains a tiny LM on the synthetic corpus, fits the screen (Algorithm 1), and
serves ``ServeRequest`` batches through both heads via
``DecodeEngine.serve_batch`` + ``StaticPolicy``, reporting decode time and
token agreement.

``--scheduler`` serves the same traffic through the continuous-batching
``ContinuousScheduler`` instead: mixed latency tiers, a ``BudgetAdmission``
policy against the head catalog's flops numbers, and a ``ServerStats``
report (admit/reject/downgrade counts, per-head tokens/s, p50/p95
latency).

A fast head that needs a screen (``--head screened`` without ``--l2s``)
fails BEFORE training with exit code 2 and the fix-it message — the
screening factories raise a typed ``MissingScreenError``. A head named by
``--head`` or ``--draft-head`` that cannot be built after the screen is fit
is also exit code 2: it is never quietly replaced by ``exact``. The screen
is fit at the block its head needs — ``V_BLK`` (128) words when a requested
head runs the Pallas kernels, single words otherwise.

``prepare`` (train + fit + engine) and ``serve_scheduler`` are the pieces
``chip_smoke.py`` drives; ``main`` is the command line around them.
"""
from __future__ import annotations

import argparse
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from repro import heads as heads_registry
from repro.configs import L2SConfig, get_config
from repro.core import collect_contexts, fit_l2s
from repro.data import ZipfMarkovCorpus, make_lm_batches
from repro.heads import MissingScreenError
from repro.kernels.screen import V_BLK
from repro.launch.steps import make_train_step
from repro.models import build_model
from repro.optim import adamw_init
from repro.serving import DecodeEngine, ServeRequest, StaticPolicy
from repro.configs import TrainConfig
from repro.utils.compile_cache import enable_compile_cache

MAX_SLOTS = 4           # decode width of every scheduler stream

# heads that run the Pallas block kernels and so need a V_BLK-word screen
_BLOCK_HEADS = ("screened-pallas",)


def screen_block(*head_names) -> int:
    """Vocab block the screen is fit at for the requested heads."""
    return V_BLK if any(n in _BLOCK_HEADS for n in head_names) else 1


def screen_budget(block: int, budget=None) -> int:
    """Average candidate words per cluster: ``budget`` when given, else 100
    words for a word screen and 8 blocks for a block screen. A budget under
    one block leaves most clusters without a candidate block, and a query
    routed to such a cluster has no word to emit."""
    if budget is not None:
        return budget
    return 100 if block == 1 else 8 * block


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="ptb-small-lstm")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--l2s", action="store_true")
    ap.add_argument("--scheduler", action="store_true",
                    help="serve through the continuous-batching "
                         "ContinuousScheduler (admission control + live "
                         "ServerStats) instead of one serve_batch call")
    ap.add_argument("--head", default=None,
                    help="registry name of the fast decode head served "
                         "against exact (screened, screened-sharded, "
                         "exact-sharded, screened-pallas, ...); defaults "
                         "to screened when --l2s fits a screen")
    ap.add_argument("--draft-head", default=None,
                    help="speculative decoding: registry name of the cheap "
                         "DRAFT head; the exact head verifies every draft, "
                         "so output is unchanged. Needs --scheduler (spec "
                         "runs on SpecDecodeStream lanes) and a head "
                         "distinct from the verify head")
    ap.add_argument("--log-jsonl", default=None, metavar="PATH",
                    help="--scheduler only: write one structured JSON "
                         "record per scheduler tick (numeric stats deltas "
                         "+ breaker states) to PATH; the human-readable "
                         "summary lines are unchanged")
    ap.add_argument("--train-steps", type=int, default=150)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--clusters", type=int, default=50)
    ap.add_argument("--budget", type=int, default=None,
                    help="L2S average candidate words per cluster "
                         "(default: 100, or 8 blocks for a block screen)")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def main(argv=None):
    args = build_parser().parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    assert cfg.supports_decode, f"{cfg.name} is encoder-only"
    model = build_model(cfg)
    params = model.init(jax.random.key(args.seed), dtype=jnp.float32)

    # fail FAST on a screening head without --l2s: probe the factory with a
    # tiny weight slice BEFORE spending time on training. Screening heads
    # raise MissingScreenError from their constructor regardless of shapes;
    # any other failure is inconclusive at probe scale (the head may just
    # need the real tables) and is re-raised properly after training.
    head_name = args.head if args.head is not None else \
        ("screened" if args.l2s else None)
    # an unknown head name is conclusive NOW (the registry is static) — a
    # typo must not cost a full training run before the KeyError surfaces
    if head_name is not None and head_name not in heads_registry.names():
        print(f"[serve] unknown head {head_name!r}; registered: "
              f"{heads_registry.names()}")
        return 2
    if head_name not in (None, "exact") and not args.l2s:
        W0, b0 = model.softmax_weights(params)
        try:
            heads_registry.get(head_name, W=W0[:8], b=b0[:8], screen=None)
        except MissingScreenError as e:
            print(f"[serve] cannot build head {head_name!r}: {e} "
                  f"(pass --l2s to fit one)")
            return 2
        except Exception:
            pass
    # --draft-head combos are all conclusive BEFORE training: unknown names,
    # drafting with the verify head itself, serving modes that have no spec
    # lane, and screening drafts without a screen to fit
    if args.draft_head is not None:
        if args.draft_head not in heads_registry.names():
            print(f"[serve] unknown draft head {args.draft_head!r}; "
                  f"registered: {heads_registry.names()}")
            return 2
        if not args.scheduler:
            print("[serve] --draft-head needs --scheduler: speculative "
                  "decoding runs on the scheduler's SpecDecodeStream lanes")
            return 2
        if args.draft_head == "exact":
            print("[serve] --draft-head 'exact' IS the verify head — "
                  "drafting with the head that verifies speculates "
                  "nothing; pick a cheaper draft (screened, "
                  "screened-pallas, adaptive)")
            return 2
        if args.draft_head != "exact" and not args.l2s:
            W0, b0 = model.softmax_weights(params)
            try:
                heads_registry.get(args.draft_head, W=W0[:8], b=b0[:8],
                                   screen=None)
            except MissingScreenError as e:
                print(f"[serve] cannot build draft head "
                      f"{args.draft_head!r}: {e} (pass --l2s to fit one)")
                return 2
            except Exception:
                pass
    if args.log_jsonl is not None and not args.scheduler:
        print("[serve] --log-jsonl needs --scheduler: the per-tick records "
              "come from the ContinuousScheduler's tick loop")
        return 2

    enable_compile_cache()
    setup = prepare(args, cfg, model, params, head_name)
    engine, requests = setup.engine, setup.requests
    # a head asked for by name is served or refused — never swapped for exact
    for name in dict.fromkeys(filter(None, (head_name, args.draft_head))):
        if name == "exact":
            continue
        try:
            engine.resolve_head(name)
        except (MissingScreenError, AssertionError, ValueError) as e:
            print(f"[serve] cannot build head {name!r}: {e}")
            return 2

    if args.scheduler:
        serve_scheduler(engine, requests, head_name,
                        draft=args.draft_head, log_jsonl=args.log_jsonl)
        return 0

    t0 = time.time()
    exact = engine.serve_batch(requests, policy=StaticPolicy("exact"))
    t_exact = time.time() - t0
    print(f"[serve] exact decode: {args.requests}×{args.max_new} tokens "
          f"in {t_exact:.2f}s")
    if head_name is not None and head_name != "exact":
        t0 = time.time()
        fast = engine.serve_batch(requests, policy=StaticPolicy(head_name))
        t_fast = time.time() - t0
        agree = float(np.mean([
            (f.tokens == e.tokens).mean() for f, e in zip(fast, exact)]))
        print(f"[serve] {head_name} decode:  {t_fast:.2f}s  "
              f"token agreement {agree:.3f}")
    return 0


def prepare(args, cfg, model, params, head_name=None) -> SimpleNamespace:
    """Train ``model`` for ``args.train_steps`` from ``params``, fit the L2S
    screen when ``args.l2s`` (at the block ``head_name`` and
    ``args.draft_head`` need), and build the engine and the request set.
    → namespace(params, screen, engine, requests)."""
    corpus = ZipfMarkovCorpus(cfg.vocab_size, branching=min(64, cfg.vocab_size // 4),
                              seed=args.seed)

    # quick train so context vectors are meaningful
    tcfg = TrainConfig(lr=1e-3, total_steps=args.train_steps,
                       warmup_steps=10, remat="none", loss_chunk=None)
    step_fn = jax.jit(make_train_step(model, tcfg))
    opt_state = adamw_init(params)
    for batch in make_lm_batches(corpus, args.train_steps, 16, 64, seed=1):
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        params, opt_state, metrics = step_fn(params, opt_state, batch)
    print(f"[serve] trained {args.train_steps} steps, loss "
          f"{float(metrics['loss']):.3f}")

    screen = None
    if args.l2s:
        batches = [jnp.asarray(b["tokens"])
                   for b in make_lm_batches(corpus, 16, 16, 64, seed=7)]
        H, y = collect_contexts(model, params, batches, max_vectors=15_000)
        block = screen_block(head_name, args.draft_head)
        budget = screen_budget(block, args.budget)
        state = fit_l2s(H, y, cfg.vocab_size,
                        L2SConfig(num_clusters=args.clusters, budget=budget,
                                  outer_iters=2, sgd_steps=100,
                                  vocab_block=block))
        screen = state.screen
        print(f"[serve] L2S fitted: r={args.clusters} budget={budget} "
              f"C_max={screen.c_max} block={screen.block}")

    # spec decode can transiently write draft_len − 1 rejected positions
    # past a request's final token (SpecPolicy default draft_len = 4);
    # without this slack the policy's headroom check would always decline
    spec_slack = 3 if args.draft_head is not None else 0
    engine = DecodeEngine(model, params, screen=screen,
                          max_len=args.prompt_len + args.max_new + spec_slack)
    prompts = corpus.sample_batch(args.requests, args.prompt_len, seed=42)
    requests = [ServeRequest(prompt=p, max_new=args.max_new)
                for p in prompts]
    return SimpleNamespace(params=params, screen=screen, engine=engine,
                           requests=requests)


def _tick_delta(prev: dict, cur: dict) -> dict:
    """Numeric top-level deltas between two ``ServerStats.snapshot()``s —
    the per-tick payload of ``--log-jsonl`` (counters that didn't move are
    omitted, so quiet ticks stay one short line)."""
    out = {}
    import math
    for k, v in cur.items():
        p = prev.get(k, 0)
        if isinstance(v, bool) or not isinstance(v, (int, float)):
            continue
        if not isinstance(p, (int, float)) or not math.isfinite(v) \
                or not math.isfinite(p):
            continue
        if v != p:
            out[k] = v - p
    return out


def serve_scheduler(engine, requests, head_name, draft=None,
                    log_jsonl=None):
    """--scheduler mode: continuous batching with admission control.

    Traffic is the launcher's request set re-tiered round-robin
    (realtime / standard / batch); the fast head serves the realtime
    tier, "exact" everything else. The flops budget is sized
    to the catalog so a burst sheds load through the typed reject path.
    Families the paged KV pool supports additionally serve over a
    ``PagePool`` (shared-prefix radix cache + COW pages) and report pool
    utilization in the log. With ``draft`` set (--draft-head) every
    request carries it explicitly and exact-routed traffic decodes
    speculatively on ``SpecDecodeStream`` lanes — same tokens, fewer
    exact-head weight streams. The caller has already resolved ``head_name``
    and ``draft`` (``main`` exits 2 on a head it cannot build). Returns the
    drained ``ContinuousScheduler`` (``.stats``, ``.results()``)."""
    import dataclasses

    from repro.serving import (BudgetAdmission, ContinuousScheduler,
                               PagePool, ServeResult, SpecPolicy, TierPolicy)

    fast = head_name if head_name not in (None, "exact") else None
    candidates = tuple(dict.fromkeys(filter(None, (fast, draft, "exact"))))
    catalog = engine.head_catalog(candidates)
    policy = TierPolicy({"realtime": fast or "exact"}, default="exact")
    budget = 4.0 * max(m["flops_per_query"] for m in catalog.values())
    tiers = ["realtime", "standard", "batch"]
    traffic = [dataclasses.replace(r, latency_tier=tiers[i % 3],
                                   draft_head=draft)
               for i, r in enumerate(requests)]
    spec = SpecPolicy(drafts=(draft,)) if draft is not None else None

    kv_pool = None
    if engine.model.cfg.family in ("lstm", "dense", "moe") \
            and engine.model.cfg.sliding_window is None:
        page = 8 if engine.max_len % 8 == 0 else 4
        while engine.max_len % page:
            page //= 2                     # max_len is even in practice
        kv_pool = PagePool(num_pages=4 * (engine.max_len // page),
                           page_size=page)
    sched = ContinuousScheduler(engine, policy=policy,
                                admission=BudgetAdmission(flops_budget=budget),
                                max_slots=MAX_SLOTS, kv_pool=kv_pool,
                                spec=spec)
    t0 = time.time()
    if log_jsonl is None:
        results = sched.serve(traffic)
    else:
        # submit-all + explicit tick loop so every tick emits one
        # structured record (stats delta + breaker states); identical
        # serving behavior to sched.serve(traffic)
        import json
        for r in traffic:
            sched.submit(r)
        prev = sched.stats.snapshot()
        with open(log_jsonl, "w") as f:
            while sched.busy:
                sched.step()
                snap = sched.stats.snapshot()
                rz = snap.get("resilience") or {}
                rec = {"tick": snap["ticks"],
                       "delta": _tick_delta(prev, snap),
                       "queue_depth": snap["queue_depth"],
                       "breaker_states": rz.get("breaker_states", {})}
                f.write(json.dumps(rec) + "\n")
                prev = snap
        results = sched.results()
        print(f"[serve] per-tick JSONL log: {log_jsonl}")
    wall = time.time() - t0
    snap = sched.stats.snapshot()
    tokens = sum(len(r.tokens) for r in results if isinstance(r, ServeResult))
    print(f"[serve] scheduler: {tokens} tokens in {wall:.2f}s = "
          f"{tokens / max(wall, 1e-9):.0f} tok/s | admitted "
          f"{snap['admitted']}/{snap['submitted']} rejected "
          f"{snap['rejected']} downgraded {snap['downgraded']} "
          f"preempted {snap['preempted']}")
    print(f"[serve] scheduler: latency p50 {snap['latency']['p50_s']:.3f}s "
          f"p95 {snap['latency']['p95_s']:.3f}s | per-head "
          + ", ".join(f"{h}: {d['requests']} req {d['tokens_per_s']:.0f} "
                      f"tok/s" for h, d in snap["per_head"].items()))
    if snap.get("spec"):
        sp = snap["spec"]
        print(f"[serve] scheduler: spec {sp['rounds']} rounds | "
              f"{sp['accepted_tokens_per_step']:.2f} accepted tok/step | "
              f"draft acceptance {sp['draft_acceptance']:.3f} | "
              f"{sp['verify_queries']} verify queries "
              f"({sp['verify_flops']:.3g} flops)")
    if snap.get("resilience"):
        rz = snap["resilience"]
        states = ", ".join(f"{h}: {s}" for h, s in
                           rz["breaker_states"].items()) or "all closed"
        print(f"[serve] scheduler: resilience "
              f"{rz['faults_transient']}+{rz['faults_permanent']} faults "
              f"(transient+permanent) | {rz['retries']} retries "
              f"{rz['fallbacks']} fallbacks {rz['faulted']} faulted "
              f"{rz['timed_out']} timed out | breakers {states} "
              f"(trips {rz['breaker_trips']}, half-opens "
              f"{rz['breaker_half_opens']}, closes {rz['breaker_closes']})")
    if snap.get("pool"):
        p = snap["pool"]
        print(f"[serve] scheduler: kv pool {p['pages_in_use']}/"
              f"{p['pages_total']} pages in use (peak "
              f"{p['peak_pages_in_use']}, {p['pages_free']} free) | "
              f"prefix hit rate {p['prefix']['hit_rate']:.3f} | "
              f"cow {p['cow_copies']} ({p['cow_copies_per_tick']:.2f}/tick) "
              f"| hbm resident {p['hbm_resident_bytes']} B")
    return sched


if __name__ == "__main__":
    raise SystemExit(main())
