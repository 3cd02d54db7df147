"""Speculative-decoding serving benchmark: accepted-tokens-per-step and
tokens/s for each registered cheap draft head against one exact verify
head, vs plain exact continuous batching.

For every draft head in {screened, screened-pallas, adaptive} that is
buildable in the engine, traffic is served twice through a
``ContinuousScheduler`` whose ``SpecPolicy`` pins that draft: once to warm
the compiled draft/verify steps, once timed. The report per draft head:

  accepted tok/step   emitted tokens / per-slot verify rounds (plain
                      decode scores exactly 1.0 on this metric)
  acceptance          drafted tokens the verify head kept
  tokens/s, speedup   timed drain vs the plain exact baseline
  recompiles          XLA executables added between warmup and the timed
                      run — the headline is that it stays 0: the adaptive
                      draft-length controller shrinks n inside ONE padded
                      verify executable
  parity              greedy spec tokens are BIT-identical to plain exact

With more than one jax device (e.g.
``XLA_FLAGS=--xla_force_host_platform_device_count=8``) the verify head
upgrades to ``exact-sharded``, exercising the mesh-aware batched verify
step; drafting and acceptance are unchanged (sharded verify is greedy-only
by design).

    PYTHONPATH=src python benchmarks/serve_spec.py              # full
    PYTHONPATH=src python benchmarks/serve_spec.py --reduced    # CI smoke

The CI smoke additionally ASSERTS the spec-serving contract: zero
recompiles, acceptance > 0, and bit-parity (see .github/workflows/ci.yml).
Results merge into ``BENCH_serving.json`` under ``serve_spec``.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

try:
    from benchmarks.common import update_bench_json
    from benchmarks.serve_mixed import build_engine
except ImportError:                      # script's own dir is sys.path[0]
    from common import update_bench_json
    from serve_mixed import build_engine

from repro.serving import (ContinuousScheduler, ServeRequest, ServeResult,
                           SpecPolicy, StaticPolicy)
from repro.utils.compile_cache import enable_compile_cache

DRAFTS = ("screened", "screened-pallas", "adaptive")


def _serve_timed(engine, requests, verify, spec=None):
    """One fresh scheduler drain; returns (results, wall seconds, stats)."""
    sched = ContinuousScheduler(engine, policy=StaticPolicy(verify),
                                spec=spec)
    t0 = time.perf_counter()
    results = sched.serve(requests)
    return results, time.perf_counter() - t0, sched.stats


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=None,
                    help="concurrent requests (default 8 reduced / 24)")
    ap.add_argument("--max-new", type=int, default=None)
    ap.add_argument("--draft-len", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default="BENCH_serving.json",
                    help="machine-readable output file ('' disables)")
    args = ap.parse_args(argv)
    n_req = args.requests or (8 if args.reduced else 24)
    max_new = args.max_new or (8 if args.reduced else 32)

    cfg, corpus, engine = build_engine(args.reduced, args.seed)
    verify = "exact-sharded" if jax.device_count() > 1 else "exact"
    prompts = corpus.sample_batch(n_req, 16, seed=42)
    requests = [ServeRequest(prompt=p, max_new=max_new) for p in prompts]

    # plain exact baseline: warm once, time a fresh drain
    _serve_timed(engine, requests, verify)
    base, t_base, _ = _serve_timed(engine, requests, verify)
    base_tokens = {i: r.tokens for i, r in enumerate(base)}
    total_tokens = sum(len(t) for t in base_tokens.values())
    print(f"\n[serve_spec] vocab={cfg.vocab_size} requests={n_req} "
          f"max_new={max_new} draft_len={args.draft_len} "
          f"devices={jax.device_count()} verify={verify}")
    print(f"[serve_spec] baseline {verify}: {total_tokens} tokens in "
          f"{t_base:.2f}s = {total_tokens / t_base:.0f} tok/s")

    catalog = engine.head_catalog(DRAFTS)
    print(f"{'draft':<18}{'acc tok/step':>13}{'acceptance':>11}"
          f"{'tok/s':>9}{'speedup':>8}{'recompiles':>11}{'parity':>7}")
    per_draft = {}
    smoke_ok = True
    for draft in DRAFTS:
        if draft not in catalog:
            print(f"{draft:<18}{'-- not buildable in this engine --':>40}")
            continue
        spec = SpecPolicy(drafts=(draft,), draft_len=args.draft_len)
        _serve_timed(engine, requests, verify, spec=spec)     # warmup
        counts0 = engine.compiled_step_counts()
        results, t_spec, stats = _serve_timed(engine, requests, verify,
                                              spec=spec)
        counts1 = engine.compiled_step_counts()
        recompiles = sum(counts1.values()) - sum(counts0.values())
        parity = all(
            isinstance(r, ServeResult) and
            np.array_equal(r.tokens, base_tokens[i])
            for i, r in enumerate(results))
        sp = stats.snapshot()["spec"] or {}
        # SpecPolicy may decline a draft whose flops advantage over this
        # verify head is too thin (e.g. adaptive vs per-shard exact-sharded
        # flops) — those requests serve plain, which is correct behavior,
        # not a contract violation
        engaged = sp.get("rounds", 0) > 0
        acc_step = sp.get("accepted_tokens_per_step", float("nan"))
        acc_rate = sp.get("draft_acceptance", float("nan"))
        tok_s = total_tokens / t_spec
        if engaged:
            print(f"{draft:<18}{acc_step:>13.2f}{acc_rate:>11.3f}"
                  f"{tok_s:>9.0f}{t_base / t_spec:>8.2f}{recompiles:>11}"
                  f"{str(parity):>7}")
        else:
            note = ("-- policy declined (served plain: flops advantage "
                    "below min_ratio) --")
            print(f"{draft:<18}{note:>58}")
        per_draft[draft] = {
            "engaged": engaged,
            "accepted_tokens_per_step": acc_step,
            "acceptance_rate": acc_rate,
            "accepted": sp.get("accepted", 0),
            "drafted": sp.get("drafted", 0),
            "verify_queries": sp.get("verify_queries", 0),
            "verify_flops": sp.get("verify_flops", 0.0),
            "decode_s": t_spec, "tokens_per_s": tok_s,
            "speedup": t_base / t_spec,
            "recompiles": recompiles, "parity": parity,
        }
        smoke_ok &= parity and recompiles == 0 and \
            (not engaged or sp.get("accepted", 0) > 0)
    if not any(d["engaged"] for d in per_draft.values()):
        print("[serve_spec] no draft head engaged — nothing speculated")
        return 1
    if args.json:
        path = update_bench_json("serve_spec", {
            "devices": jax.device_count(), "vocab": cfg.vocab_size,
            "requests": n_req, "max_new": max_new,
            "draft_len": args.draft_len, "reduced": args.reduced,
            "verify_head": verify,
            "baseline": {"head": verify, "tokens": total_tokens,
                         "decode_s": t_base,
                         "tokens_per_s": total_tokens / t_base},
            "per_draft": per_draft,
        }, path=args.json)
        print(f"[serve_spec] wrote {path}")
    print(f"[serve_spec] contract (parity, 0 recompiles, acceptance>0): "
          f"{'OK' if smoke_ok else 'VIOLATED'}")
    return 0 if smoke_ok else 1


if __name__ == "__main__":
    enable_compile_cache()
    raise SystemExit(main())
