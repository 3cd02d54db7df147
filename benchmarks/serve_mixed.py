"""Heavy-traffic serving harness: N concurrent ServeRequests across >= 3
decode heads through ONE DecodeEngine.serve_batch call.

Reports, per resolved head: request count, tokens served, tokens/s (timed on
a single-head sub-batch after warmup), and the RECOMPILE count the mixed
batch caused (XLA executables added to the engine's cached steps between
warmup and the timed run — the headline number is that it stays 0: routing
mixed traffic reuses each head's one compiled step).

    PYTHONPATH=src python benchmarks/serve_mixed.py              # full
    PYTHONPATH=src python benchmarks/serve_mixed.py --reduced    # CI smoke

The standard tier rides the frequency-tiered "adaptive" head (unigram
counts accumulated during the training loop); with more than one jax
device (e.g. XLA_FLAGS=--xla_force_host_platform_device_count=8) it
upgrades to "adaptive-sharded", exercising the mesh-aware step path with
the rare-tail region vocab-sharded.

Alongside the human-readable table the run merges a machine-readable
section into ``BENCH_serving.json`` (per-head tokens/s, p50/p95 request
latency, recompile counts — see benchmarks/README.md) so the serving perf
trajectory is tracked across PRs.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

try:
    from benchmarks.common import update_bench_json
except ImportError:
    from common import update_bench_json  # script's own dir is sys.path[0]

from repro.configs import L2SConfig, TrainConfig, get_config
from repro.core import collect_contexts, fit_l2s
from repro.data import ZipfMarkovCorpus, make_lm_batches
from repro.launch.steps import make_train_step
from repro.models import build_model
from repro.optim import adamw_init
from repro.serving import DecodeEngine, ServeRequest, TierPolicy
from repro.utils.timing import LatencyTracker
from repro.utils.compile_cache import enable_compile_cache


def build_engine(reduced: bool, seed: int):
    vocab, d, steps = (600, 64, 60) if reduced else (4000, 128, 400)
    cfg = dataclasses.replace(get_config("ptb-small-lstm"), vocab_size=vocab,
                              d_model=d, dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.key(seed), dtype=jnp.float32)
    corpus = ZipfMarkovCorpus(vocab, branching=64, seed=seed)
    tcfg = TrainConfig(lr=2e-3, total_steps=steps, warmup_steps=10,
                       remat="none", loss_chunk=None)
    step_fn = jax.jit(make_train_step(model, tcfg))
    opt = adamw_init(params)
    # unigram token counts ride along with training — they parameterize the
    # adaptive head's frequency tiers and its tier-weighted cost model
    counts = np.zeros(vocab, np.int64)
    for batch in make_lm_batches(corpus, steps, 16, 64, seed=1):
        counts += np.bincount(np.asarray(batch["tokens"]).ravel(),
                              minlength=vocab)
        params, opt, _ = step_fn(
            params, opt, {k: jnp.asarray(v) for k, v in batch.items()})
    H, y = collect_contexts(
        model, params,
        [jnp.asarray(b["tokens"])
         for b in make_lm_batches(corpus, 16, 16, 64, seed=9)],
        max_vectors=10_000)
    st = fit_l2s(H, y, vocab,
                 L2SConfig(num_clusters=16 if reduced else 64,
                           budget=48 if reduced else 120,
                           outer_iters=1, sgd_steps=60))
    return cfg, corpus, DecodeEngine(
        model, params, screen=st.screen, max_len=16 + 64,
        head_kwargs=dict(rho=min(16, d), counts=counts,
                         shortlist=max(64, vocab // 8)))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=None,
                    help="total concurrent requests (default 12 reduced / 48)")
    ap.add_argument("--max-new", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default="BENCH_serving.json",
                    help="machine-readable output file ('' disables)")
    args = ap.parse_args(argv)
    n_req = args.requests or (12 if args.reduced else 48)
    max_new = args.max_new or (8 if args.reduced else 32)

    cfg, corpus, engine = build_engine(args.reduced, args.seed)

    # tier → head spread: >= 3 heads always; the standard tier rides the
    # frequency-tiered adaptive head (tail region vocab-sharded whenever a
    # mesh is available), so mixed screened + adaptive traffic shares the
    # engine's cached steps
    standard = "adaptive-sharded" if jax.device_count() > 1 else "adaptive"
    policy = TierPolicy({"realtime": "screened", "standard": standard,
                         "batch": "exact"}, default="screened")
    tiers = ["realtime", "standard", "batch"]
    prompts = corpus.sample_batch(n_req, 16, seed=42)
    requests = []
    for i, p in enumerate(prompts):
        # a slice of sampled traffic rides the same batched steps
        sampled = (i % 6 == 5)
        requests.append(ServeRequest(
            prompt=p, max_new=max_new, latency_tier=tiers[i % 3],
            temperature=0.8 if sampled else None,
            top_p=0.95 if sampled else 1.0))

    engine.serve_batch(requests, policy=policy)          # warmup compiles
    counts0 = engine.compiled_step_counts()
    t0 = time.perf_counter()
    results = engine.serve_batch(requests, policy=policy)
    t_mixed = time.perf_counter() - t0
    counts1 = engine.compiled_step_counts()

    total_tokens = sum(len(r.tokens) for r in results)
    by_head = {}
    for r in results:
        by_head.setdefault(r.head, []).append(r)
    recompiles = {}
    for (head, kind), n in counts1.items():
        d = n - counts0.get((head, kind), 0)
        recompiles[head] = recompiles.get(head, 0) + d

    print(f"\n[serve_mixed] vocab={cfg.vocab_size} requests={n_req} "
          f"max_new={max_new} devices={jax.device_count()}")
    print(f"[serve_mixed] mixed batch: {total_tokens} tokens in "
          f"{t_mixed:.2f}s = {total_tokens / t_mixed:.0f} tok/s, "
          f"{len(by_head)} heads, {engine._cache_size()} cached steps")
    print(f"{'head':<18}{'requests':>9}{'tokens':>8}{'tok/s':>10}"
          f"{'recompiles':>11}")
    per_head_json = {}
    latency = LatencyTracker()
    for head, rs in sorted(by_head.items()):
        # per-head throughput: serve only this head's requests (still warm),
        # pinned via the explicit-head escape hatch
        sub = [dataclasses.replace(r.request, head=head) for r in rs]
        t0 = time.perf_counter()
        engine.serve_batch(sub)
        t_head = time.perf_counter() - t0
        toks = sum(len(r.tokens) for r in rs)
        print(f"{head:<18}{len(rs):>9}{toks:>8}{toks / t_head:>10.0f}"
              f"{recompiles.get(head, 0):>11}")
        per_head_json[head] = {"requests": len(rs), "tokens": toks,
                               "decode_s": t_head,
                               "tokens_per_s": toks / t_head,
                               "recompiles": recompiles.get(head, 0)}
        # batch-mode latency: every request in the sub-batch observes the
        # whole sub-batch's wall time (they finish together)
        for _ in rs:
            latency.record(t_head)
    new_compiles = sum(max(0, v) for v in recompiles.values())
    print(f"[serve_mixed] recompiles caused by the mixed batch: "
          f"{new_compiles} (expected 0)")
    if args.json:
        path = update_bench_json("serve_mixed", {
            "devices": jax.device_count(), "vocab": cfg.vocab_size,
            "requests": n_req, "max_new": max_new, "reduced": args.reduced,
            "total_tokens": total_tokens, "mixed_s": t_mixed,
            "tokens_per_s": total_tokens / t_mixed,
            "recompiles": new_compiles, "latency": latency.snapshot(),
            "per_head": per_head_json,
        }, path=args.json)
        print(f"[serve_mixed] wrote {path}")
    return 0


if __name__ == "__main__":
    enable_compile_cache()
    raise SystemExit(main())
