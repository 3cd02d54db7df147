"""Mixed-traffic serving demo: one DecodeEngine, many ServeRequests, a
RoutingPolicy deciding per request which softmax head decodes it.

Run: PYTHONPATH=src python examples/serve_batch.py            # full demo
     PYTHONPATH=src python examples/serve_batch.py --reduced  # CI smoke
"""
import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import L2SConfig, TrainConfig, get_config
from repro.core import collect_contexts, fit_l2s
from repro.data import ZipfMarkovCorpus, make_lm_batches
from repro.launch.steps import make_train_step
from repro.models import build_model
from repro.optim import adamw_init
from repro.serving import (AdmissionRejected, BudgetAdmission,
                           ContinuousScheduler, CostAwarePolicy,
                           DecodeEngine, PagePool, ServeRequest, TierPolicy)

ap = argparse.ArgumentParser()
ap.add_argument("--reduced", action="store_true",
                help="tiny model + short decode for CI smoke runs")
args = ap.parse_args()

if args.reduced:
    VOCAB, D, STEPS, BATCH, NEW = 600, 64, 60, 8, 8
else:
    VOCAB, D, STEPS, BATCH, NEW = 3000, 128, 250, 16, 48

cfg = dataclasses.replace(get_config("ptb-small-lstm"), vocab_size=VOCAB,
                          d_model=D, dtype="float32")
model = build_model(cfg)
params = model.init(jax.random.key(0), dtype=jnp.float32)
corpus = ZipfMarkovCorpus(VOCAB, branching=64, seed=0)
tcfg = TrainConfig(lr=2e-3, total_steps=STEPS, warmup_steps=20,
                   remat="none", loss_chunk=None)
step_fn = jax.jit(make_train_step(model, tcfg))
opt = adamw_init(params)
print("training ...")
for batch in make_lm_batches(corpus, STEPS, 16, 64, seed=1):
    params, opt, _ = step_fn(params, opt,
                             {k: jnp.asarray(v) for k, v in batch.items()})

H, y = collect_contexts(
    model, params,
    [jnp.asarray(b["tokens"]) for b in make_lm_batches(corpus, 30, 16, 64,
                                                       seed=9)],
    max_vectors=20_000)
state = fit_l2s(H, y, VOCAB, L2SConfig(num_clusters=100 if not args.reduced
                                       else 16,
                                       budget=150 if not args.reduced else 48,
                                       outer_iters=2, sgd_steps=150))
engine = DecodeEngine(model, params, screen=state.screen,
                      max_len=16 + NEW)

# -- mixed traffic: every request carries its own latency tier / accuracy
#    floor, and the policy resolves each to a head. One engine, one batch.
prompts = corpus.sample_batch(BATCH, 16, seed=11)
requests = []
for i, p in enumerate(prompts):
    if i % 4 == 0:       # quality tier: caller demands exact decode
        requests.append(ServeRequest(prompt=p, max_new=NEW,
                                     latency_tier="batch",
                                     accuracy_floor=1.0))
    elif i % 4 == 1:     # explicit override: escape hatch past the policy
        requests.append(ServeRequest(prompt=p, max_new=NEW, head="exact"))
    else:                # latency tier: cheapest acceptable head
        requests.append(ServeRequest(prompt=p, max_new=NEW,
                                     latency_tier="realtime"))

policy = CostAwarePolicy(["screened", "exact"])
engine.serve_batch(requests, policy=policy)          # warmup compiles
t0 = time.perf_counter()
results = engine.serve_batch(requests, policy=policy)
t_mixed = time.perf_counter() - t0
by_head = {}
for r in results:
    by_head.setdefault(r.head, []).append(r)
total_tokens = sum(len(r.tokens) for r in results)
print(f"mixed batch : {total_tokens / t_mixed:8.0f} tok/s over "
      f"{len(results)} requests -> "
      + ", ".join(f"{k}×{len(v)}" for k, v in sorted(by_head.items())))

# routed results agree with solo exact decode on most tokens
agree = np.mean([
    (r.tokens == engine.generate(r.request.prompt[None], r.request.max_new,
                                 head="exact").tokens[0]).mean()
    for r in results])
print(f"agreement vs exact: {agree:.3f}  "
      f"(screened requests trade a little fidelity for speed)")

# same engine still answers tier-mapped traffic with zero new compiles
tier_policy = TierPolicy({"realtime": "screened", "batch": "exact"},
                         default="screened")
res2 = engine.serve_batch(requests, policy=tier_policy)
print(f"tier policy routes: "
      + ", ".join(sorted({r.head for r in res2}))
      + f"; cached steps: {engine._cache_size()}")

# -- continuous batching: the same traffic as a live stream ------------------
#    Requests are submitted one at a time; the scheduler admits each against
#    a flops budget from the head catalog (over-budget arrivals come back as
#    typed AdmissionRejected results — here the budget is roomy), joins them
#    into running fixed-width decode streams at sequence boundaries, and
#    retires them as they finish. Each stream slot holds its request's cache
#    as pages of a PagePool: two pages of max_len per request (its own, and
#    the copy its first decode write makes of the prompt page the radix
#    cache keeps) let every request hold its pages at once. Greedy tokens
#    are bit-identical to the serve_batch results above.
catalog = engine.head_catalog(("screened", "exact"))
sched = ContinuousScheduler(
    engine, policy=tier_policy,
    admission=BudgetAdmission(
        flops_budget=8 * max(m["flops_per_query"] for m in catalog.values())),
    max_slots=4,
    kv_pool=PagePool(num_pages=1 + 2 * len(requests),
                     page_size=engine.max_len))
t0 = time.perf_counter()
res3 = sched.serve(requests)
t_sched = time.perf_counter() - t0
snap = sched.stats.snapshot()
served = [r for r in res3 if not isinstance(r, AdmissionRejected)]
for r2, r3 in zip(res2, res3):
    if isinstance(r3, AdmissionRejected) or r3.request.temperature is not None:
        continue
    if r3.head == r2.head:                # admission may have downgraded
        assert np.array_equal(r2.tokens, r3.tokens)   # continuous == batch
print(f"scheduler   : {snap['tokens'] / t_sched:8.0f} tok/s over "
      f"{len(served)} requests (admitted {snap['admitted']}, rejected "
      f"{snap['rejected']}, downgraded {snap['downgraded']}); "
      f"p50 latency {snap['latency']['p50_s'] * 1e3:.0f}ms, "
      f"p95 {snap['latency']['p95_s'] * 1e3:.0f}ms; "
      f"cached steps: {engine._cache_size()}")
