"""Continuous-batching scheduler subsystem: PagedDecodeStream join-at-step
parity (LSTM + transformer KV-cache, single- and multi-device), scheduler
drain bit-parity vs serve_batch with zero recompiles after warmup,
admission control against flops budgets (reject / downgrade, typed
results, tier deadlines), preemption of over-deadline low-tier work, the
RequestQueue stamps, and compiled_step_counts telemetry under mixed
scheduler traffic."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import L2SConfig, TrainConfig, get_config
from repro.core import collect_contexts, fit_l2s
from repro.data import ZipfMarkovCorpus, make_lm_batches
from repro.launch.steps import make_train_step
from repro.models import build_model
from repro.optim import adamw_init
from repro.serving import (AdmissionRejected, BudgetAdmission,
                           ContinuousScheduler, DecodeEngine, PagePool,
                           ServeRequest, ServeResult, StaticPolicy,
                           TierPolicy)
from repro.serving.scheduler import (AdmissionDecision, RequestQueue,
                                     SchedulerLoad, TIER_DEADLINES)


class FakeClock:
    """Deterministic monotonic clock: advances ``dt`` per read."""

    def __init__(self, dt=0.0):
        self.t = 0.0
        self.dt = dt

    def __call__(self):
        self.t += self.dt
        return self.t


@pytest.fixture(scope="module")
def trained():
    """Small trained LSTM + fitted screen shared by the scheduler tests."""
    cfg = get_config("ptb-small-lstm").reduced()
    m = build_model(cfg)
    params = m.init(jax.random.key(0), dtype=jnp.float32)
    corpus = ZipfMarkovCorpus(cfg.vocab_size, branching=32, seed=3)
    tcfg = TrainConfig(lr=2e-3, total_steps=60, warmup_steps=5,
                       remat="none", loss_chunk=None)
    step = jax.jit(make_train_step(m, tcfg))
    opt = adamw_init(params)
    for batch in make_lm_batches(corpus, 60, 8, 32, seed=1):
        params, opt, _ = step(params, opt,
                              {k: jnp.asarray(v) for k, v in batch.items()})
    H, y = collect_contexts(
        m, params, [jnp.asarray(b["tokens"])
                    for b in make_lm_batches(corpus, 8, 8, 32, seed=9)],
        max_vectors=2000)
    st = fit_l2s(H, y, cfg.vocab_size,
                 L2SConfig(num_clusters=16, budget=64, outer_iters=1,
                           sgd_steps=50))
    return cfg, m, params, corpus, st


def _reqs(corpus, n, tiers=("realtime", "standard", "batch"),
          sampled_idx=(), prompt_len=6, max_new0=4, seed=21):
    prompts = corpus.sample_batch(n, prompt_len, seed=seed)
    out = []
    for i in range(n):
        sampled = i in sampled_idx
        out.append(ServeRequest(
            prompt=prompts[i], max_new=max_new0 + (i % 3),
            latency_tier=tiers[i % len(tiers)],
            temperature=0.9 if sampled else None,
            top_p=0.95 if sampled else 1.0, seed=7))
    return out


# -- PagedDecodeStream: join-at-step, bit-parity, fixed shapes ----------------

@pytest.mark.parametrize("arch", ["ptb-small-lstm", "smollm-360m"])
def test_stream_join_mid_decode_matches_solo_generate(arch, page_pool):
    """Requests joining a RUNNING stream — at different ticks, with
    different prompt lengths — decode bit-identically to solo generate.
    Covers both the position-free LSTM state cache and the transformer
    KV cache through the per-row positions of the paged decode step."""
    cfg = get_config(arch).reduced()
    m = build_model(cfg)
    params = m.init(jax.random.key(0), dtype=jnp.float32)
    eng = DecodeEngine(m, params, max_len=32)
    rng = np.random.default_rng(0)
    mk = lambda tp, n: ServeRequest(
        prompt=rng.integers(0, cfg.vocab_size, tp).astype(np.int32),
        max_new=n)
    a, b, c = mk(6, 8), mk(9, 5), mk(6, 1)
    stream = eng.open_paged_stream(page_pool(eng), "exact", width=3)
    stream.join(a, tag="a")
    done = stream.step() + stream.step()        # a is 2 ticks deep
    stream.join(b, tag="b")                     # join-at-step, longer prompt
    done += stream.step()
    stream.join(c, tag="c")                     # max_new=1: done at join
    while stream.n_active:
        done += stream.step()
    done += stream.pop_finished()
    got = {tag: toks for tag, _, toks in done}
    assert set(got) == {"a", "b", "c"}
    for tag, req in (("a", a), ("b", b), ("c", c)):
        solo = eng.generate(req.prompt[None], req.max_new).tokens[0]
        np.testing.assert_array_equal(got[tag], solo)


@pytest.mark.parametrize("case", ["cold", "prefix-hits"])
def test_stream_width1_sampled_reproduces_solo_generate(trained, page_pool,
                                                        case):
    """The documented sampling contract: an isolated width-1 sampled stream
    advances the same PRNG chain as generate(seed), so its draws match.
    ``prefix-hits``: requests sharing a two-page prefix, each on a fresh
    stream over one pool, so the later joins resume from the radix cache's
    state snapshots — and still draw what generate draws."""
    cfg, m, params, corpus, st = trained
    eng = DecodeEngine(m, params, screen=st.screen, max_len=30)
    if case == "cold":
        head, temperature, pool = "screened", 0.9, page_pool(eng)
        reqs = [ServeRequest(prompt=corpus.sample_batch(1, 6, seed=4)[0],
                             max_new=5)]
    else:
        head, temperature, pool = "exact", 0.8, PagePool(64, 5)
        tmpl = corpus.sample_batch(1, 10, seed=13)[0]
        reqs = [ServeRequest(prompt=np.concatenate([tmpl, sfx]), max_new=4)
                for sfx in corpus.sample_batch(3, 3, seed=14)]
    for req in reqs:
        stream = eng.open_paged_stream(pool, head, width=1,
                                       temperature=temperature, top_p=0.95,
                                       seed=11)
        stream.join(req, tag=0)
        done = []
        while stream.n_active:
            done += stream.step()
        solo = eng.generate(req.prompt[None], req.max_new, head=head,
                            temperature=temperature, top_p=0.95,
                            key=jax.random.key(11)).tokens[0]
        np.testing.assert_array_equal(done[0][2], solo)
    if case == "prefix-hits":
        assert pool.radix.tokens_hit == 2 * 10     # both later joins resumed


def test_stream_capacity_and_guards(trained, page_pool):
    cfg, m, params, corpus, st = trained
    eng = DecodeEngine(m, params, max_len=16)
    stream = eng.open_paged_stream(page_pool(eng), "exact", width=2)
    p = corpus.sample_batch(1, 6, seed=1)[0]
    stream.join(ServeRequest(prompt=p, max_new=3))
    stream.join(ServeRequest(prompt=p, max_new=3))
    assert stream.free_slots == 0 and not stream.idle
    with pytest.raises(RuntimeError):
        stream.join(ServeRequest(prompt=p, max_new=3))
    with pytest.raises(ValueError):          # 6 + 20 > max_len 16
        eng.open_paged_stream(page_pool(eng), "exact", width=1).join(
            ServeRequest(prompt=p, max_new=20))
    with pytest.raises(ValueError):
        eng.open_paged_stream(page_pool(eng), "exact", width=0)


def test_unpageable_family_is_refused(page_pool):
    """The scheduler always serves over its page pool: on a family the pool
    cannot page (mamba2's recurrent SSM state) the first request it serves
    raises the pool's NotImplementedError naming the family — it is never
    served on a dense cache instead."""
    cfg = get_config("mamba2-1.3b").reduced()
    m = build_model(cfg)
    params = m.init(jax.random.key(0), dtype=jnp.float32)
    eng = DecodeEngine(m, params, max_len=16)
    sched = ContinuousScheduler(eng, kv_pool=page_pool(eng))
    sched.submit(ServeRequest(prompt=np.arange(4, dtype=np.int32),
                              max_new=2))
    with pytest.raises(NotImplementedError, match=cfg.family):
        sched.step()
    assert sched.results() == [] and sched.stats.tokens == 0


# -- ContinuousScheduler: drain parity + compile discipline -------------------

def test_scheduler_drain_matches_serve_batch(trained, page_pool):
    """The acceptance bar: draining a fixed request set through the
    scheduler yields greedy results bit-identical to one serve_batch call,
    and a second drain adds ZERO step executables (compiled_step_counts)."""
    cfg, m, params, corpus, st = trained
    eng = DecodeEngine(m, params, screen=st.screen, max_len=30,
                       head_kwargs=dict(rho=cfg.d_model,
                                        n_top=cfg.vocab_size))
    policy = TierPolicy({"realtime": "screened", "standard": "svd",
                         "batch": "exact"}, default="exact")
    reqs = _reqs(corpus, 7)
    ref = eng.serve_batch(reqs, policy=policy)

    sched = ContinuousScheduler(eng, policy=policy, max_slots=3,
                                kv_pool=page_pool(eng))
    out = sched.serve(reqs)
    assert len(out) == len(reqs)
    assert {r.head for r in out} == {"screened", "svd", "exact"}
    for r, e in zip(out, ref):
        assert isinstance(r, ServeResult)
        assert r.request is e.request
        assert r.head == e.head
        np.testing.assert_array_equal(r.tokens, e.tokens)

    counts0 = eng.compiled_step_counts()
    out2 = ContinuousScheduler(eng, policy=policy, max_slots=3,
                               kv_pool=page_pool(eng)).serve(reqs)
    assert eng.compiled_step_counts() == counts0      # zero recompiles
    for r, e in zip(out2, ref):
        np.testing.assert_array_equal(r.tokens, e.tokens)
    assert sched.stats.completed == len(reqs)
    assert sched.stats.rejected == 0


@pytest.mark.multidevice
def test_scheduler_drain_parity_with_sharded_head(trained, multidevice,
                                                  page_pool):
    """The multidevice acceptance case: a *-sharded head in the scheduler
    mix — join-at-step over the mesh-aware cached step, bit-identical to
    serve_batch, zero recompiles on the second drain."""
    cfg, m, params, corpus, st = trained
    eng = DecodeEngine(m, params, screen=st.screen, max_len=30,
                       head_kwargs=dict(n_shards=8))
    policy = TierPolicy({"realtime": "screened",
                         "standard": "screened-sharded",
                         "batch": "exact"}, default="exact")
    reqs = _reqs(corpus, 6)
    ref = eng.serve_batch(reqs, policy=policy)
    out = ContinuousScheduler(eng, policy=policy, max_slots=2,
                              kv_pool=page_pool(eng)).serve(reqs)
    assert {r.head for r in out} == {"screened", "screened-sharded", "exact"}
    assert eng.resolve_head("screened-sharded").n_shards == 8
    for r, e in zip(out, ref):
        assert r.head == e.head
        np.testing.assert_array_equal(r.tokens, e.tokens)
    counts0 = eng.compiled_step_counts()
    out2 = ContinuousScheduler(eng, policy=policy, max_slots=2,
                               kv_pool=page_pool(eng)).serve(reqs)
    assert eng.compiled_step_counts() == counts0
    for r, e in zip(out2, ref):
        np.testing.assert_array_equal(r.tokens, e.tokens)


def test_compiled_step_counts_under_mixed_scheduler_traffic(trained,
                                                            page_pool):
    """The telemetry satellite: mixed greedy + sampled scheduler traffic
    across heads surfaces exactly one (head, kind) entry per combination
    in compiled_step_counts — each head's paged LSTM join executable is a
    cached step of kind "join" beside its decode steps — and repeat drains
    leave every count flat."""
    cfg, m, params, corpus, st = trained
    eng = DecodeEngine(m, params, screen=st.screen, max_len=30)
    policy = TierPolicy({"realtime": "screened"}, default="exact")
    reqs = _reqs(corpus, 6, tiers=("realtime", "standard"),
                 sampled_idx=(5,))
    ContinuousScheduler(eng, policy=policy, max_slots=2,
                        kv_pool=page_pool(eng)).serve(reqs)
    counts = eng.compiled_step_counts()
    assert set(counts) == {("screened", "greedy"), ("exact", "greedy"),
                           ("exact", "sample"), ("screened", "join"),
                           ("exact", "join")}
    assert all(n >= 1 for n in counts.values())
    ContinuousScheduler(eng, policy=policy, max_slots=2,
                        kv_pool=page_pool(eng)).serve(reqs)
    assert eng.compiled_step_counts() == counts


def test_scheduler_interleaves_mixed_prompt_lengths(trained, page_pool):
    """Streams prefill per request, so one lane serves mixed prompt
    lengths — which serve_batch would split into separate groups."""
    cfg, m, params, corpus, st = trained
    eng = DecodeEngine(m, params, max_len=30)
    long = corpus.sample_batch(2, 9, seed=5)
    short = corpus.sample_batch(2, 5, seed=6)
    reqs = [ServeRequest(prompt=p, max_new=4) for p in (*long, *short)]
    out = ContinuousScheduler(eng, max_slots=4,
                              kv_pool=page_pool(eng)).serve(reqs)
    assert all(r.group_size == 4 for r in out)
    for r in out:
        solo = eng.generate(r.request.prompt[None], 4).tokens[0]
        np.testing.assert_array_equal(r.tokens, solo)


# -- admission control --------------------------------------------------------

def test_budget_admission_rejects_over_budget_typed(trained, page_pool):
    """Traffic past the flops budget: over-budget submissions come back as
    typed AdmissionRejected with the budget in the reason, while admitted
    traffic completes within its tier deadline."""
    cfg, m, params, corpus, st = trained
    eng = DecodeEngine(m, params, max_len=30)
    flops = eng.head_catalog(["exact"])["exact"]["flops_per_query"]
    clk = FakeClock(dt=1e-4)                  # well inside "standard" 1.0s
    sched = ContinuousScheduler(
        eng, policy=StaticPolicy("exact"),
        admission=BudgetAdmission(flops_budget=2.5 * flops),
        max_slots=4, clock=clk, kv_pool=page_pool(eng))
    reqs = [ServeRequest(prompt=p, max_new=3, latency_tier="standard")
            for p in corpus.sample_batch(5, 6, seed=8)]
    out = sched.serve(reqs)
    kinds = [type(r).__name__ for r in out]
    assert kinds == ["ServeResult", "ServeResult"] + ["AdmissionRejected"] * 3
    for r in out[2:]:
        assert r.stage == "admission"
        assert "flops budget exhausted" in r.reason
        assert r.tokens is None
    assert sched.stats.rejected == 3 and sched.stats.admitted == 2
    assert sched.stats.completed == 2
    # admitted traffic met the standard-tier deadline (fake-clock time)
    assert sched.stats.deadline_met == 2 and sched.stats.deadline_missed == 0
    assert sched.stats.latency.p95 < TIER_DEADLINES["standard"]


def test_budget_admission_downgrades_to_cheaper_eligible_head():
    """Unit-level: routed head over budget → cheapest eligible head that
    fits is a DOWNGRADE; accuracy_floor=1.0 forbids it → typed reject;
    queue_limit rejects regardless of flops."""
    catalog = {
        "exact": {"flops_per_query": 1e6, "memory_bytes": 4_000_000,
                  "n_shards": None, "supports_sampling": True},
        "screened": {"flops_per_query": 5e4, "memory_bytes": 4_400_000,
                     "n_shards": None, "supports_sampling": True},
    }
    adm = BudgetAdmission(flops_budget=1e5)
    req = ServeRequest(prompt=np.arange(4), max_new=2)
    d = adm.admit(req, "exact", catalog, SchedulerLoad(flops_in_flight=0))
    assert (d.action, d.head) == ("downgrade", "screened")
    assert "rerouted exact -> screened" in d.reason
    exact_only = ServeRequest(prompt=np.arange(4), max_new=2,
                              accuracy_floor=1.0)
    d = adm.admit(exact_only, "exact", catalog, SchedulerLoad())
    assert d.action == "reject" and "budget exhausted" in d.reason
    roomy = BudgetAdmission(flops_budget=1e7)
    d = roomy.admit(exact_only, "exact", catalog, SchedulerLoad())
    assert (d.action, d.head) == ("accept", "exact")
    limited = BudgetAdmission(queue_limit=2)
    d = limited.admit(req, "exact", catalog, SchedulerLoad(queued=2))
    assert d.action == "reject" and "queue full" in d.reason
    assert isinstance(d, AdmissionDecision)


def test_budget_admission_never_admits_nan_cost_heads():
    """ISSUE 7 NaN-cost regression: with a flops budget in force, a head
    whose flops_per_query is NaN (documented "unmodeled") must never be
    admitted or offered as a downgrade — pre-fix it was charged 0.0 and
    rode the budget for free, preferred as the "cheapest" stand-in."""
    catalog = {
        "nan-head": {"flops_per_query": float("nan"), "memory_bytes": 1,
                     "n_shards": None, "supports_sampling": True},
        "exact": {"flops_per_query": 1e6, "memory_bytes": 4_000_000,
                  "n_shards": None, "supports_sampling": True},
    }
    req = ServeRequest(prompt=np.arange(4), max_new=2)
    adm = BudgetAdmission(flops_budget=2e6, accuracy={"nan-head": 0.99})
    # a request ROUTED to the NaN head gets rerouted to a modeled head
    d = adm.admit(req, "nan-head", catalog, SchedulerLoad())
    assert (d.action, d.head) == ("downgrade", "exact")
    # budget nearly spent: exact no longer fits, and the NaN head must NOT
    # be the downgrade (pre-fix: admitted at charge 0.0)
    d = adm.admit(req, "exact", catalog,
                  SchedulerLoad(flops_in_flight=1.5e6))
    assert d.action == "reject" and d.head is None
    assert "budget exhausted" in d.reason
    # only the NaN head exists → typed reject naming the unmodeled cost
    d = adm.admit(req, "nan-head", {"nan-head": catalog["nan-head"]},
                  SchedulerLoad())
    assert d.action == "reject" and "unmodeled" in d.reason
    # without a flops budget the NaN head is admissible (nothing to charge)
    lim = BudgetAdmission(queue_limit=4, accuracy={"nan-head": 0.99})
    d = lim.admit(req, "nan-head", catalog, SchedulerLoad())
    assert (d.action, d.head) == ("accept", "nan-head")


def test_budget_admission_downgrade_end_to_end(trained, page_pool):
    """Integration: the policy routes everything to exact but lists
    screened as a candidate; a budget sized for one exact + change
    reroutes the overflow onto the (much cheaper) screened head, and the
    downgraded requests still complete. The downgrade universe is exactly
    the policy's candidate list — nothing admission discovered by
    accident."""
    cfg, m, params, corpus, st = trained
    eng = DecodeEngine(m, params, screen=st.screen, max_len=30)
    cat = eng.head_catalog(["exact", "screened"])
    assert cat["screened"]["flops_per_query"] < cat["exact"]["flops_per_query"]
    sched = ContinuousScheduler(
        eng, policy=TierPolicy({"never": "screened"}, default="exact"),
        admission=BudgetAdmission(
            flops_budget=1.5 * cat["exact"]["flops_per_query"]),
        max_slots=4, kv_pool=page_pool(eng))
    reqs = [ServeRequest(prompt=p, max_new=3)
            for p in corpus.sample_batch(3, 6, seed=12)]
    out = sched.serve(reqs)
    assert [r.head for r in out] == ["exact", "screened", "screened"]
    assert all(isinstance(r, ServeResult) for r in out)
    assert sched.stats.downgraded == 2
    for r in out:                             # downgraded decodes are real
        solo = eng.generate(r.request.prompt[None], 3, head=r.head).tokens[0]
        np.testing.assert_array_equal(r.tokens, solo)


def test_memory_budget_excludes_heads_from_admission():
    catalog = {
        "big": {"flops_per_query": 1e4, "memory_bytes": 8_000_000,
                "n_shards": None, "supports_sampling": True},
        "big-sharded": {"flops_per_query": 2e4, "memory_bytes": 8_000_000,
                        "n_shards": 8, "supports_sampling": True},
    }
    adm = BudgetAdmission(memory_budget_bytes=2_000_000)
    req = ServeRequest(prompt=np.arange(4), max_new=2)
    d = adm.admit(req, "big", catalog, SchedulerLoad())
    # the unsharded head busts the per-device budget; the sharded variant
    # divides by n_shards and fits
    assert (d.action, d.head) == ("downgrade", "big-sharded")


# -- preemption ---------------------------------------------------------------

def test_preempts_over_deadline_low_tier_for_waiting_realtime(trained,
                                                              page_pool):
    """Two batch-tier hogs fill the only stream; once their deadline lapses
    and a realtime request is starving, exactly ONE hog is preempted (typed
    result, partial tokens) and the realtime request completes."""
    cfg, m, params, corpus, st = trained
    eng = DecodeEngine(m, params, max_len=40)
    clk = FakeClock()
    sched = ContinuousScheduler(
        eng, policy=StaticPolicy("exact"), max_slots=2, max_streams=1,
        deadlines={"batch": 0.5, "realtime": 10.0, "standard": 1.0},
        clock=clk, kv_pool=page_pool(eng))
    prompts = corpus.sample_batch(3, 6, seed=2)
    sched.submit(ServeRequest(prompt=prompts[0], max_new=20,
                              latency_tier="batch"))
    sched.submit(ServeRequest(prompt=prompts[1], max_new=20,
                              latency_tier="batch"))
    sched.step()                              # hogs placed and running
    clk.t = 1.0                               # past the batch deadline
    sched.submit(ServeRequest(prompt=prompts[2], max_new=3,
                              latency_tier="realtime"))
    out = sched.drain()
    assert [type(r).__name__ for r in out] == \
        ["AdmissionRejected", "ServeResult", "ServeResult"]
    pre = out[0]
    assert pre.stage == "preempt" and "preempted" in pre.reason
    assert pre.head == "exact" and 1 <= len(pre.tokens) < 20
    assert len(out[1].tokens) == 20           # the surviving hog finished
    assert len(out[2].tokens) == 3            # realtime served
    assert sched.stats.preempted == 1
    # the preempted prefix is the real decode up to the eviction point
    solo = eng.generate(prompts[0][None], 20).tokens[0]
    np.testing.assert_array_equal(pre.tokens, solo[:len(pre.tokens)])


def test_preempts_deadline_less_batch_tier_by_default(trained, page_pool):
    """Default TIER_DEADLINES: "batch" work has NO deadline — that means
    best-effort, not immune. A starving realtime request displaces it
    without any clock advance."""
    cfg, m, params, corpus, st = trained
    eng = DecodeEngine(m, params, max_len=40)
    sched = ContinuousScheduler(eng, policy=StaticPolicy("exact"),
                                max_slots=2, max_streams=1,
                                clock=FakeClock(), kv_pool=page_pool(eng))
    prompts = corpus.sample_batch(3, 6, seed=9)
    sched.submit(ServeRequest(prompt=prompts[0], max_new=20,
                              latency_tier="batch"))
    sched.submit(ServeRequest(prompt=prompts[1], max_new=20,
                              latency_tier="batch"))
    sched.step()
    sched.submit(ServeRequest(prompt=prompts[2], max_new=3,
                              latency_tier="realtime"))
    out = sched.drain()
    assert sched.stats.preempted == 1
    assert isinstance(out[0], AdmissionRejected) and out[0].stage == "preempt"
    assert len(out[1].tokens) == 20 and len(out[2].tokens) == 3


def test_no_useless_preemption_on_signature_mismatch(trained, page_pool):
    """Eviction must HELP the waiter: a sampled request that can never join
    the greedy stream (and whose eviction would not idle the lane — a
    non-preemptable realtime job shares it) must not cost the over-deadline
    victim its partial decode."""
    cfg, m, params, corpus, st = trained
    eng = DecodeEngine(m, params, max_len=40)
    clk = FakeClock()
    sched = ContinuousScheduler(
        eng, policy=StaticPolicy("exact"), max_slots=2, max_streams=1,
        deadlines={"standard": 0.5, "realtime": 100.0, "batch": 100.0},
        clock=clk, kv_pool=page_pool(eng))
    prompts = corpus.sample_batch(3, 6, seed=14)
    sched.submit(ServeRequest(prompt=prompts[0], max_new=12,
                              latency_tier="standard"))
    sched.submit(ServeRequest(prompt=prompts[1], max_new=12,
                              latency_tier="realtime"))
    sched.step()
    clk.t = 1.0                               # standard hog now over-deadline
    sched.submit(ServeRequest(prompt=prompts[2], max_new=2,
                              latency_tier="realtime", temperature=0.8,
                              seed=5))        # needs a NEW (sample) stream
    out = sched.drain()
    assert sched.stats.preempted == 0         # eviction would help nobody
    assert all(isinstance(r, ServeResult) for r in out)
    assert [len(r.tokens) for r in out] == [12, 12, 2]


def test_preemption_fires_despite_unrelated_placements(trained, page_pool):
    """Per-waiter gating: a placement in some OTHER lane the same tick must
    not suppress preemption for a request starving on a full lane."""
    cfg, m, params, corpus, st = trained
    eng = DecodeEngine(m, params, screen=st.screen, max_len=40)
    clk = FakeClock()
    sched = ContinuousScheduler(
        eng, max_slots=1, max_streams=4,
        deadlines={"standard": 0.5, "realtime": 100.0, "batch": 100.0},
        clock=clk, kv_pool=page_pool(eng))
    prompts = corpus.sample_batch(3, 6, seed=15)
    # hog fills the engine-default (exact) greedy lane
    sched.submit(ServeRequest(prompt=prompts[0], max_new=15,
                              latency_tier="standard"))
    sched.step()
    clk.t = 1.0                               # hog over-deadline
    # same tick: an unrelated screened request (placeable, new lane) AND a
    # starving realtime request for the full exact lane
    sched.submit(ServeRequest(prompt=prompts[1], max_new=2,
                              head="screened"))
    sched.submit(ServeRequest(prompt=prompts[2], max_new=2,
                              latency_tier="realtime"))
    sched.step()                              # places screened; must ALSO preempt
    assert sched.stats.preempted == 1
    out = sched.drain()
    assert isinstance(out[0], AdmissionRejected)
    assert len(out[2].tokens) == 2


def test_preemption_freed_slot_goes_to_the_starving_waiter(trained, page_pool):
    """No cascade: with [batchA, batchB, realtime] queued on one width-1
    lane, exactly ONE batch request is preempted — the freed slot goes to
    the realtime waiter (priority placement), not FIFO to batchB for
    stage 3 to evict again."""
    cfg, m, params, corpus, st = trained
    eng = DecodeEngine(m, params, max_len=40)
    sched = ContinuousScheduler(eng, policy=StaticPolicy("exact"),
                                max_slots=1, max_streams=1,
                                clock=FakeClock(), kv_pool=page_pool(eng))
    prompts = corpus.sample_batch(3, 6, seed=16)
    sched.submit(ServeRequest(prompt=prompts[0], max_new=20,
                              latency_tier="batch"))
    sched.submit(ServeRequest(prompt=prompts[1], max_new=20,
                              latency_tier="batch"))
    sched.step()                              # batchA running, batchB queued
    sched.submit(ServeRequest(prompt=prompts[2], max_new=3,
                              latency_tier="realtime"))
    out = sched.drain()
    assert sched.stats.preempted == 1         # batchA only — no cascade
    assert isinstance(out[0], AdmissionRejected)
    assert isinstance(out[1], ServeResult) and len(out[1].tokens) == 20
    assert isinstance(out[2], ServeResult) and len(out[2].tokens) == 3


def test_admission_downgrade_is_submission_order_independent(trained,
                                                             page_pool):
    """The downgrade universe is the policy's full candidate list, loaded
    before the FIRST admission — an explicit-head request submitted first
    must reach the same decision as one submitted after routed traffic."""
    cfg, m, params, corpus, st = trained
    eng = DecodeEngine(m, params, screen=st.screen, max_len=30)
    cat = eng.head_catalog(["exact", "screened"])
    policy = TierPolicy({"realtime": "screened"}, default="screened")
    p = corpus.sample_batch(1, 6, seed=17)[0]
    # budget below exact: the explicit-exact request must downgrade to
    # screened even as the very first submission
    sched = ContinuousScheduler(
        eng, policy=policy,
        admission=BudgetAdmission(
            flops_budget=0.5 * cat["exact"]["flops_per_query"]),
        max_slots=2, kv_pool=page_pool(eng))
    out = sched.serve([ServeRequest(prompt=p, max_new=2, head="exact")])
    assert isinstance(out[0], ServeResult) and out[0].head == "screened"
    assert sched.stats.downgraded == 1


def test_preemption_picks_lowest_tier_victim_first(trained, page_pool):
    """In one full lane holding an over-deadline standard request AND a
    deadline-less batch request, the batch work (no completion promise)
    yields — the merely-late standard request keeps its decode."""
    cfg, m, params, corpus, st = trained
    eng = DecodeEngine(m, params, max_len=40)
    clk = FakeClock()
    sched = ContinuousScheduler(
        eng, policy=StaticPolicy("exact"), max_slots=2, max_streams=1,
        deadlines={"standard": 0.5, "realtime": 100.0,
                   "batch": math.inf}, clock=clk, kv_pool=page_pool(eng))
    prompts = corpus.sample_batch(3, 6, seed=18)
    sched.submit(ServeRequest(prompt=prompts[0], max_new=15,
                              latency_tier="standard"))
    sched.submit(ServeRequest(prompt=prompts[1], max_new=15,
                              latency_tier="batch"))
    sched.step()
    clk.t = 1.0                               # standard now over-deadline too
    sched.submit(ServeRequest(prompt=prompts[2], max_new=3,
                              latency_tier="realtime"))
    out = sched.drain()
    assert sched.stats.preempted == 1
    assert isinstance(out[0], ServeResult) and len(out[0].tokens) == 15
    assert isinstance(out[1], AdmissionRejected)      # batch yielded
    assert len(out[2].tokens) == 3


def test_one_eviction_per_signature_per_tick(trained, page_pool):
    """Two same-signature waiters needing a new lane trigger ONE eviction —
    the recycled lane serves both; the second lane's occupant survives."""
    cfg, m, params, corpus, st = trained
    eng = DecodeEngine(m, params, screen=st.screen, max_len=40)
    sched = ContinuousScheduler(eng, max_slots=1, max_streams=2,
                                clock=FakeClock(), kv_pool=page_pool(eng))
    prompts = corpus.sample_batch(4, 6, seed=19)
    # two lanes, each a width-1 batch hog on a distinct signature
    sched.submit(ServeRequest(prompt=prompts[0], max_new=15,
                              latency_tier="batch", head="exact"))
    sched.submit(ServeRequest(prompt=prompts[1], max_new=15,
                              latency_tier="batch", head="screened"))
    sched.step()
    # two realtime SAMPLED waiters sharing one new-lane signature
    for i in (2, 3):
        sched.submit(ServeRequest(prompt=prompts[i], max_new=2,
                                  latency_tier="realtime", temperature=0.8,
                                  seed=5))
    out = sched.drain()
    assert sched.stats.preempted == 1         # one lane freed, not two
    done = [r for r in out if isinstance(r, ServeResult)]
    assert len(done) == 3                     # surviving hog + both sampled


def test_pop_results_consumes_and_ids_stay_monotonic(trained, page_pool):
    cfg, m, params, corpus, st = trained
    eng = DecodeEngine(m, params, max_len=30)
    sched = ContinuousScheduler(eng, max_slots=2, kv_pool=page_pool(eng))
    prompts = corpus.sample_batch(4, 6, seed=20)
    sched.serve([ServeRequest(prompt=p, max_new=2) for p in prompts[:2]])
    first = sched.pop_results()
    assert len(first) == 2
    assert sched.results() == [] and sched.pop_results() == []
    # later submissions still resolve after the pop (monotonic rids)
    out = sched.serve([ServeRequest(prompt=p, max_new=2)
                       for p in prompts[2:]])
    assert len(out) == 2
    assert all(isinstance(r, ServeResult) for r in first + out)
    solo = eng.generate(prompts[3][None], 2).tokens[0]
    np.testing.assert_array_equal(out[1].tokens, solo)


# -- RequestQueue / plumbing --------------------------------------------------

def test_request_queue_stamps_arrival_and_tier_deadline():
    clk = FakeClock()
    q = RequestQueue(clock=clk)
    clk.t = 5.0
    a = q.push(ServeRequest(prompt=np.arange(4), max_new=2,
                            latency_tier="realtime"), "exact", cost=7.0)
    clk.t = 6.0
    b = q.push(ServeRequest(prompt=np.arange(4), max_new=2,
                            latency_tier="batch"), None, cost=3.0)
    assert a.arrival == 5.0
    assert a.deadline == pytest.approx(5.0 + TIER_DEADLINES["realtime"])
    assert b.deadline == math.inf             # batch never expires
    assert a.priority < b.priority
    assert [qr.id for qr in q] == [a.id, b.id]       # FIFO
    assert q.flops_pending == 10.0
    q.remove(a)
    assert len(q) == 1 and q.flops_pending == 3.0


def test_scheduler_rejects_oversized_request_at_submit(trained, page_pool):
    cfg, m, params, corpus, st = trained
    eng = DecodeEngine(m, params, max_len=10)
    sched = ContinuousScheduler(eng, kv_pool=page_pool(eng))
    with pytest.raises(ValueError):
        sched.submit(ServeRequest(prompt=corpus.sample_batch(1, 6, seed=1)[0],
                                  max_new=20))
