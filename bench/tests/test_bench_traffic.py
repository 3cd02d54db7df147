"""The traffic generator is a function of the seed, and the due-time book
charges a stall to every request that came due during it."""
from __future__ import annotations

import hashlib
from collections import Counter

import numpy as np
import pytest

from bench import harness, traffic

WORK = {"arrival": {"law": "poisson", "rate": 50.0}, "ramp_s": 1.0,
        "prompt_len": {"law": "lognormal", "median": 20, "sigma": 0.45,
                       "min": 8, "max": 48},
        "output_len": {"law": "uniform", "min": 1, "max": 4}}
BIG = 2 ** 31 + 4242           # seeds may exceed 32 bits


def schedule(seed, work=WORK):
    return traffic.plan(work, traffic.Corpus(1000, seed), seed, 10.0)


def test_same_seed_same_schedule():
    a, b = schedule(BIG), schedule(BIG)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x.due == y.due and x.max_new == y.max_new
        assert np.array_equal(x.prompt, y.prompt)


def test_other_seed_same_work_other_order():
    a, b = schedule(BIG), schedule(BIG + 1)
    assert [p.due for p in a] != [p.due for p in b]
    window = lambda s: [p for p in s if 1.0 <= p.due < 11.0]
    wa, wb = window(a), window(b)
    assert len(wa) == len(wb) == 500          # rate 50 over 10 s, exactly
    assert Counter(len(p.prompt) for p in wa) == \
        Counter(len(p.prompt) for p in wb)
    assert Counter(p.max_new for p in wa) == Counter(p.max_new for p in wb)
    assert wa[0].due == pytest.approx(1.0) and wb[0].due == pytest.approx(1.0)


def test_strata_hold_the_same_work_for_every_seed():
    work = dict(WORK, stratum_s=2.0)
    a, b = schedule(BIG, work), schedule(BIG + 1, work)
    for s0 in np.arange(1.0, 11.0, 2.0):
        sa = [p for p in a if s0 <= p.due < s0 + 2.0]
        sb = [p for p in b if s0 <= p.due < s0 + 2.0]
        assert len(sa) == len(sb) == 100       # rate 50 over 2 s
        assert Counter(len(p.prompt) for p in sa) == \
            Counter(len(p.prompt) for p in sb)
        assert Counter(p.max_new for p in sa) == \
            Counter(p.max_new for p in sb)
    assert [p.due for p in a] != [p.due for p in b]


def test_lengths_follow_the_law():
    law = traffic.Lengths.from_dict(WORK["prompt_len"])
    s = law.fixed_set(1001)
    assert s.min() >= 8 and s.max() <= 48 and np.median(s) == 20
    u = traffic.Lengths.from_dict(WORK["output_len"]).fixed_set(400)
    assert Counter(u.tolist()) == {1: 100, 2: 100, 3: 100, 4: 100}


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += max(s, 1e-4)


class FakeScheduler:
    """Serves one request per tick, ``tick_s`` each; the tick that starts
    at or after ``stall_at`` takes ``stall_s`` instead, once."""

    def __init__(self, clock, tick_s=0.002, stall_at=None, stall_s=0.0):
        from repro.serving import ServeResult
        self.ServeResult = ServeResult
        self.clock, self.tick_s = clock, tick_s
        self.stall_at, self.stall_s = stall_at, stall_s
        self.queue, self.done, self.fault_rids = [], [], set()
        self.rid = 0

    @property
    def busy(self):
        return bool(self.queue)

    def submit(self, req):
        self.queue.append(req)
        self.rid += 1
        return self.rid - 1

    def step(self):
        if self.stall_at is not None and self.clock.t >= self.stall_at:
            self.clock.t += self.stall_s
            self.stall_at = None
        else:
            self.clock.t += self.tick_s
        req = self.queue.pop(0)
        self.done.append(self.ServeResult(
            tokens=np.zeros(req.max_new, np.int32), head="exact",
            request=req))

    def pop_results(self):
        out, self.done = self.done, []
        return out


class FakeSystem:
    head = "exact"

    def request(self, p):
        from repro.serving import ServeRequest
        return ServeRequest(prompt=p.prompt, max_new=p.max_new)


def drive(stall_at=None, stall_s=0.0):
    clock = FakeClock()
    sched = FakeScheduler(clock, stall_at=stall_at, stall_s=stall_s)
    plan = schedule(BIG)
    drv = harness.Driver(FakeSystem(), sched, plan, seconds=4.0, ramp_s=1.0,
                         drain_s=5.0, clock=clock, sleep=clock.sleep)
    return drv.run()


def test_stall_raises_latency_of_later_requests_and_shows_as_lateness():
    calm = drive()
    stalled = drive(stall_at=100.0 + 2.0, stall_s=0.5)
    assert calm.lateness()["max_s"] < 0.01
    assert stalled.lateness()["max_s"] > 0.4
    # requests due during the stall wait for it: latency from the due time
    hit = [r for r in stalled.due_in_window() if 102.0 < r.due < 102.4]
    assert hit and all(r.latency > 0.1 for r in hit)
    assert stalled.latency_quantile(0.95, 200.0) > \
        calm.latency_quantile(0.95, 200.0)
    # the same requests, the same tokens: only the clock moved
    assert len(calm.due_in_window()) == len(stalled.due_in_window())
    assert calm.tokens_per_s() > 0


@pytest.mark.parametrize("seed", [0, 1, BIG, 2 ** 40 + 3])
def test_seed32_takes_any_whole_number(seed):
    k = traffic.seed32(seed)
    assert 0 <= k < 2 ** 32 and k == traffic.seed32(seed)


def test_window_rate_counts_tokens_emitted_between_the_two_reads():
    from bench.harness import window_tokens_per_s
    win = {"t_open": 10.0, "t_close": 12.5, "tokens0": 400, "tokens1": 900}
    assert window_tokens_per_s(win) == 200.0


# -- closed loop --------------------------------------------------------------

CLOSED = dict(WORK, arrival={"law": "closed", "clients": 8},
              output_len={"law": "lognormal", "median": 24, "sigma": 0.4,
                          "min": 8, "max": 48})


def closed_plan(seed, work=CLOSED):
    return traffic.plan(work, traffic.Corpus(1000, seed), seed, 10.0)


def test_closed_plan_is_seeded_and_repeatable():
    a, b = closed_plan(BIG), closed_plan(BIG)
    assert isinstance(a, traffic.ClosedPlan) and a.clients == 8
    # drawn in another order, the same requests
    for c, k in [(3, 5), (0, 0), (7, 2), (3, 0)]:
        x, y = a.request(c, k), b.request(c, k)
        assert x.idx == y.idx == 8 * k + c and a.client_of(x) == c
        assert x.max_new == y.max_new and np.array_equal(x.prompt, y.prompt)
    other = closed_plan(BIG + 1)
    assert any(not np.array_equal(a.request(c, 0).prompt,
                                  other.request(c, 0).prompt)
               for c in range(8))


def test_closed_rounds_hold_the_same_lengths_for_every_seed():
    plans = [closed_plan(s) for s in (BIG, BIG + 1, 2 ** 40 + 3)]
    for k in range(4):
        rounds = [[p.request(c, k) for c in range(8)] for p in plans]
        for key in (lambda r: len(r.prompt), lambda r: r.max_new):
            counts = [Counter(key(r) for r in rnd) for rnd in rounds]
            assert counts[0] == counts[1] == counts[2]
    # the order within a round follows the seed
    assert [len(plans[0].request(c, 0).prompt) for c in range(8)] != \
        [len(plans[1].request(c, 0).prompt) for c in range(8)]


class CountingScheduler(FakeScheduler):
    """The fake scheduler, noting how many requests are outstanding at the
    start of each tick."""

    def __init__(self, clock):
        super().__init__(clock, tick_s=0.01)
        self.outstanding = []

    def step(self):
        self.outstanding.append((self.clock.t, len(self.queue)))
        super().step()


def test_closed_loop_keeps_n_outstanding():
    clock = FakeClock()
    sched = CountingScheduler(clock)
    drv = harness.Driver(FakeSystem(), sched, closed_plan(BIG), seconds=2.0,
                         ramp_s=0.5, drain_s=5.0, clock=clock,
                         sleep=clock.sleep)
    book = drv.run()
    t1 = book.t1
    before = [n for t, n in sched.outstanding if t < t1]
    after = [n for t, n in sched.outstanding if t >= t1]
    assert len(before) > 200 and set(before) == {8}
    # once the window closes nobody sends again, and the rest drain
    assert after == list(range(after[0], 0, -1))
    # each client's requests go out in its own order, due when submitted
    assert all(r.due <= r.submitted for r in book.records)
    for c in range(8):
        mine = [r.planned.idx for r in book.records if r.planned.idx % 8 == c]
        assert mine == [8 * k + c for k in range(len(mine))]
    assert not [r for r in book.records if r.failed]


# digests of the planned requests (index, output length, prompt length, due
# time, prompt tokens) the open loop made before the closed loop was added
POISSON_PLANS = {
    ("deen-l2s-poisson", 2147483700): (2014, "16aa9fbc83876d43"),
    ("deen-l2s-poisson", 2 ** 40 + 3): (2014, "83c3ada641b5b5db"),
    ("ptbl-l2s-nextword", 2147483700): (2120, "607d366c64929546"),
    ("ptbl-l2s-nextword", 2 ** 40 + 3): (2120, "54fb5aec881ce64b"),
}


def plan_digest(planned) -> str:
    m = hashlib.sha256()
    for a in (np.array([(p.idx, p.max_new, len(p.prompt)) for p in planned]),
              np.array([p.due for p in planned]),
              np.concatenate([p.prompt for p in planned])):
        m.update(str(a.dtype).encode())
        m.update(str(a.shape).encode())
        m.update(np.ascontiguousarray(a).tobytes())
    return m.hexdigest()[:16]


@pytest.mark.parametrize("cell,seed", sorted(POISSON_PLANS))
def test_poisson_plans_unchanged(cell, seed):
    from bench import system
    work = system.load_workload(cell)
    conf = system.load_config(work["config"])
    planned = traffic.plan(work, traffic.Corpus(conf["vocab_size"], seed),
                           seed, 51.0)
    assert (len(planned), plan_digest(planned)) == POISSON_PLANS[cell, seed]


def test_poisson_requests_go_out_in_due_order():
    clock = FakeClock()
    sched = FakeScheduler(clock)
    plan = schedule(BIG)
    drv = harness.Driver(FakeSystem(), sched, plan, seconds=4.0, ramp_s=1.0,
                         drain_s=5.0, clock=clock, sleep=clock.sleep)
    book = drv.run()
    due = sorted((p for p in plan if p.due < 5.0), key=lambda p: p.due)
    assert [r.planned.idx for r in book.records] == [p.idx for p in due]
    assert all(r.due == pytest.approx(100.0 + r.planned.due)
               for r in book.records)
