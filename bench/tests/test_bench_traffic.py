"""The traffic generator is a function of the seed, and the due-time book
charges a stall to every request that came due during it."""
from __future__ import annotations

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
