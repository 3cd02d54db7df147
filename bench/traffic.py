"""Traffic for the benchmark: prompts, lengths and arrivals, all from
``--seed``, plus the due-time latency book.

Everything here is the yardstick's own. The program sees only the
``ServeRequest``s the harness builds from what this module returns.

Lengths and inter-arrival gaps are drawn as a fixed set per cell and only
their ORDER follows the seed: every seed offers the window the same
number of requests with the same multiset of prompt lengths, output
lengths and gaps, so two seeds differ in which request comes when and in
the tokens, never in how much work the window holds.

Two arrival laws: ``poisson``, an open loop at a fixed rate, each request
due at a planned time (``plan``); and ``closed``, a fixed pool of clients,
each sending its next request as soon as its last one is back
(``ClosedPlan``), so the server is always offered as much as it can take.

The corpus is a first-order Zipf–Markov chain (the same shape as the
program's ``ZipfMarkovCorpus``: Zipfian successor popularity, a small
Dirichlet-skewed successor set per word), drawn vectorized so that a
25,000-word table costs milliseconds of set-up.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from statistics import NormalDist
from typing import Dict, List, Optional

import numpy as np


def seed32(seed: int, salt: int = 0) -> int:
    """A 32-bit key from any whole-number seed (seeds may exceed 2**32)."""
    return int(np.random.SeedSequence([int(seed) % (1 << 63), salt])
               .generate_state(1)[0])


def rng_for(seed: int, salt: int, *more: int) -> np.random.Generator:
    """An independent numpy stream per purpose (``salt``, and any further
    whole numbers, such as a round) of one seed."""
    return np.random.default_rng(np.random.SeedSequence(
        [int(seed) % (1 << 63), salt, *more]))


# purposes of the seed's streams (fixed: changing one changes the traffic)
SALT_CORPUS, SALT_CONTEXTS, SALT_ORDER, SALT_PROMPTS, SALT_SAMPLE = range(5)


class Corpus:
    """First-order Zipf–Markov chain over ``vocab`` words."""

    def __init__(self, vocab: int, seed: int, branching: int = 64,
                 alpha: float = 1.1, concentration: float = 0.15):
        rng = rng_for(seed, SALT_CORPUS)
        self.vocab = int(vocab)
        self.branching = int(min(branching, vocab))
        pop = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** alpha
        cdf = np.cumsum(pop / pop.sum())
        u = rng.random((vocab, self.branching))
        self.succ = np.minimum(np.searchsorted(cdf, u), vocab - 1
                               ).astype(np.int32)
        p = rng.gamma(concentration, size=(vocab, self.branching)) + 1e-12
        self.cum = np.cumsum(p / p.sum(axis=1, keepdims=True), axis=1)

    def tokens(self, rng: np.random.Generator, n: int, length: int
               ) -> np.ndarray:
        """(n, length) int32 sequences, each from a uniform start word."""
        s = rng.integers(self.vocab, size=n)
        out = np.empty((n, length), np.int32)
        for t in range(length):
            j = (rng.random(n)[:, None] > self.cum[s]).sum(axis=1)
            s = self.succ[s, np.minimum(j, self.branching - 1)]
            out[:, t] = s
        return out


@dataclass(frozen=True)
class Lengths:
    """A length law: lognormal with ``median`` and ``sigma`` clipped to
    [``min``, ``max``], or ``uniform`` over [``min``, ``max``]."""
    min: int
    max: int
    law: str = "lognormal"
    median: float = 0.0
    sigma: float = 0.5

    @classmethod
    def from_dict(cls, d: dict) -> "Lengths":
        return cls(**d)

    def quantile(self, q: np.ndarray) -> np.ndarray:
        if self.law == "uniform":
            x = self.min + q * (self.max - self.min + 1)
            return np.clip(np.floor(x), self.min, self.max).astype(np.int64)
        if self.law != "lognormal":
            raise ValueError(f"unknown length law {self.law!r}")
        z = np.array([NormalDist().inv_cdf(float(v)) for v in q])
        x = np.exp(math.log(self.median) + self.sigma * z)
        return np.clip(np.rint(x), self.min, self.max).astype(np.int64)

    def fixed_set(self, n: int) -> np.ndarray:
        """n lengths at the mid-quantiles (i + 0.5) / n: the cell's fixed
        multiset, the same for every seed."""
        return self.quantile((np.arange(n) + 0.5) / n)


@dataclass
class Planned:
    """One request as planned: its prompt, output length and due time
    (seconds after the traffic starts)."""
    idx: int
    prompt: np.ndarray
    max_new: int
    due: float


def exp_gaps(n: int, rate: float) -> np.ndarray:
    """n exponential inter-arrival gaps at the mid-quantiles (mean 1/rate)."""
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate


def _phase(rng, n: int, rate: float, start: float, span: float,
           p_len: Lengths, o_len: Lengths):
    """n arrivals over [start, start + span): the fixed gap set in a seeded
    order, scaled to fill the span exactly, and the fixed length sets in a
    seeded order. → (due times, prompt lengths, output lengths)."""
    g = rng.permutation(exp_gaps(n, rate))
    g *= span / g.sum()
    due = start + np.concatenate([[0.0], np.cumsum(g)[:-1]])
    return due, rng.permutation(p_len.fixed_set(n)), \
        rng.permutation(o_len.fixed_set(n))


def strata(start: float, span: float, stratum: Optional[float]):
    """[start, start + span) cut into equal spans of about ``stratum``
    seconds (one span when ``stratum`` is None)."""
    k = max(int(round(span / stratum)), 1) if stratum else 1
    return [(start + i * span / k, span / k) for i in range(k)]


class ClosedPlan:
    """A closed loop (``arrival.law == "closed"``): ``arrival.clients``
    clients, each with its own sequence of requests. The k-th request of
    every client makes round k, the closed loop's stratum: its prompt and
    output lengths are the fixed sets of ``clients`` lengths in a seeded
    order, so every round holds the same work for every seed. A round is
    drawn from streams of the seed of its own the first time a client
    reaches it, so how many rounds a run uses changes none of them.
    Request k of client c has ``idx`` k·clients + c; its due time is its
    submission's, set by the driver."""

    def __init__(self, work: dict, corpus: Corpus, seed: int):
        self.clients = int(work["arrival"]["clients"])
        if self.clients < 1:
            raise ValueError(f"a closed loop needs clients, got "
                             f"{self.clients}")
        self.p_len = Lengths.from_dict(work["prompt_len"])
        self.o_len = Lengths.from_dict(work["output_len"])
        self.corpus, self.seed = corpus, seed
        self._rounds: Dict[int, List[Planned]] = {}

    def request(self, client: int, k: int) -> Planned:
        """Client ``client``'s ``k``-th request (from 0)."""
        if k not in self._rounds:
            self._rounds[k] = self._draw(k)
        return self._rounds[k][client]

    def client_of(self, p: Planned) -> int:
        return p.idx % self.clients

    def _draw(self, k: int) -> List[Planned]:
        n = self.clients
        order = rng_for(self.seed, SALT_ORDER, k)
        pl = order.permutation(self.p_len.fixed_set(n))
        ol = order.permutation(self.o_len.fixed_set(n))
        toks = self.corpus.tokens(rng_for(self.seed, SALT_PROMPTS, k), n,
                                  int(self.p_len.max))
        return [Planned(idx=k * n + c, prompt=toks[c, :int(pl[c])],
                        max_new=int(ol[c]), due=math.nan) for c in range(n)]


def plan(work: dict, corpus: Corpus, seed: int, seconds: float):
    """The cell's requests. Open loop (``arrival.law == "poisson"``): a list
    in the order they are offered, ``rate·ramp_s`` arrivals in the ramp and
    ``rate·seconds`` in the window. Each phase, or with ``stratum_s`` each
    stratum of about that many seconds of it, has its own fixed sets of
    gaps and lengths in a seeded order, so the window, and each stratum,
    holds the same requests' worth of work for every seed. Closed loop
    (``"closed"``): a ``ClosedPlan``."""
    arr = work["arrival"]
    if arr["law"] == "closed":
        return ClosedPlan(work, corpus, seed)
    if arr["law"] != "poisson":
        raise ValueError(f"unknown arrival law {arr['law']!r}")
    p_len = Lengths.from_dict(work["prompt_len"])
    o_len = Lengths.from_dict(work["output_len"])
    order = rng_for(seed, SALT_ORDER)
    prompts_rng = rng_for(seed, SALT_PROMPTS)
    rate, ramp = float(arr["rate"]), float(work["ramp_s"])
    stratum = work.get("stratum_s")
    phases = [_phase(order, max(int(round(rate * sp)), 1), rate, s0, sp,
                     p_len, o_len)
              for start, span in ((0.0, ramp), (ramp, float(seconds)))
              if span > 0
              for s0, sp in strata(start, span, stratum)]
    due, pl, ol = (np.concatenate(x) for x in zip(*phases))
    toks = corpus.tokens(prompts_rng, len(due), int(p_len.max))
    return [Planned(idx=i, prompt=toks[i, :int(pl[i])], max_new=int(ol[i]),
                    due=float(due[i]))
            for i in range(len(due))]


@dataclass
class Record:
    """What the harness saw of one planned request."""
    planned: Planned
    due: float                       # absolute, on the harness clock
    submitted: float
    done: Optional[float] = None
    tokens: Optional[np.ndarray] = None
    failed: str = ""                 # why it counts as failed, or ""

    @property
    def latency(self) -> Optional[float]:
        """Due time to last token (None until done)."""
        return None if self.done is None else self.done - self.due

    @property
    def lateness(self) -> float:
        """How late the generator submitted it."""
        return self.submitted - self.due


@dataclass
class LatencyBook:
    """Due-time accounting over one measured window [t0, t1).

    A request is timed from when it was DUE, not from when the generator
    got round to submitting it, so a stalled generator (or a long tick that
    holds the single-threaded loop) shows up as latency of every request
    that came due meanwhile, and separately as generator lateness."""
    t0: float
    t1: float
    records: List[Record] = field(default_factory=list)

    def add(self, rec: Record) -> Record:
        self.records.append(rec)
        return rec

    def due_in_window(self) -> List[Record]:
        return [r for r in self.records if self.t0 <= r.due < self.t1]

    def completed_in_window(self) -> List[Record]:
        return [r for r in self.records
                if r.done is not None and not r.failed
                and self.t0 <= r.done < self.t1]

    def tokens_per_s(self) -> float:
        n = sum(len(r.tokens) for r in self.completed_in_window())
        return n / (self.t1 - self.t0)

    def latency_quantile(self, q: float, drain_end: float) -> float:
        """Quantile ``q`` of due-to-last-token latency over every request
        due in the window. One that failed or never finished counts as
        finishing no earlier than ``drain_end`` (a lower bound on its
        latency: it missed every limit)."""
        lat = sorted(r.latency if (r.latency is not None and not r.failed)
                     else drain_end - r.due for r in self.due_in_window())
        if not lat:
            return math.nan
        return float(np.percentile(lat, 100.0 * q, method="linear"))

    def lateness(self) -> Dict[str, float]:
        late = np.array([r.lateness for r in self.due_in_window()] or [0.0])
        return {"mean_s": float(late.mean()),
                "p95_s": float(np.percentile(late, 95)),
                "max_s": float(late.max())}
