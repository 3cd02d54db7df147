"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the finished requests, drawn from the seed and always holding the longest,
is teacher-forced through the plain reference (``reference.py``): each
request's prompt followed by the tokens it was served. At every served
position the reference says which token its head semantics allow and by
how much the served token's logit falls short of the best allowed one.

Numbers compared, each the widest over every checked position:

  token_gap  reference logit of the best allowed token minus that of the
             served token (exact head: over the whole vocabulary; screened
             head: over the candidate set of a cluster that holds the
             served token, see ``route_gap``)
  route_gap  screened head only: the reference's best route score minus
             that cluster's; of the clusters holding the served token, the
             one whose larger gap is least (``Reference.gaps``)

Their limits live in the workload file, under ``check.limits``; PERF.md
gives the readings each was set from. ``p_at_1`` (the paper's precision@1,
the share of positions where the served token is the exact head's top-1
under the reference) comes from the same states and is reported, not
compared.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import reference, system, traffic


@dataclass
class Verdict:
    correct: bool
    compared: Dict[str, dict] = field(default_factory=dict)
    p_at_1: Optional[float] = None
    positions: int = 0

    def lines(self) -> List[str]:
        out = [f"check: {self.positions} positions compared; correct "
               f"{str(self.correct).lower()}"]
        for name, d in self.compared.items():
            out.append(f"check {name} {d['value']!r} limit {d['limit']!r}")
        return out


def sample(book: traffic.LatencyBook, n: int, seed: int) -> list:
    """Up to ``n`` finished requests: the longest, then a seeded draw."""
    done = sorted((r for r in book.records
                   if r.tokens is not None and not r.failed),
                  key=lambda r: r.planned.idx)
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.planned.prompt) + len(r.tokens),
                                       -r.planned.idx))
    rest = [r for r in done if r is not longest]
    rng = traffic.rng_for(seed, traffic.SALT_SAMPLE)
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def readings(ref: reference.Reference, recs: list,
             control: Optional[str] = None) -> Dict[str, np.ndarray]:
    """Per-position gaps of the served tokens (and of the picks of the
    control named by ``control``), plus whether each served token is the
    exact top-1."""
    out = ref.run([r.planned.prompt for r in recs],
                  [r.tokens for r in recs], control=control)
    res = {f"served.{k}": v for k, v in
           ref.gaps(out, out["served"], out["z_served"]).items()}
    res["top1"] = out["served"] == out["zarg"]
    if control:
        res.update({f"control.{k}": v for k, v in
                    ref.gaps(out, out["ctl_tok"], out["z_ctl"]).items()})
    return res


def judge(r: Dict[str, np.ndarray], who: str, limits: Dict[str, float]
          ) -> Verdict:
    """Each number's widest reading for ``who`` ("served" or "control")
    against its limit."""
    v = Verdict(correct=True, positions=int(len(r["top1"])))
    for name, limit in limits.items():
        value = float(np.max(r[f"{who}.{name}"]))
        ok = math.isfinite(value) and value <= limit
        v.correct = v.correct and ok
        # JSON has no infinity: a token no cluster holds reads "inf"
        v.compared[name] = {"value": value if math.isfinite(value)
                            else "inf", "limit": limit}
    return v


def reference_for(conf: dict, work: dict, seed: int, screen_host):
    weights = system.make_weights(conf, seed)
    return reference.Reference(system.load_family(conf["family"]), conf,
                               weights, screen_host, conf["vocab_size"],
                               block_rows=work["check"]["block_rows"],
                               seq_len=system.max_len(work))


def run_check(conf: dict, work: dict, seed: int, book: traffic.LatencyBook,
              screen_host, log: Callable = lambda *a: None) -> Verdict:
    recs = sample(book, work["check"]["requests"], seed)
    limits = work["check"]["limits"]
    if not recs:
        log("check: no finished request to compare")
        return Verdict(correct=False,
                       compared={k: {"value": None, "limit": v}
                                 for k, v in limits.items()})
    ref = reference_for(conf, work, seed, screen_host)
    r = readings(ref, recs)
    v = judge(r, "served", limits)
    v.p_at_1 = float(np.mean(r["top1"]))
    log(f"check: {len(recs)} requests, {v.positions} positions, "
        f"P@1 {v.p_at_1:.4f}, mean token gap "
        f"{float(np.mean(np.minimum(r['served.token_gap'], 1e30))):.3g}, "
        f"positions with a gap > 0: "
        f"{int(np.sum(r['served.token_gap'] > 0))}")
    return v
