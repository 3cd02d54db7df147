"""Builds the system under test for one cell: weights from the seed, the
benchmark's own screen (``screen.py``), the engine, and a scheduler over a
page pool — the objects ``repro.launch.serve`` builds for ``--scheduler``,
with the traffic's own width, pool and head.

What a configuration's model family means to the benchmark (its weights,
their place in the program's parameter tree, the reference trunk, the
control's precision, the trunk's operations) lives in
``bench/families/<family>.py``, found by the configuration's ``family``.

This is the benchmark's only contact with the program's construction API;
the harness drives what it returns through ``submit`` / ``step``.
"""
from __future__ import annotations

import importlib.util
import json
import time
from pathlib import Path

import jax

from bench import screen, traffic

BENCH = Path(__file__).resolve().parent
CONFIG_DIR = BENCH / "configs"
WORKLOAD_DIR = BENCH / "workloads"
FAMILY_DIR = BENCH / "families"

# The configuration's numbers that must equal the program's registry entry,
# beside its family's own ``checked_keys``.
CHECKED_KEYS = ("num_layers", "d_model", "vocab_size", "dtype", "family")


def load_config(name: str) -> dict:
    return json.loads((CONFIG_DIR / f"{name}.json").read_text())


def load_workload(name: str) -> dict:
    return json.loads((WORKLOAD_DIR / f"{name}.json").read_text())


def load_family(name: str):
    """``bench/families/<name>.py``, the module of a model family."""
    path = FAMILY_DIR / f"{name}.py"
    if not path.is_file():
        known = sorted(f.stem for f in FAMILY_DIR.glob("*.py"))
        raise ValueError(f"unknown model family {name!r}: no {path.name} in "
                         f"{FAMILY_DIR} (known: {known})")
    spec = importlib.util.spec_from_file_location(
        "bench_family_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def model_config(conf: dict):
    """The program's ModelConfig for ``conf``, checked against the file."""
    from repro.configs import get_config
    cfg = get_config(conf["registry"])
    if conf.get("registry_reduced"):
        cfg = cfg.reduced()         # the program's own small variant (tests)
    for key in CHECKED_KEYS + tuple(load_family(conf["family"]).checked_keys):
        if getattr(cfg, key) != conf[key]:
            raise ValueError(f"{conf['name']}: {key} is {getattr(cfg, key)!r} "
                             f"in the program's registry, {conf[key]!r} in "
                             f"the configuration file")
    return cfg


def make_weights(conf: dict, seed: int):
    """The served weights, on the device, in one jitted call of the
    family's ``init_weights``."""
    family = load_family(conf["family"])
    key = jax.random.key(traffic.seed32(seed, 100))
    return jax.jit(lambda k: family.init_weights(k, conf))(key)


def max_len(work: dict) -> int:
    """Cache length: the longest prompt plus the longest output, rounded
    up to the page size (the pool's pages must tile it)."""
    P = work["page_size"]
    n = work["prompt_len"]["max"] + work["output_len"]["max"]
    return -(-n // P) * P


class System:
    """The cell's engine and the factory of its schedulers."""

    def __init__(self, conf: dict, work: dict, seed: int,
                 log=lambda *a: None):
        from repro.models import build_model
        from repro.serving import DecodeEngine
        self.conf, self.work, self.seed = conf, work, seed
        self.head = work["head"]
        t = time.perf_counter()
        self.family = load_family(conf["family"])
        self.cfg = model_config(conf)
        self.model = build_model(self.cfg)
        self.weights = make_weights(conf, seed)
        self.params = self.family.program_params(self.weights, conf)
        jax.block_until_ready(self.params)
        log(f"weights {time.perf_counter() - t:.2f} s")
        t = time.perf_counter()
        self.corpus = traffic.Corpus(conf["vocab_size"], seed)
        log(f"corpus {time.perf_counter() - t:.2f} s")
        self.screen = None
        self.screen_host = None
        if self.head != "exact":
            t = time.perf_counter()
            self.screen_host = screen.fit(self.family, self.weights, conf,
                                          self.corpus, seed)
            self.screen = screen.program_screen(self.screen_host,
                                                conf["vocab_size"])
            log(f"screen {time.perf_counter() - t:.2f} s "
                f"(r={self.screen.r}, C_max={self.screen.c_max})")
        self.max_len = max_len(work)
        self.engine = DecodeEngine(self.model, self.params,
                                   screen=self.screen, max_len=self.max_len)
        self.engine.resolve_head(self.head).prepare()

    def scheduler(self, tracer=None):
        """A fresh scheduler over a fresh page pool: the cell's head for
        every request (static routing), default admission (accept all)."""
        from repro.serving import (ContinuousScheduler, PagePool,
                                   StaticPolicy)
        w = self.work
        pool = PagePool(num_pages=w["pool_pages"], page_size=w["page_size"])
        return ContinuousScheduler(
            self.engine, policy=StaticPolicy(self.head),
            max_slots=w["width"], kv_pool=pool, clock=time.perf_counter,
            tracer=tracer)

    def request(self, planned: traffic.Planned):
        from repro.serving import ServeRequest
        return ServeRequest(prompt=planned.prompt, max_new=planned.max_new)

    def warm(self) -> int:
        """Compile every shape the cell's traffic uses: a join at every
        prompt length the cell can draw (so every prefill chunk the page
        grid can cut), the first-token head call, and the decode step at
        the cell's width with every slot busy. Returns requests served."""
        from repro.serving import ServeRequest
        w = self.work
        lo, hi = w["prompt_len"]["min"], w["prompt_len"]["max"]
        rng = traffic.rng_for(self.seed, 200)
        lengths = list(range(lo, hi + 1))
        lengths += [hi] * max(0, w["width"] - len(lengths))
        toks = self.corpus.tokens(rng, len(lengths), hi)
        sched = self.scheduler()
        for n, t in zip(lengths, toks):
            sched.submit(ServeRequest(prompt=t[:n], max_new=2))
        sched.drain()
        return len(lengths)

    def free(self):
        """Drop every array the program holds (before the reference runs)."""
        self.engine = self.params = self.screen = self.weights = None
