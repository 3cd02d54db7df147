#!/usr/bin/env python3
"""Chip smoke test: the paper's DE→EN LSTM served on a TPU through the
normal serving path.

    python chip_smoke.py                # one TPU: nmt-deen-lstm at full width
    python chip_smoke.py --four-chips   # vocab-sharded heads on a 4-way mesh
    python chip_smoke.py --reduced      # every phase at the reduced config

One chip (default). ``nmt-deen-lstm`` (2-layer LSTM, d=500, vocabulary
25,000, ``configs/nmt_deen.py``) with weights drawn from ``--seed``:

  setup   train a few dozen steps and fit a 128-word block screen
          (``repro.launch.serve.prepare``: Algorithm 1 at vocab_block=128)
  serve   requests through DecodeEngine → ContinuousScheduler → PagePool →
          the head registry (``repro.launch.serve.serve_scheduler``): the
          realtime tier on screened-pallas, the others on exact
  kernel  the served screened-pallas decode step compiled to a Mosaic
          kernel (``tpu_custom_call`` in its compiled text)
  check   on the hidden states of the served sequences, each head's top-5
          ids against a float32 reference computed on the host (``h·Wᵀ+b``;
          for screened-pallas the same over the row's routed candidate
          blocks); P@1/P@5 of screened-pallas vs exact (paper §4.2)

Four chips (``--four-chips``): the same setup, then exact-sharded and
screened-sharded (local="pallas") over a 4-way "model" mesh against the
one-device exact and screened-pallas heads on the same hidden states, the
same check, each shard's weight bytes per device, and a short scheduler
pass on screened-sharded.

The ids of a head may differ from the reference at a rank only where the
two words' reference logits are within their summed error bounds. A
logit's bound is ``2⁻⁷·Σᵢ|hᵢ||wᵢ|``: the TPU's default precision for a
float32 matmul rounds both operands to bfloat16 (8 significant bits, unit
roundoff 2⁻⁸), so each product is off by at most 2·2⁻⁸ of its magnitude;
float32 accumulation adds far less. Routing ties are judged the same way:
a row may follow any cluster whose score is within its bound of the best.

Everything runs in this one process. The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
printed only on a TPU when every phase passed. Without a TPU every phase
runs at the reduced config as a rehearsal and the script exits 1 without
that line; a failed phase raises and exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from contextlib import contextmanager
from pathlib import Path

import numpy as np

ARCH = "nmt-deen-lstm"
K = 5                         # top-k compared (the paper's P@5)
REL_TOL = 2.0 ** -7           # per-logit bound / Σ|h||w| (see docstring)

FULL = dict(train_steps=40, requests=8, prompt_len=16, max_new=24,
            clusters=50)
REDUCED = dict(train_steps=4, requests=6, prompt_len=8, max_new=6,
               clusters=8, budget=256)


class CompileClock:
    """Seconds JAX spends producing executables (compiling, or loading one
    from the persistent cache), and how many came from that cache."""
    BACKEND = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.secs, self.n, self.hits = 0.0, 0, 0

    def on_duration(self, event, duration, **_):
        if event == self.BACKEND:
            self.secs += duration
            self.n += 1

    def on_event(self, event, **_):
        if event == self.HIT:
            self.hits += 1


@contextmanager
def phase(name: str, clock: CompileClock):
    t0, c0 = time.time(), clock.secs
    yield
    print(f"[smoke] phase {name}: {time.time() - t0:.1f} s "
          f"(compile {clock.secs - c0:.1f} s)")


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--reduced", action="store_true",
                    help="run every phase at the reduced config")
    ap.add_argument("--four-chips", action="store_true",
                    help="only the vocab-sharded heads over a 4-way mesh, "
                         "against the one-device heads")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def launcher_args(serve, reduced: bool, seed: int, head: str):
    """The launcher's own arguments for ``--arch nmt-deen-lstm --l2s
    --head <head> --scheduler`` at the smoke sizes."""
    n = REDUCED if reduced else FULL
    argv = ["--arch", ARCH, "--l2s", "--head", head, "--scheduler",
            "--seed", str(seed)]
    for key, val in n.items():
        argv += ["--" + key.replace("_", "-"), str(val)]
    if reduced:
        argv.append("--reduced")
    return serve.build_parser().parse_args(argv)


def setup(args, head: str):
    """Model from the seed → (model, namespace(params, screen, engine,
    requests)): trained, screen fit, engine built."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.launch import serve
    from repro.models import build_model

    largs = launcher_args(serve, args.reduced, args.seed, head)
    cfg = get_config(ARCH)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(args.seed), dtype=jnp.float32)
    print(f"[smoke] config: {cfg.name} layers={cfg.num_layers} "
          f"d={cfg.d_model} vocab={cfg.vocab_size}")
    st = serve.prepare(largs, cfg, model, params, head)
    assert st.screen.block == 128, st.screen.block
    return model, st


def served_hidden_states(model, params, results):
    """(N, d) hidden states the served tokens were decoded from, recomputed
    by running each served sequence (prompt + tokens) through the model."""
    import jax
    import jax.numpy as jnp

    fwd = jax.jit(lambda p, t: model.forward(p, {"tokens": t})[0])
    hs = []
    for r in results:
        P, T = len(r.request.prompt), len(r.tokens)
        seq = np.concatenate([r.request.prompt, r.tokens[:-1]])[None]
        h = fwd(params, jnp.asarray(seq, jnp.int32))
        hs.append(np.asarray(h[0, P - 1:P - 1 + T], np.float32))
    return np.concatenate(hs)


def served(sched):
    from repro.serving import ServeResult
    results = [r for r in sched.results() if isinstance(r, ServeResult)]
    snap = sched.stats.snapshot()
    per_head = {h: d["requests"] for h, d in snap["per_head"].items()}
    print(f"[smoke] served {len(results)} requests through the scheduler | "
          "per head: " + ", ".join(f"{h} {n}" for h, n in per_head.items()))
    # a head that emits an invalid id faults and its request falls back to
    # another head: that is not the head serving
    assert snap["resilience"] is None, \
        f"faults while serving: {snap['resilience']}"
    return results, per_head


class Reference:
    """float32 logits on the host from the served hidden states, with the
    per-logit error bound, in the packed (n_blk·128) row space the block
    screen addresses; rows past the vocabulary carry the −1e30 bias the
    heads pad with."""

    def __init__(self, H, W, b, screen):
        from repro.heads.base import NEG_INF
        self.L, d = W.shape
        self.block = screen.block
        n_blk = -(-self.L // self.block)
        Wp = np.zeros((n_blk * self.block, d), np.float32)
        Wp[:self.L] = W
        bp = np.full((n_blk * self.block,), NEG_INF, np.float32)
        bp[:self.L] = b
        self.logits = H @ Wp.T + bp
        self.err = REL_TOL * (np.abs(H) @ np.abs(Wp).T) + 1e-6
        v = np.asarray(screen.v, np.float32)
        score = H @ v.T
        bound = REL_TOL * (np.abs(H) @ np.abs(v).T)
        best = score.argmax(-1)
        top = score[np.arange(len(H)), best]
        # clusters a row may route to: within the summed bounds of the best
        self.routes = [np.flatnonzero(score[i] + bound[i] + bound[i, best[i]]
                                      >= top[i]) for i in range(len(H))]
        self.cand = np.asarray(screen.cand_idx)
        self.cand_len = np.asarray(screen.cand_len)

    def cluster_rows(self, t):
        blocks = self.cand[t, :self.cand_len[t]].astype(np.int64)
        return (blocks[:, None] * self.block + np.arange(self.block)
                ).reshape(-1)

    def check(self, name, ids, vals, routed: bool):
        """Every row's top-K against the reference; returns the number of
        ranks that differ within tolerance. Raises on any other mismatch."""
        ids, vals = np.asarray(ids), np.asarray(vals)
        n_tol = 0
        for i in range(len(ids)):
            if not routed:
                choices = [np.arange(self.L)]
            else:
                choices = [self.cluster_rows(t) for t in self.routes[i]]
            results = [self._row(i, ids[i], vals[i], rows)
                       for rows in choices]
            ok = [n for n in results if n is not None]
            assert ok, (f"{name}: row {i} top-{K} ids {ids[i].tolist()} "
                        f"disagree with the reference beyond tolerance")
            n_tol += min(ok)
        return n_tol

    def _row(self, i, ids, vals, rows):
        """Ranks that differ within tolerance, or None if the row fails."""
        ref, err = self.logits[i], self.err[i]
        order = rows[np.argsort(-ref[rows], kind="stable")][:K]
        allowed = set(rows.tolist())
        n_tol = 0
        for j in range(K):
            if j >= len(order):                # fewer candidates than K
                if ids[j] < len(ref):
                    return None
                continue
            a, r = int(ids[j]), int(order[j])
            if a not in allowed:
                return None
            if abs(float(vals[j]) - ref[a]) > err[a]:
                return None
            if a != r:
                if abs(ref[a] - ref[r]) > err[a] + err[r]:
                    return None
                n_tol += 1
        if len(set(ids[:len(order)].tolist())) < min(K, len(order)):
            return None
        return n_tol


def check_heads(model, st, results, heads):
    """Top-K of each (name, head, routed) on the served hidden states,
    checked against the host reference; returns {name: ids}."""
    H = served_hidden_states(model, st.params, results)
    W, b = (np.asarray(a, np.float32)
            for a in model.softmax_weights(st.params))
    ref = Reference(H, W, b, st.screen)
    routing_report(st.screen, H)
    out = {}
    for name, head, routed in heads:
        ids, vals = head.topk(H, K)
        n_tol = ref.check(name, ids, vals, routed)
        print(f"[smoke] check {name}: top-{K} ids of {len(H)} served states "
              f"agree with the float32 host reference ({n_tol} of "
              f"{len(H) * K} ranks differ within tolerance)")
        out[name] = np.asarray(ids)
    return out, H


def routing_report(screen, H):
    """How often the jnp router and the route kernel pick the same cluster,
    and how many clusters hold no candidates (a row routed there has no
    word to emit)."""
    import jax.numpy as jnp
    from repro.core.screening import assign_clusters
    from repro.kernels.route import cluster_route_pallas
    a = np.asarray(assign_clusters(screen.v, jnp.asarray(H)))
    b = np.asarray(cluster_route_pallas(jnp.asarray(H), screen.v))
    score = H @ np.asarray(screen.v, np.float32).T
    rows = np.arange(len(H))
    tied = int(np.sum((a != b) & (score[rows, a] == score[rows, b])))
    empty = int((np.asarray(screen.cand_len) == 0).sum())
    print(f"[smoke] routing: assign_clusters and the route kernel agree on "
          f"{float(np.mean(a == b)):.4f} of {len(H)} rows ({tied} of the "
          f"others tie exactly on the host); {empty} of {screen.r} clusters "
          f"have no candidates")


def kernel_in_step(engine, head) -> bool:
    """True iff the served greedy decode step of ``head`` — the exact jitted
    step the scheduler's stream calls — compiles to a Mosaic kernel."""
    import jax.numpy as jnp
    from repro.launch.serve import MAX_SLOTS
    fn = engine._greedy_step(head)
    tok = jnp.zeros((MAX_SLOTS,), jnp.int32)
    cache = engine.model.init_cache(MAX_SLOTS, engine.max_len,
                                    dtype=engine.cache_dtype)
    text = fn.lower(engine.params, tok, cache, tok).compile().as_text()
    return "tpu_custom_call" in text


def one_chip(args, on_tpu: bool, clock: CompileClock):
    from repro.core.evaluate import precision_at_k
    from repro.launch import serve

    with phase("setup", clock):
        model, st = setup(args, "screened-pallas")

    with phase("serve", clock):
        engine = st.engine
        pallas = engine.resolve_head("screened-pallas")
        exact = engine.resolve_head("exact")
        sched = serve.serve_scheduler(engine, st.requests, "screened-pallas")
        results, per_head = served(sched)
        for name in ("screened-pallas", "exact"):
            assert per_head.get(name, 0) >= 1, f"no request served on {name}"

    with phase("kernel", clock):
        has_kernel = kernel_in_step(engine, pallas)
        print(f"[smoke] kernel: tpu_custom_call in the served "
              f"screened-pallas step: {has_kernel}")
        if on_tpu:
            assert has_kernel, "screened-pallas step compiled to no kernel"

    with phase("check", clock):
        ids, H = check_heads(model, st, results,
                             [("exact", exact, False),
                              ("screened-pallas", pallas, True)])
        p1 = precision_at_k(ids["screened-pallas"][:, :1],
                            ids["exact"][:, :1])
        p5 = precision_at_k(ids["screened-pallas"], ids["exact"])
        print(f"[smoke] screened-pallas vs exact on {len(H)} served states: "
              f"P@1 {p1:.4f} P@5 {p5:.4f}")


def four_chips(args, on_tpu: bool, clock: CompileClock):
    import jax
    from repro.launch import serve
    from repro.serving import DecodeEngine

    n = len(jax.devices())
    assert n >= 4, f"--four-chips needs 4 devices, found {n}"
    with phase("setup", clock):
        model, st = setup(args, "screened-pallas")

    with phase("serve", clock):
        engine = DecodeEngine(model, st.params, screen=st.screen,
                              max_len=st.engine.max_len,
                              head_kwargs={"local": "pallas", "n_shards": 4})
        sharded = {name: engine.resolve_head(name).prepare()
                   for name in ("exact-sharded", "screened-sharded")}
        for name, head in sharded.items():
            shards = head.Wp.addressable_shards
            per_dev = {s.device.id: s.data.nbytes for s in shards}
            print(f"[smoke] {name}: W {tuple(head.Wp.shape)} over "
                  f"{len(per_dev)} devices, bytes per device {per_dev}")
            assert len(per_dev) == 4 and all(
                s.data.shape[0] * 4 == head.Wp.shape[0] for s in shards), \
                f"{name} weights are not split over 4 devices: {per_dev}"
        sched = serve.serve_scheduler(engine, st.requests, "screened-sharded")
        results, per_head = served(sched)

    with phase("check", clock):
        one = st.engine
        ids, H = check_heads(model, st, results, [
            ("exact-sharded", sharded["exact-sharded"], False),
            ("screened-sharded", sharded["screened-sharded"], True),
            ("exact", one.resolve_head("exact"), False),
            ("screened-pallas", one.resolve_head("screened-pallas"), True)])
        for a, b in (("exact-sharded", "exact"),
                     ("screened-sharded", "screened-pallas")):
            same = float(np.mean(ids[a] == ids[b]))
            print(f"[smoke] {a} vs {b}: {same:.4f} of top-{K} ids identical")
        if on_tpu:
            ss = sharded["screened-sharded"]
            text = ss._pallas_fns.topk.lower(
                ss.Wp, ss.bp, ss.v, ss.cand_blocks,
                jax.device_put(H[:serve.MAX_SLOTS], ss._repl),
                k=K).compile().as_text()
            assert "tpu_custom_call" in text, "screened-sharded has no kernel"
            print("[smoke] kernel: tpu_custom_call in the screened-sharded "
                  "top-k step: True")
        # checked after the heads, so a head fault shows as a row above
        assert per_head.get("screened-sharded", 0) >= 1, \
            "no request served on screened-sharded"


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.four_chips and "jax" not in sys.modules:
        # a CPU rehearsal needs four devices; the flag touches only the
        # host platform, never a TPU
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = \
                f"{flags} --xla_force_host_platform_device_count=4".strip()
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"[smoke] the repro package is not beside this script: {e}",
              file=sys.stderr)
        return 2
    import jax
    from repro.utils.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    count = len(jax.devices())
    print(f"[smoke] device: {dev.device_kind} (platform {dev.platform}, "
          f"{count} devices)")
    if not on_tpu:
        print("[smoke] no TPU: rehearsing every phase at the reduced config")
        args.reduced = True
    print(f"[smoke] compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock.on_duration)
    jax.monitoring.register_event_listener(clock.on_event)
    try:
        t0 = time.time()
        (four_chips if args.four_chips else one_chip)(args, on_tpu, clock)
        print(f"[smoke] compile seconds: {clock.secs:.1f} over {clock.n} "
              f"executables ({clock.hits} from the persistent cache); "
              f"wall {time.time() - t0:.1f} s")
    finally:
        jax.monitoring.unregister_event_duration_listener(clock.on_duration)
        jax.monitoring.unregister_event_listener(clock.on_event)
    if not on_tpu:
        print("[smoke] rehearsal passed; no TPU, so no result")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
