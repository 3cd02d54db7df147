"""The mla_moe family (Moonlight-16B-A3B, one chip of an 8-way expert-parallel
deployment) at the program's reduced size: its reference against the
program through latent pages, the screen, the check and the work counts,
the two readers it brings, and whole reduced runs of its cell.

The program and the reference both compute in float32 on the CPU, so they
differ by the order of float32 sums (~1e-6 of these logits), where one
bfloat16 rounding moves a logit by ~1e-2.
"""
from __future__ import annotations

import argparse
import copy
import io
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, peaks, reference, screen, system, traffic, work
from bench.tests.helpers import CPU_LIMIT, small_work

CONF = "moonlight-16b-a3b-ep8"
SEED = 2 ** 31 + 41


def small_conf() -> dict:
    """The configuration file at the program's reduced size (the registry
    entry's ``reduced()``): every checked key follows it."""
    conf = system.load_config(CONF)
    conf.update(registry_reduced=True, num_layers=2, d_model=128,
                vocab_size=512, dtype="float32", num_heads=4,
                kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                v_head_dim=16, d_ff=256, num_experts=4, experts_per_token=2,
                expert_d_ff=64, experts_held=1)
    conf["screen"].update(clusters=8, budget_blocks=2, kmeans_iters=3,
                          ctx_batches=2)
    return conf


def test_configuration_matches_the_registry():
    conf = system.load_config(CONF)
    cfg = system.model_config(conf)
    cut = conf["reduced_from"]
    assert set(cut) == set(conf["reduced"])
    depth = cut["num_hidden_layers"]
    assert (depth["published"], depth["here"]) == (27, cfg.num_layers)
    assert conf["num_experts"] == cut["n_routed_experts"]["published"] == 64
    assert conf["n_routed_experts"] == cfg.experts_held == 8
    assert conf["deployment"]["expert_parallel_chips"] == \
        cfg.num_experts // cfg.experts_held
    system.model_config(small_conf())


def test_program_through_latent_pages_matches_the_reference():
    """Prefill (a bucketed join) and paged decode through latent pages give
    the logits of the reference's teacher-forced forward at every served
    position."""
    from repro.models import build_model
    from repro.serving import DecodeEngine, PagePool, ServeRequest
    conf = small_conf()
    fam = system.load_family("mla_moe")
    w = system.make_weights(conf, SEED)
    eng = DecodeEngine(build_model(system.model_config(conf)),
                       fam.program_params(w, conf), max_len=32)
    stream = eng.open_paged_stream(PagePool(num_pages=12, page_size=8),
                                   width=2)
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, 512, 11).astype(np.int32)
    stream.join(ServeRequest(prompt=prompt, max_new=9), tag=0)
    hs, toks = [], []
    real = eng._jit_decode_paged
    while stream.n_active:
        s = stream.slots[0]
        toks.append(int(stream.tok[0]))
        stream._ensure_pages([0])           # the step's own page growth
        h, _ = real(eng.params, jnp.asarray(stream.tok),
                    stream.pool.store.arrays, jnp.asarray(stream.table),
                    jnp.asarray(stream.pos))
        hs.append(np.asarray(h)[0])
        assert s is not None
        stream.step()
    seq = np.concatenate([prompt, toks])
    ref = np.asarray(fam.trunk(w, jnp.asarray(seq[None]), conf))[0]
    z_ref = ref[len(prompt):] @ np.asarray(w["w_out"]).T
    z = np.stack(hs) @ np.asarray(w["w_out"]).T
    np.testing.assert_allclose(z, z_ref, atol=2e-5, rtol=2e-5)
    assert toks[1:] == list(np.argmax(z_ref, -1)[:-1])


def test_family_runs_through_screen_check_and_work():
    conf = small_conf()
    fam = system.load_family("mla_moe")
    w = system.make_weights(conf, SEED)
    assert "b_out" not in w and w["blocks"]["e_gate"].shape == (1, 1, 128, 64)
    s = screen.fit(fam, w, conf, traffic.Corpus(512, SEED), SEED)
    assert s["v"].shape == (8, 128)
    ref = reference.Reference(fam, conf, w, s, 512, block_rows=2)
    prompts, served = [np.arange(3, 12), np.arange(20, 26)], \
        [np.array([5, 6, 7]), np.array([1, 2])]
    out = ref.run(prompts, served, control="float8")
    assert out["s"].shape == (5, 8)
    g = ref.gaps(out, out["ctl_tok"], out["z_ctl"])
    assert np.all(np.isfinite(g["token_gap"]))
    f = work.model_flops(fam, conf, 10.0, np.arange(9), np.array([9, 10]), 3)
    assert f == fam.trunk_flops(conf, np.arange(11)) + 3 * 2 * 10.0


def test_float8_control_rounds_every_matrix():
    conf = small_conf()
    fam = system.load_family("mla_moe")
    w = system.make_weights(conf, SEED)
    low = fam.lower(w, "float8")
    assert np.array_equal(low["blocks"]["bias"], w["blocks"]["bias"])
    for k in ("wq", "e_gate", "s_down", "w_router"):
        assert not np.array_equal(low["blocks"][k], w["blocks"][k]), k
    assert not np.array_equal(low["dense"][0]["w_gate"],
                              w["dense"][0]["w_gate"])


def test_work_counts_at_the_published_widths():
    """The decode step's weights are the 1.77 GB of the trunk a step reads
    (no embedding table, no head); the latents 576 float32 values per
    position and layer; the chip's expert share in the operations."""
    conf = system.load_config(CONF)
    fam = system.load_family("mla_moe")
    weights = fam.decode_bytes(conf, np.zeros(0))
    assert 1.7e9 < weights < 1.85e9
    per_row = fam.decode_bytes(conf, np.array([99])) - weights
    assert per_row == 100 * 9 * 576 * 4 + 2048 * 2
    f0, f1 = (fam.trunk_flops(conf, np.array([p])) for p in (0, 1))
    assert f1 - f0 == 2.0 * 9 * 16 * (128 + 64 + 128)
    # the routed experts: 6 of 64 chosen, 8 held → 0.75 expert a token
    routed = 2 * 3 * 2048 * 1408 * 6 * 8 / 64
    dense_token = 2 * (3 * 2048 * 11264)
    assert f0 > 8 * routed + dense_token


def _art(evs, op_s):
    return {"tracer_events": evs, "book": type("B", (), {"t0": 0.0,
                                                         "t1": 1.0})(),
            "trace": {"busy_s": 1.0, "op_s": op_s,
                      "op_label": {k: "" for k in op_s}},
            "family": system.load_family("mla_moe"),
            "conf": system.load_config(CONF), "kind": "TPU v5 lite",
            "work": system.load_workload("moonlight-l2s-batch"),
            "records": {5: type("R", (), {"planned": type(
                "P", (), {"prompt": np.zeros(10)})()})()}}


def _span(name, sid, ts, **args):
    return {"name": name, "ph": "X", "ts": ts, "dur": 0.01, "tid": 5,
            "args": {"id": sid, "parent": None, **args}}


# the two layer loops as the chip's trace names them (layouts taken out),
# beside a body op of one and a loop of another width that count for
# neither reader
OPS = {"%while.66 = (s32[], f32[128,1,2048], f32[9,6400,16,576], "
       "bf16[8,512]) while(...)": 0.004,
       "%while.48 = (s32[], f32[1,256,2048], f32[9,1,256,576], "
       "bf16[8,512]) while(...)": 0.002,
       "%while.48 = (s32[], f32[1,512,2048], f32[9,1,512,576], "
       "bf16[8,512]) while(...)": 0.001,
       "%while.9 = (s32[], f32[16,1,2048], f32[9,6400,16,576]) while(...)":
       0.5,
       "%dynamic-slice_bitcast_fusion.6 = bf16[8,2048,1408] fusion(...)":
       0.3}


def test_readers_count_the_served_work():
    evs = [_span("serve.join.prefill", 1, 0.1, prompt_len=10, bucket=64),
           _span("serve.step", 2, 0.2, rids=[5]),
           _span("serve.step", 3, 0.3, rids=[5])]
    a = _art(evs, OPS)
    fam, conf = a["family"], a["conf"]
    want = (fam.decode_bytes(conf, np.array([10]), layers="experts")
            + fam.decode_bytes(conf, np.array([11]), layers="experts")
            ) / (0.004 * 819e9)
    assert harness.load_reader("decode_hbm_roofline")(a) == \
        pytest.approx(100 * want)
    want = fam.trunk_flops(conf, np.arange(10), layers="experts") / (
        0.003 * 197e12)
    assert harness.load_reader("prefill_mfu")(a) == pytest.approx(100 * want)


def test_readers_find_nothing_without_what_they_read():
    """A program without the join's ``prompt_len`` (the parent), or a
    trace without the layer loops, leaves the readers nothing to read."""
    evs = [_span("serve.join.prefill", 1, 0.1),
           _span("serve.step", 2, 0.2, rids=[5])]
    no_loops = {k: v for k, v in OPS.items() if "while" not in k}
    for name in ("decode_hbm_roofline", "prefill_mfu"):
        read = harness.load_reader(name)
        assert read(_art(evs, no_loops)) is None
        assert read(dict(_art(evs, OPS), trace=None)) is None
    assert harness.load_reader("prefill_mfu")(_art(evs, OPS)) is None


def test_expert_layer_counts_are_the_trunks_less_the_dense_layer():
    conf = system.load_config(CONF)
    fam = system.load_family("mla_moe")
    pos = np.array([3, 70, 300])
    one = dict(conf, num_layers=1, first_k_dense=1)
    assert fam.trunk_flops(conf, pos) == pytest.approx(
        fam.trunk_flops(conf, pos, layers="experts")
        + fam.trunk_flops(one, pos))
    assert fam.decode_bytes(conf, pos) == pytest.approx(
        fam.decode_bytes(conf, pos, layers="experts")
        + fam.decode_bytes(one, pos))


def small_moonlight_work(limit: float = CPU_LIMIT) -> dict:
    work_ = small_work("moonlight-l2s-batch", limit)
    work_.update(page_size=8)
    return work_


def run_moonlight(seed: int = SEED, trace: int = 0) -> dict:
    """One run of the reduced ``moonlight-l2s-batch`` on the CPU, past the
    harness's look for a chip; the result line as a dict."""
    bench = harness.load_benchmark()
    args = argparse.Namespace(workload="moonlight-l2s-batch", seed=seed,
                              seconds=1.5, trace=trace, trace_dir=None)
    out = io.StringIO()
    saved = dict(peaks.CHIP_PEAKS)
    peaks.CHIP_PEAKS["cpu"] = peaks.CHIP_PEAKS["TPU v5 lite"]
    try:
        rc = harness.run(args, time.perf_counter(), require_accelerator=False,
                         bench=bench, conf=small_conf(),
                         work=small_moonlight_work(), out=out)
    finally:
        peaks.CHIP_PEAKS.clear()
        peaks.CHIP_PEAKS.update(saved)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def stick_the_pages(monkeypatch):
    """The fault: a paged decode step that leaves the latent pages as they
    were, so no decoded token is ever seen again."""
    from repro.models.model import Model
    real = Model.decode_step_paged

    def stuck(self, params, token, pool, page_table, pos):
        h, _ = real(self, params, token, pool, page_table, pos)
        return h, pool

    monkeypatch.setattr(Model, "decode_step_paged", stuck)


@pytest.mark.parametrize("fault", [None, stick_the_pages],
                         ids=["sound", "stuck-pages"])
def test_moonlight_cell_run(monkeypatch, fault):
    if fault is not None:
        fault(monkeypatch)
    res = run_moonlight()
    assert res["correct"] is (fault is None)
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["check"]) == {"token_gap", "route_gap"}


def test_new_cells_keep_their_traffic():
    """The MoE cell fills a 128-wide batch of up to 768 positions, with
    pages for every row at its longest."""
    moon = copy.deepcopy(system.load_workload("moonlight-l2s-batch"))
    assert system.max_len(moon) == 768
    assert moon["pool_pages"] >= moon["width"] * 768 // moon["page_size"]
