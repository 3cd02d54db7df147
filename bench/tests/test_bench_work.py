"""Work counts by hand, the table of peaks, and the refusal to measure
anything but the chip."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import peaks, system, work

LSTM = system.load_family("lstm")

ROOT = Path(__file__).resolve().parents[2]


@pytest.mark.parametrize("d,layers,vocab,r,lbar,trunk_macs,exact_macs,l2s_macs", [
    # DE-EN: 2·(500·2000 + 500·2000) = 4M trunk MACs; 25,000·500 = 12.5M
    (500, 2, 25_000, 50, 1024, 4_000_000, 12_500_000, (50 + 1024) * 500),
    # PTB-Large: 2·2·1500·6000 = 36M trunk MACs; 10,000·1500 = 15M;
    # a 512-word screen: (50 + 512)·1500 = 843,000
    (1500, 2, 10_000, 50, 512, 36_000_000, 15_000_000, 843_000),
])
def test_macs_by_hand(d, layers, vocab, r, lbar, trunk_macs, exact_macs,
                      l2s_macs):
    conf = {"d_model": d, "num_layers": layers}
    assert LSTM.trunk_flops(conf, [0]) == 2 * trunk_macs
    # the LSTM's trunk costs the same at every position
    assert LSTM.trunk_flops(conf, [0, 7, 4095]) == 3 * 2 * trunk_macs
    assert work.head_macs("exact", vocab, d) == exact_macs
    assert work.head_macs("screened-pallas", vocab, d, r, lbar) == l2s_macs


def test_model_flops_counts_each_trunk_step_once():
    # 2 requests: prompts 10 + 6 tokens, 4 + 3 outputs; the first output of
    # each comes from the prefill, so 16 + (7 - 2) trunk steps
    conf = {"d_model": 500, "num_layers": 2}
    prompts = np.concatenate([np.arange(10), np.arange(6)])
    decodes = np.array([10, 11, 12, 6, 7])
    f = work.model_flops(LSTM, conf, 1000.0, prompts, decodes,
                         output_tokens=7)
    assert f == 21 * 8_000_000 + 7 * 2 * 1000.0


def test_fused_topk_work_and_roofline():
    flops, nbytes = work.fused_topk_work(16, 1024, 500)
    assert flops == 2 * 16 * 1024 * 500 and nbytes == 4 * 16 * 1024 * 500
    t, bound = peaks.least_time_s(flops, nbytes, "TPU v5 lite")
    assert bound == "memory" and t == pytest.approx(nbytes / 819e9)
    t, bound = peaks.least_time_s(1e15, 1.0, "TPU v5 lite")
    assert bound == "compute" and t == pytest.approx(1e15 / 197e12)


def test_mean_candidate_words():
    assert work.mean_candidate_words([8, 0, 4], 128) == 4 * 128


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.chip_peaks("TPU v9 imaginary")


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "deen-l2s-poisson",
         "--seed", str(2 ** 31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_alone_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""
