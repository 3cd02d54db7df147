"""Whole runs of reduced cells on the CPU, past the harness's look for a
chip: a sound run is correct; the control and each fault a served cell can
have are not.

Faults, each planted in the timed path underneath the harness, on an open
and on the closed loop:
  * a decode step that returns its recurrent state unchanged;
  * a token altered where it is produced (after the head, before commit).
Each control, the reference computed at a lower precision (float8 weights,
or bfloat16 throughout) in the program's place, is read on the served
prompts and tokens of a sound run.
"""
from __future__ import annotations

import numpy as np
import pytest

from bench import control, harness, reference
from bench.tests.helpers import CPU_LIMIT, run_small, small_conf, small_work

CELLS = ["deen-l2s-poisson", "ptbl-l2s-nextword", "deen-l2s-batch"]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    res = run_small(cell)
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    assert {"setup_s", "tokens_per_s"} == set(res["metrics"])
    assert list(res)[-1] == "check"
    assert all(d["value"] <= d["limit"] for d in res["check"].values())


def test_traced_run_reports_per_layer_metrics():
    res = run_small("deen-l2s-poisson", trace=1)
    assert res["correct"] is True
    # the CPU's trace has no TPU plane: the trace's readers return nothing
    assert {"latency_p95_s.overload", "queue_wait_p95_s", "join_ms",
            "tick_ms"} == set(res["metrics"])


def stick_the_state(monkeypatch):
    from repro.models.model import Model
    real = Model.decode_step

    def stuck(self, params, token, cache, pos, window=None):
        h, _ = real(self, params, token, cache, pos, window)
        return h, cache

    monkeypatch.setattr(Model, "decode_step", stuck)


def alter_a_token(monkeypatch):
    from repro.serving.kvpool import stream
    real = stream.guard_tokens

    def altered(injector, site, head, tokens, vocab, rows=None):
        out = np.array(real(injector, site, head, tokens, vocab, rows=rows))
        out.reshape(-1)[0] = (out.reshape(-1)[0] + 1) % vocab
        return out

    monkeypatch.setattr(stream, "guard_tokens", altered)


def test_state_left_unchanged_is_caught(monkeypatch):
    stick_the_state(monkeypatch)
    assert run_small("deen-l2s-poisson")["correct"] is False


def test_altered_token_is_caught(monkeypatch):
    alter_a_token(monkeypatch)
    assert run_small("ptbl-l2s-nextword")["correct"] is False


@pytest.mark.parametrize("fault", [stick_the_state, alter_a_token],
                         ids=["state", "token"])
def test_closed_loop_faults_are_caught(monkeypatch, fault):
    fault(monkeypatch)
    assert run_small("deen-l2s-batch")["correct"] is False


@pytest.mark.parametrize("precision", reference.CONTROLS)
def test_control_fails_where_the_program_passes(precision):
    """The control (the reference at a lower precision, put in the
    program's place) judged by ``check.judge`` against the same limits, on
    the served prompts and tokens of a reduced run."""
    out = control.one_seed(small_conf(), small_work("deen-l2s-poisson"),
                           2 ** 31 + 8, 1.0, harness.log, precision)
    assert out["program_correct"] is True
    assert out["control_correct"] is False
