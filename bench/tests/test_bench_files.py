"""Every name in BENCHMARK.json has its file, and the file says what the
name promises: a configuration that matches the program's registry, a
traffic mix the generator reads, a per-layer reader with ``read``."""
from __future__ import annotations

import json
import re

import pytest

from bench import harness, system, traffic

BENCH = harness.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
CONFIGS = [c["name"] for c in BENCH["configs"]]
METRICS = [m["name"] for m in BENCH["per_layer"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert BENCH["command"][1].startswith("bench/")
    assert 1 <= BENCH["run_seconds"] <= 51
    names = CELLS + CONFIGS + [m["name"] for m in BENCH["end_to_end"]] \
        + METRICS
    for group in (CELLS, CONFIGS, METRICS):
        assert len(set(group)) == len(group)
    for n in names:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    setup = [m for m in BENCH["end_to_end"] if m["name"] == "setup_s"]
    assert setup and setup[0]["bound"] <= 0.25


@pytest.mark.parametrize("name", CONFIGS)
def test_config_file_matches_the_program(name):
    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    assert entry["file"] == f"bench/configs/{name}.json"
    conf = system.load_config(name)
    assert conf["name"] == name and conf["reduced"] == entry["reduced"]
    system.model_config(conf)          # raises on any number that differs
    assert any(w["config"] == name for w in BENCH["workloads"])


@pytest.mark.parametrize("cell", CELLS)
def test_workload_file_loads(cell):
    entry = harness.cell_entry(BENCH, cell)
    work = system.load_workload(entry["traffic"])
    assert work["config"] == entry["config"] and entry["chips"] == 1
    assert system.max_len(work) % work["page_size"] == 0
    planned = traffic.plan(work, traffic.Corpus(64, 1), 5,
                           work["ramp_s"] + BENCH["run_seconds"])
    if isinstance(planned, traffic.ClosedPlan):
        assert planned.clients <= work["width"]
        planned = [planned.request(c, k) for k in range(3)
                   for c in range(planned.clients)]
    assert planned and all(
        work["prompt_len"]["min"] <= len(p.prompt) <= work["prompt_len"]["max"]
        for p in planned)
    assert set(work["check"]["limits"]) == (
        {"token_gap"} if work["head"] == "exact"
        else {"token_gap", "route_gap"})


@pytest.mark.parametrize("cell", CELLS)
def test_cell_reports_enough(cell):
    e2e = {m["name"] for m in BENCH["end_to_end"]
           if harness.reports(m, cell)}
    assert "setup_s" in e2e and len(e2e) >= 2
    per = [m for m in BENCH["per_layer"] if harness.reports(m, cell, e2e)]
    assert per and all(m["moves"] in e2e for m in per)


@pytest.mark.parametrize("name", METRICS)
def test_metric_reader_exists(name):
    m = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert callable(harness.load_reader(name))
    assert set(m.get("workloads", [])) <= set(CELLS)
    assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
    if name.endswith(".batch"):
        assert m["moves"] == "tokens_per_s"


def test_a_missing_file_fails(tmp_path, monkeypatch):
    monkeypatch.setattr(system, "WORKLOAD_DIR", tmp_path)
    with pytest.raises(FileNotFoundError):
        system.load_workload(CELLS[0])
    monkeypatch.setattr(harness, "METRICS_DIR", tmp_path)
    with pytest.raises(FileNotFoundError):
        harness.load_reader(METRICS[0])
    (tmp_path / "x.json").write_text("{")
    monkeypatch.setattr(system, "CONFIG_DIR", tmp_path)
    with pytest.raises(json.JSONDecodeError):
        system.load_config("x")
