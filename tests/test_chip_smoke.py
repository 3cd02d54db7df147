"""chip_smoke.py's phases, run in process on the CPU at the reduced config.

Without a TPU the script rehearses every phase and must then exit non-zero
with no result line; a run that passes here has driven the launcher's
train → fit → scheduler → check path end to end. The compile-cache helper
the entry points share is checked here too.
"""
import importlib.util
from pathlib import Path

import jax
import pytest

from repro.utils.compile_cache import DEFAULT_DIR, ENV_VAR, \
    enable_compile_cache

ROOT = Path(__file__).resolve().parents[1]


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("argv,checked", [
    ([], "screened-pallas vs exact on"),
    (["--four-chips"], "screened-sharded vs screened-pallas:"),
], ids=["one-chip", "four-chips"])
def test_no_tpu_rehearses_then_exits_nonzero(argv, checked, capsys,
                                             isolated_compile_cache):
    if argv and jax.device_count() < 4:
        pytest.skip("the four-chip rehearsal needs 4 host devices")
    rc = _chip_smoke().main(argv)
    out = capsys.readouterr().out
    assert rc != 0
    assert '"ok"' not in out
    assert not out.strip().splitlines()[-1].startswith("{")
    # every phase ran at the reduced config and its checks passed
    assert "nmt-deen-lstm-reduced" in out
    assert checked in out
    assert "rehearsal passed" in out


def test_compile_cache_helper_placement(monkeypatch, isolated_compile_cache):
    # set from outside: JAX's own reading stands, the helper places nothing
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(isolated_compile_cache)
    assert jax.config.jax_compilation_cache_dir == before
    # unset: one fixed, git-ignored directory inside the checkout
    monkeypatch.delenv(ENV_VAR)
    path = enable_compile_cache()
    assert path == str(ROOT / ".jax_cache") == str(DEFAULT_DIR)
    assert jax.config.jax_compilation_cache_dir == path
    assert enable_compile_cache() == path
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
