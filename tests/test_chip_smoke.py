"""chip_smoke.py rehearsed on the CPU, and the compile-cache helper.

The smoke script's phases run in this process at a small scale: every phase
check must hold, and the verdict must stay ``ok: false`` because the
platform is not a TPU.  Scale 12 (4,096 nodes) is the smallest R-MAT scale
at which the default engine choice (``numpy_below=4096``) puts the finest
level on the device path, which the partition phase checks for.
"""

import importlib.util
import json
import os
from pathlib import Path

import pytest

import jax

from repro import compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_phases_pass_on_cpu_with_false_verdict(chip_smoke, capsys):
    out = chip_smoke.run(scale=12)
    assert out["on_tpu"] is False
    assert out["device"]["platform"] == "cpu"
    part, serve, kernel = out["partition"], out["serve"], out["kernel"]
    assert part["cut"] < part["hash_cut"]
    assert part["imbalance"] <= chip_smoke.EPS
    assert serve["audit_ok"] and serve["cut"] == serve["host_cut"]
    assert kernel["max_abs_diff"] == 0.0 and kernel["interpret"] is True
    assert out["capacity"]["fits"] is None  # the CPU reports no limit
    # per-layer breakdown: (calls, wall seconds, compile seconds) per span
    assert part["spans"]["vcycle.sweep"][0] > 0
    assert part["spans"]["vcycle.contract"][0] > 0
    assert serve["spans"]["session.update"][0] == chip_smoke.SERVE_BATCHES
    logged = capsys.readouterr().out
    for tag in ("[partition]", "[partition spans]", "[serve]",
                "[serve spans]", "[kernel]"):
        assert tag in logged


def test_default_size_refused_without_accelerator(chip_smoke, capsys):
    assert chip_smoke.main([]) == 1
    assert capsys.readouterr().out == ""


def test_failed_check_raises(chip_smoke):
    with pytest.raises(chip_smoke.SmokeFailure, match="hash cut"):
        chip_smoke.check(False, "cut 10 not below hash cut 5")


def test_verdict_line_is_last_and_false_on_cpu(chip_smoke, capsys, monkeypatch):
    monkeypatch.setattr(chip_smoke, "run", lambda scale, chips: dict(
        on_tpu=False, device=chip_smoke.device_info()))
    monkeypatch.setattr(compile_cache, "enable", lambda: "unused")
    assert chip_smoke.main(["--scale", "12"]) == 1
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {
        "ok": False,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }


@pytest.mark.parametrize("env", [None, "/srv/cache/jax"])
def test_compile_cache_dir_is_fixed(monkeypatch, env):
    if env is None:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        expect = str(ROOT / ".jax_cache")
    else:
        monkeypatch.setenv(compile_cache.ENV_VAR, env)
        expect = env
    assert compile_cache.cache_dir() == expect
    assert compile_cache.cache_dir() == expect  # same answer on every call
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable() == expect
        # with the variable set, JAX reads it itself: nothing is configured
        want = before if env is not None else expect
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert os.path.isabs(expect)
