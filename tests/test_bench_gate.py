"""Continuous perf-regression gate (PR 10).

Unit tests for ``benchmarks/history.py`` (trajectory loading, min-of-window
baselines, signature-aware comparison) plus the end-to-end gate: a
``--smoke --check-regression`` run must pass against its own recorded
baseline and must *fail* (exit nonzero) when a synthetic 2.5x slowdown is
injected into the recorded latencies — the gate is exercised in both
directions inside the default suite.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

import history  # noqa: E402  (benchmarks/history.py)


def _row(name, us, graph="ba-1024", n=1024, m=6138, k=4, **extra):
    d = dict(graph=graph, n=n, m=m, k=k)
    d.update(extra)
    return dict(name=name, us_per_call=us, derived=d)


def _bundle(us_steady, us_thr, **sig):
    return {
        "dynamic_hot": [
            _row("dynamic_hot_steady", us_steady, **sig),
            _row("dynamic_hot_throughput", us_thr, **sig),
        ],
        "_trajectory_delta": {"rows": []},   # metadata key: must be skipped
    }


# ------------------------------------------------------------------- units


def test_load_history_orders_by_pr_number(tmp_path):
    for pr, us in ((10, 30.0), (2, 10.0), (9, 20.0)):
        (tmp_path / f"BENCH_PR{pr}.json").write_text(
            json.dumps(_bundle(us, us)))
    (tmp_path / "BENCH_notes.json").write_text("{}")     # no PR number
    (tmp_path / "BENCH_PR3.json").write_text("not json")  # corrupt: skipped
    hist = history.load_history(str(tmp_path))
    assert [pr for pr, _, _ in hist] == [2, 9, 10]


def test_derive_baselines_min_of_recent_window(tmp_path):
    # series 100, 40, 80, 60 -> window of 3 sees (40, 80, 60) -> baseline 40
    for pr, us in ((1, 100.0), (2, 40.0), (3, 80.0), (4, 60.0)):
        (tmp_path / f"BENCH_PR{pr}.json").write_text(
            json.dumps(_bundle(us, us)))
    base = history.derive_baselines(history.load_history(str(tmp_path)))
    rec = base[("dynamic_hot", "dynamic_hot_steady")]
    assert rec["baseline_us"] == 40.0
    assert rec["window"] == 3
    assert [v for _, v in rec["series"]] == [100.0, 40.0, 80.0, 60.0]
    assert "graph=ba-1024" in rec["signature"]
    # the metadata table never becomes a baseline
    assert not any(t == "_trajectory_delta" for t, _ in base)


def test_check_regression_statuses(tmp_path):
    (tmp_path / "BENCH_PR1.json").write_text(json.dumps(_bundle(100.0, 100.0)))
    base = history.derive_baselines(history.load_history(str(tmp_path)))
    results = {
        "dynamic_hot": [
            _row("dynamic_hot_steady", 120.0),           # 1.2x: ok
            _row("dynamic_hot_throughput", 300.0),       # 3.0x: regression
            _row("brand_new_row", 50.0),                 # no baseline: new
        ],
        "_trajectory_delta": {"rows": []},               # skipped
    }
    rep = history.check_regression(results, base, tolerance=1.75)
    by = {r["name"]: r for r in rep}
    assert by["dynamic_hot_steady"]["status"] == "ok"
    assert by["dynamic_hot_throughput"]["status"] == "regression"
    assert by["dynamic_hot_throughput"]["ratio"] == pytest.approx(3.0)
    assert by["brand_new_row"]["status"] == "new"
    # improvement direction
    rep = history.check_regression(
        {"dynamic_hot": [_row("dynamic_hot_steady", 20.0)]}, base, 1.75)
    assert rep[0]["status"] == "improved"


def test_signature_mismatch_is_incomparable_not_gated(tmp_path):
    """A --smoke run (ba-1024) must never gate against the recorded
    full-size trajectory (ba-16384) — measured, reported, not compared."""
    (tmp_path / "BENCH_PR1.json").write_text(json.dumps(
        _bundle(100.0, 100.0, graph="ba-16384", n=16384, m=98148)))
    base = history.derive_baselines(history.load_history(str(tmp_path)))
    rep = history.check_regression(
        {"dynamic_hot": [_row("dynamic_hot_steady", 10_000.0)]}, base, 1.75)
    assert rep[0]["status"] == "incomparable"
    assert rep[0]["ratio"] is None
    txt = history.format_report(rep)
    assert "gate passed" in txt and "GATE FAILED" not in txt


def test_format_report_flags_failures(tmp_path):
    (tmp_path / "BENCH_PR1.json").write_text(json.dumps(_bundle(100.0, 100.0)))
    base = history.derive_baselines(history.load_history(str(tmp_path)))
    rep = history.check_regression(
        {"dynamic_hot": [_row("dynamic_hot_steady", 500.0)]}, base, 1.75)
    txt = history.format_report(rep, 1.75)
    assert "GATE FAILED" in txt
    assert "regression=1" in txt


# ------------------------------------------------------------- end to end


def _run_bench(extra_args, tmp, env_extra=None, json_name=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src") + os.pathsep + env.get(
        "PYTHONPATH", "")
    # every run compiles afresh, as the recorded history did: a persistent
    # cache filled by an earlier run would move compiles out of the timed
    # windows of later runs only
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    if env_extra:
        env.update(env_extra)
    cmd = [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
           "dynamic_hot", "--smoke"]
    if json_name:
        cmd += ["--json", os.path.join(tmp, json_name)]
    cmd += extra_args
    return subprocess.run(cmd, capture_output=True, text=True, timeout=300,
                          env=env, cwd=ROOT)


def test_gate_end_to_end_passes_then_catches_injected_slowdown(tmp_path):
    """Three smoke runs: (1) record a baseline bundle, (2) gate a fresh run
    against it — must pass and embed ``_trajectory_delta``, (3) gate a much
    slower run — must exit nonzero with the slow rows flagged
    ``regression``.

    Run-to-run CPU noise on the tiny smoke graph can exceed the 1.75x
    tolerance on its own (min of 2 batches, shared machine), so the
    injection hook sets the *spread* deterministically instead of trusting
    the clock: the baseline records with a 3x injected slowdown (honest
    run vs inflated baseline -> ratio ~1/3, "improved", never gated) and
    the failing run injects 10x (ratio ~10/3 vs that baseline — a >1.75x
    regression unless the machine sped up ~2x mid-test)."""
    tmp = str(tmp_path)
    hist_dir = os.path.join(tmp, "hist")
    os.makedirs(hist_dir)

    # (1) baseline recording (inflated 3x via the injection hook)
    out = _run_bench([], tmp, json_name="base.json",
                     env_extra={"REPRO_BENCH_INJECT_SLOWDOWN": "3.0"})
    assert out.returncode == 0, out.stderr[-2000:]
    shutil.copy(os.path.join(tmp, "base.json"),
                os.path.join(hist_dir, "BENCH_PR1.json"))

    # (2) honest re-run gates clean (smoke-vs-smoke signatures match)
    out = _run_bench(["--check-regression", "--history", hist_dir], tmp,
                     json_name="pass.json")
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-2000:])
    assert "trajectory delta" in out.stdout
    assert "gate passed" in out.stdout
    with open(os.path.join(tmp, "pass.json")) as f:
        bundle = json.load(f)
    delta = bundle["_trajectory_delta"]
    assert delta["rows"], "gate embedded no trajectory delta rows"
    assert {"BENCH_PR1.json"} == set(delta["history_bundles"])
    assert all(r["status"] != "regression" for r in delta["rows"])
    assert any(r["status"] in ("ok", "improved") for r in delta["rows"])

    # (3) a slowdown past the tolerance trips the gate
    out = _run_bench(["--check-regression", "--history", hist_dir], tmp,
                     env_extra={"REPRO_BENCH_INJECT_SLOWDOWN": "10.0"})
    assert out.returncode != 0, "gate did not fail on the slowdown"
    assert "GATE FAILED" in out.stdout
    flagged = [ln for ln in out.stdout.splitlines()
               if ln.rstrip().endswith("regression")]
    assert any("dynamic_hot_steady" in ln for ln in flagged), flagged
