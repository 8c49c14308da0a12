"""The comparison that decides ``correct``: sound runs pass, the control and
every planted fault fail.  Runs whole cells on the CPU at a small size, with
the harness's look for a chip skipped."""

import json
import time

import pytest

from bench import drive, faults, harness
from bench.harness import BENCH

SEED = 3_000_000_017        # more than 32 signed bits hold
CELL = "rgg-mesh.offline"


def _config(name, scale):
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    return dict(cfg, scale=scale)


def _run(prog, config, scale, seconds=0.5, trace=False):
    """A run of the cell at a small ``scale`` of the deployment ``config``
    (the R-MAT deployment has no cell of its own yet: PERF.md, section 7)."""
    traffic = json.loads((BENCH / "traffic" / "offline.json").read_text())
    return harness.run_cell(CELL, SEED, seconds, trace, t_start=time.perf_counter(),
                            prog=prog, require_tpu=False,
                            config=_config(config, scale), traffic=traffic)


@pytest.fixture(scope="module")
def prog():
    return drive.program()


@pytest.mark.parametrize("config,scale", [("rmat-web", 9), ("rgg-mesh", 9)])
def test_offline_sound_run_is_correct(prog, config, scale):
    out = _run(prog, config, scale)
    assert out["correct"], out["checks"]
    seeds = json.loads((BENCH / "traffic" / "offline.json").read_text())["partition_seeds"]
    assert out["attempted"] % len(seeds) == 0 and out["failed"] == 0   # whole rounds
    assert set(out["metrics"]) == {"partition_s", "cut_share", "setup_s"}
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == {"bad_labels", "overload", "cut_gap", "kept_ratio"}


# "unchanged" never calls the program, so it runs at each deployment's own
# size, where its kept_ratio limit was set
@pytest.mark.parametrize("kind,fails,config,scale", [
    ("unchanged", "kept_ratio", "rgg-mesh", 15),
    ("unchanged", "kept_ratio", "rmat-web", 14),
    ("half", "cut_gap", "rmat-web", 9),
    ("altered", "cut_gap", "rmat-web", 9),
])
def test_offline_faults_are_caught(prog, kind, fails, config, scale):
    out = _run(faults.offline_fault(prog, kind), config, scale)
    assert not out["correct"]
    c = out["checks"][fails]
    assert c["value"] > c["limit"], out["checks"]


def test_offline_control_is_caught(prog):
    out = _run(faults.control(prog), "rgg-mesh", 10)
    assert not out["correct"]
    assert out["checks"]["overload"]["value"] > 0
