"""The benchmark's own copies of the generators, and the traffic generator's
rounds of partition seeds."""

import json

import numpy as np
import pytest

from bench import drive
from bench.data import graphs
from bench.harness import BENCH


def _same(a: graphs.Csr, b) -> None:
    np.testing.assert_array_equal(a.indptr, b.indptr)
    np.testing.assert_array_equal(a.indices, b.indices)
    np.testing.assert_array_equal(a.ew, b.ew)
    np.testing.assert_array_equal(a.nw, b.nw)
    assert (a.indptr.dtype, a.indices.dtype, a.ew.dtype) == (
        b.indptr.dtype, b.indices.dtype, b.ew.dtype)


@pytest.mark.parametrize("scale,seed", [(8, 0), (10, 1), (11, 7)])
def test_rmat_copy_equals_program(scale, seed):
    from repro.graph import rmat

    _same(graphs.rmat(scale, 16, 0.57, 0.19, 0.19, seed), rmat(scale, 16, seed=seed))


@pytest.mark.parametrize("scale,seed", [(8, 0), (11, 1)])
def test_rgg_copy_equals_program(scale, seed):
    from repro.graph import rgg

    _same(graphs.rgg(scale, 0.55, seed), rgg(scale, seed=seed))


@pytest.mark.parametrize("name", ["rmat-web", "rgg-mesh"])
def test_build_reads_config(name):
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    small = dict(cfg, scale=8)
    g = graphs.build(small, cfg["graph_seed"])
    assert g.n == 256 and g.m > 0
    assert np.all(g.indices[:-1] >= 0)


class _Recorder:
    """A program stand-in that records the seed of every partition() call."""

    def __init__(self):
        self.calls = []
        self.GraphNP = lambda **kw: kw
        self.PartitionerConfig = lambda **kw: kw

    def partition(self, g, cfg):
        self.calls.append(cfg["seed"])
        return type("Rep", (), dict(labels=np.zeros(1, np.int64), cut=0.0))()


def _offline(seed, seeds=(1, 2, 3)):
    cfg = json.loads((BENCH / "configs" / "rmat-web.json").read_text())
    g = graphs.build(dict(cfg, scale=6), 1)
    rec = _Recorder()
    return rec, drive.Offline(rec, g, cfg, {"partition_seeds": list(seeds)}, seed)


@pytest.mark.parametrize("seed", [0, 3_000_000_023])
def test_rounds_hold_every_partition_seed_once(seed):
    rec, d = _offline(seed)
    d.setup()
    w = d.run(rounds=4)
    assert w.attempted == 12 and len(w.answers) == 12
    calls = rec.calls
    for r in range(5):          # set-up's round, then the window's four
        assert sorted(calls[3 * r: 3 * r + 3]) == [1, 2, 3]


def test_round_order_is_drawn_from_the_run_seed():
    orders = []
    for seed in (5, 5, 6, 7, 8):
        rec, d = _offline(seed)
        d.run(rounds=3)
        orders.append(tuple(rec.calls))
    assert orders[0] == orders[1]
    assert len(set(orders)) > 2


def test_window_ends_on_a_round_boundary():
    rec, d = _offline(9)
    w = d.run(seconds=0.0)
    assert w.attempted == 3 and w.elapsed >= 0.0
