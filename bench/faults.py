"""The control and the planted faults that the comparison must catch.

Each function takes the program namespace of :func:`bench.drive.program`
and returns a copy with one thing broken underneath the timed path:

* :func:`control` -- the program run at twice the configuration's ``eps``:
  it breaks the stated balance guarantee, the step that would buy a lower
  cut (what a later change might be tempted to do);
* :func:`offline_fault` -- a step that returns its state unchanged (the
  initial assignment, never improved), half of the work left out (the cut
  taken over half of the nodes), and an answer altered where it is
  produced (one label moved).  (One chip: there is no exchange between
  chips to drop.)

``bench/control.py`` reads them on the chip at a cell's own size; the tests
run them at a small size and see ``correct`` come out false.
"""

from __future__ import annotations

import dataclasses
from types import SimpleNamespace

import numpy as np

from .reference import cut

__all__ = ["FAULTS", "control", "offline_fault"]

FAULTS = ("unchanged", "half", "altered")


def _with(prog, **kw) -> SimpleNamespace:
    return SimpleNamespace(**{**vars(prog), **kw})


def control(prog) -> SimpleNamespace:
    def doubled(cfg_cls):
        def make(*a, **kw):
            cfg = cfg_cls(*a, **kw)
            cfg.eps = 2.0 * cfg.eps
            return cfg
        return make

    return _with(prog, PartitionerConfig=doubled(prog.PartitionerConfig))


def _hub(g) -> int:
    return int(np.argmax(np.diff(np.asarray(g.indptr))))


def _cut(g, labels, rows=None) -> float:
    """The cut of ``labels``, over the arcs of the first ``rows`` nodes."""
    src = np.repeat(np.arange(g.n), np.diff(g.indptr))
    keep = (src < g.indices) & (src < (g.n if rows is None else rows))
    return cut(src[keep], g.indices[keep], g.ew[keep], labels)


def offline_fault(prog, kind: str) -> SimpleNamespace:
    part = prog.partition

    def partition(g, cfg):
        if kind == "unchanged":     # the initial assignment, never improved
            lab = (np.arange(g.n) % cfg.k).astype(np.int64)
            return SimpleNamespace(labels=lab, cut=_cut(g, lab))
        rep = part(g, cfg)
        if kind == "half":          # the cut taken over half of the nodes
            return dataclasses.replace(rep, cut=_cut(g, rep.labels, g.n // 2))
        lab = np.array(rep.labels, copy=True)   # "altered": one label moved
        h = _hub(g)
        lab[h] = (lab[h] + 1) % cfg.k
        return dataclasses.replace(rep, labels=lab)

    if kind not in FAULTS:
        raise ValueError(kind)
    return _with(prog, partition=partition)
