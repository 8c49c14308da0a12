"""The general traffic generator: reads a mix's data file and drives the
program with it.

A mix runs whole ``partition()`` calls back to back, each on a fresh host
graph object, so no per-graph cache carries over from one call to the next.
The calls come in rounds: each round partitions the graph once with every
seed of the mix's ``partition_seeds``, in an order drawn from ``--seed``.
So every run does the same work, whatever its ``--seed``, in another order,
and the window always holds whole rounds.

The program builds some executables for exact sizes (its coarsest level),
which differ from one partition seed to the next, so set-up runs one round:
it builds (or loads from the persistent cache) every shape the window
meets.  The generator knows the program only through ``prog`` (see
:func:`program`), which the tests replace to plant faults.
"""

from __future__ import annotations

import gc
import statistics
import sys
import time
import traceback
from dataclasses import dataclass, field
from types import SimpleNamespace
from typing import List, Optional

import numpy as np

from . import reference as ref
from .data.graphs import Csr

__all__ = ["Window", "Offline", "program"]


def program() -> SimpleNamespace:
    """The system under test: its entry point and input types."""
    from repro.core import PartitionerConfig, partition
    from repro.graph.csr import GraphNP

    return SimpleNamespace(partition=partition, PartitionerConfig=PartitionerConfig,
                           GraphNP=GraphNP)


@dataclass
class Window:
    t0: float = 0.0
    t1: float = 0.0
    attempted: int = 0
    failed: int = 0
    answers: List[dict] = field(default_factory=list)

    @property
    def elapsed(self) -> float:
        return self.t1 - self.t0


class Offline:
    units = "partitions"

    def __init__(self, prog, g: Csr, config: dict, traffic: dict, seed: int):
        self.prog, self.g, self.config = prog, g, config
        self.k, self.eps = int(config["k"]), float(config["eps"])
        self.seeds = [int(x) for x in traffic["partition_seeds"]]
        self.rng = np.random.default_rng([int(seed), 2])

    def _round(self) -> List[int]:
        """The next round: every partition seed once, in a drawn order."""
        return [self.seeds[i] for i in self.rng.permutation(len(self.seeds))]

    def _call(self, s: int):
        g = self.g
        fresh = self.prog.GraphNP(indptr=g.indptr, indices=g.indices, ew=g.ew,
                                  nw=g.nw)
        return self.prog.partition(
            fresh, self.prog.PartitionerConfig(k=self.k, eps=self.eps, seed=s))

    def setup(self) -> None:
        for s in self._round():
            self._call(s)

    def run(self, seconds: Optional[float] = None, rounds: Optional[int] = None) -> Window:
        """Whole rounds back to back until ``rounds`` are done, or until the
        first round that ends after ``seconds``."""
        w = Window(t0=time.perf_counter())
        done = 0
        while True:
            for s in self._round():
                w.attempted += 1
                try:
                    rep = self._call(s)
                    w.answers.append(dict(seed=s, labels=np.asarray(rep.labels),
                                          cut=float(rep.cut)))
                except Exception:       # an answer that never comes
                    w.failed += 1
                    print("bench: partition() raised:", file=sys.stderr)
                    traceback.print_exc(file=sys.stderr)
            w.t1 = time.perf_counter()
            done += 1
            if rounds is not None and done >= rounds:
                break
            if rounds is None and w.elapsed >= seconds:
                break
        return w

    def end_to_end(self, w: Window) -> dict:
        if not w.answers:
            return {}
        total = float(self.g.ew.astype(np.float64).sum() / 2.0)
        return dict(
            partition_s=w.elapsed / len(w.answers),
            cut_share=100.0 * statistics.fmean(a["cut"] for a in w.answers) / total,
        )

    def checks(self, w: Window) -> List[ref.Check]:
        g = self.g
        ref_lab = ref.reference_partition(g.indptr, g.indices, g.ew, g.nw,
                                          self.k, self.eps, ref.SEED)
        src = g.arc_sources()
        fwd = src < g.indices
        ref_cut = ref.cut(src[fwd], g.indices[fwd], g.ew[fwd], ref_lab)
        limit = float(self.config["limits"]["kept_ratio"])
        return ref.merge_checks(
            ref.offline_checks(g, a["labels"], a["cut"], self.k, self.eps,
                               ref_cut, limit)
            for a in w.answers)

    def free(self, w: Window) -> None:
        gc.collect()
