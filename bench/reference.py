"""Plain reference of the partitioner's semantics, and the comparison that
decides ``correct``.

Nothing here imports the program or takes anything it made: cuts, block
weights and the balance bound are recomputed from the labels and the
benchmark's own copy of the graph, and the quality yardstick is a plain
numpy partitioner (the best of a few runs of BFS-grown blocks refined by
synchronous size-constrained label propagation).

Every number compared is "lower is better" and passes when it is at most
its limit.  Exact numbers have the limit 0: labels out of range, weight
above the balance bound ``L_max = (1 + eps) * ceil(c(V) / k)``, the gap
between a reported and a recomputed cut.  ``kept_ratio`` is the reference's
uncut edge weight over the program's: its limit comes from the
configuration file, set from measured readings (PERF.md).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Iterable, List, Sequence

import numpy as np

__all__ = [
    "SEED", "Check", "bfs_order", "block_weights", "cut", "lmax",
    "merge_checks", "offline_checks", "reference_partition",
]


# The quality yardstick's own seed, fixed: a reference whose cut moved with
# the run's seed would move both readings of ``kept_ratio`` (PERF.md).
SEED = 1


@dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value)) and self.value <= self.limit


def merge_checks(rows: Iterable[Sequence[Check]]) -> List[Check]:
    """The worst reading of each number over several answers."""
    worst: Dict[str, Check] = {}
    for row in rows:
        for c in row:
            w = worst.get(c.name)
            if w is None or not (c.value <= w.value):   # NaN is worst
                worst[c.name] = c
    return list(worst.values())


def lmax(total_weight: float, k: int, eps: float) -> float:
    return (1.0 + eps) * float(np.ceil(total_weight / k))


def cut(src: np.ndarray, dst: np.ndarray, w: np.ndarray, labels: np.ndarray) -> float:
    """Weight of the undirected edges (u < v) whose ends lie in two blocks."""
    lab = np.asarray(labels, dtype=np.int64)
    return float(np.asarray(w, dtype=np.float64)[lab[src] != lab[dst]].sum())


def block_weights(labels: np.ndarray, nw: np.ndarray, k: int) -> np.ndarray:
    return np.bincount(labels, weights=nw, minlength=k)[:k]


def _bad_labels(labels, n: int, k: int) -> int:
    lab = np.asarray(labels).reshape(-1)
    if lab.shape[0] != n:
        return max(n, lab.shape[0])
    return int(np.count_nonzero((lab < 0) | (lab >= k)))


def _balance(labels, nw, k: int, eps: float) -> float:
    bw = block_weights(np.asarray(labels, np.int64), nw, k)
    return max(0.0, float(bw.max()) - lmax(float(nw.sum()), k, eps))


# ---------------------------------------------------------------- offline


def bfs_order(indptr: np.ndarray, indices: np.ndarray, seed: int) -> np.ndarray:
    """Nodes in breadth-first order from random starts, one component after
    another; isolated nodes last."""
    n = indptr.shape[0] - 1
    deg = np.diff(indptr)
    seen = deg == 0
    parts = []
    for s in np.random.default_rng(seed).permutation(np.flatnonzero(~seen)):
        if seen[s]:
            continue
        seen[s] = True
        front = np.array([s], dtype=np.int64)
        while front.size:
            parts.append(front)
            lens = deg[front]
            off = np.cumsum(lens) - lens
            idx = np.repeat(indptr[front] - off, lens) + np.arange(lens.sum())
            nb = np.unique(indices[idx])
            nb = nb[~seen[nb]]
            seen[nb] = True
            front = nb.astype(np.int64)
    parts.append(np.flatnonzero(deg == 0))
    order = np.concatenate(parts)
    assert order.shape[0] == n
    return order


def _grow_and_refine(indptr, indices, ew, nw, k: int, eps: float,
                     seed: int, rounds: int) -> np.ndarray:
    """Plain size-constrained partition: blocks of equal weight cut from a
    BFS order, then ``rounds`` synchronous label-propagation rounds in which
    a random half of the nodes may move to the neighbouring block they are
    most connected to, admitted by gain while the target block stays within
    ``L_max`` (weights at the start of the round, so no block ever exceeds
    it)."""
    n = indptr.shape[0] - 1
    nw = np.asarray(nw, np.float64)
    L = lmax(nw.sum(), k, eps)
    order = bfs_order(indptr, indices, seed)
    cum = np.cumsum(nw[order]) - 0.5 * nw[order]
    lab = np.empty(n, np.int64)
    lab[order] = np.minimum((cum * k / nw.sum()).astype(np.int64), k - 1)
    src = np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr))
    dst = np.asarray(indices, np.int64)
    w = np.asarray(ew, np.float64)
    rows = np.arange(n)
    rng = np.random.default_rng(seed + 1)
    for _ in range(rounds):
        conn = np.bincount(src * k + lab[dst], weights=w,
                           minlength=n * k).reshape(n, k)
        own = conn[rows, lab]
        conn[rows, lab] = -1.0
        best = conn.argmax(1)
        gain = conn[rows, best] - own
        cand = np.flatnonzero((gain > 0) & (rng.random(n) < 0.5))
        if cand.size == 0:
            continue
        cand = cand[np.lexsort((-gain[cand], best[cand]))]
        tgt = best[cand]
        cw = np.cumsum(nw[cand])
        first = np.flatnonzero(np.r_[True, tgt[1:] != tgt[:-1]])
        grp = np.repeat(first, np.diff(np.r_[first, cand.size]))
        inflow = cw - cw[grp] + nw[cand[grp]]
        room = L - block_weights(lab, nw, k)
        ok = inflow <= room[tgt]
        lab[cand[ok]] = tgt[ok]
    return lab


def reference_partition(indptr, indices, ew, nw, k: int, eps: float,
                        seed: int, restarts: int = 4, rounds: int = 24) -> np.ndarray:
    """The lowest-cut partition of ``restarts`` plain runs (BFS-grown blocks,
    synchronous size-constrained label propagation), seeded from ``seed``.
    One run's cut swings with its BFS starts; the best of a few is a steady
    yardstick."""
    src = np.repeat(np.arange(indptr.shape[0] - 1, dtype=np.int64), np.diff(indptr))
    fwd = src < indices
    best, best_cut = None, np.inf
    for s in np.random.default_rng(seed).integers(1 << 30, size=restarts):
        lab = _grow_and_refine(indptr, indices, ew, nw, k, eps, int(s), rounds)
        c = cut(src[fwd], indices[fwd], ew[fwd], lab)
        if c < best_cut:
            best, best_cut = lab, c
    return best


def offline_checks(g, labels, reported_cut: float, k: int, eps: float,
                   ref_cut: float, kept_limit: float) -> List[Check]:
    """Numbers of one offline answer: a partition of ``g`` (the benchmark's
    CSR) with the cut the program reported for it."""
    n = g.n
    bad = _bad_labels(labels, n, k)
    src = g.arc_sources()
    fwd = src < g.indices
    total = float(g.ew[fwd].astype(np.float64).sum())
    if bad:
        return [Check("bad_labels", bad, 0), Check("overload", np.inf, 0),
                Check("cut_gap", np.inf, 0), Check("kept_ratio", np.inf, kept_limit)]
    lab = np.asarray(labels, np.int64)
    c = cut(src[fwd], g.indices[fwd], g.ew[fwd], lab)
    kept = total - c
    return [
        Check("bad_labels", 0, 0),
        Check("overload", _balance(lab, g.nw, k, eps), 0),
        Check("cut_gap", abs(float(reported_cut) - c), 0),
        Check("kept_ratio", (total - ref_cut) / kept if kept > 0 else np.inf,
              kept_limit),
    ]
