"""Graph generators of the benchmark's deployments, built on the host.

Copies of the R-MAT (Graph500 Kronecker) and random-geometric-graph
generators as the program had them when the benchmark was defined, so that
a change to the program cannot change the data it is measured on.  A test
holds them equal to ``repro.graph.rmat`` / ``repro.graph.rgg``.  Both return
plain CSR arrays (each undirected edge as two arcs, parallel edges merged
with their weights summed, self loops dropped, unit node weights).
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

__all__ = ["Csr", "build", "csr_from_edges", "rgg", "rmat"]


@dataclass(frozen=True)
class Csr:
    indptr: np.ndarray      # (n + 1,) int64
    indices: np.ndarray     # (m,) int32
    ew: np.ndarray          # (m,) float32
    nw: np.ndarray          # (n,) float32

    @property
    def n(self) -> int:
        return int(self.indptr.shape[0] - 1)

    @property
    def m(self) -> int:
        return int(self.indices.shape[0])

    def arc_sources(self) -> np.ndarray:
        return np.repeat(np.arange(self.n, dtype=np.int64), np.diff(self.indptr))


def csr_from_edges(n: int, u: np.ndarray, v: np.ndarray) -> Csr:
    """Symmetrize, drop self loops, merge parallel arcs (weights summed)."""
    u = np.asarray(u, dtype=np.int64)
    v = np.asarray(v, dtype=np.int64)
    keep = u != v
    u, v = u[keep], v[keep]
    uu = np.concatenate([u, v])
    vv = np.concatenate([v, u])
    key = np.sort(uu * np.int64(n) + vv)
    first = np.flatnonzero(np.concatenate([[True], key[1:] != key[:-1]]))
    weight = np.diff(np.append(first, key.shape[0])).astype(np.float32)
    key = key[first]
    src = key // n
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(src, minlength=n), out=indptr[1:])
    return Csr(indptr=indptr, indices=(key % n).astype(np.int32), ew=weight,
               nw=np.ones(n, dtype=np.float32))


def rmat_pairs(rng: np.random.Generator, scale: int, count: int,
               a: float, b: float, c: float) -> tuple[np.ndarray, np.ndarray]:
    """``count`` Kronecker (u, v) draws over ``2**scale`` ids, unpermuted."""
    u = np.zeros(count, dtype=np.int64)
    v = np.zeros(count, dtype=np.int64)
    ab = a + b
    a_norm = a / ab if ab > 0 else 0.5
    c_norm = c / (1.0 - ab) if ab < 1 else 0.5
    for _ in range(scale):
        u <<= 1
        v <<= 1
        go_down = rng.random(count) >= ab
        r2 = rng.random(count)
        u |= go_down.astype(np.int64)
        v |= np.where(go_down, r2 >= c_norm, r2 >= a_norm).astype(np.int64)
    return u, v


def rmat(scale: int, edge_factor: int, a: float, b: float, c: float,
         seed: int) -> Csr:
    """R-MAT graph with ``2**scale`` nodes and ``edge_factor * n`` draws;
    node ids are permuted so that degree does not follow the id."""
    rng = np.random.default_rng(seed)
    n = 1 << scale
    u, v = rmat_pairs(rng, scale, n * edge_factor, a, b, c)
    perm = rng.permutation(n)
    return csr_from_edges(n, perm[u], perm[v])


def rgg(scale: int, radius_coeff: float, seed: int) -> Csr:
    """``2**scale`` uniform points in the unit square, joined within
    ``radius_coeff * sqrt(ln n / n)`` (the paper's rggX), by a cell grid of
    side r so that each point meets only its neighbouring cells."""
    rng = np.random.default_rng(seed)
    n = 1 << scale
    pts = rng.random((n, 2))
    r = radius_coeff * np.sqrt(np.log(n) / n)
    ncell = max(1, int(1.0 / r))
    cell = (pts[:, 0] * ncell).astype(np.int64) * ncell + (
        pts[:, 1] * ncell).astype(np.int64)
    order = np.argsort(cell, kind="stable")
    uniq, starts = np.unique(cell[order], return_index=True)
    starts = np.append(starts, n)
    slot_of = {int(cc): i for i, cc in enumerate(uniq)}
    us, vs = [], []
    r2 = r * r
    for slot in range(uniq.shape[0]):
        cx, cy = divmod(int(uniq[slot]), ncell)
        pa_ids = order[starts[slot]:starts[slot + 1]]
        pa = pts[pa_ids]
        for dx, dy in ((0, 0), (0, 1), (1, -1), (1, 0), (1, 1)):
            nx, ny = cx + dx, cy + dy
            if not (0 <= nx < ncell and 0 <= ny < ncell):
                continue
            s2 = slot_of.get(nx * ncell + ny)
            if s2 is None:
                continue
            pb_ids = order[starts[s2]:starts[s2 + 1]]
            d2 = ((pa[:, None, :] - pts[pb_ids][None, :, :]) ** 2).sum(-1)
            if dx == 0 and dy == 0:
                iu, iv = np.triu_indices(pa_ids.shape[0], k=1)
                hit = d2[iu, iv] <= r2
                us.append(pa_ids[iu[hit]])
                vs.append(pa_ids[iv[hit]])
            else:
                iu, iv = np.nonzero(d2 <= r2)
                us.append(pa_ids[iu])
                vs.append(pb_ids[iv])
    u = np.concatenate(us) if us else np.empty(0, np.int64)
    v = np.concatenate(vs) if vs else np.empty(0, np.int64)
    return csr_from_edges(n, u, v)


def build(config: dict, seed: int) -> Csr:
    """The graph of a deployment's config file, from a graph seed."""
    gen = config["generator"]
    if gen == "rmat":
        return rmat(config["scale"], config["edge_factor"], config["a"],
                    config["b"], config["c"], seed)
    if gen == "rgg":
        return rgg(config["scale"], config["radius_coeff"], seed)
    raise ValueError(f"unknown generator {gen!r}")
