"""CSR graph container used throughout the partitioning engine.

An undirected graph G = (V, E, c, omega) is stored as a *symmetric* CSR
adjacency structure: every undirected edge {u, v} appears as the two arcs
(u, v) and (v, u).  Edge weights ``ew`` are per-arc (both arcs of one edge
carry the same weight); node weights ``nw`` are per-node.  This mirrors the
adjacency-array representation of the paper (Section IV-A) and is the native
layout for the sort/segment primitives the TPU adaptation is built on.

Three twin types exist:

* :class:`GraphNP` — host-side numpy arrays.  Generators, shard splitting,
  and the host fallback contraction live here.
* :class:`Graph` — a registered JAX pytree with the same fields, used inside
  jitted/shard_mapped computations whose shapes are static per level.
* :class:`GraphDev` — a *device-resident* bucket-padded CSR handle: the
  output of the LP engine's device contraction
  (``repro.core.contraction.contract_device``).  Arrays are padded to
  power-of-two buckets (so one compiled contraction/pack executable serves
  many levels); only the ``(n, m)`` scalars live on host.  ``to_host()``
  materializes a :class:`GraphNP` lazily — the escape hatch for the host
  engines (numpy SCLaP, FM) and the evolutionary coarsest stage.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..obs.memory import account as _mem_account

__all__ = [
    "Graph",
    "GraphDev",
    "GraphNP",
    "arc_bucket",
    "from_edges",
    "pow2",
    "sort_by_keys",
    "sort_values",
    "to_device",
    "to_device_csr",
    "to_host",
    "validate",
]


def pow2(x: int) -> int:
    """Smallest power of two >= x (the node/label-axis bucket policy)."""
    return 1 << max(0, int(x) - 1).bit_length()


def sort_values(x: jax.Array) -> jax.Array:
    """Ascending value-only sort.  Equal elements are indistinguishable, so
    an unstable sort returns the same array as a stable one -- and the TPU
    compiles it several times faster (a single-operand stable sort is one of
    the slowest programs its compiler builds)."""
    return jax.lax.sort(x, is_stable=False)


def sort_by_keys(*keys: jax.Array) -> jax.Array:
    """int32 permutation ordering by ``keys`` (first key most significant),
    equal to ``jnp.lexsort(keys[::-1])``: the element index is the last key,
    so no two entries tie and the unstable sort yields the stable order."""
    iota = jnp.arange(keys[0].shape[0], dtype=jnp.int32)
    return jax.lax.sort((*keys, iota), num_keys=len(keys) + 1,
                        is_stable=False)[-1]


def arc_bucket(m: int) -> int:
    """Arc-axis bucket: pow2 below 16384, then 16384-arc rungs.

    Single source of truth shared by the LP engine's contraction buckets and
    the dynamic store's compaction buckets: value-only key sorts over the
    arc axis are the critical path and scale with the PADDED arc count, so
    hot (large) levels get a tight rung (<= 8% padding) instead of the
    up-to-2x tax of pure pow2; small levels keep pow2 rungs so the bucket
    count stays O(log m)."""
    if m <= 16384:
        return pow2(max(m, 8))
    return -(-m // 16384) * 16384


@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class Graph:
    """Device-side CSR graph (a JAX pytree).

    Attributes:
      indptr:  (n + 1,) int32 — CSR row pointers.
      indices: (m,)     int32 — arc heads (m counts *arcs*, i.e. 2x edges).
      ew:      (m,)     float32 — arc weights.
      nw:      (n,)     float32 — node weights.
    """

    indptr: jax.Array
    indices: jax.Array
    ew: jax.Array
    nw: jax.Array

    @property
    def n(self) -> int:
        return self.indptr.shape[0] - 1

    @property
    def m(self) -> int:  # number of arcs (2x undirected edges)
        return self.indices.shape[0]

    @property
    def total_node_weight(self) -> jax.Array:
        return jnp.sum(self.nw)

    def degrees(self) -> jax.Array:
        return self.indptr[1:] - self.indptr[:-1]

    def arc_sources(self) -> jax.Array:
        """(m,) int32 — source node of each arc (CSR row expansion)."""
        return jnp.repeat(
            jnp.arange(self.n, dtype=jnp.int32),
            self.degrees(),
            total_repeat_length=self.m,
        )

    # -- pytree protocol ---------------------------------------------------
    def tree_flatten(self):
        return (self.indptr, self.indices, self.ew, self.nw), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)


@dataclass(frozen=True)
class GraphNP:
    """Host-side CSR graph (numpy); see :class:`Graph` for field semantics."""

    indptr: np.ndarray
    indices: np.ndarray
    ew: np.ndarray
    nw: np.ndarray

    @property
    def n(self) -> int:
        return int(self.indptr.shape[0] - 1)

    @property
    def m(self) -> int:
        return int(self.indices.shape[0])

    @property
    def total_node_weight(self) -> float:
        return float(self.nw.sum())

    def degrees(self) -> np.ndarray:
        return self.indptr[1:] - self.indptr[:-1]

    def arc_sources(self) -> np.ndarray:
        return np.repeat(np.arange(self.n, dtype=np.int32), self.degrees())


class GraphDev:
    """Device-resident bucket-padded CSR graph (coarse levels of the V-cycle).

    Invariants (as emitted by ``contract_device`` and relied on by the LP
    engine's device pack builder and arena):

    * ``indptr`` has ``Nb + 1`` entries with ``Nb = 2^ceil(log2 n)``; rows
      ``>= n`` all hold ``m`` (so sentinel-node gathers read degree 0).
    * ``indices`` / ``ew`` / ``src`` have ``Mb = 2^ceil(log2 m)`` entries;
      arcs ``>= m`` hold index 0 / weight 0 (inert under any masked use).
    * ``nw`` has ``Nb`` entries, 0 beyond ``n``.

    Only ``n``, ``m``, and ``nw_max`` are host scalars.  ``degrees()`` and
    ``to_host()`` materialize lazily and cache; ``on_materialize(nbytes)``
    (when set) lets the owning engine account the device->host traffic.
    """

    def __init__(self, indptr, indices, ew, nw, src, n: int, m: int,
                 nw_max: float = 0.0, ew_max: float = 0.0,
                 ew_integral: bool = False, on_materialize=None):
        self.indptr = indptr
        self.indices = indices
        self.ew = ew
        self.nw = nw
        self.src = src
        self._n = int(n)
        self._m = int(m)
        self.nw_max = float(nw_max)
        # weight metadata for the next contraction's packed-key decision:
        # integral weights stay integral under contraction (sums)
        self.ew_max = float(ew_max)
        self.ew_integral = bool(ew_integral)
        self.on_materialize = on_materialize
        self._indptr_host: np.ndarray | None = None
        self._host: GraphNP | None = None
        # every base-CSR level flows through this constructor (upload,
        # contraction output, store merge/vacuum) — the one accounting
        # chokepoint for the base_csr family
        _mem_account("base_csr", indptr, indices, ew, nw, src)

    @property
    def n(self) -> int:
        return self._n

    @property
    def m(self) -> int:
        return self._m

    @property
    def total_node_weight(self) -> float:
        """Total node weight, reduced on device (padding is 0 — inert)."""
        return float(jnp.sum(self.nw))

    def _indptr_np(self) -> np.ndarray:
        if self._indptr_host is None:
            self._indptr_host = np.asarray(self.indptr[: self._n + 1], dtype=np.int64)
            if self.on_materialize is not None:
                self.on_materialize(self._indptr_host.nbytes)
        return self._indptr_host

    def degrees(self) -> np.ndarray:
        return np.diff(self._indptr_np())

    def to_host(self) -> GraphNP:
        """Materialize a :class:`GraphNP` (cached) — one O(n + m) download."""
        if self._host is None:
            self._host = GraphNP(
                indptr=self._indptr_np(),
                indices=np.asarray(self.indices[: self._m], dtype=np.int32),
                ew=np.asarray(self.ew[: self._m], dtype=np.float32),
                nw=np.asarray(self.nw[: self._n], dtype=np.float32),
            )
            if self.on_materialize is not None:
                self.on_materialize(self._m * 8 + self._n * 4)
        return self._host


def from_edges(
    n: int,
    u: np.ndarray,
    v: np.ndarray,
    w: np.ndarray | None = None,
    nw: np.ndarray | None = None,
    symmetrize: bool = True,
    dedup: bool = True,
) -> GraphNP:
    """Build a :class:`GraphNP` from an edge list.

    Args:
      n: number of nodes.
      u, v: int arrays of endpoints.  Self loops are dropped.
      w: optional edge weights (default: all ones).
      nw: optional node weights (default: all ones).
      symmetrize: if True, adds both arcs per input edge.
      dedup: if True, parallel arcs are merged (weights summed).
    """
    u = np.asarray(u, dtype=np.int64)
    v = np.asarray(v, dtype=np.int64)
    if w is None:
        w = np.ones(u.shape[0], dtype=np.float32)
    w = np.asarray(w, dtype=np.float32)

    keep = u != v
    u, v, w = u[keep], v[keep], w[keep]

    if symmetrize:
        uu = np.concatenate([u, v])
        vv = np.concatenate([v, u])
        ww = np.concatenate([w, w])
    else:
        uu, vv, ww = u, v, w

    if dedup and uu.size:
        key = uu * np.int64(n) + vv
        order = np.argsort(key, kind="stable")
        key = key[order]
        ww = ww[order]
        boundary = np.empty(key.shape[0], dtype=bool)
        boundary[0] = True
        boundary[1:] = key[1:] != key[:-1]
        run_id = np.cumsum(boundary) - 1
        n_runs = int(run_id[-1]) + 1
        merged_w = np.zeros(n_runs, dtype=np.float64)
        np.add.at(merged_w, run_id, ww)
        first = np.flatnonzero(boundary)
        uu = (key[first] // n).astype(np.int32)
        vv = (key[first] % n).astype(np.int32)
        ww = merged_w.astype(np.float32)
    else:
        order = np.argsort(uu * np.int64(n) + vv, kind="stable")
        uu = uu[order].astype(np.int32)
        vv = vv[order].astype(np.int32)
        ww = ww[order]

    counts = np.bincount(uu, minlength=n).astype(np.int64)
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(counts, out=indptr[1:])
    if nw is None:
        nw = np.ones(n, dtype=np.float32)
    return GraphNP(
        indptr=indptr.astype(np.int64),
        indices=vv.astype(np.int32),
        ew=ww.astype(np.float32),
        nw=np.asarray(nw, dtype=np.float32),
    )


def to_device(g: GraphNP) -> Graph:
    dev = Graph(
        indptr=jnp.asarray(g.indptr, dtype=jnp.int32)
        if g.m < 2**31
        else jnp.asarray(g.indptr),
        indices=jnp.asarray(g.indices, dtype=jnp.int32),
        ew=jnp.asarray(g.ew, dtype=jnp.float32),
        nw=jnp.asarray(g.nw, dtype=jnp.float32),
    )
    _mem_account("base_csr", dev.indptr, dev.indices, dev.ew, dev.nw)
    return dev


def to_device_csr(g: GraphNP, on_materialize=None, on_upload=None) -> GraphDev:
    """Upload a host CSR into a bucket-padded device-resident :class:`GraphDev`.

    The handle satisfies exactly the invariants ``contract_device`` outputs
    satisfy (pow2 node bucket, ``arc_bucket`` arc bucket, inert padding:
    rows >= n hold m, arcs >= m hold index 0 / weight 0), so downstream
    consumers (the LP engine's device pack gather, the dynamic store's
    compaction) cannot tell an uploaded finest graph from a contracted
    coarse level.  ``on_upload(nbytes)``, when set, lets the owner account
    the host->device traffic of the one-time upload."""
    n, m = g.n, g.m
    Nb = pow2(max(n, 8))
    Mb = arc_bucket(m)
    indptr = np.full(Nb + 1, m, dtype=np.int64)
    indptr[: n + 1] = g.indptr
    indices = np.zeros(Mb, dtype=np.int32)
    indices[:m] = g.indices
    ew = np.zeros(Mb, dtype=np.float32)
    ew[:m] = g.ew
    src = np.zeros(Mb, dtype=np.int32)
    src[:m] = g.arc_sources()
    nw = np.zeros(Nb, dtype=np.float32)
    nw[:n] = g.nw
    if on_upload is not None:
        on_upload(indptr.nbytes // 2 + indices.nbytes + ew.nbytes
                  + src.nbytes + nw.nbytes)
    return GraphDev(
        indptr=jnp.asarray(indptr, dtype=jnp.int32),
        indices=jnp.asarray(indices),
        ew=jnp.asarray(ew),
        nw=jnp.asarray(nw),
        src=jnp.asarray(src),
        n=n, m=m,
        nw_max=float(g.nw.max()) if n else 0.0,
        ew_max=float(g.ew.max()) if m else 0.0,
        ew_integral=bool(np.all(g.ew == np.round(g.ew))) if m else True,
        on_materialize=on_materialize,
    )


def to_host(g: Graph) -> GraphNP:
    return GraphNP(
        indptr=np.asarray(g.indptr, dtype=np.int64),
        indices=np.asarray(g.indices),
        ew=np.asarray(g.ew),
        nw=np.asarray(g.nw),
    )


def validate(g: GraphNP) -> None:
    """Raise AssertionError if the CSR structure is inconsistent/asymmetric."""
    assert g.indptr[0] == 0 and g.indptr[-1] == g.m
    assert np.all(np.diff(g.indptr) >= 0)
    assert g.nw.shape == (g.n,)
    assert g.ew.shape == (g.m,)
    if g.m == 0:
        return
    assert g.indices.min() >= 0 and g.indices.max() < g.n
    # symmetry: the multiset of (u, v, w) must equal the multiset of (v, u, w)
    src = g.arc_sources().astype(np.int64)
    dst = g.indices.astype(np.int64)
    fwd = np.lexsort((dst, src))
    bwd = np.lexsort((src, dst))
    assert np.array_equal(src[fwd], dst[bwd])
    assert np.array_equal(dst[fwd], src[bwd])
    np.testing.assert_allclose(g.ew[fwd], g.ew[bwd], rtol=1e-5)
