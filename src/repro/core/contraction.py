"""Cluster contraction (paper §III/IV-C).

Each cluster of a (size-constrained) clustering becomes one coarse node;
coarse node weight = sum of member node weights; coarse edge (A, B) weight =
total weight of edges running between clusters A and B.  By construction a
partition of the coarse graph projects to a partition of the fine graph with
*identical* cut and balance — the property the whole multilevel scheme rests
on (tested property-style in tests/test_property.py).

Three implementations:

* :func:`contract_device` — the production path.  The paper's §IV-C parallel
  hash-based quotient construction expressed as the TPU-idiomatic segment
  sort: relabel (sort + prefix-sum distinct count), coarse node-weight
  segment-sum, quotient-arc dedup, and CSR rebuild run as ONE compiled
  executable over bucket-padded device arrays.  The LP engine
  (``repro.core.engine.LPEngine.contract``) wraps it with power-of-two
  shape bucketing so a handful of compilations serve every level of every
  V-cycle, and only the ``(n_c, m_c, max nw_c)`` scalars cross to host for
  the driver's termination/bucket decision — the coarse adjacency itself
  stays device-resident (:class:`~repro.graph.csr.GraphDev`) and feeds the
  next level's pack gather directly.
* :func:`contract` — the host/numpy **fallback** (numpy engine, graphs below
  the engine threshold, and the test oracle the device path is
  parity-checked against in tests/test_device_contraction.py).  Same
  algorithm expressed serially; coarse IDs are assigned in increasing
  original-label order by both paths, so their outputs are identical
  structure-for-structure.
* :func:`contract_arcs_jnp` — the per-shard building block used by the
  distributed pipeline: maps + deduplicates + weight-sums arcs for a
  shard's local subgraph on device (static shapes, padded);
  :func:`contract_device` is its whole-graph generalization.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..graph.csr import GraphNP, sort_by_keys, sort_values

__all__ = [
    "CoarseMap",
    "contract",
    "contract_device",
    "packed_key_wbits",
    "relabel",
    "contract_arcs_jnp",
    "project_labels",
]

# The packed-key fast path rides (cu, cv, weight) in ONE uint32 sort key, so
# the pair space times the weight space must fit in 2^32 — the fallback
# threshold a future x64 enablement would want to revisit (64-bit keys lift
# both bounds).  Pinned by tests/test_device_contraction.py.
PACKED_KEY_SPACE = 2**32


def packed_key_wbits(Nb: int, Mb: int, ew_max: float, ew_integral: bool) -> int:
    """Weight-bit count for :func:`contract_device`'s packed-key fast path.

    Returns ``b > 0`` when every live arc weight is an integer in
    ``[1, 2^b - 1]`` AND the fused key ``(cu * Nb + cv) << b | w`` fits a
    uint32 (``Nb^2 * 2^b <= PACKED_KEY_SPACE``) AND the exact int32 cumsum
    of per-run weights cannot overflow (``Mb * (2^b - 1) < 2^31``); 0 selects
    the general scatter-add path.  Callers evaluate this once per graph —
    it is the single place the fast-path/fallback boundary is decided."""
    if not ew_integral or ew_max < 1.0:
        return 0
    b = int(ew_max).bit_length()
    if Nb * Nb * (1 << b) <= PACKED_KEY_SPACE and Mb * ((1 << b) - 1) < 2**31:
        return b
    return 0


def relabel(labels: np.ndarray) -> Tuple[np.ndarray, int]:
    """Map arbitrary cluster IDs to the contiguous range [0, n').

    Sort-based: equivalent to the paper's distributed distinct-counting +
    prefix-sum scheme (§IV-C), collapsed onto one host.
    """
    uniq, C = np.unique(labels, return_inverse=True)
    return C.astype(np.int32), int(uniq.shape[0])


def contract(g: GraphNP, labels: np.ndarray) -> Tuple[GraphNP, np.ndarray]:
    """Host-fallback contraction; returns (coarse graph, fine->coarse map C).

    The engine path uses :func:`contract_device`; this serves the numpy
    engine, sub-threshold levels, and as the parity oracle."""
    C, n_c = relabel(labels)
    nw_c = np.zeros(n_c, dtype=np.float64)
    np.add.at(nw_c, C, g.nw)

    src = g.arc_sources()
    cu = C[src].astype(np.int64)
    cv = C[g.indices].astype(np.int64)
    keep = cu != cv
    cu, cv = cu[keep], cv[keep]
    w = g.ew[keep].astype(np.float64)

    if cu.size == 0:
        coarse = GraphNP(
            indptr=np.zeros(n_c + 1, dtype=np.int64),
            indices=np.zeros(0, dtype=np.int32),
            ew=np.zeros(0, dtype=np.float32),
            nw=nw_c.astype(np.float32),
        )
        return coarse, C

    key = cu * np.int64(n_c) + cv
    order = np.argsort(key, kind="stable")
    key_s = key[order]
    w_s = w[order]
    boundary = np.empty(key_s.shape[0], dtype=bool)
    boundary[0] = True
    boundary[1:] = key_s[1:] != key_s[:-1]
    run = np.cumsum(boundary) - 1
    m_c = int(run[-1]) + 1
    w_c = np.zeros(m_c, dtype=np.float64)
    np.add.at(w_c, run, w_s)
    first = np.flatnonzero(boundary)
    cu_c = (key_s[first] // n_c).astype(np.int32)
    cv_c = (key_s[first] % n_c).astype(np.int32)

    counts = np.bincount(cu_c, minlength=n_c)
    indptr = np.zeros(n_c + 1, dtype=np.int64)
    np.cumsum(counts, out=indptr[1:])
    coarse = GraphNP(
        indptr=indptr,
        indices=cv_c,
        ew=w_c.astype(np.float32),
        nw=nw_c.astype(np.float32),
    )
    return coarse, C


def project_labels(coarse_labels: np.ndarray, C: np.ndarray) -> np.ndarray:
    """Uncoarsening: fine node inherits the block of its coarse representative."""
    return coarse_labels[C]


@dataclass
class CoarseMap:
    """Fine->coarse mapping of one device contraction (hierarchy handle).

    ``dev`` is bucket-padded to the fine level's node bucket; entries
    ``>= n_fine`` are meaningless.  ``host()`` materializes the exact-length
    numpy map lazily (for the host-path engines), caching the download.
    """

    dev: jax.Array          # (Nb,) int32, valid through n_fine
    n_fine: int
    n_coarse: int
    on_materialize: Optional[object] = None
    _host: Optional[np.ndarray] = field(default=None, repr=False)

    def host(self) -> np.ndarray:
        if self._host is None:
            self._host = np.asarray(self.dev[: self.n_fine], dtype=np.int32)
            if self.on_materialize is not None:
                self.on_materialize(self._host.nbytes)
        return self._host


@functools.partial(jax.jit, static_argnames=("wbits",))
def contract_device(src, dst, ew, nw, labels, n, m, *, wbits: int = 0):
    """Whole-graph device contraction: one executable per shape bucket.

    Args:
      src, dst: (Mb,) int32 arc endpoints; entries >= ``m`` hold in-range
        garbage (masked).
      ew:       (Mb,) f32 arc weights, 0 beyond ``m``.
      nw:       (Nb,) f32 node weights, 0 beyond ``n``.
      labels:   (Nb,) int32 cluster ids in [0, n) for valid nodes.
      n, m:     traced scalars — the live node/arc counts, so ONE compiled
        executable per padded bucket shape ``(Nb, Mb)`` serves every level
        that lands in that bucket.
      wbits:    static — when > 0, a promise that every live arc weight is
        an integer in ``[1, 2^wbits - 1]`` and ``Nb^2 * 2^wbits <= 2^32``.
        The weight is then PACKED into the low bits of the uint32 sort key,
        and the per-run weight sums become exact int32 cumsum differences:
        the whole quotient build is one value-only sort plus vectorized
        scans — no payload sort, no scatter (the fast path on every
        backend; the caller detects eligibility once per graph).  0 selects
        the general float path (scatter-add segment sums).

    Returns ``(C, n_c, nw_c, indptr_c, src_c, dst_c, ew_c, m_c, nwmax_c,
    ewmax_c)``, all device-resident and padded to the input bucket: the
    fine->coarse map, coarse node count, coarse node weights, coarse CSR
    (arcs sorted by (cu, cv) — identical order to the host
    :func:`contract`), coarse arc sources, arc count, and the max coarse
    node/arc weights (the scalars the driver and the next level's ``wbits``
    decision need).  Coarse IDs follow increasing original-label order
    (== ``np.unique`` semantics), so the result is structure-identical to
    the host path.  Quotient weights are exact for integral inputs; for
    float weights the general path's segment sums run in unspecified order
    (tolerance-level reordering vs the host oracle).
    """
    Nb = nw.shape[0]
    Mb = src.shape[0]
    iota_n = jnp.arange(Nb, dtype=jnp.int32)
    iota_m = jnp.arange(Mb, dtype=jnp.int32)
    node_valid = iota_n < n
    sent = jnp.int32(Nb)

    # ---- relabel (paper §IV-C's distinct-count + prefix-sum): value-only
    # sort of the labels, dense ranks via cumsum, and C[v] recovered by
    # binary search for the first occurrence — no payload sort needed.
    lab = jnp.where(node_valid, labels, sent)
    sl = sort_values(lab)
    newrun_n = jnp.concatenate(
        [sl[:1] < sent, (sl[1:] != sl[:-1]) & (sl[1:] < sent)]
    )
    rank_n = (jnp.cumsum(newrun_n) - 1).astype(jnp.int32)
    n_c = jnp.sum(newrun_n).astype(jnp.int32)
    posn = jnp.minimum(jnp.searchsorted(sl, lab), Nb - 1)
    C = jnp.where(node_valid, rank_n[posn], 0).astype(jnp.int32)

    # ---- coarse node weights (invalid nodes add 0 at slot 0: inert)
    nw_c = jnp.zeros((Nb,), jnp.float32).at[C].add(
        jnp.where(node_valid, nw, 0.0)
    )
    nwmax_c = jnp.max(nw_c)

    # ---- quotient arcs: map, drop self-arcs, sort (cu, cv) keys
    arc_valid = iota_m < m
    cu = C[jnp.where(arc_valid, src, 0)]
    cv = C[jnp.where(arc_valid, dst, 0)]
    ok = arc_valid & (cu != cv)
    if wbits:
        # weight-packed uint32 key, sorted VALUE-ONLY (XLA's fast sort
        # path).  The (cu, cv) pair lives in the high bits so run grouping
        # is unchanged; the integral weight rides in the low bits and the
        # per-run sums fall out of one exact int32 cumsum.  The sentinel
        # encodes a max-weight SELF-arc of node Nb-1 — never a valid
        # quotient arc — so it needs no key-space headroom.
        big = jnp.uint32(Nb * Nb * (1 << wbits) - 1)
        pair = cu.astype(jnp.uint32) * jnp.uint32(Nb) + cv.astype(jnp.uint32)
        key = jnp.where(
            ok, (pair << wbits) | ew.astype(jnp.uint32), big
        )
        ks = sort_values(key)
        oks = ks < big
        khi = ks >> wbits
        first = jnp.concatenate([oks[:1], oks[1:] & (khi[1:] != khi[:-1])])
        # compaction by sorting the masked iota: run-first positions are
        # increasing, so a second value-only sort IS the compaction (cheaper
        # than a searchsorted over Mb queries on every backend measured)
        firstpos = sort_values(jnp.where(first, iota_m, jnp.int32(Mb)))
        fp = jnp.minimum(firstpos, Mb - 1)
        m_c = jnp.sum(first).astype(jnp.int32)
        arc_ok = iota_m < m_c
        uk = khi[fp]
        src_c = jnp.where(arc_ok, (uk // jnp.uint32(Nb)).astype(jnp.int32), 0)
        dst_c = jnp.where(arc_ok, (uk % jnp.uint32(Nb)).astype(jnp.int32), 0)
        w_s = jnp.where(oks, ks & jnp.uint32((1 << wbits) - 1), 0)
        cumw = jnp.cumsum(w_s.astype(jnp.int32))
        n_ok = jnp.sum(oks).astype(jnp.int32)
        fpe = jnp.concatenate([firstpos[1:], jnp.full((1,), Mb, jnp.int32)])
        ends = jnp.minimum(fpe, n_ok)
        hi = cumw[jnp.clip(ends - 1, 0, Mb - 1)]
        lo = jnp.where(fp > 0, cumw[jnp.maximum(fp - 1, 0)], 0)
        ew_c = jnp.where(arc_ok, (hi - lo).astype(jnp.float32), 0.0)
    elif Nb * Nb < 2**31:
        # general weights, fused int32 key: value-only sort, then the run
        # id of each unsorted arc by binary search and a scatter-add for
        # the f32 segment sums
        big = jnp.int32(2**31 - 1)
        key = jnp.where(ok, cu * jnp.int32(Nb) + cv, big)
        ks = sort_values(key)
        oks = ks < big
        first = jnp.concatenate([oks[:1], oks[1:] & (ks[1:] != ks[:-1])])
        firstpos = sort_values(jnp.where(first, iota_m, jnp.int32(Mb)))
        fp = jnp.minimum(firstpos, Mb - 1)
        m_c = jnp.sum(first).astype(jnp.int32)
        arc_ok = iota_m < m_c
        uk = ks[fp]
        src_c = jnp.where(arc_ok, uk // jnp.int32(Nb), 0)
        dst_c = jnp.where(arc_ok, uk % jnp.int32(Nb), 0)
        run = (jnp.cumsum(first) - 1).astype(jnp.int32)
        pos_m = jnp.minimum(jnp.searchsorted(ks, key), Mb - 1)
        run_of = jnp.where(ok, run[pos_m], Mb)
        ew_c = jnp.zeros((Mb,), jnp.float32).at[run_of].add(
            jnp.where(ok, ew, 0.0), mode="drop"
        )
    else:
        # > 46k-node levels: two-pass lexicographic payload sort (rare at
        # this repo's scales; correct for any size without int64)
        aorder = sort_by_keys(
            jnp.where(ok, cu, sent), jnp.where(ok, cv, sent)
        )
        oks = ok[aorder]
        cu_s = jnp.where(oks, cu[aorder], sent)
        cv_s = jnp.where(oks, cv[aorder], sent)
        first = jnp.concatenate(
            [
                oks[:1],
                oks[1:] & ((cu_s[1:] != cu_s[:-1]) | (cv_s[1:] != cv_s[:-1])),
            ]
        )
        firstpos = sort_values(jnp.where(first, iota_m, jnp.int32(Mb)))
        fp = jnp.minimum(firstpos, Mb - 1)
        m_c = jnp.sum(first).astype(jnp.int32)
        arc_ok = iota_m < m_c
        src_c = jnp.where(arc_ok, cu_s[fp], 0)
        dst_c = jnp.where(arc_ok, cv_s[fp], 0)
        run = (jnp.cumsum(first) - 1).astype(jnp.int32)
        run_of = jnp.zeros((Mb,), jnp.int32).at[aorder].set(
            jnp.where(oks, run, Mb)
        )
        run_of = jnp.where(ok, run_of, Mb)
        ew_c = jnp.zeros((Mb,), jnp.float32).at[run_of].add(
            jnp.where(ok, ew, 0.0), mode="drop"
        )
    ewmax_c = jnp.max(ew_c)

    # ---- CSR rebuild: src_c is non-decreasing over the live prefix, so the
    # row pointers are binary searches, not scatters
    cu_sorted = jnp.where(arc_ok, src_c, sent)
    indptr_c = jnp.searchsorted(
        cu_sorted, jnp.arange(Nb + 1, dtype=jnp.int32)
    ).astype(jnp.int32)
    return C, n_c, nw_c, indptr_c, src_c, dst_c, ew_c, m_c, nwmax_c, ewmax_c


def contract_arcs_jnp(
    cu: jnp.ndarray, cv: jnp.ndarray, w: jnp.ndarray, valid: jnp.ndarray, n_c: int
):
    """Device-side quotient-arc dedup for one shard (static shapes).

    Args:
      cu, cv: (E,) int32 coarse endpoints of local arcs.
      w:      (E,) f32 arc weights.
      valid:  (E,) bool — padding / self-arc mask (False entries are dropped).
      n_c:    static upper bound on coarse node count.
    Returns:
      (cu', cv', w', valid'): deduplicated arcs, padded to E.
    """
    E = cu.shape[0]
    ok = valid & (cu != cv)
    # key sorts invalid arcs to the end
    big = jnp.int64(n_c)
    key = jnp.where(ok, cu.astype(jnp.int64) * big + cv.astype(jnp.int64), big * big)
    order = jnp.argsort(key)
    key_s = key[order]
    w_s = jnp.where(ok, w, 0.0)[order]
    newrun = jnp.concatenate(
        [jnp.ones((1,), bool), key_s[1:] != key_s[:-1]]
    ) & (key_s < big * big)
    run = jnp.cumsum(newrun) - 1
    run = jnp.where(key_s < big * big, run, E - 1)
    w_out = jnp.zeros((E,), jnp.float32).at[run].add(w_s)
    cu_out = jnp.zeros((E,), jnp.int32).at[run].set((key_s // big).astype(jnp.int32))
    cv_out = jnp.zeros((E,), jnp.int32).at[run].set((key_s % big).astype(jnp.int32))
    n_runs = jnp.sum(newrun)
    valid_out = jnp.arange(E) < n_runs
    return cu_out, cv_out, jnp.where(valid_out, w_out, 0.0), valid_out
