"""Mutable device-resident graph store (dynamic subsystem, layer 1).

The static pipeline treats the graph as immutable: ``GraphNP`` is built once
and every device structure (arenas, chunk packs, ELL packs) is cached
against its identity.  A serving workload instead sees a *stream* of edge
and node updates.  This module keeps the graph resident on device across
that stream:

* **Base CSR** — a bucket-padded :class:`~repro.graph.csr.GraphDev`
  (uploaded once via :func:`~repro.graph.csr.to_device_csr`, or the output
  of the previous compaction).  All O(m) state stays on device.
* **Delta overlay** — a bounded host-side COO buffer of signed arc-weight
  deltas (``add_edges`` appends ``+w`` arcs, ``remove_edges`` appends
  ``-w``; both directions of each undirected edge).  Batches are cheap
  appends; nothing is re-sorted until compaction.  Weight deltas are
  integral (int32 semantics) so merged float32 sums are exact in any
  order — the precondition every bit-reproducibility guarantee of the
  subsystem rests on.
* **Compaction** — :func:`merge_overlay_device` folds the overlay back into
  CSR as ONE bucketed executable: the PR-2 contraction machinery minus the
  relabel (fused ``u * Nb + v`` value-only key sort, run segmentation,
  scatter-add weight sums, searchsorted CSR rebuild), plus a *drop* of runs
  whose merged weight reaches zero (removed edges).  Overlay batches are
  padded to pow2 buckets and the live count is traced, so a steady update
  stream compiles once per ``(Mb, Rb, Nb)`` bucket — the PR-1 jit-cache
  discipline applied to mutation.

An inverse update stream is lossless: appending ``+w`` then ``-w`` for the
same arcs and compacting reproduces the original CSR bit-for-bit (same
(u, v) sort order as :func:`~repro.graph.csr.from_edges`, exact integral
sums) — regression-tested in tests/test_dynamic.py.
"""

from __future__ import annotations

import struct
import zlib
from dataclasses import dataclass, field
from typing import Callable, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..graph.csr import (
    GraphDev,
    GraphNP,
    arc_bucket,
    pow2,
    sort_by_keys,
    sort_values,
    to_device_csr,
)
from ..obs import MetricsRegistry, RegistryBackedStats
from ..obs import span as _obs_span
from ..obs import watchdog as _obs_watchdog
from ..obs.memory import account as _mem_account

__all__ = [
    "DynamicGraphStore",
    "GraphUpdate",
    "StoreStats",
    "UpdateValidationError",
    "churn_updates",
    "merge_overlay_device",
    "overlay_view_device",
    "vacuum_device",
]


class UpdateValidationError(ValueError):
    """A :class:`GraphUpdate` failed pre-apply validation.

    Subclasses ``ValueError`` (the historical raise type) and carries a
    structured ``reason`` tag so the resilience layer can quarantine by
    fault class instead of parsing messages.
    """

    def __init__(self, reason: str, detail: str):
        super().__init__(f"{reason}: {detail}")
        self.reason = reason
        self.detail = detail


# Wire format of one serialized GraphUpdate (the WAL record body):
#
#   header  "<4sBBHQI" = magic b"GUPD" | version u8 | flags u8 (reserved 0)
#                        | reserved u16 | payload_len u64 | crc32 u32
#   payload 7 x u64 field lengths (add_u, add_v, add_w, rem_u, rem_v,
#           rem_w, add_node_w) followed by the fields as little-endian
#           int64 in that order.
#
# The crc32 covers the payload only, so a truncated header, a truncated
# payload, and a bit-flipped payload are three distinguishable rejection
# reasons — the durable WAL relies on that to stop replay at the first
# torn/corrupt record instead of applying garbage.
_WIRE_MAGIC = b"GUPD"
_WIRE_VERSION = 1
_WIRE_HEADER = struct.Struct("<4sBBHQI")
_WIRE_FIELDS = ("add_u", "add_v", "add_w", "rem_u", "rem_v", "rem_w",
                "add_node_w")


def _as_ids(a) -> np.ndarray:
    return np.asarray(a, dtype=np.int64).reshape(-1)


def _as_w(w, size: int) -> np.ndarray:
    if w is None:
        return np.ones(size, dtype=np.int64)
    w = np.asarray(w, dtype=np.float64).reshape(-1)
    if not np.all(w == np.round(w)):
        raise ValueError("update weights must be integral (int32 deltas)")
    if w.size and np.abs(w).max() >= 2**24:
        # f32 loses integer exactness at 2^24 — the bound every
        # bit-reproducibility guarantee of the subsystem rests on
        raise ValueError("update weight deltas must stay below 2^24")
    return w.astype(np.int64)


@dataclass
class GraphUpdate:
    """One batched mutation request (all arrays host numpy, int semantics).

    ``add_u/add_v/add_w`` are undirected edges whose weight is *increased*
    by ``w`` (creating the edge if absent); ``rem_u/rem_v/rem_w`` decrease
    it (an edge whose merged weight reaches zero disappears).  ``add_node_w``
    appends new nodes with the given weights; new node ids are assigned
    contiguously from the current n, so a batch may add nodes and then wire
    them up with edges in the same request.
    """

    add_u: np.ndarray = field(default_factory=lambda: np.zeros(0, np.int64))
    add_v: np.ndarray = field(default_factory=lambda: np.zeros(0, np.int64))
    add_w: np.ndarray = field(default_factory=lambda: np.zeros(0, np.int64))
    rem_u: np.ndarray = field(default_factory=lambda: np.zeros(0, np.int64))
    rem_v: np.ndarray = field(default_factory=lambda: np.zeros(0, np.int64))
    rem_w: np.ndarray = field(default_factory=lambda: np.zeros(0, np.int64))
    add_node_w: np.ndarray = field(default_factory=lambda: np.zeros(0, np.int64))

    @staticmethod
    def add_edges(u, v, w=None) -> "GraphUpdate":
        u, v = _as_ids(u), _as_ids(v)
        return GraphUpdate(add_u=u, add_v=v, add_w=_as_w(w, u.shape[0]))

    @staticmethod
    def remove_edges(u, v, w=None) -> "GraphUpdate":
        u, v = _as_ids(u), _as_ids(v)
        return GraphUpdate(rem_u=u, rem_v=v, rem_w=_as_w(w, u.shape[0]))

    @staticmethod
    def add_nodes(nw) -> "GraphUpdate":
        return GraphUpdate(add_node_w=_as_w(nw, len(np.atleast_1d(nw))))

    @property
    def num_new_nodes(self) -> int:
        return int(self.add_node_w.shape[0])

    def merged(self, other: "GraphUpdate") -> "GraphUpdate":
        """Concatenate two requests into one batch (other's edges may
        reference nodes this batch adds)."""
        cat = np.concatenate
        return GraphUpdate(
            add_u=cat([self.add_u, other.add_u]),
            add_v=cat([self.add_v, other.add_v]),
            add_w=cat([self.add_w, other.add_w]),
            rem_u=cat([self.rem_u, other.rem_u]),
            rem_v=cat([self.rem_v, other.rem_v]),
            rem_w=cat([self.rem_w, other.rem_w]),
            add_node_w=cat([self.add_node_w, other.add_node_w]),
        )

    def validate(self, n_before: int) -> None:
        """Raise :class:`UpdateValidationError` unless the batch is applicable
        to a graph with ``n_before`` nodes.  Covers everything the factory
        helpers enforce (integral weights below 2^24) plus the structural
        checks (endpoint range against the post-batch node set, self loops) —
        so a request built by direct field construction is held to the same
        contract.  Pure read-only: validation never touches store state,
        which is what makes rejection atomic by construction."""
        n_after = int(n_before) + self.num_new_nodes
        for tag, arr in (
            ("add_w", self.add_w), ("rem_w", self.rem_w),
            ("add_node_w", self.add_node_w),
        ):
            a = np.asarray(arr, dtype=np.float64).reshape(-1)
            if a.size and not np.all(a == np.round(a)):
                raise UpdateValidationError(
                    "non_integral_weight", f"{tag} must be integral"
                )
            if a.size and np.abs(a).max() >= 2**24:
                raise UpdateValidationError(
                    "weight_overflow", f"{tag} must stay below 2^24"
                )
        if not (self.add_u.shape[0] == self.add_v.shape[0] == self.add_w.shape[0]):
            raise UpdateValidationError("shape_mismatch", "add arrays disagree")
        if not (self.rem_u.shape[0] == self.rem_v.shape[0] == self.rem_w.shape[0]):
            raise UpdateValidationError("shape_mismatch", "rem arrays disagree")
        u, v, _ = self.arcs()
        if u.size:
            if u.min() < 0 or v.min() < 0 or max(u.max(), v.max()) >= n_after:
                raise UpdateValidationError(
                    "endpoint_out_of_range",
                    f"edge endpoint outside [0, {n_after})",
                )
            if np.any(u == v):
                raise UpdateValidationError(
                    "self_loop", "self loops are not representable"
                )

    # ------------------------------------------------------------ wire format

    def to_bytes(self) -> bytes:
        """Serialize to the length + checksum framed wire format (the WAL
        record body).  Self-delimiting: the header carries the payload
        length, so records can be concatenated into a log and re-split
        without an outer index."""
        fields = [np.ascontiguousarray(getattr(self, f), dtype="<i8")
                  for f in _WIRE_FIELDS]
        payload = struct.pack("<7Q", *(f.size for f in fields))
        payload += b"".join(f.tobytes() for f in fields)
        crc = zlib.crc32(payload) & 0xFFFFFFFF
        return _WIRE_HEADER.pack(
            _WIRE_MAGIC, _WIRE_VERSION, 0, 0, len(payload), crc
        ) + payload

    @staticmethod
    def wire_size(data: bytes) -> int:
        """Total record size (header + payload) of the record at the start
        of ``data``; raises :class:`UpdateValidationError` when even the
        header is torn or unrecognizable."""
        if len(data) < _WIRE_HEADER.size:
            raise UpdateValidationError(
                "wal_truncated",
                f"{len(data)} bytes < {_WIRE_HEADER.size}-byte header",
            )
        magic, ver, _, _, plen, _ = _WIRE_HEADER.unpack_from(data)
        if magic != _WIRE_MAGIC:
            raise UpdateValidationError("wal_bad_magic", repr(magic))
        if ver != _WIRE_VERSION:
            raise UpdateValidationError("wal_bad_version", str(ver))
        return _WIRE_HEADER.size + plen

    @staticmethod
    def from_bytes(data: bytes) -> "GraphUpdate":
        """Parse one record produced by :meth:`to_bytes`.

        Rejects (with :class:`UpdateValidationError`, never a partial
        object) torn headers/payloads (``wal_truncated``), foreign bytes
        (``wal_bad_magic`` / ``wal_bad_version``), bit flips anywhere in
        the payload (``wal_corrupt``, via crc32), and internally
        inconsistent field lengths (``wal_corrupt``).  Trailing bytes
        beyond the framed record are rejected too (``wal_trailing``) so a
        mis-split log cannot silently drop records."""
        total = GraphUpdate.wire_size(data)
        if len(data) < total:
            raise UpdateValidationError(
                "wal_truncated", f"{len(data)} bytes < {total}-byte record"
            )
        if len(data) > total:
            raise UpdateValidationError(
                "wal_trailing", f"{len(data) - total} bytes past the record"
            )
        _, _, _, _, plen, crc = _WIRE_HEADER.unpack_from(data)
        payload = data[_WIRE_HEADER.size:total]
        if (zlib.crc32(payload) & 0xFFFFFFFF) != crc:
            raise UpdateValidationError("wal_corrupt", "payload crc mismatch")
        if plen < 56:
            raise UpdateValidationError(
                "wal_corrupt", f"payload {plen} bytes < 56-byte length block"
            )
        counts = struct.unpack_from("<7Q", payload)
        if 56 + 8 * sum(counts) != plen:
            raise UpdateValidationError(
                "wal_corrupt",
                f"field lengths {counts} disagree with payload size {plen}",
            )
        out, off = {}, 56
        for name, c in zip(_WIRE_FIELDS, counts):
            out[name] = np.frombuffer(
                payload, dtype="<i8", count=c, offset=off
            ).astype(np.int64)
            off += 8 * c
        return GraphUpdate(**out)

    def arcs(self) -> tuple:
        """Symmetric signed arc deltas ``(u, v, w)`` of the batch: both arcs
        per undirected edge, ``+w`` for adds, ``-w`` for removals."""
        u = np.concatenate([self.add_u, self.add_v, self.rem_u, self.rem_v])
        v = np.concatenate([self.add_v, self.add_u, self.rem_v, self.rem_u])
        w = np.concatenate([self.add_w, self.add_w, -self.rem_w, -self.rem_w])
        return u, v, w

    def net_arcs(self, n: int) -> tuple:
        """Deduplicated net arc deltas over the batch — the batch's true
        effect.  Arcs whose adds and removals cancel vanish here, which is
        what makes a net-no-op batch leave labels bit-identical: the session
        skips repair entirely when this comes back empty."""
        u, v, w = self.arcs()
        if u.size == 0:
            return u.astype(np.int64), v.astype(np.int64), w
        key = u * np.int64(n) + v
        order = np.argsort(key, kind="stable")
        key_s, w_s = key[order], w[order]
        boundary = np.empty(key_s.shape[0], dtype=bool)
        boundary[0] = True
        boundary[1:] = key_s[1:] != key_s[:-1]
        run = np.cumsum(boundary) - 1
        net = np.zeros(int(run[-1]) + 1, dtype=np.int64)
        np.add.at(net, run, w_s)
        first = key_s[np.flatnonzero(boundary)]
        live = net != 0
        return (first[live] // n, first[live] % n, net[live])


def churn_updates(g: GraphNP, nb: int, rng: np.random.Generator):
    """Endless edge-churn stream over ``g``'s fixed node set: each batch adds
    ``nb`` random edges and removes ``nb`` edges of ``g`` not removed by an
    earlier batch (each original edge is sampled once, from its canonical
    ``src < dst`` arc)."""
    src0 = g.arc_sources()
    removed = src0 >= g.indices
    while True:
        au = rng.integers(0, g.n, nb)
        av = (au + 1 + rng.integers(0, g.n - 1, nb)) % g.n
        cand = rng.permutation(np.flatnonzero(~removed))[:nb]
        removed[cand] = True
        yield GraphUpdate.add_edges(au, av).merged(
            GraphUpdate.remove_edges(src0[cand], g.indices[cand])
        )


class StoreStats(RegistryBackedStats):
    """Counters surfaced through ``PartitionSession.stats()``.

    Counter fields live in a :class:`~repro.obs.MetricsRegistry` (attribute
    access reads/writes through); bucket-key sets stay plain sets — tests
    unpack them.  ``compact_compiles`` counts distinct (Mb, Rb, Nb) merge
    buckets, ``view_compiles`` the view buckets, ``vacuum_compiles`` the
    (Mb, Nb) relabel buckets; ``compact_deferred`` counts compactions
    dispatched asynchronously."""

    _COUNTER_FIELDS = (
        "update_batches", "edges_added", "edges_removed",
        "nodes_added", "nodes_removed",
        "compact_calls", "compact_compiles", "compact_deferred",
        "view_calls", "view_compiles",
        "vacuum_calls", "vacuum_compiles",
    )
    _SET_FIELDS = ("compact_buckets", "view_buckets", "vacuum_buckets")

    @property
    def compact_bucket_count(self) -> int:
        return len(self.compact_buckets)

    @property
    def view_bucket_count(self) -> int:
        return len(self.view_buckets)

    @property
    def vacuum_bucket_count(self) -> int:
        return len(self.vacuum_buckets)


def _merge_body(src, dst, ew, ou, ov, ow, nw, n, m, r):
    Mb = src.shape[0]
    Rb = ou.shape[0]
    Nb = nw.shape[0]
    T = Mb + Rb
    iota = jnp.arange(T, dtype=jnp.int32)
    u = jnp.concatenate([src, ou])
    v = jnp.concatenate([dst, ov])
    w = jnp.concatenate([ew, ow])
    valid = jnp.concatenate(
        [jnp.arange(Mb, dtype=jnp.int32) < m, jnp.arange(Rb, dtype=jnp.int32) < r]
    )
    if Nb * Nb < 2**31:
        # fused int32 key, value-only sort (the PR-2 general path): run ids
        # recovered by binary search, weights merged by scatter-add — exact
        # for the integral deltas the store enforces
        big = jnp.int32(2**31 - 1)
        key = jnp.where(valid, u * jnp.int32(Nb) + v, big)
        ks = sort_values(key)
        oks = ks < big
        first = jnp.concatenate([oks[:1], oks[1:] & (ks[1:] != ks[:-1])])
        run = (jnp.cumsum(first) - 1).astype(jnp.int32)
        pos = jnp.minimum(jnp.searchsorted(ks, key), T - 1)
        run_of = jnp.where(valid, run[pos], T)
        firstpos = sort_values(jnp.where(first, iota, jnp.int32(T)))
        fp = jnp.minimum(firstpos, T - 1)
        uk = ks[fp]
        ru = (uk // jnp.int32(Nb)).astype(jnp.int32)
        rv = (uk % jnp.int32(Nb)).astype(jnp.int32)
    else:
        # > 46k-node graphs: two-pass payload lexsort (mirrors the
        # contract_device fallback; rare at this repo's scales)
        sent = jnp.int32(Nb)
        aorder = sort_by_keys(jnp.where(valid, u, sent), jnp.where(valid, v, sent))
        oks = valid[aorder]
        u_s = jnp.where(oks, u[aorder], sent)
        v_s = jnp.where(oks, v[aorder], sent)
        first = jnp.concatenate(
            [oks[:1], oks[1:] & ((u_s[1:] != u_s[:-1]) | (v_s[1:] != v_s[:-1]))]
        )
        run = (jnp.cumsum(first) - 1).astype(jnp.int32)
        run_of = jnp.zeros((T,), jnp.int32).at[aorder].set(
            jnp.where(oks, run, T)
        )
        run_of = jnp.where(valid, run_of, T)
        firstpos = sort_values(jnp.where(first, iota, jnp.int32(T)))
        fp = jnp.minimum(firstpos, T - 1)
        ru = u_s[fp]
        rv = v_s[fp]
    nrun = jnp.sum(first).astype(jnp.int32)
    rw = jnp.zeros((T,), jnp.float32).at[run_of].add(
        jnp.where(valid, w, 0.0), mode="drop"
    )
    # drop runs whose merged weight hit zero (removed edges); kept runs stay
    # in (u, v) key order, so a second value-only sort IS the compaction
    keep = (iota < nrun) & (rw > 0.0)
    kpos = sort_values(jnp.where(keep, iota, jnp.int32(T)))
    kp = jnp.minimum(kpos, T - 1)
    m_new = jnp.sum(keep).astype(jnp.int32)
    arc_ok = iota < m_new
    src_c = jnp.where(arc_ok, ru[kp], 0).astype(jnp.int32)
    dst_c = jnp.where(arc_ok, rv[kp], 0).astype(jnp.int32)
    ew_c = jnp.where(arc_ok, rw[kp], 0.0)
    cu_sorted = jnp.where(arc_ok, src_c, jnp.int32(Nb))
    indptr_c = jnp.searchsorted(
        cu_sorted, jnp.arange(Nb + 1, dtype=jnp.int32)
    ).astype(jnp.int32)
    return indptr_c, src_c, dst_c, ew_c, m_new, jnp.max(nw), jnp.max(ew_c)


merge_overlay_device = jax.jit(_merge_body)
merge_overlay_device.__doc__ = """Fold a COO delta overlay into a CSR on device (one bucketed executable).

Args:
  src, dst, ew: (Mb,) base arcs; entries >= ``m`` are inert padding.
  ou, ov, ow:   (Rb,) overlay arc deltas (symmetric, signed f32 with
    integral values); entries >= ``r`` are inert padding.
  nw:           (Nb,) node weights of the POST-update node set (0 beyond n).
  n, m, r:      traced live counts — one compiled executable per
    ``(Mb, Rb, Nb)`` bucket serves the whole update stream.

Returns ``(indptr, src, dst, ew, m_new, nw_max, ew_max)``, all
device-resident: a merged CSR in (u, v) sort order — identical to what
``from_edges`` would emit for the merged edge list — with zero-weight
(fully removed) edges dropped and GraphDev padding invariants restored.
Removal is saturating: a merged weight at or below zero (removing more
weight than the edge carries, or removing an edge that never existed)
drops the edge rather than raising — the host side cannot cheaply know
per-edge weights without materializing the CSR, so over-removal is defined
as deletion.
"""


def _view_body(indptr, src, dst, ew, ou, ov, ow, n, m, r):
    """Overlay-aware CSR *view*: the merged adjacency without the merge sort.

    Instead of re-sorting all ``m + r`` arcs (``_merge_body``), the overlay
    is deduplicated alone (an O(r log r) sort), each net delta is matched
    into its base CSR row by vectorized binary search (rows are v-sorted by
    the canonical compaction order), matched weights are patched in place,
    dead arcs (merged weight <= 0) are compacted out by a rank scatter, and
    genuinely new arcs are inserted at the tail of their source row.  Total
    device work is O(m) elementwise/cumsum/scatter + O(r log r) — no
    O((m + r) log (m + r)) key sort on the hot path.

    The emitted view has exact merged row degrees and the exact merged arc
    multiset per node; only the within-row arc order differs from the
    canonical CSR (surviving base arcs stay v-sorted, new arcs append
    v-sorted after them).  Every downstream repair kernel is insensitive to
    within-row order — the sweep re-sorts by (slot, candidate label), gain
    rounds and cuts are scatter/reduce sums over integral f32 weights
    (exact in any order) — so repairing on the view is bit-identical to
    repairing on the compacted CSR (regression-tested).
    """
    Mb = src.shape[0]
    Rb = ou.shape[0]
    Nb = indptr.shape[0] - 1
    Mv = Mb + Rb
    iota_r = jnp.arange(Rb, dtype=jnp.int32)
    iota_m = jnp.arange(Mb, dtype=jnp.int32)
    valid_o = iota_r < r
    # ---- dedup the overlay: net signed delta per distinct (u, v) ----
    big = jnp.int32(2**31 - 1)
    key = jnp.where(valid_o, ou * jnp.int32(Nb) + ov, big)
    ks = sort_values(key)
    oks = ks < big
    first = jnp.concatenate([oks[:1], oks[1:] & (ks[1:] != ks[:-1])])
    run = (jnp.cumsum(first) - 1).astype(jnp.int32)
    pos = jnp.minimum(jnp.searchsorted(ks, key), Rb - 1)
    run_of = jnp.where(valid_o, run[pos], Rb)
    nrun = jnp.sum(first).astype(jnp.int32)
    dw = jnp.zeros((Rb,), jnp.float32).at[run_of].add(
        jnp.where(valid_o, ow, 0.0), mode="drop"
    )
    firstpos = sort_values(jnp.where(first, iota_r, jnp.int32(Rb)))
    fp = jnp.minimum(firstpos, Rb - 1)
    uk = ks[fp]
    run_live = iota_r < nrun
    du = jnp.where(run_live, (uk // jnp.int32(Nb)).astype(jnp.int32), 0)
    dv = jnp.where(run_live, (uk % jnp.int32(Nb)).astype(jnp.int32), 0)
    # ---- match each net delta into its base row (vectorized bisect) ----
    lo = indptr[du]
    row_end = indptr[du + 1]

    def bisect(_, lh):
        lo, hi = lh
        mid = ((lo + hi) >> 1).astype(jnp.int32)
        ltv = dst[jnp.clip(mid, 0, Mb - 1)] < dv
        cont = lo < hi
        lo2 = jnp.where(cont & ltv, mid + 1, lo)
        hi2 = jnp.where(cont & ~ltv, mid, hi)
        return lo2, hi2

    lo, _ = jax.lax.fori_loop(0, 32, bisect, (lo, row_end))
    found = run_live & (lo < row_end) \
        & (dst[jnp.clip(lo, 0, Mb - 1)] == dv)
    # ---- patch matched weights; identical saturating drop semantics to
    # the merge (a merged weight <= 0 removes the arc) ----
    idx = jnp.where(found, lo, jnp.int32(Mb))
    ew_eff = jnp.concatenate(
        [ew, jnp.zeros((1,), jnp.float32)]
    ).at[idx].add(jnp.where(found, dw, 0.0))[:Mb]
    arc_live = (iota_m < m) & (ew_eff > 0.0)
    dead = (iota_m < m) & ~arc_live
    src_s = jnp.where(iota_m < m, src, 0)
    dst_s = jnp.where(iota_m < m, dst, 0)
    dead_cnt = jnp.zeros((Nb,), jnp.int32).at[src_s].add(
        dead.astype(jnp.int32), mode="drop"
    )
    is_new = run_live & ~found & (dw > 0.0)
    new_cnt = jnp.zeros((Nb,), jnp.int32).at[du].add(
        is_new.astype(jnp.int32), mode="drop"
    )
    # ---- merged row pointers: survivors first, new arcs at the tail ----
    deg_base = (indptr[1:] - indptr[:-1]).astype(jnp.int32)
    deg_live = deg_base - dead_cnt
    cum_view = jnp.cumsum(deg_live + new_cnt).astype(jnp.int32)
    zero1 = jnp.zeros((1,), jnp.int32)
    indptr_v = jnp.concatenate([zero1, cum_view])
    live_before = jnp.concatenate(
        [zero1, jnp.cumsum(deg_live).astype(jnp.int32)]
    )[:-1]
    new_before = jnp.concatenate(
        [zero1, jnp.cumsum(new_cnt).astype(jnp.int32)]
    )[:-1]
    gr = jnp.cumsum(arc_live.astype(jnp.int32)) - 1
    pos_base = indptr_v[src_s] + (gr - live_before[src_s])
    gn = jnp.cumsum(is_new.astype(jnp.int32)) - 1
    pos_new = indptr_v[du] + deg_live[du] + (gn - new_before[du])
    tb = jnp.where(arc_live, pos_base, jnp.int32(Mv))
    tn = jnp.where(is_new, pos_new, jnp.int32(Mv))
    # padding arcs stay (0, 0, 0.0) — the arc-array inertness invariant the
    # expansion / gain / cut kernels already rely on for base padding
    src_v = jnp.zeros((Mv,), jnp.int32) \
        .at[tb].set(src_s, mode="drop").at[tn].set(du, mode="drop")
    dst_v = jnp.zeros((Mv,), jnp.int32) \
        .at[tb].set(dst_s, mode="drop").at[tn].set(dv, mode="drop")
    ew_v = jnp.zeros((Mv,), jnp.float32) \
        .at[tb].set(jnp.where(arc_live, ew_eff, 0.0), mode="drop") \
        .at[tn].set(jnp.where(is_new, dw, 0.0), mode="drop")
    return indptr_v, src_v, dst_v, ew_v, cum_view[-1]


overlay_view_device = jax.jit(_view_body)
overlay_view_device.__doc__ = """Build the merged-adjacency view of (base CSR + COO overlay) on device.

Args:
  indptr:       (Nb + 1,) int32 base row pointers (rows >= n hold m).
  src, dst, ew: (Mb,) base arcs; entries >= ``m`` are inert (0, 0, 0).
  ou, ov, ow:   (Rb,) overlay arc deltas (symmetric, signed, integral f32);
    entries >= ``r`` are inert padding.
  n, m, r:      traced live counts — one executable per ``(Mb, Rb, Nb)``.

Returns ``(indptr_v, src_v, dst_v, ew_v, m_view)``: a per-row-contiguous
CSR over ``Mb + Rb`` arc slots whose rows, degrees, and weighted arc
multisets equal the compacted merge's exactly (within-row order differs;
downstream kernels are order-insensitive).  Requires ``Nb * Nb < 2**31``
(int32 fused keys; bigger node buckets take the compaction path).
"""


def _vacuum_body(src, dst, ew, newid, keep, nw, m):
    """Relabel-on-compact: rewrite arcs through ``newid`` and drop
    tombstoned rows.  ``newid`` must be monotone on kept ids (cumsum of
    ``keep``), so within-row v-order and global (u, v) order survive the
    remap — the canonical-CSR invariant the view's binary search needs."""
    Mb = src.shape[0]
    Nb = newid.shape[0]
    iota_m = jnp.arange(Mb, dtype=jnp.int32)
    arc_ok = iota_m < m
    src_r = jnp.where(arc_ok, newid[jnp.where(arc_ok, src, 0)], 0)
    dst_r = jnp.where(arc_ok, newid[jnp.where(arc_ok, dst, 0)], 0)
    ew_r = jnp.where(arc_ok, ew, 0.0)
    cu = jnp.where(arc_ok, src_r, jnp.int32(Nb))
    indptr_r = jnp.searchsorted(
        cu, jnp.arange(Nb + 1, dtype=jnp.int32)
    ).astype(jnp.int32)
    nw_r = jnp.zeros((Nb,), jnp.float32).at[
        jnp.where(keep, newid, jnp.int32(Nb))
    ].add(jnp.where(keep, nw, 0.0), mode="drop")
    return indptr_r, src_r, dst_r, ew_r, nw_r


vacuum_device = jax.jit(_vacuum_body)
vacuum_device.__doc__ = """Compact tombstoned nodes out of a CSR on device.

Args:
  src, dst, ew: the base CSR's arc arrays (no arc may touch a tombstoned
    node — the store enforces isolation before marking).
  newid: (Nb,) int32 old -> new id map (``cumsum(keep) - 1``, clipped 0).
  keep:  (Nb,) bool — False for tombstoned rows.
  nw:    (Nb,) f32 node weights (old id space).
  m:     traced live arc count of the INPUT graph.

Returns ``(indptr, src, dst, ew, nw)`` in the new id space: removed nodes
leave the CSR entirely (rows dropped, ids re-packed contiguously), arcs and
weights are preserved bit-for-bit under the monotone remap (arc count and
within-row order are unchanged, so the output reuses the input buckets).
"""


class DynamicGraphStore:
    """Device-resident base CSR + bounded COO delta overlay.

    ``apply`` appends update batches to the overlay (O(batch) host work,
    no device dispatch); ``compact`` merges the overlay into a fresh
    :class:`GraphDev` base.  ``graph()`` hands out the up-to-date handle,
    compacting first when dirty — callers that need merged adjacency (the
    repair's region gather, cut evaluation) go through it.  The overlay is
    bounded by ``overlay_cap`` arcs; exceeding it triggers an automatic
    compaction, so device memory for pending deltas is O(cap) regardless of
    stream length.
    """

    def __init__(
        self,
        g: GraphNP,
        *,
        overlay_cap: int = 1 << 16,
        on_h2d: Optional[Callable[[int], None]] = None,
        on_d2h: Optional[Callable[[int], None]] = None,
        registry: Optional[MetricsRegistry] = None,
    ):
        if g.m and not bool(np.all(g.ew == np.round(g.ew))):
            raise ValueError("dynamic store requires integral edge weights")
        if g.m and float(g.ew.max()) >= 2**24:
            raise ValueError("edge weights must stay below 2^24 (f32-exact)")
        self._on_h2d = on_h2d or (lambda b: None)
        self._on_d2h = on_d2h or (lambda b: None)
        self.overlay_cap = int(overlay_cap)
        self.stats = StoreStats(registry)
        self.n = g.n
        self._nw = g.nw.astype(np.float64).copy()   # host mirror, authoritative
        self.base: GraphDev = to_device_csr(
            g, on_materialize=self._on_d2h, on_upload=self._on_h2d
        )
        self._nw_dev: Optional[jax.Array] = self.base.nw  # survives compacts
        self._base_host: Optional[GraphNP] = g
        self._ou: List[np.ndarray] = []
        self._ov: List[np.ndarray] = []
        self._ow: List[np.ndarray] = []
        self._olen = 0
        self._pending: Optional[dict] = None    # in-flight deferred merge
        self._tomb: Optional[np.ndarray] = None  # (n,) bool tombstone column
        self.last_vacuum_map: Optional[np.ndarray] = None

    # ------------------------------------------------------------- properties

    @property
    def m(self) -> int:
        """Arc count of the last compacted base (overlay arcs not included
        until ``compact``)."""
        return self.base.m

    @property
    def overlay_len(self) -> int:
        return self._olen

    @property
    def dirty(self) -> bool:
        return self._olen > 0

    @property
    def compact_pending(self) -> bool:
        """A deferred compaction has been dispatched but not finalized."""
        return self._pending is not None

    @property
    def pending_removals(self) -> int:
        """Tombstoned nodes awaiting the relabel-on-compact vacuum."""
        return 0 if self._tomb is None else int(self._tomb.sum())

    @property
    def total_node_weight(self) -> float:
        return float(self._nw.sum())

    def node_weights(self) -> np.ndarray:
        return self._nw

    # ---------------------------------------------------------------- updates

    def apply(self, upd: GraphUpdate) -> None:
        """Append one batch: new nodes first (ids from the current n), then
        the batch's symmetric arc deltas into the overlay.  The whole batch
        is validated up front (:meth:`GraphUpdate.validate`), so a rejected
        request leaves the store untouched (no half-applied node adds)."""
        upd.validate(self.n)
        u, v, w = upd.arcs()
        n_after = self.n + upd.num_new_nodes
        if upd.num_new_nodes:
            self._nw = np.concatenate(
                [self._nw, upd.add_node_w.astype(np.float64)]
            )
            self.n = n_after
            self.stats.nodes_added += upd.num_new_nodes
            self._nw_dev = None         # device mirror is stale
        if u.size:
            self._ou.append(u.astype(np.int32))
            self._ov.append(v.astype(np.int32))
            self._ow.append(w.astype(np.float32))
            self._olen += u.size
        self.stats.update_batches += 1
        self.stats.edges_added += int(upd.add_u.shape[0])
        self.stats.edges_removed += int(upd.rem_u.shape[0])
        if self._olen > self.overlay_cap:
            self.compact()

    def add_edges(self, u, v, w=None) -> None:
        self.apply(GraphUpdate.add_edges(u, v, w))

    def remove_edges(self, u, v, w=None) -> None:
        self.apply(GraphUpdate.remove_edges(u, v, w))

    def add_nodes(self, nw) -> None:
        self.apply(GraphUpdate.add_nodes(nw))

    # ------------------------------------------------------------- compaction

    def _pack_overlay(self, Rb: int) -> tuple:
        """Concatenate the overlay chunk lists into Rb-padded COO arrays
        (shared by the merge dispatch and the view build)."""
        ou = np.zeros(Rb, np.int32)
        ov = np.zeros(Rb, np.int32)
        ow = np.zeros(Rb, np.float32)
        o = 0
        for cu, cv, cw in zip(self._ou, self._ov, self._ow):
            ou[o : o + cu.size] = cu
            ov[o : o + cu.size] = cv
            ow[o : o + cu.size] = cw
            o += cu.size
        return ou, ov, ow

    def _dispatch_merge(self) -> None:
        """Dispatch the overlay merge executable WITHOUT blocking on its
        result.  The merge's outputs (and the consumed overlay prefix's
        bookkeeping) park in ``_pending`` until :meth:`_finalize_pending`
        downloads the three result scalars and swaps the base — JAX async
        dispatch lets the caller overlap that device work with the next
        batch's repair."""
        self.stats.compact_calls += 1
        r = self._olen
        Rb = pow2(max(r, 8))
        ou, ov, ow = self._pack_overlay(Rb)
        Nb = pow2(max(self.n, 8))
        # node weights re-upload only after node churn (edge-only streams —
        # the common case — reuse the resident array across compactions)
        if self._nw_dev is None or self._nw_dev.shape[0] != Nb:
            nw = np.zeros(Nb, np.float32)
            nw[: self.n] = self._nw
            self._nw_dev = jnp.asarray(nw)
            _mem_account("base_csr", self._nw_dev)
            self._on_h2d(nw.nbytes)
        ou_d, ov_d, ow_d = jnp.asarray(ou), jnp.asarray(ov), jnp.asarray(ow)
        _mem_account("overlay_chunks", ou_d, ov_d, ow_d)
        self._on_h2d(ou.nbytes + ov.nbytes + ow.nbytes)
        Mb = self.base.indices.shape[0]
        ckey = (Mb, Rb, Nb)
        if ckey not in self.stats.compact_buckets:
            self.stats.compact_buckets.add(ckey)
            self.stats.compact_compiles += 1
            _obs_watchdog().note("store.compact", ckey)
        # base node bucket may be smaller than Nb after node adds; the merge
        # only reads arc arrays + the new nw, so no base re-pad is needed
        with _obs_span(
            "store.compact", cat="store", overlay=int(r), m=int(self.base.m)
        ):
            # deliberately NO sync_on: the merge's async dispatch (deferred
            # compaction overlaps the next batch's repair) must survive
            # tracing — the span covers dispatch, not device completion
            res = merge_overlay_device(
                self.base.src, self.base.indices, self.base.ew,
                ou_d, ov_d, ow_d,
                self._nw_dev,
                jnp.int32(self.n), jnp.int32(self.base.m), jnp.int32(r),
            )
            _mem_account("base_csr", *res[:4])  # in-flight merge outputs
        self._pending = dict(
            res=res, r=r, nchunks=len(self._ou), n=self.n,
            nw_dev=self._nw_dev,
        )

    def _finalize_pending(self) -> bool:
        """Block on a dispatched merge and install its result as the base.

        Returns False (discarding the pending result) when the node set
        changed since dispatch — the merge ran against a stale ``nw`` — so
        the caller re-compacts synchronously.  Overlay chunks consumed by
        the dispatch are dropped only here, which is what keeps snapshots
        and views taken while the merge was in flight consistent: they see
        (old base + full overlay), an equivalent graph."""
        p = self._pending
        self._pending = None
        if p is None:
            return False
        if p["n"] != self.n or p["nw_dev"] is not self._nw_dev:
            return False
        indptr, src_c, dst_c, ew_c, m_new, nwmax, ewmax = p["res"]
        m_new, nwmax, ewmax = jax.device_get((m_new, nwmax, ewmax))
        m_new = int(m_new)
        self._on_d2h(12)
        if float(ewmax) >= 2**24:
            # the first merge whose sums could round in f32: refuse rather
            # than silently break the exact-merge / bit-round-trip contract
            raise ValueError(
                "merged edge weight reached 2^24 — f32 exactness lost"
            )
        Mcb = arc_bucket(m_new)

        def fit(a, L, fill=0):
            if a.shape[0] == L:
                return a
            if a.shape[0] > L:
                return a[:L]
            return jnp.concatenate(
                [a, jnp.full((L - a.shape[0],), fill, a.dtype)]
            )

        self.base = GraphDev(
            indptr=indptr,
            indices=fit(dst_c, Mcb),
            ew=fit(ew_c, Mcb),
            nw=self._nw_dev,
            src=fit(src_c, Mcb),
            n=self.n, m=m_new,
            nw_max=float(nwmax), ew_max=float(ewmax), ew_integral=True,
            on_materialize=self._on_d2h,
        )
        self._base_host = None
        self._ou = self._ou[p["nchunks"]:]
        self._ov = self._ov[p["nchunks"]:]
        self._ow = self._ow[p["nchunks"]:]
        self._olen -= p["r"]
        return True

    def compact(self, deferred: bool = False) -> GraphDev:
        """Merge the overlay into a fresh base CSR (no-op when clean).

        One bucketed device executable (:func:`merge_overlay_device`); only
        the ``(m_new, nw_max, ew_max)`` scalars sync to host.  The previous
        base handle is dropped — callers caching device state against the
        old handle's identity must evict (the session does).

        ``deferred=True`` dispatches the merge and returns immediately with
        the OLD base still installed (the overlay stays queued, so views and
        snapshots remain correct); the swap happens at the next
        ``compact()``/``graph()`` call, by which time the device has
        finished the merge in the background.  Deferral requires a stable
        node set — node adds force the synchronous path."""
        if self._pending is not None and self._finalize_pending():
            if not self.dirty and self.n == self.base.n:
                return self.base
        if not self.dirty and self.n == self.base.n:
            return self.base
        if deferred and self.n == self.base.n and self.dirty:
            self._dispatch_merge()
            self.stats.compact_deferred += 1
            return self.base
        self._dispatch_merge()
        self._finalize_pending()
        return self.base

    # ------------------------------------------------------------ overlay view

    def can_view(self) -> bool:
        """True when :meth:`view` can serve the current state: pending arc
        deltas only — a stable node set (no adds since the last compaction,
        no tombstones awaiting vacuum) and a node bucket small enough for
        the view kernel's fused int32 keys."""
        Nb = self.base.indptr.shape[0] - 1
        return (
            self.dirty
            and self.n == self.base.n
            and self.pending_removals == 0
            and Nb * Nb < 2**31
        )

    def overlay_fraction(self) -> float:
        """Pending overlay arcs as a fraction of the base arc count — the
        quantity the session's ``compact_fraction`` policy thresholds on."""
        return self._olen / max(self.base.m, 1)

    def view(self) -> tuple:
        """Merged-adjacency device view of (base + overlay) WITHOUT
        compacting: ``(indptr, src, dst, ew, m_view)`` over ``Mb + Rb`` arc
        slots (see :func:`overlay_view_device`).  O(m) elementwise device
        work instead of the merge's O((m + r) log (m + r)) sort, and the
        base handle (with every cache keyed on its identity) survives.
        Requires :meth:`can_view`."""
        if not self.can_view():
            raise ValueError("store state not viewable (see can_view)")
        self.stats.view_calls += 1
        r = self._olen
        Rb = pow2(max(r, 8))
        ou, ov, ow = self._pack_overlay(Rb)
        Mb = self.base.indices.shape[0]
        Nb = self.base.indptr.shape[0] - 1
        vkey = (Mb, Rb, Nb)
        if vkey not in self.stats.view_buckets:
            self.stats.view_buckets.add(vkey)
            self.stats.view_compiles += 1
            _obs_watchdog().note("store.view", vkey)
        self._on_h2d(ou.nbytes + ov.nbytes + ow.nbytes)
        ou_d, ov_d, ow_d = jnp.asarray(ou), jnp.asarray(ov), jnp.asarray(ow)
        _mem_account("overlay_chunks", ou_d, ov_d, ow_d)
        with _obs_span(
            "store.view", cat="store", overlay=int(r), m=int(self.base.m)
        ) as sp:
            indptr_v, src_v, dst_v, ew_v, m_view = overlay_view_device(
                self.base.indptr, self.base.src, self.base.indices,
                self.base.ew,
                ou_d, ov_d, ow_d,
                jnp.int32(self.n), jnp.int32(self.base.m), jnp.int32(r),
            )
            sp.sync_on(m_view)
        _mem_account("overlay_chunks", indptr_v, src_v, dst_v, ew_v)
        return indptr_v, src_v, dst_v, ew_v, m_view

    def graph(self) -> GraphDev:
        """The up-to-date device graph: finalizes any in-flight deferred
        merge, compacts when the overlay has pending arcs OR nodes were
        added since the last compaction (node adds leave the overlay clean
        but the base's node set stale), then vacuums pending tombstones
        (relabel-on-compact; consult ``last_vacuum_map`` for the id
        remap)."""
        if self.dirty or self.n != self.base.n or self._pending is not None:
            self.compact()
        if self.pending_removals:
            self.vacuum()
        return self.base

    def csr_host(self) -> GraphNP:
        """Host CSR of the CURRENT graph (compacts, then materializes —
        the escalation path's one O(n + m) download)."""
        g = self.graph()
        if self._base_host is None:
            self._base_host = g.to_host()
        return self._base_host

    # ------------------------------------------------------------- tombstones

    def remove_nodes(self, ids) -> None:
        """Tombstone nodes for removal.  Only *isolated* nodes may be
        removed (disconnect them first with ``remove_edges``); the ids
        leave the CSR — and the id space re-packs contiguously — at the
        next vacuum (:meth:`graph` triggers one automatically)."""
        ids = np.unique(np.asarray(ids, dtype=np.int64))
        if ids.size == 0:
            return
        if ids.min() < 0 or ids.max() >= self.n:
            raise UpdateValidationError(
                "endpoint_out_of_range", f"node id outside [0, {self.n})"
            )
        # degrees must be judged on the MERGED graph: compact pending arc
        # deltas first so an edge removed in this same stream counts
        if self.dirty or self.n != self.base.n or self._pending is not None:
            self.compact()
        ii = jnp.asarray(ids.astype(np.int32))
        self._on_h2d(ids.size * 4)
        deg = np.asarray(
            jax.device_get(self.base.indptr[ii + 1] - self.base.indptr[ii])
        ).astype(np.int64)
        self._on_d2h(deg.nbytes // 2)
        if np.any(deg > 0):
            bad = ids[deg > 0][0]
            raise UpdateValidationError(
                "node_not_isolated",
                f"node {bad} still has degree {int(deg[deg > 0][0])}",
            )
        if self._tomb is None:
            self._tomb = np.zeros(self.n, dtype=bool)
        if np.any(self._tomb[ids]):
            raise UpdateValidationError(
                "node_already_removed", "duplicate tombstone"
            )
        self._tomb[ids] = True
        self.stats.nodes_removed += ids.size

    def vacuum(self) -> Optional[np.ndarray]:
        """Relabel-on-compact: physically drop tombstoned rows from the
        base CSR on device and re-pack node ids contiguously.

        Returns the old -> new id map ((old_n,) int64, -1 for removed
        nodes; also stashed as ``last_vacuum_map``), or None when no
        tombstones are pending.  Arc data survives bit-for-bit under the
        monotone remap; buckets are reused (no re-bucket churn), so the
        only host sync is the map itself."""
        if self.pending_removals == 0:
            return None
        if self.dirty or self.n != self.base.n or self._pending is not None:
            self.compact()
        self.stats.vacuum_calls += 1
        n_old = self.n
        tomb = self._tomb
        keep_h = ~tomb
        newid_h = np.cumsum(keep_h).astype(np.int32) - 1
        mapping = np.where(keep_h, newid_h.astype(np.int64), -1)
        n_new = int(keep_h.sum())
        Mb = self.base.indices.shape[0]
        Nb = self.base.indptr.shape[0] - 1
        vkey = (Mb, Nb)
        if vkey not in self.stats.vacuum_buckets:
            self.stats.vacuum_buckets.add(vkey)
            self.stats.vacuum_compiles += 1
            _obs_watchdog().note("store.vacuum", vkey)
        newid = np.zeros(Nb, np.int32)
        newid[:n_old] = np.maximum(newid_h, 0)
        keep = np.zeros(Nb, bool)
        keep[:n_old] = keep_h
        self._on_h2d(newid.nbytes + keep.nbytes)
        newid_d, keep_d = jnp.asarray(newid), jnp.asarray(keep)
        _mem_account("base_csr", newid_d, keep_d)
        with _obs_span(
            "store.vacuum", cat="store", removed=int(n_old - n_new)
        ) as sp:
            indptr_r, src_r, dst_r, ew_r, nw_r = vacuum_device(
                self.base.src, self.base.indices, self.base.ew,
                newid_d, keep_d, self.base.nw,
                jnp.int32(self.base.m),
            )
            sp.sync_on(nw_r)
        self._nw = self._nw[keep_h]
        self._nw_dev = nw_r
        self.base = GraphDev(
            indptr=indptr_r, indices=dst_r, ew=ew_r, nw=nw_r, src=src_r,
            n=n_new, m=self.base.m,
            nw_max=float(self._nw.max()) if n_new else 0.0,
            ew_max=self.base.ew_max, ew_integral=True,
            on_materialize=self._on_d2h,
        )
        self.n = n_new
        self._tomb = None
        self._base_host = None
        self.last_vacuum_map = mapping
        return mapping

    # ------------------------------------------------------- snapshot support

    def snapshot_state(self) -> dict:
        """O(overlay-chunks) structural snapshot of the store's graph state.

        Every payload array is captured *by reference*: the base
        :class:`GraphDev` holds immutable jax arrays, ``_nw`` and
        ``_nw_dev`` are rebind-only (``apply`` concatenates into a fresh
        array), and overlay chunks are appended but never mutated in place —
        so only the chunk *lists* need copying.  Counters (``stats``) are
        monitoring state, not serving state, and are deliberately excluded."""
        return dict(
            n=self.n,
            base=self.base,
            nw=self._nw,
            nw_dev=self._nw_dev,
            base_host=self._base_host,
            ou=list(self._ou),
            ov=list(self._ov),
            ow=list(self._ow),
            olen=self._olen,
            tomb=None if self._tomb is None else self._tomb.copy(),
        )

    def restore_state(self, st: dict) -> None:
        """Rebind graph state to a :meth:`snapshot_state` capture — restores
        node set, base CSR handle, and the pending overlay bit-identically.
        An in-flight deferred merge is discarded: its consumed-prefix
        bookkeeping refers to the pre-restore chunk lists, and a later
        compaction of the restored overlay reproduces the same graph."""
        self._pending = None
        self.n = st["n"]
        self.base = st["base"]
        self._nw = st["nw"]
        self._nw_dev = st["nw_dev"]
        self._base_host = st["base_host"]
        self._ou = list(st["ou"])
        self._ov = list(st["ov"])
        self._ow = list(st["ow"])
        self._olen = st["olen"]
        tomb = st.get("tomb")
        self._tomb = None if tomb is None else tomb.copy()
