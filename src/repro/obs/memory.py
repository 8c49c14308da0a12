"""Device-memory accounting and capacity planning (PR 10).

Two halves of one question — *how many bytes does each stage hold live on
device, and will graph G fit?*

**Accounting.**  :class:`DeviceMemoryAccountant` attributes live device
buffers to named *families* (:data:`MEMORY_FAMILIES`): the base CSR levels,
the LP engine's chunk packs, the dynamic store's overlay chunks, label
arenas, the evolutionary population batch, deployed block shards, and
snapshot reference captures.  Allocation sites call :func:`account`
(``graph/csr.py``, ``core/engine.py``, ``dynamic/store.py``,
``deploy/extract.py``, …) with the arrays they just made resident; a
``weakref.finalize`` per buffer decrements the family total when the last
Python reference drops (jax arrays are immutable and refcounted, so the
finalizer fires synchronously at release — the family totals track
*liveness*, not allocation volume).  Snapshot captures :func:`pin` instead:
pins are counted per family but excluded from the additive total, because a
snapshot holds references to arrays another family already owns — the
additive total therefore stays comparable to a ``jax.live_arrays()`` sweep
(the oracle the tests use).

Accounting is **off by default** (:func:`set_accounting`); every
instrumented site pays one attribute load + one bool test when disabled —
the same contract as the span tracer, pinned under the 2% obs gate.

When enabled, the accountant feeds three surfaces:

* per-family byte gauges (``mem.<family>_bytes``) in a
  :class:`~repro.obs.registry.MetricsRegistry` handed to
  :func:`set_accounting`;
* peak watermarks — global (:attr:`peak_by_family`) and per span close
  (the tracer calls :meth:`note_span`, so every V-cycle level and repair
  phase records the footprint it peaked at);
* Perfetto counter tracks — the tracer appends a ``"ph": "C"`` event per
  span close, so the Chrome trace shows family bytes as stacked counters
  under the spans that allocated them.

**Capacity planning.**  :func:`estimate_footprint` is the closed form of
the allocator: every persistent buffer in the stack is sized by the two
bucket policies (``pow2`` node/label axes, ``arc_bucket`` arc axes) plus
the chunk geometry, so the expected footprint of partitioning or serving
an (n, m, k) graph is computable *before uploading anything*.
``LPEngine.will_fit`` exposes it as the pre-upload check.

``KNOWN_ALLOC_SITES`` is the registration manifest for the AST static
check (:mod:`repro.obs.static_check`): every syntactic device-allocation
site in the instrumented modules must map to a buffer family (or carry an
``exempt:`` reason), so new allocations cannot land unaccounted.
"""

from __future__ import annotations

import threading
import weakref
from collections import deque
from typing import Dict, Optional

from .registry import MetricsRegistry

__all__ = [
    "MEMORY_FAMILIES",
    "KNOWN_ALLOC_SITES",
    "ALLOC_CHECK_MODULES",
    "DeviceMemoryAccountant",
    "accountant",
    "set_accounting",
    "account",
    "pin",
    "estimate_footprint",
    "will_fit",
]


#: Buffer families every persistent device allocation maps to.
MEMORY_FAMILIES = (
    "base_csr",        # GraphDev levels: indptr/indices/ew/src/nw + contraction scratch
    "chunk_packs",     # LP engine packs: chunk/ELL gathers, repair region packs
    "overlay_chunks",  # dynamic store COO overlay uploads + view materializations
    "label_arenas",    # arena-sized label/weight arrays (labels, restrict, cw)
    "evo_population",  # coarsest-stage GA population batch + degree scratch
    "block_shards",    # deployed BlockShard arrays (block CSR + ghost halo)
    "snapshot_refs",   # resilience snapshots (reference captures; pinned, not additive)
)


class DeviceMemoryAccountant:
    """Attributes live device buffers to :data:`MEMORY_FAMILIES`.

    ``register`` is idempotent per buffer identity (re-registering the
    array object jax returned unchanged is free) and thread-safe; release
    is automatic via ``weakref.finalize``.  All byte totals are *live*
    bytes: peak watermarks (global and per span) are the capacity numbers.
    """

    def __init__(self, registry: Optional[MetricsRegistry] = None):
        self.enabled = False
        self.registry = registry
        self._lock = threading.Lock()
        self._live: Dict[int, tuple] = {}     # id(arr) -> (family, nbytes)
        self._pins: Dict[int, tuple] = {}     # id(arr) -> (family, nbytes)
        self.bytes_by_family: Dict[str, int] = {f: 0 for f in MEMORY_FAMILIES}
        self.pinned_by_family: Dict[str, int] = {f: 0 for f in MEMORY_FAMILIES}
        self.peak_by_family: Dict[str, int] = {f: 0 for f in MEMORY_FAMILIES}
        self.total = 0
        self.peak_total = 0
        #: enabled register()/pin() invocations — the obs_overhead bench
        #: multiplies this per-update count by the disabled-path ns/call to
        #: bound the accounting-off cost (the span-overhead idiom)
        self.calls = 0
        #: bounded span-close watermark log: (span name, total, {family: bytes})
        self.span_marks = deque(maxlen=4096)

    # ------------------------------------------------------------- register

    def register(self, family: str, *arrays) -> None:
        """Attribute ``arrays`` (anything with ``.nbytes``) to ``family``."""
        if not self.enabled:
            return
        if family not in self.bytes_by_family:
            raise KeyError(f"unknown memory family {family!r}")
        self.calls += 1
        for a in arrays:
            nb = getattr(a, "nbytes", None)
            if nb is None:
                continue
            aid = id(a)
            with self._lock:
                if aid in self._live:
                    continue
                self._live[aid] = (family, nb)
                self.bytes_by_family[family] += nb
                self.total += nb
                if self.bytes_by_family[family] > self.peak_by_family[family]:
                    self.peak_by_family[family] = self.bytes_by_family[family]
                if self.total > self.peak_total:
                    self.peak_total = self.total
            try:
                weakref.finalize(a, self._release, aid)
            except TypeError:
                pass   # not weakrefable: stays attributed until reset()
            self._publish(family)

    def pin(self, family: str, *arrays) -> None:
        """Like :meth:`register`, but *non-additive*: pins record that a
        family (snapshots) holds references to buffers another family
        already owns, so they are tracked per family but excluded from
        ``total`` — keeping the additive total equal to a
        ``jax.live_arrays()`` sweep."""
        if not self.enabled:
            return
        if family not in self.pinned_by_family:
            raise KeyError(f"unknown memory family {family!r}")
        self.calls += 1
        for a in arrays:
            nb = getattr(a, "nbytes", None)
            if nb is None:
                continue
            aid = id(a)
            with self._lock:
                if aid in self._pins:
                    continue
                self._pins[aid] = (family, nb)
                self.pinned_by_family[family] += nb
            try:
                weakref.finalize(a, self._release_pin, aid)
            except TypeError:
                pass
            self._publish(family)

    def _release(self, aid: int) -> None:
        with self._lock:
            ent = self._live.pop(aid, None)
            if ent is None:
                return
            family, nb = ent
            self.bytes_by_family[family] -= nb
            self.total -= nb
        self._publish(family)

    def _release_pin(self, aid: int) -> None:
        with self._lock:
            ent = self._pins.pop(aid, None)
            if ent is None:
                return
            family, nb = ent
            self.pinned_by_family[family] -= nb
        self._publish(family)

    def _publish(self, family: str) -> None:
        reg = self.registry
        if reg is not None:
            reg.gauge(
                f"mem.{family}_bytes",
                self.bytes_by_family[family] + self.pinned_by_family[family],
            )
            reg.gauge("mem.total_bytes", self.total)

    # ------------------------------------------------------------ queries

    def live_bytes(self, family: Optional[str] = None) -> int:
        if family is None:
            return self.total
        return self.bytes_by_family[family]

    def note_span(self, name: str, args: Optional[dict] = None) -> None:
        """Span-close watermark hook (called by ``Tracer._record``): records
        the live footprint this span closed at, keyed by span name — the
        per-V-cycle-level / per-repair-phase capacity trail."""
        if not self.enabled:
            return
        rec = dict(
            name=name,
            total=self.total,
            by_family={f: b for f, b in self.bytes_by_family.items() if b},
        )
        if args:
            for key in ("n", "level", "step", "mode", "region"):
                if key in args:
                    rec[key] = args[key]
        self.span_marks.append(rec)

    def counter_event(self, ts: float, pid: int) -> dict:
        """Chrome-trace counter ("ph": "C") sample of the family bytes."""
        return dict(
            name="device_memory", cat="mem", ph="C", ts=ts, pid=pid, tid=0,
            args={f: self.bytes_by_family[f] for f in MEMORY_FAMILIES},
        )

    def snapshot(self) -> dict:
        with self._lock:
            return dict(
                enabled=self.enabled,
                total=self.total,
                peak_total=self.peak_total,
                by_family=dict(self.bytes_by_family),
                pinned_by_family=dict(self.pinned_by_family),
                peak_by_family=dict(self.peak_by_family),
                buffers=len(self._live),
            )

    # ----------------------------------------------------------- lifecycle

    def reset_peaks(self) -> None:
        with self._lock:
            self.peak_by_family = dict(self.bytes_by_family)
            self.peak_total = self.total
            self.span_marks.clear()

    def reset(self) -> None:
        """Forget every attribution (finalizers become no-ops)."""
        with self._lock:
            self._live.clear()
            self._pins.clear()
            self.bytes_by_family = {f: 0 for f in MEMORY_FAMILIES}
            self.pinned_by_family = {f: 0 for f in MEMORY_FAMILIES}
            self.peak_by_family = {f: 0 for f in MEMORY_FAMILIES}
            self.total = 0
            self.peak_total = 0
            self.calls = 0
            self.span_marks.clear()


_acct = DeviceMemoryAccountant()


def accountant() -> DeviceMemoryAccountant:
    """The process-global accountant (mirrors ``watchdog()``)."""
    return _acct


def set_accounting(
    enabled: bool, registry: Optional[MetricsRegistry] = None
) -> bool:
    """Enable/disable device-memory accounting; returns the previous state.

    ``registry``, when given, receives ``mem.<family>_bytes`` gauges on
    every attribution change (pass the serving stack's registry so the
    gauges ride the existing SLO export)."""
    prev = _acct.enabled
    if registry is not None:
        _acct.registry = registry
    _acct.enabled = bool(enabled)
    return prev


def account(family: str, *arrays) -> None:
    """Allocation-site entry point: attribute ``arrays`` to ``family``.

    Disabled fast path: one global load + one bool test (same contract as
    ``obs.span``)."""
    a = _acct
    if not a.enabled:
        return
    a.register(family, *arrays)


def pin(family: str, *arrays) -> None:
    """Reference-capture entry point (snapshots): non-additive accounting."""
    a = _acct
    if not a.enabled:
        return
    a.pin(family, *arrays)


# --------------------------------------------------------------------------
# capacity planning: the closed form of the allocator
# --------------------------------------------------------------------------


def _pow2(x: int) -> int:
    return 1 << max(0, int(x) - 1).bit_length()


def _arc_bucket(m: int) -> int:
    if m <= 16384:
        return _pow2(max(m, 8))
    return -(-m // 16384) * 16384


def _csr_bytes(n: int, m: int) -> int:
    """One GraphDev level: indptr + nw on the pow2 node bucket, three
    arc-bucket arrays (indices/ew/src), all 4-byte dtypes."""
    Nb = _pow2(max(n, 8))
    Mb = _arc_bucket(max(m, 8))
    return 4 * (Nb + 1) + 4 * Nb + 12 * Mb


def _pack_geometry(n: int, m: int, target_chunks: int) -> tuple:
    """(Cb, N, E) of the engine's frozen chunk geometry for an (n, m)
    graph: ``chunk_geometry`` floors, pow2-snapped per-chunk edge capacity,
    and a chunk count bounded by BOTH caps — on power-law graphs the greedy
    planner closes hub chunks on the edge cap and tail chunks on the node
    cap, so the two quotas are additive (measured: ba-16384 plans 96
    chunks -> pow2 128, exactly node-quota 64 + edge-quota 32)."""
    tc = max(target_chunks, 2)
    N = max(256, -(-n // tc))
    E_raw = max(4096, -(-m // (tc // 2)))
    E = _pow2(E_raw)
    Cb = _pow2(-(-n // N) + -(-m // E_raw))
    return Cb, N, E


def _pack_bytes(Cb: int, N: int, E: int) -> int:
    """One chunk pack: nodes (Cb, N) i32 + node_valid bool + edge
    dst/w/src_slot (Cb, E) 4-byte + edge_valid bool."""
    return Cb * N * 5 + Cb * E * 13


def estimate_footprint(
    n: int,
    m: int,
    k: int,
    cfg=None,
    *,
    workload: str = "partition",
    arc_retention: float = 0.62,
    overlay_cap: int = 1 << 16,
    islands: int = 2,
    pop_per_island: int = 2,
) -> dict:
    """Closed-form expected peak device footprint for an (n, m, k) graph.

    Derived from the stack's bucket policies — pow2 node/label axes,
    ``arc_bucket`` arc axes, the engine's frozen chunk geometry — plus the
    measured structure of the pipeline on complex networks:

    * size-constrained LP clustering contracts to the coarsest target in
      ONE level (ba-16384 -> 1800 nodes in a single contraction), retaining
      ``arc_retention`` of the arcs (measured 0.616 on ba-16384; complex
      networks keep most inter-hub arcs under clustering);
    * three chunk packs over the finest level are co-resident (the engine
      caches one pack per sweep mode), plus one coarse pack in flight;
    * two V-cycles keep two coarse GraphDev levels briefly co-resident.

    ``workload="partition"`` models a full multilevel run (GraphDev
    hierarchy + packs + arenas + GA population); ``workload="dynamic"``
    models the serving peak (compaction triple-buffers the base CSR: old
    base + in-flight merge outputs + new level).  ``cfg`` may be a
    ``PartitionerConfig`` / ``SessionConfig``-like object;
    ``target_chunks`` / ``coarsest_factor`` / ``islands`` /
    ``pop_per_island`` / ``overlay_cap`` / ``compact_fraction`` are read
    off it when present.

    Returns a dict with per-family byte estimates plus ``"total"`` (sum of
    the per-family peaks — families peak in different phases, so this is
    the planning bound, not a single instant).  Validated against measured
    peak family bytes (tests/test_memory.py, 15% tolerance on ba-16384)."""
    compact_fraction = 0.0
    if cfg is not None:
        target_chunks = getattr(cfg, "target_chunks", 64)
        cf = getattr(cfg, "coarsest_factor", 0)
        islands = getattr(cfg, "islands", islands)
        pop_per_island = getattr(cfg, "pop_per_island", pop_per_island)
        overlay_cap = getattr(cfg, "overlay_cap", overlay_cap)
        compact_fraction = getattr(cfg, "compact_fraction", 0.0)
    else:
        target_chunks = 64
        cf = 0
    coarsest = cf * k if cf and cf > 0 else max(k, min(10000 * k, n // 8))

    fam = {f: 0 for f in MEMORY_FAMILIES}
    A = _pow2(max(n + 1, 8))
    Mb = _arc_bucket(max(m, 8))
    Cb, N, E = _pack_geometry(n, m, target_chunks)
    levels = 1 if coarsest < n else 0
    m1 = int(m * arc_retention)

    if workload == "partition":
        # --- base_csr ----------------------------------------------------
        # finest level stays host-resident; its device footprint is the
        # engine arena's arc triplet (src/dst/ew, exact m) + the padded
        # contraction inputs (3 arc-bucket arrays)
        fam["base_csr"] = 12 * m + 12 * Mb
        if levels:
            # two V-cycles: two coarse GraphDev levels briefly co-resident
            fam["base_csr"] += 2 * _csr_bytes(coarsest, m1)
        # CoarseMap labels + indptr scratch on the finest pow2 bucket
        fam["base_csr"] += 8 * _pow2(max(n, 8))

        # --- chunk_packs: 3 finest packs + one coarse in flight ----------
        fam["chunk_packs"] = 3 * _pack_bytes(Cb, N, E)
        if levels:
            C1 = _pow2(max(-(-m1 // E), 1))   # frozen (N, E), edge-bound
            fam["chunk_packs"] += _pack_bytes(C1, N, E)

        # --- label_arenas: labels / restrict / projected / refined + cw --
        fam["label_arenas"] = 6 * 4 * A

        # --- evo_population: (pow2(I*P), pow2(nc)) labels+keys + degrees -
        nc = max(int(coarsest), k)
        Sb = _pow2(max(islands * pop_per_island, 1))
        Ab = _pow2(max(nc, 8))
        fam["evo_population"] = Sb * Ab * 8 + Ab * 4

    elif workload == "dynamic":
        # compaction triple-buffers the base: old handle + in-flight merge
        # outputs + the fresh GraphDev all live until the swap completes
        fam["base_csr"] = 3 * _csr_bytes(n, m)
        Rb = _pow2(max(min(overlay_cap, max(m // 2, 8)), 8))
        if compact_fraction > 0.0:
            # view serving: overlay chunks accrue to the threshold and the
            # materialized view quadruplet spans base + overlay arcs
            fam["overlay_chunks"] = (
                12 * Rb + 4 * (_pow2(max(n, 8)) + 1) + 12 * (Mb + Rb)
            )
        else:
            # compact-every-step: only one batch's COO upload in flight
            fam["overlay_chunks"] = 12 * _pow2(max(overlay_cap // 64, 8))
        fam["label_arenas"] = 4 * 4 * A
        # repair region packs: 2-hop regions gather about a third of the
        # full-graph pack on power-law graphs (measured on ba-16384)
        fam["chunk_packs"] = _pack_bytes(Cb, N, E) // 3
    else:
        raise ValueError(f"unknown workload {workload!r}")

    fam["total"] = sum(v for f, v in fam.items() if f != "total")
    fam["levels"] = levels if workload == "partition" else 0
    fam["coarsest_target"] = coarsest
    return fam


def will_fit(
    n: int,
    m: int,
    k: int,
    cfg=None,
    *,
    budget_bytes: Optional[int] = None,
    workload: str = "partition",
    safety: float = 1.25,
) -> dict:
    """Pre-upload capacity check: does (n, m, k) fit the device?

    ``budget_bytes`` defaults to the device's reported memory limit
    (``device.memory_stats()['bytes_limit']``) on accelerators, where a
    failing or limit-less ``memory_stats()`` raises; on the CPU, which has
    no limit, the check reports the estimate with ``fits=None`` unless a
    budget is given.  ``safety`` head-room multiplies the estimate
    (fragmentation + XLA scratch)."""
    est = estimate_footprint(n, m, k, cfg, workload=workload)
    if budget_bytes is None:
        import jax

        dev = jax.devices()[0]
        if dev.platform != "cpu":
            stats = dev.memory_stats() or {}
            if "bytes_limit" not in stats:
                raise RuntimeError(
                    f"{dev.device_kind} reports no bytes_limit: {stats}"
                )
            budget_bytes = int(stats["bytes_limit"])
    need = int(est["total"] * safety)
    return dict(
        estimate=est,
        required_bytes=need,
        budget_bytes=budget_bytes,
        fits=None if budget_bytes is None else bool(need <= budget_bytes),
    )


# --------------------------------------------------------------------------
# static-check manifest: device-allocation sites -> buffer family
# --------------------------------------------------------------------------

#: Modules (relative to ``src/repro``) whose device-allocation sites the
#: AST static check requires to be present in :data:`KNOWN_ALLOC_SITES`.
ALLOC_CHECK_MODULES = (
    "graph/csr.py",
    "graph/packing.py",
    "core/engine.py",
    "dynamic/store.py",
    "deploy/extract.py",
    "resilience/snapshot.py",
)

#: ``"<relpath>::<site>" -> family`` (or ``"exempt:<reason>"``).  Filled in
#: lock-step with the ``account()`` calls at the allocation chokepoints;
#: ``tests/test_obs.py`` fails if a site is missing or stale.
KNOWN_ALLOC_SITES: Dict[str, str] = {
    # graph/csr.py — GraphDev.__init__ is the single base-CSR chokepoint:
    # every level (upload, contraction output, store merge/vacuum) flows
    # through it, so upload helpers inherit its registration
    "graph/csr.py::arc_sources": "base_csr",
    "graph/csr.py::to_device": "base_csr",
    "graph/csr.py::to_device_csr": "base_csr",
    # core/engine.py
    "core/engine.py::_arena": "label_arenas",
    "core/engine.py::_contract_inputs": "base_csr",
    "core/engine.py::_deg_f": "evo_population",
    "core/engine.py::_ell": "chunk_packs",
    "core/engine.py::_evo_table": "chunk_packs",
    "core/engine.py::_evolve_sharded": "evo_population",
    "core/engine.py::_indptr_dev": "base_csr",
    "core/engine.py::_iota": "label_arenas",
    "core/engine.py::_pack_dev": "chunk_packs",
    "core/engine.py::_pack_host_build": "chunk_packs",
    "core/engine.py::contract": "base_csr",
    "core/engine.py::evolve_device": "evo_population",
    "core/engine.py::project": "label_arenas",
    "core/engine.py::project_restrict": "label_arenas",
    "core/engine.py::repair": "chunk_packs",
    "core/engine.py::to_arena": "label_arenas",
    "core/engine.py::block_weights": "exempt:O(k) reduction scratch",
    "core/engine.py::cluster": "exempt:O(k) scalar/round scratch",
    "core/engine.py::_weights_exact": "exempt:scalar-reduction scratch",
    "core/engine.py::refine": "exempt:O(k) block-weight scratch",
    # dynamic/store.py
    "dynamic/store.py::_dispatch_merge": "overlay_chunks",
    "dynamic/store.py::_finalize_pending": "base_csr",
    "dynamic/store.py::vacuum": "base_csr",
    "dynamic/store.py::view": "overlay_chunks",
    "dynamic/store.py::remove_nodes": "exempt:O(removed) validation upload",
    # deploy/extract.py
    # graph/csr.py: sort_by_keys is only ever traced inside jitted kernels
    "graph/csr.py::sort_by_keys": "exempt:traced inside jitted kernels",
    "deploy/extract.py::_labels_nb": "label_arenas",
}
