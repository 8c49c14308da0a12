"""Device-resident LP engine: pack caching, shape bucketing, sweep dispatch.

The multilevel driver (``repro.core.multilevel``) used to derive fresh chunk
shapes from every level's exact ``(n, m)`` and re-jit ``_lp_sweep`` at every
level of every V-cycle, repacking and re-uploading the graph for each
``lp_cluster``/``lp_refine`` call.  :class:`LPEngine` owns all of that state
for one ``partition()`` run instead:

* **Shape bucketing** — chunk geometry is frozen from the finest graph and
  every level's :class:`~repro.graph.packing.ChunkPack` is padded
  (:func:`~repro.graph.packing.pad_pack`) up to shared power-of-two buckets
  ``(C, N, E)``; label/weight arrays live in a power-of-two *arena*
  ``A >= n_finest + 1``.  Combined with the sweep's traced ``num_labels`` /
  ``num_chunks`` scalars, one compiled executable per
  ``(iters, mode, restrict)`` combination serves the whole hierarchy —
  compile count is ``O(#buckets)``, not ``O(#levels x #cycles)``.
* **Pack caching** — packs, ELL packs, and per-graph device arrays (arena
  node weights, cluster weight bases, arc endpoints for cut evaluation) are
  cached per ``(graph, order-mode)`` and uploaded once.  The finest graph is
  identical across V-cycles, so cycles 2..N reuse cycle-1 packs; traversal
  is re-randomized by permuting chunk visit order *on device* (see
  ``_lp_sweep``), not by repacking on host.
* **Device-resident refinement** — ``refine``/``refine_dense`` take and
  return arena-sized device label arrays; projection through the hierarchy
  (``project``), cut evaluation (``cut``) and block weights
  (``block_weights``) all run on device, so uncoarsening never round-trips
  labels through numpy between levels.
* **Dense fast path** — ``refine_dense`` iterates the Pallas-backed
  synchronous round (``repro.kernels.lp_score.dense_round_device``) on a
  cached ELL pack: one kernel launch per iteration instead of a sequential
  chunk walk.  ELL packs are padded to power-of-two row/node buckets so the
  dense round also compiles once per bucket, not once per level.
* **Device-resident coarsening** — ``contract`` runs the whole §IV-C
  quotient-graph construction on device (``contract_device``): relabel,
  node-weight segment-sum, arc dedup, and CSR rebuild in one bucketed
  executable.  The coarse graph stays on device as a
  :class:`~repro.graph.csr.GraphDev` handle whose adjacency feeds the next
  level's pack *gather* (``gather_pack_device``) directly — only the O(n)
  chunk plan is computed on host, so ``cluster -> contract -> next-level
  pack`` chains device-to-device and only the ``(n_c, m_c, max nw)``
  scalars cross per level.

Engine state is per-``partition()``-run; it is not thread-safe and holds
strong references to every level's graph until released.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp

from ..graph.csr import GraphDev, GraphNP, arc_bucket, pow2
from ..graph.packing import (
    chunk_geometry,
    ell_pack,
    gather_ell_device,
    gather_pack_device,
    layout_nodes,
    pack_chunks,
    pad_pack,
    plan_chunks,
    plan_ell_rows,
    plan_region_pack,
)
from ..kernels.lp_score.lp_score import default_interpret
from ..obs import MetricsRegistry, RegistryBackedStats
from ..obs import span as _obs_span
from ..obs import watchdog as _obs_watchdog
from ..obs.memory import account as _mem_account
from .contraction import CoarseMap, contract_device, packed_key_wbits
from .label_propagation import _lp_sweep, make_order
from .metrics import cut_from_arcs_jnp

__all__ = ["LPEngine", "EngineStats"]

AnyGraph = Union[GraphNP, GraphDev]


# bucket policies live in graph/csr.py (shared with the dynamic store)
_pow2 = pow2
_mbucket = arc_bucket


@dataclass
class _DevicePack:
    """A chunk pack padded to bucket shape, uploaded (or gathered) once."""

    graph: AnyGraph         # strong ref: pins id(graph) for cache identity
    nodes: jax.Array
    node_valid: jax.Array
    edge_dst: jax.Array
    edge_w: jax.Array
    edge_src_slot: jax.Array
    edge_valid: jax.Array
    num_chunks: int         # live chunks (<= padded C)
    shape: Tuple[int, int, int]


@dataclass
class _Arena:
    """Per-graph device arrays shared by every sweep over that graph."""

    graph: AnyGraph
    nw_arena: jax.Array     # (A,) f32 — node weights, 0 beyond n
    cluster_w: jax.Array    # (A,) f32 — per-node weights, +inf beyond n
    src: jax.Array          # (>= m,) int32 — arc sources (padding carries w 0)
    dst: jax.Array          # (>= m,) int32
    ew: jax.Array           # (>= m,) f32
    integral: bool          # integral arc weights totalling < 2**31: cuts
                            # sum exactly in int32


@dataclass
class _DeviceEll:
    graph: AnyGraph
    dst: jax.Array          # (Rb, W) int32 — rows padded to a pow2 bucket
    w: jax.Array            # (Rb, W) f32
    row_node: jax.Array     # (Rb,) int32, sentinel n
    nb: int                 # node bucket: pow2(n + 1) <= arena size


class EngineStats(RegistryBackedStats):
    """Counters surfaced through ``PartitionReport.engine_stats``.

    Counter fields live in a :class:`~repro.obs.MetricsRegistry` (one per
    serving stack — the dynamic session threads its registry in so
    engine + store + session share one snapshot/reset/export path);
    bucket-key sets stay real sets (tests unpack them).
    """

    _COUNTER_FIELDS = (
        "sweep_calls",
        "sweep_compiles",       # distinct (bucket, statics) combinations
        "pack_builds",
        "pack_hits",
        "dense_rounds",
        "dense_compiles",       # distinct dense-round bucket shapes
        "evo_calls",            # batched-evolution executable dispatches
        "evo_compiles",         # distinct evo (phase, bucket) shapes
        "contract_calls",
        "contract_compiles",    # distinct (Nb, Mb) contraction buckets
        "gather_builds",        # device pack gathers (GraphDev levels)
        "gather_compiles",      # distinct gather shape combinations
        "repair_calls",         # incremental-repair dispatches (dynamic)
        "repair_compiles",      # distinct repair-kernel shape buckets
        "audit_calls",          # invariant-audit dispatches (resilience)
        "audit_compiles",       # distinct audit-kernel shape buckets
        "h2d_bytes",            # host->device uploads the engine issued
        "d2h_bytes",            # device->host downloads (scalars + lazy
                                # materializations of GraphDev/CoarseMap)
        "host_reads",           # blocking device->host reads (``host_read``
                                # and GraphDev/CoarseMap materializations)
        "evo_grow_rounds",      # device GA grow-loop trips, summed over seed
                                # steps; counted only where read (tracing)
        "evo_grow_budget",      # grow_rounds_bound summed over seed steps
        "evo_table_steps",      # seed + generation steps whose node rounds
                                # read the neighbour table (no scatter)
    )
    _SET_FIELDS = (
        "buckets",              # distinct (C, N, E, A, W)
        "contract_buckets",     # distinct (Nb, Mb)
        "evo_buckets",          # distinct evo shape keys
        "repair_buckets",       # distinct repair shapes
        "audit_buckets",        # distinct audit shapes
    )

    @property
    def bucket_count(self) -> int:
        return len(self.buckets)

    @property
    def contract_bucket_count(self) -> int:
        return len(self.contract_buckets)

    @property
    def evo_bucket_count(self) -> int:
        return len(self.evo_buckets)

    @property
    def repair_bucket_count(self) -> int:
        return len(self.repair_buckets)

    @property
    def audit_bucket_count(self) -> int:
        return len(self.audit_buckets)

    def note_audit_key(self, key) -> None:
        """Record one audit-kernel dispatch shape (the resilience auditor's
        compile-accounting hook — same discipline as every other kernel
        family: ``audit_compiles == audit_bucket_count``)."""
        if key not in self.audit_buckets:
            self.audit_buckets.add(key)
            self.audit_compiles += 1
            _obs_watchdog().note("engine.audit", key)


class LPEngine:
    """Owns packing, caching, and sweep dispatch for one multilevel run."""

    def __init__(
        self,
        g0: AnyGraph,
        *,
        target_chunks: int = 64,
        seed: int = 0,
        use_pallas: bool = True,
        interpret: Optional[bool] = None,
        pack_block: int = 8,
        registry: Optional[MetricsRegistry] = None,
    ):
        n0, m0 = g0.n, g0.m
        # Small packing mini-blocks keep the max block-degree-sum (which
        # forces the per-chunk edge capacity) low on coarse power-law levels,
        # so levels rarely overflow the shared E bucket.
        self.pack_block = int(pack_block)
        # Chunk geometry frozen from the finest level (same request floors
        # the driver used to recompute per level).  N is rounded to a power
        # of two; the shared edge bucket E_floor is *learned* from the first
        # pack actually built (the finest, hottest level), so the hot level
        # pays near-zero edge-axis padding and coarser levels pad up into
        # its bucket.
        n_req, e_req = chunk_geometry(n0, m0, target_chunks)
        self.N = _pow2(n_req)
        self._e_request = e_req
        self.E_floor = 0
        self._g0_id = id(g0)
        # label/weight arena; floored at the GraphDev node bucket's minimum
        # (to_device_csr/contract emit Nb >= 8) so _arena's device extend
        # never sees a negative pad on tiny graphs
        self.A = _pow2(max(n0 + 1, 8))
        self.C_bucket = 8                   # grows to the finest pack's C
        self.seed = int(seed)
        self.use_pallas = bool(use_pallas)
        self.interpret = (
            default_interpret() if interpret is None else bool(interpret)
        )
        self.stats = EngineStats(registry)
        self._packs: Dict[Tuple[int, str], _DevicePack] = {}
        self._arenas: Dict[int, _Arena] = {}
        self._ells: Dict[int, _DeviceEll] = {}
        self._cin: Dict[int, tuple] = {}    # padded contraction inputs (GraphNP)
        self._degs: Dict[int, jax.Array] = {}  # (Ab,) f32 degree arrays (evo)
        self._tables: Dict[int, tuple] = {}  # id(g) -> (g, evo table or None)
        self._indptrs: Dict[int, jax.Array] = {}  # device row ptrs (GraphNP)
        self._repair_E = 0                  # sticky region-pack edge bucket
        self._iota_cache: Optional[jax.Array] = None  # lazy: dist path may never sweep
        self._compile_keys = set()
        self._gather_keys = set()
        self._dense_keys = set()
        self._exact: Dict[int, tuple] = {}  # id(g) -> (g, GA weights exact)
        self._shard_steps: Dict[tuple, object] = {}
        self._grow_rounds: List[jax.Array] = []  # unread seed-step counts

    @property
    def _iota(self) -> jax.Array:
        if self._iota_cache is None:
            self._iota_cache = jnp.arange(self.A, dtype=jnp.int32)
            _mem_account("label_arenas", self._iota_cache)
        return self._iota_cache

    @staticmethod
    def will_fit(n: int, m: int, k: int, cfg=None, *, budget_bytes=None,
                 workload: str = "partition", safety: float = 1.25) -> dict:
        """Pre-upload capacity check: closed-form footprint of partitioning
        (or serving) an (n, m, k) graph vs the device budget — call BEFORE
        ``to_device_csr`` / ``partition`` (see ``repro.obs.memory``)."""
        from ..obs.memory import will_fit as _wf

        return _wf(n, m, k, cfg, budget_bytes=budget_bytes,
                   workload=workload, safety=safety)

    # ------------------------------------------------------------------ caches

    def _arena(self, g: AnyGraph) -> _Arena:
        hit = self._arenas.get(id(g))
        if hit is not None and hit.graph is g:
            return hit
        n = g.n
        if isinstance(g, GraphDev):
            # arrays are already device-resident and inert beyond (n, m):
            # nw is 0 past n, arc padding carries weight 0 — extend to the
            # arena entirely on device, no host round-trip.
            Nb = g.nw.shape[0]
            nw_arena = jnp.concatenate(
                [g.nw, jnp.zeros((self.A - Nb,), jnp.float32)]
            )
            cw = jnp.where(self._iota < n, nw_arena, jnp.inf)
            ar = _Arena(
                graph=g, nw_arena=nw_arena, cluster_w=cw,
                src=g.src, dst=g.indices, ew=g.ew,
                integral=(g.m == 0 or g.ew_integral)
                and float(self.host_read(jnp.sum(g.ew), "arc_total"))
                < 2**31,
            )
        else:
            nw = np.zeros(self.A, np.float32)
            nw[:n] = g.nw
            cw = np.full(self.A, np.inf, np.float32)
            cw[:n] = g.nw
            ar = _Arena(
                graph=g,
                nw_arena=jnp.asarray(nw),
                cluster_w=jnp.asarray(cw),
                src=jnp.asarray(g.arc_sources(), dtype=jnp.int32),
                dst=jnp.asarray(g.indices, dtype=jnp.int32),
                ew=jnp.asarray(g.ew, dtype=jnp.float32),
                integral=bool(np.all(g.ew == np.round(g.ew)))
                and float(g.ew.sum()) < 2**31,
            )
            self.stats.h2d_bytes += self.A * 8 + g.m * 12
        # GraphDev aliases (src/dst/ew) are already owned by base_csr —
        # registration is id-idempotent, so no double count
        _mem_account("label_arenas", ar.nw_arena, ar.cluster_w)
        _mem_account("base_csr", ar.src, ar.dst, ar.ew)
        self._arenas[id(g)] = ar
        return ar

    def _pack(self, g: AnyGraph, mode: str) -> _DevicePack:
        if isinstance(g, GraphDev):
            return self._pack_dev(g, mode)
        key = (id(g), mode)
        hit = self._packs.get(key)
        if hit is not None and hit.graph is g:
            self.stats.pack_hits += 1
            return hit
        self.stats.pack_builds += 1
        with _obs_span(
            "vcycle.pack", cat="vcycle", mode=mode, n=int(g.n), host=True
        ):
            return self._pack_host_build(g, key, mode)

    def _pack_host_build(self, g: AnyGraph, key, mode: str) -> _DevicePack:
        order = make_order(g, mode, self.seed)
        pack = pack_chunks(
            g, order, max_nodes=self.N,
            max_edges=max(self._e_request, self.E_floor),
            block=self.pack_block,
        )
        C, N = pack.nodes.shape
        E = pack.edge_dst.shape[1]
        # Bucket up: N is bounded by the frozen geometry; E only exceeds the
        # floor when a level's max block-degree-sum does (rare; power-law
        # hubs on coarse levels), C only grows at the finest level.
        self.C_bucket = max(self.C_bucket, _pow2(C))
        # E snaps to 512-arc multiples, not powers of two: a pack just past
        # the current bucket (one hub-heavy block) would otherwise pay a ~2x
        # sort-width tax on every chunk.  The raise is sticky, so later
        # levels (and the next V-cycle) land in the same bucket instead of
        # re-compiling.
        Eb = max(self.E_floor, -(-E // 512) * 512)
        self.E_floor = Eb
        padded = pad_pack(pack, self.C_bucket, self.N, Eb)
        dp = _DevicePack(
            graph=g,
            nodes=jnp.asarray(padded.nodes),
            node_valid=jnp.asarray(padded.node_valid),
            edge_dst=jnp.asarray(padded.edge_dst),
            edge_w=jnp.asarray(padded.edge_w),
            edge_src_slot=jnp.asarray(padded.edge_src_slot),
            edge_valid=jnp.asarray(padded.edge_valid),
            num_chunks=pack.num_chunks,
            shape=(self.C_bucket, self.N, Eb),
        )
        self.stats.h2d_bytes += sum(
            int(np.asarray(a).nbytes) for a in
            (padded.nodes, padded.node_valid, padded.edge_dst, padded.edge_w,
             padded.edge_src_slot, padded.edge_valid)
        )
        _mem_account("chunk_packs", dp.nodes, dp.node_valid, dp.edge_dst,
                     dp.edge_w, dp.edge_src_slot, dp.edge_valid)
        self._packs[key] = dp
        return dp

    def _pack_dev(self, g: GraphDev, mode: str) -> _DevicePack:
        """Pack a device-resident coarse graph without materializing it.

        Host work is O(n): the degree sequence (cached on the handle), the
        traversal order, and the greedy chunk plan.  The O(m) edge arrays are
        gathered on device from the still-resident CSR
        (:func:`~repro.graph.packing.gather_pack_device`) — the coarse
        adjacency never crosses to host.  Emits arrays bit-identical to the
        host ``_pack`` on the materialized graph (same plan, same order).
        """
        key = (id(g), mode)
        hit = self._packs.get(key)
        if hit is not None and hit.graph is g:
            self.stats.pack_hits += 1
            return hit
        self.stats.pack_builds += 1
        self.stats.gather_builds += 1
        with _obs_span(
            "vcycle.pack", cat="vcycle", mode=mode, n=int(g.n), host=False
        ) as sp:
            order = make_order(g, mode, self.seed)
            deg = g.degrees().astype(np.int64)[order]
            node_chunk, C, N, E = plan_chunks(
                deg, g.n, max_nodes=self.N,
                max_edges=max(self._e_request, self.E_floor),
                block=self.pack_block,
            )
            # same sticky bucket raising as the host path
            self.C_bucket = max(self.C_bucket, _pow2(C))
            Eb = max(self.E_floor, -(-E // 512) * 512)
            self.E_floor = Eb
            nodes, node_valid = layout_nodes(order, node_chunk, C, N, g.n)
            # Tight pow2 LIVE-chunk prefix: the sweep's fori_loop only ever
            # visits ``num_chunks`` live chunks, so dead chunks of the
            # finest level's shared bucket are pure shape padding — emitting
            # them would multiply the gather (and every sweep dispatch) by
            # the dead/live ratio.  Coarse GraphDev levels therefore get
            # their own pow2 chunk bucket; the few extra sweep shapes are
            # reused across levels and V-cycles like every other bucket.
            Cg = _pow2(C)
            nodes = np.pad(
                nodes, ((0, Cg - C), (0, self.N - N)), constant_values=g.n
            )
            node_valid = np.pad(node_valid, ((0, Cg - C), (0, self.N - N)))
            nodes_d = jnp.asarray(nodes)
            nv_d = jnp.asarray(node_valid)
            self.stats.h2d_bytes += nodes.nbytes + node_valid.nbytes
            gkey = (nodes.shape, g.indptr.shape[0], g.indices.shape[0], Eb)
            if gkey not in self._gather_keys:
                self._gather_keys.add(gkey)
                self.stats.gather_compiles += 1
                _obs_watchdog().note("engine.gather", gkey)
            sp.set(chunks=int(C), edge_bucket=int(Eb))
            edge_dst, edge_w, edge_slot, edge_valid = gather_pack_device(
                nodes_d, nv_d, g.indptr, g.indices, g.ew, jnp.int32(g.n), E=Eb
            )
            sp.sync_on(edge_valid)
            dp = _DevicePack(
                graph=g,
                nodes=nodes_d,
                node_valid=nv_d,
                edge_dst=edge_dst,
                edge_w=edge_w,
                edge_src_slot=edge_slot,
                edge_valid=edge_valid,
                num_chunks=C,
                shape=(Cg, self.N, Eb),
            )
            _mem_account("chunk_packs", dp.nodes, dp.node_valid, dp.edge_dst,
                         dp.edge_w, dp.edge_src_slot, dp.edge_valid)
            self._packs[key] = dp
            return dp

    def _ell(self, g: AnyGraph) -> _DeviceEll:
        hit = self._ells.get(id(g))
        if hit is not None and hit.graph is g:
            self.stats.pack_hits += 1
            return hit
        self.stats.pack_builds += 1
        # Pow2 row bucket + pow2(n + 1) node bucket: with dense_round_device's
        # traced n, one compiled round serves every level in the bucket
        # instead of compiling per level (padded rows are sentinel-owned and
        # weight-0, so they contribute nothing).
        if isinstance(g, GraphDev) and g.m > 0:
            # Device ELL gather: the O(n) row plan comes from the (cached)
            # host indptr, the O(m) dst/w fill gathers from the still-
            # resident CSR — bit-identical to ``ell_pack`` on the
            # materialized graph, without the O(m) download it used to take.
            row_node, row_first, row_end = plan_ell_rows(
                g._indptr_np(), g.n
            )
            R = row_node.shape[0]
            Rb = _pow2(R)
            row_node = np.pad(row_node, (0, Rb - R), constant_values=g.n)
            row_first = np.pad(row_first, (0, Rb - R))
            row_end = np.pad(row_end, (0, Rb - R))
            rn_d = jnp.asarray(row_node)
            rf_d = jnp.asarray(row_first)
            re_d = jnp.asarray(row_end)
            self.stats.h2d_bytes += row_node.nbytes + row_first.nbytes + row_end.nbytes
            self.stats.gather_builds += 1
            gkey = ("ell", Rb, g.indices.shape[0])
            if gkey not in self._gather_keys:
                self._gather_keys.add(gkey)
                self.stats.gather_compiles += 1
                _obs_watchdog().note("engine.gather", gkey)
            dst_d, w_d = gather_ell_device(
                rf_d, re_d, g.indices, g.ew, jnp.int32(g.n)
            )
            de = _DeviceEll(
                graph=g, dst=dst_d, w=w_d, row_node=rn_d, nb=_pow2(g.n + 1)
            )
            _mem_account("chunk_packs", de.dst, de.w, de.row_node)
            self._ells[id(g)] = de
            return de
        gh = g.to_host() if isinstance(g, GraphDev) else g
        ell = ell_pack(gh)
        R = ell.rows
        Rb = _pow2(R)
        dst = np.pad(ell.dst, ((0, Rb - R), (0, 0)), constant_values=g.n)
        w = np.pad(ell.w, ((0, Rb - R), (0, 0)))
        row_node = np.pad(ell.row_node, (0, Rb - R), constant_values=g.n)
        de = _DeviceEll(
            graph=g,
            dst=jnp.asarray(dst),
            w=jnp.asarray(w),
            row_node=jnp.asarray(row_node),
            nb=_pow2(g.n + 1),
        )
        self.stats.h2d_bytes += dst.nbytes + w.nbytes + row_node.nbytes
        _mem_account("chunk_packs", de.dst, de.w, de.row_node)
        self._ells[id(g)] = de
        return de

    def _drop_single_use(self, g: GraphNP, mode: str) -> None:
        """Release a coarse level's pack right after its one use.

        Only the finest graph's packs are ever re-hit (V-cycles 2..N reuse
        them; coarse graphs are rebuilt every cycle), and every cached pack
        is padded to the finest bucket shape — so keeping a coarse pack
        around would cost O(finest pack) device memory per level for zero
        reuse.  Arenas (O(graph)) stay until cycle-end ``evict``: the same
        level's refine/guard calls still need them.
        """
        if id(g) != self._g0_id:
            self._packs.pop((id(g), mode), None)

    def carry_from(self, old: "LPEngine") -> None:
        """Adopt a predecessor engine's cumulative stats and compile-key
        sets (the dynamic session's node-growth rebuild path).  The jit
        caches are process-global, so every shape the old engine dispatched
        is still compiled — sharing the key sets (and the stats object
        itself, so transfer/counter deltas observed across the swap stay
        coherent) keeps the compile counters honest: ``compiles ==
        bucket_count`` holds across rebuilds."""
        self.stats = old.stats
        self._compile_keys = old._compile_keys
        self._gather_keys = old._gather_keys
        self._dense_keys = old._dense_keys
        self._repair_E = max(self._repair_E, old._repair_E)

    def evict(self, keep: Tuple[GraphNP, ...] = ()) -> None:
        """Drop cached packs/arenas/ELLs for all graphs not in ``keep``.

        Coarse graphs are rebuilt fresh every V-cycle (restricted clustering
        changes the hierarchy), so their cache entries — each padded to the
        finest bucket shape — are dead weight once the cycle ends.  The
        driver calls this at the end of each cycle keeping only the finest
        graph, whose packs are the ones cycles 2..N actually reuse.
        """
        keep_ids = {id(g) for g in keep}
        self._packs = {k: v for k, v in self._packs.items() if k[0] in keep_ids}
        self._arenas = {k: v for k, v in self._arenas.items() if k in keep_ids}
        self._ells = {k: v for k, v in self._ells.items() if k in keep_ids}
        self._cin = {k: v for k, v in self._cin.items() if k in keep_ids}
        self._degs = {k: v for k, v in self._degs.items() if k in keep_ids}
        self._tables = {k: v for k, v in self._tables.items() if k in keep_ids}
        self._indptrs = {k: v for k, v in self._indptrs.items() if k in keep_ids}
        self._exact = {k: v for k, v in self._exact.items() if k in keep_ids}

    # ------------------------------------------------------------------ sweeps

    def _sweep(self, dp, labels, weights, nw_arena, restrict, U, seed, num_labels,
               *, iters, refine_mode, use_restrict, permute_chunks):
        self.stats.sweep_calls += 1
        bucket = dp.shape + (labels.shape[0], weights.shape[0])
        self.stats.buckets.add(bucket)
        ckey = bucket + (restrict.shape[0], iters, refine_mode, use_restrict,
                         permute_chunks)
        if ckey not in self._compile_keys:
            self._compile_keys.add(ckey)
            self.stats.sweep_compiles += 1
            _obs_watchdog().note("engine.sweep", ckey)
        return _lp_sweep(
            dp.nodes, dp.node_valid, dp.edge_dst, dp.edge_w, dp.edge_src_slot,
            dp.edge_valid,
            labels, weights, nw_arena, restrict,
            jnp.float32(U),
            jnp.int32(seed & 0x7FFFFFFF),
            jnp.int32(num_labels),
            jnp.int32(dp.num_chunks),
            iters=iters,
            refine_mode=refine_mode,
            use_restrict=use_restrict,
            permute_chunks=permute_chunks,
        )

    def cluster(
        self,
        g: AnyGraph,
        U: float,
        iters: int,
        seed: int,
        restrict: Optional[Union[np.ndarray, jax.Array]] = None,
    ) -> jax.Array:
        """SCLaP clustering for coarsening; returns DEVICE labels (length n)
        so the device contraction can consume them without a round-trip.
        Degree traversal order, packs cached per graph; a device ``restrict``
        must already be arena-sized (``project_restrict`` output)."""
        dp = self._pack(g, "degree")
        ar = self._arena(g)
        if restrict is None:
            r_dev = jnp.zeros(1, jnp.int32)
        elif isinstance(restrict, jax.Array):
            r_dev = restrict
        else:
            r = np.full(self.A, -1, np.int32)
            r[: g.n] = restrict
            r_dev = jnp.asarray(r)
            self.stats.h2d_bytes += r.nbytes
        with _obs_span(
            "vcycle.sweep", cat="vcycle", mode="cluster", n=int(g.n),
            m=int(g.m), iters=int(iters), chunks=int(dp.num_chunks),
        ) as sp:
            labels, _, _ = self._sweep(
                dp, self._iota, ar.cluster_w, ar.nw_arena, r_dev, U, seed,
                g.n,
                iters=iters, refine_mode=False,
                use_restrict=restrict is not None, permute_chunks=False,
            )
            sp.sync_on(labels)
        self._drop_single_use(g, "degree")
        return labels[: g.n]

    def refine(
        self,
        g: AnyGraph,
        labels: Union[np.ndarray, jax.Array],
        k: int,
        U: float,
        iters: int,
        seed: int,
    ) -> jax.Array:
        """Chunked-sequential SCLaP local search; arena labels in/out (device
        arrays stay device-resident across levels)."""
        dp = self._pack(g, "random")
        ar = self._arena(g)
        lab = self.to_arena(labels, g.n, fill=k)
        # (k + 1)-sized block weights: k is constant for the whole run, so
        # this costs no extra compiles and keeps the sweep's weight updates
        # and influx gating O(k) instead of O(arena) per chunk.
        bw = jnp.zeros((k + 1,), jnp.float32).at[jnp.minimum(lab, k)].add(
            ar.nw_arena
        )
        w0 = bw.at[k].set(jnp.inf)
        with _obs_span(
            "vcycle.sweep", cat="vcycle", mode="refine", n=int(g.n),
            m=int(g.m), iters=int(iters), chunks=int(dp.num_chunks),
        ) as sp:
            lab_out, _, _ = self._sweep(
                dp, lab, w0, ar.nw_arena, jnp.zeros(1, jnp.int32), U, seed,
                k,
                iters=iters, refine_mode=True,
                use_restrict=False, permute_chunks=True,
            )
            sp.sync_on(lab_out)
        self._drop_single_use(g, "random")
        return lab_out

    def refine_dense(
        self,
        g: AnyGraph,
        labels: Union[np.ndarray, jax.Array],
        k: int,
        U: float,
        iters: int,
        seed: int,
        move_fraction: float = 0.5,
    ) -> jax.Array:
        """Synchronous dense refinement: ``iters`` Pallas-scored rounds on a
        cached (bucket-padded) ELL pack, labels device-resident throughout."""
        from ..kernels.lp_score.ops import dense_round_device

        de = self._ell(g)
        ar = self._arena(g)
        # bucketed node axis: arena labels/weights sliced to the pow2 node
        # bucket (slots >= n carry label k / weight 0 — inert)
        lab = self.to_arena(labels, g.n, fill=k)[: de.nb]
        nw_nb = ar.nw_arena[: de.nb]
        dkey = (de.dst.shape, de.nb, k, self.use_pallas, self.interpret)
        if dkey not in self._dense_keys:
            self._dense_keys.add(dkey)
            self.stats.dense_compiles += 1
            _obs_watchdog().note("engine.dense", dkey)
        with _obs_span(
            "vcycle.sweep", cat="vcycle", mode="dense", n=int(g.n),
            m=int(g.m), iters=int(iters),
        ) as sp:
            for r in range(iters):
                lab = dense_round_device(
                    de.dst, de.w, de.row_node, lab, nw_nb,
                    jnp.float32(U),
                    jnp.int32((seed + 0x9E37 * r) & 0x7FFFFFFF),
                    jnp.float32(move_fraction),
                    jnp.int32(g.n),
                    k=k,
                    use_pallas=self.use_pallas, interpret=self.interpret,
                )
                self.stats.dense_rounds += 1
            sp.sync_on(lab)
        if id(g) != self._g0_id:
            self._ells.pop(id(g), None)
        return self.to_arena(lab, g.n, fill=k)

    # --------------------------------------------------------------- repair

    def _indptr_dev(self, g: AnyGraph) -> jax.Array:
        """Device CSR row pointers for region gathers; GraphDev handles carry
        their own, a GraphNP uploads its (n + 1) pointer array once."""
        if isinstance(g, GraphDev):
            return g.indptr
        hit = self._indptrs.get(id(g))
        if hit is not None:
            return hit
        ip = np.asarray(g.indptr, dtype=np.int32)
        arr = jnp.asarray(ip)
        self.stats.h2d_bytes += ip.nbytes
        _mem_account("base_csr", arr)
        self._indptrs[id(g)] = arr
        return arr

    def _note_repair_key(self, key) -> None:
        if key not in self.stats.repair_buckets:
            self.stats.repair_buckets.add(key)
            self.stats.repair_compiles += 1
            _obs_watchdog().note("engine.repair", key)

    def repair(
        self,
        g: AnyGraph,
        labels: Union[np.ndarray, jax.Array],
        touched: np.ndarray,
        k: int,
        U: float,
        *,
        hops: int = 2,
        iters: int = 6,
        gain_rounds: int = 2,
        balance_rounds: int = 3,
        seed: int = 0,
        hop_degree_cap: Optional[int] = None,
        adjacency: Optional[Tuple[jax.Array, ...]] = None,
    ) -> Tuple[jax.Array, int, float, np.ndarray]:
        """Incremental size-constrained repair after a graph mutation.

        The dynamic subsystem's hot path (ISSUE 4): expand the ``hops``-hop
        affected region around the ``touched`` node ids on device, pack only
        the region's nodes into sweep chunks (host plans O(region), device
        gathers O(region edges) from the resident CSR), and run the cached
        ``_lp_sweep`` in refine mode over that pack — against **exact
        global block weights** and the true size bound ``U = L_max``, the
        paper's §III-A refinement invariants (an overloaded block's nodes
        must leave it; eligibility is measured on real weights, never
        region-local estimates).  Region-masked gain and balance-repair
        rounds (``repro.dynamic.repair``, fm.py spec twins) follow, and a
        cut/feasibility guard — the uncoarsening monotonicity guard's twin
        — keeps the repaired labels only if the cut did not worsen or
        feasibility was restored.

        ``hop_degree_cap`` bounds the region on power-law graphs: hops past
        the first only expand *through* nodes of degree <= cap, so a hub
        adjacent to the touched set joins the region but no longer drags
        its entire neighbourhood in (the ROADMAP repair-locality item).
        ``None`` or a non-positive value disables the cap (bit-identical
        to the uncapped expansion).

        ``adjacency`` (the ISSUE-8 overlay-aware path) substitutes device
        ``(indptr, src, dst, ew)`` arrays — e.g. a
        :meth:`~repro.dynamic.store.DynamicGraphStore.view` of base CSR +
        uncompacted overlay — for ``g``'s own arcs in every arc consumer
        (region expansion, pack gather, gain rounds, the guard's cuts).
        ``g`` still supplies the node set, node weights, and cache
        identity, which must describe the SAME node set as the adjacency;
        because all those consumers are insensitive to within-row arc
        order and to inert padding, repairing on a view is bit-identical
        to compacting first (regression-tested in tests/test_throughput).

        Every kernel is shape-bucketed with traced live counts, so a steady
        update stream compiles once per bucket (``repair_compiles ==
        repair_bucket_count``).  Returns ``(arena labels, region size, cut,
        block weights)`` — the guard already evaluates the returned labels'
        cut and (k,) block-weight vector, so the serving loop scores an
        update without re-running the O(m)/O(n) reductions.  Labels outside
        the region are bit-identical to the input.
        """
        from ..dynamic.repair import (
            TAG_DYN_GAIN,
            TAG_DYN_GAIN_GATE,
            balance_rounds_device,
            expand_region_device,
            gain_round_device,
        )
        from .label_propagation import hash_base_u32

        self.stats.repair_calls += 1
        n = g.n
        ar = self._arena(g)
        if adjacency is not None:
            ip, a_src, a_dst, a_ew = adjacency[:4]
        else:
            ip = self._indptr_dev(g)
            a_src, a_dst, a_ew = ar.src, ar.dst, ar.ew

        def cut_now(labels_: jax.Array) -> float:
            if adjacency is None:
                return self.cut(g, labels_)
            return float(cut_from_arcs_jnp(
                labels_, a_src, a_dst, a_ew, integral=ar.integral
            ))

        lab = self.to_arena(labels, n, fill=k)
        t_ids = np.unique(np.asarray(touched, dtype=np.int64))
        t_ids = t_ids[(t_ids >= 0) & (t_ids < n)].astype(np.int32)
        if t_ids.size == 0:
            return lab, 0, cut_now(lab), self.block_weights(g, lab, k)
        # ---- h-hop affected region (device frontier expansion) ----
        Tb = _pow2(max(t_ids.size, 8))
        tpad = np.full(Tb, n, np.int32)
        tpad[: t_ids.size] = t_ids
        self.stats.h2d_bytes += tpad.nbytes
        # None and <= 0 both disable the cap (the session's "0 = off"
        # convention holds at the engine too — a literal cap of 0 would
        # silently freeze expansion at hop 1)
        cap = (0x7FFFFFFF if hop_degree_cap is None or hop_degree_cap <= 0
               else int(hop_degree_cap))
        self._note_repair_key(
            ("frontier", Tb, a_src.shape[0], ip.shape[0], self.A)
        )
        with _obs_span("repair.expand", cat="repair",
                       touched=int(t_ids.size), hops=int(hops)):
            mask = expand_region_device(
                jnp.asarray(tpad), a_src, a_dst, ip, jnp.int32(n),
                jnp.int32(hops), jnp.int32(cap), A=self.A,
            )
            mask_np = np.asarray(mask[:n])
        self.stats.d2h_bytes += mask_np.nbytes
        region = np.flatnonzero(mask_np)
        if region.size == 0:
            return lab, 0, cut_now(lab), self.block_weights(g, lab, k)
        # ---- region pack: host O(region) plan, device O(region m) gather
        order = np.random.default_rng(seed).permutation(region).astype(np.int64)
        if adjacency is not None or isinstance(g, GraphDev):
            # region degrees gathered ON device: every compaction hands
            # repair a fresh handle whose O(n) host degree cache is cold,
            # so g.degrees() here would download the full indptr per update
            # — O(region) is all the plan needs
            oi = jnp.asarray(order.astype(np.int32))
            self.stats.h2d_bytes += order.size * 4
            deg_r = np.asarray(ip[oi + 1] - ip[oi]).astype(np.int64)
            self.stats.d2h_bytes += deg_r.nbytes // 2
        else:
            deg_r = g.degrees()[order]
        nodes, node_valid, C, N, E = plan_region_pack(
            deg_r, order, n, max_nodes=self.N,
            max_edges=self._e_request, block=self.pack_block,
        )
        Cb = _pow2(C)
        Eb = max(self._repair_E, -(-E // 512) * 512)  # sticky, like E_floor
        self._repair_E = Eb
        nodes = np.pad(
            nodes, ((0, Cb - C), (0, self.N - N)), constant_values=n
        )
        node_valid = np.pad(node_valid, ((0, Cb - C), (0, self.N - N)))
        nodes_d = jnp.asarray(nodes)
        nv_d = jnp.asarray(node_valid)
        self.stats.h2d_bytes += nodes.nbytes + node_valid.nbytes
        self._note_repair_key(
            ("gather", nodes.shape, ip.shape[0], a_dst.shape[0], Eb)
        )
        with _obs_span("repair.gather", cat="repair",
                       region=int(region.size)) as sp:
            edge_dst, edge_w, edge_slot, edge_valid = gather_pack_device(
                nodes_d, nv_d, ip, a_dst, a_ew, jnp.int32(n), E=Eb
            )
            sp.sync_on(edge_valid)
        dp = _DevicePack(
            graph=g, nodes=nodes_d, node_valid=nv_d, edge_dst=edge_dst,
            edge_w=edge_w, edge_src_slot=edge_slot, edge_valid=edge_valid,
            num_chunks=C, shape=(Cb, self.N, Eb),
        )
        _mem_account("chunk_packs", nodes_d, nv_d, edge_dst, edge_w,
                     edge_slot, edge_valid, mask)
        # ---- LP sweeps against exact global block weights ----
        bw = jnp.zeros((k + 1,), jnp.float32).at[jnp.minimum(lab, k)].add(
            ar.nw_arena
        )
        bw_old_max = float(jnp.max(bw[:k]))
        before_cut = cut_now(lab)
        w0 = bw.at[k].set(jnp.inf)
        self._note_repair_key(("sweep", dp.shape, self.A, k + 1, iters))
        with _obs_span("repair.sweep", cat="repair", iters=int(iters)) as sp:
            out, _, _ = self._sweep(
                dp, lab, w0, ar.nw_arena, jnp.zeros(1, jnp.int32), U, seed, k,
                iters=iters, refine_mode=True, use_restrict=False,
                permute_chunks=True,
            )
            sp.sync_on(out)
        # ---- region-masked gain + balance rounds ----
        Kb = k + 1
        with _obs_span("repair.gain", cat="repair",
                       rounds=int(gain_rounds)) as sp:
            for r in range(gain_rounds):
                base_s = hash_base_u32(seed, r, TAG_DYN_GAIN)
                base_g = hash_base_u32(seed, r, TAG_DYN_GAIN_GATE)
                self._note_repair_key(("gain", self.A, a_src.shape[0], Kb))
                out = gain_round_device(
                    a_src, a_dst, a_ew, ar.nw_arena, out, mask,
                    jnp.int32(n), jnp.int32(k), jnp.float32(U),
                    jnp.uint32(base_s), jnp.uint32(base_g), Kb=Kb,
                )
            sp.sync_on(out)
        if balance_rounds:
            self._note_repair_key(("balance", self.A, Kb, balance_rounds))
            with _obs_span("repair.balance", cat="repair",
                           rounds=int(balance_rounds)) as sp:
                out = balance_rounds_device(
                    ar.nw_arena, out, mask, jnp.int32(n), jnp.int32(k),
                    jnp.float32(U), jnp.int32(seed & 0x7FFFFFFF),
                    Kb=Kb, rounds=balance_rounds,
                )
                sp.sync_on(out)
        # ---- guard (the uncoarsening monotonicity guard's twin, plus a
        # feasibility clause): keep the repaired labels only if the cut did
        # not worsen AND the balance bound did not degrade, or if they
        # restored a violated bound.  Repair therefore never trades
        # feasibility for cut — the session-level invariant that edge-only
        # update streams stay feasible forever.
        bw_new = jnp.zeros((k + 1,), jnp.float32).at[jnp.minimum(out, k)].add(
            ar.nw_arena
        )
        bw_new_max = float(jnp.max(bw_new[:k]))
        after_cut = cut_now(out)
        self.stats.d2h_bytes += 16  # the guard's two cut + two bw scalars
        ok_cut = (
            after_cut <= before_cut
            and bw_new_max <= max(bw_old_max, U + 1e-6)
        )
        if ok_cut or bw_old_max > U >= bw_new_max:
            return out, int(region.size), after_cut, np.asarray(bw_new[:k])
        return lab, int(region.size), before_cut, np.asarray(bw[:k])

    # ---------------------------------------------------------- evolutionary

    def _deg_f(self, g: AnyGraph, Ab: int) -> jax.Array:
        """(Ab,) float32 degrees (0 beyond n), uploaded once per graph."""
        hit = self._degs.get(id(g))
        if hit is not None and hit.shape[0] == Ab:
            return hit
        deg = np.zeros(Ab, np.float32)
        deg[: g.n] = g.degrees()
        arr = jnp.asarray(deg)
        self.stats.h2d_bytes += deg.nbytes
        _mem_account("evo_population", arr)
        self._degs[id(g)] = arr
        return arr

    def _evo_table(self, g: AnyGraph, Ab: int):
        """One-row-per-node neighbour table ``(nbr, wt)`` of the GA's node
        rounds, or None where the graph's shape says the per-arc scatter is
        cheaper.  ``W`` is the max degree rounded up to 8 and ``Nt`` is ``n``
        rounded up to the 256-row tile (at most ``Ab``); padding slots point
        at their own row with weight 0, so every slot indexes one of the
        first ``Nt`` labels (see ``evo_device._table_conn``).  The table is
        taken when its ``Nt * W`` slots are at most ``4 * m``: bounded-degree
        meshes, not graphs whose hubs would pad every row.  Only the O(n)
        row plan is built on the host, from the indptr the GA's degrees
        already read; the slots are gathered on device from the arena's CSR
        arcs."""
        hit = self._tables.get(id(g))
        if hit is not None and hit[0] is g:
            return hit[1]
        indptr = g._indptr_np() if isinstance(g, GraphDev) else g.indptr
        deg = np.diff(np.asarray(indptr[: g.n + 1], dtype=np.int64))
        W = -(-max(int(deg.max(initial=0)), 1) // 8) * 8
        Nt = min(-(-g.n // 256) * 256, Ab)
        table = None
        if Nt * W <= 4 * g.m:
            _, first, end = plan_ell_rows(indptr, g.n, width=W)
            ar = self._arena(g)
            self.stats.gather_builds += 1
            gkey = ("evo_table", Nt, W, ar.dst.shape[0])
            if gkey not in self._gather_keys:
                self._gather_keys.add(gkey)
                self.stats.gather_compiles += 1
                _obs_watchdog().note("engine.gather", gkey)
            nbr, wt = gather_ell_device(
                jnp.asarray(first[:Nt]), jnp.asarray(end[:Nt]), ar.dst, ar.ew,
                jnp.int32(g.n), width=W,
            )
            rows = jnp.arange(Nt, dtype=jnp.int32)[:, None]
            table = (jnp.where(nbr == g.n, rows, nbr), wt)
            self.stats.h2d_bytes += 2 * Nt * 4
            _mem_account("chunk_packs", *table)
        self._tables[id(g)] = (g, table)
        return table

    def _weights_exact(self, g: AnyGraph) -> bool:
        """Whether the GA's sums are exact in any order on ``g``:
        integral weights, total node weight (block weights) and the largest
        weighted degree (per-node block connections) below 2**24 in f32, and
        an arc total the int32 cut of the fitness key can hold.  Checked on
        the graph the GA runs on: contraction keeps the node total, shrinks
        the arc total and grows weighted degrees."""
        hit = self._exact.get(id(g))
        if hit is not None and hit[0] is g:
            return hit[1]
        ar = self._arena(g)
        if isinstance(g, GraphDev):
            nw_ok = (bool(self.host_read(jnp.all(g.nw == jnp.round(g.nw)),
                                         "weights_exact"))
                     and float(self.host_read(jnp.sum(g.nw), "node_total"))
                     < 2**24)
            wdeg = 0.0
            if ar.integral and g.m:
                cs = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                      jnp.cumsum(g.ew.astype(jnp.int32))])
                wdeg = float(self.host_read(
                    jnp.max(cs[g.indptr[1:]] - cs[g.indptr[:-1]]), "max_wdeg"))
        else:
            nw_ok = (bool(np.all(g.nw == np.round(g.nw)))
                     and float(g.nw.sum()) < 2**24)
            cs = np.concatenate([[0.0], np.cumsum(g.ew, dtype=np.float64)])
            wdeg = float(np.max(cs[g.indptr[1:]] - cs[g.indptr[:-1]])) if g.m else 0.0
        ok = bool(ar.integral and nw_ok and wdeg < 2**24)
        self._exact[id(g)] = (g, ok)
        return ok

    def can_evolve_device(self, g: AnyGraph, k: int, islands: int,
                          pop: int) -> bool:
        """Eligibility gate for the batched device evolution: exact-weight
        precondition plus shape guards (overlay keys fit int32, dense
        (pop, Ab, Kb) score tensors fit a sane memory budget)."""
        n = g.n
        if n < 1 or k < 1 or k * (k + 1) >= 2**31:
            return False
        Ab = _pow2(n + 1)
        Kb = _pow2(k + 1)
        Sb = _pow2(max(islands * pop, 1))
        if Sb * Ab * Kb * 4 > 2**28:
            return False
        return self._weights_exact(g)

    def _evo_arrays(self, g: AnyGraph):
        """(pack, arena, Ab) for one evolution run; the pack is the cached
        "random" pack (shared with refine sweeps), so the graph uploads once
        per run, not once per individual."""
        dp = self._pack(g, "random")
        ar = self._arena(g)
        Ab = _pow2(g.n + 1)
        return dp, ar, Ab

    def evolve_device(self, g: AnyGraph, cfg, shard: bool = False) -> jax.Array:
        """Batched island GA on device; returns the best coarsest-graph
        partition as a DEVICE (n,) int32 label array (bit-identical to
        :meth:`evolve_oracle` under the same config — tested).

        ``shard=True`` maps islands onto the available devices via
        ``shard_map`` (requires ``islands %% device_count == 0``); gossip
        becomes an all_gather collective and results stay bit-identical.
        """
        from .evo_device import (
            evo_generation_step,
            evo_seed_step,
            make_generation_sharded,
        )

        n, k = g.n, cfg.k
        I, P, G = cfg.islands, cfg.pop_per_island, cfg.generations
        Ab, Kb = _pow2(n + 1), _pow2(k + 1)
        Sb, Ib = _pow2(I * P), _pow2(I)
        dp, ar, _ = self._evo_arrays(g)
        nw_ab = ar.nw_arena[:Ab]
        deg = self._deg_f(g, Ab)
        seed_eff = int(cfg.seed) & 0x7FFFFFFF
        seed_lab = np.full((Sb, Ab), k, np.int32)
        seed_mask = np.zeros(Sb, bool)
        if cfg.seed_individuals:
            for isl in range(I):
                row = isl * P
                seed_lab[row, :n] = np.asarray(
                    cfg.seed_individuals[isl % len(cfg.seed_individuals)][:n],
                    dtype=np.int32,
                )
                seed_mask[row] = True
        self.stats.h2d_bytes += seed_lab.nbytes + seed_mask.nbytes
        table = self._evo_table(g, Ab)
        tshape = None if table is None else table[0].shape
        skey = ("evo_seed", dp.shape, Sb, Ab, Kb, cfg.refine_iters, tshape)
        self.stats.evo_calls += 1
        if skey not in self.stats.evo_buckets:
            self.stats.evo_buckets.add(skey)
            self.stats.evo_compiles += 1
            _obs_watchdog().note("engine.evo", skey)
        from .evolutionary import grow_rounds_bound

        budget = grow_rounds_bound(n, k, g.m)
        self.stats.evo_grow_budget += budget
        labs, keys, rounds = evo_seed_step(
            dp.nodes, dp.node_valid, dp.edge_dst, dp.edge_w,
            dp.edge_src_slot, dp.edge_valid,
            jnp.asarray(seed_lab), jnp.asarray(seed_mask),
            ar.src, ar.dst, ar.ew, nw_ab, deg,
            jnp.float32(cfg.Lmax), jnp.int32(seed_eff),
            jnp.int32(I), jnp.int32(P), jnp.int32(n), jnp.int32(k),
            jnp.int32(dp.num_chunks),
            jnp.int32(budget), table,
            refine_iters=cfg.refine_iters, Kb=Kb,
        )
        self._grow_rounds.append(rounds)    # read only by read_grow_rounds
        self.stats.evo_table_steps += int(table is not None)
        _mem_account("evo_population", labs, keys)
        D = jax.device_count()
        if shard and G > 0 and D > 1 and I % D == 0:
            labs, keys = self._evolve_sharded(
                g, cfg, dp, ar, labs, keys, nw_ab, seed_eff, D,
                make_generation_sharded, table,
            )
        else:
            gkey = ("evo_gen", dp.shape, Sb, Ab, Ib, Kb, cfg.refine_iters,
                    tshape)
            for gen in range(G):
                self.stats.evo_calls += 1
                if gkey not in self.stats.evo_buckets:
                    self.stats.evo_buckets.add(gkey)
                    self.stats.evo_compiles += 1
                    _obs_watchdog().note("engine.evo", gkey)
                labs, keys = evo_generation_step(
                    dp.nodes, dp.node_valid, dp.edge_dst, dp.edge_w,
                    dp.edge_src_slot, dp.edge_valid,
                    labs, keys, ar.src, ar.dst, ar.ew, nw_ab,
                    jnp.float32(cfg.Lmax), jnp.int32(seed_eff),
                    jnp.int32(gen), jnp.int32(0),
                    jnp.int32(I), jnp.int32(P), jnp.int32(n), jnp.int32(k),
                    jnp.int32(dp.num_chunks), table,
                    refine_iters=cfg.refine_iters, Kb=Kb, Ib=Ib,
                )
                self.stats.evo_table_steps += int(table is not None)
                _mem_account("evo_population", labs, keys)
        Sb_cur = labs.shape[0]
        valid = jnp.arange(Sb_cur) < I * P
        bkey = jnp.min(jnp.where(valid, keys, 2**31 - 1))
        bidx = jnp.min(
            jnp.where(valid & (keys == bkey), jnp.arange(Sb_cur), Sb_cur)
        )
        return labs[jnp.minimum(bidx, Sb_cur - 1)][:n]

    def _evolve_sharded(self, g, cfg, dp, ar, labs, keys, nw_ab, seed_eff,
                        D, make_step, table):
        """Generation loop over ``shard_map`` island shards (device evo's
        distributed mode); state is resharded (D, Sb_loc, Ab) around the
        single-device seed phase and flattened back for best-selection."""
        from ..launch.mesh import make_mesh

        n, k = g.n, cfg.k
        I, P, G = cfg.islands, cfg.pop_per_island, cfg.generations
        Ab = labs.shape[1]
        I_loc = I // D
        S_loc = I_loc * P
        Sb_loc = _pow2(S_loc)
        Kb = _pow2(k + 1)
        Ib_loc = _pow2(I_loc)
        lab_h, key_h = self.host_read((labs, keys), "population")
        self.stats.d2h_bytes += lab_h.nbytes + key_h.nbytes
        lab_sh = np.full((D, Sb_loc, Ab), k, np.int32)
        key_sh = np.full((D, Sb_loc), 2**31 - 1, np.int32)
        for d in range(D):
            lab_sh[d, :S_loc] = lab_h[d * S_loc:(d + 1) * S_loc]
            key_sh[d, :S_loc] = key_h[d * S_loc:(d + 1) * S_loc]
        offs = (np.arange(D, dtype=np.int32) * I_loc)[:, None]
        stat_key = ("evo_gen_sharded", dp.shape, D, Sb_loc, Ab, Ib_loc, Kb,
                    cfg.refine_iters,
                    None if table is None else table[0].shape)
        # keyed on the step's actual statics (a mesh identity would miss on
        # every call — make_mesh returns a fresh object — and re-jit the
        # shard_map executable once per V-cycle)
        step_key = (D, cfg.refine_iters, Kb, Ib_loc)
        step = self._shard_steps.get(step_key)
        if step is None:
            step = make_step(
                make_mesh((D,), ("island",)), cfg.refine_iters, Kb, Ib_loc
            )
            self._shard_steps[step_key] = step
        labs_d = jnp.asarray(lab_sh)
        keys_d = jnp.asarray(key_sh)
        self.stats.h2d_bytes += lab_sh.nbytes + key_sh.nbytes
        offs_d = jnp.asarray(offs)
        _mem_account("evo_population", labs_d, keys_d, offs_d)
        for gen in range(G):
            self.stats.evo_calls += 1
            if stat_key not in self.stats.evo_buckets:
                self.stats.evo_buckets.add(stat_key)
                self.stats.evo_compiles += 1
                _obs_watchdog().note("engine.evo", stat_key)
            labs_d, keys_d = step(
                dp.nodes, dp.node_valid, dp.edge_dst, dp.edge_w,
                dp.edge_src_slot, dp.edge_valid,
                labs_d, keys_d, ar.src, ar.dst, ar.ew, nw_ab,
                jnp.float32(cfg.Lmax), jnp.int32(seed_eff), jnp.int32(gen),
                offs_d,
                jnp.int32(I_loc), jnp.int32(P), jnp.int32(n), jnp.int32(k),
                jnp.int32(dp.num_chunks), table,
            )
            self.stats.evo_table_steps += int(table is not None)
        # flatten back to island-major flat order (gossip already global)
        lab_fh, key_fh = self.host_read((labs_d, keys_d), "population")
        self.stats.d2h_bytes += lab_fh.nbytes + key_fh.nbytes
        Sb = _pow2(I * P)
        lab_out = np.full((Sb, Ab), k, np.int32)
        key_out = np.full(Sb, 2**31 - 1, np.int32)
        for d in range(D):
            lab_out[d * S_loc:(d + 1) * S_loc] = lab_fh[d, :S_loc]
            key_out[d * S_loc:(d + 1) * S_loc] = key_fh[d, :S_loc]
        return jnp.asarray(lab_out), jnp.asarray(key_out)

    def evolve_oracle(self, g: AnyGraph, cfg, trace=None,
                      grow_rounds=None) -> np.ndarray:
        """Sequential host-numpy oracle on the SAME pack/arc arrays the
        device path dispatches — the parity reference and the
        host-sequential baseline of the ``evo_hot`` benchmark
        (``trace``/``grow_rounds``: see ``evolve_batched_numpy``)."""
        from .evolutionary import EvoInputs, evolve_batched_numpy

        dp, ar, Ab = self._evo_arrays(g)
        deg = np.zeros(Ab, np.int32)
        deg[: g.n] = g.degrees()
        inp = EvoInputs(
            nodes=np.asarray(dp.nodes),
            node_valid=np.asarray(dp.node_valid),
            edge_dst=np.asarray(dp.edge_dst),
            edge_w=np.asarray(dp.edge_w),
            edge_src_slot=np.asarray(dp.edge_src_slot),
            edge_valid=np.asarray(dp.edge_valid),
            num_chunks=dp.num_chunks,
            src=np.asarray(ar.src),
            dst=np.asarray(ar.dst),
            ew=np.asarray(ar.ew),
            nw=np.asarray(ar.nw_arena[:Ab]),
            deg=deg,
            n=g.n,
        )
        return evolve_batched_numpy(inp, cfg, trace=trace,
                                    grow_rounds=grow_rounds)

    # ------------------------------------------------------------ contraction

    def _contract_inputs(self, g: AnyGraph, Nb: int, Mb: int):
        """(src, dst, ew, nw, ew_integral, ew_max) for the (Nb, Mb) bucket.

        GraphDev handles are born exactly in their bucket (contract slices
        its outputs down), so they pass through untouched and carry their
        weight metadata; GraphNP inputs (the finest level) pad from the
        cached arena arrays on device, once per graph.  The weight scan for
        the packed-key fast path runs once here: an O(m) host scan per
        *call* would trash the CPU cache the contraction executable is
        about to use."""
        if isinstance(g, GraphDev):
            return g.src, g.indices, g.ew, g.nw, g.ew_integral, g.ew_max
        hit = self._cin.get(id(g))
        if hit is not None and hit[0] is g:
            return hit[1:]
        ar = self._arena(g)
        pm = Mb - g.m
        src = jnp.concatenate([ar.src, jnp.zeros((pm,), jnp.int32)])
        dst = jnp.concatenate([ar.dst, jnp.zeros((pm,), jnp.int32)])
        ew = jnp.concatenate([ar.ew, jnp.zeros((pm,), jnp.float32)])
        nw = ar.nw_arena[:Nb]
        integral = bool(np.all(g.ew == np.round(g.ew))) if g.m else True
        ew_max = float(g.ew.max()) if g.m else 0.0
        _mem_account("base_csr", src, dst, ew)
        self._cin[id(g)] = (g, src, dst, ew, nw, integral, ew_max)
        return src, dst, ew, nw, integral, ew_max

    def contract(
        self, g: AnyGraph, labels: Union[np.ndarray, jax.Array]
    ) -> Tuple[GraphDev, CoarseMap]:
        """Device-resident contraction: the §IV-C quotient build as one
        bucketed executable (``contract_device``).

        ``labels`` are cluster ids in ``[0, n)`` (a ``cluster`` result —
        device or host).  Returns a :class:`GraphDev` whose arrays live in
        the coarse level's own buckets plus the fine->coarse
        :class:`CoarseMap`; only the ``(n_c, m_c, max nw_c)`` scalars are
        synced to host."""
        n, m = g.n, g.m
        Nb = _pow2(max(n, 8))
        Mb = _mbucket(m)
        src, dst, ew, nw, integral, ew_max = self._contract_inputs(g, Nb, Mb)
        # packed-key fast path: integral weights small enough to ride in the
        # low bits of the uint32 sort key (see contract_device)
        wbits = packed_key_wbits(Nb, Mb, ew_max, integral)
        if isinstance(labels, jax.Array):
            lab = labels.astype(jnp.int32)
        else:
            lab = jnp.asarray(np.asarray(labels[:n], dtype=np.int32))
            self.stats.h2d_bytes += n * 4
        if lab.shape[0] != Nb:
            lab = jnp.concatenate(
                [lab[:n], jnp.zeros((Nb - n,), jnp.int32)]
            )
        self.stats.contract_calls += 1
        ckey = (Nb, Mb, wbits)
        if ckey not in self.stats.contract_buckets:
            self.stats.contract_buckets.add(ckey)
            self.stats.contract_compiles += 1
            _obs_watchdog().note("engine.contract", ckey)
        with _obs_span(
            "vcycle.contract", cat="vcycle", n=int(n), m=int(m),
        ):
            (C, n_c, nw_c, indptr_c, src_c, dst_c, ew_c, m_c, nwmax,
             ewmax) = contract_device(
                src, dst, ew, nw, lab, jnp.int32(n), jnp.int32(m),
                wbits=wbits,
            )
            # the only host sync of the level: all four scalars in one
            # transfer (it also bounds the span — no extra block needed)
            n_c, m_c, nwmax, ewmax = self.host_read(
                (n_c, m_c, nwmax, ewmax), "contract_sizes")
        n_c, m_c, nwmax, ewmax = int(n_c), int(m_c), float(nwmax), float(ewmax)
        self.stats.d2h_bytes += 16
        Ncb = _pow2(max(n_c, 8))
        Mcb = _mbucket(m_c)
        coarse = GraphDev(
            indptr=indptr_c[: Ncb + 1],
            indices=dst_c[:Mcb],
            ew=ew_c[:Mcb],
            nw=nw_c[:Ncb],
            src=src_c[:Mcb],
            n=n_c, m=m_c, nw_max=nwmax,
            ew_max=ewmax, ew_integral=integral,
            on_materialize=self._note_d2h,
        )
        cmap = CoarseMap(
            dev=C, n_fine=n, n_coarse=n_c, on_materialize=self._note_d2h
        )
        _mem_account("base_csr", C)
        return coarse, cmap

    def project_restrict(self, C: CoarseMap, restrict: jax.Array) -> jax.Array:
        """Push a V-cycle restriction one level down on device:
        ``r_c[C[v]] = r[v]`` (consistent — clusters never straddle cells).
        Returns an arena-sized int32 array, -1 beyond the coarse n."""
        Nb = C.dev.shape[0]
        idx = jnp.where(self._iota[:Nb] < C.n_fine, C.dev, self.A)
        out = jnp.full((self.A,), -1, jnp.int32).at[idx].set(
            restrict[:Nb].astype(jnp.int32), mode="drop"
        )
        _mem_account("label_arenas", out)
        return out

    def _note_d2h(self, nbytes: int) -> None:
        """A GraphDev/CoarseMap materialization: one read, counted after it."""
        self.stats.d2h_bytes += int(nbytes)
        self.stats.host_reads += 1

    def host_read(self, x, what: str):
        """Every blocking device->host read of the partition path goes
        through here: ``x`` is a device array (-> numpy) or a tuple of them
        (-> tuple of numpy), counted in ``host_reads`` and spanned as
        ``host.read``.  The read is the caller's; this adds none."""
        self.stats.host_reads += 1
        if isinstance(x, tuple):
            nbytes = sum(int(a.nbytes) for a in x)
            with _obs_span("host.read", cat="host", what=what, bytes=nbytes):
                return tuple(jax.device_get(x))
        with _obs_span("host.read", cat="host", what=what,
                       bytes=int(x.nbytes)):
            return np.asarray(x)

    def read_grow_rounds(self) -> int:
        """Fold the device GA's pending grow-loop trip counts (one scalar
        per seed step, left on the device) into ``evo_grow_rounds``, in one
        read, and return the total.  Tracing alone calls this, at
        ``partition`` close with the labels already on the host, so with
        tracing off the counts are never read and nothing syncs."""
        if self._grow_rounds:
            self.stats.evo_grow_rounds += sum(
                int(r) for r in jax.device_get(self._grow_rounds))
            self._grow_rounds.clear()
        return self.stats.evo_grow_rounds

    # --------------------------------------------------------- device helpers

    def to_arena(
        self, labels: Union[np.ndarray, jax.Array], n: int, fill: int
    ) -> jax.Array:
        """Lift labels of length >= n into an (A,) int32 arena array."""
        if isinstance(labels, jax.Array):
            lab = labels.astype(jnp.int32)
            if lab.shape[0] == self.A:
                return lab
            lab = jnp.concatenate(
                [lab[:n], jnp.full((self.A - n,), fill, jnp.int32)]
            )
            _mem_account("label_arenas", lab)
            return lab
        out = np.full(self.A, fill, np.int32)
        out[:n] = np.asarray(labels[:n], dtype=np.int32)
        arr = jnp.asarray(out)
        _mem_account("label_arenas", arr)
        return arr

    def project(
        self,
        coarse_labels: Union[np.ndarray, jax.Array],
        C: Union[np.ndarray, CoarseMap],
        fill: int,
    ) -> jax.Array:
        """Project coarse labels through a contraction map C (fine -> coarse)
        entirely on device; returns arena-sized fine labels.  ``C`` may be a
        host numpy map or a device :class:`CoarseMap` (no upload needed)."""
        if isinstance(coarse_labels, jax.Array):
            base = coarse_labels.astype(jnp.int32)
        else:
            base = jnp.asarray(np.asarray(coarse_labels, dtype=np.int32))
            self.stats.h2d_bytes += coarse_labels.shape[0] * 4
        if isinstance(C, CoarseMap):
            n_f = C.n_fine
            Nb = C.dev.shape[0]
            fine = jnp.where(
                self._iota[:Nb] < n_f, base[C.dev], jnp.int32(fill)
            )
            out = jnp.concatenate(
                [fine, jnp.full((self.A - Nb,), fill, jnp.int32)]
            )
            _mem_account("label_arenas", out)
            return out
        n_f = C.shape[0]
        C_dev = jnp.asarray(np.asarray(C, dtype=np.int32))
        self.stats.h2d_bytes += n_f * 4
        fine = base[C_dev]
        out = jnp.concatenate(
            [fine, jnp.full((self.A - n_f,), fill, jnp.int32)]
        )
        _mem_account("label_arenas", out)
        return out

    def cut(self, g: AnyGraph, labels: jax.Array) -> float:
        """Edge cut of arena labels, evaluated on device (one scalar sync);
        exact for integral weights at any size."""
        ar = self._arena(g)
        return float(self.host_read(cut_from_arcs_jnp(
            labels, ar.src, ar.dst, ar.ew, integral=ar.integral
        ), "cut"))

    def block_weights(self, g: AnyGraph, labels: jax.Array, k: int) -> np.ndarray:
        ar = self._arena(g)
        bw = jnp.zeros((k + 1,), jnp.float32).at[jnp.minimum(labels, k)].add(
            ar.nw_arena
        )
        return self.host_read(bw[:k], "block_weights")

    def to_host(self, labels: jax.Array, n: int) -> np.ndarray:
        return self.host_read(labels[:n], "labels")

    # ---------------------------------------------------------------- metrics

    @property
    def compile_count(self) -> int:
        """Distinct sweep (bucket, statics) combinations dispatched — each is
        one XLA compilation of ``_lp_sweep``."""
        return self.stats.sweep_compiles

    @staticmethod
    def jit_cache_size() -> Optional[int]:
        """Size of the jit cache of ``_lp_sweep`` itself, when available."""
        try:
            return int(_lp_sweep._cache_size())
        except Exception:
            return None

    def stats_dict(self) -> dict:
        return dict(
            sweep_calls=self.stats.sweep_calls,
            sweep_compiles=self.stats.sweep_compiles,
            bucket_count=self.stats.bucket_count,
            pack_builds=self.stats.pack_builds,
            pack_hits=self.stats.pack_hits,
            dense_rounds=self.stats.dense_rounds,
            dense_compiles=self.stats.dense_compiles,
            evo_calls=self.stats.evo_calls,
            evo_compiles=self.stats.evo_compiles,
            evo_bucket_count=self.stats.evo_bucket_count,
            contract_calls=self.stats.contract_calls,
            contract_compiles=self.stats.contract_compiles,
            contract_bucket_count=self.stats.contract_bucket_count,
            gather_builds=self.stats.gather_builds,
            gather_compiles=self.stats.gather_compiles,
            repair_calls=self.stats.repair_calls,
            repair_compiles=self.stats.repair_compiles,
            repair_bucket_count=self.stats.repair_bucket_count,
            audit_calls=self.stats.audit_calls,
            audit_compiles=self.stats.audit_compiles,
            audit_bucket_count=self.stats.audit_bucket_count,
            h2d_bytes=self.stats.h2d_bytes,
            d2h_bytes=self.stats.d2h_bytes,
            host_reads=self.stats.host_reads,
            evo_grow_rounds=self.stats.evo_grow_rounds,
            evo_grow_budget=self.stats.evo_grow_budget,
            evo_table_steps=self.stats.evo_table_steps,
            arena=self.A,
            chunk_bucket=(self.C_bucket, self.N, self.E_floor),
        )
