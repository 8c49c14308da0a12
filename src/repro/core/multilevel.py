"""The overall multilevel system (paper §IV-E) with iterated V-cycles.

Pipeline per V-cycle:

  coarsen:   l iterations of parallel SCLaP (U = max(max_v c(v), L_max/f),
             degree order) -> cluster contraction, repeated until the graph
             has <= coarsest_factor * k nodes or contraction stalls.  On the
             jnp engine the whole chain is device-resident: clustering,
             contraction (``LPEngine.contract``), and the next level's pack
             gather all run on device over a GraphDev hierarchy; only the
             (n_c, m_c, max nw) scalars cross to host per level;
  initial:   the island evolutionary algorithm (KaFFPaE) on the replicated
             coarsest graph — seeded with the projected current solution
             from the 2nd V-cycle on, so quality never regresses;
  uncoarsen: project labels through the hierarchy, r iterations of SCLaP
             local search per level (U = L_max, random order), final
             feasibility repair at the finest level.

Presets mirror the paper §V-A: *fast* (3/6 LP iters, 2 V-cycles, GA gets
only its initial population), *eco* (5 V-cycles + GA generations), *minimal*
(1 V-cycle).  f = 14 for social/web graphs, "20000" for meshes in the first
V-cycle (scale-capped — the paper's value presumes billion-edge graphs),
random in [10, 25] afterwards.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np

from ..graph.csr import GraphDev, GraphNP
from ..graph.packing import chunk_geometry
from ..obs import gc_spans as _gc_spans
from ..obs import span as _obs_span
from .contraction import CoarseMap, contract, project_labels
from .engine import LPEngine
from .evolutionary import EvoConfig, evolve, grow_rounds_bound
from .initial_partition import repair_balance
from .label_propagation import lp_cluster, lp_refine, sclap_numpy
from .metrics import cut_np, imbalance_np, lmax

__all__ = ["PartitionerConfig", "PartitionReport", "partition"]


@dataclass
class PartitionerConfig:
    k: int = 2
    eps: float = 0.03
    preset: str = "fast"            # fast | eco | minimal
    graph_type: str = "auto"        # social | mesh | auto
    lp_iters_coarsen: int = 3
    lp_iters_refine: int = 6
    f_social: float = 14.0
    f_mesh: float = 20000.0
    # stop coarsening at coarsest_factor * k nodes; 0 = auto-scale to the
    # input: max(k, min(10000 * k, n // 8)).  The paper's 10000*k constant
    # targets million-node graphs — as a fixed default it meant any graph
    # under ~40k nodes (at k=4) never coarsened at all, turning "multilevel"
    # into flat LP on the bench sizes.  Explicit positive values are
    # honored verbatim (tests pin small targets with e.g. 256).
    coarsest_factor: int = 0
    max_levels: int = 64
    shrink_stall: float = 0.95      # stop if n' > stall * n
    seed: int = 0
    # engine
    engine: str = "auto"            # jnp | numpy | dist | auto
    numpy_below: int = 4096         # use the sequential engine below this n
    target_chunks: int = 64
    # coarsening path for the jnp engine: "device" keeps cluster -> contract
    # -> next-level pack chained on device (GraphDev hierarchy, only scalars
    # cross to host per level); "host" is the legacy numpy contract()
    # round-trip (also the benchmark baseline).
    coarsen_engine: str = "device"  # device | host
    dist_shards: int = 0            # engine="dist": number of mesh PEs
    dist_chunks_per_shard: int = 4
    # refinement engine for the jnp path: "chunked" = chunked-sequential LP
    # sweep; "dense" = synchronous Pallas-scored dense rounds at fine levels
    # (>= dense_min_n nodes), falling back to chunked/numpy below.
    refine_engine: str = "chunked"  # chunked | dense
    dense_min_n: int = 4096
    # coarsest-stage evolutionary engine: "device" runs the batched island
    # GA on device (population as a (pop, n) batch over the still-resident
    # coarsest graph — GraphDev levels never materialize to host); "host" is
    # the legacy sequential KaFFPaE loop; "auto" picks device whenever the
    # LP engine is active and the exact-weight eligibility gate passes
    # (LPEngine.can_evolve_device), host otherwise.
    evo_engine: str = "auto"        # auto | device | host
    # map islands onto shard_map shards (one mesh axis over the local
    # devices; per-epoch gossip becomes an all_gather collective).  Requires
    # islands % device_count == 0; results stay bit-identical to the
    # single-device path, so this is purely a throughput knob.
    evo_shard_islands: bool = False
    # BEYOND-PAPER: gain-based FM pass on the finest level (the paper's fine
    # refinement is LP-only; see EXPERIMENTS.md §Paper-validation for the
    # separate accounting).  Enabled by the "strong" preset.
    fm_finest: bool = False
    fm_finest_max_n: int = 2_000_000
    # evolutionary budget (scaled by preset)
    islands: int = 2
    pop_per_island: int = 2
    generations: int = 0
    # seed the FIRST V-cycle with an existing k-way partition via the
    # restrict machinery (cycle 0 then behaves exactly like cycle >= 2 of
    # an iterated run: clustering never merges across the seed's cut edges
    # and the coarsest GA is seeded with the projected labels).  Used by
    # the dynamic session's escalation path so a full re-partition starts
    # from the served solution instead of from scratch.
    initial_labels: Optional[np.ndarray] = None

    def __post_init__(self):
        if self.preset == "eco":
            self.islands = max(self.islands, 4)
            self.pop_per_island = max(self.pop_per_island, 3)
            self.generations = max(self.generations, 8)
            self.vcycles = 5
        elif self.preset == "minimal":
            self.vcycles = 1
        elif self.preset == "strong":  # beyond-paper: eco + finest-level FM
            self.islands = max(self.islands, 4)
            self.pop_per_island = max(self.pop_per_island, 3)
            self.generations = max(self.generations, 8)
            self.vcycles = 5
            self.fm_finest = True
        else:  # fast
            self.vcycles = 2

    vcycles: int = field(default=2, init=False)


@dataclass
class PartitionReport:
    labels: np.ndarray
    cut: float
    imbalance: float
    feasible: bool
    level_sizes: List[tuple]        # [(n, m) per level incl. finest]
    shrink_first: float             # n_1 / n_0 after first contraction
    cycle_cuts: List[float]
    seconds: float
    engine_stats: Optional[dict] = None  # LPEngine counters (jnp path only)


def _detect_type(g: GraphNP) -> str:
    deg = g.degrees().astype(np.float64)
    if deg.size == 0:
        return "mesh"
    cv = deg.std() / max(deg.mean(), 1e-9)
    return "social" if cv > 0.7 else "mesh"


def _f_value(cfg: PartitionerConfig, gtype: str, cycle: int, rng) -> float:
    if cycle > 0:
        return float(rng.uniform(10.0, 25.0))
    return cfg.f_social if gtype == "social" else cfg.f_mesh


def _use_numpy(g, cfg) -> bool:
    return cfg.engine == "numpy" or (
        cfg.engine in ("auto", "dist") and g.n < cfg.numpy_below
    )


def _cluster(g, U, iters, seed, restrict, cfg, eng=None) -> np.ndarray:
    if _use_numpy(g, cfg):
        return sclap_numpy(
            g, np.arange(g.n), U=U, iters=iters, seed=seed, restrict=restrict
        ).labels
    if cfg.engine == "dist" and restrict is None:
        # V-cycle-restricted clustering keeps the single-mesh path; the
        # unrestricted (hot) first cycle runs on the device mesh.  The plan
        # is keyed on cfg.seed (the run's seed-epoch), not the per-call
        # sweep seed, so repeated calls on one graph hit the plan cache.
        from .distributed_lp import build_plan, lp_cluster_distributed

        plan = build_plan(
            g, cfg.dist_shards, chunks_per_shard=cfg.dist_chunks_per_shard,
            order="degree", seed=cfg.seed,
        )
        return lp_cluster_distributed(plan, U=U, iters=iters, seed=seed)
    if eng is not None:
        return eng.host_read(
            eng.cluster(g, U=U, iters=iters, seed=seed, restrict=restrict),
            "labels",
        )
    max_nodes, max_edges = chunk_geometry(g.n, g.m, cfg.target_chunks)
    return lp_cluster(
        g, U=U, iters=iters, seed=seed, restrict=restrict,
        max_nodes=max_nodes, max_edges=max_edges,
    ).labels


def _refine(g, labels, k, Lmax, iters, seed, cfg) -> np.ndarray:
    """Host-path refinement (numpy / dist / legacy jnp without an engine).

    The engine-owned device-resident path lives in ``_uncoarsen``."""
    use_numpy = _use_numpy(g, cfg)
    if not use_numpy and cfg.engine == "dist":
        from .distributed_lp import build_plan, lp_refine_distributed

        plan = build_plan(
            g, cfg.dist_shards, chunks_per_shard=cfg.dist_chunks_per_shard,
            order="random", seed=cfg.seed,
        )
        return lp_refine_distributed(plan, labels, k=k, U=Lmax, iters=iters, seed=seed)
    if use_numpy:
        from .fm import fm_refine

        lab = sclap_numpy(
            g, labels, U=Lmax, iters=iters, seed=seed, refine_mode=True, num_labels=k
        ).labels
        # strong gain-based search on small (coarse) levels, like KaFFPa
        return fm_refine(g, lab, k, Lmax, seed=seed)
    max_nodes, max_edges = chunk_geometry(g.n, g.m, cfg.target_chunks)
    return lp_refine(
        g, labels, k=k, U=Lmax, iters=iters, seed=seed,
        max_nodes=max_nodes, max_edges=max_edges,
    ).labels


def _uncoarsen(g, hierarchy, lab, k, L, cfg, rng, eng):
    """Project + refine through the hierarchy (uncoarsening local search).

    On the engine (jnp) path, labels stay device-resident across levels:
    projection, the sweep/dense rounds, and the monotonicity-guard cut and
    balance evaluations all run on device; only two scalars per level cross
    back to host.  Host-path levels (numpy below ``numpy_below``, dist)
    keep the original numpy flow.
    """
    lab_dev = None  # engine arena labels, device-resident once set
    for gg_f, C in reversed(hierarchy):
        with _obs_span("vcycle.uncoarsen", cat="vcycle", n=int(gg_f.n),
                       m=int(gg_f.m)):
            lab, lab_dev = _uncoarsen_level(
                gg_f, C, lab, lab_dev, k, L, cfg, rng, eng
            )
    if lab is None:
        lab = eng.to_host(lab_dev, g.n)
    elif not isinstance(lab, np.ndarray):  # device-evo labels, no hierarchy
        lab = eng.host_read(lab, "labels")
    return lab


def _uncoarsen_level(gg_f, C, lab, lab_dev, k, L, cfg, rng, eng):
    """One uncoarsening level: project, refine, keep the better labels.
    Returns ``(lab, lab_dev)``: host labels or the device arena labels."""
    seed_r = int(rng.integers(1 << 30))
    eng_level = (
        eng is not None
        and cfg.engine in ("auto", "jnp")
        and not _use_numpy(gg_f, cfg)
    )
    if eng_level:
        with _obs_span(
            "vcycle.project", cat="vcycle", n=int(gg_f.n)
        ) as sp:
            lab_dev = eng.project(
                lab_dev if lab_dev is not None else lab, C, fill=k
            )
            sp.sync_on(lab_dev)
        before = eng.cut(gg_f, lab_dev)
        if cfg.refine_engine == "dense" and gg_f.n >= cfg.dense_min_n:
            ref = eng.refine_dense(
                gg_f, lab_dev, k, L, cfg.lp_iters_refine, seed_r
            )
        else:
            ref = eng.refine(gg_f, lab_dev, k, L, cfg.lp_iters_refine, seed_r)
        # monotonicity guard: chunked-synchronous LP may oscillate; keep
        # the refined labels only if they did not worsen the cut (unless
        # they were needed to restore feasibility)
        bw_ref = float(eng.block_weights(gg_f, ref, k).max())
        bw_old = float(eng.block_weights(gg_f, lab_dev, k).max())
        if eng.cut(gg_f, ref) <= before or bw_old > L >= bw_ref:
            lab_dev = ref
        return None, lab_dev
    gg_h = gg_f.to_host() if isinstance(gg_f, GraphDev) else gg_f
    C_np = C.host() if isinstance(C, CoarseMap) else C
    if lab is None:  # leaving the device path (defensive; host levels
        lab = eng.host_read(lab_dev, "labels")  # precede device levels)
        lab_dev = None
    elif not isinstance(lab, np.ndarray):
        lab = eng.host_read(lab, "labels")  # device-evo labels, host level
    lab = project_labels(lab, C_np)
    before = cut_np(gg_h, lab)
    ref = _refine(gg_h, lab, k, L, cfg.lp_iters_refine, seed_r, cfg)
    bw_ref = np.bincount(ref, weights=gg_h.nw, minlength=k).max()
    bw_old = np.bincount(lab, weights=gg_h.nw, minlength=k).max()
    if cut_np(gg_h, ref) <= before or bw_old > L >= bw_ref:
        lab = ref
    return lab, lab_dev


# engine counters a traced ``partition`` span closes with
_SPAN_COUNTERS = ("host_reads", "evo_grow_rounds", "evo_grow_budget",
                  "evo_table_steps")


def partition(g, cfg: PartitionerConfig) -> PartitionReport:
    """Iterated multilevel V-cycles on ``g`` (GraphNP or GraphDev).

    A :class:`GraphDev` finest graph keeps the whole run device-first: the
    engine and the coarsening chain consume the resident handle directly
    (no arena re-upload), and only the host-side finalization steps
    (type detection, balance repair, final metrics) touch the cached
    ``to_host()`` view.  This is the dynamic session's escalation path.

    The whole call is the ``partition`` span (args n, m, k, seed, vcycles);
    when tracing, it closes with the engine's ``host_reads``,
    ``evo_grow_rounds``, ``evo_grow_budget`` and ``evo_table_steps`` as
    metadata.
    """
    with _obs_span(
        "partition", cat="partition", n=int(g.n), m=int(g.m), k=int(cfg.k),
        seed=int(cfg.seed), vcycles=int(cfg.vcycles),
    ) as sp, _gc_spans():
        return _partition(g, cfg, sp)


def _partition(g, cfg: PartitionerConfig, sp) -> PartitionReport:
    t0 = time.time()
    rng = np.random.default_rng(cfg.seed)
    k = cfg.k
    # host view for host-only ops (cached on GraphDev: one O(n+m) download,
    # which the caller typically already paid for serving)
    gh = g.to_host() if isinstance(g, GraphDev) else g
    L = lmax(gh.total_node_weight, k, cfg.eps)
    gtype = cfg.graph_type if cfg.graph_type != "auto" else _detect_type(gh)
    coarsest_target = (
        cfg.coarsest_factor * k
        if cfg.coarsest_factor > 0
        else max(k, min(10000 * k, gh.n // 8))
    )
    # One LP engine per run: owns pack/jit caches and device-resident state
    # for every level of every V-cycle (numpy engine needs none).
    eng = (
        LPEngine(g, target_chunks=cfg.target_chunks, seed=cfg.seed)
        if cfg.engine != "numpy"
        else None
    )

    best_labels: Optional[np.ndarray] = None
    best_cut = np.inf
    cycle_cuts: List[float] = []
    level_sizes: List[tuple] = []
    shrink_first = 1.0

    # device coarsening: cluster -> contract -> next-level pack chains
    # device-to-device (GraphDev hierarchy); the host contract() round-trip
    # remains for the numpy/dist engines and as an explicit fallback
    dev_coarsen = (
        eng is not None
        and cfg.coarsen_engine == "device"
        and cfg.engine in ("auto", "jnp")
    )

    cur_labels: Optional[np.ndarray] = None
    if cfg.initial_labels is not None:
        il = np.asarray(cfg.initial_labels, dtype=np.int64).reshape(-1)
        if il.shape[0] != g.n:
            raise ValueError("initial_labels length must equal g.n")
        if il.size and (il.min() < 0 or il.max() >= k):
            raise ValueError("initial_labels must lie in [0, k)")
        cur_labels = il
    for cycle in range(cfg.vcycles):
        # ---------------- coarsening ----------------
        f = _f_value(cfg, gtype, cycle, rng)
        hierarchy = []  # [(graph, C)] — C is np or CoarseMap, graph NP or Dev
        gg = g
        restrict = cur_labels  # protect cut edges from the 2nd cycle on
        # ``restrict`` mirrors the level type: numpy on host levels, an
        # arena-sized device array on device levels
        for lev in range(cfg.max_levels):
            if gg.n <= coarsest_target:
                break
            with _obs_span("vcycle.level", cat="vcycle", level=lev,
                           n=int(gg.n), m=int(gg.m)):
                coarse, C, gg, restrict = _coarsen_level(
                    gg, restrict, L, f, k, cfg, rng, eng, dev_coarsen
                )
            if coarse is None:  # stall, or overshoot below k
                break
            hierarchy.append((gg, C))
            if cycle == 0 and lev == 0:
                shrink_first = coarse.n / max(gg.n, 1)
            gg = coarse
        if cycle == 0:
            level_sizes = [(h[0].n, h[0].m) for h in hierarchy] + [(gg.n, gg.m)]

        # ---------------- initial partitioning ----------------
        seeds = []
        if cur_labels is not None:
            if not isinstance(restrict, np.ndarray):
                restrict = eng.host_read(
                    restrict[: gg.n], "restrict").astype(np.int64)
            seeds.append(restrict.astype(np.int32))  # projected current solution
        evo = EvoConfig(
            k=k,
            Lmax=L,
            islands=cfg.islands,
            pop_per_island=cfg.pop_per_island,
            generations=cfg.generations,
            refine_iters=cfg.lp_iters_refine,
            seed=int(rng.integers(1 << 30)),
            seed_individuals=seeds,
        )
        use_dev_evo = (
            eng is not None
            and cfg.engine in ("auto", "jnp")
            and cfg.evo_engine in ("auto", "device")
            and eng.can_evolve_device(gg, k, cfg.islands, cfg.pop_per_island)
        )
        with _obs_span(
            "vcycle.evolve", cat="vcycle", device=use_dev_evo, n=int(gg.n),
            m=int(gg.m), islands=cfg.islands, pop=cfg.pop_per_island,
            generations=cfg.generations,
            grow_budget=grow_rounds_bound(gg.n, k, gg.m) if use_dev_evo else 0,
        ) as ev:
            if use_dev_evo:
                # the coarsest stage consumes the still-resident GraphDev (or
                # the finest GraphNP) directly: batched device GA, labels stay
                # on device into the uncoarsening projection
                lab = eng.evolve_device(gg, evo, shard=cfg.evo_shard_islands)
                ev.sync_on(lab)
            else:
                gg_host = gg.to_host() if isinstance(gg, GraphDev) else gg
                lab = evolve(gg_host, evo)

        # ---------------- uncoarsening + local search ----------------
        lab = _uncoarsen(g, hierarchy, lab, k, L, cfg, rng, eng)
        with _obs_span("partition.finalize", cat="partition", cycle=cycle):
            if cfg.fm_finest and g.n <= cfg.fm_finest_max_n:
                from .fm import fm_refine

                lab = fm_refine(gh, lab, k, L, seed=int(rng.integers(1 << 30)))
            lab = repair_balance(gh, lab, k, L, seed=cfg.seed)
            c = cut_np(gh, lab)
        cycle_cuts.append(c)
        cur_labels = lab.astype(np.int64)
        if c < best_cut:
            best_cut, best_labels = c, lab
        if eng is not None:
            eng.evict(keep=(g,))  # coarse graphs never recur across cycles

    with _obs_span("partition.finalize", cat="partition", cycle=-1):
        imbalance = imbalance_np(gh, best_labels, k)
        feasible = bool(
            np.bincount(best_labels, weights=gh.nw, minlength=k).max() <= L + 1e-6
        )
    if eng is not None and sp.active:
        # tracing only: the labels are on the host, so reading the GA's
        # grow-loop trip counts stalls nothing
        eng.read_grow_rounds()
        sp.set(**{c: getattr(eng.stats, c) for c in _SPAN_COUNTERS})
    return PartitionReport(
        labels=best_labels,
        cut=float(best_cut),
        imbalance=imbalance,
        feasible=feasible,
        level_sizes=level_sizes,
        shrink_first=shrink_first,
        cycle_cuts=cycle_cuts,
        seconds=time.time() - t0,
        engine_stats=eng.stats_dict() if eng is not None else None,
    )


def _coarsen_level(gg, restrict, L, f, k, cfg, rng, eng, dev_coarsen):
    """One coarsening level: cluster, contract.  Returns ``(coarse, C, gg,
    restrict)`` — ``gg`` as clustered (handed back to the host below the
    engine threshold), ``restrict`` pushed down to ``coarse`` — or
    ``coarse`` None when contraction stalls or overshoots below k."""
    seed = int(rng.integers(1 << 30))
    if isinstance(gg, GraphDev) and (_use_numpy(gg, cfg) or not dev_coarsen):
        # below the engine threshold (or host coarsening requested): hand
        # the level chain back to the host engines (lazy materialization,
        # one download — cached on the finest level)
        gg = gg.to_host()
        if restrict is not None and not isinstance(restrict, np.ndarray):
            restrict = eng.host_read(
                restrict[: gg.n], "restrict").astype(np.int64)
    if dev_coarsen and not _use_numpy(gg, cfg):
        nw_max = gg.nw_max if isinstance(gg, GraphDev) else float(gg.nw.max())
        U = max(nw_max, L / f)
        if restrict is not None and isinstance(restrict, np.ndarray):
            restrict = eng.to_arena(restrict, gg.n, fill=-1)
        clus = eng.cluster(
            gg, U=U, iters=cfg.lp_iters_coarsen, seed=seed, restrict=restrict,
        )
        coarse, C = eng.contract(gg, clus)
        if coarse.n >= cfg.shrink_stall * gg.n or coarse.n < k:
            return None, None, gg, restrict
        if restrict is not None:
            restrict = eng.project_restrict(C, restrict)
        return coarse, C, gg, restrict
    U = max(float(gg.nw.max()), L / f)
    clus = _cluster(gg, U, cfg.lp_iters_coarsen, seed, restrict, cfg, eng)
    coarse, C = contract(gg, clus)
    if coarse.n >= cfg.shrink_stall * gg.n or coarse.n < k:
        return None, None, gg, restrict
    if restrict is not None:
        rc = np.zeros(coarse.n, dtype=np.int64)
        rc[C] = restrict  # consistent: clusters never straddle blocks
        restrict = rc
    return coarse, C, gg, restrict
