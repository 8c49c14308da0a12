"""Batched device evolution vs the sequential numpy oracle.

The contract under test (ISSUE 3): the device-batched island GA — vmapped
population over the engine's cached chunk pack, overlay-cell combine,
device-side elitism/selection/gossip — produces labels BIT-IDENTICAL to the
one-individual-at-a-time numpy oracle under the same seeds, preserves the
paper's offspring-never-worse-than-better-parent invariant, compiles once
per shape bucket, and consumes a still-resident GraphDev coarsest graph
without materializing it to host.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import LPEngine, PartitionerConfig, initial_partition, partition
from repro.core.evolutionary import EvoConfig, evolve_batched_numpy
from repro.core.metrics import cut_np, lmax
from _subproc import run_with_devices
from repro.graph import (
    GraphDev, GraphNP, barabasi_albert, from_edges, mesh2d, planted_partition,
    rgg, ring,
)


def _cfg(k, L, I, P, G, seed, seeds=()):
    return EvoConfig(k=k, Lmax=L, islands=I, pop_per_island=P, generations=G,
                     refine_iters=3, seed=seed,
                     seed_individuals=list(seeds))


@pytest.mark.parametrize(
    "case",
    [
        # (graph builder, k, islands, pop, generations, neighbour table?)
        (lambda: planted_partition(700, 6, p_in=0.05, p_out=0.004, seed=1),
         2, 2, 2, 3, True),
        (lambda: barabasi_albert(500, 4, seed=2), 4, 4, 3, 2, False),  # hubs
        (lambda: planted_partition(300, 4, p_in=0.06, p_out=0.01, seed=3),
         3, 1, 2, 2, True),
        (lambda: barabasi_albert(64, 3, seed=4), 2, 2, 1, 3, False),  # mutate
        # meshes at the benchmark's k = 32 (Kb = 64): the table path
        (lambda: mesh2d(40), 32, 2, 2, 2, True),
        (lambda: rgg(11, seed=1), 32, 2, 2, 0, True),         # "fast" preset
    ],
)
def test_device_matches_oracle_bit_for_bit(case):
    gbuild, k, I, P, G, table = case
    g = gbuild()
    L = lmax(g.n, k, 0.03)
    eng = LPEngine(g, seed=0)
    assert eng.can_evolve_device(g, k, I, P)
    cfg = _cfg(k, L, I, P, G, seed=11 + k)
    lab_dev = np.asarray(eng.evolve_device(g, cfg))
    lab_ora = eng.evolve_oracle(g, cfg)
    np.testing.assert_array_equal(lab_dev, lab_ora)
    # the seed step and every generation step took the path the shape says
    assert eng.stats.evo_table_steps == (1 + G if table else 0)


def _table_gate(g):
    """The gate's rule from the graph's shape alone: ``Nt * W <= 4 * m``."""
    W = -(-max(int(g.degrees().max()), 1) // 8) * 8
    Nt = min(-(-g.n // 256) * 256, 1 << g.n.bit_length())
    return Nt * W <= 4 * g.m


def _with_hub(g, hub_deg):
    """``g`` plus arcs from node 0 to ``hub_deg`` far nodes."""
    src = g.arc_sources()
    keep = src < g.indices
    far = np.arange(g.n // 2, g.n // 2 + hub_deg)
    return from_edges(
        g.n, np.concatenate([src[keep], np.zeros(hub_deg, np.int64)]),
        np.concatenate([g.indices[keep], far]),
    )


def test_neighbour_table_gate_follows_shape_alone():
    """The GA takes the neighbour table exactly when ``Nt * W <= 4 * m``:
    a 256-ring sits on the bound (2,048 slots, 512 arcs) and takes it; one
    more node (a second 256-row tile) or a hub (W = 16) tips it over.  The
    weights never enter: tripled arc weights decide the same."""
    r = ring(256)
    heavy = GraphNP(indptr=r.indptr, indices=r.indices, ew=r.ew * 3,
                    nw=r.nw * 2)
    cases = [(r, True), (heavy, True), (ring(257), False),
             (_with_hub(r, 9), False), (mesh2d(40), True),
             (barabasi_albert(500, 4, seed=2), False)]
    for g, want in cases:
        assert _table_gate(g) == want
        eng = LPEngine(g, seed=0)
        table = eng._evo_table(g, 1 << g.n.bit_length())
        assert (table is not None) == want
        if want:
            nbr, wt = table
            W = nbr.shape[1]
            assert W % 8 == 0 and W >= g.degrees().max()
            assert nbr.shape[0] * W <= 4 * g.m


def _king(side):
    """``side x side`` grid with all eight neighbours: interior degree 8."""
    i, j = np.divmod(np.arange(side * side), side)
    us, vs = [], []
    for di, dj in ((0, 1), (1, 0), (1, 1), (1, -1)):
        ok = (i + di < side) & (j + dj >= 0) & (j + dj < side)
        us.append(np.flatnonzero(ok))
        vs.append((i[ok] + di) * side + j[ok] + dj)
    return from_edges(side * side, np.concatenate(us), np.concatenate(vs))


def test_grow_and_gain_rounds_on_a_full_table_row():
    """A node of degree exactly ``W`` fills its table row with no padding
    slot: the grow loop and the gain round read the same connectivity from
    the table as from the per-arc scatter (labels and trips equal), and the
    whole GA stays bit-identical to the numpy oracle."""
    from repro.core.evo_device import _gain_round, _greedy_one
    from repro.core.evolutionary import grow_rounds_bound

    g = _king(20)
    assert g.degrees().max() == 8
    k, Kb = 8, 16
    L = lmax(g.n, k, 0.03)
    eng = LPEngine(g, seed=0)
    Ab = 1 << g.n.bit_length()
    nbr, wt = eng._evo_table(g, Ab)
    assert nbr.shape[1] == 8
    full = np.flatnonzero(g.degrees() == 8)
    assert full.size and np.all(np.asarray(wt)[full] > 0)   # no pad slot
    rows = np.sort(np.asarray(nbr)[full], axis=1)
    adj = np.sort(g.indices[g.indptr[full][:, None] + np.arange(8)], axis=1)
    np.testing.assert_array_equal(rows, adj)                # the whole row
    ar = eng._arena(g)
    nw = ar.nw_arena[:Ab]
    deg = eng._deg_f(g, Ab)
    args = (ar.src, ar.dst, ar.ew, nw, deg, jnp.int32(g.n), jnp.int32(k),
            Kb, jnp.float32(L), jnp.int32(5),
            jnp.int32(grow_rounds_bound(g.n, k, g.m)))
    for s in range(3):
        lab_s, trips_s = _greedy_one(jnp.int32(s), *args)
        lab_t, trips_t = _greedy_one(jnp.int32(s), *args, (nbr, wt))
        np.testing.assert_array_equal(np.asarray(lab_t), np.asarray(lab_s))
        assert int(trips_t) == int(trips_s) > 0
        base = jnp.uint32(0x9E3779B1 * (s + 1) & 0xFFFFFFFF)
        gain = [_gain_round(ar.src, ar.dst, ar.ew, nw, lab_s, jnp.int32(g.n),
                            jnp.int32(k), Kb, jnp.float32(L), base, base + 1,
                            t) for t in (None, (nbr, wt))]
        np.testing.assert_array_equal(np.asarray(gain[1]), np.asarray(gain[0]))
    cfg = _cfg(k, L, 2, 2, 1, seed=3)
    np.testing.assert_array_equal(np.asarray(eng.evolve_device(g, cfg)),
                                  eng.evolve_oracle(g, cfg))
    assert eng.stats.evo_table_steps == 2


def test_seeded_device_evo_parity_and_never_worse_than_seed():
    """The V-cycle guarantee on the device path: the projected previous
    solution joins every island unrefined; elitism + gossip can only match
    or improve it — and the whole run still mirrors the oracle exactly."""
    g = planted_partition(800, 6, p_in=0.05, p_out=0.003, seed=5)
    L = lmax(g.n, 2, 0.03)
    seed_lab = initial_partition(g, 2, L, seed=3)
    eng = LPEngine(g, seed=0)
    cfg = _cfg(2, L, 2, 2, 3, seed=9, seeds=[seed_lab.astype(np.int64)])
    lab_dev = np.asarray(eng.evolve_device(g, cfg))
    lab_ora = eng.evolve_oracle(g, cfg)
    np.testing.assert_array_equal(lab_dev, lab_ora)
    assert cut_np(g, lab_dev) <= cut_np(g, seed_lab)


def test_offspring_never_worse_than_better_parent():
    """Per-generation elitism property, asserted on the oracle's trace (the
    device path is bit-identical to it, so the invariant transfers)."""
    g = planted_partition(600, 6, p_in=0.05, p_out=0.004, seed=7)
    L = lmax(g.n, 2, 0.03)
    eng = LPEngine(g, seed=0)
    cfg = _cfg(2, L, 2, 3, 4, seed=21)
    trace = []
    lab = eng.evolve_oracle(g, cfg, trace=trace)
    assert len(trace) == cfg.generations * cfg.islands
    for gen, isl, base_key, child_key in trace:
        # post-elitism the inserted key is min(child, base): never above base
        assert min(child_key, base_key) <= base_key
    # ... and parity still holds for this config
    np.testing.assert_array_equal(np.asarray(eng.evolve_device(g, cfg)), lab)


def test_graphdev_coarsest_consumed_without_host_materialization():
    """The coarsest stage must feed the still-resident GraphDev straight
    into the batched GA: no ``to_host`` materialization of the coarse CSR."""
    g = barabasi_albert(4096, 5, seed=1)
    L = lmax(g.n, 2, 0.03)
    eng = LPEngine(g, seed=0)
    clus = eng.cluster(g, U=max(1.0, L / 14), iters=3, seed=7)
    cdev, _ = eng.contract(g, clus)
    assert isinstance(cdev, GraphDev)
    cfg = _cfg(2, L, 2, 2, 1, seed=3)
    lab_dev = eng.evolve_device(cdev, cfg)
    assert isinstance(lab_dev, jax.Array)
    assert cdev._host is None            # never materialized
    lab_ora = eng.evolve_oracle(cdev, cfg)
    np.testing.assert_array_equal(np.asarray(lab_dev), lab_ora)


def _evo_compiles_within_buckets(g):
    """Partition ``g`` over two V-cycles with two generations; the evo entry
    points' jit caches may grow by at most the engine's bucket count.
    Returns the engine stats."""
    from repro.core.evo_device import evo_generation_step, evo_seed_step

    def _jit_entries():
        try:
            return int(evo_seed_step._cache_size()) + int(
                evo_generation_step._cache_size()
            )
        except Exception:
            return None

    cfg = PartitionerConfig(k=2, preset="fast", coarsest_factor=50, seed=0,
                            engine="jnp", generations=2, islands=2,
                            pop_per_island=2)
    before = _jit_entries()
    rep = partition(g, cfg)
    st = rep.engine_stats
    assert rep.feasible
    # 2 V-cycles x (1 seed step + 2 generation steps)
    assert st["evo_calls"] >= 4
    assert st["evo_compiles"] <= st["evo_calls"]
    assert st["evo_compiles"] < st["evo_calls"]
    after = _jit_entries()
    if before is not None and after is not None:
        assert after - before <= st["evo_bucket_count"]
    return st


def test_evo_compile_count_bounded_by_buckets():
    """Compile-count regression: across a multi-V-cycle partition run the
    batched evo compiles once per (phase, shape-bucket) — never per call.

    The engine's ``evo_compiles == evo_bucket_count`` is definitional (both
    derive from the same key set), so the real assertion is against the jit
    caches of the evo entry points themselves: their growth across the run
    must not exceed the reported bucket count (a per-call shape drift would
    blow straight past it)."""
    _evo_compiles_within_buckets(barabasi_albert(4096, 5, seed=1))


def test_evo_compile_count_bounded_on_the_table_path():
    """The same bound where the GA reads the neighbour table: the table's
    shape is part of the evo bucket key, so it adds no unaccounted
    executable."""
    st = _evo_compiles_within_buckets(mesh2d(64))
    assert st["evo_table_steps"] > 0


def test_partition_evo_engine_host_fallback_matches_legacy():
    """evo_engine='host' must keep the legacy sequential KaFFPaE behaviour
    byte-for-byte (guards the fallback for non-integral-weight inputs)."""
    g = barabasi_albert(4096, 5, seed=2)
    base = dict(k=2, preset="fast", coarsest_factor=100, seed=0)
    rep_h = partition(g, PartitionerConfig(**base, evo_engine="host"))
    assert rep_h.feasible
    assert rep_h.engine_stats["evo_calls"] == 0
    rep_d = partition(g, PartitionerConfig(**base))
    assert rep_d.feasible
    assert rep_d.engine_stats["evo_calls"] >= 1


def test_non_integral_weights_fall_back_to_host_evo():
    g = planted_partition(512, 4, p_in=0.05, p_out=0.01, seed=0)
    g2 = type(g)(indptr=g.indptr, indices=g.indices,
                 ew=g.ew + np.float32(0.5), nw=g.nw)
    eng = LPEngine(g2, seed=0)
    assert not eng.can_evolve_device(g2, 2, 2, 2)


def test_greedy_growing_k_ge_n_guard():
    """Satellite regression: k >= n used to crash the degree-biased seed
    draw (rng.choice without replacement); now falls back to round-robin."""
    from repro.core import greedy_growing

    g = mesh2d(2)  # n = 4
    for k in (4, 5, 9):
        lab = greedy_growing(g, k, Lmax=10.0, seed=0)
        assert lab.shape == (g.n,)
        assert lab.min() >= 0 and lab.max() < k
        # round-robin: every node its own block (mod k)
        np.testing.assert_array_equal(lab, np.arange(g.n) % k)


def test_grow_rounds_scale_with_diameter_on_deep_path_like_graph():
    """ISSUE 4 satellite: the fixed GROW_ROUNDS=16 frontier truncated deep
    coarsest graphs — on a ring, all but ~2*(16+1) nodes used to land in the
    round-robin leftover fallback, whose alternating labels cut almost every
    edge.  The degree/diameter-proportional budget
    (evolutionary.grow_rounds_bound) lets both seeds grow to contiguous
    arcs: tiny cut, and the device path stays bit-identical to the oracle on
    the deep graph (the traced bound + stall exit change neither side's
    hash streams)."""
    from repro.core.evolutionary import GROW_ROUNDS, grow_rounds_bound
    from repro.graph import ring

    g = ring(300)
    assert grow_rounds_bound(g.n, 2, g.m) >= g.n // 2   # deep graph: ~n
    assert grow_rounds_bound(1600, 2, 1600 * 11) == max(
        GROW_ROUNDS, int(np.ceil(4 * 1600 / 2 / 11))
    )                                                   # shallow: ~n/(k*deg)
    L = lmax(g.n, 2, 0.03)
    eng = LPEngine(g, seed=0)
    cfg = _cfg(2, L, 1, 1, 0, seed=5)
    lab_dev = np.asarray(eng.evolve_device(g, cfg))
    lab_ora = eng.evolve_oracle(g, cfg)
    np.testing.assert_array_equal(lab_dev, lab_ora)
    # two contiguous blocks cut O(1) edges; 16-round truncation left the
    # leftover tail alternating (cut ~ hundreds)
    assert cut_np(g, lab_dev) <= 20


def test_device_ell_gather_matches_host_pack():
    """Satellite: dense refinement's ELL pack for a GraphDev level is now
    gathered on device — bit-identical to ell_pack on the materialized
    graph, with no O(m) adjacency download."""
    g = barabasi_albert(4096, 5, seed=3)
    L = lmax(g.n, 2, 0.03)
    eng = LPEngine(g, seed=0)
    clus = eng.cluster(g, U=max(1.0, L / 14), iters=3, seed=1)
    cdev, _ = eng.contract(g, clus)
    d2h_before = eng.stats.d2h_bytes
    ell_dev = eng._ell(cdev)
    d2h_delta = eng.stats.d2h_bytes - d2h_before
    # only the O(n) indptr may cross, never the O(m) adjacency
    assert d2h_delta <= (cdev.n + 1) * 8 + 64
    assert cdev._host is None
    # host oracle on the materialized graph through a fresh engine
    eng2 = LPEngine(g, seed=0)
    ell_host = eng2._ell(cdev.to_host())
    np.testing.assert_array_equal(np.asarray(ell_dev.dst), np.asarray(ell_host.dst))
    np.testing.assert_array_equal(np.asarray(ell_dev.w), np.asarray(ell_host.w))
    np.testing.assert_array_equal(
        np.asarray(ell_dev.row_node), np.asarray(ell_host.row_node)
    )
    assert ell_dev.nb == ell_host.nb


def test_dense_partition_on_device_levels_stays_resident():
    """refine_engine='dense' end-to-end with device coarsening: feasible,
    dense rounds ran, and the whole-run d2h stays far below one download of
    the fine graph (the old _ell host materialization would blow this)."""
    g = barabasi_albert(8192, 6, seed=3)
    cfg = PartitionerConfig(k=2, preset="fast", coarsest_factor=100, seed=0,
                            refine_engine="dense", dense_min_n=256,
                            numpy_below=64, engine="jnp")
    rep = partition(g, cfg)
    assert rep.feasible
    assert rep.engine_stats["dense_rounds"] > 0
    assert rep.engine_stats["d2h_bytes"] < g.m * 4


@pytest.mark.slow
def test_sharded_islands_match_single_device():
    """Island sharding over shard_map: per-epoch gossip as an all_gather
    collective, global island ids in every hash — bit-identical labels."""
    code = """
import numpy as np
import jax
from repro.core import LPEngine
from repro.core.evolutionary import EvoConfig
from repro.core.metrics import lmax
from repro.graph import planted_partition

assert jax.device_count() == 2
g = planted_partition(600, 6, p_in=0.05, p_out=0.004, seed=1)
L = lmax(g.n, 2, 0.03)
cfg = EvoConfig(k=2, Lmax=L, islands=4, pop_per_island=2, generations=3,
                refine_iters=3, seed=5)
eng = LPEngine(g, seed=0)
lab_single = np.asarray(eng.evolve_device(g, cfg, shard=False))
eng2 = LPEngine(g, seed=0)
lab_shard = np.asarray(eng2.evolve_device(g, cfg, shard=True))
assert np.array_equal(lab_single, lab_shard), (lab_single != lab_shard).sum()
oracle = eng.evolve_oracle(g, cfg)
assert np.array_equal(lab_single, oracle)
print("SHARDED_OK")
"""
    out = run_with_devices(code, n_devices=2)
    assert "SHARDED_OK" in out


@pytest.mark.device
def test_evo_device_on_tpu_backend():
    """TPU-only smoke (device marker): the batched GA end-to-end on real
    hardware, uncompromised by interpret-mode shims."""
    if jax.default_backend() != "tpu":
        pytest.skip("requires a real TPU backend")
    g = planted_partition(512, 4, p_in=0.05, p_out=0.01, seed=0)
    L = lmax(g.n, 2, 0.03)
    eng = LPEngine(g, seed=0)
    cfg = _cfg(2, L, 2, 2, 2, seed=1)
    lab = np.asarray(eng.evolve_device(g, cfg))
    assert lab.shape == (g.n,)
    np.testing.assert_array_equal(lab, eng.evolve_oracle(g, cfg))


def test_sweep_refine_numpy_matches_lp_sweep_bitwise():
    """The oracle's inner mirror: numpy chunk sweep == jitted _lp_sweep in
    refine mode, including the device-side chunk permutation, run-reduction
    jitter, and influx gating (integral weights)."""
    from repro.core.label_propagation import (
        _lp_sweep, make_order, sweep_refine_numpy,
    )
    from repro.graph import pack_chunks
    from repro.graph.packing import pad_pack

    g = planted_partition(600, 6, p_in=0.05, p_out=0.004, seed=1)
    n, k = g.n, 3
    Ab = 1 << n.bit_length()
    Kb = 4
    L = np.float32(lmax(g.n, k, 0.03))
    pack = pack_chunks(g, make_order(g, "random", 0), max_nodes=128,
                       max_edges=2048, block=8)
    C0 = pack.nodes.shape[0]
    pack = pad_pack(pack, 1 << (C0 - 1).bit_length(), 128,
                    pack.edge_dst.shape[1])
    rng = np.random.default_rng(0)
    lab0 = np.full(Ab, k, np.int32)
    lab0[:n] = rng.integers(0, k, n)
    nw = np.zeros(Ab, np.float32)
    nw[:n] = g.nw
    bw = np.zeros(Kb, np.float32)
    np.add.at(bw, lab0, nw)
    w0 = np.where(np.arange(Kb) < k, bw, np.float32(np.inf)).astype(np.float32)
    for seed in (7, 12345):
        out_dev, _, _ = _lp_sweep(
            jnp.asarray(pack.nodes), jnp.asarray(pack.node_valid),
            jnp.asarray(pack.edge_dst), jnp.asarray(pack.edge_w),
            jnp.asarray(pack.edge_src_slot), jnp.asarray(pack.edge_valid),
            jnp.asarray(lab0), jnp.asarray(w0), jnp.asarray(nw),
            jnp.zeros(1, jnp.int32), jnp.float32(L), jnp.int32(seed),
            jnp.int32(k), jnp.int32(pack.num_chunks),
            iters=4, refine_mode=True, use_restrict=False, permute_chunks=True,
        )
        out_np, _ = sweep_refine_numpy(
            pack.nodes, pack.node_valid, pack.edge_dst, pack.edge_w,
            pack.edge_src_slot, pack.edge_valid,
            lab0, w0, nw, L, seed, k, pack.num_chunks, 4,
        )
        np.testing.assert_array_equal(np.asarray(out_dev), out_np)
