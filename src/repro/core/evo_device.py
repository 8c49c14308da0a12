"""Device-batched island evolutionary search (KaFFPaE, §II-C/IV-E).

The production twin of the numpy oracle in ``repro.core.evolutionary``: the
whole population is a ``(pop, n)`` label batch on device and one generation
runs as ONE bucketed jitted executable —

* **batched greedy-growing seeds** — hash-scored degree-biased seed draw,
  degree/diameter-proportional synchronous frontier rounds
  (``evolutionary.grow_rounds_bound``, traced; converged/stalled frontiers
  exit early), round-robin leftovers.  On bounded-degree graphs the
  frontier and gain rounds read node-block connectivity from a
  one-row-per-node neighbour table (``LPEngine._evo_table``) instead of
  per-arc scatter-adds;
* **batched LP refinement** — a ``vmap`` population axis over the engine's
  cached ``_lp_sweep`` chunk pack (the graph uploads once per run, not once
  per individual), followed by synchronous gain (FM-lite) and balance-repair
  rounds;
* **overlay-cell combine** — ``(P1(v), P2(v))`` cell ids via the same
  packed-key sort/rank relabel the device contraction uses, cell-granular
  block moves instead of a per-individual host contraction;
* **device-side elitism/selection/gossip** — int32 fitness keys
  (feasibility-first, then cut; exact because the engine gates this path on
  integral weights), stateless hash jitter for every tie-break, and the
  offspring-never-worse-than-better-parent elitism step of the paper.

Islands optionally map onto ``shard_map`` shards (``launch.mesh``); the
per-epoch best-individual gossip then becomes an ``all_gather`` collective.
Island hashes are keyed on *global* island ids, so the sharded run is
bit-identical to the single-device run (and hence to the numpy oracle).

Shape bucketing: arrays carry a pow2 population bucket ``Sb`` (seed phase) /
``Ib`` (children) and the node arena ``Ab = 2^ceil(log2(n + 1))``; the live
``(I, P, n, k, num_chunks)`` are traced scalars, so one compiled executable
per bucket serves every V-cycle (counted by ``LPEngine``'s ``evo_compiles``
against ``evo_buckets``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..graph.csr import sort_by_keys, sort_values
from .evolutionary import (
    CELL_ROUNDS,
    COMBINE_PROB,
    GAIN_ROUNDS,
    INFEAS_PENALTY,
    MUTATE_FRAC,
    REPAIR_ROUNDS,
    TAG_CELL,
    TAG_CELL_GATE,
    TAG_GAIN,
    TAG_GAIN_GATE,
    TAG_GROW,
    TAG_MUT_FLIP,
    TAG_MUT_LBL,
    TAG_OP,
    TAG_P1,
    TAG_P2,
    TAG_REPAIR,
    TAG_SEEDKEY,
    TAG_SWEEP,
)
from .label_propagation import _hash_base, _hash_jitter, _hash_mix, _lp_sweep
from .metrics import block_weights_dense_jnp, cut_from_arcs_jnp

__all__ = ["evo_seed_step", "evo_generation_step", "make_generation_sharded"]

_NEG = -1e30
_IMAX = 2**31 - 1
_IMIN = -(2**31)


def _hash_unit(base, a, b):
    """Uniform-ish float32 in [0, 1) (twin of ``hash_unit_np``)."""
    h = _hash_mix(_hash_mix(base, a), b)
    return (h & jnp.uint32(0xFFFFFF)).astype(jnp.float32) / float(1 << 24)


def _hash_u32(base, a, b):
    """Raw uint32 stream (twin of ``hash_u32_np``)."""
    return _hash_mix(_hash_mix(base, a), b)


# --------------------------------------------------------------------------
# per-individual building blocks (all vmapped over the population axis;
# every op mirrors its numpy-oracle twin bit-for-bit)
# --------------------------------------------------------------------------


def _bw_dev(lab, nw, k, Kb):
    kio = jnp.arange(Kb, dtype=jnp.int32)
    bw = block_weights_dense_jnp(lab, nw, k, Kb)
    return bw, jnp.where(kio < k, bw, jnp.inf)


def _evaluate(lab, src, dst, ew, nw, k, Kb, Lmax):
    """int32 fitness key: cut + INFEAS_PENALTY if infeasible (oracle twin)."""
    kio = jnp.arange(Kb, dtype=jnp.int32)
    cut = cut_from_arcs_jnp(lab, src, dst, ew, integral=True)
    bw, _ = _bw_dev(lab, nw, k, Kb)
    bwmax = jnp.max(jnp.where(kio < k, bw, -jnp.inf))
    feas = bwmax <= Lmax + 1e-6
    return cut + jnp.where(feas, 0, INFEAS_PENALTY)


def _table_conn(table, lab, Kb):
    """``(Nt, Kb)`` node-block connectivity from the one-row-per-node
    neighbour table ``(nbr, wt)``: ``conn[v, b]`` sums the weights of ``v``'s
    slots whose neighbour holds label ``b``.  A gather of the label row and
    a compare-sum over the ``W`` slots, one reduce fusion: no scatter.
    Unassigned neighbours (-1) match no block; padding slots weigh 0.

    Every slot indexes a node below ``Nt``, so the gather reads the fresh
    ``Nt``-label slice, which the TPU compiler keeps in on-chip memory: on
    a v5e that gather costs a quarter of one from the loop-carried arena
    in HBM."""
    nbr, wt = table
    t = lab[: nbr.shape[0]][nbr]
    kio = jnp.arange(Kb, dtype=jnp.int32)
    return jnp.sum(
        jnp.where(t[:, :, None] == kio, wt[:, :, None], 0.0), axis=1
    )


def _greedy_one(s_idx, src, dst, ew, nw, deg_f, n, k, Kb, Lmax, seed, rounds,
                table=None):
    """Batched greedy growing, one individual (oracle: ``_greedy_grow_np``).

    ``rounds`` is the traced degree/diameter-proportional budget
    (``evolutionary.grow_rounds_bound``) — one executable still serves
    every coarsest graph in the bucket.  With a neighbour ``table`` each
    round reads its connectivity from the table and does its dense work on
    the table's ``Nt`` rows; without, from per-arc scatter-adds over the
    ``Ab`` arena.  Both give the same labels: every sum is of integers
    below 2**24.  Returns the labels and the rounds this individual ran."""
    Ab = nw.shape[0]
    iota = jnp.arange(Ab, dtype=jnp.int32)
    kio = jnp.arange(Kb, dtype=jnp.int32)
    unit = _hash_unit(_hash_base(seed, jnp.int32(0), TAG_SEEDKEY), iota, s_idx)
    skey = jnp.where(iota < n, unit * (deg_f + 1.0), -jnp.inf)
    order = sort_by_keys(-skey)
    rank = jnp.zeros((Ab,), jnp.int32).at[order].set(iota)
    lab0 = jnp.where((rank < k) & (iota < n), rank, jnp.int32(-1))
    R = Ab if table is None else table[0].shape[0]
    io, nwr = iota[:R], nw[:R]

    def grow_round(r, lab):
        lr = lab[:R]
        if table is None:
            tgt = lab[dst]
            mask = tgt >= 0
            conn = jnp.zeros((Ab, Kb), jnp.float32).at[
                src, jnp.where(mask, tgt, 0)
            ].add(jnp.where(mask, ew, 0.0))
            asg = lab >= 0
            bw = jnp.zeros((Kb,), jnp.float32).at[jnp.where(asg, lab, 0)].add(
                jnp.where(asg, nw, 0.0)
            )
        else:
            conn = _table_conn(table, lab, Kb)
            bw = jnp.sum(jnp.where(lr[:, None] == kio, nwr[:, None], 0.0),
                         axis=0)
        bwx = jnp.where(kio < k, bw, jnp.inf)
        base_r = _hash_u32(_hash_base(seed, r, TAG_GROW), s_idx, jnp.int32(0))
        jit = _hash_jitter(base_r, io[:, None], kio[None, :])
        fits = bwx[None, :] + nwr[:, None] <= Lmax
        elig = (conn > 0) & fits
        score = jnp.where(elig, conn + jit, _NEG)
        b = jnp.argmax(score, axis=1).astype(jnp.int32)
        has = jnp.max(score, axis=1) > _NEG / 2
        unas = (lr < 0) & (io < n)
        return lab.at[:R].set(jnp.where(unas & has, b, lr))

    # while_loop instead of a fixed fori: once every node is assigned — or a
    # round assigns nothing (a stalled frontier can never recover, since
    # assignments are the only state a round reads) — the remaining rounds
    # are no-ops by construction (the oracle early-exits on exactly these
    # conditions), so skipping them cannot change a label.  Under vmap the
    # loop runs until the slowest individual converges, with converged rows
    # riding along untouched; the stall exit is what keeps the
    # diameter-proportional budget from costing anything on disconnected
    # graphs.
    def _unas_count(lab):
        return jnp.sum(((lab < 0) & (iota < n)).astype(jnp.int32))

    def grow_cond(state):
        r, lab, prev = state
        cnt = _unas_count(lab)
        return (r < rounds) & (cnt > 0) & ((r == 0) | (cnt < prev))

    def grow_body(state):
        r, lab, prev = state
        return r + 1, grow_round(r, lab), _unas_count(lab)

    trips, lab, _ = lax.while_loop(
        grow_cond, grow_body, (jnp.int32(0), lab0, jnp.int32(_IMAX))
    )
    unas = (lab < 0) & (iota < n)
    pos = jnp.cumsum(unas.astype(jnp.int32)) - 1
    lab = jnp.where(unas, pos % k, lab)
    return jnp.where(iota < n, lab, k).astype(jnp.int32), trips


def _gain_round(src, dst, ew, nw, lab, n, k, Kb, Lmax, base_score, base_gate,
                table=None):
    """Synchronous best-gain round (oracle: ``repro.core.fm.gain_round_np``);
    connectivity from the neighbour ``table`` where there is one, as in
    :func:`_greedy_one`."""
    Ab = lab.shape[0]
    kio = jnp.arange(Kb, dtype=jnp.int32)
    if table is None:
        R = Ab
        conn = jnp.zeros((Ab, Kb), jnp.float32).at[src, lab[dst]].add(ew)
    else:
        R = table[0].shape[0]
        conn = _table_conn(table, lab, Kb)
    iota = jnp.arange(R, dtype=jnp.int32)
    lr = lab[:R]
    own = jnp.sum(jnp.where(kio == lr[:, None], conn, 0.0), axis=1)
    _, bwx = _bw_dev(lab, nw, k, Kb)
    jit = _hash_jitter(base_score, iota[:, None], kio[None, :])
    fits = bwx[None, :] + nw[:R, None] <= Lmax
    elig = fits & (kio[None, :] != lr[:, None]) & (conn > own[:, None])
    score = jnp.where(elig, conn + jit, _NEG)
    b = jnp.argmax(score, axis=1).astype(jnp.int32)
    has = jnp.max(score, axis=1) > _NEG / 2
    u = _hash_unit(base_gate, iota, jnp.int32(0))
    move = has & (u < 0.5) & (iota < n)
    return lab.at[:R].set(jnp.where(move, b, lr))


def _repair_rounds(src, dst, ew, nw, lab, ctx, phase, n, k, Kb, Lmax, seed):
    """Synchronous repair rounds (oracle: ``_repair_rounds_np``)."""
    del src, dst, ew
    Ab = lab.shape[0]
    iota = jnp.arange(Ab, dtype=jnp.int32)

    def rep_round(r, lab):
        _, bwx = _bw_dev(lab, nw, k, Kb)
        tgt = jnp.argmin(bwx).astype(jnp.int32)
        excess = jnp.clip((bwx - Lmax) / jnp.maximum(bwx, 1.0), 0.0, 1.0)
        base_r = _hash_u32(_hash_base(seed, phase, TAG_REPAIR), ctx, r)
        u = _hash_unit(base_r, iota, jnp.int32(0))
        over = bwx > Lmax
        movable = (
            (iota < n)
            & over[jnp.minimum(lab, k)]
            & (lab != tgt)
            & (bwx[tgt] + nw <= Lmax)
        )
        gate = u < 1.5 * excess[jnp.minimum(lab, k)]
        return jnp.where(movable & gate, tgt, lab)

    return lax.fori_loop(0, REPAIR_ROUNDS, rep_round, lab)


def _mutate_init(src, dst, nw, lab, i_ctx, gen, n, k, seed):
    """Boundary perturbation (oracle: ``_mutate_init_np``)."""
    Ab = lab.shape[0]
    iota = jnp.arange(Ab, dtype=jnp.int32)
    bnd = jnp.zeros((Ab,), bool).at[src].max(lab[src] != lab[dst])
    u = _hash_unit(
        _hash_u32(_hash_base(seed, gen + 1, TAG_MUT_FLIP), i_ctx, jnp.int32(0)),
        iota, jnp.int32(0),
    )
    newl = (
        _hash_u32(
            _hash_u32(_hash_base(seed, gen + 1, TAG_MUT_LBL), i_ctx,
                      jnp.int32(0)),
            iota, jnp.int32(0),
        ) % k.astype(jnp.uint32)
    ).astype(jnp.int32)
    flip = bnd & (u < MUTATE_FRAC) & (iota < n)
    return jnp.where(flip, newl, lab)


def _combine_init(src, dst, ew, nw, lab1, lab2, lab_better, i_ctx, gen, n, k,
                  Kb, Lmax, seed):
    """Overlay-cell combine (oracle: ``_combine_init_np``): packed-key
    relabel of the ``(P1(v), P2(v))`` cells, better-parent seeding, and
    CELL_ROUNDS synchronous cell-granular moves."""
    Ab = lab1.shape[0]
    iota = jnp.arange(Ab, dtype=jnp.int32)
    kio = jnp.arange(Kb, dtype=jnp.int32)
    ov = jnp.where(iota < n, lab1 * k + lab2, jnp.int32(_IMAX))
    sl = sort_values(ov)
    newrun = jnp.concatenate(
        [sl[:1] < _IMAX, (sl[1:] != sl[:-1]) & (sl[1:] < _IMAX)]
    )
    rank = (jnp.cumsum(newrun) - 1).astype(jnp.int32)
    posn = jnp.minimum(jnp.searchsorted(sl, ov), Ab - 1)
    cf = jnp.where(iota < n, rank[posn], jnp.int32(Ab - 1))
    blk_raw = jnp.full((Ab,), -1, jnp.int32).at[cf].max(
        jnp.where(iota < n, lab_better, jnp.int32(-1))
    )
    blk0 = jnp.where(blk_raw >= 0, blk_raw, k).astype(jnp.int32)
    cw = jnp.zeros((Ab,), jnp.float32).at[cf].add(nw)
    cu = cf[src]
    cv = cf[dst]
    mask = cu != cv
    blk = blk0
    for r in range(CELL_ROUNDS):
        bw = jnp.zeros((Kb,), jnp.float32).at[blk].add(cw)
        bwx = jnp.where(kio < k, bw, jnp.inf)
        conn = jnp.zeros((Ab, Kb), jnp.float32).at[cu, blk[cv]].add(
            jnp.where(mask, ew, 0.0)
        )
        own = jnp.take_along_axis(conn, jnp.minimum(blk, Kb - 1)[:, None], 1)[:, 0]
        jit = _hash_jitter(
            _hash_u32(_hash_base(seed, gen + 1, TAG_CELL), i_ctx, jnp.int32(r)),
            iota[:, None], kio[None, :],
        )
        fits = bwx[None, :] + cw[:, None] <= Lmax
        elig = fits & (kio[None, :] != blk[:, None]) & (conn > own[:, None])
        score = jnp.where(elig, conn + jit, _NEG)
        b = jnp.argmax(score, axis=1).astype(jnp.int32)
        has = jnp.take_along_axis(score, b[:, None], 1)[:, 0] > _NEG / 2
        u = _hash_unit(
            _hash_u32(_hash_base(seed, gen + 1, TAG_CELL_GATE), i_ctx,
                      jnp.int32(r)),
            iota, jnp.int32(0),
        )
        blk = jnp.where(has & (u < 0.5), b, blk)
    return jnp.where(iota < n, blk[cf], k).astype(jnp.int32)


def _refine_batch(pack, labs, ctxs, phase, src, dst, ew, nw, n, k, Kb, Lmax,
                  num_chunks, seed, refine_iters, table=None):
    """Batched refine: vmapped ``_lp_sweep`` + gain rounds + repair rounds.

    ``labs`` is ``(B, Ab)``; ``ctxs`` the per-row hash contexts (flat
    individual index in the seed phase, global island id in generations);
    ``phase`` 0 for seeding, ``gen + 1`` for generations (oracle twin:
    ``_refine_np``); ``table`` the neighbour table of the gain rounds, or
    None."""
    nodes, node_valid, edge_dst, edge_w, edge_src_slot, edge_valid = pack
    kio = jnp.arange(Kb, dtype=jnp.int32)
    sw = (
        _hash_u32(_hash_base(seed, phase, TAG_SWEEP), ctxs, jnp.int32(0))
        & jnp.uint32(0x7FFFFFFF)
    ).astype(jnp.int32)

    def bw_init(lab):
        bw = jnp.zeros((Kb,), jnp.float32).at[lab].add(nw)
        return jnp.where(kio < k, bw, jnp.inf)

    ws = jax.vmap(bw_init)(labs)

    def sweep_one(lab, w, sd):
        out, _, _ = _lp_sweep(
            nodes, node_valid, edge_dst, edge_w, edge_src_slot, edge_valid,
            lab, w, nw, jnp.zeros(1, jnp.int32),
            Lmax, sd, k, num_chunks,
            iters=refine_iters, refine_mode=True, use_restrict=False,
            permute_chunks=True,
        )
        return out

    with jax.named_scope("evo.sweep"):
        labs = jax.vmap(sweep_one)(labs, ws, sw)
    with jax.named_scope("evo.gain"):
        for r in range(GAIN_ROUNDS):
            base_s = _hash_u32(
                _hash_base(seed, phase, TAG_GAIN), ctxs, jnp.int32(r)
            )
            base_g = _hash_u32(
                _hash_base(seed, phase, TAG_GAIN_GATE), ctxs, jnp.int32(r)
            )
            labs = jax.vmap(
                lambda lab, bs, bg: _gain_round(
                    src, dst, ew, nw, lab, n, k, Kb, Lmax, bs, bg, table
                )
            )(labs, base_s, base_g)
    with jax.named_scope("evo.repair"):
        labs = jax.vmap(
            lambda lab, ctx: _repair_rounds(
                src, dst, ew, nw, lab, ctx, phase, n, k, Kb, Lmax, seed
            )
        )(labs, ctxs)
    return labs


def _worst_slots(keys, I, P, Sb):
    """Per-island replacement victim: max key, first member (oracle twin of
    ``_worst_member_np``).  Returns flat slot ids, valid for islands < I."""
    iota_s = jnp.arange(Sb, dtype=jnp.int32)
    isl = iota_s // P
    valid = iota_s < I * P
    seg = jnp.where(valid, isl, Sb)
    wk = jnp.full((Sb,), _IMIN, jnp.int32).at[seg].max(keys, mode="drop")
    member = iota_s - isl * P
    is_worst = valid & (keys == wk[jnp.minimum(isl, Sb - 1)])
    wmem = jnp.full((Sb,), _IMAX, jnp.int32).at[seg].min(
        jnp.where(is_worst, member, _IMAX), mode="drop"
    )
    return wk, wmem


# --------------------------------------------------------------------------
# jitted phase entry points
# --------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("refine_iters", "Kb"))
def evo_seed_step(
    nodes, node_valid, edge_dst, edge_w, edge_src_slot, edge_valid,
    seed_labels,        # (Sb, Ab) int32 — V-cycle seed rows; fill k elsewhere
    seed_mask,          # (Sb,) bool — rows taken verbatim from seed_labels
    src, dst, ew,       # arc arrays (zero-weight padding allowed)
    nw,                 # (Ab,) f32, 0 beyond n
    deg_f,              # (Ab,) f32 degrees, 0 beyond n
    Lmax,               # scalar f32
    seed,               # scalar int32
    I, P, n, k, num_chunks, grow_rounds,   # traced scalars
    table=None,         # (nbr (Nt, W) int32, wt (Nt, W) f32) or None
    *,
    refine_iters: int,
    Kb: int,
):
    """Build + evaluate the initial population: batched greedy growing for
    unseeded rows (``grow_rounds`` frontier-round budget — traced, computed
    by ``evolutionary.grow_rounds_bound``), verbatim seed rows (the
    V-cycle's projected solution), batched refine, int32 fitness keys.  ONE
    executable per ``(pack bucket, Sb, Ab, Kb, table shape)``; the grow and
    gain rounds read the neighbour ``table`` where one is given.  Its
    phases are named scopes ``evo.grow``, ``evo.sweep``, ``evo.gain``,
    ``evo.repair`` and ``evo.evaluate`` in the device trace.

    Returns ``(labels, keys, grow_trips)``: the last is the number of trips
    the device made through the grow loop, the max over all ``Sb`` rows of
    the rounds each ran (under ``vmap`` the loop runs until the slowest
    row stops)."""
    Sb, Ab = seed_labels.shape
    iota_s = jnp.arange(Sb, dtype=jnp.int32)
    valid_s = iota_s < I * P
    pack = (nodes, node_valid, edge_dst, edge_w, edge_src_slot, edge_valid)
    with jax.named_scope("evo.grow"):
        grown, trips = jax.vmap(
            lambda s: _greedy_one(
                s, src, dst, ew, nw, deg_f, n, k, Kb, Lmax, seed, grow_rounds,
                table,
            )
        )(iota_s)
    refined = _refine_batch(
        pack, grown, iota_s, jnp.int32(0), src, dst, ew, nw, n, k, Kb, Lmax,
        num_chunks, seed, refine_iters, table,
    )
    labs = jnp.where(seed_mask[:, None], seed_labels, refined)
    with jax.named_scope("evo.evaluate"):
        keys = jax.vmap(
            lambda lab: _evaluate(lab, src, dst, ew, nw, k, Kb, Lmax)
        )(labs)
    keys = jnp.where(valid_s, keys, jnp.int32(_IMAX))
    return labs, keys, jnp.max(trips)


def _generation_core(
    pack, labs, keys, src, dst, ew, nw, Lmax, seed, gen, island_offset,
    I, P, n, k, num_chunks, Kb: int, Ib: int, refine_iters: int,
    axis_name=None, table=None,
):
    """One generation: selection, combine/mutate, batched refine, elitism,
    replacement, gossip.  Shared by the single-device jit and the
    ``shard_map`` island wrapper (``axis_name`` set -> gossip is an
    ``all_gather`` collective over the island axis)."""
    Sb, Ab = labs.shape
    iota_s = jnp.arange(Sb, dtype=jnp.int32)
    valid_s = iota_s < I * P
    i_io = jnp.arange(Ib, dtype=jnp.int32)
    valid_i = i_io < I
    i_ctx = i_io + island_offset

    # ---- selection (stateless hash draws, global island ids) ----
    u_op = _hash_unit(_hash_base(seed, gen + 1, TAG_OP), i_ctx, jnp.int32(0))
    r1 = (
        _hash_u32(_hash_base(seed, gen + 1, TAG_P1), i_ctx, jnp.int32(0))
        % P.astype(jnp.uint32)
    ).astype(jnp.int32)
    off = 1 + (
        _hash_u32(_hash_base(seed, gen + 1, TAG_P2), i_ctx, jnp.int32(0))
        % jnp.maximum(P - 1, 1).astype(jnp.uint32)
    ).astype(jnp.int32)
    r2 = (r1 + off) % P
    do_combine = (P >= 2) & (u_op < COMBINE_PROB)
    p1 = jnp.minimum(i_io * P + r1, Sb - 1)
    p2 = jnp.minimum(i_io * P + r2, Sb - 1)
    k1 = keys[p1]
    k2 = keys[p2]
    better = jnp.where(k1 <= k2, p1, p2)
    base_flat = jnp.where(do_combine, better, p1)

    lab_p1 = labs[p1]
    lab_p2 = labs[p2]
    lab_base = labs[base_flat]

    comb = jax.vmap(
        lambda l1, l2, lb, ic: _combine_init(
            src, dst, ew, nw, l1, l2, lb, ic, gen, n, k, Kb, Lmax, seed
        )
    )(lab_p1, lab_p2, lab_base, i_ctx)
    mut = jax.vmap(
        lambda lb, ic: _mutate_init(src, dst, nw, lb, ic, gen, n, k, seed)
    )(lab_base, i_ctx)
    init = jnp.where(do_combine[:, None], comb, mut)

    children = _refine_batch(
        pack, init, i_ctx, gen + 1, src, dst, ew, nw, n, k, Kb, Lmax,
        num_chunks, seed, refine_iters, table,
    )
    ckeys = jax.vmap(
        lambda lab: _evaluate(lab, src, dst, ew, nw, k, Kb, Lmax)
    )(children)

    # ---- elitism: offspring never worse than its baseline ----
    bkeys_par = keys[base_flat]
    keep = ckeys <= bkeys_par
    children = jnp.where(keep[:, None], children, lab_base)
    ckeys = jnp.where(keep, ckeys, bkeys_par)

    # ---- synchronous replacement of each island's worst ----
    wk, wmem = _worst_slots(keys, I, P, Sb)
    wflat = jnp.minimum(i_io * P + wmem[jnp.minimum(i_io, Sb - 1)], Sb - 1)
    cond = valid_i & (ckeys <= keys[wflat])
    tgt = jnp.where(cond, wflat, Sb)
    labs = labs.at[tgt].set(children, mode="drop")
    keys = keys.at[tgt].set(ckeys, mode="drop")

    # ---- gossip: global best replaces each island's worst ----
    bkey = jnp.min(jnp.where(valid_s, keys, _IMAX))
    bidx = jnp.min(jnp.where(valid_s & (keys == bkey), iota_s, _IMAX))
    blab = labs[jnp.minimum(bidx, Sb - 1)]
    if axis_name is not None:
        bkeys_g = lax.all_gather(bkey, axis_name)          # (D,)
        blabs_g = lax.all_gather(blab, axis_name)          # (D, Ab)
        gmin = jnp.min(bkeys_g)
        d = jnp.min(
            jnp.where(bkeys_g == gmin, jnp.arange(bkeys_g.shape[0]),
                      bkeys_g.shape[0])
        )
        bkey = gmin
        blab = blabs_g[jnp.minimum(d, bkeys_g.shape[0] - 1)]
    wk2, wmem2 = _worst_slots(keys, I, P, Sb)
    wflat2 = jnp.minimum(i_io * P + wmem2[jnp.minimum(i_io, Sb - 1)], Sb - 1)
    cond2 = valid_i & (bkey < keys[wflat2])
    tgt2 = jnp.where(cond2, wflat2, Sb)
    labs = labs.at[tgt2].set(
        jnp.broadcast_to(blab, (Ib, labs.shape[1])), mode="drop"
    )
    keys = keys.at[tgt2].set(jnp.broadcast_to(bkey, (Ib,)), mode="drop")
    return labs, keys


@functools.partial(jax.jit, static_argnames=("refine_iters", "Kb", "Ib"))
def evo_generation_step(
    nodes, node_valid, edge_dst, edge_w, edge_src_slot, edge_valid,
    labs, keys,
    src, dst, ew, nw,
    Lmax, seed, gen, island_offset,
    I, P, n, k, num_chunks,
    table=None,
    *,
    refine_iters: int,
    Kb: int,
    Ib: int,
):
    """One generation as ONE executable per (pack bucket, Sb, Ab, Ib, Kb,
    table shape)."""
    pack = (nodes, node_valid, edge_dst, edge_w, edge_src_slot, edge_valid)
    return _generation_core(
        pack, labs, keys, src, dst, ew, nw, Lmax, seed, gen, island_offset,
        I, P, n, k, num_chunks, Kb, Ib, refine_iters, table=table,
    )


def make_generation_sharded(mesh, refine_iters: int, Kb: int, Ib: int):
    """Build the shard_mapped generation step: state carries a leading
    ``(D,)`` island-shard axis, gossip runs as an ``all_gather`` collective.
    Hash contexts use global island ids via the sharded ``island_offset``
    column, so results are bit-identical to the single-device step."""
    from jax.sharding import PartitionSpec as PS

    def step(pack_and_state):
        (nodes, node_valid, edge_dst, edge_w, edge_src_slot, edge_valid,
         labs, keys, src, dst, ew, nw, Lmax, seed, gen, island_offset,
         I_loc, P, n, k, num_chunks, table) = pack_and_state
        pack = (nodes, node_valid, edge_dst, edge_w, edge_src_slot, edge_valid)
        labs, keys = _generation_core(
            pack, labs[0], keys[0], src, dst, ew, nw, Lmax, seed, gen,
            island_offset[0, 0], I_loc, P, n, k, num_chunks,
            Kb, Ib, refine_iters, axis_name="island", table=table,
        )
        return labs[None], keys[None]

    rep = PS()
    spec_in = (
        rep, rep, rep, rep, rep, rep,                   # pack (replicated)
        PS("island"), PS("island"),                     # labs, keys
        rep, rep, rep, rep,                             # arc arrays + nw
        rep, rep, rep, PS("island"),                    # Lmax, seed, gen, off
        rep, rep, rep, rep, rep,                        # I_loc, P, n, k, chunks
        rep,                                            # neighbour table
    )
    sharded = jax.shard_map(
        lambda *a: step(a), mesh=mesh,
        in_specs=spec_in, out_specs=(PS("island"), PS("island")),
        check_vma=False,
    )
    return jax.jit(sharded)
