"""Size-constrained label propagation (SCLaP) — the paper's core algorithm.

Two modes, exactly as in the paper (§III-A):

* ``cluster`` — coarsening clustering.  Labels live in ``[0, n)`` (initially
  each node is its own cluster), the size bound is ``U = max(max_v c(v),
  L_max / f)`` and the constraint is *soft*.  Traversal order: increasing
  node degree (paper's ordering that improves quality *and* time).
* ``refine``  — local search during uncoarsening.  Labels live in ``[0, k)``,
  the bound is the partitioning problem's own ``U = L_max`` and nodes in an
  *overloaded* block must leave it (their own block is excluded from the
  argmax).  Traversal order: random.

TPU adaptation (DESIGN.md §2): the sequential sweep becomes a
*chunked-sequential* sweep.  Nodes are host-packed into fixed-shape chunks;
a ``lax.fori_loop`` walks chunks sequentially and moves all nodes of a chunk
synchronously.  The per-chunk "strongest eligible cluster" reduction is
sort-based (a single argsort on the fused key ``slot * A + cand`` + run
segmentation) instead of the paper's linear-probing hash tables — hashing is
hostile to TPUs, sorting is native.  Tie-breaking is random via sub-0.5
jitter (valid because all cluster-connection weights are integral for
integer-weight inputs).

The same kernel serves the V-cycle restriction (§IV-D): when ``restrict`` is
given, a node may only join clusters inside its own restriction cell, so cut
edges of the input partition are never contracted.

Shape-bucketing contract (PR 1, consumed by ``repro.core.engine.LPEngine``):
``_lp_sweep`` is written so that one compiled executable serves *every*
level of a multilevel hierarchy once the inputs are padded to a common
bucket shape:

* the label universe size ``num_labels`` and the live chunk count
  ``num_chunks`` are **traced** scalars, not static — padded chunks beyond
  ``num_chunks`` are simply never visited, and label/weight arrays are
  arena-sized (``A >= n + 1``) with +inf weight sentinels above
  ``num_labels``;
* the tie-break jitter is a stateless integer hash of
  ``(seed, iteration, chunk, node slot, candidate label)`` rather than a
  draw from a shape-``(E,)`` PRNG stream, so padding the edge axis cannot
  change any move decision — bucketed and exact-shape packs produce
  *bit-identical* labels (tested in tests/test_engine.py);
* refinement sweeps re-randomize the traversal *per call* (per level, per
  V-cycle) by permuting the chunk visit order **on device** (same hash
  family), which is what lets V-cycles 2..N reuse the packs built in cycle 1
  instead of repacking.  The order is deliberately held fixed across the
  iterations of one call: chunked-synchronous LP needs a stationary visit
  order to damp oscillation (re-shuffling every iteration was measured to
  blow up the cut on the mesh bisection task).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..graph.csr import GraphNP, sort_by_keys
from ..graph.packing import ChunkPack, pack_chunks

__all__ = [
    "LPResult",
    "lp_cluster",
    "lp_refine",
    "make_order",
    "sclap_numpy",
    "hash_mix_np",
    "hash_base_u32",
    "hash_jitter_np",
    "hash_unit_np",
    "hash_u32_np",
    "sweep_refine_numpy",
]

_NEG = -1e30


@dataclass
class LPResult:
    labels: np.ndarray   # (n,) final labels
    moves: int           # total number of node moves
    iters: int


def make_order(g: GraphNP, mode: str, seed: int) -> np.ndarray:
    """Traversal order: 'degree' (coarsening) or 'random' (refinement)."""
    rng = np.random.default_rng(seed)
    if mode == "degree":
        # increasing degree, random within equal degrees (paper §III-A)
        return np.argsort(g.degrees() + rng.random(g.n), kind="stable").astype(np.int64)
    return rng.permutation(g.n).astype(np.int64)


# --------------------------------------------------------------------------
# jitted chunk sweep
# --------------------------------------------------------------------------


def _hash_mix(h, x):
    """One round of a murmur-style integer mixer (uint32, wrap-around mul)."""
    h = (h ^ x.astype(jnp.uint32)) * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 15)


def _hash_jitter(base, a, b):
    """Stateless tie-break jitter in [0, 0.49) from integer coordinates.

    Unlike a ``jax.random.uniform(key, (E,))`` draw, the value of each
    element depends only on ``(base, a[i], b[i])`` — never on the array
    *shape* — so padding the edge axis to a bucket size cannot perturb any
    tie-break (the parity guarantee of the bucketed engine).
    """
    h = _hash_mix(_hash_mix(base, a), b)
    return (h & jnp.uint32(0xFFFFFF)).astype(jnp.float32) / float(1 << 24) * 0.49


def _hash_base(seed, it, extra):
    s = (
        seed.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
        + it.astype(jnp.uint32) * jnp.uint32(0x85EBCA77)
        + jnp.uint32(extra) * jnp.uint32(0x27D4EB2F)
    )
    return _hash_mix(jnp.uint32(0x165667B1), s)


@functools.partial(
    jax.jit,
    static_argnames=("iters", "refine_mode", "use_restrict", "permute_chunks"),
)
def _lp_sweep(
    nodes,          # (C, N) int32, padded with n
    node_valid,     # (C, N) bool
    edge_dst,       # (C, E) int32, padded with n
    edge_w,         # (C, E) f32
    edge_src_slot,  # (C, E) int32
    edge_valid,     # (C, E) bool
    labels,         # (A,) int32 arena, A >= n + 1; slots >= n are unused
    weights,        # (W,) f32 cluster/block weights; slots >= num_labels +inf
    nw_ext,         # (A,) f32 node weights; slots >= n hold 0
    restrict,       # (A,) int32 or (1,) dummy
    U,              # scalar f32
    seed,           # scalar int32 — drives the stateless tie-break hash
    num_labels,     # traced scalar int32 — T: n in cluster mode, k in refine
    num_chunks,     # traced scalar int32 — live chunks; <= C (rest is pad)
    *,
    iters: int,
    refine_mode: bool,
    use_restrict: bool,
    permute_chunks: bool,
):
    C, N = nodes.shape
    E = edge_dst.shape[1]
    A = labels.shape[0]
    sent_lbl = num_labels.astype(jnp.int32)  # padded-weight slot (holds +inf)

    def chunk_step_for(it, perm):
        def chunk_step(c, carry):
            labels, weights, moves = carry
            cc = perm[c]
            nd = nodes[cc]
            ndv = node_valid[cc]
            dst = edge_dst[cc]
            w0 = edge_w[cc]
            slot = edge_src_slot[cc]
            ev = edge_valid[cc]

            lbl_d = labels[dst]                      # candidate label per arc
            src_node = nd[slot]
            if use_restrict:
                ok = ev & (restrict[dst] == restrict[src_node])
            else:
                ok = ev
            cand = jnp.where(ok, lbl_d, sent_lbl).astype(jnp.int32)
            wv = jnp.where(ok, w0, 0.0)

            # ---- sort-based (node, label) run reduction -------------------
            # Packing emits each chunk's arcs grouped by source slot (see
            # graph/packing.py), so the fused key `slot * A + cand` both
            # orders runs correctly and keeps the sort a *single* key pass
            # instead of the two passes of lexsort((cand, slot)).  cand is
            # always <= num_labels < A, so the key is collision-free; the
            # int32 fast path is valid whenever N * A fits in 31 bits.
            if N * A < 2**31:
                perm_e = sort_by_keys(slot * jnp.int32(A) + cand)
            else:
                perm_e = sort_by_keys(slot, cand)
            s_slot = slot[perm_e]
            s_lbl = cand[perm_e]
            s_w = wv[perm_e]
            new_run = jnp.concatenate(
                [
                    jnp.ones((1,), bool),
                    (s_slot[1:] != s_slot[:-1]) | (s_lbl[1:] != s_lbl[:-1]),
                ]
            )
            run_id = jnp.cumsum(new_run) - 1          # (E,) in [0, E)
            run_w = jnp.zeros((E,), jnp.float32).at[run_id].add(s_w)
            run_slot = jnp.full((E,), N, jnp.int32).at[run_id].set(s_slot)
            run_lbl = jnp.full((E,), sent_lbl, jnp.int32).at[run_id].set(s_lbl)

            # ---- eligibility + scoring -----------------------------------
            own = labels[nd]                          # (N,)
            own_r = own[jnp.minimum(run_slot, N - 1)]
            node_w_r = nw_ext[nd[jnp.minimum(run_slot, N - 1)]]
            cand_w = weights[jnp.minimum(run_lbl, num_labels)]
            fits = cand_w + node_w_r <= U
            if refine_mode:
                own_w = weights[jnp.minimum(own, num_labels)]
                overloaded = own_w[jnp.minimum(run_slot, N - 1)] > U
                eligible = jnp.where(
                    overloaded,
                    fits & (run_lbl != own_r),                     # must leave
                    (run_w > 0) & (fits | (run_lbl == own_r)),
                )
            else:
                eligible = (run_w > 0) & (fits | (run_lbl == own_r))
            eligible &= run_slot < N
            base = _hash_base(seed, it, 0x51ED2701) + cc.astype(jnp.uint32)
            jitter = _hash_jitter(base, run_slot, run_lbl)
            score = jnp.where(eligible, run_w + jitter, _NEG)

            # ---- per-node argmax over runs --------------------------------
            seg = jnp.minimum(run_slot, N)            # runs of padded slots -> N
            best = jnp.full((N + 1,), _NEG, jnp.float32).at[seg].max(score)
            is_best = (score >= best[seg]) & (score > _NEG / 2)
            win = (
                jnp.full((N + 1,), sent_lbl, jnp.int32)
                .at[seg]
                .min(jnp.where(is_best, run_lbl, sent_lbl))
            )[:N]
            new_lbl = jnp.where(ndv & (win < sent_lbl), win, own)

            moved = ndv & (new_lbl != own)
            nwv = nw_ext[nd]
            if refine_mode:
                # Influx gating: every node of a chunk sees the same stale
                # block weights, so a chunk can pile far more weight into a
                # block than its headroom — overshooting U and triggering a
                # synchronous "must leave" stampede out of the now-overloaded
                # block (measured: sustained oscillation at ~chunk-size moves
                # per iteration under unlucky visit orders).  Cap each
                # block's *net* inflow at its headroom in expectation:
                # accept an incoming mover with probability
                # clip((U - w + outflow) / inflow, 0, 1).  Swap-heavy
                # refinement (inflow ~ outflow) passes through untouched.
                mv_w = jnp.where(moved, nwv, 0.0)
                tgt_i = jnp.where(moved, new_lbl, num_labels)
                src_i = jnp.where(moved, own, num_labels)
                zero_w = jnp.zeros(weights.shape, jnp.float32)
                inflow = zero_w.at[tgt_i].add(mv_w, mode="drop")
                outflow = zero_w.at[src_i].add(mv_w, mode="drop")
                head = U - weights + outflow
                p_in = jnp.clip(head / jnp.maximum(inflow, 1e-9), 0.0, 1.0)
                gate_u = _hash_jitter(
                    _hash_base(seed, it, 0x2545F491) + cc.astype(jnp.uint32),
                    nd, new_lbl,
                ) / 0.49
                moved &= gate_u < p_in[jnp.minimum(new_lbl, num_labels)]
                new_lbl = jnp.where(moved, new_lbl, own)
            labels = labels.at[nd].set(jnp.where(ndv, new_lbl, own), mode="drop")
            weights = weights.at[jnp.where(moved, own, num_labels)].add(
                jnp.where(moved, -nwv, 0.0), mode="drop"
            )
            weights = weights.at[jnp.where(moved, new_lbl, num_labels)].add(
                jnp.where(moved, nwv, 0.0), mode="drop"
            )
            # keep the sentinel weight slot at +inf (the adds above target it
            # with value 0 for unmoved nodes; re-pin to be safe)
            weights = weights.at[num_labels].set(jnp.inf)
            moves = moves + jnp.sum(moved)
            return labels, weights, moves

        return chunk_step

    if permute_chunks:
        # Device-side traversal re-randomization: pseudo-random visit order
        # over the *live* chunks, padded chunks sorted last (and never
        # visited — the loop stops at num_chunks).  Hash-based, so
        # independent of the padded chunk-axis size.  The order is fixed for
        # the whole call (it varies with the per-call seed, i.e. per level
        # and per V-cycle): re-shuffling every iteration was measured to
        # *prevent* convergence — chunked-synchronous LP relies on a
        # stationary visit order to damp oscillation, exactly like the
        # sequential oracle converges under any fixed sweep order.
        hc = _hash_mix(
            _hash_base(seed, jnp.int32(0), 0x7F4A7C15),
            jnp.arange(C, dtype=jnp.int32),
        ).astype(jnp.float32)
        hc = hc + jnp.where(jnp.arange(C) >= num_chunks, jnp.float32(1e10), 0.0)
        perm = jnp.argsort(hc).astype(jnp.int32)
    else:
        perm = jnp.arange(C, dtype=jnp.int32)

    def iter_step(it, carry):
        return jax.lax.fori_loop(0, num_chunks, chunk_step_for(it, perm), carry)

    labels, weights, moves = jax.lax.fori_loop(
        0, iters, iter_step, (labels, weights, jnp.zeros((), jnp.int32))
    )
    return labels, weights, moves


# --------------------------------------------------------------------------
# host wrappers
# --------------------------------------------------------------------------


def _ext(arr: np.ndarray, fill) -> np.ndarray:
    return np.concatenate([arr, np.array([fill], dtype=arr.dtype)])


def lp_cluster(
    g: GraphNP,
    U: float,
    iters: int = 3,
    seed: int = 0,
    restrict: Optional[np.ndarray] = None,
    pack: Optional[ChunkPack] = None,
    max_nodes: int = 4096,
    max_edges: int = 65536,
    order: str = "degree",
) -> LPResult:
    """Size-constrained LP *clustering* (coarsening phase)."""
    n = g.n
    if pack is None:
        pack = pack_chunks(
            g, make_order(g, order, seed), max_nodes=max_nodes, max_edges=max_edges
        )
    labels0 = np.arange(n + 1, dtype=np.int32)
    weights0 = _ext(g.nw.astype(np.float32), np.float32(np.inf))
    nw_ext = _ext(g.nw.astype(np.float32), np.float32(0.0))
    if restrict is not None:
        r = _ext(restrict.astype(np.int32), np.int32(-1))
    else:
        r = np.zeros(1, np.int32)  # dummy
    labels, _, moves = _lp_sweep(
        jnp.asarray(pack.nodes),
        jnp.asarray(pack.node_valid),
        jnp.asarray(pack.edge_dst),
        jnp.asarray(pack.edge_w),
        jnp.asarray(pack.edge_src_slot),
        jnp.asarray(pack.edge_valid),
        jnp.asarray(labels0),
        jnp.asarray(weights0),
        jnp.asarray(nw_ext),
        jnp.asarray(r),
        jnp.float32(U),
        jnp.int32(seed & 0x7FFFFFFF),
        jnp.int32(n),
        jnp.int32(pack.num_chunks),
        iters=iters,
        refine_mode=False,
        use_restrict=restrict is not None,
        permute_chunks=False,
    )
    return LPResult(labels=np.asarray(labels[:n]), moves=int(moves), iters=iters)


def lp_refine(
    g: GraphNP,
    labels_in: np.ndarray,
    k: int,
    U: float,
    iters: int = 6,
    seed: int = 0,
    pack: Optional[ChunkPack] = None,
    max_nodes: int = 4096,
    max_edges: int = 65536,
    order: str = "random",
) -> LPResult:
    """Size-constrained LP as *local search* (uncoarsening phase)."""
    n = g.n
    if pack is None:
        pack = pack_chunks(
            g, make_order(g, order, seed), max_nodes=max_nodes, max_edges=max_edges
        )
    labels0 = _ext(labels_in.astype(np.int32), np.int32(k))
    bw = np.bincount(labels_in, weights=g.nw, minlength=k)[:k].astype(np.float32)
    weights0 = _ext(bw, np.float32(np.inf))
    nw_ext = _ext(g.nw.astype(np.float32), np.float32(0.0))
    labels, _, moves = _lp_sweep(
        jnp.asarray(pack.nodes),
        jnp.asarray(pack.node_valid),
        jnp.asarray(pack.edge_dst),
        jnp.asarray(pack.edge_w),
        jnp.asarray(pack.edge_src_slot),
        jnp.asarray(pack.edge_valid),
        jnp.asarray(labels0),
        jnp.asarray(weights0),
        jnp.asarray(nw_ext),
        jnp.zeros(1, jnp.int32),
        jnp.float32(U),
        jnp.int32(seed & 0x7FFFFFFF),
        jnp.int32(k),
        jnp.int32(pack.num_chunks),
        iters=iters,
        refine_mode=True,
        use_restrict=False,
        permute_chunks=False,
    )
    return LPResult(labels=np.asarray(labels[:n]), moves=int(moves), iters=iters)


# --------------------------------------------------------------------------
# numpy mirrors of the device hash family (bit-exact)
#
# The batched evolutionary engine's parity oracle (repro.core.evolutionary)
# re-derives every tie-break and gate on host, so the uint32 mixer above
# needs exact numpy twins.  Scalar mixing runs in python ints masked to 32
# bits (numpy SCALAR uint32 overflow warns; python ints don't); array mixing
# runs on uint32 ndarrays, whose overflow wraps silently.  All float steps
# are forced to float32 so IEEE results match XLA bit-for-bit.
# --------------------------------------------------------------------------

_M32 = 0xFFFFFFFF


def hash_u32_scalar(h: int, x: int) -> int:
    """Scalar twin of ``_hash_mix`` (python ints, wrap-around 32-bit)."""
    h = ((h ^ (x & _M32)) * 0xC2B2AE35) & _M32
    return h ^ (h >> 15)


def hash_base_u32(seed: int, it: int, extra: int) -> int:
    """Scalar twin of ``_hash_base``; returns a python int in [0, 2^32)."""
    s = (
        (seed & _M32) * 0x9E3779B1
        + (it & _M32) * 0x85EBCA77
        + (extra & _M32) * 0x27D4EB2F
    ) & _M32
    return hash_u32_scalar(0x165667B1, s)


def hash_mix_np(h, x):
    """Array twin of ``_hash_mix``: h is a python int or uint32 array."""
    xa = np.asarray(x)
    if isinstance(h, (int, np.integer)) and xa.ndim == 0:
        return np.uint32(hash_u32_scalar(int(h) & _M32, int(xa)))
    if isinstance(h, (int, np.integer)):
        h = np.uint32(h & _M32)
    h = (h ^ xa.astype(np.uint32)) * np.uint32(0xC2B2AE35)
    return h ^ (h >> np.uint32(15))


def hash_jitter_np(base, a, b) -> np.ndarray:
    """Array twin of ``_hash_jitter``: float32 jitter in [0, 0.49)."""
    h = hash_mix_np(hash_mix_np(base, a), b)
    return (
        (h & np.uint32(0xFFFFFF)).astype(np.float32)
        / np.float32(1 << 24)
        * np.float32(0.49)
    )


def hash_unit_np(base, a, b) -> np.ndarray:
    """Uniform-ish float32 in [0, 1) from integer coordinates (array twin of
    the device ``_hash_unit`` in repro.core.evo_device)."""
    h = hash_mix_np(hash_mix_np(base, a), b)
    return (h & np.uint32(0xFFFFFF)).astype(np.float32) / np.float32(1 << 24)


def hash_u32_np(base, a, b) -> np.ndarray:
    """Raw uint32 stream from integer coordinates (array twin of
    ``_hash_u32``)."""
    return hash_mix_np(hash_mix_np(base, a), b)


def sweep_refine_numpy(
    nodes: np.ndarray,          # (C, N) int32 pack layout (padded, sentinel n)
    node_valid: np.ndarray,     # (C, N) bool
    edge_dst: np.ndarray,       # (C, E) int32
    edge_w: np.ndarray,         # (C, E) float32
    edge_src_slot: np.ndarray,  # (C, E) int32
    edge_valid: np.ndarray,     # (C, E) bool
    labels: np.ndarray,         # (A,) int32, A >= n + 1; k beyond n
    weights: np.ndarray,        # (W,) float32 block weights; +inf at slots >= k
    nw_ext: np.ndarray,         # (A,) float32 node weights, 0 beyond n
    U: float,
    seed: int,
    num_labels: int,            # k
    num_chunks: int,
    iters: int,
) -> tuple:
    """Bit-exact numpy mirror of ``_lp_sweep(refine_mode=True,
    use_restrict=False, permute_chunks=True)``.

    This is the parity oracle the batched evolutionary engine refines
    against: same chunk visit permutation, same (slot, label) run sums, same
    stateless tie-break jitter, same influx gating, same weight updates.
    Bit-identity holds for integral node/edge weights (float32 sums are then
    exact in any order — the same precondition the device path is gated on);
    see tests/test_evo_device.py.  Returns ``(labels, weights)`` copies.
    """
    C, N = nodes.shape
    labels = labels.astype(np.int32).copy()
    weights = weights.astype(np.float32).copy()
    U = np.float32(U)
    k = int(num_labels)
    NEG = np.float32(_NEG)
    # device-side chunk visit permutation (uint32 hash -> f32, stable sort)
    hc = hash_mix_np(
        hash_base_u32(seed, 0, 0x7F4A7C15), np.arange(C, dtype=np.int32)
    ).astype(np.float32)
    hc = hc + np.where(
        np.arange(C) >= num_chunks, np.float32(1e10), np.float32(0.0)
    )
    perm = np.argsort(hc, kind="stable")
    for it in range(iters):
        base1 = hash_base_u32(seed, it, 0x51ED2701)
        base2 = hash_base_u32(seed, it, 0x2545F491)
        for ci in range(num_chunks):
            cc = int(perm[ci])
            nd = nodes[cc]
            ndv = node_valid[cc]
            ev = edge_valid[cc]
            dst = edge_dst[cc][ev]
            w0 = edge_w[cc][ev].astype(np.float32)
            slot = edge_src_slot[cc][ev]
            cand = labels[dst].astype(np.int64)
            # ---- (slot, label) run reduction (order-independent: integral
            # weights make the float32 segment sums exact) ----
            key = slot.astype(np.int64) * np.int64(k + 1) + cand
            uniq, inv = np.unique(key, return_inverse=True)
            run_w = np.zeros(uniq.shape[0], np.float32)
            np.add.at(run_w, inv, w0)
            run_slot = (uniq // (k + 1)).astype(np.int32)
            run_lbl = (uniq % (k + 1)).astype(np.int32)
            # ---- eligibility + scoring (mirror of the device rules) ----
            own = labels[nd]                       # (N,) label k at sentinels
            own_r = own[run_slot]
            node_w_r = nw_ext[nd[run_slot]]
            cand_w = weights[np.minimum(run_lbl, k)]
            fits = cand_w + node_w_r <= U
            overloaded = weights[np.minimum(own_r, k)] > U
            eligible = np.where(
                overloaded,
                fits & (run_lbl != own_r),
                (run_w > 0) & (fits | (run_lbl == own_r)),
            )
            base_c = (base1 + cc) & _M32
            jitter = hash_jitter_np(base_c, run_slot, run_lbl)
            score = np.where(eligible, run_w + jitter, NEG)
            # ---- per-node argmax with min-label tie-break ----
            best = np.full(N + 1, NEG, np.float32)
            np.maximum.at(best, run_slot, score)
            is_best = (score >= best[run_slot]) & (score > NEG / 2)
            win = np.full(N + 1, k, np.int32)
            np.minimum.at(
                win, run_slot, np.where(is_best, run_lbl, np.int32(k))
            )
            win = win[:N]
            new_lbl = np.where(ndv & (win < k), win, own).astype(np.int32)
            moved = ndv & (new_lbl != own)
            nwv = nw_ext[nd]
            # ---- influx gating (same expectation cap as the device) ----
            mv_w = np.where(moved, nwv, np.float32(0.0)).astype(np.float32)
            inflow = np.zeros(weights.shape[0], np.float32)
            outflow = np.zeros(weights.shape[0], np.float32)
            np.add.at(inflow, np.where(moved, new_lbl, k), mv_w)
            np.add.at(outflow, np.where(moved, own, k), mv_w)
            head = (U - weights + outflow).astype(np.float32)
            with np.errstate(invalid="ignore", over="ignore"):
                p_in = np.clip(
                    head / np.maximum(inflow, np.float32(1e-9)),
                    np.float32(0.0),
                    np.float32(1.0),
                )
            gate_u = hash_jitter_np(
                (base2 + cc) & _M32, nd, new_lbl
            ) / np.float32(0.49)
            moved &= gate_u < p_in[np.minimum(new_lbl, k)]
            new_lbl = np.where(moved, new_lbl, own).astype(np.int32)
            labels[nd[ndv]] = new_lbl[ndv]
            np.add.at(
                weights, np.where(moved, own, k),
                np.where(moved, -nwv, np.float32(0.0)).astype(np.float32),
            )
            np.add.at(
                weights, np.where(moved, new_lbl, k),
                np.where(moved, nwv, np.float32(0.0)).astype(np.float32),
            )
            weights[k] = np.inf
    return labels, weights


# --------------------------------------------------------------------------
# numpy reference: the paper's exact sequential semantics (used as test
# oracle and for the small coarsest-level graphs inside the evolutionary
# algorithm, where python-loop costs are negligible)
# --------------------------------------------------------------------------


def sclap_numpy(
    g: GraphNP,
    labels: np.ndarray,
    U: float,
    iters: int,
    seed: int = 0,
    refine_mode: bool = False,
    num_labels: Optional[int] = None,
    restrict: Optional[np.ndarray] = None,
    order: Optional[str] = None,
) -> LPResult:
    """Asynchronous sequential SCLaP — one node at a time, moves instantly
    visible (the paper's original sequential algorithm)."""
    rng = np.random.default_rng(seed)
    labels = labels.astype(np.int64).copy()
    T = num_labels if num_labels is not None else g.n
    weights = np.zeros(T, dtype=np.float64)
    np.add.at(weights, labels, g.nw)
    if order is None:
        order = "random" if refine_mode else "degree"
    total_moves = 0
    for it in range(iters):
        perm = make_order(g, order, seed + 17 * it)
        for v in perm:
            lo, hi = g.indptr[v], g.indptr[v + 1]
            if hi == lo:
                continue
            nbr = g.indices[lo:hi]
            wts = g.ew[lo:hi].astype(np.float64)
            lbl = labels[nbr]
            if restrict is not None:
                m = restrict[nbr] == restrict[v]
                nbr, wts, lbl = nbr[m], wts[m], lbl[m]
                if nbr.size == 0:
                    continue
            cand, inv = np.unique(lbl, return_inverse=True)
            conn = np.zeros(cand.shape[0])
            np.add.at(conn, inv, wts)
            own = labels[v]
            nw_v = g.nw[v]
            fits = weights[cand] + nw_v <= U
            if refine_mode and weights[own] > U:
                elig = fits & (cand != own)
            else:
                elig = (conn > 0) & (fits | (cand == own))
            if not elig.any():
                continue
            conn = conn + rng.random(conn.shape[0]) * 0.49
            conn[~elig] = -np.inf
            tgt = cand[int(np.argmax(conn))]
            if tgt != own:
                weights[own] -= nw_v
                weights[tgt] += nw_v
                labels[v] = tgt
                total_moves += 1
    return LPResult(labels=labels.astype(np.int32), moves=total_moves, iters=iters)
