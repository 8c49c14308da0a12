"""Jitted wrapper: node-level block scores + a synchronous dense refinement
round built on the Pallas kernel (the beyond-paper "SpMM refinement" path).

As of PR 1 this path is wired into the multilevel pipeline: with
``PartitionerConfig(refine_engine="dense")`` the LP engine
(``repro.core.engine``) calls :func:`dense_round_device` once per refinement
iteration at fine levels, reusing a per-level cached ELL pack and keeping
labels device-resident between rounds.  The chunked-sequential sweep remains
the fallback below the size threshold.
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from ...graph.csr import GraphNP
from ...graph.packing import EllPack, ell_pack
from .lp_score import LANE, TILE_R, default_interpret, lp_score_rows
from .ref import lp_score_rows_ref

__all__ = [
    "node_scores",
    "lp_refine_dense_round",
    "dense_round_device",
    "dense_round_device_batched",
    "dense_eligibility",
    "pad_k",
]


def pad_k(k: int) -> int:
    return max(LANE, ((k + LANE - 1) // LANE) * LANE)


def _row_scores(ell_dst, ell_w, row_node, lab_pad, n, *, k, use_pallas, interpret):
    """Shared body: ELL row scores segment-summed into (nb, k) node scores.

    Shapes are *bucket* shapes: ``lab_pad`` has ``nb >= n + 1`` entries with
    label ``k`` beyond ``n`` (so sentinel destinations contribute nothing),
    and ``n`` is a TRACED scalar — one compiled executable per
    ``(row bucket, node bucket, k)`` combination serves every level that
    lands in the bucket, instead of re-compiling per level."""
    k_p = pad_k(k)
    R = ell_dst.shape[0]
    nb = lab_pad.shape[0]
    if R % TILE_R:
        pad = TILE_R - R % TILE_R
        # padded rows carry weight 0 and scatter to the dummy slot: inert
        ell_dst = jnp.pad(ell_dst, ((0, pad), (0, 0)))
        ell_w = jnp.pad(ell_w, ((0, pad), (0, 0)))
        row_node = jnp.pad(row_node, (0, pad), constant_values=nb)
    lbl = lab_pad[ell_dst]  # XLA gather; sentinel dst (== n) -> label k
    if use_pallas:
        row_scores = lp_score_rows(lbl, ell_w, k_pad=k_p, interpret=interpret)
    else:
        row_scores = lp_score_rows_ref(lbl, ell_w, k_pad=k_p)
    # row-split ELL: segment-sum rows into nodes; sentinel rows -> dummy nb
    seg = jnp.where(row_node >= n, jnp.int32(nb), row_node)
    out = jnp.zeros((nb + 1, k_p), jnp.float32).at[seg].add(row_scores)
    return out[:nb, :k]


@functools.partial(jax.jit, static_argnames=("k", "use_pallas", "interpret"))
def _node_scores_impl(
    ell_dst, ell_w, row_node, lab_pad, n, *, k: int, use_pallas: bool,
    interpret: bool,
):
    return _row_scores(
        ell_dst, ell_w, row_node, lab_pad, n,
        k=k, use_pallas=use_pallas, interpret=interpret,
    )


def node_scores(
    g: GraphNP,
    labels: np.ndarray,
    k: int,
    ell: EllPack | None = None,
    use_pallas: bool = True,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """S[v, b] for all nodes; Pallas on the row tiles, XLA for gather/segsum.
    ``interpret=None`` compiles the kernel on a TPU and interprets it
    elsewhere (:func:`default_interpret`)."""
    if ell is None:
        ell = ell_pack(g, width=128, tile_rows=TILE_R)
    labels_ext = jnp.concatenate(
        [jnp.asarray(labels, jnp.int32), jnp.array([k], jnp.int32)]
    )
    return _node_scores_impl(
        jnp.asarray(ell.dst),
        jnp.asarray(ell.w),
        jnp.asarray(ell.row_node),
        labels_ext,
        jnp.int32(g.n),
        k=k,
        use_pallas=use_pallas,
        interpret=default_interpret() if interpret is None else interpret,
    )[: g.n]


def dense_eligibility(S, lab, bw, nw, U, k: int):
    """Vectorized SCLaP refine-mode eligibility — exact mirror of the
    sequential oracle (``sclap_numpy``):

      * node in an overloaded block: may move to any *connected* block that
        fits, own block excluded ("must leave");
      * otherwise: any connected block that fits, or its own block.

    Connectivity (``S > 0``) applies in both branches because the oracle only
    ever considers neighbouring blocks as candidates.  Note the explicit
    parenthesisation: ``&`` binds tighter than ``|``, which previously turned
    this rule into ``fits | (own & ~overloaded)`` — letting overloaded nodes
    "stay put" and non-fitting moves through (regression-tested in
    tests/test_kernels.py::test_dense_eligibility_matches_sclap_numpy).
    """
    own = jnp.arange(k, dtype=lab.dtype)[None, :] == lab[:, None]
    fits = bw[None, :] + nw[:, None] <= U
    overloaded = (bw[lab] > U)[:, None]
    return (S > 0) & jnp.where(overloaded, fits & ~own, fits | own)


def _dense_round_body(
    ell_dst, ell_w, row_node, lab, nw, U, seed, move_fraction, n,
    *, k, use_pallas, interpret,
):
    nb = lab.shape[0]
    valid = jnp.arange(nb, dtype=jnp.int32) < n
    # padded slots must keep label k: that is the sentinel-destination label
    # the ELL gather relies on, and it keeps them out of every block weight
    lab = jnp.where(valid, lab, jnp.int32(k))
    nw = jnp.where(valid, nw, 0.0)
    S = _row_scores(
        ell_dst, ell_w, row_node, lab, n,
        k=k, use_pallas=use_pallas, interpret=interpret,
    )
    lab_c = jnp.minimum(lab, k - 1)         # clamp for (k,)-table lookups
    bw = jnp.zeros((k,), jnp.float32).at[jnp.minimum(lab, k)].add(
        nw, mode="drop"
    )
    key = jax.random.PRNGKey(seed)
    own_score = jnp.take_along_axis(S, lab_c[:, None], axis=1)[:, 0]
    overloaded = bw[lab_c] > U
    eligible = dense_eligibility(S, lab_c, bw, nw, U, k)
    masked = jnp.where(eligible, S + jax.random.uniform(key, S.shape) * 0.49, -jnp.inf)
    best = jnp.argmax(masked, axis=1).astype(jnp.int32)
    has = jnp.isfinite(jnp.max(masked, axis=1))
    gate = jax.random.uniform(jax.random.fold_in(key, 1), (nb,)) < move_fraction
    # strict improvement only: cut-neutral moves oscillate under synchronous
    # updates (stale block weights), so they are rejected
    improve = jnp.take_along_axis(S, best[:, None], axis=1)[:, 0] > own_score
    # overloaded blocks shed only their EXCESS in expectation — a synchronous
    # "everyone leaves" stampede would just overload the destination
    excess = jnp.clip((bw[lab_c] - U) / jnp.maximum(bw[lab_c], 1.0), 0.0, 1.0)
    ov_gate = jax.random.uniform(jax.random.fold_in(key, 2), (nb,)) < 1.5 * excess
    move = valid & has & ((gate & improve) | (overloaded & ov_gate))
    return jnp.where(move, best, lab)


@functools.partial(jax.jit, static_argnames=("k", "use_pallas", "interpret"))
def dense_round_device(
    ell_dst,            # (Rb, W) int32 — cached device ELL pack (row bucket)
    ell_w,              # (Rb, W) f32
    row_node,           # (Rb,)  int32, sentinel n
    lab,                # (nb,)  int32 — device labels, k beyond n
    nw,                 # (nb,)  f32 — node weights, 0 beyond n
    U,                  # scalar f32
    seed,               # scalar int32
    move_fraction,      # scalar f32
    n,                  # TRACED scalar int32 — live node count
    *,
    k: int,
    use_pallas: bool,
    interpret: bool,
):
    """One fully synchronous dense LP round, device arrays in and out.

    All array arguments are *bucket*-shaped (pow2 rows / pow2 node count)
    with the live node count traced, so the LP engine compiles this once per
    bucket rather than once per level; iterating it is ``iters`` kernel
    launches with zero host round-trips.
    """
    return _dense_round_body(
        ell_dst, ell_w, row_node, lab, nw, U, seed, move_fraction, n,
        k=k, use_pallas=use_pallas, interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("k", "use_pallas", "interpret"))
def dense_round_device_batched(
    ell_dst,            # (Rb, W) int32 — shared cached ELL pack
    ell_w,              # (Rb, W) f32
    row_node,           # (Rb,)  int32, sentinel n
    labs,               # (B, nb) int32 — population label batch
    nw,                 # (nb,)  f32 — shared node weights
    U,                  # scalar f32
    seeds,              # (B,) int32 — per-individual round seeds
    move_fraction,      # scalar f32
    n,                  # traced scalar int32
    *,
    k: int,
    use_pallas: bool,
    interpret: bool,
):
    """Population-batched synchronous dense round: a ``vmap`` label axis over
    :func:`dense_round_device`'s body with the ELL pack shared across the
    batch — one kernel dispatch refines every individual, and each row is
    bit-identical to a per-individual :func:`dense_round_device` call with
    the same seed (tested in tests/test_kernels.py)."""
    return jax.vmap(
        lambda lab, sd: _dense_round_body(
            ell_dst, ell_w, row_node, lab, nw, U, sd, move_fraction, n,
            k=k, use_pallas=use_pallas, interpret=interpret,
        )
    )(labs, seeds)


def lp_refine_dense_round(
    g: GraphNP,
    labels: np.ndarray,
    k: int,
    U: float,
    seed: int = 0,
    move_fraction: float = 0.5,
    ell: EllPack | None = None,
    use_pallas: bool = True,
    interpret: Optional[bool] = None,
) -> np.ndarray:
    """One fully synchronous LP refinement round using dense scores.

    All nodes see consistent block weights; a random ``move_fraction`` of
    the proposed moves is applied per round (the standard damping that makes
    synchronous LP converge).  Host convenience wrapper around
    :func:`dense_round_device`.
    """
    if ell is None:
        ell = ell_pack(g, width=128, tile_rows=TILE_R)
    lab_pad = np.concatenate(
        [np.asarray(labels, np.int32), np.array([k], np.int32)]
    )
    nw_pad = np.concatenate([g.nw.astype(np.float32), np.zeros(1, np.float32)])
    new = dense_round_device(
        jnp.asarray(ell.dst),
        jnp.asarray(ell.w),
        jnp.asarray(ell.row_node),
        jnp.asarray(lab_pad),
        jnp.asarray(nw_pad),
        jnp.float32(U),
        jnp.int32(seed & 0x7FFFFFFF),
        jnp.float32(move_fraction),
        jnp.int32(g.n),
        k=k,
        use_pallas=use_pallas,
        interpret=default_interpret() if interpret is None else interpret,
    )
    return np.asarray(new[: g.n])
