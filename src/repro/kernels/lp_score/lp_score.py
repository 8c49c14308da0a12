"""Pallas TPU kernel: per-node block-connection scores for LP refinement.

The refinement inner loop of the paper — "move v to the eligible block with
the strongest connection" — reduces, for labels in [0, k), to

    S[v, b] = sum_{u in Gamma(v), label(u) = b} w(v, u)

The paper computes this with per-node hash maps (linear probing), which has
no sensible TPU mapping.  The TPU-native formulation: adjacency in row-split
ELL layout (``repro.graph.packing.ell_pack``), neighbour labels pre-gathered
by XLA, and the kernel accumulating a dense score tile in VMEM with VPU
compare+select one-hot accumulation, one ELL column at a time.

Layout & tiling:
  * the kernel works on the transposed problem: ELL columns on sublanes,
    rows on lanes.  Each grid step owns TILE_R = 256 rows and a
    (K, TILE_R) fp32 accumulator (128 x 256 x 4 B = 128 KiB);
  * K padded to a lane multiple (128), so the transposed-back output is
    lane-dense (R, K);
  * the ELL width is swept in WC = 8 sublane slabs: every dynamic slice
    starts at a multiple of 8 sublanes, which Mosaic can prove aligned at
    any width (lane-axis slices of 8 at a dynamic offset it refuses).

A node of degree d owns ceil(d / W) consecutive rows; the caller
segment-sums row scores into node scores (XLA), so power-law degrees cannot
blow up the tile width.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["lp_score_rows", "default_interpret", "TILE_R", "LANE"]

TILE_R = 256  # rows per grid step
LANE = 128    # TPU lane width; K is padded to a multiple of this
_WC = 8       # ELL-width slice per inner step


def default_interpret() -> bool:
    """Interpret the kernel unless the default backend is a TPU, the only
    backend Mosaic compiles for."""
    return jax.default_backend() != "tpu"


def _kernel(lbl_ref, w_ref, out_ref, *, k_pad: int, width: int):
    """Accumulate one transposed (k_pad, TILE_R) score tile.

    The ELL width runs along sublanes, so each step loads a (WC, TILE_R)
    slab at a sublane offset that is a multiple of WC -- an aligned dynamic
    slice Mosaic accepts at every width -- and folds its WC rows into the
    tile one compare+select at a time."""
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (k_pad, 1), 0)

    def body(j, acc):
        base = pl.multiple_of(j * _WC, _WC)
        sl = lbl_ref[pl.ds(base, _WC), :]                 # (WC, TILE_R)
        sw = w_ref[pl.ds(base, _WC), :]                   # (WC, TILE_R)
        for r in range(_WC):
            acc = acc + jnp.where(sl[r:r + 1, :] == iota_k, sw[r:r + 1, :], 0.0)
        return acc

    acc = jnp.zeros((k_pad, lbl_ref.shape[1]), jnp.float32)
    out_ref[...] = jax.lax.fori_loop(0, width // _WC, body, acc)


@functools.partial(jax.jit, static_argnames=("k_pad", "interpret"))
def lp_score_rows(
    lbl: jnp.ndarray,   # (R, W) int32 — neighbour labels; invalid slots = k_pad (or any >= k)
    w: jnp.ndarray,     # (R, W) f32   — edge weights; invalid slots = 0
    *,
    k_pad: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Per-ELL-row dense block scores, shape (R, k_pad)."""
    R, W = lbl.shape
    assert R % TILE_R == 0, f"rows {R} must be a multiple of {TILE_R}"
    assert k_pad % LANE == 0, f"k_pad {k_pad} must be a multiple of {LANE}"
    assert W % _WC == 0, f"ELL width {W} must be a multiple of {_WC}"
    grid = (R // TILE_R,)
    out_t = pl.pallas_call(
        functools.partial(_kernel, k_pad=k_pad, width=W),
        grid=grid,
        in_specs=[
            pl.BlockSpec((W, TILE_R), lambda i: (0, i)),
            pl.BlockSpec((W, TILE_R), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((k_pad, TILE_R), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((k_pad, R), jnp.float32),
        interpret=interpret,
    )(lbl.T, w.T)
    return out_t.T
