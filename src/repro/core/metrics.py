"""Partition quality metrics: edge cut, balance, quotient graph, comm volume."""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from ..graph.csr import Graph, GraphNP

__all__ = [
    "cut_np",
    "cut_jnp",
    "cut_from_arcs_jnp",
    "block_weights_np",
    "block_weights_dense_jnp",
    "imbalance_np",
    "is_feasible",
    "quotient_graph_np",
    "comm_volume_np",
]


def cut_from_arcs_jnp(labels, src, dst, ew, integral: bool = False):
    """Edge cut from flat arc arrays on device (one individual; ``vmap`` the
    labels axis for a population batch).  Trailing zero-weight arc padding is
    inert.  ``integral=True`` promises integral weights with an arc total
    below 2**31 and sums in int32 -- exact in any order, where an f32 sum
    stops being exact once the cut passes 2**24 -- returning an int32."""
    diff = labels[src] != labels[dst]
    if integral:
        return jnp.sum(jnp.where(diff, ew, 0.0).astype(jnp.int32)) // 2
    return jnp.sum(jnp.where(diff, ew, 0.0)) / 2.0


def block_weights_dense_jnp(labels, nw, k, Kb: int):
    """(Kb,) block weights of arena labels on device: slots >= ``k`` (traced)
    collect the arena's sentinel label with weight 0 — inert.  Returns the
    raw vector; callers mask or +inf-pad the dead slots as needed."""
    return jnp.zeros((Kb,), jnp.float32).at[labels].add(nw)


def cut_np(g: GraphNP, labels: np.ndarray) -> float:
    """Total weight of edges between blocks (each undirected edge once)."""
    src = g.arc_sources()
    diff = labels[src] != labels[g.indices]
    return float(g.ew[diff].sum() / 2.0)


def cut_jnp(g: Graph, labels: jnp.ndarray) -> jnp.ndarray:
    src = g.arc_sources()
    diff = labels[src] != labels[g.indices]
    return jnp.sum(jnp.where(diff, g.ew, 0.0)) / 2.0


def block_weights_np(g: GraphNP, labels: np.ndarray, k: int) -> np.ndarray:
    return np.bincount(labels, weights=g.nw, minlength=k)[:k]


def lmax(total_weight: float, k: int, eps: float) -> float:
    """The balance bound L_max = (1 + eps) * ceil(c(V) / k)."""
    return (1.0 + eps) * np.ceil(total_weight / k)


def imbalance_np(g: GraphNP, labels: np.ndarray, k: int) -> float:
    """max_i c(V_i) * k / c(V) - 1  (0.0 == perfectly balanced)."""
    bw = block_weights_np(g, labels, k)
    return float(bw.max() * k / max(g.total_node_weight, 1e-12) - 1.0)


def is_feasible(g: GraphNP, labels: np.ndarray, k: int, eps: float) -> bool:
    bw = block_weights_np(g, labels, k)
    return bool(bw.max() <= lmax(g.total_node_weight, k, eps) + 1e-6)


def quotient_graph_np(g: GraphNP, labels: np.ndarray, k: int):
    """Weighted quotient graph: (k,k) dense inter-block weight matrix + block weights."""
    src = g.arc_sources()
    dst = g.indices
    q = np.zeros((k, k), dtype=np.float64)
    np.add.at(q, (labels[src], labels[dst]), g.ew)
    np.fill_diagonal(q, 0.0)
    return q / 2.0, block_weights_np(g, labels, k)


def comm_volume_np(g: GraphNP, labels: np.ndarray, k: int) -> float:
    """Total communication volume: sum over v of #distinct foreign blocks adjacent."""
    src = g.arc_sources().astype(np.int64)
    dst_lbl = labels[g.indices].astype(np.int64)
    key = src * np.int64(k + 1) + dst_lbl
    uniq = np.unique(key)
    usrc = uniq // (k + 1)
    ulbl = uniq % (k + 1)
    return float((ulbl != labels[usrc]).sum())
