"""Mixture-of-Experts layer with real expert parallelism.

Production path (``mode="ep"``): a ``jax.shard_map`` region over the
(data, model) mesh axes implementing the standard two-hop token routing:

  1. activations are *sequence-sharded* on entry (tokens split over both
     axes), so every shard owns T_local tokens;
  2. local top-k routing; tokens are packed into per-expert capacity
     buffers by a sort + positional cumsum (static shapes, dropless up to
     the capacity factor — overflow tokens fall through on the residual);
  3. ``all_to_all`` over the *model* axis ships buffers to expert owners
     (experts are sharded over "model");
  4. expert FFN (weights FSDP-sharded over "data" are all-gathered on use —
     explicit FSDP);
  5. ``all_to_all`` back + weighted combine.

A dense fallback (``mode="dense"``) computes every expert for every token —
used by CPU smoke tests and as the oracle in unit tests (the EP path must
match it wherever no token overflows capacity).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["MoEParams", "init_moe_params", "moe_dense", "moe_ep", "router_topk"]


def init_moe_params(key, d_model, d_ff, n_experts, glu, dtype):
    ks = jax.random.split(key, 4)
    si, so = d_model ** -0.5, d_ff ** -0.5
    p = {
        "router": jax.random.normal(ks[0], (d_model, n_experts), jnp.float32) * si,
        "w_up": jax.random.normal(ks[1], (n_experts, d_model, d_ff), dtype) * si,
        "w_down": jax.random.normal(ks[2], (n_experts, d_ff, d_model), dtype) * so,
    }
    if glu:
        p["w_gate"] = jax.random.normal(ks[3], (n_experts, d_model, d_ff), dtype) * si
    return p


def router_topk(x, router_w, topk):
    """x (T, D) -> (probs (T,k), idx (T,k), aux load-balancing loss)."""
    logits = (x.astype(jnp.float32) @ router_w).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, topk)
    topv = topv / jnp.clip(topv.sum(-1, keepdims=True), 1e-9)
    E = router_w.shape[1]
    # Switch-style aux loss: E * sum_e mean_prob_e * mean_assign_e
    assign = jnp.zeros((x.shape[0], E), jnp.float32).at[
        jnp.arange(x.shape[0])[:, None], topi
    ].set(1.0)
    aux = E * jnp.sum(probs.mean(0) * assign.mean(0))
    return topv, topi, aux


def _expert_ffn(xe, w_up, w_gate, w_down, glu, act):
    a = {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}[act]
    if glu:
        h = a(jnp.einsum("ecd,edf->ecf", xe, w_gate)) * jnp.einsum(
            "ecd,edf->ecf", xe, w_up
        )
    else:
        h = a(jnp.einsum("ecd,edf->ecf", xe, w_up))
    return jnp.einsum("ecf,efd->ecd", h, w_down)


def moe_dense(params, x, *, topk, glu=True, act="silu"):
    """Dense fallback: every expert computes every token (oracle/smoke)."""
    B, S, D = x.shape
    xt = x.reshape(-1, D)
    topv, topi, aux = router_topk(xt, params["router"], topk)
    E = params["router"].shape[1]
    ys = []
    a = {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}[act]
    for e in range(E):
        if glu:
            h = a(xt @ params["w_gate"][e]) * (xt @ params["w_up"][e])
        else:
            h = a(xt @ params["w_up"][e])
        ys.append(h @ params["w_down"][e])
    ys = jnp.stack(ys, axis=1)  # (T, E, D)
    gate = jnp.zeros((xt.shape[0], E), ys.dtype).at[
        jnp.arange(xt.shape[0])[:, None], topi
    ].add(topv.astype(ys.dtype))
    y = jnp.einsum("ted,te->td", ys, gate)
    return y.reshape(B, S, D), aux


def moe_ep(
    params,
    x,                      # (B, S, D), sharded P(dp, None, None) on entry
    *,
    mesh: Mesh,
    topk: int,
    n_experts: int,
    capacity_factor: float = 1.25,
    glu: bool = True,
    act: str = "silu",
    dp_axes=("data",),
    tp_axis: str = "model",
):
    """Expert-parallel MoE via shard_map + all_to_all (see module docstring)."""
    B, S, D = x.shape
    P_m = mesh.shape[tp_axis]
    dp_size = 1
    for a in dp_axes:
        dp_size *= mesh.shape[a]
    dp = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    E_local = n_experts // P_m
    assert E_local * P_m == n_experts
    # adaptive activation sharding: batch over dp if divisible, sequence over
    # tp if divisible (decode steps with S == 1 replicate over tp — the small
    # redundant-compute path; B == 1 long-context decode replicates over dp)
    b_ax = dp if B % dp_size == 0 else None
    s_ax = tp_axis if (S > 1 and S % P_m == 0) else None

    glu_flag, act_name = glu, act

    def body(xl, router_w, w_up, w_gate, w_down):
        # xl: (B_local, S_local, D) — tokens sequence-sharded over tp too
        Bl, Sl, _ = xl.shape
        T = Bl * Sl
        xt = xl.reshape(T, D)
        topv, topi, aux = router_topk(xt, router_w, topk)
        cap = int(T * topk / n_experts * capacity_factor) + 1

        a_tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), topk)
        a_exp = topi.reshape(-1).astype(jnp.int32)
        a_w = topv.reshape(-1)
        order = jnp.argsort(a_exp, stable=True)
        se, st, sw = a_exp[order], a_tok[order], a_w[order]
        start = jnp.searchsorted(se, jnp.arange(n_experts, dtype=jnp.int32))
        pos = jnp.arange(T * topk, dtype=jnp.int32) - start[se]
        keep = pos < cap
        slot = jnp.where(keep, se * cap + pos, n_experts * cap)  # overflow -> dump slot

        buf = jnp.zeros((n_experts * cap + 1, D), xl.dtype).at[slot].set(xt[st])
        buf = buf[:-1].reshape(n_experts, cap, D)
        # token origin bookkeeping for the combine
        src_tok = jnp.full((n_experts * cap + 1,), -1, jnp.int32).at[slot].set(st)
        src_w = jnp.zeros((n_experts * cap + 1,), jnp.float32).at[slot].set(sw)

        # ---- ship to expert owners over the model axis --------------------
        # (E, cap, D) -> (E_local, P_m * cap, D)
        recv = jax.lax.all_to_all(
            buf.reshape(P_m, E_local * cap, D), tp_axis, split_axis=0,
            concat_axis=0, tiled=True,
        ).reshape(P_m, E_local, cap, D).transpose(1, 0, 2, 3).reshape(
            E_local, P_m * cap, D
        )

        # ---- expert FFN (FSDP all-gather of weights over data axes) -------
        wu = jax.lax.all_gather(w_up, dp, axis=1, tiled=True)
        wd = jax.lax.all_gather(w_down, dp, axis=2, tiled=True)
        wg = (
            jax.lax.all_gather(w_gate, dp, axis=1, tiled=True)
            if glu_flag
            else None
        )
        ye = _expert_ffn(recv, wu, wg, wd, glu_flag, act_name)

        # ---- ship results back & combine -----------------------------------
        back = jax.lax.all_to_all(
            ye.reshape(E_local, P_m, cap, D).transpose(1, 0, 2, 3).reshape(
                P_m, E_local * cap, D
            ),
            tp_axis, split_axis=0, concat_axis=0, tiled=True,
        ).reshape(n_experts * cap, D)
        back = jnp.concatenate([back, jnp.zeros((1, D), back.dtype)], axis=0)
        contrib = back * src_w[:, None].astype(back.dtype)
        y = jnp.zeros((T, D), xl.dtype).at[jnp.maximum(src_tok, 0)].add(
            jnp.where((src_tok >= 0)[:, None], contrib, 0.0).astype(xl.dtype)
        )
        aux_g = jax.lax.pmean(jax.lax.pmean(aux, dp), tp_axis)
        return y.reshape(Bl, Sl, D), aux_g

    # sequence-shard over the tp axis on entry, restore on exit
    from jax.sharding import NamedSharding

    xs = jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(b_ax, s_ax, None)))
    y, aux = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(b_ax, s_ax, None),
            P(None, None),                       # router replicated
            P(tp_axis, dp, None),                # experts E/tp, D/fsdp
            P(tp_axis, dp, None) if glu else P(None),
            P(tp_axis, None, dp),
        ),
        out_specs=(P(b_ax, s_ax, None), P()),
        check_vma=False,
    )(
        xs,
        params["router"],
        params["w_up"],
        params.get("w_gate", jnp.zeros((1,), x.dtype)),
        params["w_down"],
    )
    y = jax.lax.with_sharding_constraint(y, NamedSharding(mesh, P(b_ax, None, None)))
    return y, jnp.mean(aux)
