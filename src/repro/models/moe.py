"""Mixture-of-Experts FFN: capacity-based routing with three execution
strategies.

1. one-hot einsum dispatch (baseline; Switch/MaxText style) — static-shaped,
   GSPMD-partitionable, but the dispatch/combine matmuls cost O(g·E·C·d).
2. gather/scatter dispatch (``ctx.moe_gather_dispatch``) — same routing,
   ~zero dispatch FLOPs (confirmed win for inference, see EXPERIMENTS §Perf).
3. expert parallelism (``ctx.moe_ep``) — experts sharded over the data axis
   inside a shard_map island; tokens travel to their experts via
   ``lax.all_to_all`` and return, TP partials psum'd explicitly.  This is
   the structural fix for MoE training's expert-gradient all-reduce and for
   big-MoE weight memory (requires E %% ep_size == 0, e.g. Jamba's 16
   experts on the 16-wide data axis).

Tokens over an expert's per-group capacity are dropped (residual passes
through).  Shared experts (Qwen2-MoE) run as an always-on dense MLP.  A
Switch-style load-balance auxiliary loss is returned for training.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig
from repro.models.layers import mlp
from repro.models.sharding import ExecContext

GROUP_SIZE = 512


def _capacity(g: int, top_k: int, n_experts: int, cf: float) -> int:
    c = int(math.ceil(g * top_k * cf / n_experts))
    return max(4, ((c + 3) // 4) * 4) if g >= 16 else max(1, c)


# ----------------------------------------------------------------- routing
def _route(xt, router_w, m, E: int, C: int):
    """xt: (n, g, d) -> routing tensors (all (n, g, k)-shaped or similar)."""
    dtype = xt.dtype
    logits = jnp.einsum("ngd,de->nge", xt, router_w.astype(dtype))
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)    # (n,g,E)
    top_gates, top_idx = jax.lax.top_k(gates, m.top_k)             # (n,g,k)
    top_gates = top_gates / jnp.maximum(
        jnp.sum(top_gates, axis=-1, keepdims=True), 1e-9)
    onehot = jax.nn.one_hot(top_idx, E, dtype=jnp.int32)           # (n,g,k,E)
    n_g, g = xt.shape[:2]
    flat = onehot.reshape(n_g, g * m.top_k, E)
    pos = jnp.cumsum(flat, axis=1) - flat                          # exclusive
    within = jnp.sum(pos.reshape(n_g, g, m.top_k, E) * onehot, axis=-1)
    keep = within < C
    return dict(gates=gates, top_gates=top_gates, top_idx=top_idx,
                onehot=onehot, within=within, keep=keep)


# ---------------------------------------------------------------- dispatch
def _dispatch_gather(xt, r, E: int, C: int, top_k: int):
    """-> (xe: (n,E,C,d), state for combine). ~zero FLOPs."""
    n_g, g, d = xt.shape
    dtype = xt.dtype
    flat_tok = jnp.broadcast_to(
        jnp.arange(g, dtype=jnp.int32)[None, :, None], r["top_idx"].shape)
    n_idx = jnp.broadcast_to(
        jnp.arange(n_g, dtype=jnp.int32)[:, None, None], r["top_idx"].shape)
    # dropped tokens go to out-of-bounds slot C, discarded by mode="drop"
    safe_pos = jnp.where(r["keep"], r["within"], C)
    slot_token = jnp.zeros((n_g, E, C), jnp.int32).at[
        n_idx, r["top_idx"], safe_pos].set(flat_tok, mode="drop")
    slot_valid = jnp.zeros((n_g, E, C), jnp.bool_).at[
        n_idx, r["top_idx"], safe_pos].set(r["keep"], mode="drop")
    xe = jnp.take_along_axis(
        xt[:, :, None, :], slot_token.reshape(n_g, E * C)[:, :, None, None],
        axis=1, mode="clip").reshape(n_g, E, C, d)
    xe = xe * slot_valid[..., None].astype(dtype)
    return xe, safe_pos


def _combine_gather(ye, r, safe_pos, E: int, C: int, top_k: int):
    n_g = ye.shape[0]
    d = ye.shape[-1]
    g = r["top_idx"].shape[1]
    dtype = ye.dtype
    ye_flat = ye.reshape(n_g, E * C, d)
    slot_of_tok = r["top_idx"] * C + safe_pos                      # (n,g,k)
    y_k = jnp.take_along_axis(
        ye_flat[:, :, None, :],
        slot_of_tok.reshape(n_g, g * top_k)[:, :, None, None],
        axis=1, mode="clip").reshape(n_g, g, top_k, d)
    w_k = (r["top_gates"] * r["keep"]).astype(dtype)               # (n,g,k)
    return jnp.einsum("ngk,ngkd->ngd", w_k, y_k)


def _dispatch_einsum(xt, r, E: int, C: int):
    dtype = xt.dtype
    pos_oh = jax.nn.one_hot(jnp.where(r["keep"], r["within"], C), C + 1,
                            dtype=jnp.float32)[..., :C]            # (n,g,k,C)
    disp = jnp.einsum("ngke,ngkc->ngec", r["onehot"].astype(jnp.float32),
                      pos_oh)
    xe = jnp.einsum("ngec,ngd->necd", disp.astype(dtype), xt)
    return xe, pos_oh


def _combine_einsum(ye, r, pos_oh):
    comb = jnp.einsum("ngk,ngke,ngkc->ngec",
                      r["top_gates"].astype(jnp.float32),
                      r["onehot"].astype(jnp.float32), pos_oh)
    return jnp.einsum("ngec,necd->ngd", comb.astype(ye.dtype), ye)


# ------------------------------------------------------------- expert FFN
def _expert_ffn(xe, p_exp, mlp_type: str):
    dtype = xe.dtype
    we_i = p_exp["wi"].astype(dtype)
    we_o = p_exp["wo"].astype(dtype)
    if mlp_type == "swiglu":
        we_g = p_exp["wg"].astype(dtype)
        h = jax.nn.silu(jnp.einsum("necd,edf->necf", xe, we_g)) * \
            jnp.einsum("necd,edf->necf", xe, we_i)
    else:
        h = jnp.einsum("necd,edf->necf", xe, we_i)
        h = jnp.square(jax.nn.relu(h)) if mlp_type == "relu2" \
            else jax.nn.gelu(h)
    return jnp.einsum("necf,efd->necd", h, we_o)


def _aux_loss(r, E: int):
    density = jnp.mean(jnp.max(r["onehot"].astype(jnp.float32), axis=2),
                       axis=1)                                     # (n,E)
    prob = jnp.mean(r["gates"], axis=1)
    return (E * jnp.mean(jnp.sum(density * prob, axis=-1))
            ).astype(jnp.float32)


# ------------------------------------------------------- token grouping io
def _group_tokens(x, g: int):
    B, S, d = x.shape
    T = B * S
    pad = (-T) % g
    xt = x.reshape(T, d)
    if pad:
        xt = jnp.concatenate([xt, jnp.zeros((pad, d), x.dtype)], axis=0)
    return xt.reshape(-1, g, d), T, pad


def _ungroup(y, T: int, B: int, S: int, d: int):
    y = y.reshape(-1, d)[:T]
    return y.reshape(B, S, d)


def _token_axes(ctx: ExecContext, S: int):
    if S == 1:
        return ctx.batch_axes
    if ctx.sp_axis is not None:
        return tuple(a for a in (ctx.pod_axis, ctx.sp_axis) if a)
    return ctx.batch_axes


# ------------------------------------------------------------- main layer
def moe_layer(x: jax.Array, p: dict, cfg: ModelConfig, ctx: ExecContext
              ) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (out, aux_loss)."""
    m = cfg.moe
    B, S, d = x.shape
    E = m.n_experts
    g = min(GROUP_SIZE, B * S)
    C = _capacity(g, m.top_k, E, m.capacity_factor)
    token_axes = _token_axes(ctx, S)

    xt, T, pad = _group_tokens(x, g)
    n_g = xt.shape[0]

    ep_ax = ctx.moe_ep_axis()
    tok_div = 1
    if token_axes:
        for a in (token_axes if isinstance(token_axes, tuple)
                  else (token_axes,)):
            tok_div *= ctx.axis_size(a)
    if ep_ax is not None and E % ctx.axis_size(ep_ax) == 0 \
            and n_g % max(tok_div, 1) == 0 and ctx.mesh is not None:
        y, aux = _moe_ep(xt, p, cfg, ctx, ep_ax, E, C, token_axes)
    else:
        xt = ctx.constrain(xt, token_axes, None, None)
        r = _route(xt, p["router"], m, E, C)
        if ctx.moe_gather_dispatch:
            xe, st = _dispatch_gather(xt, r, E, C, m.top_k)
        else:
            xe, st = _dispatch_einsum(xt, r, E, C)
        xe = ctx.constrain(xe, token_axes, None, None, None)
        ye = _expert_ffn(xe, p["experts"], cfg.mlp_type)
        if ctx.moe_gather_dispatch:
            y = _combine_gather(ye, r, st, E, C, m.top_k)
        else:
            y = _combine_einsum(ye, r, st)
        aux = _aux_loss(r, E)

    y = _ungroup(y, T, B, S, d)
    if m.n_shared:
        y = y + mlp(x, p["shared"], cfg.mlp_type)
    return y, aux


# -------------------------------------------------------- expert parallel
def _moe_ep(xt, p, cfg: ModelConfig, ctx: ExecContext, ep_ax: str,
            E: int, C: int, token_axes):
    """Expert-parallel MoE: experts sharded over ``ep_ax``; tokens all_to_all
    to their experts and back; TP partials psum'd inside the island."""
    m = cfg.moe
    n_ep = ctx.axis_size(ep_ax)
    tp = ctx.tp_axis if (ctx.tp_axis and
                         m.d_expert % ctx.axis_size(ctx.tp_axis) == 0) \
        else None

    def body(xt_l, router_w, exp_l):
        # xt_l: (n_l, g, d) local token groups; exp_l: experts (E/n, d, f_l)
        r = _route(xt_l, router_w, m, E, C)
        xe, st = _dispatch_gather(xt_l, r, E, C, m.top_k)  # (n_l, E, C, d)
        n_l, _, _, d = xe.shape
        # ship token slots to their expert owners:
        # (E, n_l*C, d) --all_to_all--> (E/n, n*n_l*C, d)
        xe = xe.transpose(1, 0, 2, 3).reshape(E, n_l * C, d)
        xe = lax.all_to_all(xe, ep_ax, split_axis=0, concat_axis=1,
                            tiled=True)
        ye = _expert_ffn(xe[None], exp_l, cfg.mlp_type)[0]
        if tp is not None:
            ye = lax.psum(ye, tp)              # TP partials over d_expert
        # return outputs to the token owners
        ye = lax.all_to_all(ye, ep_ax, split_axis=1, concat_axis=0,
                            tiled=True)
        ye = ye.reshape(E, n_l, C, d).transpose(1, 0, 2, 3)
        y = _combine_gather(ye, r, st, E, C, m.top_k)
        aux = lax.pmean(_aux_loss(r, E), token_axes)
        return y, aux

    exp_specs = jax.tree.map(
        lambda _: P(None, ep_ax, None, tp), p["experts"])
    # wo is (E, f, d): shard f over tp instead of the last dim
    exp_specs["wo"] = P(None, ep_ax, tp, None)
    # strip the stacked-block leading axis handling: inside the layer the
    # experts are (E, d, f) — specs above include the n_blocks axis at dim 0
    exp_specs = jax.tree.map(
        lambda s: P(*s[1:]), exp_specs, is_leaf=lambda s: isinstance(s, P))

    y, aux = shard_map(
        body, mesh=ctx.mesh,
        in_specs=(P(token_axes, None, None), P(), exp_specs),
        out_specs=(P(token_axes, None, None), P()),
        check_vma=False,
    )(xt, p["router"], p["experts"])
    return y, aux
