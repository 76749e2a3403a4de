"""Decoder stack (+ optional encoder) with pattern-block layer scan.

The layer stack is ``n_blocks`` repetitions of ``cfg.pattern`` (a tuple of
LayerSpec).  Parameters for each pattern position are stacked along a leading
n_blocks axis and the stack is traversed with ``lax.scan`` — HLO size is one
block body regardless of depth, which keeps 512-way SPMD compiles tractable.
Heterogeneous stacks (Jamba: 1 attention + 7 mamba per block, MoE every other
layer) unroll the pattern *inside* the scan body.

Modes: "train" (logits for loss), "prefill" (logits at last position +
caches), "decode" (one token + updated caches).  Caches mirror the block
structure: dict keyed by pattern position, leaves stacked over n_blocks.
Decode attention caches come in two layouts (see models/attention.py):
dense (B, S_max, KVH, D) buffers, or the serving engine's paged form —
per-layer physical pools (n_blocks, n_pages, page, KVH, D) plus a shared
``block_table`` leaf broadcast over n_blocks.  Dense buffers, recurrent
and cross state, and the sharded paged layout (a 3-dim table per layer)
ride the scan's ``xs``/``ys``: each block's slice goes in and a new
stacked tree comes out.  The one-device paged layout (a 2-dim table per
layer) does not: ``forward`` hands its pools to ``_stack_forward``
separately, donated, the scan carries the stacked pools, and each layer
appends to and attends over its own slice in place — no layer's pool is
sliced out, relaid out or stacked back (``in_place_pools``).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.attention import attention_block, qkv_proj
from repro.models.config import LayerSpec, ModelConfig
from repro.models.layers import embed, learned_pos, mlp, rms_norm, unembed
from repro.models.moe import moe_layer
from repro.models.sharding import ExecContext
from repro.models.ssm import mamba_block


def _layer(x, spec: LayerSpec, p: dict, cfg: ModelConfig, ctx: ExecContext,
           positions, mode: str, cache: Optional[dict], cache_len,
           encoder_out, causal: bool, history: Optional[dict] = None):
    """One layer (pre-norm). Returns (x, new_cache, aux)."""
    aux = jnp.zeros((), jnp.float32)
    new_cache: Dict[str, Any] = {}

    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    if spec.mixer == "attn":
        window = ctx.window if ctx.window is not None else cfg.sliding_window
        attn_mode = mode
        o, c = attention_block(h, p, cfg, ctx, positions, attn_mode,
                               cache=None if cache is None else cache.get("self"),
                               cache_len=cache_len, window=window,
                               causal=causal,
                               history=None if history is None
                               else history.get("self"))
        if c is not None and mode in ("prefill", "decode"):
            new_cache["self"] = c
    else:
        hist = None if history is None else history.get("self")
        o, c = mamba_block(h, p, cfg, ctx, mode,
                           cache=(hist if hist is not None else
                                  (None if cache is None else cache.get("self"))))
        if c is not None:
            new_cache["self"] = c
    x = x + o

    if spec.cross_attn:
        h = rms_norm(x, p["normx"], cfg.norm_eps)
        if mode == "decode":
            o, _ = attention_block(h, p, cfg, ctx, positions, "cross_decode",
                                   cache=cache["cross"], prefix="x_")
            new_cache["cross"] = cache["cross"]
        else:
            # compute cross KV from encoder output (prefill/train)
            _, kx, vx = qkv_proj(encoder_out, p, cfg, prefix="x_")
            xc = {"k": kx, "v": vx}
            o, _ = attention_block(h, p, cfg, ctx, positions, "cross",
                                   cache=xc, prefix="x_")
            if mode == "prefill":
                new_cache["cross"] = xc
        x = x + o

    if spec.ffn != "none":
        h = rms_norm(x, p["norm2"], cfg.norm_eps)
        if spec.ffn == "moe":
            o, aux = moe_layer(h, p["moe"], cfg, ctx)
        else:
            o = mlp(h, p["ffn"], cfg.mlp_type)
        x = x + o
    return x, new_cache, aux


def _residual_spec(ctx: ExecContext, mode: str):
    if mode == "train":
        # Megatron-SP: checkpointed residual sharded (batch, seq) =
        # ((pod, dp), tp) — see DESIGN.md §4.
        return (ctx.batch_axes, ctx.tp_axis, None)
    if mode in ("prefill", "encode"):
        return (ctx.pod_axis, ctx.sp_axis, None)
    return (ctx.batch_axes, None, None)       # decode


def in_place_pools(caches: dict) -> Tuple[dict, dict]:
    """Split a decode cache tree into the paged pools that the layer scan
    updates in place and the rest, which rides its ``xs``/``ys``.

    A pool goes in place when its entry is paged with a 2-dim block table
    per layer — the stacked leaf is (n_blocks, B, pages_per_seq) — which
    is the one-device layout: the serving engine builds it whenever its
    pool has a single shard.  Returns ``(pools, rest)``: ``pools`` maps
    pattern position -> {"k","v"} stacked pools, and ``rest`` is the tree
    with those entries' ``self`` cut to their block table.  Dense
    buffers, the ring-buffer and windowed caches, recurrent and cross
    state and the sharded layout (a 3-dim table per layer) stay in
    ``rest`` whole."""
    pools, rest = {}, {}
    for key, ent in caches.items():
        c = ent.get("self")
        if c is not None and "block_table" in c and c["block_table"].ndim == 3:
            pools[key] = {"k": c["k"], "v": c["v"]}
            ent = dict(ent, self={"block_table": c["block_table"]})
        rest[key] = ent
    return pools, rest


@functools.partial(jax.jit,
                   static_argnames=("cfg", "ctx", "mode", "causal", "pattern"),
                   donate_argnames=("pools",))
def _stack_forward(x, blocks_p, cfg: ModelConfig, ctx: ExecContext, positions,
                   mode: str, caches, cache_len, encoder_out,
                   causal: bool, pattern, history=None, pools=None):
    """Scan over the stacked pattern blocks.

    Jitted so that an eagerly called forward (the serving engine's chunks
    and ticks) reuses the compiled layer stack for every call of the same
    shapes: an eager ``lax.scan`` traces a fresh body closure per call and
    so compiled the whole stack again on every decode tick.

    ``pools`` (decode; see ``in_place_pools``): pattern position ->
    {"k","v"} layer-stacked paged pools whose ``caches`` entry holds only
    the block table.  They ride the scan's carry, and block ``l``'s layer
    appends to and attends over slice ``l`` of them in place.  They are
    donated, so the pools that come out (in the returned caches, beside
    the block table, as the ``xs``/``ys`` path would return them) are the
    buffers that went in."""
    res_spec = _residual_spec(ctx, mode)
    # constrained inside the jit: a sequence that does not divide the SP
    # axis shards unevenly here, where an eager constraint would refuse it
    x = ctx.constrain(x, *res_spec)
    pools = pools or {}

    def body(carry, xs):
        x, aux_tot, pools = carry
        block_p, block_cache, block_hist, layer = xs
        new_caches, pools = {}, dict(pools)
        for i, spec in enumerate(pattern):
            key = str(i)
            c_i = None if block_cache is None else block_cache.get(key)
            h_i = None if block_hist is None else block_hist.get(key)
            if key in pools:
                c_i = dict(c_i, self=dict(c_i["self"], **pools[key],
                                          layer=layer))
            x, nc, aux = _layer(x, spec, block_p[key], cfg, ctx, positions,
                                mode, c_i, cache_len, encoder_out, causal,
                                history=h_i)
            x = ctx.constrain(x, *res_spec)
            if key in pools:
                c = nc.pop("self")
                pools[key] = {"k": c["k"], "v": c["v"]}
            new_caches[key] = nc
            aux_tot = aux_tot + aux
        return (x, aux_tot, pools), new_caches

    if ctx.remat and mode == "train":
        body = jax.checkpoint(body)

    aux0 = jnp.zeros((), jnp.float32)
    nb = jax.tree.leaves(blocks_p)[0].shape[0]
    if ctx.unroll_scan:
        carry = (x, aux0, pools)
        ys = []
        for b in range(nb):
            xs_b = jax.tree.map(lambda a: a[b], (blocks_p, caches, history))
            carry, y = body(carry, xs_b + (b,))
            ys.append(y)
        (x, aux, pools) = carry
        if ys and jax.tree.leaves(ys[0]):
            new_caches = jax.tree.map(lambda *ls: jnp.stack(ls), *ys)
        else:
            new_caches = ys[0] if ys else {}
    else:
        (x, aux, pools), new_caches = jax.lax.scan(
            body, (x, aux0, pools),
            (blocks_p, caches, history, jnp.arange(nb, dtype=jnp.int32)))
    for key, p in pools.items():
        new_caches[key]["self"] = dict(p, **caches[key]["self"])
    return x, aux, new_caches


def forward(params: dict, cfg: ModelConfig, ctx: ExecContext,
            tokens: jax.Array, positions: jax.Array, mode: str,
            caches: Optional[dict] = None,
            cache_len: Optional[jax.Array] = None,
            encoder_frames: Optional[jax.Array] = None,
            history: Optional[dict] = None,
            ) -> Tuple[jax.Array, jax.Array, Optional[dict]]:
    """Run the model.

    tokens: (B, S) int32 — or for pure-encoder input models, see
    ``encoder_frames`` (B, S_enc, d_model) stubbed frontend embeddings.
    Returns (logits, aux_loss, caches).
    decode: tokens (B, 1); positions (B, 1) = cache_len; caches required —
    attention entries either dense per-sequence buffers or paged
    {"k","v","block_table"} pools (see models/attention.py); the updated
    caches come back in the same layout.  Pools of the one-device paged
    layout are DONATED (``in_place_pools``): the ones passed in are
    deleted, and the caller rebinds the pools that come back.
    """
    dtype = jnp.dtype(cfg.dtype)
    x = embed(tokens, params["embed"], dtype)
    if cfg.pos_embedding == "learned":
        x = x + learned_pos(positions, params["pos_emb"], dtype)
    encoder_out = None
    if cfg.encoder_decoder:
        if mode == "decode":
            encoder_out = None            # cross caches already materialised
        else:
            assert encoder_frames is not None
            e = encoder_frames.astype(dtype)
            e_pos = jnp.broadcast_to(
                jnp.arange(e.shape[1], dtype=jnp.int32)[None], e.shape[:2])
            e = e + learned_pos(e_pos, params["encoder"]["pos_emb"], dtype)
            enc_pattern = (LayerSpec(mixer="attn", ffn="dense"),)
            enc_mode = "train" if mode == "train" else "encode"
            e, _, _ = _stack_forward(
                e, params["encoder"]["blocks"], cfg, ctx, e_pos,
                enc_mode, None, None, None, causal=False,
                pattern=enc_pattern)
            encoder_out = rms_norm(e, params["encoder"]["final_norm"],
                                   cfg.norm_eps)

    pools = None
    if mode == "decode":
        pools, caches = in_place_pools(caches)
    x, aux, new_caches = _stack_forward(
        x, params["blocks"], cfg, ctx, positions, mode, caches, cache_len,
        encoder_out, causal=True, pattern=cfg.pattern, history=history,
        pools=pools or None)

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if mode == "prefill":
        # next-token logits only; under zigzag layout the max-position token
        # is not at storage index -1, so gather it per batch row.
        pos2d = positions[0] if positions.ndim == 3 else positions
        last = jnp.argmax(pos2d, axis=1)                  # (B,)
        x = x[jnp.arange(x.shape[0]), last][:, None]      # (B, 1, d)
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = unembed(x, table)
    if mode == "train":
        logits = ctx.constrain(logits, ctx.batch_axes, None,
                               ctx.shardable(table.shape[0], ctx.tp_axis))
    return logits, aux, (new_caches if mode in ("prefill", "decode") else None)
