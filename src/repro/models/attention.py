"""Attention block: projections + RoPE + mode-dispatched attention core.

Modes:
  train   — full causal attention, batch-parallel (per-device local compute)
  prefill — ring attention over ctx.sp_axis when set (sequence sharded,
            zigzag or contiguous order carried by position arrays); KV cache
            returned in shard order
  decode  — one token per sequence against a KV cache; split-KV flash decode
            over ctx.kv_split_axis when set
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.ring_attention import (ring_attention, ring_paged_prefill,
                                       sharded_cache_update,
                                       sharded_paged_decode, split_kv_decode)
from repro.kernels import ops
from repro.kernels.flash_decode import POS_PAD
from repro.models.config import ModelConfig
from repro.models.layers import apply_rope, rms_norm
from repro.models.sharding import ExecContext


def qkv_proj(x: jax.Array, p: dict, cfg: ModelConfig, prefix: str = ""
             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    B, S, _ = x.shape
    dh = cfg.head_dim_
    dtype = x.dtype
    q = jnp.einsum("bsd,dh->bsh", x, p[prefix + "wq"].astype(dtype))
    k = jnp.einsum("bsd,dh->bsh", x, p[prefix + "wk"].astype(dtype))
    v = jnp.einsum("bsd,dh->bsh", x, p[prefix + "wv"].astype(dtype))
    if cfg.qkv_bias:
        q = q + p[prefix + "bq"].astype(dtype)
        k = k + p[prefix + "bk"].astype(dtype)
        v = v + p[prefix + "bv"].astype(dtype)
    q = q.reshape(B, S, cfg.padded_heads, dh)
    k = k.reshape(B, S, cfg.n_kv_heads, dh)
    v = v.reshape(B, S, cfg.n_kv_heads, dh)
    return q, k, v


def out_proj(o: jax.Array, p: dict, prefix: str = "") -> jax.Array:
    B, S = o.shape[:2]
    return jnp.einsum("bsh,hd->bsd", o.reshape(B, S, -1),
                      p[prefix + "wo"].astype(o.dtype))


def _qkv_specs(cfg: ModelConfig, ctx: ExecContext, seq_axis):
    h_ax = ctx.shardable(cfg.padded_heads, ctx.tp_axis)
    kv_ax = ctx.shardable(cfg.n_kv_heads, ctx.tp_axis)
    return h_ax, kv_ax, seq_axis


def _ring_pad(x: jax.Array, pos: jax.Array, n: int):
    """Pad a chunk operand (axis 1) and its (B, S) positions up to a
    multiple of the ring size ``n``, which the shard_map ring islands need.
    Padded slots sit at ``POS_PAD``, past every real position: as causal
    keys they are masked for every real query, and their own query rows
    are sliced off by the caller.  A Pallas kernel cannot be partitioned
    by GSPMD, so on TPU a sequence-sharded chunk must ride the ring."""
    pad = (-x.shape[1]) % n
    if not pad:
        return x, pos
    x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
    pos = jnp.pad(jnp.broadcast_to(pos, (x.shape[0], pos.shape[-1])),
                  [(0, 0), (0, pad)], constant_values=POS_PAD)
    return x, pos


def attention_block(x: jax.Array, p: dict, cfg: ModelConfig,
                    ctx: ExecContext, positions: jax.Array, mode: str,
                    cache: Optional[dict] = None,
                    cache_len: Optional[jax.Array] = None,
                    window: Optional[int] = None,
                    causal: bool = True, prefix: str = "",
                    history: Optional[dict] = None):
    """Returns (out, new_cache_or_None).

    positions: (B, S) int32 (or (3, B, S) for M-RoPE) in storage order.
    decode: x is (B, 1, d); cache_len (B,); the cache is either
      * dense — {"k","v"}: (B, S_max, KVH, D), or
      * paged — {"k","v","block_table"} where k/v are physical pools
        (n_pages, page, KVH, D) and block_table is (B, pages_per_seq)
        int32 page ids (the serving engine's BlockManager layout).  The
        decode tick is FUSED: one ``ops.paged_decode_attention`` call
        writes the new token's K/V into its page slot AND attends off the
        pool (Pallas scalar-prefetch kernel on TPU, gather fallback
        elsewhere) — no dense (B, max_seq) view, no scatter-then-gather
        over the same page.  With a ``"layer"`` entry the k/v are the
        layer-stacked pools (n_blocks, n_pages, page, KVH, D) that the
        decode layer scan carries, and the call writes and reads layer
        ``layer`` of them in place (models/transformer._stack_forward,
        which donates them); the updated stacked pools come back.
        A *sharded* paged cache — pools (n_shards, blocks_per_shard + 1,
        page, KVH, D) split over ctx.kv_split_axis, block_table
        (n_shards, B, npg_local) per-shard local ids — runs as a split-KV
        shard_map island (per-shard partial softmax over device-local
        pages with native stripe-position length/window masks + LSE
        merge; core/ring_attention.sharded_paged_decode).  When KVH
        divides ctx.tp_axis the pool is additionally HEAD-SHARDED (the
        TP×SP layout, ExecContext.pool_head_axis): each device stores
        only its KVH/tp slice and the island consumes it directly.
    history (CDSP chunked prefill), two layouts:
      * dense — {"k","v","pos"}: previous chunks' KV, already re-balanced
        (evenly re-sharded) over the current chunk's group; position-array
        masking makes the cross-chunk causal mask automatic.
      * paged — {"k_pool","v_pool","block_table","len"}: previous chunks'
        KV in physical pages in natural token order (the serving engine's
        prefill-direct-to-pages path, core/cdsp.pages_history_view); the
        chunk attends through the table via ops.paged_prefill_attention.
        Under ctx.sp_axis with the sharded pool layout, history pages
        rotate through the ring alongside the chunk's own KV shards
        (core/ring_attention.ring_paged_prefill).
    """
    B, S, _ = x.shape
    q, k, v = qkv_proj(x, p, cfg, prefix)
    q = apply_rope(q, positions, cfg)
    k = apply_rope(k, positions, cfg)

    h_ax, kv_ax, _ = _qkv_specs(cfg, ctx, None)
    pos2d = positions[0] if positions.ndim == 3 else positions

    if mode == "decode" and cache is not None and "block_table" in cache:
        # native block-table paged decode: one fused invocation appends
        # this token's K/V into its physical page AND attends over the
        # pool through the table.  Rows whose table points at the scratch
        # page (inactive batch slots) write and read garbage that no
        # caller consumes.
        assert cache_len is not None
        qd = q[:, 0]                                         # (B, H, D)
        if cache["block_table"].ndim == 3:
            # sharded pool layout: split-KV paged decode island — the
            # append lands on the shard owning the target page (fused with
            # the attend), each shard attends its own pages, partials
            # merge by LSE.  kv_ax marks the pool head-sharded over TP
            # (same rule as PagedKVCache construction via
            # ExecContext.pool_head_axis).
            assert ctx.kv_split_axis is not None and ctx.mesh is not None, \
                "a sharded paged cache needs ctx.kv_split_axis and a mesh"
            o, k_pool, v_pool = sharded_paged_decode(
                qd, cache["k"], cache["v"], cache["block_table"], cache_len,
                mesh=ctx.mesh, split_axis=ctx.kv_split_axis,
                batch_axis=ctx.batch_axes,
                head_axis=kv_ax if h_ax is not None else None,
                window=window,
                impl=ctx.impl, k_new=k[:, 0], v_new=v[:, 0],
                active_shards=ctx.active_pool_shards)
            out = out_proj(o[:, None], p, prefix)
            return out, {"k": k_pool, "v": v_pool,
                         "block_table": cache["block_table"]}
        if (ctx.kv_split_axis is not None and ctx.mesh is not None
                and ctx.axis_size(ctx.kv_split_axis) > 1):
            # an UNSHARDED pool under split-KV decode would make GSPMD
            # silently replicate the whole pool per device — demand the
            # sharded layout instead (it exists now: PagedKVCache with
            # kv_shards > 1 produces the 3-dim local tables)
            raise ValueError(
                "paged decode with ExecContext.kv_split_axis="
                f"{ctx.kv_split_axis!r} needs the SHARDED pool layout "
                "(pools (n_shards, blocks_per_shard + 1, page, KVH, D), "
                "block_table (n_shards, B, npg_local) — build the "
                "PagedKVCache with kv_shards > 1), got an unsharded "
                "2-dim block table; running it would silently replicate "
                "the whole pool on every device.  Either hand over the "
                "sharded layout or run with ctx.with_(kv_split_axis"
                "=None).")
        bt = cache["block_table"]                            # (B, npg) int32
        page = cache["k"].shape[-3]
        bidx = jnp.arange(B)
        # fused append+attend: rebind the pools that come back
        o, k_pool, v_pool = ops.paged_decode_attention(
            qd, cache["k"], cache["v"], bt, cache_len, window=window,
            impl=ctx.impl, k_new=k[:, 0], v_new=v[:, 0],
            append_page=bt[bidx, cache_len // page],
            append_slot=cache_len % page, layer=cache.get("layer"))
        out = out_proj(o[:, None], p, prefix)
        return out, {"k": k_pool, "v": v_pool, "block_table": bt}

    if mode == "decode":
        assert cache is not None and cache_len is not None
        qd = q[:, 0]                                         # (B, H, D)
        S_max = cache["k"].shape[1]
        if (ctx.ring_cache and window is not None and S_max <= window):
            # ring-buffer SWA cache: the buffer holds exactly the last
            # S_max(=window) tokens; attention is permutation-invariant so
            # slot order is irrelevant once the buffer wraps.
            bidx = jnp.arange(B)
            slot = cache_len % S_max
            k_cache = cache["k"].at[bidx, slot].set(
                k[:, 0].astype(cache["k"].dtype))
            v_cache = cache["v"].at[bidx, slot].set(
                v[:, 0].astype(cache["v"].dtype))
            o = ops.decode_attention(qd, k_cache, v_cache,
                                     jnp.minimum(cache_len + 1, S_max),
                                     impl=ctx.impl)
            out = out_proj(o[:, None], p, prefix)
            return out, {"k": k_cache, "v": v_cache}
        if (ctx.window_slice and window is not None
                and S_max >= 4 * window):
            # windowed decode: persist the new KV into the (sharded) full
            # buffer, but ATTEND only over the last `window` tokens — turns
            # an O(S_max) cache stream into O(window) per step.
            if ctx.kv_split_axis is not None and ctx.mesh is not None:
                k_cache, v_cache = sharded_cache_update(
                    cache["k"], cache["v"], k[:, 0], v[:, 0], cache_len,
                    mesh=ctx.mesh, split_axis=ctx.kv_split_axis,
                    batch_axis=ctx.batch_axes)
            else:
                bidx = jnp.arange(B)
                k_cache = cache["k"].at[bidx, cache_len].set(
                    k[:, 0].astype(cache["k"].dtype))
                v_cache = cache["v"].at[bidx, cache_len].set(
                    v[:, 0].astype(cache["v"].dtype))
            wbuf = window + 8
            start = jnp.clip(cache_len - (wbuf - 1), 0, S_max - wbuf)
            k_win = jax.vmap(
                lambda c, s: jax.lax.dynamic_slice_in_dim(c, s, wbuf, 0)
            )(k_cache, start)
            v_win = jax.vmap(
                lambda c, s: jax.lax.dynamic_slice_in_dim(c, s, wbuf, 0)
            )(v_cache, start)
            o = ops.decode_attention(qd, k_win, v_win,
                                     cache_len + 1 - start,
                                     window=window, impl=ctx.impl)
            out = out_proj(o[:, None], p, prefix)
            return out, {"k": k_cache, "v": v_cache}
        if ctx.kv_split_axis is not None and ctx.mesh is not None:
            # scatter + attention inside the sharded island so the cache
            # never leaves its (batch, seq-split) layout
            o, k_cache, v_cache = split_kv_decode(
                qd, cache["k"], cache["v"], cache_len, mesh=ctx.mesh,
                split_axis=ctx.kv_split_axis, batch_axis=ctx.batch_axes,
                window=window, impl=ctx.impl,
                k_new=k[:, 0], v_new=v[:, 0])
        else:
            bidx = jnp.arange(B)
            k_cache = cache["k"].at[bidx, cache_len].set(
                k[:, 0].astype(cache["k"].dtype))
            v_cache = cache["v"].at[bidx, cache_len].set(
                v[:, 0].astype(cache["v"].dtype))
            o = ops.decode_attention(qd, k_cache, v_cache, cache_len + 1,
                                     window=window, impl=ctx.impl)
        out = out_proj(o[:, None], p, prefix)
        return out, {"k": k_cache, "v": v_cache}

    if mode == "cross_decode":
        # cross attention with a fixed precomputed cache (whisper decoder)
        assert cache is not None
        S_x = cache["k"].shape[1]
        lengths = jnp.full((B,), S_x, jnp.int32)
        qd = q[:, 0]
        o = ops.decode_attention(qd, cache["k"], cache["v"], lengths,
                                 impl=ctx.impl)
        return out_proj(o[:, None], p, prefix), cache

    # train / prefill / encoder self-attention / cross-attention
    if mode == "cross":
        # q from x; k/v from the "cache" (precomputed cross KV)
        o = ops.attention(q, cache["k"], cache["v"],
                          q_pos=pos2d,
                          kv_pos=jnp.arange(cache["k"].shape[1], dtype=jnp.int32),
                          causal=False, impl=ctx.impl)
        return out_proj(o, p, prefix), cache

    k_self, v_self = k, v
    kv_pos = pos2d
    if history is not None and "block_table" in history:
        # paged cross-chunk history (CDSP prefill-direct-to-pages): the
        # previous chunks' KV lives in physical pages in natural token
        # order; attend over [pages ++ own chunk] through the block table
        # without ever gathering a dense history view (Pallas
        # paged_flash_prefill + merge on TPU, gather fallback elsewhere).
        sp_n = (ctx.axis_size(ctx.sp_axis)
                if ctx.sp_axis is not None and ctx.mesh is not None else 1)
        if history["block_table"].ndim == 2 and sp_n > 1:
            # mirror of the decode-side guard: an UNSHARDED history pool
            # under ring attention would be all-gathered onto every
            # device each chunk — demand the sharded layout
            raise ValueError(
                "paged cross-chunk history under ring attention "
                f"(ExecContext.sp_axis={ctx.sp_axis!r}) needs the "
                "SHARDED pool layout (PagedKVCache with kv_shards > 1; "
                "block_table (n_shards, B, npg_local)), got an unsharded "
                "2-dim block table; running it would replicate the whole "
                "history pool on every device.  Either hand over the "
                "sharded layout or run with ctx.with_(sp_axis=None).")
        if (history["block_table"].ndim == 3 and sp_n > 1
                and (causal or S % sp_n == 0)):
            # sharded pool + ring attention: the chunk's queries/KV ride
            # the ring as usual and each shard's history pages rotate
            # along with them — no dense history view, no page migration
            qr, qpos = _ring_pad(q, pos2d, sp_n)
            kr, _ = _ring_pad(k, pos2d, sp_n)
            vr, _ = _ring_pad(v, pos2d, sp_n)
            o = ring_paged_prefill(
                qr, kr, vr, qpos, qpos, history["k_pool"],
                history["v_pool"], history["block_table"], history["len"],
                mesh=ctx.mesh, sp_axis=ctx.sp_axis, head_axis=h_ax,
                kv_head_axis=kv_ax if h_ax is not None else None,
                batch_axis=ctx.pod_axis, causal=causal,
                window=window, impl=ctx.impl,
                active_shards=ctx.active_pool_shards)[:, :S]
        else:
            # single-group chunk, or a non-causal chunk that does not
            # divide over the ring: the paged prefill op.  A sharded pool
            # here has only the gather oracle (impl "ref"; the Pallas
            # impls raise), whose logical-order view stripes over exactly
            # the table's leading rows, so an elastically narrowed pool
            # hands over only its active rows
            bt = history["block_table"]
            if bt.ndim == 3 and ctx.active_pool_shards:
                bt = bt[:min(ctx.active_pool_shards, bt.shape[0])]
            o = ops.paged_prefill_attention(
                q, k, v, pos2d, pos2d, history["k_pool"], history["v_pool"],
                bt, history["len"], causal=causal,
                window=window, impl=ctx.impl)
        out = out_proj(o, p, prefix)
        return out, ({"k": k_self, "v": v_self} if mode == "prefill"
                     else None)
    if history is not None:
        dtype = k.dtype
        k = jnp.concatenate([history["k"].astype(dtype), k], axis=1)
        v = jnp.concatenate([history["v"].astype(dtype), v], axis=1)
        hpos = history["pos"]
        if hpos.ndim == 1:
            hpos = jnp.broadcast_to(hpos[None], (B, hpos.shape[0]))
        kv_pos = jnp.concatenate([hpos, pos2d], axis=1)

    sp_n = (ctx.axis_size(ctx.sp_axis)
            if ctx.sp_axis is not None and ctx.mesh is not None else 1)
    even = S % sp_n == 0 and k.shape[1] % sp_n == 0
    if sp_n > 1 and (causal or even):
        qr, qpos = _ring_pad(q, pos2d, sp_n)
        kr, kpos = _ring_pad(k, kv_pos, sp_n)
        vr, _ = _ring_pad(v, kv_pos, sp_n)
        o = ring_attention(qr, kr, vr, qpos, kpos, mesh=ctx.mesh,
                           sp_axis=ctx.sp_axis, head_axis=h_ax,
                           kv_head_axis=kv_ax, batch_axis=ctx.pod_axis,
                           causal=causal, window=window,
                           impl=ctx.impl,
                           zigzag_skip=(ctx.zigzag_skip and history is None
                                        and even))[:, :S]
    else:
        o = ops.attention(q, k, v, pos2d, kv_pos, causal=causal,
                          window=window, impl=ctx.impl)
    out = out_proj(o, p, prefix)
    new_cache = {"k": k_self, "v": v_self} if mode == "prefill" else None
    return out, new_cache
