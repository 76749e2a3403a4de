"""Ring attention (sequence-parallel distributed attention) via shard_map.

The paper's prefill engine: the sequence is sharded across the SP axis; each
device computes flash attention of its local queries against the KV shard it
currently holds, then rotates the KV shard to its ring neighbour with
``lax.ppermute`` (the TPU-native analogue of the paper's NVSHMEM P2P).  After
``n`` steps every query has seen every key.  Partial results are merged with
log-sum-exp statistics.

The ring loop is unrolled in Python (n = mesh-axis size is static), which
lets XLA overlap the next permute with the current block's compute — the
"communication hidden behind attention" property the paper relies on — and
avoids a wasted final rotation.

Masking is position-array driven (see kernels/), so the zigzag layout and
CDSP historical-KV chunks need no special-casing here.

Also provides the decode-side split-KV attention (flash-decode over a
sequence-sharded cache with LSE merge over the shard axis) and the
sequence-parallel SSD scan (Mamba-2) with a ppermute prefix-scan of the
cross-shard recurrent state.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from repro.kernels import ops

NEG_INF = -1e30


def _merge(o, lse, o_i, lse_i):
    """Merge running (o, lse) with a new partial block (fp32)."""
    lse_new = jnp.logaddexp(lse, lse_i)
    w_old = jnp.exp(lse - lse_new)
    w_new = jnp.exp(lse_i - lse_new)
    o = (o * w_old.transpose(0, 2, 1)[..., None]
         + o_i.astype(jnp.float32) * w_new.transpose(0, 2, 1)[..., None])
    return o, lse_new


def ring_attention_local(q, k, v, q_pos, kv_pos, *, axis_name: str,
                         causal: bool = True, window: Optional[int] = None,
                         softmax_scale=None, impl: Optional[str] = None,
                         head_shard_axis: Optional[str] = None,
                         zigzag_skip: bool = False):
    """Per-shard body (call inside shard_map). Shapes are local shards.

    q: (B, S_loc, H_loc, D); k/v: (B, S_loc, KVH, D); pos: (B, S_loc).

    When q heads are sharded over ``head_shard_axis`` (TP) but the KV heads
    are replicated (GQA with n_kv < tp), each device slices out just the KV
    head(s) its local q-head group needs before entering the ring — so ring
    traffic carries each KV head group/H_loc times instead of tp times.
    Requires H_loc | group or group | H_loc (holds for every config in the
    pool; asserted).
    """
    if head_shard_axis is not None:
        tp = lax.psum(1, head_shard_axis)
        H_loc, KVH_full = q.shape[2], k.shape[2]
        group_global = (H_loc * tp) // KVH_full
        if tp > 1 and KVH_full > 1 and group_global > 1:
            n_kv_loc = max(1, H_loc // group_global)
            assert (group_global % H_loc == 0) or (H_loc % group_global == 0), \
                (H_loc, group_global)
            idx = lax.axis_index(head_shard_axis)
            start = (idx * H_loc) // group_global
            k = lax.dynamic_slice_in_dim(k, start, n_kv_loc, axis=2)
            v = lax.dynamic_slice_in_dim(v, start, n_kv_loc, axis=2)
    n = lax.psum(1, axis_name)  # static under shard_map
    perm = [(j, (j + 1) % n) for j in range(n)]

    if zigzag_skip and causal and window is None and n > 1 \
            and q.shape[1] == k.shape[1] and q.shape[1] % 2 == 0:
        return _ring_zigzag_skip(q, k, v, q_pos, kv_pos, axis_name=axis_name,
                                 n=n, perm=perm,
                                 softmax_scale=softmax_scale, impl=impl)

    o = jnp.zeros(q.shape, jnp.float32)
    lse = jnp.full((q.shape[0], q.shape[2], q.shape[1]), NEG_INF, jnp.float32)
    k_c, v_c, kvp_c = k, v, kv_pos
    for step in range(n):
        o_i, lse_i = ops.attention(q, k_c, v_c, q_pos, kvp_c, causal=causal,
                                   window=window, softmax_scale=softmax_scale,
                                   with_lse=True, impl=impl)
        o, lse = _merge(o, lse, o_i, lse_i)
        if step != n - 1:
            k_c = lax.ppermute(k_c, axis_name, perm)
            v_c = lax.ppermute(v_c, axis_name, perm)
            kvp_c = lax.ppermute(kvp_c, axis_name, perm)
    return o.astype(q.dtype), lse


def _ring_zigzag_skip(q, k, v, q_pos, kv_pos, *, axis_name, n, perm,
                      softmax_scale, impl):
    """Causal-skip ring attention for the zigzag layout (beyond-paper perf).

    With zigzag, device d's queries are slices {d, 2n-1-d} ("early"/"late")
    and the KV arriving at ring step t originates from device j=(d-t)%n with
    slices {j, 2n-1-j}.  Causality then implies, for t>0:
      q_late  x kv_early : always fully visible      (computed every step)
      q_early x kv_early : visible iff j < d     \\  exactly one of these,
      q_late  x kv_late  : visible iff j > d     /   selected by jnp.where
      q_early x kv_late  : never visible             (skipped)
    so every device does exactly HALF the pair-work of the naive ring at
    every non-local step — an SPMD-uniform program (the branch is a data
    select, not control flow).  Step t=0 (the local diagonal) runs the plain
    causal path.  Correctness of the diagonal/selection masking falls out of
    position-array masking.  ~2x attention FLOP/byte reduction; validated in
    tests/dist_progs/ring_attention_prog.py.
    """
    B, S, H, D = q.shape
    half = S // 2
    d_idx = lax.axis_index(axis_name)

    def halves(x, axis=1):
        return (lax.slice_in_dim(x, 0, half, axis=axis),
                lax.slice_in_dim(x, half, S, axis=axis))

    q_e, q_l = halves(q)
    qp_e, qp_l = halves(q_pos)
    acc = {
        "e": (jnp.zeros(q_e.shape, jnp.float32),
              jnp.full((B, H, half), NEG_INF, jnp.float32)),
        "l": (jnp.zeros(q_l.shape, jnp.float32),
              jnp.full((B, H, half), NEG_INF, jnp.float32)),
    }
    k_c, v_c, kvp_c = k, v, kv_pos
    for t in range(n):
        if t == 0:
            o_i, lse_i = ops.attention(q, k_c, v_c, q_pos, kvp_c,
                                       causal=True,
                                       softmax_scale=softmax_scale,
                                       with_lse=True, impl=impl)
            oi_e, oi_l = halves(o_i)
            li_e, li_l = halves(lse_i, axis=2)
            acc["e"] = _merge(*acc["e"], oi_e, li_e)
            acc["l"] = _merge(*acc["l"], oi_l, li_l)
        else:
            k_e, k_l = halves(k_c)
            v_e, v_l = halves(v_c)
            kp_e, kp_l = halves(kvp_c)
            # A: q_late x kv_early — always fully visible
            o_a, lse_a = ops.attention(q_l, k_e, v_e, qp_l, kp_e,
                                       causal=True,
                                       softmax_scale=softmax_scale,
                                       with_lse=True, impl=impl)
            acc["l"] = _merge(*acc["l"], o_a, lse_a)
            # B: (q_early x kv_early) if j < d else (q_late x kv_late)
            j = (d_idx - t) % n
            pred = j < d_idx
            q_b = jnp.where(pred, q_e, q_l)
            qp_b = jnp.where(pred, qp_e, qp_l)
            k_b = jnp.where(pred, k_e, k_l)
            v_b = jnp.where(pred, v_e, v_l)
            kp_b = jnp.where(pred, kp_e, kp_l)
            o_b, lse_b = ops.attention(q_b, k_b, v_b, qp_b, kp_b,
                                       causal=True,
                                       softmax_scale=softmax_scale,
                                       with_lse=True, impl=impl)
            acc["e"] = _merge(*acc["e"], o_b,
                              jnp.where(pred, lse_b, NEG_INF))
            acc["l"] = _merge(*acc["l"], o_b,
                              jnp.where(pred, NEG_INF, lse_b))
        if t != n - 1:
            k_c = lax.ppermute(k_c, axis_name, perm)
            v_c = lax.ppermute(v_c, axis_name, perm)
            kvp_c = lax.ppermute(kvp_c, axis_name, perm)
    o = jnp.concatenate([acc["e"][0], acc["l"][0]], axis=1)
    lse = jnp.concatenate([acc["e"][1], acc["l"][1]], axis=2)
    return o.astype(q.dtype), lse


def ring_attention(q, k, v, q_pos, kv_pos, *, mesh, sp_axis: str,
                   head_axis: Optional[str] = None,
                   kv_head_axis: Optional[str] = None,
                   batch_axis=None,
                   causal: bool = True, window: Optional[int] = None,
                   softmax_scale=None, impl: Optional[str] = None,
                   zigzag_skip: bool = False):
    """Global-view ring attention.  Sequence dims sharded over ``sp_axis``;
    optionally heads over ``head_axis`` (TP), batch over ``batch_axis``
    (multi-pod).  ``zigzag_skip`` enables the causal block-skip fast path
    (valid only when the storage layout is zigzag).  Returns (B, S, H, D)."""
    q_spec = P(batch_axis, sp_axis, head_axis, None)
    kv_spec = P(batch_axis, sp_axis, kv_head_axis, None)
    pos_spec = P(batch_axis, sp_axis)
    body = partial(ring_attention_local, axis_name=sp_axis, causal=causal,
                   window=window, softmax_scale=softmax_scale, impl=impl,
                   head_shard_axis=(head_axis if kv_head_axis is None
                                    else None),
                   zigzag_skip=zigzag_skip)

    def f(q, k, v, qp, kvp):
        o, _ = body(q, k, v, qp, kvp)
        return o

    return shard_map(
        f, mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec, pos_spec, pos_spec),
        out_specs=q_spec, check_vma=False,
    )(q, k, v, q_pos, kv_pos)


# --------------------------------------------------------------- decode side
def _axis_index_multi(axis_name):
    """axis_index for a single axis or a collapsed tuple of axes."""
    if isinstance(axis_name, str):
        return lax.axis_index(axis_name)
    idx = 0
    for a in axis_name:
        idx = idx * lax.psum(1, a) + lax.axis_index(a)
    return idx


def split_kv_decode_local(q, k_loc, v_loc, lengths, *, axis_name,
                          window: Optional[int] = None, softmax_scale=None,
                          impl: Optional[str] = None):
    """Per-shard flash-decode over a sequence-sharded KV cache.

    q: (B_loc, H, D) replicated over ``axis_name``; k/v: (B_loc, S_loc, KVH, D)
    holding shard ``axis_index``; lengths: (B_loc,) global valid lengths.
    The paper's decode insight — ship the (tiny) queries to the KV, never the
    KV to the queries — expressed as split-KV + LSE-merge over the axis.
    ``axis_name`` may be a tuple of mesh axes (collapsed split, used when the
    batch is too small to occupy the data axis, e.g. long_500k)."""
    idx = _axis_index_multi(axis_name)
    s_loc = k_loc.shape[1]
    offset = idx * s_loc
    local_len = jnp.clip(lengths - offset, 0, None)
    o_i, lse_i = ops.decode_attention(q, k_loc, v_loc, local_len,
                                      window=window,
                                      softmax_scale=softmax_scale,
                                      with_lse=True, impl=impl)
    # window masking must be global: re-mask via global positions is handled
    # by shifting lengths; a window that straddles shards is applied inside
    # decode_attention through (local_len - window).  For shards entirely
    # below the window, local_len-window >= s_loc masks everything.
    o = _lse_merge_over_axis(o_i, lse_i, axis_name)
    return o.astype(q.dtype)


def split_kv_decode(q, k_cache, v_cache, lengths, *, mesh, split_axis,
                    batch_axis: Optional[str] = None,
                    window: Optional[int] = None, softmax_scale=None,
                    impl: Optional[str] = None,
                    k_new: Optional[jax.Array] = None,
                    v_new: Optional[jax.Array] = None):
    """q: (B, H, D); caches: (B, S, KVH, D) sharded (batch_axis, split_axis).

    When (k_new, v_new): (B, KVH, D) are given, the new token's KV is
    scattered into the cache INSIDE the island — the write lands on whichever
    shard owns position ``lengths`` and the cache never leaves its sharded
    layout (a global-view scatter would force GSPMD to unshard the sequence
    dim).  ``lengths`` must then be the length EXCLUDING the new token;
    attention runs over lengths+1.  Returns (o, k_cache, v_cache).
    """
    if k_new is None:
        body = partial(split_kv_decode_local, axis_name=split_axis,
                       window=window, softmax_scale=softmax_scale, impl=impl)
        return shard_map(
            body, mesh=mesh,
            in_specs=(P(batch_axis, None, None),
                      P(batch_axis, split_axis, None, None),
                      P(batch_axis, split_axis, None, None), P(batch_axis,)),
            out_specs=P(batch_axis, None, None), check_vma=False,
        )(q, k_cache, v_cache, lengths)

    def body(q, k_loc, v_loc, lengths, k_new, v_new):
        idx = _axis_index_multi(split_axis)
        s_loc = k_loc.shape[1]
        B = k_loc.shape[0]
        local_pos = lengths - idx * s_loc                    # (B,)
        in_range = (local_pos >= 0) & (local_pos < s_loc)
        safe = jnp.clip(local_pos, 0, s_loc - 1)
        bidx = jnp.arange(B)
        old_k = k_loc[bidx, safe]
        old_v = v_loc[bidx, safe]
        sel = in_range[:, None, None]
        k_loc = k_loc.at[bidx, safe].set(
            jnp.where(sel, k_new.astype(k_loc.dtype), old_k))
        v_loc = v_loc.at[bidx, safe].set(
            jnp.where(sel, v_new.astype(v_loc.dtype), old_v))
        o = split_kv_decode_local(q, k_loc, v_loc, lengths + 1,
                                  axis_name=split_axis, window=window,
                                  softmax_scale=softmax_scale, impl=impl)
        return o, k_loc, v_loc

    cache_spec = P(batch_axis, split_axis, None, None)
    return shard_map(
        body, mesh=mesh,
        in_specs=(P(batch_axis, None, None), cache_spec, cache_spec,
                  P(batch_axis,), P(batch_axis, None, None),
                  P(batch_axis, None, None)),
        out_specs=(P(batch_axis, None, None), cache_spec, cache_spec),
        check_vma=False,
    )(q, k_cache, v_cache, lengths, k_new, v_new)


def sharded_cache_update(k_cache, v_cache, k_new, v_new, positions, *,
                         mesh, split_axis, batch_axis=None):
    """Scatter one token's KV into a sequence-sharded cache without leaving
    the sharded layout (the write lands on whichever shard owns
    ``positions``).  Used by the windowed-decode fast path."""
    def body(k_loc, v_loc, k_new, v_new, positions):
        idx = _axis_index_multi(split_axis)
        s_loc = k_loc.shape[1]
        B = k_loc.shape[0]
        local_pos = positions - idx * s_loc
        in_range = (local_pos >= 0) & (local_pos < s_loc)
        safe = jnp.clip(local_pos, 0, s_loc - 1)
        bidx = jnp.arange(B)
        sel = in_range[:, None, None]
        k_loc = k_loc.at[bidx, safe].set(
            jnp.where(sel, k_new.astype(k_loc.dtype), k_loc[bidx, safe]))
        v_loc = v_loc.at[bidx, safe].set(
            jnp.where(sel, v_new.astype(v_loc.dtype), v_loc[bidx, safe]))
        return k_loc, v_loc

    cache_spec = P(batch_axis, split_axis, None, None)
    return shard_map(
        body, mesh=mesh,
        in_specs=(cache_spec, cache_spec, P(batch_axis, None, None),
                  P(batch_axis, None, None), P(batch_axis,)),
        out_specs=(cache_spec, cache_spec), check_vma=False,
    )(k_cache, v_cache, k_new, v_new, positions)


# ---------------------------------------------------- sharded paged decode
def _lse_merge_over_axis(o_i, lse_i, axis_name):
    """All-gather per-shard (o, lse) partials over ``axis_name`` and merge
    them by log-sum-exp — the split-KV combine shared by the dense and
    paged decode islands.  o_i: (B, H, D); lse_i: (B, H)."""
    o_all = lax.all_gather(o_i.astype(jnp.float32), axis_name)   # (n, B, H, D)
    lse_all = lax.all_gather(lse_i, axis_name)                   # (n, B, H)
    lse = jax.scipy.special.logsumexp(lse_all, axis=0)
    w = jnp.exp(lse_all - lse[None])
    return jnp.sum(o_all * w[..., None], axis=0)


def _local_page_slab(k_loc, v_loc, bt_loc, lengths, n, idx):
    """Assemble one shard's pages into a positional KV slab.

    Gathers the local pages in table order and computes each slot's
    GLOBAL token position from the stripe layout (local page j holds
    global page ``j * n + idx``); slots at/past the valid length —
    including scratch-padded table columns, whose computed positions are
    always past it — are pushed to INT32_MAX, where causal position
    masking retires them.  Returns (k_slab, v_slab, positions), each
    (B, npg_local * page, ...)."""
    B, npg = bt_loc.shape
    page = k_loc.shape[1]
    kg = k_loc[bt_loc].reshape(B, npg * page, *k_loc.shape[2:])
    vg = v_loc[bt_loc].reshape(B, npg * page, *v_loc.shape[2:])
    gpage = jnp.arange(npg, dtype=jnp.int32) * n + idx
    pos = (gpage[:, None] * page
           + jnp.arange(page, dtype=jnp.int32)[None]).reshape(-1)
    pos = jnp.broadcast_to(pos[None], (B, npg * page))
    pos = jnp.where(pos < lengths[:, None], pos, jnp.int32(2**31 - 1))
    return kg, vg, pos


def sharded_paged_decode_local(q, k_loc, v_loc, bt_loc, lengths, *,
                               axis_name, window: Optional[int] = None,
                               softmax_scale=None, impl: Optional[str] = None,
                               k_new=None, v_new=None,
                               active_shards: Optional[int] = None):
    """Per-shard body of the split-KV *paged* decode (call inside
    shard_map).

    k_loc/v_loc: (blocks_per_shard + 1, page, KVH, D) — this shard's slice
    of the striped pool (last page is scratch); bt_loc: (B, npg_local)
    local page ids, where column j is the sequence's logical page ``j * n
    + idx``; lengths: (B,) GLOBAL valid lengths (excluding the new token
    when ``k_new`` is given); q replicated over the axis.

    The new token's K/V is appended INSIDE the island by whichever shard
    owns the page that position ``lengths`` falls in (the others route the
    write to their scratch page), FUSED with the attend: the append and
    the per-shard paged decode run in one ``ops.paged_decode_attention``
    invocation with the pools donated, so each tick touches the pool once
    instead of scatter-then-gather over the same page.

    Length and sliding-``window`` masks are native to the stripe layout:
    table column j holds global page ``j * n + idx``, so the shard passes
    ``page_pos`` — each column's first-token GLOBAL position — and the
    kernel masks by global positions directly.  No positional gather slab,
    no contiguous local-length reduction; scratch-padded columns compute
    positions at/past the valid length and mask themselves.

    ``active_shards`` (default: the full axis) is the live stripe width
    of an elastically restriped pool — logical page i is on shard ``i %
    active_shards``.  Shards at index >= active_shards hold no pages:
    their lengths mask to zero, so every position is invalid, their
    partial merges with weight zero (lse = NEG_INF) and the append is
    routed to scratch.
    """
    n = lax.psum(1, axis_name) if active_shards is None else active_shards
    idx = lax.axis_index(axis_name)
    lengths = jnp.where(idx < n, lengths, 0)
    B, npg = bt_loc.shape
    page = k_loc.shape[1]
    scratch = k_loc.shape[0] - 1
    # native stripe masking: column j's first token sits at global
    # position (j*n+idx)*page
    gpage = jnp.arange(npg, dtype=jnp.int32) * n + idx      # (npg,)
    page_pos = jnp.broadcast_to((gpage * page)[None], (B, npg))
    if k_new is not None:
        tgt = lengths // page                               # global page (B,)
        own = (tgt % n) == idx
        bidx = jnp.arange(B)
        safe = jnp.clip(tgt // n, 0, npg - 1)
        phys = jnp.where(own, bt_loc[bidx, safe], scratch)
        o_i, lse_i, k_loc, v_loc = ops.paged_decode_attention(
            q, k_loc, v_loc, bt_loc, lengths, window=window,
            softmax_scale=softmax_scale, with_lse=True, impl=impl,
            page_pos=page_pos, k_new=k_new, v_new=v_new,
            append_page=phys, append_slot=lengths % page)
    else:
        o_i, lse_i = ops.paged_decode_attention(
            q, k_loc, v_loc, bt_loc, lengths, window=window,
            softmax_scale=softmax_scale, with_lse=True, impl=impl,
            page_pos=page_pos)
    o = _lse_merge_over_axis(o_i, lse_i, axis_name)
    return o.astype(q.dtype), k_loc, v_loc


def sharded_paged_decode(q, k_pool, v_pool, block_tables, lengths, *,
                         mesh, split_axis: str, batch_axis=None,
                         head_axis: Optional[str] = None,
                         window: Optional[int] = None, softmax_scale=None,
                         impl: Optional[str] = None,
                         k_new=None, v_new=None,
                         active_shards: Optional[int] = None):
    """Split-KV decode over a sequence-parallel *sharded paged* pool.

    q: (B, H, D); k_pool/v_pool: (n, blocks_per_shard + 1, page, KVH, D)
    sharded over ``split_axis`` on the leading device axis (the serving
    engine's striped PagedKVCache layout); block_tables: (n, B, npg_local)
    per-shard local page ids; lengths: (B,) global cache lengths EXCLUDING
    the new token when (k_new, v_new): (B, KVH, D) are given — the append
    happens inside the island on the owning shard, fused with the attend,
    so pages never leave their device and each tick touches the pool once.
    Returns (o, k_pool, v_pool).  This is the paged twin of
    ``split_kv_decode``: per-shard partial softmax over device-local pages
    + LSE merge across the axis.  ``active_shards`` narrows the stripe to
    the first so-many shards of the axis (elastic restriping) — the
    block_tables rows past it must be all-scratch
    (cache_manager.shard_block_table with ``n_slots``).

    ``head_axis`` (TP) additionally shards the pool's KVH axis, plus the
    head axes of q / k_new / v_new / o: each device stores and touches
    only its ``KVH / tp`` slice (the head-sharded PagedKVCache layout).
    Pass it only when KVH divides the axis — the per-shard body maps local
    q-head groups onto local kv heads positionally, so q and KV must be
    sliced by the SAME head partition.
    """
    body = partial(sharded_paged_decode_local, axis_name=split_axis,
                   window=window, softmax_scale=softmax_scale, impl=impl,
                   active_shards=active_shards)
    pool_spec = P(split_axis, None, None, head_axis)
    bt_spec = P(split_axis, batch_axis, None)
    rep3 = P(batch_axis, head_axis, None)

    if k_new is None:
        def f(q, kp, vp, bt, ln):
            o, _, _ = body(q, kp[0], vp[0], bt[0], ln)
            return o
        return shard_map(
            f, mesh=mesh,
            in_specs=(rep3, pool_spec, pool_spec, bt_spec, P(batch_axis,)),
            out_specs=rep3, check_vma=False,
        )(q, k_pool, v_pool, block_tables, lengths)

    def f(q, kp, vp, bt, ln, kn, vn):
        o, k_loc, v_loc = body(q, kp[0], vp[0], bt[0], ln,
                               k_new=kn, v_new=vn)
        return o, k_loc[None], v_loc[None]

    return shard_map(
        f, mesh=mesh,
        in_specs=(rep3, pool_spec, pool_spec, bt_spec, P(batch_axis,),
                  rep3, rep3),
        out_specs=(rep3, pool_spec, pool_spec), check_vma=False,
    )(q, k_pool, v_pool, block_tables, lengths, k_new, v_new)


# ------------------------------------------------------- ring paged prefill
def ring_paged_prefill_local(q, k, v, q_pos, kv_pos, k_pool_loc, v_pool_loc,
                             bt_loc, hist_len, *, axis_name: str,
                             causal: bool = True,
                             window: Optional[int] = None,
                             softmax_scale=None, impl: Optional[str] = None,
                             head_shard_axis: Optional[str] = None,
                             active_shards: Optional[int] = None):
    """Per-shard body of CDSP chunk prefill against *sharded paged*
    history (call inside shard_map).

    q/k/v: the chunk's local sequence shard (B, S_loc, ·, D); pools: this
    shard's slice of the striped history pool; bt_loc: (B, npg_local)
    local page ids (logical page ``j * n + idx`` at column j); hist_len:
    (B,) global history tokens.

    Each shard assembles its history pages into a positional KV slab
    (natural-order positions fall out of the stripe layout; invalid /
    scratch slots are pushed to INT32_MAX where the causal mask kills
    them) and the ring then rotates BOTH the chunk's own KV shard and the
    history slab: after n steps every query has seen every own-chunk key
    and every history page, without any page leaving its owner.  Partials
    merge by LSE exactly like the dense ring.

    KV heads arrive in one of two layouts.  Head-sharded pool (the TP×SP
    PagedKVCache layout): the pool slice AND the chunk's own KV are
    already the device's ``KVH / tp`` head range (the caller's in_specs
    slice them), matching the local q-head group positionally — pass
    ``head_shard_axis=None`` and the body does no head slicing.  Legacy
    replicated pool (KVH not divisible by tp): KV arrives full-width and
    ``head_shard_axis`` makes each device slice out exactly the kv-head
    range its local q-head group reads — for both the own-chunk KV and
    the history pool — before entering the ring."""
    if head_shard_axis is not None:
        tp = lax.psum(1, head_shard_axis)
        H_loc, KVH_full = q.shape[2], k.shape[2]
        group_global = (H_loc * tp) // KVH_full
        if tp > 1 and KVH_full > 1:
            n_kv_loc = max(1, H_loc // group_global)
            idx_h = lax.axis_index(head_shard_axis)
            start = (idx_h * H_loc) // group_global
            k = lax.dynamic_slice_in_dim(k, start, n_kv_loc, axis=2)
            v = lax.dynamic_slice_in_dim(v, start, n_kv_loc, axis=2)
            # pool slice: (bps + 1, page, KVH, D) — heads on axis 2
            k_pool_loc = lax.dynamic_slice_in_dim(k_pool_loc, start,
                                                  n_kv_loc, axis=2)
            v_pool_loc = lax.dynamic_slice_in_dim(v_pool_loc, start,
                                                  n_kv_loc, axis=2)
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    perm = [(j, (j + 1) % n) for j in range(n)]
    # the ring always rotates over the FULL axis (the chunk's own KV is
    # sharded over every device) — only the history stripe narrows when
    # the pool is running on fewer active shards; idle shards contribute
    # an empty (fully masked) history slab
    n_hist = n if active_shards is None else active_shards
    hl = jnp.where(idx < n_hist, hist_len, 0)
    hk, hv, hpos = _local_page_slab(k_pool_loc, v_pool_loc, bt_loc,
                                    hl, n_hist, idx)

    o = jnp.zeros(q.shape, jnp.float32)
    lse = jnp.full((q.shape[0], q.shape[2], q.shape[1]), NEG_INF, jnp.float32)
    k_c, v_c, kvp_c = k, v, kv_pos
    hk_c, hv_c, hp_c = hk, hv, hpos
    for step in range(n):
        o_i, lse_i = ops.attention(q, k_c, v_c, q_pos, kvp_c, causal=causal,
                                   window=window, softmax_scale=softmax_scale,
                                   with_lse=True, impl=impl)
        o, lse = _merge(o, lse, o_i, lse_i)
        o_h, lse_h = ops.attention(q, hk_c, hv_c, q_pos, hp_c, causal=True,
                                   window=window, softmax_scale=softmax_scale,
                                   with_lse=True, impl=impl)
        o, lse = _merge(o, lse, o_h, lse_h)
        if step != n - 1:
            k_c = lax.ppermute(k_c, axis_name, perm)
            v_c = lax.ppermute(v_c, axis_name, perm)
            kvp_c = lax.ppermute(kvp_c, axis_name, perm)
            hk_c = lax.ppermute(hk_c, axis_name, perm)
            hv_c = lax.ppermute(hv_c, axis_name, perm)
            hp_c = lax.ppermute(hp_c, axis_name, perm)
    return o.astype(q.dtype), lse


def ring_paged_prefill(q, k, v, q_pos, kv_pos, k_pool, v_pool, block_tables,
                       hist_len, *, mesh, sp_axis: str,
                       head_axis: Optional[str] = None,
                       kv_head_axis: Optional[str] = None,
                       batch_axis=None, causal: bool = True,
                       window: Optional[int] = None, softmax_scale=None,
                       impl: Optional[str] = None,
                       active_shards: Optional[int] = None):
    """Global-view ring attention for a CDSP chunk whose cross-chunk
    history lives in a sequence-parallel sharded page pool.

    q/k/v sequence-sharded over ``sp_axis`` (the chunk itself); k_pool/
    v_pool (n, blocks_per_shard + 1, page, KVH, D) sharded over the same
    axis on the leading device axis; block_tables (n, B, npg_local);
    hist_len (B,).  History pages rotate through the ring alongside the
    chunk's own KV shards — this is what deletes the dense-history
    fallback for distributed chunks (models/attention.py).  Returns
    (B, S, H, D) sharded like the dense ring output.

    ``kv_head_axis`` (TP, requires KVH divisible by the axis) marks the
    pool as *head-sharded*: the pool's KVH axis and the own-chunk KV head
    axis are sharded over it, so each device's ring lane carries only its
    ``KVH / tp`` slice and the body never slices heads per call.  Leave
    it None for the legacy replicated pool (``head_axis`` alone then
    makes the body slice the kv-head range per device)."""
    q_spec = P(batch_axis, sp_axis, head_axis, None)
    # own-chunk KV rides the pool's head layout: sharded over
    # kv_head_axis for a head-sharded pool, else replicated full-width
    # (sliced per device inside the body when q heads are TP-sharded)
    kv_spec = P(batch_axis, sp_axis, kv_head_axis, None)
    pos_spec = P(batch_axis, sp_axis)
    pool_spec = P(sp_axis, None, None, kv_head_axis, None)
    bt_spec = P(sp_axis, None, None)
    body = partial(ring_paged_prefill_local, axis_name=sp_axis,
                   causal=causal, window=window, softmax_scale=softmax_scale,
                   impl=impl,
                   head_shard_axis=None if kv_head_axis else head_axis,
                   active_shards=active_shards)

    def f(q, k, v, qp, kvp, kp, vp, bt, ln):
        o, _ = body(q, k, v, qp, kvp, kp[0], vp[0], bt[0], ln)
        return o

    return shard_map(
        f, mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec, pos_spec, pos_spec,
                  pool_spec, pool_spec, bt_spec, P(batch_axis,)),
        out_specs=q_spec, check_vma=False,
    )(q, k, v, q_pos, kv_pos, k_pool, v_pool, block_tables, hist_len)


# ------------------------------------------------------ sequence-parallel SSD
def _ssd_scan_combine(a, b):
    """Compose segment summaries (decay, state): apply segment b after a."""
    da, sa = a
    db, sb = b
    return (da * db, sa * db[..., None, None] + sb)


def sp_ssd_local(x, dt, A, Bm, Cm, *, axis_name: str, chunk: int = 128,
                 h0=None, impl: Optional[str] = None):
    """Per-shard SSD with cross-shard recurrent state (contiguous layout).

    x: (B, S_loc, H, P) — the *contiguous* shard ``axis_index`` of the
    sequence.  A Hillis-Steele ppermute prefix scan composes the per-shard
    (decay, state) summaries so each shard starts from the correct incoming
    state; the local outputs are then corrected with the inter-chunk term.
    """
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    y0, s_local = ops.ssd(x, dt, A, Bm, Cm, h0=None, chunk=chunk, impl=impl)
    a_total = jnp.sum(dt.astype(jnp.float32) * A[None, None, :], axis=1)  # (B,H)
    d_local = jnp.exp(a_total)

    # inclusive prefix scan over (d, s)
    d, s = d_local, s_local
    offset = 1
    while offset < n:
        d_r = lax.ppermute(d, axis_name, [(j, (j + offset) % n) for j in range(n)])
        s_r = lax.ppermute(s, axis_name, [(j, (j + offset) % n) for j in range(n)])
        use = (idx >= offset)
        d_new, s_new = _ssd_scan_combine((d_r, s_r), (d, s))
        d = jnp.where(use, d_new, d)
        s = jnp.where(use, s_new[..., :, :], s)
        offset *= 2
    # exclusive: shift right by one shard
    d_in = lax.ppermute(d, axis_name, [(j, (j + 1) % n) for j in range(n)])
    s_in = lax.ppermute(s, axis_name, [(j, (j + 1) % n) for j in range(n)])
    h_in = jnp.where(idx == 0, jnp.zeros_like(s_in), s_in)       # (B,H,P,N)
    if h0 is not None:
        # incoming state from a previous CDSP chunk: compose in front
        d_excl = jnp.where(idx == 0, jnp.ones_like(d_in), d_in)
        h_in = h_in + h0.astype(jnp.float32) * d_excl[..., None, None]

    # correction: y += C_t exp(a_cum_t) h_in
    G = Bm.shape[2]
    rep = x.shape[2] // G
    Cf = jnp.repeat(Cm.astype(jnp.float32), rep, axis=2)         # (B,S,H,N)
    a_cum = jnp.cumsum(dt.astype(jnp.float32) * A[None, None, :], axis=1)
    y_corr = jnp.einsum("bshn,bsh,bhpn->bshp", Cf, jnp.exp(a_cum), h_in)
    y = (y0.astype(jnp.float32) + y_corr).astype(x.dtype)
    # final global state for this shard's prefix (used by chunked prefill)
    h_out = h_in * d_local[..., None, None] + s_local
    return y, h_out


def sp_ssd(x, dt, A, Bm, Cm, *, mesh, sp_axis: str, chunk: int = 128,
           h0=None, head_axis: Optional[str] = None, batch_axis=None,
           impl: Optional[str] = None):
    """Sequence-parallel SSD. x sharded (batch, sp, head_axis, None)."""
    body = partial(sp_ssd_local, axis_name=sp_axis, chunk=chunk, impl=impl)
    x_spec = P(batch_axis, sp_axis, head_axis, None)
    h_spec = P(batch_axis, head_axis, None, None)

    def f(x, dt, A, Bm, Cm, *maybe_h0):
        y, h = body(x, dt, A, Bm, Cm,
                    h0=maybe_h0[0] if maybe_h0 else None)
        # h is only correct on the LAST shard; select it.
        n = lax.psum(1, sp_axis)
        idx = lax.axis_index(sp_axis)
        h = jnp.where(idx == n - 1, h, 0.0)
        h = lax.psum(h, sp_axis)
        return y, h

    in_specs = [x_spec, P(batch_axis, sp_axis, head_axis),
                P(head_axis,), P(batch_axis, sp_axis, None, None),
                P(batch_axis, sp_axis, None, None)]
    args = [x, dt, A, Bm, Cm]
    if h0 is not None:
        in_specs.append(h_spec)
        args.append(h0)
    return shard_map(
        f, mesh=mesh, in_specs=tuple(in_specs),
        out_specs=(x_spec, h_spec), check_vma=False,
    )(*args)
