"""Pallas TPU flash-decoding: single-token attention over a long KV cache.

Decode attention is HBM-bandwidth bound (the whole KV cache is streamed once
per token), so the kernel's job is a clean sequential pipeline over KV blocks
with fp32 running statistics in VMEM — the Tetris/FlashDecoding pattern.
Grid is (batch, kv_blocks) with kv innermost; all heads of one sequence are
processed together ((H, D) easily fits VMEM).

Out-of-range cache slots are masked with per-sequence ``lengths``; a sliding
window (Mixtral / the beyond-paper long-context variant) masks slots older
than ``length - window``.  Blocks fully outside the valid range are skipped
via predication, which matters for continuous batching where sequence lengths
in a decode batch differ wildly.

Paged variants for the serving engine's block-table KV layout
(PagedAttention-style, pool (n_pages, page, KVH, D) + table (B, pages/seq)):

* ``paged_flash_decode`` — the same streaming kernel with the page table as
  a scalar-prefetch argument; the KV BlockSpec index map dereferences the
  table so each grid step DMAs the right physical page, in the pool's own
  ``(page, KVH, D)`` layout (no materialised dense copy, no relayout of
  the pool).  It also reads a layer-stacked pool ``(L, n_pages, page,
  KVH, D)`` at a traced ``layer``.  This is the TPU execution path behind
  ``ops.paged_decode_attention``, which the model's decode attention uses
  natively (models/attention.py); on CPU the gather fallback in
  ``kernels/ref.paged_decode_attention_ref`` takes over.
* ``scatter_kv_chunk`` — jitted XLA scatter that writes one prefill
  chunk's KV into pages at its *logical positions* (the production write
  path, via PagedKVCache.write_chunk: each CDSP chunk lands in pages the
  moment it completes — there is no dense per-request KV at any point).
  ``scatter_kv_prefill`` is the whole-sequence special case.
* ``copy_kv_blocks`` / ``copy_kv_block_within`` — page-granular block
  copies: prefill-pool -> decode-pool admission handoff, and the
  copy-on-write split of a shared block (serving/cache_manager.py).
* ``gather_kv_blocks`` / ``scatter_kv_blocks`` — device<->host staging for
  the host KV offload tier (serving/kv_offload.py): gather pulls a
  victim's pages off the device for a swap-out / demotion, scatter lands
  host pages back into the pool for a swap-in / prefix-cache promotion.
* ``paged_append_attend`` — the fused decode tick: writes the new token's
  K/V into its page AND attends in one jitted invocation (the production
  path behind ``ops.paged_decode_attention(..., k_new, v_new)`` and the
  sharded decode island) — the pool is touched once per tick, not
  scatter-then-gather.  Its own donation holds only when it is called on
  its own; inside the model's layer scan the decode step that donates the
  pools is ``models/transformer._stack_forward``.
* ``scatter_kv_token`` and ``gather_kv_pages`` are validation/debug
  helpers only; the production per-step append is the fused path above.

All pool-writing helpers donate their pool argument (``donate_argnums``):
the caller rebinds the result over the input, so XLA updates the pool
buffers in place instead of functionally rebuilding the (large) arrays on
every write — do NOT keep references to a pool you pass in.

Checked against kernels/ref.decode_attention_ref in interpret mode
(tests/test_kernels.py, tests/test_paged_engine.py) and compiled for a TPU
v5e at yi-9b widths (tests/test_tpu_compile.py); on TPU they run natively.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import (NEG_INF, check_head_dim,
                                           init_scratch, pad_seq, seq_block)


def _decode_step(q_ref, k_heads, v_heads, valid, acc_scr, m_scr, l_scr, *,
                scale: float, group: int):
    """One online-softmax step of single-token attention for all heads.

    q_ref: (1, H, D); k_heads/v_heads: one KV block's (bk, D) slice per KV
    head; valid: (1, bk).  Each KV head's scores are computed for every
    query row and kept for the rows of its group, so the body needs no
    sublane slicing whatever the group size; decode is bandwidth bound,
    so the redundant MXU work is free.  Scores, softmax weights and both
    matmuls are f32, as in kernels/ref.py."""
    q = q_ref[0].astype(jnp.float32) * scale                     # (H, D)
    head_kv = jax.lax.broadcasted_iota(jnp.int32, (q.shape[0], 1), 0) // group
    s = None
    for h, k_h in enumerate(k_heads):
        s_h = jax.lax.dot_general(q, k_h.astype(jnp.float32),
                                  (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        s = s_h if s is None else jnp.where(head_kv == h, s_h, s)
    s = jnp.where(valid, s, NEG_INF)                             # (H, bk)
    m_prev = m_scr[...]                                          # (H, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    pv = None
    for h, v_h in enumerate(v_heads):
        p_h = jnp.where(head_kv == h, p, 0.0)
        o_h = jax.lax.dot_general(p_h, v_h.astype(jnp.float32),
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        pv = o_h if pv is None else pv + o_h
    acc_scr[...] = acc_scr[...] * alpha + pv
    m_scr[...] = m_new


def _decode_finish(o_ref, lse_ref, acc_scr, m_scr, l_scr):
    l = l_scr[...]
    safe_l = jnp.where(l > 0.0, l, 1.0)
    o_ref[0] = (acc_scr[...] / safe_l).astype(o_ref.dtype)
    lse_ref[0] = jnp.where(l > 0.0, m_scr[...] + jnp.log(safe_l),
                           NEG_INF).astype(lse_ref.dtype)         # (H, 1)


def _decode_scratch(H: int, D: int):
    return [pltpu.VMEM((H, D), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32)]


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                   acc_scr, m_scr, l_scr,
                   *, scale: float, nk: int, bk: int, group: int, D: int,
                   window: Optional[int], kv_offset: int,
                   kv_len: Optional[int]):
    b = pl.program_id(0)
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        init_scratch(acc_scr, m_scr, l_scr)

    length = len_ref[b]
    idx = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    kv_pos = kv_offset + idx                                     # (1, bk)
    valid = kv_pos < length
    if window is not None:
        valid &= kv_pos >= (length - window)
    if kv_len is not None:                # cache padded up to whole blocks
        valid &= idx < kv_len

    @pl.when(jnp.any(valid))
    def _compute():
        KVH = k_ref.shape[-1] // D
        heads = lambda ref: [ref[0][:, h * D:(h + 1) * D] for h in range(KVH)]
        _decode_step(q_ref, heads(k_ref), heads(v_ref), valid, acc_scr,
                     m_scr, l_scr, scale=scale, group=group)

    @pl.when(ik == nk - 1)
    def _finalize():
        _decode_finish(o_ref, lse_ref, acc_scr, m_scr, l_scr)


@functools.partial(
    jax.jit,
    static_argnames=("window", "softmax_scale", "block_k", "interpret",
                     "with_lse", "kv_offset"))
def flash_decode(
    q: jax.Array,                      # (B, H, D)
    k_cache: jax.Array,                # (B, S, KVH, D)
    v_cache: jax.Array,
    lengths: jax.Array,                # (B,) int32
    *,
    window: Optional[int] = None,
    softmax_scale: Optional[float] = None,
    block_k: int = 256,
    interpret: bool = False,
    with_lse: bool = False,
    kv_offset: int = 0,
) -> jax.Array | Tuple[jax.Array, jax.Array]:
    B, H, D = q.shape
    _, S, KVH, _ = k_cache.shape
    check_head_dim(D, interpret)
    group = H // KVH
    scale = softmax_scale if softmax_scale is not None else D ** -0.5
    bk, S_p = seq_block(S, block_k)
    nk = S_p // bk
    k3 = pad_seq(k_cache, S_p).reshape(B, S_p, KVH * D)
    v3 = pad_seq(v_cache, S_p).reshape(B, S_p, KVH * D)

    kernel = functools.partial(_decode_kernel, scale=scale, nk=nk, bk=bk,
                               group=group, D=D, window=window,
                               kv_offset=kv_offset,
                               kv_len=S if S_p != S else None)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,         # lengths
        grid=(B, nk),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda b, ik, ln: (b, 0, 0)),
            pl.BlockSpec((1, bk, KVH * D), lambda b, ik, ln: (b, ik, 0)),
            pl.BlockSpec((1, bk, KVH * D), lambda b, ik, ln: (b, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, H, D), lambda b, ik, ln: (b, 0, 0)),
            pl.BlockSpec((1, H, 1), lambda b, ik, ln: (b, 0, 0)),
        ],
        scratch_shapes=_decode_scratch(H, D),
    )
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, 1), jnp.float32),
        ],
        interpret=interpret,
    )(lengths.astype(jnp.int32), q, k3, v3)
    if with_lse:
        return out, lse[..., 0]
    return out


# ------------------------------------------------------------ paged layout
@jax.jit
def gather_kv_pages(pool: jax.Array, block_table: jax.Array) -> jax.Array:
    """Dense per-batch view of paged KV (debug/validation helper — the
    serving decode path consumes the pool through block tables natively
    and never materialises this).

    pool: (nb, n_pages, page, KVH, D); block_table: (B, pages_per_seq)
    int32 physical page ids -> (nb, B, pages_per_seq * page, KVH, D).
    """
    nb = pool.shape[0]
    B, npg = block_table.shape
    g = pool[:, block_table]              # (nb, B, npg, page, KVH, D)
    return g.reshape(nb, B, npg * pool.shape[2], *pool.shape[3:])


@functools.partial(jax.jit, donate_argnums=(0,))
def scatter_kv_token(pool: jax.Array, block_table: jax.Array,
                     lengths: jax.Array, new: jax.Array) -> jax.Array:
    """Write one token per sequence at logical position ``lengths[b]``
    (validation/debug helper — production decode appends inline in
    models/attention.py's paged branch).

    new: (nb, B, KVH, D).  Rows whose table points at a scratch page are
    harmless no-ops for live data (the engine pads inactive rows that way).
    """
    page = pool.shape[2]
    B = block_table.shape[0]
    phys = block_table[jnp.arange(B), lengths // page]         # (B,)
    return pool.at[:, phys, lengths % page].set(
        new.astype(pool.dtype))


@functools.partial(jax.jit, donate_argnums=(0,))
def scatter_kv_chunk(pool: jax.Array, blocks: jax.Array,
                     seq_kv: jax.Array, positions: jax.Array) -> jax.Array:
    """Scatter one chunk's KV into pages at its logical positions.

    blocks: (pages_per_seq,) physical ids covering the whole allocation;
    seq_kv: (nb, L, KVH, D); positions: (L,) int32 logical token positions
    — token j lands in page ``blocks[positions[j] // page]`` at slot
    ``positions[j] % page``.  Scattering by *position* (not storage index)
    keeps pages in natural token order even when the chunk's storage order
    is permuted (zigzag ring layouts).  The pool argument is donated.
    """
    page = pool.shape[2]
    pos = positions.astype(jnp.int32)
    return pool.at[:, blocks[pos // page], pos % page].set(
        seq_kv.astype(pool.dtype))


@functools.partial(jax.jit, donate_argnums=(0,))
def scatter_kv_prefill(pool: jax.Array, blocks: jax.Array,
                       seq_kv: jax.Array) -> jax.Array:
    """Scatter a whole prefilled sequence into its pages.

    blocks: (pages_per_seq,) physical ids; seq_kv: (nb, S, KVH, D) with
    S <= pages_per_seq * page, token i lands in page blocks[i // page].
    The pool argument is donated.
    """
    page = pool.shape[2]
    S = seq_kv.shape[1]
    pos = jnp.arange(S, dtype=jnp.int32)
    return pool.at[:, blocks[pos // page], pos % page].set(
        seq_kv.astype(pool.dtype))


@functools.partial(jax.jit, donate_argnums=(0,))
def copy_kv_blocks(dst_pool: jax.Array, src_pool: jax.Array,
                   src_blocks: jax.Array, dst_blocks: jax.Array) -> jax.Array:
    """Copy whole physical pages between two pools (prefill -> decode
    admission handoff).  Page-granular: no dense per-request view is ever
    assembled.  The destination pool is donated; the source is read-only.
    """
    return dst_pool.at[:, dst_blocks].set(
        src_pool[:, src_blocks].astype(dst_pool.dtype))


@jax.jit
def gather_kv_blocks(pool: jax.Array, blocks: jax.Array) -> jax.Array:
    """Gather whole physical pages out of a pool — the device-side staging
    read of a swap-out / host demotion (serving/kv_offload.py).

    pool: (nb, n_pages, page, KVH, D); blocks: (n,) int32 physical ids ->
    (nb, n, page, KVH, D).  Not donated: the pool stays live (the caller
    moves the gathered pages to host and only then releases the blocks).
    """
    return pool[:, blocks]


@functools.partial(jax.jit, donate_argnums=(0,))
def scatter_kv_blocks(pool: jax.Array, blocks: jax.Array,
                      pages: jax.Array) -> jax.Array:
    """Scatter whole pages into a pool — the device-side staging write of
    a swap-in / host-prefix-cache promotion (serving/kv_offload.py).

    blocks: (n,) int32 destination physical ids; pages: (nb, n, page, KVH,
    D), typically a host (numpy) slice that XLA uploads as it scatters.
    The pool argument is donated like the other page copiers.
    """
    return pool.at[:, blocks].set(pages.astype(pool.dtype))


@functools.partial(jax.jit, donate_argnums=(0,))
def copy_kv_block_within(pool: jax.Array, src_block: jax.Array,
                         dst_block: jax.Array) -> jax.Array:
    """Copy one page to another within the same pool — the physical half
    of a copy-on-write split (serving/cache_manager.BlockManager).  The
    pool argument is donated."""
    return pool.at[:, dst_block].set(pool[:, src_block])


# ----------------------------------------------- sharded (split-KV) layout
#
# Sequence-parallel sharded pools (serving/cache_manager.PagedKVCache with
# kv_shards > 1): per layer the pool is (nb, n_shards, blocks_per_shard + 1,
# page, KVH, D), placed over a mesh axis, with a request's logical page i
# striped onto shard i % n_shards.  On a 2D (SP x TP) mesh the pool is
# additionally head-sharded: the KVH axis (pool axis 4) is placed over
# ``head_axis`` so each device stores only its KVH / tp slice — the page
# bodies below index pages, never heads, so the same code runs on the
# sliced width; the head axis only appears in the partition specs.  The
# helpers below are shard_map bodies over those axes: every page
# write/copy/gather happens on the device that owns the page — tokens and
# staged pages move, pages never do.  Local page id ``blocks_per_shard`` is
# the shard's scratch page; routing a payload at scratch is the
# uniform-SPMD way to say "not mine".
#
# The per-(mesh, axis, head_axis) jitted wrappers are cached: the engine
# calls these every chunk/tick with the same mesh, so the shard_map closure
# and its donation setup are built once.

from jax import lax, shard_map
from jax.sharding import PartitionSpec as P


@functools.lru_cache(maxsize=None)
def _sharded_page_ops(mesh, axis: str, head_axis: Optional[str] = None):
    """Build the jitted shard_map page helpers for one (mesh, axis[, tp])."""
    h = head_axis                             # None -> replicated KV heads
    pool_spec = P(None, axis, None, None, h)  # (nb, n, bps+1, page, KVH, D)
    ids_spec = P(axis,)                       # leading shard axis
    kv_spec = P(None, None, h)                # (nb, L, KVH, D) chunk payload
    pages_spec = P(None, axis, None, None, h)  # (nb, n, m, page, KVH, D)

    def _scatter_chunk(pool, local_pages, seq_kv, positions, n_act):
        # pool: (nb, 1, bps+1, page, KVH/tp, D); local_pages: (1, npg_loc);
        # seq_kv: (nb, L, KVH/tp, D) — the in-spec slices the chunk's KV
        # heads to this device's slice; positions: (L,) replicated;
        # n_act: replicated scalar — the ACTIVE stripe width (<= mesh
        # axis size; traced so stripe resizes never recompile)
        pl_, lp = pool[:, 0], local_pages[0]
        idx = lax.axis_index(axis)
        page = pl_.shape[2]
        scratch = pl_.shape[1] - 1
        pos = positions.astype(jnp.int32)
        pg = pos // page
        own = (pg % n_act) == idx     # idle shards (idx >= n_act): never
        phys = jnp.where(own, lp[pg // n_act], scratch)
        # non-owned tokens land on the scratch page (garbage, never read)
        return pl_.at[:, phys, pos % page].set(
            seq_kv.astype(pl_.dtype))[:, None]

    def _copy_blocks(dst, src, src_local, dst_local):
        d, s = dst[:, 0], src[:, 0]
        return d.at[:, dst_local[0]].set(
            s[:, src_local[0]].astype(d.dtype))[:, None]

    def _scatter_blocks(pool, dst_local, pages):
        # pages: (nb, 1, m, page, KVH, D) — this shard's payload
        pl_ = pool[:, 0]
        return pl_.at[:, dst_local[0]].set(
            pages[:, 0].astype(pl_.dtype))[:, None]

    def _gather_blocks(pool, local):
        return pool[:, 0][:, local[0]][:, None]

    def _copy_within(pool, src_local, dst_local):
        pl_ = pool[:, 0]
        return pl_.at[:, dst_local[0]].set(pl_[:, src_local[0]])[:, None]

    def _restripe_blocks(pool, send_local, recv_local):
        # pool: (nb, 1, bps+1, page, KVH, D); send_local/recv_local:
        # (1, N, m) after sharding the (N, N, m) grids on their leading
        # axis — send_local[s, d] = local ids shard s sends to shard d,
        # recv_local[d, s] = destination local ids on d for shard s's
        # payload, aligned slot-for-slot.  Scratch-padded slots move the
        # scratch page onto the scratch page: harmless, uniform SPMD.
        pl_ = pool[:, 0]
        snd, rcv = send_local[0], recv_local[0]           # (N, m)
        nb = pl_.shape[0]
        N, m = snd.shape
        x = pl_[:, snd.reshape(-1)].reshape((nb, N, m) + pl_.shape[2:])
        # all_to_all: y[:, s, t] on shard d is the page shard s addressed
        # to d at slot t — exactly what rcv[s, t] names a home for
        y = lax.all_to_all(x, axis, split_axis=1, concat_axis=1)
        return pl_.at[:, rcv.reshape(-1)].set(
            y.reshape((nb, N * m) + pl_.shape[2:]))[:, None]

    def sm(f, in_specs, out_specs, donate=None):
        g = shard_map(f, mesh=mesh, in_specs=in_specs,
                          out_specs=out_specs, check_vma=False)
        return (jax.jit(g) if donate is None
                else jax.jit(g, donate_argnums=donate))

    rep = P()
    return {
        "scatter_chunk": sm(
            _scatter_chunk, (pool_spec, ids_spec, kv_spec, rep, rep),
            pool_spec, donate=(0,)),
        "restripe_blocks": sm(
            _restripe_blocks, (pool_spec, ids_spec, ids_spec), pool_spec,
            donate=(0,)),
        "copy_blocks": sm(
            _copy_blocks, (pool_spec, pool_spec, ids_spec, ids_spec),
            pool_spec, donate=(0,)),
        "scatter_blocks": sm(
            _scatter_blocks, (pool_spec, ids_spec, pages_spec),
            pool_spec, donate=(0,)),
        "gather_blocks": sm(
            _gather_blocks, (pool_spec, ids_spec), pages_spec),
        "copy_within": sm(
            _copy_within, (pool_spec, ids_spec, ids_spec), pool_spec,
            donate=(0,)),
    }


def shard_scatter_kv_chunk(pool, local_pages, seq_kv, positions, *,
                           mesh, axis: str, active: Optional[int] = None,
                           head_axis: Optional[str] = None):
    """Sharded ``scatter_kv_chunk``: the chunk's tokens are visible on
    every shard (the in-spec replicates over the stripe axis and, with
    ``head_axis``, slices the KV heads to the device's slice); each shard
    writes only the tokens whose logical page it owns (page ``p`` belongs
    to shard ``p % active``), routing the rest to its scratch page.
    ``active`` (default all shards) is the live stripe width — shards past
    it idle.  The pool argument is donated."""
    n_act = jnp.int32(active or mesh.shape[axis])
    return _sharded_page_ops(mesh, axis, head_axis)["scatter_chunk"](
        pool, local_pages, seq_kv, positions, n_act)


def shard_restripe_kv_blocks(pool, send_local, recv_local, *, mesh,
                             axis: str, head_axis: Optional[str] = None):
    """Cross-shard page migration for a live stripe resize — the ONE
    operation that moves pages between shards.  ``send_local`` is an
    (N, N, m) grid: row s holds, per destination d, the local page ids
    shard s must send to d (scratch-padded to m); ``recv_local[d, s]``
    the destination local ids on d for shard s's payload, slot-aligned
    with ``send_local[s, d]``.  One ``all_to_all`` exchanges every
    payload; each shard then scatters what it received.  Head-sharded
    pools migrate only the local head slice — the all_to_all stays within
    each TP row.  The pool argument is donated."""
    return _sharded_page_ops(mesh, axis, head_axis)["restripe_blocks"](
        pool, send_local, recv_local)


def shard_copy_kv_blocks(dst_pool, src_pool, src_local, dst_local, *,
                         mesh, axis: str, head_axis: Optional[str] = None):
    """Sharded ``copy_kv_blocks``: per-shard (m,) local id lists, aligned
    pairs guaranteed same-shard by stripe alignment — a purely
    device-local page copy (admission handoff between sharded pools).
    The destination pool is donated."""
    return _sharded_page_ops(mesh, axis, head_axis)["copy_blocks"](
        dst_pool, src_pool, src_local, dst_local)


def shard_scatter_kv_blocks(pool, dst_local, pages, *, mesh, axis: str,
                            head_axis: Optional[str] = None):
    """Sharded ``scatter_kv_blocks``: ``pages`` is (nb, n_shards, m, page,
    KVH, D) grouped per destination shard (host swap-in / promotion
    payloads, or re-grouped pages from an unsharded pool).  Payloads stay
    full KV-head width host-side; with ``head_axis`` the in-spec slices
    each device's KVH / tp share during the upload.  The pool argument is
    donated."""
    return _sharded_page_ops(mesh, axis, head_axis)["scatter_blocks"](
        pool, dst_local, pages)


def shard_gather_kv_blocks(pool, local, *, mesh, axis: str,
                           head_axis: Optional[str] = None):
    """Sharded ``gather_kv_blocks``: each shard reads its own pages;
    result is (nb, n_shards, m, page, KVH, D) in per-shard grouping order
    (the caller reassembles logical order host-side).  The out-spec keeps
    the head axis sharded, so a head-sharded pool's gather reassembles the
    full KVH width only when the result is pulled to host."""
    return _sharded_page_ops(mesh, axis, head_axis)["gather_blocks"](
        pool, local)


def shard_copy_kv_block_within(pool, src_local, dst_local, *, mesh,
                               axis: str, head_axis: Optional[str] = None):
    """Sharded ``copy_kv_block_within``: per-shard (scalar) local ids —
    the owning shard copies the CoW page, every other shard copies scratch
    onto scratch.  The pool argument is donated."""
    return _sharded_page_ops(mesh, axis, head_axis)["copy_within"](
        pool, src_local, dst_local)


# Position base for table columns past a sequence's allocation (scratch
# columns of a striped shard-local table): far past any real length, and
# small enough that base + slot never overflows int32.
POS_PAD = jnp.int32(2 ** 30)


def _paged_decode_kernel(layer_ref, bt_ref, len_ref, pp_ref, q_ref, k_ref,
                         v_ref, o_ref, lse_ref, acc_scr, m_scr, l_scr,
                         *, scale: float, nk: int, bk: int, group: int,
                         window: Optional[int]):
    b = pl.program_id(0)
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        init_scratch(acc_scr, m_scr, l_scr)

    length = len_ref[b]
    # logical position of each slot: the prefetched page_pos gives the
    # page's first-token position (flat table order by default; the global
    # stripe positions for a shard-local table) — the physical indirection
    # happened in the index map, the *logical* one happens here, so window
    # masks are native however the pages are striped
    kv_pos = pp_ref[b, ik] + jax.lax.broadcasted_iota(
        jnp.int32, (1, bk), 1)
    valid = kv_pos < length
    if window is not None:
        valid &= kv_pos >= (length - window)

    @pl.when(jnp.any(valid))
    def _compute():
        # k_ref/v_ref: one page as the pool stores it, (page, KVH, D)
        KVH = k_ref.shape[1]
        heads = lambda ref: [ref[:, h, :] for h in range(KVH)]
        _decode_step(q_ref, heads(k_ref), heads(v_ref), valid, acc_scr,
                     m_scr, l_scr, scale=scale, group=group)

    @pl.when(ik == nk - 1)
    def _finalize():
        _decode_finish(o_ref, lse_ref, acc_scr, m_scr, l_scr)


@functools.partial(
    jax.jit,
    static_argnames=("window", "softmax_scale", "interpret", "with_lse"))
def paged_flash_decode(
    q: jax.Array,                      # (B, H, D)
    k_pool: jax.Array,                 # ([L,] n_pages, page, KVH, D)
    v_pool: jax.Array,
    block_tables: jax.Array,           # (B, pages_per_seq) int32
    lengths: jax.Array,                # (B,) int32
    *,
    window: Optional[int] = None,
    softmax_scale: Optional[float] = None,
    interpret: bool = False,
    with_lse: bool = False,
    page_pos: Optional[jax.Array] = None,  # (B, pages_per_seq) int32
    layer: Optional[jax.Array] = None,     # () int32, with an L axis
) -> jax.Array | Tuple[jax.Array, jax.Array]:
    """Flash decode straight off the paged pool: the block table is a
    scalar-prefetch argument and the KV BlockSpec index map dereferences it,
    so each (b, ik) grid step DMAs physical page ``block_tables[b, ik]``
    (all KV heads of it, in the pool's own ``(page, KVH, D)`` layout: no
    relayout of the pool before the call).

    The pools may carry a leading layer axis — the layer-stacked pools of
    ``PagedKVCache`` — and ``layer`` (a traced scalar) then picks the
    layer inside the index map, so a layer scan attends over its slice of
    the stacked pool without slicing it out.

    ``page_pos[b, j]`` is the logical position of page j's first token
    (default: flat table order, ``j * page``).  A shard of a striped pool
    passes its pages' *global* stripe positions instead, which makes both
    the length mask and the sliding-window mask native in the kernel — no
    positional gather slab, no contiguous-local-length requirement.
    Columns past the allocation should carry ``POS_PAD`` so they mask out.
    """
    if k_pool.ndim == 4:               # one layer's pool: a unit layer axis
        k_pool, v_pool, layer = k_pool[None], v_pool[None], 0
    B, H, D = q.shape
    _, n_pages, bk, KVH, _ = k_pool.shape
    nk = block_tables.shape[1]
    check_head_dim(D, interpret)
    group = H // KVH
    scale = softmax_scale if softmax_scale is not None else D ** -0.5
    if page_pos is None:
        page_pos = jnp.broadcast_to(
            jnp.arange(nk, dtype=jnp.int32)[None] * bk, (B, nk))
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))

    kernel = functools.partial(_paged_decode_kernel, scale=scale, nk=nk,
                               bk=bk, group=group, window=window)
    page_spec = pl.BlockSpec((None, None, bk, KVH, D),
                             lambda b, ik, ly, bt, ln, pp:
                             (ly[0], bt[b, ik], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,         # layer, block_tables, lengths, page_pos
        grid=(B, nk),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda b, ik, ly, bt, ln, pp: (b, 0, 0)),
            page_spec,
            page_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, H, D), lambda b, ik, ly, bt, ln, pp: (b, 0, 0)),
            pl.BlockSpec((1, H, 1), lambda b, ik, ly, bt, ln, pp: (b, 0, 0)),
        ],
        scratch_shapes=_decode_scratch(H, D),
    )
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, 1), jnp.float32),
        ],
        interpret=interpret,
    )(layer, block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      page_pos.astype(jnp.int32), q, k_pool, v_pool)
    if with_lse:
        return out, lse[..., 0]
    return out


def fused_append_attend(k_pool, v_pool, append_page, append_slot,
                        k_new, v_new, layer=None):
    """The append half of the fused decode tick: write each sequence's new
    token K/V into its page slot (of layer ``layer`` of a layer-stacked
    pool, when given).  Rows routed to the scratch page (padded batch
    rows; non-owning shards of a striped pool) write garbage that is
    never read.  Shared by ``paged_append_attend`` and the sharded decode
    island — one invocation writes AND attends, so the pool is touched
    once per tick instead of scatter-then-gather."""
    at = ((append_page, append_slot) if layer is None
          else (layer, append_page, append_slot))
    k_pool = k_pool.at[at].set(k_new.astype(k_pool.dtype))
    v_pool = v_pool.at[at].set(v_new.astype(v_pool.dtype))
    return k_pool, v_pool


@functools.partial(
    jax.jit, donate_argnums=(1, 2),
    static_argnames=("window", "softmax_scale", "with_lse", "impl"))
def paged_append_attend(
    q: jax.Array,                      # (B, H, D)
    k_pool: jax.Array,                 # ([L,] n_pages, page, KVH, D) — donated
    v_pool: jax.Array,                 # donated
    block_tables: jax.Array,           # (B, pages_per_seq) int32
    lengths: jax.Array,                # (B,) int32, EXCLUDING the new token
    append_page: jax.Array,            # (B,) int32 physical page ids
    append_slot: jax.Array,            # (B,) int32 slots within the page
    k_new: jax.Array,                  # (B, KVH, D)
    v_new: jax.Array,
    page_pos: Optional[jax.Array] = None,
    layer: Optional[jax.Array] = None,
    *,
    window: Optional[int] = None,
    softmax_scale: Optional[float] = None,
    with_lse: bool = False,
    impl: str = "pallas",
):
    """Fused append+attend decode tick: scatter the new token's K/V into
    its page and attend over ``lengths + 1`` tokens in one jitted
    invocation.  Called on its own, the pools are donated, so XLA performs
    the append as an in-place dynamic-update on the live buffers.  Traced
    inside a larger program (the model's layer scan) the donation here
    means nothing: there the enclosing program owns the buffers, and the
    decode tick's layer scan keeps the layer-stacked pools in its carry
    and passes ``layer`` (models/transformer._stack_forward).

    Returns ``(o[, lse], k_pool, v_pool)``.
    """
    from repro.kernels import ref as _ref
    k_pool, v_pool = fused_append_attend(k_pool, v_pool, append_page,
                                         append_slot, k_new, v_new, layer)
    att = lengths + 1
    if impl == "ref":
        o = _ref.paged_decode_attention_ref(
            q, k_pool, v_pool, block_tables, att, window=window,
            softmax_scale=softmax_scale, with_lse=with_lse,
            page_pos=page_pos, layer=layer)
    else:
        o = paged_flash_decode(
            q, k_pool, v_pool, block_tables, att, window=window,
            softmax_scale=softmax_scale, with_lse=with_lse,
            interpret=(impl == "interpret"), page_pos=page_pos, layer=layer)
    if with_lse:
        return o[0], o[1], k_pool, v_pool
    return o, k_pool, v_pool
