"""Pallas TPU flash attention with position-array masking.

Target: TPU MXU — (bq, bk) = (128, 128) tiles, head_dim 128, fp32
accumulation in VMEM scratch.  The kv-block axis is the innermost
(sequential) grid dimension; running (max, sum, acc) statistics live in VMEM
scratch across kv steps, the classic flash schedule.

Layout: q/k/v are viewed as ``(B, S, heads * D)``, so a block is
``(block_q, group * D)`` of queries — the ``group`` query heads that share
one KV head — against ``(block_k, D)`` of that KV head.  Heads are sliced
out of the lane axis, which the TPU's (8, 128) block tiling allows when
``D`` is a multiple of 128; the grid is (batch, kv_head, q_block,
kv_block), so each K/V block is loaded once per query block, not once per
query head.

Masking is driven by explicit q/kv position arrays (see kernels/ref.py), so
the same kernel serves plain causal prefill, CDSP chunked prefill against
historical KV, zigzag ring-attention shards and sliding windows.  Blocks
whose mask is entirely zero are skipped via predication (``pl.when``) — with
the zigzag layout this recovers the ~2x causal-skip saving.  Sequence
lengths need not divide the block: the op pads queries and keys up to whole
blocks inside the call and masks the padded keys.

Checked two ways: against kernels/ref.py in interpret mode, and compiled for
a TPU v5e at yi-9b widths by tests/test_tpu_compile.py; on TPU it runs
natively.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128          # TPU lane width: head slices must be whole lane tiles
SUBLANES = 16        # bf16 sublane packing: sequence blocks are multiples
BLOCK_Q = 128        # query tile of the paged prefill kernel


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def check_head_dim(D: int, interpret: bool) -> None:
    """The TPU kernels slice heads out of the lane axis; refuse a head dim
    the chip cannot tile instead of failing deep inside the compiler."""
    if not interpret and D % LANES:
        raise ValueError(
            f"head_dim {D} is not a multiple of {LANES}: the Pallas TPU "
            "attention kernels slice heads out of the lane axis")


def seq_block(S: int, block: int) -> Tuple[int, int]:
    """(block, padded length) for a sequence axis of ``S`` tokens: one
    whole block when ``S`` fits, else ``block``-sized tiles."""
    if S <= block:
        b = round_up(S, SUBLANES)
        return b, b
    return block, round_up(S, block)


def pad_seq(x: jax.Array, S_pad: int, value=0) -> jax.Array:
    """Pad axis 1 of ``x`` up to ``S_pad``."""
    pad = S_pad - x.shape[1]
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[1] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _attend_block(q_ref, k, v, valid, acc_scr, m_scr, l_scr, *,
                  scale: float, group: int, D: int):
    """One online-softmax step for the ``group`` query heads of a block.

    q_ref: (1, bq, group * D) query block; k, v: (bk, D) of their KV head;
    valid: (bq, bk) mask; scratch rows are indexed by the head in group.
    Scores, softmax weights and both matmuls are f32, as in kernels/ref.py."""
    k = k.astype(jnp.float32)
    v = v.astype(jnp.float32)
    for g in range(group):
        q = q_ref[0, :, g * D:(g + 1) * D].astype(jnp.float32) * scale
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_scr[g]                                     # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[g] = l_scr[g] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[g] = acc_scr[g] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[g] = m_new


def init_scratch(acc_scr, m_scr, l_scr):
    """Reset the online-softmax accumulators at the first kv step."""
    acc_scr[...] = jnp.zeros_like(acc_scr)
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)


def _finish_block(o_ref, lse_ref, acc_scr, m_scr, l_scr, *, group: int,
                  D: int):
    """Normalise the accumulators into the output block and write the
    log-sum-exp row of each head (lse block: (1, group, 1, bq))."""
    for g in range(group):
        l = l_scr[g]
        safe_l = jnp.where(l > 0.0, l, 1.0)
        o_ref[0, :, g * D:(g + 1) * D] = (acc_scr[g] / safe_l
                                          ).astype(o_ref.dtype)
        lse = jnp.where(l > 0.0, m_scr[g] + jnp.log(safe_l), NEG_INF)
        lse_ref[0, g] = lse.T.astype(lse_ref.dtype)           # (1, bq)


def _block_scratch(group: int, bq: int, D: int):
    return [pltpu.VMEM((group, bq, D), jnp.float32),
            pltpu.VMEM((group, bq, 1), jnp.float32),
            pltpu.VMEM((group, bq, 1), jnp.float32)]


def _flash_kernel(q_pos_ref, kv_pos_ref, q_ref, k_ref, v_ref,
                  o_ref, lse_ref, acc_scr, m_scr, l_scr,
                  *, scale: float, nk: int, bk: int, group: int, D: int,
                  causal: bool, window: Optional[int],
                  kv_len: Optional[int]):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        init_scratch(acc_scr, m_scr, l_scr)

    q_pos = q_pos_ref[0]                                      # (bq, 1)
    kv_pos = kv_pos_ref[0]                                    # (1, bk)
    mask = jnp.ones((q_pos.shape[0], bk), dtype=jnp.bool_)
    if causal:
        mask &= kv_pos <= q_pos
    if window is not None:
        mask &= (q_pos - kv_pos) < window
    if kv_len is not None:                # keys padded up to whole blocks
        idx = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        mask &= idx < kv_len

    @pl.when(jnp.any(mask))
    def _compute():
        _attend_block(q_ref, k_ref[0], v_ref[0], mask, acc_scr, m_scr,
                      l_scr, scale=scale, group=group, D=D)

    @pl.when(ik == nk - 1)
    def _finalize():
        _finish_block(o_ref, lse_ref, acc_scr, m_scr, l_scr, group=group,
                      D=D)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softmax_scale", "block_q",
                     "block_k", "interpret", "with_lse"))
def flash_attention(
    q: jax.Array,                      # (B, Sq, H, D)
    k: jax.Array,                      # (B, Sk, KVH, D)
    v: jax.Array,
    q_pos: jax.Array,                  # (B, Sq) int32
    kv_pos: jax.Array,                 # (B, Sk) int32
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softmax_scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
    with_lse: bool = False,
) -> jax.Array | Tuple[jax.Array, jax.Array]:
    B, Sq, H, D = q.shape
    _, Sk, KVH, _ = k.shape
    check_head_dim(D, interpret)
    group = H // KVH
    scale = softmax_scale if softmax_scale is not None else D ** -0.5
    bq, Sq_p = seq_block(Sq, block_q)
    bk, Sk_p = seq_block(Sk, block_k)
    nq, nk = Sq_p // bq, Sk_p // bk

    if q_pos.ndim == 1:
        q_pos = jnp.broadcast_to(q_pos[None], (B, Sq))
    if kv_pos.ndim == 1:
        kv_pos = jnp.broadcast_to(kv_pos[None], (B, Sk))
    # padded queries are computed and dropped, padded keys masked.  Pad on
    # the major axis before folding heads into lanes: reshaping an
    # unaligned sequence length makes XLA emit a pathological relayout
    q3 = pad_seq(q, Sq_p).reshape(B, Sq_p, H * D)
    k3 = pad_seq(k, Sk_p).reshape(B, Sk_p, KVH * D)
    v3 = pad_seq(v, Sk_p).reshape(B, Sk_p, KVH * D)
    qp = pad_seq(q_pos.astype(jnp.int32), Sq_p)[:, :, None]   # (B, Sq_p, 1)
    kvp = pad_seq(kv_pos.astype(jnp.int32), Sk_p)[:, None, :]  # (B, 1, Sk_p)

    kernel = functools.partial(_flash_kernel, scale=scale, nk=nk, bk=bk,
                               group=group, D=D, causal=causal,
                               window=window,
                               kv_len=Sk if Sk_p != Sk else None)
    out, lse = pl.pallas_call(
        kernel,
        grid=(B, KVH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, 1), lambda b, h, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, 1, bk), lambda b, h, iq, ik: (b, 0, ik)),
            pl.BlockSpec((1, bq, group * D), lambda b, h, iq, ik: (b, iq, h)),
            pl.BlockSpec((1, bk, D), lambda b, h, iq, ik: (b, ik, h)),
            pl.BlockSpec((1, bk, D), lambda b, h, iq, ik: (b, ik, h)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, group * D), lambda b, h, iq, ik: (b, iq, h)),
            pl.BlockSpec((1, group, 1, bq),
                         lambda b, h, iq, ik: (b, h, 0, iq)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Sq_p, H * D), q.dtype),
            jax.ShapeDtypeStruct((B, H, 1, Sq_p), jnp.float32),
        ],
        scratch_shapes=_block_scratch(group, bq, D),
        interpret=interpret,
    )(qp, kvp, q3, k3, v3)
    out = out.reshape(B, Sq_p, H, D)[:, :Sq]
    if with_lse:
        return out, lse[:, :, 0, :Sq]
    return out


def _paged_prefill_kernel(bt_ref, len_ref, qpos_ref, q_ref, k_ref, v_ref,
                          o_ref, lse_ref, acc_scr, m_scr, l_scr,
                          *, scale: float, nk: int, page: int, group: int,
                          D: int, causal: bool, window: Optional[int]):
    b = pl.program_id(0)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        init_scratch(acc_scr, m_scr, l_scr)

    # history pages hold KV in natural token order, so the logical position
    # is the flat table index (the physical indirection happened in the
    # BlockSpec index map) and validity is simply idx < hist_len
    kv_pos = ik * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
    q_pos = qpos_ref[0]                                      # (bq, 1)
    valid = jnp.broadcast_to(kv_pos < len_ref[b], (q_pos.shape[0], page))
    if causal:
        valid &= kv_pos <= q_pos
    if window is not None:
        valid &= (q_pos - kv_pos) < window

    @pl.when(jnp.any(valid))
    def _compute():
        _attend_block(q_ref, k_ref[0], v_ref[0], valid, acc_scr, m_scr,
                      l_scr, scale=scale, group=group, D=D)

    @pl.when(ik == nk - 1)
    def _finalize():
        _finish_block(o_ref, lse_ref, acc_scr, m_scr, l_scr, group=group,
                      D=D)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softmax_scale", "interpret"))
def paged_flash_prefill(
    q: jax.Array,                      # (B, Sq, H, D) — chunk queries
    k_pool: jax.Array,                 # (n_pages, page, KVH, D)
    v_pool: jax.Array,
    block_tables: jax.Array,           # (B, pages_per_seq) int32
    hist_len: jax.Array,               # (B,) int32 — valid history tokens
    q_pos: jax.Array,                  # (B, Sq) int32
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softmax_scale: Optional[float] = None,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Partial flash attention of a prefill chunk over paged history KV.

    The gather-from-block-table variant of the prefill flash kernel: the
    page table rides in as a scalar-prefetch argument and the KV BlockSpec
    index map dereferences it, so each (b, kv_head, q_block, page) grid
    step DMAs that KV head's slice of physical page ``block_tables[b, ik]``
    straight from the pool.  Queries are tiled by ``BLOCK_Q``, so VMEM use
    is bounded whatever the chunk length.  History tokens are in natural
    order (position == flat index).  Returns ``(out, lse)`` — normalised
    within the history shard — for ``ref.merge_partials`` with the chunk's
    own causal self-attention (see ops.paged_prefill_attention).
    """
    B, Sq, H, D = q.shape
    n_pages, page, KVH, _ = k_pool.shape
    nk = block_tables.shape[1]
    check_head_dim(D, interpret)
    group = H // KVH
    scale = softmax_scale if softmax_scale is not None else D ** -0.5
    if nk == 0:                                   # no history pages at all
        return (jnp.zeros_like(q),
                jnp.full((B, H, Sq), NEG_INF, jnp.float32))
    bq, Sq_p = seq_block(Sq, BLOCK_Q)
    nq = Sq_p // bq

    if q_pos.ndim == 1:
        q_pos = jnp.broadcast_to(q_pos[None], (B, Sq))
    q3 = pad_seq(q, Sq_p).reshape(B, Sq_p, H * D)
    qp = pad_seq(q_pos.astype(jnp.int32), Sq_p)[:, :, None]
    kp = k_pool.reshape(n_pages, page, KVH * D)
    vp = v_pool.reshape(n_pages, page, KVH * D)
    kernel = functools.partial(_paged_prefill_kernel, scale=scale, nk=nk,
                               page=page, group=group, D=D, causal=causal,
                               window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,         # block_tables, hist_len
        grid=(B, KVH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, 1), lambda b, h, iq, ik, bt, ln: (b, iq, 0)),
            pl.BlockSpec((1, bq, group * D),
                         lambda b, h, iq, ik, bt, ln: (b, iq, h)),
            pl.BlockSpec((1, page, D),
                         lambda b, h, iq, ik, bt, ln: (bt[b, ik], 0, h)),
            pl.BlockSpec((1, page, D),
                         lambda b, h, iq, ik, bt, ln: (bt[b, ik], 0, h)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, group * D),
                         lambda b, h, iq, ik, bt, ln: (b, iq, h)),
            pl.BlockSpec((1, group, 1, bq),
                         lambda b, h, iq, ik, bt, ln: (b, h, 0, iq)),
        ],
        scratch_shapes=_block_scratch(group, bq, D),
    )
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Sq_p, H * D), q.dtype),
            jax.ShapeDtypeStruct((B, H, 1, Sq_p), jnp.float32),
        ],
        interpret=interpret,
    )(block_tables.astype(jnp.int32), hist_len.astype(jnp.int32), qp, q3,
      kp, vp)
    return out.reshape(B, Sq_p, H, D)[:, :Sq], lse[:, :, 0, :Sq]
