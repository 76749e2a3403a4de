"""Backend-dispatching wrappers around the Pallas kernels.

On TPU the Pallas kernels run natively (``impl="pallas"``, the default
there); tests/test_tpu_compile.py compiles each main-path kernel for a TPU
v5e at yi-9b widths, so a kernel the chip's compiler would refuse fails the
tests on any machine.  On CPU (the unit tests) the pure-jnp oracles in
ref.py are the execution path — identical math, identical shapes, so the
sharding/collective structure of the surrounding program is unchanged.
``impl="interpret"`` forces the Pallas kernel bodies through the
interpreter, which checks their numerics against the oracles on CPU.

A Pallas kernel that cannot take a shape raises; it never hands the call
to an oracle.  The sharded (3-dim table) pool layout has only gather
oracles here, so under ``impl="pallas"``/``"interpret"`` it raises too: on
the engine's mesh path decode runs the split-KV island and causal prefill
chunks of any length ride the ring (models/attention.py).
"""

from __future__ import annotations

import os
from typing import Optional

import jax

from repro.kernels import ref as _ref
from repro.kernels.flash_attention import flash_attention as _flash_attention
from repro.kernels.flash_attention import (
    paged_flash_prefill as _paged_flash_prefill)
from repro.kernels.flash_decode import flash_decode as _flash_decode
from repro.kernels.flash_decode import paged_append_attend as _paged_append_attend
from repro.kernels.flash_decode import paged_flash_decode as _paged_flash_decode
from repro.kernels.ssd_scan import ssd_scan as _ssd_scan

_FORCED = os.environ.get("REPRO_KERNEL_IMPL")  # ref | pallas | interpret


def default_impl() -> str:
    if _FORCED:
        return _FORCED
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _no_sharded_kernel(impl: str, op: str, path: str) -> None:
    """Refuse the sharded pool layout under a Pallas impl: only the gather
    oracle reads it, and it must not stand in for a kernel unseen."""
    if impl not in ("ref", "ref_blocked"):
        raise NotImplementedError(
            f"{op} under impl={impl!r}: no Pallas kernel reads the sharded "
            f"(3-dim table) pool layout directly; run it through {path}")


def attention(q, k, v, q_pos, kv_pos, *, causal: bool = True,
              window: Optional[int] = None, softmax_scale=None,
              with_lse: bool = False, impl: Optional[str] = None):
    impl = impl or default_impl()
    if impl == "ref_blocked":
        return _ref.attention_ref_blocked(
            q, k, v, q_pos, kv_pos, causal=causal, window=window,
            softmax_scale=softmax_scale, with_lse=with_lse)
    if impl == "ref":
        return _ref.attention_ref(q, k, v, q_pos, kv_pos, causal=causal,
                                  window=window, softmax_scale=softmax_scale,
                                  with_lse=with_lse)
    return _flash_attention(q, k, v, q_pos, kv_pos, causal=causal,
                            window=window, softmax_scale=softmax_scale,
                            with_lse=with_lse,
                            interpret=(impl == "interpret"))


def decode_attention(q, k_cache, v_cache, lengths, *,
                     window: Optional[int] = None, softmax_scale=None,
                     with_lse: bool = False, kv_offset: int = 0,
                     impl: Optional[str] = None):
    impl = impl or default_impl()
    if impl in ("ref", "ref_blocked"):
        return _ref.decode_attention_ref(
            q, k_cache, v_cache, lengths, window=window,
            softmax_scale=softmax_scale, with_lse=with_lse,
            kv_offset=kv_offset)
    return _flash_decode(q, k_cache, v_cache, lengths, window=window,
                         softmax_scale=softmax_scale, with_lse=with_lse,
                         kv_offset=kv_offset, interpret=(impl == "interpret"))


def paged_decode_attention(q, k_pool, v_pool, block_tables, lengths, *,
                           window: Optional[int] = None, softmax_scale=None,
                           with_lse: bool = False, impl: Optional[str] = None,
                           page_pos=None, k_new=None, v_new=None,
                           append_page=None, append_slot=None, layer=None):
    """Block-table decode attention: one query token per sequence against a
    paged KV pool, no dense ``(batch, max_seq)`` cache anywhere.

    q: (B, H, D); k_pool/v_pool: (n_pages, page, KVH, D);
    block_tables: (B, pages_per_seq) int32 physical page ids (pad dead rows
    with a scratch page); lengths: (B,) valid cache length per sequence.

    ``page_pos`` (B, pages_per_seq) optionally gives each table column's
    first-token logical position — a shard of a striped pool passes its
    pages' *global* stripe positions, making the length and sliding-window
    masks native however the pages are distributed (no positional gather
    slab).

    Fused append+attend: pass ``k_new``/``v_new`` (B, KVH, D) with
    ``append_page``/``append_slot`` (B,) and the new token's K/V is written
    into its page inside the same (donated) invocation that attends —
    ``lengths`` then EXCLUDES the new token and the return value becomes
    ``(o[, lse], k_pool, v_pool)``; the pools are donated, so rebind them.

    ``layer`` (2-dim tables): the pools are layer-stacked, ``(L, n_pages,
    page, KVH, D)``, and the call appends to and attends over layer
    ``layer`` of them without slicing it out — the decode layer scan's
    in-place path (models/transformer._stack_forward).

    On TPU (``impl="pallas"``) this is ``paged_flash_decode`` — the block
    table rides in as a scalar-prefetch argument and the kernel DMAs pages
    directly from the pool.  On CPU (``impl="ref"``) it gathers the table
    into a per-step dense view sized to the table width and reuses the
    decode oracle; ``impl="interpret"`` runs the Pallas kernel body through
    the interpreter for validation.

    A *sequence-parallel sharded* pool (3-dim block_tables (n_shards, B,
    npg_local), 5-dim pools — serving/cache_manager with kv_shards > 1)
    has only the logical-order gather oracle (``impl="ref"``); the Pallas
    impls raise on it.  The distributed execution path for that layout is
    the shard_map split-KV island (core/ring_attention.sharded_paged_decode),
    whose per-shard partials dispatch back here with the shard-local 2-dim
    layout + ``page_pos``.
    """
    impl = impl or default_impl()
    if k_new is not None:
        assert block_tables.ndim == 2, "fused append needs 2-dim tables"
        return _paged_append_attend(
            q, k_pool, v_pool, block_tables, lengths, append_page,
            append_slot, k_new, v_new, page_pos, layer, window=window,
            softmax_scale=softmax_scale, with_lse=with_lse,
            impl=("ref" if impl in ("ref", "ref_blocked") else impl))
    if block_tables.ndim == 3:
        _no_sharded_kernel(impl, "paged_decode_attention",
                           "core/ring_attention.sharded_paged_decode")
        return _ref.paged_decode_attention_ref(
            q, k_pool, v_pool, block_tables, lengths, window=window,
            softmax_scale=softmax_scale, with_lse=with_lse)
    if impl in ("ref", "ref_blocked"):
        return _ref.paged_decode_attention_ref(
            q, k_pool, v_pool, block_tables, lengths, window=window,
            softmax_scale=softmax_scale, with_lse=with_lse,
            page_pos=page_pos, layer=layer)
    return _paged_flash_decode(q, k_pool, v_pool, block_tables, lengths,
                               window=window, softmax_scale=softmax_scale,
                               with_lse=with_lse, page_pos=page_pos,
                               layer=layer, interpret=(impl == "interpret"))


def paged_prefill_attention(q, k_new, v_new, q_pos, kv_pos_new,
                            k_pool, v_pool, block_tables, hist_len, *,
                            causal: bool = True,
                            window: Optional[int] = None,
                            softmax_scale=None, impl: Optional[str] = None):
    """Prefill-chunk attention with paged cross-chunk history.

    The CDSP chunk's queries attend over [history pages ++ own chunk KV]
    without a dense history view: history KV sits in a block pool in
    natural token order (pages written per chunk by
    ``PagedKVCache.write_chunk``), addressed through ``block_tables``
    (B, pages_per_seq) with per-row validity ``hist_len``.

    On TPU (``impl="pallas"``) this composes the scalar-prefetch kernel
    ``flash_attention.paged_flash_prefill`` (history shard) with the plain
    flash kernel over the chunk's own KV, merged via ``ref.merge_partials``
    — numerically the single-softmax result.  On CPU (``impl="ref"``) the
    gather fallback ``ref.paged_prefill_attention_ref`` runs instead;
    ``impl="interpret"`` pushes both Pallas kernel bodies through the
    interpreter for validation.

    The sequence-parallel sharded pool layout (3-dim block_tables, 5-dim
    pools) has only the gather oracle (``impl="ref"``); the Pallas impls
    raise on it.  Distributed execution of that layout is
    ``core/ring_attention.ring_paged_prefill`` (history pages rotate
    through the ring; models/attention.py pads causal chunks of any length
    onto it).
    """
    impl = impl or default_impl()
    if block_tables.ndim == 3:
        _no_sharded_kernel(impl, "paged_prefill_attention",
                           "core/ring_attention.ring_paged_prefill")
        return _ref.paged_prefill_attention_ref(
            q, k_new, v_new, q_pos, kv_pos_new, k_pool, v_pool,
            block_tables, hist_len, causal=causal, window=window,
            softmax_scale=softmax_scale)
    if impl in ("ref", "ref_blocked"):
        return _ref.paged_prefill_attention_ref(
            q, k_new, v_new, q_pos, kv_pos_new, k_pool, v_pool,
            block_tables, hist_len, causal=causal, window=window,
            softmax_scale=softmax_scale)
    interpret = impl == "interpret"
    o_h, lse_h = _paged_flash_prefill(
        q, k_pool, v_pool, block_tables, hist_len, q_pos, causal=causal,
        window=window, softmax_scale=softmax_scale, interpret=interpret)
    o_s, lse_s = _flash_attention(
        q, k_new, v_new, q_pos, kv_pos_new, causal=causal, window=window,
        softmax_scale=softmax_scale, with_lse=True, interpret=interpret)
    out, _ = _ref.merge_partials([o_h, o_s], [lse_h, lse_s])
    return out


def ssd(x, dt, A, Bm, Cm, *, h0=None, chunk: int = 128,
        impl: Optional[str] = None):
    import jax.numpy as jnp
    impl = impl or default_impl()
    S = x.shape[1]
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        # dt=0 padding is the identity element of the SSD recurrence
        # (decay exp(0)=1, zero input contribution), so pad freely.
        zpad = lambda a: jnp.concatenate(
            [a, jnp.zeros((a.shape[0], pad) + a.shape[2:], a.dtype)], axis=1)
        x, dt, Bm, Cm = zpad(x), zpad(dt), zpad(Bm), zpad(Cm)
    if impl in ("ref", "ref_blocked"):
        y, h = _ref.ssd_chunked_ref(x, dt, A, Bm, Cm, chunk=chunk, h0=h0,
                                    return_state=True)
    else:
        y, h = _ssd_scan(x, dt, A, Bm, Cm, h0=h0, chunk=chunk,
                         interpret=(impl == "interpret"))
    return (y[:, :S], h) if pad else (y, h)


def ssd_decode(x, dt, A, Bm, Cm, h):
    # O(1) state update; no kernel needed (bandwidth trivial per token).
    return _ref.ssd_decode_ref(x, dt, A, Bm, Cm, h)


merge_partials = _ref.merge_partials
