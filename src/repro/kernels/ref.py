"""Pure-jnp oracles for every kernel in this package.

These are the single source of truth for numerics: the Pallas kernels are
validated against them in interpret mode, the ring-attention / flash-decode
shard_map paths are validated against them end-to-end, and on CPU (this
container) they ARE the execution path.

Position-array masking: instead of baking "causal with offset" variants into
each implementation, attention takes explicit integer position arrays for the
query and key sides.  Causality is ``kv_pos <= q_pos`` — this uniformly
expresses plain causal prefill, chunked (CDSP) prefill against historical KV,
zigzag ring layouts, sliding windows, and decode-with-cache.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _broadcast_pos(pos: jax.Array, batch: int) -> jax.Array:
    if pos.ndim == 1:
        pos = pos[None]
    return jnp.broadcast_to(pos, (batch, pos.shape[-1]))


def attention_ref(
    q: jax.Array,                      # (B, Sq, H, D)
    k: jax.Array,                      # (B, Sk, KVH, D)
    v: jax.Array,                      # (B, Sk, KVH, D)
    q_pos: jax.Array,                  # (Sq,) or (B, Sq) int32
    kv_pos: jax.Array,                 # (Sk,) or (B, Sk) int32
    *,
    causal: bool = True,
    window: Optional[int] = None,      # sliding window size (tokens)
    kv_valid: Optional[jax.Array] = None,   # (B, Sk) bool — padded-cache mask
    softmax_scale: Optional[float] = None,
    with_lse: bool = False,
) -> jax.Array | Tuple[jax.Array, jax.Array]:
    """Grouped-query attention with position-array masking.

    Returns out (B, Sq, H, D); if with_lse, also lse (B, H, Sq) — the
    log-sum-exp of the (scaled) logits, used to merge partial results across
    ring steps / KV shards.
    """
    B, Sq, H, D = q.shape
    _, Sk, KVH, _ = k.shape
    assert H % KVH == 0, (H, KVH)
    group = H // KVH
    scale = softmax_scale if softmax_scale is not None else D ** -0.5

    q_pos = _broadcast_pos(q_pos, B)
    kv_pos = _broadcast_pos(kv_pos, B)

    qf = q.astype(jnp.float32).reshape(B, Sq, KVH, group, D)
    kf = k.astype(jnp.float32)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kf) * scale    # (B,KVH,g,Sq,Sk)

    mask = jnp.ones((B, Sq, Sk), dtype=bool)
    if causal:
        mask &= kv_pos[:, None, :] <= q_pos[:, :, None]
    if window is not None:
        mask &= (q_pos[:, :, None] - kv_pos[:, None, :]) < window
    if kv_valid is not None:
        mask &= kv_valid[:, None, :]
    logits = jnp.where(mask[:, None, None, :, :], logits, NEG_INF)

    m = jnp.max(logits, axis=-1, keepdims=True)
    m = jnp.maximum(m, NEG_INF)                                 # rows fully masked
    unnorm = jnp.exp(logits - m)
    denom = jnp.sum(unnorm, axis=-1, keepdims=True)
    probs = unnorm / jnp.maximum(denom, 1e-30)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(jnp.float32))
    out = out.reshape(B, Sq, H, D).astype(q.dtype)
    if not with_lse:
        return out
    lse = (m[..., 0] + jnp.log(jnp.maximum(denom[..., 0], 1e-30)))  # (B,KVH,g,Sq)
    lse = lse.reshape(B, H, Sq)
    return out, lse


def attention_ref_blocked(q, k, v, q_pos, kv_pos, *, causal=True,
                          window=None, kv_valid=None, softmax_scale=None,
                          with_lse=False, block_q: int = 256):
    """Memory-bounded oracle: lax.map over query blocks.

    Numerically identical to attention_ref, but live intermediates are
    bounded to one (block_q x Sk) logits tile — this is the execution path
    for full-depth dry-run compiles, where the plain oracle's (Sq x Sk)
    materialisation would report unrealistic per-device temp memory (on TPU
    the Pallas flash kernel keeps those tiles in VMEM).
    """
    B, Sq, H, D = q.shape
    bq = min(block_q, Sq)
    pad = (-Sq) % bq
    q_pos = _broadcast_pos(q_pos, B)
    if pad:
        q = jnp.concatenate(
            [q, jnp.zeros((B, pad) + q.shape[2:], q.dtype)], axis=1)
        # padded queries sit at INT32_MAX: fully masked under causal+window
        q_pos = jnp.concatenate(
            [q_pos, jnp.full((B, pad), 2**31 - 1, jnp.int32)], axis=1)
    nb = q.shape[1] // bq
    qb = q.reshape(B, nb, bq, H, D).transpose(1, 0, 2, 3, 4)
    pb = q_pos.reshape(B, nb, bq).transpose(1, 0, 2)

    def body(xs):
        qi, pi = xs
        return attention_ref(qi, k, v, pi, kv_pos, causal=causal,
                             window=window, kv_valid=kv_valid,
                             softmax_scale=softmax_scale, with_lse=True)

    outs, lses = jax.lax.map(body, (qb, pb))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(B, nb * bq, H, D)[:, :Sq]
    if not with_lse:
        return out
    lse = lses.transpose(1, 2, 0, 3).reshape(B, H, nb * bq)[:, :, :Sq]
    return out, lse


def merge_partials(outs: list[jax.Array], lses: list[jax.Array]
                   ) -> Tuple[jax.Array, jax.Array]:
    """Merge partial attention results (o_i, lse_i) over disjoint KV shards.

    outs[i]: (B, Sq, H, D) — softmax-normalised within shard i.
    lses[i]: (B, H, Sq).
    """
    lse_all = jnp.stack(lses)                                   # (N, B, H, Sq)
    lse = jax.scipy.special.logsumexp(lse_all, axis=0)          # (B, H, Sq)
    out = 0.0
    for o_i, l_i in zip(outs, lses):
        w = jnp.exp(l_i - lse)                                  # (B, H, Sq)
        out = out + o_i.astype(jnp.float32) * w.transpose(0, 2, 1)[..., None]
    return out.astype(outs[0].dtype), lse


def decode_attention_ref(
    q: jax.Array,                      # (B, H, D) — one new token per seq
    k_cache: jax.Array,                # (B, S, KVH, D)
    v_cache: jax.Array,                # (B, S, KVH, D)
    lengths: jax.Array,                # (B,) int32 — valid cache length
    *,
    window: Optional[int] = None,
    softmax_scale: Optional[float] = None,
    with_lse: bool = False,
    kv_offset: int = 0,                # global position of k_cache[:, 0]
):
    """Single-token decode attention over a (possibly sharded) KV cache."""
    B, S, KVH, D = k_cache.shape
    kv_pos = kv_offset + jnp.arange(S, dtype=jnp.int32)
    kv_valid = kv_pos[None, :] < lengths[:, None]
    if window is not None:
        kv_valid &= kv_pos[None, :] >= (lengths[:, None] - window)
    res = attention_ref(q[:, None], k_cache, v_cache,
                        q_pos=lengths[:, None] - 1 + jnp.zeros((B, 1), jnp.int32),
                        kv_pos=jnp.broadcast_to(kv_pos[None], (B, S)),
                        causal=False, kv_valid=kv_valid,
                        softmax_scale=softmax_scale, with_lse=with_lse)
    if with_lse:
        out, lse = res
        return out[:, 0], lse[:, :, 0]                          # (B,H,D), (B,H)
    return res[:, 0]


def sharded_pool_view(pool: jax.Array, tables: jax.Array) -> jax.Array:
    """Dense logical-order view of a *sequence-parallel sharded* paged
    pool (serving/cache_manager.PagedKVCache with ``kv_shards > 1``).

    pool: (n_shards, blocks_per_shard + 1, page, KVH, D); tables:
    (n_shards, B, npg_local) local page ids, where row s column j holds
    the sequence's logical page ``j * n_shards + s`` (striped layout).
    Returns (B, npg_local * n_shards * page, KVH, D) with tokens at their
    logical flat positions — scratch-padded table entries land at
    positions at/past the valid length, so the usual ``idx < length``
    masking covers them."""
    n, B, npg = tables.shape
    page = pool.shape[2]
    g = pool[jnp.arange(n)[:, None, None], tables]  # (n, B, npg, page, ...)
    g = jnp.moveaxis(g, 0, 2)                       # (B, npg, n, page, ...)
    return g.reshape(B, npg * n * page, *pool.shape[3:])


def paged_decode_attention_ref(
    q: jax.Array,                      # (B, H, D)
    k_pool: jax.Array,                 # ([L,] n_pages, page, KVH, D)
    v_pool: jax.Array,
    block_tables: jax.Array,           # (B, pages_per_seq) int32 page ids
    lengths: jax.Array,                # (B,) int32 — valid cache length
    *,
    window: Optional[int] = None,
    softmax_scale: Optional[float] = None,
    with_lse: bool = False,
    page_pos: Optional[jax.Array] = None,  # (B, pages_per_seq) int32
    layer: Optional[jax.Array] = None,     # () int32, with an L axis
):
    """Single-token decode attention straight off a paged KV pool.

    Pure-JAX gather fallback for the block-table layout: dereference each
    sequence's page list into a dense per-batch view sized to the current
    table width (``pages_per_seq * page``, i.e. the longest live allocation
    — NOT a global max_seq), then run ``decode_attention_ref``.  This is
    the CPU/non-Pallas execution path behind
    ``ops.paged_decode_attention``; on TPU the scalar-prefetch kernel
    ``flash_decode.paged_flash_decode`` skips the materialisation entirely.

    ``page_pos`` (2-dim tables only) gives each table column's first-token
    logical position — a shard of a striped pool passes its pages' global
    stripe positions, so length AND window masks apply natively to the
    shard-local view (the per-shard paged decode path; matches the
    kernel's scalar-prefetch argument of the same name).  ``layer`` picks
    one layer of layer-stacked pools (2-dim tables only), gathered in the
    same indexing as the pages, as the kernel's index map does.

    Also accepts the sequence-parallel sharded layout (3-dim
    ``block_tables`` (n_shards, B, npg_local) + 5-dim pools): the striped
    pages are gathered back into logical order first — the single-process
    oracle the shard_map split-KV path
    (core/ring_attention.sharded_paged_decode) is validated against.
    """
    if block_tables.ndim == 3:
        assert page_pos is None, "page_pos applies to shard-local tables"
        k = sharded_pool_view(k_pool, block_tables)
        v = sharded_pool_view(v_pool, block_tables)
    else:
        B, npg = block_tables.shape
        page, kv_dims = k_pool.shape[-3], k_pool.shape[-2:]
        at = block_tables if layer is None else (layer, block_tables)
        k = k_pool[at].reshape(B, npg * page, *kv_dims)
        v = v_pool[at].reshape(B, npg * page, *kv_dims)
        if page_pos is not None:
            kv_pos = (page_pos[:, :, None] +
                      jnp.arange(page, dtype=jnp.int32)[None, None]
                      ).reshape(B, npg * page)
            kv_valid = kv_pos < lengths[:, None]
            if window is not None:
                kv_valid &= kv_pos >= (lengths[:, None] - window)
            res = attention_ref(
                q[:, None], k, v,
                q_pos=lengths[:, None] - 1 + jnp.zeros((B, 1), jnp.int32),
                kv_pos=kv_pos, causal=False, kv_valid=kv_valid,
                softmax_scale=softmax_scale, with_lse=with_lse)
            if with_lse:
                out, lse = res
                return out[:, 0], lse[:, :, 0]
            return res[:, 0]
    return decode_attention_ref(q, k, v, lengths, window=window,
                                softmax_scale=softmax_scale,
                                with_lse=with_lse)


def paged_prefill_attention_ref(
    q: jax.Array,                      # (B, Sq, H, D) — current chunk queries
    k_new: jax.Array,                  # (B, Sq, KVH, D) — current chunk K
    v_new: jax.Array,                  # (B, Sq, KVH, D)
    q_pos: jax.Array,                  # (Sq,) or (B, Sq) int32
    kv_pos_new: jax.Array,             # (Sq,) or (B, Sq) int32
    k_pool: jax.Array,                 # (n_pages, page, KVH, D)
    v_pool: jax.Array,
    block_tables: jax.Array,           # (B, pages_per_seq) int32 page ids
    hist_len: jax.Array,               # (B,) int32 — valid history tokens
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softmax_scale: Optional[float] = None,
):
    """CDSP chunk prefill attending to paged cross-chunk history.

    The chunk's queries attend over [history pages ++ own chunk K/V]:
    history KV lives in a block pool in *natural token order* (the engine
    scatters each chunk's KV into pages by logical position), so history
    positions are simply the flat table index and validity is
    ``idx < hist_len``.  Pure-JAX gather fallback — the CPU/non-Pallas
    execution path behind ``ops.paged_prefill_attention``; on TPU the
    scalar-prefetch kernel ``flash_attention.paged_flash_prefill`` +
    ``merge_partials`` skips the dense materialisation.

    Accepts the sequence-parallel sharded pool layout too (3-dim
    ``block_tables`` + 5-dim pools, see ``sharded_pool_view``) — the
    single-process oracle for ``core/ring_attention.ring_paged_prefill``
    and the fallback when a chunk's length does not divide over the ring.
    """
    B, Sq = q.shape[:2]
    if block_tables.ndim == 3:
        hk = sharded_pool_view(k_pool, block_tables)
        hv = sharded_pool_view(v_pool, block_tables)
        S_h = hk.shape[1]
    else:
        npg = block_tables.shape[1]
        page = k_pool.shape[1]
        S_h = npg * page
        hk = k_pool[block_tables].reshape(B, S_h, *k_pool.shape[2:])
        hv = v_pool[block_tables].reshape(B, S_h, *v_pool.shape[2:])
    hist_pos = jnp.arange(S_h, dtype=jnp.int32)
    k = jnp.concatenate([hk.astype(k_new.dtype), k_new], axis=1)
    v = jnp.concatenate([hv.astype(v_new.dtype), v_new], axis=1)
    kv_pos = jnp.concatenate(
        [jnp.broadcast_to(hist_pos[None], (B, S_h)),
         _broadcast_pos(kv_pos_new, B)], axis=1)
    kv_valid = jnp.concatenate(
        [hist_pos[None, :] < hist_len[:, None],
         jnp.ones((B, Sq), bool)], axis=1)
    return attention_ref(q, k, v, q_pos, kv_pos, causal=causal,
                         window=window, kv_valid=kv_valid,
                         softmax_scale=softmax_scale)


# ------------------------------------------------------------------ mamba-2
def ssd_ref(x: jax.Array,              # (B, S, H, P)  — per-head inputs
            dt: jax.Array,             # (B, S, H)     — softplus'd step sizes
            A: jax.Array,              # (H,)          — negative decay rates
            Bm: jax.Array,             # (B, S, G, N)  — input matrices
            Cm: jax.Array,             # (B, S, G, N)  — output matrices
            *,
            h0: Optional[jax.Array] = None,   # (B, H, P, N) initial state
            return_state: bool = False):
    """Naive sequential SSD (state-space duality) recurrence — the oracle.

    h_t = exp(dt_t * A) * h_{t-1} + dt_t * x_t B_t^T ;  y_t = C_t h_t^T
    Grouped B/C: head h uses group h // (H // G).
    """
    B_, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    Bf = jnp.repeat(Bm.astype(jnp.float32), rep, axis=2)        # (B,S,H,N)
    Cf = jnp.repeat(Cm.astype(jnp.float32), rep, axis=2)
    decay = jnp.exp(dtf * A[None, None, :])                     # (B,S,H)

    def step(h, t):
        d, xt, bt, ct, dtt = t
        h = h * d[:, :, None, None] + (dtt[:, :, None] * xt)[..., None] * bt[:, :, None, :]
        y = jnp.einsum("bhn,bhpn->bhp", ct, h)
        return h, y

    init = (jnp.zeros((B_, H, P, N), jnp.float32) if h0 is None
            else h0.astype(jnp.float32))
    xs = (jnp.moveaxis(decay, 1, 0), jnp.moveaxis(xf, 1, 0),
          jnp.moveaxis(Bf, 1, 0), jnp.moveaxis(Cf, 1, 0),
          jnp.moveaxis(dtf, 1, 0))
    h_final, ys = jax.lax.scan(step, init, xs)
    y = jnp.moveaxis(ys, 0, 1).astype(x.dtype)                  # (B,S,H,P)
    if return_state:
        return y, h_final.astype(jnp.float32)
    return y


def ssd_chunked_ref(x, dt, A, Bm, Cm, *, chunk: int = 64,
                    h0=None, return_state: bool = False):
    """Chunked (quadratic-intra / recurrent-inter) SSD — matches ssd_ref.

    This is the blocked algorithm the Pallas kernel and the sharded
    (sequence-parallel) path implement; kept in jnp as a second oracle.
    """
    B_, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    f32 = jnp.float32

    xf = x.astype(f32).reshape(B_, nc, chunk, H, P)
    dtf = dt.astype(f32).reshape(B_, nc, chunk, H)
    Bf = jnp.repeat(Bm.astype(f32), rep, axis=2).reshape(B_, nc, chunk, H, N)
    Cf = jnp.repeat(Cm.astype(f32), rep, axis=2).reshape(B_, nc, chunk, H, N)

    a = dtf * A[None, None, None, :]                            # (B,nc,L,H) ≤ 0
    a_cum = jnp.cumsum(a, axis=2)                               # inclusive
    a_total = a_cum[:, :, -1]                                   # (B,nc,H)

    # ---- intra-chunk (attention-like, causal) ----
    # L[i,j] = exp(a_cum_i - a_cum_j) for i >= j
    seg = a_cum[:, :, :, None, :] - a_cum[:, :, None, :, :]     # (B,nc,L,L,H)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    L = jnp.where(causal[None, None, :, :, None], jnp.exp(seg), 0.0)
    scores = jnp.einsum("bclhn,bcmhn->bclmh", Cf, Bf)           # CB^T
    y_intra = jnp.einsum("bclmh,bclmh,bcmh,bcmhp->bclhp",
                         scores, L, dtf, xf)

    # ---- chunk states ----
    # state contribution of chunk c: sum_j exp(a_total - a_cum_j) dt_j B_j x_j^T
    w = jnp.exp(a_total[:, :, None, :] - a_cum) * dtf           # (B,nc,L,H)
    states = jnp.einsum("bclh,bclhn,bclhp->bchpn", w, Bf, xf)   # (B,nc,H,P,N)

    # ---- inter-chunk recurrence over chunk states ----
    def step(h, t):
        dtot, s = t
        h_new = h * jnp.exp(dtot)[:, :, None, None] + s
        return h_new, h                                         # emit state BEFORE chunk
    init = (jnp.zeros((B_, H, P, N), f32) if h0 is None else h0.astype(f32))
    h_final, h_prev = jax.lax.scan(
        step, init, (jnp.moveaxis(a_total, 1, 0), jnp.moveaxis(states, 1, 0)))
    h_prev = jnp.moveaxis(h_prev, 0, 1)                         # (B,nc,H,P,N)

    # ---- inter-chunk output: y += C_i exp(a_cum_i) h_prev ----
    y_inter = jnp.einsum("bclhn,bclh,bchpn->bclhp",
                         Cf, jnp.exp(a_cum), h_prev)
    y = (y_intra + y_inter).reshape(B_, S, H, P).astype(x.dtype)
    if return_state:
        return y, h_final
    return y


def ssd_decode_ref(x, dt, A, Bm, Cm, h):
    """One-token SSD state update.  x:(B,H,P) dt:(B,H) Bm/Cm:(B,G,N)
    h:(B,H,P,N) -> (y:(B,H,P), h_new)."""
    H = x.shape[1]
    G = Bm.shape[1]
    rep = H // G
    f32 = jnp.float32
    Bf = jnp.repeat(Bm.astype(f32), rep, axis=1)
    Cf = jnp.repeat(Cm.astype(f32), rep, axis=1)
    decay = jnp.exp(dt.astype(f32) * A[None, :])                # (B,H)
    h_new = (h.astype(f32) * decay[:, :, None, None]
             + (dt.astype(f32)[:, :, None] * x.astype(f32))[..., None]
             * Bf[:, :, None, :])
    y = jnp.einsum("bhn,bhpn->bhp", Cf, h_new).astype(x.dtype)
    return y, h_new
