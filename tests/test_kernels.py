"""Pallas kernel validation: interpret-mode sweeps vs the jnp oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_decode import flash_decode
from repro.kernels.ssd_scan import ssd_scan
from repro.kernels import ref

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


@pytest.mark.parametrize("B,Sq,Sk,H,KVH,D", [
    (1, 128, 128, 1, 1, 32),
    (2, 256, 256, 4, 2, 64),
    (2, 128, 384, 8, 8, 64),     # MHA, Sq != Sk (CDSP chunk w/ history)
    (1, 512, 512, 4, 1, 128),    # MQA, head_dim 128
    (1, 200, 333, 4, 2, 64),     # lengths off the block grid: padded
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, Sq, Sk, H, KVH, D, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand(ks[0], (B, Sq, H, D), dtype)
    k = _rand(ks[1], (B, Sk, KVH, D), dtype)
    v = _rand(ks[2], (B, Sk, KVH, D), dtype)
    # chunked-prefill style positions: queries sit AFTER the kv prefix
    q_pos = jnp.arange(Sk - Sq, Sk, dtype=jnp.int32)
    kv_pos = jnp.arange(Sk, dtype=jnp.int32)
    got, lse_got = flash_attention(q, k, v, q_pos, kv_pos, causal=True,
                                   interpret=True, with_lse=True)
    want, lse_want = ref.attention_ref(q, k, v, q_pos, kv_pos, causal=True,
                                       with_lse=True)
    tol = TOL[dtype]
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol,
                               rtol=tol)
    np.testing.assert_allclose(lse_got, lse_want, atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("window", [16, 64])
def test_flash_attention_window(window):
    B, S, H, D = 2, 256, 4, 32
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (_rand(ks[i], (B, S, H if i == 0 else 2, D), jnp.float32)
               for i in range(3))
    pos = jnp.arange(S, dtype=jnp.int32)
    got = flash_attention(q, k, v, pos, pos, causal=True, window=window,
                          interpret=True)
    want = ref.attention_ref(q, k, v, pos, pos, causal=True, window=window)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_flash_attention_zigzag_positions():
    """Kernel masking must be correct for non-contiguous (zigzag) layouts."""
    from repro.core.zigzag import zigzag_positions, zigzag_shard, zigzag_unshard
    B, S, H, D, N = 1, 256, 2, 32, 4
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q, k, v = (_rand(ks[i], (B, S, H, D), jnp.float32) for i in range(3))
    pos = zigzag_positions(S, N)
    got = flash_attention(zigzag_shard(q, N), zigzag_shard(k, N),
                          zigzag_shard(v, N), pos, pos, causal=True,
                          interpret=True)
    got = zigzag_unshard(got, N)
    want = ref.attention_ref(q, k, v, jnp.arange(S), jnp.arange(S))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("B,S,H,KVH,D", [
    (2, 256, 4, 2, 64), (3, 512, 8, 8, 64), (1, 1024, 8, 1, 128),
    (2, 300, 4, 2, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_sweep(B, S, H, KVH, D, dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = _rand(ks[0], (B, H, D), dtype)
    k = _rand(ks[1], (B, S, KVH, D), dtype)
    v = _rand(ks[2], (B, S, KVH, D), dtype)
    lens = jax.random.randint(ks[3], (B,), 1, S + 1)
    got, lg = flash_decode(q, k, v, lens, interpret=True, with_lse=True)
    want, lw = ref.decode_attention_ref(q, k, v, lens, with_lse=True)
    tol = TOL[dtype]
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol,
                               rtol=tol)
    np.testing.assert_allclose(lg, lw, atol=1e-3, rtol=1e-3)


def test_flash_decode_window():
    B, S, H, KVH, D = 2, 512, 4, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = _rand(ks[0], (B, H, D), jnp.float32)
    k = _rand(ks[1], (B, S, KVH, D), jnp.float32)
    v = _rand(ks[2], (B, S, KVH, D), jnp.float32)
    lens = jnp.array([400, 512])
    got = flash_decode(q, k, v, lens, window=128, interpret=True)
    want = ref.decode_attention_ref(q, k, v, lens, window=128)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("B,S,H,P,G,N,chunk", [
    (1, 64, 2, 16, 1, 16, 16),
    (2, 128, 4, 16, 2, 32, 32),
    (2, 256, 8, 32, 1, 64, 64),
])
def test_ssd_scan_sweep(B, S, H, P, G, N, chunk):
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    x = _rand(ks[0], (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(_rand(ks[1], (B, S, H), jnp.float32))
    A = -jnp.exp(_rand(ks[2], (H,), jnp.float32))
    Bm = _rand(ks[3], (B, S, G, N), jnp.float32)
    Cm = _rand(ks[4], (B, S, G, N), jnp.float32)
    h0 = _rand(ks[5], (B, H, P, N), jnp.float32)
    y0, h_f0 = ref.ssd_ref(x, dt, A, Bm, Cm, h0=h0, return_state=True)
    y1, h_f1 = ref.ssd_chunked_ref(x, dt, A, Bm, Cm, chunk=chunk, h0=h0,
                                   return_state=True)
    y2, h_f2 = ssd_scan(x, dt, A, Bm, Cm, h0=h0, chunk=chunk, interpret=True)
    np.testing.assert_allclose(y1, y0, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(y2, y0, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(h_f1, h_f0, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(h_f2, h_f0, atol=2e-4, rtol=2e-4)


def test_ssd_decode_matches_scan_step():
    B, H, P, G, N = 2, 4, 16, 1, 16
    ks = jax.random.split(jax.random.PRNGKey(6), 6)
    S = 8
    x = _rand(ks[0], (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(_rand(ks[1], (B, S, H), jnp.float32))
    A = -jnp.exp(_rand(ks[2], (H,), jnp.float32))
    Bm = _rand(ks[3], (B, S, G, N), jnp.float32)
    Cm = _rand(ks[4], (B, S, G, N), jnp.float32)
    y_all, h = ref.ssd_ref(x, dt, A, Bm, Cm, return_state=True)
    # replay the same sequence one token at a time
    state = jnp.zeros((B, H, P, N))
    for t in range(S):
        y_t, state = ref.ssd_decode_ref(x[:, t], dt[:, t], A, Bm[:, t],
                                        Cm[:, t], state)
        np.testing.assert_allclose(y_t, y_all[:, t], atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(state, h, atol=2e-4, rtol=2e-4)


def test_attention_ref_blocked_equals_plain():
    B, S, H, D = 2, 300, 4, 32           # deliberately not a block multiple
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = _rand(ks[0], (B, S, H, D), jnp.float32)
    k = _rand(ks[1], (B, S, 2, D), jnp.float32)
    v = _rand(ks[2], (B, S, 2, D), jnp.float32)
    pos = jnp.arange(S, dtype=jnp.int32)
    a, la = ref.attention_ref_blocked(q, k, v, pos, pos, with_lse=True,
                                      block_q=128)
    b, lb = ref.attention_ref(q, k, v, pos, pos, with_lse=True)
    np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(la, lb, atol=1e-4, rtol=1e-4)


def test_merge_partials_property():
    """Merging disjoint KV shards == attention over the full KV."""
    B, S, H, D = 2, 128, 4, 32
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    q = _rand(ks[0], (B, 16, H, D), jnp.float32)
    k = _rand(ks[1], (B, S, H, D), jnp.float32)
    v = _rand(ks[2], (B, S, H, D), jnp.float32)
    q_pos = jnp.arange(S - 16, S, dtype=jnp.int32)
    outs, lses = [], []
    for i in range(4):
        sl = slice(i * 32, (i + 1) * 32)
        o, l = ref.attention_ref(q, k[:, sl], v[:, sl], q_pos,
                                 jnp.arange(i * 32, (i + 1) * 32),
                                 causal=True, with_lse=True)
        outs.append(o)
        lses.append(l)
    got, _ = ref.merge_partials(outs, lses)
    want = ref.attention_ref(q, k, v, q_pos, jnp.arange(S), causal=True)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def _stripe_shard(rng, n, idx, k, v, page):
    """One shard's view of an n-way striped pool: this shard holds global
    pages ``j * n + idx`` (permuted local ids, last local id = scratch).
    Returns (k_loc, v_loc, bt_loc, page_pos) — the exact inputs the
    sharded decode island hands to ``ops.paged_decode_attention``."""
    k, v = np.asarray(k), np.asarray(v)
    B, S = k.shape[:2]
    npg = S // page
    npg_loc = -(-npg // n)
    bps = B * npg_loc
    kp = np.zeros((bps + 1, page) + k.shape[2:], np.float32)
    vp = np.zeros_like(kp)
    bt = np.full((B, npg_loc), bps, np.int32)
    order = list(rng.permutation(bps))
    for b in range(B):
        for jloc in range(npg_loc):
            g = jloc * n + idx
            if g >= npg:
                continue
            lid = order.pop()
            bt[b, jloc] = lid
            kp[lid] = k[b, g * page:(g + 1) * page]
            vp[lid] = v[b, g * page:(g + 1) * page]
    gpage = np.arange(npg_loc, dtype=np.int32) * n + idx
    page_pos = np.broadcast_to((gpage * page)[None], (B, npg_loc))
    return (jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(bt),
            jnp.asarray(page_pos.copy()))


@pytest.mark.parametrize("window", [None, 11])
def test_paged_decode_stripe_page_pos_interpret(window):
    """Windowed sharded-decode shard partials, interpret-mode kernel:
    each stripe shard's ``paged_flash_decode`` call (strided global
    ``page_pos``, native length/window masks) merges by LSE into exactly
    the dense-window oracle — the kernel-level half of
    ``sharded_paged_decode`` with the gather-slab fallback gone."""
    from repro.kernels.flash_decode import paged_flash_decode
    B, H, KVH, D, page, n = 2, 4, 2, 16, 8, 2
    S = 6 * page
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = _rand(ks[0], (B, H, D), jnp.float32)
    k = _rand(ks[1], (B, S, KVH, D), jnp.float32)
    v = _rand(ks[2], (B, S, KVH, D), jnp.float32)
    lengths = jnp.asarray([S - 3, 17], jnp.int32)
    rng = np.random.default_rng(3)
    outs, lses = [], []
    for idx in range(n):
        kp, vp, bt, pp = _stripe_shard(rng, n, idx, k, v, page)
        o, l = paged_flash_decode(q, kp, vp, bt, lengths, window=window,
                                  page_pos=pp, with_lse=True,
                                  interpret=True)
        outs.append(o[:, None])
        lses.append(l[..., None])
    got, _ = ref.merge_partials(outs, lses)
    want = ref.decode_attention_ref(q, k, v, lengths, window=window)
    np.testing.assert_allclose(np.asarray(got[:, 0]), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_paged_append_attend_fused_and_donated():
    """The fused decode tick: ``ops.paged_decode_attention(..., k_new)``
    matches scatter-then-attend exactly, and the donated pools are
    updated IN PLACE — buffer identity, no silent copy."""
    from repro.kernels import ops
    B, H, KVH, D, page, npg = 2, 4, 2, 16, 8, 4
    npages = 16
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    kp = _rand(ks[0], (npages + 1, page, KVH, D), jnp.float32)
    vp = _rand(ks[1], (npages + 1, page, KVH, D), jnp.float32)
    q = _rand(ks[2], (B, H, D), jnp.float32)
    kn = _rand(ks[3], (B, KVH, D), jnp.float32)
    vn = _rand(ks[4], (B, KVH, D), jnp.float32)
    bt = jnp.asarray(
        np.random.default_rng(0).permutation(npages)[:B * npg]
        .reshape(B, npg).astype(np.int32))
    lengths = jnp.asarray([13, 29], jnp.int32)
    bidx = jnp.arange(B)
    phys, slot = bt[bidx, lengths // page], lengths % page
    # oracle: separate scatter then attend
    kp_o = kp.at[phys, slot].set(kn)
    vp_o = vp.at[phys, slot].set(vn)
    want = ops.paged_decode_attention(q, kp_o, vp_o, bt, lengths + 1,
                                      impl="ref")
    ptr_k = kp.unsafe_buffer_pointer()
    ptr_v = vp.unsafe_buffer_pointer()
    o, kp2, vp2 = ops.paged_decode_attention(
        q, kp, vp, bt, lengths, impl="ref", k_new=kn, v_new=vn,
        append_page=phys, append_slot=slot)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(kp2), np.asarray(kp_o))
    np.testing.assert_array_equal(np.asarray(vp2), np.asarray(vp_o))
    assert kp2.unsafe_buffer_pointer() == ptr_k, "k pool was copied"
    assert vp2.unsafe_buffer_pointer() == ptr_v, "v pool was copied"


def test_page_helper_donation_no_copy():
    """donate_argnums audit: every pool-writing page helper updates its
    (donated) pool buffer in place — buffer identity across the call."""
    from repro.kernels import flash_decode as fd
    nb, npages, page, KVH, D = 2, 8, 4, 2, 8
    pool = jnp.zeros((nb, npages + 1, page, KVH, D), jnp.float32)
    ptr = pool.unsafe_buffer_pointer()
    pool = fd.scatter_kv_prefill(
        pool, jnp.arange(4, dtype=jnp.int32),
        jnp.ones((nb, 3 * page, KVH, D), jnp.float32))
    assert pool.unsafe_buffer_pointer() == ptr
    pool = fd.scatter_kv_token(
        pool, jnp.zeros((1, 4), jnp.int32), jnp.asarray([5], jnp.int32),
        jnp.ones((nb, 1, KVH, D), jnp.float32))
    assert pool.unsafe_buffer_pointer() == ptr
    pool = fd.scatter_kv_blocks(
        pool, jnp.asarray([6], jnp.int32),
        jnp.ones((nb, 1, page, KVH, D), jnp.float32))
    assert pool.unsafe_buffer_pointer() == ptr
    pool = fd.copy_kv_block_within(pool, jnp.asarray(6, jnp.int32),
                                   jnp.asarray(7, jnp.int32))
    assert pool.unsafe_buffer_pointer() == ptr


@pytest.mark.parametrize("impl", ["ref", "pallas", "interpret"])
def test_sharded_pool_layout_only_on_the_gather_oracle(impl):
    """Only the gather oracle reads the sharded (3-dim table) pool layout:
    under a Pallas impl the ops raise instead of handing it the call."""
    from repro.kernels import ops
    n, B, npg, page, KVH, H, D, S = 2, 1, 2, 8, 1, 2, 16, 4
    pool = jnp.zeros((n, npg + 1, page, KVH, D), jnp.float32)
    bt = jnp.zeros((n, B, npg), jnp.int32)
    lens = jnp.ones((B,), jnp.int32)
    q = jnp.zeros((B, S, H, D), jnp.float32)
    kv = jnp.zeros((B, S, KVH, D), jnp.float32)
    pos = jnp.arange(S, dtype=jnp.int32)[None] + 1
    calls = (
        lambda: ops.paged_decode_attention(q[:, 0], pool, pool, bt, lens,
                                           impl=impl),
        lambda: ops.paged_prefill_attention(q, kv, kv, pos, pos, pool, pool,
                                            bt, lens, impl=impl))
    for call in calls:
        if impl == "ref":
            assert bool(jnp.all(jnp.isfinite(call())))
        else:
            with pytest.raises(NotImplementedError, match="sharded"):
                call()
