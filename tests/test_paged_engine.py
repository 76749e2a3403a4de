"""Chunk-granular engine + paged KV: equivalence, event ordering,
preemption/requeue (mid-prefill and decode-side), grow-on-demand block
allocation under pool pressure, and the paged kernel primitives."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import generate_dense as _generate
from repro.core.chunk_planner import Allocation, Chunk
from repro.core.improvement_rate import DynamicRateController
from repro.core.latency_model import table1_model
from repro.serving.engine import ServingEngine
from repro.serving.request import Request
from repro.serving.simulator import ClusterSpec, Policy, make_policy

MODEL = table1_model()


class TwoChunkPolicy(Policy):
    """Deterministic plan: prompts >= 32 tokens run as two chunks with an
    SP-size change (1 -> 2); shorter remainders run single-chunk.  Keeps
    chunk-granular paths exercised at test-sized prompts (the real CDSP
    planner only chunks above min_chunk_tokens)."""
    name = "two_chunk"

    def plan(self, req, pool, now):
        L = req.prompt_len
        if L >= 32:
            l0 = L // 2
            t_q = max(pool[i] for i in (0,))
            t0 = t_q + self.model.latency(1, 0, l0)
            t1 = max(t0, pool[1]) + self.model.latency(2, l0, L - l0)
            return Allocation([Chunk(l0, (0,), t_q, t0),
                               Chunk(L - l0, (0, 1), t0, t1)])
        t_q = max(pool[i] for i in (2,))
        t_p = self.model.latency(1, 0, L)
        return Allocation([Chunk(L, (2,), t_q, t_q + t_p)])


def _spec():
    return ClusterSpec(n_prefill=8, n_decode=2, sp_candidates=(1, 2, 4))


# -------------------------------------------------------------- equivalence
@pytest.mark.parametrize("arch", ["yi-9b", "mamba2-1.3b"])
def test_multichunk_paged_equivalence(arch, reduced_params_cache):
    """Token-for-token: chunk-granular events + paged KV decode == direct
    dense autoregressive generation, across an SP-size change mid-prefill."""
    cfg, params = reduced_params_cache(arch)
    spec = _spec()
    eng = ServingEngine(cfg, params, spec, TwoChunkPolicy(MODEL, spec),
                        max_batch=4, max_seq=256, block_size=32)
    rng = np.random.default_rng(7)
    reqs = []
    for i in range(3):
        plen = int(rng.integers(40, 90))
        req = Request(rid=i, arrival=i * 0.03, prompt_len=plen, output_len=4)
        prompt = rng.integers(0, cfg.vocab_size, plen).astype(np.int32)
        eng.submit(req, prompt)
        reqs.append((req, prompt))
    outs = eng.serve()
    for req, prompt in reqs:
        assert len(req.chunk_plan) == 2, "plan must be multi-chunk"
        want = _generate(params, cfg, prompt, len(outs[req.rid]))
        assert outs[req.rid] == want, f"rid {req.rid} diverged"
        assert req.done is not None
    # every tick with rows took the in-place pool path; a model with no
    # attention layer has no pools and counts neither
    ticks = (eng.mixed_stats["standalone_ticks"]
             + eng.mixed_stats["piggyback_ticks"])
    counters = eng.metrics.snapshot()["counters"]
    has_pools = any(sp.mixer == "attn" for sp in cfg.pattern)
    assert counters.get("ticks/pool_in_place", 0) == (ticks if has_pools
                                                      else 0)
    assert "ticks/pool_copied" not in counters


# ------------------------------------------- decode tick: pools in place
def _paged_decode_state(cfg, params, lens=(9, 17, 12), page=8, npg=4):
    """Prefill each row on its own and land its attention KV in a shared
    page pool at permuted pages (the scratch page last), as the engine's
    decode instance holds it.  Returns (caches, first tokens, lengths):
    the decode cache tree ``DecodeInstance.build_caches`` would build."""
    from repro.kernels.flash_decode import scatter_kv_prefill
    from repro.models.sharding import CPU_CTX
    from repro.models.transformer import forward
    rng = np.random.default_rng(3)
    B, nb = len(lens), cfg.n_blocks
    n_pages = B * npg
    pages = rng.permutation(n_pages).reshape(B, npg).astype(np.int32)
    bt = jnp.broadcast_to(jnp.asarray(pages)[None], (nb, B, npg))
    pool_shape = (nb, n_pages + 1, page, cfg.n_kv_heads, cfg.head_dim_)
    caches, rows, first = {}, [], []
    for i, spec in enumerate(cfg.pattern):
        if spec.mixer == "attn":
            caches[str(i)] = {"self": {
                "k": jnp.zeros(pool_shape, jnp.dtype(cfg.dtype)),
                "v": jnp.zeros(pool_shape, jnp.dtype(cfg.dtype)),
                "block_table": bt}}
    for b, L in enumerate(lens):
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, L)),
                           jnp.int32)
        pos = jnp.arange(L, dtype=jnp.int32)[None]
        logits, _, c = forward(params, cfg, CPU_CTX, toks, pos, "prefill")
        first.append(int(jnp.argmax(logits[0, -1, :cfg.vocab_size])))
        rows.append(c)
        for key, ent in caches.items():
            for part in ("k", "v"):
                ent["self"][part] = scatter_kv_prefill(
                    ent["self"][part], jnp.asarray(pages[b]),
                    c[key]["self"][part][:, 0])
    for i, spec in enumerate(cfg.pattern):
        if spec.mixer != "attn":
            caches[str(i)] = {"self": jax.tree.map(
                lambda *xs: jnp.concatenate(xs, axis=1),
                *[c[str(i)]["self"] for c in rows])}
    return (caches, jnp.asarray(first, jnp.int32)[:, None],
            jnp.asarray(lens, jnp.int32))


@pytest.mark.parametrize("unroll", [False, True], ids=["scan", "unrolled"])
@pytest.mark.parametrize("arch", ["yi-9b", "chatglm3-6b",
                                  "jamba-1.5-large-398b"])
def test_in_place_decode_matches_scan_path(reduced_params_cache, monkeypatch,
                                           arch, unroll):
    """The decode step that carries the paged pools through the layer
    scan and updates them in place gives the same logits and the same
    pools, tick after tick, as the path that slices each layer's pool out
    as the scan's ``xs`` and stacks it back as its ``ys``; and it takes
    the pools it is given (donated: deleted after the call), where the
    ``xs``/``ys`` path leaves them alone.  Dense GQA (yi-9b), partial
    rotary with a 16:1 group (chatglm3-6b), and an attention layer among
    mamba and MoE layers (jamba), with the layer scan and unrolled."""
    from repro.models import transformer
    from repro.models.sharding import CPU_CTX
    cfg, params = reduced_params_cache(arch)
    ctx = CPU_CTX.with_(impl="ref", unroll_scan=unroll)
    caches, toks, clen = _paged_decode_state(cfg, params)
    attn = [k for k, e in caches.items() if "block_table" in e["self"]]
    assert attn, "the architecture must have an attention layer"
    copied = jax.tree.map(jnp.copy, caches)

    def tick(caches, toks, clen):
        logits, _, new = transformer.forward(
            params, cfg, ctx, toks, clen[:, None], "decode", caches=caches,
            cache_len=clen)
        return logits, new

    for _ in range(3):
        given = [caches[k]["self"][p] for k in attn for p in ("k", "v")]
        logits, caches = tick(caches, toks, clen)
        assert all(a.is_deleted() for a in given), "pools not donated"
        assert transformer.in_place_pools(copied)[0]
        with monkeypatch.context() as m:
            m.setattr(transformer, "in_place_pools", lambda c: ({}, c))
            kept = [copied[k]["self"][p] for k in attn for p in ("k", "v")]
            want, copied = tick(copied, toks, clen)
            assert not any(a.is_deleted() for a in kept)
        np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        for k in attn:
            for p in ("k", "v"):
                np.testing.assert_array_equal(
                    np.asarray(caches[k]["self"][p]),
                    np.asarray(copied[k]["self"][p]))
            assert (caches[k]["self"]["block_table"].shape
                    == copied[k]["self"]["block_table"].shape)
        toks = jnp.argmax(logits[:, :, :cfg.vocab_size], axis=-1).astype(
            jnp.int32)
        clen = clen + 1


# ------------------------------------------------------------ event ordering
def test_chunks_execute_at_scheduled_times(reduced_params_cache):
    """Every chunk's execution event fires exactly at the CDSP plan's
    scheduled start; prefill_done is the last chunk's scheduled end."""
    cfg, params = reduced_params_cache("yi-9b")
    spec = ClusterSpec(n_prefill=16, n_decode=2, sp_candidates=(1, 2, 4, 8))
    eng = ServingEngine(cfg, params, spec,
                        make_policy("tetris", MODEL, spec),
                        max_batch=4, max_seq=256)
    rng = np.random.default_rng(3)
    for i in range(4):
        plen = int(rng.integers(24, 80))
        req = Request(rid=i, arrival=i * 0.05, prompt_len=plen, output_len=3)
        eng.submit(req, rng.integers(0, cfg.vocab_size, plen))
    eng.serve()
    for r in eng.reqs.values():
        assert len(r.chunk_exec) == len(r.chunk_plan) >= 1
        for e, (s0, _) in zip(r.chunk_exec, r.chunk_sched):
            assert e == pytest.approx(s0, abs=1e-9)
        assert r.prefill_done == pytest.approx(r.chunk_sched[-1][1])
        assert r.chunk_exec == sorted(r.chunk_exec)
    # per-chunk log mirrors the request records
    for rid, log in eng.chunk_log.items():
        assert [c["exec_start"] for c in log] == eng.reqs[rid].chunk_exec


# -------------------------------------------------------- preempt / requeue
def test_preempt_requeues_and_matches_oracle(reduced_params_cache):
    """Preempting between chunks cancels the remaining schedule, re-plans
    the remainder under current load, and still generates exactly the
    dense-reference tokens."""
    cfg, params = reduced_params_cache("yi-9b")
    spec = _spec()
    eng = ServingEngine(cfg, params, spec, TwoChunkPolicy(MODEL, spec),
                        max_batch=4, max_seq=256, block_size=32)
    rng = np.random.default_rng(11)
    plen = 64
    req = Request(rid=0, arrival=0.0, prompt_len=plen, output_len=4)
    prompt = rng.integers(0, cfg.vocab_size, plen).astype(np.int32)
    eng.submit(req, prompt)
    # flag lands after chunk 0 executes (t=0) and before chunk 1's slot
    eng.preempt(0, at=1e-6)
    outs = eng.serve()
    assert req.preemptions == 1
    # 3 chunks total: original chunk 0, then the requeued remainder
    assert len(req.chunk_exec) == len(req.chunk_plan) == 3
    assert req.chunk_plan[0][0] + req.chunk_plan[1][0] \
        + req.chunk_plan[2][0] == plen
    # the requeued chunk runs at its re-scheduled time, not the stale one
    for e, (s0, _) in zip(req.chunk_exec, req.chunk_sched):
        assert e == pytest.approx(s0, abs=1e-9)
    want = _generate(params, cfg, prompt, len(outs[0]))
    assert outs[0] == want, "preempted request diverged from reference"


def test_preempt_with_delayed_replan(reduced_params_cache):
    """If the pool can't take the remainder at preemption time, the old
    plan must still be cancelled immediately (no stale chunk/prefill
    events) and the request must complete once re-planning succeeds."""
    cfg, params = reduced_params_cache("yi-9b")
    spec = _spec()

    class DelayedReplanPolicy(TwoChunkPolicy):
        def plan(self, req, pool, now):
            if req.arrival > 0 and now < 0.2:
                return None          # shadow re-plans rejected until t=0.2
            return super().plan(req, pool, now)

    eng = ServingEngine(cfg, params, spec,
                        DelayedReplanPolicy(MODEL, spec),
                        max_batch=4, max_seq=256, block_size=32)
    rng = np.random.default_rng(13)
    plen = 64
    req = Request(rid=0, arrival=0.0, prompt_len=plen, output_len=3)
    prompt = rng.integers(0, cfg.vocab_size, plen).astype(np.int32)
    eng.submit(req, prompt)
    eng.preempt(0, at=1e-6)
    outs = eng.serve()
    assert req.preemptions == 1
    assert len(req.chunk_exec) == len(req.chunk_plan)
    assert sum(c for c, _ in req.chunk_plan) == plen
    assert req.chunk_exec[1] >= 0.2          # remainder ran after re-plan
    want = _generate(params, cfg, prompt, len(outs[0]))
    assert outs[0] == want


# -------------------------------------------- grow-on-demand / exhaustion
class ParallelTwoChunkPolicy(TwoChunkPolicy):
    """TwoChunkPolicy, but each request prefills on its own instance pair
    (by rid) so several requests become co-resident in decode — needed to
    create genuine block-pool pressure at test scale."""
    name = "parallel_two_chunk"

    def plan(self, req, pool, now):
        L = req.prompt_len
        base = (2 * req.rid) % (self.spec.n_prefill - 1)
        if L >= 32:
            l0 = L // 2
            t_q = pool[base]
            t0 = t_q + self.model.latency(1, 0, l0)
            t1 = max(t0, pool[base + 1]) + self.model.latency(2, l0, L - l0)
            return Allocation([Chunk(l0, (base,), t_q, t0),
                               Chunk(L - l0, (base, base + 1), t0, t1)])
        t_q = pool[base]
        t_p = self.model.latency(1, 0, L)
        return Allocation([Chunk(L, (base,), t_q, t_q + t_p)])


def _serve_batch(cfg, params, max_seq, *, n_req=3, prompt_len=60,
                 output_len=12, watermark=0.0):
    """Serve ``n_req`` identical-shape requests on one decode instance with
    a block pool of ``4 * max_seq / 16`` blocks; returns the engine."""
    spec = ClusterSpec(n_prefill=8, n_decode=1, sp_candidates=(1, 2, 4))
    eng = ServingEngine(cfg, params, spec,
                        ParallelTwoChunkPolicy(MODEL, spec),
                        max_batch=4, max_seq=max_seq, block_size=16,
                        preempt_watermark=watermark)
    rng = np.random.default_rng(21)
    for i in range(n_req):
        # near-simultaneous arrivals: everyone is admitted (at prompt-sized
        # allocations) before the first page-boundary crossing, so decode
        # growth — not admission — is what hits the pool limit
        req = Request(rid=i, arrival=i * 0.005, prompt_len=prompt_len,
                      output_len=output_len)
        eng.submit(req, rng.integers(0, cfg.vocab_size,
                                     prompt_len).astype(np.int32))
    eng.serve()
    return eng


def test_block_exhaustion_preemption_equivalence(reduced_params_cache):
    """Grow-on-demand: admission commits only prompt blocks, decode growth
    exhausts a tight pool, a decode-side preemption fires automatically,
    and after requeue generation is token-for-token identical to the
    unpressured run."""
    cfg, params = reduced_params_cache("yi-9b")
    # roomy pool: 32 blocks, 3 x blocks_for(72)=5 fits, no preemption
    calm = _serve_batch(cfg, params, max_seq=128)
    assert calm.preempt_log == []
    # tight pool: 12 blocks; 3 x blocks_for(60)=4 admit (grow-on-demand),
    # but growth past the 64-token page boundary cannot fit all three
    tight = _serve_batch(cfg, params, max_seq=48)
    assert tight.preempt_log, "pool pressure must trigger decode preemption"
    assert any(e["reason"] == "exhaustion" for e in tight.preempt_log)
    preempted = {e["rid"] for e in tight.preempt_log}
    assert all(tight.reqs[r].preemptions >= 1 for r in preempted)
    for rid in calm.outputs:
        assert tight.outputs[rid] == calm.outputs[rid], \
            f"rid {rid} diverged under block-pool pressure"
        assert tight.reqs[rid].done is not None
    # every block returned to the pool once the trace drains
    bm = tight.dstates[0].blocks
    assert bm.n_free == bm.total_blocks and not bm.allocs


def test_watermark_preemption_fires_before_exhaustion(reduced_params_cache):
    """With preempt_watermark set, the automatic policy preempts while free
    blocks remain (reason 'watermark', free_blocks > 0) instead of waiting
    for hard exhaustion — and generation still matches the calm run."""
    cfg, params = reduced_params_cache("yi-9b")
    calm = _serve_batch(cfg, params, max_seq=128, n_req=2, output_len=8)
    # 12-block pool, 2 x 4 admitted -> 4 free; watermark keeps ceil(3)
    # blocks free, so the second grower is preempted with blocks to spare
    tight = _serve_batch(cfg, params, max_seq=48, n_req=2, output_len=8,
                         watermark=0.25)
    assert any(e["reason"] == "watermark" for e in tight.preempt_log)
    assert all(e["free_blocks"] > 0 for e in tight.preempt_log)
    for rid in calm.outputs:
        assert tight.outputs[rid] == calm.outputs[rid]


def test_manual_decode_preempt_matches_oracle(reduced_params_cache):
    """preempt() on a DECODE-phase request evicts it at the next tick,
    recompute-requeues the generated prefix, and the final tokens still
    match the dense reference."""
    cfg, params = reduced_params_cache("yi-9b")
    spec = _spec()
    rng = np.random.default_rng(17)
    plen = 48
    prompt = rng.integers(0, cfg.vocab_size, plen).astype(np.int32)

    def serve(preempt_at=None):
        eng = ServingEngine(cfg, params, spec, TwoChunkPolicy(MODEL, spec),
                            max_batch=4, max_seq=256, block_size=32)
        req = Request(rid=0, arrival=0.0, prompt_len=plen, output_len=6)
        eng.submit(req, prompt)
        if preempt_at is not None:
            eng.preempt(0, at=preempt_at)
        return eng, eng.serve()

    base_eng, base = serve()
    tt = base_eng.reqs[0].token_times
    mid = 0.5 * (tt[2] + tt[3])          # squarely inside the decode span
    eng, outs = serve(preempt_at=mid)
    assert eng.reqs[0].preemptions == 1
    assert [e["reason"] for e in eng.preempt_log] == ["manual"]
    assert outs[0] == base[0] == _generate(params, cfg, prompt, len(base[0]))
    # a flag landing in the TRANSFER window (prefill done, KV in flight)
    # is honoured at the first decode tick instead of silently dropped
    r0 = base_eng.reqs[0]
    eng2, outs2 = serve(
        preempt_at=0.5 * (r0.prefill_done + r0.transfer_done))
    assert eng2.reqs[0].preemptions == 1
    assert [e["reason"] for e in eng2.preempt_log] == ["manual"]
    assert outs2[0] == base[0]


# ------------------------------------------------------- controller wiring
def test_rate_controller_wired_into_engine(reduced_params_cache):
    """The engine feeds arrivals + chunk-boundary queue load into the
    controller, and the policy's improvement rate comes from it."""
    cfg, params = reduced_params_cache("yi-9b")
    spec = ClusterSpec(n_prefill=8, n_decode=1, sp_candidates=(1, 2, 4))
    ctl = DynamicRateController({0.5: 0.1, 4.0: 0.6}, window=10.0,
                                queue_gain=0.5)
    eng = ServingEngine(cfg, params, spec,
                        make_policy("tetris", MODEL, spec),
                        max_batch=4, max_seq=256, rate_controller=ctl)
    assert eng.policy.rate_fn == ctl.rate
    rng = np.random.default_rng(5)
    for i in range(3):
        plen = int(rng.integers(24, 60))
        req = Request(rid=i, arrival=i * 0.02, prompt_len=plen, output_len=3)
        eng.submit(req, rng.integers(0, cfg.vocab_size, plen))
    outs = eng.serve()
    assert len(ctl._arrivals) == 3
    assert len(ctl._queue_obs) >= 3          # one per executed chunk
    assert all(len(t) == 4 for t in outs.values())


def test_engine_rejects_impossible_requests(reduced_params_cache):
    """Oversized requests fail fast at submit (not an infinite transfer
    retry loop); a policy-owned controller conflicting with
    rate_controller fails fast at construction."""
    cfg, params = reduced_params_cache("yi-9b")
    spec = _spec()
    eng = ServingEngine(cfg, params, spec, TwoChunkPolicy(MODEL, spec),
                        max_batch=2, max_seq=128, block_size=32)
    big = Request(rid=0, arrival=0.0, prompt_len=250, output_len=10)
    with pytest.raises(ValueError, match="capacity"):
        eng.submit(big, np.zeros(250, np.int32))
    from repro.serving.simulator import DynamicTetrisPolicy
    pol = DynamicTetrisPolicy(MODEL, spec,
                              DynamicRateController({1.0: 0.3}))
    with pytest.raises(ValueError, match="controller"):
        ServingEngine(cfg, params, spec, pol, max_batch=2, max_seq=128,
                      rate_controller=DynamicRateController({1.0: 0.3}))


def test_sharded_pool_keeps_the_scan_path():
    """A decode pool sharded over a 2-device mesh (3-dim tables) keeps the
    ``xs``/``ys`` path and ``ticks/pool_copied`` counts every tick; the
    one-device engine updates in place and serves the same tokens.  Runs
    in a subprocess with two host devices, which a process that has
    initialised a one-device JAX cannot make."""
    import os
    import subprocess
    import sys
    prog = os.path.join(os.path.dirname(__file__), "dist_progs",
                        "pool_path_prog.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, prog], capture_output=True,
                         text=True, timeout=600, env=env)
    assert out.returncode == 0, f"{out.stdout}\n{out.stderr}"
    assert "DIST_OK" in out.stdout


# ------------------------------------------------------- paged primitives
def test_paged_gather_scatter_roundtrip():
    from repro.kernels.flash_decode import (gather_kv_pages,
                                            scatter_kv_prefill,
                                            scatter_kv_token)
    rng = np.random.default_rng(0)
    nb, B, KVH, D, page, npg = 2, 3, 2, 8, 8, 4
    S = page * npg
    k = jnp.asarray(rng.standard_normal((nb, B, S, KVH, D)), jnp.float32)
    pool = jnp.zeros((nb, B * npg + 1, page, KVH, D), jnp.float32)
    perm = rng.permutation(B * npg)          # non-contiguous physical pages
    bt = np.zeros((B, npg), np.int32)
    for b in range(B):
        bt[b] = perm[b * npg:(b + 1) * npg]
        pool = scatter_kv_prefill(pool, jnp.asarray(bt[b]), k[:, b])
    bt = jnp.asarray(bt)
    np.testing.assert_array_equal(np.asarray(gather_kv_pages(pool, bt)),
                                  np.asarray(k))
    lengths = jnp.asarray([5, 17, 31], jnp.int32)
    new = jnp.asarray(rng.standard_normal((nb, B, KVH, D)), jnp.float32)
    pool = scatter_kv_token(pool, bt, lengths, new)
    dense = np.asarray(gather_kv_pages(pool, bt))
    for b in range(B):
        np.testing.assert_array_equal(dense[:, b, int(lengths[b])],
                                      np.asarray(new[:, b]))
        mask = np.ones(S, bool)
        mask[int(lengths[b])] = False
        np.testing.assert_array_equal(dense[:, b, mask],
                                      np.asarray(k[:, b, mask]))


def test_paged_decode_attention_op_matches_dense():
    """ops.paged_decode_attention (gather fallback) == dense decode oracle
    on a permuted block layout, with and without a sliding window."""
    from repro.kernels import ops
    from repro.kernels.flash_decode import scatter_kv_prefill
    from repro.kernels.ref import decode_attention_ref
    rng = np.random.default_rng(9)
    B, H, KVH, D, page, npg = 2, 4, 2, 16, 8, 3
    S = page * npg
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, KVH, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, KVH, D)), jnp.float32)
    lengths = jnp.asarray([9, 23], jnp.int32)
    pool_shape = (1, B * npg + 1, page, KVH, D)
    kp = jnp.zeros(pool_shape, jnp.float32)
    vp = jnp.zeros(pool_shape, jnp.float32)
    perm = rng.permutation(B * npg)
    bt = np.zeros((B, npg), np.int32)
    for b in range(B):
        bt[b] = perm[b * npg:(b + 1) * npg]
        kp = scatter_kv_prefill(kp, jnp.asarray(bt[b]), k[None, b])
        vp = scatter_kv_prefill(vp, jnp.asarray(bt[b]), v[None, b])
    bt = jnp.asarray(bt)
    for window in (None, 8):
        got = ops.paged_decode_attention(q, kp[0], vp[0], bt, lengths,
                                         window=window, impl="ref")
        want = decode_attention_ref(q, k, v, lengths, window=window)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("layer", [None, 1], ids=["one_layer", "stacked"])
def test_paged_flash_decode_matches_ref(layer):
    """The interpret-mode kernel over one layer's pool, and over layer 1
    of a three-layer stack (the others filled with noise), read through
    the traced layer index as the decode layer scan passes it."""
    from repro.kernels.flash_decode import (paged_flash_decode,
                                            scatter_kv_prefill)
    from repro.kernels.ref import decode_attention_ref
    rng = np.random.default_rng(1)
    B, H, KVH, D, page, npg = 2, 4, 2, 16, 8, 3
    S = page * npg
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, KVH, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, KVH, D)), jnp.float32)
    lengths = jnp.asarray([7, 20], jnp.int32)
    nl = 1 if layer is None else 3
    pool_shape = (nl, B * npg + 1, page, KVH, D)
    kp = jnp.asarray(rng.standard_normal(pool_shape), jnp.float32)
    vp = jnp.asarray(rng.standard_normal(pool_shape), jnp.float32)
    at = 0 if layer is None else layer
    perm = rng.permutation(B * npg)
    bt = np.zeros((B, npg), np.int32)
    for b in range(B):
        bt[b] = perm[b * npg:(b + 1) * npg]
        kp = kp.at[at].set(scatter_kv_prefill(kp[at][None], jnp.asarray(
            bt[b]), k[None, b])[0])
        vp = vp.at[at].set(scatter_kv_prefill(vp[at][None], jnp.asarray(
            bt[b]), v[None, b])[0])
    if layer is None:
        kp, vp = kp[0], vp[0]
    else:
        layer = jnp.int32(layer)
    got = paged_flash_decode(q, kp, vp, jnp.asarray(bt), lengths,
                             interpret=True, layer=layer)
    want = decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
