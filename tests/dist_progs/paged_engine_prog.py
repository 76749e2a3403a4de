"""Subprocess: the full paged serving engine on a 4-device mesh.

The engine's prefill page pool stripes over the SP axis (chunks run ring
attention with history pages rotating through the ring) and the decode
pool stripes over the same axis (split-KV paged decode island).  A mixed
schedule — multi-chunk prefills with an SP-size change mid-prefill,
plus a decode-phase preemption — must generate token-for-token exactly
what the single-device engine (and the dense autoregressive oracle)
produces."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_config
from repro.core.chunk_planner import Allocation, Chunk
from repro.core.latency_model import table1_model
from repro.models.params import init_params
from repro.models.sharding import CPU_CTX, ExecContext
from repro.models.transformer import forward
from repro.serving.engine import ServingEngine
from repro.serving.request import Request
from repro.serving.simulator import ClusterSpec, Policy

assert jax.device_count() == 4, jax.device_count()
MODEL = table1_model()


class ParallelTwoChunkPolicy(Policy):
    """Two chunks with an SP-size change (1 -> 2), each request on its own
    prefill instance pair so later arrivals join decode while earlier ones
    are still resident (the prefix-sharing window)."""
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


def generate_dense(params, cfg, prompt, n):
    toks = list(prompt)
    for _ in range(n):
        t = jnp.asarray(toks)[None]
        pos = jnp.arange(len(toks), dtype=jnp.int32)[None]
        logits, _, _ = forward(params, cfg, CPU_CTX, t, pos, "train")
        toks.append(int(jnp.argmax(logits[0, -1, :cfg.vocab_size])))
    return toks[len(prompt):]


def run(ctx, prompts, preempt_at=None):
    spec = ClusterSpec(n_prefill=8, n_decode=1, sp_candidates=(1, 2, 4))
    eng = ServingEngine(cfg, params, spec,
                        ParallelTwoChunkPolicy(MODEL, spec),
                        ctx=ctx, max_batch=4, max_seq=128, block_size=16)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, arrival=i * 0.001, prompt_len=len(p),
                           output_len=8), p)
    if preempt_at is not None:
        eng.preempt(0, at=preempt_at)
    outs = eng.serve()
    return eng, outs


cfg = get_config("yi-9b").reduced()
params = init_params(cfg, jax.random.PRNGKey(0))
mesh = jax.sharding.Mesh(np.array(jax.devices()), ("x",))
ctx = ExecContext(mesh=mesh, sp_axis="x", kv_split_axis="x")

rng = np.random.default_rng(42)
# 64 -> chunks of 32 and 56 -> chunks of 28 divide the 4-way ring; 58 ->
# chunks of 29 do not, and ride the ring padded to 32 (masked slots): every
# path must agree with the oracle at the token level
prompts = [rng.integers(0, cfg.vocab_size, L).astype(np.int32)
           for L in (64, 56, 64, 58)]
# twin prompt: request 2 repeats request 0 -> prefix sharing on the
# striped decode pool (shared blocks + CoW splits cross the islands)
prompts[2] = prompts[0].copy()

eng, outs = run(ctx, prompts)
d = eng.dstates[0]
assert d.kv_shards == 4 and eng.pkv.kv_shards == 4
assert d.blocks.stats["shared"] > 0, "twin admission must share blocks"
for i, p in enumerate(prompts):
    assert len(eng.reqs[i].chunk_plan) == 2, "plan must change SP mid-prefill"
    want = generate_dense(params, cfg, p, len(outs[i]))
    assert outs[i] == want, f"rid {i}: {outs[i]} != {want}"
bm = d.blocks
assert bm.n_free == bm.total_blocks and not bm.allocs
print("sharded engine == dense oracle (SP change + prefix sharing)")

# single-device engine, same workload: identical tokens
_, outs_cpu = run(CPU_CTX, prompts)
assert outs == outs_cpu, "sharded engine diverged from single-device engine"
print("sharded engine == single-device engine")

# decode-phase preemption mid-stream (recompute path over sharded pools)
tt = eng.reqs[0].token_times
eng2, outs2 = run(ctx, prompts, preempt_at=0.5 * (tt[2] + tt[3]))
assert eng2.reqs[0].preemptions >= 1, "the flag must actually preempt"
for i in range(len(prompts)):
    assert outs2[i] == outs[i], f"rid {i} diverged after preemption"
print("preemption over sharded pools token-identical")

# the layout-mismatch guards: an UNSHARDED pool under an active split /
# ring axis must refuse loudly (silent GSPMD replication of the whole
# pool is the hazard) — only reachable on a real multi-device mesh
from repro.models.attention import attention_block

p0 = jax.tree.map(lambda a: a[0], params["blocks"]["0"])
x1 = jnp.zeros((1, 1, cfg.d_model), jnp.dtype(cfg.dtype))
flat_cache = {"k": None, "v": None,
              "block_table": jnp.zeros((1, 2), jnp.int32)}
try:
    attention_block(x1, p0, cfg, ctx, jnp.zeros((1, 1), jnp.int32),
                    "decode", cache=flat_cache,
                    cache_len=jnp.zeros((1,), jnp.int32))
    raise SystemExit("unsharded pool + kv_split_axis must raise")
except ValueError as e:
    assert "kv_shards" in str(e) and "kv_split_axis" in str(e), e
x4 = jnp.zeros((1, 4, cfg.d_model), jnp.dtype(cfg.dtype))
flat_hist = {"k_pool": None, "v_pool": None,
             "block_table": jnp.zeros((1, 2), jnp.int32),
             "len": jnp.zeros((1,), jnp.int32)}
try:
    attention_block(x4, p0, cfg, ctx,
                    jnp.arange(4, dtype=jnp.int32)[None], "prefill",
                    history=flat_hist)
    raise SystemExit("unsharded history + sp_axis must raise")
except ValueError as e:
    assert "kv_shards" in str(e) and "sp_axis" in str(e), e
print("unsharded-layout guards raise actionably")

print("DIST_OK")
