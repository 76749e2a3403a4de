"""Subprocess: which path the decode tick's pools take, by layout.

On a 2-device mesh the engine's decode pool is sharded over the split-KV
axis (a 3-dim block table per layer): its ticks keep the layer scan's
``xs``/``ys`` path, and ``ticks/pool_copied`` counts each of them.  The
single-device engine on the same requests updates its pools in place
(``ticks/pool_in_place``) and serves the same tokens."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

import jax
import numpy as np

from repro.configs.registry import get_config
from repro.core.latency_model import table1_model
from repro.models.params import init_params
from repro.models.sharding import CPU_CTX, ExecContext
from repro.serving.engine import ServingEngine
from repro.serving.request import Request
from repro.serving.simulator import ClusterSpec, make_policy

assert jax.device_count() == 2, jax.device_count()
cfg = get_config("yi-9b").reduced()
params = init_params(cfg, jax.random.PRNGKey(0))
rng = np.random.default_rng(5)
prompts = [rng.integers(0, cfg.vocab_size, L).astype(np.int32)
           for L in (24, 40)]


def run(ctx):
    spec = ClusterSpec(n_prefill=2, n_decode=1, sp_candidates=(1,))
    eng = ServingEngine(cfg, params, spec,
                        make_policy("single_chunk", table1_model(), spec),
                        ctx=ctx, max_batch=2, max_seq=64, block_size=16)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, arrival=0.0, prompt_len=len(p),
                           output_len=5), p)
    outs = eng.serve()
    stats = eng.mixed_stats
    ticks = stats["standalone_ticks"] + stats["piggyback_ticks"]
    return eng, outs, ticks, eng.metrics.snapshot()["counters"]


mesh = jax.sharding.Mesh(np.array(jax.devices()), ("x",))
eng, outs, ticks, c = run(ExecContext(mesh=mesh, sp_axis="x",
                                      kv_split_axis="x"))
assert eng.dstates[0].kv_shards == 2
assert ticks > 0 and c.get("ticks/pool_copied") == ticks, (ticks, c)
assert "ticks/pool_in_place" not in c, c
print(f"sharded pool: {ticks} ticks, all through xs/ys")

_, outs1, ticks1, c1 = run(CPU_CTX)
assert c1.get("ticks/pool_in_place") == ticks1 > 0, (ticks1, c1)
assert "ticks/pool_copied" not in c1, c1
assert outs == outs1, (outs, outs1)
print(f"one-device pool: {ticks1} ticks, all in place, same tokens")
print("DIST_OK")
