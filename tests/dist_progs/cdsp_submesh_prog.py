"""Subprocess: CDSP chunk execution on NESTED sub-meshes with real KV
re-balancing between chunks (the paper's Sec. 4.1 procedure, distributed).

Chunk 0 runs ring-attention prefill on a 2-device SP group; its KV history
is then re-balanced — re-sharded via device_put — onto the 4-device group
(a superset, as Algorithm 2 guarantees), and chunk 1 runs there attending to
the re-balanced history.  The result must equal single-device monolithic
prefill.  The device_put IS the cache-balancing DMA on real hardware.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.registry import get_config
from repro.models.params import init_params
from repro.models.sharding import CPU_CTX, ExecContext
from repro.models.transformer import forward

assert jax.device_count() == 8
devs = jax.devices()

mesh2 = jax.sharding.Mesh(np.array(devs[:2]), ("sp",))
mesh4 = jax.sharding.Mesh(np.array(devs[:4]), ("sp",))

cfg = get_config("yi-9b").reduced()
params = init_params(cfg, jax.random.PRNGKey(0))
B, L0, L1 = 2, 32, 64
S = L0 + L1
tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

# oracle: single-device monolithic prefill
ref, _, _ = forward(params, cfg, CPU_CTX, tokens, pos, "prefill")


def put(tree, mesh, spec_fn):
    return jax.tree.map(
        lambda x: jax.device_put(x, NamedSharding(mesh, spec_fn(x))), tree)


# ---- chunk 0 on the SP=2 group --------------------------------------------
ctx2 = ExecContext(mesh=mesh2, sp_axis="sp")
p2 = put(params, mesh2, lambda x: P())
t0 = jax.device_put(tokens[:, :L0], NamedSharding(mesh2, P(None, "sp")))
pos0 = jax.device_put(pos[:, :L0], NamedSharding(mesh2, P(None, "sp")))
with jax.set_mesh(mesh2):
    logits0, _, caches0 = jax.jit(
        lambda p, t, ps: forward(p, cfg, ctx2, t, ps, "prefill"))(p2, t0, pos0)

# ---- cache balancing: re-shard chunk-0 KV onto the SP=4 group --------------
# history tree: {"i": {"self": {"k","v","pos"}}} with seq axis 2 (k/v) / 2 (pos)
history = {}
for i in range(len(cfg.pattern)):
    c = caches0[str(i)]["self"]
    nb = c["k"].shape[0]
    ent = {
        "k": jax.device_put(c["k"], NamedSharding(mesh4, P(None, None, "sp"))),
        "v": jax.device_put(c["v"], NamedSharding(mesh4, P(None, None, "sp"))),
        "pos": jax.device_put(
            jnp.broadcast_to(pos[None, :, :L0], (nb, B, L0)),
            NamedSharding(mesh4, P(None, None, "sp"))),
    }
    history[str(i)] = {"self": ent}

# ---- chunk 1 on the SP=4 group, attending to the re-balanced history ------
ctx4 = ExecContext(mesh=mesh4, sp_axis="sp")
p4 = put(params, mesh4, lambda x: P())
t1 = jax.device_put(tokens[:, L0:], NamedSharding(mesh4, P(None, "sp")))
pos1 = jax.device_put(pos[:, L0:], NamedSharding(mesh4, P(None, "sp")))
with jax.set_mesh(mesh4):
    logits1, _, _ = jax.jit(
        lambda p, t, ps, h: forward(p, cfg, ctx4, t, ps, "prefill",
                                    history=h))(p4, t1, pos1, history)

np.testing.assert_allclose(np.asarray(logits1), np.asarray(ref),
                           atol=2e-4, rtol=2e-3)
print("chunk0@SP2 -> rebalance -> chunk1@SP4 == monolithic ✓")
print("DIST_OK")
