"""Subprocess: ring attention / split-KV decode / SP-SSD on 8 host devices."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.ring_attention import ring_attention, split_kv_decode, sp_ssd
from repro.core import zigzag as zz
from repro.kernels.ref import attention_ref, decode_attention_ref, ssd_ref

assert jax.device_count() == 8, jax.device_count()
from repro.compat import make_mesh
mesh = make_mesh((4, 2), ("sp", "tp"))

B, S, H, KVH, D, N = 2, 64, 8, 2, 32, 4

q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, D))
k = jax.random.normal(jax.random.PRNGKey(1), (B, S, KVH, D))
v = jax.random.normal(jax.random.PRNGKey(2), (B, S, KVH, D))

# --- zigzag ring attention, q heads sharded (kv replicated + sliced) -------
qz, kz, vz = (zz.zigzag_shard(x, N) for x in (q, k, v))
pos = jnp.broadcast_to(zz.zigzag_positions(S, N)[None], (B, S))
with jax.set_mesh(mesh):
    o = ring_attention(qz, kz, vz, pos, pos, mesh=mesh, sp_axis="sp",
                       head_axis="tp", kv_head_axis=None, causal=True)
o = zz.zigzag_unshard(o, N)
ref = attention_ref(q, k, v, jnp.arange(S), jnp.arange(S))
np.testing.assert_allclose(np.asarray(o), np.asarray(ref), atol=1e-5)

# --- zigzag causal-skip fast path (beyond-paper §Perf) ----------------------
with jax.set_mesh(mesh):
    o = ring_attention(qz, kz, vz, pos, pos, mesh=mesh, sp_axis="sp",
                       head_axis="tp", kv_head_axis=None, causal=True,
                       zigzag_skip=True)
o = zz.zigzag_unshard(o, N)
ref = attention_ref(q, k, v, jnp.arange(S), jnp.arange(S))
np.testing.assert_allclose(np.asarray(o), np.asarray(ref), atol=1e-5)

# --- ring attention with sliding window ------------------------------------
with jax.set_mesh(mesh):
    o = ring_attention(qz, kz, vz, pos, pos, mesh=mesh, sp_axis="sp",
                       head_axis="tp", kv_head_axis=None, causal=True,
                       window=13)
o = zz.zigzag_unshard(o, N)
ref = attention_ref(q, k, v, jnp.arange(S), jnp.arange(S), window=13)
np.testing.assert_allclose(np.asarray(o), np.asarray(ref), atol=1e-5)

# --- split-KV decode with in-island scatter --------------------------------
lens = jnp.array([37, 61], jnp.int32)
qd = jax.random.normal(jax.random.PRNGKey(3), (B, H, D))
k_new = jax.random.normal(jax.random.PRNGKey(4), (B, KVH, D))
v_new = jax.random.normal(jax.random.PRNGKey(5), (B, KVH, D))
with jax.set_mesh(mesh):
    od, k2, v2 = split_kv_decode(qd, k, v, lens, mesh=mesh, split_axis="sp",
                                 batch_axis="tp", k_new=k_new, v_new=v_new)
bidx = jnp.arange(B)
k_ref = k.at[bidx, lens].set(k_new)
v_ref = v.at[bidx, lens].set(v_new)
ref = decode_attention_ref(qd, k_ref, v_ref, lens + 1)
np.testing.assert_allclose(np.asarray(od), np.asarray(ref), atol=1e-5)
np.testing.assert_allclose(np.asarray(k2), np.asarray(k_ref), atol=0)

# --- collapsed-axis split decode (long_500k path) --------------------------
with jax.set_mesh(mesh):
    od2 = split_kv_decode(qd, k_ref, v_ref, lens + 1, mesh=mesh,
                          split_axis=("sp", "tp"), batch_axis=None)
np.testing.assert_allclose(np.asarray(od2), np.asarray(ref), atol=1e-5)

# --- sequence-parallel SSD with initial state ------------------------------
Hs, Ps, G, Ns = 4, 16, 1, 8
x = jax.random.normal(jax.random.PRNGKey(6), (B, S, Hs, Ps))
dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(7), (B, S, Hs)))
A = -jnp.exp(jax.random.normal(jax.random.PRNGKey(8), (Hs,)))
Bm = jax.random.normal(jax.random.PRNGKey(9), (B, S, G, Ns))
Cm = jax.random.normal(jax.random.PRNGKey(10), (B, S, G, Ns))
h0 = jax.random.normal(jax.random.PRNGKey(11), (B, Hs, Ps, Ns))
with jax.set_mesh(mesh):
    y, hf = sp_ssd(x, dt, A, Bm, Cm, mesh=mesh, sp_axis="sp", chunk=8,
                   head_axis="tp", h0=h0)
yr, hr = ssd_ref(x, dt, A, Bm, Cm, h0=h0, return_state=True)
np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=2e-4)
np.testing.assert_allclose(np.asarray(hf), np.asarray(hr), atol=2e-4)

print("DIST_OK")
