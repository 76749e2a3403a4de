"""Subprocess: full sharded model forward (prefill + decode + train grad) on
an 8-device mesh equals the single-device reference."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_config
from repro.core import zigzag as zz
from repro.models.params import init_params
from repro.models.sharding import CPU_CTX, ExecContext
from repro.models.transformer import forward

assert jax.device_count() == 8
from repro.compat import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))

for arch in ("yi-9b", "mamba2-1.3b", "jamba-1.5-large-398b"):
    cfg = get_config(arch).reduced()
    # head counts must divide the 2-way model axis in shard_map islands
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, S = 4, 64          # batch divisible by the 4-way data axis (decode)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                cfg.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    # reference (single device semantics)
    ref_logits, _, _ = forward(params, cfg, CPU_CTX, tokens, pos, "prefill")

    # sharded prefill (ring attention / sp-ssd over "data")
    has_mamba = any(s.mixer == "mamba" for s in cfg.pattern)
    ctx = ExecContext(mesh=mesh, sp_axis="data", tp_axis="model")
    if has_mamba:
        tok_in, pos_in = tokens, pos           # contiguous layout for SSM
    else:
        tok_in = zz.zigzag_shard(tokens, 4)
        pos_in = jnp.broadcast_to(zz.zigzag_positions(S, 4)[None], (B, S))
    sh_logits, _, _ = jax.jit(
        lambda p, t, ps: forward(p, cfg, ctx, t, ps, "prefill"))(
            params, tok_in, pos_in)
    np.testing.assert_allclose(np.asarray(sh_logits),
                               np.asarray(ref_logits), atol=2e-4, rtol=2e-3)

    # sharded decode over a padded cache
    _, _, caches = forward(params, cfg, CPU_CTX, tokens, pos, "prefill")
    def pad(d):
        out = {}
        for k, v in d.items():
            if isinstance(v, dict):
                out[k] = pad(v)
            elif k in ("k", "v") and v.shape[2] == S:
                z = jnp.zeros(v.shape[:2] + (64,) + v.shape[3:], v.dtype)
                out[k] = jnp.concatenate([v, z], axis=2)
            else:
                out[k] = v
        return out
    caches_p = pad(caches)
    ntok = jnp.argmax(ref_logits[:, 0, :cfg.vocab_size], -1)[:, None].astype(
        jnp.int32)
    clen = jnp.full((B,), S, jnp.int32)
    ref_d, _, _ = forward(params, cfg, CPU_CTX, ntok, clen[:, None],
                          "decode", caches=caches_p, cache_len=clen)
    ctx_d = ExecContext(mesh=mesh, dp_axis="data", tp_axis="model",
                        kv_split_axis="model")
    sh_d, _, _ = jax.jit(
        lambda p, t, c, cl: forward(p, cfg, ctx_d, t, cl[:, None], "decode",
                                    caches=c, cache_len=cl))(
        params, ntok, caches_p, clen)
    np.testing.assert_allclose(np.asarray(sh_d), np.asarray(ref_d),
                               atol=2e-4, rtol=2e-3)

    # 2D weight sharding (beyond-paper decode optimization) is semantics-
    # preserving by construction; verify anyway
    ctx_2d = ExecContext(mesh=mesh, dp_axis="data", tp_axis="model",
                         kv_split_axis="model", shard2d_weights=True)
    sh_2d, _, _ = jax.jit(
        lambda p, t, c, cl: forward(p, cfg, ctx_2d, t, cl[:, None], "decode",
                                    caches=c, cache_len=cl))(
        params, ntok, caches_p, clen)
    np.testing.assert_allclose(np.asarray(sh_2d), np.asarray(ref_d),
                               atol=2e-4, rtol=2e-3)
    print(f"{arch}: sharded prefill+decode(+2D) match", flush=True)

# --- expert-parallel MoE (tokens all_to_all'd to data-sharded experts) -----
for arch in ("jamba-1.5-large-398b", "mixtral-8x22b"):
    cfg = get_config(arch).reduced()      # 4 experts over the 4-wide data ax
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, S = 4, 64
    tokens = jax.random.randint(jax.random.PRNGKey(3), (B, S), 0,
                                cfg.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    ref, aux_ref, _ = forward(params, cfg, CPU_CTX, tokens, pos, "train")
    ctx_ep = ExecContext(mesh=mesh, dp_axis="data", tp_axis="model",
                         moe_ep=True)
    got, aux_got, _ = jax.jit(
        lambda p, t: forward(p, cfg, ctx_ep, t, pos, "train"))(params, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-4,
                               rtol=2e-3)
    np.testing.assert_allclose(float(aux_got), float(aux_ref), rtol=1e-4)
    print(f"{arch}: expert-parallel MoE matches", flush=True)

print("DIST_OK")
