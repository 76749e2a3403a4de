"""Parameter initialisation and sharding-spec trees.

``init_params`` builds the nested-dict pytree (pattern-position params
stacked over a leading n_blocks axis); ``param_specs`` builds a matching
pytree of PartitionSpec for pjit in_shardings.  ``abstract_params`` gives
ShapeDtypeStructs for dry-run lowering without allocation.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.models.config import LayerSpec, ModelConfig
from repro.models.sharding import ExecContext


# ----------------------------------------------------------------- shapes
def _attn_shapes(cfg: ModelConfig, prefix: str = "") -> dict:
    d, dh = cfg.d_model, cfg.head_dim_
    hp, kv = cfg.padded_heads, cfg.n_kv_heads
    s = {prefix + "wq": (d, hp * dh), prefix + "wk": (d, kv * dh),
         prefix + "wv": (d, kv * dh), prefix + "wo": (hp * dh, d)}
    if cfg.qkv_bias:
        s.update({prefix + "bq": (hp * dh,), prefix + "bk": (kv * dh,),
                  prefix + "bv": (kv * dh,)})
    return s


def _ffn_shapes(cfg: ModelConfig, d_ff: int) -> dict:
    d = cfg.d_model
    s = {"wi": (d, d_ff), "wo": (d_ff, d)}
    if cfg.mlp_type == "swiglu":
        s["wg"] = (d, d_ff)
    return s


def _mamba_shapes(cfg: ModelConfig) -> dict:
    s = cfg.ssm
    d = cfg.d_model
    d_in = s.expand * d
    H = d_in // s.head_dim
    conv_ch = d_in + 2 * s.ngroups * s.d_state
    return {"wz": (d, d_in), "wxbc": (d, conv_ch), "wdt": (d, H),
            "dt_bias": (H,), "A_log": (H,), "D": (H,),
            "conv_w": (s.d_conv, conv_ch), "conv_b": (conv_ch,),
            "norm": (d_in,), "wout": (d_in, d)}


def _layer_shapes(cfg: ModelConfig, spec: LayerSpec) -> dict:
    d = cfg.d_model
    s = {"norm1": (d,)}
    if spec.mixer == "attn":
        s.update(_attn_shapes(cfg))
    else:
        s.update(_mamba_shapes(cfg))
    if spec.cross_attn:
        s["normx"] = (d,)
        s.update(_attn_shapes(cfg, prefix="x_"))
    if spec.ffn != "none":
        s["norm2"] = (d,)
        if spec.ffn == "moe":
            m = cfg.moe
            moe = {"router": (d, m.n_experts),
                   "experts": {k: (m.n_experts,) + v
                               for k, v in _ffn_shapes(cfg, m.d_expert).items()}}
            if m.n_shared:
                moe["shared"] = _ffn_shapes(cfg, m.n_shared * m.d_shared)
            s["moe"] = moe
        else:
            s["ffn"] = _ffn_shapes(cfg, cfg.d_ff)
    return s


def param_shapes(cfg: ModelConfig) -> dict:
    d = cfg.d_model
    shapes = {"embed": (cfg.padded_vocab, d), "final_norm": (d,)}
    if not cfg.tie_embeddings:
        shapes["unembed"] = (cfg.padded_vocab, d)
    if cfg.pos_embedding == "learned":
        shapes["pos_emb"] = (min(cfg.max_position, 1 << 16), d)
    shapes["blocks"] = {
        str(i): jax.tree.map(lambda sh: (cfg.n_blocks,) + sh,
                             _layer_shapes(cfg, spec),
                             is_leaf=lambda x: isinstance(x, tuple))
        for i, spec in enumerate(cfg.pattern)}
    if cfg.encoder_decoder:
        enc_layer = _layer_shapes(cfg, LayerSpec(mixer="attn", ffn="dense"))
        shapes["encoder"] = {
            "blocks": {"0": jax.tree.map(
                lambda sh: (cfg.n_encoder_layers,) + sh, enc_layer,
                is_leaf=lambda x: isinstance(x, tuple))},
            "final_norm": (d,),
            "pos_emb": (min(cfg.max_position, 1 << 16), d),
        }
    return shapes


# ------------------------------------------------------------------- specs
def _matrix_spec(key: str, shape: tuple, cfg: ModelConfig,
                 ctx: ExecContext) -> P:
    """Sharding rule per parameter name (relative to its unstacked shape).

    With ctx.shard2d_weights, the dimension NOT sharded by TP is sharded
    over the data axis too (2D weight sharding for small-batch decode):
    GSPMD turns the contraction over a sharded input dim into a partial
    matmul + psum of the (tiny at batch 1) activations.
    """
    tp = ctx.tp_axis
    if tp is None or ctx.mesh is None:
        return P()
    n = ctx.axis_size(tp)
    dp = None
    if ctx.shard2d_weights:
        # 2D sharding uses the data axis regardless of whether the batch is
        # sharded over it (long_500k has batch 1)
        cand = ctx.dp_axis or ("data" if "data" in ctx.mesh.axis_names
                               else None)
        if cand is not None and ctx.axis_size(cand) > 1:
            dp = cand

    def ok(dim):
        return dim % n == 0

    def ok_dp(dim):
        return dp is not None and dim % ctx.axis_size(dp) == 0

    if key in ("embed", "unembed"):
        return P(tp if ok(shape[0]) else None,
                 dp if ok_dp(shape[1]) else None)
    if key == "pos_emb":
        return P()
    base = key[2:] if key.startswith("x_") else key
    if base in ("wq",):
        return P(dp if ok_dp(shape[0]) else None,
                 tp if ok(shape[-1]) else None)
    if base in ("wk", "wv"):
        kv_dim_ok = (cfg.n_kv_heads % n == 0)
        return P(dp if ok_dp(shape[0]) else None,
                 tp if kv_dim_ok else None)
    if base == "wo":
        return P(tp if ok(shape[-2]) else None,
                 dp if ok_dp(shape[-1]) else None)
    if base in ("wi", "wg"):
        if len(shape) == 3:                    # stacked expert (E, d, f)
            return P(None, dp if ok_dp(shape[-2]) else None,
                     tp if ok(shape[-1]) else None)
        return P(dp if ok_dp(shape[0]) else None,
                 tp if ok(shape[-1]) else None)
    if base == "wout":                          # mamba out proj (d_in, d)
        return P(tp if ok(shape[-2]) else None,
                 dp if ok_dp(shape[-1]) else None)
    if base in ("wz",):
        return P(dp if ok_dp(shape[0]) else None,
                 tp if ok(shape[-1]) else None)
    if base == "wxbc" and dp is not None and len(shape) == 2:
        return P(dp if ok_dp(shape[0]) else None, None)
    return P()                                  # norms, router, conv, small


def param_specs(cfg: ModelConfig, ctx: ExecContext) -> dict:
    shapes = param_shapes(cfg)

    def walk(tree, path=()):
        if isinstance(tree, dict):
            return {k: walk(v, path + (k,)) for k, v in tree.items()}
        key = path[-1]
        stacked = path[0] in ("blocks", "encoder")
        base_shape = tree[1:] if stacked else tree
        spec = _matrix_spec(key, base_shape, cfg, ctx)
        if key == "wo" and len(base_shape) == 3:     # expert wo (E, f, d)
            n = ctx.axis_size(ctx.tp_axis)
            spec = (P(None, ctx.tp_axis, None)
                    if ctx.tp_axis and base_shape[1] % n == 0 else P())
        if stacked:
            spec = P(*((None,) + tuple(spec)))
        return spec

    return walk(shapes)


# -------------------------------------------------------------------- init
@functools.partial(jax.jit, static_argnames=("shape", "std", "dtype"))
def _scaled_normal(key, shape, std, dtype):
    # one fused program: a bf16 weight stack never materialises its float32
    # draw, which at full width would not fit next to the weights on a chip
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def init_params(cfg: ModelConfig, key: jax.Array,
                dtype: Optional[str] = None) -> dict:
    dtype = jnp.dtype(dtype or "float32")
    shapes = param_shapes(cfg)
    leaves, treedef = jax.tree.flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(key, len(leaves))
    paths = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))[0]

    inits = []
    for (path, shape), k in zip(paths, keys):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if name.startswith("norm") or name in ("final_norm", "conv_b", "D"):
            v = jnp.ones(shape, dtype) if "norm" in name or name == "D" \
                else jnp.zeros(shape, dtype)
        elif name in ("dt_bias",):
            # dt bias so softplus(dt) spans ~[1e-3, 1e-1] (mamba2 default)
            u = jax.random.uniform(k, shape, jnp.float32,
                                   math.log(1e-3), math.log(1e-1))
            v = jnp.log(jnp.expm1(jnp.exp(u))).astype(dtype)
        elif name == "A_log":
            v = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0)
                        ).astype(dtype)
        elif name.startswith("b"):              # attention biases
            v = jnp.zeros(shape, dtype)
        else:
            fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
            std = 1.0 / math.sqrt(max(fan_in, 1))
            v = _scaled_normal(k, shape, std, dtype)
        inits.append(v)
    params = jax.tree.unflatten(treedef, inits)

    # zero the padded query heads (phi4: 24 -> 32) so they are inert.
    # Pads are interleaved per KV group — each group of n_heads/n_kv real
    # heads is padded to padded_heads/n_kv — so the q->kv GQA mapping
    # (h // group) of the REAL heads is unchanged by padding.
    if cfg.pad_heads_to and cfg.pad_heads_to > cfg.n_heads:
        for idx in padded_head_indices(cfg):
            dh = cfg.head_dim_
            for i, spec in enumerate(cfg.pattern):
                if spec.mixer != "attn":
                    continue
                blk = params["blocks"][str(i)]
                blk["wq"] = blk["wq"].at[..., idx * dh:(idx + 1) * dh].set(0.0)
                blk["wo"] = blk["wo"].at[..., idx * dh:(idx + 1) * dh, :] \
                    .set(0.0)
    return params


def padded_head_indices(cfg: ModelConfig) -> list:
    """Indices (in the padded head axis) that are inert zero pads."""
    if not cfg.pad_heads_to or cfg.pad_heads_to <= cfg.n_heads:
        return []
    kv = cfg.n_kv_heads
    assert cfg.n_heads % kv == 0 and cfg.pad_heads_to % kv == 0, \
        (cfg.n_heads, cfg.pad_heads_to, kv)
    rg, pg = cfg.n_heads // kv, cfg.pad_heads_to // kv
    return [g * pg + j for g in range(kv) for j in range(rg, pg)]


def abstract_params(cfg: ModelConfig, dtype: str = "bfloat16") -> dict:
    shapes = param_shapes(cfg)
    return jax.tree.map(lambda sh: jax.ShapeDtypeStruct(sh, jnp.dtype(dtype)),
                        shapes, is_leaf=lambda x: isinstance(x, tuple))


def count_params(params) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
