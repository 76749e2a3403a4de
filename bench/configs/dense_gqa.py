"""Plain reference of a dense pre-norm decoder with grouped-query attention.

The Llama-family block that Yi-9B and ChatGLM3-6B share: RMSNorm before
attention and before the SwiGLU MLP, rotary embeddings on all of the head
dim (or its first ``partial_rotary_factor`` share), optional q/k/v biases,
untied input and output embeddings.  Written from the published
description in plain ``jax.numpy``, in float32 at ``highest`` matmul
precision, with no kernel, cache, paging or batching: one sequence at a
time, one layer at a time, and attention in blocks of query rows so that
a long prompt fits next to the weights.

Departure from the published checkpoints, noted: the rotary embedding
rotates the two halves of the rotated dims (the GPT-NeoX layout), where
ChatGLM3 rotates interleaved pairs.  The two are the same function up to a
fixed permutation of the q and k weight columns, which a checkpoint
converter applies; with random weights the served program and this
reference must use one layout, and this is the one the served program's
weight tree uses.

``weights`` draws the weights from a seed in one jitted program, in the
layout the served program takes as its parameter tree.  ``logits`` runs
the reference over one token sequence and returns the logits at the
positions asked for.  ``quant="fp8"`` computes every linear layer with its
inputs and weights rounded to float8 e4m3 (per-row activation scale,
per-output-column weight scale): the control, one precision step below
the bf16 that the configurations state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 256          # query rows per attention block
ROW_BLOCK = 1024       # rows per MLP block
PAD = 1024             # sequences are padded to a multiple of this


def _dims(c: dict) -> dict:
    d = c["hidden_size"]
    H = c["num_attention_heads"]
    return {"L": c["num_hidden_layers"], "d": d, "H": H,
            "KVH": c["num_key_value_heads"],
            "Dh": c.get("head_dim") or d // H, "F": c["intermediate_size"],
            "V": c["vocab_size"], "bias": bool(c.get("qkv_bias", False)),
            "rot": c.get("partial_rotary_factor", 1.0),
            "theta": float(c["rope_theta"]), "eps": float(c["rms_norm_eps"])}


def layout(c: dict) -> dict:
    """Leaf shapes of the weight tree: layer leaves stacked over layers."""
    m = _dims(c)
    L, d, H, KVH, Dh, F, V = (m[k] for k in ("L", "d", "H", "KVH", "Dh",
                                             "F", "V"))
    blk = {"norm1": (L, d), "wq": (L, d, H * Dh), "wk": (L, d, KVH * Dh),
           "wv": (L, d, KVH * Dh), "wo": (L, H * Dh, d), "norm2": (L, d),
           "ffn": {"wi": (L, d, F), "wg": (L, d, F), "wo": (L, F, d)}}
    if m["bias"]:
        blk.update({"bq": (L, H * Dh), "bk": (L, KVH * Dh),
                    "bv": (L, KVH * Dh)})
    return {"embed": (V, d), "final_norm": (d,), "unembed": (V, d),
            "blocks": {"0": blk}}


def _init_leaf(name: str, shape: tuple, key, dtype):
    """Norm scales near 1, biases small, token embeddings at unit scale,
    every other matrix at 1/sqrt(fan-in) so activations keep unit scale."""
    if name.startswith("norm") or name == "final_norm":
        v = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif name in ("bq", "bk", "bv"):
        v = 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif name == "embed":
        v = jax.random.normal(key, shape, jnp.float32)
    else:
        fan_in = shape[-1] if name == "unembed" else shape[-2]
        v = jax.random.normal(key, shape, jnp.float32) / np.sqrt(fan_in)
    return v.astype(dtype)


def seed_key(seed: int):
    """A threefry key from a seed of up to 64 bits, taken whole."""
    seed = int(seed) % 2**64
    return jax.random.wrap_key_data(
        np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32))


def weights(c: dict, seed: int, dtype: str = "bfloat16") -> dict:
    """All weights from ``seed`` in one jitted program, in ``dtype``."""
    shapes = layout(c)
    paths = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))[0]
    treedef = jax.tree.structure(shapes,
                                 is_leaf=lambda x: isinstance(x, tuple))

    def build(key):
        leaves = [_init_leaf(path[-1].key, shape, jax.random.fold_in(key, i),
                             jnp.dtype(dtype))
                  for i, (path, shape) in enumerate(paths)]
        return jax.tree.unflatten(treedef, leaves)

    return jax.jit(build)(seed_key(seed))


# ------------------------------------------------------------------ model
def _fp8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _linear(x, w, quant):
    w = w.astype(jnp.float32)
    if quant == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    return x @ w


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
        g.astype(jnp.float32)


def _rope(x, pos, m):
    """x (S, heads, Dh); rotate the first ``rot`` dims by halves."""
    Dh = x.shape[-1]
    rot = (int(Dh * m["rot"]) // 2) * 2
    inv = 1.0 / (m["theta"] ** (jnp.arange(0, rot, 2, dtype=jnp.float32)
                                / rot))
    ang = pos[:, None].astype(jnp.float32) * inv            # (S, rot/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], axis=-1)


@functools.partial(jax.jit, static_argnames=("c", "quant"))
def _layer(x, blocks, i, c, quant):
    m = _dims(dict(c))
    S = x.shape[0]
    H, KVH, Dh = m["H"], m["KVH"], m["Dh"]
    g = H // KVH
    w = jax.tree.map(lambda a: a[i], blocks["0"])
    pos = jnp.arange(S, dtype=jnp.int32)

    h = _rms(x, w["norm1"], m["eps"])
    q = _linear(h, w["wq"], quant)
    k = _linear(h, w["wk"], quant)
    v = _linear(h, w["wv"], quant)
    if m["bias"]:
        q = q + w["bq"].astype(jnp.float32)
        k = k + w["bk"].astype(jnp.float32)
        v = v + w["bv"].astype(jnp.float32)
    q = _rope(q.reshape(S, H, Dh), pos, m)
    k = _rope(k.reshape(S, KVH, Dh), pos, m)
    v = v.reshape(S, KVH, Dh)

    def attend(qpos):
        rows = jax.lax.dynamic_slice_in_dim(q, qpos, Q_BLOCK, 0)
        rows = rows.reshape(Q_BLOCK, KVH, g, Dh)
        s = jnp.einsum("qhgd,khd->hgqk", rows, k) * Dh ** -0.5
        mask = pos[None, :] <= (qpos + jnp.arange(Q_BLOCK))[:, None]
        s = jnp.where(mask, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, v).reshape(Q_BLOCK, H * Dh)

    o = jax.lax.map(attend, jnp.arange(0, S, Q_BLOCK))
    x = x + _linear(o.reshape(S, H * Dh), w["wo"], quant)

    def mlp(rows):
        hh = _rms(rows, w["norm2"], m["eps"])
        a = jax.nn.silu(_linear(hh, w["ffn"]["wg"], quant)) * \
            _linear(hh, w["ffn"]["wi"], quant)
        return rows + _linear(a, w["ffn"]["wo"], quant)

    d = x.shape[1]
    return jax.lax.map(mlp, x.reshape(S // ROW_BLOCK, ROW_BLOCK, d)
                       ).reshape(S, d)


@functools.partial(jax.jit, static_argnames=("c", "quant"))
def _head(x, rows, final_norm, unembed, c, quant):
    m = _dims(dict(c))
    h = _rms(x[rows], final_norm, m["eps"])
    return _linear(h, unembed.T, quant)


def logits(w: dict, c: dict, tokens: np.ndarray, rows: np.ndarray,
           quant: str = "none") -> np.ndarray:
    """float32 logits (len(rows), V) of the next token after each of
    ``rows`` (indices into ``tokens``)."""
    S = len(tokens)
    Sp = -(-S // PAD) * PAD                # causal: trailing pads are inert
    toks = np.zeros(Sp, np.int32)
    toks[:S] = tokens
    key = tuple(sorted((k, v) for k, v in c.items()
                       if isinstance(v, (int, float, str, bool))))
    with jax.default_matmul_precision("highest"):
        x = jnp.take(w["embed"], jnp.asarray(toks), axis=0).astype(
            jnp.float32)
        for i in range(_dims(c)["L"]):
            x = _layer(x, w["blocks"], i, key, quant)
        out = _head(x, jnp.asarray(rows, jnp.int32), w["final_norm"],
                    w["unembed"], key, quant)
    return np.asarray(out)
