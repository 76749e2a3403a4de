"""Small helpers shared by the model and the multi-device programs.

The repo runs on jax 0.9.0: ``jax.shard_map`` and ``jax.set_mesh`` are
called directly, with no shims for older jax generations.
"""

from __future__ import annotations

import math

import jax


def make_mesh(shape, axis_names):
    """Build a Mesh with Auto axes over the first prod(shape) devices, in
    ``jax.devices()`` order (``jax.make_mesh`` would default to Explicit
    axes and may reorder devices for the physical topology)."""
    import numpy as np
    n = math.prod(shape)
    return jax.sharding.Mesh(np.asarray(jax.devices()[:n]).reshape(shape),
                             axis_names)


def causal_depthwise_conv(x, w, init=None):
    """Depthwise causal conv (VALID over [carry, x]) as K shifted
    multiply-adds.

    ``x``: (B, S, ch); ``w``: (K, ch); ``init``: optional (B, K-1, ch)
    carry-in from a previous chunk (zeros = sequence start).  Returns
    (B, S, ch).

    The obvious spellings are both miscompiled by jax 0.4.x GSPMD when
    the sequence dim is sharded: depthwise ``conv_general_dilated``
    (wrong halo exchange with feature_group_count) and slice windows
    taken out of ``concatenate([carry, x])`` (the K-1-row leading operand
    breaks shard alignment and the slices silently read wrong rows) —
    tests/dist_progs/sharded_model_prog.py caught both on the Mamba-2
    archs.  Zero-pad + shifted multiply-adds partitions correctly on
    every jax generation, so every version runs this spelling; the carry
    contributes only to the first K-1 outputs and is added as a tiny
    boundary correction instead of being concatenated in."""
    import jax.numpy as jnp
    B, S, ch = x.shape
    K = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    out = xp[:, 0:S] * w[0][None, None]
    for k in range(1, K):
        out = out + xp[:, k:k + S] * w[k][None, None]
    if init is not None and K > 1:
        t_max = min(K - 1, S)
        rows = []
        for t in range(t_max):
            r = jnp.zeros((B, ch), out.dtype)
            for k in range(K - 1 - t):
                r = r + init[:, t + k].astype(out.dtype) * w[k][None]
            rows.append(r)
        out = out.at[:, :t_max].add(jnp.stack(rows, axis=1))
    return out
