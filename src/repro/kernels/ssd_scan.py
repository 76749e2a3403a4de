"""Pallas TPU kernel for the Mamba-2 SSD chunked scan.

TPU-native formulation of state-space duality: the sequence is processed in
chunks; within a chunk the recurrence is materialised as a (chunk x chunk)
lower-triangular "attention-like" matmul (MXU work), and the running state
``h: (P, N)`` is carried across chunks in VMEM scratch — the chunk axis is
the innermost, sequential grid dimension, so the cross-chunk recurrence costs
no HBM round-trips.  This is the adaptation of Mamba-2's GPU kernel to the
TPU memory hierarchy (HBM→VMEM→MXU) described in DESIGN.md.

Supports an initial state ``h0`` — required by CDSP chunked prefill, where a
request's SSD state is handed from one chunk's instance group to the next.

Checked against kernels/ref.ssd_ref (sequential oracle) and
kernels/ref.ssd_chunked_ref in interpret mode, and compiled for a TPU v5e
by tests/test_tpu_compile.py; on TPU it runs natively.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(A_ref, x_ref, dt_ref, b_ref, c_ref, h0_ref,
                y_ref, hout_ref, h_scr, *, nc: int, chunk: int):
    h_idx = pl.program_id(1)
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_scr[...] = h0_ref[0, 0].astype(jnp.float32)           # (P, N)

    x = x_ref[0, 0].astype(jnp.float32)                         # (L, P)
    dt = dt_ref[0, 0].astype(jnp.float32)                       # (1, L)
    A = A_ref[h_idx]                                            # scalar
    Bm = b_ref[0, 0].astype(jnp.float32)                        # (L, N)
    Cm = c_ref[0, 0].astype(jnp.float32)                        # (L, N)

    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = li >= lj
    a = dt * A                                                  # (1, L) <= 0
    # inclusive cumsum as a masked lane sum (exact f32, no scan primitive)
    a_cum = jnp.sum(jnp.where(causal, a, 0.0), axis=1, keepdims=True)  # (L, 1)
    a_cum_row = a_cum.T                                         # (1, L)
    a_total = jnp.sum(a)

    # intra-chunk: y_i += sum_{j<=i} exp(a_cum_i - a_cum_j) dt_j (C_i.B_j) x_j
    L = jnp.where(causal, jnp.exp(a_cum - a_cum_row), 0.0)      # (L, L)
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    scores = scores * L * dt
    y = jax.lax.dot_general(scores, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk: y_i += exp(a_cum_i) C_i h_prev^T
    h = h_scr[...]                                              # (P, N)
    y = y + jax.lax.dot_general(
        Cm * jnp.exp(a_cum), h, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # state update: h = exp(a_total) h + sum_j exp(a_total - a_cum_j) dt_j x_j B_j^T
    w = jnp.exp(a_total - a_cum) * dt.T                         # (L, 1)
    s_c = jax.lax.dot_general(x * w, Bm,
                              (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (P, N)
    h_scr[...] = h * jnp.exp(a_total) + s_c

    @pl.when(ic == nc - 1)
    def _emit_state():
        hout_ref[0, 0] = h_scr[...].astype(hout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(
    x: jax.Array,                      # (B, S, H, P)
    dt: jax.Array,                     # (B, S, H)
    A: jax.Array,                      # (H,)
    Bm: jax.Array,                     # (B, S, G, N)
    Cm: jax.Array,                     # (B, S, G, N)
    *,
    h0: Optional[jax.Array] = None,    # (B, H, P, N)
    chunk: int = 128,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y: (B,S,H,P), h_final: (B,H,P,N) fp32).

    The kernel runs head-major: inputs are transposed to (B, H, S, ·) so a
    block is one head's (chunk, P) tile, and ``dt`` rides as (B, H, 1, S)
    rows — both satisfy the TPU's (8, 128) block tiling for any P, N."""
    B, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    if h0 is None:
        h0 = jnp.zeros((B, H, P, N), jnp.float32)
    xh = x.transpose(0, 2, 1, 3)                                # (B, H, S, P)
    dth = dt.transpose(0, 2, 1)[:, :, None, :]                  # (B, H, 1, S)
    bh = Bm.transpose(0, 2, 1, 3)                               # (B, G, S, N)
    ch = Cm.transpose(0, 2, 1, 3)

    kernel = functools.partial(_ssd_kernel, nc=nc, chunk=chunk)
    y, h_final = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda b, h, ic: (b, h, 0, ic)),
            pl.BlockSpec((1, 1, chunk, N),
                         lambda b, h, ic, r=rep: (b, h // r, ic, 0)),
            pl.BlockSpec((1, 1, chunk, N),
                         lambda b, h, ic, r=rep: (b, h // r, ic, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, ic: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, ic: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(A.astype(jnp.float32), xh, dth, bh, ch, h0)
    return y.transpose(0, 2, 1, 3), h_final
