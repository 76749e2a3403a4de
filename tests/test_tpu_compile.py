"""Compile the main-path Pallas kernels for a TPU v5e, without a chip.

The TPU compiler is installed wherever jaxlib's TPU support is, and it
compiles for a *described* chip: these tests lower each kernel the serving
engine runs — flash attention, paged prefill over history pages, paged
decode and the fused append+attend tick, the SSD scan — at real widths
(yi-9b: 32 query heads, 4 KV heads, head_dim 128, bf16, 64-token pages,
decode batch 8; the SSD scan at mamba2-1.3b's 64 heads of width 64, state
128, since yi-9b has no SSM layer) and fail on anything the chip's compiler
refuses: block shapes off the (8, 128) tiling, VMEM overflow, unsupported
primitives.  Interpret-mode tests (test_kernels.py) cannot see those.

The topology is described inside a module fixture, never at import: only one
process may load libtpu at a time, and every pytest-xdist worker imports
this file.  Compiles run in this process (~1 s each).
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as fa
from repro.kernels import flash_decode as fd
from repro.kernels.ssd_scan import ssd_scan

H, KVH, D = 32, 4, 128              # yi-9b attention widths
PAGE, N_PAGES, B_DECODE = 64, 512, 8


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:           # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    # the Pallas kernel itself is in the program, not an XLA stand-in
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


BF16, I32, F32 = jnp.bfloat16, jnp.int32, jnp.float32


@pytest.mark.parametrize("Sq", [2048, 2334, 40])
def test_flash_attention_compiles(one_chip, Sq):
    """A CDSP chunk of any length: a whole tile count, one off the 128
    grid (padded inside the op), and a short prompt."""
    _compile(one_chip,
             lambda q, k, v, qp, kp: fa.flash_attention(
                 q, k, v, qp, kp, with_lse=True),
             ((1, Sq, H, D), BF16), ((1, Sq, KVH, D), BF16),
             ((1, Sq, KVH, D), BF16), ((1, Sq), I32), ((1, Sq), I32))


@pytest.mark.parametrize("Sq", [2048, 2334])
def test_paged_flash_prefill_compiles(one_chip, Sq):
    """Chunk queries over 64 history pages: VMEM stays bounded because the
    queries are tiled, whatever the chunk length."""
    npg = 64
    _compile(one_chip,
             lambda q, kp, vp, bt, hl, qp: fa.paged_flash_prefill(
                 q, kp, vp, bt, hl, qp),
             ((1, Sq, H, D), BF16), ((N_PAGES, PAGE, KVH, D), BF16),
             ((N_PAGES, PAGE, KVH, D), BF16), ((1, npg), I32), ((1,), I32),
             ((1, Sq), I32))


def test_paged_flash_decode_compiles(one_chip):
    npg = 64
    _compile(one_chip,
             lambda q, kp, vp, bt, ln: fd.paged_flash_decode(
                 q, kp, vp, bt, ln, with_lse=True),
             ((B_DECODE, H, D), BF16), ((N_PAGES, PAGE, KVH, D), BF16),
             ((N_PAGES, PAGE, KVH, D), BF16), ((B_DECODE, npg), I32),
             ((B_DECODE,), I32))


def test_paged_append_attend_compiles(one_chip):
    """The fused decode tick: append the new token's K/V into its page and
    attend over the pool in one program."""
    npg = 64
    _compile(one_chip,
             lambda q, kp, vp, bt, ln, ap, sl, kn, vn: fd.paged_append_attend(
                 q, kp, vp, bt, ln, ap, sl, kn, vn),
             ((B_DECODE, H, D), BF16), ((N_PAGES, PAGE, KVH, D), BF16),
             ((N_PAGES, PAGE, KVH, D), BF16), ((B_DECODE, npg), I32),
             ((B_DECODE,), I32), ((B_DECODE,), I32), ((B_DECODE,), I32),
             ((B_DECODE, KVH, D), BF16), ((B_DECODE, KVH, D), BF16))


def test_ssd_scan_compiles(one_chip):
    S, Hs, P, G, N, chunk = 2048, 64, 64, 1, 128, 256
    _compile(one_chip,
             lambda x, dt, A, b, c, h0: ssd_scan(x, dt, A, b, c, h0=h0,
                                                 chunk=chunk),
             ((1, S, Hs, P), BF16), ((1, S, Hs), F32), ((Hs,), F32),
             ((1, S, G, N), BF16), ((1, S, G, N), BF16),
             ((1, Hs, P, N), F32))
