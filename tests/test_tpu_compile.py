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


# (KV heads, decode rows, layers of a stacked pool, pages): one layer's
# pool at yi-9b widths, and the layer-stacked pools the decode layer scan
# carries at the benchmark's yi-9b-24l and chatglm3-6b-20l sizes
DECODE_POOLS = {"one_layer": (KVH, B_DECODE, None, N_PAGES),
                "yi-9b-24l": (4, 8, 24, 513),
                "chatglm3-6b-20l": (2, 16, 20, 2049)}


@pytest.mark.parametrize("pool", list(DECODE_POOLS))
def test_paged_flash_decode_compiles(one_chip, pool):
    """The kernel reads a page in the pool's own (page, KVH, D) layout;
    a stacked pool is read at a traced layer, in the index map."""
    kvh, b, layers, n_pages = DECODE_POOLS[pool]
    npg = 128
    lead = () if layers is None else (layers,)
    pool_shape = (lead + (n_pages, PAGE, kvh, D), BF16)
    compiled = _compile(
        one_chip,
        lambda q, kp, vp, bt, ln, ly: fd.paged_flash_decode(
            q, kp, vp, bt, ln, with_lse=True,
            layer=None if layers is None else ly),
        ((b, H, D), BF16), pool_shape, pool_shape, ((b, npg), I32),
        ((b,), I32), ((), I32))
    text = compiled.as_text()
    # the trace reduction finds the kernel by this name
    assert "%paged_flash_decode" in text
    _assert_no_pool_copy(text, pool_shape[0][-4:])


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


def _assert_no_pool_copy(text: str, layer_shape) -> None:
    """No reshape, copy or dynamic-slice (alone or fused) in the compiled
    program outputs one layer's pool or a whole stack of them, whatever
    the shape it gives them: the pool is read and written where it
    lies."""
    import math
    import re
    n_layer = math.prod(layer_shape)
    bad = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = \(?\w+\[([\d,]*)\]", line)
        if not m:
            continue
        name, dims = m.groups()
        op = line.split("=", 1)[1]
        moves = re.search(r"\b(reshape|copy|copy-start|dynamic-slice)\(",
                          op) or re.search(r"reshape|copy|dynamic-slice",
                                           name)
        size = math.prod(int(d) for d in dims.split(",") if d)
        if moves and size % n_layer == 0:
            bad.append(line.strip()[:160])
    assert not bad, "whole-pool copies:\n" + "\n".join(bad)


@pytest.mark.parametrize("arch,kvh,layers,rows", [
    ("chatglm3-6b", 2, 2, 16), ("yi-9b", 4, 2, 8)])
def test_decode_step_updates_pools_in_place(one_chip, arch, kvh, layers,
                                            rows):
    """The donated decode step at real widths (two layers: the scan body
    is the same at any depth): both pools are aliased input to output,
    and no reshape, copy or dynamic-slice moves a layer's pool."""
    import dataclasses
    import re
    from repro.configs.registry import get_config
    from repro.models.params import param_shapes
    from repro.models.sharding import ExecContext
    from repro.models.transformer import _stack_forward
    cfg = dataclasses.replace(get_config(arch), n_layers=layers,
                              dtype="bfloat16")
    assert cfg.n_kv_heads == kvh and len(cfg.pattern) == 1
    npg, n_pages = 128, 1031         # no weight has a pool's size

    def shape(s, dt=BF16):
        return jax.ShapeDtypeStruct(tuple(s), dt, sharding=one_chip)
    blocks = jax.tree.map(shape, param_shapes(cfg)["blocks"],
                          is_leaf=lambda x: isinstance(x, tuple))
    pool = (cfg.n_blocks, n_pages, PAGE, kvh, D)
    caches = {"0": {"self": {"block_table": shape(
        (cfg.n_blocks, rows, npg), I32)}}}
    pools = {"0": {"k": shape(pool), "v": shape(pool)}}
    compiled = _stack_forward.lower(
        shape((rows, 1, cfg.d_model)), blocks, cfg,
        ExecContext(impl="pallas"), shape((rows, 1), I32), "decode",
        caches, shape((rows,), I32), None, causal=True,
        pattern=cfg.pattern, pools=pools).compile()
    text = compiled.as_text()
    assert "%paged_flash_decode" in text
    header = text.splitlines()[0]
    aliased = re.search(r"input_output_alias=\{(.*?)\}, entry", header)
    assert aliased, "the pools must alias their outputs"
    n_params = len(jax.tree.leaves((blocks, caches)))
    # parameters in argument order: x, the blocks, positions, the tables,
    # cache lengths, then the two pools
    params = {int(p) for p in re.findall(r"\((\d+), \{\}",
                                         aliased.group(1))}
    assert params == {n_params + 3, n_params + 4}, aliased.group(1)
    _assert_no_pool_copy(text, pool[1:])


def test_ssd_scan_compiles(one_chip):
    S, Hs, P, G, N, chunk = 2048, 64, 64, 1, 128, 256
    _compile(one_chip,
             lambda x, dt, A, b, c, h0: ssd_scan(x, dt, A, b, c, h0=h0,
                                                 chunk=chunk),
             ((1, S, Hs, P), BF16), ((1, S, Hs), F32), ((Hs,), F32),
             ((1, S, G, N), BF16), ((1, S, G, N), BF16),
             ((1, Hs, P, N), F32))
