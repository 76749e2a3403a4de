"""Roofline extraction: HLO collective parsing + model-FLOPs accounting."""

import jax.numpy as jnp
import pytest

from repro.configs.registry import get_config
from repro.launch.roofline import (_shape_bytes, collective_bytes,
                                   model_flops, peaks)
from repro.models.config import INPUT_SHAPES

HLO = """
  %ag = bf16[16,1024,512]{2,1,0} all-gather(bf16[16,64,512] %x), replica_groups=[16,16]<=[256], dimensions={1}
  %ar.start = f32[4096,4096]{1,0} all-reduce-start(f32[4096,4096] %g), replica_groups=[16,16]<=[256]
  %rs = f32[64,512]{1,0} reduce-scatter(%y), replica_groups={{0,1,2,3}, {4,5,6,7}}
  %cp = bf16[2,2048,128]{2,1,0} collective-permute(%kv), source_target_pairs={{0,1},{1,2}}
  %a2a = (f32[1,64]{1,0}, f32[1,64]{1,0}) all-to-all(%p, %q), replica_groups=[2,8]<=[16]
"""


def test_shape_bytes():
    assert _shape_bytes("bf16[16,1024,512]{2,1,0}") == 16 * 1024 * 512 * 2
    assert _shape_bytes("(f32[2,3]{1,0}, s32[4]{0})") == 24 + 16


def test_collective_bytes_accounting():
    out = collective_bytes(HLO)
    ag = 16 * 1024 * 512 * 2
    assert abs(out["all-gather"] - ag * 15 / 16) < 1
    ar = 4096 * 4096 * 4
    assert abs(out["all-reduce"] - 2 * ar * 15 / 16) < 1
    rs = 64 * 512 * 4
    assert abs(out["reduce-scatter"] - rs * 3) < 1
    cp = 2 * 2048 * 128 * 2
    assert abs(out["collective-permute"] - cp) < 1
    assert out["total"] == sum(v for k, v in out.items() if k != "total")


def test_model_flops_structure():
    cfg = get_config("yi-9b")
    tr = model_flops(cfg, INPUT_SHAPES["train_4k"])
    pf = model_flops(cfg, INPUT_SHAPES["prefill_32k"])
    dc = model_flops(cfg, INPUT_SHAPES["decode_32k"])
    # train is fwd+bwd (3x) of the same token count as prefill linear part
    assert tr > pf > dc
    # decode flops ~ 2*N*B + attention reads
    n = cfg.active_param_count()
    assert dc > 2 * n * 128
    # MoE counts only active params
    moe = get_config("mixtral-8x22b")
    assert moe.active_param_count() < 0.45 * moe.param_count()


def test_long500k_window_capping():
    cfg = get_config("yi-9b")          # long_context_window = 4096
    fl = model_flops(cfg, INPUT_SHAPES["long_500k"])
    d = cfg.d_model
    attn_layers = cfg.n_layers
    # attention term must be capped at the window, not 524288
    cap = 2.0 * cfg.active_param_count() * 1 + 4.0 * d * attn_layers * 4096
    assert fl <= cap * 1.01


def test_peaks_keyed_by_device_kind():
    """Peaks come from a table keyed by jax's device_kind; a device that is
    not in it is an error, never silently given v5e numbers."""
    v5e = peaks("TPU v5 lite")
    assert v5e["flops"] == 197e12 and v5e["hbm_bw"] == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        peaks("cpu")
