"""Roofline-term extraction from compiled dry-run artifacts.

Three terms per (arch x shape x mesh), in seconds (see EXPERIMENTS.md):

    compute    = HLO_FLOPs_per_device / peak_FLOPs_per_chip
    memory     = HLO_bytes_per_device / HBM_bw
    collective = collective_bytes_per_device / ICI_link_bw

``cost_analysis`` gives per-device FLOPs/bytes (the compiled module is the
per-partition SPMD program).  Collective bytes are parsed from the
post-partitioning HLO text: per-op wire bytes are estimated as
all-gather/all-to-all/collective-permute -> result bytes;
reduce-scatter -> operand bytes; all-reduce -> 2x operand bytes (ring).
DCN (pod axis) collectives use the same accounting but are reported
separately when identifiable via replica groups larger than a pod.

MODEL_FLOPS (useful work) per device:
    train   : 6 * N_active * tokens + attention pair-work (fwd+bwd)
    prefill : 2 * N_active * tokens + attention pair-work
    decode  : 2 * N_active * batch + batch * cache * attn pair cost
The ratio MODEL_FLOPS / HLO_FLOPs exposes remat recompute, MoE dispatch
overhead, padded heads, etc.
"""

from __future__ import annotations

import re
from dataclasses import asdict, dataclass
from typing import Dict, Optional

from repro.models.config import InputShape, ModelConfig

# Per-chip peaks keyed by ``jax.Device.device_kind``.  Source: Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s of
# inter-chip interconnect over 4 links (50 GB/s per link).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown kind is
    an error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; add them "
            "to launch/roofline.PEAKS with their source") from None

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8": 1,
                "s64": 8, "s32": 4, "u64": 8, "u32": 4, "s16": 2, "u16": 2,
                "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16}

_COLL_RE = re.compile(
    r"=\s+((?:\([^)]*\)|\S+))\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(", re.M)
_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|f8\w*|s64|s32|u64|u32|s16|u16|s8|u8|pred|c64|c128)\[([0-9,]*)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dt[:4] if dt.startswith("f8") else dt, 2)
    return total


_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_EXPL_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return max(int(m.group(2)), 1)
    m = _GROUPS_EXPL_RE.search(line)
    if m:
        return max(len(m.group(1).split(",")), 1)
    return 8  # conservative default


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-device wire bytes by collective kind, from per-partition HLO.

    Post-optimization HLO prints operands without types, so wire bytes are
    derived from the RESULT shape + replica group size n (ring algorithms):
      all-gather      res * (n-1)/n     (result = gathered full)
      all-reduce      2 * res * (n-1)/n (result == operand)
      reduce-scatter  res * (n-1)       (result = scattered shard)
      all-to-all      res * (n-1)/n
      collective-permute  res
    """
    out: Dict[str, float] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        result_type, op = m.group(1), m.group(2)
        res = _shape_bytes(result_type)
        n = _group_size(line)
        if op == "all-gather":
            wire = res * (n - 1) / n
        elif op == "reduce-scatter":
            wire = res * (n - 1)
        elif op == "all-reduce":
            wire = 2.0 * res * (n - 1) / n
        elif op == "all-to-all":
            wire = res * (n - 1) / n
        else:                                  # collective-permute
            wire = res
        out[op] = out.get(op, 0.0) + float(wire)
    out["total"] = sum(v for k, v in out.items() if k != "total")
    return out


def model_flops(cfg: ModelConfig, shape: InputShape) -> float:
    """Useful (algorithmic) FLOPs for the whole step, all chips together."""
    n_active = cfg.active_param_count()
    d = cfg.d_model
    attn_layers = sum(1 for s in cfg.pattern if s.mixer == "attn") \
        * cfg.n_blocks
    B, S = shape.global_batch, shape.seq_len
    window = cfg.sliding_window or cfg.long_context_window

    def attn_pairs(q_tokens, kv_tokens, causal=True):
        if window is not None and shape.name == "long_500k":
            kv_tokens = min(kv_tokens, window)
        pairs = q_tokens * kv_tokens
        return pairs / 2 if causal and q_tokens == kv_tokens else pairs

    if shape.kind == "train":
        tokens = B * S
        fl = 6.0 * n_active * tokens
        fl += 3 * 4.0 * d * attn_layers * B * attn_pairs(S, S)
        return fl
    if shape.kind == "prefill":
        tokens = B * S
        fl = 2.0 * n_active * tokens
        fl += 4.0 * d * attn_layers * B * attn_pairs(S, S)
        return fl
    # decode: one token per sequence, full-cache attention read
    fl = 2.0 * n_active * B
    kv = S if window is None else min(S, window)
    fl += 4.0 * d * attn_layers * B * kv
    return fl


@dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops_per_dev: float
    hlo_bytes_per_dev: float
    coll_bytes_per_dev: float
    peak_mem_per_dev: float
    compute_s: float
    memory_s: float          # spec term: HLO bytes-accessed / HBM bw
    memory_adj_s: float      # fusion-adjusted: (args+outputs+temps) / HBM bw
    collective_s: float
    model_flops_total: float
    useful_ratio: float
    bottleneck: str          # from (compute, memory_adj, collective)
    bottleneck_hlo: str      # from (compute, memory[raw], collective)
    coll_detail: Optional[dict] = None

    def to_dict(self) -> dict:
        return asdict(self)


def analyse(arch: str, shape: InputShape, mesh_name: str, chips: int,
            cfg: ModelConfig, cost: dict, *, device_kind: str,
            hlo_text: str = "", peak_mem: float = 0.0,
            coll: Optional[dict] = None) -> Roofline:
    flops = float(cost.get("flops", 0.0))
    mem_bytes = float(cost.get("bytes accessed", 0.0))
    if coll is not None:
        coll = {"total": coll.get("collective", 0.0),
                **coll.get("coll_detail", {})}
    else:
        coll = collective_bytes(hlo_text)
    peak = peaks(device_kind)
    compute_s = flops / peak["flops"]
    memory_s = mem_bytes / peak["hbm_bw"]
    memory_adj_s = peak_mem / peak["hbm_bw"]
    collective_s = coll["total"] / peak["ici_bw"]
    terms_adj = {"compute": compute_s, "memory": memory_adj_s,
                 "collective": collective_s}
    terms_hlo = {"compute": compute_s, "memory": memory_s,
                 "collective": collective_s}
    mf = model_flops(cfg, shape)
    ratio = mf / (flops * chips) if flops > 0 else float("nan")
    return Roofline(
        arch=arch, shape=shape.name, mesh=mesh_name, chips=chips,
        hlo_flops_per_dev=flops, hlo_bytes_per_dev=mem_bytes,
        coll_bytes_per_dev=coll["total"], peak_mem_per_dev=peak_mem,
        compute_s=compute_s, memory_s=memory_s, memory_adj_s=memory_adj_s,
        collective_s=collective_s,
        model_flops_total=mf, useful_ratio=ratio,
        bottleneck=max(terms_adj, key=terms_adj.get),
        bottleneck_hlo=max(terms_hlo, key=terms_hlo.get),
        coll_detail={k: v for k, v in coll.items() if k != "total"})
