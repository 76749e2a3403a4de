import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production mesh, record memory/cost/roofline terms.

The two lines above MUST stay first: jax locks the device count on first
initialisation, and the dry-run needs 512 placeholder host devices for the
(2, 16, 16) multi-pod mesh.  Nothing here allocates device memory — inputs
are ShapeDtypeStructs.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-9b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out DIR]
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax

from repro.configs.registry import (ASSIGNED, get_config, input_specs,
                                    supports_shape)
from repro.models.config import INPUT_SHAPES
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import analyse, collective_bytes
from repro.launch.steps import build_step, scanned_param_bytes_per_dev

# the production mesh is a TPU v5e pod slice (launch/mesh.py); the compile
# runs on placeholder host devices, so the roofline peaks are named here
TARGET_DEVICE_KIND = "TPU v5 lite"

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


VARIANTS = {
    "": {},
    "zigzag_skip": {"zigzag_skip": True},
    "window_slice": {"window_slice": True},
    "ring_cache": {"ring_cache": True},
    "moe_gather": {"moe_gather_dispatch": True},
    "shard2d": {"ring_cache": True, "shard2d_weights": True},
    "moe_ep": {"moe_ep": True},
    "optimized": {"zigzag_skip": True, "ring_cache": True},
}


def _cost_terms(cfg, shape, mesh, n_blocks: int,
                ctx_overrides: dict | None = None) -> dict:
    """flops / bytes / collective-bytes of an UNROLLED n_blocks-deep model.

    XLA cost_analysis counts a while-loop body once, so the layer scan is
    unrolled here; the caller extrapolates full depth from (1, 2)-block
    differences: total = c1 + (n_blocks - 1) * (c2 - c1)."""
    small = dataclasses.replace(
        cfg, n_layers=n_blocks * len(cfg.pattern),
        n_encoder_layers=(n_blocks if cfg.encoder_decoder else 0))
    fn, in_sh, args = build_step(small, shape, mesh, unroll_scan=True,
                                 ctx_overrides=ctx_overrides)
    with jax.set_mesh(mesh):
        compiled = jax.jit(fn, in_shardings=in_sh).lower(*args).compile()
        cost = compiled.cost_analysis()
        coll = collective_bytes(compiled.as_text())
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes accessed": float(cost.get("bytes accessed", 0.0)),
            "collective": coll["total"], "coll_detail": coll}


def extrapolated_cost(cfg, shape, mesh, ctx_overrides=None) -> dict:
    c1 = _cost_terms(cfg, shape, mesh, 1, ctx_overrides)
    c2 = _cost_terms(cfg, shape, mesh, 2, ctx_overrides)
    nb = cfg.n_blocks
    out = {}
    for k in ("flops", "bytes accessed", "collective"):
        body = max(c2[k] - c1[k], 0.0)
        out[k] = c1[k] + (nb - 1) * body
    out["coll_detail"] = {
        k: c1["coll_detail"].get(k, 0.0)
        + (nb - 1) * max(c2["coll_detail"].get(k, 0.0)
                         - c1["coll_detail"].get(k, 0.0), 0.0)
        for k in set(c1["coll_detail"]) | set(c2["coll_detail"])
        if k != "total"}
    return out


def run_one(arch: str, shape_name: str, multi_pod: bool = False,
            out_dir: str = RESULTS_DIR, verbose: bool = True,
            variant: str = "") -> dict:
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "variant": variant}
    if not supports_shape(cfg, shape):
        rec["status"] = "skipped"
        rec["reason"] = ("no sub-quadratic path for long_500k "
                         "(see DESIGN.md §Arch-applicability)")
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    overrides = VARIANTS[variant]
    t0 = time.time()
    # 1) full-depth compile (scan over blocks): proves the sharding config is
    #    coherent and yields the per-device memory picture.  ref_blocked
    #    bounds attention temp memory the way the TPU flash kernel does.
    fn, in_sh, args = build_step(cfg, shape, mesh, impl="ref_blocked",
                                 ctx_overrides=overrides)
    with jax.set_mesh(mesh):
        lowered = jax.jit(fn, in_shardings=in_sh).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        print(mem)
    # 2) cost terms from unrolled shallow models, extrapolated to full depth
    cost = extrapolated_cost(cfg, shape, mesh, overrides)
    peak = getattr(mem, "temp_size_in_bytes", 0) + \
        getattr(mem, "argument_size_in_bytes", 0) + \
        getattr(mem, "output_size_in_bytes", 0)
    roof = analyse(arch, shape, mesh_name, chips, cfg, cost,
                   device_kind=TARGET_DEVICE_KIND, hlo_text="",
                   peak_mem=peak, coll=cost)
    dtype_bytes = 4 if shape.kind == "train" else 2
    scan_params = scanned_param_bytes_per_dev(cfg, mesh,
                                              dtype_bytes=dtype_bytes)
    temp_raw = getattr(mem, "temp_size_in_bytes", 0)
    # CPU XLA double-buffers the while-carry param stack; TPU aliases it
    # (loop-invariant buffers).  See EXPERIMENTS.md §Dry-run notes.
    temp_adj = max(0, temp_raw - 2 * scan_params)
    rec.update(status="ok", lower_s=round(t_lower, 1),
               compile_s=round(t_compile, 1),
               memory_analysis=str(mem),
               argument_bytes=getattr(mem, "argument_size_in_bytes", None),
               temp_bytes=temp_raw,
               temp_bytes_tpu_adjusted=temp_adj,
               scanned_param_bytes=scan_params,
               output_bytes=getattr(mem, "output_size_in_bytes", None),
               roofline=roof.to_dict())
    if verbose:
        print(f"[{arch} x {shape_name} x {mesh_name}] OK "
              f"lower {t_lower:.0f}s compile {t_compile:.0f}s | "
              f"compute {roof.compute_s*1e3:.2f}ms "
              f"mem(hlo) {roof.memory_s*1e3:.2f}ms "
              f"mem(adj) {roof.memory_adj_s*1e3:.2f}ms "
              f"coll {roof.collective_s*1e3:.2f}ms -> {roof.bottleneck} | "
              f"useful {roof.useful_ratio:.2f} | temp/dev "
              f"{(rec['temp_bytes'] or 0)/2**30:.2f} GiB "
              f"(tpu-adj {rec['temp_bytes_tpu_adjusted']/2**30:.2f})",
              flush=True)
    os.makedirs(out_dir, exist_ok=True)
    suffix = f"_{variant}" if variant else ""
    fname = f"{arch}_{shape_name}_{mesh_name}{suffix}.json".replace("/", "_")
    with open(os.path.join(out_dir, fname), "w") as f:
        json.dump(rec, f, indent=1, default=str)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--variant", default="", choices=list(VARIANTS))
    ap.add_argument("--out", default=RESULTS_DIR)
    args = ap.parse_args()

    pairs = []
    archs = ASSIGNED if args.arch is None else [args.arch]
    shapes = list(INPUT_SHAPES) if args.shape is None else [args.shape]
    for a in archs:
        for s in shapes:
            pairs.append((a, s))

    failures = []
    for a, s in pairs:
        try:
            rec = run_one(a, s, multi_pod=args.multi_pod, out_dir=args.out,
                          variant=args.variant)
            if rec["status"] == "skipped":
                print(f"[{a} x {s}] SKIPPED: {rec['reason']}", flush=True)
        except Exception as e:
            failures.append((a, s, repr(e)))
            print(f"[{a} x {s}] FAIL: {e}", flush=True)
            traceback.print_exc()
    if failures:
        raise SystemExit(f"{len(failures)} dry-run failures: "
                         + ", ".join(f"{a}x{s}" for a, s, _ in failures))
    print("dry-run complete: all combinations lowered + compiled.")


if __name__ == "__main__":
    main()
