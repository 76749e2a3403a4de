"""Reduce a profiler trace (``.xplane.pb``) to busy and idle time,
per-kernel device time, the device operations that took most time, and
the idle gaps named by what the host was doing.

The harness wraps the measured window in a host annotation named
``WINDOW`` and each engine handler it runs in one named ``handler.<kind>``
(``gate_wait`` while it waits for a request's due time).  Device
operations are the events on the ``XLA Ops`` line of each ``/device:TPU:``
plane.  An event's name there is its HLO instruction (``%fusion.12 = bf16[...]
fusion(...)``); its base name drops the ``%``, the numeric suffix and
everything from `` = ``.  A loop (``while``) is listed together with the
operations of its body, so it counts towards busy time but not among the
operations that took most time.  A Pallas kernel's base name is the name of
the function that calls it; ``KERNELS`` lists those names in one place.
"""

from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

WINDOW = "bench_window"
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

# kernel -> base names of its device operations, in one place
KERNELS = {
    "flash_attention": ("flash_attention",),
    "paged_flash_prefill": ("paged_flash_prefill",),
    "paged_flash_decode": ("paged_flash_decode",),
}
CONTAINERS = ("while", "conditional", "call")
# a program that runs one of these kernels is a prefill or a decode step
STEP_OF = {"flash_attention": "prefill", "paged_flash_prefill": "prefill",
           "paged_flash_decode": "decode"}


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _merge(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def base_name(name: str) -> str:
    """``%paged_flash_decode.10 = (bf16[...]) custom-call(...)`` ->
    ``paged_flash_decode``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    stem, dot, tail = head.rpartition(".")
    return stem if dot and tail.isdigit() else head


def kernel_of(base: str) -> Optional[str]:
    for kernel, names in KERNELS.items():
        if base in names:
            return kernel
    return None


def reduce(profile, top: int = 10) -> dict:
    """``profile`` is a ``jax.profiler.ProfileData``.  Times in seconds.

    busy_s: union of device-operation intervals inside the window,
    averaged over the devices that ran any; window_s: the window
    annotation's length; kernels: {kernel: {"seconds", "count"}} summed
    over devices; device_ops: [[name, seconds]] of the ``top``
    operations by summed time (base names, loops left out); idle_gaps:
    [[host annotation, seconds]] of idle device time, summed by the
    handler annotation open at each gap's midpoint (``host_other`` where
    none is); step_seconds:
    {"prefill" | "decode": seconds} of the device programs (``XLA
    Modules`` events) that ran a prefill or a decode kernel."""
    host: List[Tuple[float, float, str]] = []
    devices: Dict[str, List[Tuple[float, float, str]]] = {}
    modules: Dict[str, List[Tuple[float, float]]] = {}
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PLANE):
            evs, mods = [], []
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    mods += [(e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events]
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    evs.append((e.start_ns, e.start_ns + e.duration_ns,
                                base_name(e.name)))
            if evs:
                devices[plane.name] = evs
                modules[plane.name] = sorted(mods)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW or e.name.startswith(
                            ("handler.", "gate_wait")):
                        host.append((e.start_ns, e.start_ns + e.duration_ns,
                                     e.name))
    wins = [(a, b) for a, b, n in host if n == WINDOW]
    if not wins:
        raise ValueError(f"the trace holds no {WINDOW!r} annotation")
    w0, w1 = min(a for a, _ in wins), max(b for _, b in wins)
    # handler and gate spans run one after another on the driving thread
    spans = sorted((a, b, n) for a, b, n in host if n != WINDOW)
    starts = [a for a, _, _ in spans]

    def doing(t: float) -> str:
        i = bisect.bisect_right(starts, t) - 1
        return spans[i][2] if i >= 0 and t < spans[i][1] else "host_other"

    busy_total, gaps = 0.0, defaultdict(float)
    kernels = {k: {"seconds": 0.0, "count": 0} for k in KERNELS}
    ops = defaultdict(float)
    steps = defaultdict(float)
    for dev, evs in devices.items():
        mods = modules[dev]
        mod_starts = [a for a, _ in mods]
        step_of_mod: Dict[int, str] = {}
        iv = []
        for a, b, name in evs:
            a, b = max(a, w0), min(b, w1)
            if b <= a:
                continue
            iv.append((a, b))
            if name not in CONTAINERS:
                ops[name] += (b - a) * 1e-9
            k = kernel_of(name)
            if k is not None:
                kernels[k]["seconds"] += (b - a) * 1e-9
                kernels[k]["count"] += 1
                i = bisect.bisect_right(mod_starts, a) - 1
                if i >= 0 and a < mods[i][1]:
                    step_of_mod[i] = STEP_OF[k]
        for i, step in step_of_mod.items():
            a, b = max(mods[i][0], w0), min(mods[i][1], w1)
            steps[step] += max(b - a, 0.0) * 1e-9
        merged = _merge(iv)
        busy_total += sum(b - a for a, b in merged)
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                gaps[doing((g0 + g1) / 2)] += (g1 - g0) * 1e-9
    n_dev = max(len(devices), 1)
    return {
        "busy_s": busy_total * 1e-9 / n_dev,
        "window_s": (w1 - w0) * 1e-9,
        "devices": len(devices),
        "kernels": kernels,
        "device_ops": [[n, s] for n, s in sorted(
            ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, s / n_dev] for n, s in sorted(
            gaps.items(), key=lambda kv: -kv[1])[:top]],
        "step_seconds": dict(steps),
    }


def reduce_dir(log_dir: str, top: int = 10) -> dict:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(find_xplane(log_dir)), top)
