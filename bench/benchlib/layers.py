"""Shared arithmetic of the per-layer metric readers in bench/metrics/.

Each reader gets ``run``: ``rec`` (the window's Records), ``trace`` (the
reduced trace, or None without ``--trace 1``), ``model`` (the
configuration's model dict), ``peak`` (the chip's peaks) and ``seconds``.
A reader that finds nothing to read returns None, never 0.
"""

from __future__ import annotations

from typing import Optional

from benchlib import flops


def idle_share(run) -> Optional[float]:
    tr = run["trace"]
    if tr is None or tr["window_s"] <= 0 or tr["devices"] == 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def kernel_roofline(run, kernel: str, calls) -> Optional[float]:
    """Roofline time of ``calls`` ((flops, bytes) per layer call, each
    run by every layer) over the kernel's summed device time, in %."""
    tr = run["trace"]
    if tr is None:
        return None
    k = tr["kernels"].get(kernel, {})
    calls = list(calls)
    if not calls or not k.get("seconds"):
        return None
    L = flops.dims(run["model"])["L"]
    best = sum(flops.roofline_seconds(f, b, run["peak"]) for f, b in calls)
    return 100.0 * L * best / k["seconds"]


def step_mfu(run, step: str, work: float) -> Optional[float]:
    """``work`` operations over the device time of the ``step`` programs
    at the chip's peak, in %."""
    tr = run["trace"]
    if tr is None or not tr["step_seconds"].get(step) or work <= 0:
        return None
    return 100.0 * work / (tr["step_seconds"][step] * run["peak"]["flops"])
