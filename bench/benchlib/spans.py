"""Reduce the program's own wall-clock spans in a profiler trace.

The serving engine opens a host annotation named ``engine.<phase>`` around
each phase of its handlers (``repro.serving.telemetry.SPANS``).  They lie
on the profiler's clock, the clock of the device's operations, so the
device's idle time can be split among them.  ``reduce`` reads the same
trace as ``trace.reduce``: it takes every host event whose name starts
with ``engine.``, clipped to the harness's ``WINDOW``, nests the spans by
interval (a span that starts inside another is inside it), and splits
every interval in which no device operation ran exactly, by time, to the
innermost span open over each piece, or to ``OUTSIDE`` where none is.
The pieces sum to the window's idle time.
"""

from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from benchlib import trace

PREFIX = "engine."
OUTSIDE = "outside"
TICK = "engine.decode_tick"


def _read(profile):
    """(window, device busy intervals by device, program spans), times in
    ns; spans as ``(start, end, name)`` clipped to the window."""
    wins, spans = [], []
    devices: Dict[str, List[Tuple[float, float]]] = {}
    for plane in profile.planes:
        if plane.name.startswith(trace.DEVICE_PLANE):
            iv = [(e.start_ns, e.start_ns + e.duration_ns)
                  for line in plane.lines if line.name == trace.OPS_LINE
                  for e in line.events]
            if iv:
                devices[plane.name] = iv
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == trace.WINDOW:
                        wins.append((e.start_ns,
                                     e.start_ns + e.duration_ns))
                    elif e.name.startswith(PREFIX):
                        spans.append((e.start_ns,
                                      e.start_ns + e.duration_ns, e.name))
    if not wins:
        raise ValueError(f"the trace holds no {trace.WINDOW!r} annotation")
    w0, w1 = min(a for a, _ in wins), max(b for _, b in wins)
    clipped = [(max(a, w0), min(b, w1), n) for a, b, n in spans]
    busy = {dev: trace._merge([(max(a, w0), min(b, w1)) for a, b in iv
                               if min(b, w1) > max(a, w0)])
            for dev, iv in devices.items()}
    return (w0, w1), busy, [s for s in clipped if s[1] > s[0]]


def _pieces(w0: float, w1: float, spans: List[Tuple[float, float, str]]):
    """Partition ``[w0, w1]`` into ``(start, end, innermost, open names)``
    pieces.  Spans are ordered by start, then longest first, so the open
    span that comes last in that order is the innermost one."""
    spans = sorted(spans, key=lambda s: (s[0], -s[1]))
    starts, ends = defaultdict(list), defaultdict(list)
    for i, (a, b, _) in enumerate(spans):
        starts[a].append(i)
        ends[b].append(i)
    edges = sorted({w0, w1} | set(starts) | set(ends))
    out, open_ = [], []
    for x0, x1 in zip(edges, edges[1:]):
        for i in ends.get(x0, ()):
            open_.remove(i)
        open_ += starts.get(x0, ())
        if open_:
            inner = spans[max(open_)][2]
            names = frozenset(spans[i][2] for i in open_)
        else:
            inner, names = OUTSIDE, frozenset()
        out.append((x0, x1, inner, names))
    return out


def reduce(profile) -> dict:
    """``profile`` is a ``jax.profiler.ProfileData``.  Times in seconds.

    window_s: the window annotation's length; idle_s: time in the window
    in which no device operation ran, averaged over the devices that ran
    any; outside_s: the part of that idle time in which no program span
    was open; spans: {name: {"count", "seconds", "self_s", "idle_s",
    "idle_self_s"}}: how many spans of that name overlap the window, their
    time in it, the time in which one is the innermost open span, the idle
    time while one is open, and the idle time while one is the innermost
    open span.  ``outside_s`` plus every ``idle_self_s`` is ``idle_s``."""
    (w0, w1), busy, spans = _read(profile)
    pieces = _pieces(w0, w1, spans)
    stats = {n: {"count": 0, "seconds": 0.0, "self_s": 0.0, "idle_s": 0.0,
                 "idle_self_s": 0.0} for _, _, n in spans}
    for a, b, n in spans:
        stats[n]["count"] += 1
        stats[n]["seconds"] += b - a
    for a, b, inner, _ in pieces:
        if inner != OUTSIDE:
            stats[inner]["self_s"] += b - a
    n_dev = max(len(busy), 1)
    idle_total, outside = 0.0, 0.0
    for merged in busy.values():
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        gaps = [(g0, g1) for g0, g1 in zip(edges[::2], edges[1::2])
                if g1 > g0]
        i = 0
        for g0, g1 in gaps:
            idle_total += g1 - g0
            while pieces[i][1] <= g0:
                i += 1
            j = i
            while j < len(pieces) and pieces[j][0] < g1:
                a, b, inner, names = pieces[j]
                cut = min(b, g1) - max(a, g0)
                if inner == OUTSIDE:
                    outside += cut
                else:
                    stats[inner]["idle_self_s"] += cut
                    for n in names:
                        stats[n]["idle_s"] += cut
                j += 1
    for s in stats.values():
        s["seconds"] *= 1e-9
        s["self_s"] *= 1e-9
        s["idle_s"] *= 1e-9 / n_dev
        s["idle_self_s"] *= 1e-9 / n_dev
    return {"window_s": (w1 - w0) * 1e-9,
            "idle_s": idle_total * 1e-9 / n_dev,
            "outside_s": outside * 1e-9 / n_dev,
            "spans": dict(sorted(stats.items()))}


def tick_idle_ms(reduced: dict) -> Optional[float]:
    """Device-idle time inside the engine's decode-tick spans per tick,
    in ms; None where the trace holds no tick span."""
    tick = reduced["spans"].get(TICK)
    if not tick or not tick["count"]:
        return None
    return 1e3 * tick["idle_s"] / tick["count"]


def table(reduced: dict) -> str:
    """The reduction as a text table, one span name per row."""
    rows = [f"{'span':32} {'count':>6} {'seconds':>10} {'self_s':>10} "
            f"{'idle_s':>10} {'idle_self_s':>11}"]
    for n, s in reduced["spans"].items():
        rows.append(f"{n:32} {s['count']:6d} {s['seconds']:10.6f} "
                    f"{s['self_s']:10.6f} {s['idle_s']:10.6f} "
                    f"{s['idle_self_s']:11.6f}")
    rows.append(f"{OUTSIDE:32} {'':6} {'':10} {'':10} "
                f"{reduced['outside_s']:10.6f} {reduced['outside_s']:11.6f}")
    rows.append(f"device idle {reduced['idle_s']:.6f} s of the "
                f"{reduced['window_s']:.6f} s window")
    return "\n".join(rows)
