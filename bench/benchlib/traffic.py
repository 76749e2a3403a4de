"""The one traffic generator: turns a traffic file's parameters into a
request schedule.

A traffic file (``bench/traffic/<mix>.json``) is data.  Its keys:

* ``schedule_seed``: draws the schedule (arrivals and lengths), so every
  run of a cell serves the same set of sizes at the same times.  The
  run's ``--seed`` draws only the weights and the prompt token ids.
* ``arrival``: ``{"process": "poisson", "rate_per_s": r}`` (an open loop
  of independent users), optionally with ``"batch": {"size": k,
  "spread_s": s}`` (fan-out: each Poisson event is one client submitting
  ``k`` requests, due at uniform times within ``s`` seconds of it; ``r``
  stays the mean rate of requests), or ``{"process": "resident",
  "sessions": n}``: ``n`` sessions that are prefilled during set-up and
  decode through the window.
* ``prompt`` and ``output``: a length distribution, or ``{"mixture":
  [{"weight": w, ...dist}, ...]}``; with ``"stratified": true`` the
  component of the i-th request follows the golden-ratio sequence
  instead of a draw, so every stretch of requests holds each component
  near its weight (a short window still sees its share of long prompts).
  A distribution is ``{"dist": "loguniform", "min", "max"}``, ``{"dist":
  "lognormal", "median", "sigma", "min", "max", "bounds": "clip" |
  "truncate"}`` or ``{"dist": "fixed", "value"}``.
"""

from __future__ import annotations

import math
from typing import Dict, List

import numpy as np


GOLDEN = (math.sqrt(5) - 1) / 2


def _draw(spec: dict, rng: np.random.Generator, i: int = 0) -> int:
    if "mixture" in spec:
        comps = spec["mixture"]
        w = np.array([c["weight"] for c in comps], float)
        if spec.get("stratified"):
            u = ((i + 1) * GOLDEN) % 1.0
            k = int(np.searchsorted(np.cumsum(w / w.sum()), u, "right"))
            return _draw(comps[min(k, len(comps) - 1)], rng)
        return _draw(comps[rng.choice(len(comps), p=w / w.sum())], rng)
    kind = spec["dist"]
    if kind == "fixed":
        return int(spec["value"])
    lo, hi = spec["min"], spec["max"]
    if kind == "loguniform":
        return int(round(math.exp(rng.uniform(math.log(lo), math.log(hi)))))
    if kind == "lognormal":
        mu, sigma = math.log(spec["median"]), spec["sigma"]
        if spec.get("bounds", "clip") == "truncate":
            while True:
                v = rng.lognormal(mu, sigma)
                if lo <= v <= hi:
                    return int(round(v))
        return int(round(min(max(rng.lognormal(mu, sigma), lo), hi)))
    raise ValueError(f"unknown length distribution {kind!r}")


def _arrivals(arr: dict, seconds: float, rng: np.random.Generator
              ) -> List[float]:
    if arr["process"] == "resident":
        return [0.0] * int(arr["sessions"])
    if arr["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    batch = arr.get("batch", {"size": 1, "spread_s": 0.0})
    k, spread = int(batch["size"]), float(batch["spread_s"])
    out, t = [], 0.0
    while True:
        t += rng.exponential(k / float(arr["rate_per_s"]))
        if t >= seconds:
            return sorted(x for x in out if x < seconds)
        out += [t + spread * u for u in rng.uniform(size=k)] if k > 1 \
            else [t]


def schedule(traffic: dict, seconds: float) -> List[Dict]:
    """Requests due in ``[0, seconds)``, in arrival order: dicts with
    ``rid``, ``arrival`` (seconds from the window's start), ``prompt_len``
    and ``output_len``."""
    seed = int(traffic["schedule_seed"])
    # arrivals and lengths draw from streams of their own, so a longer
    # window keeps every request of a shorter one
    times = _arrivals(traffic["arrival"], seconds,
                      np.random.default_rng([seed, 0]))
    rng = np.random.default_rng([seed, 1])
    reqs = []
    for rid, t in enumerate(times):
        prompt = _draw(traffic["prompt"], rng, rid)
        out = _draw(traffic["output"], rng, rid)
        reqs.append({"rid": rid, "arrival": float(t), "prompt_len": prompt,
                     "output_len": out})
    return reqs


def prompt_tokens(reqs: List[Dict], vocab: int, seed: int
                  ) -> Dict[int, np.ndarray]:
    """Token ids of every prompt, from the run's ``--seed``."""
    rng = np.random.default_rng(int(seed) % 2**64)
    return {r["rid"]: rng.integers(0, vocab, r["prompt_len"]).astype(
        np.int32) for r in reqs}
