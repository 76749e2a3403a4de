"""Drives the program's serving engine on the wall clock.

The engine (``repro.serving.engine.ServingEngine``) schedules on an event
clock: its handlers run in event order, each at an event time that the
planner's latency models set.  ``WallEngine`` runs the same handlers in
the same order and puts them on the wall clock:

* gate: an ``arrive`` event waits until its request's due wall time (an
  open loop of independent users); every other handler runs as soon as
  the one before it returns;
* stamp: after each handler returns, every request whose output grew is
  stamped with the wall clock (the engine has already synced its first
  token and each decode token to the host there);
* stop: the loop ends at the deadline, once every request due in the
  window has finished (an answer that comes late is late, not missing;
  each finished answer can be compared), and the event heap is dropped.
  Resident sessions, which outlast the window, stop at the deadline.

The event order depends only on the submitted requests, never on the
wall clock or the token ids, so serving the same schedule once ungated on
a fresh engine compiles exactly the shapes the window will use.
"""

from __future__ import annotations

import contextlib
import dataclasses
import heapq
import time
from typing import Dict, List, Optional

import numpy as np

clock = time.perf_counter


@dataclasses.dataclass
class Records:
    """What the window did, on the wall clock (seconds from ``clock``)."""
    t0: float = 0.0                  # window start
    deadline: float = 0.0
    end: float = 0.0                 # the loop stopped
    due: Dict[int, float] = dataclasses.field(default_factory=dict)
    gate: Dict[int, float] = dataclasses.field(default_factory=dict)
    first_chunk: Dict[int, float] = dataclasses.field(default_factory=dict)
    stamps: Dict[int, List[float]] = dataclasses.field(default_factory=dict)
    chunks: List[dict] = dataclasses.field(default_factory=list)
    ticks: List[dict] = dataclasses.field(default_factory=list)
    # the longest handler: (seconds, event kind, start)
    longest: tuple = (0.0, "none", 0.0)


def make_engine_class():
    from repro.serving.engine import ServingEngine

    class WallEngine(ServingEngine):
        """ServingEngine with a wall-clock gate, token stamps and
        per-chunk / per-tick records."""

        rec: Optional[Records] = None

        def _on_chunk_start(self, now, payload):
            rid = payload[0]
            n0 = len(self.chunk_log.get(rid, ()))
            st = self._prefill.get(rid)
            hist = st.off if st is not None else 0
            t0 = clock()
            super()._on_chunk_start(now, payload)
            if self.rec is not None and len(self.chunk_log.get(rid, ())) > n0:
                self.rec.first_chunk.setdefault(rid, t0)
                self.rec.chunks.append({
                    "rid": rid, "n": self.chunk_log[rid][-1]["len"],
                    "hist": hist, "t0": t0, "t1": clock()})

        def _on_decode_tick(self, now, did):
            d = self.dstates[did]
            active = [r for r in d.slots if r is not None and r in d.meta]
            ctx = {r: d.meta[r].cache_len for r in active}
            n0 = {r: len(self.outputs[r]) for r in active}
            t0 = clock()
            super()._on_decode_tick(now, did)
            grew = [r for r in active if len(self.outputs[r]) > n0[r]]
            if self.rec is not None and grew:
                self.rec.ticks.append({"ctx": [ctx[r] for r in grew],
                                       "t0": t0, "t1": clock()})

    return WallEngine


def build_engine(cls, cfg, params, eng: dict):
    """An engine as the configuration's ``engine`` block sizes it, under
    the tetris policy on the planner's Eq. (1) model."""
    from repro.core.latency_model import table1_model
    from repro.models.sharding import ExecContext
    from repro.serving.simulator import ClusterSpec, make_policy
    spec = ClusterSpec(n_prefill=eng["n_prefill"], n_decode=1,
                       sp_candidates=tuple(eng["sp_candidates"]))
    return cls(cfg, params, spec, make_policy("tetris", table1_model(), spec),
               ctx=ExecContext(), max_batch=eng["max_batch"],
               max_seq=eng["max_seq"], block_size=eng["block_size"],
               prefill_pool_blocks=eng["prefill_pool_blocks"],
               host_pool_blocks=eng["host_pool_blocks"])


def submit(engine, reqs: List[dict], tokens: Dict[int, np.ndarray]) -> None:
    from repro.serving.request import Request
    for r in reqs:
        engine.submit(Request(rid=r["rid"], arrival=r["arrival"],
                              prompt_len=r["prompt_len"],
                              output_len=r["output_len"]), tokens[r["rid"]])


def drive(engine, rec: Records, *, gated: bool, deadline: Optional[float],
          annotate: bool = False, until=None, max_drain_s: float = 60.0,
          finish: bool = False):
    """Run the engine's handlers in event order until the heap is empty,
    ``until()`` holds, or the deadline has passed and every submitted
    request has its first token, or with ``finish`` all its tokens (or
    ``max_drain_s`` more has passed).  Arrivals wait for their due wall
    time when ``gated``."""
    import jax
    ann = (jax.profiler.TraceAnnotation if annotate
           else (lambda name: contextlib.nullcontext()))
    seen = {rid: len(v) for rid, v in engine.outputs.items()}
    events = engine.events
    while events:
        if until is not None and until():
            break
        t, _, kind, payload = events[0]
        now = clock()
        if deadline is not None and now >= deadline:
            waiting = [r for r, q in engine.reqs.items()
                       if r not in engine.outputs
                       or (finish and len(engine.outputs[r]) < q.output_len)]
            if not waiting or now >= deadline + max_drain_s:
                break
        if kind == "arrive" and gated:
            due = rec.t0 + t
            rec.due[payload] = due
            if due > now:
                with ann("gate_wait"):
                    time.sleep(due - now)
            rec.gate[payload] = clock()
        heapq.heappop(events)
        h0 = clock()
        with ann("handler." + kind):
            getattr(engine, f"_on_{kind}")(t, payload)
        h1 = clock()
        if h1 - h0 > rec.longest[0]:
            rec.longest = (h1 - h0, kind, h0)
        for rid, out in engine.outputs.items():
            n = len(out)
            if n > seen.get(rid, 0):
                rec.stamps.setdefault(rid, []).extend(
                    [h1] * (n - seen.get(rid, 0)))
                seen[rid] = n
    rec.end = clock()
    if deadline is not None:
        events.clear()


def warm_decode_width(engine, did: int, width: int) -> None:
    """Run one decode step of the live batch with its block table padded
    to ``width`` pages (padding points at the scratch page), so that the
    programs of that width are compiled before the window needs them.
    The step appends each row's next token to the page that the next real
    tick writes the same token to (or to the scratch page where that page
    is not allocated yet), and its logits are dropped."""
    import jax.numpy as jnp
    from repro.models.transformer import forward
    d = engine.dstates[did]
    active = [r for r in d.slots if r is not None and r in d.meta]
    B = d.max_batch
    toks = np.zeros((B, 1), np.int32)
    clen = np.zeros((B,), np.int32)
    for r in active:
        m = d.meta[r]
        toks[m.row, 0] = m.last_token
        clen[m.row] = m.cache_len
    bt = np.asarray(d.block_table(active))
    pad = np.full((B, width), d.kv.scratch_block, np.int32)
    pad[:, :bt.shape[1]] = bt
    toks, clen = jnp.asarray(toks), jnp.asarray(clen)
    caches = d.build_caches(active, jnp.asarray(pad))
    logits, _, new = forward(engine.params, engine.cfg, engine.ctx, toks,
                             clen[:, None], "decode", caches=caches,
                             cache_len=clen)
    d.absorb(new, active)
    np.asarray(jnp.argmax(logits[:, 0, :engine.cfg.vocab_size], axis=-1))


def decode_width(engine, did: int = 0) -> int:
    d = engine.dstates[did]
    return max(len(m.blocks) for m in d.meta.values())


def pool_bytes(engine) -> dict:
    import jax
    dec = sum(a.nbytes for d in engine.dstates
              for a in jax.tree.leaves(d.kv.pools))
    pre = sum(a.nbytes for a in jax.tree.leaves(engine.pkv.pools))
    return {"decode": dec, "prefill": pre}
