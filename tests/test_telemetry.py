"""Telemetry invariants (ISSUE 9): TTFT attribution bit-equality, tick
conservation through the tracer, span well-formedness, back-compat log
views, rollup-vs-gauge audits, and the Chrome trace export.

Two layers: pure-tracer property tests drive the attribution state
machine over RANDOM synthetic preempt/swap/restripe lifecycles (the
bit-equality and partition guarantees must hold for *any* event
sequence, so random schedules are the honest test), and one real traced
engine run under block pressure (swap preemptions + fused and deferred
ticks) checks the recording sites end to end.  The engine's wall-clock
spans are checked in a profiler trace, and against a run without them.
Only this module starts a profiler session.
"""

import contextlib
import glob
import json
import math
import os
from fractions import Fraction

import jax
import numpy as np
import pytest

from hypothesis_shim import given, settings
from hypothesis_shim import strategies as st

from repro.core.chunk_planner import Allocation, Chunk
from repro.core.latency_model import table1_model
from repro.serving import telemetry
from repro.serving.request import Request
from repro.serving.simulator import ClusterSpec, Policy
from repro.serving.telemetry import (ATTRIBUTION_ORDER, MetricsRegistry,
                                     Tracer, attribution_total,
                                     exact_remainder)

MODEL = table1_model()


@pytest.fixture(autouse=True)
def _bound_live_executables():
    yield
    jax.clear_caches()


# ------------------------------------------------------------ pure metrics
def test_registry_counters_gauges_hists():
    m = MetricsRegistry()
    m.counter("a").inc()
    m.counter("a").inc(2.5)
    m.gauge("g").set(3, t=0.5)
    m.gauge("g").set(7)
    for v in (1e-7, 1e-3, 1e-3 * 1.5, 2.0):
        m.hist("h").observe(v)
    snap = m.snapshot()
    assert snap["counters"]["a"] == 3.5
    assert snap["gauges"]["g"] == 7.0
    assert m.gauge("g").samples == [(0.5, 3.0)]
    h = snap["histograms"]["h"]
    assert h["count"] == 4 and h["min"] == 1e-7 and h["max"] == 2.0
    assert "-1" in h["buckets"]            # underflow bucket took 1e-7
    assert m.hist("h").percentile(100) == 2.0
    assert 1e-3 <= m.hist("h").percentile(50) <= 2e-3


def _reachable(s: float, target: float) -> bool:
    """Whether some float q makes ``s + q == target``, decided in exact
    rationals: the q that work form an interval of floats around the
    exact difference, so it holds iff one of the floats nearest that
    difference works."""
    q = float(Fraction(target) - Fraction(s))
    return any(s + c == target for c in (
        q, math.nextafter(q, -math.inf), math.nextafter(q, math.inf)))


def test_exact_remainder_property():
    """Bit-equal wherever the fixed-order sum can reach the target, a
    ``ValueError`` everywhere else; a sum on the target's grain of at
    most twice the target (what attribution hands in) always reaches."""
    @settings(max_examples=50, deadline=None)
    @given(st.lists(st.floats(min_value=0.0, max_value=10.0), min_size=0,
                    max_size=8),
           st.floats(min_value=0.0, max_value=100.0))
    def prop(measured, target):
        s = 0.0
        for v in measured:
            s += v
        if _reachable(s, target):
            assert s + exact_remainder(target, measured) == target
        else:
            with pytest.raises(ValueError):
                exact_remainder(target, measured)
        if s > 2 * target:
            return
        grain = math.ulp(target)
        snapped = [round(v / grain) * grain for v in measured]
        s = 0.0
        for v in snapped:
            s += v
        if s <= 2 * target:
            assert s + exact_remainder(target, snapped) == target
    prop()
    # outside the domain: the floats near -1.0 are 2**-53 apart
    with pytest.raises(ValueError):
        exact_remainder(1e-300, [1.0])
    assert exact_remainder(0.0, [1.0]) == -1.0


# --------------------------------------------------------- tracer basics
def test_disabled_tracer_records_nothing():
    tr = Tracer(enabled=False)
    tr.record(0.0, "arrive", rid=1)
    tr.begin("transfer", 1, 0.0)
    assert tr.events == [] and tr.open_spans() == {}


def test_span_pairing_and_end_all():
    tr = Tracer()
    tr.begin("transfer", 1, 1.0, track=("request", 1))
    tr.begin("swap", 1, 2.0)
    tr.begin("transfer", 2, 3.0)
    assert set(tr.open_spans()) == {("transfer", 1), ("swap", 1),
                                    ("transfer", 2)}
    ev = tr.end("transfer", 1, 4.0)
    assert ev.t == 1.0 and ev.dur == 3.0 and ev.track == ("request", 1)
    tr.end_all(1, 5.0)
    assert set(tr.open_spans()) == {("transfer", 2)}
    assert tr.end("transfer", 9, 9.0) is None       # never opened: no-op
    tr.end_all(2, 6.0)
    assert tr.open_spans() == {}


def test_entries_rebuild_in_record_order():
    tr = Tracer()
    d0, d1 = {"t": 0.1, "x": 1}, {"t": 0.2, "x": 2}
    tr.record(0.1, "preempt", rid=0, entry=d0)
    tr.record(0.15, "tick", dur=0.01, rids=(0,), mode="standalone")
    tr.record(0.2, "preempt", rid=1, entry=d1)
    assert tr.entries("preempt") == [d0, d1]
    assert tr.entries("preempt")[0] is d0          # verbatim, not a copy
    assert tr.entries("restripe") == []


# ------------------------------------------ attribution: random schedules
def _random_lifecycle(rng_draws):
    """Build a random but causally-plausible lifecycle from a draw list:
    arrive, plan, chunks (with durations), then a random walk over
    requeue/preempt(swap|recompute)/transfer/admit/swap events."""
    kinds = ["requeue", "preempt_swap", "preempt_recompute", "chunk",
             "transfer_begin", "admit", "swap_out", "swap_in_done"]
    t = 0.0
    evs = [(0.0, "arrive", {})]
    for draw, gap, dur in rng_draws:
        t += gap
        k = kinds[draw % len(kinds)]
        if k == "chunk":
            evs.append((t, "chunk", {"dur": dur}))
        elif k == "preempt_swap":
            evs.append((t, "preempt", {"entry": {"policy": "swap"}}))
        elif k == "preempt_recompute":
            evs.append((t, "preempt", {"entry": {"policy": "recompute"}}))
        else:
            evs.append((t, k, {}))
    return evs, t


def test_attribution_bit_equal_on_random_schedules():
    """The partition + exact-remainder construction must reproduce the
    observed TTFT bit-for-bit for ANY lifecycle, including overlapping
    chunk spans, mid-span preemptions and swap round trips."""
    @settings(max_examples=50, deadline=None)
    @given(st.lists(st.tuples(st.integers(min_value=0, max_value=7),
                              st.floats(min_value=0.0, max_value=0.3),
                              st.floats(min_value=0.0, max_value=0.5)),
                    min_size=0, max_size=12),
           st.floats(min_value=0.0, max_value=0.4))
    def prop(draws, tail):
        tr = Tracer()
        evs, t_last = _random_lifecycle(draws)
        for t, kind, args in evs:
            dur = args.pop("dur", 0.0)
            tr.record(t, kind, rid=0, dur=dur, **args)
        prefill_done = t_last + tail
        comps = tr.attribution(0, 0.0, prefill_done)
        assert set(comps) == set(ATTRIBUTION_ORDER)
        assert attribution_total(comps) == prefill_done   # bit-equal
        for k in ATTRIBUTION_ORDER:
            if k != "queue_wait":
                assert comps[k] >= 0.0, (k, comps)
        # queue_wait is the exact remainder: may differ from the ideal
        # by float rounding but never by more than a few ULPs' worth
        assert comps["queue_wait"] >= -1e-9 * max(1.0, prefill_done)
    prop()


def test_attribution_components_land_where_expected():
    """A hand-built lifecycle with known intervals attributes exactly."""
    tr = Tracer()
    tr.record(0.0, "arrive", rid=0)
    tr.record(1.0, "plan", rid=0)                  # [0,1] queue_wait
    tr.record(1.0, "chunk", rid=0, dur=2.0)        # [1,3] chunk_compute
    tr.record(4.0, "chunk", rid=0, dur=1.0)        # [3,4] queue, [4,5] chunk
    tr.record(5.0, "transfer_begin", rid=0)        # [5,7] transfer
    tr.record(7.0, "admit", rid=0)                 # [7,8] decode_resident
    tr.record(8.0, "preempt", rid=0,
              entry={"policy": "swap"})            # [8,9] swap_wait
    tr.record(9.0, "swap_in_done", rid=0)          # [9,9.5] decode_resident
    comps = tr.attribution(0, 0.0, 9.5)
    assert comps["chunk_compute"] == 3.0
    assert comps["transfer"] == 2.0
    assert comps["swap_wait"] == 1.0
    assert comps["decode_resident"] == 1.5
    assert comps["preempt_requeue"] == 0.0
    assert attribution_total(comps) == 9.5


# ------------------------------------------------------------ TBT causes
def test_tbt_causes_priority_and_tick_modes():
    tr = Tracer()
    for i, (t, mode) in enumerate([(0.0, "standalone"), (1.0, "fused"),
                                   (2.0, "standalone"), (3.0, "standalone"),
                                   (4.0, "standalone")]):
        tr.record(t, "tick", track=("decode", 0), dur=0.1,
                  rids=(7,), mode=mode)
    # gap 1 covered by a swap span; gap 2 has a recompute preempt; gap 3
    # has a deferral on the emitting track
    tr.record(0.5, "swap", rid=7, dur=0.4)
    tr.record(1.5, "preempt", rid=7, entry={"policy": "recompute"})
    tr.record(2.5, "defer", track=("decode", 0), until=3.0)
    causes = tr.tbt_causes(7)
    assert causes == ["swap", "preempt", "deferral", "standalone"]
    # the fused emission tags its own gap when nothing overrides it
    tr2 = Tracer()
    tr2.record(0.0, "tick", track=("decode", 0), dur=0.1, rids=(1,),
               mode="standalone")
    tr2.record(1.0, "tick", track=("decode", 0), dur=0.1, rids=(1,),
               mode="fused")
    assert tr2.tbt_causes(1) == ["fused"]


# --------------------------------------------------------- chrome export
def test_chrome_export_schema_and_event_count():
    tr = Tracer()
    tr.record(0.0, "arrive", rid=0, track=("request", 0))
    tr.record(0.1, "chunk", rid=0, dur=0.2, track=("prefill", 3), sp=2)
    tr.record(0.5, "tick", track=("decode", 1), dur=0.01, rids=(0,),
              mode="standalone", np_val=np.int64(3))
    tr.metrics.gauge("decode0/batch").set(2, t=0.5)
    out = tr.to_chrome()
    xi = [e for e in out if e["ph"] in ("X", "i")]
    assert len(xi) == len(tr.events)       # count preserved exactly
    for e in out:
        assert e["ph"] in ("M", "X", "i", "C")
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        if e["ph"] == "M":
            assert e["name"] in ("process_name", "thread_name")
        else:
            assert "ts" in e
        if e["ph"] == "X":
            assert e["dur"] > 0
        if e["ph"] == "i":
            assert e["s"] == "t"
    assert sum(1 for e in out if e["ph"] == "C") == 1
    json.dumps(out)                        # payloads are JSON-clean


# ---------------------------------------------- real engine, end to end
class _TwoChunkPolicy(Policy):
    name = "two_chunk_par"

    def plan(self, req, pool, now):
        L = req.prompt_len
        base = (2 * req.rid) % (self.spec.n_prefill - 1)
        if L >= 32:
            l0 = L // 2
            t0 = self.model.latency(1, 0, l0)
            t1 = self.model.latency(2, l0, L - l0)
            return Allocation([Chunk(l0, (base,), 0.0, t0),
                               Chunk(L - l0, (base, base + 1), t0, t0 + t1)])
        t = self.model.latency(1, 0, L)
        return Allocation([Chunk(L, (base,), 0.0, t)])


def _pressure_run(cfg, params):
    """One colocated piggyback run under block pressure with the swap
    preemption policy: exercises chunks, fused AND deferred ticks,
    swap-out/swap-in round trips, transfers and finishes."""
    from repro.serving.engine import ServingEngine
    spec = ClusterSpec(n_prefill=8, n_decode=1, sp_candidates=(1, 2, 4))
    eng = ServingEngine(cfg, params, spec, _TwoChunkPolicy(MODEL, spec),
                        max_batch=4, max_seq=64, block_size=16,
                        decode_hosts={0: tuple(range(8))}, piggyback=True,
                        preempt_watermark=0.3, preempt_policy="swap",
                        prefill_pool_blocks=64)
    rng = np.random.default_rng(1)
    for i, (a, o) in enumerate([(0.0, 24), (0.05, 24), (0.1, 24),
                                (0.15, 24)]):
        eng.submit(Request(rid=i, arrival=a, prompt_len=60, output_len=o),
                   rng.integers(0, cfg.vocab_size, 60))
    out = eng.serve()
    return eng, out


@pytest.fixture(scope="module")
def traced_pressure_run(reduced_params_cache):
    return _pressure_run(*reduced_params_cache("yi-9b"))


def test_engine_run_attribution_bit_equal(traced_pressure_run):
    eng, _ = traced_pressure_run
    assert eng.preempt_log, "pressure run produced no preemption"
    for r in eng.reqs.values():
        comps = eng.tracer.attribution(r.rid, r.arrival, r.prefill_done)
        assert attribution_total(comps) == r.ttft, (r.rid, comps)
        assert comps["chunk_compute"] > 0.0
        causes = eng.tracer.tbt_causes(r.rid)
        assert len(causes) == len(r.token_times) - 1, r.rid


def test_engine_run_tick_conservation(traced_pressure_run):
    """Tracer-side half of the conservation law: tick events reproduce
    the per-instance gauges and Σ output_len exactly."""
    eng, _ = traced_pressure_run
    counts = eng.tracer.tick_token_counts()
    ms = eng.mixed_stats
    assert counts["fused"] == ms["piggyback_tokens"]
    assert counts["standalone"] == ms["standalone_tokens"]
    assert counts["fused"] + counts["standalone"] == sum(
        r.output_len for r in eng.reqs.values())


def test_engine_run_spans_closed_and_well_formed(traced_pressure_run):
    eng, _ = traced_pressure_run
    assert eng.tracer.open_spans() == {}
    # spans on one track never overlap (ticks/chunks are serialized per
    # instance; request-track spans are lifecycle-sequential)
    by_track = {}
    for e in eng.tracer.events:
        if e.dur > 0.0 and e.kind in ("chunk", "tick", "transfer", "swap",
                                      "decode_resident"):
            by_track.setdefault((e.track, e.kind), []).append(
                (e.t, e.t + e.dur))
    eps = 1e-9
    for (track, kind), spans in by_track.items():
        spans.sort()
        for (a0, b0), (a1, b1) in zip(spans, spans[1:]):
            assert a1 >= b0 - eps, (track, kind, (a0, b0), (a1, b1))


def test_engine_run_backcompat_views(traced_pressure_run):
    """The tracer-backed views rebuild the legacy list-of-dict structures
    (same keys, chronological order) the ad-hoc logs used to hold."""
    eng, _ = traced_pressure_run
    pkeys = {"t", "rid", "instance", "reason", "policy", "swap_in_ms",
             "recompute_ms", "resume_tokens", "free_blocks", "generated",
             "chunks_discarded"}
    assert eng.preempt_log
    for p in eng.preempt_log:
        assert set(p) == pkeys, p
    assert [p["t"] for p in eng.preempt_log] == sorted(
        p["t"] for p in eng.preempt_log)
    assert eng.mixed_log
    for m in eng.mixed_log:
        assert set(m) == {"t", "rid", "chunk", "instance", "ticks",
                          "tokens", "window"}, m
    assert eng.restripe_log == []          # single-device: no restripes
    ss = eng.swap_stats
    assert ss["swap_outs"] > 0 and ss["swap_ins"] > 0
    assert ss["bytes_out"] > 0 and ss["swapped_now"] == 0


def test_engine_run_rollups_equal_sum_of_parts(traced_pressure_run):
    """Satellite audit: engine-level rollups == Σ per-instance gauges,
    and the metrics registry mirrors both sides."""
    eng, _ = traced_pressure_run
    ms = eng.mixed_stats
    for key in ("piggyback_ticks", "piggyback_tokens", "standalone_ticks",
                "standalone_tokens", "deferred_ticks"):
        assert ms[key] == sum(getattr(i, key) for i in eng.decodes), key
    assert ms["fused_steps"] == len(eng.mixed_log)
    ss = eng.swap_stats
    assert ss["swap_outs"] == eng.swap.counters["swap_outs"]
    assert ss["bytes_out"] == eng.swap.counters["bytes_out"]
    # PCIe bytes: the per-instance TransferManager counters mirror the
    # swap manager's totals and the registry counters mirror those
    tm_out = sum(d.transfers.stats["swap_out_bytes"] for d in eng.dstates)
    tm_in = sum(d.transfers.stats["swap_in_bytes"] for d in eng.dstates)
    assert tm_out == ss["bytes_out"] and tm_in == ss["bytes_in"]
    reg = eng.metrics.snapshot()["counters"]
    assert sum(v for k, v in reg.items()
               if k.endswith("pcie_out_bytes")) == tm_out
    assert ss["demotions"] == reg.get("host_cache/demotions", 0)
    assert ss["host_prefix_hits"] == reg.get("host_cache/hits", 0)
    # free-block gauges track the pools' final state
    for did, d in enumerate(eng.dstates):
        assert reg is not None
        g = eng.metrics.gauge(f"decode{did}/free_blocks").value
        assert g == d.blocks.n_free
    # single instance: the cluster fabric is dormant and must publish
    # NOTHING — no fabric/* metrics, no fabric keys in swap_stats
    assert not any(k.startswith("fabric/") for k in reg)
    assert "fabric" not in ss and "per_instance" not in ss


@pytest.fixture(scope="module")
def traced_fabric_run(reduced_params_cache):
    """A two-instance run whose swap victim resumes on a non-origin
    instance: instance 0's victim is manually swap-preempted while a
    third request takes its place, so the fabric places the resume on
    the emptied instance 1 (see test_kv_offload for the scenario's
    block arithmetic)."""
    from repro.core.latency_model import HostOffloadModel
    from repro.serving.engine import ServingEngine
    cfg, params = reduced_params_cache("yi-9b")
    rng = np.random.default_rng(31)
    prompts = {i: rng.integers(0, cfg.vocab_size, 64).astype(np.int32)
               for i in range(3)}

    def serve(preempt_at=None):
        spec = ClusterSpec(n_prefill=8, n_decode=2,
                           sp_candidates=(1, 2, 4))
        eng = ServingEngine(cfg, params, spec, _TwoChunkPolicy(MODEL, spec),
                            max_batch=1, max_seq=128, block_size=16,
                            preempt_policy="swap",
                            offload_model=HostOffloadModel(pcie_bw=1e8,
                                                           base=0.0))
        for i, out in enumerate((24, 18, 16)):
            eng.submit(Request(rid=i, arrival=i * 0.005, prompt_len=64,
                               output_len=out), prompts[i])
        if preempt_at is not None:
            eng.preempt(0, at=preempt_at)
        return eng, eng.serve()

    calm, _ = serve()
    tt = calm.reqs[0].token_times
    eng, out = serve(preempt_at=0.5 * (tt[5] + tt[6]))
    return eng, out


def test_fabric_counters_equal_engine_logs(traced_fabric_run):
    """Fabric rollup audit: the fabric/* registry counters, the
    swap_stats['fabric'] rollup, the per-instance breakdown, the tracer's
    swap_place entries and the TransferManagers' interconnect books must
    all agree — one placement story, told four ways."""
    eng, _ = traced_fabric_run
    ss = eng.swap_stats
    fab = ss["fabric"]
    reg = eng.metrics.snapshot()["counters"]
    assert fab["swap_in_placed"] >= 1, "fixture must place a swap-in"
    # registry counters mirror the fabric rollup exactly
    for key in ("swap_in_placed", "swap_in_pinned", "leases_out",
                "leases_recalled", "peer_promotions",
                "interconnect_bytes"):
        assert reg.get(f"fabric/{key}", 0) == fab[key], key
    # the tracer's placement entries ARE the placed count
    assert len(eng.tracer.entries("swap_place")) == fab["swap_in_placed"]
    # every swap-in is either placed or pinned; per-instance sums match
    assert fab["swap_in_placed"] + fab["swap_in_pinned"] == ss["swap_ins"]
    pi = ss["per_instance"]
    assert sum(p["swap_ins"] for p in pi.values()) == ss["swap_ins"]
    assert sum(p["swap_outs"] for p in pi.values()) == ss["swap_outs"]
    assert sum(p["swap_in_placed"]
               for p in pi.values()) == fab["swap_in_placed"]
    # interconnect bytes: Σ per-instance transfer books == fabric rollup
    ic = sum(d.transfers.stats["ic_placed_bytes"]
             + d.transfers.stats["ic_peer_promote_bytes"]
             + d.transfers.stats["ic_lease_bytes"] for d in eng.dstates)
    assert ic == fab["interconnect_bytes"]
    # lease gauge: nothing outstanding at the end of the trace
    assert eng.metrics.gauge("fabric/leases_active").value \
        == eng.fabric.leased_blocks == 0


def test_engine_run_trace_doc_export(tmp_path, traced_pressure_run):
    eng, _ = traced_pressure_run
    path = tmp_path / "trace.json"
    doc = eng.export_trace(str(path))
    assert doc["schema"] == "trace/v1"
    with open(path) as f:
        loaded = json.load(f)
    xi = [e for e in loaded["traceEvents"] if e["ph"] in ("X", "i")]
    assert len(xi) == len(eng.tracer.events)
    for rid, r in eng.reqs.items():
        rec = loaded["requests"][str(rid)]
        comps = rec["attribution"]
        assert attribution_total(comps) == r.ttft, rid
        assert len(rec["tbt_causes"]) == len(r.token_times) - 1
    causes = [c for rec in loaded["requests"].values()
              for c in rec["tbt_causes"]]
    assert "fused" in causes or "deferral" in causes or "swap" in causes


def test_simulator_tracing_off_by_default():
    from repro.serving.simulator import Simulator, make_policy
    from repro.serving.workload import make_trace
    spec = ClusterSpec(n_prefill=4, n_decode=1)
    sim = Simulator(spec, make_policy("tetris", MODEL, spec))
    sim.run(make_trace("short", 0.5, 10.0, seed=0))
    assert sim.tracer.events == []         # off: stress sweeps pay nothing
    spec2 = ClusterSpec(n_prefill=4, n_decode=1)
    sim2 = Simulator(spec2, make_policy("tetris", MODEL, spec2),
                     trace=True)
    sim2.run(make_trace("short", 0.5, 10.0, seed=0))
    assert sim2.tracer.events
    assert sim2.tracer.open_spans() == {}
    for r in sim2.reqs.values():
        if r.prefill_done is None:
            continue
        comps = sim2.tracer.attribution(r.rid, r.arrival, r.prefill_done)
        assert attribution_total(comps) == r.ttft


# -------------------------------------------------- wall-clock spans
def _host_spans(log_dir):
    """``[(start_ns, end_ns, name, stats, thread)]`` of the engine's spans
    in the profiler trace under ``log_dir``, in start order."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    out.append((e.start_ns, e.end_ns, e.name,
                                dict(e.stats), (plane.name, line.name)))
    return sorted(out, key=lambda x: (x[0], -x[1]))


def test_engine_spans_in_a_profiler_trace(tmp_path, reduced_params_cache):
    """Two requests of two chunks each, colocated so that the second
    one's chunks run ticks of the first fused inside them, served inside
    a profiler session: the handlers' spans are in the trace under their
    canonical names, each tick's children nest inside it with exactly one
    sync, a fused tick nests inside its chunk, the chunk spans carry
    their request and length, and a finish's demotion to the host prefix
    cache nests inside its tick's bookkeeping.  The tick after the last row finishes has
    no rows, so it runs no step."""
    from repro.serving.engine import ServingEngine
    cfg, params = reduced_params_cache("yi-9b")
    spec = ClusterSpec(n_prefill=8, n_decode=1, sp_candidates=(1, 2, 4))
    eng = ServingEngine(cfg, params, spec, _TwoChunkPolicy(MODEL, spec),
                        max_batch=4, max_seq=64, block_size=16,
                        decode_hosts={0: tuple(range(8))},
                        prefill_pool_blocks=64)
    rng = np.random.default_rng(2)
    for i, a in enumerate((0.0, 0.13)):
        eng.submit(Request(rid=i, arrival=a, prompt_len=40, output_len=6),
                   rng.integers(0, cfg.vocab_size, 40))
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.serve()
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(str(tmp_path))
    names = {n for _, _, n, _, _ in spans}
    assert names <= set(telemetry.SPANS), names - set(telemetry.SPANS)
    assert {"engine.arrive", "engine.chunk", "engine.chunk.forward",
            "engine.admit", "engine.decode_tick"} <= names
    chunks = [x for x in spans if x[2] == "engine.chunk"]
    assert sorted((x[3]["rid"], x[3]["len"]) for x in chunks) == sorted(
        (rid, c["len"]) for rid, log in eng.chunk_log.items() for c in log)
    assert len(chunks) == 4
    ticks = [x for x in spans if x[2] == "engine.decode_tick"]
    stepped = [x for x in ticks if x[3]["rows"] > 0]
    ms = eng.mixed_stats
    assert len(stepped) == ms["piggyback_ticks"] + ms["standalone_ticks"]
    assert len(stepped) >= 3 and ms["piggyback_ticks"] > 0
    kids = [x for x in spans if x[2].startswith("engine.decode_tick.")]
    for a, b, name, _, thread in kids:
        assert any(t[0] <= a and b <= t[1] and t[4] == thread
                   for t in ticks), name
    for a, b, _, stats, _ in ticks:
        inside = [x[2] for x in kids if a <= x[0] and x[1] <= b]
        parts = ("grow", "inputs", "forward", "sync", "bookkeep")
        if stats["rows"] == 0:
            parts = ("grow", "inputs", "bookkeep")
        assert sorted(inside) == sorted(f"engine.decode_tick.{p}"
                                        for p in parts), inside
        in_chunk = any(c[0] <= a and b <= c[1] for c in chunks)
        assert in_chunk == bool(stats["fused"])
    assert sum(x[3]["fused"] for x in ticks) == ms["piggyback_ticks"]
    # a finish demotes the request's published pages inside the tick
    demotes = [x for x in spans if x[2] == "engine.demote"]
    assert len(demotes) == eng._demote_gathers > 0
    books = [x for x in kids if x[2] == "engine.decode_tick.bookkeep"]
    for a, b, _, stats, _ in demotes:
        assert stats["blocks"] > 0
        assert any(k[0] <= a and b <= k[1] for k in books)


def test_engine_spans_record_nothing_without_a_session(
        monkeypatch, traced_pressure_run, reduced_params_cache):
    """Outside a profiler session the spans leave the engine's own
    records as they were: the same run with every span taken out gives
    the same tokens, Tracer events, log views and registry."""
    from repro.serving import engine as engine_mod
    eng, out = traced_pressure_run
    monkeypatch.setattr(engine_mod, "span",
                        lambda name, **args: contextlib.nullcontext())
    bare, bare_out = _pressure_run(*reduced_params_cache("yi-9b"))

    def events(e):
        return [(x.seq, x.t, x.kind, x.track, x.rid, x.dur,
                 telemetry._jsonable(x.args)) for x in e.tracer.events]
    assert out == bare_out
    assert events(eng) == events(bare)
    assert eng.preempt_log == bare.preempt_log and eng.preempt_log
    assert eng.mixed_log == bare.mixed_log and eng.mixed_log
    snap, bare_snap = eng.metrics.snapshot(), bare.metrics.snapshot()
    assert snap["counters"] == bare_snap["counters"]
    assert snap["gauges"] == bare_snap["gauges"]
    assert set(snap["histograms"]) == set(bare_snap["histograms"])
    assert not any(k.startswith("engine.") for part in snap.values()
                   for k in part)
