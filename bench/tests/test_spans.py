"""The program-span reduction on a small recorded trace with known answers."""

import pytest

from benchlib import spans

# One device and the host thread; line timestamps 0, so an event's offset
# in ps is its time in ns times 1000.  The window runs 1000..21000 ns.
# Device ops 4000-6000 and 12000-15000 are busy in it (5 us), the op at
# 22000-23000 lies after it: idle [1000,4000) [6000,12000) [15000,21000).
# Program spans: an arrive 500-1500 that starts before the window; a tick
# 2000-9000 with grow 2000-3000, inputs 3000-5000, forward 5000-7000, sync
# 7000-8500 and bookkeep 8600-9000 (8500-8600 is the tick's own); a tick
# 10000-16000 with forward 10000-13000 and sync 13000-16000; a chunk
# 21500-22500 after the window.  The harness's handler span, which wraps
# the first tick, is no program span.
HOST = [
    ("bench_window", 1000, 21000),
    ("handler.decode_tick", 1500, 9500),
    ("engine.arrive", 500, 1500),
    ("engine.decode_tick", 2000, 9000),
    ("engine.decode_tick.grow", 2000, 3000),
    ("engine.decode_tick.inputs", 3000, 5000),
    ("engine.decode_tick.forward", 5000, 7000),
    ("engine.decode_tick.sync", 7000, 8500),
    ("engine.decode_tick.bookkeep", 8600, 9000),
    ("engine.decode_tick", 10000, 16000),
    ("engine.decode_tick.forward", 10000, 13000),
    ("engine.decode_tick.sync", 13000, 16000),
    ("engine.chunk", 21500, 22500),
]
DEVICE = [(4000, 6000), (12000, 15000), (22000, 23000)]


def _proto() -> str:
    names = sorted({n for n, _, _ in HOST})
    mid = {n: i + 1 for i, n in enumerate(names)}
    host = "\n".join(
        f"    events {{ metadata_id: {mid[n]} offset_ps: {a * 1000} "
        f"duration_ps: {(b - a) * 1000} }}" for n, a, b in HOST)
    meta = "\n".join(f'  event_metadata {{ key: {mid[n]} value {{ id: '
                     f'{mid[n]} name: "{n}" }} }}' for n in names)
    dev = "\n".join(f"    events {{ metadata_id: 1 offset_ps: {a * 1000} "
                    f"duration_ps: {(b - a) * 1000} }}" for a, b in DEVICE)
    return f"""
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
{dev}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name:
    "%fusion.3 = bf16[8,4096] fusion(%a)" }} }}
}}
planes {{
  id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python3" timestamp_ns: 0
{host}
  }}
{meta}
}}
"""


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData
    return spans.reduce(ProfileData.from_text_proto(_proto()))


def test_idle_splits_to_the_innermost_span(reduced):
    s = reduced["spans"]
    us = pytest.approx
    assert s["engine.arrive"]["idle_self_s"] == us(0.5e-6)
    assert s["engine.decode_tick.grow"]["idle_self_s"] == us(1e-6)
    assert s["engine.decode_tick.inputs"]["idle_self_s"] == us(1e-6)
    # 6000-7000 in the first tick, 10000-12000 in the second
    assert s["engine.decode_tick.forward"]["idle_self_s"] == us(3e-6)
    # 7000-8500, and 15000-16000 in the second tick
    assert s["engine.decode_tick.sync"]["idle_self_s"] == us(2.5e-6)
    assert s["engine.decode_tick.bookkeep"]["idle_self_s"] == us(0.4e-6)
    assert s["engine.decode_tick"]["idle_self_s"] == us(0.1e-6)
    # 1500-2000, 9000-10000 and 16000-21000
    assert reduced["outside_s"] == us(6.5e-6)
    assert s["engine.decode_tick"]["idle_s"] == us(8e-6)


def test_pieces_sum_to_the_idle_window(reduced):
    assert reduced["window_s"] == pytest.approx(20e-6)
    assert reduced["idle_s"] == pytest.approx(15e-6)
    pieces = reduced["outside_s"] + sum(
        s["idle_self_s"] for s in reduced["spans"].values())
    assert pieces == pytest.approx(reduced["idle_s"], rel=1e-12)


def test_counts_seconds_and_self(reduced):
    s = reduced["spans"]
    assert s["engine.decode_tick"]["count"] == 2
    assert s["engine.decode_tick"]["seconds"] == pytest.approx(13e-6)
    assert s["engine.decode_tick"]["self_s"] == pytest.approx(0.1e-6)
    assert s["engine.decode_tick.forward"]["count"] == 2
    assert s["engine.decode_tick.forward"]["self_s"] == pytest.approx(5e-6)
    assert s["engine.decode_tick.sync"]["seconds"] == pytest.approx(4.5e-6)


def test_outside_the_window_is_dropped(reduced):
    s = reduced["spans"]
    assert "engine.chunk" not in s
    assert "handler.decode_tick" not in s
    # the arrive is clipped at the window's start
    assert s["engine.arrive"]["count"] == 1
    assert s["engine.arrive"]["seconds"] == pytest.approx(0.5e-6)


def test_tick_idle_ms(reduced):
    assert spans.tick_idle_ms(reduced) == pytest.approx(4e-3)
    assert spans.tick_idle_ms({"spans": {}}) is None


def test_a_tiny_traced_run_reports_the_engine_spans():
    """A whole traced run of the tiny decode cell on the CPU: the window's
    decode ticks are ``engine.decode_tick`` spans, each with its five
    phases.  The CPU has no device line, so no idle time is split."""
    import jax

    import program_spans
    import tiny
    bench = tiny.load_run()
    bench.peaks = lambda kind: {"flops": 1.0, "hbm_bytes_per_s": 1.0}
    out = program_spans.traced_run(
        tiny.spec("decode"), 2**33 + 11, 1.0, jax, jax.devices()[:1],
        log=lambda *a: None, bench=bench)
    s = out["info"]["program_spans"]["spans"]
    n = s[spans.TICK]["count"]
    assert n > 0 and out["correct"]
    for part in ("grow", "inputs", "forward", "sync", "bookkeep"):
        assert s[f"{spans.TICK}.{part}"]["count"] == n
    assert out["info"]["program_spans"]["idle_s"] == 0.0
    assert out["info"]["tick_idle_ms"] == 0.0
