"""The trace reduction on a small recorded trace with known answers."""

import pytest

from benchlib import trace

# One device and the host thread, times in ns from 1000.  The window runs
# 1000..21000 (20 us).  Device ops: flash 3000-6000 and 5000-7000
# (overlapping: 4 us busy), a fusion 10000-11000, a loop 14000-18000 whose
# body ran a decode kernel 15000-17000, both inside a module 14000-18000,
# the flash ops inside a module 2500-7500, and an op 22000-23000 after the
# window.  Names are HLO instructions, as the chip's trace gives them.
TRACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 2000000 duration_ps: 3000000
             stats { metadata_id: 9 str_value: "jit(f)/flash" } }
    events { metadata_id: 1 offset_ps: 4000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 9000000 duration_ps: 1000000 }
    events { metadata_id: 5 offset_ps: 13000000 duration_ps: 4000000 }
    events { metadata_id: 3 offset_ps: 14000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 21000000 duration_ps: 1000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 4 offset_ps: 1500000 duration_ps: 5000000 }
    events { metadata_id: 4 offset_ps: 13000000 duration_ps: 4000000 }
  }
  event_metadata { key: 1 value { id: 1 name:
    "%flash_attention.7 = (bf16[1,8960,4096]) custom-call(%a, %b)" } }
  event_metadata { key: 2 value { id: 2 name:
    "%fusion.110 = bf16[8,11008] fusion(%c)" } }
  event_metadata { key: 3 value { id: 3 name:
    "%paged_flash_decode.10 = (bf16[8,32,128]) custom-call(%d)" } }
  event_metadata { key: 4 value { id: 4 name:
    "jit__stack_forward(4831229574108056660)" } }
  event_metadata { key: 5 value { id: 5 name:
    "%while.6 = (s32[], bf16[8,1,4096]) while(%e)" } }
  stat_metadata { key: 9 value { id: 9 name: "tf_op" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 6000000 }
    events { metadata_id: 3 offset_ps: 6000000 duration_ps: 4000000 }
    events { metadata_id: 4 offset_ps: 11000000 duration_ps: 7000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench_window" } }
  event_metadata { key: 2 value { id: 2 name: "handler.chunk_start" } }
  event_metadata { key: 3 value { id: 3 name: "gate_wait" } }
  event_metadata { key: 4 value { id: 4 name: "handler.decode_tick" } }
}
"""


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData
    return trace.reduce(ProfileData.from_text_proto(TRACE))


@pytest.mark.parametrize("name,base", [
    ("%paged_flash_decode.10 = (bf16[8,32,128]) custom-call(%d)",
     "paged_flash_decode"),
    ("%fusion = bf16[8] fusion(%c)", "fusion"),
    ("%bitcast_dynamic-update-slice_fusion.4 = bf16[20,2049] fusion(%x)",
     "bitcast_dynamic-update-slice_fusion"),
    ("jit__stack_forward(123)", "jit__stack_forward(123)"),
])
def test_base_name(name, base):
    assert trace.base_name(name) == base


def test_window_and_busy(reduced):
    assert reduced["window_s"] == pytest.approx(20e-6)
    # 3000-7000, 10000-11000 and the loop 14000-18000: 9 us; the op after
    # the window does not count
    assert reduced["busy_s"] == pytest.approx(9e-6)
    assert reduced["devices"] == 1


def test_kernels_by_name_and_stat(reduced):
    k = reduced["kernels"]
    assert k["flash_attention"] == {"seconds": pytest.approx(5e-6),
                                    "count": 2}
    assert k["paged_flash_decode"] == {"seconds": pytest.approx(2e-6),
                                       "count": 1}
    assert k["paged_flash_prefill"]["count"] == 0


def test_step_programs(reduced):
    # the module holding the flash ops (2500-7500) and the one holding
    # the decode kernel (14000-18000)
    assert reduced["step_seconds"]["prefill"] == pytest.approx(5e-6)
    assert reduced["step_seconds"]["decode"] == pytest.approx(4e-6)


def test_idle_gaps_named_by_host(reduced):
    gaps = dict(reduced["idle_gaps"])
    # 1000-3000 in chunk_start, 7000-10000 mid 8500 in gate_wait,
    # 11000-14000 mid 12500 in decode_tick, 18000-21000 mid 19500 in none
    assert gaps["handler.chunk_start"] == pytest.approx(2e-6)
    assert gaps["gate_wait"] == pytest.approx(3e-6)
    assert gaps["handler.decode_tick"] == pytest.approx(3e-6)
    assert gaps["host_other"] == pytest.approx(3e-6)
    assert sum(gaps.values()) + reduced["busy_s"] == \
        pytest.approx(reduced["window_s"])


def test_top_device_ops(reduced):
    ops = dict(reduced["device_ops"])
    assert ops == pytest.approx({"flash_attention": 5e-6, "fusion": 1e-6,
                                 "paged_flash_decode": 2e-6})
