"""The trace reduction on a small recorded trace, against hand counts.

The trace below is one device and one host thread, in nanoseconds:

host:   bench.window [0, 100000); bench.step [10000, 30000);
        bench.poll [30000, 60000); bench.step [60000, 90000)
device: programs jit_decode_greedy [5000, 40000), jit_prefill_slot
        [50000, 80000), jit_decode_greedy [95000, 120000) (runs past the
        window, so it is not counted as a run);
        ops %while.1 [5000, 35000) holding _mm_kernel [5000, 20000) and
        fusion.2 [20000, 35000), then _mm_kernel [50000, 70000), copy.3
        [95000, 110000)

Busy is the union [5000, 35000) + [50000, 70000) + [95000, 100000) =
55000 ns of 100000, so idle is 45%. Op times count the ops that hold
no other op, so the loop's own event is not counted twice. The idle
gaps are [0, 5000) under
the window alone, [35000, 50000) in bench.poll and [70000, 95000) in
bench.step.
"""
import pytest

from harness import tracing

TRACE = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 10 offset_ps: 5000000 duration_ps: 35000000 }
    events { metadata_id: 11 offset_ps: 50000000 duration_ps: 30000000 }
    events { metadata_id: 10 offset_ps: 95000000 duration_ps: 25000000 }
  }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 5000000 duration_ps: 30000000 }
    events { metadata_id: 1 offset_ps: 5000000 duration_ps: 15000000 }
    events { metadata_id: 2 offset_ps: 20000000 duration_ps: 15000000 }
    events { metadata_id: 1 offset_ps: 50000000 duration_ps: 20000000 }
    events { metadata_id: 3 offset_ps: 95000000 duration_ps: 15000000 }
  }
  lines { id: 3 name: "Steps" timestamp_ns: 0
    events { metadata_id: 2 offset_ps: 0 duration_ps: 100000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "_mm_kernel" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.2" } }
  event_metadata { key: 3 value { id: 3 name: "copy.3" } }
  event_metadata { key: 4 value { id: 4 name: "%while.1 = (s32[], bf16[8]) while(%tuple.1)" } }
  event_metadata { key: 10 value { id: 10 name: "jit_decode_greedy(7)" } }
  event_metadata { key: 11 value { id: 11 name: "jit_prefill_slot(8)" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 10000000 duration_ps: 20000000 }
    events { metadata_id: 3 offset_ps: 30000000 duration_ps: 30000000 }
    events { metadata_id: 2 offset_ps: 60000000 duration_ps: 30000000 }
    events { metadata_id: 4 offset_ps: 40000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.step" } }
  event_metadata { key: 3 value { id: 3 name: "bench.poll" } }
  event_metadata { key: 4 value { id: 4 name: "PjitFunction(decode_greedy)" } }
}
"""


@pytest.fixture(scope="module")
def summary():
    from jax.profiler import ProfileData

    return tracing.reduce(tracing.events_from_profile(ProfileData.from_text_proto(TRACE)))


def test_busy_and_idle_share(summary):
    assert summary.window_s == pytest.approx(100e-6)
    assert summary.busy_s == pytest.approx(55e-6)
    assert summary.idle_share == pytest.approx(0.45)
    assert summary.devices == 1


def test_device_time_by_op(summary):
    assert summary.op_s == pytest.approx({"_mm_kernel": 35e-6, "fusion.2": 15e-6, "copy.3": 5e-6})


def test_programs_inside_the_window(summary):
    assert summary.module_time("decode_greedy") == (1, pytest.approx(35e-6))
    assert summary.module_time("prefill_slot") == (1, pytest.approx(30e-6))
    assert summary.op_time_in("decode_greedy", ("_mm_kernel",)) == pytest.approx(15e-6)
    assert summary.op_time_in("prefill_slot", ("_mm_kernel",)) == pytest.approx(20e-6)


def test_idle_gaps_named_by_the_open_span(summary):
    assert sorted(summary.gaps, key=lambda g: -g[1]) == [
        ("bench.step", pytest.approx(25e-6)),
        ("bench.poll", pytest.approx(15e-6)),
        ("bench.window", pytest.approx(5e-6)),
    ]
    b = summary.breakdown(top=2)
    assert [g[0] for g in b["idle_gaps"]] == ["bench.step", "bench.poll"]
    assert b["device_ops"][0] == ["_mm_kernel", pytest.approx(35e-6)]


def test_busy_is_averaged_over_devices():
    ev = tracing.TraceEvents(
        ops={"/device:TPU:0": [(0, 40, "a")], "/device:TPU:1": [(0, 20, "a"), (10, 30, "b")]},
        modules={},
        spans=[(0, 100, "bench.window")],
    )
    s = tracing.reduce(ev)
    assert s.busy_s == pytest.approx(35e-9)
    assert s.idle_share == pytest.approx(0.65)


def test_a_trace_without_the_window_or_device_ops_is_refused():
    with pytest.raises(ValueError):
        tracing.reduce(tracing.TraceEvents({}, {}, [(0, 10, "bench.step")]))
    with pytest.raises(ValueError):
        tracing.reduce(tracing.TraceEvents({}, {}, [(0, 10, "bench.window")]))
