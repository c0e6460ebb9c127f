"""The trace reduction on a small recorded trace (an XSpace written out as
text): busy and idle time inside the window, device time per op, and idle
gaps attributed to the harness span that overlaps them."""
import pytest
from jax.profiler import ProfileData

import trace as bench_trace

# times in ns; the window span runs 1,000..11,000
XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 500000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 3000000 }
    events { metadata_id: 1 offset_ps: 4000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 9000000 duration_ps: 4000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 0 duration_ps: 20000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "%forest_kernel.1 = f32[8]{0} custom-call(f32[8]{0} %fusion.1)" } }
  event_metadata { key: 3 value { id: 3 name: "jit_run" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 1500000 }
    events { metadata_id: 3 offset_ps: 2500000 duration_ps: 5000000 }
    events { metadata_id: 4 offset_ps: 7500000 duration_ps: 1000000 }
    events { metadata_id: 5 offset_ps: 8500000 duration_ps: 100000 }
  }
  event_metadata { key: 1 value { id: 1 name: "window" } }
  event_metadata { key: 2 value { id: 2 name: "submit" } }
  event_metadata { key: 3 value { id: 3 name: "step" } }
  event_metadata { key: 4 value { id: 4 name: "collect" } }
  event_metadata { key: 5 value { id: 5 name: "other" } }
}
"""


def test_reduce_recorded_trace():
    profile = ProfileData.from_text_proto(XSPACE)
    ops, spans, other = bench_trace.events(profile, {"window", "submit",
                                                     "step", "collect"})
    assert len(ops) == 4 and len(spans) == 4  # "other" is not ours
    assert other == [("other", 8_500, 8_600)]
    out = bench_trace.reduce(ops, spans, n_devices=1, other=other)
    # busy inside 1,000..11,000: 1,000..1,500 + 2,000..6,000 + 9,000..11,000
    assert out["window_s"] == pytest.approx(10_000e-9)
    assert out["busy_s"] == pytest.approx(6_500e-9)
    assert out["ops"] == pytest.approx({"fusion.1": 2_500e-9,
                                        "forest_kernel.1": 5_000e-9})
    # gaps 1,500..2,000 (submit), 6,000..9,000 (step 2,500..7,500 overlaps
    # 1,500, collect 7,500..8,500 overlaps 1,000: step); both are shorter
    # than a millisecond, so no host event names them
    assert out["idle_gaps"] == pytest.approx({"submit": 500e-9,
                                              "step": 3_000e-9})
    assert bench_trace.top(out["ops"], 1) == [["forest_kernel.1",
                                               pytest.approx(5e-6)]]


def test_long_gap_is_named_after_the_host_event_covering_it():
    ms = 1_000_000
    ops = [(0, "fusion.1", 0, 1 * ms), (0, "fusion.1", 5 * ms, 6 * ms)]
    spans = [("window", 0, 6 * ms), ("step", 0, 6 * ms)]
    other = [("Thread", 0, 6 * ms),  # covers the gap, but is not the shortest
             ("BlockHostUntilReady#id=3#", 1 * ms, 4 * ms),
             ("short", 2 * ms, 2 * ms + 10)]  # covers too little of it
    out = bench_trace.reduce(ops, spans, n_devices=1, other=other)
    assert out["idle_gaps"] == pytest.approx(
        {"step>BlockHostUntilReady": 4 * ms * 1e-9})


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        bench_trace.reduce([(0, "op", 0, 10)], [("step", 0, 10)], 1)
