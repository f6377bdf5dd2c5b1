"""The reduction from a profiler trace to busy time, kernel time and idle
gaps, on small traces whose answers are worked out by hand."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from benchlib import tracereduce as tr  # noqa: E402

DEV, DEV1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"


def ev(plane, line, name, s, e):
    return tr.Event(plane, line, name, float(s), float(e))


KERNEL = "%crossings_candidates.3 = s32[64,1]{1,0} custom-call(s32[64])"


def toy():
    """Window 100..1100 ns.  Device ops: fusion.1 at 50..160 (clipped to
    100..160) and 1000..1200 (clipped to 1000..1100); while.5 at 400..700
    with the kernel nested at 450..620.  Busy union: 60 + 300 + 100 =
    460 ns.  Self times: fusion.1 160, the kernel 170, while.5 300 - 170
    = 130.  Idle gaps: 160..400 (midpoint inside bench/fetch) and
    700..1000 (inside bench/put, itself inside bench/assign: the
    innermost names it)."""
    return [
        ev(HOST, "python", "bench/window", 100, 1100),
        ev(HOST, "python", "bench/fetch", 150, 450),
        ev(HOST, "python", "bench/assign", 550, 1050),
        ev(HOST, "python", "bench/put", 700, 900),
        ev(HOST, "python", "not-a-label", 610, 990),
        ev(DEV, "XLA Ops", "%fusion.1 = f32[8]{0} fusion(f32[8])", 50, 160),
        ev(DEV, "XLA Ops", "%while.5 = (s32[]) while((s32[]) %t)", 400, 700),
        ev(DEV, "XLA Ops", KERNEL, 450, 620),
        ev(DEV, "XLA Ops", "%fusion.1 = f32[8]{0} fusion(f32[8])", 1000,
           1200),
        ev(DEV, "XLA Modules", "jit_assign", 0, 2000),   # not an op line
    ]


def test_busy_is_the_union_of_ops_inside_the_window():
    s = tr.summarize(toy())
    assert s.window_s == pytest.approx(1000e-9)
    assert s.busy_s == pytest.approx(460e-9)
    assert s.n_devices == 1


def test_kernel_time_matches_the_name():
    s = tr.summarize(toy())
    assert s.kernel_s("crossings_candidates") == pytest.approx(170e-9)
    assert s.kernel_s("absent", "crossings_candidates") == \
        pytest.approx(170e-9)
    assert s.kernel_s("fusion") == pytest.approx(160e-9)
    assert s.kernel_s("absent") == 0.0


def test_op_time_is_self_time_by_short_name():
    s = tr.summarize(toy())
    assert s.op_s == {"fusion.1": pytest.approx(160e-9),
                      "while.5": pytest.approx(130e-9),
                      "crossings_candidates.3": pytest.approx(170e-9)}
    b = tr.breakdown(s)
    assert [k for k, _ in b["device_ops"]] == [
        "crossings_candidates.3", "fusion.1", "while.5"]


def test_gaps_are_named_by_the_innermost_host_range():
    s = tr.summarize(toy())
    assert s.gaps == [("bench/fetch", pytest.approx(240e-9)),
                      ("bench/put", pytest.approx(300e-9))]
    b = tr.breakdown(s)
    assert b["idle_gaps"][0][0] == "bench/put"


def test_dropped_buffers_inside_the_window_are_refused():
    events = toy() + [ev(DEV, "XLA TraceMe", tr.DROPPED, 800, 5000)]
    with pytest.raises(ValueError, match="dropped"):
        tr.summarize(events)
    late = toy() + [ev(DEV, "XLA TraceMe", tr.DROPPED, 1200, 5000)]
    assert tr.summarize(late).busy_s == pytest.approx(460e-9)


def test_a_gap_under_no_host_range_is_named_so():
    events = [ev(HOST, "python", "bench/window", 0, 100),
              ev(DEV, "XLA Ops", "a", 0, 40)]
    s = tr.summarize(events)
    assert s.gaps == [(tr.NO_LABEL, pytest.approx(60e-9))]


def test_busy_is_averaged_over_the_devices_used():
    events = toy() + [ev(DEV1, "XLA Ops", "%fusion.9 = f32[8] fusion()",
                         100, 300)]
    s = tr.summarize(events)
    assert s.n_devices == 2
    assert s.busy_s == pytest.approx((460e-9 + 200e-9) / 2)


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        tr.summarize([ev(DEV, "XLA Ops", "a", 0, 1)])


def test_union_merges_touching_and_drops_empty():
    assert tr.union([(5, 7), (0, 2), (2, 3), (4, 4)]) == [(0, 3), (5, 7)]


XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 100000 duration_ps: 300000 }
    events { metadata_id: 2 offset_ps: 600000 duration_ps: 100000 }
  }
  event_metadata { key: 1 value { id: 1
    name: "%crossings_candidates.1 = s32[8,1] custom-call()" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.7" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 2 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 400000 duration_ps: 200000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench/window" } }
  event_metadata { key: 2 value { id: 2 name: "bench/fetch" } }
}
"""


def test_an_xspace_reads_through_the_profiler_api():
    from jax.profiler import ProfileData
    prof = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(XSPACE))
    s = tr.summarize(tr.events_of(prof))
    # Window 1000..2000 ns; ops 1100..1400 and 1600..1700.
    assert s.window_s == pytest.approx(1000e-9)
    assert s.busy_s == pytest.approx(400e-9)
    assert s.kernel_s("crossings_candidates") == pytest.approx(300e-9)
    assert [g[0] for g in s.gaps] == [tr.NO_LABEL, "bench/fetch",
                                      tr.NO_LABEL]
