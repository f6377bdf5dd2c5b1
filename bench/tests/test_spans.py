"""The program's phases on the profiler's clock (``benchlib/spans.py``):
scope matching, scope shares by self time, and the split of the device's
idle time by what the replica threads were doing, on small traces whose
answers are worked out by hand."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from benchlib import spans  # noqa: E402
from benchlib import tracereduce as tr  # noqa: E402

DEV, HOST = "/device:TPU:0", "/host:CPU"
REPLICA, FLUSHER, CLIENT = "python 23", "python 12", "python 1"


def ev(plane, line, name, s, e):
    return tr.Event(plane, line, name, float(s), float(e))


@pytest.mark.parametrize("op_name,scope", [
    ("jit(assign_fast)/geo/locate/add", "geo/locate"),
    ("jit(assign_fast)/geo/pip_phase2/jit(_crossings_split)/while",
     "geo/pip_phase2"),
    ("jit(assign_fast)/geo/locate_more/add", "geo/locate_more"),
    ("jit(assign_fast)/nogeo/locate/add", None),
    ("jit(assign_fast)/geo", None),
    ("reduce_window_sum", None),
    ("", None),
])
def test_a_scope_is_matched_by_whole_name_stack_components(op_name, scope):
    assert spans.scope_of(op_name) == scope


HLO = """HloModule jit_assign_fast, is_scheduled=true

%body.1 (p: (s32[], s32[8])) -> (s32[], s32[8]) {
  %p = (s32[], s32[8]) parameter(0)
  %dynamic-slice.14 = s32[1] dynamic-slice(s32[8] %x, s32[] %i)
  ROOT %tuple.2 = (s32[], s32[8]) tuple(%i, %x)
}

%fused_computation.5 (param_0: s32[8]) -> s32[8] {
  %param_0 = s32[8] parameter(0)
  ROOT %add.3 = s32[8] add(%param_0, %param_0), metadata={op_name="add"}
}

ENTRY %main.9 (a: s32[8]) -> s32[8] {
  %a = s32[8] parameter(0)
  %fusion.5 = s32[8] fusion(%a), kind=kLoop, calls=%fused_computation.5, metadata={op_name="jit(assign_fast)/geo/locate/add"}
  %while.20 = (s32[], s32[8]) while(%t), condition=%cond.1, body=%body.1, metadata={op_name="jit(assign_fast)/geo/phase2_gather/gather"}
  ROOT %copy.1 = s32[8] copy(%fusion.5)
}
"""


def test_an_instruction_without_a_scope_takes_its_callers():
    scopes = spans.hlo_scopes(HLO)
    assert scopes["fusion.5"] == "geo/locate"
    assert scopes["add.3"] == "geo/locate"         # inside the fusion
    assert scopes["while.20"] == "geo/phase2_gather"
    assert scopes["dynamic-slice.14"] == "geo/phase2_gather"  # loop body
    assert scopes["copy.1"] is None and scopes["a"] is None


WHILE = "%while.20 = (s32[]) while((s32[]) %t)"
SLICE = "%dynamic-slice.14 = s32[1,7] dynamic-slice(s32[8] %x)"
FUSION = "%fusion.5 = s32[8] fusion(s32[8] %a)"
COPY = "%copy.1 = s32[8] copy(s32[8] %f)"
SCOPES = {"while.20": "geo/phase2_gather", "dynamic-slice.14":
          "geo/phase2_gather", "fusion.5": "geo/locate", "copy.1": None}


def batch_trace():
    """Window 100..1100 ns.  fusion.5 at 50..300 (clipped 100..300: 200),
    while.20 at 400..800 with the slice nested at 500..700, copy.1 at
    900..950.  Busy 200 + 400 + 50 = 650; self times: locate 200, the
    gather 200 + 200 = 400, no scope 50."""
    return [
        ev(HOST, CLIENT, tr.WINDOW, 100, 1100),
        ev(DEV, "XLA Ops", FUSION, 50, 300),
        ev(DEV, "XLA Ops", WHILE, 400, 800),
        ev(DEV, "XLA Ops", SLICE, 500, 700),
        ev(DEV, "XLA Ops", COPY, 900, 950),
        ev(DEV, "XLA Modules", "jit_assign_fast(1)", 0, 2000),
    ]


def test_scope_time_is_self_time_with_nested_ops_in_their_scope():
    acc = spans.scope_seconds(batch_trace(), SCOPES)
    assert acc["busy"] == pytest.approx(650e-9)
    assert acc["geo/locate"] == pytest.approx(200e-9)
    assert acc["geo/phase2_gather"] == pytest.approx(400e-9)
    assert acc[None] == pytest.approx(50e-9)
    assert sum(v for k, v in acc.items() if k != "busy") == \
        pytest.approx(acc["busy"])


def served_trace():
    """Window 0..1000 ns.  Device ops at 200..300 and 500..550: busy 150,
    idle 0..200, 300..500, 550..1000 (850).  The replica holds
    complete_batch 100..600 with device_stage 150..400 inside it, then
    cache_gauges 600..650; the flusher's host_prepare and a device_stage
    on a line without complete_batch belong to no replica.

    In a device stage: 150..200 + 300..400 = 150.  In the replica's other
    ranges: 100..150 + 400..500 + 550..650 = 250, as the idle part of the
    union of all its ranges (100..200, 300..500, 550..650: 400) less the
    150.  The rest, 850 - 400 = 450, with no replica range open."""
    return [
        ev(HOST, CLIENT, tr.WINDOW, 0, 1000),
        ev(HOST, REPLICA, "geo/complete_batch", 100, 600),
        ev(HOST, REPLICA, "geo/device_stage", 150, 400),
        ev(HOST, REPLICA, "geo/pull", 250, 400),
        ev(HOST, REPLICA, "geo/cache_gauges", 600, 650),
        ev(HOST, FLUSHER, "geo/host_prepare", 650, 1000),
        ev(HOST, FLUSHER, "geo/device_stage", 700, 900),
        ev(DEV, "XLA Ops", "%fusion.1 = f32[8] fusion()", 200, 300),
        ev(DEV, "XLA Ops", "%fusion.2 = f32[8] fusion()", 500, 550),
    ]


def test_idle_time_is_split_by_overlap_in_priority_order():
    split = spans.idle_split(served_trace())
    assert split == {"device_stage": pytest.approx(15.0),
                     "after_device": pytest.approx(25.0),
                     "replica_wait": pytest.approx(45.0)}


def test_a_gap_is_split_by_overlap_not_named_by_its_midpoint():
    """One gap, 550..1000, with its midpoint (775) under no replica range:
    the 100 ns of it that complete_batch and cache_gauges cover count as
    after_device, the rest as replica_wait."""
    events = [ev(HOST, CLIENT, tr.WINDOW, 0, 1000),
              ev(HOST, REPLICA, "geo/complete_batch", 500, 600),
              ev(HOST, REPLICA, "geo/cache_gauges", 600, 650),
              ev(DEV, "XLA Ops", "%fusion.1 = f32[8] fusion()", 0, 550)]
    assert spans.idle_split(events) == {
        "device_stage": pytest.approx(0.0),
        "after_device": pytest.approx(10.0),
        "replica_wait": pytest.approx(35.0)}


def test_the_three_parts_add_up_to_device_idle():
    events = served_trace()
    summary = tr.summarize(events)
    idle = 100.0 * (1.0 - summary.busy_s / summary.window_s)
    assert sum(spans.idle_split(events).values()) == pytest.approx(idle)


def test_without_replica_ranges_or_scoped_ops_there_is_no_reading(
        monkeypatch):
    no_replica = [e for e in served_trace()
                  if e.name != "geo/complete_batch"]
    assert spans.idle_split(no_replica) is None
    ctx = {"trace": object(), "cell": object()}
    monkeypatch.setattr(spans, "trace_events", lambda ctx: no_replica)
    assert spans.idle_share(ctx, "device_stage") is None
    monkeypatch.setattr(spans, "trace_events", lambda ctx: batch_trace())
    monkeypatch.setattr(spans, "compiled_scopes", lambda ctx: {})
    assert spans.scope_share(ctx, "geo/locate") is None
    monkeypatch.setattr(spans, "compiled_scopes", lambda ctx: SCOPES)
    assert spans.scope_share(ctx, "geo/locate") == \
        pytest.approx(100.0 * 200 / 650)
    assert spans.scope_share(ctx, "geo/compact") is None
    host_only = [e for e in batch_trace() if e.plane == HOST]
    monkeypatch.setattr(spans, "trace_events", lambda ctx: host_only)
    assert spans.scope_share(ctx, "geo/locate") is None
    monkeypatch.undo()                     # an untraced run has no trace
    assert spans.scope_share({"trace": None}, "geo/locate") is None
    assert spans.idle_share({"trace": None}, "device_stage") is None


XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 200000 duration_ps: 100000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8] fusion()" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 2 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 700000 duration_ps: 200000 }
  }
  lines { id: 3 name: "python" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 100000 duration_ps: 500000 }
    events { metadata_id: 3 offset_ps: 150000 duration_ps: 250000 }
    events { metadata_id: 4 offset_ps: 600000 duration_ps: 10000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench/window" } }
  event_metadata { key: 2 value { id: 2 name: "geo/complete_batch" } }
  event_metadata { key: 3 value { id: 3 name: "geo/device_stage" } }
  event_metadata { key: 4 value { id: 4 name: "not-a-range" } }
}
"""


def test_an_xspace_reads_through_the_profiler_api():
    from jax.profiler import ProfileData
    prof = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(XSPACE))
    events = spans.events_of(prof)
    assert sorted(e.name for e in events) == sorted([
        "%fusion.1 = f32[8] fusion()", tr.WINDOW, "geo/complete_batch",
        "geo/device_stage", "geo/device_stage"])
    # Window 1000..2000 ns, busy 1200..1300; device stage 1150..1400
    # (idle in it: 50 + 100), complete_batch 1100..1600 (idle in it:
    # 100 + 300), idle 900 in all.  The threads share a name, and the
    # device stage at 1700..1900 is on the one without complete_batch:
    # not a replica's.
    split = spans.idle_split(events)
    assert split == {"device_stage": pytest.approx(15.0),
                     "after_device": pytest.approx(25.0),
                     "replica_wait": pytest.approx(50.0)}
