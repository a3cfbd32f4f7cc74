"""The trace reduction on small traces with known answers: busy time as
the union of device op intervals inside the window, op and program time
by name, and idle gaps attributed to the innermost host span."""
import pytest

from bench import trace_reduce

# Two chips, one host thread; times in ns. The window is [0, 10000].
XSPACE = '''
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 500000 }
    events { metadata_id: 2 offset_ps: 6000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 9500000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 1000000 duration_ps: 6000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "_pair_kernel" } }
  event_metadata { key: 3 value { id: 3 name: "jit_rollout(7)" } }
}
planes {
  id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
}
planes {
  id: 3 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 2500000 duration_ps: 4000000 }
    events { metadata_id: 3 offset_ps: 3500000 duration_ps: 2000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "service.plan_window" } }
  event_metadata { key: 3 value { id: 3 name: "PjitFunction(rollout)" } }
}
'''


@pytest.fixture(scope="module")
def reduction():
    from jax.profiler import ProfileData
    planes = ProfileData.from_text_proto(XSPACE).planes
    return trace_reduce.reduce_planes(planes, "bench.window")


def test_window_is_the_host_annotation(reduction):
    assert reduction.window_s == pytest.approx(10_000e-9)


def test_busy_is_the_union_of_op_intervals_clipped_to_the_window(
        reduction):
    # chip 0: [1000, 3000] (the nested op adds nothing), [6000, 7000],
    # [9500, 10000] (clipped at the window's end): 3500 ns.
    assert reduction.busy_s["/device:TPU:0"] == pytest.approx(3500e-9)
    assert reduction.busy_s["/device:TPU:1"] == pytest.approx(10_000e-9)
    assert reduction.mean_busy_s == pytest.approx(6750e-9)


def test_op_and_program_time_by_name(reduction):
    assert reduction.op_s["_pair_kernel"] == pytest.approx(1500e-9)
    assert reduction.op_s["fusion.1"] == pytest.approx(
        (2000 + 500 + 10_000) * 1e-9)
    assert reduction.module_s == {"jit_rollout(7)": pytest.approx(6000e-9)}
    assert reduction.top_ops(1)[0][0] == "fusion.1"


def test_idle_gaps_go_to_the_innermost_host_span(reduction):
    # chip 0 idles in [0, 1000], [3000, 6000] and [7000, 9500]; the middle
    # gap's midpoint 4500 lies in plan_window and, inside it, the jit
    # dispatch; the others lie in no span but the window. Chip 1 never
    # idles.
    assert reduction.idle_by_host == {
        "PjitFunction(rollout)": pytest.approx(3000e-9),
        trace_reduce.NO_HOST_SPAN: pytest.approx(3500e-9)}


def test_without_the_window_annotation_the_trace_bounds_the_window():
    from jax.profiler import ProfileData
    planes = ProfileData.from_text_proto(XSPACE).planes
    red = trace_reduce.reduce_planes(planes, window_name=None)
    assert red.window_s == pytest.approx(10_500e-9)
    assert red.busy_s["/device:TPU:0"] == pytest.approx(4000e-9)


def test_a_trace_with_no_device_reads_no_busy_time():
    from jax.profiler import ProfileData
    host_only = XSPACE[XSPACE.index("planes {\n  id: 3"):]
    red = trace_reduce.reduce_planes(
        ProfileData.from_text_proto(host_only).planes, "bench.window")
    assert red.busy_s == {} and red.mean_busy_s == 0.0
    assert red.op_s == {} and red.idle_by_host == {}


# One chip whose trace buffers overflowed at 6000 ns, a long HLO op
# name, and an empty non-chip device plane.
DROPPING = '''
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 5000000 duration_ps: 3000000 } }
  lines { id: 2 name: "XLA TraceMe" timestamp_ns: 0
    events { metadata_id: 2 offset_ps: 6000000 duration_ps: 4000000 } }
  event_metadata { key: 1 value { id: 1
    name: "%while.5 = (u32[]) while(u32[] %tuple.1), condition=%c, body=%b" } }
  event_metadata { key: 2 value { id: 2 name: "Trace Buffers Dropped" } }
}
planes { id: 2 name: "/device:CUSTOM:Megascale Trace" }
planes {
  id: 3 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
}
'''


def test_dropped_buffers_cut_the_window_and_names_are_short():
    from jax.profiler import ProfileData
    red = trace_reduce.reduce_planes(
        ProfileData.from_text_proto(DROPPING).planes, "bench.window")
    assert list(red.busy_s) == ["/device:TPU:0"]
    assert red.clipped and red.window_s == pytest.approx(6000e-9)
    # [1000, 3000] and [5000, 6000] of the op intervals lie in the window.
    assert red.busy_s["/device:TPU:0"] == pytest.approx(3000e-9)
    assert red.op_s == {"%while.5": pytest.approx(3000e-9)}


def test_gaps_under_a_microsecond_are_summed_apart():
    from jax.profiler import ProfileData
    tight = '''
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 2500000 duration_ps: 2000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.3 = f32[8] fusion()" } }
}
'''
    red = trace_reduce.reduce_planes(
        ProfileData.from_text_proto(tight).planes, None)
    assert red.window_s == pytest.approx(4500e-9)
    assert red.busy_s["/device:TPU:0"] == pytest.approx(4000e-9)
    assert red.idle_by_host == {trace_reduce.SHORT_GAPS: pytest.approx(500e-9)}
    assert red.op_s == {"%fusion.3": pytest.approx(4000e-9)}


def test_programs_wholly_inside_the_window_are_listed_in_order(reduction):
    assert reduction.programs == [("jit_rollout(7)", pytest.approx(6000e-9))]
    from jax.profiler import ProfileData
    red = trace_reduce.reduce_planes(
        ProfileData.from_text_proto(DROPPING).planes, "bench.window")
    assert red.programs == []       # the trace has no program line
