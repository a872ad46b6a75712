"""The trace reduction's arithmetic on hand-made cases, its loader on a
hand-made two-device XSpace, and both on a small trace recorded on the
v5e (``data/tiny_v5e.xplane.pb``, written by PR 22's first chip call)."""

from pathlib import Path

import pytest

from benchmark import trace_reduce as tr
from benchmark.trace_reduce import Op

DATA = Path(__file__).resolve().parent / "data"


def op(start, end, name="fusion.1", stack=""):
    return Op(float(start), float(end), name, stack)


def test_merge_and_subtract():
    merged = tr.merge([(5, 7), (0, 2), (1, 3), (7, 8), (4, 4)])
    assert merged == [(0, 3), (5, 8)]
    assert tr.length(merged) == 6
    assert tr.subtract([(0, 10)], merged) == [(3, 5), (8, 10)]
    assert tr.subtract([(0, 3), (5, 8)], [(1, 6)]) == [(0, 1), (6, 8)]
    assert tr.subtract([(0, 3)], []) == [(0, 3)]


def test_busy_is_a_union_not_a_sum():
    # a `while` containing two body ops, then a separate op
    ops = [op(0, 100, "while.1"), op(10, 40), op(50, 90), op(120, 150)]
    assert tr.busy_ns(ops) == 130
    assert tr.idle_gaps(ops, (0, 200)) == [(100, 120), (150, 200)]
    assert tr.self_times(ops) == [30, 30, 40, 30]
    assert [o.start for o in tr.leaves(ops)] == [10, 50, 120]


def test_scope_attribution_and_phase_split():
    ops = [
        op(0, 100, "while.1", "jit(round_fn)/while"),
        op(0, 30, "fusion.3", "jit(round_fn)/while/body/conv_general_dilated"),
        op(30, 50, "fusion.7", "jit(round_fn)/while/body/dopt_update/sub"),
        op(50, 60, "convert.2", "jit(round_fn)/while/body/convert_element_type"),
        op(100, 140, "fusion.9", "jit(round_fn)/dopt_mix/dot_general"),
        op(140, 150, "all-gather.1", "jit(round_fn)/dopt_mix/all_gather"),
    ]
    assert tr.scope_ns(ops, "dopt_update") == 20
    assert tr.scope_ns(ops, "dopt_mix") == 50
    phases = tr.phase_ns(ops)
    # the while's 40 ns of self time and the convert are "other"
    assert phases == {"conv": 30, "comm": 50, "update": 20, "other": 50}
    assert sum(phases.values()) == tr.busy_ns(ops)
    top = dict(tr.top_ops(ops, 3))
    assert top["dopt_mix/dot_general"] == pytest.approx(40e-9)
    assert tr.op_label(op(0, 1, "fusion.123")) == "fusion"


def test_exposed_collective_two_devices():
    """Chip 0 hides half of its all-gather behind a fusion that runs on
    another line of the same chip; chip 1 hides nothing."""
    chip0 = [op(0, 100, "all-gather.1"), op(50, 120, "fusion.2")]
    chip1 = [op(0, 100, "all-gather.1"), op(100, 160, "fusion.2")]
    assert tr.collective_ns(chip0) == (100, 50)
    assert tr.collective_ns(chip1) == (100, 100)
    assert max(tr.collective_ns(c)[1] for c in (chip0, chip1)) == 100
    # a collective nested in a while is still a leaf and still counts
    nested = [op(0, 200, "while.1"), op(10, 60, "all-reduce.4"),
              op(60, 150, "fusion.1")]
    assert tr.collective_ns(nested) == (50, 50)


def test_gaps_go_to_the_innermost_host_span():
    ops = [op(100, 200), op(300, 400)]
    spans = [("bench.window", 0, 500), ("bench.run_call", 0, 250),
             ("host_batch_plan", 10, 90), ("bench.run_call", 250, 500),
             ("round_step", 260, 500)]
    got = dict(tr.gaps_by_host_span(ops, (0, 500), spans))
    # [0,100) -> host_batch_plan; [200,300) mid 250 -> second run_call
    # (round_step opens at 260); [400,500) -> round_step
    assert got == {"host_batch_plan": pytest.approx(100e-9),
                   "bench.run_call": pytest.approx(100e-9),
                   "round_step": pytest.approx(100e-9)}


XSPACE = """
planes {
  name: "/device:TPU:0"
  lines { name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 400000
             stats { metadata_id: 1 int64_value: 7 } }
    events { metadata_id: 2 offset_ps: 600000 duration_ps: 200000 } }
  lines { name: "Async XLA Ops" timestamp_ns: 1000
    events { metadata_id: 4 offset_ps: 500000 duration_ps: 300000 }
    events { metadata_id: 5 offset_ps: 0 duration_ps: 100000 } }
  lines { name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 900000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8]{0:T(128)} fusion(f32[8]{0} %all-gather-done.5), kind=kLoop" } }
  event_metadata { key: 2 value { id: 2 name: "%all-gather-done.5 = f32[8]{0} all-gather-done(%all-gather-start.5)" } }
  event_metadata { key: 3 value { id: 3 name: "jit_f" } }
  event_metadata { key: 4 value { id: 4 name: "%all-gather-start.5 = (f32[2]{0}, f32[8]{0}) all-gather-start(f32[2]{0} %p)" } }
  event_metadata { key: 5 value { id: 5 name: "%copy-start.1 = (f32[2]{0}, f32[2]{0}, u32[]) copy-start(f32[2]{0} %p)" } }
  stat_metadata { key: 1 value { id: 1 name: "device_offset_ps" } }
}
planes {
  name: "/device:TPU:1"
  lines { name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 100000 duration_ps: 800000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
}
planes {
  name: "/host:CPU"
  lines { name: "main" timestamp_ns: 900
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1200000 }
    events { metadata_id: 2 offset_ps: 50000 duration_ps: 500000 }
    events { metadata_id: 3 offset_ps: 60000 duration_ps: 100000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.run_call" } }
  event_metadata { key: 3 value { id: 3 name: "not_ours" } }
}
"""


def test_loader_on_a_hand_made_two_device_xspace():
    from jax.profiler import ProfileData

    profile = ProfileData.from_text_proto(XSPACE)
    hlo = """
  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %all-gather-done.5), kind=kLoop, calls=%fc, metadata={op_name="jit(f)/dopt_update/sub" source_file="x.py" source_line=3}
  ROOT %all-gather-done.5 = f32[8]{0} all-gather-done(%all-gather-start.5), metadata={op_name="jit(f)/dopt_mix/all_gather"}
"""
    stacks = tr.name_stacks_from_hlo(hlo)
    assert stacks == {"fusion.1": "jit(f)/dopt_update/sub",
                      "all-gather-done.5": "jit(f)/dopt_mix/all_gather"}
    red = tr.reduce_profile(profile, ("bench.run_call",), stacks)
    assert red.window == (900.0, 2100.0)
    assert sorted(red.devices) == ["/device:TPU:0", "/device:TPU:1"]
    chip0, chip1 = red.devices["/device:TPU:0"], red.devices["/device:TPU:1"]
    assert [o.name for o in chip0] == ["fusion.1", "all-gather-done.5"]
    # the fusion consumes a collective's result and is no collective; of
    # the async line only the collective is kept, start to done
    assert [tr.is_collective(o) for o in chip0] == [False, True]
    assert [o.name for o in red.async_collectives["/device:TPU:0"]] == [
        "all-gather-start.5"]
    assert tr.busy_ns(chip0) == pytest.approx(600.0)
    assert tr.busy_ns(chip1) == pytest.approx(800.0)
    assert tr.scope_ns(chip0, "dopt_update") == pytest.approx(400.0)
    # done op alone: 200 ns, all exposed; with the async span 500..800:
    # 300 ns, all exposed (the fusion ends at 400)
    assert tr.collective_ns(chip0) == (pytest.approx(200.0),
                                       pytest.approx(200.0))
    assert tr.collective_ns(
        chip0, red.async_collectives["/device:TPU:0"]) == (
            pytest.approx(300.0), pytest.approx(300.0))
    assert {n for n, _, _ in red.host_spans} == {"bench.window",
                                                 "bench.run_call"}
    # idle share, worst chip: 1 - 600/1200
    window = red.window[1] - red.window[0]
    assert max(1 - tr.busy_ns(o) / window
                    for o in red.devices.values()) == pytest.approx(0.5)


def test_a_trace_it_cannot_read_raises():
    from jax.profiler import ProfileData

    no_device = ProfileData.from_text_proto(
        'planes { name: "/host:CPU" lines { name: "main" } }')
    with pytest.raises(ValueError, match="no 'XLA Ops' line"):
        tr.reduce_profile(no_device, ())
    no_window = ProfileData.from_text_proto(XSPACE.replace(
        "bench.window", "something_else"))
    with pytest.raises(ValueError, match="bench.window"):
        tr.reduce_profile(no_window, ())


def test_recorded_v5e_trace():
    """A trace recorded on the v5e (three calls of a jitted conv + scoped
    update + scoped mix inside one ``bench.window`` span) with its
    compiled HLO text: the raw planes carry no name stack, the HLO does."""
    stacks = tr.name_stacks_from_hlo((DATA / "tiny_v5e.hlo.txt").read_text())
    assert stacks["fusion.6"] == "jit(step)/conv_general_dilated"
    red = tr.reduce_file(DATA / "tiny_v5e.xplane.pb", ("bench.run_call",),
                         stacks)
    assert list(red.devices) == ["/device:TPU:0"]
    ops = red.devices["/device:TPU:0"]
    assert len(ops) == 45 and len(ops) % 3 == 0        # 15 ops a call
    assert red.window_s == pytest.approx(3.220131e-3)
    # ops of one TensorCore line do not overlap here: union == sum
    assert tr.busy_ns(ops) == pytest.approx(10548.0)
    assert tr.busy_ns(ops) == pytest.approx(sum(o.end - o.start for o in ops))
    phases = tr.phase_ns(ops)
    assert phases == {"conv": pytest.approx(3568.0),
                      "comm": pytest.approx(2987.0),
                      "update": pytest.approx(8.0),
                      "other": pytest.approx(3985.0)}
    assert tr.scope_ns(ops, "dopt_update") == pytest.approx(8.0)
    assert tr.scope_ns(ops, "dopt_mix") == pytest.approx(2987.0)
    assert tr.collective_ns(ops) == (0, 0)             # one chip: no wire
    assert [s for s, _, _ in red.host_spans].count("bench.run_call") == 3
    # a 10.5 us program in a 3.2 ms window: the chip waits on the host,
    # inside the calls
    idle = 1 - tr.busy_ns(ops) / (red.window[1] - red.window[0])
    assert idle == pytest.approx(0.99672, abs=1e-5)
    gaps = dict(tr.gaps_by_host_span(ops, red.window, red.host_spans))
    assert max(gaps, key=gaps.get) == "bench.run_call"
    assert tr.top_ops(ops, 1)[0][0] == "conv_general_dilated"
