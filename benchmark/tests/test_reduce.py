"""The trace reduction against hand-computed answers.

`data/resnet50-train-sync.xplane.pb` is a recording from the chip (TPU v5
lite, resnet50-train, PR 22's first chip call), cut down to device 0's
events between 0.508136 s and 0.513553 s of the trace's clock: the last
nine operations of one step, the 5.4 ms in which the host read the loss
and dispatched the next step, and the first 21 operations of that step.
What it holds, in microseconds:

    copy x5                0.360 0.851 0.853 0.852 0.232   = 3.148
    copy-done x4           0.003 0.002 0.002 0.002         = 0.009
      last operation ends            508139.826
      program (XLA Modules) ends     508140.906
      bench/loss_read ends           509610.037
      bench/dispatch starts          509622.157
      next program starts            513543.585
      its first operation starts     513549.811
    async-start x9 (slice) 0.006 each                      = 0.054
    copy-start x7          0.006 each                      = 0.042
    iota                   0.006
    loop fusion x3         0.711 0.009 0.242               = 0.962
    convert                0.865
                                                 busy     = 5.086

The window is 5417 us long, so 5411.914 us are idle: 5402.679 between the
two programs, of which bench/loss_read covers 1469.131, bench/dispatch
3921.428 and no span 12.120, and the other 9.235 inside the programs.
"""

import os

import pytest

from benchmark.reduce import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
US = 1e-6
WINDOW = (0.508136, 0.513553)


def load(name):
    from jax.profiler import ProfileData

    return xplane.from_profile(
        ProfileData.from_file(os.path.join(DATA, name)))


def from_text(text):
    from jax.profiler import ProfileData

    return xplane.from_profile(ProfileData.from_text_proto(text))


@pytest.mark.parametrize("text,expected", [
    ("%fusion.1020 = (s32[128,1,1]{0,2,1:T(1,128)S(1)}, s32[128]{0:T(128)}) "
     "fusion(s32[128,1]{0,1:T(1,128)} %ro_ins__label__.1), kind=kLoop, "
     "calls=%fused_computation.2089", ("fusion.1020", "loop fusion")),
    ("%convert_reduce_fusion.3 = (f32[256]{0:T(256)S(1)}, "
     "bf16[128,256,56,56]{1,0,3,2:T(8,128)(2,1)}) fusion(bf16[256,64,1,1]"
     "{0,3,2,1:T(2,128)(2,1)S(1)} %copy-done.154), kind=kOutput, "
     "calls=%fused_computation.11",
     ("convert_reduce_fusion.3", "output fusion")),
    ("%copy-done.132 = f32[128,128,3,3]{1,0,3,2:T(8,128)S(1)} copy-done("
     "(f32[128,128,3,3]{1,0,3,2:T(8,128)S(1)}, u32[]{:S(2)}) "
     "%copy-start.132)", ("copy-done.132", "copy-done")),
    ("%flash_attention_fwd.7 = (bf16[16,512,64]{2,1,0:T(8,128)(2,1)S(1)}, "
     "f32[16,1,512]{2,1,0:T(1,128)S(1)}) custom-call(bf16[16,512,64]"
     "{2,1,0:T(8,128)(2,1)S(1)} %bitcast.321), "
     "custom_call_target=\"tpu_custom_call\"",
     ("flash_attention_fwd.7", "custom-call")),
    ("%all-reduce.361 = (f32[64]{0:T(128)S(1)}, f32[64]{0:T(128)S(1)}) "
     "all-reduce(%get-tuple-element.1308, %get-tuple-element.1307), "
     "channel_id=2", ("all-reduce.361", "all-reduce")),
    ("%while.3 = (s32[], f32[8]{0}) while(%tuple.1), condition=%cond, "
     "body=%body", ("while.3", "while")),
    ("dot_general.1", ("dot_general.1", "dot_general")),
])
def test_parse_instruction(text, expected):
    assert xplane.parse_instruction(text) == expected


def test_interval_arithmetic():
    assert xplane.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == \
        [(0, 2.5), (3, 4)]
    assert xplane.clip([(0, 2), (3, 5), (6, 7)], 1, 4) == [(1, 2), (3, 4)]
    assert xplane.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == \
        [(0, 1), (2, 4), (6, 9)]
    assert xplane.subtract([(0, 2), (3, 5)], [(1, 4)]) == [(0, 1), (4, 5)]
    assert xplane.subtract([(0, 2)], []) == [(0, 2)]
    assert xplane.length([(0, 1), (2, 4.5)]) == 3.5


def test_recorded_trace_busy_union_and_categories():
    trace = load("resnet50-train-sync.xplane.pb")
    assert list(trace.devices) == [0]
    device = trace.devices[0]
    assert len(device.ops) == 30 and len(device.modules) == 2
    assert xplane.length(xplane.busy(device, WINDOW)) == \
        pytest.approx(5.086 * US, abs=2e-9)
    by_category = xplane.category_seconds(device, WINDOW)
    expected = {"copy": 3.148, "copy-done": 0.009, "async-start": 0.054,
                "copy-start": 0.042, "iota": 0.006, "loop fusion": 0.962,
                "convert": 0.865}
    assert set(by_category) == set(expected)
    for category, us in expected.items():
        assert by_category[category] == pytest.approx(us * US, abs=2e-9)
    # half of the first operation (copy.756, 0.360 us from 508136.659)
    # falls outside a window that starts at 508136.839
    cut = xplane.category_seconds(device, (0.508136839, WINDOW[1]))
    assert cut["copy"] == pytest.approx((3.148 - 0.180) * US, abs=2e-9)
    by_name = xplane.op_seconds(device, WINDOW)
    # suffixes dropped: the five copies are one entry, 3.148 us in all
    assert by_name["copy", "copy"] == [pytest.approx(3.148 * US, abs=2e-9),
                                       5]
    assert by_name["convert_element_type", "convert"][1] == 1


def test_recorded_trace_gap_goes_to_the_span_it_falls_in():
    trace = load("resnet50-train-sync.xplane.pb")
    # the window span is the one the benchmark recorded
    assert trace.window == pytest.approx((0.037968325, 3.370405167))
    gaps = xplane.idle_gaps(trace, 0, WINDOW)
    expected = {"bench/loss_read": 1469.131, "bench/dispatch": 3921.428,
                xplane.NO_SPAN: 12.120, xplane.IN_PROGRAM: 9.235}
    assert set(gaps) == set(expected)
    for name, us in expected.items():
        assert gaps[name] == pytest.approx(us * US, abs=3e-9)
    assert sum(gaps.values()) == pytest.approx(
        (5417.0 - 5.086) * US, abs=3e-9)
    # no collective on one chip
    assert xplane.collective_seconds(trace.devices[0], WINDOW) == (0, 0)


def test_recorded_trace_from_four_chips_has_an_exposed_all_reduce():
    """`data/resnet50-train-dp4-allreduce.xplane.pb`: device 0 of the
    four-chip host (resnet50-train-dp4, PR 22), cut to the first
    all-reduce of a step, which sums one batch normalisation's
    statistics over the chips.  Between 149553 us and 149572 us:

        fusion.4 (output fusion)  ends 149559.094   6.094 inside the window
        copy-done.31              0.002
        all-reduce.361            149559.098 .. 149564.990      = 5.892
        copy-start x6             0.001 0.001 0.002 0.002 0.002 0.002
        copy-done x8              0.003 0.002 0.003 0.003 0.002 0.212
                                  0.002 0.003
        loop fusion x2            0.017 0.582
        fusion.26 (loop fusion)   starts 149565.851  6.149 inside the window

    The all-reduce is synchronous: nothing else runs on the core while it
    does, so all of it is exposed."""
    trace = load("resnet50-train-dp4-allreduce.xplane.pb")
    device, window = trace.devices[0], (0.149553, 0.149572)
    total, exposed = xplane.collective_seconds(device, window)
    assert total == pytest.approx(5.892 * US, abs=2e-9)
    assert exposed == pytest.approx(5.892 * US, abs=2e-9)
    by_category = xplane.category_seconds(device, window)
    expected = {"output fusion": 6.094, "all-reduce": 5.892,
                "loop fusion": 0.017 + 0.582 + 6.149,
                "copy-done": 0.232, "copy-start": 0.010}
    assert set(by_category) == set(expected)
    for category, us in expected.items():
        assert by_category[category] == pytest.approx(us * US, abs=3e-9)
    assert xplane.length(xplane.busy(device, window)) == \
        pytest.approx(18.976 * US, abs=3e-9)
    # what is left of the 19 us lies inside the running program
    assert xplane.idle_gaps(trace, 0, window) == {
        xplane.IN_PROGRAM: pytest.approx(0.024 * US, abs=3e-9)}
    # cutting the window through the all-reduce cuts both numbers
    total, exposed = xplane.collective_seconds(
        device, (0.149553, 0.149562098))
    assert total == exposed == pytest.approx(3.0 * US, abs=2e-9)


# A written trace, for the two forms of collective a recording of this
# repo's programs need not hold side by side.  Times in microseconds:
#   fusion.1 (output fusion)   0 .. 10
#   all-reduce.2, synchronous 10 .. 14     nothing else runs: exposed 4
#   all-gather-start.3        14 .. 14.5   \  asynchronous, 14 .. 30 on the
#   fusion.4 (loop fusion)    15 .. 25      > async line; fusion.4 hides 10
#   all-gather-done.3         27 .. 30     /  of its 16, so 6 are exposed
#   while.5 (a container)      0 .. 30     left out of everything
WRITTEN = """
planes {
  name: "/device:TPU:1"
  lines { name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 10000000 duration_ps: 4000000 }
    events { metadata_id: 3 offset_ps: 14000000 duration_ps: 500000 }
    events { metadata_id: 4 offset_ps: 15000000 duration_ps: 10000000 }
    events { metadata_id: 5 offset_ps: 27000000 duration_ps: 3000000 }
    events { metadata_id: 6 offset_ps: 0 duration_ps: 30000000 }
  }
  lines { name: "Async XLA Ops" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 14000000 duration_ps: 16000000 }
  }
  lines { name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 7 offset_ps: 0 duration_ps: 30000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kOutput, calls=%c1" } }
  event_metadata { key: 2 value { id: 2 name: "%all-reduce.2 = f32[8]{0} all-reduce(f32[8]{0} %fusion.1), channel_id=1" } }
  event_metadata { key: 3 value { id: 3 name: "%all-gather-start.3 = (f32[8]{0}, f32[32]{0}) all-gather-start(f32[8]{0} %all-reduce.2), dimensions={0}" } }
  event_metadata { key: 4 value { id: 4 name: "%fusion.4 = f32[8]{0} fusion(f32[8]{0} %all-reduce.2), kind=kLoop, calls=%c2" } }
  event_metadata { key: 5 value { id: 5 name: "%all-gather-done.3 = f32[32]{0} all-gather-done((f32[8]{0}, f32[32]{0}) %all-gather-start.3)" } }
  event_metadata { key: 6 value { id: 6 name: "%while.5 = (s32[], f32[8]{0}) while(%t), condition=%cond, body=%body" } }
  event_metadata { key: 7 value { id: 7 name: "jit_step(1)" } }
}
planes {
  name: "/host:CPU"
  lines { name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 40000000 }
    events { metadata_id: 2 offset_ps: 30000000 duration_ps: 6000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench/window" } }
  event_metadata { key: 2 value { id: 2 name: "bench/loss_read" } }
}
"""


def test_written_trace_collective_overlapped_and_exposed():
    trace = from_text(WRITTEN)
    assert list(trace.devices) == [1]
    device, window = trace.devices[1], trace.window
    assert window == pytest.approx((0.0, 40 * US))
    total, exposed = xplane.collective_seconds(device, window)
    assert total == pytest.approx(20 * US)      # 4 + 16
    assert exposed == pytest.approx(10 * US)    # 4 + (16 - 10)
    # the container is in no sum: 10 + 4 + 0.5 + 10 + 3
    assert sum(xplane.category_seconds(device, window).values()) == \
        pytest.approx(27.5 * US)
    assert xplane.busy_seconds(trace) == pytest.approx(27.5 * US)
    gaps = xplane.idle_gaps(trace, 1)
    # 14.5..15 and 25..27 inside the program; 30..36 under the span,
    # 36..40 under none
    assert gaps[xplane.IN_PROGRAM] == pytest.approx(2.5 * US)
    assert gaps["bench/loss_read"] == pytest.approx(6 * US)
    assert gaps[xplane.NO_SPAN] == pytest.approx(4 * US)
