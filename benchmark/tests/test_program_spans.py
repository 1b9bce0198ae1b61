"""The program's spans in the profiler's trace, against hand-computed
answers.

`data/resnet50-train-read.xplane.pb` is a recording from the chip (TPU v5
lite, resnet50-train, PR 23's first traced run of the real cell), cut
down to device 0's operations between 1.457455 s and 1.462640 s of the
trace's clock and the host spans that reach into that time: the last 20
operations of one step, the 5.2 ms in which the host read the loss and
`Executor.run` got the next step on its way, and the first 25 operations
of that step.  Event metadata keeps its `tf_op` stat.  In microseconds:

    last operation (copy.2033) ends          1457463.141
    program (XLA Modules) ends               1457464.157
    bench/window                               44355.030 .. 3374340.081
      bench/loss_read                        1042597.069 .. 1459697.152
      bench/dispatch                         1459708.401 .. 1466069.381
        executor/run                         1459733.281 .. 1466052.132
          executor/feed                      1459736.832 .. 1459981.652
          executor/plan                      1459983.452 .. 1459999.192
          executor/segment                   1460007.561 .. 1466021.721
            executor/dispatch                1462519.101 .. 1465039.952
              PjitFunction(segment_fn)       1462520.852 .. 1465034.721
    next program starts                      1462627.856
    its first operation starts               1462633.787

Self times: bench/dispatch 6360.980 - 6318.851 = 42.129; executor/run
6318.851 - (244.820 + 15.740 + 6014.160) = 44.131; executor/segment
6014.160 - 2520.851 = 3493.309; executor/dispatch keeps its 2520.851
(JAX's own events are not the program's spans).

The gap between the two programs is 5163.699 us, cut at every span
boundary inside it:

    1457464.157 .. 1459697.152  bench/loss_read, no program span  2232.995
    1459697.152 .. 1459708.401  no span at all                      11.249
    1459708.401 .. 1459733.281  bench/dispatch, no program span     24.880
    1459733.281 .. 1459736.832  executor/run                         3.551
    1459736.832 .. 1459981.652  executor/feed                      244.820
    1459981.652 .. 1459983.452  executor/run                         1.800
    1459983.452 .. 1459999.192  executor/plan                       15.740
    1459999.192 .. 1460007.561  executor/run                         8.369
    1460007.561 .. 1462519.101  executor/segment                  2511.540
    1462519.101 .. 1462627.856  executor/dispatch                  108.755

so executor/run gets 13.720, all executor/* 2894.575, and with the 24.880
left to bench/dispatch that is the 2919.455 `xplane.idle_gaps` puts down to
bench/dispatch.  Most of the stall after a loss read is `executor/segment`
before its dispatch: resolving what the segment reads from the scope.
"""

import os

import pytest

from benchmark.reduce import op_scopes, program_spans, xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "resnet50-train-read.xplane.pb")
US = 1e-6
WINDOW = (1.457455, 1.462640)


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    data = ProfileData.from_file(DATA)
    return xplane.from_profile(data), program_spans.from_profile(data)


def test_spans_nest_by_containment_and_have_self_time(recorded):
    _, spans = recorded
    assert [s.name for s in spans] == [
        "bench/window", "bench/loss_read", "bench/dispatch", "executor/run",
        "executor/feed", "executor/plan", "executor/segment",
        "executor/dispatch"]
    assert len({s.line for s in spans}) == 1
    nested = {span.name: (parent.name if parent else None, own)
              for span, parent, own in program_spans.nest(spans)}
    expected = {
        "bench/window": (None, 3329985.051 - 417100.083 - 6360.980),
        "bench/loss_read": ("bench/window", 417100.083),
        "bench/dispatch": ("bench/window", 42.129),
        "executor/run": ("bench/dispatch", 44.131),
        "executor/feed": ("executor/run", 244.820),
        "executor/plan": ("executor/run", 15.740),
        "executor/segment": ("executor/run", 3493.309),
        "executor/dispatch": ("executor/segment", 2520.851)}
    assert set(nested) == set(expected)
    for name, (parent, us) in expected.items():
        assert nested[name][0] == parent
        assert nested[name][1] == pytest.approx(us * US, abs=3e-9)
    own = program_spans.self_seconds(
        [s for s in spans if s.name.startswith("executor/")])
    assert own["executor/segment"] == pytest.approx(3493.309 * US, abs=3e-9)
    assert sum(own.values()) == pytest.approx(6318.851 * US, abs=3e-9)
    assert program_spans.mean_seconds(spans, "executor/run") == \
        (pytest.approx(6318.851 * US, abs=3e-9), 1)
    assert program_spans.mean_seconds(spans, "parallel/step") == (0.0, 0)
    # only bench/window reaches over the whole cut
    assert [s.name for s in program_spans.inside(spans, (1.459, 1.467))] == \
        [s.name for s in spans[2:]]


def test_one_gap_is_split_over_the_spans_open_in_it(recorded):
    trace, spans = recorded
    idle = program_spans.idle_by_span(trace, spans, 0, WINDOW)
    expected = {
        "bench/loss_read (no program span)": 2232.995,
        xplane.NO_SPAN: 11.249,
        "bench/dispatch (no program span)": 24.880,
        "executor/run": 13.720, "executor/feed": 244.820,
        "executor/plan": 15.740, "executor/segment": 2511.540,
        "executor/dispatch": 108.755}
    assert set(idle) == set(expected)
    for name, us in expected.items():
        assert idle[name] == pytest.approx(us * US, abs=3e-9)
    assert sum(idle.values()) == pytest.approx(5163.699 * US, abs=3e-9)
    # it closes against the reduction the breakdown already had
    old = xplane.idle_gaps(trace, 0, WINDOW)
    executor = sum(s for n, s in idle.items() if n.startswith("executor/"))
    assert executor == pytest.approx(2894.575 * US, abs=3e-9)
    assert executor + idle["bench/dispatch (no program span)"] == \
        pytest.approx(old["bench/dispatch"], abs=1e-12)
    assert idle["bench/loss_read (no program span)"] == \
        pytest.approx(old["bench/loss_read"], abs=1e-12)
    # a trace from before the program had spans: the benchmark's own keep
    # everything, and nothing fails
    bench_only = [s for s in spans if not program_spans.is_program(s)]
    idle = program_spans.idle_by_span(trace, bench_only, 0, WINDOW)
    assert idle["bench/dispatch (no program span)"] == \
        pytest.approx(old["bench/dispatch"], abs=1e-12)
    assert not any(n.startswith("executor/") for n in idle)
    assert program_spans.idle_by_span(trace, [], 0, WINDOW) == {
        xplane.NO_SPAN: pytest.approx(5163.699 * US, abs=3e-9)}


def test_the_recording_keeps_the_paths_of_its_operations():
    paths = op_scopes.metadata_stat(DATA, "/device:TPU:0", "tf_op")
    by_name = {name.split(" = ")[0]: path for name, path in paths.items()}
    assert by_name["%convert_element_type.605"] == \
        "jit(segment_fn)/conv2d/convert_element_type:"
    assert by_name["%fusion.1020"] == (
        "jit(segment_fn)/softmax_with_cross_entropy_grad/"
        "jvp(jit(take_along_axis))/reshape:")
    # a copy XLA added for a parameter has the argument's name, no scope
    assert by_name["%copy.674"] == "mut_ins['conv2d_2.w_0']:"
    assert op_scopes.op_type(by_name["%copy.674"]) is None
    assert {op_scopes.op_type(p) for p in paths.values()} == {
        None, "conv2d", "elementwise_add", "softmax_with_cross_entropy",
        "softmax_with_cross_entropy_grad"}


class Run:
    """What a reader is given, as far as these readers look."""

    def __init__(self, trace, peaks, steps):
        from benchmark.harness import Lookup

        self.lookup = Lookup()
        self.reduced, self.peaks = trace, peaks
        self.trace_dir = os.path.dirname(DATA)
        self.facts = {"traced_steps": steps} if steps else {}


def test_readers_find_their_spans_or_return_none(recorded, capsys,
                                                 monkeypatch):
    trace, _ = recorded
    # the cut's window is the recording's own, 3.33 s for 70 steps; here
    # it stands for one step
    monkeypatch.setattr(xplane, "find_xplane", lambda trace_dir: DATA)

    def read(name, run):
        return run.lookup.module("layer_metrics", name).read(run)

    run = Run(trace, {"some": "peaks"}, 1)
    assert read("executor_run_host_ms", run) == pytest.approx(6.318851)
    assert read("executor_idle_ms_per_step", run) == pytest.approx(2.894575)
    printed = capsys.readouterr().out
    assert "executor/segment 3.493 ms" in printed
    assert "executor/segment 2.512" in printed
    assert "bench/dispatch (no program span) 0.025" in printed
    # no parallel/* span: the trainer's readers have nothing to read
    assert read("trainer_step_host_ms", run) is None
    assert read("trainer_idle_ms_per_step", run) is None
    # a CPU rehearsal (no peaks), an untraced run, a run without steps
    for other in (Run(trace, None, 1), Run(None, {"p": 1}, 1),
                  Run(trace, {"p": 1}, 0)):
        for name in ("executor_run_host_ms", "executor_idle_ms_per_step",
                     "fwd_ms_per_step", "bwd_ms_per_step", "opt_ms_per_step",
                     "flash_bwd_ms_per_step"):
            assert read(name, other) is None, name


def test_rehearsal_on_the_cpu_prints_the_counter_and_no_time():
    """run.py end to end on the CPU (as test_run.py rehearses it): of the
    metrics that read the program's spans, scopes and counter, only the
    counter may appear; a CPU time is never printed under their names."""
    from benchmark.tests import test_run

    result = test_run.last_line(test_run.run_cell("gpt2-tiny-train", 1))
    metrics = result["metrics"]
    assert not {"executor_run_host_ms", "executor_idle_ms_per_step",
                "trainer_step_host_ms", "trainer_idle_ms_per_step",
                "fwd_ms_per_step", "bwd_ms_per_step", "opt_ms_per_step",
                "flash_bwd_ms_per_step"} & set(metrics)
    assert not test_run.DEVICE_METRICS & set(metrics)
    assert metrics["setup_trace_lower_s"]["unit"] == "s"
    assert metrics["setup_trace_lower_s"]["value"] > 0
