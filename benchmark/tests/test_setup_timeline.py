"""The six readers that split `setup_s` by the program's start-up
timeline, on a hand-made run against hand-computed answers, and one CPU
rehearsal of a fixture cell whose line carries all six.

The timeline, in seconds of the run's clock (the process starts at 100,
the window at 130: `setup_s` 30), all on one thread:

    import                       101 .. 104      children 0.5 + 0.25
      import_fluid               102 .. 102.5
      import_v2                  103 .. 103.25
    jit_trace (pool)             105 .. 106      a jit of the caller's own
    program_optimize             106 .. 107      child 0.25
      program_backward           106.25 .. 106.5
    trainer_init                 110 .. 118      children 4 + 0.5 + 2
      executor_first_run         110.5 .. 114.5  children 0.5 + 1 + 0.5 + 1.5
        executor_plan            110.5 .. 111
        jit_trace  segment_fn    111 .. 112
        jit_lower  segment_fn    112 .. 112.5
        jit_compile segment_fn   112.5 .. 114
      functional_program         115 .. 115.5
      state_place 4e9 bytes      116 .. 118
    trainer_first_step           120 .. 126      children 2 + 3
      jit_trace  step            120.5 .. 122.5
      jit_compile step           122.5 .. 125.5
    functional_program           133 .. 133.5    after the window began
    jit_trace  <lambda>          133.5 .. 134.5  after the window began

covered = 3 + 1 + 8 + 6 = 18; outside the program 30 - 18 = 12.
"""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.harness import BENCH_ROOT, CHECKOUT, Lookup
from paddle_tpu.obs import trace as obs_trace

LOOKUP = Lookup()
FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixture")
READERS = ("setup_import_s", "setup_program_s", "setup_state_s",
           "setup_first_run_s", "setup_named_share",
           "setup_outside_program_s")


def _event(name, t0, t1, parent=-1, **args):
    return {"name": "startup/" + name, "t0": t0, "dur": t1 - t0, "tid": 1,
            "thread": "MainThread", "parent": parent, "args": args}


TIMELINE = [
    _event("import", 101, 104),                                     # 0
    _event("import_fluid", 102, 102.5, parent=0),
    _event("import_v2", 103, 103.25, parent=0),
    _event("jit_trace", 105, 106, fun_name="pool"),
    _event("program_optimize", 106, 107, op_type="adam"),           # 4
    _event("program_backward", 106.25, 106.5, parent=4),
    _event("trainer_init", 110, 118),                               # 6
    _event("executor_plan", 110.5, 111, parent=11, ops=9),
    _event("jit_trace", 111, 112, parent=11, fun_name="segment_fn"),
    _event("jit_lower", 112, 112.5, parent=11, fun_name="segment_fn"),
    _event("jit_compile", 112.5, 114, parent=11, fun_name="segment_fn"),
    _event("executor_first_run", 110.5, 114.5, parent=6,            # 11
           place="CPUPlace"),
    _event("functional_program", 115, 115.5, parent=6),
    _event("state_place", 116, 118, parent=6, bytes=4_000_000_000,
           arrays=7),
    _event("jit_trace", 120.5, 122.5, parent=16, fun_name="step"),
    _event("jit_compile", 122.5, 125.5, parent=16, fun_name="step"),
    _event("trainer_first_step", 120, 126, step=0),                 # 16
    _event("functional_program", 133, 133.5),
    _event("jit_trace", 133.5, 134.5, fun_name="<lambda>"),
]


def _run(process_start=100.0, window_start=130.0):
    clock = types.SimpleNamespace(
        process_start=process_start,
        setup_s=lambda start: start - process_start)
    return types.SimpleNamespace(clock=clock, window_start=window_start,
                                 facts={})


def _read(name, run):
    return LOOKUP.module("layer_metrics", name).read(run)


@pytest.fixture
def timeline(monkeypatch):
    monkeypatch.setattr(obs_trace, "startup_events",
                        lambda: [dict(ev) for ev in TIMELINE])


@pytest.mark.parametrize("name,value", [
    ("setup_import_s", 3.0),
    ("setup_program_s", 0.75 + 0.25 + 0.5 + 0.5),
    ("setup_state_s", 1.5 + 2.0),
    ("setup_first_run_s", 0.5 + 1.0),
    ("setup_named_share", 60.0),
    ("setup_outside_program_s", 12.0),
])
def test_a_reader_against_the_hand_made_timeline(timeline, name, value):
    assert _read(name, _run()) == pytest.approx(value, abs=1e-9)


def test_the_parts_add_up_to_setup_s(timeline, capsys):
    run = _run()
    values = {name: _read(name, run) for name in READERS}
    del values["setup_named_share"]
    out = capsys.readouterr().out
    # the jit phases under a program event, printed by the share's reader
    assert ("jit phases under a program event: trace 3.000 s lower 0.500 s "
            "compile 4.500 s; of the caller's own: trace 1.000 s" in out)
    assert sum(values.values()) + 3.0 + 0.5 + 4.5 == pytest.approx(30.0)


def test_the_readers_print_what_their_numbers_are_made_of(timeline, capsys):
    run = _run()
    for name in READERS:
        _read(name, run)
    out = capsys.readouterr().out
    assert "import 2.250 s, import_fluid 0.500 s, import_v2 0.250 s" in out
    assert "program_optimize x1 0.750 s" in out
    assert ("state_place [arrays=7 bytes=4000000000]: 2.000 s, 2.000 GB/s"
            in out)
    assert ("executor_first_run [place=CPUPlace]: 4.000 s, 0.500 its own; "
            "segment_fn compile 1.500 lower 0.500 trace 1.000" in out)
    assert ("trainer_first_step [step=0]: 6.000 s, 1.000 its own; "
            "step compile 3.000 trace 2.000" in out)
    # what began at or after the window's first instant, by its offset
    assert ("timeline: 17 events began in set-up, 2 at or after the window's "
            "first instant: +3.000 s functional_program "
            "0.500 s; +3.500 s the caller's own jit of <lambda>, 1 phase(s) "
            "1.000 s" in out)


def test_since_and_until_are_the_runs_own(timeline):
    # a process that began after the import, a window before the step
    run = _run(process_start=104.5, window_start=119.0)
    assert _read("setup_import_s", run) == 0.0
    assert _read("setup_first_run_s", run) == pytest.approx(0.5)
    assert _read("setup_outside_program_s", run) == pytest.approx(
        14.5 - (1.0 + 8.0))


@pytest.mark.parametrize("name", READERS)
def test_an_empty_timeline_or_none_gives_no_value(monkeypatch, name):
    monkeypatch.setattr(obs_trace, "startup_events", lambda: [])
    assert _read(name, _run()) is None
    # a program from before the timeline
    monkeypatch.delattr(obs_trace, "startup_summary")
    assert _read(name, _run()) is None


def test_a_traced_rehearsal_carries_all_six():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH_ROOT, "run.py"), "--workload",
         "gpt2-tiny-train", "--seed", "5", "--seconds", "1", "--trace", "1",
         "--search-path", FIXTURE],
        cwd=CHECKOUT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    metrics = json.loads(proc.stdout.strip().splitlines()[-1])["metrics"]
    assert set(READERS) <= set(metrics)
    assert 0 < metrics["setup_named_share"]["value"] <= 100
    assert metrics["setup_named_share"]["unit"] == "%"
    assert metrics["setup_import_s"]["value"] > 0
    assert metrics["compiles_in_window"]["value"] == 0
    # what follows the windows is the functional path's build
    late, = [line for line in proc.stdout.splitlines()
             if line.startswith("start-up timeline: ")]
    assert "functional_program" in late and "executor_first_run" not in late
