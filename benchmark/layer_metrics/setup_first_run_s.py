"""Seconds of set-up inside the program's first runs without their trace,
lower and compile: the self seconds of `startup/executor_first_run` (an
`Executor.run` whose plan missed or whose segment traced),
`startup/trainer_first_step` (a `ParallelTrainer.step` that traced) and
`startup/decoder_build` (a `ProgramDecoder` call that built its program)
on the program's start-up timeline: the dispatch, the first execution and
the wait for it.  The jit phases are their children and stay where
`setup_trace_lower_s`, `decode_trace_lower_s` and `setup_compile_s` read
them.  Prints each first run with the phases of its functions beside it,
so that a swing of those names its function."""

from benchmark.reduce import setup_timeline

LAYER = "executor"
MOVES = "setup_s"
UNIT = "s"
SOURCE = "program_counter"


def read(run):
    found = setup_timeline.cut(run)
    if found is None:
        return None
    for index, ev in setup_timeline.in_setup(found, setup_timeline.FIRST_RUN):
        below = setup_timeline.children(found, index)
        phases = setup_timeline.phases_by_function(below)
        print("set-up, %s: %.3f s, %.3f its own; %s" % (
            setup_timeline.describe(ev), ev["dur"],
            max(0.0, ev["dur"] - sum(c["dur"] for c in below)),
            "; ".join("%s %s" % (fun, " ".join(
                "%s %.3f" % (phase, seconds)
                for phase, seconds in sorted(by_phase.items())))
                for fun, by_phase in sorted(phases.items()))
            or "no jit phase"), flush=True)
    return setup_timeline.self_seconds(found, setup_timeline.FIRST_RUN)
