"""What the program's always-on instrumentation costs one steady-state
call with nothing recording (no profiler session, `obs.trace` disabled):
the spans a second `Executor.run` of one Program opens, and those of a
`ParallelTrainer.step` after the first, each with whatever else the run
or step does for the tracer's sake (a clock read, a test of "did this run
build or trace anything"), replayed in a loop without the work between
them.  PERF.md section 3 keeps the readings.

    PYTHONPATH=. python scripts/span_cost_bench.py          # this tree
    PYTHONPATH=<another checkout> python scripts/span_cost_bench.py

The sequences are those of `fluid/executor.py Executor._run_traced` (one
fed, jitted segment, fetched to numpy) and `parallel/trainer.py
ParallelTrainer.step`; a tree from before the start-up timeline
(`obs.trace.startup_events`) replays what it did instead (a clock read
around the feeds for `executor_feed_seconds_total`, no test).
"""

import statistics
import sys
import time
import timeit

import jax

from paddle_tpu.obs import telemetry as obs_tele
from paddle_tpu.obs import trace as obs_trace

TIMELINE = hasattr(obs_trace, "startup_events")
ROUNDS, CALLS = 15, 20000


class _Compiled:
    traces = 0


def executor_run(compiled=_Compiled(), miss=False):
    span = obs_trace.span
    with span("executor/run", cat="executor", feeds=2, fetches=1):
        if TIMELINE:
            t_run = time.perf_counter()
        with span("executor/feed", cat="executor"):
            if not TIMELINE:
                t_feed = time.perf_counter()
                obs_tele.on_feed_seconds(time.perf_counter() - t_feed)
        with span("executor/plan", cat="executor") as plan_span:
            plan_span.set(miss=miss)
        if TIMELINE:
            traces = compiled.traces
        with span("executor/segment", cat="executor", index=0,
                  segment="jit_segment[0:mul..sgd x93]", jit=True):
            with span("executor/dispatch", cat="executor"):
                pass
        with span("executor/fetch", cat="executor"):
            pass
        if TIMELINE and (miss or compiled.traces != traces):
            obs_trace.emit_span("startup/executor_first_run", t_run, 0.0,
                                cat="startup")


class _Trainer:
    _traced = jax.jit(lambda x: x)._cache_size
    _traces = 0


def trainer_step(trainer=_Trainer()):
    span = obs_trace.span
    with span("parallel/step", cat="trainer", step=7):
        with span("parallel/prepare", cat="trainer"):
            pass
        with span("parallel/dispatch", cat="trainer"):
            pass
        with span("parallel/wait", cat="trainer", for_step=6, own=0):
            pass
        if TIMELINE and trainer._traced() != trainer._traces:
            obs_trace.emit_span("startup/trainer_first_step", 0.0, 0.0,
                                cat="startup")
        with span("parallel/record", cat="trainer"):
            pass


def main():
    assert not obs_trace.is_enabled()
    before = len(obs_trace.startup_events()) if TIMELINE else 0
    print("tree %s the start-up timeline; us a call, the least and the "
          "median of %d rounds of %d calls"
          % ("with" if TIMELINE else "without", ROUNDS, CALLS))
    for name, call in (("Executor.run", executor_run),
                       ("ParallelTrainer.step", trainer_step)):
        rounds = [t / CALLS * 1e6 for t in
                  timeit.repeat(call, number=CALLS, repeat=ROUNDS)]
        print("%-22s %.3f %.3f" % (name, min(rounds),
                                   statistics.median(rounds)))
    if TIMELINE:
        assert len(obs_trace.startup_events()) == before
    return 0


if __name__ == "__main__":
    sys.exit(main())
