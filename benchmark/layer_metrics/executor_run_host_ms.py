"""Milliseconds of host time one `fluid.Executor.run` takes: the mean
duration of the program's own `executor/run` spans in the traced window
(benchmark/reduce/program_spans.py).  Prints the self time of each span
under it (`executor/feed`, `executor/plan`, `executor/segment`,
`executor/dispatch`, `executor/fetch`), a run's worth each."""

from benchmark.reduce import program_spans

LAYER = "executor"
MOVES = "train_items_per_s"
UNIT = "ms"
SOURCE = "host_clock"


def read(run):
    return program_spans.host_ms(run, "executor/run")
