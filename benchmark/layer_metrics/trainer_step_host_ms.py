"""Milliseconds of host time one `ParallelTrainer.step` takes: the mean
duration of the program's own `parallel/step` spans in the traced window
(benchmark/reduce/program_spans.py).  Prints the self time of each span
under it (`parallel/prepare`, `parallel/dispatch`, `parallel/wait`,
`parallel/record`), a step's worth each."""

from benchmark.reduce import program_spans

LAYER = "multichip"
MOVES = "train_items_per_s"
UNIT = "ms"
SOURCE = "host_clock"


def read(run):
    return program_spans.host_ms(run, "parallel/step")
