"""Milliseconds a traced step for which the first device idled between
two programs while the host was inside `ParallelTrainer.step`: each such
gap is cut at the boundaries of the program's `parallel/*` spans and goes
to the innermost one open (benchmark/reduce/program_spans.py).  Prints the
split by span; with "bench/dispatch (no program span)" it adds up to the
breakdown's `bench/dispatch` entry."""

from benchmark.reduce import program_spans

LAYER = "multichip"
MOVES = "train_items_per_s"
UNIT = "ms"
SOURCE = "device_trace"


def read(run):
    return program_spans.idle_ms_per_step(run, "parallel/")
