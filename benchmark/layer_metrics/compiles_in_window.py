"""Backend compile calls (`jax.monitoring`; a persistent-cache load is
one) plus the program's own `executor_jit_traces_total`, between the
first and the last instant of the windows.  Has to be 0."""

LAYER = "executor"
MOVES = "train_items_per_s"
UNIT = "count"
SOURCE = "program_counter"


def read(run):
    return run.facts.get("compiles_in_window")
