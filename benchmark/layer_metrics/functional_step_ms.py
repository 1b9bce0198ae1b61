"""Milliseconds a step of the same program and state takes through
`jit.FunctionalProgram` under one `jax.jit` with all state donated
(path B), timed after the window in the traced run."""

LAYER = "functional"
MOVES = "train_items_per_s"
UNIT = "ms"
SOURCE = "host_clock"


def read(run):
    if run.peaks is None:
        return None
    return run.facts.get("functional_step_ms")
