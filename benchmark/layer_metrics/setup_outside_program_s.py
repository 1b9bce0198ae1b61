"""Seconds of `setup_s` under no event of the program's start-up timeline
(`setup_s` minus `obs.trace.startup_summary`'s `covered`): the TPU
runtime's start-up, JAX's import, and the benchmark's own work (the model's
builder, the pool of batches, prompts, the reference's session and check).
Not the program's to shorten; on the ledger it tells a reference or a
runtime that got slower from a program that did."""

from benchmark.reduce import setup_timeline

LAYER = "entry"
MOVES = "setup_s"
UNIT = "s"
SOURCE = "program_counter"


def read(run):
    found = setup_timeline.cut(run)
    if found is None:
        return None
    return found.setup_s - found.covered
