"""Seconds JAX spent during set-up tracing `ProgramDecoder`'s generation
function in Python and lowering it to MLIR: the rise of the program's
`jit_phase_seconds_total` counter (phases `trace` and `lower`, function
`<lambda>`: the decoder jits a lambda) across the decoder's first call,
read by the driver, so that no other lambda of the process is in it.
The part of set-up a warm compile cache cannot save; what
`setup_trace_lower_s` is for the cells that run the executor or a
trainer (no start-up program runs here, so those jit nothing)."""

LAYER = "program"
MOVES = "setup_s"
UNIT = "s"
SOURCE = "program_counter"


def read(run):
    return run.facts.get("decode_trace_lower_s")
