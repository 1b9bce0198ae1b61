"""Milliseconds of the first device's time a decode step spends in
operations that lie under no op of the Program: inside the traced call's
scan of steps (`decode_device_step_ms`'s interval), the operations whose
`op_name` path holds no op instance (the scan's own slices and updates,
the choice of the next token) or that have no path at all (a `copy`, a
`copy-done`, a `slice-done` the compiler added, found by time).  A
step's caches are updated in place; a relayout or a copy of a whole
cache inside a step would show here first, which no test on the CPU can
see.

Prints the five categories with most time a step and the bytes a step
their instructions state."""

from benchmark.reduce import decoder_trace

LAYER = "decoding"
MOVES = "decode_tok_per_s"
UNIT = "ms"
SOURCE = "device_trace"
SHOWN = 5


def read(run):
    found = [(part, decoder_trace.unscoped(part))
             for part in decoder_trace.parts(run) or ()]
    found = [(part, by) for part, by in found if by is not None]
    if not found:
        return None
    each = sum(sum(entry[0] for entry in by.values())
               / decoder_trace.steps_of(part.call)
               for part, by in found) / len(found)
    part, by = found[0]
    steps = decoder_trace.steps_of(part.call)
    print("under no op instance inside the scan of steps, a step: %s"
          % (", ".join(
              "%s %.4f ms (x%.1f, %.3f MB stated)"
              % (category, s / steps * 1e3, calls / steps,
                 stated / steps / 1e6)
              for category, (s, calls, stated) in sorted(
                  by.items(), key=lambda item: -item[1][0])[:SHOWN])
             or "nothing"), flush=True)
    return each * 1e3
