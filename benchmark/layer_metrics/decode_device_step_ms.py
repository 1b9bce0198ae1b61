"""Milliseconds of the first device's time one decode step takes: the
seconds an operation ran inside the traced call's scan of steps, over
its steps (`max_len` - 1 after a prompt, from the `decode/call` span's
arguments).  The scan is the outermost `while` operation that holds the
operations under the compiled call's `decode_steps` scope
(`models/decode.py`), however many other `while`s the call has.  Nothing
of the host, of the prefill or of another program is in it.

Prints the `while` operation, and beside it the host clock's reading of
a step where a reader of the cell gives one (`decode_step_ms`, or its
`share_`, `session_` and `long_` copies: (mean call - prefill alone) /
steps)."""

from benchmark.reduce import decoder_trace

LAYER = "decoding"
MOVES = "decode_tok_per_s"
UNIT = "ms"
SOURCE = "device_trace"
HOST_READERS = "decode_step_ms"


def host_readings(run):
    """{reader: ms} of the host clock's step readers that find something
    to read in this run."""
    out = {}
    for name in run.lookup.names("layer_metrics"):
        if name.endswith(HOST_READERS):
            value = run.lookup.module("layer_metrics", name).read(run)
            if value is not None:
                out[name] = value
    return out


def read(run):
    found = [part for part in decoder_trace.parts(run) or ()
             if part.steps is not None]
    if not found:
        return None
    each = sum(part.busy(part.steps) / decoder_trace.steps_of(part.call)
               for part in found) / len(found)
    first = found[0]
    print("decode step on the device: %.4f ms over %d steps of %%%s "
          "(%.3f ms); on the host's clock: %s"
          % (each * 1e3, decoder_trace.steps_of(first.call),
             first.steps_name, (first.steps[1] - first.steps[0]) * 1e3,
             ", ".join("%s %.4f ms" % item
                       for item in sorted(host_readings(run).items()))
             or "no reader"), flush=True)
    return each * 1e3
