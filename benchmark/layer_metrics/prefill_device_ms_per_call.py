"""Milliseconds of the first device's time a traced call's prefill
takes: the seconds an operation ran inside the interval from the first
to the last operation under the compiled call's `decode_prefill` scope
(`models/decode.py prefill`: the remainder block or first position
outside the scan, and the scan) that ends before the scan of steps
begins.  Nothing of the host is in it: the state's way to the device,
which the host clock's `*prefill_ms_per_call` hold, ends before the
program starts.

Prints the applications of the step the prefill makes (from the
`decode/call` span's `prompt_len` and `block`), the milliseconds each
takes, and the time of the scope's operations that the compiler
scheduled after the steps began (not in the value)."""

from benchmark.reduce import decoder_trace

LAYER = "decoding"
MOVES = "decode_tok_per_s"
UNIT = "ms"
SOURCE = "device_trace"


def read(run):
    found = [part for part in decoder_trace.parts(run) or ()
             if part.prefill is not None]
    if not found:
        return None
    busy = sum(part.busy(part.prefill) for part in found) / len(found)
    first = found[0]
    applications = decoder_trace.prefill_applications(first.call)
    print("prefill on the device: %.3f ms busy inside %.3f ms, %d "
          "application(s) of %d position(s) for a prompt of %d, %.3f ms an "
          "application; %.3f ms of the scope's operations ran after the "
          "steps began"
          % (busy * 1e3, (first.prefill[1] - first.prefill[0]) * 1e3,
             applications, first.call.args["block"],
             first.call.args["prompt_len"], busy / applications * 1e3,
             first.prefill_late * 1e3), flush=True)
    return busy * 1e3
