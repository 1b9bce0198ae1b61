"""Milliseconds the long session's caches take from the host to the
device: every call hands `ProgramDecoder.greedy` the full layers' keys
and values over the whole extent and the window layers' rings as numpy
arrays (what a decode-pool chip receives from the prefill pool), and the
decoder puts them on the device before its program runs.  Timed alone on
the host after the windows: the same arrays put there once more and
waited for.  It is inside `long_prefill_ms_per_call` and inside every
timed call."""

LAYER = "decoding"
MOVES = "decode_tok_per_s"
UNIT = "ms"
SOURCE = "host_clock"


def read(run):
    if run.peaks is None:
        return None
    return run.facts.get("long_restore_ms")
