"""Milliseconds the session's caches take from the host to the device:
every call hands `ProgramDecoder.greedy` the latents and the chooser's
keys of every row as numpy arrays (what a decode-pool chip receives from
the prefill pool), and the decoder puts them on the device before its
program runs.  Timed alone on the host after the windows: the same
arrays put there once more and waited for.  It is inside
`session_prefill_ms_per_call` and inside every timed call."""

LAYER = "decoding"
MOVES = "decode_tok_per_s"
UNIT = "ms"
SOURCE = "host_clock"


def read(run):
    if run.peaks is None:
        return None
    return run.facts.get("session_restore_ms")
