"""Milliseconds a generation call of the long-session cell spends before
its first token: a call with `max_len=1`, which puts the session's
caches on the device (2.2 GB of two kinds), prefills the question
through the step's scan, a position an application, and returns the
first continuations, timed on the host after the windows (its second
call: the first loads its program).  As `session_prefill_ms_per_call` is
for the sparse latent cell."""

LAYER = "decoding"
MOVES = "decode_tok_per_s"
UNIT = "ms"
SOURCE = "host_clock"


def read(run):
    if run.peaks is None:
        return None
    return run.facts.get("long_prefill_ms")
