"""Milliseconds a generation call of the sparse key/value cell spends
before its first token: a call with `max_len=1`, which starts from the
session's caches where they lie on the device, prefills the question
through the step's scan (a position an application: the chooser picks
for one query) and returns the first continuations, timed on the host
after the windows (its second call: the first loads its program).  As
`session_prefill_ms_per_call` is for the sparse latent cell, whose calls
also bring their caches from the host."""

LAYER = "decoding"
MOVES = "decode_tok_per_s"
UNIT = "ms"
SOURCE = "host_clock"


def read(run):
    if run.peaks is None:
        return None
    return run.facts.get("sparse_prefill_ms")
