"""Milliseconds a generation call spends before its first token: a call
with `max_len=1`, which prefills the prompts and returns their first
continuations, timed on the host after the windows (its second call: the
first loads its program).  From the empty caches on the host to the
tokens on the host, as the whole calls of the window are timed."""

LAYER = "decoding"
MOVES = "decode_tok_per_s"
UNIT = "ms"
SOURCE = "host_clock"


def read(run):
    if run.peaks is None:
        return None
    return run.facts.get("prefill_ms")
