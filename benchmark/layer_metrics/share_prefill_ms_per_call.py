"""Milliseconds a generation call of the share cell spends before its
first token: a call with `max_len=1`, which prefills the prompts and
returns their first continuations, timed on the host after the windows
(its second call: the first loads its program).  As `prefill_ms_per_call`
is for the GPT-2 cell."""

LAYER = "decoding"
MOVES = "decode_tok_per_s"
UNIT = "ms"
SOURCE = "host_clock"


def read(run):
    if run.peaks is None:
        return None
    return run.facts.get("share_prefill_ms")
