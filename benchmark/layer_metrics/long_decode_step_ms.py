"""Milliseconds one decode step of the long-session cell's lockstep
batch takes: the window's mean call less `long_prefill_ms_per_call`
(which holds the session's way to the device), over the `gen_len - 1`
steps a call decodes after its question's prefill.  Host clock over
hundreds of steps at once, as `session_decode_step_ms` is for the sparse
latent cell."""

LAYER = "decoding"
MOVES = "decode_tok_per_s"
UNIT = "ms"
SOURCE = "host_clock"


def read(run):
    facts = run.facts
    if run.peaks is None or "long_prefill_ms" not in facts:
        return None
    return (facts["long_call_ms"] - facts["long_prefill_ms"]) \
        / (facts["long_gen_len"] - 1)
