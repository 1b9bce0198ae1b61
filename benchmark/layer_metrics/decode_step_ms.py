"""Milliseconds one decode step of the lockstep batch takes: the
window's mean call less `prefill_ms_per_call`, over the `gen_len - 1`
steps a call decodes after its prefill (the first token is the
prefill's).  Host clock over hundreds of steps at once: the scan yields
nothing in between."""

LAYER = "decoding"
MOVES = "decode_tok_per_s"
UNIT = "ms"
SOURCE = "host_clock"


def read(run):
    facts = run.facts
    if run.peaks is None or "prefill_ms" not in facts:
        return None
    return (facts["call_ms"] - facts["prefill_ms"]) / (facts["gen_len"] - 1)
