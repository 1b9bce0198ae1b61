"""Milliseconds one decode step of the share cell's lockstep batch takes:
the window's mean call less `share_prefill_ms_per_call`, over the
`gen_len - 1` steps a call decodes after its prefill.  Host clock over
hundreds of steps at once, as `decode_step_ms` is for the GPT-2 cell
(the two fold into one name once benchmark/flops/decode.py is dispatched
on the configuration: PERF.md section 7)."""

LAYER = "decoding"
MOVES = "decode_tok_per_s"
UNIT = "ms"
SOURCE = "host_clock"


def read(run):
    facts = run.facts
    if run.peaks is None or "share_prefill_ms" not in facts:
        return None
    return (facts["share_call_ms"] - facts["share_prefill_ms"]) \
        / (facts["share_gen_len"] - 1)
