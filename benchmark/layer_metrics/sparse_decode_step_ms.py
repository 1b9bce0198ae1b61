"""Milliseconds one decode step of the sparse key/value cell's lockstep batch
takes: the window's mean call less `sparse_prefill_ms_per_call`, over
the `gen_len - 1` steps a
call decodes after its question's prefill.  Host clock over hundreds of
steps at once, as `share_decode_step_ms` is for the share cell."""

LAYER = "decoding"
MOVES = "decode_tok_per_s"
UNIT = "ms"
SOURCE = "host_clock"


def read(run):
    facts = run.facts
    if run.peaks is None or "sparse_prefill_ms" not in facts:
        return None
    return (facts["sparse_call_ms"] - facts["sparse_prefill_ms"]) \
        / (facts["sparse_gen_len"] - 1)
