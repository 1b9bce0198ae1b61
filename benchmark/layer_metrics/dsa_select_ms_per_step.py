"""Device milliseconds a decoding step of the traced generation call
spends choosing and fetching, every layer: `dsa_select` (the top-k of
every slot's score) and `dsa_gather` (the chosen latents copied out of the cache).  What a
fused sparse-attention kernel that reads chosen latents in place would
take away; the scores of the index (`dsa_index`) and the attention's
contractions stay.  First device, inside the call's decoding scan."""

from benchmark.reduce import session_ops

LAYER = "ops"
MOVES = "decode_tok_per_s"
UNIT = "ms"
SOURCE = "device_trace"
PHASES = ("dsa_select", "dsa_gather")


def phase(kind, instance, inner):
    named = [p for p in inner if p in PHASES]
    return named[0] if named else None


def read(run):
    found = session_ops.step_seconds(run, phase)
    if not found:
        return None
    return sum(found.values()) * 1e3
