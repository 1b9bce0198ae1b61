"""Device milliseconds a decoding step of the traced generation call
spends choosing and fetching, every layer: `dsa_select` (the top-k of
every slot's score) and `kv_gather` (the chosen keys and values copied
out of both caches).  What a top-k that does not sort the whole extent
and an attention kernel that reads chosen slots in place would take
away; the scores of the index (`dsa_index`) and the attention's
products stay.  First device, inside the call's decoding scan.  What
`dsa_select_ms_per_step` is for the sparse latent cell."""

from benchmark.reduce import sparse_ops

LAYER = "ops"
MOVES = "decode_tok_per_s"
UNIT = "ms"
SOURCE = "device_trace"
PHASES = ("dsa_select", "kv_gather")


def phase(kind, instance, inner):
    named = [p for p in inner if p in PHASES]
    return named[0] if named else None


def read(run):
    found = sparse_ops.step_seconds(run, phase)
    if not found:
        return None
    print("choosing and fetching, device ms a decoding step: %s"
          % ", ".join("%s %.4f" % (name, s * 1e3)
                      for name, s in sorted(found.items())), flush=True)
    return sum(found.values()) * 1e3
