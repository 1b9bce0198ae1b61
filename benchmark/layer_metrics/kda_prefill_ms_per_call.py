"""Device milliseconds a traced generation call's prefill spends under
the `gated_delta_rule` op under a gate a key channel, every KDA layer:
the block form (`kda_chunks`: the sub-blocks' decayed products, the
triangular solve and the walk over the chunks' states) with the l2 norms
(`gdn_gates`).  First device, inside the `decode_prefill` scope's
interval before the scan of steps, a call.  Prints the op's scopes
apart.  Silent for a program without `kda_chunks`."""

from benchmark.reduce import hybrid_ops

LAYER = "ops"
MOVES = "decode_tok_per_s"
UNIT = "ms"
SOURCE = "device_trace"
OP_TYPE = "gated_delta_rule"
SCOPES = ("gdn_gates", "kda_chunks", "kda_state")


def scope(kind, instance, inner):
    if kind != OP_TYPE:
        return None
    named = [p for p in inner if p in SCOPES]
    return named[0] if named else "(no scope)"


def read(run):
    found = hybrid_ops.prefill_seconds(run, scope)
    if not found or "kda_chunks" not in found:
        return None
    print("%s inside the prefill, device ms a call: %s"
          % (OP_TYPE, ", ".join("%s %.3f" % (name, s * 1e3)
                                for name, s in sorted(found.items()))),
          flush=True)
    return sum(found.values()) * 1e3
