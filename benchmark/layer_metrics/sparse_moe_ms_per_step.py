"""Device milliseconds a decoding step of the traced generation call
spends in the expert layers of the sparse key/value cell's share: the
`moe_router` op (its product, the softmax over every scored expert and
the top-k) and `moe_experts` (the held assignments' selection and
ordering, the grouped products, the weighted combine).  There is no
shared expert.  First device, inside the call's decoding scan.  Prints
the parts apart.  What `session_moe_ms_per_step` is for the sparse
latent cell."""

from benchmark.reduce import sparse_ops

LAYER = "ops"
MOVES = "decode_tok_per_s"
UNIT = "ms"
SOURCE = "device_trace"
OP_TYPES = ("moe_router", "moe_experts")


def read(run):
    found = sparse_ops.step_seconds(
        run, lambda kind, inst, inner: kind if kind in OP_TYPES else None)
    if not found or "moe_experts" not in found:
        return None
    print("expert layers of the share, device ms a decoding step: %s"
          % ", ".join("%s %.4f" % (name, s * 1e3)
                      for name, s in sorted(found.items())), flush=True)
    return sum(found.values()) * 1e3
