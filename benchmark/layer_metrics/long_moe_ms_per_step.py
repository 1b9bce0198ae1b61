"""Device milliseconds a decoding step of the traced generation call
spends in the expert layers of the long-session cell's share: the
`moe_router` op (its product, sigmoid, the selection bias and the top-k)
and `moe_experts` (the held assignments' selection and ordering, the
grouped products, the weighted combine), and the shared expert's two
products (the `mul` ops that read a `shared_in` or `shared_out`
parameter).  First device, inside the call's decoding scan.  Prints the
parts apart.  What `session_moe_ms_per_step` is for the sparse latent
cell."""

from benchmark.reduce import long_ops

LAYER = "ops"
MOVES = "decode_tok_per_s"
UNIT = "ms"
SOURCE = "device_trace"
OP_TYPES = ("moe_router", "moe_experts")
SHARED = ("shared_in", "shared_out")


def read(run):
    if long_ops.operations(run) is None:
        return None
    shared = long_ops.instances(
        run, "mul", lambda od: od.input("Y")[0].endswith(SHARED))

    def part(kind, instance, inner):
        if kind in OP_TYPES:
            return kind
        return "shared expert" if kind == "mul" and instance in shared \
            else None

    found = long_ops.step_seconds(run, part)
    if not found or "moe_experts" not in found:
        return None
    print("expert layers of the share, device ms a decoding step: %s"
          % ", ".join("%s %.4f" % (name, s * 1e3)
                      for name, s in sorted(found.items())), flush=True)
    return sum(found.values()) * 1e3
