"""Device milliseconds a decoding step of the traced generation call
spends in the expert layers of the state cell's share: the `moe_router`
op (its product, the softmax over all scored experts and the top-k) and
`moe_experts` (the held assignments' selection and ordering, the grouped
products, the weighted combine), the shared expert's two products (the
`mul` ops that read a `shared_in` or `shared_out` parameter) and its
gate (the `mul` that reads `shared_gate` and the elementwise ops the
builder names `shared_gate`).  First device, inside the calls' scans of
steps.  Prints the parts apart.  What `long_moe_ms_per_step` is for the
long-session cell."""

from benchmark.reduce import state_ops

LAYER = "ops"
MOVES = "decode_tok_per_s"
UNIT = "ms"
SOURCE = "device_trace"
OP_TYPES = ("moe_router", "moe_experts")
SHARED = ("shared_in", "shared_out", "shared_gate")


def read(run):
    if state_ops.calls(run) is None:
        return None
    shared = state_ops.instances(
        run, "mul", lambda od: od.input("Y")[0].endswith(SHARED))

    def part(kind, instance, inner):
        if kind in OP_TYPES:
            return kind
        return "shared expert" if instance in shared \
            or instance[1:].startswith("shared_gate") else None

    found = state_ops.step_seconds(run, part)
    if not found or "moe_experts" not in found:
        return None
    print("expert layers of the share, device ms a decoding step: %s"
          % ", ".join("%s %.4f" % (name, s * 1e3)
                      for name, s in sorted(found.items())), flush=True)
    return sum(found.values()) * 1e3
