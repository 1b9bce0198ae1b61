"""Device milliseconds a decoding step of the traced generation call
spends in the expert layers of the session cell's share: the
`moe_router` op (its product, sigmoid, the selection bias, `moe_groups`
and the top-k) and `moe_experts` (the held assignments' selection and
ordering, the grouped products, the weighted combine), and the shared
expert's two products (the `mul` ops that read a `shared_in` or
`shared_out` parameter; the program is built once more to name them).
First device, inside the call's decoding scan.  Prints the parts apart,
and the grouped router's `moe_groups` scope."""

from benchmark.reduce import session_ops

LAYER = "ops"
MOVES = "decode_tok_per_s"
UNIT = "ms"
SOURCE = "device_trace"
OP_TYPES = ("moe_router", "moe_experts")
SHARED = ("shared_in", "shared_out")


def shared_products(run):
    """The instances of the `mul` ops of the shared experts."""
    from paddle_tpu.fluid import executor

    program = run.lookup.module("models", run.workload["builder"]).build(
        run.config, run.workload["batch"])["main"]
    return {executor.op_instance(od)
            for od in program.global_block().desc.ops
            if od.type == "mul" and od.input("Y")[0].endswith(SHARED)}


def read(run):
    if session_ops.operations(run) is None:
        return None
    shared = shared_products(run)

    def part(kind, instance, inner):
        if kind == "moe_router" and "moe_groups" in inner:
            return "moe_router (moe_groups)"
        if kind in OP_TYPES:
            return kind
        return "shared expert" if kind == "mul" and instance in shared \
            else None

    found = session_ops.step_seconds(run, part)
    if not found or "moe_experts" not in found:
        return None
    print("expert layers of the share, device ms a decoding step: %s"
          % ", ".join("%s %.4f" % (name, s * 1e3)
                      for name, s in sorted(found.items())), flush=True)
    return sum(found.values()) * 1e3
