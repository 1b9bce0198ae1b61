"""Device milliseconds a step application of the traced generation call
spends in the expert layers of a share: the `moe_router` and
`moe_experts` ops (the router's product, sigmoid and top-k; the held
assignments' selection and ordering, the grouped products, the weighted
combine) and the shared expert's two products (the `mul` ops that read a
`shared_in` or `shared_out` parameter; the program is built once more to
name them).  First device, traced call, over its step applications.
Prints the parts apart and, inside `moe_experts`, the scopes the op
opens."""

from benchmark.reduce import share_ops

LAYER = "ops"
MOVES = "decode_tok_per_s"
UNIT = "ms"
SOURCE = "device_trace"
OP_TYPES = ("moe_router", "moe_experts")
PHASES = ("moe_hold", "moe_route", "moe_experts", "moe_combine")
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
    if share_ops.operations(run) is None:
        return None
    shared = shared_products(run)

    def part(kind, instance, inner):
        if kind in OP_TYPES:
            return kind
        return "shared expert" if kind == "mul" and instance in shared \
            else None

    def phase(kind, instance, inner):
        if kind != "moe_experts":
            return None
        named = [p for p in inner if p in PHASES]
        # the innermost: `moe_hold` lies inside `moe_route`
        return named[-1] if named else "(no scope)"

    found = share_ops.seconds(run, part)
    if not found or "moe_experts" not in found:
        return None
    steps = run.facts["share_step_applications"]
    print("expert layers of the share: %s" % ", ".join(
        "%s %.3f ms and %.1f operations a step application"
        % (name, s / steps * 1e3, calls / steps)
        for name, (s, calls) in sorted(found.items())), flush=True)
    print("moe_experts by scope: %s" % ", ".join(
        "%s %.3f ms" % (name, s / steps * 1e3)
        for name, (s, _) in sorted(share_ops.seconds(run, phase).items())),
        flush=True)
    return sum(s for s, _ in found.values()) / steps * 1e3
