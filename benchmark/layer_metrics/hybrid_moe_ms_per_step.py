"""Device milliseconds a decoding step of the traced generation call
spends in the feed-forward halves of the hybrid cell's share: the expert
layers' `moe_router` op (its product, the sigmoid over all scored
experts, the bias, the groups and the top-k) and `moe_experts` (the held
assignments' selection and ordering, the grouped products, the weighted
combine), the shared expert's two products (the `mul` ops that read a
`shared_in` or `shared_out` parameter) and the leading dense layers' two
(`ffn_in`, `ffn_out`).  First device, inside the calls' scans of steps.
Prints the parts apart.  What `state_moe_ms_per_step` is for the state
cell."""

from benchmark.reduce import hybrid_ops

LAYER = "ops"
MOVES = "decode_tok_per_s"
UNIT = "ms"
SOURCE = "device_trace"
OP_TYPES = ("moe_router", "moe_experts")
SHARED = ("shared_in", "shared_out")
DENSE = ("ffn_in", "ffn_out")


def read(run):
    if hybrid_ops.calls(run) is None:
        return None
    shared, dense = (hybrid_ops.instances(
        run, "mul", lambda od, names=names: od.input("Y")[0].endswith(names))
        for names in (SHARED, DENSE))

    def part(kind, instance, inner):
        if kind in OP_TYPES:
            return kind
        if instance in shared:
            return "shared expert"
        return "dense layers" if instance in dense else None

    found = hybrid_ops.step_seconds(run, part)
    if not found or "moe_experts" not in found:
        return None
    print("feed-forward halves of the share, device ms a decoding step: %s"
          % ", ".join("%s %.4f" % (name, s * 1e3)
                      for name, s in sorted(found.items())), flush=True)
    return sum(found.values()) * 1e3
