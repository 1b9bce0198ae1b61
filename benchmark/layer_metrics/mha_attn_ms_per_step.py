"""Device milliseconds a decoding step of the dense state cell's traced
generation call spends in the full layers' attention, a key/value head a
query head: the `cached_attention` op (`kv_write`, `attn_full`: on the
kernel path the walk of the live slots, `gqa_decode_*`), the
whole-projection norms of q and k (the instances the builder names
`mha_attn`) and the mixer's four projections (the `mul` ops that read a
`wq`, `wk`, `wv` or a full layer's `wo`).  First device, inside the
calls' scans of steps, a step.  Prints the parts apart, and which path
the op took."""

from benchmark.reduce import dense_state_ops

LAYER = "ops"
MOVES = "decode_tok_per_s"
UNIT = "ms"
SOURCE = "device_trace"
OP_TYPE = "cached_attention"
SCOPES = ("kv_write", "attn_full")
NAMED = "mha_attn"
KERNEL = "gqa_decode_"


def projections(run):
    """The instances of the `mul` ops of the full layers' mixers."""
    reads = lambda od, ends: od.input("Y")[0].endswith(ends)
    full = {od.input("Y")[0].rsplit(".", 1)[0]
            for od in dense_state_ops.step_ops(run)
            if od.type == "mul" and reads(od, ".wq")}
    return dense_state_ops.instances(
        run, "mul", lambda od: reads(od, (".wq", ".wk", ".wv")) or (
            reads(od, ".wo") and od.input("Y")[0].rsplit(".", 1)[0] in full))


def read(run):
    if dense_state_ops.traced(run) is None:
        return None
    wanted = projections(run)

    def part(kind, instance, inner):
        if kind == OP_TYPE:
            named = [p for p in inner if p in SCOPES]
            return named[0] if named else "(no scope)"
        if instance[1:].startswith(NAMED):
            return "q and k norms"
        return "projections" if kind == "mul" and instance in wanted \
            else None

    found = dense_state_ops.step_seconds(run, part)
    if not found or "attn_full" not in found:
        return None
    kernel = dense_state_ops.kernel_step_seconds(run, KERNEL)
    print("full layers' attention, device ms a decoding step: %s; %s"
          % (", ".join("%s %.4f" % (name, s * 1e3)
                       for name, s in sorted(found.items())),
             "%s* %.4f ms (x%.1f)" % (KERNEL, kernel[0] * 1e3, kernel[1])
             if kernel[1] else "the plain path (no %s* kernel)" % KERNEL),
          flush=True)
    return sum(found.values()) * 1e3
