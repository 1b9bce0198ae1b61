"""Device milliseconds a decoding step of the dense state cell's traced
generation call spends in the dense feed-forward of every layer: its two
products (the `mul` ops that read an `ffn_in` or `ffn_out` parameter)
and the ops between them (the split, the SiLU and the product the
builder names `dense_ffn`).  First device, inside the calls' scans of
steps, a step.  Prints the parts apart."""

from benchmark.reduce import dense_state_ops

LAYER = "ops"
MOVES = "decode_tok_per_s"
UNIT = "ms"
SOURCE = "device_trace"
PRODUCTS = (".ffn_in", ".ffn_out")
NAMED = "dense_ffn"


def read(run):
    if dense_state_ops.traced(run) is None:
        return None
    products = dense_state_ops.instances(
        run, "mul", lambda od: od.input("Y")[0].endswith(PRODUCTS))

    def part(kind, instance, inner):
        if instance[1:].startswith(NAMED):
            return "gate (elementwise)"
        return "products" if kind == "mul" and instance in products \
            else None

    found = dense_state_ops.step_seconds(run, part)
    if not found or "products" not in found:
        return None
    print("dense feed-forward, device ms a decoding step: %s"
          % ", ".join("%s %.4f" % (name, s * 1e3)
                      for name, s in sorted(found.items())), flush=True)
    return sum(found.values()) * 1e3
