"""Device milliseconds a decoding step of the traced generation call
spends in the Mamba layers' two ops: `selective_scan` (the recurrence's
step over the carried state) and `causal_conv1d` (the convolution over
its carried tail), every Mamba layer.  The projections around them are
`mul` ops like any other layer's.  First device, inside the call's
decoding scan, over its `gen_len - 1` steps.  Prints the two apart: they
add up to the value."""

from benchmark.reduce import yoco_ops

LAYER = "ops"
MOVES = "decode_tok_per_s"
UNIT = "ms"
SOURCE = "device_trace"
OP_TYPES = ("selective_scan", "causal_conv1d")


def by_op(run):
    return yoco_ops.step_seconds(
        run, lambda op_type, instance, inner:
        op_type if op_type in OP_TYPES else None)


def read(run):
    found = by_op(run)
    if not found:
        return None
    print("the state-space ops, device ms a decoding step: %s"
          % ", ".join("%s %.4f" % (name, s * 1e3)
                      for name, s in sorted(found.items())), flush=True)
    return sum(found.values()) * 1e3
