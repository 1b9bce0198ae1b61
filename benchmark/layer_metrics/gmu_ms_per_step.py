"""Device milliseconds a decoding step of the traced generation call
spends in the gated memory units: the ops the builder names `gmu_<i>`
(models/sambay_program.py: the projection in, its SiLU, the product with
layer 16's memory, the projection out), every memory unit.  First
device, inside the call's decoding scan, over its `gen_len - 1` steps.
Prints the time by op type: they add up to the value."""

from benchmark.reduce import yoco_ops

LAYER = "ops"
MOVES = "decode_tok_per_s"
UNIT = "ms"
SOURCE = "device_trace"
NAME = "gmu_"


def read(run):
    found = yoco_ops.operations(run)
    if found is None:
        return None
    prefix = found[1] + NAME
    by_type = yoco_ops.step_seconds(
        run, lambda op_type, instance, inner:
        op_type if instance.startswith(prefix) else None)
    if not by_type:
        return None
    print("the gated memory units, device ms a decoding step by op type: %s"
          % ", ".join("%s %.4f" % (name, s * 1e3)
                      for name, s in sorted(by_type.items())), flush=True)
    return sum(by_type.values()) * 1e3
