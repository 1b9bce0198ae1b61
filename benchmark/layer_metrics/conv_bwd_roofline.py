"""The convolutions' gradients' share of their roofline: the time the
FLOPs of the program's `conv2d_grad` ops take at the bf16 peak, one
contraction per gradient an op really produces (the first convolution
has no input gradient: benchmark/flops/instances.py), over the device
time under those ops' instances (benchmark/reduce/op_instances.py),
which holds whatever XLA fused around them (the batch norm's and the
activation's gradients).  See `conv_fwd_roofline`."""

LAYER = "kernels"
MOVES = "train_items_per_s"
UNIT = "%"
SOURCE = "device_trace"
WHICH = "backward"


def read(run):
    return run.lookup.module(
        "layer_metrics", "conv_fwd_roofline").share(run, WHICH)
