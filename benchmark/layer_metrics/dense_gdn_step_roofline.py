"""The gated delta rule's step kernel's share of its roofline in the dense
state cell: `gdn_step_roofline`'s reading
(benchmark/layer_metrics/gdn_step_roofline.py: the same scopes, kernels and
sizes, which this cell's configuration states under the same keys; at
96 x 192 a head the kernels are `gdn_step_r<rows>_h30_k96_v192_b<rows a
step>`, the prefix is the reader's) of a run of
benchmark/drivers/decode_dense_state.py, whose facts have names of their
own (benchmark/reduce/dense_state_ops.py says why).  Silent in every
other cell, and where the op took its plain path (no
such kernel in the trace): that silence here means the kernel was not
taken."""

from benchmark.reduce import dense_state_ops

LAYER = "kernels"
MOVES = "decode_tok_per_s"
UNIT = "%"
SOURCE = "device_trace"


def read(run):
    return dense_state_ops.as_state(run, "gdn_step_roofline")
