"""The gated delta rule's step kernel's share of its roofline: the least
time the rule's step requires (benchmark/flops/gated_delta.py
`rule_step`: every linear layer's state read once and written once at
the chip's HBM peak, or its 7 operations a state element at the bfloat16
peak, whichever is larger) over the device time of the `gdn_step_*`
kernels (kernels/gdn_step.py) inside the traced calls' scans of steps, a
step.  Says which bound it is.  Silent where the op took its plain path
(no such kernel in the trace)."""

from benchmark.flops import gated_delta, grouped
from benchmark.reduce import state_ops

LAYER = "kernels"
MOVES = "decode_tok_per_s"
UNIT = "%"
SOURCE = "device_trace"
KERNEL = "gdn_step_"


def read(run):
    found = state_ops.kernel_step_seconds(run, KERNEL)
    if found is None or not found[1]:
        return None
    seconds, calls = found
    cost = gated_delta.rule_step(run.config, run.facts["state_batch"])
    least, bound = grouped.roofline(cost, run.peaks)
    print("%s*: %.4f ms a decoding step (x%.1f); the rule's step requires "
          "%.3f GB and %.2f GFLOP, %.4f ms on the chip (%s-bound)"
          % (KERNEL, seconds * 1e3, calls, cost["bytes"] / 1e9,
             cost["flops"] / 1e9, least * 1e3, bound), flush=True)
    return 100.0 * least / seconds
