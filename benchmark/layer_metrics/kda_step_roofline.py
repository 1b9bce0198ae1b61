"""The channel-gated delta rule's step kernel's share of its roofline:
the least time the rule's step requires (benchmark/flops/kimi_delta.py
`rule_step`: every KDA layer's state read once and written once at the
chip's HBM peak beside the [heads, 128] operands, or its 7 operations a
state element at the bfloat16 peak, whichever is larger) over the device
time of the `kda_step_*` kernels (kernels/gdn_step.py under a gate a key
channel) inside the traced calls' scans of steps, a step.  Says which
bound it is.  Silent where the op took its plain path or the program has
no such kernel (no `kda_step_*` in the trace)."""

from benchmark.flops import grouped, kimi_delta
from benchmark.reduce import hybrid_ops

LAYER = "kernels"
MOVES = "decode_tok_per_s"
UNIT = "%"
SOURCE = "device_trace"
KERNEL = "kda_step_"


def read(run):
    found = hybrid_ops.kernel_step_seconds(run, KERNEL)
    if found is None or not found[1]:
        return None
    seconds, calls = found
    cost = kimi_delta.rule_step(run.config, run.facts["hybrid_batch"])
    least, bound = grouped.roofline(cost, run.peaks)
    print("%s*: %.4f ms a decoding step (x%.1f); the rule's step requires "
          "%.3f GB and %.2f GFLOP, %.4f ms on the chip (%s-bound)"
          % (KERNEL, seconds * 1e3, calls, cost["bytes"] / 1e9,
             cost["flops"] / 1e9, least * 1e3, bound), flush=True)
    return 100.0 * least / seconds
