"""The Mamba-2 step's share of its roofline: the least time the
recurrence's step requires (benchmark/flops/ssd_step.py `step`: every
mamba layer's state read once and written once at the chip's HBM peak,
or its 6 operations a state element at the bfloat16 peak, whichever is
larger) over the device time of what implements it, the operations
under the `ssd_scan` op's `ssd_step` scope inside the traced calls'
scans of steps, a step (the plain step's fusions, or a kernel's calls
if a later program has one: the scope is what is read).  Says which
bound it is, and prints the op's scopes apart, a step and inside the
prefill (the block form from the state handed in, `ssd_chunks`:
`ssd_block_*`).  Silent where no traced call holds an `ssd_scan` op that
carries its state (every cell but the Mamba-2 state cell, and a program
from before the op carried one)."""

from benchmark.flops import grouped, ssd_step
from benchmark.reduce import ssd_state_ops

LAYER = "kernels"
MOVES = "decode_tok_per_s"
UNIT = "%"
SOURCE = "device_trace"
SCOPE = "ssd_step"


def read(run):
    found = ssd_state_ops.scan_scopes(run)
    if not found or SCOPE not in found:
        return None
    seconds = found[SCOPE]
    ssd_state_ops.said("ssd_scan, device ms a decoding step", found)
    prefill = ssd_state_ops.scan_scopes(run, ssd_state_ops.prefill_seconds)
    if prefill:
        ssd_state_ops.said("ssd_scan inside the prefill, device ms a call",
                           prefill)
    cost = ssd_step.step(run.config, run.facts["ssd_state_batch"])
    least, bound = grouped.roofline(cost, run.peaks)
    print("%s: %.4f ms a decoding step; the recurrence's step requires "
          "%.3f GB and %.2f GFLOP, %.4f ms on the chip (%s-bound)"
          % (SCOPE, seconds * 1e3, cost["bytes"] / 1e9,
             cost["flops"] / 1e9, least * 1e3, bound), flush=True)
    return 100.0 * least / seconds
