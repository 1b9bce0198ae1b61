"""The flash-attention forward kernel's share of its roofline: the least
time one call takes (benchmark/flops/flash.py, from the shapes; the
larger of FLOPs over the bf16 peak and bytes over the HBM peak) times the
calls the trace holds, over the device time of those calls, which are the
operations that carry the kernel's name.  Every call counts, also the
second forward that the op's generic gradient makes in the backward
pass: this is the kernel's efficiency, not the model's.  Prints which of
the two bounds it."""

from benchmark.flops import flash
from benchmark.reduce import xplane

LAYER = "kernels"
MOVES = "train_items_per_s"
UNIT = "%"
SOURCE = "device_trace"


def read(run):
    trace, facts = run.reduced, run.facts
    if trace is None or not trace.devices or run.peaks is None:
        return None
    cost = facts.get("flops", {}).get("kernels", {}).get(flash.KERNEL_NAME)
    if not cost:
        return None
    device = trace.devices[min(trace.devices)]
    seconds, calls = xplane.seconds_named(device, trace.window,
                                          flash.KERNEL_NAME)
    if not seconds:
        return None
    one = {k: cost[k] / cost["calls"] for k in ("flops", "bytes")}
    least, bound = flash.roofline(one, run.peaks)
    print("%s: %.1f calls and %.3f ms a step (the program has %d such "
          "ops), %s-bound roofline %.4f ms a call"
          % (flash.KERNEL_NAME, calls / facts["traced_steps"],
             seconds / facts["traced_steps"] * 1e3, cost["calls"], bound,
             least * 1e3), flush=True)
    return 100.0 * least * calls / seconds
