"""The flash-attention backward kernels' share of their roofline: the
least time the FLOPs the backward requires take at the bf16 peak
(benchmark/flops/flash.py `backward_flops`: four products an attended
pair, twice what the facts hold for the forward kernel; recomputing the
scores is not counted), over the device time of the operations whose
name starts with `flash_attention_bwd`, which are the backward's kernels
(`kernels/flash_attention.py:_flash_bwd_rule` names them
`flash_attention_bwd_dkv_*` and `flash_attention_bwd_dq_*`).  A step's
backward runs once for every attention op of the program.  Prints calls
and milliseconds a step for each kernel name.  A program whose backward
is no kernel has no such operation and gets no value."""

import collections
import re

from benchmark.flops import flash
from benchmark.reduce import xplane

LAYER = "kernels"
MOVES = "train_items_per_s"
UNIT = "%"
SOURCE = "device_trace"
PREFIX = "flash_attention_bwd"


def read(run):
    trace, facts = run.reduced, run.facts
    if trace is None or not trace.devices or run.peaks is None:
        return None
    cost = facts.get("flops", {}).get("kernels", {}).get(flash.KERNEL_NAME)
    steps = facts.get("traced_steps")
    if not cost or not steps:
        return None
    device = trace.devices[min(trace.devices)]
    by_name = collections.defaultdict(list)
    for op in device.work:
        if op.name.startswith(PREFIX):
            by_name[re.sub(r"\.\d+$", "", op.name)] += xplane.clip(
                [(op.start, op.end)], *trace.window)
    seconds = sum(xplane.length(spans) for spans in by_name.values())
    if not seconds:
        return None
    # the facts' forward FLOPs are two products a pair, summed over the
    # program's attention ops: the backward requires twice that a step
    least = 2 * cost["flops"] / run.peaks["bf16_flops_per_s"]
    print("%s: %s; the backward of the program's %d attention ops "
          "requires %.1f GFLOP a step, %.3f ms at the bf16 peak"
          % (PREFIX, "; ".join(
              "%s %.1f calls and %.3f ms a step"
              % (name, len(spans) / steps, xplane.length(spans) / steps * 1e3)
              for name, spans in sorted(by_name.items())),
             cost["calls"], 2 * cost["flops"] / 1e9, least * 1e3), flush=True)
    return 100.0 * least * steps / seconds
