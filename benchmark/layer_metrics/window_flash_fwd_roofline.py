"""The window flash-attention forward kernels' share of their roofline:
the least time the attended pairs under the window take on the chip
(benchmark/flops/window_flash.py: two products a pair a query keeps of
its last `window` keys, from the program's shapes), over the device
time of the operations named `flash_attention_fwd_*_w<W>*`, which are
the forward kernels that carry a window
(`paddle_tpu/kernels/flash_attention.py`).  The chunks an edge crosses
are folded whole or as a staircase and cost more than their attended
pairs: that is the kernel's, and shows here.  Prints calls and
milliseconds a step for each kernel name, and which bound it is.  A
program with no window kernel gets no value.
`window_flash_bwd_roofline` reads the backward's kernels through the
same `read`."""

import collections
import re

from benchmark.flops import window_flash
from benchmark.reduce import xplane

LAYER = "kernels"
MOVES = "train_items_per_s"
UNIT = "%"
SOURCE = "device_trace"


def window_cost(run):
    """`window_flash.program_cost(...)["window"]` of the cell's program,
    built once more for its shapes (the driver does not keep it) and
    kept among the run's facts for the backward's reader."""
    import jax.numpy as jnp

    if "window_flash_cost" not in run.facts:
        cfg = run.config
        program = run.lookup.module("models", cfg["builder"]).build(
            cfg, run.workload["batch"], train=True)["main"]
        run.facts["window_flash_cost"] = window_flash.program_cost(
            program, jnp.dtype(cfg["compute_dtype"]).itemsize)["window"]
    return run.facts["window_flash_cost"]


def read(run, name=window_flash.FWD_NAME, which="forward"):
    trace, steps = run.reduced, run.facts.get("traced_steps")
    if trace is None or not trace.devices or run.peaks is None or not steps:
        return None
    device = trace.devices[min(trace.devices)]
    by_name = collections.defaultdict(list)
    for op in device.work:
        if name.match(op.name):
            by_name[re.sub(r"\.\d+$", "", op.name)] += xplane.clip(
                [(op.start, op.end)], *trace.window)
    seconds = sum(xplane.length(spans) for spans in by_name.values())
    if not seconds:
        return None
    cost = window_cost(run)[which]
    if not cost["calls"]:
        return None
    least, bound = window_flash.roofline(cost, run.peaks)
    print("window flash %s: %s; the program's %d window op(s) require "
          "%.1f GFLOP and %.3f GB a step, %.3f ms on the chip (%s-bound)"
          % (which, "; ".join(
              "%s %.1f calls and %.3f ms a step"
              % (kernel, len(spans) / steps,
                 xplane.length(spans) / steps * 1e3)
              for kernel, spans in sorted(by_name.items())),
             cost["calls"], cost["flops"] / 1e9, cost["bytes"] / 1e9,
             least * 1e3, bound), flush=True)
    return 100.0 * least * steps / seconds
