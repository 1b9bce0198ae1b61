"""The grouped-product kernels' share of their roofline: the least time
the FLOPs and bytes the expert layers' nine products a step require
(benchmark/flops/grouped.py `program_cost`: 2 * rows * k * n a product
with the rows really routed, three forward and six backward; masked
tiles and recomputation not counted) take on the chip, over the device
time of the operations whose name starts with `moe_gmm`, which are the
kernels (`kernels/grouped_matmul.py` names them `moe_gmm_fwd_*`,
`moe_gmm_dx_*`, `moe_gmm_dw_*` with their block sizes).  Says which
bound it is, and prints calls and milliseconds a step for each kernel
name.  The program is built once more for its shapes (the driver does
not keep it).  A program whose experts run no such kernel gets no
value."""

import collections
import re

from benchmark.flops import grouped
from benchmark.reduce import xplane

LAYER = "kernels"
MOVES = "train_items_per_s"
UNIT = "%"
SOURCE = "device_trace"


def read(run):
    import jax.numpy as jnp

    trace, steps = run.reduced, run.facts.get("traced_steps")
    if trace is None or not trace.devices or run.peaks is None or not steps:
        return None
    device = trace.devices[min(trace.devices)]
    by_name = collections.defaultdict(list)
    for op in device.work:
        if op.name.startswith(grouped.KERNEL_PREFIX):
            by_name[re.sub(r"\.\d+$", "", op.name)] += xplane.clip(
                [(op.start, op.end)], *trace.window)
    seconds = sum(xplane.length(spans) for spans in by_name.values())
    if not seconds:
        return None
    cfg = run.config
    program = run.lookup.module("models", cfg["builder"]).build(
        cfg, run.workload["batch"], train=True)["main"]
    cost = grouped.program_cost(
        program, jnp.dtype(cfg["compute_dtype"]).itemsize)
    if not cost["products"]:
        return None
    least, bound = grouped.roofline(cost, run.peaks)
    print("%s: %s; the %d products of the program's %d expert layer(s) on "
          "%d routed rows require %.1f GFLOP and %.3f GB a step, %.3f ms "
          "on the chip (%s-bound)"
          % (grouped.KERNEL_PREFIX, "; ".join(
              "%s %.1f calls and %.3f ms a step"
              % (name, len(spans) / steps, xplane.length(spans) / steps * 1e3)
              for name, spans in sorted(by_name.items())),
             cost["products"], cost["layers"], cost["rows"],
             cost["flops"] / 1e9, cost["bytes"] / 1e9, least * 1e3, bound),
          flush=True)
    return 100.0 * least * steps / seconds
