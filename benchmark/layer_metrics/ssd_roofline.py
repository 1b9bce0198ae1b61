"""The chunked state-space scan's share of its roofline: the least time
the FLOPs and bytes the program's `ssd_scan` ops and their gradients
require (benchmark/flops/ssd.py `program_cost`: the causal half of each
chunk's products, the state products, every operand read and every
result written once; recomputation, whole tiles and the exponentials not
counted) take on the chip, over the device time of everything under
those ops' scopes (the kernels named `ssd_fwd_*` and `ssd_bwd_*` where
there are such, and the softplus, sums, transposes and reductions around
them: leaving those out would leave out part of the work).  Says which
bound it is, and prints calls and milliseconds a step of each kernel
name.  The program is built once more for its shapes (the driver does
not keep it).  A program without the op gets no value."""

import collections
import re

from benchmark.flops import ssd
from benchmark.reduce import xplane

LAYER = "kernels"
MOVES = "train_items_per_s"
UNIT = "%"
SOURCE = "device_trace"


def kernel_spans(run):
    """{kernel name: spans inside the window} of the operations named
    `ssd_*` on the first device."""
    trace = run.reduced
    device = trace.devices[min(trace.devices)]
    by_name = collections.defaultdict(list)
    for op in device.work:
        if op.name.startswith(ssd.KERNEL_PREFIX):
            by_name[re.sub(r"\.\d+$", "", op.name)] += xplane.clip(
                [(op.start, op.end)], *trace.window)
    return by_name


def read(run):
    import jax.numpy as jnp

    times = run.lookup.module("layer_metrics", "ssm_ms_per_step")
    found = times.type_seconds(run)
    steps = run.facts.get("traced_steps")
    if not found or not steps or run.peaks is None:
        return None
    seconds = sum(found[t][0] for t in times.SCAN_OPS if t in found)
    if not seconds:
        return None
    cfg = run.config
    program = run.lookup.module("models", cfg["builder"]).build(
        cfg, run.workload["batch"], train=True)["main"]
    cost = ssd.program_cost(
        program, jnp.dtype(cfg["compute_dtype"]).itemsize)
    if not cost["scans"]:
        return None
    least, bound = ssd.roofline(cost, run.peaks)
    kernels = "; ".join(
        "%s %.1f calls and %.3f ms a step"
        % (name, len(spans) / steps, xplane.length(spans) / steps * 1e3)
        for name, spans in sorted(kernel_spans(run).items()))
    print("ssd: %s; the program's %d scan(s) and their gradients require "
          "%.2f GFLOP, %.1fM exponentials and %.3f GB a step, %.3f ms on "
          "the chip (%s-bound), and took %.3f ms"
          % (kernels or "no kernel of that name", cost["scans"],
             cost["flops"] / 1e9, cost["exps"] / 1e6, cost["bytes"] / 1e9,
             least * 1e3, bound, seconds / steps * 1e3), flush=True)
    return 100.0 * least * steps / seconds
