"""The grouped-product kernels' share of their roofline in a share's
expert layers: the held routed experts' weights that were given a row
(benchmark/flops/latent_moe.py `held_expert_bytes`: with the cell's rows
practically all of them), read once a step application at the chip's HBM
peak, over the device time of the operations whose name starts with
`moe_gmm`, which are the kernels (kernels/grouped_matmul.py).  First
device, traced call, over its step applications.  With 8 rows an expert
the products are memory-bound by two orders of magnitude: the rows in
and out and the multiply-adds are not counted.  Prints calls and
milliseconds a step application for each kernel name."""

import collections
import re

import jax.numpy as jnp

from benchmark.flops import grouped, latent_moe
from benchmark.reduce import xplane

LAYER = "kernels"
MOVES = "decode_tok_per_s"
UNIT = "%"
SOURCE = "device_trace"


def read(run):
    trace, facts = run.reduced, run.facts
    steps = facts.get("share_step_applications")
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
    must = latent_moe.held_expert_bytes(
        run.config, facts["share_batch"],
        jnp.dtype(run.workload["weights"]["dtype"]).itemsize)
    least = must / run.peaks["hbm_bytes_per_s"]
    print("%s: %s; the held experts that are given a row hold %.3f GB, "
          "%.3f ms a step application at the HBM peak"
          % (grouped.KERNEL_PREFIX, "; ".join(
              "%s %.1f calls and %.3f ms a step application"
              % (name, len(spans) / steps,
                 xplane.length(spans) / steps * 1e3)
              for name, spans in sorted(by_name.items())),
             must / 1e9, least * 1e3), flush=True)
    return 100.0 * least * steps / seconds
