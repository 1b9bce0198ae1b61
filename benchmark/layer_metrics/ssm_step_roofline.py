"""The selective scan's step's share of its roofline: the least time
the recurrence's step requires (benchmark/flops/yoco.py `scan_step`:
every Mamba layer's float32 state read once and written once, a
position's operands and `A_log` once, at the chip's HBM peak, or its 7
operations a state element at the bfloat16 peak, whichever is larger)
over the device time under the `selective_scan` op inside the traced
call's decoding scan, a step, whatever implements it (plain `jax.numpy`
today: no kernel's name is looked for).  Says which bound it is."""

import jax.numpy as jnp

from benchmark.flops import grouped, yoco

LAYER = "kernels"
MOVES = "decode_tok_per_s"
UNIT = "%"
SOURCE = "device_trace"
OP_TYPE = "selective_scan"


def read(run):
    found = run.lookup.module("layer_metrics",
                              "ssm_step_ms_per_step").by_op(run)
    if not found or not found.get(OP_TYPE):
        return None
    seconds = found[OP_TYPE]
    itemsize = jnp.dtype(run.workload["weights"]["dtype"]).itemsize
    cost = yoco.scan_step(run.config, run.facts["yoco_batch"], itemsize)
    least, bound = grouped.roofline(cost, run.peaks)
    print("%s: %.4f ms a decoding step over %d layers; the step requires "
          "%.4f GB and %.3f GFLOP, %.4f ms on the chip (%s-bound)"
          % (OP_TYPE, seconds * 1e3, yoco.count(run.config, yoco.MAMBA),
             cost["bytes"] / 1e9, cost["flops"] / 1e9, least * 1e3, bound),
          flush=True)
    return 100.0 * least / seconds
