"""The decode kernel's share of its roofline over ungrouped heads: the
least time the full layers' two products over the live keys and values
require a decode step of the dense state cell
(benchmark/flops/olmo_hybrid.py `kv_step`: the live slots' keys and
values of 30 key/value heads read once at the chip's HBM peak, or the
scores' and the values' multiply-adds of the one query each at its
bfloat16 peak, whichever is larger, at the mean position of the call's
decode steps) over the device time of the `gqa_decode_*` kernels
(kernels/gqa_decode.py) inside the traced calls' scans of steps, a step.
Says which bound it is.  Silent where the op took its plain path."""

import jax.numpy as jnp

from benchmark.flops import grouped, olmo_hybrid
from benchmark.reduce import dense_state_ops

LAYER = "kernels"
MOVES = "decode_tok_per_s"
UNIT = "%"
SOURCE = "device_trace"
KERNEL = "gqa_decode_"


def read(run):
    found = dense_state_ops.kernel_step_seconds(run, KERNEL)
    if found is None or not found[1]:
        return None
    seconds, calls = found
    cost = olmo_hybrid.kv_step(
        run.config, run.facts["dense_state_batch"],
        dense_state_ops.mean_decode_position(run),
        jnp.dtype(run.workload["serve_dtype"]).itemsize)
    least, bound = grouped.roofline(cost, run.peaks)
    print("%s*: %.4f ms a decoding step (x%.1f); the live keys and values "
          "require %.3f GB and %.2f GFLOP, %.4f ms on the chip (%s-bound)"
          % (KERNEL, seconds * 1e3, calls, cost["bytes"] / 1e9,
             cost["flops"] / 1e9, least * 1e3, bound), flush=True)
    return 100.0 * least / seconds
