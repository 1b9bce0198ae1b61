"""The grouped-query decode kernel's share of its roofline: the least
time its two products over the live keys and values require a decode
step (benchmark/flops/gqa_window.py `kv_step`: the live slots' keys and
values read once at the chip's HBM peak, or the scores' and the values'
multiply-adds at its bfloat16 peak, whichever is larger, at the mean
position of the call's decode steps, every layer, a ring's 128 slots a
window layer), over the device time of the `gqa_decode_*` kernels
(`gqa_decode_k<block>` over a full layer's extent, `gqa_decode_w<window>`
over a ring) inside the traced call's decoding scan, over its `gen_len -
1` steps.  Says which bound it is, and the two kinds of kernel apart."""

import jax.numpy as jnp

from benchmark.flops import gqa_window, grouped
from benchmark.reduce import long_ops, xplane

LAYER = "kernels"
MOVES = "decode_tok_per_s"
UNIT = "%"
SOURCE = "device_trace"
KERNELS = {"full": "gqa_decode_k", "window": "gqa_decode_w"}


def read(run):
    found = long_ops.decoding_steps(run)
    if found is None or long_ops.operations(run) is None:
        return None
    interval, steps = found
    device = run.reduced.devices[min(run.reduced.devices)]
    itemsize = jnp.dtype(run.workload["serve_dtype"]).itemsize
    batch = run.facts["long_batch"]
    at = long_ops.mean_decode_position(run)
    least, spent, said = 0.0, 0.0, []
    for kind, fragment in sorted(KERNELS.items()):
        seconds, calls = xplane.seconds_named(device, interval, fragment)
        if not calls:
            continue
        cost = gqa_window.kv_step(run.config, batch, at, itemsize, (kind,))
        floor, bound = grouped.roofline(cost, run.peaks)
        least, spent = least + floor, spent + seconds / steps
        said.append("%s* %.4f ms a step (x%.0f), requires %.3f GB and "
                    "%.2f GFLOP, %.4f ms (%s-bound): %.1f%%"
                    % (fragment, seconds / steps * 1e3, calls / steps,
                       cost["bytes"] / 1e9, cost["flops"] / 1e9,
                       floor * 1e3, bound, 100 * floor * steps / seconds))
    if not spent:
        return None
    print("decode kernels over the live keys and values: %s"
          % "; ".join(said), flush=True)
    return 100.0 * least / spent
