"""The share of its roofline that the attention over the one
whole-extent cache reaches: the least time a decoding step's readers
require (benchmark/flops/yoco.py `shared_kv_step`: the live slots' keys
and values read once a reader, the layer that writes the cache and every
cross layer, at the chip's HBM peak, or their products' multiply-adds at
its bfloat16 peak, whichever is larger, at the mean position of the
call's decode steps) over `shared_kv_attn_ms_per_step`'s time: those
layers' `attn_full`, `attn_cross` and `diff_combine` inside the traced
call's decoding scan.  Says which bound it is."""

import jax.numpy as jnp

from benchmark.flops import grouped, yoco
from benchmark.reduce import yoco_ops

LAYER = "kernels"
MOVES = "decode_tok_per_s"
UNIT = "%"
SOURCE = "device_trace"


def read(run):
    attention = run.lookup.module("layer_metrics",
                                  "shared_kv_attn_ms_per_step")
    found = attention.by_scope(run)
    if not found:
        return None
    seconds = attention.counted(found)
    itemsize = jnp.dtype(run.workload["serve_dtype"]).itemsize
    cost = yoco.shared_kv_step(run.config, run.facts["yoco_batch"],
                               yoco_ops.mean_decode_position(run), itemsize)
    least, bound = grouped.roofline(cost, run.peaks)
    print("the shared cache's %d readers: %.4f ms a decoding step; they "
          "require %.3f GB and %.2f GFLOP, %.4f ms on the chip (%s-bound)"
          % (yoco.readers(run.config), seconds * 1e3, cost["bytes"] / 1e9,
             cost["flops"] / 1e9, least * 1e3, bound), flush=True)
    return 100.0 * least / seconds
