"""The sparse attention's share of its roofline while decoding: the
least time the attention over the *chosen* slots requires a decode step
(benchmark/flops/sparse_kv.py `attend_step`: the chosen keys and values
read once at the chip's HBM peak, or the scores' and the values'
multiply-adds at its bfloat16 peak, whichever is larger, every layer),
over the device time under `kv_gather` and `attn_sparse` inside the
traced call's decoding scan, over its `gen_len - 1` steps.  The gather
is in the time: it reads the chosen entries and writes them again
before the kernel reads them a third time, where a kernel that read
chosen slots in place would move them once, so the share is at most a
third while the gather stands.  Says which bound it is."""

import jax.numpy as jnp

from benchmark.flops import grouped, sparse_kv
from benchmark.reduce import sparse_ops

LAYER = "kernels"
MOVES = "decode_tok_per_s"
UNIT = "%"
SOURCE = "device_trace"
PHASES = ("kv_gather", "attn_sparse")


def read(run):
    found = sparse_ops.step_seconds(
        run, lambda kind, inst, inner:
        (kind == "cached_attention"
         and any(p in inner for p in PHASES)) or None)
    if not found:
        return None
    cost = sparse_kv.attend_step(
        run.config, run.facts["sparse_batch"],
        sparse_ops.mean_decode_position(run),
        jnp.dtype(run.workload["serve_dtype"]).itemsize)
    least, bound = grouped.roofline(cost, run.peaks)
    step = found[True]
    print("kv_gather + attn_sparse: %.3f ms a decode step on the device; "
          "the chosen slots' attention requires %.1f GFLOP and %.3f GB a "
          "step, %.3f ms on the chip (%s-bound)"
          % (step * 1e3, cost["flops"] / 1e9, cost["bytes"] / 1e9,
             least * 1e3, bound), flush=True)
    return 100.0 * least / step
