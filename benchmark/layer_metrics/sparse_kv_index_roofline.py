"""The chooser's scores' share of their compute roofline while decoding:
the time the index scores' multiply-adds of a decode step take at the
chip's bfloat16 peak (benchmark/flops/sparse_kv.py `index_step`: 2 FLOPs
a multiply-add, every index head against every live slot's key at the
mean live length of the call's decode steps, every layer), over the
device time under `dsa_index` inside the traced call's decoding scan,
over its `gen_len - 1` steps.  The time holds the key's write, relu, the
heads' weighted sum and the mask too, so the share reads low rather than
high.

The live keys' bytes are NOT in the bound, though at the HBM peak they
take fifteen times longer than the multiply-adds at the bfloat16 peak
(printed), for the reason `dsa_index_roofline` gives for the sparse
latent cell: XLA brings a layer's key cache (67 MB, as there) into fast
memory with asynchronous copies that run under other operations
(`copy-done` under no scope, 0.23 ms a step: my chip run, PR 58), so the
time under the scope does not hold their way from HBM, and the larger of
the two bounds over it read 116.6% on this PR's first traced run.  The
bytes are held to the memory's peak where their time is, in the whole
step: `sparse_decode_hbm_roofline`."""

import jax.numpy as jnp

from benchmark.flops import sparse_kv
from benchmark.reduce import sparse_ops

LAYER = "kernels"
MOVES = "decode_tok_per_s"
UNIT = "%"
SOURCE = "device_trace"


def read(run):
    found = sparse_ops.step_seconds(
        run, lambda kind, inst, inner: "dsa_index" in inner or None)
    if not found:
        return None
    cost = sparse_kv.index_step(
        run.config, run.facts["sparse_batch"],
        sparse_ops.mean_decode_position(run),
        jnp.dtype(run.workload["index_dtype"]).itemsize)
    least = cost["flops"] / run.peaks["bf16_flops_per_s"]
    step = found[True]
    print("dsa_index: %.3f ms a decode step on the device; the live index "
          "keys' scores require %.1f GFLOP, %.3f ms at the bfloat16 peak; "
          "their %.3f GB of keys (%.3f ms at the HBM peak) come into fast "
          "memory under other operations and are not in this time"
          % (step * 1e3, cost["flops"] / 1e9, least * 1e3,
             cost["bytes"] / 1e9,
             cost["bytes"] / run.peaks["hbm_bytes_per_s"] * 1e3), flush=True)
    return 100.0 * least / step
