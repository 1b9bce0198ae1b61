"""The chooser's scores' share of their compute roofline while decoding:
the time the index scores' multiply-adds of a decode step take at the
chip's bfloat16 peak (benchmark/flops/sparse_latent.py `index_step`: 2
FLOPs a multiply-add, every index head against every live slot's key at
the mean live length of the call's decode steps, every layer), over the
device time under `dsa_index` inside the traced call's decoding scan,
over its `gen_len - 1` steps.  The time holds the key's write, relu, the
heads' weighted sum and the mask too, so the share reads low rather than
high.

The live keys' bytes are NOT in the bound, though at the HBM peak they
take longer than the multiply-adds at the bfloat16 peak (printed): on
the chip XLA brings a layer's key cache into fast memory with
asynchronous copies that run under other operations (the cache is
`S(1)` in the compiled call, the `copy-done` and `slice-done` waits carry
no scope: my chip runs, PR 38), so the time under the scope does not
hold their way from HBM, and bytes over it read 286-293%.  The bytes are
held to the memory's peak where their time is, in the whole step:
`session_decode_hbm_roofline`."""

import jax.numpy as jnp

from benchmark.flops import sparse_latent
from benchmark.reduce import session_ops

LAYER = "kernels"
MOVES = "decode_tok_per_s"
UNIT = "%"
SOURCE = "device_trace"


def read(run):
    found = session_ops.step_seconds(
        run, lambda kind, inst, inner: "dsa_index" in inner or None)
    if not found:
        return None
    cost = sparse_latent.index_step(
        run.config, run.facts["session_batch"],
        session_ops.mean_decode_position(run),
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
