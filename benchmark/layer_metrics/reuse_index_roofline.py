"""The choosers' scores' share of their compute roofline while decoding,
where only some layers choose: the time the index scores' multiply-adds
of a decode step take at the chip's bfloat16 peak
(benchmark/flops/reuse_latent.py `index_step`: 2 FLOPs a multiply-add,
every index head against every live slot's key at the mean live length of
the call's decode steps, on the layers `indexer_types` calls full and on
no other), over the device time under `dsa_index` inside the traced
call's decoding scan, over its `gen_len - 1` steps.  The time holds the
key's write, relu, the heads' weighted sum and the mask too, so the share
reads low rather than high.  `dsa_index_roofline`'s count
(benchmark/flops/sparse_latent.py) multiplies by every layer and would
read 2.5 times too high here.

The live keys' bytes are NOT in the bound, for the reason
`dsa_index_roofline` gives: XLA brings a layer's key cache into fast
memory under other operations, so the time under the scope does not hold
their way from HBM.  The bytes are held to the memory's peak where their
time is, in the whole step: `reuse_decode_hbm_roofline`."""

import jax.numpy as jnp

from benchmark.flops import reuse_latent
from benchmark.reduce import reuse_ops

LAYER = "kernels"
MOVES = "decode_tok_per_s"
UNIT = "%"
SOURCE = "device_trace"


def read(run):
    found = reuse_ops.step_seconds(
        run, lambda kind, inst, inner: "dsa_index" in inner or None)
    if not found:
        return None
    cost = reuse_latent.index_step(
        run.config, run.facts["reuse_batch"],
        reuse_ops.mean_decode_position(run),
        jnp.dtype(run.workload["index_dtype"]).itemsize)
    least = cost["flops"] / run.peaks["bf16_flops_per_s"]
    step = found[True]
    print("dsa_index: %.3f ms a decode step on the device, on %d layers; "
          "the live index keys' scores require %.1f GFLOP, %.3f ms at the "
          "bfloat16 peak; their %.3f GB of keys (%.3f ms at the HBM peak) "
          "are not in this bound"
          % (step * 1e3, reuse_latent.choosing_layers(run.config),
             cost["flops"] / 1e9, least * 1e3, cost["bytes"] / 1e9,
             cost["bytes"] / run.peaks["hbm_bytes_per_s"] * 1e3), flush=True)
    return 100.0 * least / step
