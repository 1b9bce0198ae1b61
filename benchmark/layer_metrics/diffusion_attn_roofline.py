"""The block-causal walk's share of its own roofline: the least time the
attention of one pass requires (benchmark/flops/block_diffusion.py
`attention_pass`: the live slots' keys and values of every layer read
once at the chip's HBM peak, or the scores' and the values'
multiply-adds of B queries a head at its bfloat16 peak, whichever is
larger, at the mean position of the call's blocks) over the device time
of the `gqa_decode_*_b<B>` kernels (kernels/gqa_decode.py under
`diffusion_block`) inside the traced calls' scans of blocks, a pass.
The live slots, not the block of slots the kernel fetches: a walk that
reads the whole extent at every position pays for it here.  Says which
bound it is.  Silent where the op took its plain path."""

import jax.numpy as jnp

from benchmark.flops import block_diffusion, grouped
from benchmark.reduce import diffusion_ops

LAYER = "kernels"
MOVES = "decode_tok_per_s"
UNIT = "%"
SOURCE = "device_trace"
KERNEL = "gqa_decode_"


def read(run):
    if diffusion_ops.passes(run) is None:
        return None
    suffix = "_b%d" % run.config["generation"]["block_length"]
    found = diffusion_ops.kernel_pass_seconds(run, KERNEL, suffix)
    if found is None or not found[1]:
        return None
    seconds, calls = found
    facts = run.facts
    slots = block_diffusion.live_slots(
        run.config, facts["diffusion_prompt_len"],
        facts["diffusion_gen_len"])
    cost = block_diffusion.attention_pass(
        run.config, facts["diffusion_batch"], slots,
        jnp.dtype(run.workload["serve_dtype"]).itemsize)
    least, bound = grouped.roofline(cost, run.peaks)
    print("%s*%s: %.4f ms a pass (x%.1f); %.1f live slots a row require "
          "%.3f GB and %.2f GFLOP, %.4f ms on the chip (%s-bound)"
          % (KERNEL, suffix, seconds * 1e3, calls, slots,
             cost["bytes"] / 1e9, cost["flops"] / 1e9, least * 1e3, bound),
          flush=True)
    return 100.0 * least / seconds
