"""The latent attention's share of its roofline while decoding: the
least time its two contractions over the cache require a decode step
(benchmark/flops/latent_moe.py `mla_step`: the live slots' latents read
once at the chip's HBM peak, or the scores' and the values' multiply-adds
at its bfloat16 peak, whichever is larger, at the mean live length of
the call's decode steps, every layer), over the device time under the
`mla_cached_attention` op inside the traced call's decoding scan (the
second of its two `while` operations), over its `gen_len - 1` steps.
The time is the whole op's: the cache's update, the absorbed query, the
softmax and the up-projection are in it and not in the count, so the
share reads low rather than high.  Says which bound it is."""

import jax.numpy as jnp

from benchmark.flops import grouped, latent_moe
from benchmark.reduce import share_ops

LAYER = "kernels"
MOVES = "decode_tok_per_s"
UNIT = "%"
SOURCE = "device_trace"
OP_TYPE = "mla_cached_attention"


def read(run):
    found = share_ops.decoding_steps(run)
    if found is None or share_ops.operations(run) is None:
        return None
    interval, steps = found
    under = share_ops.seconds(
        run, lambda kind, inst, inner: kind == OP_TYPE or None, interval)
    if not under:
        return None
    facts = run.facts
    prompt, gen = facts["share_prompt_len"], facts["share_gen_len"]
    # the decode steps write slots prompt .. prompt + gen - 2
    cost = latent_moe.mla_step(
        run.config, facts["share_batch"], (2 * prompt + gen - 2) / 2.0,
        jnp.dtype(run.workload["serve_dtype"]).itemsize)
    least, bound = grouped.roofline(cost, run.peaks)
    step = under[True][0] / steps
    print("%s: %.3f ms a decode step on the device (x%.0f operations); "
          "its contractions over the live latents require %.1f GFLOP and "
          "%.3f GB a step, %.3f ms on the chip (%s-bound)"
          % (OP_TYPE, step * 1e3, under[True][1] / steps,
             cost["flops"] / 1e9, cost["bytes"] / 1e9, least * 1e3, bound),
          flush=True)
    return 100.0 * least / step
