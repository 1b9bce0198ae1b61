"""The share of the HBM roofline a pass of the block-diffusion cell
reaches on the device: the bytes one pass must move
(benchmark/flops/block_diffusion.py `pass_cost`: every layer's weights
outside the experts, the experts that have a row, the head in the share
of passes that denoise, of the embedding the rows looked up; the *live*
keys and values of every layer at the mean position of the call's
blocks, in the types they are served in) at the chip's published HBM
peak, over the device's time a pass (`diffusion_pass_ms`'s).  The share
of the whole pass: logits, scores and activations are not in the bytes,
so the share cannot read over 100%.  Says which bound the floor is."""

import jax.numpy as jnp

from benchmark.flops import block_diffusion, grouped
from benchmark.reduce import diffusion_ops

LAYER = "kernels"
MOVES = "decode_tok_per_s"
UNIT = "%"
SOURCE = "device_trace"


def read(run):
    each = diffusion_ops.pass_seconds(run)
    if each is None:
        return None
    cfg, workload, facts = run.config, run.workload, run.facts
    weights, caches = (jnp.dtype(t).itemsize for t in (
        workload["weights"]["dtype"], workload["serve_dtype"]))
    denoise, commit = diffusion_ops.passes(run)
    slots = block_diffusion.live_slots(cfg, facts["diffusion_prompt_len"],
                                       facts["diffusion_gen_len"])
    cost = block_diffusion.pass_cost(
        cfg, facts["diffusion_batch"], slots, weights, caches,
        denoise / (denoise + commit))
    least, bound = grouped.roofline(cost, run.peaks)
    print("diffusion pass: %.4f ms on the device; must move %.3f GB (%.1f "
          "live slots a row in the mean) and do %.3f TFLOP, %.3f ms at the "
          "HBM peak and %.3f ms at the bfloat16 peak (%s-bound)"
          % (each * 1e3, cost["bytes"] / 1e9, slots, cost["flops"] / 1e12,
             cost["bytes"] / run.peaks["hbm_bytes_per_s"] * 1e3,
             cost["flops"] / run.peaks["bf16_flops_per_s"] * 1e3, bound),
          flush=True)
    return 100.0 * cost["bytes"] / run.peaks["hbm_bytes_per_s"] / each
