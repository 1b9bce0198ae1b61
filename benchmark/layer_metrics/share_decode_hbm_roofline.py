"""The share of the HBM roofline a decode step of the share cell reaches
on the device: the bytes one step must move
(benchmark/flops/latent_moe.py: every weight the chip holds once and the
*live* part of the latent cache, averaged over the call's decode steps,
in the types they are served in) at the chip's published HBM peak, over
the device's time a decode step: the seconds an operation ran inside the
traced call's decoding scan (the second of the call's two `while`
operations on the first device, benchmark/reduce/scans.py), over its
`gen_len - 1` steps.  As `decode_hbm_roofline` is for the GPT-2 cell,
whose count (benchmark/flops/decode.py) reads GPT-2's keys.  Prints the
prefill scan's device time a step beside it."""

import jax.numpy as jnp

from benchmark.flops import latent_moe
from benchmark.reduce import scans, share_ops

LAYER = "kernels"
MOVES = "decode_tok_per_s"
UNIT = "%"
SOURCE = "device_trace"


def read(run):
    facts, peaks = run.facts, run.peaks
    found = share_ops.call_scans(run) if peaks is not None else None
    if found is None or facts.get("share_gen_len", 0) < 2:
        return None
    device = run.reduced.devices[min(run.reduced.devices)]
    prompt, gen = facts["share_prompt_len"], facts["share_gen_len"]
    prefill, decoding = (scans.busy_seconds(device, span) for span in found)
    step = decoding / (gen - 1)
    cfg, workload = run.config, run.workload
    weights = jnp.dtype(workload["weights"]["dtype"]).itemsize
    cache = jnp.dtype(workload["serve_dtype"]).itemsize
    batch = facts["share_batch"]
    # the decode steps write slots prompt .. prompt + gen - 2
    must = latent_moe.mean_step_bytes(cfg, batch, prompt, prompt + gen - 2,
                                      weights, cache)
    read_once = latent_moe.weight_bytes(cfg, batch, weights)
    whole = latent_moe.step_bytes(cfg, batch, cfg["serve_positions"] - 1,
                                  weights, cache)
    print("decode step: %.4f ms on the device (a prefill step %.4f); must "
          "move %.3f GB (weights %.3f, live latents %.3f), %.3f ms at the "
          "HBM peak; the whole cache extent would be %.3f GB"
          % (step * 1e3, prefill / max(prompt - 1, 1) * 1e3, must / 1e9,
             read_once / 1e9, (must - read_once) / 1e9,
             must / peaks["hbm_bytes_per_s"] * 1e3, whole / 1e9),
          flush=True)
    return 100.0 * must / peaks["hbm_bytes_per_s"] / step
