"""The share of the HBM roofline a decode step of the reuse cell reaches
on the device: a floor of the bytes one step must move
(benchmark/flops/reuse_latent.py `step_bytes`: every weight the chip
holds outside the routed experts once, the attention's output gates, the
hyper-connections' float32 parameters and the sinks among them; the
*live* index keys of the layers that choose; the *chosen* latents of
every layer; at the mean position of the call's decode steps, in the
types they are served in) at the chip's published HBM peak, over the
device's time a decode step: the seconds an operation ran inside the
traced call's decoding scan (the second of the call's two `while`
operations on the first device), over its `gen_len - 1` steps.

The routed experts are NOT in the bytes: which of the 16 held a step's 8
rows reach is the router's choice at run time and is not in a trace, and
a count of all 16 would hold bytes the step need not move.  So the share
reads low by what the visited experts weigh, never high."""

import jax.numpy as jnp

from benchmark.flops import reuse_latent
from benchmark.reduce import reuse_ops, scans

LAYER = "kernels"
MOVES = "decode_tok_per_s"
UNIT = "%"
SOURCE = "device_trace"


def read(run):
    facts, peaks = run.facts, run.peaks
    if peaks is None or "reuse_gen_len" not in facts:
        return None
    found = reuse_ops.decoding_steps(run)
    both = reuse_ops.call_scans(run)
    if found is None:
        return None
    interval, steps = found
    device = run.reduced.devices[min(run.reduced.devices)]
    step = scans.busy_seconds(device, interval) / steps
    prefill = scans.busy_seconds(device, both[0]) \
        / max(facts["reuse_prompt_len"] - 1, 1)
    cfg, workload = run.config, run.workload
    itemsizes = [jnp.dtype(t).itemsize for t in (
        workload["weights"]["dtype"], workload["serve_dtype"],
        workload["index_dtype"])]
    batch = facts["reuse_batch"]
    at = reuse_ops.mean_decode_position(run)
    must = reuse_latent.step_bytes(cfg, batch, at, *itemsizes)
    fixed = reuse_latent.fixed_weight_bytes(cfg, batch, itemsizes[0])
    keys = reuse_latent.index_step(cfg, batch, at, itemsizes[2])["bytes"]
    print("decode step: %.4f ms on the device (a prefill step %.4f); must "
          "move at least %.3f GB (weights outside the routed experts %.3f, "
          "live index keys of %d layers %.3f, chosen latents %.3f), %.3f ms "
          "at the HBM peak; the routed experts a row reached are not "
          "counted"
          % (step * 1e3, prefill * 1e3, must / 1e9, fixed / 1e9,
             reuse_latent.choosing_layers(cfg), keys / 1e9,
             (must - fixed - keys) / 1e9,
             must / peaks["hbm_bytes_per_s"] * 1e3), flush=True)
    return 100.0 * must / peaks["hbm_bytes_per_s"] / step
