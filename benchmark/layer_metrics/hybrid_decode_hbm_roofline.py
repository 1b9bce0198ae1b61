"""The share of the HBM roofline a decode step of the hybrid cell
reaches on the device: a floor of the bytes one step must move
(benchmark/flops/kimi_delta.py `step_bytes`: every weight the chip holds
outside the routed experts once, every KDA layer's recurrent state and
convolution tail read and written, and the *live* latents of the latent
layers at the mean position of the call's decode steps, in the types
they are served in) at the chip's published HBM peak, over the device's
time a decode step (`decode_device_step_ms`'s: the seconds an operation
ran inside the traced calls' scans of steps, over their steps).

The routed experts are NOT in the bytes: which of the 32 held a step's
128 rows reach is the router's choice at run time and is not in a trace
(an even router's are printed beside), so the share reads low by what
the visited experts weigh, never high, as `state_decode_hbm_roofline`
does."""

import jax.numpy as jnp

from benchmark.flops import kimi_delta
from benchmark.reduce import hybrid_ops

LAYER = "kernels"
MOVES = "decode_tok_per_s"
UNIT = "%"
SOURCE = "device_trace"


def read(run):
    step = hybrid_ops.device_step_seconds(run)
    if step is None:
        return None
    cfg, workload = run.config, run.workload
    weights, caches = (jnp.dtype(t).itemsize for t in (
        workload["weights"]["dtype"], workload["serve_dtype"]))
    batch = run.facts["hybrid_batch"]
    at = hybrid_ops.mean_decode_position(run)
    must = kimi_delta.step_bytes(cfg, batch, at, weights, caches)
    fixed = kimi_delta.fixed_weight_bytes(cfg, batch, weights)
    states = kimi_delta.state_bytes(cfg, batch, weights)
    print("decode step: %.4f ms on the device; must move at least %.3f GB "
          "(states read and written %.3f, %.1f%% of them; weights outside "
          "the routed experts %.3f; live latents %.3f), %.3f ms at the HBM "
          "peak; the routed experts a row reached are not counted (an even "
          "router's %.3f GB)"
          % (step * 1e3, must / 1e9, states / 1e9, 100 * states / must,
             fixed / 1e9, (must - fixed - states) / 1e9,
             must / run.peaks["hbm_bytes_per_s"] * 1e3,
             kimi_delta.held_expert_bytes(cfg, batch, weights) / 1e9),
          flush=True)
    return 100.0 * must / run.peaks["hbm_bytes_per_s"] / step
