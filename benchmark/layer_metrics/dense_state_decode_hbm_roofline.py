"""The share of the HBM roofline a decode step of the dense state cell
reaches on the device: the bytes one step must move
(benchmark/flops/olmo_hybrid.py `step_bytes`: every weight the chip
holds once but the embedding, of which the rows looked up; every linear
layer's recurrent state and convolution tail read and written; the
*live* keys and values of the full layers at the mean position of the
call's decode steps, in the types they are served in) at the chip's
published HBM peak, over the device's time a decode step
(`decode_device_step_ms`'s: the seconds an operation ran inside the
traced calls' scans of steps, over their steps).  The share of the whole
step: a dense model has no part a trace cannot count, so nothing is left
out of the bytes and the share cannot read over 100%."""

import jax.numpy as jnp

from benchmark.flops import olmo_hybrid
from benchmark.reduce import dense_state_ops

LAYER = "kernels"
MOVES = "decode_tok_per_s"
UNIT = "%"
SOURCE = "device_trace"


def read(run):
    step = dense_state_ops.device_step_seconds(run)
    if step is None:
        return None
    cfg, workload = run.config, run.workload
    weights, caches = (jnp.dtype(t).itemsize for t in (
        workload["weights"]["dtype"], workload["serve_dtype"]))
    batch = run.facts["dense_state_batch"]
    at = dense_state_ops.mean_decode_position(run)
    must = olmo_hybrid.step_bytes(cfg, batch, at, weights, caches)
    fixed = olmo_hybrid.weight_bytes(cfg, batch, weights)
    states = olmo_hybrid.state_bytes(cfg, batch, weights)
    print("decode step: %.4f ms on the device; must move %.3f GB (weights "
          "%.3f; states and tails read and written %.3f, %.1f%% of it; "
          "live keys and values %.3f), %.3f ms at the HBM peak"
          % (step * 1e3, must / 1e9, fixed / 1e9, states / 1e9,
             100 * states / must, (must - fixed - states) / 1e9,
             must / run.peaks["hbm_bytes_per_s"] * 1e3), flush=True)
    return 100.0 * must / run.peaks["hbm_bytes_per_s"] / step
