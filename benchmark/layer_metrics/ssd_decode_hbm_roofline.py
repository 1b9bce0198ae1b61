"""The share of the HBM roofline a decode step of the Mamba-2 state cell
reaches on the device: the bytes one step must move
(benchmark/flops/ssd_step.py `step_bytes`: every mamba layer's state and
convolution tail read and written; every weight the chip holds once,
the 18 held experts of every layer among them, which 64 rows of ten
experts each reach whole every step; the *live* keys and values of the
attention layers at the mean position of the call's decode steps, in the
types they are served in) at the chip's published HBM peak, over the
device's time a decode step (`decode_device_step_ms`'s: the seconds an
operation ran inside the traced calls' scans of steps, over their
steps).  The share of the whole step; it cannot read over 100%.
Prints where the step's device time went, by the step Program's ops
(benchmark/reduce/ssd_state_ops.py `step_split`: the mamba mixers' scan,
convolution, gated norm and projections, the expert layers' router,
held experts and shared expert, the attention layers' op and
projections), and the same parts of a call's prefill."""

import jax.numpy as jnp

from benchmark.flops import ssd_step
from benchmark.reduce import ssd_state_ops

LAYER = "kernels"
MOVES = "decode_tok_per_s"
UNIT = "%"
SOURCE = "device_trace"


def read(run):
    step = ssd_state_ops.device_step_seconds(run)
    if step is None:
        return None
    ssd_state_ops.said("a decoding step by the Program's ops, device ms",
                       ssd_state_ops.step_split(run))
    ssd_state_ops.said("a call's prefill by the Program's ops, device ms",
                       ssd_state_ops.prefill_split(run))
    cfg, workload = run.config, run.workload
    weights, caches = (jnp.dtype(t).itemsize for t in (
        workload["weights"]["dtype"], workload["serve_dtype"]))
    batch = run.facts["ssd_state_batch"]
    at = ssd_state_ops.mean_decode_position(run)
    must = ssd_step.step_bytes(cfg, batch, at, weights, caches)
    fixed = ssd_step.weight_bytes(cfg, weights)
    states = ssd_step.state_bytes(cfg, batch, weights)
    experts = weights * cfg["num_hidden_layers"] \
        * ssd_step.held_expert_parameters(cfg)
    print("decode step: %.4f ms on the device; must move %.3f GB (states "
          "and tails read and written %.3f, %.1f%% of it; weights %.3f, of "
          "them the held experts %.3f; live keys and values %.3f), %.3f ms "
          "at the HBM peak"
          % (step * 1e3, must / 1e9, states / 1e9, 100 * states / must,
             fixed / 1e9, experts / 1e9, (must - fixed - states) / 1e9,
             must / run.peaks["hbm_bytes_per_s"] * 1e3), flush=True)
    return 100.0 * must / run.peaks["hbm_bytes_per_s"] / step
