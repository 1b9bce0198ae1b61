"""The share of the HBM roofline a decode step of the long-session cell
reaches on the device: a floor of the bytes one step must move
(benchmark/flops/gqa_window.py `step_bytes`: every weight the chip holds
outside the routed experts once and the *live* keys and values, at the
mean position of the call's decode steps, in the types they are served
in) at the chip's published HBM peak, over the device's time a decode
step: the seconds an operation ran inside the traced call's decoding
scan (the second of the call's two `while` operations on the first
device), over its `gen_len - 1` steps.

The routed experts are NOT in the bytes: which of the 8 held a step's 8
rows reach (3 to 4 a layer, about 0.25 GB a layer) is the router's
choice at run time and is not in a trace, and a count of all 8 would
hold bytes the step need not move.  So the share reads low by what the
visited experts weigh (about a quarter more bytes), never high, as
`session_decode_hbm_roofline` does."""

import jax.numpy as jnp

from benchmark.flops import gqa_window
from benchmark.reduce import long_ops, scans

LAYER = "kernels"
MOVES = "decode_tok_per_s"
UNIT = "%"
SOURCE = "device_trace"


def read(run):
    facts, peaks = run.facts, run.peaks
    if peaks is None or "long_gen_len" not in facts:
        return None
    found = long_ops.decoding_steps(run)
    both = long_ops.call_scans(run)
    if found is None:
        return None
    interval, steps = found
    device = run.reduced.devices[min(run.reduced.devices)]
    step = scans.busy_seconds(device, interval) / steps
    prefill = scans.busy_seconds(device, both[0]) \
        / max(facts["long_prompt_len"] - 1, 1)
    cfg, workload = run.config, run.workload
    weights, caches = (jnp.dtype(t).itemsize for t in (
        workload["weights"]["dtype"], workload["serve_dtype"]))
    batch = facts["long_batch"]
    at = long_ops.mean_decode_position(run)
    must = gqa_window.step_bytes(cfg, batch, at, weights, caches)
    fixed = gqa_window.fixed_weight_bytes(cfg, batch, weights)
    print("decode step: %.4f ms on the device (a prefill step %.4f); must "
          "move at least %.3f GB (weights outside the routed experts %.3f, "
          "live keys and values %.3f, %.1f%% of them), %.3f ms at the HBM "
          "peak; the routed experts a row reached are not counted"
          % (step * 1e3, prefill * 1e3, must / 1e9, fixed / 1e9,
             (must - fixed) / 1e9, 100 * (must - fixed) / must,
             must / peaks["hbm_bytes_per_s"] * 1e3), flush=True)
    return 100.0 * must / peaks["hbm_bytes_per_s"] / step
