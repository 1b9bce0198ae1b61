"""The share of the HBM roofline a decode step of the
decoder-hybrid-decoder cell reaches on the device: the bytes one step
must move (benchmark/flops/yoco.py `step_bytes`: every weight once, the
tied head's table among them; the one whole-extent cache's live slots
once a reader, eight of them; the rings' live slots; the scan states and
the convolutions' tails read and written; at the mean position of the
call's decode steps, in the types they are served in) at the chip's
published HBM peak, over the device's time a decode step: the seconds an
operation ran inside the traced call's decoding scan (the second of the
call's two `while` operations on the first device), over its `gen_len -
1` steps."""

import jax.numpy as jnp

from benchmark.flops import yoco
from benchmark.reduce import scans, yoco_ops

LAYER = "kernels"
MOVES = "decode_tok_per_s"
UNIT = "%"
SOURCE = "device_trace"


def read(run):
    facts, peaks = run.facts, run.peaks
    if peaks is None or "yoco_gen_len" not in facts:
        return None
    found = yoco_ops.decoding_steps(run)
    if found is None:
        return None
    interval, steps = found
    device = run.reduced.devices[min(run.reduced.devices)]
    step = scans.busy_seconds(device, interval) / steps
    cfg, workload = run.config, run.workload
    weights, caches = (jnp.dtype(t).itemsize for t in (
        workload["weights"]["dtype"], workload["serve_dtype"]))
    batch = facts["yoco_batch"]
    at = yoco_ops.mean_decode_position(run)
    must = yoco.step_bytes(cfg, batch, at, weights, caches)
    shared = yoco.shared_kv_step(cfg, batch, at, caches)["bytes"]
    print("decode step: %.4f ms on the device; must move %.3f GB (weights "
          "%.3f, the shared cache's %d readers %.3f, %.1f%% of it, rings "
          "%.3f, scan states and tails %.3f), %.3f ms at the HBM peak"
          % (step * 1e3, must / 1e9, yoco.weight_bytes(cfg, weights) / 1e9,
             yoco.readers(cfg), shared / 1e9, 100 * shared / must,
             yoco.window_step(cfg, batch, at, caches)["bytes"] / 1e9,
             (yoco.scan_step(cfg, batch, weights)["bytes"]
              + yoco.tail_bytes(cfg, batch, weights)) / 1e9,
             must / peaks["hbm_bytes_per_s"] * 1e3), flush=True)
    return 100.0 * must / peaks["hbm_bytes_per_s"] / step
