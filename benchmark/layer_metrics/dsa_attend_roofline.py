"""The sparse attention's share of its roofline while decoding: the
least time its two contractions over the *chosen* latents require a
decode step (benchmark/flops/sparse_latent.py `attend_step`: the chosen
latents read once at the chip's HBM peak, or the scores' and the values'
multiply-adds at its bfloat16 peak, whichever is larger, every layer),
over the device time under `mla_scores` and `mla_values` inside the
traced call's decoding scan, over its `gen_len - 1` steps.  The softmax
and the values' up-projection are in the time and not in the count, so
the share reads low rather than high; the gather that made the chosen
latents contiguous is not in it (`dsa_select_ms_per_step`).  Says which
bound it is."""

import jax.numpy as jnp

from benchmark.flops import grouped, sparse_latent
from benchmark.reduce import session_ops

LAYER = "kernels"
MOVES = "decode_tok_per_s"
UNIT = "%"
SOURCE = "device_trace"
PHASES = ("mla_scores", "mla_values")


def read(run):
    found = session_ops.step_seconds(
        run, lambda kind, inst, inner:
        (kind == "mla_cached_attention"
         and any(p in inner for p in PHASES)) or None)
    if not found:
        return None
    cost = sparse_latent.attend_step(
        run.config, run.facts["session_batch"],
        session_ops.mean_decode_position(run),
        jnp.dtype(run.workload["serve_dtype"]).itemsize)
    least, bound = grouped.roofline(cost, run.peaks)
    step = found[True]
    print("mla_scores + mla_values: %.3f ms a decode step on the device; "
          "the chosen latents' contractions require %.1f GFLOP and %.3f GB "
          "a step, %.3f ms on the chip (%s-bound)"
          % (step * 1e3, cost["flops"] / 1e9, cost["bytes"] / 1e9,
             least * 1e3, bound), flush=True)
    return 100.0 * least / step
