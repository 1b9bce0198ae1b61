"""Explicit data-parallel step with bucketed ring-allreduce overlap.

The fused GSPMD path (`parallel.make_parallel_step`) hands XLA the
whole step and lets SPMD partitioning insert one all-reduce per
gradient use site — correct, but the reduction of the first layer's
gradient then waits on the whole backward.  This module builds the
classic DDP schedule instead (reference: the gradient ring in
MultiGradientMachine.h:61-83): forward+backward run per device on the
local batch shard inside `shard_map`, gradients ring-reduce in
BUCKETS as the backward produces them (last-produced grads first),
and the optimizer segment applies the reduced means identically on
every device.  Each bucket is an independent `ring.ring_allreduce`
chain, so the XLA scheduler can overlap bucket k's ICI hops with the
backward compute still producing bucket k+1's members.

Semantics: the per-device loss is the LOCAL batch mean; with equal
shards the mean of local means equals the global mean, and dividing
the ring-summed gradients by dp yields exactly the fused path's
gradients — the parity test in tests/test_spmd.py holds to float
tolerance.  The mode is restricted to layouts where that equivalence
is exact: a pure-dp mesh, replicated parameters (no zero1), and no
train-mode batch_norm (its cross-batch statistics would silently
become per-shard statistics).  `overlap_supported` is the gate;
`SpmdTrainer` falls back to the fused GSPMD path when it says no.
"""

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..fluid.executor import ExecContext, apply_op, RNG_STATE_NAME
from ..jit import FunctionalProgram
from ..obs import trace as obs_trace
from ..ops.optimizer_ops import UPDATE_OPS
from ..parallel import sharding as psharding
from ..parallel.ring import bucketed_allreduce

__all__ = ["make_overlapped_dp_step", "overlap_supported",
           "DEFAULT_BUCKET_BYTES"]

# 4 MiB buckets: large enough to amortize ring latency per hop, small
# enough that several buckets exist to overlap (the DDP default class)
DEFAULT_BUCKET_BYTES = 4 << 20


def _split_point(ops):
    """(first optimizer-op index, grad names in production order).

    The split is where every gradient the optimizer consumes exists
    but no parameter has been updated yet — the reduction seam."""
    split = None
    grads = set()
    for i, od in enumerate(ops):
        if od.type in UPDATE_OPS:
            if split is None:
                split = i
            grads.update(n for n in od.input("Grad") if n)
    if split is None:
        return None, []
    order = []
    seen = set()
    for od in ops[:split]:
        for n in od.output_names():
            if n in grads and n not in seen:
                seen.add(n)
                order.append(n)
    return split, order


def overlap_supported(program, mesh, dp_axis="dp", zero_stage=0):
    """(ok, reason) — whether the explicit overlapped-dp schedule is
    exactly equivalent to the fused GSPMD step for this program/mesh.
    """
    axes = dict(mesh.shape)
    if int(axes.get(dp_axis, 1)) <= 1:
        return False, "mesh has no %s axis wider than 1" % dp_axis
    others = [a for a, s in axes.items()
              if a != dp_axis and int(s) > 1]
    if others:
        return False, ("mesh is not pure data-parallel (axes %s also "
                       "shard)" % ",".join(sorted(others)))
    if zero_stage >= 1:
        return False, ("zero%d shards optimizer state over dp — the "
                       "GSPMD reduce-scatter path owns that layout"
                       % zero_stage)
    ops = list(program.desc.block(0).ops)
    split, grad_order = _split_point(ops)
    if split is None:
        return False, "program has no optimizer op (no reduction seam)"
    if not grad_order:
        return False, "optimizer ops consume no gradients"
    for od in ops[:split]:
        if od.type == "batch_norm" and not od.attr("is_test", False):
            return False, ("train-mode batch_norm computes cross-batch "
                           "statistics; per-shard execution would "
                           "change them")
    return True, None


def make_overlapped_dp_step(program, feed_names, fetch_names, mesh,
                            state_template, dp_axis="dp",
                            bucket_bytes=DEFAULT_BUCKET_BYTES,
                            donate_state=None, feed_specs=None,
                            skip_reduce=False):
    """Compile the program into the overlapped explicit-dp step.

    Returns (step, state_shardings) with the `make_parallel_step`
    contract: step(state, feeds, rng) -> (fetches, new_state), state
    replicated (pure dp), feeds sharded on their batch dim, scalar
    fetches returned as the cross-shard mean (== the global-batch
    value).  Callers gate on `overlap_supported` first.

    donate_state: None (default) routes through the donation plan —
    FLAGS_donation=off disables state donation, any other mode keeps
    it (analysis.state_donation); an explicit bool overrides (the
    compute-only comm twin passes False to keep its state alive).

    skip_reduce=True elides the bucketed ring entirely — the
    optimizer applies LOCAL gradients, so the result is numerically
    WRONG across shards.  It exists for one purpose: the compute-only
    twin `obs.comm.overlap_report` times against the real step, so
    `step_wall - compute_only_wall` isolates the EXPOSED comm time
    (pair it with donate_state=False to keep the measured trainer's
    state buffers alive).
    """
    if donate_state is None:
        from ..analysis.alias import state_donation

        donate_state = state_donation()
    ok, reason = overlap_supported(program, mesh, dp_axis=dp_axis)
    if not ok:
        raise ValueError("overlapped dp step unsupported: %s" % reason)
    fp = FunctionalProgram(program, feed_names, fetch_names)
    ops = fp.ops
    split, grad_order = _split_point(ops)
    reduce_order = list(reversed(grad_order))
    feed_specs = feed_specs or {}

    def local_step(state, feeds, rng):
        env = dict(state)
        env.update(feeds)
        ctx = ExecContext(None, program, fp.block_idx, env, rng=rng)
        for i, od in enumerate(ops):
            if i == split:
                grads = {g: env[g] for g in grad_order if g in env}
                obs_trace.instant("comm/reduce_seam", cat="comm",
                                  n_grads=len(grads),
                                  bucket_bytes=int(bucket_bytes),
                                  skip_reduce=bool(skip_reduce))
                if not skip_reduce:
                    env.update(bucketed_allreduce(
                        grads, bucket_bytes, axis_name=dp_axis,
                        mean=True, order=[g for g in reduce_order
                                          if g in grads]))
            apply_op(ctx, od)
        new_state = dict(state)
        for n in fp.state_out_names:
            if n in env:
                new_state[n] = env[n]
        if ctx.rng is not None and RNG_STATE_NAME in state:
            new_state[RNG_STATE_NAME] = ctx.rng
        fetches = []
        for n in fp.fetch_names:
            v = env[n]
            # scalar losses/metrics: local-batch mean -> global mean
            if getattr(v, "size", 0) == 1:
                v = jax.lax.pmean(v, dp_axis)
            fetches.append(v)
        return fetches, new_state

    state_specs = {n: P() for n in state_template}
    state_shardings = {n: NamedSharding(mesh, P())
                       for n in state_template}

    def step(state, feeds, rng):
        in_feed_specs = {
            n: feed_specs.get(n, psharding.batch_spec(
                getattr(v, "shape", ()), mesh, dp_axis))
            for n, v in feeds.items()
        }
        sharded = psharding.shard_map_norep(
            local_step, mesh=mesh,
            in_specs=(state_specs, in_feed_specs, P()),
            out_specs=([P()] * len(fp.fetch_names), state_specs))
        return sharded(state, feeds, rng)

    jitted = jax.jit(
        step,
        in_shardings=(state_shardings, None, None),
        out_shardings=(None, state_shardings),
        donate_argnums=(0,) if donate_state else (),
    )
    return jitted, state_shardings
