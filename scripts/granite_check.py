"""granite-4.0-h-micro's state-space layers against the plain float32
reference, on the device JAX finds, at the benchmark's configuration
(published widths, the cell's own batch of 1 x 4096 seeded tokens),
outside any timed window.  The cell's own check compares one scalar, the
loss; a wrong decay or a gradient left out could hide inside its
tolerance, so this is run once on the chip beside it (PERF.md section 6,
PR 31).

    python scripts/granite_check.py [--config granite-4.0-h-micro] [--seed 7]

(a) the `ssd_scan` op and its gradient op, as the cell runs them
    (bfloat16 operands, float32 islands), on seeded [batch, seq, heads *
    head_dim] inputs against the reference's sequential recurrence and
    `jax.grad` of it: Y and the seven gradients (X, Dt, DtBias, ALog, B,
    C, D), relative error (largest difference over the largest entry)
    and cosine; and milliseconds a call of each;
(b) the `causal_conv1d` op and its gradient against the reference's
    four shifted adds;
(c) from one run of the whole program (forward and backward, no
    optimizer) on the start-up weights: the first layer's mixer output,
    the loss, and the last `--last` positions' logits against the
    reference's.
Also prints how far the reference's loss moves when it is computed in
bfloat16 throughout (the precision below the configuration's), which
the configuration's `reference_tolerance` has to tell from float32.
Exits non-zero when a number is outside its limit (the options'
defaults: the limits and their reasons are beside them).
"""

import argparse
import math
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def compare(name, got, want, rtol, min_cos, failures):
    got = np.asarray(got, np.float64).reshape(-1)
    want = np.asarray(want, np.float64).reshape(-1)
    rel = np.abs(got - want).max() / max(np.abs(want).max(), 1e-30)
    cos = float(got @ want / max(np.linalg.norm(got) * np.linalg.norm(want),
                                 1e-30))
    ok = rel <= rtol and cos >= min_cos
    print("%-28s rel %.3e (limit %.1e)  cosine %.6f (limit %.4f)  %s"
          % (name, rel, rtol, cos, min_cos, "ok" if ok else "FAIL"),
          flush=True)
    if not ok:
        failures.append(name)


def ms_a_call(fn, *args, calls=20):
    import jax

    jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / calls * 1e3


def time_paths(x, dt_raw, dt_bias, a_log, b, c, d_skip, states, dy, chunk):
    """The Mosaic kernels against the plain chunked path, each alone at
    the cell's shape, bfloat16 operands."""
    import functools

    import jax
    import jax.numpy as jnp
    from paddle_tpu.kernels import ssd
    from paddle_tpu.ops import ssm

    dt = jax.nn.softplus(dt_raw + dt_bias)
    a = -jnp.exp(a_log) * dt
    kind = jnp.bfloat16
    args = (x.astype(kind), dt, a, b.astype(kind), c.astype(kind), d_skip)
    paths = [("plain", ssm.chunked_scan, ssm.chunked_scan_grad)]
    if jax.default_backend() == "tpu":
        paths.insert(0, ("kernels", ssd.fwd_kernels, ssd.bwd_kernels))
    for name, fwd, bwd in paths:
        fwd_j = jax.jit(functools.partial(fwd, chunk=chunk))
        bwd_j = jax.jit(functools.partial(bwd, chunk=chunk))
        print("ssd %-8s %.3f ms a forward call, %.3f ms a gradient call"
              % (name, ms_a_call(fwd_j, *args),
                 ms_a_call(bwd_j, *args, states, dy.astype(kind))),
              flush=True)


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--config", default="granite-4.0-h-micro")
    p.add_argument("--seed", type=int, default=7)
    p.add_argument("--batch", type=int, default=1)
    p.add_argument("--last", type=int, default=256)
    p.add_argument("--time-paths", action="store_true",
                   help="also time the scan's kernels and its plain "
                        "chunked path, each alone")
    p.add_argument("--search-path", action="append", default=[],
                   help="a directory laid out like benchmark/, searched "
                        "first (a tiny configuration for a rehearsal)")
    # bfloat16 operands (2^-9 a rounding) through products of 256 and
    # 128 terms and a recurrence 16 chunks long: seen on the chip 3e-3
    # to 8e-3 of the largest entry; a decay left out, a chunk's state
    # dropped or a transposed product is off by a tenth or more, and its
    # cosine falls under 0.99
    p.add_argument("--op-rtol", type=float, default=3e-2)
    p.add_argument("--op-cos", type=float, default=0.999)
    # the loss at initialisation is ln(vocab) plus what small logits
    # decide: limits as the configuration's reference_tolerance
    p.add_argument("--loss-rtol", type=float, default=None)
    p.add_argument("--logit-atol", type=float, default=5e-2)
    args = p.parse_args(argv)
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid
    from benchmark import harness
    from paddle_tpu.ops import registry

    lookup = harness.Lookup(args.search_path)
    cfg = lookup.json("configs", args.config)
    reference = lookup.module("reference", cfg["reference"])
    device = jax.devices()[0]
    print("platform=%s device_kind=%s config=%s seed=%d"
          % (device.platform, device.device_kind, cfg["name"], args.seed),
          flush=True)
    if cfg["compute_dtype"] == "bfloat16":
        fluid.amp.enable_bf16()
    failures = []
    heads, dim = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    state, chunk = cfg["mamba_d_state"], cfg["mamba_chunk_size"]
    seq, batch = cfg["sequence_length"], args.batch
    inner = heads * dim

    # -- (a) the scan op ------------------------------------------------------
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 12)
    x = jax.random.normal(keys[0], (batch, seq, inner), jnp.float32)
    b = jax.random.normal(keys[1], (batch, seq, state), jnp.float32) * 0.5
    c = jax.random.normal(keys[2], (batch, seq, state), jnp.float32) * 0.5
    dt_raw = jax.random.normal(keys[3], (batch, seq, heads), jnp.float32)
    dt_bias = jnp.log(jnp.expm1(jnp.exp(jax.random.uniform(
        keys[4], (heads,), jnp.float32, np.log(1e-3), np.log(1e-1)))))
    a_log = jnp.log(jax.random.uniform(keys[5], (heads,), jnp.float32,
                                       1.0, 16.0))
    d_skip = 1.0 + 0.1 * jax.random.normal(keys[6], (heads,), jnp.float32)
    dy = jax.random.normal(keys[7], (batch, seq, inner), jnp.float32)
    attrs = {"num_heads": heads, "chunk_size": chunk}
    slots = ("X", "Dt", "DtBias", "ALog", "B", "C", "D")
    values = (x, dt_raw, dt_bias, a_log, b, c, d_skip)
    info = registry.get_op_info("ssd_scan")

    def forward(*vals):
        out = info.kernel(None, {s: [v] for s, v in zip(slots, vals)}, attrs)
        return out["Y"][0], out["States"][0]

    def backward(y, states, dy, *vals):
        ins = {s: [v] for s, v in zip(slots, vals)}
        ins.update({"O@Y": [y], "O@States": [states], "OG@Y": [dy]})
        out = info.grad_kernel(None, ins, attrs)
        return tuple(out[s + "@GRAD"][0] for s in slots)

    def plain(x, dt_raw, dt_bias, a_log, b, c, d_skip):
        y = reference.recurrence(
            x.reshape(batch, seq, heads, dim),
            jax.nn.softplus(dt_raw + dt_bias), -jnp.exp(a_log), b, c, d_skip,
            segment=math.gcd(seq, 64))
        return y.reshape(batch, seq, inner)

    with jax.default_matmul_precision("highest"):
        want_y, vjp = jax.vjp(jax.jit(plain), *values)
        want_grads = vjp(dy)

    forward_j, backward_j = jax.jit(forward), jax.jit(backward)
    y, states = forward_j(*values)
    grads = backward_j(y, states, dy.astype(y.dtype), *values)
    compare("ssd_scan Y", y, want_y, args.op_rtol, args.op_cos, failures)
    for slot, got, want in zip(slots, grads, want_grads):
        compare("ssd_scan %s@GRAD" % slot, got, want, args.op_rtol,
                args.op_cos, failures)
    print("ssd_scan: %.3f ms a forward call, %.3f ms a gradient call at "
          "[%d, %d, %d x %d], state %d, chunk %d (host clock over 20 calls)"
          % (ms_a_call(forward_j, *values),
             ms_a_call(backward_j, y, states, dy.astype(y.dtype), *values),
             batch, seq, heads, dim, state, chunk), flush=True)
    if args.time_paths:
        time_paths(x, dt_raw, dt_bias, a_log, b, c, d_skip, states, dy,
                   chunk)

    # -- (b) the convolution --------------------------------------------------
    width, channels = cfg["mamba_d_conv"], inner + 2 * state
    cx = jax.random.normal(keys[8], (batch, seq, channels), jnp.float32)
    cw = jax.random.uniform(keys[9], (channels, width), jnp.float32, -.87,
                            .87)
    cb = 0.1 * jax.random.normal(keys[10], (channels,), jnp.float32)
    cdy = jax.random.normal(keys[11], (batch, seq, channels), jnp.float32)
    conv = registry.get_op_info("causal_conv1d")
    cattrs = {"activation": "silu"}
    cins = {"X": [cx], "Filter": [cw], "Bias": [cb]}
    got = jax.jit(lambda i: conv.kernel(None, i, cattrs)["Out"][0])(cins)
    got_grads = jax.jit(lambda i, g: conv.grad_kernel(
        None, dict(i, **{"OG@Out": [g]}), cattrs))(cins, cdy)
    want, cvjp = jax.vjp(reference.causal_conv, cx, cw, cb)
    # float32 throughout, elementwise: sums of 4 terms forward, of 4096
    # for the filter's gradient
    compare("causal_conv1d Out", got, want, 1e-5, 0.999999, failures)
    for slot, w in zip(("X", "Filter", "Bias"), cvjp(cdy)):
        compare("causal_conv1d %s@GRAD" % slot, got_grads[slot + "@GRAD"][0],
                w, 1e-4, 0.99999, failures)

    # -- (c) the program --------------------------------------------------------
    from paddle_tpu.models.hybrid_program import (
        build_granite_hybrid_program, granite_hybrid_param_names)

    model = lookup.module("models", cfg["builder"])
    main_p, startup, loss, parts = build_granite_hybrid_program(
        batch, **model.program_sizes(cfg))
    with fluid.program_guard(main_p, startup):
        fluid.backward.append_backward(loss)
    names = granite_hybrid_param_names(cfg["layer_types"])
    startup.random_seed = main_p.random_seed = args.seed
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    for name in list(scope.local_var_names()):
        value = scope.get(name)
        if isinstance(value, jax.Array) and value.dtype == jnp.bfloat16:
            scope.set(name, value.astype(jnp.float32))
    feeds = model.sample(cfg, batch, jax.random.PRNGKey(args.seed))
    got_loss, got_logits, got_mixer = exe.run(
        main_p, feed=feeds, scope=scope, return_numpy=False,
        fetch_list=[loss, parts["logits"], parts["mixer_out"][0]])
    got_loss = float(np.asarray(got_loss).reshape(-1)[0])
    params = jax.tree_util.tree_map(scope.get, names)

    def first_mixer(params, tokens):
        with jax.default_matmul_precision("highest"):
            block = params["blocks"][0]
            h = reference.rms_norm(
                cfg["embedding_multiplier"] * params["embed"][tokens],
                block["norm_1"], cfg["rms_norm_eps"])
            return reference.MIXERS[cfg["layer_types"][0]](cfg, block, h)

    compare("layer 0 mixer output", got_mixer,
            jax.jit(first_mixer)(params, feeds["tokens"]), args.op_rtol,
            args.op_cos, failures)
    want_loss = float(jax.jit(lambda p, f: reference.loss(cfg, p, f))(
        params, feeds))
    low_loss = float(jax.jit(lambda p, f: reference.loss(
        cfg, p, f, dtype=jnp.bfloat16))(params, feeds))
    tol = args.loss_rtol or cfg["reference_tolerance"]["loss_rel"]
    off = abs(got_loss - want_loss) / abs(want_loss)
    print("loss %.6f, the reference's %.6f: off by %.3e (limit %.1e) %s; "
          "the reference in bfloat16 throughout %.6f: off by %.3e"
          % (got_loss, want_loss, off, tol, "ok" if off <= tol else "FAIL",
             low_loss, abs(low_loss - want_loss) / abs(want_loss)),
          flush=True)
    if off > tol:
        failures.append("loss")
    want_logits = np.asarray(jax.jit(lambda p, t: reference.logits(
        cfg, p, t, last=args.last))(params, feeds["tokens"]))
    got_last = np.asarray(got_logits, np.float32)[:, -args.last:]
    worst = np.abs(got_last - want_logits).max()
    print("logits of the last %d positions: largest difference %.3e "
          "(limit %.1e), root mean square of the reference's %.3e %s"
          % (args.last, worst, args.logit_atol,
             float(np.sqrt(np.mean(np.square(want_logits)))),
             "ok" if worst <= args.logit_atol else "FAIL"), flush=True)
    if worst > args.logit_atol:
        failures.append("logits")

    print("granite_check: %s" % ("FAILED: " + ", ".join(failures)
                                 if failures else "ok"), flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
