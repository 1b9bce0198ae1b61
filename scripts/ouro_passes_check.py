"""Ouro's passes one by one against the plain float32 reference, on the
device JAX finds, at the benchmark's configuration: each pass's mean
cross-entropy, mean gate probability and mean exit share, and the loss.
The cell's own check compares one scalar; a pass or the gate left out
could hide inside its tolerance, so this is run once on the chip beside
it (PERF.md section 6, PR 25).

    python scripts/ouro_passes_check.py [--config ouro-2.6b] [--seed 7]

Exits non-zero when the loss or a pass's cross-entropy is further from
the reference's than `--rtol` of it, or a gate's mean than `--gate-rtol`
(the gate reads bfloat16 activations that have been through every block:
its probabilities differ token by token in the second decimal).  Also
prints how far the reference itself moves when its weights are rounded to
three mantissa bits (float8_e4m3): what the configuration's
`reference_tolerance` has to stay under.
"""

import argparse
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--config", default="ouro-2.6b")
    p.add_argument("--seed", type=int, default=7)
    p.add_argument("--batch", type=int, default=1)
    p.add_argument("--rtol", type=float, default=1e-4)
    p.add_argument("--gate-rtol", type=float, default=2e-2)
    args = p.parse_args(argv)
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid
    from benchmark import harness

    lookup = harness.Lookup()
    cfg = lookup.json("configs", args.config)
    device = jax.devices()[0]
    print("platform=%s device_kind=%s config=%s seed=%d"
          % (device.platform, device.device_kind, cfg["name"], args.seed),
          flush=True)
    if cfg["compute_dtype"] == "bfloat16":
        fluid.amp.enable_bf16()
    from paddle_tpu.models.looped_program import (
        build_looped_program, looped_param_names)

    # the forward program alone: no backward, no optimizer
    model = lookup.module("models", cfg["builder"])
    main, startup, loss, passes = build_looped_program(
        args.batch, **model.program_sizes(cfg))
    startup.random_seed = args.seed
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    master = jnp.dtype(cfg["master_dtype"])
    for name in list(scope.local_var_names()):
        value = scope.get(name)
        if isinstance(value, jax.Array) and value.dtype != master \
                and jnp.issubdtype(value.dtype, jnp.floating):
            scope.set(name, value.astype(master))
    feeds = jax.jit(lambda key: model.sample(cfg, args.batch, key))(
        jax.random.PRNGKey(args.seed))

    reference = lookup.module("reference", cfg["reference"])
    params = jax.tree_util.tree_map(
        scope.get, looped_param_names(cfg["num_hidden_layers"]))
    want = jax.jit(lambda p, f: reference.loss_terms(cfg, p, f))(
        params, feeds)
    want = jax.tree_util.tree_map(np.asarray, want)
    coarse = float(jax.jit(lambda p, f: reference.loss(
        cfg, jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.float32), p),
        f))(params, feeds))
    del params

    n = len(passes["ce"])
    fetch = [loss] + [v for k in ("ce", "lambdas", "exit_p")
                      for v in passes[k]]
    got = exe.run(main, feed=feeds, fetch_list=fetch, scope=scope)
    got = [np.asarray(g, np.float32) for g in got]
    rows = [("loss", float(got[0].reshape(-1)[0]), float(want["loss"]))]
    for t in range(n):
        rows.append(("pass %d mean cross-entropy" % (t + 1),
                     float(got[1 + t].mean()), float(want["pass_ce"][t])))
        rows.append(("pass %d mean gate probability" % (t + 1),
                     float(got[1 + n + t].mean()),
                     float(want["lambdas"][t].mean())))
        rows.append(("pass %d mean exit share" % (t + 1),
                     float(got[1 + 2 * n + t].mean()),
                     float(want["p"][t].mean())))
    ok = True
    for name, mine, theirs in rows:
        off = abs(mine - theirs) / abs(theirs)
        ok = ok and off <= (args.gate_rtol if "gate" in name
                            or "exit" in name else args.rtol)
        print("%-32s %.6f reference %.6f off by %.2e" % (name, mine, theirs,
                                                         off), flush=True)
    print("the reference with weights rounded to three mantissa bits: loss "
          "%.6f, off its own float32 by %.2e"
          % (coarse, abs(coarse - float(want["loss"]))
             / float(want["loss"])), flush=True)
    for t in range(n):
        for key, ref_key in (("lambdas", "lambdas"), ("exit_p", "p")):
            index = 1 + (1 if key == "lambdas" else 2) * n + t
            diff = np.abs(got[index].reshape(-1)
                          - want[ref_key][t].reshape(-1)).max()
            print("pass %d %s: largest difference of a token %.2e"
                  % (t + 1, key, diff), flush=True)
    print("%s: losses within %.1e, gates within %.1e"
          % ("ok" if ok else "FAIL", args.rtol, args.gate_rtol))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
