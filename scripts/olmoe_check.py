"""OLMoE's expert layer against the plain float32 reference, on the device
JAX finds, at the benchmark's configuration (published widths, all 64
experts, the cell's own batch of 1 x 4096 seeded tokens), outside any
timed window.  The cell's own check compares one scalar, the loss; a
dropped assignment or a wrong gradient could hide inside its tolerance,
so this is run once on the chip beside it (PERF.md section 6, PR 29).

    python scripts/olmoe_check.py [--config olmoe-1b-7b] [--seed 7]

(a) the share of tokens whose experts are the reference's own, and for
    the others the reference's margin between the last expert taken and
    the first left out: the program's router reads bfloat16 activations,
    so near-ties change places (`--same-routing` of the tokens have to
    agree, and no token that does not may have a margin above
    `--tie-margin`: that one would be wrong arithmetic, not a tie);
(b) with the program's indices handed to the reference: the logits, the
    expert layer's output, L_lb, L_z, the cross-entropy and the loss;
(c) the gradients of one expert's three matrices, of the router and of
    W_q against `jax.grad` of the reference (same indices);
(d) rows an expert: min, mean, max, and their sum, which is
    tokens * experts a token when nothing is dropped.
Also prints how far the reference's loss moves when it is computed in
bfloat16 throughout (the precision below the configuration's: bfloat16
sums and statistics), when its weights are rounded to three mantissa
bits (float8_e4m3) and when every token's last expert is dropped: what
the configuration's `reference_tolerance` has to tell from float32.  Exits non-zero when a number is
outside its limit (the options' defaults).
"""

import argparse
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--config", default="olmoe-1b-7b")
    p.add_argument("--seed", type=int, default=7)
    p.add_argument("--batch", type=int, default=1)
    p.add_argument("--expert", type=int, default=5)
    p.add_argument("--search-path", action="append", default=[],
                   help="a directory laid out like benchmark/, searched "
                        "first (a tiny configuration for a rehearsal)")
    p.add_argument("--same-routing", type=float, default=0.93)
    p.add_argument("--tie-margin", type=float, default=5e-3)
    p.add_argument("--loss-rtol", type=float, default=1e-5)
    p.add_argument("--aux-rtol", type=float, default=1e-3)
    p.add_argument("--logit-atol", type=float, default=5e-2)
    p.add_argument("--grad-rtol", type=float, default=5e-2)
    args = p.parse_args(argv)
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid
    from benchmark import harness

    lookup = harness.Lookup(args.search_path)
    cfg = lookup.json("configs", args.config)
    device = jax.devices()[0]
    print("platform=%s device_kind=%s config=%s seed=%d"
          % (device.platform, device.device_kind, cfg["name"], args.seed),
          flush=True)
    if cfg["compute_dtype"] == "bfloat16":
        fluid.amp.enable_bf16()
    from paddle_tpu.models.moe_program import (build_olmoe_program,
                                               olmoe_param_names)

    # forward and backward, no optimizer
    model = lookup.module("models", cfg["builder"])
    main, startup, loss, parts = build_olmoe_program(
        args.batch, **model.program_sizes(cfg))
    names = olmoe_param_names(cfg["num_hidden_layers"])
    block0 = names["blocks"][0]
    checked = [block0[w] for w in ("w_gate", "w_up", "w_down", "router",
                                   "wq")]
    with fluid.program_guard(main, startup):
        grads = dict((p.name, g) for p, g in
                     fluid.backward.append_backward(loss))
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

    layers = cfg["num_hidden_layers"]
    fetch = [loss, parts["ce"], parts["lb"], parts["z"], parts["logits"]] \
        + parts["moe_out"] + parts["router_logits"] + parts["top_idx"] \
        + parts["counts"] + [grads[n] for n in checked]
    out = exe.run(main, feed=feeds, fetch_list=fetch, scope=scope,
                  return_numpy=False)
    e = args.expert
    got_grads = [np.asarray(g[e] if g.ndim == 3 else g, np.float32)
                 for g in out[5 + 4 * layers:]]
    mine = {key: float(np.asarray(v, np.float32).reshape(-1)[0])
            for key, v in zip(("loss", "ce", "lb", "z"), out)}
    mine["logits"] = np.asarray(out[4]).astype(np.float32)
    for i, key in enumerate(("moe_out", "router_logits", "top_idx",
                             "counts")):
        mine[key] = [np.asarray(v) for v in
                     out[5 + i * layers:5 + (i + 1) * layers]]
    mine["moe_out"] = [v.astype(np.float32) for v in mine["moe_out"]]
    del out
    # the scope keeps only the parameters from here on
    keep = set(jax.tree_util.tree_leaves(names))
    for name in list(scope.local_var_names()):
        if name not in keep:
            scope.erase(name)

    reference = lookup.module("reference", cfg["reference"])
    params = jax.tree_util.tree_map(scope.get, names)
    ok = True

    def line(ok_now, text):
        print("%s %s" % ("ok  " if ok_now else "FAIL", text), flush=True)
        return ok_now

    # (a) the reference's own routing
    own = jax.jit(lambda p, f: reference.loss_terms(cfg, p, f))(params, feeds)
    own_idx = np.sort(np.concatenate(
        [np.asarray(i) for i in own["indices"]]), axis=1)
    my_idx = np.sort(np.concatenate(mine["top_idx"]), axis=1)
    same = (own_idx == my_idx).all(axis=1)
    own_logits = jnp.concatenate(own["router_logits"])
    probs = np.sort(np.asarray(jax.nn.softmax(own_logits, axis=-1)),
                    axis=1)[:, ::-1]
    k = cfg["num_experts_per_tok"]
    margin = probs[:, k - 1] - probs[:, k]
    ties = same.all() or margin[~same].max() <= args.tie_margin
    ok &= line(same.mean() >= args.same_routing and ties,
               "(a) %d of %d tokens (%.2f%%) take the reference's %d "
               "experts; router logits off by at most %.2e"
               % (same.sum(), same.size, 100 * same.mean(), k,
                  np.abs(np.concatenate(mine["router_logits"])
                         - np.asarray(own_logits)).max()))
    if not same.all():
        off = margin[~same]
        print("    the others' margin between expert %d and %d in the "
              "reference's probabilities: median %.2e, largest %.2e (all "
              "tokens: median %.2e); loss with the reference's own "
              "routing %.6f"
              % (k, k + 1, np.median(off), off.max(), np.median(margin),
                 float(own["loss"])), flush=True)
    del own

    # (b) the program's routing handed to the reference
    idx = [jnp.asarray(i) for i in mine["top_idx"]]
    want = jax.jit(lambda p, f, i: reference.loss_terms(cfg, p, f, i))(
        params, feeds, idx)
    for key in ("loss", "ce", "lb", "z"):
        theirs = float(want[key])
        off = abs(mine[key] - theirs) / abs(theirs)
        ok &= line(off <= (args.loss_rtol if key in ("loss", "ce")
                           else args.aux_rtol),
                   "(b) %-4s %.6f reference %.6f off by %.2e"
                   % (key, mine[key], theirs, off))
    for key, got, ref in (
            [("logits", mine["logits"], want["logits"])]
            + [("moe_out", m, w)
               for m, w in zip(mine["moe_out"], want["moe_out"])]):
        ref = np.asarray(ref).reshape(got.shape)
        diff = np.abs(got - ref)
        ok &= line(diff.max() <= args.logit_atol,
                   "(b) %-7s largest difference %.2e, root mean square "
                   "%.2e, of values of root mean square %.3f"
                   % (key, diff.max(), np.sqrt(np.mean(diff ** 2)),
                      np.sqrt(np.mean(ref ** 2))))
    # what the cell's tolerance has to tell from the float32 reference
    own_loss = float(want["loss"])
    del want

    def off_own(value):
        return abs(float(value) - own_loss) / own_loss

    narrow = jax.jit(lambda p, f, i: reference.loss_terms(
        cfg, p, f, i, dtype=jnp.bfloat16)["loss"])(params, feeds, idx)
    coarse = jax.jit(lambda p, f, i: reference.loss_terms(
        cfg, jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.float32), p),
        f, i)["loss"])(params, feeds, idx)
    dropped = jax.jit(lambda p, f, i: reference.loss_terms(
        cfg, p, f, i)["loss"])(params, feeds, [i[:, :-1] for i in idx])
    print("    the reference in bfloat16 throughout (weights, sums, "
          "softmax, loss): loss %.6f, off its own float32 by %.2e; with "
          "weights rounded to three mantissa bits: %.6f, off by %.2e; "
          "with every token's last expert dropped: %.6f, off by %.2e"
          % (float(narrow), off_own(narrow), float(coarse), off_own(coarse),
             float(dropped), off_own(dropped)), flush=True)

    # (c) gradients, by the checked pieces alone
    def pieces_loss(pieces, p, f, i):
        block = dict(p["blocks"][0])
        for w in ("w_gate", "w_up", "w_down"):
            block[w] = block[w].at[e].set(pieces[w])
        block["router"], block["wq"] = pieces["router"], pieces["wq"]
        p = dict(p, blocks=[block] + list(p["blocks"][1:]))
        return reference.loss_terms(cfg, p, f, i)["loss"]

    b0 = params["blocks"][0]
    pieces = {"w_gate": b0["w_gate"][e], "w_up": b0["w_up"][e],
              "w_down": b0["w_down"][e], "router": b0["router"],
              "wq": b0["wq"]}
    want_grads = jax.jit(jax.grad(pieces_loss))(pieces, params, feeds, idx)
    for key, g in zip(("w_gate", "w_up", "w_down", "router", "wq"),
                      got_grads):
        w = np.asarray(want_grads[key])
        off = np.abs(g - w).max() / np.abs(w).max()
        cos = float((g * w).sum()
                    / np.sqrt((g * g).sum() * (w * w).sum()))
        ok &= line(off <= args.grad_rtol,
                   "(c) gradient of %-16s largest entry %.3e, off by at "
                   "most %.2e of it, cosine %.6f"
                   % (key + ("[%d]" % e if g.ndim == 2 and key.startswith(
                       "w_") else ""), np.abs(w).max(), off, cos))

    # (d) rows an expert
    counts = np.stack(mine["counts"])
    tokens = args.batch * cfg["sequence_length"]
    ok &= line((counts.sum(axis=1) == k * tokens).all(),
               "(d) rows an expert: min %d, mean %.1f, max %d; %d "
               "assignments computed a layer, %d tokens x %d"
               % (counts.min(), counts.mean(), counts.max(),
                  int(counts.sum(axis=1)[0]), tokens, k))
    print("%s" % ("ok" if ok else "FAIL"))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
