"""SmallThinker-21BA3B's training share against the plain float32
reference, on the device JAX finds, at the benchmark's configuration
(published widths, 8 of 64 experts held, the cell's own batch of 1 x
16384 seeded tokens), outside any timed window.  The cell's own check
compares one scalar, the loss, and at initialisation the loss barely
reads the layers (PERF.md section 7): a window off by one, a router fed
the wrong tensor or a dropped expert would hide inside its tolerance,
so this is run on the chip beside it (PERF.md section 6, PR 48).

    python scripts/smallthinker_check.py [--config smallthinker-21b-a3b]
        [--seed 7] [--control key=value ...]

(a) the `flash_attention` op and its gradient op at the cell's shape on
    a *counting* input: q = k = 0, so a query weighs its keys alike, and
    v a one-hot of the position modulo the head's width, so an output
    entry counts the keys of one residue class a query attended (and a
    dV entry the queries that attended a key), over how many there
    were.  Exact in bfloat16 (a window of 4096 keys holds 32 of each
    class: 2^-7), and one key more or fewer moves an entry by a
    thirty-second, where on seeded weights it moves an output by 1 in
    4096, under the compute type's rounding.  The full layer and the
    window layer apart, through the forward kernel and the backward's;
(b) from one run of the whole program (forward and backward, no
    optimizer) on the start-up weights, with the program's own indices
    handed to the reference: each layer's attention output (window and
    full apart), its router logits and its expert layer's output, the
    loss, and every parameter's gradient against `jax.grad` of the
    reference; relative error (largest difference over the largest
    entry) and cosine.  Also the share of tokens whose experts are the
    reference's own, by layer.

`--control key=value` changes that key of the configuration *for the
reference alone* (a list as comma-separated integers; `dtype=bfloat16`
computes the reference in bfloat16 throughout, the precision below the
configuration's): the program stays what it is, the reference is made
wrong, and the script has to exit 1.  The controls PERF.md names:
sliding_window_size=4095, sliding_window_size=4097,
sliding_window_layout=0,0,0,0 (the window ignored), rope_layout=1,1,1,1
(positions applied to the full layer),
router_reads=post_attention_layernorm, hidden_act=silu,
moe_num_active_primary_experts=5 (a token's sixth expert dropped),
dtype=bfloat16 (`controls_of`).  `--all-controls` makes the sound
comparison and then each of those against the same run of the program,
in one process, and exits 0 only if the sound one passed and every
control failed; a control's gradients are compared although it has
failed already, because the gradients' limits stand on those readings.
Alone, a comparison that has failed before the gradients skips them.
Exits non-zero when a number is outside its limit (the
options' defaults: the limits and their reasons are beside them).
"""

import argparse
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def controls_of(cfg):
    """The controls of `--all-controls`, at the configuration's sizes:
    the window off by one either way, the window ignored, positions
    applied to every layer, the router fed the experts' input, SiLU for
    ReLU, a token's last expert dropped, bfloat16 throughout."""
    window, layers = cfg["sliding_window_size"], cfg["num_hidden_layers"]
    return ("sliding_window_size=%d" % (window - 1),
            "sliding_window_size=%d" % (window + 1),
            "sliding_window_layout=" + ",".join("0" * layers),
            "rope_layout=" + ",".join("1" * layers),
            "router_reads=post_attention_layernorm", "hidden_act=silu",
            "moe_num_active_primary_experts=%d"
            % (cfg["moe_num_active_primary_experts"] - 1),
            "dtype=bfloat16")


def compare(name, got, want, rtol, min_cos, failures, whole=False):
    """Largest difference over the largest entry (`whole`: the
    difference's norm over the reference's) and cosine, each against its
    limit."""
    got = np.asarray(got, np.float64).reshape(-1)
    want = np.asarray(want, np.float64).reshape(-1)
    if whole:
        rel = np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30)
    else:
        rel = np.abs(got - want).max() / max(np.abs(want).max(), 1e-30)
    cos = float(got @ want / max(np.linalg.norm(got) * np.linalg.norm(want),
                                 1e-30))
    ok = rel <= rtol and cos >= min_cos
    print("%-36s rel %.3e (limit %.1e)  cosine %.6f (limit %.4f)  %s"
          % (name, rel, rtol, cos, min_cos, "ok" if ok else "FAIL"),
          flush=True)
    if not ok:
        failures.append(name)


def controlled(cfg, controls):
    """(the reference's configuration, its dtype name) under
    `--control`."""
    cfg, dtype = dict(cfg), "float32"
    for item in controls:
        key, _, value = item.partition("=")
        if key == "dtype":
            dtype = value
        elif isinstance(cfg[key], list):
            cfg[key] = [int(v) for v in value.split(",")]
        elif isinstance(cfg[key], (int, float)) \
                and not isinstance(cfg[key], bool):
            cfg[key] = type(cfg[key])(value)
        else:
            cfg[key] = value
    return cfg, dtype


def counting_op(cfg, seq):
    """(a), the program's side: {kind of layer: (Out, dV of the
    key/value heads)} of the op and its gradient op on the counting
    input, as float32 numpy."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import registry

    heads, kv, d = (cfg[k] for k in ("num_attention_heads",
                                     "num_key_value_heads", "head_dim"))
    kind = jnp.dtype(cfg["compute_dtype"])
    one_hot = jax.nn.one_hot(jnp.arange(seq) % d, d, dtype=jnp.float32)
    zeros = jnp.zeros((1, seq, heads * d), kind)
    v = jnp.tile(one_hot, (1, heads))[None].astype(kind)
    info = registry.get_op_info("flash_attention")
    found = {}
    for name, window in (("full", 0),
                         ("window", cfg["sliding_window_size"])):
        attrs = {"num_heads": heads, "causal": True}
        if window:
            attrs["window"] = window

        def run(q, k, v, do):
            outs = info.kernel(None, {"Q": [q], "K": [k], "V": [v]}, attrs)
            ins = {"Q": [q], "K": [k], "V": [v], "O@Out": outs["Out"],
                   "O@Lse": outs["Lse"], "OG@Out": [do]}
            return outs["Out"][0], info.grad_kernel(
                None, ins, attrs)["V@GRAD"][0]

        out, dv = jax.jit(run)(zeros, zeros, v, v)
        # dV of a key/value head: its group's query heads added up
        dv = dv.astype(jnp.float32).reshape(
            1, seq, kv, heads // kv, d).sum(axis=3)
        found[name] = (np.asarray(out, np.float32), np.asarray(dv))
    return found


def counting_check(cfg, ref_cfg, reference, seq, counted, failures, rtol):
    """(a): the op on the counting input against the reference's masked
    attention, per kind of layer."""
    import jax
    import jax.numpy as jnp

    heads, kv, d = (cfg[k] for k in ("num_attention_heads",
                                     "num_key_value_heads", "head_dim"))
    one_hot = jax.nn.one_hot(jnp.arange(seq) % d, d, dtype=jnp.float32)
    v_kv = jnp.tile(one_hot, (1, kv)).reshape(1, seq, kv, d)
    for name, (out, dv) in counted.items():
        # the window the reference gives the program's first layer of
        # this kind
        layer = cfg["sliding_window_layout"].index(int(name == "window"))
        window = ref_cfg["sliding_window_size"] \
            if ref_cfg["sliding_window_layout"][layer] else 0

        def plain(v):
            q = jnp.zeros((1, seq, heads, d), jnp.float32)
            return reference.masked_attention(q, q[:, :, :kv], v, window)

        want, vjp = jax.vjp(jax.jit(plain), v_kv)
        want_dv = vjp(jnp.tile(one_hot, (1, heads))[None])[0]
        for what, got, ref in (("Out", out, want), ("dV", dv, want_dv)):
            got = got.reshape(-1)
            ref = np.asarray(ref, np.float32).reshape(-1)
            # by entry, over the entry: every count is its own size
            worst = float((np.abs(got - ref)
                           / np.maximum(np.abs(ref), 1e-6)).max())
            ok = worst <= rtol
            print("counting input, %-6s layer %-4s largest error of an "
                  "entry over the entry %.3e (limit %.1e)  %s"
                  % (name, what, worst, rtol, "ok" if ok else "FAIL"),
                  flush=True)
            if not ok:
                failures.append("counting %s %s" % (name, what))


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--config", default="smallthinker-21b-a3b")
    p.add_argument("--seed", type=int, default=7)
    p.add_argument("--batch", type=int, default=1)
    p.add_argument("--control", action="append", default=[],
                   help="key=value: the reference alone computes with "
                        "this; the script then has to exit 1")
    p.add_argument("--all-controls", action="store_true",
                   help="the sound comparison, then each of controls_of "
                        "against the same run; 0 only if the one passed "
                        "and every other failed")
    p.add_argument("--search-path", action="append", default=[],
                   help="a directory laid out like benchmark/, searched "
                        "first (a tiny configuration for a rehearsal)")
    # an entry is a count over a count, exact in float32 and a
    # bfloat16 rounding (2^-9) off at most; a key more or fewer is a
    # thirty-second of the entries it touches
    p.add_argument("--count-rtol", type=float, default=1e-2)
    # bfloat16 operands (2^-9 a rounding) through products of 2560 and
    # 3584 terms, a softmax over up to 16384 keys and four layers of a
    # bfloat16 residual stream: seen on the chip at most 1.5e-2 of the
    # largest entry forward; a fault of the controls' kind is off by a
    # tenth or more and its cosine falls under 0.99
    p.add_argument("--layer-rtol", type=float, default=4e-2)
    p.add_argument("--layer-cos", type=float, default=0.999)
    # gradients, by the norm of the difference over the reference's
    # norm, not by the largest entry: a ReLU's derivative is a step, and
    # where the program's bfloat16 pre-activation and the reference's
    # float32 one fall on different sides of 0 a row's whole
    # contribution to an expert's gate matrix comes or goes, which moved
    # single entries by 0.05 to 0.45 of the largest (more where the load
    # is even and the largest entry small) while the cosine stayed at
    # 0.9983 to 0.9992, a difference of 4 to 6% in norm; the other
    # gradients 0.9990 and up.  Both limits lie between two readings on
    # the chip (PERF.md section 6, PR 48): the sound comparison's worst
    # parameter over seven seeds, 5.7e-2 and 0.9984 (bfloat16
    # throughout, which the loss holds: 6.1e-2 and 0.9981), and the
    # worst parameter under the control nearest to it, a token's sixth
    # expert dropped: 0.30 and 0.955 (SiLU for ReLU 0.39 and 0.937, the
    # window ignored 0.62 and 0.847, the router fed s 0.64 and 0.774,
    # positions on the full layer 1.10 and 0.452; 31 to 42 of the 43
    # parameters outside).  The window off by one moves no gradient
    # past 5.6e-2: the counting input holds it
    p.add_argument("--grad-rtol", type=float, default=1e-1)
    p.add_argument("--grad-cos", type=float, default=0.995)
    # the loss: the configuration's reference_tolerance
    p.add_argument("--loss-rtol", type=float, default=None)
    args = p.parse_args(argv)
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid
    from benchmark import harness

    lookup = harness.Lookup(args.search_path)
    cfg = lookup.json("configs", args.config)
    reference = lookup.module("reference", cfg["reference"])
    device = jax.devices()[0]
    print("platform=%s device_kind=%s config=%s seed=%d controls=%s"
          % (device.platform, device.device_kind, cfg["name"], args.seed,
             args.control or "none"), flush=True)
    if cfg["compute_dtype"] == "bfloat16":
        fluid.amp.enable_bf16()
    seq, layers = cfg["sequence_length"], cfg["num_hidden_layers"]
    counted = counting_op(cfg, seq)

    # -- (b) the program ------------------------------------------------------
    from paddle_tpu.models.smallthinker_program import (
        build_smallthinker_program, smallthinker_param_names)

    model = lookup.module("models", cfg["builder"])
    main_p, startup, loss, parts = build_smallthinker_program(
        args.batch, **model.program_sizes(cfg))
    names = smallthinker_param_names(layers)
    leaves = jax.tree_util.tree_leaves(names)
    with fluid.program_guard(main_p, startup):
        grads = dict((p.name, g) for p, g in
                     fluid.backward.append_backward(loss))
    startup.random_seed = main_p.random_seed = args.seed
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
    per_layer = ("attn_out", "moe_out", "router_logits", "top_idx", "counts")
    fetch = [loss] + [v for key in per_layer for v in parts[key]] \
        + [grads[n] for n in leaves]
    out = exe.run(main_p, feed=feeds, fetch_list=fetch, scope=scope,
                  return_numpy=False)
    mine = {"loss": float(np.asarray(out[0], np.float32).reshape(-1)[0])}
    for i, key in enumerate(per_layer):
        mine[key] = [np.asarray(v) for v in
                     out[1 + i * layers:1 + (i + 1) * layers]]
    mine["grads"] = {n: np.asarray(g, np.float32) for n, g in
                     zip(leaves, out[1 + len(per_layer) * layers:])}
    del out
    # the scope keeps only the parameters from here on
    for name in list(scope.local_var_names()):
        if name not in leaves:
            scope.erase(name)
    params = jax.tree_util.tree_map(scope.get, names)
    for layer, counts in enumerate(mine["counts"]):
        print("layer %d (%s): rows a held expert min %d, mean %.0f, max %d; "
              "%d of %d assignments held"
              % (layer, "window" if cfg["sliding_window_layout"][layer]
                 else "full", counts.min(), counts.mean(), counts.max(),
                 counts.sum(), mine["top_idx"][layer].size), flush=True)

    def against_reference(controls, gradients_anyway=False):
        """The names of what lies outside its limit with the reference
        computed under `controls`; `gradients_anyway` compares the
        gradients after a failure too."""
        ref_cfg, ref_dtype = controlled(cfg, controls)
        print("-- the reference under %s" % (controls or "no control"),
              flush=True)
        failures = []
        counting_check(cfg, ref_cfg, reference, seq, counted, failures,
                       args.count_rtol)
        dtype = jnp.dtype(ref_dtype)
        top_k = ref_cfg["moe_num_active_primary_experts"]
        handed = [jnp.asarray(i[:, :top_k]) for i in mine["top_idx"]]

        def terms(p, f, indices):
            return reference.loss_terms(ref_cfg, p, f, indices, dtype)

        own = [] if controls else jax.jit(
            lambda p, f: terms(p, f, None)["indices"])(params, feeds)
        for layer, (idx, ref_idx) in enumerate(zip(mine["top_idx"], own)):
            same = np.all(np.sort(idx, axis=1)
                          == np.sort(np.asarray(ref_idx), axis=1), axis=1)
            print("layer %d: %.2f%% of the tokens take the reference's own "
                  "experts" % (layer, 100.0 * same.mean()), flush=True)
        del own
        keep = ("loss", "attn_out", "moe_out", "router_logits")
        want = jax.jit(lambda p, f, i: {
            k: v for k, v in terms(p, f, i).items() if k in keep})(
            params, feeds, handed)
        for layer in range(layers):
            kind = "window" if cfg["sliding_window_layout"][layer] \
                else "full"
            for what, key in (("(%s) attention output" % kind, "attn_out"),
                              ("router logits", "router_logits"),
                              ("expert layer output", "moe_out")):
                compare("layer %d %s" % (layer, what),
                        mine[key][layer].astype(np.float32),
                        want[key][layer], args.layer_rtol, args.layer_cos,
                        failures)
        want_loss = float(want["loss"])
        del want
        tol = args.loss_rtol or cfg["reference_tolerance"]["loss_rel"]
        off = abs(mine["loss"] - want_loss) / abs(want_loss)
        print("loss %.6f, the reference's (the program's indices) %.6f: off "
              "by %.3e (limit %.1e) %s"
              % (mine["loss"], want_loss, off, tol,
                 "ok" if off <= tol else "FAIL"), flush=True)
        if off > tol:
            failures.append("loss")
        if failures and not gradients_anyway:
            print("gradients: not compared, the comparison has failed",
                  flush=True)
            return failures
        before = len(failures)
        want_grads = jax.jit(jax.grad(
            lambda p, f, i: terms(p, f, i)["loss"].astype(jnp.float32)))(
            params, feeds, handed)
        for name, ref in zip(leaves, jax.tree_util.tree_leaves(want_grads)):
            compare("gradient %s" % name, mine["grads"][name],
                    np.asarray(ref, np.float32), args.grad_rtol,
                    args.grad_cos, failures, whole=True)
        print("gradients: %d of %d outside their limits"
              % (len(failures) - before, len(leaves)), flush=True)
        return failures

    failures = against_reference(args.control)
    print("smallthinker_check: %s" % ("FAILED: " + ", ".join(failures)
                                      if failures else "ok"), flush=True)
    if not args.all_controls:
        return 1 if failures else 0
    controls = controls_of(cfg)
    passed = [c for c in controls if not against_reference([c], True)]
    print("smallthinker_check, controls: %s"
          % ("NOT TOLD FROM THE SOUND REFERENCE: " + ", ".join(passed)
             if passed else "each of %d failed, as it has to"
             % len(controls)), flush=True)
    return 1 if failures or passed else 0


if __name__ == "__main__":
    sys.exit(main())
