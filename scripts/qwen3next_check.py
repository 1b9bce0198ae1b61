"""Qwen3-Next-80B-A3B's served share against its plain float32 reference
at the published widths, outside any timed window: the cached step
Program of benchmark/models/qwen3next_decode.py (Gated DeltaNet layers
with a convolution tail and a float32 recurrent state, gated full
attention over 256-wide heads, the held experts beside a gated shared
expert) driven through `fluid.ProgramDecoder`'s step from empty states:
a prefill of `--prefill` positions as one block (the rule's chunked
form, the tail handed to the steps, a block of queries through the
cache), then `--decode` positions a step at a time (the step kernel,
the state rewritten whole every step), against the reference's full
forward position by position (benchmark/reference/qwen3_next.py, a layer
at a time).

    chiprun --timeout 1500 -- python scripts/qwen3next_check.py --seeds 1,2,3
    chiprun --timeout 1800 -- python scripts/qwen3next_check.py --seeds 1 \
        --all-controls
    python scripts/qwen3next_check.py --config qwen3next-tiny \
        --workload qwen3next-tiny-decode --search-path \
        benchmark/tests/fixture --prefill 16 --decode 24   # on the CPU

Numbers, a seed, each the worst over its layers: `mixer_off_linear` and
`mixer_off_full`, the mixer's output of the last step (after `wo`)
against the reference's at that position, root mean square of the
difference over the reference's; `state_off`, each linear layer's
recurrent state after the last step against the reference's, and
`state_off_first`, the first layer's alone (its input is the embedding,
the same on both sides: a state kept in a narrower type shows here);
`held_part_off`, each expert layer's held part of the last step under
the program's own choice of experts; and over the decoded positions
`logits_off`, `not_first_share` (the share of positions whose largest
logit is not the reference's) and `gap_mean` (by how much the
reference's logit of the step's choice lies below its best).  Exit code
1 when a number is outside its limit (LIMITS, with the readings they
were set from).  `--control key=value` (benchmark/reference/
qwen3_next.py lists them) holds the served step to a reference made
wrong in that way: it must exit 1.  `--all-controls` runs the sound
comparison and every control of benchmark/tests/state_control.py on the
same served outputs, in one process, and exits 1 unless the sound one
passes and every control is refused.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# my chip runs, PR 52, at the published widths (2 rows, 128 + 128
# positions, bfloat16 weights, tails and caches and a float32 state
# against the float32 reference).  Sound, seeds 1, 2, 3, 4:
# mixer_off_linear 0.0368-0.0403, mixer_off_full 0.0462-0.0653,
# state_off 0.0298-0.0303, state_off_first 4.44e-3 to 4.54e-3,
# held_part_off 4.4e-3 to 4.9e-3, logits_off 0.0220-0.0225, gap_mean
# 4.7e-4 to 1.0e-3 (3.9-5.4% of the positions' largest logit is not the
# reference's).  Controls, seed 4 (`--all-controls`): the state in
# bfloat16 reads state_off_first 0.0118 and nothing else outside (0.0513,
# 0.0680, 0.0403, -, 0.0285, 1.8e-3); a token's tenth expert dropped
# held_part_off 0.431 and nothing else; the tail not carried 0.329, 0.603,
# 0.309, 0.0616, -, 0.386, 0.274; the other seven mixer_off_linear 0.69 or
# more, state_off 0.56 or more, logits_off 0.45 or more, gap_mean 0.35 or
# more.  Each limit lies 1.6 to 20 times over the largest sound reading
# and 1.6 to 20 under the smallest reading of the controls it is to
# refuse: a step that is not the model is refused, rounding is not.
LIMITS = {"mixer_off_linear": 0.1, "mixer_off_full": 0.15,
          "state_off": 0.06, "state_off_first": 0.0072,
          "held_part_off": 0.02, "logits_off": 0.1, "gap_mean": 0.02}


def serve(lookup, cfg, workload, seed, rows, prefill, decode):
    """What the step served: {"tokens", "logits" [rows, 1 + decode,
    vocab] (after the block and after every step), "probes": per layer
    the last step's parts}."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu.fluid as fluid

    model = lookup.module("models", workload["builder"])
    spec = dict(workload["weights"], seed=seed)
    total = prefill + decode
    cfg = dict(cfg, serve_positions=-(-total // 128) * 128)
    # the step itself, for its `parts`: the mixers' outputs of the last
    # position beside what the cell's probes carry
    from paddle_tpu.models.linear_moe_program import (
        build_linear_moe_cached_step_program, linear_moe_param_names)
    main, _, logits, pairs, found = build_linear_moe_cached_step_program(
        rows, cfg["serve_positions"], state_rows=rows, **model.sizes(cfg))
    made = jax.jit(lambda k: model.weights(cfg, spec, k))(
        jax.random.PRNGKey(seed))
    scope = fluid.Scope()
    names = jax.tree_util.tree_leaves(
        linear_moe_param_names(model.layer_types(cfg)))
    for name, value in zip(names, jax.tree_util.tree_leaves(made)):
        scope.set(name, value)
    del made
    probes = {"probe.%s_%d" % (key, i): var.name
              for key in ("attn_out", "moe_in", "top_idx", "moe_out",
                          "delta_state")
              for i, var in enumerate(found[key])}
    decoder = fluid.ProgramDecoder(
        main.clone(for_test=True), token_name="tok",
        logits_name=logits.name, state_pairs=pairs + list(probes.items()),
        scope=scope, max_positions=cfg["serve_positions"])
    del scope
    tokens = np.random.default_rng([seed, 0x93E]).integers(
        0, cfg["vocab_size"], (rows, total), dtype=np.int32)
    weights = jnp.dtype(workload["weights"]["dtype"])
    types = {"state": jnp.float32, "tail": weights,
             "cache": jnp.dtype(workload["serve_dtype"])}
    state = {feed: jnp.zeros(shape, types[kind]) for feed, (shape, kind)
             in model.state_shapes(cfg, rows).items()}
    state["pos"] = jnp.zeros((rows,), jnp.int32)
    shape_of = model.state_shapes(cfg, rows)["delta_state_0"][0]
    for feed in probes:
        state[feed] = jnp.zeros(
            (rows, cfg["num_experts_per_tok"]), jnp.int32) \
            if "top_idx" in feed else jnp.zeros(shape_of, jnp.float32) \
            if "delta_state" in feed \
            else jnp.zeros((rows, 1, cfg["hidden_size"]), weights)

    @jax.jit
    def drive(params, state, tokens):
        step = decoder._step_fn(params)
        first, state = step(state, tokens[:, :prefill])

        def body(state, tok):
            logits, state = step(state, tok)
            return state, logits

        state, rest = jax.lax.scan(body, state, tokens[:, prefill:].T)
        return jnp.concatenate([first[None], rest]), state

    logits, last = drive(decoder._params, state, jnp.asarray(tokens))
    return {"tokens": tokens, "cfg": cfg,
            "logits": np.asarray(jnp.moveaxis(logits, 0, 1), np.float32),
            "probes": {feed: np.asarray(last[feed]) for feed in probes}}


def compare(lookup, workload, seed, served, prefill, control=None):
    """The numbers of the module's docstring, of `served` against the
    reference (made wrong by `control`)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    model = lookup.module("models", workload["builder"])
    reference = lookup.module("reference", workload["reference"])
    cfg = dict(served["cfg"], control=control or {})
    spec = dict(workload["weights"], seed=seed)
    key = model.root(jax.random.PRNGKey(seed))
    f32 = lambda tree: jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, jnp.float32), tree)
    ends = f32(jax.jit(lambda k: model.ends(cfg, spec, k))(key))
    tokens, probes = jnp.asarray(served["tokens"]), served["probes"]
    first = cfg.get("first_expert", 0)

    off = reference.state_off   # rms of the difference over the reference's
    worst = {"mixer_off_linear": 0.0, "mixer_off_full": 0.0,
             "state_off": 0.0, "held_part_off": 0.0, "state_off_first": None}
    x = ends["embed"][tokens]
    linear = 0
    for i in range(cfg["num_hidden_layers"]):
        block = f32(jax.jit(lambda k, i=i: model.block(cfg, spec, k, i))(key))
        with jax.default_matmul_precision("highest"):
            x, found = jax.jit(lambda b, x, i=i: reference.layer(
                cfg, i, b, x, first))(block, x)
        kind = "linear" if found["state"] is not None else "full"
        mixer = off(probes["probe.attn_out_%d" % i][:, 0],
                    found["mixer"][:, -1])
        worst["mixer_off_" + kind] = max(worst["mixer_off_" + kind], mixer)
        if kind == "linear":
            state = off(probes["probe.delta_state_%d" % linear],
                        found["state"])
            worst["state_off"] = max(worst["state_off"], state)
            if worst["state_off_first"] is None:
                worst["state_off_first"] = state
            linear += 1
        worst["held_part_off"] = max(
            worst["held_part_off"], reference.held_part_off(cfg, block, {
                "in": probes["probe.moe_in_%d" % i],
                "idx": probes["probe.top_idx_%d" % i],
                "out": probes["probe.moe_out_%d" % i]}))
        del block
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference.rms_norm(
            x[:, prefill - 1:], ends["norm_f"], cfg["rms_norm_eps"])
            @ ends["head"])
    got = served["logits"]
    chosen = got.argmax(-1)
    gaps = want.max(-1) - np.take_along_axis(want, chosen[..., None],
                                             -1)[..., 0]
    return dict(worst, logits_off=off(got, want),
                not_first_share=float((gaps > 0).mean()),
                gap_mean=float(gaps.mean()))


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--config", default="qwen3-next-80b-a3b")
    p.add_argument("--workload", default="qwen3next-decode-ep16")
    p.add_argument("--seeds", default="1")
    p.add_argument("--rows", type=int, default=2)
    p.add_argument("--prefill", type=int, default=128)
    p.add_argument("--decode", type=int, default=128)
    p.add_argument("--control", action="append", default=[])
    p.add_argument("--all-controls", action="store_true")
    p.add_argument("--search-path", action="append", default=[])
    args = p.parse_args(argv)

    from benchmark import harness

    lookup = harness.Lookup(args.search_path)
    cfg = lookup.json("configs", args.config)
    workload = lookup.json("workloads", args.workload)
    control = lookup.module("tests", "state_control")
    harness.place_compile_cache()
    controls = {None: None}
    if args.all_controls:
        controls.update(control.controls_of(
            cfg, dict(workload, prompt_len=args.prefill)))
    for spelling in args.control:
        controls = {spelling: control.parsed(spelling)}
    ok = True
    for seed in (int(s) for s in args.seeds.split(",")):
        served = serve(lookup, cfg, workload, seed, args.rows, args.prefill,
                       args.decode)
        for spelling, wrong in controls.items():
            got = compare(lookup, workload, seed, served, args.prefill,
                          wrong)
            over = sorted(k for k, limit in LIMITS.items()
                          if not got[k] <= limit)
            sound = spelling is None
            ok &= bool(over) != sound if args.all_controls else not over
            print(json.dumps(dict(got, seed=seed, control=spelling,
                                  outside=over)), flush=True)
    print("ok" if ok else "FAIL: a number outside its limit (or a control "
          "inside all of them)", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
