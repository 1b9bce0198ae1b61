"""Ling-3.0-flash's served share against its plain float32 reference at
the published widths, outside any timed window: the cached step Program
of benchmark/models/ling3_decode.py (KDA layers with a convolution tail
and a float32 recurrent state under a gate a key channel, a
latent-attention layer over a cache of latents, both gated a head, two
leading dense layers, the held experts beside a shared expert) driven
through `fluid.ProgramDecoder`'s step from empty states: a prefill of
`--prefill` positions in blocks of the step's own `prefill_block` (the
rule's chunks of sub-blocks, the tail handed on, a block of queries
through the latent cache), then `--decode` positions a step at a time
(the `kda_step_*` kernel, the state rewritten whole every step, the
latent walk), against the reference's full forward position by position
with latent attention unabsorbed (benchmark/reference/ling3_flash.py, a
layer at a time).  Logits, not tokens.

    chiprun --timeout 1500 -- python scripts/ling3_check.py --seeds 1,2,3
    chiprun --timeout 1800 -- python scripts/ling3_check.py --seeds 1 \
        --all-controls
    python scripts/ling3_check.py --config ling3-tiny \
        --workload ling3-tiny-decode --search-path \
        benchmark/tests/fixture --prefill 16 --decode 24   # on the CPU

Numbers, a seed, each the worst over its layers: `mixer_off_kda` and
`mixer_off_latent`, the mixer's output of the last step (after `wo`)
against the reference's at that position, root mean square of the
difference over the reference's; `state_off`, each KDA layer's recurrent
state after the last step against the reference's, and
`state_off_first`, the first layer's alone (its input is the embedding,
the same on both sides: a state kept in a narrower type shows here);
`held_part_off`, each expert layer's held part of the last step under
the program's own choice of experts; and over the decoded positions
`logits_off`, `not_first_share` (the share of positions whose largest
logit is not the reference's) and `gap_mean` (by how much the
reference's logit of the step's choice lies below its best).  Exit code
1 when a number is outside its limit (LIMITS, with the readings they
were set from).  `--control key=value` (benchmark/reference/
ling3_flash.py lists them) holds the served step to a reference made
wrong in that way: it must exit 1.  `--all-controls` runs the sound
comparison and every control of benchmark/tests/hybrid_control.py on the
same served outputs, in one process, and exits 1 unless the sound one
passes and every control is refused.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# my chip runs, PR 54, at the published widths (2 rows, 128 + 128
# positions, bfloat16 weights, tails and latents and a float32 state
# against the float32 reference; calls 5 and 6, every line kept:
# PERF.md section 6).  Sound, seeds 1-7: mixer_off_kda 0.0246-0.0416,
# mixer_off_latent 0.0261-0.0345, state_off 0.0219-0.0301,
# state_off_first 4.23e-3 to 4.30e-3, held_part_off 4.6e-3 to 5.3e-3,
# logits_off 0.0166-0.0222, gap_mean 3.1e-4 to 1.04e-3 (2.7-4.3% of the
# positions' largest logit is not the reference's).  Controls, seeds 4
# and 7 (`--all-controls`): the state in bfloat16 reads state_off_first
# 0.0112 and 0.0108 and nothing else outside (logits_off 0.0245,
# 0.0242); a token's eighth expert dropped reads logits_off 0.0377 and
# 0.0350 and nothing else outside (mixer_off_kda 0.072, 0.062, state_off
# 0.049, 0.051): **held_part_off did not see it on either seed**, since
# with 2 rows and six expert layers a row's last choice is one of the 32
# held of 512 on about half the seeds (the cell's own control, 128 rows,
# reads it 0.49), so logits_off stands 1.26 times over the largest sound
# reading and 1.25 under the smaller of these two: little room, and a
# seed that reads between them is this limit's fault, not the step's;
# rotation over all 192 values mixer_off_latent 1.05-1.11, mixer_off_kda
# 0.14-0.16, state_off 0.12; the latent's norm left out 0.63-0.64,
# logits_off 0.12; the tail not carried state_off_first 0.046-0.052,
# logits_off 0.25; the other five mixer_off_kda 0.81 or more, logits_off
# 0.49 or more, gap_mean 0.47 or more.  The other limits lie 1.7 to 4.3
# times over the largest sound reading (gap_mean 19) and 1.4 to 2 under
# the smallest reading of the controls they are to refuse.
LIMITS = {"mixer_off_kda": 0.1, "mixer_off_latent": 0.15,
          "state_off": 0.06, "state_off_first": 0.0072,
          "held_part_off": 0.02, "logits_off": 0.028, "gap_mean": 0.02}


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
    names = jax.tree_util.tree_leaves(linear_moe_param_names(
        model.layer_types(cfg), cfg["first_k_dense_replace"], "channel",
        shared_gate=False, router_bias=True))
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

    block = min(prefill, decoder._prefill_block)

    @jax.jit
    def drive(params, state, tokens):
        step = decoder._step_fn(params)
        for at in range(0, prefill, block):     # the step's own blocks
            first, state = step(state, tokens[:, at:min(at + block,
                                                        prefill)])

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
    worst = {"mixer_off_kda": 0.0, "mixer_off_latent": 0.0,
             "state_off": 0.0, "held_part_off": 0.0, "state_off_first": None}
    dense = cfg["first_k_dense_replace"]
    x = ends["embed"][tokens]
    linear = 0
    for i in range(cfg["num_hidden_layers"]):
        block = f32(jax.jit(lambda k, i=i: model.block(cfg, spec, k, i))(key))
        with jax.default_matmul_precision("highest"):
            x, found = jax.jit(lambda b, x, i=i: reference.layer(
                cfg, i, b, x, first))(block, x)
        kind = "kda" if found["state"] is not None else "latent"
        mixer = off(probes["probe.attn_out_%d" % i][:, 0],
                    found["mixer"][:, -1])
        worst["mixer_off_" + kind] = max(worst["mixer_off_" + kind], mixer)
        if kind == "kda":
            state = off(probes["probe.delta_state_%d" % linear],
                        found["state"])
            worst["state_off"] = max(worst["state_off"], state)
            if worst["state_off_first"] is None:
                worst["state_off_first"] = state
            linear += 1
        if i >= dense:      # the expert layers' parts count from there
            worst["held_part_off"] = max(
                worst["held_part_off"], reference.held_part_off(cfg, block, {
                    "in": probes["probe.moe_in_%d" % (i - dense)],
                    "idx": probes["probe.top_idx_%d" % (i - dense)],
                    "out": probes["probe.moe_out_%d" % (i - dense)]}))
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
    p.add_argument("--config", default="ling-3.0-flash")
    p.add_argument("--workload", default="ling3-decode-ep16")
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
    control = lookup.module("tests", "hybrid_control")
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
