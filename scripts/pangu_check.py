"""One chip's share of openPangu-Ultra-MoE-718B against the plain float32
reference, on the device JAX finds, at the benchmark's configuration
(published widths, 16 of 256 experts held, an eighth of the vocabulary),
outside any timed window (PERF.md section 6, PR 36).

    python scripts/pangu_check.py [--config openpangu-ultra-moe-718b]
        [--seed 7] [--rows 8] [--positions 256] [--prefill 128]

The cached step Program (absorbed latent attention, a cache of latents,
the held range of the routed experts), in the types it is served in, is
driven over `--positions` seeded tokens of `--rows` sequences through
its cache as `ProgramDecoder` drives it: the first `--prefill` positions
in blocks of the positions an application that the served cell's step
states (`latent_moe_program.prefill_block` at the workload's rows; since
PR 53), one scan of them, then a position an
application, one scan of steps.  A block hands out what its last
position computed, so the positions compared are every block's last and
every stepped one: their logits, every layer's output and every expert
layer's chosen experts are kept.  (The stepped positions read the cache
the blocks wrote, every prompt position of every layer.)  The chosen
experts of *every* position are kept too, as the expert op was handed
them (`moe_experts`' own `TopIdx`, a block's whole).  The program's
weights are then
let go of and the reference (paddle_tpu/models/reference/pangu_moe.py:
the unabsorbed full-sequence forward, no cache) runs in float32 on the
same seeded weights, a layer at a time (one expert layer is 4 GB in
float32), twice: with its own routing, and with the program's indices
handed to it at every position (what a stepped position attends, the
prompt's latents, is then of the program's routing in the reference
too).  Prints and holds to the options' limits:

(a) per expert layer, the share of tokens whose 8 experts are the
    reference's own, and for the others the reference's margin between
    the last score taken and the first left out (the program's router
    reads bfloat16 activations, so near-ties change places: a token that
    disagrees at a margin above `--tie-margin` would be wrong arithmetic,
    not a tie);
(b) with the program's indices: every layer's output, as the root mean
    square of the difference over the reference's (`--hidden-tol`), and
    the logits, as the largest difference over it (`--logit-tol`);
(c) with the reference's own routing: the same, and the gap statistics
    the benchmark's `correct` reads (by how much the reference's logit
    of the program's first token lies below the reference's best);
(d) rows a held expert: min, mean, max, and the share of the assignments
    that fell on held experts (16 / 256 with an even router);
(e) the held experts' part alone: what `moe_experts` gave for the
    program's own input and indices against the reference's routed sum
    of the same input and indices, as the root mean square of the
    difference over the reference's (`--routed-tol`).  The benchmark's
    `correct` reads the same quantity of a served call's last step
    (`held_part_off`): the served tokens cannot tell the held experts'
    weights from rounded ones (`--routed-mantissa-bits 3` serves them
    rounded to float8's three mantissa bits: this line then fails, and
    `gap_mean` reads 1.04-1.09 times its sound runs: PERF.md section 6).
Exits non-zero when a number is outside its limit.  Every limit is a
root mean square or a share over the run: the widest single element of
a layer's output is printed and not compared (it is one value of half a
million a layer and rides on whichever token's eighth expert changed
places; on seed 3000000031 it read 0.0875-0.113 where the root mean
square read 0.007-0.02).
"""

import argparse
import gc
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--config", default="openpangu-ultra-moe-718b")
    p.add_argument("--workload", default="pangu-decode-ep16",
                   help="whose `weights` draw the parameters")
    p.add_argument("--seed", type=int, default=7)
    p.add_argument("--rows", type=int, default=8)
    p.add_argument("--positions", type=int, default=256)
    p.add_argument("--prefill", type=int, default=128,
                   help="the leading positions that go through in blocks")
    p.add_argument("--search-path", action="append", default=[])
    # my chip runs, PR 36, 8 x 256 positions: four sound runs (seeds
    # 3000000031, 3100000019, 3100000023, 3100000029; the last three of
    # this script as committed, each exit 0) and one with the held
    # experts' weights rounded to three mantissa bits (seed 3100000019,
    # exit 1).  Each limit below with its two readings, the sound runs'
    # worst and the rounded run's: only --routed-tol stands between two
    # readings; the rounding hardly moves the others, which sit at about
    # twice the sound runs' worst (limits set after the first run's
    # reading; the three later seeds stayed inside them).
    #   --same-routing: a layer's share of tokens with the reference's own
    #     8 experts, smallest 72.2% sound (layer 4), 73.4% rounded
    #   --tie-margin: the others' widest margin 0.0171 sound, 0.0171 rounded
    #   --hidden-tol: a layer's output, rms, 0.0209 sound (layer 4; layer 0
    #     0.0073), 0.0228 rounded
    #   --logit-tol: 0.130 sound, 0.140 rounded
    #   --routed-tol: the held part, 0.00753 sound (0.00636 layer 1 to
    #     0.00753 layer 4 on every seed), 0.04713 rounded at the least
    p.add_argument("--same-routing", type=float, default=0.6)
    p.add_argument("--tie-margin", type=float, default=4e-2)
    p.add_argument("--hidden-tol", type=float, default=0.04)
    p.add_argument("--logit-tol", type=float, default=0.25)
    p.add_argument("--routed-tol", type=float, default=0.018)
    p.add_argument("--routed-mantissa-bits", type=int, default=None,
                   help="the control: serve the held experts' weights "
                        "rounded to so many mantissa bits")
    args = p.parse_args(argv)
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    from benchmark import harness
    from paddle_tpu.jit import FunctionalProgram
    from paddle_tpu.models.latent_moe_program import (
        build_latent_moe_cached_step_program, latent_moe_param_names,
        prefill_block)
    from paddle_tpu.models.reference import pangu_moe as reference

    lookup = harness.Lookup(args.search_path)
    cfg = lookup.json("configs", args.config)
    workload = lookup.json("workloads", args.workload)
    spec = workload["weights"]
    model = lookup.module("models", cfg["builder"])
    device = jax.devices()[0]
    rows, positions = args.rows, args.positions
    sizes = model.sizes(cfg)
    chunk = prefill_block(workload["batch"], sizes["n_head"],
                          sizes["kv_rank"], sizes["d_rope"])
    prefill = min(args.prefill, positions) // chunk * chunk
    # the positions whose outputs are kept: each block's last, and every
    # stepped one
    kept = np.concatenate([np.arange(chunk - 1, prefill, chunk),
                           np.arange(prefill, positions)])
    print("platform=%s device_kind=%s config=%s seed=%d rows=%d "
          "positions=%d: %d in blocks of %d, %d compared"
          % (device.platform, device.device_kind, cfg["name"], args.seed,
             rows, positions, prefill, chunk, kept.size), flush=True)

    layers, dense = sizes["n_layer"], sizes["n_dense"]
    moe = layers - dense
    first, held = sizes["held"]
    main, _, logits, pairs, parts = build_latent_moe_cached_step_program(
        rows, positions, **sizes)
    names = latent_moe_param_names(layers, dense)
    feeds = ["tok"] + [f for f, _ in pairs]
    # `parts` are of an application's last position; the indices of all
    # its positions [rows * T, top_k] are the expert op's own input
    whole_idx = [op.input("TopIdx")[0] for op in main.global_block().ops
                 if op.type == "moe_experts"]
    fetches = [logits.name] + [o for _, o in pairs] \
        + [v.name for v in parts["hidden"]] \
        + [v.name for v in parts["top_idx"]] \
        + [v.name for v in parts["counts"]] \
        + [v.name for v in parts["moe_in"]] \
        + [v.name for v in parts["moe_out"]] + whole_idx
    fp = FunctionalProgram(main.clone(for_test=True), feeds, fetches)
    key = jax.random.PRNGKey(args.seed)
    served = spec if args.routed_mantissa_bits is None else dict(
        spec, routed_mantissa_bits=args.routed_mantissa_bits)
    made = jax.block_until_ready(
        jax.jit(lambda k: model.weights(cfg, served, k))(key))
    params = dict(zip(jax.tree_util.tree_leaves(names),
                      jax.tree_util.tree_leaves(made)))
    del made
    tokens = np.random.default_rng([args.seed, 1]).integers(
        0, cfg["vocab_size"], (rows, positions), dtype=np.int32)
    width = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    state = {f: jnp.zeros((rows, positions, width),
                          jnp.dtype(workload["serve_dtype"]))
             for f, _ in pairs if f != "pos"}
    state["pos"] = jnp.zeros((rows,), jnp.int32)
    n_state = len(pairs)

    def run(params, state, blocks, steps):
        def body(state, tok):
            out, _ = fp(params, dict(state, tok=tok))
            new = {f: v for (f, _), v in zip(pairs, out[1:1 + n_state])}
            # what T does not shape, and every position's indices
            return new, ((out[0],) + tuple(out[1 + n_state:-moe]),
                         tuple(i.reshape(rows, tok.shape[1], -1)
                               for i in out[-moe:]))
        # [applications, rows, T] tokens: the blocks, then the steps
        state, (first, first_idx) = jax.lax.scan(body, state, blocks)
        later, later_idx = jax.lax.scan(body, state, steps)[1]
        # [applications, rows, T, top_k] -> [rows, positions, top_k]
        every_idx = tuple(
            jnp.concatenate([jnp.swapaxes(i, 0, 1).reshape(
                rows, -1, i.shape[-1]) for i in pair], axis=1)
            for pair in zip(first_idx, later_idx))
        return jax.tree_util.tree_map(
            lambda a, b: jnp.concatenate([a, b]), first, later), every_idx

    out, every_idx = jax.device_get(jax.jit(run)(
        params, state,
        jnp.asarray(np.moveaxis(tokens[:, :prefill].reshape(
            rows, -1, chunk), 1, 0)),
        jnp.asarray(tokens[:, prefill:].T[:, :, None])))
    # [kept, rows, ...] -> [rows, kept, ...]
    got_logits = np.swapaxes(np.asarray(out[0], np.float32), 0, 1)
    got_hidden = [np.swapaxes(np.asarray(h, np.float32)[:, :, 0], 0, 1)
                  for h in out[1:1 + layers]]
    got_idx = [np.swapaxes(np.asarray(i), 0, 1).reshape(
        rows * kept.size, -1) for i in out[1 + layers:1 + layers + moe]]
    at = 1 + layers + moe
    counts = [np.asarray(c) for c in out[at:at + moe]]
    moe_in, moe_out = ([np.swapaxes(np.asarray(v, np.float32)[:, :, 0], 0,
                                    1).reshape(rows * kept.size, -1)
                        for v in out[lo:lo + moe]]
                       for lo in (at + moe, at + 2 * moe))
    del params, out
    gc.collect()

    root = model.root(key)
    ends = jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, jnp.float32),
        jax.jit(lambda: model.ends(cfg, spec, root))())
    one = jax.jit(lambda block, x, idx: reference.layer(
        cfg, block, x, first, idx))
    margin_of = jax.jit(lambda block, x: _margins(reference, cfg, block, x))
    routed_of = jax.jit(lambda block, u, idx: reference.routed(
        cfg, block, u, first, idx)[0])
    ok = True

    def rel(a, b):
        return float(np.abs(a - b).max() / np.sqrt(np.mean(np.square(b))))

    def rms(a, b):
        return float(np.sqrt(np.mean(np.square(a - b))
                             / np.mean(np.square(b))))

    with jax.default_matmul_precision("highest"):
        x_own = x_same = ends["embed"][jnp.asarray(tokens)]
        for i in range(layers):
            block = jax.tree_util.tree_map(
                lambda a: jnp.asarray(a, jnp.float32),
                jax.jit(lambda i=i: model.block(cfg, spec, root, i))())
            if i < dense:
                x_own, _ = one(block, x_own, None)
                x_same = x_own
            else:
                idx = got_idx[i - dense]
                margins, own_idx = (np.asarray(a) for a in
                                    margin_of(block, x_own))
                # the reference is handed the program's indices at every
                # position; the kept ones are `parts`' own
                handed = np.asarray(every_idx[i - dense])
                ok &= np.array_equal(handed[:, kept],
                                     idx.reshape(rows, kept.size, -1))
                margins, own_idx = (
                    a.reshape((rows, positions) + a.shape[1:])[:, kept]
                    .reshape((-1,) + a.shape[1:])
                    for a in (margins, own_idx))
                x_own, _ = one(block, x_own, None)
                x_same, _ = one(block, x_same, jnp.asarray(
                    handed.reshape(rows * positions, -1)))
                same = (np.sort(idx, 1) == np.sort(own_idx, 1)).all(1)
                widest = float(margins[~same].max()) if (~same).any() \
                    else 0.0
                on_held = ((idx >= first) & (idx < first + held)).mean()
                # an application's counts are of all its positions: the
                # steps' are held to the indices, the blocks' are shown
                stepped = idx.reshape(rows, kept.size, -1)[
                    :, prefill // chunk:]
                c = counts[i - dense].sum(axis=0)
                print("layer %d: %.2f%% of %d tokens take the reference's "
                      "own %d experts; the others' widest margin %.3g "
                      "(limit %.3g); %.2f%% of the assignments on held "
                      "experts (%.2f%% with an even router); rows a held "
                      "expert over the run min %d mean %.1f max %d"
                      % (i, 100 * same.mean(), same.size, idx.shape[1],
                         widest, args.tie_margin, 100 * on_held,
                         100 * held / cfg["scored_experts"], c.min(),
                         c.mean(), c.max()), flush=True)
                ok &= same.mean() >= args.same_routing \
                    and widest <= args.tie_margin
                ok &= int(counts[i - dense][prefill // chunk:].sum()) == int(
                    ((stepped >= first) & (stepped < first + held)).sum())
                want = np.asarray(routed_of(
                    block, jnp.asarray(moe_in[i - dense]), jnp.asarray(idx)))
                off = rms(moe_out[i - dense], want)
                print("layer %d held experts' part: off the reference's "
                      "routed sum of the same input and indices by %.5f "
                      "of its root mean square (limit %.3g)"
                      % (i, off, args.routed_tol), flush=True)
                ok &= off <= args.routed_tol
            want_same, want_own = (np.asarray(x)[:, kept]
                                   for x in (x_same, x_own))
            r_same = rms(got_hidden[i], want_same)
            print("layer %d output: off the reference by %.5f of its root "
                  "mean square with the program's indices (limit %.3g; "
                  "%.4f at the widest), %.5f (%.4f) with its own"
                  % (i, r_same, args.hidden_tol,
                     rel(got_hidden[i], want_same),
                     rms(got_hidden[i], want_own),
                     rel(got_hidden[i], want_own)), flush=True)
            ok &= r_same <= args.hidden_tol
            del block
        eps = cfg["rms_norm_eps"]
        z_same, z_own = (np.asarray(
            reference.rms_norm(x[:, kept], ends["norm_f"], eps)
            @ ends["head"]) for x in (x_same, x_own))
    for name, z in (("the program's indices", z_same),
                    ("the reference's own routing", z_own)):
        first_tok = got_logits.argmax(-1)
        picked = np.take_along_axis(z, first_tok[..., None], -1)[..., 0]
        gaps = z.max(-1) - picked
        print("logits with %s: off by %.4f of the reference's root mean "
              "square %.3f (largest difference %.4f); the program's first "
              "token is not the reference's at %.2f%% of %d positions, "
              "gap widest %.4f mean %.3g"
              % (name, rel(got_logits, z), float(np.sqrt(np.mean(z * z))),
                 float(np.abs(got_logits - z).max()),
                 100 * (gaps > 0).mean(), gaps.size, gaps.max(),
                 gaps.mean()), flush=True)
    ok &= rel(got_logits, z_same) <= args.logit_tol
    print("pangu_check: %s" % ("ok" if ok else "FAIL"), flush=True)
    return 0 if ok else 1


def _margins(reference, cfg, block, x):
    """(the reference's margin between the last score it takes and the
    first it leaves out, its own indices) for the tokens entering the
    expert layer of `block` after x."""
    import jax
    import jax.numpy as jnp

    eps = cfg["rms_norm_eps"]
    a = x + reference.rms_norm(
        reference.attention(cfg, block, reference.rms_norm(
            x, block["input_norm"], eps)), block["post_attn_norm"], eps)
    u = reference.rms_norm(a, block["pre_mlp_norm"], eps)
    scores = jax.nn.sigmoid(u.reshape(-1, u.shape[-1]) @ block["router"])
    k = cfg["num_experts_per_tok"]
    top, idx = jax.lax.top_k(scores, k + 1)
    return top[:, k - 1] - top[:, k], idx[:, :k]


if __name__ == "__main__":
    sys.exit(main())
