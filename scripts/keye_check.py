"""Keye-VL-2.0-30B-A3B's served share against its plain float32 reference
at the published widths, outside any timed window: the cached step
Program of benchmark/models/keye_decode.py (a learned chooser over
grouped key/value caches, three-part rotary positions, the held softmax
experts and no shared one) from empty caches.  A prefill of `--prefill`
positions with one image span among them goes through the step that
takes a tower's vectors and three-part positions (a position a call, as
a prefill pool would run it: `build(..., images=True)` under a
`FunctionalProgram`); then `--decode` more text positions through
`fluid.ProgramDecoder`'s own step, handed the `rope_delta` the image
left.  With more positions than `sa_config.topk` the chooser leaves
slots out (at 4096 + 512 positions and 2048 chosen, more than half of
them at the end).  Logits at every position against the reference's full
forward (benchmark/reference/keye_vl2.py, queries in blocks).

    chiprun --timeout 1500 -- python scripts/keye_check.py --seeds 1,2
    python scripts/keye_check.py --config keye-tiny \
        --workload keye-tiny-turn --search-path benchmark/tests/fixture \
        --prefill 24 --decode 16 --image 4,2,3      # a rehearsal on the CPU

Numbers, a seed: `logits_off`, the root mean square of the logits'
difference over the reference's, over the prefill's positions, over the
image span's alone and over the decoded ones; `not_first_share`, the
share of positions whose largest logit is not the reference's;
`gap_mean`, by how much the reference's logit of the step's choice lies
below its best; `left_out`, the share of the last position's slots its
chooser left out.  Exit code 1 when a number is outside its limit
(LIMITS, with the readings they were set from).  `--control
indexer=[16,64,1024]` (or any `--control key=value` of the step
builder's arguments) serves a step that is not the model: it must exit
1.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# my chip runs, PR 58, call 5, the committed files alone, at the
# published widths (2 rows, 4096 + 512 positions, one 32 x 32 image span
# from slot 1500, bfloat16 weights and caches against the float32
# reference on the same weights read up; 55.6% of the last position's
# slots left out).  Sound, seeds 1 and 2: logits_off_prefill 0.0451,
# 0.0446; logits_off_image 0.0206, 0.0227; logits_off_decode 0.0967,
# 0.0964 (past 2048 positions a slot at the edge of the chosen set
# changes places between bfloat16 and float32, and carries as much
# attention as any other); gap_mean 0.00507, 0.00474 (7.8-7.9% of the
# positions' largest logit is not the reference's).  The control
# `indexer=[16,64,1024]`, seed 1: 0.385, 0.443, 0.498, 0.295 (56.3%).
# Each limit lies 2.4 to 7.9 times over the larger sound reading and 2.2
# to 7.4 under the control's.
LIMITS = {"logits_off_prefill": 0.14, "logits_off_image": 0.11,
          "logits_off_decode": 0.23, "gap_mean": 0.04}


def check(lookup, cfg, workload, seed, rows, prefill, decode, image,
          control):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.jit import FunctionalProgram

    model = lookup.module("models", workload["builder"])
    reference = lookup.module("reference", workload["reference"])
    spec = dict(workload["weights"], seed=seed)
    total = prefill + decode
    cfg = dict(cfg, serve_positions=total)
    key = jax.random.PRNGKey(seed)
    made = jax.jit(lambda k: model.weights(cfg, spec, k))(key)
    built = model.build(cfg, rows, **control)
    seeing = model.build(cfg, rows, images=True, **control)
    scope = fluid.Scope()
    names = jax.tree_util.tree_leaves(built["param_names"])
    for name, value in zip(names, jax.tree_util.tree_leaves(made)):
        scope.set(name, value)
    del made
    decoder = fluid.ProgramDecoder(
        built["main"].clone(for_test=True), token_name="tok",
        logits_name=built["logits"].name, state_pairs=built["state_pairs"],
        scope=scope, max_positions=total)
    pairs = seeing["state_pairs"]
    extra = ["tok", "mrope_pos", "image_embeds", "image_mask"]
    fp = FunctionalProgram(
        seeing["main"].clone(for_test=True),
        extra + [f for f, _ in pairs],
        [seeing["logits"].name] + [o for _, o in pairs])
    del scope

    rng = np.random.default_rng([seed, 0xE7A])
    tokens = rng.integers(0, cfg["vocab_size"], (rows, total),
                          dtype=np.int32)
    slot, h, w = image
    slots = slot + np.arange(h * w)
    vectors = rng.standard_normal((rows, h * w, cfg["hidden_size"]),
                                  dtype=np.float32) \
        * np.float32(spec.get("embed_std", 1.0))
    positions, after = reference.layout(total, [image])
    delta = after - total
    dtype = jnp.dtype(workload["serve_dtype"])
    state = {feed: jnp.zeros(shape, jnp.dtype(workload["index_dtype"])
                             if feed.startswith("index") else dtype)
             for feed, shape in built["cache_shapes"].items()}
    state["pos"] = jnp.zeros((rows,), jnp.int32)
    state["rope_delta"] = jnp.zeros((rows,), jnp.int32)
    held = np.zeros((prefill, rows, 1, cfg["hidden_size"]), np.float32)
    held[slots] = vectors.transpose(1, 0, 2)[:, :, None]
    mask = np.zeros((prefill, rows, 1, 1), np.float32)
    mask[slots] = 1.0
    three = np.broadcast_to(positions.T[:prefill, :, None, None],
                            (prefill, 3, rows, 1)).astype(np.int32)

    @jax.jit
    def drive(params, state, tokens, three, held, mask):
        """(logits [positions, rows, vocab]): the prefill through the
        step that sees, then the decoder's own step over the text."""
        def sees(state, fed):
            tok, at, vector, is_image = fed
            (logits, *new), _ = fp(params, dict(
                state, tok=tok, mrope_pos=at, image_embeds=vector,
                image_mask=is_image))
            return {f: v for (f, _), v in zip(pairs, new)}, logits

        state, first = jax.lax.scan(
            sees, state, (tokens[:, :prefill].T, three, held, mask))
        state["rope_delta"] = jnp.full_like(state["rope_delta"], delta)
        step = decoder._step_fn(params)

        def reads(state, tok):
            logits, state = step(state, tok)
            return state, logits

        return jnp.concatenate(
            [first, jax.lax.scan(reads, state, tokens[:, prefill:].T)[1]])

    t0 = time.perf_counter()
    got = np.asarray(drive(
        decoder._params, state, jnp.asarray(tokens), jnp.asarray(three),
        jnp.asarray(held, dtype), jnp.asarray(mask)),
        np.float32).transpose(1, 0, 2)
    served_s = time.perf_counter() - t0
    del decoder, drive, fp

    # the served weights, read up to float32 by the reference
    params = jax.jit(lambda k: model.weights(cfg, spec, k))(key)
    block = min(workload["reference_query_block"] * 4, total)
    while total % block:
        block //= 2
    want = reference.forward(
        cfg, params, tokens,
        positions=np.broadcast_to(positions[:, None], (3, rows, total)),
        vectors=vectors, image_slots=np.broadcast_to(slots,
                                                     (rows, slots.size)),
        held=(cfg["first_expert"], cfg["num_experts"]), query_block=block)
    left_out = 1.0 - float(np.mean(np.asarray(
        want["selection"][-1][:, -1]).sum(-1))) / total
    want = np.asarray(want["logits"])

    def off(a, b):
        return float(np.sqrt(np.mean(np.square(a - b))
                             / np.mean(np.square(b))))

    chosen = np.argmax(got, axis=-1)
    gaps = want.max(-1) - np.take_along_axis(want, chosen[..., None],
                                             -1)[..., 0]
    return {"seed": seed, "control": control, "rows": rows,
            "prefill": prefill, "decode": decode, "image": list(image),
            "rope_delta": delta, "left_out": left_out,
            "logits_off_prefill": off(got[:, :prefill], want[:, :prefill]),
            "logits_off_image": off(got[:, slots], want[:, slots]),
            "logits_off_decode": off(got[:, prefill:], want[:, prefill:]),
            "gap_mean": float(gaps.mean()),
            "not_first_share": float((gaps > 0).mean()),
            "served_s": served_s}


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--config", default="keye-vl-2.0-30b-a3b")
    p.add_argument("--workload", default="keye-turn-64k-ep8")
    p.add_argument("--seeds", default="1")
    p.add_argument("--rows", type=int, default=2)
    p.add_argument("--prefill", type=int, default=4096)
    p.add_argument("--decode", type=int, default=512)
    p.add_argument("--image", default="1500,32,32",
                   help="slot,h,w of the one image span of the prefill")
    p.add_argument("--control", action="append", default=[])
    p.add_argument("--search-path", action="append", default=[])
    args = p.parse_args(argv)
    from benchmark import harness

    lookup = harness.Lookup(args.search_path)
    cfg = lookup.json("configs", args.config)
    workload = lookup.json("workloads", args.workload)
    control = {}
    for assignment in args.control:
        name, _, text = assignment.partition("=")
        control[name] = json.loads(text)
    image = tuple(int(n) for n in args.image.split(","))
    if image[0] + image[1] * image[2] > args.prefill:
        raise SystemExit("the image span %s does not lie inside a prefill "
                         "of %d positions" % (image, args.prefill))
    import jax

    print("devices: %s" % jax.devices(), flush=True)
    harness.place_compile_cache()
    os.makedirs("chiprun_out", exist_ok=True)
    ok = True
    with open("chiprun_out/keye_check.jsonl", "a") as out:
        for seed in (int(s) for s in args.seeds.split(",") if s):
            got = check(lookup, cfg, workload, seed, args.rows, args.prefill,
                        args.decode, image, control)
            got["ok"] = all(got[name] <= limit
                            for name, limit in LIMITS.items())
            ok = ok and got["ok"]
            print(json.dumps(got), flush=True)
            out.write(json.dumps(got) + "\n")
    print(json.dumps({"ok": ok, "limits": LIMITS}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
