"""Keye-VL-2.0-30B-A3B's served share against its plain float32 reference
at the published widths, outside any timed window: the cached step
Program of benchmark/models/keye_decode.py (a learned chooser over
grouped key/value caches, three-part rotary positions, the held softmax
experts and no shared one) from empty caches.  A prefill of `--prefill`
positions with one image span among them goes through the step that
takes a tower's vectors and three-part positions (as a prefill pool
would run it: `build(..., images=True)` under a `FunctionalProgram`),
`--block` positions an application (default: what the step's own
`prefill_block` says for these rows, 128 for 2 and 64 for the cell's 8;
1 is a position a call, as the step decodes); then `--decode` more text
positions through `fluid.ProgramDecoder`'s own step, handed the
`rope_delta` the image left.  With more positions than `sa_config.topk`
the chooser leaves slots out (at 4096 + 512 positions and 2048 chosen,
more than half of them at the end).  Every position's final stream (read
where the block still holds all its positions, before the step cuts out
its last: the head's logits are made of it here, position by position)
and chosen sets, a layer, and the caches as the last step left them,
against the reference's full forward (benchmark/reference/keye_vl2.py,
queries in blocks).

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
chooser left out; `selected_share`, over the prefill's queries that
choose (position >= topk), the share of the reference's own set that
the step chose too, the smallest layer's (the reference chooses on its
own float32 stream: deeper layers carry the drift); `cache_off`, the
three caches the steps wrote against what the reference would hold, the
root mean square of the difference over the reference's, the largest
over the first layer's three (whose inputs are the embeddings: no
drift), and `head_off`, the logits made here of a block's last position
against the step's own (a check of this script, not of the model).  Exit
code 1 when a number is outside its limit (LIMITS, with the readings
they were set from).  `--control indexer=[16,64,1024]` (or any
`--control key=value` of the step builder's arguments) and
`--index-dtype float8_e4m3fn` (the chooser's keys cached in three
mantissa bits) serve a step that is not the model: each must exit 1.
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
#
# My chip runs, PR 66, call 3, the prefill in blocks (seeds 66001 and
# 66002 at 64 positions an application, 66003 at the step's own 128 for
# 2 rows, 66001 again a position an application): the four numbers above
# 0.0443-0.0460, 0.0211-0.0233, 0.0952-0.0957, 0.0049-0.0053 (a block
# and a position an application differ in the fourth digit: 0.04602 and
# 0.04581 on seed 66001); `selected_share` 0.98397-0.98453 (by layer
# 0.9989, 0.9968, 0.9936, 0.9891, 0.9840: the reference chooses on its
# own float32 stream, and the deeper the layer the further the two
# streams have drifted); `cache_off` 0.00297 on every run (layers 1-4
# 0.0093-0.0419: drift again, which is why the first layer's is held);
# `head_off` at most 0.00014.  The controls, seed 66001 at 64 positions:
# `indexer=[16,64,1024]` `selected_share` 0.471 (0.500 in layer 0),
# logits 0.386 / 0.444 / 0.501 / 0.292 as PR 58 read them; index keys
# cached in float8_e4m3fn `cache_off` 0.0267 and `selected_share` 0.9709
# (layer 0 0.9935), logits 0.0707 / 0.0354 / 0.141 / 0.0126, inside
# their limits: the caches and the sets are what refuses it.  `cache_off`'s
# limit is three times the sound reading and a third of the control's;
# `selected_share`'s floor lies midway between the sound runs' lowest
# and the float8 control's; `head_off` checks this script's own head
LIMITS = {"logits_off_prefill": 0.14, "logits_off_image": 0.11,
          "logits_off_decode": 0.23, "gap_mean": 0.04,
          "cache_off": 0.009, "head_off": 0.002}
FLOORS = {"selected_share": 0.9775}


def check(lookup, cfg, workload, seed, rows, prefill, decode, image,
          control, block=0, index_dtype=None):
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
    layers, eps = cfg["num_hidden_layers"], cfg["rms_norm_eps"]
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
    ops = seeing["main"].global_block().desc.ops
    made_by = {name: od for od in ops for name in od.output_names()}

    def whole(name, until=None):
        """What a part of the block's last position was cut from: the
        Variable of all the block's positions (`until` "slice": what the
        first slice on the way back from `name` cut, the stream the head
        read)."""
        while True:
            od = made_by[name]
            if od.type not in ("slice", "gather", "reshape") \
                    and until is None:
                return name
            name = od.input("Input" if od.type == "slice" else "X")[0]
            if od.type == until:
                return name

    span = block or decoder._prefill_block
    if prefill % span:
        raise SystemExit("keye_check: a prefill of %d positions is not "
                         "whole blocks of %d" % (prefill, span))
    print("blocks of %d positions, %d applications" % (span,
                                                       prefill // span),
          flush=True)
    chosen = [whole(probe["selected"][1]) for _, probe in seeing["probes"]]
    extra = ["tok", "mrope_pos", "image_embeds", "image_mask"]
    fp = FunctionalProgram(
        seeing["main"].clone(for_test=True),
        extra + [f for f, _ in pairs],
        [seeing["logits"].name] + [o for _, o in pairs]
        + [whole(seeing["logits"].name, "slice")] + chosen)
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
    state = {feed: jnp.zeros(shape, jnp.dtype(
        index_dtype or workload["index_dtype"])
        if feed.startswith("index") else dtype)
             for feed, shape in built["cache_shapes"].items()}
    state["pos"] = jnp.zeros((rows,), jnp.int32)
    state["rope_delta"] = jnp.zeros((rows,), jnp.int32)
    # [applications, ..., block, ...], as the step's feeds lie
    count = prefill // span
    held = np.zeros((rows, prefill, cfg["hidden_size"]), np.float32)
    held[:, slots] = vectors
    held = held.reshape(rows, count, span, -1).swapaxes(0, 1)
    mask = np.zeros((rows, prefill, 1), np.float32)
    mask[:, slots] = 1.0
    mask = mask.reshape(rows, count, span, 1).swapaxes(0, 1)
    three = np.broadcast_to(
        positions[:, None, :prefill], (3, rows, prefill)).astype(np.int32) \
        .reshape(3, rows, count, span).transpose(2, 0, 1, 3)
    n_state = len(pairs)

    @jax.jit
    def drive(params, state, tokens, three, held, mask):
        """(logits [positions, rows, vocab], the step's own after each
        application, the chosen sets [layers, applications, rows, block,
        topk], the state after the last step): the prefill through the
        step that sees, a block an application, then the decoder's own
        step over the text."""
        norm_f, head = params["norm_f"], params["head.w"]

        def logits_of(stream):
            x = stream.astype(jnp.float32)
            x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                                  + eps) * norm_f.astype(jnp.float32)
            return jnp.dot(x.astype(head.dtype), head)

        def sees(state, fed):
            tok, at, vector, is_image = fed
            out, _ = fp(params, dict(
                state, tok=tok, mrope_pos=at, image_embeds=vector,
                image_mask=is_image))
            new = {f: v for (f, _), v in zip(pairs, out[1:1 + n_state])}
            return new, (logits_of(out[1 + n_state]), out[0],
                         tuple(out[2 + n_state:]))

        state, (first, own, sets) = jax.lax.scan(
            sees, state,
            (tokens[:, :prefill].reshape(rows, count, span).swapaxes(0, 1),
             three, held, mask))
        # [applications, rows, block, vocab] -> [positions, rows, vocab]
        first = first.transpose(0, 2, 1, 3).reshape(prefill, rows, -1)
        state["rope_delta"] = jnp.full_like(state["rope_delta"], delta)
        step = decoder._step_fn(params)

        def reads(state, tok):
            logits, state = step(state, tok)
            return state, logits

        state, rest = jax.lax.scan(reads, state, tokens[:, prefill:].T)
        return jnp.concatenate([first, rest]), own, sets, state

    t0 = time.perf_counter()
    got, own, sets, last = jax.device_get(drive(
        decoder._params, state, jnp.asarray(tokens), jnp.asarray(three),
        jnp.asarray(held, dtype), jnp.asarray(mask)))
    got = np.asarray(got, np.float32).transpose(1, 0, 2)
    served_s = time.perf_counter() - t0
    del decoder, drive, fp

    def off(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        return float(np.sqrt(np.mean(np.square(a - b))
                             / np.mean(np.square(b))))

    # this script's head against the step's own, a block's last position
    head_off = off(got[:, span - 1:prefill:span],
                   np.asarray(own, np.float32).swapaxes(0, 1))

    # the served weights, read up to float32 by the reference
    params = jax.jit(lambda k: model.weights(cfg, spec, k))(key)
    query_block = min(workload["reference_query_block"] * 4, total)
    while total % query_block:
        query_block //= 2
    want = reference.forward(
        cfg, params, tokens,
        positions=np.broadcast_to(positions[:, None], (3, rows, total)),
        vectors=vectors, image_slots=np.broadcast_to(slots,
                                                     (rows, slots.size)),
        held=(cfg["first_expert"], cfg["num_experts"]),
        query_block=query_block)
    left_out = 1.0 - float(np.mean(np.asarray(
        want["selection"][-1][:, -1]).sum(-1))) / total
    # the prefill's queries that choose: the share of the reference's
    # own set the step chose too
    top_k = cfg["sa_config"]["topk"]
    shares = []
    for i in range(layers):
        picked = np.asarray(sets[i]).swapaxes(0, 1).reshape(
            rows, prefill, -1)[:, top_k:]
        if not picked.size:
            break
        own_set = np.asarray(want["selection"][i])[:, top_k:prefill]
        both = np.take_along_axis(own_set, picked, axis=-1).sum()
        shares.append(float(both / own_set.sum()))
    cache_off = [max(
        off(last["k_cache_%d" % i],
            np.asarray(want["keys"][i]).transpose(0, 2, 1, 3)),
        off(last["v_cache_%d" % i],
            np.asarray(want["values"][i]).transpose(0, 2, 1, 3)),
        off(last["index_cache_%d" % i], want["index_keys"][i]))
        for i in range(layers)]
    want = np.asarray(want["logits"])

    chosen = np.argmax(got, axis=-1)
    gaps = want.max(-1) - np.take_along_axis(want, chosen[..., None],
                                             -1)[..., 0]
    return {"seed": seed, "control": control, "index_dtype": index_dtype,
            "rows": rows, "block": span,
            "prefill": prefill, "decode": decode, "image": list(image),
            "rope_delta": delta, "left_out": left_out,
            "logits_off_prefill": off(got[:, :prefill], want[:, :prefill]),
            "logits_off_image": off(got[:, slots], want[:, slots]),
            "logits_off_decode": off(got[:, prefill:], want[:, prefill:]),
            "gap_mean": float(gaps.mean()),
            "not_first_share": float((gaps > 0).mean()),
            "selected_share": min(shares, default=1.0),
            "selected_share_by_layer": shares,
            "cache_off": cache_off[0], "cache_off_by_layer": cache_off,
            "head_off": head_off, "served_s": served_s}


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
    p.add_argument("--block", type=int, default=0,
                   help="positions an application of the prefill (default: "
                        "the step's own prefill_block)")
    p.add_argument("--index-dtype", default=None,
                   help="a control: the type the index keys are cached in")
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
                        args.decode, image, control, args.block,
                        args.index_dtype)
            got["ok"] = all(got[name] <= limit
                            for name, limit in LIMITS.items()) \
                and all(got[name] >= limit for name, limit in FLOORS.items())
            ok = ok and got["ok"]
            print(json.dumps(got), flush=True)
            out.write(json.dumps(got) + "\n")
    print(json.dumps({"ok": ok, "limits": LIMITS, "floors": FLOORS}),
          flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
