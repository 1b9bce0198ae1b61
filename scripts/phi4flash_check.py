"""Phi-4-mini-flash-reasoning, whole, against its plain float32 reference
at the published widths, outside any timed window: the cached step
Program of benchmark/models/phi4flash_decode.py (Mamba-1 layers through
`selective_scan` and `causal_conv1d`, differential attention over rings
and one whole-extent cache, cross layers that read that cache, gated
memory units) driven through `fluid.ProgramDecoder`'s step from empty
states, twice: position by position throughout, its logits at every
position; and with the first `--prefill` positions in blocks of
`models.decode.PREFILL_BLOCK`, as the decoder prefills a prompt (the
cross-decoder at each block's last position alone; the logits after each
block and at every decoded position); both against the reference's full
forward, every layer at every position (benchmark/reference/
phi4_flash.py, a turn at a time).

    chiprun --timeout 1500 -- python scripts/phi4flash_check.py --seeds 1,2,3
    python scripts/phi4flash_check.py --config phi4flash-tiny \
        --workload phi4flash-tiny-turn --search-path benchmark/tests/fixture \
        --prefill 16 --decode 24            # a rehearsal on the CPU

Numbers, a seed: `logits_off`, the root mean square of the logits'
difference over the reference's, over the prefill's positions and over
the decoded ones apart (`_blocks`: the same two of the run whose prefill
went in blocks, the first over the blocks' last positions alone);
`logits_off_first`, the same at position 0 alone, where every cache is
empty but for the step's own slot; `not_first_share`, the share of
positions whose largest logit is not the reference's; `gap_mean`, by how much the reference's logit of the step's
choice lies below its best.  Exit code 1 when a number is outside its
limit (LIMITS, with the readings they were set from).  `--control
key=value` (any of the step builder's arguments: `window=256`,
`subtract=false`, `memory_after_gate=true`, `cross_before_write=true`)
serves a step that is not the model: it must exit 1.  From empty states
a cross layer that reads the cache before its write attends nothing of
its own at position 0, which the cell's long session hides.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# my chip runs, PR 63, call 3, at the published widths (2 rows, 256 +
# 512 positions from empty states, bfloat16 weights and caches against
# the float32 reference).  Sound, seeds 1, 2, 3: logits_off_prefill
# 0.0730-0.0745, logits_off_decode 0.0784-0.0795, in blocks 0.0697-0.0785
# (the blocks' last positions) and 0.0781-0.0788, logits_off_first
# 0.0327-0.0352, gap_mean 0.0132-0.0147 (17.8-20.1% of the positions'
# largest logit is not the reference's).  The controls, seed 1, each
# exit 1: `cross_before_write=true` logits_off_first 0.1028 and the
# blocks' last positions 0.1140 (every other number sound: a slot among
# hundreds moves little, the only slot of position 0 moves that
# position); `memory_after_gate=true` 0.212-0.239, gap_mean 0.130;
# `subtract=false` 0.896-0.930, gap_mean 1.98; `window=256` the decoded
# positions 1.015, gap_mean 1.58 (the prefill's 256 positions lie inside
# either window).  Each limit is the geometric mean of the largest sound
# reading and the least control reading it has to refuse (1.6-1.7 times
# from either): 0.0795 and 0.2124 (the memory's), 0.0352 and 0.1028,
# 0.0147 and 0.130.
LIMITS = {"logits_off_prefill": 0.13, "logits_off_decode": 0.13,
          "logits_off_prefill_blocks": 0.13, "logits_off_decode_blocks": 0.13,
          "logits_off_first": 0.06, "gap_mean": 0.044}


def check(lookup, cfg, workload, seed, rows, prefill, decode, control):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu.fluid as fluid

    model = lookup.module("models", workload["builder"])
    reference = lookup.module("reference", workload["reference"])
    spec = dict(workload["weights"], seed=seed)
    total = prefill + decode
    cfg = dict(cfg, serve_positions=total)
    built = model.build(cfg, rows, **control)
    key = jax.random.PRNGKey(seed)
    made = jax.jit(lambda k: model.weights(cfg, spec, k))(key)
    scope = fluid.Scope()
    names = jax.tree_util.tree_leaves(built["param_names"])
    for name, value in zip(names, jax.tree_util.tree_leaves(made)):
        scope.set(name, value)
    del made
    decoder = fluid.ProgramDecoder(
        built["main"].clone(for_test=True), token_name="tok",
        logits_name=built["logits"].name, state_pairs=built["state_pairs"],
        scope=scope, max_positions=total)
    del scope
    tokens = np.random.default_rng([seed, 0xE7A]).integers(
        0, cfg["vocab_size"], (rows, total), dtype=np.int32)
    dtype = jnp.dtype(workload["serve_dtype"])
    state = {feed: jnp.zeros(shape, jnp.float32
                             if feed.startswith("ssm_state") else dtype)
             for feed, shape in built["state_shapes"].items()}
    state["pos"] = jnp.zeros((rows,), jnp.int32)

    from paddle_tpu.models.decode import PREFILL_BLOCK

    chunk = min(PREFILL_BLOCK, prefill)
    if prefill % chunk:
        raise SystemExit("--prefill %d is not whole blocks of %d"
                         % (prefill, chunk))

    def steps(step, state, tokens):
        """(state, logits [positions, rows, vocab]): a position an
        application."""
        def body(state, tok):
            logits, state = step(state, tok)
            return state, logits

        return jax.lax.scan(body, state, tokens.T)

    @jax.jit
    def drive(params, state, tokens):
        return steps(decoder._step_fn(params), state, tokens)[1]

    @jax.jit
    def drive_blocks(params, state, tokens):
        """The prefill in blocks (the logits after each), then a
        position an application."""
        step = decoder._step_fn(params)

        def body(state, toks):
            logits, state = step(state, toks)
            return state, logits

        state, ends = jax.lax.scan(
            body, state,
            tokens[:, :prefill].reshape(rows, -1, chunk).swapaxes(0, 1))
        return ends, steps(step, state, tokens[:, prefill:])[1]

    def host(logits):   # [positions, rows, vocab] -> [rows, positions, ...]
        return np.asarray(logits, np.float32).transpose(1, 0, 2)

    t0 = time.perf_counter()
    got = host(drive(decoder._params, state, jnp.asarray(tokens)))
    served_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    ends_got, rest_got = (host(z) for z in drive_blocks(
        decoder._params, state, jnp.asarray(tokens)))
    served_blocks_s = time.perf_counter() - t0
    del decoder, drive, drive_blocks

    # the reference: every layer at every position, a turn at a time
    root = model.root(key)
    ends = reference._f32(jax.jit(lambda k: model.ends(cfg, spec, k))(root))
    layers = reference.Layers(cfg, workload["reference_query_block"])
    size = chunk
    while total % size:
        size //= 2
    xs = [[ends["embed"][jnp.asarray(tokens[r, at:at + size])]
           for at in range(0, total, size)] for r in range(rows)]
    turns = range(total // size)
    shared = [[None] * len(turns) for _ in range(rows)]
    memory = [[None] * len(turns) for _ in range(rows)]
    for i, kind in enumerate(layers.kinds):
        block = reference._f32(
            jax.jit(lambda k: model.block(cfg, spec, k, i))(root))
        for r in range(rows):
            held = layers.nothing_before(i, total)
            for t in turns:
                xs[r][t], held, given, _ = layers(
                    i, block, xs[r][t], t * size, held, shared[r][t],
                    memory[r][t])
                if kind == reference.FULL:
                    shared[r][t] = given
                elif given is not None:
                    memory[r][t] = given
        del block

    @jax.jit
    def head(ends, x):
        with jax.default_matmul_precision("highest"):
            return reference.layer_norm(
                x, ends["norm_f"]["w"], ends["norm_f"]["b"],
                cfg["layer_norm_eps"]) @ ends["embed"].T

    want = np.stack([np.concatenate([np.asarray(head(ends, x))
                                     for x in xs[r]])
                     for r in range(rows)])

    def off(a, b):
        return float(np.sqrt(np.mean(np.square(a - b))
                             / np.mean(np.square(b))))

    chosen = np.argmax(got, axis=-1)
    gaps = want.max(-1) - np.take_along_axis(want, chosen[..., None], -1)[..., 0]
    return {"seed": seed, "control": control, "rows": rows,
            "prefill": prefill, "decode": decode,
            "logits_off_prefill": off(got[:, :prefill], want[:, :prefill]),
            "logits_off_decode": off(got[:, prefill:], want[:, prefill:]),
            "logits_off_prefill_blocks": off(
                ends_got, want[:, chunk - 1:prefill:chunk]),
            "logits_off_decode_blocks": off(rest_got, want[:, prefill:]),
            "logits_off_first": off(got[:, :1], want[:, :1]),
            "gap_mean": float(gaps.mean()),
            "not_first_share": float((gaps > 0).mean()),
            "served_s": served_s, "served_blocks_s": served_blocks_s}


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--config", default="phi-4-mini-flash-reasoning")
    p.add_argument("--workload", default="phi4flash-turn-16k")
    p.add_argument("--seeds", default="1")
    p.add_argument("--rows", type=int, default=2)
    p.add_argument("--prefill", type=int, default=256)
    p.add_argument("--decode", type=int, default=512)
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
    import jax

    print("devices: %s" % jax.devices(), flush=True)
    harness.place_compile_cache()
    os.makedirs("chiprun_out", exist_ok=True)
    ok = True
    with open("chiprun_out/phi4flash_check.jsonl", "a") as out:
        for seed in (int(s) for s in args.seeds.split(",") if s):
            got = check(lookup, cfg, workload, seed, args.rows, args.prefill,
                        args.decode, control)
            got["ok"] = all(got[name] <= limit
                            for name, limit in LIMITS.items())
            ok = ok and got["ok"]
            print(json.dumps(got), flush=True)
            out.write(json.dumps(got) + "\n")
    print(json.dumps({"ok": ok, "limits": LIMITS}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
