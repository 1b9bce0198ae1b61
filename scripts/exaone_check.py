"""K-EXAONE-236B-A23B's served share against its plain float32 reference
at the published widths, outside any timed window: the cached step
Program of benchmark/models/exaone_decode.py (window and full attention
layers over grouped heads, a ring beside a whole-extent cache, the held
experts) driven through `fluid.ProgramDecoder`'s step from empty caches
(a prefill of `--prefill` positions, then decoding `--decode` more: at
least three window lengths through the ring), twice: position by
position throughout, its logits at every position, and with the prefill
in blocks of `models.decode.PREFILL_BLOCK` positions, as the decoder
prefills a prompt (a block through the rings and through the live-slot
kernel; the logits after each block and at every decoded position);
both against the reference's full forward
(benchmark/reference/exaone_moe.py, a turn at a time).

    chiprun --timeout 1500 -- python scripts/exaone_check.py --seeds 1,2,3
    python scripts/exaone_check.py --config exaone-tiny \
        --workload exaone-tiny-turn --search-path benchmark/tests/fixture \
        --prefill 16 --decode 32            # a rehearsal on the CPU

Numbers, a seed: `logits_off`, the root mean square of the logits'
difference over the reference's, over the prefill's positions and over
the decoded ones apart (`_blocks`: the same two of the run whose prefill
went in blocks, the first over the blocks' last positions alone);
`not_first_share`, the share of positions whose
largest logit is not the reference's; `gap_mean`, by how much the
reference's logit of the step's choice lies below its best.  Exit code 1
when a number is outside its limit (LIMITS, with the readings they were
set from).  `--control window=64` (or any `--control key=value` of the
step builder's arguments) serves a step that is not the model: it must
exit 1.  `--kernel` times the decode kernel alone at the cell's shape,
over blocks of slots and live lengths, a step (T = 1) and a block of
128 positions (the question's prefill).
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# my chip runs, PR 44, at the published widths (2 rows, 256 + 512
# positions, bfloat16 weights and caches against the float32 reference).
# Sound, seeds 1, 2, 3: logits_off_prefill 0.0349-0.0448,
# logits_off_decode 0.0412-0.0462, gap_mean 0.0048-0.0067 (7.2-8.9% of
# the positions' largest logit is not the reference's).  The control
# `window=64`, seed 1: 0.891, 1.103, 3.43 (91%).  Each limit lies 4.3 to
# 7.5 times over the largest sound reading and 4.5 to 70 under the
# control's: a step that is not the model is refused, rounding is not.
# Since PR 46 the builder's residual stream is float32 and the prefill is
# also run in blocks of 128, held to the same limits (my chip runs, PR
# 46, seeds 1, 2, 3): logits_off_prefill 0.0348-0.0388, logits_off_decode
# 0.0391-0.0437; in blocks 0.0240-0.0284 (the blocks' last positions) and
# 0.0375-0.0435; gap_mean 0.0053-0.0062.
LIMITS = {"logits_off_prefill": 0.2, "logits_off_decode": 0.2,
          "logits_off_prefill_blocks": 0.2, "logits_off_decode_blocks": 0.2,
          "gap_mean": 0.05}


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
    state = {feed: jnp.zeros(shape, dtype)
             for feed, shape in built["cache_shapes"].items()}
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

    root = model.root(key)
    ends = reference._f32(jax.jit(lambda k: model.ends(cfg, spec, k))(root))
    layers = reference.Layers(cfg, min(workload["reference_query_block"],
                                       prefill))
    size = prefill
    while total % size:
        size //= 2
    xs = [[ends["embed"][jnp.asarray(tokens[r, at:at + size])]
           for at in range(0, total, size)] for r in range(rows)]
    for i in range(cfg["num_hidden_layers"]):
        block = reference._f32(
            jax.jit(lambda k: model.block(cfg, spec, k, i))(root))
        for r in range(rows):
            k = v = layers.nothing_before(i)
            for t in range(total // size):
                xs[r][t], k, v, _ = layers(i, block, xs[r][t], t * size, k, v)
        del block

    @jax.jit
    def head(x):
        with jax.default_matmul_precision("highest"):
            return reference.rms_norm(x, ends["norm_f"],
                                      cfg["rms_norm_eps"]) @ ends["head"]

    want = np.stack([np.concatenate([np.asarray(head(x)) for x in xs[r]])
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
            "gap_mean": float(gaps.mean()),
            "not_first_share": float((gaps > 0).mean()),
            "served_s": served_s, "served_blocks_s": served_blocks_s}


def kernel(out):
    """The decode kernel alone at the cell's shape: 8 rows, 8 key/value
    heads, 8 query heads a group, 32,768 slots, bfloat16; a step, and a
    block of 128 positions a row from the cell's session length on."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.kernels import gqa_decode

    rows, kv, group, dim, slots = 8, 8, 8, 128, 32768
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    k, v = (jax.random.normal(key, (rows, kv, slots, dim), jnp.bfloat16)
            for key in keys[1:])
    cases = [(0, 1, bk, last) for bk in (2048, 1024, 512, 256)
             for last in (8191, 16383, 32255)] + [(128, 1, 128, 127)] \
        + [(0, 128, bk, last) for bk in (2048, 1024, 512, 256)
           for last in (31744, 31800)]
    for window, positions, bk, last in cases:
        cache = window or slots
        kc, vc = k[:, :, :cache], v[:, :, :cache]
        q = jax.random.normal(keys[0], (rows, kv, group * positions, dim),
                              jnp.bfloat16)
        fn = jax.jit(lambda q, kc, vc, last, bk=bk, window=window,
                     positions=positions: gqa_decode.gqa_decode(
                         q, kc, vc, last, dim ** -0.5, window, bk, positions))
        got = fn(q, kc, vc, jnp.int32(last))
        # the plain products of the first row's first head: at 128
        # positions every head's float32 scores would be 8.6 GB
        top = last + positions
        s = jnp.einsum("gd,kd->gk", q[0, 0].astype(jnp.float32),
                       kc[0, 0, :top].astype(jnp.float32)) * dim ** -0.5
        limit = last + jnp.arange(group * positions) % positions
        s = jnp.where(jnp.arange(top)[None, :] <= limit[:, None], s, -1e30)
        want = jax.nn.softmax(s, -1) @ vc[0, 0, :top].astype(jnp.float32)
        err = float(jnp.max(jnp.abs(got[0, 0].astype(jnp.float32) - want)))
        jax.block_until_ready(fn(q, kc, vc, jnp.int32(last)))
        t0 = time.perf_counter()
        for _ in range(20):
            got = fn(q, kc, vc, jnp.int32(last))
        jax.block_until_ready(got)
        ms = (time.perf_counter() - t0) / 20 * 1e3
        live = rows * kv * top * dim * 2 * 2
        # two products a pair of query and attended slot
        pairs = rows * kv * group * sum(last + 1 + t
                                        for t in range(positions))
        line = {"kernel": "gqa_decode", "window": window,
                "positions": positions, "block_k": bk, "last": last,
                "ms": ms, "live_gb": live / 1e9,
                "hbm_share": live / 819e9 / (ms / 1e3),
                "mxu_share": 4 * pairs * dim / 197e12 / (ms / 1e3),
                "max_abs_err": err}
        print(json.dumps(line), flush=True)
        out.write(json.dumps(line) + "\n")


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--config", default="k-exaone-236b-a23b")
    p.add_argument("--workload", default="exaone-turn-32k-ep16")
    p.add_argument("--seeds", default="1")
    p.add_argument("--rows", type=int, default=2)
    p.add_argument("--prefill", type=int, default=256)
    p.add_argument("--decode", type=int, default=512)
    p.add_argument("--control", action="append", default=[])
    p.add_argument("--kernel", action="store_true")
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
    with open("chiprun_out/exaone_check.jsonl", "a") as out:
        if args.kernel:
            kernel(out)
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
