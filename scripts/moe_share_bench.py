"""Times `moe_experts` and its gradient op alone (ops/moe.py, the range
form) on the chip at smallthinker-train-16k-ep8's shape, 16384 tokens x 6
of 64 experts scored, experts 24..31 held, 2560 -> 768, ReGLU, bfloat16
compute, by how many of the 98304 assignments the routers send the held
range: milliseconds a call of the forward op, and of the gradient op as
what the two in one program take more than the forward alone (host clock
around 10 calls of a jitted program that end in block_until_ready), and
the first digits of the SHA-256 of the output and of each gradient, which
two checkouts on the same chip can be held to; one JSON line a variant on
the output and under `chiprun_out/moe_share_bench.jsonl`.

`python scripts/moe_share_bench.py [--root DIR] [--chunks 2048,4096]
[--held 0,12288,...] [--shape 16384,2560,768]`: `--root` names the
checkout whose `paddle_tpu` is timed (another commit unpacked beside this
one); `--chunks` sets `ops.moe._CHUNK_ROWS` to each value in turn where
the checkout has it (one program a value; the held rows are data);
`--shape` is tokens, hidden and expert width (smaller: a rehearsal on the
CPU).
"""

import argparse
import hashlib
import json
import os
import sys
import time

import numpy as np

K, SCORED, HELD, FIRST = 6, 64, 8, 24
CALLS = 10


def routing(held_rows, seed, N):
    """TopIdx [N, K]: `held_rows` of the assignments on the held range,
    the rest on the 56 other experts."""
    rs = np.random.RandomState(seed)
    absent = np.array([e for e in range(SCORED)
                       if not FIRST <= e < FIRST + HELD])
    flat = absent[rs.randint(0, len(absent), N * K)]
    where = rs.permutation(N * K)[:held_rows]
    flat[where] = FIRST + rs.randint(0, HELD, held_rows)
    return flat.reshape(N, K).astype(np.int32)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=".")
    ap.add_argument("--chunks", default="")
    ap.add_argument("--held", default="0,12288,24576,28672,40960,73728,98304")
    ap.add_argument("--shape", default="16384,2560,768",
                    help="tokens, hidden, expert width (smaller: a "
                    "rehearsal on the CPU)")
    args = ap.parse_args()
    sys.path.insert(0, args.root)
    N, D, F = (int(v) for v in args.shape.split(","))

    import jax
    import jax.numpy as jnp

    import paddle_tpu.fluid as fluid
    from paddle_tpu.ops import moe as moe_ops
    from paddle_tpu.ops import registry

    info = registry.get_op_info("moe_experts")
    attrs = {"first_expert": FIRST, "scored": SCORED, "activation": "relu"}
    rs = np.random.RandomState(0)
    bf16 = jnp.bfloat16
    ins = {"X": [jnp.asarray(rs.randn(1, N, D), bf16)],
           "TopW": [jnp.asarray(rs.uniform(0.1, 0.3, (N, K)), jnp.float32)],
           "WGate": [jnp.asarray(rs.randn(HELD, D, F) * 0.02, jnp.float32)],
           "WUp": [jnp.asarray(rs.randn(HELD, D, F) * 0.02, jnp.float32)],
           "WDown": [jnp.asarray(rs.randn(HELD, F, D) * 0.02, jnp.float32)]}
    d_out = jnp.asarray(rs.randn(1, N, D), bf16)

    def forward(ins):
        with fluid.amp.bf16_guard():
            return info.kernel(None, ins, attrs)

    def both(ins, d_out):
        """The op and its gradient op as one program, as a training
        step holds them: what the forward keeps is the program's own."""
        outs = forward(ins)
        grad_ins = dict(ins, **{"OG@Out": [d_out]})
        grad_ins.update({"O@" + slot: v for slot, v in outs.items()})
        with fluid.amp.bf16_guard():
            grads = info.grad_kernel(None, grad_ins, attrs)
        return outs["Out"][0], outs["Counts"][0], grads

    def ms(fn, *a):
        out = jax.block_until_ready(fn(*a))
        t0 = time.perf_counter()
        for _ in range(CALLS):
            out = fn(*a)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / CALLS * 1e3, out

    def digest(a):
        return hashlib.sha256(np.asarray(a).tobytes()).hexdigest()[:16]

    os.makedirs("chiprun_out", exist_ok=True)
    chunks = [int(c) for c in args.chunks.split(",") if c] or [None]
    for chunk in chunks:
        if chunk is not None:
            moe_ops._CHUNK_ROWS = chunk
        # a function of its own a value: jit keeps what it traced
        fwd = jax.jit(lambda *a: forward(*a))
        step = jax.jit(lambda *a: both(*a))
        for held_rows in (int(h) for h in args.held.split(",")):
            ins["TopIdx"] = [jnp.asarray(routing(held_rows, held_rows, N))]
            fwd_ms, _ = ms(fwd, ins)
            step_ms, (out, counts, grads) = ms(step, ins, d_out)
            line = {"root": args.root, "chunk": chunk, "held_rows": held_rows,
                    "counts_sum": int(np.asarray(counts).sum()),
                    "fwd_ms": fwd_ms, "bwd_ms": step_ms - fwd_ms,
                    "sha256": {"Out": digest(out), **{
                        slot: digest(v[0]) for slot, v in grads.items()}},
                    "device": jax.devices()[0].device_kind}
            print(json.dumps(line), flush=True)
            with open("chiprun_out/moe_share_bench.jsonl", "a") as f:
                f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
