"""Times Mamba-2's step kernel (kernels/ssd_step.py) alone on the chip at
granite-decode-ep4's shape (64 rows, 128 heads of 64 over 128 state
entries: a float32 state [64, 128, 8192], 268 MB a layer) by the rows of
state a grid step takes and by the copies a block comes in and goes out
as, beside the plain step (`ops/ssm.py ssd_update`): what `_STEP_BYTES`,
`_READ_CUTS` and `_WRITE_CUTS` in `paddle_tpu/kernels/ssd_step.py` were
decided from (PERF.md section 6, PR 72).

    chiprun -- python scripts/ssd_step_bench.py               # the sweep
    chiprun -- python scripts/ssd_step_bench.py --block 2 \
        --read-cuts 8 --write-cuts 16,4 --layers 1 --layers 9 --check
    chiprun -- python scripts/ssd_step_bench.py --streams     # yardsticks

A call runs as a decoder's scan runs it: the state carried from call to
call through the kernel's alias, the next call's x this one's y.  With
`--layers 9` an iteration is nine layers' calls, a state each, one after
the other, as a step of the cell has them.  ms a call is the slope
between a short and a long loop in one program, a layer
(`scripts/gdn_step_bench.py`'s `slope`); the share of the HBM peak is the
step's bytes (the state in and out, x, y, dt, the decay, B and C at
their own sizes: what `benchmark/flops/ssd_step.py step` counts) at 819
GB/s over that time.  `call` is the kernel alone over operands that are
there; `step` is the op's whole step, the rows made from x, dt and a
beside it and `D x` added; `plain` is `ssd_update`.  `--check` holds
every block's output and state to `ssd_update` on the chip (one call
from the same state; exits 1 past 2e-5).

`--streams` are `scripts/gdn_step_bench.py`'s yardsticks over the same
268 MB (read as [64, 64, 128, 128]): a stream of reads, of writes, both
at once and XLA's own pass.  One JSON line a variant, all of them in
`chiprun_out/ssd_step_bench.jsonl`.
"""

import argparse
import itertools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp
from jax import lax
import numpy as np

import gdn_step_bench as yardsticks
from paddle_tpu.kernels import ssd_step
from paddle_tpu.ops import ssm

OUT = "chiprun_out/ssd_step_bench.jsonl"
# slices of the sublanes x slices of the lanes a block goes out as
WRITES = ((4, 1), (16, 1), (1, 16), (8, 4), (16, 4), (16, 16))


def operands(rows, heads, dim, entries, layers, seed=0):
    """x, dt, a, b, c, d_skip and `layers` states as the decoder
    carries them."""
    rs = np.random.RandomState(seed)
    f32 = lambda t: jnp.asarray(t, jnp.float32)
    dt = f32(np.exp(rs.uniform(np.log(1e-3), np.log(0.3), (rows, heads))))
    return (f32(rs.randn(rows, heads * dim)), dt,
            dt * f32(-np.exp(rs.uniform(0, 2.7, heads))),
            f32(0.5 * rs.randn(rows, entries)),
            f32(0.5 * rs.randn(rows, entries)),
            f32(1.0 + 0.1 * rs.randn(heads)),
            tuple(f32(0.3 * rs.randn(rows, entries, heads * dim))
                  for _ in range(layers)))


def step_bytes(rows, heads, dim, entries):
    """benchmark/flops/ssd_step.py `step`, a layer."""
    return rows * (2 * entries * heads * dim
                   + 2 * heads * dim + 2 * heads + 2 * entries) * 4


def calls(kind, block, cuts):
    """fn(n, x, dt, a, b, c, d_skip, states): n iterations of every
    layer's call, the states carried."""
    def fn(n, x, dt, a, b, c, d_skip, states):
        made = ssd_step._operands(x, dt, a, b, c)

        def body(_, carry):
            y, states = carry
            after = []
            for state in states:
                if kind == "call":
                    y, state = ssd_step._call(*made, state, block=block,
                                              cuts=cuts, interpret=False)
                else:
                    # the next step's x is this one's y at x's size:
                    # nothing of a step leaves the loop
                    y = y * lax.rsqrt(jnp.mean(y * y) + 1e-6)
                    y, state = ssd_step.step(
                        state, y, dt, a, b, c, d_skip, plain=None,
                        block=block) if kind == "step" \
                        else ssm.ssd_update(state, y, dt, a, b, c, d_skip)
                after.append(state)
            return y, tuple(after)
        return lax.fori_loop(0, n, body,
                             (made[1] if kind == "call" else x, states))
    return jax.jit(fn, donate_argnums=(7,), static_argnums=(0,))


def off_plain(block, cuts, ins):
    """The largest difference of one call's y and state from
    `ssd_update`'s."""
    x, dt, a, b, c, d_skip, states = ins
    want = jax.jit(ssm.ssd_update)(states[0], x, dt, a, b, c, d_skip)

    def one(state, x, dt, a, b, c, d_skip):
        y, state = ssd_step._call(*ssd_step._operands(x, dt, a, b, c),
                                  state, block=block, cuts=cuts,
                                  interpret=False)
        return y + jnp.repeat(d_skip, x.shape[-1] // dt.shape[-1]) * x, state
    got = jax.jit(one)(states[0], x, dt, a, b, c, d_skip)
    return max(float(jnp.max(jnp.abs(g - w))) for g, w in zip(got, want))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=64)
    ap.add_argument("--heads", type=int, default=128)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--entries", type=int, default=128)
    ap.add_argument("--block", type=int, action="append",
                    help="rows a grid step; 1, 2 and 4 when not given")
    ap.add_argument("--read-cuts", type=int, action="append",
                    help="copies a block comes in as; 8 and 16 when not "
                         "given")
    ap.add_argument("--write-cuts", action="append",
                    type=lambda t: tuple(int(n) for n in t.split(",")),
                    help="slices of the sublanes, slices of the lanes a "
                         "block goes out as, as `16,4`; %s when not given"
                         % " ".join("%d,%d" % w for w in WRITES))
    ap.add_argument("--kind", choices=("call", "step", "plain"),
                    action="append",
                    help="the kernel alone, the op's step, or `ssd_update`; "
                         "the kernel and `ssd_update` when not given")
    ap.add_argument("--layers", type=int, action="append",
                    help="calls an iteration, a state each; 1 when not "
                         "given")
    ap.add_argument("--streams", action="store_true",
                    help="gdn_step_bench's yardsticks in the kernel's place")
    ap.add_argument("--check", action="store_true")
    args = ap.parse_args()

    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit("ssd_step_bench: times a Mosaic kernel; no TPU here (%s)"
                 % device.platform)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    out = open(OUT, "a")

    def say(line, fn, ins, moved, layers=1):
        line["device"] = device.device_kind
        try:
            ms = yardsticks.slope(fn, ins) / layers
            line.update(ms_per_call=ms, hbm_share=moved / yardsticks.HBM
                        / (ms * 1e-3) * 100)
        except Exception as e:  # a block the compiler refuses
            line["refused"] = "%s: %s" % (type(e).__name__, str(e)[:300])
        print(json.dumps(line), flush=True)
        out.write(json.dumps(line) + "\n")

    size = (args.rows, args.heads, args.head_dim, args.entries)
    if args.streams:
        heads = args.entries * args.heads * args.head_dim \
            // yardsticks.DIM ** 2
        state = jnp.zeros((args.rows, heads, yardsticks.DIM, yardsticks.DIM),
                          jnp.float32) + 0.5
        for way, held in itertools.product(("read", "write", "both", "xla"),
                                           args.block or (2,)):
            say({"stream": way, "rows": args.rows, "heads": heads,
                 "rows_step": held}, yardsticks.stream(way, held), (state,),
                state.nbytes * (2 if way in ("both", "xla") else 1))
        return
    worst = 0.0
    chosen = ssd_step.choose_block(args.rows, args.entries,
                                   args.heads * args.head_dim, jnp.float32)
    ours = (ssd_step._READ_CUTS, ssd_step._WRITE_CUTS)
    for layers in args.layers or (1,):
        ins = operands(*size, layers)
        for kind in args.kind or ("plain", "call"):
            blocks = itertools.product(
                args.block or (1, 2, 4), args.read_cuts or (8, 16),
                args.write_cuts or WRITES) \
                if kind != "plain" else [(0, 0, (0, 0))]
            for block, *cuts in blocks:
                cuts = tuple(cuts)
                if kind == "step" and cuts != ours:
                    continue
                line = {"kind": kind, "layers": layers, "rows": args.rows,
                        "heads": args.heads, "head_dim": args.head_dim,
                        "entries": args.entries, "block": block,
                        "cuts": cuts,
                        "chosen": (block, cuts) == (chosen, ours),
                        "step_mib": block * args.entries * args.heads
                        * args.head_dim * 4 / 2 ** 20}
                if args.check and kind == "call" and layers == 1:
                    line["off_plain"] = off_plain(block, cuts, ins)
                    worst = max(worst, line["off_plain"])
                say(line, calls(kind, block, cuts), ins,
                    step_bytes(*size), layers)
    if worst > 2e-5:
        sys.exit("ssd_step_bench: a block is %.3g off ssd_update" % worst)


if __name__ == "__main__":
    main()
