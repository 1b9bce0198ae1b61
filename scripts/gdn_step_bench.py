"""Times the gated delta rule's step kernel (kernels/gdn_step.py) alone
on the chip at a cell's shape (qwen3next-decode-ep16's: 128 rows, 16 key
/ 32 value heads of 128 x 128, a float32 state; `--key-heads 32` is
ling3-decode-ep16's under `--gate channel`) by the block of state a grid
step takes: what `_STEP_BYTES` in `paddle_tpu/kernels/gdn_step.py` was
decided from (PERF.md section 6, PR 64).

    chiprun -- python scripts/gdn_step_bench.py            # the sweep
    chiprun -- python scripts/gdn_step_bench.py --gate channel \
        --rows-step 2 --heads 32                           # one block
    chiprun -- python scripts/gdn_step_bench.py --streams  # the yardsticks
    chiprun -- python scripts/gdn_step_bench.py --gate head --all-heads 30 \
        --key-heads 30 --key-dim 96 --value-dim 192 --heads 30 --check \
        --pad-to 0 --pad-to 256     # Olmo-Hybrid's state, both layouts

A call runs as a decoder's scan runs it: the state carried from call to
call through the kernel's alias, the operands the same every call.  ms a
call is the slope between a short and a long loop of calls in one
program (the dispatch and the state's one copy into the loop drop out);
the share of the HBM peak is the rule's bytes (the state in and out, the
five operands and the output, what `benchmark/flops/gated_delta.py
rule_step` counts) at 819 GB/s over that time.  `call` is the kernel
alone over operands that are there; `step` is the op's whole step, the
operands made from v, g and beta beside it.  `--check` holds every
block's output and state to the plain recurrence on the chip (one call
from the same state; exits 1 past 2e-5).

A state that is not 128 x 128 a head (`--key-dim`, `--value-dim`) lies
in HBM one of two ways, and `--pad-to` says which: 0, `state_pack` heads
side by side ([rows, heads / 2, 96, 384] for 192 values a head: the
state's own bytes), or the values a head zero-padded to that many
(256: what an array of 192 lanes costs in HBM anyway).  The share of the
HBM peak counts the state's own bytes in both, so the larger share is
the faster call.

`--streams` are the yardsticks the kernel's form was chosen by: the same
268 MB of state moved with no arithmetic by a Pallas kernel whose blocks
the compiler's own pipeline fetches and writes back (`pl.BlockSpec`, two
buffers, `--rows-step` rows of all heads a grid step), `read` alone (a
block summed to a row), `write` alone (a row broadcast to a block) and
`both` (a block copied: in and out at once, as the step kernel did
before PR 64), and XLA's own `state * c`.  One JSON line a variant, all
of them in `chiprun_out/gdn_step_bench.jsonl`.
"""

import argparse
import itertools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
import numpy as np

from paddle_tpu.kernels import gdn_step
from paddle_tpu.ops import linear_attention

SHORT, LONG = 16, 144
DIM = 128
HBM = 819e9
OUT = "chiprun_out/gdn_step_bench.jsonl"


def operands(rows, key_heads, heads, channel, seed=0, key_dim=DIM,
             value_dim=DIM, pad_to=0, beta_most=0.95):
    """q, k, v, g, beta and the state as the recurrence has it, the
    values a head zero-padded to `pad_to` where given."""
    rs = np.random.RandomState(seed)
    q, k = (linear_attention.l2norm(jnp.asarray(
        rs.randn(rows, key_heads, key_dim), jnp.float32)) for _ in range(2))
    gate = (rows, heads, key_dim) if channel else (rows, heads)
    pad = ((0, 0),) * 2 + ((0, max(pad_to - value_dim, 0)),)
    return (q * key_dim ** -0.5, k,
            jnp.pad(jnp.asarray(rs.randn(rows, heads, value_dim),
                                jnp.bfloat16), pad),
            -jnp.asarray(rs.uniform(1e-3, 0.6, gate), jnp.float32),
            jnp.asarray(rs.uniform(0.05, beta_most, (rows, heads)),
                        jnp.float32),
            jnp.pad(jnp.asarray(
                0.3 * rs.randn(rows, heads, key_dim, value_dim),
                jnp.float32), ((0, 0),) + pad))


def rule_bytes(rows, key_heads, heads, key_dim=DIM, value_dim=DIM):
    """The state in and out, q and k, beta * v, the decay and beta a row
    each, the output."""
    return rows * (2 * heads * key_dim * value_dim + 2 * key_heads * key_dim
                   + 4 * heads * value_dim) * 4


def calls(kind, block, pack=1):
    """fn(n, q, k, v, g, beta, state): n calls, the state carried (as
    it lies: `pack` heads side by side)."""
    def fn(n, q, k, v, g, beta, state):
        made = gdn_step._operands(q, k, v, g, beta, pack)
        if kind == "call":
            def body(_, carry):
                return gdn_step._call(*made, carry[1], block=block,
                                      channel=g.ndim == 3, interpret=False,
                                      pack=pack)
        else:
            def body(_, carry):
                # the next call's values are this one's output: nothing
                # of a step leaves the loop
                return gdn_step.step(
                    q, k, carry[0].reshape(v.shape).astype(v.dtype), g,
                    beta, carry[1], plain=None, block=block, pack=pack)
        return lax.fori_loop(
            0, n, body,
            (made[2] if kind == "call" else v.astype(jnp.float32), state))
    return jax.jit(fn, donate_argnums=(6,), static_argnums=(0,))


def slope(fn, ins, repeats=3):
    """ms a call: the slope between SHORT and LONG.  `ins`' last is the
    state the loop carries (an array, or several), fn's last result."""
    best = {}
    for n in (SHORT, LONG):
        state = jax.tree.map(jnp.copy, ins[-1])
        state = jax.block_until_ready(fn(n, *ins[:-1], state))[-1]
        best[n] = float("inf")
        for _ in range(repeats):
            start = time.perf_counter()
            state = jax.block_until_ready(fn(n, *ins[:-1], state))[-1]
            best[n] = min(best[n], time.perf_counter() - start)
    return (best[LONG] - best[SHORT]) / (LONG - SHORT) * 1e3


def off_plain(block, ins, pack=1):
    """The largest difference of one call's output and state from the
    plain recurrence's; `ins`' state as the recurrence has it."""
    q, k, v, g, beta, state = ins
    want, want_state = jax.jit(linear_attention.recurrent)(
        q[:, None], k[:, None], v[:, None].astype(jnp.float32), g[:, None],
        beta[:, None], state)
    got, got_state = jax.jit(
        lambda *a: gdn_step.step(*a[:-1], gdn_step.pack_state(a[-1], pack),
                                 plain=None, block=block, pack=pack))(*ins)
    return max(float(jnp.max(jnp.abs(got - want[:, 0]))),
               float(jnp.max(jnp.abs(gdn_step.unpack_state(got_state, pack)
                                     - want_state))))


def stream(way, held):
    """fn(n, state): n passes over the state with no arithmetic to
    speak of, `held` rows of all heads a grid step through the
    compiler's own pipeline; `xla`: the compiler's own `state * c`."""
    def kernel(row_ref, src_ref, dst_ref):
        if way == "read":
            # the row before: a pass needs the one before it, or the
            # compiler makes one pass of them all
            dst_ref[...] = row_ref[...] + jnp.sum(src_ref[...], axis=2)
        elif way == "write":
            dst_ref[...] = jnp.broadcast_to(src_ref[...][:, :, None],
                                            dst_ref.shape)
        else:
            dst_ref[...] = src_ref[...]

    def moved(state, row):
        rows, heads = state.shape[:2]
        whole = state, pl.BlockSpec((held, heads, DIM, DIM),
                                    lambda b: (b, 0, 0, 0))
        thin = row, pl.BlockSpec((held, heads, DIM), lambda b: (b, 0, 0))
        src, dst = {"read": (whole, thin), "write": (thin, whole),
                    "both": (whole, whole)}[way]
        return pl.pallas_call(
            kernel, grid=(rows // held,), in_specs=[thin[1], src[1]],
            out_specs=dst[1],
            out_shape=jax.ShapeDtypeStruct(dst[0].shape, jnp.float32),
            input_output_aliases={1: 0} if way == "both" else {},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=4 * held * heads * DIM * DIM * 4 + (4 << 20)),
            name="stream_%s_b%d" % (way, held))(row, src[0])

    def fn(n, state):
        def body(_, carry):
            state, row = carry
            if way == "xla":
                return state * 0.999, row
            out = moved(state, row)
            return (state, out * 1e-9) if way == "read" else (out, row)
        return lax.fori_loop(0, n, body,
                             (state, state[:, :, 0] * 1e-9))[::-1]
    return jax.jit(fn, donate_argnums=(1,), static_argnums=(0,))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--gate", choices=("head", "channel"), action="append",
                    help="both when not given")
    ap.add_argument("--rows", type=int, default=128)
    ap.add_argument("--all-heads", type=int, default=32)
    ap.add_argument("--key-heads", type=int,
                    help="16 under a gate a head, 32 under one a channel")
    ap.add_argument("--rows-step", type=int, action="append",
                    help="rows a grid step; 1, 2, 4 and 8 when not given")
    ap.add_argument("--heads", type=int, action="append",
                    help="value heads a grid step; 16 and 32 when not given")
    ap.add_argument("--kind", choices=("call", "step"), action="append",
                    help="the kernel alone, or the op's step; the kernel "
                         "when not given")
    ap.add_argument("--key-dim", type=int, default=DIM)
    ap.add_argument("--value-dim", type=int, default=DIM)
    ap.add_argument("--pad-to", type=int, action="append",
                    help="the values a head the state is zero-padded to "
                         "in HBM; 0 (the default): `state_pack` heads "
                         "side by side, no padding")
    ap.add_argument("--beta-most", type=float, default=0.95,
                    help="beta is drawn in (0.05, this): 1.95 with "
                         "negative eigenvalues allowed")
    ap.add_argument("--streams", action="store_true",
                    help="the yardsticks in the kernel's place")
    ap.add_argument("--check", action="store_true")
    args = ap.parse_args()

    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit("gdn_step_bench: times a Mosaic kernel; no TPU here (%s)"
                 % device.platform)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    out = open(OUT, "a")

    def say(line, fn, ins, moved):
        line["device"] = device.device_kind
        try:
            ms = slope(fn, ins)
            line.update(ms_per_call=ms,
                        hbm_share=moved / HBM / (ms * 1e-3) * 100)
        except Exception as e:  # a block the compiler refuses
            line["refused"] = "%s: %s" % (type(e).__name__, str(e)[:300])
        print(json.dumps(line), flush=True)
        out.write(json.dumps(line) + "\n")

    if args.streams:
        state = operands(args.rows, 1, args.all_heads, False)[-1]
        for way, held in itertools.product(
                ("read", "write", "both", "xla"), args.rows_step or (2, 8)):
            if way == "xla" and held != (args.rows_step or (2,))[0]:
                continue
            say({"stream": way, "rows": args.rows, "heads": args.all_heads,
                 "rows_step": held}, stream(way, held), (state,),
                state.nbytes * (2 if way in ("both", "xla") else 1))
        return
    worst = 0.0
    for gate, pad_to in itertools.product(args.gate or ("head", "channel"),
                                          args.pad_to or (0,)):
        channel = gate == "channel"
        key_heads = args.key_heads or (32 if channel else 16)
        ins = operands(args.rows, key_heads, args.all_heads, channel,
                       key_dim=args.key_dim, value_dim=args.value_dim,
                       pad_to=pad_to, beta_most=args.beta_most)
        lies = max(pad_to, args.value_dim)
        pack = gdn_step.state_pack(args.all_heads, lies)
        chosen = gdn_step.choose_block(
            args.rows, args.all_heads, key_heads, args.key_dim, lies,
            jnp.float32, pack)
        for kind, held, heads in itertools.product(
                args.kind or ("call",), args.rows_step or (1, 2, 4, 8),
                args.heads or (16, 32)):
            block = (held, heads)
            line = {"gate": gate, "kind": kind, "rows": args.rows,
                    "key_heads": key_heads, "heads": args.all_heads,
                    "head": [args.key_dim, args.value_dim], "lies": lies,
                    "pack": pack, "block": block, "chosen": block == chosen,
                    "step_mib": held * heads * args.key_dim * lies * 4
                    / 2 ** 20}
            if args.check:
                line["off_plain"] = off_plain(block, ins, pack)
                worst = max(worst, line["off_plain"])
            say(line, calls(kind, block, pack),
                ins[:-1] + (gdn_step.pack_state(ins[-1], pack),),
                rule_bytes(args.rows, key_heads, args.all_heads,
                           args.key_dim, args.value_dim))
    if worst > 2e-5:
        sys.exit("gdn_step_bench: a block is %.3g off the plain recurrence"
                 % worst)


if __name__ == "__main__":
    main()
