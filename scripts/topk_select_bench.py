"""Times the chooser's selection on the chip at the two cells' shapes
(`keye-turn-64k-ep8`: 2048 of `[8, 65536]` float32 scores;
`dsv32-turn-16k-ep16`: 2048 of `[16, 16384]`): `kernels/topk_select.py`
beside `jax.lax.top_k`, and holds the kernel's set to `lax.top_k`'s on
the chip (continuous scores, scores with ties at the threshold, a
relu's zeros, `-0.0`, fewer live slots than asked for): what PERF.md
section 6, PR 59, quotes for the kernel alone.  `chiprun -- python
scripts/topk_select_bench.py`; one JSON line a case, all of them in
`chiprun_out/topk_select_bench.jsonl`.  `--shape rows,slots,top_k`
rehearses one shape (on the CPU the times are the interpreter's and say
nothing)."""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax
import numpy as np

from paddle_tpu.kernels import topk_select

SHORT, LONG = 8, 72
SHAPES = ((8, 65536, 2048), (16, 16384, 2048))


def sorted_top_k(score, top_k):
    return jnp.sort(lax.top_k(score, top_k)[1], axis=-1)


def applications(select, top_k):
    """fn(n, score): n selections, each from scores that read one entry
    of the selection before (nothing hoists out of the loop)."""
    def fn(n, score):
        def body(_, carry):
            score, _ = carry
            chosen = select(score, top_k)
            first = score[:, :1] + (chosen[:, :1] < 0).astype(score.dtype)
            return lax.dynamic_update_slice(score, first, (0, 0)), chosen
        return lax.fori_loop(
            0, n, body,
            (score, jnp.zeros((score.shape[0], top_k), jnp.int32)))[1]
    return jax.jit(fn, static_argnums=0)


def slope(fn, *args, repeats=3):
    """ms an application: the slope between SHORT and LONG."""
    best = {}
    for n in (SHORT, LONG):
        jax.block_until_ready(fn(n, *args))
        best[n] = float("inf")
        for _ in range(repeats):
            start = time.perf_counter()
            jax.block_until_ready(fn(n, *args))
            best[n] = min(best[n], time.perf_counter() - start)
    return (best[LONG] - best[SHORT]) / (LONG - SHORT) * 1e3


def draws(rs, rows, slots, top_k):
    """(name, scores) of the cases the set is held on."""
    x = rs.randn(rows, slots).astype(np.float32)
    yield "continuous", x
    yield "ties", np.round(x * 4) / 4
    relu = np.maximum(x, 0) * np.maximum(rs.randn(rows, slots), 0)
    yield "zeros", relu.astype(np.float32)
    signed = relu.astype(np.float32)
    signed[:, ::3] *= -1.0     # -0.0 among the zeros, and negative scores
    yield "negative_zeros", signed
    few = x.copy()
    few[:, top_k // 2:] = -np.inf
    yield "few_live", few


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--shape", default=None)
    args = parser.parse_args()
    shapes = SHAPES if args.shape is None else (
        tuple(int(v) for v in args.shape.split(",")),)
    os.makedirs("chiprun_out", exist_ok=True)
    out = open("chiprun_out/topk_select_bench.jsonl", "w")

    def emit(row):
        row["platform"] = jax.devices()[0].platform
        line = json.dumps(row)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    wrong = 0
    for rows, slots, top_k in shapes:
        rs = np.random.RandomState(slots)
        for name, x in draws(rs, rows, slots, top_k):
            x = jnp.asarray(x)
            got = np.asarray(topk_select.select_slots(x, top_k))
            want = np.asarray(sorted_top_k(x, top_k))
            same = bool((got == want).all())
            wrong += not same
            emit({"case": name, "shape": [rows, slots, top_k],
                  "same_set_as_lax_top_k": same,
                  "ascending": bool((np.diff(got, axis=-1) > 0).all())})
        x = jnp.asarray(rs.randn(rows, slots), jnp.float32)
        for kind, select in (
                ("kernel", topk_select.select_slots),
                ("lax.top_k", lambda s, k: lax.top_k(s, k)[1])):
            emit({"kind": kind, "shape": [rows, slots, top_k],
                  "ms": slope(applications(select, top_k), x)})
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
