"""Times the flash-attention kernels on the chip at the two cells' shapes
over block sizes and staircase widths: what the rule beside `_BLOCKS` in
`paddle_tpu/kernels/flash_attention.py` was decided from (PERF.md section
6, PR 34).  `chiprun -- python scripts/flash_stair_bench.py`; one JSON
line a variant, all of them in `chiprun_out/flash_stair_bench.jsonl`."""

import importlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax
import numpy as np

fa = importlib.import_module("paddle_tpu.kernels.flash_attention")

SHORT, LONG = 8, 72
SHAPES = {
    "gpt2": ((8, 1024, 1024), 16,
             {"fwd": [(512, 512), (1024, 512), (1024, 1024)],
              "bwd": [(512, 512), (256, 512), (1024, 256)]}),
    # heads held apart, as ring attention and the functional transformer
    # hold them
    "gpt2_apart": ((8, 16, 1024, 64), None,
                   {"fwd": [(512, 512), (1024, 1024)],
                    "bwd": [(512, 512), (1024, 512)]}),
    "ouro": ((1, 4096, 2048), 16,
             {"fwd": [(1024, 512), (1024, 1024), (512, 1024)],
              "bwd": [(512, 256), (256, 512)]}),
}


def _time(step, carry, args, repeats=3):
    """ms a call of `step(carry, *args) -> carry`: the slope between a
    program that makes SHORT calls and one that makes LONG, so that what
    a dispatch and its wait cost drops out."""
    fn = jax.jit(lambda n, carry, *args: lax.fori_loop(
        0, n, lambda _, c: step(c, *args), carry))
    best = {}
    for n in (SHORT, LONG):
        jax.block_until_ready(fn(n, carry, *args))
        best[n] = float("inf")
        for _ in range(repeats):
            start = time.perf_counter()
            jax.block_until_ready(fn(n, carry, *args))
            best[n] = min(best[n], time.perf_counter() - start)
    return (best[LONG] - best[SHORT]) / (LONG - SHORT) * 1e3


def main():
    assert jax.devices()[0].platform == "tpu", jax.devices()
    os.makedirs("chiprun_out", exist_ok=True)
    out = open("chiprun_out/flash_stair_bench.jsonl", "w")
    only = sys.argv[1:]
    for name, (shape, heads, blocks) in SHAPES.items():
        if only and name not in only:
            continue
        rs = np.random.RandomState(0)
        q, k, v, do = (jnp.asarray(rs.randn(*shape) * 0.5, jnp.bfloat16)
                       for _ in range(4))
        scale = (shape[2] // heads if heads else shape[3]) ** -0.5
        fa._STAIR = None
        o, m, l = fa._fwd(q, k, v, scale, True, None, None, 0, heads)
        lse = m + jnp.log(l)
        delta = fa.row_sums(do, o, heads)
        whole = {"fwd": o.astype(jnp.float32),
                 "bwd": fa._bwd(q, k, v, do, lse, delta, scale, True, None,
                                None, 0, heads)[0].astype(jnp.float32)}
        for stair in (None, 128, 256):
            for kind in ("fwd", "bwd"):
                for bq, bk in blocks[kind]:
                    fa._STAIR = stair
                    jax.clear_caches()

                    def fwd(q, k, v):
                        return fa._fwd(q, k, v, scale, True, bq, bk, 0,
                                       heads)[0]

                    def bwd(do, q, k, v, lse, delta):
                        # every gradient read, or a kernel that makes
                        # one nobody reads is dropped from the program
                        dq, dk, dv = fa._bwd(q, k, v, do, lse, delta,
                                             scale, True, bq, bk, 0, heads)
                        return dq + dk + dv

                    row = {"shape": name, "kind": kind, "bq": bq, "bk": bk,
                           "stair": stair}
                    try:
                        if kind == "fwd":
                            row["ms"] = _time(fwd, q, (k, v))
                            got = fwd(q, k, v)
                        else:
                            row["ms"] = _time(bwd, do,
                                              (q, k, v, lse, delta))
                            got = fa._bwd(q, k, v, do, lse, delta, scale,
                                          True, bq, bk, 0, heads)[0]
                        # against the whole-chunk kernel at its own blocks
                        row["max_diff"] = float(jnp.max(jnp.abs(
                            got.astype(jnp.float32) - whole[kind])))
                    except Exception as e:  # what Mosaic refuses is a row
                        row["error"] = str(e)[-300:]
                    line = json.dumps(row)
                    print(line, flush=True)
                    out.write(line + "\n")
                    out.flush()


if __name__ == "__main__":
    main()
