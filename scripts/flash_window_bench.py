"""Times the flash-attention kernels under a window on the chip, at
smallthinker-train-16k-ep8's shape ([1, 16384, 28 x 128] bfloat16, window
4096, and no window beside it) over block sizes: what the choosers pick
under a window (`_window_blocks`) against their neighbours, and what the
bound saves of the causal kernels' time (PERF.md section 6, PR 48).
`chiprun -- python scripts/flash_window_bench.py`; one JSON line a
variant, all of them in `chiprun_out/flash_window_bench.jsonl`."""

import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

import jax
import jax.numpy as jnp
import numpy as np

from flash_stair_bench import _time

fa = importlib.import_module("paddle_tpu.kernels.flash_attention")

SHAPE, HEADS = (1, 16384, 28 * 128), 28
BLOCKS = {"fwd": [None, (1024, 1024), (1024, 512), (512, 1024), (512, 512)],
          "bwd": [None, (1024, 512), (512, 1024), (512, 512), (512, 256)]}


def main():
    assert jax.devices()[0].platform == "tpu", jax.devices()
    os.makedirs("chiprun_out", exist_ok=True)
    out = open("chiprun_out/flash_window_bench.jsonl", "w")
    rs = np.random.RandomState(0)
    q, k, v, do = (jnp.asarray(rs.randn(*SHAPE) * 0.5, jnp.bfloat16)
                   for _ in range(4))
    scale = (SHAPE[2] // HEADS) ** -0.5
    call = fa._Call.of(SHAPE, SHAPE, HEADS)
    for window in (4096, 0):
        o, m, l = fa._fwd(q, k, v, scale, True, None, None, 0, HEADS,
                          window=window)
        lse = m + jnp.log(l)
        delta = fa.row_sums(do, o, HEADS)
        for kind in ("fwd", "bwd"):
            for blocks in BLOCKS[kind]:
                bq, bk = blocks or (None, None)

                def fwd(q, k, v):
                    return fa._fwd(q, k, v, scale, True, bq, bk, 0, HEADS,
                                   window=window)[0]

                def bwd(do, q, k, v, lse, delta):
                    # every gradient read, or a kernel that makes one
                    # nobody reads is dropped from the program
                    dq, dk, dv = fa._bwd(q, k, v, do, lse, delta, scale,
                                         True, bq, bk, 0, HEADS,
                                         window=window)
                    return dq + dk + dv

                if kind == "fwd":
                    chosen = fa._choose_blocks(*call.step_shapes, 2, bq, bk,
                                               window)
                    widest = fa._STAIR
                else:
                    chosen = fa._choose_bwd_blocks(*call.step_shapes, 2, bq,
                                                   bk, call.g, window)
                    widest = fa._STAIR if chosen[2] else None
                folded, attended = fa.score_pairs(
                    SHAPE[1], SHAPE[1], True, 0, chosen[0], chosen[1],
                    widest, window)
                row = {"kind": kind, "window": window,
                       "blocks": "chosen" if blocks is None else "named",
                       "bq": chosen[0], "bk": chosen[1],
                       "resident_or_one_kernel": bool(chosen[2]),
                       "folded_over_attended": folded / attended}
                try:
                    if kind == "fwd":
                        row["ms"] = _time(fwd, q, (k, v))
                    else:
                        row["ms"] = _time(bwd, do, (q, k, v, lse, delta))
                    products = 4 if kind == "fwd" else 8
                    row["roofline_pct"] = 100.0 * (
                        products * HEADS * attended * 128 / 197e12) \
                        / (row["ms"] * 1e-3)
                except Exception as e:  # what Mosaic refuses is a row
                    row["error"] = str(e)[-300:]
                line = json.dumps(row)
                print(line, flush=True)
                out.write(line + "\n")
                out.flush()


if __name__ == "__main__":
    main()
