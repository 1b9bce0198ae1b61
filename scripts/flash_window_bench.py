"""Times the flash-attention kernels under a window on the chip, at
smallthinker-train-16k-ep8's shape ([1, 16384, 28 x 128] bfloat16, window
4096, and no window beside it) over block sizes: what the choosers pick
under a window (`_window_blocks`) against their neighbours, and what the
bound saves of the causal kernels' time (PERF.md section 6, PR 48); and
the backward's three forms beside each other (PR 51): the one kernel
that walks the keys with a ring of dq^T at every pair of blocks the
chooser admits under the window (and at the pair's own, which it does
not: Mosaic's refusal is a row), the pair that walks at the same
blocks, each with its grid steps and folded / attended pairs.
`chiprun -- python scripts/flash_window_bench.py [fwd|bwd ...]`; one
JSON line a variant, all of them in
`chiprun_out/flash_window_bench.jsonl`."""

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
# (block_q, block_k, the backward's form): None the chooser's own
CHOSEN = (None, None, None)
BLOCKS = {"fwd": [CHOSEN] + [(bq, bk, None) for bq, bk in (
              (1024, 1024), (1024, 512), (512, 1024), (512, 512))],
          "bwd": [CHOSEN] + [(bq, bk, form) for form in ("ring", "pair")
                           for bq, bk in ((1024, 512), (512, 512),
                                          (1024, 256), (256, 1024),
                                          (512, 256), (256, 512))]}


def grid_steps(call, form, bq, bk, window):
    """Grid steps a call of the backward's `form` makes at (bq, bk)."""
    nq, nk = call.tq // bq, call.tk // bk
    per_head = {"one": nk, "pair": 2 * nq * nk,
                "ring": nk * fa._ring_slots(bq, bk, call.tq, window)}
    return call.batch * call.heads // call.g * per_head[form]


def main():
    assert jax.devices()[0].platform == "tpu", jax.devices()
    os.makedirs("chiprun_out", exist_ok=True)
    out = open("chiprun_out/flash_window_bench.jsonl", "w")
    rs = np.random.RandomState(0)
    q, k, v, do = (jnp.asarray(rs.randn(*SHAPE) * 0.5, jnp.bfloat16)
                   for _ in range(4))
    scale = (SHAPE[2] // HEADS) ** -0.5
    call = fa._Call.of(SHAPE, SHAPE, HEADS)
    kinds = sys.argv[1:] or ("fwd", "bwd")
    for window in (4096, 0):
        o, m, l = fa._fwd(q, k, v, scale, True, None, None, 0, HEADS,
                          window=window)
        lse = m + jnp.log(l)
        delta = fa.row_sums(do, o, HEADS)
        for kind in kinds:
            for blocks in BLOCKS[kind]:
                bq, bk, form = blocks
                if form == "ring" and not window:
                    continue

                def fwd(q, k, v):
                    return fa._fwd(q, k, v, scale, True, bq, bk, 0, HEADS,
                                   window=window)[0]

                def bwd(do, q, k, v, lse, delta):
                    # every gradient read, or a kernel that makes one
                    # nobody reads is dropped from the program
                    dq, dk, dv = fa._bwd_kernels(
                        q, k, v, do, lse, delta, num_heads=HEADS,
                        sm_scale=scale, causal=True, q_offset=0,
                        bq=chosen[0], bk=chosen[1], form=chosen[2],
                        window=window)
                    return dq + dk + dv

                if kind == "fwd":
                    chosen = fa._choose_blocks(*call.step_shapes, 2, bq, bk,
                                               window)
                    widest = fa._STAIR
                else:
                    chosen = fa._choose_bwd_blocks(*call.step_shapes, 2, bq,
                                                   bk, call.g, window)
                    # a named form at named blocks, whatever the budget
                    chosen = chosen[:2] + (form or chosen[2],)
                    widest = None if chosen[2] == "pair" else fa._STAIR
                folded, attended = fa.score_pairs(
                    SHAPE[1], SHAPE[1], True, 0, chosen[0], chosen[1],
                    widest, window)
                row = {"kind": kind, "window": window,
                       "blocks": "chosen" if blocks is CHOSEN else "named",
                       "bq": chosen[0], "bk": chosen[1],
                       "folded_over_attended": folded / attended,
                       "folded_pairs": HEADS * folded,
                       "attended_pairs": HEADS * attended}
                if kind == "fwd":
                    row["kv_resident"] = bool(chosen[2])
                else:
                    row["form"] = chosen[2]
                    row["grid_steps"] = grid_steps(call, chosen[2],
                                                   chosen[0], chosen[1],
                                                   window)
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
