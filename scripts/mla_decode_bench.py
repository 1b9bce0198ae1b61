"""Times the latent-attention decode kernel on the chip at
pangu-decode-ep16's shape (256 rows, 128 heads, a 1024-slot cache of
512 + 64 values) over its blocks of slots, rows a grid step and
positions: what the order of `_BLOCKS` and `_ROWS` in
`paddle_tpu/kernels/mla_decode.py` was decided from (PERF.md section 6,
PR 39).  `chiprun -- python scripts/mla_decode_bench.py`; one JSON line
a variant, all of them in `chiprun_out/mla_decode_bench.jsonl`.  `op`
rows time the whole `mla_cached_attention` op with the cache carried
from call to call, as a decoder's scan carries it.  `block` rows (PR 53)
time the kernel alone over a block of 16 positions a row, a prefill
application of the cell, from
an empty cache and inside a session, by block of slots and heads a
grid step, with the FLOPs its products require (each position over its
own live slots) and what the kernel multiplies (whole blocks of slots)
beside them; `block_op` rows the whole op on such a block.  `--blocks` runs those two kinds alone.
`chosen` (PR 70; `python scripts/mla_decode_bench.py chosen`, half a
minute) runs `chosen` rows alone: the op over a chosen set of 2048 slots,
all live, at `dsv32-turn-16k-ep16`'s shape (16 rows x 128 heads over a
16,384-slot cache) and at `hy4-turn-32k-ep16`'s (8 rows x 64 heads over
32,768 slots, a sink a head), the cache carried from call to call and a
slot written a call as a decoder's scan carries it: the gather and the
kernel that reads its rows (`kernel`: the op as it is) beside the gather
and the plain products (`plain`: `mla_decode.fits` made to refuse, the
op has no switch), ms a layer and ns a gathered row."""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax
import numpy as np

from paddle_tpu.kernels import mla_decode
from paddle_tpu.ops import registry

SHORT, LONG = 8, 72
ROWS, HEADS, SLOTS = 256, 128, 1024
LATENT, ROPE, NOPE, VALUE = 512, 64, 128, 128
POSITIONS = (128, 300, 511, 512, 700, 1023)
SCALE = (NOPE + ROPE) ** -0.5
BLOCK = 16
BLOCK_STARTS = (0, 112, 500, 1008)
BLOCK_VARIANTS = ((256, 64), (128, 64), (512, 32), (256, 32), (128, 32))


def _slope(fn, *args, repeats=3):
    """ms a step of `fn(n, *args)`, a program of n steps: the slope
    between SHORT and LONG, so that a dispatch and its wait drop out."""
    best = {}
    for n in (SHORT, LONG):
        jax.block_until_ready(fn(n, *args))
        best[n] = float("inf")
        for _ in range(repeats):
            start = time.perf_counter()
            jax.block_until_ready(fn(n, *args))
            best[n] = min(best[n], time.perf_counter() - start)
    return (best[LONG] - best[SHORT]) / (LONG - SHORT) * 1e3


def plain(q, cache, pos):
    """The op's plain path over the whole extent (held against, not
    timed: alone it is not laid out as a decoder's scan lays it out)."""
    s = jnp.einsum("bhw,btw->bht", q, cache,
                   preferred_element_type=jnp.float32) * SCALE
    valid = jnp.arange(cache.shape[1]) <= pos
    p = jax.nn.softmax(jnp.where(valid[None, None, :], s, -1e30), axis=-1)
    return jnp.einsum("bht,btw->bhw", p.astype(q.dtype), cache,
                      preferred_element_type=jnp.float32)[..., :LATENT]


def block_rows(emit, draw, cache):
    """The kernel and the op over a block of BLOCK positions a row."""
    tokens = ROWS * BLOCK
    q_lat = draw(HEADS, tokens, LATENT, std=0.5)
    q_rope = draw(HEADS, tokens, ROPE, std=0.5)

    def plain_block(q_lat, q_rope, cache, pos):
        # a position at a time: the whole block's float32 scores are 2 GB
        q = jnp.concatenate([q_lat, q_rope], axis=-1).reshape(
            HEADS, ROWS, BLOCK, LATENT + ROPE)
        return jnp.stack(
            [plain(jnp.swapaxes(q[:, :, t], 0, 1), cache, pos + t)
             for t in range(BLOCK)], axis=2)    # [rows, heads, T, latent]

    chosen = mla_decode.choose_group(HEADS, SLOTS, ROPE, LATENT, 2)
    for blocks in BLOCK_VARIANTS:
        bk, group = blocks
        for pos in BLOCK_STARTS:
            def attend(q_lat, q_rope, cache, at):
                return mla_decode.mla_decode_block(q_lat, q_rope, cache, at,
                                                   SCALE, blocks)

            def steps(n, q_lat, q_rope, cache):
                def body(_, c):
                    at = pos + jnp.isnan(c).astype(jnp.int32)
                    return attend(q_lat, q_rope, cache,
                                  at)[0, 0, 0].astype(jnp.float32)
                return lax.fori_loop(0, n, body, jnp.float32(0))

            # multiply-adds: a query row over its own live slots, and
            # over the whole blocks of slots its tile folds
            tile = mla_decode._TILE
            live = sum(pos + t + 1 for t in range(BLOCK))
            folded = sum((-(-(pos + t0 + tile) // bk)) * bk * tile
                         for t0 in range(0, BLOCK, tile))
            row = {"kind": "block", "block_k": bk, "heads_a_step": group,
                   "positions": BLOCK, "position": pos,
                   "chosen": blocks == chosen,
                   "gflop_required": 2e-9 * ROWS * HEADS * live
                   * (LATENT + ROPE + LATENT),
                   "gflop_folded": 2e-9 * ROWS * HEADS * folded
                   * (LATENT + ROPE + LATENT)}
            try:
                row["ms"] = _slope(jax.jit(steps), q_lat, q_rope, cache)
                row["tflops_required"] = row["gflop_required"] / row["ms"]
                got = jax.jit(attend)(q_lat, q_rope, cache, pos).reshape(
                    HEADS, ROWS, BLOCK, LATENT)
                row["max_diff"] = float(jnp.max(jnp.abs(
                    jnp.transpose(got, (1, 0, 2, 3)).astype(jnp.float32)
                    - jax.jit(plain_block)(q_lat, q_rope, cache, pos))))
            except Exception as e:  # what Mosaic refuses is a row
                row["error"] = str(e)[-300:]
            emit(row)

    kernel = registry.get_op_info("mla_cached_attention").kernel
    ins = {"QNope": [draw(ROWS, BLOCK, HEADS * NOPE)],
           "QRope": [draw(ROWS, BLOCK, HEADS * ROPE)],
           "CNew": [draw(ROWS, BLOCK, LATENT)],
           "RNew": [draw(ROWS, BLOCK, ROPE)],
           "WUk": [draw(LATENT, HEADS * NOPE, std=0.05)],
           "WUv": [draw(LATENT, HEADS * VALUE, std=0.05)]}
    for start in (0, 112):
        def steps(n, ins, cache):
            def body(i, carry):
                cache, seen = carry
                at = start + jnp.isnan(seen).astype(jnp.int32)
                outs = kernel(None, dict(
                    ins, Cache=[cache],
                    Position=[jnp.full((ROWS,), at, jnp.int32)]),
                    {"num_heads": HEADS})
                return outs["CacheOut"][0], \
                    seen + outs["Out"][0][0, 0, 0].astype(jnp.float32)
            return lax.fori_loop(0, n, body, (cache, jnp.float32(0)))

        # the absorb and the values' up-projection beside the kernel's
        emit({"kind": "block_op", "positions": BLOCK,
              "first_position": start,
              "gflop_projections": 2e-9 * ROWS * BLOCK * HEADS * LATENT
              * (NOPE + VALUE),
              "ms": _slope(jax.jit(steps), ins, cache)})


# (cell, rows, heads, slots, nope, value, a sink): the two chooser cells
# whose steps attend 2048 chosen latents
CHOSEN = (("dsv32-turn-16k-ep16", 16, 128, 16384, 128, 128, False),
          ("hy4-turn-32k-ep16", 8, 64, 32768, 192, 256, True))
TOP_K, CHOSEN_START = 2048, 15488


def chosen_rows(emit, draw, rs):
    """The op's chosen-set step, the gather and what reads it, as a
    decoder's scan carries them."""
    kernel = registry.get_op_info("mla_cached_attention").kernel
    takes = mla_decode.fits
    for cell, rows, heads, slots, nope, value, sink in CHOSEN:
        cache = draw(rows, slots, LATENT + ROPE)
        ins = {"QNope": [draw(rows, 1, heads * nope, std=0.5)],
               "QRope": [draw(rows, 1, heads * ROPE, std=0.5)],
               "CNew": [draw(rows, 1, LATENT)], "RNew": [draw(rows, 1, ROPE)],
               "WUk": [draw(LATENT, heads * nope, std=0.05)],
               "WUv": [draw(LATENT, heads * value, std=0.05)],
               "Selected": [jnp.asarray(np.stack(
                   [np.sort(rs.choice(CHOSEN_START, TOP_K, replace=False))
                    for _ in range(rows)]), jnp.int32)],
               "Live": [jnp.full((rows,), TOP_K, jnp.int32)]}
        if sink:
            ins["Sink"] = [jnp.asarray(rs.randn(heads), jnp.float32)]

        got = {}
        for variant in ("kernel", "plain"):
            # traced anew a variant: jit's cache is keyed by the function
            def attend(ins, cache, at):
                return kernel(None, dict(
                    ins, Cache=[cache],
                    Position=[jnp.full((rows,), at, jnp.int32)]),
                    {"num_heads": heads})

            def steps(n, ins, cache):
                def body(i, carry):
                    cache, seen = carry
                    outs = attend(ins, cache, CHOSEN_START + i)
                    return outs["CacheOut"][0], \
                        seen + outs["Out"][0][0, 0, 0].astype(jnp.float32)
                return lax.fori_loop(0, n, body, (cache, jnp.float32(0)))

            mla_decode.fits = takes if variant == "kernel" \
                else lambda *shape: False
            try:
                ms = _slope(jax.jit(steps), ins, cache)
                got[variant] = jax.jit(attend)(ins, cache, CHOSEN_START)[
                    "Out"][0].astype(jnp.float32)
            finally:
                mla_decode.fits = takes
            emit({"kind": "chosen", "cell": cell, "variant": variant,
                  "rows": rows, "heads": heads, "slots": slots,
                  "top_k": TOP_K, "sink": sink, "ms_a_layer": ms,
                  "ns_a_gathered_row": ms * 1e6 / (rows * TOP_K)})
        emit({"kind": "chosen", "cell": cell, "max_diff": float(jnp.max(
            jnp.abs(got["kernel"] - got["plain"]))),
            "max_abs": float(jnp.max(jnp.abs(got["plain"])))})


def main():
    assert jax.devices()[0].platform == "tpu", jax.devices()
    os.makedirs("chiprun_out", exist_ok=True)
    only_chosen = "chosen" in sys.argv[1:]
    out = open("chiprun_out/mla_decode_bench%s.jsonl"
               % ("_chosen" if only_chosen else ""), "w")

    def emit(row):
        line = json.dumps(row)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    rs = np.random.RandomState(0)

    def draw(*shape, std=1.0):
        return jnp.asarray(rs.randn(*shape) * std, jnp.bfloat16)

    if only_chosen:
        return chosen_rows(emit, draw, rs)
    q = draw(ROWS, HEADS, LATENT + ROPE, std=0.5)
    cache = draw(ROWS, SLOTS, LATENT + ROPE)
    block_rows(emit, draw, cache)
    if "--blocks" in sys.argv[1:]:
        return
    for blocks in [(bk, rows) for bk in mla_decode._BLOCKS
                   for rows in mla_decode._ROWS]:
        for pos in POSITIONS:
            def attend(q, cache, at):
                return mla_decode.mla_decode(q, cache, at, SCALE, LATENT,
                                             blocks)

            def steps(n, q, cache):
                # the position hangs on the carry, so that no call can
                # be moved out of the loop
                def body(_, c):
                    at = pos + jnp.isnan(c).astype(jnp.int32)
                    return attend(q, cache, at)[0, 0, 0].astype(jnp.float32)
                return lax.fori_loop(0, n, body, jnp.float32(0))

            row = {"kind": "kernel", "block_k": blocks[0],
                   "rows": blocks[1], "position": pos}
            try:
                row["ms"] = _slope(jax.jit(steps), q, cache)
                row["max_diff"] = float(jnp.max(jnp.abs(
                    jax.jit(attend)(q, cache, pos).astype(jnp.float32)
                    - jax.jit(plain)(q, cache, pos))))
            except Exception as e:  # what Mosaic refuses is a row
                row["error"] = str(e)[-300:]
            emit(row)

    # the op, its cache carried and one slot written a call
    kernel = registry.get_op_info("mla_cached_attention").kernel
    ins = {"QNope": [draw(ROWS, 1, HEADS * NOPE)],
           "QRope": [draw(ROWS, 1, HEADS * ROPE)],
           "CNew": [draw(ROWS, 1, LATENT)], "RNew": [draw(ROWS, 1, ROPE)],
           "WUk": [draw(LATENT, HEADS * NOPE, std=0.05)],
           "WUv": [draw(LATENT, HEADS * VALUE, std=0.05)]}
    for start in (128, 512, 900):
        def steps(n, ins, cache):
            def body(i, carry):
                cache, seen = carry
                outs = kernel(None, dict(
                    ins, Cache=[cache],
                    Position=[jnp.full((ROWS,), start + i, jnp.int32)]),
                    {"num_heads": HEADS})
                return outs["CacheOut"][0], \
                    seen + outs["Out"][0][0, 0, 0].astype(jnp.float32)
            return lax.fori_loop(0, n, body, (cache, jnp.float32(0)))

        emit({"kind": "op", "first_position": start,
              "ms": _slope(jax.jit(steps), ins, cache)})


if __name__ == "__main__":
    main()
