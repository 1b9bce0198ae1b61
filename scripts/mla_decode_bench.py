"""Times the latent-attention decode kernel on the chip at
pangu-decode-ep16's shape (256 rows, 128 heads, a 1024-slot cache of
512 + 64 values) over its blocks of slots, rows a grid step and
positions: what the order of `_BLOCKS` and `_ROWS` in
`paddle_tpu/kernels/mla_decode.py` was decided from (PERF.md section 6,
PR 39).  `chiprun -- python scripts/mla_decode_bench.py`; one JSON line
a variant, all of them in `chiprun_out/mla_decode_bench.jsonl`.  `op`
rows time the whole `mla_cached_attention` op with the cache carried
from call to call, as a decoder's scan carries it."""

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


def main():
    assert jax.devices()[0].platform == "tpu", jax.devices()
    os.makedirs("chiprun_out", exist_ok=True)
    out = open("chiprun_out/mla_decode_bench.jsonl", "w")

    def emit(row):
        line = json.dumps(row)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    rs = np.random.RandomState(0)

    def draw(*shape, std=1.0):
        return jnp.asarray(rs.randn(*shape) * std, jnp.bfloat16)

    q = draw(ROWS, HEADS, LATENT + ROPE, std=0.5)
    cache = draw(ROWS, SLOTS, LATENT + ROPE)
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
