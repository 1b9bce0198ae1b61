"""Times the 64-wide grouped-query decode kernels on the chip at
gpt2m-decode's shape (48 rows, 16 heads of 64, 1024-slot bfloat16
caches) over the rows and key/value heads a grid step takes and its
block of slots, beside the `cached_attention` op's plain path: what
`_NARROW_BLOCKS` and `_NARROW_STEP_BYTES` in
`paddle_tpu/kernels/gqa_decode.py` were decided from (PERF.md section 5,
PR 47).  `chiprun -- python scripts/gqa_decode_bench.py`; one JSON line
a variant, all of them in `chiprun_out/gqa_decode_bench.jsonl`.  A
`step` runs as a decoder's scan runs it: the caches carried from
application to application, the step's slot written, then attended;
`attend` is the attention kernel alone over caches that stay, `write`
the slot's write alone."""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax
import numpy as np

from paddle_tpu.kernels import gqa_decode

SHORT, LONG = 8, 72
ROWS, HEADS, SLOTS, DIM = 48, 16, 1024, 64
SCALE = DIM ** -0.5
HBM = 819e9
# ((rows, key/value heads) a grid step, slots a block)
SHAPES = (((2, 16), 256), ((1, 16), 512), ((1, 16), 256), ((4, 16), 128),
          ((2, 16), 128), ((1, 16), 128), ((4, 16), 256), ((2, 8), 256))
POSITIONS = (512, 767, 1022)


def plain(q, k, v, last):
    """The op's plain path: float32 products at the highest precision
    over every slot under a mask."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   precision=lax.Precision.HIGHEST) * SCALE
    p = jax.nn.softmax(jnp.where(jnp.arange(SLOTS) <= last, s, -1e30),
                       axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32),
                      precision=lax.Precision.HIGHEST).astype(q.dtype)


def applications(kind, shape):
    """fn(n, q, k, v, new, last): n applications of a step at `last`;
    the queries of the next are the values of the one before."""
    step, bk = shape or (None, None)

    def attend(q, k, v, last):
        if shape is None:
            return plain(q, k, v, last)
        return gqa_decode.gqa_decode(q, k, v, last, SCALE, block_k=bk,
                                     step=step)

    def write(k, v, new, last):
        if shape is None:
            return tuple(lax.dynamic_update_slice_in_dim(c, new, last, axis=2)
                         for c in (k, v))
        return gqa_decode.write_step(k, v, new, new, last)

    def fn(n, q, k, v, new, last):
        def body(_, carry):
            q, k, v = carry
            if kind != "attend":
                k, v = write(k, v, new, last)
            if kind != "write":
                q = attend(q, k, v, last)
            return q, k, v
        q, k, v = lax.fori_loop(0, n, body, (q, k, v))
        # a write alone still has to be read by something
        return q + k[:, :, :1] + v[:, :, :1] if kind == "write" else q
    return jax.jit(fn)


def slope(fn, *args, repeats=3):
    """ms an application: the slope between SHORT and LONG, so that the
    dispatch, its wait and the caches' one copy into the loop drop
    out."""
    best = {}
    for n in (SHORT, LONG):
        jax.block_until_ready(fn(n, *args))
        best[n] = float("inf")
        for _ in range(repeats):
            start = time.perf_counter()
            jax.block_until_ready(fn(n, *args))
            best[n] = min(best[n], time.perf_counter() - start)
    return (best[LONG] - best[SHORT]) / (LONG - SHORT) * 1e3


def main():
    assert jax.devices()[0].platform == "tpu", jax.devices()
    os.makedirs("chiprun_out", exist_ok=True)
    out = open("chiprun_out/gqa_decode_bench.jsonl", "w")

    def emit(row):
        line = json.dumps(row)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    rs = np.random.RandomState(0)

    def draw(*shape):
        return jnp.asarray(rs.randn(*shape), jnp.bfloat16)

    k, v = draw(ROWS, HEADS, SLOTS, DIM), draw(ROWS, HEADS, SLOTS, DIM)
    q, new = draw(ROWS, HEADS, 1, DIM), draw(ROWS, HEADS, 1, DIM)
    cases = [("step", None), ("write", None), ("write", SHAPES[0])] \
        + [(kind, shape) for shape in SHAPES for kind in ("step", "attend")]
    for kind, shape in cases:
        fn = applications(kind, shape)
        for last in POSITIONS:
            at = jnp.int32(last)
            got = fn(1, q, k, v, new, at)
            want = plain(q[:2], *(lax.dynamic_update_slice_in_dim(
                c[:2], new[:2], last, axis=2) for c in (k, v)), at)
            err = float(jnp.max(jnp.abs(got[:2].astype(jnp.float32)
                                        - want.astype(jnp.float32))))
            ms = slope(fn, q, k, v, new, at)
            # the bytes of both caches as they lie: all of them on the
            # plain path, the blocks up to the last live slot in the
            # kernel
            block_k = shape[1] if shape else SLOTS
            fetched = -(-(last + 1) // block_k) * block_k
            moved = 2 * ROWS * HEADS * fetched * DIM * 2
            emit({"kind": kind, "last": last,
                  "step": list(shape[0]) if shape else None,
                  "block_k": shape[1] if shape else 0, "ms": ms,
                  "gb_fetched": moved / 1e9,
                  "hbm_share": moved / HBM / (ms / 1e3)
                  if kind != "write" else None,
                  "max_abs_err": err if kind == "step" else None})


if __name__ == "__main__":
    main()
