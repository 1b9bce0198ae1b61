"""Times the grouped-query decode kernels on the chip: the 64-wide ones at
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
the slot's write alone.

`chosen` (PR 60; `python scripts/gqa_decode_bench.py chosen`) is the
attention over a chosen set at keye-turn-64k-ep8's shape (8 rows, 4
key/value heads of 128 under 32 query heads, 65,536-slot bfloat16
caches, 2048 chosen in ascending order, all live), an application as a
decoder's scan runs it: the caches carried, the step's slot written,
the set gathered and attended.  `apart` is what the op did before PR 60
(a gather a cache that leaves the heads apart, `[8, 4, 2048, 128]`, and
`gqa_decode_k2048` over it, an entry out of the extent filled in by a
pass over the copies), `whole` what it does now (whole slots, a slot's
heads side by side, an entry clipped, and `gqa_decode_sel2048_c<chunk>`
over the copies as they lie), by chunk; `copies` is what a kernel pays to fetch
chosen slots itself, ns a copy by what one copy covers (one, two or all
four heads of a slot's 32-bit word row; one cache or both; both of the
queue's priorities): a Mosaic kernel that only starts the copies of 512
entries a row and waits for them.

`block` (PR 66; `python scripts/gqa_decode_bench.py block`) is the op
itself, `cached_attention` with `Selected` [8, 64, 2048] and `Live` [8,
64] at the same shape: an application of 64 positions as a decoder's
prefill scan runs it (the caches carried, the block's 64 slots written,
then a tile of positions at a time the sets gathered and attended), by
the positions a tile takes (`ops.attention._CHOSEN_TILE_BYTES` set for 1
to 16),
beside 64 single steps of the same op: ms an application and ns a
gathered slot, what the tile was decided from.

`wide` (PR 68; `python scripts/gqa_decode_bench.py wide [batch kv_heads
slots group window]`) is the 128-wide walk over caches that stay, at
olmohybrid-decode-pp4's full layers' shape unless given one (128 rows,
30 ungrouped heads, 512 slots; `wide 8 8 128 8 128` and `wide 16 10 512 4
512` are exaone-turn-32k-ep16's and phi4flash-turn-16k's rings), by the
(rows, key/value heads) a grid step takes and its block of slots: ms a
call, us a grid step, and the share of the HBM's peak that the live and
the fetched bytes are of it, what `_WIDE_STEP_BYTES`, `_WIDE_HEAD_BYTES`
and the whole-extent block were decided from (PERF.md section 5)."""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
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


def plain(q, k, v, last, scale=SCALE):
    """The op's plain path: float32 products at the highest precision
    over every slot under a mask."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   precision=lax.Precision.HIGHEST) * scale
    p = jax.nn.softmax(jnp.where(jnp.arange(k.shape[2]) <= last, s, -1e30),
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


# -- a chosen set at keye-turn-64k-ep8's shape --------------------------------

C_ROWS, C_KV, C_GROUP, C_SLOTS, C_DIM, C_TOP_K = 8, 4, 8, 65536, 128, 2048
C_SCALE = C_DIM ** -0.5
C_ENTRIES = 512     # the entries a row of the `copies` kernel fetches


def _moved(q):
    """0, which the compiler cannot know: added to a loop's indices it
    ties them to the carry, so that no fetch leaves the loop."""
    return (jnp.sum(q.astype(jnp.float32)) > 1e30).astype(jnp.int32)


def chosen_applications(form, chunk=None):
    """fn(n, q, k, v, new, selected): n steps of the op's chosen-set path
    as a scan carries it."""
    def attend(q, k, v, selected):
        live = jnp.int32(C_TOP_K)
        if form == "apart":
            at = selected[:, None, :, None]
            k_live, v_live = (jnp.take_along_axis(c, at, axis=2)
                              for c in (k, v))
            return gqa_decode.gqa_decode(q, k_live, v_live, live - 1,
                                         C_SCALE)
        at = selected[:, :, None, None]
        k_live, v_live = (jnp.take_along_axis(jnp.swapaxes(c, 1, 2), at,
                                              axis=1, mode="clip")
                          for c in (k, v))
        return gqa_decode.gqa_decode_chosen(q, k_live, v_live, live, C_SCALE,
                                            chunk)

    def fn(n, q, k, v, new, selected):
        def body(i, carry):
            q, k, v = carry
            k, v = (lax.dynamic_update_slice_in_dim(c, new, C_SLOTS - 1024 + i,
                                                    axis=2) for c in (k, v))
            return attend(q, k, v, selected + _moved(q)), k, v
        return lax.fori_loop(0, n, body, (q, k, v))[0]
    return jax.jit(fn)


def _copies_kernel(pair_ref, at_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sems,
                   *, heads, covered, caches, priority):
    """Starts the copies of `C_ENTRIES` chosen word rows of one row of
    the batch, `heads` of the `covered` key/value heads a copy, and
    waits for all."""
    b = pl.program_id(0)
    words = [c.bitcast(jnp.uint32) for c in (k_hbm, v_hbm)][:caches]
    bufs = (k_buf, v_buf)[:caches]

    def start(i):
        pair, at = pair_ref[b, i], at_ref[b, i]
        for j, (src, dst) in enumerate(zip(words, bufs)):
            for h in range(0, covered, heads):
                pltpu.make_async_copy(
                    src.at[b, pl.ds(h, heads), pair],
                    dst.at[pl.ds(h, heads), pl.ds(at, 1), :],
                    sems.at[j]).start(priority=priority if j else 0)

    def eight(u, _):
        for j in range(8):
            start(u * 8 + j)

    lax.fori_loop(0, C_ENTRIES // 8, eight, None)
    for j, dst in enumerate(bufs):
        # one wait a cache: the semaphore counts the bytes of all copies
        held = dst.at[pl.ds(0, covered)]
        pltpu.make_async_copy(held, held, sems.at[j]).wait()
    o_ref[0] = pltpu.bitcast(k_buf[0, :8] ^ v_buf[0, :8], jnp.float32)


@functools.partial(jax.jit, static_argnames=("heads", "covered", "caches",
                                             "priority"))
def copies(q, k, v, pair, at, heads, covered, caches, priority):
    """The caches come as they lie, a slot pair the two bfloat16 rows of
    a 32-bit word row: `[.., S / 2, 2, D]` is no copy, its sublane tile
    is one word row, and a word row is what one copy can name."""
    view = (C_ROWS, C_KV, C_SLOTS // 2, 2, C_DIM)
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    buf = pltpu.VMEM((C_KV, C_ENTRIES, C_DIM), jnp.uint32)
    out = pl.pallas_call(
        functools.partial(_copies_kernel, heads=heads, covered=covered,
                          caches=caches, priority=priority),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(C_ROWS,),
            in_specs=[any_space, any_space],
            out_specs=pl.BlockSpec((1, 8, C_DIM), lambda b, *_: (b, 0, 0)),
            scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((C_ROWS, 8, C_DIM), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="gqa_chosen_copies_h%d_c%d" % (heads, caches),
    )(pair, at, k.reshape(view), v.reshape(view))
    return q + (out[:, None] * 1e-30).astype(q.dtype)


def chosen(emit):
    rs = np.random.RandomState(0)
    key = jax.random.PRNGKey(0)
    k, v = (jax.random.normal(jax.random.fold_in(key, i),
                              (C_ROWS, C_KV, C_SLOTS, C_DIM), jnp.bfloat16)
            for i in range(2))
    q = jax.random.normal(jax.random.fold_in(key, 2),
                          (C_ROWS, C_KV, C_GROUP, C_DIM), jnp.bfloat16)
    new = jax.random.normal(jax.random.fold_in(key, 3),
                            (C_ROWS, C_KV, 1, C_DIM), jnp.bfloat16)
    selected = jnp.asarray(np.stack(
        [np.sort(rs.choice(C_SLOTS - 1024, C_TOP_K, replace=False))
         for _ in range(C_ROWS)]), jnp.int32)
    want = None
    for form, chunk in [("apart", None)] + [("whole", c)
                                            for c in (2048, 1024, 512, 256)]:
        fn = chosen_applications(form, chunk)
        got = fn(1, q, k, v, new, selected).astype(jnp.float32)
        want = got if want is None else want
        emit({"kind": "chosen", "form": form, "chunk": chunk,
              "ms": slope(fn, q, k, v, new, selected),
              "max_abs_off_apart": float(jnp.max(jnp.abs(got - want)))})
    pair = selected[:, :C_ENTRIES] >> 1
    at = jnp.asarray(np.stack([rs.permutation(C_ENTRIES)
                               for _ in range(C_ROWS)]), jnp.int32)
    for heads, covered, caches, priority in (
            (1, 1, 1, 0), (2, 2, 1, 0), (4, 4, 1, 0), (4, 4, 2, 0),
            (4, 4, 2, 1), (1, 4, 2, 0)):
        def fn(n, q, k, v, pair, at):
            return lax.fori_loop(
                0, n, lambda _, q: copies(q, k, v, pair + _moved(q), at,
                                          heads, covered, caches, priority),
                q)
        ms = slope(jax.jit(fn), q, k, v, pair, at)
        started = C_ROWS * C_ENTRIES * caches * (covered // heads)
        emit({"kind": "copies", "heads_a_copy": heads,
              "heads_covered": covered, "caches": caches,
              "priority": priority, "copies": started, "ms": ms,
              "ns_a_copy": ms * 1e6 / started})


B_POSITIONS = 64


def block_applications(positions):
    """fn(n, q, k, v, k_new, v_new, selected, live): n applications of
    `cached_attention` over `positions` positions with a chosen set each
    as a scan carries them."""
    import paddle_tpu.fluid  # noqa: F401  (registers the ops)
    from paddle_tpu.ops import registry

    op = registry.get_op_info("cached_attention").kernel
    attrs = {"num_heads": C_KV * C_GROUP, "num_kv_heads": C_KV}
    first = C_SLOTS - (LONG + 1) * B_POSITIONS

    def fn(n, q, k, v, k_new, v_new, selected, live):
        def body(i, carry):
            q, k, v = carry
            out = op(None, {
                "Q": [q], "KNew": [k_new], "VNew": [v_new], "KCache": [k],
                "VCache": [v],
                "Position": [jnp.full((C_ROWS,), first + i * positions)],
                "Selected": [selected + _moved(q)], "Live": [live]}, attrs)
            return out["Out"][0], out["KCacheOut"][0], out["VCacheOut"][0]
        return lax.fori_loop(0, n, body, (q, k, v))[0]
    return jax.jit(fn)


def block(emit):
    from paddle_tpu.ops import attention

    rs = np.random.RandomState(0)
    key = jax.random.PRNGKey(0)
    heads = C_KV * C_GROUP
    k, v = (jax.random.normal(jax.random.fold_in(key, i),
                              (C_ROWS, C_KV, C_SLOTS, C_DIM), jnp.bfloat16)
            for i in range(2))
    q, k_new, v_new = (
        jax.random.normal(jax.random.fold_in(key, 2 + i),
                          (C_ROWS, B_POSITIONS, n * C_DIM), jnp.bfloat16)
        for i, n in enumerate((heads, C_KV, C_KV)))
    selected = jnp.asarray(np.sort(rs.randint(
        0, C_SLOTS - (LONG + 1) * B_POSITIONS,
        (C_ROWS, B_POSITIONS, C_TOP_K)), -1), jnp.int32)
    live = jnp.full((C_ROWS, B_POSITIONS), C_TOP_K, jnp.int32)
    slots = C_ROWS * B_POSITIONS * C_TOP_K * 2
    a_position = C_ROWS * C_TOP_K * 2 * C_KV * C_DIM * 2
    own = attention._tile_positions(B_POSITIONS, a_position,
                                    attention._CHOSEN_TILE_BYTES)
    step = block_applications(1)
    ms = slope(step, q[:, :1], k, v, k_new[:, :1], v_new[:, :1],
               selected[:, 0], live[:, 0]) * B_POSITIONS
    emit({"kind": "block", "tile": 0, "form": "64 single steps", "ms": ms,
          "ns_a_slot": ms * 1e6 / slots})
    want = None
    for tile in (1, 2, 4, 8, 16):
        attention._CHOSEN_TILE_BYTES = tile * a_position
        fn = block_applications(B_POSITIONS)
        got = fn(1, q, k, v, k_new, v_new, selected, live) \
            .astype(jnp.float32)
        want = got if want is None else want
        ms = slope(fn, q, k, v, k_new, v_new, selected, live)
        emit({"kind": "block", "tile": tile, "the_ops_own": tile == own,
              "ms": ms, "ns_a_slot": ms * 1e6 / slots,
              "max_abs_off_tile_1": float(jnp.max(jnp.abs(got - want)))})


# -- 128-wide heads, one block of a head no step's worth ---------------------

W_DIM = 128
W_SCALE = W_DIM ** -0.5


def wide(emit, batch=128, kv_heads=30, slots=512, group=1, window=0):
    """The 128-wide walk at [batch, kv_heads, slots, 128] bfloat16 under
    `group` queries a key/value head (`window`: a ring of that many
    slots, one block; the defaults are olmohybrid-decode-pp4's full
    layers), by the (rows, heads) a grid step takes and its
    block of slots: ms a call over caches that stay, us a grid step, and
    the share of the HBM's peak that the live bytes and the fetched
    bytes (whole blocks) are of it."""
    rs = np.random.RandomState(0)

    def draw(*shape):
        return jnp.asarray(rs.randn(*shape), jnp.bfloat16)

    k, v = (draw(batch, kv_heads, slots, W_DIM) for _ in range(2))
    q = draw(batch, kv_heads, group, W_DIM)
    own_block = gqa_decode.choose_block(slots, group)
    own = (gqa_decode.choose_step(batch, kv_heads, own_block, 2, group,
                                  W_DIM), own_block)
    divisors = [d for d in range(1, kv_heads + 1) if kv_heads % d == 0]
    blocks = (slots,) if window else [
        b for b in (512, 256, 128) if slots % b == 0]
    variants = [((r, h), bk) for bk in blocks for r in (1, 2, 4, 8)
                if batch % r == 0 for h in divisors
                if (r == 1 or h >= 5)
                and r * h * gqa_decode._vmem_bytes(group, bk, 2)
                <= gqa_decode._VMEM_BYTES]
    # a ring: not wrapped yet, and wrapped (every slot live)
    positions = (slots // 4, slots - 1) if window \
        else (slots // 4, slots * 5 // 8, slots - 2)

    for step, bk in variants:
        def fn(n, q, k, v, last):
            return lax.fori_loop(0, n, lambda _, q: gqa_decode.gqa_decode(
                q, k, v, last, W_SCALE, window, bk, step=step), q)
        fn = jax.jit(fn)
        for last in positions:
            at = jnp.int32(last)
            err = float(jnp.max(jnp.abs(
                fn(1, q, k, v, at)[:2].astype(jnp.float32)
                - plain(q[:2], k[:2], v[:2], at, W_SCALE)
                .astype(jnp.float32))))
            ms = slope(fn, q, k, v, at)
            a_slot = 2 * batch * kv_heads * W_DIM * 2
            fetched = -(-(last + 1) // bk) * bk
            grid = batch // step[0] * (kv_heads // step[1]) * (slots // bk)
            emit({"kind": "wide",
                  "shape": [batch, kv_heads, slots, group, window],
                  "step": list(step), "block_k": bk, "last": last,
                  "the_kernels_own": (step, bk) == own, "ms": ms,
                  "us_a_grid_step": ms * 1e3 / grid,
                  "live_share": a_slot * (last + 1) / HBM / (ms / 1e3),
                  "fetched_share": a_slot * fetched / HBM / (ms / 1e3),
                  "max_abs_err": err})


def main():
    assert jax.devices()[0].platform == "tpu", jax.devices()
    os.makedirs("chiprun_out", exist_ok=True)
    out = open("chiprun_out/gqa_decode_bench.jsonl", "w")

    def emit(row):
        line = json.dumps(row)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    cases = sys.argv[1:] or ["narrow", "chosen"]
    if "wide" in cases:
        shape = [int(n) for n in cases[cases.index("wide") + 1:]]
        wide(emit, *shape)
    if "chosen" in cases:
        chosen(emit)
    if "block" in cases:
        block(emit)
    if "narrow" in cases:
        narrow(emit)


def narrow(emit):
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
