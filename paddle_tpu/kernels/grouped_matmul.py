"""Grouped matrix products for routed experts (pallas TPU kernels).

`M` rows are ordered by group (expert): group `e` owns the `counts[e]`
rows after those of the groups before it, `sum(counts) == M`.  The
sizes are data on the device; every shape is static.

    gmm(x[M, K], w[E, K, N], counts)     -> [M, N]     rows of e @ w[e]
    gmm_dx(dy[M, N], w[E, K, N], counts) -> [M, K]     rows of e @ w[e]^T
    gmm_dw(x[M, K], dy[M, N], counts)    -> [E, K, N]  x_e^T @ dy_e, f32

No row is padded or copied in HBM.  The rows are cut into tiles of
`block_m`; a tile that holds rows of several groups is visited once for
each of them, with the rows of the others masked, so the kernels walk a
list of at most `M / block_m + E - 1` (group, tile) visits that is
computed on the device from `counts` and handed to the index maps by
scalar prefetch.  The list is in row order, so consecutive visits of one
group keep its weight block (gmm, gmm_dx) or its output block (gmm_dw)
in VMEM, and consecutive visits of one tile keep that tile.  Visits
past the end of the list name the last real one again and do nothing.

Which groups a product visits is what it has to write.  gmm and gmm_dx
write rows, and an empty group owns none: they walk the non-empty groups
alone, so no block of a weight nobody chose is fetched (an expert layer
that holds 16 experts for 8 assignments reads those that got one).
Rows of no group (`sum(counts) < M`: the rows past the last group's, and
every row where all counts are zero, when the list is empty and no visit
does anything) are not written: they hold whatever was there.  gmm_dw
writes a block a group and gives an empty group one visit (of the tile
its offset lies in, where it owns no row), which writes its zeros.

Products are in the operands' type (bfloat16 under AMP) with float32
accumulation; gmm_dw adds up in float32 and returns float32, the
master weights' type.

Lowered for the TPU these are Mosaic kernels named
`moe_gmm_{fwd,dx,dw}_m<block_m>_n<block_n>_k<block_k>`; lowered for any
other platform the same products are `jax.lax.ragged_dot_general`, the
plain path of the CPU tests and the chip's yardstick.

    unwritten(shape, dtype, after)       -> an array no pass has filled

is what a caller starts a row operand from that it writes itself, only
as far as the products will read.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs import telemetry

# what one grid step may hold in VMEM (`_step_bytes`), and what Mosaic
# is told it may use: a v5e core has 128 MiB
_VMEM_BUDGET = 40 * 2 ** 20
_VMEM_LIMIT = 64 * 2 ** 20
_BLOCKS = (2048, 1024, 512, 256, 128)


def _largest_block(size, most):
    for b in _BLOCKS:
        if b <= most and size % b == 0:
            return b
    return size


def _step_bytes(rows, depth, cols, itemsize, out_itemsize):
    """VMEM bytes of one grid step of a product [rows, depth] x [depth,
    cols]: both operands and the result, each double-buffered by the
    pipeline, and the float32 product before it is cast or added."""
    return (2 * (rows * depth * itemsize + depth * cols * itemsize
                 + rows * cols * out_itemsize) + rows * cols * 4)


def choose_blocks(m, k, n, itemsize, kernel):
    """(block_m, block_n, block_k) from the shapes.  Rows come in tiles
    of 256: every group boundary inside a tile costs one more visit of
    it.  The contraction is never cut (the whole `k` of gmm, the whole
    `n` of gmm_dx, the row tile of gmm_dw): a step is one product with
    no accumulator to carry, and the other side is as wide as the VMEM
    budget allows, so that a group's weight or output block is read or
    written once."""
    bm = _largest_block(m, 256)
    if kernel == "dw":
        bk, bn = _largest_block(k, 2048), _largest_block(n, 2048)
        while _step_bytes(bk, bm, bn, itemsize, 4) > _VMEM_BUDGET \
                and bn > 128:
            bn //= 2
        return bm, bn, bk
    depth, cols = (k, n) if kernel == "fwd" else (n, k)
    bc = _largest_block(cols, 2048)
    while _step_bytes(bm, depth, bc, itemsize, itemsize) > _VMEM_BUDGET \
            and bc > 128:
        bc //= 2
    return (bm, bc, k) if kernel == "fwd" else (bm, n, bc)


def visits(counts, m, block_m, empty_groups):
    """The (group, tile) visits in row order, from the group sizes, as
    int32 arrays for the scalar prefetch: `group[v]`, `tile[v]` for
    `v < length`, padded to the static `m / block_m + E - 1` by naming
    the last visit again; `offsets[E + 1]`, the groups' first rows; and
    `length[1]`.  With `empty_groups` (gmm_dw) an empty group gets one
    visit, of the tile its offset lies in, where it owns no row, so that
    the kernel writes its zeros; without (gmm, gmm_dx) it gets none.
    Where no group is empty the two lists are the same.  Where every
    group is and none is visited, `length` is 0 and every entry names
    the last group and tile 0: a block a kernel may fetch and must not
    write."""
    e = counts.shape[0]
    tiles_m = m // block_m
    counts = counts.astype(jnp.int32)
    ends = jnp.cumsum(counts)
    starts = ends - counts
    first = jnp.minimum(starts // block_m, tiles_m - 1)
    per_group = jnp.where(counts > 0, (ends - 1) // block_m - first + 1,
                          1 if empty_groups else 0)
    upto = jnp.cumsum(per_group)
    length = upto[-1]
    most = tiles_m + e - 1
    v = jnp.minimum(jnp.arange(most, dtype=jnp.int32),
                    jnp.maximum(length - 1, 0))
    # in an empty list no group reaches past v = 0: the last one is named
    group = jnp.minimum(jnp.searchsorted(upto, v, side="right"),
                        e - 1).astype(jnp.int32)
    tile = first[group] + v - (upto - per_group)[group]
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return group, tile, offsets, length.reshape(1)


def _own_rows(group_ref, tile_ref, offsets_ref, v, bm):
    """[bm, 1] mask of the visited tile's rows that are the group's."""
    g = group_ref[v]
    rows = tile_ref[v] * bm + lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
    return (rows >= offsets_ref[g]) & (rows < offsets_ref[g + 1])


def _rows_kernel(group_ref, tile_ref, offsets_ref, length_ref, x_ref, w_ref,
                 o_ref, *, bm, contract_rhs):
    """One visit of gmm (`contract_rhs` 0: x @ w) or gmm_dx (1: x @
    w^T): the tile's product with the group's weight block, kept where
    the rows are the group's."""
    v = pl.program_id(1)

    @pl.when(v < length_ref[0])
    def _():
        acc = lax.dot_general(
            x_ref[...], w_ref[...], (((1,), (contract_rhs,)), ((), ())),
            preferred_element_type=jnp.float32)
        own = _own_rows(group_ref, tile_ref, offsets_ref, v, bm)
        o_ref[...] = jnp.where(own, acc.astype(o_ref.dtype), o_ref[...])


def _dw_kernel(group_ref, tile_ref, offsets_ref, length_ref, x_ref, dy_ref,
               o_ref, *, bm):
    """One visit of gmm_dw: x_tile^T @ dy_tile over the group's rows,
    added into the group's output block, which stays in VMEM from the
    group's first visit to its last."""
    v = pl.program_id(2)
    g = group_ref[v]

    @pl.when(v < length_ref[0])
    def _():
        @pl.when((v == 0) | (group_ref[jnp.maximum(v - 1, 0)] != g))
        def _():
            o_ref[...] = jnp.zeros_like(o_ref)

        own = _own_rows(group_ref, tile_ref, offsets_ref, v, bm)
        x = jnp.where(own, x_ref[...], jnp.zeros_like(x_ref))
        o_ref[...] += lax.dot_general(
            x, dy_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def _params(semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


def _check(m, bm, what):
    if m % bm:
        raise ValueError("%s: %d rows are not a multiple of the row tile "
                         "%d" % (what, m, bm))


def _rows_call(kernel, blocks, x, w, counts):
    """gmm (`kernel` "fwd") and gmm_dx ("dx") through Mosaic in tiles
    of `blocks` (block_m, block_n, block_k)."""
    m, depth = x.shape
    _, k, n = w.shape
    fwd = kernel == "fwd"
    cols = n if fwd else k
    bm, bn, bk = blocks
    bc = bn if fwd else bk
    _check(m, bm, "moe_gmm_" + kernel)
    group, tile, offsets, length = visits(counts, m, bm, empty_groups=False)
    if fwd:
        w_spec = pl.BlockSpec((None, k, bc),
                              lambda j, v, g, t, o, l: (g[v], 0, j))
    else:
        w_spec = pl.BlockSpec((None, bc, n),
                              lambda j, v, g, t, o, l: (g[v], j, 0))
    return pl.pallas_call(
        functools.partial(_rows_kernel, bm=bm, contract_rhs=0 if fwd else 1),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(cols // bc, group.shape[0]),
            in_specs=[
                pl.BlockSpec((bm, depth),
                             lambda j, v, g, t, o, l: (t[v], 0)),
                w_spec,
            ],
            out_specs=pl.BlockSpec((bm, bc),
                                   lambda j, v, g, t, o, l: (t[v], j)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, cols), x.dtype),
        compiler_params=_params(("parallel", "arbitrary")),
        # the trace shows which tiling ran; readers match the prefix
        name="moe_gmm_%s_m%d_n%d_k%d" % (kernel, bm, bn, bk),
    )(group, tile, offsets, length, x, w)


def _dw_call(blocks, x, dy, counts):
    m, k = x.shape
    n = dy.shape[1]
    e = counts.shape[0]
    bm, bn, bk = blocks
    _check(m, bm, "moe_gmm_dw")
    group, tile, offsets, length = visits(counts, m, bm, empty_groups=True)
    return pl.pallas_call(
        functools.partial(_dw_kernel, bm=bm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(k // bk, n // bn, group.shape[0]),
            in_specs=[
                pl.BlockSpec((bm, bk),
                             lambda i, j, v, g, t, o, l: (t[v], i)),
                pl.BlockSpec((bm, bn),
                             lambda i, j, v, g, t, o, l: (t[v], j)),
            ],
            out_specs=pl.BlockSpec(
                (None, bk, bn), lambda i, j, v, g, t, o, l: (g[v], i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((e, k, n), jnp.float32),
        compiler_params=_params(("parallel", "parallel", "arbitrary")),
        name="moe_gmm_dw_m%d_n%d_k%d" % (bm, bn, bk),
    )(group, tile, offsets, length, x, dy)


def _ragged(lhs, rhs, counts, dims, out_dtype):
    return lax.ragged_dot_general(
        lhs, rhs, counts.astype(jnp.int32), dims,
        preferred_element_type=jnp.float32).astype(out_dtype)


_FWD_DIMS = lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((1,), (1,)), ((), ())),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[0])
_DX_DIMS = lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((1,), (2,)), ((), ())),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[0])
_DW_DIMS = lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


def ragged_gmm(x, w, counts):
    """`gmm` as XLA's own ragged product."""
    return _ragged(x, w, counts, _FWD_DIMS, x.dtype)


def ragged_gmm_dx(dy, w, counts):
    return _ragged(dy, w, counts, _DX_DIMS, dy.dtype)


def ragged_gmm_dw(x, dy, counts):
    return _ragged(x, dy, counts, _DW_DIMS, jnp.float32)


def _grouped(kernel, plain, a, b, counts):
    """One grouped product of a program: the Mosaic kernel, in tiles
    chosen from the shapes, where the computation is lowered for the
    TPU, XLA's ragged product anywhere else (chosen by the platform of
    the lowering, as the flash kernels are)."""
    m = a.shape[0]
    k, n = (a.shape[1], b.shape[1]) if kernel == "dw" else b.shape[1:]
    blocks = choose_blocks(m, k, n, a.dtype.itemsize, kernel)
    telemetry.on_moe_gmm_lowering(
        kernel, *blocks,
        empty_groups="visited" if kernel == "dw" else "skipped")
    call = (functools.partial(_dw_call, blocks) if kernel == "dw"
            else functools.partial(_rows_call, kernel, blocks))
    return lax.platform_dependent(a, b, counts, tpu=call, default=plain)


def unwritten(shape, dtype, after):
    """An array that nothing has written, as `gmm` and `gmm_dx` leave
    the rows of no group: for a loop that writes the rows it goes on to
    read, and for operands of which a product reads no other row.
    Lowered for the TPU it holds whatever was there, at the cost of no
    pass over it (a Mosaic kernel, `moe_unwritten`, whose body is empty;
    under the Pallas interpreter: NaN); lowered for any other platform
    it is zeros.  `after` is an array it is made no earlier than, and
    not read: the call's one operand, so that the array is not there
    before its place in the program, and two calls are two arrays (the
    compiler makes equal calls of equal operands one)."""
    def call(after):
        return pl.pallas_call(
            lambda after_ref, o_ref: None,
            out_shape=jax.ShapeDtypeStruct(shape, dtype),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            name="moe_unwritten")(after)

    return lax.platform_dependent(
        after, tpu=call, default=lambda _: jnp.zeros(shape, dtype))


def gmm(x, w, counts):
    """out[rows of group e] = x[rows of group e] @ w[e].  x [M, K], w
    [E, K, N], counts [E] (integers, summing to M) -> [M, N] in x's
    type."""
    return _grouped("fwd", ragged_gmm, x, w, counts)


def gmm_dx(dy, w, counts):
    """dx[rows of group e] = dy[rows of group e] @ w[e]^T: the gradient
    of `gmm` to its rows.  dy [M, N], w [E, K, N] -> [M, K]."""
    return _grouped("dx", ragged_gmm_dx, dy, w, counts)


def gmm_dw(x, dy, counts):
    """dw[e] = x[rows of group e]^T @ dy[rows of group e]: the gradient
    of `gmm` to its weights, float32 [E, K, N], zeros for an empty
    group."""
    return _grouped("dw", ragged_gmm_dw, x, dy, counts)
