"""Mamba-2's chunked selective scan (arXiv:2405.21060 section 6) and its
gradient as Mosaic kernels, for the `ssd_scan` op (ops/ssm.py, whose
docstring has the equations).

Both kernels walk a grid (batch, chunk, head group): the chunk axis is
sequential, forward for `y`, backward in reverse for the state's
cotangent, and the carried state of *every* head, [heads / g, d_state, g
* head_dim] float32 (2 MB at 64 heads of 64 and state 128), stays in VMEM
scratch from one chunk to the next.  The heads are the innermost axis
because all of them read one B and C (one group): a chunk's B, C and `C
B^T` are fetched and computed once, at the chunk's first head group, and
the gradient adds up `d(C B^T)`, dB and dC over the head groups in VMEM
and writes them once.  A grid step takes g = 128 // head_dim heads side
by side in the 128 lanes of X's own [batch, seq, heads * head_dim]
layout (two at 64 wide, as the flash kernels do since PR 30): the
products with the state take all g at once, the [chunk, chunk] ones take
one head at a time with the other heads' lanes zeroed in one operand (a
64-deep contraction costs a 128-deep pass anyway).  The state is held
transposed, [d_state, lanes], so that no product contracts over the
sublanes of both operands but two in the gradient (`M^T dY` and `dG^T
C`).  What the gradient sums per head and position (the decays', dt's)
it sums in float32 on the vector unit, as columns, and transposes once
into rows: a product with a matrix of ones would round one side of sums
that cancel (measured on the chip: the gradients of ALog and DtBias off
by a fifth).

A cached step's block of positions (`scan_from`, the op with `State`)
runs the forward kernel's body from a state handed in, [batch, d_state,
heads * head_dim] float32 (the scratch's own layout a head group: a
group's [d_state, 128] block is fetched at the first chunk), and writes
the state it carries out again after every chunk, over the one before
(the last chunk's stays); it keeps no state a chunk, which nothing
reads.  That kernel is named `ssd_block_c<chunk>_h<g>`.

The kernels are named `ssd_fwd_c<chunk>_h<g>` and `ssd_bwd_c<chunk>_h<g>`.
Around them, in XLA: the sums of `dt A` inside each chunk, B and C
transposed ([batch, d_state, seq], 1 MB each), the per-head rows the
kernels read and write as [batch, heads / g, g, seq]; after the gradient
kernel the reverse sums that turn `d cum` into `d(dt A)`.  `scan` and
`scan_grad` choose by the platform of the lowering: the kernels for the
TPU, the plain chunked `jax.numpy` path (`plain`, ops/ssm.py) anywhere
else, and for head widths that do not divide 128.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs import telemetry

F32 = jnp.float32
LANES = 128
_NEG = -1e30


def heads_a_step(width, heads):
    """How many heads share a grid step's 128 lanes, or 0 where the
    kernels do not apply (a head width that does not divide 128, or
    heads that do not fill the lanes)."""
    dim = width // heads
    if LANES % dim or LANES // dim > 8 or heads % (LANES // dim):
        return 0
    return LANES // dim


def _dot(a, b, dims):
    return lax.dot_general(a, b, (dims, ((), ())),
                           preferred_element_type=F32)


def _nn(a, b):
    return _dot(a, b, ((1,), (0,)))


def _nt(a, b):
    return _dot(a, b, ((1,), (1,)))


def _tn(a, b):
    return _dot(a, b, ((0,), (0,)))


def _columns(ref, first, g):
    """Columns first .. first + g - 1 of a [chunk, heads] block as g
    [chunk, 1] arrays: the block's lanes are all the heads, the step's
    heads a run of them that moves with the grid."""
    block = ref[...]
    lane = lax.broadcasted_iota(jnp.int32, block.shape, 1)
    return [jnp.sum(jnp.where(lane == first + k, block, 0.0), axis=1,
                    keepdims=True) for k in range(g)]


def _by_lane(columns, lane_head):
    """[chunk, 128]: each lane holds its head's column."""
    out = columns[0]
    for k in range(1, len(columns)):
        out = jnp.where(lane_head == k, columns[k], out)
    return jnp.broadcast_to(out, (out.shape[0], lane_head.shape[1])) \
        if out.shape[1] == 1 else out


def _head_sums(t, lane_head, g):
    """[chunk, 128] -> [chunk, 128] whose lane k holds the sum over head
    k's lanes (k < g; zeros beyond): float32 sums on the vector unit, as
    columns, which one transpose turns into the [g, chunk] rows the
    per-head arrays are blocked in."""
    lane = lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    out = jnp.zeros(t.shape, F32)
    for k in range(g):
        out = jnp.where(lane == k, jnp.sum(
            jnp.where(lane_head == k, t, 0.0), axis=1, keepdims=True), out)
    return out


def _only_head(t, lane_head, k):
    return jnp.where(lane_head == k, t, jnp.zeros_like(t))


def _below(q):
    """i - j over [q, q]: positive strictly below the diagonal."""
    return lax.broadcasted_iota(jnp.int32, (q, q), 0) \
        - lax.broadcasted_iota(jnp.int32, (q, q), 1)


def _decay_mask(cum_col, cum_row, below):
    """L[i, j] = exp(cum_i - cum_j) for i >= j, else 0."""
    return jnp.exp(jnp.where(below >= 0, cum_col - cum_row, _NEG))


def _step_decays(cum_cols, lane_head):
    """(exp(cum), exp(cum_last - cum)) as [chunk, 128] and exp(cum_last)
    as [1, 128], each lane its head's."""
    cum = _by_lane(cum_cols, lane_head)
    last = cum[-1:, :]
    return jnp.exp(cum), jnp.exp(last - cum), jnp.exp(last)


def _fwd_kernel(x_ref, dt_ref, cumc_ref, cumr_ref, bt_ref, c_ref, d_ref,
                y_ref, states_ref, state_scr, g_scr, *, g):
    chunk, group = pl.program_id(1), pl.program_id(2)

    @pl.when(chunk == 0)
    def _():
        state_scr[group] = jnp.zeros(state_scr.shape[1:], F32)

    states_ref[...] = state_scr[group]
    _fwd_chunk(x_ref, dt_ref, cumc_ref, cumr_ref, bt_ref, c_ref, d_ref,
               y_ref, state_scr, g_scr, g)


def _block_kernel(x_ref, dt_ref, cumc_ref, cumr_ref, bt_ref, c_ref, d_ref,
                  s_ref, y_ref, so_ref, state_scr, g_scr, *, g):
    """The forward kernel from a state handed in: the carried state is
    written out after every chunk, the last one's stays."""
    chunk, group = pl.program_id(1), pl.program_id(2)

    @pl.when(chunk == 0)
    def _():
        state_scr[group] = s_ref[...]

    _fwd_chunk(x_ref, dt_ref, cumc_ref, cumr_ref, bt_ref, c_ref, d_ref,
               y_ref, state_scr, g_scr, g)
    so_ref[...] = state_scr[group]


def _fwd_chunk(x_ref, dt_ref, cumc_ref, cumr_ref, bt_ref, c_ref, d_ref,
               y_ref, state_scr, g_scr, g):
    """A chunk of one head group: y, and the group's carried state moved
    on."""
    group = pl.program_id(2)
    kind = x_ref.dtype

    @pl.when(group == 0)
    def _():
        g_scr[...] = _nn(c_ref[...], bt_ref[...])

    entering = state_scr[group]
    lane_head = lax.broadcasted_iota(jnp.int32, (1, LANES), 1) \
        // (LANES // g)
    cum_cols = _columns(cumc_ref, group * g, g)
    x = x_ref[...].astype(F32)
    xd = x * _by_lane(_columns(dt_ref, group * g, g), lane_head)
    from_start, to_end, whole = _step_decays(cum_cols, lane_head)
    y = from_start * _nn(c_ref[...], entering.astype(kind)) \
        + d_ref[...] * x
    xd_k = xd.astype(kind)
    below = _below(x.shape[0])
    for k in range(g):
        m = (_decay_mask(cum_cols[k], cumr_ref[k:k + 1, :], below)
             * g_scr[...]).astype(kind)
        y = y + _nn(m, _only_head(xd_k, lane_head, k))
    y_ref[...] = y.astype(y_ref.dtype)
    state_scr[group] = whole * entering \
        + _nn(bt_ref[...], (xd * to_end).astype(kind))


def _bwd_kernel(x_ref, dy_ref, dt_ref, cumc_ref, cumr_ref, b_ref,
                c_ref, bt_ref, ct_ref, states_ref, d_ref,
                dx_ref, ddt_ref, dcum_ref, db_ref, dc_ref, dd_ref, carry_ref,
                dstate_scr, g_scr, dg_scr, *, g):
    step, group = pl.program_id(1), pl.program_id(2)
    last_group = pl.num_programs(2) - 1
    kind = x_ref.dtype
    chunk = x_ref.shape[0]

    @pl.when(step == 0)
    def _():
        dstate_scr[group] = jnp.zeros(dstate_scr.shape[1:], F32)

    @pl.when(group == 0)
    def _():
        g_scr[...] = _nn(c_ref[...], bt_ref[...])
        dg_scr[...] = jnp.zeros(dg_scr.shape, F32)
        db_ref[...] = jnp.zeros(db_ref.shape, F32)
        dc_ref[...] = jnp.zeros(dc_ref.shape, F32)

    leaving = dstate_scr[group]      # the cotangent of the state handed on
    entering = states_ref[...]
    lane_head = lax.broadcasted_iota(jnp.int32, (1, LANES), 1) \
        // (LANES // g)
    cum_cols = _columns(cumc_ref, group * g, g)
    dt = _by_lane(_columns(dt_ref, group * g, g), lane_head)
    from_start, to_end, whole = _step_decays(cum_cols, lane_head)
    x, dy = x_ref[...].astype(F32), dy_ref[...].astype(F32)
    xd = x * dt
    xd_k, dy_k = xd.astype(kind), dy_ref[...].astype(kind)

    # what the entering state gave y, and the state's own recurrence
    dy_dec = (dy * from_start).astype(kind)
    entering_k = entering.astype(kind)
    dc_ref[...] += _nt(dy_dec, entering_k)
    y_inter = from_start * _nn(c_ref[...], entering_k)
    leaving_k = leaving.astype(kind)
    dxd_state = to_end * _nn(b_ref[...], leaving_k)
    db_ref[...] += _nt((xd * to_end).astype(kind), leaving_k)
    dstate_scr[group] = whole * leaving + _nn(ct_ref[...], dy_dec)

    # the chunk's own positions, a head at a time
    dxd = dxd_state
    below = _below(chunk)
    lane = lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    row_of = lax.broadcasted_iota(jnp.int32, (8, chunk), 0)
    gained = jnp.zeros((chunk, LANES), F32)     # lane k: head k, a column
    lost = jnp.zeros((8, chunk), F32)           # row k: head k
    for k in range(g):
        mask = _decay_mask(cum_cols[k], cumr_ref[k:k + 1, :], below)
        dm = _nt(dy_k, _only_head(xd_k, lane_head, k)) * mask
        dg_scr[...] += dm
        dxd = dxd + _tn((mask * g_scr[...]).astype(kind),
                        _only_head(dy_k, lane_head, k))
        # E = dL . L below the diagonal: position i's sum of decays gains
        # E's row i and loses its column i.  Both sums in float32 on the
        # vector unit: they cancel but for the pairs that straddle a
        # position, and a product with ones would round E on one side
        e = jnp.where(below > 0, dm * g_scr[...], 0.0)
        gained = jnp.where(lane == k, jnp.sum(e, axis=1, keepdims=True),
                           gained)
        lost = jnp.where(row_of == k, jnp.sum(e, axis=0, keepdims=True),
                         lost)

    d_skip = d_ref[...]
    owed = _head_sums(dxd_state * xd, lane_head, g)
    d_cum = gained + _head_sums(dy * y_inter, lane_head, g) - owed
    at_end = lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) == chunk - 1
    d_cum = d_cum + jnp.where(at_end, jnp.sum(owed, axis=0, keepdims=True),
                              0.0)
    dcum_ref[...] = jnp.transpose(d_cum)[:g] - lost[:g]
    ddt_ref[...] = jnp.transpose(_head_sums(dxd * x, lane_head, g))[:g]
    dx_ref[...] = (dxd * dt + d_skip * dy).astype(dx_ref.dtype)
    dd_ref[...] = jnp.sum(dy * x, axis=0, keepdims=True)
    carry_ref[...] = whole * jnp.sum(leaving * entering, axis=0,
                                     keepdims=True)

    @pl.when(group == last_group)
    def _():
        dg = dg_scr[...].astype(kind)
        dc_ref[...] += _nn(dg, b_ref[...])
        db_ref[...] += _tn(dg, c_ref[...])


def _params(interpret):
    if interpret:
        return {"interpret": True}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=64 * 1024 * 1024)}


def _rows(t, g):
    """[batch, seq, heads] -> [batch, heads / g, g, seq]."""
    batch, seq, heads = t.shape
    return jnp.swapaxes(t, 1, 2).reshape(batch, heads // g, g, seq)


def _from_rows(t):
    """[batch, heads / g, g, seq] -> [batch, seq, heads]."""
    batch, groups, g, seq = t.shape
    return jnp.swapaxes(t.reshape(batch, groups * g, seq), 1, 2)


def _chunk_sums(a, chunk):
    batch, seq, heads = a.shape
    return jnp.cumsum(a.reshape(batch, seq // chunk, chunk, heads),
                      axis=2).reshape(a.shape)


def _by_lane_row(per_head, dim):
    """[heads] -> [1, heads * dim], each head's value over its lanes."""
    return jnp.repeat(per_head.astype(F32), dim)[None, :]


def fwd_kernels(x, dt, a, b, c, d_skip, chunk, interpret=False,
                entering=None):
    """`ops.ssm.chunked_scan` as the forward kernel: y in x's type and
    the entering states, float32; with `entering` [batch, d_state, heads
    * head_dim] float32, from it, and the state after the last chunk."""
    batch, seq, width = x.shape
    heads, state = dt.shape[-1], b.shape[-1]
    g = heads_a_step(width, heads)
    chunks, groups = seq // chunk, heads // g
    cum = _chunk_sums(a, chunk)
    wide = pl.BlockSpec((None, chunk, LANES), lambda i, j, h: (i, j, h))
    per_head = pl.BlockSpec((None, chunk, heads), lambda i, j, h: (i, j, 0))
    rows = pl.BlockSpec((None, None, g, chunk),
                        lambda i, j, h: (i, h, 0, j))
    in_specs = [
        wide, per_head, per_head, rows,
        pl.BlockSpec((None, state, chunk), lambda i, j, h: (i, 0, j)),
        pl.BlockSpec((None, chunk, state), lambda i, j, h: (i, j, 0)),
        pl.BlockSpec((1, LANES), lambda i, j, h: (0, h)),
    ]
    operands = (x, dt, cum, _rows(cum, g), jnp.swapaxes(b, 1, 2), c,
                _by_lane_row(d_skip, width // heads))
    scratch = [pltpu.VMEM((groups, state, LANES), F32),
               pltpu.VMEM((chunk, chunk), F32)]
    if entering is not None:
        a_group = pl.BlockSpec((None, state, LANES),
                               lambda i, j, h: (i, 0, h))
        return tuple(pl.pallas_call(
            functools.partial(_block_kernel, g=g),
            grid=(batch, chunks, groups),
            in_specs=in_specs + [a_group], out_specs=[wide, a_group],
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                       jax.ShapeDtypeStruct(entering.shape, F32)],
            scratch_shapes=scratch,
            name="ssd_block_c%d_h%d" % (chunk, g),
            **_params(interpret),
        )(*operands, entering))
    return tuple(pl.pallas_call(
        functools.partial(_fwd_kernel, g=g),
        grid=(batch, chunks, groups),
        in_specs=in_specs,
        out_specs=[
            wide,
            pl.BlockSpec((None, None, state, LANES),
                         lambda i, j, h: (i, j, 0, h)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((batch, chunks, state, width), F32),
        ],
        scratch_shapes=scratch,
        name="ssd_fwd_c%d_h%d" % (chunk, g),
        **_params(interpret),
    )(*operands))


def bwd_kernels(x, dt, a, b, c, d_skip, states, dy, chunk,
                interpret=False):
    """`ops.ssm.chunked_scan_grad` as the backward kernel and the sums
    around it."""
    batch, seq, width = x.shape
    heads, state = dt.shape[-1], b.shape[-1]
    g = heads_a_step(width, heads)
    chunks, groups = seq // chunk, heads // g
    dim = width // heads
    cum = _chunk_sums(a, chunk)
    back = lambda j: chunks - 1 - j
    wide = pl.BlockSpec((None, chunk, LANES),
                        lambda i, j, h: (i, back(j), h))
    per_head = pl.BlockSpec((None, chunk, heads),
                            lambda i, j, h: (i, back(j), 0))
    rows = pl.BlockSpec((None, None, g, chunk),
                        lambda i, j, h: (i, h, 0, back(j)))
    narrow = pl.BlockSpec((None, chunk, state),
                          lambda i, j, h: (i, back(j), 0))
    turned = pl.BlockSpec((None, state, chunk),
                          lambda i, j, h: (i, 0, back(j)))
    a_lane_row = pl.BlockSpec((None, None, 1, LANES),
                              lambda i, j, h: (i, back(j), 0, h))
    row_shape = jax.ShapeDtypeStruct((batch, groups, g, seq), F32)
    lane_rows = jax.ShapeDtypeStruct((batch, chunks, 1, width), F32)
    dx, ddt, d_cum, db, dc, dd, carry = pl.pallas_call(
        functools.partial(_bwd_kernel, g=g),
        grid=(batch, chunks, groups),
        in_specs=[
            wide, wide, per_head, per_head, rows, narrow, narrow,
            turned, turned,
            pl.BlockSpec((None, None, state, LANES),
                         lambda i, j, h: (i, back(j), 0, h)),
            pl.BlockSpec((1, LANES), lambda i, j, h: (0, h)),
        ],
        out_specs=[wide, rows, rows, narrow, narrow, a_lane_row,
                   a_lane_row],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, dy.dtype), row_shape, row_shape,
            jax.ShapeDtypeStruct(b.shape, F32),
            jax.ShapeDtypeStruct(c.shape, F32), lane_rows, lane_rows,
        ],
        scratch_shapes=[pltpu.VMEM((groups, state, LANES), F32),
                        pltpu.VMEM((chunk, chunk), F32),
                        pltpu.VMEM((chunk, chunk), F32)],
        name="ssd_bwd_c%d_h%d" % (chunk, g),
        **_params(interpret),
    )(x, dy, dt, cum, _rows(cum, g), b, c, jnp.swapaxes(b, 1, 2),
      jnp.swapaxes(c, 1, 2), states, _by_lane_row(d_skip, dim))
    # the last position of a chunk also owes what the handed-on state
    # owes the decay of the whole chunk
    d_cum = _from_rows(d_cum).reshape(batch, chunks, chunk, heads)
    d_cum = d_cum.at[:, :, -1].add(
        jnp.sum(carry.reshape(batch, chunks, heads, dim), axis=-1))
    da = jnp.flip(jnp.cumsum(jnp.flip(d_cum, 2), axis=2), 2)
    return (dx, _from_rows(ddt), da.reshape(batch, seq, heads), db, dc,
            jnp.sum(dd.reshape(-1, heads, dim), axis=(0, 2)))


def scan(x, dt, a, b, c, d_skip, chunk, plain):
    """y (x's type or float32) and the states entering the chunks."""
    g = heads_a_step(x.shape[-1], dt.shape[-1])
    telemetry.on_ssd_lowering("fwd", chunk, g)
    if not g:
        return plain(x, dt, a, b, c, d_skip, chunk)
    with jax.named_scope("ssd_chunks"):
        return lax.platform_dependent(
            x, dt, a, b, c, d_skip,
            tpu=functools.partial(fwd_kernels, chunk=chunk),
            default=lambda *args: _typed(plain(*args, chunk), x.dtype))


def scan_from(x, dt, a, b, c, d_skip, state, chunk, plain):
    """y and the state after the block, from `state` [batch, d_state,
    heads * head_dim] float32: `plain(..., chunk, state=state)`
    anywhere but on the TPU."""
    if not heads_a_step(x.shape[-1], dt.shape[-1]):
        return plain(x, dt, a, b, c, d_skip, chunk, state=state)
    with jax.named_scope("ssd_chunks"):
        return lax.platform_dependent(
            x, dt, a, b, c, d_skip, state,
            tpu=lambda *args: fwd_kernels(*args[:-1], chunk=chunk,
                                          entering=args[-1]),
            default=lambda *args: _typed(
                plain(*args[:-1], chunk, state=args[-1]), x.dtype))


def _typed(outs, dtype):
    return (outs[0].astype(dtype),) + tuple(outs[1:])


def scan_grad(x, dt, a, b, c, d_skip, states, dy, chunk, plain):
    """(dx, ddt, da, db, dc, dd): `ops.ssm.chunked_scan_grad`."""
    g = heads_a_step(x.shape[-1], dt.shape[-1])
    telemetry.on_ssd_lowering("bwd", chunk, g)
    if not g:
        return plain(x, dt, a, b, c, d_skip, states, dy, chunk)
    with jax.named_scope("ssd_chunks"):
        return lax.platform_dependent(
            x, dt, a, b, c, d_skip, states, dy,
            tpu=functools.partial(bwd_kernels, chunk=chunk),
            default=functools.partial(plain, chunk=chunk))
