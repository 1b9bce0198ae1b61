"""A state-space layer's ops: Mamba-2's selective scan (`ssd_scan`),
Mamba-1's (`selective_scan`, which a decoder carries a state through)
and the causal depthwise convolution in front of either.

`ssd_scan` is the recurrence of "Transformers are SSMs"
(arXiv:2405.21060), one state `S` [head_dim, d_state] a head:

    dt_t = softplus(Dt_t + DtBias)          A = -exp(ALog)
    S_t  = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        S_{-1} = 0
    y_t  = S_t C_t + D x_t

computed in chunks (section 6 of the paper, "SSD"): with `cum` the sums
of `dt A` inside a chunk, `Y = (L . (C B^T)) (dt X)` with `L[i, j] =
exp(cum_i - cum_j)` for `i >= j` is what a chunk's own positions give
one another, `C S_in^T` decayed by `exp(cum)` is what the state entering
the chunk gives, and the state is handed on as `exp(cum_last) S_in +
(exp(cum_last - cum) dt X)^T B`.  Nothing is [seq, seq] and nothing
walks the positions one by one; `seq` that is no multiple of the chunk
is an error when the program is built.  Every head reads the same B and
C (one group).

The op takes Dt *before* the softplus, with DtBias: under bfloat16
compute the projection that makes Dt is bfloat16, and a softplus op of
its own between the two would round dt, the step of every decay, to
eight bits.  Here Dt, DtBias, ALog, `dt`, `dt A`, their sums, every
decay and every carried state are float32 whatever the compute type;
the products take the compute type's operands and add up in float32;
the gradients of ALog, D and DtBias add up in float32.

The gradient is explicit, for the reason `flash_attention`'s and
`moe_experts`' are: `jax.vjp` of the op would run the forward's chunk
products a second time.  The forward op keeps, beside Y,

    States [batch, chunks, d_state, heads * head_dim] float32

the state entering each chunk (33.5 MB a layer at 4096 x 64 x 64 x 128;
transposed, state rows by head lanes, as the kernel carries it), and the
gradient op reads it.  What it computes again: the softplus and the sums
(three [batch, seq, heads] float32 arrays, 1 MB each: keeping them would
cost 3 MB to save three elementwise passes over 1 MB); `C B^T` and the
decay mask `L` (0.27 GFLOP and 67M exponentials a layer; keeping `L . (C
B^T)` would be 134 MB a layer in bfloat16); and `C S_in^T` (4.3 GFLOP a
layer, a third of the forward's 13), because the decays' gradient needs
`sum_p dY_i Y_i` of what the entering state gave.  The same sum over what
the chunk's own positions gave is taken from `dL . L` without its
diagonal, not from the Y the forward wrote: Y is rounded to the compute
type, and on the diagonal, where L is 1 whatever the decay, gains and
losses cancel to nothing but their rounding.  It runs none of the
forward's other products (`M (dt X)`, the chunk states).

A cached step's scan **carries its state**: with the optional input
`State` [batch, d_state, heads * head_dim] float32, what the positions
before the block left (zeros at a sequence's start; the kernels' own
layout, state rows by head lanes, which pads nothing: 128 sublanes by
8192 lanes at granite-4.0-h-small's 128 heads of 64), `S_{-1}` is that
state and the op gives `StateOut`, the state after the block's last
position: a `fluid.ProgramDecoder` state pair.  T = 1 is one update of
the state: `ssd_update`, plain `jax.numpy`, which the compiler fuses
into one pass over the state at 81% of a v5e's HBM peak at 128 heads of
64 over 128, its own pipeline's ceiling (a Pallas kernel through that
pipeline did not beat it: PERF.md section 6, PR 71); lowered for the
TPU at a shape kernels/ssd_step.py takes (`choose_block`: a float32
state, `d_state` whole sublane tiles, `heads * head_dim` whole lane
blocks) the same arithmetic as `ssd_step_r<rows>_b<rows a grid step>`,
which moves the state with copies of its own, reads and writes taking
turns, and hands the state's buffer back as `StateOut` (PERF.md section
6, PR 72).  T a multiple of the chunk is the
chunked scan started from `State` (kernels/ssd.py's `ssd_block_*`, which
keeps no `States` a chunk: nothing reads them), leaving what T steps
leave to rounding; any other T is the error it always was.  `heads_apart`
gives a state as the recurrence has it, [batch, heads, head_dim,
d_state].  That form is forward only; without `State` the op lowers as
it always did.

`causal_conv1d` is out_t = act(bias + sum_j filter[:, j] x_{t-(K-1)+j}),
each channel by itself, zeros before position 0: one fused pass.  Its
gradient is explicit too and reads X, Filter, Bias and dOut only: it
computes the pre-activation again (one more fused pass over X) where
`jax.vjp` would keep K shifted copies of X.

A cached step's convolution **carries its tail**: with the optional
input `Tail` [batch, K - 1, channels], the K - 1 positions before the
block (zeros at a sequence's start), position 0 reads them in place of
zeros, and the op gives `TailOut`, the last K - 1 positions of tail and
block together, in Tail's type: a `fluid.ProgramDecoder` state pair, so
that a sequence fed in blocks of any lengths (a prompt's, then a
position a step) convolves as it would whole.  `Bias` is optional too
(Qwen3-Next's convolution has none).  That form is forward only; without
`Tail` the op lowers as it always did.

`selective_scan` is Mamba-1's recurrence (arXiv:2312.00752), a decay a
channel *and* a state entry and one step size a channel, B and C shared
by all channels, with the state handed in and handed on:

    dt_t = softplus(Dt_t + DtBias)          A = -exp(ALog)   [D, N]
    S_t  = exp(dt_t (x) A) * S_{t-1} + (dt_t * x_t) (x) B_t
    y_t  = S_t C_t + D * x_t

for x_t, dt_t [D] channels and B_t, C_t [N] state entries.  `State`
[batch, N, D] float32 is what the positions before the block left (zeros
at a sequence's start: the state's entries are the sublanes and the
channels the lanes, so that 16 entries pad nothing) and `StateOut` what
the block leaves, a `fluid.ProgramDecoder` state pair; Dt comes before
the softplus as `ssd_scan` takes it.  Everything inside is float32
whatever the operands' type.  T = 1 is one fused update of the state;
T > 1 walks the block's positions one by one through that same update
(`lax.scan`), so a block leaves, rounding for rounding, what T steps
leave: a decay a channel and entry has no chunked form with one
`[chunk, chunk]` mask a head, which is what `ssd_scan` rests on.  No
gradient: generation needs none, and training the layer wants a
parallel scan this op does not have.
"""

import jax
import jax.numpy as jnp

from ..obs import telemetry
from .amp_util import amp_result, mxu_operands
from .registry import (register_grad_kernel, register_op,
                       same_meta_infer_shape)

F32 = jnp.float32
_ACC = {"preferred_element_type": F32}


def _set_meta(block, name, shape, dtype):
    desc = block.var_recursive(name).desc
    desc.shape, desc.dtype, desc.lod_level = tuple(shape), dtype, 0


# -- the chunked scan as plain jax.numpy ---------------------------------------

def _chunks(t, chunk):
    """[batch, seq, ...] -> [batch, chunks, chunk, ...]."""
    return t.reshape(t.shape[0], t.shape[1] // chunk, chunk, *t.shape[2:])


def _decay_mask(cum):
    """L [batch, chunks, heads, i, j] = exp(cum_i - cum_j) for i >= j,
    else 0, from cum [batch, chunks, chunk, heads]."""
    ch = jnp.swapaxes(cum, 2, 3)
    q = ch.shape[-1]
    lower = jnp.tril(jnp.ones((q, q), bool))
    return jnp.exp(jnp.where(lower, ch[..., :, None] - ch[..., None, :],
                             -jnp.inf))


def _carried(decay, local, reverse=False, start=None, handed=False):
    """The state entering each chunk (leaving it, walked in `reverse`):
    s_0 = `start` (zeros), s_{c+1} = decay_c s_c + local_c.  decay
    [batch, chunks, heads], local [batch, chunks, d_state, heads,
    head_dim].  With `handed`, also the state after the last chunk."""
    def step(s, inputs):
        d, loc = inputs
        return d[:, None, :, None] * s + loc, s

    last, entering = jax.lax.scan(
        step, jnp.zeros_like(local[:, 0]) if start is None else start,
        (jnp.moveaxis(decay, 1, 0), jnp.moveaxis(local, 1, 0)),
        reverse=reverse)
    entering = jnp.moveaxis(entering, 0, 1)
    return (entering, last) if handed else entering


def _state_product(rows, cols):
    """sum_j rows[.., j, n] cols[.., j, h, p] -> [.., n, h, p], float32.
    (With the heads folded into one axis: XLA's CPU backend has no
    bfloat16 product of this form over two free axes.)"""
    flat = cols.reshape(*cols.shape[:3], -1)
    return jnp.einsum("bcjn,bcjw->bcnw", rows, flat, **_ACC).reshape(
        *cols.shape[:2], rows.shape[-1], *cols.shape[3:])


def chunked_scan(x, dt, a, b, c, d_skip, chunk, state=None):
    """x [batch, seq, heads * head_dim], b and c [batch, seq, d_state] in
    the compute type; dt (after the softplus), a = dt * A [batch, seq,
    heads] and d_skip [heads] float32 -> y float32 [batch, seq, heads *
    head_dim] and the states entering the chunks, float32 [batch,
    chunks, d_state, heads * head_dim].  With `state` [batch, d_state,
    heads * head_dim] float32 the first chunk enters from it, and the
    second result is the state after the last chunk, in its shape."""
    heads = dt.shape[-1]
    xc = _chunks(x.reshape(*x.shape[:2], heads, -1), chunk)
    bc, cc, dtc = _chunks(b, chunk), _chunks(c, chunk), _chunks(dt, chunk)
    cum = jnp.cumsum(_chunks(a, chunk), axis=2)
    xd = xc.astype(F32) * dtc[..., None]
    with jax.named_scope("ssd_intra"):
        g = jnp.einsum("bcin,bcjn->bcij", cc, bc, **_ACC)
        m = (_decay_mask(cum) * g[:, :, None]).astype(x.dtype)
        y = jnp.einsum("bchij,bcjhp->bcihp", m, xd.astype(x.dtype), **_ACC)
    with jax.named_scope("ssd_state"):
        to_end = jnp.exp(cum[:, :, -1:] - cum)
        local = _state_product(bc, (xd * to_end[..., None]).astype(x.dtype))
        if state is None:
            entering = _carried(jnp.exp(cum[:, :, -1]), local)
        else:
            entering, handed = _carried(
                jnp.exp(cum[:, :, -1]), local, handed=True,
                start=state.reshape(*state.shape[:2], heads, -1))
    with jax.named_scope("ssd_inter"):
        y = y + jnp.exp(cum)[..., None] * jnp.einsum(
            "bcin,bcnhp->bcihp", cc, entering.astype(x.dtype), **_ACC)
    y = y + d_skip[:, None] * xc.astype(F32)
    if state is not None:
        return y.reshape(x.shape), handed.reshape(state.shape)
    return y.reshape(x.shape), entering.reshape(*entering.shape[:3], -1)


def chunked_scan_grad(x, dt, a, b, c, d_skip, states, dy, chunk):
    """The gradient of `chunked_scan` to x (in dy's type), dt and a (as
    if independent), b, c and d_skip (float32), from the states the
    forward kept."""
    heads = dt.shape[-1]
    split = lambda t: _chunks(t.reshape(*t.shape[:2], heads, -1), chunk)
    xc, dyc = split(x), split(dy)
    bc, cc, dtc = _chunks(b, chunk), _chunks(c, chunk), _chunks(dt, chunk)
    cum = jnp.cumsum(_chunks(a, chunk), axis=2)
    entering = states.reshape(*states.shape[:3], heads, -1)
    kind = x.dtype
    xf, dyf = xc.astype(F32), dyc.astype(F32)
    xd = xf * dtc[..., None]
    with jax.named_scope("ssd_inter"):
        from_start = jnp.exp(cum)[..., None]
        dy_dec = (dyf * from_start).astype(kind)
        d_entering = _state_product(cc, dy_dec)
        dc = jnp.einsum("bcihp,bcnhp->bcin", dy_dec, entering.astype(kind),
                        **_ACC)
        # sum_p dY_i Y_i of what the entering state gave: C S_in^T again
        d_cum = jnp.sum(dyf * from_start * jnp.einsum(
            "bcin,bcnhp->bcihp", cc, entering.astype(kind), **_ACC), axis=-1)
    with jax.named_scope("ssd_state"):
        dec = jnp.exp(cum[:, :, -1])
        # the cotangent of the state leaving each chunk
        d_leaving = _carried(dec, d_entering, reverse=True)
        to_end = jnp.exp(cum[:, :, -1:] - cum)
        dxd_state = to_end[..., None] * jnp.einsum(
            "bcjn,bcnhp->bcjhp", bc, d_leaving.astype(kind), **_ACC)
        db = jnp.einsum("bcjhp,bcnhp->bcjn",
                        (xd * to_end[..., None]).astype(kind),
                        d_leaving.astype(kind), **_ACC)
        owed = jnp.sum(dxd_state * xd, axis=-1)
        # the last position's decay is also the whole chunk's, to the
        # state that is handed on
        d_cum = (d_cum - owed).at[:, :, -1].add(
            jnp.sum(owed, axis=2)
            + dec * jnp.sum(d_leaving * entering, axis=(2, 4)))
    with jax.named_scope("ssd_intra"):
        g = jnp.einsum("bcin,bcjn->bcij", cc, bc, **_ACC)
        mask = _decay_mask(cum)
        dm = jnp.einsum("bcihp,bcjhp->bchij", dyc.astype(kind),
                        xd.astype(kind), **_ACC) * mask
        dg = jnp.sum(dm, axis=2).astype(kind)
        dc = dc + jnp.einsum("bcij,bcjn->bcin", dg, bc, **_ACC)
        db = db + jnp.einsum("bcij,bcin->bcjn", dg, cc, **_ACC)
        dxd = dxd_state + jnp.einsum(
            "bchij,bcihp->bcjhp", (mask * g[:, :, None]).astype(kind),
            dyc.astype(kind), **_ACC)
        # E[i, j] = dL[i, j] L[i, j] for i > j: position i's sum gains its
        # row, position j's loses its column (the diagonal, where L is 1
        # whatever the decay, would cancel, and is left out of both)
        q = mask.shape[-1]
        e = jnp.where(jnp.tril(jnp.ones((q, q), bool), -1),
                      dm * g[:, :, None], 0.0)
        d_cum = d_cum + jnp.swapaxes(jnp.sum(e, -1) - jnp.sum(e, -2), 2, 3)
    da = jnp.flip(jnp.cumsum(jnp.flip(d_cum, 2), axis=2), 2)
    ddt = jnp.sum(dxd * xf, axis=-1)
    dx = dxd * dtc[..., None] + d_skip[:, None] * dyf
    dd = jnp.sum(dyf * xf, axis=(0, 1, 2, 4))
    flat = lambda t: t.reshape(t.shape[0], -1, *t.shape[3:])
    return (dx.astype(dy.dtype).reshape(x.shape), flat(ddt), flat(da),
            flat(db), flat(dc), dd)


# -- ssd_scan --------------------------------------------------------------------

def _scan_sizes(x_shape, dt_shape, chunk, carried=False):
    """`carried`: the form with `State`, whose block may also be one
    position, or left open when the program is built (-1: the lowering
    sees the length and checks it)."""
    batch, seq, width = (int(s) for s in x_shape)
    heads = int(dt_shape[-1])
    if width % heads:
        raise ValueError("ssd_scan: X's width %d is no multiple of %d heads"
                         % (width, heads))
    if seq % chunk and not (carried and seq in (1, -1)):
        raise ValueError(
            "ssd_scan: a sequence of %d positions is no multiple of the "
            "chunk (%d)%s; pad the data, the op pads nothing"
            % (seq, chunk, " nor one position" if carried else ""))
    return batch, seq, width, heads


def _scan_infer_shape(block, op_desc):
    x = block.var_recursive(op_desc.input("X")[0]).desc
    dt = block.var_recursive(op_desc.input("Dt")[0]).desc
    b = block.var_recursive(op_desc.input("B")[0]).desc
    chunk = int(op_desc.attrs["chunk_size"])
    if op_desc.input("State"):
        _scan_sizes(x.shape, dt.shape, chunk, carried=True)
        state = block.var_recursive(op_desc.input("State")[0]).desc
        _set_meta(block, op_desc.output("Y")[0], x.shape, x.dtype)
        _set_meta(block, op_desc.output("StateOut")[0], state.shape,
                  state.dtype)
        return
    batch, seq, width, _ = _scan_sizes(x.shape, dt.shape, chunk)
    _set_meta(block, op_desc.output("Y")[0], x.shape, x.dtype)
    _set_meta(block, op_desc.output("States")[0],
              (batch, seq // chunk, int(b.shape[-1]), width), "float32")


def _steps(dt_raw, dt_bias, a_log):
    """dt = softplus(Dt + DtBias) and a = dt * A, A = -exp(ALog), float32
    [batch, seq, heads]; and A."""
    neg_a = -jnp.exp(a_log.astype(F32))
    dt = jax.nn.softplus(dt_raw.astype(F32) + dt_bias.astype(F32))
    return dt, dt * neg_a, neg_a


def _scan_inputs(ins):
    x, b, c = mxu_operands(ins["X"][0], ins["B"][0], ins["C"][0])
    return x, b, c, ins["Dt"][0], ins["DtBias"][0], ins["ALog"][0], \
        ins["D"][0].astype(F32)


@register_op("ssd_scan", infer_shape=_scan_infer_shape)
def ssd_scan(ctx, ins, attrs):
    """X [batch, seq, heads * head_dim], Dt [batch, seq, heads] (before
    the softplus), DtBias, ALog, D [heads], B and C [batch, seq,
    d_state] -> Y, X's shape and type, and States (the module's
    docstring)."""
    from ..kernels import ssd

    chunk = int(attrs["chunk_size"])
    if ins.get("State"):
        return _scan_carried(ins, chunk)
    x, b, c, dt_raw, dt_bias, a_log, d_skip = _scan_inputs(ins)
    _scan_sizes(x.shape, dt_raw.shape, chunk)
    with jax.named_scope("ssd_decay"):
        dt, a, _ = _steps(dt_raw, dt_bias, a_log)
    y, states = ssd.scan(x, dt, a, b, c, d_skip, chunk,
                         plain=chunked_scan)
    return {"Y": [amp_result(y, ins["X"][0].dtype)], "States": [states]}


def heads_apart(state, heads):
    """A carried state [batch, d_state, heads * head_dim] as the
    recurrence has it, [batch, heads, head_dim, d_state]."""
    batch, entries, width = state.shape
    return jnp.transpose(state.reshape(batch, entries, heads, width // heads),
                         (0, 2, 3, 1))


def ssd_update(state, x, dt, a, b, c, d_skip):
    """One position of Mamba-2's recurrence, all float32, over the state
    as it is carried: `state` [batch, d_state, heads * head_dim], `x`
    [batch, heads * head_dim], `dt` (after the softplus) and `a` = dt * A
    [batch, heads], `b` and `c` [batch, d_state], `d_skip` [heads] ->
    (y [batch, heads * head_dim], the state after the position)."""
    dim = x.shape[-1] // dt.shape[-1]
    by_lane = lambda t: jnp.repeat(t, dim, axis=-1)
    state = by_lane(jnp.exp(a))[:, None, :] * state \
        + b[:, :, None] * (by_lane(dt) * x)[:, None, :]
    return jnp.sum(state * c[:, :, None], axis=1) + by_lane(d_skip) * x, \
        state


def _scan_carried(ins, chunk):
    """The op with `State`: a step (T = 1) or a block of whole chunks
    from the state handed in; Y and StateOut."""
    from ..kernels import ssd, ssd_step

    x, b, c, dt_raw, dt_bias, a_log, d_skip = _scan_inputs(ins)
    state = ins["State"][0]
    rows, length, width = x.shape
    heads, entries = dt_raw.shape[-1], b.shape[-1]
    _scan_sizes(x.shape, dt_raw.shape, chunk, carried=True)
    if state.shape != (rows, entries, width) or state.dtype != F32:
        raise ValueError(
            "ssd_scan: State %s %s is not float32 [batch, d_state, heads * "
            "head_dim] = %s" % (state.shape, state.dtype,
                                (rows, entries, width)))
    step = length == 1
    kernel = ssd_step.choose_block(rows, entries, width, state.dtype) \
        if step else ssd.heads_a_step(width, heads)
    telemetry.on_ssd_scan_lowering(
        "step" if step else "block", "kernel" if kernel else "plain",
        0 if step else chunk, heads, state.dtype,
        state[0].size * state.dtype.itemsize)
    with jax.named_scope("ssd_decay"):
        dt, a, _ = _steps(dt_raw, dt_bias, a_log)
    if step:
        at = (state, x[:, 0].astype(F32), dt[:, 0], a[:, 0],
              b[:, 0].astype(F32), c[:, 0].astype(F32), d_skip)
        with jax.named_scope("ssd_step"):
            y, new = ssd_step.step(*at, plain=ssd_update) if kernel \
                else ssd_update(*at)
        y = y[:, None]
    else:
        y, new = ssd.scan_from(x, dt, a, b, c, d_skip, state, chunk,
                               plain=chunked_scan)
    return {"Y": [amp_result(y, ins["X"][0].dtype)], "StateOut": [new]}


@register_grad_kernel("ssd_scan")
def ssd_scan_grad(ctx, ins, attrs):
    """The seven gradients from O@States and OG@Y; X's in dY's type,
    B's and C's in theirs, the parameters' float32."""
    from ..kernels import ssd

    if ins.get("State"):
        raise NotImplementedError(
            "ssd_scan: the form that carries its state is forward only "
            "(a cached step's)")
    x, b, c, dt_raw, dt_bias, a_log, d_skip = _scan_inputs(ins)
    chunk = int(attrs["chunk_size"])
    states, dy = ins["O@States"][0], ins["OG@Y"][0]
    with jax.named_scope("ssd_decay"):
        dt, a, neg_a = _steps(dt_raw, dt_bias, a_log)
    dx, ddt, da, db, dc, dd = ssd.scan_grad(
        x, dt, a, b, c, d_skip, states, dy.astype(x.dtype), chunk,
        plain=chunked_scan_grad)
    with jax.named_scope("ssd_decay"):
        ddt = ddt + da * neg_a
        d_a_log = jnp.sum(da * dt, axis=(0, 1)) * neg_a
        # softplus' = sigmoid = 1 - exp(-softplus)
        d_raw = ddt * -jnp.expm1(-dt)
    return {"X@GRAD": [dx.astype(dy.dtype)],
            "Dt@GRAD": [d_raw.astype(dt_raw.dtype)],
            "DtBias@GRAD": [jnp.sum(d_raw, axis=(0, 1))
                            .astype(dt_bias.dtype)],
            "ALog@GRAD": [d_a_log.astype(a_log.dtype)],
            "B@GRAD": [db.astype(ins["B"][0].dtype)],
            "C@GRAD": [dc.astype(ins["C"][0].dtype)],
            "D@GRAD": [dd.astype(ins["D"][0].dtype)]}


# -- causal_conv1d ---------------------------------------------------------------

_ACTIVATIONS = ("", "silu")


def _conv_infer_shape(block, op_desc):
    x = block.var_recursive(op_desc.input("X")[0]).desc
    _set_meta(block, op_desc.output("Out")[0], x.shape, x.dtype)
    if op_desc.input("Tail"):
        tail = block.var_recursive(op_desc.input("Tail")[0]).desc
        _set_meta(block, op_desc.output("TailOut")[0], tail.shape,
                  tail.dtype)


def _shifted(x, width, j):
    """x_{t - (width - 1) + j} at position t, zeros before position 0."""
    back = width - 1 - j
    if not back:
        return x
    return jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :x.shape[1]]


def _pre_activation(x, filt, bias):
    """bias + sum_j filter[:, j] x_{t-(K-1)+j}, float32."""
    width = filt.shape[1]
    xf, w = x.astype(F32), filt.astype(F32)
    return bias.astype(F32) + sum(
        _shifted(xf, width, j) * w[:, j] for j in range(width))


def _conv_attrs(attrs):
    act = attrs.get("activation", "") or ""
    if act not in _ACTIVATIONS:
        raise ValueError("causal_conv1d: activation %r (one of %s)"
                         % (act, _ACTIVATIONS))
    return act


def _pre_activation_after(x, filt, bias, tail):
    """(bias + sum_j filter[:, j] x_{t-(K-1)+j} in float32, the tail
    handed on) with `tail` in front of the block."""
    width, seq = filt.shape[1], x.shape[1]
    if tail.shape != (x.shape[0], width - 1, x.shape[2]):
        raise ValueError(
            "causal_conv1d: Tail %s is not the %d positions before a "
            "block %s" % (tail.shape, width - 1, x.shape))
    joined = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    xf, w = joined.astype(F32), filt.astype(F32)
    pre = sum(xf[:, j:j + seq] * w[:, j] for j in range(width))
    if bias is not None:
        pre = pre + bias.astype(F32)
    return pre, joined[:, seq:].astype(tail.dtype)


@register_op("causal_conv1d", infer_shape=_conv_infer_shape)
def causal_conv1d(ctx, ins, attrs):
    """X [batch, seq, channels], Filter [channels, width], Bias
    [channels] -> Out, X's shape and type (the module's docstring);
    attr `activation` "" or "silu".  Sums in float32.  With `Tail`
    [batch, width - 1, channels] also TailOut; `Bias` may then be left
    out."""
    x, filt = ins["X"][0], ins["Filter"][0]
    act = _conv_attrs(attrs)
    telemetry.on_causal_conv1d_lowering(filt.shape[1], act or "none")
    handed = {}
    if ins.get("Tail"):
        tail = ins["Tail"][0]
        telemetry.on_causal_conv1d_tail_lowering(
            filt.shape[1], tail[0].size * tail.dtype.itemsize)
        pre, tail = _pre_activation_after(
            x, filt, (ins.get("Bias") or [None])[0], tail)
        handed["TailOut"] = [tail]
    else:
        pre = _pre_activation(x, filt, ins["Bias"][0])
    out = pre * jax.nn.sigmoid(pre) if act == "silu" else pre
    return dict({"Out": [out.astype(x.dtype)]}, **handed)


@register_grad_kernel("causal_conv1d")
def causal_conv1d_grad(ctx, ins, attrs):
    """X@GRAD (dOut's type), Filter@GRAD and Bias@GRAD (float32 sums,
    the parameters' type) from X, Filter, Bias and dOut alone."""
    if ins.get("Tail"):
        raise NotImplementedError(
            "causal_conv1d: the form that carries its tail is forward "
            "only (a cached step's)")
    x, filt, bias = ins["X"][0], ins["Filter"][0], ins["Bias"][0]
    d_out = ins["OG@Out"][0]
    width = filt.shape[1]
    d_pre = d_out.astype(F32)
    if _conv_attrs(attrs) == "silu":
        pre = _pre_activation(x, filt, bias)
        sig = jax.nn.sigmoid(pre)
        d_pre = d_pre * sig * (1.0 + pre * (1.0 - sig))
    xf, w = x.astype(F32), filt.astype(F32)
    seq = x.shape[1]
    # position t reaches the outputs t .. t + width - 1
    ahead = jnp.pad(d_pre, ((0, 0), (0, width - 1), (0, 0)))
    dx = sum(ahead[:, width - 1 - j:width - 1 - j + seq] * w[:, j]
             for j in range(width))
    d_filter = jnp.stack(
        [jnp.sum(d_pre * _shifted(xf, width, j), axis=(0, 1))
         for j in range(width)], axis=1)
    return {"X@GRAD": [dx.astype(d_out.dtype)],
            "Filter@GRAD": [d_filter.astype(filt.dtype)],
            "Bias@GRAD": [jnp.sum(d_pre, axis=(0, 1)).astype(bias.dtype)]}


# -- selective_scan --------------------------------------------------------------

def _selective_infer_shape(block, op_desc):
    """`Out` is `X`'s and `StateOut` is `State`'s: stated, so that a
    block axis the Program leaves open (-1) stays open."""
    for src, dst in (("X", "Out"), ("State", "StateOut")):
        same_meta_infer_shape(src, dst)(block, op_desc)


def selective_update(state, x, dt, b, c, neg_a, d_skip):
    """One position of Mamba-1's recurrence, all float32: `state`
    [batch, N, D], `x` and `dt` (after the softplus) [batch, D], `b`
    and `c` [batch, N], `neg_a` = -exp(ALog) transposed [N, D], `d_skip`
    [D] -> (the state after the position, y [batch, D])."""
    state = jnp.exp(dt[:, None, :] * neg_a) * state \
        + (dt * x)[:, None, :] * b[:, :, None]
    return state, jnp.sum(state * c[:, :, None], axis=1) + d_skip * x


@register_op("selective_scan", stop_gradient_op=True,
             infer_shape=_selective_infer_shape)
def selective_scan(ctx, ins, attrs):
    """X, Dt (before the softplus) [batch, T, D], DtBias [D], ALog
    [D, N], B and C [batch, T, N], D [D], State [batch, N, D] -> Out, X's
    shape and type, and StateOut, State's (the module's docstring).
    Under the op's own scope, `selective_scan`, whatever implements it:
    plain `jax.numpy`, a step one fusion over the state."""
    x, dt_raw, state = ins["X"][0], ins["Dt"][0], ins["State"][0]
    b, c = ins["B"][0].astype(F32), ins["C"][0].astype(F32)
    a_log = ins["ALog"][0].astype(F32)
    rows, length, width = x.shape
    entries = a_log.shape[1]
    if state.shape != (rows, entries, width) \
            or a_log.shape[0] != width or b.shape != (rows, length, entries):
        raise ValueError(
            "selective_scan: X %s, ALog %s, B %s and State %s are not "
            "[batch, T, D], [D, N], [batch, T, N] and [batch, N, D]"
            % (x.shape, a_log.shape, b.shape, state.shape))
    telemetry.on_selective_scan_lowering(
        "step" if length == 1 else "block", state.dtype,
        state[0].size * state.dtype.itemsize)
    neg_a = -jnp.exp(a_log).T
    d_skip = ins["D"][0].astype(F32)
    dt = jax.nn.softplus(dt_raw.astype(F32) + ins["DtBias"][0].astype(F32))
    xf = x.astype(F32)
    if length == 1:
        new, y = selective_update(state.astype(F32), xf[:, 0], dt[:, 0],
                                  b[:, 0], c[:, 0], neg_a, d_skip)
        y = y[:, None]
    else:
        def one(s, at):
            return selective_update(s, *at, neg_a, d_skip)

        new, y = jax.lax.scan(one, state.astype(F32), tuple(
            jnp.moveaxis(t, 1, 0) for t in (xf, dt, b, c)))
        y = jnp.moveaxis(y, 0, 1)
    return {"Out": [y.astype(x.dtype)],
            "StateOut": [new.astype(state.dtype)]}


@register_grad_kernel("selective_scan")
def selective_scan_grad(ctx, ins, attrs):
    raise NotImplementedError(
        "selective_scan is forward only: generation needs no gradient, "
        "and training the layer wants a parallel scan over the "
        "positions, which this op does not have")
