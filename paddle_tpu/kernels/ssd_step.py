"""One position of Mamba-2's recurrence (ops/ssm.py `ssd_update`) as a
Pallas TPU kernel that reads and writes every row's state once, in
place, with copies of its own.

    step(state[B, N, W], x[B, W], dt[B, H], a[B, H], b[B, N], c[B, N],
         d_skip[H]) -> (y [B, W], state')          W = H * head_dim

    S' = exp(a) S + B (dt x)^T;    y = S'^T C + D x

all float32, over the state as a decoder carries it: state entries by
head lanes (128 sublanes by 8192 lanes a row at granite-4.0-h-small's
128 heads of 64: 4.19 MB, no lane of it padding).  A step moves that
both ways and nothing else of its size, so it is bound by those bytes.
Plain `jax.numpy` is one fused pass the compiler makes at 81% of a
v5e's HBM peak, and a Pallas kernel whose blocks travelled through the
compiler's own pipeline (`pl.BlockSpec`, two buffers) read the same 81%
whatever the block (PERF.md section 6, PR 71): the pipeline's ceiling,
which kernels/gdn_step.py found and broke in PR 64.  This kernel's
arithmetic is `ssd_update`'s to the letter; what differs is **how the
blocks travel**, and that is gdn_step.py's schedule (its docstring has
the yardsticks: a stream of reads gets 94% of the peak, a stream of
writes in one-chunk copies 82%, both at once 5% less than one after the
other):

- the state stays in HBM (`memory_space=pl.ANY`) and
  `input_output_aliases` hands its buffer back as the new state: a
  decoder's scan carries it without a copy;
- `_BUFFERS` = 3 blocks of VMEM, `rows a grid step` rows of state each
  (one coming in, one worked where it lies, one going out); **reads and
  writes take turns**: block i + 1 comes in beside the first half of
  block i's work and block i - 1 goes out beside the second.  The way in
  is `_READ_CUTS` slices of the sublanes, and the way out starts when
  all but the last have landed (the switch's latency hides under that
  slice);
- **a block goes out as copies that each span all the block's rows**,
  a slice of the sublanes by a slice of the lanes of every row
  (`_WRITE_CUTS` = 16 x 4: 64 KB a row and copy, the rows 4.19 MB
  apart; whole rows of sublanes a copy read 86% where these read 89);
- the grid is sequential ("arbitrary"): a step's copies are its
  neighbours' blocks.

Everything is on the vector unit.  The decay and `dt * x` arrive as rows
[1, W] (the head's scalar spread along the lanes by the caller: 2 x 32
KB a row beside 4.19 MB of state), B and C as the rows [1, N] they are
(zero-padded to whole lane blocks) and are made columns on a diagonal as
gdn_step.py makes k and q: the row broadcast down the sublanes, the
diagonal kept, summed over the lanes.  The update is the column B times
the row `dt x`; y is the sum over the sublanes of `S' * C`.  The body's
text is `_WORK_LANES` lanes of one row, which lanes and which row two
loops' indices.  `D x` is left to the caller's fusion with what reads y
(one row operand fewer).

**The rows beside the state lie as XLA has them**: [B, W] and [B, N], a
row of the batch a sublane of a tile of 8 (`_group`: a grid step sees
the tile its rows lie in, four grid steps of 2 rows the same one), and
the kernel reads a tile whole and keeps its row by a select over the
sublanes (Mosaic takes no sublane offset that is not a tile's); y is
written the same way, the tile read, the row replaced.  As [B, 1, W],
a block a row, Mosaic asks for tiles of one sublane (`T(1,128)`), XLA
carries that layout back through everything that makes x: four
relayouts of 2 MB a layer, and the convolution in front in tiles of 4
sublanes at 0.40 ms a step of `granite-decode-ep4` for 0.10 (PERF.md
section 6, PR 72).

**It states no cost.**  With `cost_estimate=pl.CostEstimate(...)` (the
state's bytes both ways, the rows beside it, 6 operations an element)
XLA's scheduler laid more of the step's weight prefetches over the call,
which is bound by the same HBM: in `granite-decode-ep4` a call took 0.833
ms with the estimate and 0.802 without (0.743 alone), and
`decode_tok_per_s` read 3,336 against 3,358 (PERF.md section 6, PR 72;
ROADMAP Speed 21: a stated cost moves a neighbour either way).

Which shapes it takes (`choose_block`): a float32 state, `d_state` whole
sublane tiles, `heads * head_dim` whole lane blocks, a row's state
within `_STEP_BYTES`; the block is the most rows that divide the
batch's and keep within it.  The op asks, and keeps `ssd_update`
otherwise.

Lowered for the TPU this is a Mosaic kernel named `ssd_step_r<rows>_b<
rows a grid step>` (a trace's `device_ops` row says which block ran).
Lowered for any other platform the caller's plain step runs in its place
(`step`'s `plain`, as kernels/gdn_step.py's; `interpret=True` runs the
kernel's body, copies and all, under the Pallas interpreter: tests).
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_SUBLANES = 8
# the bytes of state a grid step takes in (and a step later gives out):
# 4 rows of granite-4.0-h-small's 4.19 MB.  At [64, 128, 8192] a call
# takes 0.789 ms in blocks of 1 row, 0.743 of 2 and 0.736 of 4, and
# granite-decode-ep4 reads +0.37% with 4 over 2 (two pairs; PERF.md
# section 6, PR 72, scripts/ssd_step_bench.py)
_STEP_BYTES = 16 << 20
# a block comes in, one is worked where it lies, one goes out
_BUFFERS = 3
# what a call may hold in VMEM beside its blocks of state: the rows'
# and the output's buffers and the compiler's own scratch
_VMEM_BESIDE = 4 << 20
# A block comes in as `_READ_CUTS` copies, a slice of every row's
# sublanes each, and the way out starts when all but one have landed.
_READ_CUTS = 8
# A block goes out as copies of a slice of the sublanes by a slice of the
# lanes over all the block's rows each, `_WRITE_CUTS` = (slices of the
# sublanes, slices of the lanes): a chunk a row, megabytes apart.
_WRITE_CUTS = (16, 4)
# the lanes of a row one iteration of the work's loop takes: its text
_WORK_LANES = 512


def choose_block(rows, entries, width, dtype):
    """The rows of state a grid step holds, or None where the kernel
    does not take the shape: a float32 state [rows, entries, width],
    `entries` whole sublane tiles and `width` whole lane blocks, a row
    within `_STEP_BYTES`; the most rows that divide `rows` and keep the
    block within it (three of them and `_VMEM_BESIDE`: `vmem_limit`)."""
    row_bytes = entries * width * 4
    if jnp.dtype(dtype) != jnp.float32 or entries % _SUBLANES \
            or width % _LANES or not 0 < row_bytes <= _STEP_BYTES:
        return None
    return max(n for n in range(1, rows + 1)
               if rows % n == 0 and n * row_bytes <= _STEP_BYTES)


def vmem_limit(held, entries, width):
    """The VMEM a call may take: `_BUFFERS` blocks of `held` rows of
    state and `_VMEM_BESIDE`."""
    return _BUFFERS * held * entries * width * 4 + _VMEM_BESIDE


def _cuts(size, most, tile=_SUBLANES):
    """`size` sublanes (lanes: `tile` = `_LANES`) in the most slices,
    `most` at most, that are whole tiles each."""
    n = max(n for n in range(1, most + 1) if size % (tile * n) == 0)
    return [pl.ds(i * (size // n), size // n) for i in range(n)]


def _group(rows, held):
    """The rows of the operands beside the state a grid step sees: they
    lie as XLA has them, a row of the batch a sublane, so a block of
    them is whole tiles of 8 rows (or all of them), whatever the rows
    of state a grid step takes."""
    if held % _SUBLANES == 0:
        return held
    return _SUBLANES if rows % _SUBLANES == 0 and _SUBLANES % held == 0 \
        else rows


def _kernel(decay_ref, dtx_ref, b_ref, c_ref, s_hbm, y_ref, so_hbm,
            buf, came, went, *, held, cuts):
    entries, width = buf.shape[-2:]
    at, last = pl.program_id(0), pl.num_programs(0) - 1
    group = decay_ref.shape[0]
    # a row of the operands is one sublane of a tile of `tile` rows: the
    # tile is read whole, the row kept (Mosaic takes no sublane offset
    # that is not a tile's)
    tile = _SUBLANES if group % _SUBLANES == 0 else group
    sublane = lax.broadcasted_iota(jnp.int32, (tile, _LANES), 0)
    reads = _cuts(entries, cuts[0])
    writes = [(sublanes, lanes) for sublanes in _cuts(entries, cuts[1][0])
              for lanes in _cuts(width, cuts[1][1], _LANES)]

    def placed(t):
        """Block t where it lies in HBM, and its buffer."""
        slot = t % _BUFFERS
        return pl.ds(t * held, held), buf.at[slot], slot

    def fetch(t):
        """Starts block t's copies in."""
        where, to, slot = placed(t)
        for cut in reads:
            pltpu.make_async_copy(s_hbm.at[where, cut], to.at[:, cut],
                                  came.at[slot]).start()

    def fetched(t, sublanes):
        """Waits for as much of block t's way in as these sublanes of
        its rows are (one wait: the semaphore counts bytes)."""
        _, to, slot = placed(t)
        part = to.at[:, sublanes]
        pltpu.make_async_copy(part, part, came.at[slot]).wait()

    def give(t):
        """Starts block t's copies out, all the block's rows each."""
        where, out, slot = placed(t)
        for cut in writes:
            pltpu.make_async_copy(out.at[(slice(None),) + cut],
                                  so_hbm.at[(where,) + cut],
                                  went.at[slot]).start()

    def given(t):
        """Waits for all of block t's way out."""
        _, out, slot = placed(t)
        pltpu.make_async_copy(out, out, went.at[slot]).wait()

    s_ref = placed(at)[1]
    # B and C arrive in whole lane blocks (`_operands`)
    diagonal = \
        lax.broadcasted_iota(jnp.int32, (entries, b_ref.shape[-1]), 0) \
        == lax.broadcasted_iota(jnp.int32, (entries, b_ref.shape[-1]), 1)

    def column(row):
        """[1, size] -> [size, 1]: the row down the sublanes, the
        diagonal kept, summed over the lanes."""
        return jnp.sum(jnp.where(diagonal, row, 0.0), axis=1, keepdims=True)

    lanes = next(n for n in (_WORK_LANES, 2 * _LANES, _LANES)
                 if width % n == 0)
    spans = width // lanes
    # Reads and writes take turns at the HBM: block at + 1 comes in
    # beside the first half of this block's work, block at - 1 goes out
    # beside the second.
    half = held * spans // 2

    def turn():
        if reads[-1].start:
            @pl.when(at < last)
            def _():
                fetched(at + 1, pl.ds(0, reads[-1].start))

        @pl.when(at > 0)
        def _():
            give(at - 1)

    def row(r, _):
        # where row r of this block lies in the operands' block
        lies = (at * held) % group + r
        tiled = pl.ds(pl.multiple_of(lies // tile * tile, tile), tile)
        mine = sublane == lies % tile

        def of(ref, lanes):
            """This row of an operand, [1, lanes' extent]."""
            return jnp.sum(jnp.where(mine, ref[tiled, lanes], 0.0), axis=0,
                           keepdims=True)

        def columns(ref):
            """This row of B or C a column, along a lane block."""
            row = jnp.concatenate(
                [of(ref, pl.ds(k * _LANES, _LANES))
                 for k in range(ref.shape[-1] // _LANES)], axis=1)
            return jnp.broadcast_to(column(row), (entries, _LANES))

        b_col, c_col = columns(b_ref), columns(c_ref)

        def span(i, _):
            pl.when(r * spans + i == half)(turn)
            for j in range(lanes // _LANES):
                one = pl.ds(pl.multiple_of(i * lanes + j * _LANES, _LANES),
                            _LANES)
                s = s_ref[r, :, one] * of(decay_ref, one) \
                    + b_col * of(dtx_ref, one)
                y = jnp.sum(s * c_col, axis=0, keepdims=True)
                y_ref[tiled, one] = jnp.where(mine, y, y_ref[tiled, one])
                s_ref[r, :, one] = s

        lax.fori_loop(0, spans, span, None)

    @pl.when(at == 0)
    def _():
        fetch(0)
        fetched(0, pl.ds(0, entries))

    @pl.when(at < last)
    def _():
        fetch(at + 1)

    lax.fori_loop(0, held, row, None)

    @pl.when(at < last)
    def _():
        fetched(at + 1, reads[-1])

    @pl.when(at > 0)
    def _():
        given(at - 1)

    @pl.when(at == last)
    def _():
        give(at)
        given(at)


def _call(decay, dtx, b, c, state, *, block, interpret,
          cuts=(_READ_CUTS, _WRITE_CUTS)):
    """decay, dtx [B, W]; b, c [B, N in whole lane blocks]; state
    [B, N, W] -> (S'^T C [B, W], state')."""
    rows, entries, width = state.shape
    group = _group(rows, block)
    where = lambda i: (i * block // group, 0)
    beside = pl.BlockSpec((group, width), where)
    column = pl.BlockSpec((group, b.shape[-1]), where)
    # the state stays in HBM: the kernel's own copies move it
    where_it_lies = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_kernel, held=block, cuts=cuts),
        grid=(rows // block,),
        in_specs=[beside, beside, column, column, where_it_lies],
        out_specs=[beside, where_it_lies],
        out_shape=[jax.ShapeDtypeStruct(decay.shape, jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        scratch_shapes=[
            pltpu.VMEM((_BUFFERS, block, entries, width), jnp.float32),
            pltpu.SemaphoreType.DMA((_BUFFERS,)),
            pltpu.SemaphoreType.DMA((_BUFFERS,))],
        # the state's buffer is the new state's
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            # a step's copies are the steps' before and after it
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_limit(block, entries, width)),
        interpret=interpret,
        name="ssd_step_r%d_b%d" % (rows, block),
    )(decay, dtx, b, c, state)


def _operands(x, dt, a, b, c):
    """The kernel's operands beside the state: the decay and `dt x` a
    row [1, W] each (a head's scalar spread along its lanes), B and C
    the rows they are in whole lane blocks (16 entries zero-padded to
    128: 512 bytes a row)."""
    dim = x.shape[-1] // dt.shape[-1]
    by_lane = lambda t: jnp.repeat(t, dim, axis=-1)
    short = -b.shape[-1] % _LANES
    if short:
        b, c = (jnp.pad(t, ((0, 0), (0, short))) for t in (b, c))
    return by_lane(jnp.exp(a)), by_lane(dt) * x, b, c


# Under `jax.jit`: the layers of a program that hold the same instance
# share one traced body and one lowered function.
@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _kernel_step(state, x, dt, a, b, c, d_skip, block, interpret):
    """`_call` on `_operands` and the state; `D x` beside it."""
    y, state = _call(*_operands(x, dt, a, b, c), state, block=block,
                     interpret=interpret)
    return y + jnp.repeat(d_skip, x.shape[-1] // dt.shape[-1]) * x, state


def step(state, x, dt, a, b, c, d_skip, plain, block=None, interpret=False):
    """(y [B, W] float32, the state after the position): the module's
    docstring, `ssd_update`'s arguments, all float32.  `plain(state, x,
    dt, a, b, c, d_skip)` is what every platform but the TPU lowers in
    the kernel's place (`ssd_update` itself); `block` (rows a grid step)
    is chosen from the shapes unless given, and `interpret` runs the
    kernel's body under the Pallas interpreter whatever the platform
    (tests, sweeps)."""
    rows, entries, width = state.shape
    heads = dt.shape[-1]
    block = block or choose_block(rows, entries, width, state.dtype)
    operands = (state, x, dt, a, b, c, d_skip)
    if not block or rows % block \
            or not choose_block(block, entries, width, state.dtype) \
            or x.shape != (rows, width) or width % heads \
            or dt.shape != (rows, heads) or a.shape != dt.shape \
            or b.shape != (rows, entries) or c.shape != b.shape \
            or d_skip.shape != (heads,) \
            or any(t.dtype != jnp.float32 for t in operands):
        raise ValueError(
            "ssd_step: x %s, dt %s, a %s, b %s, c %s, d %s over a state of "
            "%s %s in blocks of %s rows are no step the kernel takes"
            % (x.shape, dt.shape, a.shape, b.shape, c.shape, d_skip.shape,
               state.shape, state.dtype, block))

    kernel = functools.partial(_kernel_step, block=int(block),
                               interpret=bool(interpret))
    if interpret:
        return kernel(*operands)
    return lax.platform_dependent(*operands, tpu=kernel, default=plain)
