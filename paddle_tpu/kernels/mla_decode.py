"""One decode step of latent attention over the cache as it lies, or a
block of T consecutive steps at once (pallas TPU kernels).

    mla_decode(q[B, H, latent + rope], cache[B, P, latent + rope],
               position, sm_scale, latent[, sink=[H]]) -> [B, H, latent]
    mla_decode_block(q_lat[H, B * T, latent], q_rope[H, B * T, rope],
                     cache[B, P, latent + rope], position, sm_scale)
        -> [H, B * T, latent]

`q` is the absorbed query of `mla_cached_attention` (ops/attention.py:
q_nope W_uk^T beside the rotated q_rope), `cache` the latents `c | r` of
every slot *after* this step's slot is written, `position` an int32
scalar, the last live slot.  A row's result is

    softmax_t(sm_scale * q . cache[t]) over t <= position,
    times cache[t, :latent]

the weighted sum of latents; the values' up-projection stays with the
caller.  The op's plain path makes the scores of all P slots under a
mask, takes their softmax through HBM in float32 and multiplies the
probabilities with the whole cache, rope columns included.  Here the
grid is (B / rows, P / block_k) with the slot axis sequential: a step
folds one block of slots of `rows` rows into each row's running maximum,
sum and [H, latent] accumulator held in VMEM (the flash kernels' online
softmax), a block past `position` is neither fetched nor computed, and
the mask is applied in the one block `position` falls in, whose dead
slots' values are zeroed too, so that nothing a dead slot holds reaches
a sum.  A row's dead steps come *first* and its live blocks last (step
j folds block j - dead, and the index map names block 0 until then, so
the pipeline has nothing more to copy): the next rows' first block is
then fetched under the last live block's products.  With the live
blocks first that fetch fell into a dead step, where nothing hides it,
and a call cost the same 0.76 ms on the v5e whether one block a row was
live or two; dead steps first, 0.44 and 0.75 (PERF.md section 6, PR 39).
Several rows a step (four at the cell's shape: 0.32 and 0.60 ms) give
the scheduler independent chains of products and softmax to interleave.
Scores are [H, latent + rope] x [block_k, latent + rope]^T on the MXU in
the operands' type with float32 sums, the softmax is float32, the
probabilities are rounded to the operands' type (as the plain path
rounds them) for [H, block_k] x [block_k, latent]: the block's first
`latent` columns, a lane-aligned slice.

A block of T > 1 positions (`mla_decode_block`: a prompt's prefill).
The 128 heads of a row share its one stream of latents, as a key/value
head's group of query heads shares its keys in kernels/gqa_decode.py, so
T positions are H * T query rows over the same slots: a block reads a
row's live latents once for all of them and is bound by its products and
its softmax, not by bytes.  `position` is the slot the block's first
position writes, and position t attends slots 0 .. position + t.  What
costs beside the products is what the queries cross HBM as: H * 576
values a token, a hundred times its hidden state.  The heads' absorbing
product makes them a head after a head, [H, B * T, latent] (the heads
are the product's batch), so the kernel takes them as they come, the
rotated part beside them and not joined to them ([H, B * T, rope]: the
scores are the sum of two products, over the block's first `latent`
columns and over its last `rope`), and gives the weighted sums of
latents head-major too, which is how the values' product wants them: no
copy turns 0.5 GB of queries around, none joins them (on the v5e a
transposing copy and a concatenation were 3.8 of an op's 12 ms at 256
rows x 16 positions, PERF.md section 6, PR 53).  H * T query rows with a
[., latent] float32 accumulator do not fit VMEM whole past T of about 8
at 128 heads, so the grid's first axis runs over (row, tile of 16
positions, group of heads) and a grid step holds `heads` heads at 16
positions, at most 1024 query rows, row h * 16 + t of the step head h
at the tile's position t: 16 positions are a whole sublane tile of a
16-bit type, so the [heads, 16, .] block of the queries is the
[heads * 16, .] operand as it lies.  (A grid axis, not a loop of the op
over tiles: every tile shares one call, its blocks' copies overlap the
neighbour's products, and a trace shows one kernel an op.)  A tile that
starts at the block's position t0 attends slots up to `position + t0 +
15`, fetches and folds no block past that one, and masks, by each query
row's own bound `position + t0 + row % 16`, only the blocks that hold a
slot from `position + t0` on (at most two).  The groups of heads of a
tile fetch the row's blocks of slots again, a few hundred KB beside
their megabytes of queries.

Which shapes they take (`fits`): P a multiple of 128, `latent` a
multiple of 128 (the slice of the values and the output's lanes), and
either one query position a row or a multiple of 16.  The op asks and
falls back to its plain path; a
cache in a narrower type than the query's is read up by the caller
first.

A step over a chosen set (`Selected`, DeepSeek-V3.2's sparse attention;
since PR 70).  The op gathers the 2048 chosen rows of a row's cache into
[batch, top_k, latent + rope], the first `Live` of them live: that is a
cache whose last live slot is `Live - 1`, and the step form reads it as
it reads any other, `mla_decode(q, gathered, Live - 1, ...)`, the whole
set one block a row where that fits (`choose_blocks(whole=True)`): no
block of it is dead, and a fold is dearer than a grid step.  Before, two
plain products read the gathered set behind a transposing copy of all of
it (37.7 MB a layer at 16 rows, a second copy in the fast memory the
gather had written: 0.29 ms a step of `dsv32-turn-16k-ep16`), which was
what the compiler made of the default fill of the step's
`take_along_axis`; the op's gather clips now, and with the clip alone
the plain products read the gather's result as it lies too.  Measured
on that cell the two readers are on a par (10.27 ms a step through this
kernel, 10.25 through the plain products, 10.52 before: PERF.md section
6, PR 70): the kernel's own part is that no float32 score array is made
(`mla_scores` + `mla_values` 0.342 ms a step against 0.360).  What the
kernel does not do is fetch the chosen rows itself: PR 60 closed that by
measurement (ROADMAP Reach A8(a), Speed 3): the fetch is bound by the
count of its copy descriptors, 10-13 ns each whoever starts them, and a
kernel starts as many as the gather does.  The call states its cost
(`pl.CostEstimate`: both products over the slots it is given, an
exponential a score, the cache operand, the queries and the output
once): a custom call is opaque to the compiler, whose memory-space
assignment keeps every gathered copy in fast memory only where it knows
what its reader costs (without the estimate one of that cell's five
gathered copies a step lay in HBM in the compiled text).  A block of
T > 1 positions with a set each stays with the op's plain products.

A learned sink (`sink` float32 [heads]: one logit a head in the
softmax's denominator and none in the sum) is one more term of the
step's last fold, `l += exp(sink - m)` before `acc / l`; without one
the kernel traces as it did.  The block form takes none: a block of
positions with a sink keeps the op's plain path.

Lowered for the TPU these are Mosaic kernels named
`mla_decode_k<block_k>` and `mla_decode_k<block_k>_t<T>` (a trace tells
a prefill block's calls from a decode step's, as
`gqa_decode_k<block_k>_t<T>` does); lowered for the CPU the same kernels
run under the Pallas interpreter (tests), chosen by the platform of the
lowering as the flash kernels are.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128
# what one grid step may hold in VMEM (`_step_bytes`); under Mosaic's
# default scoped limit of 16 MiB, so no limit has to be asked for
_VMEM_BUDGET = 12 * 2 ** 20
# the blocks of slots and the rows a grid step the chooser tries.  512
# slots of 576 bfloat16 values are 0.59 MB, 0.7 us of the v5e's HBM and
# of its MXU alike, twice a grid step's fixed cost
_BLOCKS = (512, 256, 128)
_ROWS = (4, 2, 1)
# a block of positions: the positions a tile (a whole sublane tile of a
# 16-bit type, two of a 32-bit one) and the most query rows, heads times
# the tile's positions, a grid step holds
_TILE = 16
_QUERY_ROWS = 1024


def fits(q_positions, positions, latent):
    """Whether a kernel takes `q_positions` positions a row over these
    slots: see the module's docstring."""
    return ((q_positions == 1 or q_positions % _TILE == 0)
            and positions % _BLOCKS[-1] == 0 and latent % _LANES == 0)


def _pad(n, to):
    return -(-n // to) * to


def _step_bytes(rows, heads, bk, width, latent, itemsize):
    """VMEM bytes one grid step holds: of each of its rows the query
    [heads, width] and the block of slots [bk, width] (their minor
    dimension padded to whole lane tiles) and the output [heads,
    latent], each double-buffered by the pipeline, and the float32
    accumulator; of the row being folded the scores and probabilities
    [heads, bk] in float32, the probabilities and the masked block's
    values in the operands' type."""
    lanes = _pad(width, _LANES)
    tiles = 2 * itemsize * (heads * lanes + bk * lanes + heads * latent)
    scratch = 4 * heads * (latent + 2 * _LANES)
    fold = heads * bk * (4 + 4 + itemsize) + bk * latent * itemsize
    return rows * (tiles + scratch) + fold


def choose_blocks(batch, heads, positions, width, latent, itemsize,
                  whole=False):
    """(block_k, rows) from the shapes: the largest of 512, 256 and 128
    slots that tiles the cache, and of 4, 2 and 1 rows a step that tile
    the batch, that fit the VMEM budget together (slots before rows).
    A larger block is fewer grid steps a row and more slots past
    `position` multiplied in the one block it falls in.  ms a call on
    the v5e at 256 rows x 128 heads over a 1024-slot cache, the mean
    over positions 128..1023 (scripts/mla_decode_bench.py; PERF.md
    section 6, PR 39): (512, 4) 0.48, (512, 2) 0.50, (512, 1) 0.57,
    (256, 4) 0.56, (256, 1) 0.69, (128, 4) 0.77, (128, 1) 1.12; eight
    rows a step were 2-3% under four, not worth twice the VMEM.

    `whole`: the cache is a step's gathered set, live from end to end
    but in a session's first `positions` steps, so no block is there to
    be skipped and the whole set is tried first as one block a row:
    every further fold of a row rescales its [heads, latent] float32
    accumulator and reduces its scores' maximum and sum again.  ms a
    call on the v5e over 2048 gathered slots, all live (my chip runs,
    PR 70): 16 rows x 128 heads (2048, 1) 0.062, (1024, 2) 0.071,
    (512, 4) 0.082; 8 rows x 64 heads 0.019, 0.023, 0.029."""
    for bk in ((positions,) if whole else ()) + _BLOCKS:
        for rows in _ROWS:
            if positions % bk == 0 and batch % rows == 0 and _step_bytes(
                    rows, heads, bk, width, latent,
                    itemsize) <= _VMEM_BUDGET:
                return bk, rows
    raise ValueError(
        "mla_decode: no block among %s tiles %d positions of %d heads "
        "and %d values a slot within %d bytes of VMEM"
        % (_BLOCKS[::-1], positions, heads, width, _VMEM_BUDGET))


def _fold_in(s, values, m_scr, l_scr, acc_scr, at):
    """The online softmax's step: a block's masked scores `s` [queries,
    bk] and values [bk, latent] folded into the running maximum, sum and
    accumulator at index `at` of their scratch."""
    m_prev = m_scr[at]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_scr[at] = alpha * l_scr[at] + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[at] = alpha * acc_scr[at] + lax.dot_general(
        p.astype(values.dtype), values, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[at] = m_new


def _kernel(pos_ref, q_ref, c_ref, *refs, sm_scale, bk, rows, latent):
    """One grid step: block `j - dead` of each of the step's rows folded
    into the row's running maximum `m`, sum `l` [heads, 1] and
    accumulator [heads, latent]; nothing in a row's first `dead` steps.
    `refs`: a sink's logits [heads, 1] where the call has one, then the
    output and the three scratch arrays."""
    sink_ref = refs[0] if len(refs) == 5 else None
    o_ref, m_scr, l_scr, acc_scr = refs[-4:]
    j = pl.program_id(1)
    pos = pos_ref[0]
    last = pos // bk
    k = j - (pl.num_programs(1) - 1 - last)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def fold(r, masked):
        block = c_ref[r]
        values = block[:, :latent]
        s = lax.dot_general(
            q_ref[r], block, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if masked:
            first = k * bk
            live = first + lax.broadcasted_iota(jnp.int32, (1, bk), 1) <= pos
            s = jnp.where(live, s, NEG_INF)
            live = first + lax.broadcasted_iota(jnp.int32, (bk, 1), 0) <= pos
            values = jnp.where(live, values, jnp.zeros_like(values))
        _fold_in(s, values, m_scr, l_scr, acc_scr, r)

    @pl.when((k >= 0) & (k < last))
    def _whole():
        for r in range(rows):
            fold(r, masked=False)

    @pl.when(k == last)
    def _crossed():
        for r in range(rows):
            fold(r, masked=True)
        total = l_scr[...]
        if sink_ref is not None:
            # one more term in the denominator and none in the sum
            total = total + jnp.exp(sink_ref[...][None] - m_scr[...])
        o_ref[...] = (acc_scr[...] / total).astype(o_ref.dtype)


def _call(q, cache, position, *sink, sm_scale, latent, bk, rows, interpret):
    batch, heads, width = q.shape
    slots_held = cache.shape[1]
    steps = slots_held // bk

    def slots(b, j, pos):
        # a row's dead steps name its first block, which the step before
        # them has fetched: no block past the position is ever copied
        return b, jnp.maximum(j - (steps - 1 - pos[0] // bk), 0), 0

    def row(b, j, pos):
        return b, 0, 0

    return pl.pallas_call(
        functools.partial(_kernel, sm_scale=sm_scale, bk=bk, rows=rows,
                          latent=latent),
        # what the call reads and computes over the slots it is given,
        # for the compiler: a custom call's cost is opaque to it, and
        # where the cache operand is a step's gathered set its
        # memory-space assignment keeps every such copy in fast memory
        # only where it knows what the one reader costs
        # (kernels/gqa_decode.py `_chosen_call`; PERF.md section 6, PRs
        # 66 and 70)
        cost_estimate=pl.CostEstimate(
            flops=2 * batch * heads * slots_held * (width + latent),
            transcendentals=batch * heads * slots_held,
            bytes_accessed=(cache.size + q.size + batch * heads * latent)
            * q.dtype.itemsize),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(batch // rows, steps),
            in_specs=[pl.BlockSpec((rows, heads, width), row),
                      pl.BlockSpec((rows, bk, width), slots)]
            + [pl.BlockSpec((heads, 1), lambda b, j, pos: (0, 0))
               for _ in sink],
            out_specs=pl.BlockSpec((rows, heads, latent), row),
            scratch_shapes=[pltpu.VMEM((rows, heads, 1), jnp.float32),
                            pltpu.VMEM((rows, heads, 1), jnp.float32),
                            pltpu.VMEM((rows, heads, latent), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((batch, heads, latent), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        # the trace shows which block ran; readers match the prefix
        name="mla_decode_k%d" % bk,
    )(position, q, cache, *sink)


def mla_decode(q, cache, position, sm_scale, latent, blocks=None, sink=None):
    """The weighted sum of latents of one decode step, [batch, heads,
    latent] in q's type: see the module's docstring.  `blocks`
    (block_k, rows) are chosen from the shapes unless given (tests,
    sweeps); `sink` float32 [heads] is a learned sink's logits."""
    batch, heads, width = q.shape
    if cache.shape[0] != batch or cache.shape[2] != width \
            or cache.dtype != q.dtype or not fits(1, cache.shape[1], latent):
        raise ValueError(
            "mla_decode: a query %s %s over a cache %s %s with %d latent "
            "values a slot is no step the kernel takes"
            % (q.shape, q.dtype, cache.shape, cache.dtype, latent))
    bk, rows = blocks or choose_blocks(batch, heads, cache.shape[1], width,
                                       latent, q.dtype.itemsize)
    call = functools.partial(_call, sm_scale=float(sm_scale), latent=latent,
                             bk=bk, rows=rows)
    sink = () if sink is None else (
        jnp.reshape(sink, (heads, 1)).astype(jnp.float32),)
    return lax.platform_dependent(
        q, cache, jnp.reshape(position, (1,)).astype(jnp.int32), *sink,
        tpu=functools.partial(call, interpret=False),
        cpu=functools.partial(call, interpret=True))


# -- a block of positions ------------------------------------------------------

def _block_bytes(queries, bk, rope, latent, itemsize):
    """VMEM bytes one grid step of the block form holds: `queries` query
    rows' latent and rotated parts and their output, the block of slots
    (minor dimensions padded to whole lane tiles), each double-buffered
    by the pipeline, the float32 accumulator with the running maximum
    and sum, and of the fold the scores and probabilities [queries, bk]
    in float32, the probabilities and the masked block's values in the
    operands' type."""
    width = _pad(latent + rope, _LANES)
    tiles = 2 * itemsize * (queries * (2 * latent + _pad(rope, _LANES))
                            + bk * width)
    scratch = 4 * queries * (latent + 2 * _LANES)
    fold = queries * bk * (4 + 4 + itemsize) + bk * latent * itemsize
    return tiles + scratch + fold


def choose_group(heads, positions, rope, latent, itemsize):
    """(block_k, heads a grid step) of the block form from the shapes:
    the most heads that divide `heads` with at most `_QUERY_ROWS` query
    rows a step, then the largest block of slots that tiles the cache
    and fits the VMEM budget with them.  At 128 heads over 512 + 64
    values in bfloat16: 64 heads, 1024 query rows, over blocks of 256
    slots.  ms a call on the v5e at 256 rows x 16 positions from slot 0,
    112, 500 and 1008 on (scripts/mla_decode_bench.py; PERF.md section
    6, PR 53): (256, 64) 3.01, 3.01, 6.95, 8.39; (128, 64) 3.10, 3.10,
    11.19, 16.77; (512, 32) 4.69, 4.69, 7.98, 7.89; (256, 32) 3.37, 3.37,
    7.75, 9.36; (128, 32) 3.81, 3.81, 11.73, 17.45: a grid step of 1024
    query rows is bound by its own products and softmax (5.9 us), not by
    the 2.25 MB it moves, and a prompt's prefill walks the first
    positions, where the smaller block multiplies fewer dead slots."""
    group = max(g for g in range(1, heads + 1)
                if heads % g == 0 and (g * _TILE <= _QUERY_ROWS or g == 1))
    for bk in _BLOCKS:
        if positions % bk == 0 and _block_bytes(
                group * _TILE, bk, rope, latent, itemsize) <= _VMEM_BUDGET:
            return bk, group
    raise ValueError(
        "mla_decode_block: no block among %s tiles %d positions under "
        "%d query rows of %d + %d values within %d bytes of VMEM"
        % (_BLOCKS[::-1], positions, group * _TILE, latent, rope,
           _VMEM_BUDGET))


def _tile_bounds(pos, step, *, tiles, groups):
    """(first, top): the last slot the first and the last position of
    the tile of grid step `step` (along the first axis: a row's tiles
    one after the other, a tile's groups of heads likewise) attend."""
    first = pos + lax.rem(lax.div(step, groups), tiles) * _TILE
    return first, first + (_TILE - 1)


def _block_kernel(pos_ref, ql_ref, qr_ref, c_ref, o_ref, m_scr, l_scr,
                  acc_scr, *, sm_scale, bk, latent, tiles, groups):
    """One grid step: block `j - dead` of the row's slots folded into
    the running maximum `m`, sum `l` [queries, 1] and accumulator
    [queries, latent] of a group of heads at a tile's 16 positions;
    nothing in the tile's first `dead` steps."""
    j = pl.program_id(1)
    first, top = _tile_bounds(pos_ref[0], pl.program_id(0), tiles=tiles,
                              groups=groups)
    last = top // bk
    k = j - (pl.num_programs(1) - 1 - last)
    queries = acc_scr.shape[0]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def fold(masked):
        block = c_ref[0]
        values = block[:, :latent]
        # query row h * 16 + t is head h at the tile's position t
        s = (lax.dot_general(
            ql_ref[...].reshape(queries, latent), values,
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            + lax.dot_general(
                qr_ref[...].reshape(queries, -1), block[:, latent:],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)) * sm_scale
        if masked:
            start = k * bk
            limit = first + lax.rem(lax.broadcasted_iota(
                jnp.int32, (queries, 1), 0), _TILE)
            live = start + lax.broadcasted_iota(jnp.int32, (1, bk), 1) \
                <= limit
            s = jnp.where(live, s, NEG_INF)
            live = start + lax.broadcasted_iota(jnp.int32, (bk, 1), 0) <= top
            values = jnp.where(live, values, jnp.zeros_like(values))
        _fold_in(s, values, m_scr, l_scr, acc_scr, Ellipsis)

    # the first block some position of the tile does not attend whole
    # (block 0 holds slot 0, which every position attends: no row's
    # first fold is of nothing); a tile is no more than a block of
    # slots, so at most one masked block comes before the last
    first_masked = first // bk

    @pl.when((k >= 0) & (k < first_masked))
    def _whole():
        fold(masked=False)

    @pl.when((k >= first_masked) & (k < last))
    def _crossed_before_the_last():
        fold(masked=True)

    @pl.when(k == last)
    def _crossed():
        fold(masked=True)
        o_ref[...] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype) \
            .reshape(o_ref.shape)


def _block_call(q_lat, q_rope, cache, position, *, sm_scale, bk, group,
                interpret):
    heads, tokens, latent = q_lat.shape
    batch = cache.shape[0]
    steps = cache.shape[1] // bk
    tiles, groups = tokens // batch // _TILE, heads // group
    queries = group * _TILE

    def slots(i, j, pos):
        # a tile's dead steps name the row's first block, which the step
        # before them has fetched: no block past the tile's last
        # position is ever copied
        top = _tile_bounds(pos[0], i, tiles=tiles, groups=groups)[1]
        return (lax.div(i, tiles * groups),
                jnp.maximum(j - (steps - 1 - top // bk), 0), 0)

    def tile(i, j, pos):
        # a row's tiles lie one after the other along the tokens
        return lax.rem(i, groups), lax.div(i, groups), 0

    return pl.pallas_call(
        functools.partial(_block_kernel, sm_scale=sm_scale, bk=bk,
                          latent=latent, tiles=tiles, groups=groups),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(batch * tiles * groups, steps),
            in_specs=[pl.BlockSpec((group, _TILE, latent), tile),
                      pl.BlockSpec((group, _TILE, q_rope.shape[2]), tile),
                      pl.BlockSpec((1, bk, cache.shape[2]), slots)],
            out_specs=pl.BlockSpec((group, _TILE, latent), tile),
            scratch_shapes=[pltpu.VMEM((queries, 1), jnp.float32),
                            pltpu.VMEM((queries, 1), jnp.float32),
                            pltpu.VMEM((queries, latent), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(q_lat.shape, q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        # the trace shows which block and how many positions ran;
        # readers match the prefix
        name="mla_decode_k%d_t%d" % (bk, tiles * _TILE),
    )(position, q_lat, q_rope, cache)


def mla_decode_block(q_lat, q_rope, cache, position, sm_scale, blocks=None):
    """The weighted sums of latents of a block of T consecutive decode
    steps of every row, [heads, batch * T, latent] in q_lat's type: see
    the module's docstring.  `blocks` (block_k, heads a grid step) are
    chosen from the shapes unless given (tests, sweeps)."""
    heads, tokens, latent = q_lat.shape
    batch, positions, width = cache.shape
    rope = width - latent
    if q_rope.shape != (heads, tokens, rope) or q_rope.dtype != q_lat.dtype \
            or cache.dtype != q_lat.dtype or tokens % (batch * _TILE) \
            or not fits(tokens // batch, positions, latent):
        raise ValueError(
            "mla_decode_block: queries %s and %s %s over a cache %s %s are "
            "no block the kernel takes"
            % (q_lat.shape, q_rope.shape, q_lat.dtype, cache.shape,
               cache.dtype))
    bk, group = blocks or choose_group(
        heads, positions, rope, latent, q_lat.dtype.itemsize)
    if heads % group or positions % bk:
        raise ValueError(
            "mla_decode_block: blocks %s do not tile %d heads over %d "
            "slots" % ((bk, group), heads, positions))
    call = functools.partial(_block_call, sm_scale=float(sm_scale), bk=bk,
                             group=group)
    return lax.platform_dependent(
        q_lat, q_rope, cache, jnp.reshape(position, (1,)).astype(jnp.int32),
        tpu=functools.partial(call, interpret=False),
        cpu=functools.partial(call, interpret=True))
