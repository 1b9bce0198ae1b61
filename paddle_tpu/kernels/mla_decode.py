"""One decode step of latent attention over the cache as it lies (a
pallas TPU kernel).

    mla_decode(q[B, H, latent + rope], cache[B, P, latent + rope],
               position, sm_scale, latent) -> [B, H, latent]

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

Which shapes it takes (`fits`): one query position a row, P a multiple
of 128, `latent` a multiple of 128 (the slice of the values and the
output's lanes).  The op asks and falls back to its plain path; a
cache in a narrower type than the query's is read up by the caller
first.  The chosen-set path of the op (`Selected`, DeepSeek-V3.2's
sparse attention) does not come here: its two contractions run over
2048 *gathered* entries, all live, at 63% of their roofline, and what
costs there is the gather, which a kernel that reads the chosen slots
where they lie would take away (ROADMAP Reach A8), not this one.

Lowered for the TPU this is a Mosaic kernel named
`mla_decode_k<block_k>`; lowered for the CPU the same kernel runs under
the Pallas interpreter (tests), chosen by the platform of the lowering
as the flash kernels are.
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


def fits(q_positions, positions, latent):
    """Whether the kernel takes a step of these shapes: see the module's
    docstring."""
    return (q_positions == 1 and positions % _BLOCKS[-1] == 0
            and latent % _LANES == 0)


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


def choose_blocks(batch, heads, positions, width, latent, itemsize):
    """(block_k, rows) from the shapes: the largest of 512, 256 and 128
    slots that tiles the cache, and of 4, 2 and 1 rows a step that tile
    the batch, that fit the VMEM budget together (slots before rows).
    A larger block is fewer grid steps a row and more slots past
    `position` multiplied in the one block it falls in.  ms a call on
    the v5e at 256 rows x 128 heads over a 1024-slot cache, the mean
    over positions 128..1023 (scripts/mla_decode_bench.py; PERF.md
    section 6, PR 39): (512, 4) 0.48, (512, 2) 0.50, (512, 1) 0.57,
    (256, 4) 0.56, (256, 1) 0.69, (128, 4) 0.77, (128, 1) 1.12; eight
    rows a step were 2-3% under four, not worth twice the VMEM."""
    for bk in _BLOCKS:
        for rows in _ROWS:
            if positions % bk == 0 and batch % rows == 0 and _step_bytes(
                    rows, heads, bk, width, latent,
                    itemsize) <= _VMEM_BUDGET:
                return bk, rows
    raise ValueError(
        "mla_decode: no block among %s tiles %d positions of %d heads "
        "and %d values a slot within %d bytes of VMEM"
        % (_BLOCKS[::-1], positions, heads, width, _VMEM_BUDGET))


def _kernel(pos_ref, q_ref, c_ref, o_ref, m_scr, l_scr, acc_scr, *,
            sm_scale, bk, rows, latent):
    """One grid step: block `j - dead` of each of the step's rows folded
    into the row's running maximum `m`, sum `l` [heads, 1] and
    accumulator [heads, latent]; nothing in a row's first `dead` steps."""
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
        m_prev = m_scr[r]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[r] = alpha * l_scr[r] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[r] = alpha * acc_scr[r] + lax.dot_general(
            p.astype(values.dtype), values, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[r] = m_new

    @pl.when((k >= 0) & (k < last))
    def _whole():
        for r in range(rows):
            fold(r, masked=False)

    @pl.when(k == last)
    def _crossed():
        for r in range(rows):
            fold(r, masked=True)
        o_ref[...] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


def _call(q, cache, position, *, sm_scale, latent, bk, rows, interpret):
    batch, heads, width = q.shape
    steps = cache.shape[1] // bk

    def slots(b, j, pos):
        # a row's dead steps name its first block, which the step before
        # them has fetched: no block past the position is ever copied
        return b, jnp.maximum(j - (steps - 1 - pos[0] // bk), 0), 0

    def row(b, j, pos):
        return b, 0, 0

    return pl.pallas_call(
        functools.partial(_kernel, sm_scale=sm_scale, bk=bk, rows=rows,
                          latent=latent),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(batch // rows, steps),
            in_specs=[pl.BlockSpec((rows, heads, width), row),
                      pl.BlockSpec((rows, bk, width), slots)],
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
    )(position, q, cache)


def mla_decode(q, cache, position, sm_scale, latent, blocks=None):
    """The weighted sum of latents of one decode step, [batch, heads,
    latent] in q's type: see the module's docstring.  `blocks`
    (block_k, rows) are chosen from the shapes unless given (tests,
    sweeps)."""
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
    return lax.platform_dependent(
        q, cache, jnp.reshape(position, (1,)).astype(jnp.int32),
        tpu=functools.partial(call, interpret=False),
        cpu=functools.partial(call, interpret=True))
