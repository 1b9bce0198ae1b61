"""One decode step of grouped-query attention over a key/value cache as
it lies (a pallas TPU kernel).

    gqa_decode(q[B, KV, G, D], k_cache[B, KV, S, D], v_cache[B, KV, S, D],
               last, sm_scale) -> [B, KV, G, D]

`q` holds, for every key/value head, the group of `G` query heads that
read it (query head j reads key/value head j // G: the group is an
index, no key or value is repeated in memory); the caches hold every
slot *after* this step's slot is written; `last` is an int32 scalar, the
last live slot: slots 0 .. last attend.  A row's result, per key/value
head, is

    softmax_s(sm_scale * q . k_cache[s]) over s <= last, times v_cache[s]

Two shapes of cache come here (`cached_attention`, ops/attention.py).  A
full layer's holds the whole extent, `last` is the position the step
writes, and the kernel walks the live slots alone.  A window layer's is
a ring of `window` slots written at position mod window: it is one
block, `last` = min(position, window - 1), and once the ring has
wrapped every slot of it is live (a softmax does not care in which
order the ring holds its positions).

The op's plain path makes float32 scores of all S slots under a mask,
takes their softmax through HBM and multiplies the probabilities with
the whole value cache: at 32,768 slots and 64 heads that reads the dead
slots of every layer every step.  Here the grid is (B, KV, S / block_k)
with the slot axis sequential: a step folds one block of slots of one
key/value head into the running maximum, sum [G, 1] and accumulator [G,
D] of its group of queries, held in VMEM (the flash kernels' online
softmax); a block past `last` is neither fetched nor computed, and the
mask is applied in the one block `last` falls in, whose dead slots'
values are zeroed too, so that nothing a dead slot holds reaches a sum.
A head's dead steps come first and its live blocks last (step j folds
block j - dead, and the index maps name block 0 until then), as
kernels/mla_decode.py has them and for its reason: the next head's
first block is fetched under the last live block's products.  Scores
are [G, D] x [block_k, D]^T in the operands' type with float32 sums, the
softmax is float32, the probabilities are rounded to the operands' type
for [G, block_k] x [block_k, D].  The step is bound by the bytes of the
live keys and values: a block of 2048 slots of 128 bfloat16 values is
0.5 MB of each cache, 1.3 us of the v5e's HBM, four times a grid step's
fixed cost.

Which shapes it takes (`fits`): one query position a row, heads 128
wide (the lanes), S a multiple of 128.  The op asks and falls back to
its plain path; a cache in a narrower type than the query's is read up
by the caller first.

Lowered for the TPU this is a Mosaic kernel named `gqa_decode_k<block_k>`
over a whole-extent cache and `gqa_decode_w<window>` over a ring;
lowered for the CPU the same kernel runs under the Pallas interpreter
(tests), chosen by the platform of the lowering as the flash kernels
are.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128
# the blocks of slots the chooser tries, largest first
_BLOCKS = (2048, 1024, 512, 256, 128)


def fits(q_positions, slots, head_dim):
    """Whether the kernel takes a step of these shapes: see the module's
    docstring."""
    return (q_positions == 1 and head_dim == _LANES
            and slots % _BLOCKS[-1] == 0)


def choose_block(slots):
    """The largest of the blocks that tiles `slots`: fewer grid steps a
    head, and more slots past `last` fetched in the one block it falls
    in (at most a block's worth, 6% of a 32k session at 2048).  A ring of
    128 is one block."""
    for bk in _BLOCKS:
        if slots % bk == 0:
            return bk
    raise ValueError("gqa_decode: no block among %s tiles %d slots"
                     % (_BLOCKS[::-1], slots))


def _kernel(last_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            sm_scale, bk):
    """One grid step: block `j - dead` of one key/value head folded into
    its group's running maximum `m`, sum `l` [G, 1] and accumulator [G,
    D]; nothing in the head's first `dead` steps."""
    j = pl.program_id(2)
    last = last_ref[0]
    last_block = last // bk
    k = j - (pl.num_programs(2) - 1 - last_block)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def fold(masked):
        keys, values = k_ref[0, 0], v_ref[0, 0]
        s = lax.dot_general(
            q_ref[0, 0], keys, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if masked:
            first = k * bk
            live = first + lax.broadcasted_iota(jnp.int32, (1, bk), 1) <= last
            s = jnp.where(live, s, NEG_INF)
            live = first + lax.broadcasted_iota(jnp.int32, (bk, 1), 0) <= last
            values = jnp.where(live, values, jnp.zeros_like(values))
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = alpha * acc_scr[...] + lax.dot_general(
            p.astype(values.dtype), values, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when((k >= 0) & (k < last_block))
    def _whole():
        fold(masked=False)

    @pl.when(k == last_block)
    def _crossed():
        fold(masked=True)
        o_ref[0, 0] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


def _call(q, k_cache, v_cache, last, *, sm_scale, bk, name, interpret):
    batch, kv_heads, group, dim = q.shape
    steps = k_cache.shape[2] // bk

    def slots(b, h, j, last):
        # a head's dead steps name its first block, which the step
        # before them has fetched: no block past `last` is ever copied
        return b, h, jnp.maximum(j - (steps - 1 - last[0] // bk), 0), 0

    def head(b, h, j, last):
        return b, h, 0, 0

    return pl.pallas_call(
        functools.partial(_kernel, sm_scale=sm_scale, bk=bk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(batch, kv_heads, steps),
            in_specs=[pl.BlockSpec((1, 1, group, dim), head),
                      pl.BlockSpec((1, 1, bk, dim), slots),
                      pl.BlockSpec((1, 1, bk, dim), slots)],
            out_specs=pl.BlockSpec((1, 1, group, dim), head),
            scratch_shapes=[pltpu.VMEM((group, 1), jnp.float32),
                            pltpu.VMEM((group, 1), jnp.float32),
                            pltpu.VMEM((group, dim), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        # the trace shows which cache and block ran; readers match the
        # prefix
        name=name,
    )(last, q, k_cache, v_cache)


def gqa_decode(q, k_cache, v_cache, last, sm_scale, window=0, block_k=None):
    """The attended values of one decode step, [batch, kv_heads, group,
    128] in q's type: see the module's docstring.  `window` names the
    kernel of a ring (`gqa_decode_w<window>`, one block); `block_k` is
    chosen from the extent unless given (tests, sweeps)."""
    slots = k_cache.shape[2]
    if q.ndim != 4 or k_cache.shape != v_cache.shape \
            or k_cache.shape[:2] != q.shape[:2] \
            or k_cache.shape[3] != q.shape[3] \
            or k_cache.dtype != q.dtype or v_cache.dtype != q.dtype \
            or not fits(1, slots, q.shape[3]) \
            or (window and window != slots):
        raise ValueError(
            "gqa_decode: queries %s %s over caches %s %s and %s %s "
            "(window %d) are no step the kernel takes"
            % (q.shape, q.dtype, k_cache.shape, k_cache.dtype,
               v_cache.shape, v_cache.dtype, window))
    bk = block_k or choose_block(slots)
    call = functools.partial(
        _call, sm_scale=float(sm_scale), bk=bk,
        name="gqa_decode_w%d" % window if window
        else "gqa_decode_k%d" % bk)
    return lax.platform_dependent(
        q, k_cache, v_cache, jnp.reshape(last, (1,)).astype(jnp.int32),
        tpu=functools.partial(call, interpret=False),
        cpu=functools.partial(call, interpret=True))
