"""One decode step of grouped-query attention over a key/value cache as
it lies, or a block of T consecutive steps at once (a pallas TPU
kernel).

    gqa_decode(q[B, KV, G * T, D], k_cache[B, KV, S, D],
               v_cache[B, KV, S, D], last, sm_scale, positions=T)
        -> [B, KV, G * T, D]

`q` holds, for every key/value head, the group of `G` query heads that
read it (query head j reads key/value head j // G: the group is an
index, no key or value is repeated in memory), each at the block's T
positions: row g * T + t is head g of the group at the block's position
t (T = 1: one row a head).  The caches hold every slot *after* the
block's slots are written; `last` is an int32 scalar, the last slot the
block's first position attends: position t attends slots 0 .. last + t.
A row's result, per key/value head, is

    softmax_s(sm_scale * q . k_cache[s]) over s <= last + t, times
    v_cache[s]

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
key/value head into the running maximum, sum [G * T, 1] and accumulator
[G * T, D] of its group's queries, resident in VMEM (the flash kernels'
online softmax); a block past `last + T - 1` is neither fetched nor
computed, and the mask is applied in the blocks that hold a slot from
`last` to `last + T - 1` alone (one at T = 1, at most two where T is no
more than a block), where a slot no query of the block attends has its
values zeroed too, so that nothing a dead slot holds reaches a sum.  A
head's dead steps come first and its live blocks last (step j folds
block j - dead, and the index maps name block 0 until then), as
kernels/mla_decode.py has them and for its reason: the next head's
first block is fetched under the last live block's products.  Scores
are [G * T, D] x [block_k, D]^T in the operands' type with float32 sums,
the softmax is float32, the probabilities are rounded to the operands'
type for [G * T, block_k] x [block_k, D]: T changes the rows of the two
products and nothing else.  At T = 1 the step is bound by the bytes of
the live keys and values: a block of 2048 slots of 128 bfloat16 values
is 0.5 MB of each cache, 1.3 us of the v5e's HBM, four times a grid
step's fixed cost.  A block of T = 128 positions reads the same bytes
once for all of them and is bound by its products and its softmax; its
float32 scores, [G * T, block_k], are what fills VMEM, so its block of
slots is smaller (`choose_block`: 1024 at 1024 rows).

Which shapes it takes (`fits`): heads 128 wide (the lanes), S a multiple
of 128, and the G * T rows of a key/value head small enough that their
scores over the smallest block of slots fit VMEM beside the operands
(`_vmem_bytes`).  The op asks and falls back to its plain path; a cache
in a narrower type than the query's is read up by the caller first.

Lowered for the TPU this is a Mosaic kernel named `gqa_decode_k<block_k>`
over a whole-extent cache, `gqa_decode_k<block_k>_t<T>` where T > 1 (a
trace tells a prefill block's calls from a decode step's) and
`gqa_decode_w<window>` over a ring; lowered for the CPU the same kernel
runs under the Pallas interpreter (tests), chosen by the platform of the
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
# the blocks of slots the chooser tries, largest first
_BLOCKS = (2048, 1024, 512, 256, 128)


# what a grid step may hold in VMEM: under the 16 MiB a kernel gets on a
# v5e unasked (the smallest of the chips')
_VMEM_BYTES = 12 << 20


def _vmem_bytes(rows, bk, itemsize):
    """The bytes a grid step of `rows` resident queries over a block of
    `bk` slots holds in VMEM: queries and output (double-buffered), the
    running maximum and sum (a column each, padded to the lanes) and the
    accumulator in float32; the block's keys and values
    (double-buffered); the scores in float32, the probabilities in
    their place, and the probabilities rounded."""
    resident = rows * _LANES * (4 * itemsize + 3 * 4)
    blocks = 4 * bk * _LANES * itemsize
    scores = rows * bk * (4 + itemsize)
    return resident + blocks + scores


def choose_block(slots, rows=8, itemsize=2):
    """The largest of the blocks that tiles `slots` and whose step fits
    VMEM with `rows` resident queries a key/value head (a group's heads
    times the block's positions), or 0 where none does: fewer grid steps
    a head, and more slots past the last live one fetched in the block
    it falls in (at most a block's worth, 6% of a 32k session at 2048).
    A ring of 128 is one block; a group of 8 heads at 128 positions
    takes 1024 slots a step (on the chip, ms a call of 8 rows x 8 heads
    over 31,872 live slots: 2048 8.3, 1024 7.8, 512 15.2, 256 30.0: a
    step costs 3.7 us whatever it folds up to 1024 slots)."""
    for bk in _BLOCKS:
        if slots % bk == 0 and _vmem_bytes(rows, bk, itemsize) <= _VMEM_BYTES:
            return bk
    return 0


def fits(rows, slots, head_dim, itemsize=2):
    """Whether the kernel takes `rows` queries a key/value head (a
    group's heads times the block's positions) over these caches: see
    the module's docstring."""
    return head_dim == _LANES and choose_block(slots, rows, itemsize) > 0


def _top(last, positions):
    """The last live slot, which the block's last position writes."""
    return last if positions == 1 else last + (positions - 1)


def _kernel(last_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            sm_scale, bk, positions):
    """One grid step: block `j - dead` of one key/value head folded into
    its queries' running maximum `m`, sum `l` [G * T, 1] and accumulator
    [G * T, D]; nothing in the head's first `dead` steps."""
    j = pl.program_id(2)
    last = last_ref[0]
    top = _top(last, positions)
    last_block = top // bk
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
            limit = last
            if positions > 1:   # row g * T + t is position t
                limit = last + lax.rem(lax.broadcasted_iota(
                    jnp.int32, (s.shape[0], 1), 0), positions)
            live = first + lax.broadcasted_iota(jnp.int32, (1, bk), 1) <= limit
            s = jnp.where(live, s, NEG_INF)
            live = first + lax.broadcasted_iota(jnp.int32, (bk, 1), 0) <= top
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

    # the first block some position of the block does not attend whole
    # (block 0 holds slot 0, which every position attends: no row's first
    # fold is of nothing)
    first_masked = last_block if positions == 1 else last // bk

    @pl.when((k >= 0) & (k < first_masked))
    def _whole():
        fold(masked=False)

    if positions > 1:
        @pl.when((k >= first_masked) & (k < last_block))
        def _crossed_before_the_last():
            fold(masked=True)

    @pl.when(k == last_block)
    def _crossed():
        fold(masked=True)
        o_ref[0, 0] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


def _call(q, k_cache, v_cache, last, *, sm_scale, bk, positions, name,
          interpret):
    batch, kv_heads, rows, dim = q.shape
    steps = k_cache.shape[2] // bk

    def slots(b, h, j, last):
        # a head's dead steps name its first block, which the step
        # before them has fetched: no block past the last live slot is
        # ever copied
        top = _top(last[0], positions)
        return b, h, jnp.maximum(j - (steps - 1 - top // bk), 0), 0

    def head(b, h, j, last):
        return b, h, 0, 0

    return pl.pallas_call(
        functools.partial(_kernel, sm_scale=sm_scale, bk=bk,
                          positions=positions),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(batch, kv_heads, steps),
            in_specs=[pl.BlockSpec((1, 1, rows, dim), head),
                      pl.BlockSpec((1, 1, bk, dim), slots),
                      pl.BlockSpec((1, 1, bk, dim), slots)],
            out_specs=pl.BlockSpec((1, 1, rows, dim), head),
            scratch_shapes=[pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, dim), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        # the trace shows which cache, block and positions ran; readers
        # match the prefix
        name=name,
    )(last, q, k_cache, v_cache)


def gqa_decode(q, k_cache, v_cache, last, sm_scale, window=0, block_k=None,
               positions=1):
    """The attended values of one decode step, or of a block of
    `positions` consecutive ones, [batch, kv_heads, group * positions,
    128] in q's type: see the module's docstring.  `window` names the
    kernel of a ring (`gqa_decode_w<window>`, one block, one position);
    `block_k` is chosen from the shapes unless given (tests, sweeps)."""
    slots = k_cache.shape[2]
    if q.ndim != 4 or k_cache.shape != v_cache.shape \
            or k_cache.shape[:2] != q.shape[:2] \
            or k_cache.shape[3] != q.shape[3] \
            or k_cache.dtype != q.dtype or v_cache.dtype != q.dtype \
            or positions < 1 or q.shape[2] % positions \
            or not fits(q.shape[2], slots, q.shape[3], q.dtype.itemsize) \
            or (window and (window != slots or positions != 1)):
        raise ValueError(
            "gqa_decode: queries %s %s at %d positions over caches %s %s "
            "and %s %s (window %d) are no step the kernel takes"
            % (q.shape, q.dtype, positions, k_cache.shape, k_cache.dtype,
               v_cache.shape, v_cache.dtype, window))
    bk = block_k or choose_block(slots, q.shape[2], q.dtype.itemsize)
    name = "gqa_decode_w%d" % window if window else "gqa_decode_k%d" % bk
    if positions > 1:
        name += "_t%d" % positions
    call = functools.partial(_call, sm_scale=float(sm_scale), bk=bk,
                             positions=positions, name=name)
    return lax.platform_dependent(
        q, k_cache, v_cache, jnp.reshape(last, (1,)).astype(jnp.int32),
        tpu=functools.partial(call, interpret=False),
        cpu=functools.partial(call, interpret=True))
