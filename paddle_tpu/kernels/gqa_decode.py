"""One decode step of grouped-query attention over a key/value cache as
it lies, or a block of T consecutive steps at once (pallas TPU
kernels).

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

128-wide heads, one block no step's worth (Olmo-Hybrid's full layers:
30 key/value heads with one query each over an extent of 512 slots, 128
rows).  The grid above, a head a step, is right where a head's block is
a step's worth of bytes: every grouped caller walks blocks of 2048 slots,
0.5 MB of each cache.  One head's 512 slots are 128 KB, 0.32 us of the
HBM for both caches, and a grid step costs 0.3 us on top of what it
moves (on the chip, [128, 30, 512, 128] bfloat16, us a grid step by the
heads it takes: 1 0.63, 2 0.98, 3 1.25, 5 1.95, 6 2.20, 10 3.49; the
bytes alone 0.32 a head): 3,840 steps a layer took 2.41 ms a call, 1.96
times the whole extents' bytes' time.  So there a grid step takes
several key/value heads of a row, and then several rows of the batch
(`choose_step`): blocks `[rows, heads, block_k, D]` of each cache,
`[rows, heads, G * T, D]` of the queries and the output, scratch with
the same two leading axes, and the body folds the step's (row, head)
pairs one after another with the same `_fold` (traced once, unrolled
when lowered).  Ten heads a step, 1.25 MB of each cache, is 384 steps a
layer and 1.34 ms a call whatever the position, 92% of the HBM's peak on
the bytes fetched; more heads or rows a step move nothing (15 heads
1.34, 2 rows x 10 1.34).  The block stays the whole extent: blocks of
256 or 128 slots skip the dead ones but reach 61% of the peak on what
they fetch (ms a call at positions 128 / 320 / 510: 512 slots 1.34 /
1.34 / 1.34; 256 slots, 8 rows x 5 heads, 1.06 / 2.05 / 2.05; 128 slots,
2 rows x 30 heads, 1.06 / 1.57 / 2.07), so `choose_block` is as it was.
The one-row products stay on the MXU: at 1.09 times its bytes' time the
call has nothing left for the vector unit to win.  `choose_step` answers
(1, 1), the grid, blocks and name every caller had before, where a
head's block is over `_WIDE_HEAD_BYTES` of each cache or its queries are
more than a sublane tile (a block of positions is bound by its products
and its softmax), and never shares so far that a call has fewer than
`_WIDE_MIN_STEPS` grid steps.  The rings are small blocks of the same
walk and share a step by the same rule (ms a call, a head a step / as
chosen: exaone's [8, 8, 128, 128] under groups of 8, 32 KB a head,
0.035 / 0.013-0.014 at a row's 8 heads; phi4flash's [16, 10, 512, 128]
under groups of 4, 0.101-0.103 / 0.060-0.062 at a row's 10, wrapped or
not).  The sweep: `scripts/gqa_decode_bench.py wide`, PERF.md section 5
(PR 68).

64-wide heads (GPT-2's: 16 heads, no grouping, one query a key/value
head).  A `[B, KV, S, 64]` array with its rows in the sublanes pads
every 64-wide row to the 128 lanes: twice the bytes, fetched whatever
reads them.  Unasked, the TPU's compiler holds such an array the other
way, slots-minor (the layout of `[B, KV, 64, S]`): no lane is padding.
So at this width the kernels take the caches *with their last two axes
swapped*, which is no copy of anything where the cache lies that way
(the caller's `jnp.swapaxes` is a bitcast, and a decoder's scan then
carries the caches as the call received them).  `_narrow_kernel` walks
blocks `[rows, heads, 64, block]`: a head's 512 slots of bfloat16 are 64
KB, no step's worth of bytes, so as many of a row's heads as divide
them, up to 16, and then rows of the batch share a grid step, up to a
megabyte of each cache (`choose_step`).  The scores are [1, 64] x
[64, block] on the MXU; the values are weighted on the vector unit (one
query a head is no product for the MXU: it would load each value tile
as weights for one row of results) in float32, probabilities and all,
and the weighted sum's reduction over the lanes is taken once, with the
last block.  Slots-minor, the compiler's own `dynamic_update_slice` of
one slot is a write of one lane of every (head, value) row, 0.17 to
0.33 ms a pair of 100 MB caches; `write_step` is that update as a
kernel that rewrites the 128 slots around the position in place, 0.04
ms alone (0.08 in gpt2m-decode's step).  (It is a kernel of its own, not a part of the walk: a cache that
is both an operand and an aliased result of the walk's call loses the
overlap of its blocks' copies with the folds, 0.22 ms a call.)  Where
the time goes, and the sweep these sizes came from:
`scripts/gqa_decode_bench.py`, PERF.md section 5.

A chosen set (`cached_attention` with `Selected`: 2048 of 65,536 slots
a query, one set for every key/value head).

    gqa_decode_chosen(q[B, KV, G, D], k_chosen[B, top_k, KV, D],
                      v_chosen[B, top_k, KV, D], live, sm_scale)
        -> [B, KV, G, D]        (live: an int32 scalar, or [B] a count a row)

The chosen slots are fetched by the op's gather, not by this file: a
fetch of scattered slots is bound by its copies' count, about 12 ns a
copy descriptor on a v5e whether the compiler's gather starts them
(10-13 ns a row of 1 KB) or a Mosaic kernel (`pltpu.make_async_copy`:
12.2 ns a copy of one to four 512-byte rows, no queue beside it, 5.6 ns
a loop step more; `scripts/gqa_decode_bench.py chosen`, PERF.md section
6, PR 60), not by their bytes, so a kernel that fetched the slots where
they lie would start as many copies as the gather and run no faster (a
prototype read 0.85-0.89 ms a layer at keye-turn-64k-ep8's shape beside
the 0.46 of gather and kernel).  What this entry takes away is the
turn between the two: the compiler carries such caches heads-minor
through a decoder's scan (a slot's KV x D values together, 1 KB: one
copy a slot), so a gather of whole slots, `[B, top_k, KV, D]`, is what
it writes anyway, and a kernel that wants the heads apart, `[B, KV,
top_k, D]`, costs a transposing pass over both copies a layer.  Here
the copies are read as they lie: a grid step is `chunk` entries of one
row of the batch, every head of them, `[chunk * KV, D]` with row e * KV
+ h head h of entry e.  In bfloat16 a 32-bit word of a sublane row holds
two heads of one entry, so the block is folded two heads at a time:
every (KV / 2)-th word row (a strided load) is `[chunk * 2, D]`, column
2 e + i head i of the pair, under the pair's 2 G queries, each attending
its own head's columns; the fold is the one above (`_fold`).  In float32
a word is a head and a fold takes one.  Entry i >= `live` is masked and
its values zeroed; a chunk past the last live entry is neither fetched
nor folded.  `live` is one count for every row (the rows of a decode
step move in lockstep) or a count a row, `[B]`: a block of positions
comes here a position a row, B = batch * P, and position t of a session
that has not filled its `top_k` yet has one live entry more than
position t - 1.  It takes (`choose_chunk`) 128-wide heads, operands of 16 or
32 bits in q's type, a count of key/value heads that fills the words
(even in bfloat16, or one), and a `top_k` that a chunk of 128 to 2048
entries tiles.  The call states its cost (`pl.CostEstimate`: the copies'
bytes, the two products' operations): what a custom call costs is
opaque to the compiler otherwise, and its memory-space assignment then
places the gathered copies in fast memory by chance (PERF.md section 6,
PR 66).

The block-causal mask (`gqa_decode`'s `diffusion` B > 0; generation by
diffusion over blocks, `cached_attention`'s `diffusion_block`).  The T
positions are T / B whole blocks of B counted from the block's first,
and position t attends slots 0 .. last + B (t // B + 1) - 1: to the end
of its own block, the later positions of it among them.  One line of the
fold differs, the limit a row's scores are masked by; the last live slot,
the blocks fetched and the blocks folded whole are what they were (a
pass, T = B, masks nothing but the slots past the block; SDAR's pass of
4 positions under groups of 8 is 32 rows a key/value head over one block
of 1024 slots, the whole extent of the cell's cache, whatever the
position).  The kernel's name says it, `_b<B>` after `_t<T>`, and with
`diffusion` 0 every call is, argument for argument, what it was.

Which shapes it takes (`fits`): S a multiple of 128; heads a multiple of
128 wide (the lanes: one lane block a head, or two at 256) with G * T rows a key/value head small enough that their scores
over the smallest block of slots fit VMEM beside the operands
(`_vmem_bytes`), or heads 64 wide with one row a key/value head (T = 1,
no grouping, no ring).  The op asks and falls back to its plain path; a
cache in a narrower type than the query's is read up by the caller
first at 128 wide, and keeps the plain path at 64.

Lowered for the TPU these are Mosaic kernels named
`gqa_decode_k<block_k>` over a whole-extent cache,
`gqa_decode_k<block_k>_t<T>` where T > 1 (a trace tells a prefill
block's calls from a decode step's; `_b<B>` after it under the
block-causal mask; `_d<head_dim>` after either where a
head is wider than the lanes), `gqa_decode_w<window>` over a ring,
each with `_h<heads>` after it where a grid step takes several
key/value heads (`_r<rows>` after that where rows share it too:
`gqa_decode_k512_h10`), at 64 wide always
`gqa_decode_k<block_k>_h<heads>` (`_r<rows>`) and `gqa_write_r<rows>`,
over a chosen set
`gqa_decode_sel<top_k>_c<chunk>`; lowered for the CPU
the same kernels run under the Pallas interpreter (tests), chosen by the
platform of the lowering as the flash kernels are.  Each entry is under
`jax.jit`: the layers of a program that hold the same instance share
one traced body and one lowered function.
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
# the entries of a chosen set a grid step folds, largest first
_CHUNKS = (2048, 1024, 512, 256, 128)
# the narrower head the kernel takes, its block of slots, and the bytes
# of each cache a grid step of it moves (section "64-wide heads")
_NARROW = 64
_NARROW_BLOCKS = (512, 256, 128)
_NARROW_STEP_BYTES = 1 << 20
# the 128-wide walk: the bytes of a head's block of each cache over which
# a head is a grid step by itself, what a step that heads and rows share
# moves of each cache, and the grid steps a call keeps
_WIDE_HEAD_BYTES = 1 << 18
_WIDE_STEP_BYTES = 3 << 19
_WIDE_MIN_STEPS = 8
_SUBLANES = 8


# what a grid step may hold in VMEM: under the 16 MiB a kernel gets on a
# v5e unasked (the smallest of the chips')
_VMEM_BYTES = 12 << 20


def _vmem_bytes(rows, bk, itemsize, dim=_LANES):
    """The bytes a grid step of `rows` resident queries of `dim` values
    over a block of `bk` slots holds in VMEM: queries and output
    (double-buffered) and the accumulator in float32, the running
    maximum and sum (a column each, padded to the lanes); the block's
    keys and values (double-buffered); the scores in float32, the
    probabilities in their place, and the probabilities rounded."""
    resident = rows * (dim * (4 * itemsize + 4) + _LANES * 2 * 4)
    blocks = 4 * bk * dim * itemsize
    scores = rows * bk * (4 + itemsize)
    return resident + blocks + scores


def choose_block(slots, rows=8, itemsize=2, head_dim=_LANES):
    """The largest of the blocks that tiles `slots` and whose step fits
    VMEM with `rows` resident queries a key/value head (a group's heads
    times the block's positions), or 0 where none does: fewer grid steps
    a head, and more slots past the last live one fetched in the block
    it falls in (at most a block's worth, 6% of a 32k session at 2048).
    A ring of 128 is one block; a group of 8 heads at 128 positions
    takes 1024 slots a step (on the chip, ms a call of 8 rows x 8 heads
    over 31,872 live slots: 2048 8.3, 1024 7.8, 512 15.2, 256 30.0: a
    step costs 3.7 us whatever it folds up to 1024 slots).  64-wide
    heads, one query a head: the largest of `_NARROW_BLOCKS` that tiles
    the extent (`choose_step` says how many heads and rows share the
    step).  A head of several lane blocks (Qwen3-Next's 256) is the
    128-wide kernel with wider blocks, and its resident queries and
    accumulator weigh twice as much: in bfloat16 a group of 8 at 128
    positions still takes 1024 slots a step, in float32 512."""
    if head_dim == _NARROW:
        return next((bk for bk in _NARROW_BLOCKS if slots % bk == 0), 0) \
            if rows == 1 else 0
    if head_dim % _LANES:
        return 0
    for bk in _BLOCKS:
        if slots % bk == 0 \
                and _vmem_bytes(rows, bk, itemsize, head_dim) <= _VMEM_BYTES:
            return bk
    return 0


def _heads_a_word(kv_heads, itemsize):
    """The heads of one chosen slot that a 32-bit word of a sublane row
    holds where the slot's heads lie side by side: two of bfloat16, one
    of float32; one head lies as a cache does."""
    return 1 if kv_heads == 1 else 4 // itemsize


def choose_chunk(top_k, kv_heads, group, itemsize=2, head_dim=_LANES):
    """The entries of a chosen set a grid step of `gqa_decode_chosen`
    folds: the largest of `_CHUNKS` that tiles `top_k` and whose block of
    every head's keys and values fits VMEM beside the `group` queries a
    head, or 0 where the kernel takes no such set: heads of another
    width than the lanes, operands that are no 16- or 32-bit floats, or
    key/value heads that do not fill the 32-bit words of a sublane row
    (an odd count of bfloat16 heads; one head is a set that lies as a
    cache does)."""
    if head_dim != _LANES or itemsize not in (2, 4) \
            or kv_heads % _heads_a_word(kv_heads, itemsize):
        return 0
    for chunk in _CHUNKS:
        if top_k % chunk == 0 and _vmem_bytes(
                kv_heads * group, chunk * kv_heads, itemsize) <= _VMEM_BYTES:
            return chunk
    return 0


def choose_step(batch, kv_heads, block_k, itemsize=2, rows=1,
                head_dim=_NARROW):
    """(rows of the batch, key/value heads) a grid step takes over blocks
    of `block_k` slots: a grid step costs 0.3 us and more on top of what
    it moves, so where one head's block is no step's worth of bytes,
    heads of a row, and then rows of the batch, share the step.

    The 64-wide kernel: as many of a row's heads as divide them, up to
    16, then as many rows as divide the batch, while the step's block of
    each cache stays within `_NARROW_STEP_BYTES`.  One head's 512 slots
    are 64 KB.  On
    the chip (48 rows x 16 heads x 1024 slots, ms the walk alone at
    positions 767 / 1022: scripts/gqa_decode_bench.py, PERF.md section
    5): 16 heads x 512 slots 0.29 / 0.29, 2 rows x 16 x 256 0.31 / 0.38,
    16 x 256 0.32 / 0.38, 16 x 128 0.38 / 0.46.  What a shorter block
    skips does not pay for its steps, so `choose_block` takes the
    largest that tiles the extent.

    The 128-wide walk under `rows` queries a key/value head (the module
    docstring's section "128-wide heads, one block no step's worth"):
    (1, 1), the grid every caller had before PR 68, where a head's block
    is over `_WIDE_HEAD_BYTES` of each cache (a step's worth: 2048 slots
    are 0.5 MB) or its queries are more than a sublane tile (a block of
    positions is products and a softmax, not bytes); else heads, then
    rows, while the step's block of each cache stays within
    `_WIDE_STEP_BYTES`, the step fits VMEM, and the call keeps
    `_WIDE_MIN_STEPS` grid steps (nothing hides the first step's
    fetch)."""
    head = block_k * head_dim * itemsize
    if head_dim == _NARROW:
        room = _NARROW_STEP_BYTES // head
        heads = _divisor(kv_heads, min(16, room))
        return _divisor(batch, room // heads), heads
    if rows > _SUBLANES or head > _WIDE_HEAD_BYTES:
        return 1, 1
    room = min(_WIDE_STEP_BYTES // head,
               _VMEM_BYTES // _vmem_bytes(rows, block_k, itemsize, head_dim),
               batch * kv_heads // _WIDE_MIN_STEPS)
    heads = _divisor(kv_heads, room)
    return _divisor(batch, room // heads), heads


def _divisor(n, most):
    """The largest divisor of `n` that is at most `most` (at least 1)."""
    return max(d for d in range(1, max(min(n, most), 1) + 1) if n % d == 0)


def fits(rows, slots, head_dim, itemsize=2):
    """Whether the kernel takes `rows` queries a key/value head (a
    group's heads times the block's positions) over these caches: see
    the module's docstring."""
    return choose_block(slots, rows, itemsize, head_dim) > 0


def _top(last, positions):
    """The last live slot, which the block's last position writes."""
    return last if positions == 1 else last + (positions - 1)


def _fold(q, keys, values, m_ref, l_ref, acc_ref, sm_scale, attended=None,
          held=None):
    """`keys` and `values` [entries, D] folded into the running maximum
    `m`, sum `l` [rows, 1] and accumulator [rows, D] of the queries `q`
    [rows, D]: the flash kernels' online softmax, scores in the
    operands' type with float32 sums, the probabilities rounded to the
    operands' type.  `attended` (bool, over [rows, entries]) says which
    scores count, `held` (bool, over [entries, D]) which values are not
    zeroed, so that nothing a dead entry holds reaches a sum; None:
    all."""
    s = lax.dot_general(q, keys, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * sm_scale
    if attended is not None:
        s = jnp.where(attended, s, NEG_INF)
    if held is not None:
        values = jnp.where(held, values, jnp.zeros_like(values))
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = alpha * acc_ref[...] + lax.dot_general(
        p.astype(values.dtype), values, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _kernel(last_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            sm_scale, bk, positions, diffusion=0):
    """One grid step: block `j - dead` of each of the step's rows and
    key/value heads (blocks [rows, heads, ..]; one of each but where a
    head's block is no step's worth, `choose_step`) folded into its
    queries' running maximum `m`, sum `l` [G * T, 1] and accumulator
    [G * T, D], one (row, head) pair after another; nothing in the first
    `dead` steps."""
    j = pl.program_id(2)
    last = last_ref[0]
    top = _top(last, positions)
    last_block = top // bk
    k = j - (pl.num_programs(2) - 1 - last_block)
    heads = k_ref.shape[1]
    pairs = k_ref.shape[0] * heads

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def fold(masked, write=False):
        attended = held = None
        if masked:
            first = k * bk
            limit = last
            if positions > 1:   # row g * T + t is position t
                at = lax.rem(lax.broadcasted_iota(
                    jnp.int32, (q_ref.shape[2], 1), 0), positions)
                if diffusion:   # block-causal: to the end of t's block
                    at = lax.div(at, diffusion) * diffusion \
                        + (diffusion - 1)
                limit = last + at
            attended = first + lax.broadcasted_iota(
                jnp.int32, (1, bk), 1) <= limit
            held = first + lax.broadcasted_iota(
                jnp.int32, (bk, 1), 0) <= top

        def pair(at, m_ref, l_ref, acc_ref):
            _fold(q_ref[at], k_ref[at], v_ref[at], m_ref, l_ref, acc_ref,
                  sm_scale, attended, held)
            if write:
                o_ref[at] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)

        if pairs == 1:      # the scratch is the pair's own
            return pair((0, 0), m_scr, l_scr, acc_scr)

        def body(i, _):
            # traced once, unrolled when lowered: the folds of a step's
            # pairs are independent, and interleave
            at = (lax.div(i, heads), lax.rem(i, heads))
            pair(at, m_scr.at[at], l_scr.at[at], acc_scr.at[at])

        lax.fori_loop(0, pairs, body, None, unroll=True)

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
        fold(masked=True, write=True)


def _call(q, k_cache, v_cache, last, *, sm_scale, bk, positions, step, name,
          interpret, **block_causal):
    batch, kv_heads, rows, dim = q.shape
    steps = k_cache.shape[2] // bk
    shared = step if step != (1, 1) else ()     # the scratch's leading axes

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
                          positions=positions, **block_causal),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(batch // step[0], kv_heads // step[1], steps),
            in_specs=[pl.BlockSpec(step + (rows, dim), head),
                      pl.BlockSpec(step + (bk, dim), slots),
                      pl.BlockSpec(step + (bk, dim), slots)],
            out_specs=pl.BlockSpec(step + (rows, dim), head),
            scratch_shapes=[pltpu.VMEM(shared + (rows, 1), jnp.float32),
                            pltpu.VMEM(shared + (rows, 1), jnp.float32),
                            pltpu.VMEM(shared + (rows, dim), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        # the trace shows which cache, block and positions ran; readers
        # match the prefix
        name=name,
    )(last, q, k_cache, v_cache)


def _narrow_kernel(last_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                   *, sm_scale, bk):
    """One grid step over caches handed in with the slots in the lanes,
    [rows, heads, D, block]: block `j - dead` of each of the step's rows
    and heads folded into its one query's running maximum, sum [1, 1]
    and accumulator.  The scores are a product on the MXU, [1, D] x
    [D, block]; the values are weighted on the vector unit, [D, block]
    times the probabilities along the lanes, and added up 128 lanes
    wide: their sum over the lanes is taken once, with the last
    block."""
    j = pl.program_id(2)
    last = last_ref[0]
    last_block = last // bk
    k = j - (pl.num_programs(2) - 1 - last_block)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def fold(at, masked):
        s = lax.dot_general(
            q_ref[at], k_ref[at], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        values = v_ref[at].astype(jnp.float32)
        if masked:
            live = k * bk + lax.broadcasted_iota(jnp.int32, (1, bk), 1) <= last
            s = jnp.where(live, s, NEG_INF)
            values = jnp.where(live, values, 0.0)
        m_prev = m_scr[at]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[at] = alpha * l_scr[at] + jnp.sum(p, axis=-1, keepdims=True)
        weighted = values * p
        acc_scr[at] = alpha * acc_scr[at] + sum(
            weighted[:, lane:lane + _LANES] for lane in range(0, bk, _LANES))
        m_scr[at] = m_new

    def fold_all(masked, then=None):
        # traced once, unrolled when lowered: the folds of a step's
        # heads are independent, and interleave
        rows, heads = k_ref.shape[:2]

        def body(i, _):
            at = (lax.div(i, heads), lax.rem(i, heads))
            fold(at, masked)
            if then is not None:
                then(at)

        lax.fori_loop(0, rows * heads, body, None, unroll=True)

    @pl.when((k >= 0) & (k < last_block))
    def _whole():
        fold_all(masked=False)

    @pl.when(k == last_block)
    def _crossed():
        # the accumulator's sum over the lanes is a column [D, 1]; the
        # output wants it a row: through the diagonal of a [D, D] tile
        diagonal = _diagonal((k_ref.shape[2],) * 2)

        def write_out(at):
            column = jnp.sum(acc_scr[at], axis=-1, keepdims=True) / l_scr[at]
            o_ref[at] = jnp.sum(jnp.where(diagonal, column, 0.0), axis=0,
                                keepdims=True).astype(o_ref.dtype)

        fold_all(masked=True, then=write_out)


def _diagonal(shape):
    """Where the last two indices of `shape` are equal."""
    return lax.broadcasted_iota(jnp.int32, shape, len(shape) - 2) \
        == lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)


def _narrow_call(q, k_cache, v_cache, last, *, sm_scale, bk, step, name,
                 interpret):
    """q [B, KV, 1, D] over caches [B, KV, D, S] -> [B, KV, 1, D]."""
    batch, kv_heads, dim, slots = k_cache.shape
    rows, heads = step
    steps = slots // bk

    def block(b, h, j, last):
        # dead steps name the first block, as `_call` has them
        return b, h, 0, jnp.maximum(j - (steps - 1 - last[0] // bk), 0)

    def head(b, h, j, last):
        return b, h, 0, 0

    return pl.pallas_call(
        functools.partial(_narrow_kernel, sm_scale=sm_scale, bk=bk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(batch // rows, kv_heads // heads, steps),
            in_specs=[pl.BlockSpec((rows, heads, 1, dim), head),
                      pl.BlockSpec((rows, heads, dim, bk), block),
                      pl.BlockSpec((rows, heads, dim, bk), block)],
            out_specs=pl.BlockSpec((rows, heads, 1, dim), head),
            scratch_shapes=[pltpu.VMEM((rows, heads, 1, 1), jnp.float32),
                            pltpu.VMEM((rows, heads, 1, 1), jnp.float32),
                            pltpu.VMEM((rows, heads, dim, _LANES),
                                       jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(last, q, k_cache, v_cache)


def _write_kernel(at_ref, k_new_ref, v_new_ref, k_ref, v_ref, k_out, v_out):
    """One grid step: the 128 slots that hold slot `at`, of every head
    of the step's rows, [rows, KV, D, 128], with the new entries
    [rows, KV, 1, D] a column in it (a row turned through the diagonal
    of a [D, D] tile: one term a sum, exact)."""
    shape = k_ref.shape
    here = lax.broadcasted_iota(jnp.int32, shape, 3) \
        == lax.rem(at_ref[0], _LANES)
    diagonal = _diagonal(shape[:3] + (shape[2],))
    for new_ref, ref, out in ((k_new_ref, k_ref, k_out),
                              (v_new_ref, v_ref, v_out)):
        column = jnp.sum(
            jnp.where(diagonal, new_ref[...].astype(jnp.float32), 0.0),
            axis=3, keepdims=True).astype(ref.dtype)
        out[...] = jnp.where(here, column, ref[...])


def _write_call(k_new, v_new, k_cache, v_cache, at, *, rows, interpret):
    batch, kv_heads, dim, _ = k_cache.shape

    def column(b, at):
        return b, 0, 0, 0

    def tile(b, at):
        return b, 0, 0, at[0] // _LANES

    new = pl.BlockSpec((rows, kv_heads, 1, dim), column)
    held = pl.BlockSpec((rows, kv_heads, dim, _LANES), tile)
    return pl.pallas_call(
        _write_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(batch // rows,),
            in_specs=[new, new, held, held], out_specs=[held, held]),
        out_shape=[jax.ShapeDtypeStruct(c.shape, c.dtype)
                   for c in (k_cache, v_cache)],
        # in place: the prefetched scalar is operand 0
        input_output_aliases={3: 0, 4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="gqa_write_r%d" % rows,
    )(at, k_new, v_new, k_cache, v_cache)


def _chosen_kernel(live_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                   *, sm_scale, chunk, kv_heads, pack):
    """One grid step: `chunk` entries of a row's chosen set, every
    key/value head of them, [chunk * KV, D] as the gather left them (row
    e * KV + h is head h of entry e), folded into the running maximum,
    sum [KV * G, 1] and accumulator [KV * G, D] of all the row's
    queries.  A 32-bit word of the block's sublanes holds `pack` heads of
    one entry (two bfloat16 heads, one float32 head), so the block is
    read `pack` heads at a time: every (KV / pack)-th word row, which is
    [chunk * pack, D] in the operands' type, column e * pack + i head i
    of those of entry e, under the `pack * G` queries that read one of
    them, each attending its own head's columns."""
    j = pl.program_id(1)
    # one count for every row, or a row's own of the prefetched vector
    live = live_ref[pl.program_id(0) if live_ref.shape[0] > 1 else 0]
    last_chunk = (live - 1) // chunk
    group = q_ref.shape[1] // kv_heads
    rows, sets = pack * group, kv_heads // pack

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def fold(masked):
        width = chunk * pack
        attended = held = None
        col = lax.broadcasted_iota(jnp.int32, (1, width), 1)
        if pack > 1:    # query row r reads head r // G of the word's
            attended = lax.rem(col, pack) == lax.div(
                lax.broadcasted_iota(jnp.int32, (rows, 1), 0), group)
        if masked:      # entry >= Live is dead, whatever it holds
            alive = j * chunk + lax.div(col, pack) < live
            attended = alive if attended is None else attended & alive
            held = j * chunk + lax.div(lax.broadcasted_iota(
                jnp.int32, (width, 1), 0), pack) < live
        for g in range(sets):
            if sets == 1 and pack == 1:
                keys, values = k_ref[0], v_ref[0]
            else:
                keys, values = (
                    pltpu.bitcast(ref.bitcast(jnp.uint32)[
                        0, pl.ds(g, chunk, stride=sets), :], ref.dtype)
                    for ref in (k_ref, v_ref))
            at = pl.ds(g * rows, rows)
            _fold(q_ref[0, at], keys, values, m_scr.at[at], l_scr.at[at],
                  acc_scr.at[at], sm_scale, attended, held)

    @pl.when(j < last_chunk)
    def _whole():
        fold(masked=False)

    @pl.when(j == last_chunk)
    def _crossed():
        fold(masked=True)
        o_ref[0] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


def _chosen_call(q, k_chosen, v_chosen, live, *, sm_scale, chunk, kv_heads,
                 name, interpret):
    """q [B, KV * G, D] over chosen slots [B, top_k * KV, D]."""
    batch, rows, dim = q.shape
    a_row = live.shape[0] > 1   # a count a row, else one for every row

    def entries(b, j, live):
        # a chunk past the last live entry names that one: fetched once
        return b, jnp.minimum(j, (live[b if a_row else 0] - 1) // chunk), 0

    def row(b, j, live):
        return b, 0, 0

    block = pl.BlockSpec((1, chunk * kv_heads, dim), entries)
    entries_held = k_chosen.shape[1] // kv_heads
    return pl.pallas_call(
        functools.partial(
            _chosen_kernel, sm_scale=sm_scale, chunk=chunk,
            kv_heads=kv_heads,
            pack=_heads_a_word(kv_heads, q.dtype.itemsize)),
        # what the call reads and computes, for the compiler: a custom
        # call's cost is opaque to it, and the memory-space assignment
        # places the gathered copies in fast memory only where it knows
        # that their one reader is bound by their bytes (with no
        # estimate it placed 6 of a step's 10 copies there in one
        # program and none in the next: PERF.md section 6, PR 66)
        cost_estimate=pl.CostEstimate(
            flops=4 * batch * rows * entries_held * dim,
            transcendentals=batch * rows * entries_held,
            bytes_accessed=(k_chosen.size + v_chosen.size + 2 * q.size)
            * q.dtype.itemsize),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(batch, k_chosen.shape[1] // (chunk * kv_heads)),
            in_specs=[pl.BlockSpec((1, rows, dim), row), block, block],
            out_specs=pl.BlockSpec((1, rows, dim), row),
            scratch_shapes=[pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, dim), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(live, q, k_chosen, v_chosen)


def _by_platform(call, *operands):
    """`call` lowered for the platform of the lowering: Mosaic on the
    TPU, the Pallas interpreter on the CPU."""
    return lax.platform_dependent(
        *operands, tpu=functools.partial(call, interpret=False),
        cpu=functools.partial(call, interpret=True))


# Under `jax.jit`: `platform_dependent` traces the interpreter's branch
# beside Mosaic's, and the layers of a program that hold the same
# instance share one traced body and one lowered function.

@functools.partial(jax.jit, static_argnames=("sm_scale", "bk", "positions",
                                             "step", "name", "diffusion"))
def _wide(q, k_cache, v_cache, last, **static):
    return _by_platform(functools.partial(_call, **static), q, k_cache,
                        v_cache, last)


@functools.partial(jax.jit, static_argnames=("sm_scale", "bk", "step",
                                             "name"))
def _narrow(q, k_cache, v_cache, last, **static):
    # the caches with their last two axes swapped: no copy where the
    # compiler holds them slots-minor, which is where it holds a
    # [.., slots, 64] array unasked (no lane is padding)
    return _by_platform(functools.partial(_narrow_call, **static), q,
                        jnp.swapaxes(k_cache, 2, 3),
                        jnp.swapaxes(v_cache, 2, 3), last)


@functools.partial(jax.jit, static_argnames=("sm_scale", "chunk", "kv_heads",
                                             "name"))
def _chosen(q, k_chosen, v_chosen, live, **static):
    return _by_platform(functools.partial(_chosen_call, **static), q,
                        k_chosen, v_chosen, live)


@functools.partial(jax.jit, static_argnames=("rows",))
def _written(k_new, v_new, k_cache, v_cache, at, rows):
    caches = _by_platform(functools.partial(_write_call, rows=rows), k_new,
                          v_new, jnp.swapaxes(k_cache, 2, 3),
                          jnp.swapaxes(v_cache, 2, 3), at)
    return tuple(jnp.swapaxes(c, 2, 3) for c in caches)


def write_step(k_cache, v_cache, k_new, v_new, at):
    """The 64-wide caches [B, KV, S, 64] with slot `at` (an int32
    scalar) of every row and head set to `k_new`, `v_new` [B, KV, 1,
    64], in place where the caller gives them up: what
    `dynamic_update_slice` does, by a kernel that rewrites the 128 slots
    around `at` as they lie, slots-minor (there the compiler's own
    update takes 0.09-0.16 ms a cache of 100 MB; this 0.02-0.04)."""
    batch, kv_heads = k_cache.shape[:2]
    if not fits(1, k_cache.shape[2], k_cache.shape[3]) \
            or k_cache.shape[3] != _NARROW or k_cache.shape != v_cache.shape \
            or k_new.shape != (batch, kv_heads, 1, _NARROW) \
            or v_new.shape != k_new.shape \
            or len({x.dtype for x in (k_cache, v_cache, k_new, v_new)}) != 1:
        raise ValueError(
            "gqa_decode.write_step: %s %s and %s %s into caches %s %s and "
            "%s %s are no step the kernel writes"
            % (k_new.shape, k_new.dtype, v_new.shape, v_new.dtype,
               k_cache.shape, k_cache.dtype, v_cache.shape, v_cache.dtype))
    rows = _divisor(batch, _NARROW_STEP_BYTES // (
        kv_heads * _NARROW * _LANES * k_cache.dtype.itemsize))
    return _written(k_new, v_new, k_cache, v_cache,
                    jnp.reshape(at, (1,)).astype(jnp.int32), rows=rows)


def gqa_decode(q, k_cache, v_cache, last, sm_scale, window=0, block_k=None,
               positions=1, step=None, diffusion=0):
    """The attended values of one decode step, or of a block of
    `positions` consecutive ones, [batch, kv_heads, group * positions,
    D] in q's type: see the module's docstring.  `window` names the
    kernel of a ring (`gqa_decode_w<window>`, one block, one position);
    `block_k` and `step` (the rows of the batch and the key/value heads
    a grid step takes, which have to divide them) are chosen from the
    shapes unless given (tests, sweeps).  `diffusion` B > 0: the
    block-causal mask over whole blocks of B positions (the module's
    docstring), `_b<B>` in the kernel's name."""
    slots = k_cache.shape[2]
    narrow = q.shape[-1] == _NARROW
    if q.ndim != 4 or k_cache.shape != v_cache.shape \
            or k_cache.shape[:2] != q.shape[:2] \
            or k_cache.shape[3] != q.shape[3] \
            or k_cache.dtype != q.dtype or v_cache.dtype != q.dtype \
            or positions < 1 or q.shape[2] % positions \
            or not fits(q.shape[2], slots, q.shape[3], q.dtype.itemsize) \
            or (window and (window != slots or positions != 1 or narrow)) \
            or (diffusion and (window or narrow or positions % diffusion)):
        raise ValueError(
            "gqa_decode: queries %s %s at %d positions over caches %s %s "
            "and %s %s (window %d, diffusion block %d) are no step the "
            "kernel takes"
            % (q.shape, q.dtype, positions, k_cache.shape, k_cache.dtype,
               v_cache.shape, v_cache.dtype, window, diffusion))
    bk = block_k or choose_block(slots, q.shape[2], q.dtype.itemsize,
                                 q.shape[3])
    last = jnp.reshape(last, (1,)).astype(jnp.int32)
    step = tuple(step or choose_step(q.shape[0], q.shape[1], bk,
                                     q.dtype.itemsize, q.shape[2],
                                     q.shape[3]))
    if len(step) != 2 or min(step) < 1 or q.shape[0] % step[0] \
            or q.shape[1] % step[1]:
        raise ValueError(
            "gqa_decode: a grid step of %s (rows, key/value heads) does not "
            "divide the %d rows and %d key/value heads of %s"
            % (step, q.shape[0], q.shape[1], q.shape))
    # what a grid step takes, in the kernel's name
    shared = "_h%d" % step[1] + ("_r%d" % step[0] if step[0] > 1 else "")
    if narrow:
        return _narrow(q, k_cache, v_cache, last, sm_scale=float(sm_scale),
                       bk=bk, step=step, name="gqa_decode_k%d" % bk + shared)
    name = "gqa_decode_w%d" % window if window else "gqa_decode_k%d" % bk
    if positions > 1:
        name += "_t%d" % positions
    if diffusion:   # said only where asked: every other call is as it was
        name += "_b%d" % diffusion
    if q.shape[3] != _LANES:
        name += "_d%d" % q.shape[3]
    if step != (1, 1):
        name += shared
    return _wide(q, k_cache, v_cache, last, sm_scale=float(sm_scale), bk=bk,
                 positions=positions, step=step, name=name,
                 **({"diffusion": diffusion} if diffusion else {}))


def gqa_decode_chosen(q, k_chosen, v_chosen, live, sm_scale, chunk=None):
    """The attended values of one decode step over a chosen set,
    [batch, kv_heads, group, D] in q's type: `k_chosen`, `v_chosen`
    [batch, top_k, kv_heads, D] hold the chosen slots as a gather of
    whole slots leaves them, a slot's heads side by side, and the
    queries attend the first `live` of a row's entries (int32, at least
    1: a scalar, one count for every row, as the rows of a decode step
    have; or [batch], a count a row, as the positions of a block have
    that lie a row each): see the module's docstring.  `chunk`, the
    entries a grid step folds, is chosen from the shapes unless given
    (tests, sweeps)."""
    batch, kv_heads, group, dim = q.shape
    top_k = k_chosen.shape[1]
    chunk = chunk or choose_chunk(top_k, kv_heads, group, q.dtype.itemsize,
                                  dim)
    if k_chosen.shape != (batch, top_k, kv_heads, dim) \
            or v_chosen.shape != k_chosen.shape \
            or k_chosen.dtype != q.dtype or v_chosen.dtype != q.dtype \
            or jnp.size(live) not in (1, batch) \
            or not chunk or top_k % chunk \
            or not choose_chunk(chunk, kv_heads, group, q.dtype.itemsize,
                                dim):     # a given chunk: tiled and held too
        raise ValueError(
            "gqa_decode_chosen: queries %s %s over chosen slots %s %s and "
            "%s %s (chunk %s) are no step the kernel takes"
            % (q.shape, q.dtype, k_chosen.shape, k_chosen.dtype,
               v_chosen.shape, v_chosen.dtype, chunk))
    out = _chosen(
        q.reshape(batch, kv_heads * group, dim),
        k_chosen.reshape(batch, top_k * kv_heads, dim),
        v_chosen.reshape(batch, top_k * kv_heads, dim),
        jnp.reshape(live, (-1,)).astype(jnp.int32), sm_scale=float(sm_scale),
        chunk=chunk, kv_heads=kv_heads,
        name="gqa_decode_sel%d_c%d" % (top_k, chunk))
    return out.reshape(q.shape)
