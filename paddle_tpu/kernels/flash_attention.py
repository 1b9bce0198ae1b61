"""Flash attention (pallas TPU kernels, online softmax).

No reference counterpart (the 2018 snapshot predates flash attention;
its attention is composed ops — reference: python/paddle/v2/fluid/
nets.py:338 scaled_dot_product_attention materializes the full [T,T]
probability matrix).  These kernels never materialize T×T in HBM: a
grid step of the forward holds one block of queries and a block of K/V
in VMEM and folds the K/V block into the running max, sum and
accumulator one (block_k, block_q) chunk of scores at a time, both
products on the MXU.  The backward (custom VJP) recomputes the
probabilities chunk by chunk from the forward's row statistics, in the
same layout, in one of three forms (`_choose_bwd_blocks`).  Where a
head's queries and its dq fit VMEM, one kernel holds a block of keys
and makes dq, dk and dv together: five products a chunk, the four the
gradients are and the scores.  Where they do not but the call has a
window, one kernel still makes all three, five products a chunk, as it
walks the blocks of keys in order: a block of keys is seen by the
window + block queries after it alone, so the dq that has to stay in
VMEM is that of the few blocks of queries the keys can still reach, a
ring of them, and a block of queries leaves the ring as dq when the
keys on its own diagonal have been folded.  Else one kernel holds a
block of keys and gathers dk and dv over the queries that see it, and
another holds a block of queries and gathers dq over the keys it sees:
seven products a chunk, the scores and dp made twice.

How much one grid step does is chosen from the shapes
(`_choose_blocks`, `_choose_bwd_blocks`): a grid step costs about
0.5 us empty and every chunk has costs of its own, so the blocks are
as large as the VMEM budget allows, under a causal mask too.  Where the
side a kernel walks (one head's K and V in the forward, its queries in
the backward) fits the budget beside a chunk it stays resident across
the head's blocks and the chunk loop stops at the causal diagonal;
where it does not, the grid's innermost axis walks it one chunk a step.

A chunk every query sees is folded whole and unmasked, one above the
causal diagonal not at all.  One the diagonal crosses is folded as a
staircase (`_stairs`): pieces of `_STAIR` keys, each over only the
queries at or after its first key, the mask's iota / compare / select
on the piece's leading square alone.  The
queries a piece leaves out are those the mask would set to NEG_INF,
which add exp(NEG_INF - m) = 0: the sums hold the same terms.  A chunk
of n pieces then costs (n + 1) / 2n of its pairs, whatever the blocks,
which is why a causal block may be its whole sequence.  Where the
diagonal enters a chunk has to be known when the kernel is traced: the
blocks and `q_offset` multiples of a piece (`_stair_width`; a `pl.when`
for each place, `_leads`, where several chunks of a grid step are
crossed).  Elsewhere (a decode step's offset, a ragged sequence that is
one block, a sequence shard at an odd offset) and in the backward's
pair of kernels that walk, a crossed chunk is folded whole through the
same fold, every score compared.  `score_pairs` counts what either way
folds, and the counter `flash_attention_pairs_total` reports it.

A `window` W > 0 under the causal mask bounds the other side: query i
folds keys max(0, i - W + 1) .. i.  The lower edge is the diagonal moved
W keys back, so the chunk loops get its two marks from the formulas that
give the diagonal's: a chunk that lies wholly before a block's first
visible key is neither fetched (the index maps re-name the first visible
block) nor folded; one the lower edge crosses is folded whole, every
score compared against both edges (`_see`), before the unmasked chunks
and the diagonal's staircase; the blocks are no larger than the window
needs (`_window_blocks`).  The kernels' names carry `_w<W>`.  With
`window` 0, or one no query reaches past, nothing here is traced and
the kernels are the causal ones.

Two layouts, one set of kernel bodies.  `flash_attention`,
`flash_attention_with_lse` and `_bwd` take q, k, v (and do) as
[batch, heads, seq, dim], one head a grid step: for the callers that
hold heads apart for a reason (ring attention's sequence shards,
ulysses' head-sharded all-to-all, the functional transformer).  Given
`num_heads`, the last two take them as [batch, seq, heads * dim], as a
projection writes them and the `flash_attention` op (ops/attention.py)
holds them, and write o, dq, dk and dv so: the BlockSpecs pick the
head, so nothing is transposed around the kernels.  A grid step's lane
block is max(dim, 128): one head where dim is a multiple of 128, and
128 // dim heads side by side in one lane-dense block where dim
divides 128 (two at GPT-2's 64; all the heads of a model narrower than
128 in all), every one of them at each turn of the chunk loop, which
gives the scheduler one head's products to run beside another's
exponentials.  The transposed scratch ([d, positions]) gives head h the
rows [h * dim, (h + 1) * dim), a sublane slice; a product that
contracts over the lanes takes one operand with the other heads' lanes
zeroed, which adds zeros to the sum and costs the MXU what a 64-deep
pass costs, a 128-deep one.  Any other width (96, 80, 192) is split
into [batch, heads, seq, dim] around the kernels, as every width once
was.  The row statistics are float32 [batch, heads, seq] in both layouts.

The kernels compile through Mosaic when lowered for the TPU and run
under pallas interpret mode when lowered for the CPU (tests, dry runs);
any other platform is refused at lowering.
"""

import collections
import functools
import itertools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs import telemetry

NEG_INF = -1e30
_LANES = 128
# what one grid step may hold in VMEM (`_step_bytes`); under Mosaic's
# default scoped limit of 16 MiB, so no limit has to be asked for
_VMEM_BUDGET = 14 * 2 ** 20
# the block sizes the chooser tries: multiples of the MXU's 128 rows
_BLOCKS = (1024, 512, 256, 128)
# the keys a piece of a staircase holds at most (`_stairs`).  ms a call
# on the v5e at (bq, bk), whole / 128 / 256 (scripts/flash_stair_bench.py;
# PERF.md section 6, PR 34): forward [8, 1024, 16 x 64] at (1024, 1024)
# 0.570 / 0.425 / 0.405 and at (512, 512) 0.488 / 0.478 / 0.466,
# [1, 4096, 16 x 128] at (1024, 512) 0.792 / 0.717 / 0.718; backward at
# (512, 512) 0.735 / 0.626 / 0.634, at (512, 256) 1.278 / 1.227 / 1.222.
# A piece rescales the forward's accumulator and adds to the backward's
# dq^T once more, and is a body more to trace and lower at every start
# of a process: 128 buys the backward 1.4% and the forward nothing.
_STAIR = 256
# the scope the backward's operations lie under, whoever calls `_bwd`:
# the benchmark's readers find them by it
BWD_SCOPE = "flash_attention_bwd"
# the kernels of each form of the backward (`_choose_bwd_blocks`), as the
# counters name them
_BWD_KERNELS = {"one": ("dq_dkv",), "ring": ("ring",), "pair": ("dkv", "dq")}


def _block(seq, block, what, shape):
    """The block size along one sequence axis.  It must divide the
    sequence: the grid has no ragged last tile, and shrinking the block
    until it fits would hand the MXU slivers without saying so."""
    block = min(block, seq)
    if seq % block:
        raise ValueError(
            "flash_attention: %s length %d of shape %s is not a multiple "
            "of its block size %d" % (what, seq, tuple(shape), block))
    return block


def _candidates(seq):
    """Block sizes to try along one axis, largest first: those that
    divide the sequence, else the whole of one that fits in a block."""
    found = [b for b in _BLOCKS if seq % b == 0]
    return found or ([seq] if seq <= _BLOCKS[0] else [])


def _window_blocks(blocks, window):
    """`blocks` (largest first) without those larger than the smallest
    that holds a whole `window`: a chunk the lower edge crosses is
    folded whole, so a block wider than the window folds mostly scores
    nobody attends.  All of them where there is no window."""
    if not window:
        return blocks
    return [b for b in blocks if b < 2 * max(window, _BLOCKS[-1])] \
        or blocks[-1:]


def _pad_to_lanes(n):
    return -(-n // _LANES) * _LANES


def split_heads(x, num_heads):
    """[batch, seq, heads * dim] -> [batch, heads, seq, dim]."""
    b, t, d = x.shape
    return x.reshape(b, t, num_heads, d // num_heads).transpose(0, 2, 1, 3)


def merge_heads(x):
    """[batch, heads, seq, dim] -> [batch, seq, heads * dim]."""
    b, h, t, dh = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, t, h * dh)


class _Call(collections.namedtuple("_Call", "merged batch heads tq tk d g")):
    """What the kernels see of one call: operands [batch, heads, seq, d]
    (`merged` false) or [batch, seq, heads * d], and `g`, the heads one
    grid step holds: 1, or of merged operands the 128 // d that share a
    lane block (all of them where their whole width is one block), or
    None where no lane block holds whole heads."""

    @classmethod
    def of(cls, q_shape, k_shape, num_heads):
        if num_heads is None:
            (b, h, tq, d), tk = q_shape, k_shape[2]
            return cls(False, b, h, tq, tk, d, 1)
        (b, tq, width), tk = q_shape, k_shape[1]
        d = width // num_heads
        if d % _LANES == 0:
            g = 1
        elif _LANES % d == 0 and num_heads % (_LANES // d) == 0:
            g = _LANES // d
        elif width <= _LANES:
            g = num_heads
        else:
            g = None
        return cls(True, b, num_heads, tq, tk, d, g)

    @property
    def lanes(self):
        return self.g * self.d

    @property
    def steps(self):
        """The grid's two leading axes."""
        return (self.batch, self.heads // self.g)

    @property
    def step_shapes(self):
        """q's and k's shapes with a grid step's heads as one head, for
        the block choosers: they size VMEM by what a step holds."""
        return tuple(self.steps + (t, self.lanes) for t in (self.tq, self.tk))

    @property
    def suffix(self):
        """What the kernels' names end in: the heads a step holds of
        merged operands, nothing for [batch, heads, seq, d]."""
        return "_h%d" % self.g if self.merged else ""

    def rows(self, n, index):
        """BlockSpec of the [n, lanes] tile of q, k, v, o, do or a
        gradient that holds rows index(*steps) * n .. of the grid
        step's heads; `steps` are the grid indices after (batch,
        head)."""
        if self.merged:
            return pl.BlockSpec((None, n, self.lanes),
                                lambda b, h, *steps: (b, index(*steps), h))
        return pl.BlockSpec((None, None, n, self.lanes),
                            lambda b, h, *steps: (b, h, index(*steps), 0))

    def stats(self, n, index):
        """BlockSpec of the [g, n] float32 rows of per-query statistics
        of the grid step's heads, from [batch, heads / g, g, seq]."""
        return pl.BlockSpec((None, None, self.g, n),
                            lambda b, h, *steps: (b, h, 0, index(*steps)))

    @property
    def stats_shape(self):
        """[batch, heads, Tq] statistics as `stats` blocks them."""
        return self.steps + (self.g, self.tq)


def _step_bytes(bq, bk, kv_rows, d, itemsize):
    """VMEM bytes one grid step holds, `d` the width of its heads (of
    one head, or of those that share a lane block): the q and o tiles
    [bq, d] and the K and V blocks [kv_rows, d], each double-buffered
    by the pipeline, their minor dimension padded to 128 lanes; V
    transposed [d, kv_rows]; the float32 accumulator, its rows padded
    likewise; the m and l rows [1, bq] a head (they pad to 8 sublanes,
    and they are double-buffered too); one chunk's scores and
    probabilities [bk, bq] in float32 and the probabilities cast for
    the second product."""
    lanes = _pad_to_lanes(d)
    tiles = 2 * itemsize * lanes * (2 * bq + 2 * kv_rows)
    scratch = itemsize * d * kv_rows + 4 * lanes * bq
    stats = 2 * 2 * 8 * bq * 4
    chunk = bq * bk * (4 + 4 + itemsize)
    return tiles + scratch + stats + chunk


def _choose_blocks(q_shape, k_shape, itemsize, block_q=None, block_k=None,
                   window=0):
    """(block_q, block_k, kv_resident) for one call, from what the
    kernel sees: the sequence lengths, the width of a grid step's heads
    and the item size (not the mask: the staircase cuts off what lies
    above a causal diagonal whatever the blocks).  A block the caller
    names is kept as it is; what is chosen is the pair that folds most
    scores at a time under the VMEM budget, with half its block_k if
    all of one head's K and V then fit beside the fold: `kv_resident`
    says a grid step holds them all, not one block_k chunk of them.
    Under a `window` no chosen block is wider than the window needs
    (`_window_blocks`)."""
    tq, d = q_shape[2], q_shape[3]
    tk = k_shape[2]
    qs = (_window_blocks(_candidates(tq), window) if block_q is None
          else [_block(tq, block_q, "query", q_shape)])
    ks = (_window_blocks(_candidates(tk), window) if block_k is None
          else [_block(tk, block_k, "key", k_shape)])
    named = block_q is not None and block_k is not None
    fit = [(bq, bk) for bq, bk in sorted(
        itertools.product(qs, ks), key=lambda qk: (-qk[0] * qk[1], -qk[0]))
        if named or _step_bytes(bq, bk, bk, d, itemsize) <= _VMEM_BUDGET]
    if not fit:
        raise ValueError(
            "flash_attention: no block among %s tiles query length %d and "
            "key length %d of shapes %s and %s within %d bytes of VMEM"
            % (_BLOCKS[::-1], tq, tk, tuple(q_shape), tuple(k_shape),
               _VMEM_BUDGET))
    bq, bk = fit[0]
    for smaller in (bk, bk // 2):
        if (bq, smaller) in fit and _step_bytes(
                bq, smaller, tk, d, itemsize) <= _VMEM_BUDGET:
            return bq, smaller, True
    return bq, bk, False


def _matmul(a, b, rhs_contracts=0):
    return lax.dot_general(a, b, (((1,), (rhs_contracts,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _fold_chunks(fold, first, last):
    lax.fori_loop(first, last, lambda c, _: fold(c), None)


def _stair_width(bq, bk, q_offset, widest):
    """How many keys wide the pieces are that a chunk the causal
    diagonal crosses is folded in (`_stairs`): the widest multiple of a
    lane block up to `widest` that the blocks and the offset are
    multiples of, or None where there is none and the chunk is folded
    whole: where the diagonal enters a chunk is then not known when the
    kernel is traced, or a piece's queries would not start on a lane
    block (a decode step's offset, a ragged sequence that is one block,
    a sequence shard at an odd offset)."""
    for s in range(widest or 0, 0, -_LANES):
        if bq % s == bk % s == q_offset % s == 0:
            return s
    return None


def _leads(bq, bk, q_offset):
    """Every place the diagonal can enter a [bk, bq] chunk it crosses,
    as how far the chunk's first key lies ahead of its first query: a
    multiple of what both blocks are multiples of, less the offset,
    above -bk (at or below it every query sees every key) and below bq
    (there no query sees any)."""
    g = math.gcd(bq, bk)
    return [t for t in range(-q_offset % g - bk, bq, g) if t > -bk]


def _stairs(lead, bq, bk, s):
    """The pieces (first key, keys, first query, crossed) of a [bk, bq]
    chunk whose first key lies `lead` ahead of its first query, `lead`
    and both blocks multiples of `s`: the keys behind the first query,
    which every query sees, as one piece the diagonal does not cross;
    then `s` keys a piece, each with the queries at or after its first
    key alone, until the keys or the queries end.  The queries a piece
    leaves out see none of its keys: whole, the chunk would give them
    exp(NEG_INF - m) = 0 to add."""
    pieces = [(0, -lead, 0, False)] if lead < 0 else []
    for key in range(max(0, -lead), min(bk, bq - lead), s):
        pieces.append((key, s, lead + key, True))
    return pieces


def _fold_crossed(fold, lead, bq, bk, q_offset, s):
    """Fold the [bk, bq] chunk the causal diagonal crosses, whose first
    key lies `lead` (traced) ahead of its first query, through
    `fold(first key, keys, first query, lead)`: as a staircase of pieces
    `s` keys wide (`_stairs`) under a `pl.when` for each place the
    diagonal can enter the chunk; where `s` is None whole, every score
    compared."""
    if s is None:
        return fold(0, bk, 0, lead)

    def staircase(t):
        def run():
            for key, keys, query, crossed in _stairs(t, bq, bk, s):
                fold(key, keys, query, 0 if crossed else None)
        return run

    leads = _leads(bq, bk, q_offset)
    if len(leads) == 1:
        return staircase(leads[0])()
    for t in leads:
        pl.when(lax.eq(lead, t))(staircase(t))


def _chunk_pairs(lead, bq, bk, s, window=0):
    """The score pairs a kernel folds of a [bk, bq] chunk whose first
    key lies `lead` ahead of its first query: none of one that lies
    wholly before the `window`, all of one its lower edge crosses."""
    if lead >= bq or (window and lead <= 1 - bk - window):
        return 0
    if lead <= 1 - bk or s is None or (window and lead < bq - window):
        return bq * bk
    return sum(keys * (bq - query)
               for _, keys, query, _ in _stairs(lead, bq, bk, s))


@functools.lru_cache(maxsize=None)
def score_pairs(tq, tk, causal, q_offset, bq, bk, widest, window=0):
    """(folded, attended): the score pairs the kernels compute for one
    head at blocks (bq, bk) with pieces up to `widest` keys (None: a
    kernel that folds a crossed chunk whole whatever the shapes, as the
    backward's pair of kernels that walk), and those among them a query
    attends.  The forward (a block of queries over chunks of keys) and
    the backward (a block of keys over chunks of queries) meet the same
    [bk, bq] chunks.  Under a `window` a query attends the last
    `window` of the keys the causal mask leaves it."""
    if not causal:
        return tq * tk, tq * tk
    s = _stair_width(bq, bk, q_offset, widest)
    folded = sum(_chunk_pairs(c * bk - i * bq - q_offset, bq, bk, s, window)
                 for i in range(tq // bq) for c in range(tk // bk))
    seen = [min(max(q_offset + i + 1, 0), tk) for i in range(tq)]
    if window:
        seen = [n - min(max(q_offset + i + 1 - window, 0), n)
                for i, n in enumerate(seen)]
    return folded, sum(seen)


def _see(x, lead, fill, window=0):
    """`x` [keys, queries] with `fill` where the query does not see the
    key: a query sees the keys at or before its own position, and the
    first key lies `lead` ahead of the first query.  Where `lead` is
    known when the kernel is traced, only the first lead + keys queries
    can miss a key, and only their columns are compared.  Under a
    `window` a query sees the last `window` of those keys alone, and
    every score is compared against both edges."""
    keys, queries = x.shape
    if window:
        ahead = lax.sub(lax.broadcasted_iota(jnp.int32, x.shape, 1),
                        lax.broadcasted_iota(jnp.int32, x.shape, 0))
        seen = lax.bitwise_and(lax.ge(ahead, lead),
                               lax.lt(ahead, lax.add(lead, window)))
        return lax.select(seen, x, lax.full_like(x, fill))
    crossed = min(lead + keys, queries) if isinstance(lead, int) else queries
    part = x if crossed == queries else lax.slice_in_dim(x, 0, crossed, axis=1)
    ahead = lax.sub(lax.broadcasted_iota(jnp.int32, part.shape, 1),
                    lax.broadcasted_iota(jnp.int32, part.shape, 0))
    part = lax.select(lax.ge(ahead, lead), part, lax.full_like(part, fill))
    if crossed == queries:
        return part
    return lax.concatenate(
        [part, lax.slice_in_dim(x, crossed, queries, axis=1)], 1)


def _each_head(heads, d, fold):
    """`fold(h, head, stat)` for each of the `heads` heads a grid step
    holds, `head` the head's d rows of the transposed scratch and `stat`
    its row of the statistics.  Several heads are the turns of a loop
    that the lowering unrolls: h reaches the compiler as a constant and
    both heads' operations lie in one block for its scheduler, as if
    written out, but the fold is traced once and not once a head."""
    if heads == 1:
        return fold(0, slice(0, d), slice(0, 1))

    def turn(h, _):
        fold(h, pl.ds(pl.multiple_of(lax.mul(h, d), d), d), pl.ds(h, 1))

    lax.fori_loop(0, heads, turn, None, unroll=True)


def _head_lanes(h, d, lanes, dtype):
    """The [1, lanes] row `_only_head` keeps the h-th head's lanes with,
    of the heads side by side in a tile's lanes: all-ones 32-bit words
    over lanes [h * d, (h + 1) * d) for a 16-bit tile (zeros elsewhere),
    a boolean row for a wider one; None where the lanes are one head's.
    One vreg: a fold then pays a broadcast and an AND a tile."""
    if lanes == d:
        return None
    lane = lax.broadcasted_iota(jnp.int32, (1, lanes), 1)
    first = lax.mul(h, d)
    own = lax.bitwise_and(lax.ge(lane, first), lax.lt(lane, lax.add(first, d)))
    if dtype.itemsize < 4:
        own = lax.select(own, lax.full(own.shape, 0xFFFFFFFF, jnp.uint32),
                         lax.full(own.shape, 0, jnp.uint32))
    return own


def _only_head(x, own):
    """`x` [rows, lanes] with the lanes of every head but one zeroed,
    `own` that head's row of `_head_lanes`, so that a product
    contracting `x` with all the lanes of another tile is that head's
    product alone; `x` itself where its lanes are one head's."""
    if own is None:
        return x
    if own.dtype == jnp.uint32 and x.shape[0] * x.dtype.itemsize % 4 == 0:
        # a 16-bit tile: one AND a vreg on its packed 32-bit words (two
        # rows a word, the same lane); a select would unpack it to
        # float32 and pack it again (PERF.md section 6, PR 30)
        words = pltpu.bitcast(x, jnp.uint32)
        keep = lax.broadcast_in_dim(own, words.shape, (0, 1))
        return pltpu.bitcast(lax.bitwise_and(words, keep), x.dtype)
    if own.dtype == jnp.uint32:
        own = lax.ne(own, lax.full_like(own, 0))
    return lax.select(lax.broadcast_in_dim(own, x.shape, (0, 1)), x,
                      lax.full_like(x, 0))


def _on_platform(call, *args):
    """The Mosaic kernel where the computation is lowered for the TPU,
    the Pallas interpreter where for the CPU.  Chosen by the platform
    of the lowering, not by the default backend: an export for the TPU
    from a CPU host gets the kernel, a CPUPlace program on a TPU host
    the interpreter, and with no default branch anything else is an
    error."""
    return lax.platform_dependent(
        *args, tpu=call(interpret=False), cpu=call(interpret=True))


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_scr, vt_scr,
                *, sm_scale, causal, q_offset, bk, resident, d, stair,
                window=0):
    """One (batch, heads, q_block, kv_block) grid step: the K/V block
    in VMEM (all of the keys, or one chunk of them) is folded, bk keys
    at a time and for every head of the step's, into the running max
    and sum, which are the m and l output blocks themselves, and into
    the float32 accumulator.  The kv_block axis is
    innermost and sequential: the three are initialised on its first
    step, and o is written on its last.

    The scores are held transposed, [keys, queries], and so are the
    accumulator and V, [d, positions].  The softmax's reductions then
    run down the sublanes, elementwise from vreg to vreg, and the
    per-query statistics are [1, bq] rows, dense in the lanes; held as
    [queries, keys] every statistic is a [bq, 1] column of one useful
    lane a vreg and every reduction crosses the lanes.  V is transposed
    into scratch once a head where its keys are resident (the q_block
    axis is sequential for that), else once a step; o is transposed
    back as it is written.  Where the tiles hold several heads side by
    side in their lanes, head h has rows [h * d, (h + 1) * d) of the
    transposed scratch and row h of the statistics.

    Written in lax primitives, not jnp: every jnp call or operator on a
    tracer is a jitted function to trace besides."""
    (bq, lanes), kv_rows = q_ref.shape, k_ref.shape[0]
    i, j = pl.program_id(2), pl.program_id(3)

    def _transpose_v():
        vt_scr[...] = lax.transpose(v_ref[...], (1, 0))

    if resident:
        pl.when(lax.eq(i, 0))(_transpose_v)
    else:
        _transpose_v()

    @pl.when(lax.eq(j, 0))
    def _init():
        m_ref[...] = lax.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = lax.full(l_ref.shape, 0, jnp.float32)
        acc_scr[...] = lax.full(acc_scr.shape, 0, jnp.float32)

    # how far this step's first query is ahead of its first key
    ahead = lax.sub(lax.add(lax.mul(i, bq), q_offset),
                    lax.mul(j, kv_rows))

    def _fold(c, key, keys, query, lead, edge=0):
        """`keys` keys of chunk c from its `key`-th into the block's
        queries from the `query`-th on; `lead` as `_see` takes it, None
        where every query sees every key; `edge` the window where its
        lower edge crosses the chunk too."""
        if keys == kv_rows:
            # one chunk, read whole: a block that is the whole of a
            # ragged sequence has no aligned slice
            rows = slice(None)
        else:
            rows = pl.ds(pl.multiple_of(lax.add(lax.mul(c, bk), key),
                                        math.gcd(bk, key)), keys)
        cols = slice(query, bq)
        k, q = k_ref[rows, :], q_ref[cols, :]

        def one(h, head, stat):
            vt = vt_scr[head, rows]
            own = _head_lanes(h, d, lanes, q.dtype)
            s = lax.mul(_matmul(k, _only_head(q, own), 1),
                        sm_scale)                         # [keys, queries]
            if lead is not None:
                s = _see(s, lead, NEG_INF, edge)
            m_prev = m_ref[stat, cols]                       # [1, queries]
            m_new = lax.max(
                m_prev, lax.expand_dims(lax.reduce_max(s, (0,)), (0,)))
            alpha = lax.exp(lax.sub(m_prev, m_new))
            p = lax.exp(lax.sub(s, m_new))
            l_ref[stat, cols] = lax.add(
                lax.mul(alpha, l_ref[stat, cols]),
                lax.expand_dims(lax.reduce_sum(p, (0,)), (0,)))
            acc_scr[head, cols] = lax.add(
                lax.mul(alpha, acc_scr[head, cols]),
                _matmul(vt, lax.convert_element_type(p, vt.dtype)))
            m_ref[stat, cols] = m_new

        _each_head(lanes // d, d, one)

    def _fold_whole(c):
        _fold(c, 0, bk, 0, None)

    def _fold_masked(c):
        _fold_crossed(functools.partial(_fold, c),
                      lax.sub(lax.mul(c, bk), ahead), bq, bk, q_offset,
                      stair)

    chunks = kv_rows // bk
    if causal:
        # chunks every query sees whole need no mask; those the
        # diagonal crosses are masked, or folded as a staircase; those
        # above it are never touched.  (lax.div truncates: nothing
        # negative reaches it.)
        whole = lax.min(lax.div(lax.max(lax.add(ahead, 1), 0), bk),
                        chunks)
        seen = lax.min(lax.div(lax.max(lax.add(ahead, bq + bk - 1), 0),
                               bk), chunks)
        first = 0
        if window:
            # the lower edge is the diagonal `window` keys back: chunks
            # wholly before it are never touched, those it crosses are
            # compared against both edges.  They come first, and a
            # query that sees none of their keys gathers exp(0) terms
            # at a maximum of NEG_INF, which its first visible key (a
            # later chunk's: its own position's at the latest) scales
            # by exp(NEG_INF - m) = 0
            back = lax.sub(ahead, window)
            first = lax.min(lax.div(lax.max(lax.add(back, bq + bk - 1), 0),
                                    bk), chunks)
            _fold_chunks(
                lambda c: _fold(c, 0, bk, 0, lax.sub(lax.mul(c, bk), ahead),
                                window),
                lax.min(lax.div(lax.max(lax.add(back, 1), 0), bk), chunks),
                first)
            whole = lax.max(whole, first)
        _fold_chunks(_fold_whole, first, whole)
        _fold_chunks(_fold_masked, whole, seen)
    else:
        _fold_chunks(_fold_whole, 0, chunks)

    @pl.when(lax.eq(j, lax.sub(pl.num_programs(3), 1)))
    def _finish():
        for h in range(lanes // d):
            head, l = slice(h * d, (h + 1) * d), l_ref[h:h + 1, :]
            acc_scr[head] = lax.div(
                acc_scr[head],
                lax.select(lax.gt(l, 0.0), l, lax.full_like(l, 1)))
        o_ref[...] = lax.convert_element_type(
            lax.transpose(acc_scr[...], (1, 0))[:, :lanes], o_ref.dtype)


def _stair_suffix(stair, window=0):
    """What a kernel's name says of its staircase: the pieces' width,
    nothing where a crossed chunk is folded whole; and of its window."""
    return ("_s%d" % stair if stair else "") \
        + ("_w%d" % window if window else "")


def _live_window(window, causal, tq, q_offset):
    """`window` where it cuts a key off some query, else 0: the causal
    kernels serve a window no query reaches past."""
    if window and not causal:
        raise ValueError("flash_attention: a window of %d keys is the "
                         "causal mask's lower edge; causal is False"
                         % window)
    return window if 0 < window < q_offset + tq else 0


def _fwd(q, k, v, sm_scale, causal, block_q, block_k, q_offset,
         num_heads=None, split=False, window=0):
    """o, laid out as q, and the float32 [batch, heads, Tq] row
    statistics m and l, at blocks chosen here from what a grid step
    holds.  With `num_heads` the operands are [batch, seq, heads * d];
    where no lane block holds whole heads of that they are split into
    [batch, heads, seq, d] around the call (`split` says this is that
    inner call)."""
    call = _Call.of(q.shape, k.shape, num_heads)
    if call.g is None:
        o, m, l = _fwd(*(split_heads(x, num_heads) for x in (q, k, v)),
                       sm_scale, causal, block_q, block_k, q_offset,
                       split=True, window=window)
        return merge_heads(o), m, l
    window = _live_window(window, causal, call.tq, q_offset)
    bq, bk, resident = _choose_blocks(*call.step_shapes, q.dtype.itemsize,
                                      block_q, block_k, window)
    telemetry.on_flash_attention_lowering(
        bq, bk, resident, "split" if split else call.g)
    telemetry.on_flash_attention_pairs("fwd", *(
        call.batch * call.heads * n for n in score_pairs(
            call.tq, call.tk, causal, q_offset, bq, bk, _STAIR, window)))
    if window:
        telemetry.on_flash_window_lowering("fwd", window, bq, bk)
    return _fwd_kernels(q, k, v, num_heads=num_heads, sm_scale=sm_scale,
                        causal=causal, q_offset=q_offset, bq=bq, bk=bk,
                        resident=resident, window=window)


@functools.partial(jax.jit, static_argnames=(
    "num_heads", "sm_scale", "causal", "q_offset", "bq", "bk", "resident",
    "window"))
def _fwd_kernels(q, k, v, *, num_heads, sm_scale, causal, q_offset, bq, bk,
                 resident, window=0):
    """The forward kernel at blocks already chosen; under `jax.jit` for
    the reason `_bwd_kernels` gives."""
    call = _Call.of(q.shape, k.shape, num_heads)
    kv_rows = call.tk if resident else bk
    stair = _stair_width(bq, bk, q_offset, _STAIR) if causal else None

    def kv_index(i, j):
        if causal:
            # a skipped block re-names the last visible one, so the
            # pipeline does not fetch what the kernel will not read
            last = lax.add(lax.mul(i, bq), q_offset + bq - 1)
            j = lax.min(j, lax.div(lax.max(last, 0), kv_rows))
        if window:
            # and so does one wholly before the block's first visible key
            first = lax.add(lax.mul(i, bq), q_offset - window + 1)
            j = lax.max(j, lax.div(lax.max(first, 0), kv_rows))
        return j

    def q_index(i, j):
        return i

    stats = jax.ShapeDtypeStruct(call.stats_shape, jnp.float32)
    pallas_call = functools.partial(
        pl.pallas_call,
        functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                          q_offset=q_offset, bk=bk, resident=resident,
                          d=call.d, stair=stair, window=window),
        grid=call.steps + (call.tq // bq, call.tk // kv_rows),
        in_specs=[call.rows(bq, q_index), call.rows(kv_rows, kv_index),
                  call.rows(kv_rows, kv_index)],
        out_specs=[call.rows(bq, q_index), call.stats(bq, q_index),
                   call.stats(bq, q_index)],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype), stats, stats],
        scratch_shapes=[
            # the accumulator [lanes, bq], its rows padded to 128 so
            # that the last step can transpose it; V transposed
            pltpu.VMEM((_pad_to_lanes(call.lanes), bq), jnp.float32),
            pltpu.VMEM((call.lanes, kv_rows), v.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel",
                "arbitrary" if resident else "parallel", "arbitrary")),
        # the trace shows which tiling ran; readers match the prefix
        name="flash_attention_fwd_q%d_k%d%s%s%s"
             % (bq, bk, "_kvres" if resident else "",
                _stair_suffix(stair, window), call.suffix),
    )
    o, m, l = _on_platform(pallas_call, q, k, v)
    rows = (call.batch, call.heads, call.tq)
    return o, m.reshape(rows), l.reshape(rows)


def _flash_fwd_rule(q, k, v, sm_scale, causal, block_q, block_k,
                    q_offset, num_heads, window=0):
    if sm_scale is None:
        sm_scale = _Call.of(q.shape, k.shape, num_heads).d ** -0.5
    o, m, l = _fwd(q, k, v, sm_scale, causal, block_q, block_k, q_offset,
                   num_heads, window=window)
    # a probability is exp(s - lse): of the two statistics the backward
    # reads only their log-sum-exp; a row that saw no key has l = 0
    lse = m + jnp.log(jnp.where(l > 0, l, 1.0))
    return (o, lse), (q, k, v, o, lse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def flash_attention_with_lse(q, k, v, sm_scale=None, causal=False,
                             block_q=None, block_k=None, q_offset=0,
                             num_heads=None, window=0):
    """`flash_attention` and, beside its result, the float32
    [batch, heads, Tq] log-sum-exp of every row of scores: what a
    caller that writes its own gradient (`ops/attention.py`) keeps for
    `_bwd`, so that its backward pass need not run the forward kernel
    again.  q, k, v: [batch, heads, seq, dim], or with `num_heads`
    [batch, seq, num_heads * dim], and the result laid out as they
    are.  With `window` W > 0 (and `causal`) query i attends keys
    max(0, i - W + 1) .. i."""
    return _flash_fwd_rule(q, k, v, sm_scale, causal, block_q, block_k,
                           q_offset, num_heads, window)[0]


def flash_attention(q, k, v, sm_scale=None, causal=False, block_q=None,
                    block_k=None, q_offset=0, window=0):
    """softmax(q k^T * scale [+ causal mask]) v without materializing
    the score matrix.  q,k,v: [B, H, T, D]; q_offset shifts the causal
    diagonal (used by ring attention where q is a sequence shard).
    A block left None is chosen by the kernel from the shapes; one that
    is named must divide its sequence.  `window` W > 0 bounds a causal
    query to its last W keys."""
    return flash_attention_with_lse(q, k, v, sm_scale, causal, block_q,
                                    block_k, q_offset, None, window)[0]


def _ring_slots(bq, bk, tq, window):
    """The query blocks one block of bk keys can reach under `window`,
    from the block its first key lies in to the one the window's lower
    edge crosses on its last key's side (one more where a key block can
    start inside a query block), and no more than the sequence has:
    what the ring kernel's inner grid axis walks and its ring of dq^T
    holds a slot for."""
    return min((max(bq, bk) + window - 2) // bq + 1, tq // bq)


def _bwd_step_bytes(bq, bk, d, itemsize, tq=None, heads=1, ring=0):
    """VMEM bytes one grid step of the backward holds, `d` the width of
    its `heads` heads together.  Every kernel holds four [bk, bq]
    float32 chunks (scores, probabilities, dp, ds) and the casts of two
    of them, and the lse and delta rows (padded and double-buffered as
    `_step_bytes` says).  With `tq` the one kernel that holds all tq
    queries of a head: their q, do and dq [tq, d] and the K, V, dk and
    dv tiles [bk, d], double-buffered; dq^T in float32 [d, tq], its
    rows padded to 128; K transposed [d, bk]; two float32 accumulators
    [bk, d] a head.  With `ring` the one kernel that walks the keys
    under a window: the same but for the queries' side, which is one
    [bq, d] tile of q, do and dq and `ring` slots [d, bq] of dq^T
    (`_ring_slots`).  With neither, the larger of the two kernels that
    walk: dK/dV holds the tiles of q, do, K, V, dk and dv and two
    accumulators a head, dQ those of q, do, dq, K and V, K transposed
    and one accumulator."""
    lanes = _pad_to_lanes(d)
    chunk = bq * bk * (4 * 4 + 2 * itemsize)
    accumulators = heads * 2 * 4 * lanes * bk
    if tq is not None or ring:
        held, slots = (tq, 1) if tq is not None else (bq, ring)
        return (chunk + 2 * itemsize * lanes * (3 * held + 4 * bk)
                + 4 * lanes * slots * held + itemsize * d * bk + accumulators
                + 2 * 2 * 8 * held * 4)
    dkv = 2 * itemsize * lanes * (2 * bq + 4 * bk) + accumulators
    dq = (2 * itemsize * lanes * (3 * bq + 2 * bk) + itemsize * d * bk
          + 4 * lanes * bq)
    return chunk + max(dkv, dq) + 2 * 2 * 8 * bq * 4


def _choose_bwd_blocks(q_shape, k_shape, itemsize, block_q=None,
                       block_k=None, heads=1, window=0, q_offset=0):
    """(block_q, block_k, form) for the backward of one call, from what
    `_choose_blocks` reads, the `heads` a grid step holds and, under a
    live `window`, `q_offset`: a named block is kept, and the chosen
    pair is the one that holds most scores at a time under the VMEM
    budget (the squarer of two that hold as many), in the first of
    three forms that has room for one.  "one": all of a head's queries
    and its dq beside the chunk, so that one kernel makes dq, dk and dv
    together.  "ring": under a window, the dq of the queries a block of
    keys can still reach (`_ring_slots`), so that one kernel makes all
    three as it walks the keys in order; a square call from position 0
    at blocks of whole lane blocks, one a multiple of the other, where
    the diagonal leaves a block of queries at the end of a block of
    keys.  "pair": one kernel dk and dv and another dq, each walking
    the other side a block a grid step.  Under a `window` no chosen
    block is wider than the window needs."""
    tq, d = q_shape[2], q_shape[3]
    tk = k_shape[2]
    qs = (_window_blocks(_candidates(tq), window) if block_q is None
          else [_block(tq, block_q, "query", q_shape)])
    ks = (_window_blocks(_candidates(tk), window) if block_k is None
          else [_block(tk, block_k, "key", k_shape)])
    named = block_q is not None and block_k is not None
    pairs = sorted(itertools.product(qs, ks),
                   key=lambda qk: (-qk[0] * qk[1], -min(qk), -qk[0]))
    forms = ["one", "pair"]
    if window and tq == tk and q_offset == 0:
        forms.insert(1, "ring")
    for form in forms:
        for bq, bk in pairs:
            if form == "ring" and (bq % _LANES or bk % _LANES
                                   or max(bq, bk) % min(bq, bk)):
                continue
            if _bwd_step_bytes(
                    bq, bk, d, itemsize, tq if form == "one" else None,
                    heads, _ring_slots(bq, bk, tq, window)
                    if form == "ring" else 0) <= _VMEM_BUDGET:
                return bq, bk, form
    if named:
        return pairs[0] + ("pair",)
    raise ValueError(
        "flash_attention: no block among %s tiles query length %d and "
        "key length %d of shapes %s and %s within %d bytes of VMEM in "
        "the backward"
        % (_BLOCKS[::-1], tq, tk, tuple(q_shape), tuple(k_shape),
           _VMEM_BUDGET))


def _bwd_matmul(a, b, rhs_contracts, widen):
    """a . b in float32, `a` first rounded to b's type (p and ds enter
    their second products in the compute type, as the forward's p
    does).  `widen` then takes both to float32, which changes no
    product: XLA's CPU runtime, which runs the interpreter's dots,
    has none for bfloat16 operands."""
    a = lax.convert_element_type(a, b.dtype)
    if widen:
        a, b = (lax.convert_element_type(x, jnp.float32) for x in (a, b))
    return _matmul(a, b, rhs_contracts)


def _bwd_chunk(q, k, v, do, lse, delta, sm_scale, behind, widen, window=0):
    """Probabilities and ds of one chunk of one head, both
    [keys, queries] float32: p = exp(s - lse), ds = p * (dp - delta),
    from the scores k q^T and dp = v do^T; lse and delta are
    [1, queries] rows.  Where the tiles hold several heads, k and v
    come with the other heads' lanes zeroed (`_only_head`).  `behind`
    is None where every query sees every key of the chunk, else how far
    the chunk's first key lies ahead of its first query, as `_see`
    takes it: p is 0 where the query does not see the key (under
    `window`, where it lies before the query's last `window` keys
    too)."""
    s = lax.mul(_bwd_matmul(k, q, 1, widen), sm_scale)
    p = lax.exp(lax.sub(s, lse))
    if behind is not None:
        p = _see(p, behind, 0, window)
    ds = lax.mul(p, lax.sub(_bwd_matmul(v, do, 1, widen), delta))
    return p, ds


def _add_dkv(dk_scr, dv_scr, h, p, ds, q, do, widen, keys=slice(None)):
    """Head h's p do and ds q into the rows `keys` of its own
    accumulators, all the lanes wide: the lanes of the tile's other
    heads gather products nobody reads, at no cost to the MXU, which
    pads a narrower result."""
    dv_scr[h, keys] = lax.add(dv_scr[h, keys], _bwd_matmul(p, do, 0, widen))
    dk_scr[h, keys] = lax.add(dk_scr[h, keys], _bwd_matmul(ds, q, 0, widen))


def _side_by_side(scr, d):
    """[rows, lanes] with head h's d lanes from `scr[h]`, of the
    per-head accumulators [heads, rows, lanes]."""
    out = scr[0]
    lane = lax.broadcasted_iota(jnp.int32, out.shape, 1)
    for h in range(1, scr.shape[0]):
        out = lax.select(lax.ge(lane, h * d), scr[h], out)
    return out


def _write_dkv(dk_ref, dv_ref, dk_scr, dv_scr, sm_scale, d):
    dk_ref[...] = lax.convert_element_type(
        lax.mul(_side_by_side(dk_scr, d), sm_scale), dk_ref.dtype)
    dv_ref[...] = lax.convert_element_type(_side_by_side(dv_scr, d),
                                           dv_ref.dtype)


def _write_dq(dq_ref, dqt_scr, sm_scale):
    """dq from dq^T [lanes padded to 128, queries] in float32 scratch."""
    dq_ref[...] = lax.convert_element_type(
        lax.transpose(lax.mul(dqt_scr[...], sm_scale),
                      (1, 0))[:, :dq_ref.shape[1]], dq_ref.dtype)


def _fold_three(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dqt_ref,
                kt_scr, dk_scr, dv_scr, rows, these, lead, edge, *, sm_scale,
                widen, d):
    """The keys `these` of a grid step's block meet the queries `rows`
    of its q tile, for every head of the step's: dv += p do and
    dk += ds q into the heads' accumulators, dq^T += k^T ds into
    `dqt_ref` [lanes, queries]; `lead` as `_see` takes it, None where
    every query sees every key; `edge` the window where its lower edge
    crosses the chunk too.  Five products: the scores and dp are made
    once for all three gradients."""
    lanes = k_ref.shape[1]
    q, do = q_ref[rows, :], do_ref[rows, :]
    k, v = k_ref[these, :], v_ref[these, :]

    def one(h, head, stat):
        own = _head_lanes(h, d, lanes, k.dtype)
        p, ds = _bwd_chunk(
            q, _only_head(k, own), _only_head(v, own), do,
            lse_ref[stat, rows], delta_ref[stat, rows], sm_scale, lead,
            widen, edge)
        ds = lax.convert_element_type(ds, q.dtype)
        _add_dkv(dk_scr, dv_scr, h, p, ds, q, do, widen, these)
        dqt_ref[head, rows] = lax.add(
            dqt_ref[head, rows],
            _bwd_matmul(kt_scr[head, these], ds, 0, widen))

    _each_head(lanes // d, d, one)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                dk_ref, dv_ref, dqt_scr, kt_scr, dk_scr, dv_scr, *,
                sm_scale, causal, q_offset, widen, bq, d, stair, window=0):
    """One (batch, heads, k_block) grid step of the whole backward: the
    step's bk keys meet the queries, all in VMEM, bq at a time and for
    every head of the step's, from the first chunk that sees a key of
    the block: dv += p do, dk += ds q, and dq^T += k^T ds into
    the float32 [lanes, Tq] scratch, which the k_block axis
    (sequential) fills and its last step scales, transposes back and
    writes.  Five products a chunk (`_fold_three`).  Chunks are
    [keys, queries] as in `_fwd_kernel`, so the row statistics are
    lane-dense rows and every product takes its operands as they are
    but dq's, which is why K is transposed (once a step) and dq
    accumulates transposed: head h has rows [h * d, (h + 1) * d) of
    both."""
    bk, tq = k_ref.shape[0], q_ref.shape[0]
    j = pl.program_id(2)
    kt_scr[...] = lax.transpose(k_ref[...], (1, 0))
    dk_scr[...] = lax.full(dk_scr.shape, 0, jnp.float32)
    dv_scr[...] = lax.full(dv_scr.shape, 0, jnp.float32)

    @pl.when(lax.eq(j, 0))
    def _init():
        dqt_scr[...] = lax.full(dqt_scr.shape, 0, jnp.float32)

    # how far this step's first key is ahead of the first query
    behind = lax.sub(lax.mul(j, bk), q_offset)

    def _fold(c, key, keys, query, lead, edge=0):
        """The block's `keys` keys from its `key`-th meet the queries of
        chunk c from the chunk's `query`-th on."""
        if tq == bq:
            # one chunk, read whole: a block that is the whole of a
            # ragged sequence has no aligned slice
            rows = slice(query, bq) if query else slice(None)
        else:
            rows = pl.ds(pl.multiple_of(lax.add(lax.mul(c, bq), query),
                                        math.gcd(bq, query)), bq - query)
        _fold_three(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dqt_scr,
                    kt_scr, dk_scr, dv_scr, rows, slice(key, key + keys),
                    lead, edge, sm_scale=sm_scale, widen=widen, d=d)

    def _fold_whole(c):
        _fold(c, 0, bk, 0, None)

    def _fold_masked(c):
        _fold_crossed(functools.partial(_fold, c),
                      lax.sub(behind, lax.mul(c, bq)), bq, bk, q_offset,
                      stair)

    chunks = tq // bq
    if causal:
        # query chunks before the diagonal are never touched; those it
        # crosses are masked, or folded as a staircase; those after it
        # see the block whole
        seen = lax.min(lax.div(lax.max(behind, 0), bq), chunks)
        whole = lax.min(lax.div(lax.max(lax.add(behind, bk + bq - 2), 0),
                                bq), chunks)
        last = chunks
        if window:
            # the lower edge is the diagonal `window` queries on: the
            # chunks it crosses are compared against both edges, those
            # wholly past it never touched
            on = lax.add(behind, window)
            last = lax.min(lax.div(lax.max(on, 0), bq), chunks)
            whole = lax.min(whole, last)
            _fold_chunks(
                lambda c: _fold(c, 0, bk, 0, lax.sub(behind, lax.mul(c, bq)),
                                window),
                last,
                lax.min(lax.div(lax.max(lax.add(on, bk + bq - 2), 0), bq),
                        chunks))
        _fold_chunks(_fold_masked, seen, whole)
        _fold_chunks(_fold_whole, whole, last)
    else:
        _fold_chunks(_fold_whole, 0, chunks)
    _write_dkv(dk_ref, dv_ref, dk_scr, dv_scr, sm_scale, d)
    pl.when(lax.eq(j, lax.sub(pl.num_programs(2), 1)))(
        lambda: _write_dq(dq_ref, dqt_scr, sm_scale))


def _bwd_ring_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                     dk_ref, dv_ref, ring_scr, kt_scr, dk_scr, dv_scr, *,
                     sm_scale, widen, d, stair, window, chunks):
    """One (batch, heads, k_block, reach) grid step of the whole
    backward of a square causal call under a `window`, where a head's
    queries do not fit VMEM: the step's bk keys meet one block of bq
    queries, the `reach`-th from the block the first key lies in, as
    `_bwd_kernel`'s meet a chunk (`_fold_three`: five products; the
    diagonal's chunks as a staircase, the lower edge's whole under both
    compares).  Both axes are sequential.  dk and dv gather over the
    reach axis, which ends on the block the lower edge leaves the keys
    by.  dq^T gathers over the k_block axis in a ring of float32
    [lanes, bq] slots, block i of the queries in slot i mod their
    number: a block's slot is zeroed at the first block of keys that
    reaches it and written out as dq at the last, the one its own
    diagonal ends in, by when the block whose slot it takes next has
    not been reached.  A step past the window's reach or the sequence's
    end re-names the last block fetched and folds nothing."""
    bq, bk = q_ref.shape[0], k_ref.shape[0]
    j, r = pl.program_id(2), pl.program_id(3)

    @pl.when(lax.eq(r, 0))
    def _init():
        kt_scr[...] = lax.transpose(k_ref[...], (1, 0))
        dk_scr[...] = lax.full(dk_scr.shape, 0, jnp.float32)
        dv_scr[...] = lax.full(dv_scr.shape, 0, jnp.float32)

    i = lax.add(lax.div(lax.mul(j, bk), bq), r)
    dqt_ref = ring_scr.at[lax.rem(i, ring_scr.shape[0])]
    # how far the block's first key is ahead of the chunk's first query
    lead = lax.sub(lax.mul(j, bk), lax.mul(i, bq))
    live = lax.bitwise_and(lax.lt(i, chunks), lax.gt(lead, 1 - bk - window))
    # the lower edge crosses the chunk; else the diagonal does, or neither
    edge = lax.lt(lax.add(lead, window), bq)
    crossed = lax.bitwise_and(lax.bitwise_not(edge), lax.gt(lead, 1 - bk))

    def _fold(key, keys, query, lead, edge=0):
        _fold_three(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dqt_ref,
                    kt_scr, dk_scr, dv_scr, slice(query, bq),
                    slice(key, key + keys), lead, edge, sm_scale=sm_scale,
                    widen=widen, d=d)

    def _when(which):
        return pl.when(lax.bitwise_and(live, which))

    # the block of keys before this one did not reach the chunk's queries
    @_when(lax.bitwise_or(lax.eq(j, 0), lax.le(lead, 1 - window)))
    def _enter():
        dqt_ref[...] = lax.full(dqt_ref.shape, 0, jnp.float32)

    _when(edge)(lambda: _fold(0, bk, 0, lead, window))
    _when(crossed)(lambda: _fold_crossed(_fold, lead, bq, bk, 0, stair))
    _when(lax.bitwise_not(lax.bitwise_or(edge, crossed)))(
        lambda: _fold(0, bk, 0, None))
    # the queries' block ends where this block of keys does, or before
    _when(lax.ge(lead, bq - bk))(
        lambda: _write_dq(dq_ref, dqt_ref, sm_scale))
    pl.when(lax.eq(r, lax.sub(pl.num_programs(3), 1)))(
        lambda: _write_dkv(dk_ref, dv_ref, dk_scr, dv_scr, sm_scale, d))


def _fold_where_seen(fold, causal, behind, bq, bk, window=0):
    """`fold(masked)` for the one [bk, bq] chunk of a walking kernel's
    grid step, whose first key lies `behind` ahead of its first query:
    unmasked where every query sees every key, masked and whole where
    the diagonal (or, `window` keys behind it, the window's lower edge)
    crosses the chunk (these kernels take no staircase), not at all
    above the one or wholly before the other."""
    if not causal:
        return fold(False)
    whole = lax.le(lax.add(behind, bk - 1), 0)
    if window:
        whole = lax.bitwise_and(whole, lax.ge(lax.add(behind, window), bq))
    pl.when(whole)(lambda: fold(False))
    crossed = lax.bitwise_and(lax.bitwise_not(whole),
                              lax.le(behind, bq - 1))
    if window:
        crossed = lax.bitwise_and(
            crossed, lax.gt(lax.add(behind, window + bk - 1), 0))
    pl.when(crossed)(lambda: fold(True))


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, sm_scale, causal,
                    q_offset, widen, d, window=0):
    """One (batch, heads, k_block, q_block) grid step of dK and dV
    where a head's queries do not fit VMEM: dv += p do and dk += ds q
    in float32 scratch for the step's bk keys and bq queries, a head at
    a time.  The q_block axis is innermost and sequential: the scratch
    is zeroed on its first step and written, dk scaled, on its last."""
    (bq, lanes), bk = q_ref.shape, k_ref.shape[0]
    j, i = pl.program_id(2), pl.program_id(3)

    @pl.when(lax.eq(i, 0))
    def _init():
        dk_scr[...] = lax.full(dk_scr.shape, 0, jnp.float32)
        dv_scr[...] = lax.full(dv_scr.shape, 0, jnp.float32)

    behind = lax.sub(lax.mul(j, bk), lax.add(lax.mul(i, bq), q_offset))

    def _fold(masked):
        q, do = q_ref[...], do_ref[...]
        for h in range(lanes // d):
            own = _head_lanes(h, d, lanes, k_ref.dtype)
            p, ds = _bwd_chunk(
                q, _only_head(k_ref[...], own),
                _only_head(v_ref[...], own), do, lse_ref[h:h + 1, :],
                delta_ref[h:h + 1, :], sm_scale,
                behind if masked else None, widen, window)
            _add_dkv(dk_scr, dv_scr, h, p, ds, q, do, widen)

    _fold_where_seen(_fold, causal, behind, bq, bk, window)
    pl.when(lax.eq(i, lax.sub(pl.num_programs(3), 1)))(
        lambda: _write_dkv(dk_ref, dv_ref, dk_scr, dv_scr, sm_scale, d))


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   acc_scr, *, sm_scale, causal, q_offset, widen, d,
                   window=0):
    """One (batch, heads, q_block, k_block) grid step of dQ where a
    head's keys do not fit VMEM, shaped as `_fwd_kernel`'s walk:
    dq^T += k^T ds as [lanes, bq] float32 for the step's bq queries and
    bk keys, a head (d rows) at a time; the k_block axis is innermost
    and sequential, and its last step scales dq, transposes it back and
    writes it."""
    (bq, lanes), bk = q_ref.shape, k_ref.shape[0]
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(lax.eq(j, 0))
    def _init():
        acc_scr[...] = lax.full(acc_scr.shape, 0, jnp.float32)

    behind = lax.sub(lax.mul(j, bk), lax.add(lax.mul(i, bq), q_offset))

    def _fold(masked):
        k = k_ref[...]
        kt = lax.transpose(k, (1, 0))
        for h in range(lanes // d):
            head = slice(h * d, (h + 1) * d)
            own = _head_lanes(h, d, lanes, k.dtype)
            _, ds = _bwd_chunk(
                q_ref[...], _only_head(k, own),
                _only_head(v_ref[...], own), do_ref[...],
                lse_ref[h:h + 1, :], delta_ref[h:h + 1, :], sm_scale,
                behind if masked else None, widen, window)
            acc_scr[head] = lax.add(
                acc_scr[head],
                _bwd_matmul(lax.slice_in_dim(kt, h * d, (h + 1) * d),
                            lax.convert_element_type(ds, k.dtype), 0,
                            widen))

    _fold_where_seen(_fold, causal, behind, bq, bk, window)
    pl.when(lax.eq(j, lax.sub(pl.num_programs(3), 1)))(
        lambda: _write_dq(dq_ref, acc_scr, sm_scale))


def row_sums(do, o, num_heads=None):
    """`delta`: the float32 [batch, heads, seq] sums over a head's
    width of do * o, both laid out as the attention's operands are
    ([batch, heads, seq, dim], or with `num_heads`
    [batch, seq, num_heads * dim])."""
    prod = do.astype(jnp.float32) * o.astype(jnp.float32)
    if num_heads is None:
        return jnp.sum(prod, axis=-1)
    b, t, width = prod.shape
    return jnp.sum(prod.reshape(b, t, num_heads, width // num_heads),
                   axis=-1).transpose(0, 2, 1)


def _flash_bwd_rule(sm_scale, causal, block_q, block_k, q_offset,
                    num_heads, window, res, cotangents):
    """The flash-attention VJP as kernels that recompute the
    probabilities chunk by chunk from the forward's statistics:
    dv = p^T do; dp = do v^T; ds = p * (dp - rowsum(do * o));
    dq = ds k; dk = ds^T q.  Where a head's queries and its dq fit VMEM
    one kernel holds a block of keys and makes all three (five products
    a chunk); where they do not and the call has a window, one kernel
    walks the blocks of keys and makes all three, the dq of the blocks
    of queries the keys can still reach in a ring in VMEM (five); else
    one kernel holds a block of keys and gathers dk and dv over the
    queries that see it and another holds a block of queries and
    gathers dq over the keys it sees (seven).
    Products take their operands in the type they arrive in and
    accumulate in float32; nothing the size of the score square reaches
    HBM.  A cotangent of the log-sum-exp enters with the row sums:
    d lse / d s = p, so ds = p * (dp - (rowsum(do * o) - dlse))."""
    q, k, v, o, lse = res
    do, dlse = cotangents
    with jax.named_scope(BWD_SCOPE):
        return _bwd(q, k, v, do, lse, row_sums(do, o, num_heads) - dlse,
                    sm_scale, causal, block_q, block_k, q_offset,
                    num_heads, window=window)


def _bwd(q, k, v, do, lse, delta, sm_scale, causal, block_q, block_k,
         q_offset=0, num_heads=None, split=False, window=0):
    """dq, dk, dv of one call, laid out as q, k and v are (as `_fwd`
    takes them), from the forward's row statistics `lse` and the row
    sums `delta` of do * o (`row_sums`), both float32
    [batch, heads, Tq], at blocks chosen here as the forward chooses
    its own: what the custom VJP above and the `flash_attention` op's
    gradient (`ops/attention.py`), which saved `lse`, both end in."""
    call = _Call.of(q.shape, k.shape, num_heads)
    if call.g is None:
        grads = _bwd(*(split_heads(x, num_heads) for x in (q, k, v, do)),
                     lse, delta, sm_scale, causal, block_q, block_k,
                     q_offset, split=True, window=window)
        return tuple(merge_heads(g) for g in grads)
    if sm_scale is None:
        sm_scale = call.d ** -0.5
    window = _live_window(window, causal, call.tq, q_offset)
    bq, bk, form = _choose_bwd_blocks(
        *call.step_shapes, q.dtype.itemsize, block_q, block_k, call.g,
        window, q_offset)
    for kernel in _BWD_KERNELS[form]:
        telemetry.on_flash_attention_bwd_lowering(
            kernel, bq, bk, "split" if split else call.g)
        telemetry.on_flash_attention_pairs("bwd", *(
            call.batch * call.heads * n for n in score_pairs(
                call.tq, call.tk, causal, q_offset, bq, bk,
                None if form == "pair" else _STAIR, window)))
        if window:
            telemetry.on_flash_window_lowering(kernel, window, bq, bk)
    return _bwd_kernels(q, k, v, do, lse, delta, num_heads=num_heads,
                        sm_scale=sm_scale, causal=causal, q_offset=q_offset,
                        bq=bq, bk=bk, form=form, window=window)


@functools.partial(jax.jit, static_argnames=(
    "num_heads", "sm_scale", "causal", "q_offset", "bq", "bk", "form",
    "window"))
def _bwd_kernels(q, k, v, do, lse, delta, *, num_heads, sm_scale, causal,
                 q_offset, bq, bk, form, window=0):
    """dq, dk, dv from the row statistics and do, at blocks and in the
    form already chosen (`_choose_bwd_blocks`).  Under `jax.jit` so
    that a program holding the same attention many times (one a layer)
    traces these kernels once, and the build's shape inference, the
    executor's program and the functional step share that trace: a
    kernel body is some hundred primitives and a step program would
    trace it twice an op (PERF.md section 6, PR 26)."""
    call = _Call.of(q.shape, k.shape, num_heads)
    tq, tk, lanes = call.tq, call.tk, call.lanes
    operands = [q, k, v, do] \
        + [x.reshape(call.stats_shape) for x in (lse, delta)]
    stair = (_stair_width(bq, bk, q_offset, _STAIR)
             if causal and form != "pair" else None)
    name = "flash_attention_bwd%%s_q%d_k%d%s%s" % (
        bq, bk, _stair_suffix(stair, window), call.suffix)
    accumulator = pltpu.VMEM((call.g, bk, lanes), jnp.float32)
    masked = {"causal": causal, "q_offset": q_offset}

    def transposed(queries, *slots):
        # dq^T of `queries` queries, its rows padded to 128 so that the
        # step that writes dq can transpose it
        return pltpu.VMEM(slots + (_pad_to_lanes(lanes), queries),
                          jnp.float32)

    def pallas_call(kernel, interpret, **args):
        return pl.pallas_call(
            functools.partial(kernel, sm_scale=sm_scale, widen=interpret,
                              d=call.d, window=window),
            interpret=interpret, **args)

    def like(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)

    def held(a, b):
        return a

    if form == "one":
        def whole(j):
            return 0

        def block(j):
            return j

        queries, keys = call.rows(tq, whole), call.rows(bk, block)
        return tuple(_on_platform(functools.partial(
            pallas_call,
            functools.partial(_bwd_kernel, bq=bq, stair=stair, **masked),
            grid=call.steps + (tk // bk,),
            in_specs=[queries, keys, keys, queries,
                      call.stats(tq, whole), call.stats(tq, whole)],
            out_specs=[queries, keys, keys],
            out_shape=[like(q), like(k), like(v)],
            scratch_shapes=[transposed(tq), pltpu.VMEM((lanes, bk), k.dtype),
                            accumulator, accumulator],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            name=name % ""), *operands))

    if form == "ring":
        slots = _ring_slots(bq, bk, tq, window)

        def diagonal(j):
            return lax.div(lax.mul(j, bk), bq)

        def reached(j, r):
            # a step past the last block the keys reach, or the last
            # there is, re-names that one: it is not fetched again
            last = lax.div(lax.add(lax.mul(j, bk), bk + window - 2), bq)
            return lax.min(lax.add(diagonal(j), r),
                           lax.min(last, tq // bq - 1))

        def leaving(j, r):
            # the blocks whose diagonal ends in these keys, each while
            # it is folded, then the last of them: written once each
            return lax.add(diagonal(j), lax.min(r, max(bk // bq, 1) - 1))

        queries, stats = call.rows(bq, reached), call.stats(bq, reached)
        keys = call.rows(bk, held)
        return tuple(_on_platform(functools.partial(
            pallas_call,
            functools.partial(_bwd_ring_kernel, stair=stair, chunks=tq // bq),
            grid=call.steps + (tk // bk, slots),
            in_specs=[queries, keys, keys, queries, stats, stats],
            out_specs=[call.rows(bq, leaving), keys, keys],
            out_shape=[like(q), like(k), like(v)],
            scratch_shapes=[transposed(bq, slots),
                            pltpu.VMEM((lanes, bk), k.dtype),
                            accumulator, accumulator],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary",
                                     "arbitrary")),
            name=name % "_ring"), *operands))

    def q_walked(j, i):
        if causal:
            # a q block before the first that sees this k block
            # re-names that one: it is fetched once, not per step
            first = lax.div(lax.max(lax.sub(lax.mul(j, bk), q_offset), 0),
                            bq)
            i = lax.max(i, lax.min(first, tq // bq - 1))
        if window:
            # and so does one past the last that sees it
            last = lax.add(lax.mul(j, bk), bk + window - 2 - q_offset)
            i = lax.min(i, lax.div(lax.max(last, 0), bq))
        return i

    def k_walked(i, j):
        if causal:
            last = lax.add(lax.mul(i, bq), q_offset + bq - 1)
            j = lax.min(j, lax.div(lax.max(last, 0), bk))
        if window:
            first = lax.add(lax.mul(i, bq), q_offset - window + 1)
            j = lax.max(j, lax.div(lax.max(first, 0), bk))
        return j

    walk = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel",
                             "arbitrary"))
    queries, stats = call.rows(bq, q_walked), call.stats(bq, q_walked)
    keys = call.rows(bk, held)
    dk, dv = _on_platform(functools.partial(
        pallas_call, functools.partial(_bwd_dkv_kernel, **masked),
        grid=call.steps + (tk // bk, tq // bq),
        in_specs=[queries, keys, keys, queries, stats, stats],
        out_specs=[keys, keys], out_shape=[like(k), like(v)],
        scratch_shapes=[accumulator, accumulator], compiler_params=walk,
        name=name % "_dkv"), *operands)
    queries, stats = call.rows(bq, held), call.stats(bq, held)
    keys = call.rows(bk, k_walked)
    dq = _on_platform(functools.partial(
        pallas_call, functools.partial(_bwd_dq_kernel, **masked),
        grid=call.steps + (tq // bq, tk // bk),
        in_specs=[queries, keys, keys, queries, stats, stats],
        out_specs=queries, out_shape=like(q),
        scratch_shapes=[transposed(bq)],
        compiler_params=walk, name=name % "_dq"), *operands)
    return dq, dk, dv


flash_attention_with_lse.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def reference_attention(q, k, v, sm_scale=None, causal=False, q_offset=0):
    """Dense O(T^2)-memory attention for parity tests."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if causal:
        Tq, Tk = q.shape[2], k.shape[2]
        mask = (q_offset + jnp.arange(Tq))[:, None] >= jnp.arange(Tk)
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
