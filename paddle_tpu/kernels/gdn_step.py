"""One decode step of the gated delta rule (ops/linear_attention.py) as
a Pallas TPU kernel that reads and writes every head's state once, in
place.

    step(q[B, Hk, Dk], k[B, Hk, Dk], v[B, H, Dv], g[B, H], beta[B, H],
         state[B, H, Dk, Dv]) -> (out [B, H, Dv], state')
    (state[B, H / p, Dk, p Dv] where p heads lie side by side)

    S  = exp(g) S;  r = S^T k;  S = S + k (beta (v - r))^T;  o = S^T q

or, under a gate a key channel (g[B, H, Dk]: Kimi Delta Attention's),
`S = diag(exp(g)) S`, row d of a head's state by its own exp(g[d]); the
other three lines as they are.

q and k come normed and scaled, float32; value head j reads key head
j // (H / Hk).  A step of Qwen3-Next's share moves 2.1 MB of float32
state a row and layer in and out again, and nothing else of its size:
the step is bound by those bytes, so the state must cross HBM once each
way.  Plain `jax.numpy` makes the decayed state, the read, the update
and the second read as fusions of their own wherever the compiler cuts
them; here a grid step holds a block of state in VMEM ([rows, heads,
Dk, Dv] float32: `choose_block`), works it where it lies, and
`input_output_aliases` hands the state's buffer back as the result: a
decoder's scan carries it without a copy.

How the blocks travel is what the kernel's time is made of (PERF.md
section 6, PR 64; `scripts/gdn_step_bench.py`).  Until PR 64 the
compiler's own pipeline moved them (`pl.BlockSpec`, two buffers, a
block in and a block out at once) and a call at [128, 32, 128, 128]
took 0.82 ms, 81% of the HBM peak, whatever the block (1 to 16 MiB):
not the grid steps' boundaries but the HBM itself, which a v5e gives
to a stream of reads at 94% of its peak, to a stream of writes in
one-chunk copies at 82%, and to both at once at 5% less than to one
after the other.  So the state stays in HBM (`memory_space=pl.ANY`)
and the kernel starts its own copies over `_BUFFERS` blocks of VMEM:
**reads and writes take turns**, block i + 1 coming in beside the first
half of block i's work and block i - 1 going out beside the second (the
way out starts when all but the last of `_READ_CUTS` slices of the way
in have landed: the switch's latency hides under that slice); and **a
block goes out as many copies of several chunks each**, a slice of one
head's sublanes over all the block's rows (`_WRITE_CUTS` a head: chunks
megabytes apart in one copy write at 93%, the same bytes as one chunk a
copy at 82).  A turn costs about a microsecond whatever it moves, and
the first block's way in and the last one's way out lie open, so a
block is `_STEP_BYTES` = 8 MiB (0.74 ms a call, 90%; 4 MiB 0.76, 16
MiB 0.745), not the 1 MiB that the pipeline's grid steps were content
with.  The grid is sequential ("arbitrary"): a step's copies are its
neighbours' blocks.  The body's text is a quarter of a block's key
heads, which quarter a loop's index (32 heads unrolled lowered 0.6 s
longer than 16 did, six times a cell's start).

Everything is on the vector unit.  A head's products are one row of
results each (`S^T k`, `S^T q`: a [1, Dk] x [Dk, Dv] product would load
the state into the MXU as weights for one row), so they are sums over
the sublanes of `S * k` with k a column; the update is the column k
times the row delta.  A column is made from the row it arrives as
without a transpose: the row broadcast down the sublanes, kept on the
diagonal, summed over the lanes.  One pair of columns serves the value
heads that share a key head.  The decay, beta and beta * v arrive as
rows [1, Dv] (the two scalars broadcast along the lanes by the caller:
2 x 16 KB a row of the batch beside 2.1 MB of state), so no scalar is
read out of a vector.  A gate a key channel arrives as the row [1, Dk]
it is (the operand's shape is the broadcast scalar's, Dk = Dv) and is
made a column like k and q, a third a value head: the state's row d
times element d.

Which shapes it takes (`choose_block`): a float32 state of 128 x 128 a
head (the lanes, and a column the sublanes tile) under either gate; a
block's value heads hold whole key heads and tile the sublanes of the
[Hk, Dk] and [H, Dv] operands (all heads where a row's state fits
`_STEP_BYTES`), and its rows divide the batch's.  And, under a gate a
head with a key head a value head, **a state that is not square**: a
key_dim of whole sublane tiles (96: twelve) and, along the lanes, whole
lane blocks.  192 values a head are a lane block and a half, and an
array of 192 lanes lies in HBM as 256 (a step would move 2 x 2.95 MB a
row and layer of Olmo-Hybrid's 30 heads, not 2 x 2.21), so the program
lays **two heads side by side** (`state_pack`, `pack_state`: [rows, 15,
96, 384], three whole lane blocks a unit, no lane of it padding) and the
kernel works a unit as it lies: the pair's v, decay, beta and output are
one row [1, 384] each (the operands [B, H, Dv] read as [B, H / 2, 2
Dv]), and the key column beside each half of the lanes is chosen by a
lane select between the pair's two columns, so `S * k`, the update and
`S * q` stay one pass over the unit on the vector unit.  q and k arrive
zero-padded to a whole lane block (96 -> 128: 15 KB a row) and a column
is made on a [96, 128] diagonal.  At [128, 30, 96, 192] a call takes
0.790 ms in blocks of 4 rows (8.4 MiB, `_WIDE_STEP_BYTES`; 2 rows 0.815,
8 rows 0.792), 89.8% of the HBM peak on the state's own bytes; the same
state zero-padded to 256 values a head takes 1.057 ms, 67% (PERF.md
section 6, PR 67; `scripts/gdn_step_bench.py --key-dim 96 --value-dim
192 --pad-to 0 --pad-to 256`).  The op asks, and keeps its plain path
otherwise.

Lowered for the TPU this is a Mosaic kernel named `gdn_step_r<rows>_h<
heads>_b<rows a step>` (rows of the batch, value heads and rows a grid
step: a trace's `device_ops` row says which block ran) under a gate a
head and `kda_step_r<rows>_h<heads>_b<rows a step>` under a gate a key
channel (one body, the decay a row or a column; a trace's readers tell
Gated DeltaNet's steps from KDA's by the prefix); a state that is not
128 x 128 a head says its shape, `gdn_step_r<rows>_h<heads>_k<key_dim>_
v<value_dim>_b<rows a step>` (the prefix stays).  Lowered for any
other platform the caller's plain step runs in its place (`step`'s
`plain`, as kernels/ssd.py's entries take theirs; `interpret=True` runs
the kernel's body, copies and all, under the Pallas interpreter: tests).
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_SUBLANES = 8
_HEAD_BYTES = _LANES * _LANES * 4
# the bytes of state a grid step takes in (and a step later gives out)
_STEP_BYTES = 8 << 20
# the same for a state that is not 128 x 128 a head, whose rows are no
# power of two of bytes (Olmo-Hybrid's 30 heads of 96 x 192: 2.11 MiB a
# row, so 4 rows are 8.44 MiB; scripts/gdn_step_bench.py --key-dim 96
# --value-dim 192, PERF.md section 6, PR 67)
_WIDE_STEP_BYTES = 9 << 20
# a block comes in, one is worked where it lies, one goes out
_BUFFERS = 3
# what a call may hold in VMEM beside its blocks of state: the operands'
# and the output's buffers and the compiler's own scratch
_VMEM_BESIDE = 4 << 20
# A block comes in as `_READ_CUTS` copies, a slice of every head's
# sublanes each, and the way out starts when all but one have landed.
_READ_CUTS = 8
# A block goes out as `_WRITE_CUTS` copies a head, a slice of the head's
# sublanes over all the block's rows each: several chunks a copy,
# megabytes apart.
_WRITE_CUTS = 4


def state_pack(heads, value_dim):
    """How many value heads a program lays side by side along the lanes
    of one [key_dim, pack * value_dim] tile of state, so that the state
    is not padded in HBM (an array's last axis is stored in whole blocks
    of 128 lanes: 192 values a head would be stored as 256): 1 where
    `value_dim` fills its lane blocks, else the fewest heads (2 or 4)
    that together do and divide `heads`; 1 where none does."""
    if value_dim % _LANES:
        for pack in (2, 4):
            if (pack * value_dim) % _LANES == 0 and heads % pack == 0:
                return pack
    return 1


def pack_state(state, pack):
    """[rows, heads, key_dim, value_dim] -> [rows, heads / pack, key_dim,
    pack * value_dim]: heads pack * u .. pack * u + pack - 1 side by
    side in unit u, head after head along the lanes."""
    if pack == 1:
        return state
    rows, heads, key_dim, value_dim = state.shape
    return state.reshape(rows, heads // pack, pack, key_dim, value_dim) \
        .transpose(0, 1, 3, 2, 4) \
        .reshape(rows, heads // pack, key_dim, pack * value_dim)


def unpack_state(state, pack):
    """`pack_state`'s inverse: the state as the recurrence has it."""
    if pack == 1:
        return state
    rows, units, key_dim, wide = state.shape
    return state.reshape(rows, units, key_dim, pack, wide // pack) \
        .transpose(0, 1, 3, 2, 4) \
        .reshape(rows, units * pack, key_dim, wide // pack)


def choose_block(rows, heads, key_heads, key_dim, value_dim, dtype, pack=1,
                 channel=False):
    """(rows, value heads) a grid step holds, or None where the kernel
    does not take the shape.

    128 x 128 a head: the heads first, the most that divide `heads`,
    hold whole key heads, tile the operands' sublanes (a multiple of 8
    key heads, or all of them) and keep one row's state within
    `_STEP_BYTES`; then the most rows that divide `rows` and keep the
    block's within it.

    Any other head, with `pack` heads side by side in the state
    (`state_pack`), under a gate a head alone (not `channel`): a key
    head a value head, `key_dim` whole sublane tiles and `pack *
    value_dim` whole lane blocks; all the heads of the most rows that
    divide `rows` and keep the block within `_WIDE_STEP_BYTES`."""
    if jnp.dtype(dtype) != jnp.float32 or heads % key_heads:
        return None
    if (key_dim, value_dim, pack) != (_LANES, _LANES, 1):
        row_bytes = heads * key_dim * value_dim * 4
        if channel or heads != key_heads or heads % pack \
                or key_dim % _SUBLANES \
                or (pack * value_dim) % _LANES \
                or row_bytes > _WIDE_STEP_BYTES:
            return None
        return max(n for n in range(1, rows + 1)
                   if rows % n == 0
                   and n * row_bytes <= _WIDE_STEP_BYTES), heads
    group = heads // key_heads
    room = max(_STEP_BYTES // _HEAD_BYTES, group)
    held = max((n for n in range(group, min(heads, room) + 1, group)
                if heads % n == 0
                and (n == heads or (n // group) % 8 == 0)), default=0)
    if not held:
        return None
    return max(n for n in range(1, rows + 1)
               if rows % n == 0 and n * held <= max(room, held)), held


def vmem_limit(block, head_bytes=_HEAD_BYTES):
    """The VMEM a call over `block` may take: `_BUFFERS` blocks of state
    (`head_bytes` a value head) and `_VMEM_BESIDE`."""
    return _BUFFERS * block[0] * block[1] * head_bytes + _VMEM_BESIDE


def _cuts(size, most):
    """`size` sublanes in the most slices, `most` at most, that are
    whole sublane tiles each."""
    n = max(n for n in range(1, most + 1) if size % (_SUBLANES * n) == 0)
    return [pl.ds(i * (size // n), size // n) for i in range(n)]


def _kernel(q_ref, k_ref, bv_ref, decay_ref, beta_ref, s_hbm, o_ref, so_hbm,
            buf, came, went, *, block, group, channel, pack):
    held, heads = block
    # what the state's second axis counts: a head, or `pack` of them
    # side by side
    units = heads // pack
    key_dim, wide = buf.shape[-2:]
    value_dim = wide // pack
    across = pl.num_programs(1)
    at = pl.program_id(0) * across + pl.program_id(1)
    last = pl.num_programs(0) * across - 1
    reads, writes = _cuts(key_dim, _READ_CUTS), _cuts(key_dim, _WRITE_CUTS)

    def placed(t):
        """Block t where it lies in HBM, and its buffer."""
        slot = t % _BUFFERS
        return (pl.ds(t // across * held, held),
                pl.ds(t % across * units, units)), buf.at[slot], slot

    def fetch(t):
        """Starts block t's copies in."""
        where, to, slot = placed(t)
        for cut in reads:
            pltpu.make_async_copy(s_hbm.at[where + (cut,)],
                                  to.at[:, :, cut], came.at[slot]).start()

    def fetched(t, sublanes):
        """Waits for as much of block t's way in as these sublanes of
        its heads are (one wait: the semaphore counts bytes)."""
        _, to, slot = placed(t)
        part = to.at[:, :, sublanes]
        pltpu.make_async_copy(part, part, came.at[slot]).wait()

    def give(t):
        """Starts block t's copies out."""
        (rows_at, units_at), out, slot = placed(t)

        def head(h, _):
            for cut in writes:
                pltpu.make_async_copy(
                    out.at[:, pl.ds(h, 1), cut],
                    so_hbm.at[rows_at, pl.ds(units_at.start + h, 1), cut],
                    went.at[slot]).start()

        lax.fori_loop(0, units, head, None)

    def given(t):
        """Waits for all of block t's way out."""
        _, out, slot = placed(t)
        pltpu.make_async_copy(out, out, went.at[slot]).wait()

    s_ref = placed(at)[1]
    # q and k arrive in whole lane blocks (`_operands`)
    key_lanes = k_ref.shape[-1]
    diagonal = lax.broadcasted_iota(jnp.int32, (key_dim, key_lanes), 0) \
        == lax.broadcasted_iota(jnp.int32, (key_dim, key_lanes), 1)

    def column(row):
        """[1, size] -> [size, 1]: the row down the sublanes, the
        diagonal kept, summed over the lanes."""
        return jnp.sum(jnp.where(diagonal, row, 0.0), axis=1, keepdims=True)

    lane = lax.broadcasted_iota(jnp.int32, (1, wide), 1)

    def columns(ref, b, first):
        """The columns of heads first .. first + pack - 1, each beside
        its own head's lanes of a unit: [key_dim, 1] for one head,
        [key_dim, pack * value_dim] by a lane select for several."""
        out = column(ref[b, pl.ds(first + pack - 1, 1), :])
        for i in reversed(range(pack - 1)):
            out = jnp.where(lane < (i + 1) * value_dim,
                            column(ref[b, pl.ds(first + i, 1), :]), out)
        return out

    # Reads and writes take turns at the HBM: block at + 1 comes in
    # beside the first half of this block's work, block at - 1 goes out
    # beside the second.  The work is one body over a quarter of the
    # block's key heads (a half, or all, where they do not divide; a
    # fifth or a third of the units of heads side by side), which
    # quarter a loop's index: the kernel's text is a quarter's heads.
    keyed = units if pack > 1 else heads // group
    parts = next(n for n in (4, 2, 5, 3, 1) if keyed % n == 0)
    tail = reads[-1]

    def work(part):
        """The step of this part's key heads' value heads in every row
        of the block, where the state lies."""
        def row(b, _):
            for key_head in range(keyed // parts):
                key_head = part * (keyed // parts) + key_head
                k_col = columns(k_ref, b, key_head * pack)
                q_col = columns(q_ref, b, key_head * pack)
                for j in range(group):
                    j = key_head * group + j
                    one = pl.ds(j, 1)
                    # one decay a head along the lanes as it comes, or
                    # one a key channel down the sublanes
                    s, decay = s_ref[b, j], decay_ref[b, one, :]
                    s = s * (column(decay) if channel else decay)
                    read = jnp.sum(s * k_col, axis=0, keepdims=True)
                    delta = bv_ref[b, one, :] - beta_ref[b, one, :] * read
                    s = s + k_col * delta
                    o_ref[b, one, :] = jnp.sum(s * q_col, axis=0,
                                               keepdims=True)
                    s_ref[b, j] = s
        if held == 1:
            row(0, None)
        else:
            lax.fori_loop(0, held, row, None)

    @pl.when(at == 0)
    def _():
        fetch(0)
        fetched(0, pl.ds(0, key_dim))

    @pl.when(at < last)
    def _():
        fetch(at + 1)

    def turn(part, _):
        work(part)
        halfway = part == (parts - 1) // 2

        @pl.when(halfway & (at < last))
        def _():
            fetched(at + 1, pl.ds(0, tail.start))

        @pl.when(halfway & (at > 0))
        def _():
            give(at - 1)

    lax.fori_loop(0, parts, turn, None)

    @pl.when(at < last)
    def _():
        fetched(at + 1, tail)

    @pl.when(at > 0)
    def _():
        given(at - 1)

    @pl.when(at == last)
    def _():
        give(at)
        given(at)


def _call(q, k, bv, decay, beta, state, *, block, channel, interpret,
          pack=1):
    """q, k [B, Hk, key lanes]; bv, decay, beta [B, H / pack, pack * Dv]
    (a gate a key channel: decay [B, H, Dk]); state [B, H / pack, Dk,
    pack * Dv]."""
    rows, units, key_dim, wide = state.shape
    all_heads = units * pack
    group = all_heads // q.shape[1]
    held, heads = block

    def keyed(b, h):
        return b, h, 0

    key_block = pl.BlockSpec((held, heads // group, q.shape[-1]), keyed)
    value_block = pl.BlockSpec((held, heads // pack, wide), keyed)
    # the state stays in HBM: the kernel's own copies move it
    where_it_lies = pl.BlockSpec(memory_space=pl.ANY)
    square = (key_dim, wide, pack) == (_LANES, _LANES, 1)
    return pl.pallas_call(
        functools.partial(_kernel, block=block, group=group,
                          channel=channel, pack=pack),
        grid=(rows // held, all_heads // heads),
        in_specs=[key_block, key_block, value_block, value_block,
                  value_block, where_it_lies],
        out_specs=[value_block, where_it_lies],
        out_shape=[jax.ShapeDtypeStruct(bv.shape, jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        scratch_shapes=[
            pltpu.VMEM((_BUFFERS, held, heads // pack, key_dim, wide),
                       jnp.float32),
            pltpu.SemaphoreType.DMA((_BUFFERS,)),
            pltpu.SemaphoreType.DMA((_BUFFERS,))],
        # the state's buffer is the new state's
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            # a step's copies are the steps' before and after it
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit(
                block, key_dim * (wide // pack) * 4)),
        interpret=interpret,
        # the trace's readers match the prefix; a state that is not
        # 128 x 128 a head says its shape
        name="%s_step_r%d_h%d_%sb%d" % (
            "kda" if channel else "gdn", rows, heads,
            "" if square else "k%d_v%d_" % (key_dim, wide // pack), held),
    )(q, k, bv, decay, beta, state)


def _operands(q, k, v, g, beta, pack=1):
    """The kernel's operands beside the state: q and k in whole lane
    blocks (a key of 96 values zero-padded to 128: 15 KB a row beside
    2.2 MB of state), beta * v, the decay and beta a row [1, Dv] each (a
    gate a key channel: the decay the row [1, Dk] it is), `pack` heads'
    rows side by side as their states lie."""
    wide = lambda t: jnp.broadcast_to(t.astype(jnp.float32)[..., None],
                                      v.shape)
    beside = lambda t: t.reshape(t.shape[0], -1, pack * t.shape[-1])
    short = -q.shape[-1] % _LANES
    if short:
        q, k = (jnp.pad(t, ((0, 0), (0, 0), (0, short))) for t in (q, k))
    return (q, k, beside(wide(beta) * v.astype(jnp.float32)),
            jnp.exp(g.astype(jnp.float32)) if g.ndim == 3
            else beside(wide(jnp.exp(g))), beside(wide(beta)))


# Under `jax.jit`: the layers of a program that hold the same instance
# share one traced body and one lowered function.
@functools.partial(jax.jit, static_argnames=("block", "interpret", "pack"))
def _kernel_step(q, k, v, g, beta, state, block, interpret, pack):
    """`_call` on `_operands` and the state; the output a head a row
    again."""
    out, state = _call(*_operands(q, k, v, g, beta, pack), state,
                       block=block, channel=g.ndim == 3,
                       interpret=interpret, pack=pack)
    return out.reshape(v.shape), state


def step(q, k, v, g, beta, state, plain, block=None, interpret=False,
         pack=1):
    """(out [B, H, Dv] float32, the state after the step): the module's
    docstring; g [B, H] (a gate a head) or [B, H, Dk] (a gate a key
    channel); `state` [B, H / pack, Dk, pack * Dv], `pack` heads side by
    side (`pack_state`).  `plain(q, k, v, g, beta, state)` is what every
    platform but the TPU lowers in the kernel's place (the op's own
    step, over the state as it is handed in); `block` ((rows, value
    heads) a grid step) is chosen from the shapes unless given, and
    `interpret` runs the kernel's body under the Pallas interpreter
    whatever the platform (tests, sweeps)."""
    rows, units, key_dim, wide = state.shape
    all_heads, value_dim = units * pack, wide // pack
    block = block or choose_block(rows, all_heads, q.shape[1], key_dim,
                                  value_dim, state.dtype, pack, g.ndim == 3)
    if not block or q.shape != k.shape \
            or q.shape != (rows, q.shape[1], key_dim) \
            or v.shape != (rows, all_heads, value_dim) \
            or g.shape not in ((rows, all_heads),
                               (rows, all_heads, key_dim)) \
            or (g.ndim == 3 and (pack > 1 or key_dim != value_dim)) \
            or beta.shape != (rows, all_heads) \
            or q.dtype != jnp.float32 \
            or rows % block[0] or all_heads % block[1] or block[1] % pack:
        raise ValueError(
            "gdn_step: q %s %s, k %s, v %s, g %s, beta %s over a state of "
            "%s %s (%d heads side by side) are no step the kernel takes"
            % (q.shape, q.dtype, k.shape, v.shape, g.shape, beta.shape,
               state.shape, state.dtype, pack))

    kernel = functools.partial(_kernel_step, block=tuple(block),
                               interpret=bool(interpret), pack=int(pack))
    operands = (q, k, v, g, beta, state)
    if interpret:
        return kernel(*operands)
    return lax.platform_dependent(*operands, tpu=kernel, default=plain)
