"""One decode step of the gated delta rule (ops/linear_attention.py) as
a Pallas TPU kernel that reads and writes every head's state once, in
place.

    step(q[B, Hk, Dk], k[B, Hk, Dk], v[B, H, Dv], g[B, H], beta[B, H],
         state[B, H, Dk, Dv]) -> (out [B, H, Dv], state')

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
head (the lanes, and a column the sublanes tile); a block's value heads
hold whole key heads and tile the sublanes of the [Hk, Dk] and [H, Dv]
operands (all heads where a row's state fits `_STEP_BYTES`), and its
rows divide the batch's.  The op asks, and keeps its plain path
otherwise.

Lowered for the TPU this is a Mosaic kernel named `gdn_step_r<rows>_h<
heads>_b<rows a step>` (rows of the batch, value heads and rows a grid
step: a trace's `device_ops` row says which block ran) under a gate a
head and `kda_step_r<rows>_h<heads>_b<rows a step>` under a gate a key
channel (one body, the decay a row or a column; a trace's readers tell
Gated DeltaNet's steps from KDA's by the prefix); lowered for any
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
_HEAD_BYTES = _LANES * _LANES * 4
# the bytes of state a grid step takes in (and a step later gives out)
_STEP_BYTES = 8 << 20
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


def choose_block(rows, heads, key_heads, key_dim, value_dim, dtype):
    """(rows, value heads) a grid step holds, or None where the kernel
    does not take the shape.  The heads first: the most that divide
    `heads`, hold whole key heads, tile the operands' sublanes (a
    multiple of 8 key heads, or all of them) and keep one row's state
    within `_STEP_BYTES`; then the most rows that divide `rows` and keep
    the block's within it."""
    if key_dim != _LANES or value_dim != _LANES \
            or jnp.dtype(dtype) != jnp.float32 or heads % key_heads:
        return None
    group = heads // key_heads
    room = max(_STEP_BYTES // _HEAD_BYTES, group)
    held = max((n for n in range(group, min(heads, room) + 1, group)
                if heads % n == 0
                and (n == heads or (n // group) % 8 == 0)), default=0)
    if not held:
        return None
    return max(n for n in range(1, rows + 1)
               if rows % n == 0 and n * held <= max(room, held)), held


def vmem_limit(block):
    """The VMEM a call over `block` may take: `_BUFFERS` blocks of state
    and `_VMEM_BESIDE`."""
    return _BUFFERS * block[0] * block[1] * _HEAD_BYTES + _VMEM_BESIDE


def _cuts(n):
    """A head's sublanes in n slices."""
    return [pl.ds(i * (_LANES // n), _LANES // n) for i in range(n)]


def _kernel(q_ref, k_ref, bv_ref, decay_ref, beta_ref, s_hbm, o_ref, so_hbm,
            buf, came, went, *, block, group, channel):
    held, heads = block
    across = pl.num_programs(1)
    at = pl.program_id(0) * across + pl.program_id(1)
    last = pl.num_programs(0) * across - 1

    def placed(t):
        """Block t where it lies in HBM, and its buffer."""
        slot = t % _BUFFERS
        return (pl.ds(t // across * held, held),
                pl.ds(t % across * heads, heads)), buf.at[slot], slot

    def fetch(t):
        """Starts block t's copies in."""
        where, to, slot = placed(t)
        for cut in _cuts(_READ_CUTS):
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
        (rows_at, heads_at), out, slot = placed(t)

        def head(h, _):
            for cut in _cuts(_WRITE_CUTS):
                pltpu.make_async_copy(
                    out.at[:, pl.ds(h, 1), cut],
                    so_hbm.at[rows_at, pl.ds(heads_at.start + h, 1), cut],
                    went.at[slot]).start()

        lax.fori_loop(0, heads, head, None)

    def given(t):
        """Waits for all of block t's way out."""
        _, out, slot = placed(t)
        pltpu.make_async_copy(out, out, went.at[slot]).wait()

    s_ref = placed(at)[1]
    diagonal = lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 0) \
        == lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 1)

    def column(row):
        """[1, size] -> [size, 1]: the row down the sublanes, the
        diagonal kept, summed over the lanes."""
        return jnp.sum(jnp.where(diagonal, row, 0.0), axis=1, keepdims=True)

    # Reads and writes take turns at the HBM: block at + 1 comes in
    # beside the first half of this block's work, block at - 1 goes out
    # beside the second.  The work is one body over a quarter of the
    # block's key heads (a half, or all, where they do not divide), which
    # quarter a loop's index: the kernel's text is a quarter's heads.
    key_heads = heads // group
    parts = max(n for n in (4, 2, 1) if key_heads % n == 0)
    tail = _cuts(_READ_CUTS)[-1]

    def work(part):
        """The step of this part's key heads' value heads in every row
        of the block, where the state lies."""
        def row(b, _):
            for key_head in range(key_heads // parts):
                key_head = part * (key_heads // parts) + key_head
                k_col = column(k_ref[b, pl.ds(key_head, 1), :])
                q_col = column(q_ref[b, pl.ds(key_head, 1), :])
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
        fetched(0, pl.ds(0, _LANES))

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


def _call(q, k, bv, decay, beta, state, *, block, channel, interpret):
    rows, all_heads, key_dim, value_dim = state.shape
    group = all_heads // q.shape[1]
    held, heads = block

    def keyed(b, h):
        return b, h, 0

    key_block = pl.BlockSpec((held, heads // group, key_dim), keyed)
    value_block = pl.BlockSpec((held, heads, value_dim), keyed)
    # the state stays in HBM: the kernel's own copies move it
    where_it_lies = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_kernel, block=block, group=group,
                          channel=channel),
        grid=(rows // held, all_heads // heads),
        in_specs=[key_block, key_block, value_block, value_block,
                  value_block, where_it_lies],
        out_specs=[value_block, where_it_lies],
        out_shape=[jax.ShapeDtypeStruct(bv.shape, jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        scratch_shapes=[
            pltpu.VMEM((_BUFFERS, held, heads, key_dim, value_dim),
                       jnp.float32),
            pltpu.SemaphoreType.DMA((_BUFFERS,)),
            pltpu.SemaphoreType.DMA((_BUFFERS,))],
        # the state's buffer is the new state's
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            # a step's copies are the steps' before and after it
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit(block)),
        interpret=interpret,
        # the trace's readers match the prefix
        name="%s_step_r%d_h%d_b%d" % ("kda" if channel else "gdn", rows,
                                      heads, held),
    )(q, k, bv, decay, beta, state)


def _operands(q, k, v, g, beta):
    """The kernel's operands beside the state: q, k, beta * v, the decay
    and beta a row [1, Dv] each (a gate a key channel: the decay the row
    [1, Dk] it is)."""
    wide = lambda t: jnp.broadcast_to(t.astype(jnp.float32)[..., None],
                                      v.shape)
    return (q, k, wide(beta) * v.astype(jnp.float32),
            jnp.exp(g.astype(jnp.float32)) if g.ndim == 3
            else wide(jnp.exp(g)), wide(beta))


# Under `jax.jit`: the layers of a program that hold the same instance
# share one traced body and one lowered function.
@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _kernel_step(q, k, v, g, beta, state, block, interpret):
    """`_call` on `_operands` and the state."""
    return _call(*_operands(q, k, v, g, beta), state, block=block,
                 channel=g.ndim == 3, interpret=interpret)


def step(q, k, v, g, beta, state, plain, block=None, interpret=False):
    """(out [B, H, Dv] float32, the state after the step): the module's
    docstring; g [B, H] (a gate a head) or [B, H, Dk] (a gate a key
    channel).  `plain(q, k, v, g, beta, state)` is what every platform
    but the TPU lowers in the kernel's place (the op's own step);
    `block` ((rows, value heads) a grid step) is chosen from the shapes
    unless given, and `interpret` runs the kernel's body under the
    Pallas interpreter whatever the platform (tests, sweeps)."""
    rows, all_heads, key_dim, value_dim = state.shape
    block = block or choose_block(rows, all_heads, q.shape[1], key_dim,
                                  value_dim, state.dtype)
    if not block or q.shape != k.shape \
            or q.shape != (rows, q.shape[1], key_dim) \
            or v.shape != (rows, all_heads, value_dim) \
            or g.shape not in ((rows, all_heads),
                               (rows, all_heads, key_dim)) \
            or beta.shape != (rows, all_heads) \
            or q.dtype != jnp.float32 \
            or rows % block[0] or all_heads % block[1]:
        raise ValueError(
            "gdn_step: q %s %s, k %s, v %s, g %s, beta %s over a state of "
            "%s %s are no step the kernel takes"
            % (q.shape, q.dtype, k.shape, v.shape, g.shape, beta.shape,
               state.shape, state.dtype))

    kernel = functools.partial(_kernel_step, block=tuple(block),
                               interpret=bool(interpret))
    operands = (q, k, v, g, beta, state)
    if interpret:
        return kernel(*operands)
    return lax.platform_dependent(*operands, tpu=kernel, default=plain)
