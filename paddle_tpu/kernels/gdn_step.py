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
them; here the grid is (rows, blocks of `heads` value heads), a grid
step holds its heads' states in VMEM ([heads, Dk, Dv] float32 in, the
same out: 1 MB each way at 16 heads of 128 x 128, against a grid step's
0.35 us), and `input_output_aliases` hands the state's buffer back as
the result: a decoder's scan carries it without a copy.

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

Which shapes it takes (`choose_heads`): a float32 state of 128 x 128 a
head (the lanes, and a column the sublanes tile), and a block of value
heads that holds whole key heads and tiles the sublanes of the [Hk, Dk]
and [H, Dv] operands.  The op asks, and keeps its plain path otherwise.

Lowered for the TPU this is a Mosaic kernel named `gdn_step_r<rows>_h<
heads>` (rows of the batch, value heads a grid step) under a gate a
head and `kda_step_r<rows>_h<heads>` under a gate a key channel (one
body, the decay a row or a column; a trace's readers tell Gated
DeltaNet's steps from KDA's by the prefix); lowered for any
other platform the caller's plain step runs in its place (`step`'s
`plain`, as kernels/ssd.py's entries take theirs; `interpret=True` runs
the kernel's body under the Pallas interpreter: tests).
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
# the bytes of state a grid step takes in (and gives out)
_STEP_BYTES = 1 << 20


def choose_heads(rows, heads, key_heads, key_dim, value_dim, dtype):
    """The value heads a grid step holds, or 0 where the kernel does not
    take the shape: the most that divide `heads`, hold whole key heads,
    tile the operands' sublanes (a multiple of 8 key heads, or all of
    them) and keep the step's state within `_STEP_BYTES`."""
    if key_dim != _LANES or value_dim != _LANES \
            or jnp.dtype(dtype) != jnp.float32 or heads % key_heads:
        return 0
    group = heads // key_heads
    room = max(_STEP_BYTES // (key_dim * value_dim * 4), group)
    return max((n for n in range(group, min(heads, room) + 1, group)
                if heads % n == 0
                and (n == heads or (n // group) % 8 == 0)), default=0)


def _kernel(q_ref, k_ref, bv_ref, decay_ref, beta_ref, s_ref, o_ref, so_ref,
            *, heads, group, channel):
    size = q_ref.shape[-1]
    diagonal = lax.broadcasted_iota(jnp.int32, (size, size), 0) \
        == lax.broadcasted_iota(jnp.int32, (size, size), 1)

    def column(row):
        """[1, size] -> [size, 1]: the row down the sublanes, the
        diagonal kept, summed over the lanes."""
        return jnp.sum(jnp.where(diagonal, row, 0.0), axis=1, keepdims=True)

    for key_head in range(heads // group):
        k_col = column(k_ref[0, pl.ds(key_head, 1), :])
        q_col = column(q_ref[0, pl.ds(key_head, 1), :])
        for j in range(key_head * group, (key_head + 1) * group):
            at = pl.ds(j, 1)
            # one decay a head along the lanes as it comes, or one a
            # key channel down the sublanes
            s, decay = s_ref[0, j], decay_ref[0, at, :]
            s = s * (column(decay) if channel else decay)
            held = jnp.sum(s * k_col, axis=0, keepdims=True)
            delta = bv_ref[0, at, :] - beta_ref[0, at, :] * held
            s = s + k_col * delta
            o_ref[0, at, :] = jnp.sum(s * q_col, axis=0, keepdims=True)
            so_ref[0, j] = s


def _call(q, k, bv, decay, beta, state, *, heads, channel, interpret):
    rows, all_heads, key_dim, value_dim = state.shape
    group = all_heads // q.shape[1]

    def keyed(b, h):
        return b, h, 0

    def stated(b, h):
        return b, h, 0, 0

    key_block = pl.BlockSpec((1, heads // group, key_dim), keyed)
    value_block = pl.BlockSpec((1, heads, value_dim), keyed)
    state_block = pl.BlockSpec((1, heads, key_dim, value_dim), stated)
    return pl.pallas_call(
        functools.partial(_kernel, heads=heads, group=group,
                          channel=channel),
        grid=(rows, all_heads // heads),
        in_specs=[key_block, key_block, value_block, value_block,
                  value_block, state_block],
        out_specs=[value_block, state_block],
        out_shape=[jax.ShapeDtypeStruct(bv.shape, jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # the state's buffer is the new state's
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        # the trace's readers match the prefix
        name="%s_step_r%d_h%d" % ("kda" if channel else "gdn", rows, heads),
    )(q, k, bv, decay, beta, state)


# Under `jax.jit`: the layers of a program that hold the same instance
# share one traced body and one lowered function.
@functools.partial(jax.jit, static_argnames=("heads", "interpret"))
def _kernel_step(q, k, v, g, beta, state, heads, interpret):
    """`_call` on the kernel's operands: q, k, beta * v, the decay and
    beta a row [1, Dv] each (a gate a key channel: the decay the row
    [1, Dk] it is), the state."""
    wide = lambda t: jnp.broadcast_to(t.astype(jnp.float32)[..., None],
                                      v.shape)
    channel = g.ndim == 3
    return _call(q, k, wide(beta) * v.astype(jnp.float32),
                 jnp.exp(g.astype(jnp.float32)) if channel
                 else wide(jnp.exp(g)), wide(beta), state, heads=heads,
                 channel=channel, interpret=interpret)


def step(q, k, v, g, beta, state, plain, heads=None, interpret=False):
    """(out [B, H, Dv] float32, the state after the step): the module's
    docstring; g [B, H] (a gate a head) or [B, H, Dk] (a gate a key
    channel).  `plain(q, k, v, g, beta, state)` is what every platform
    but the TPU lowers in the kernel's place (the op's own step);
    `heads` (a grid step's) is chosen from the shapes unless given, and
    `interpret` runs the kernel's body under the Pallas interpreter
    whatever the platform (tests, sweeps)."""
    rows, all_heads, key_dim, value_dim = state.shape
    heads = heads or choose_heads(rows, all_heads, q.shape[1], key_dim,
                                  value_dim, state.dtype)
    if not heads or q.shape != k.shape \
            or q.shape != (rows, q.shape[1], key_dim) \
            or v.shape != (rows, all_heads, value_dim) \
            or g.shape not in ((rows, all_heads),
                               (rows, all_heads, key_dim)) \
            or beta.shape != (rows, all_heads) \
            or q.dtype != jnp.float32 or all_heads % heads:
        raise ValueError(
            "gdn_step: q %s %s, k %s, v %s, g %s, beta %s over a state of "
            "%s %s are no step the kernel takes"
            % (q.shape, q.dtype, k.shape, v.shape, g.shape, beta.shape,
               state.shape, state.dtype))

    kernel = functools.partial(_kernel_step, heads=heads,
                               interpret=bool(interpret))
    operands = (q, k, v, g, beta, state)
    if interpret:
        return kernel(*operands)
    return lax.platform_dependent(*operands, tpu=kernel, default=plain)
