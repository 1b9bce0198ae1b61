"""The ops a step with a recurrent state is built on, each by itself:
the gated delta rule with a state handed in and on (`gated_delta_rule`:
the step, the step kernel under the interpreter, the chunked block form)
against the recurrence position by position and the forms against one
another (a block then steps, all steps, one block); the convolution
that carries its tail over a split sequence against the unsplit call,
and granite's lowering of it without one; 256-wide heads with a quarter
rotated through `rope(rotary_dim=)` and `cached_attention` (plain and
kernel paths) against plain attention.  The cached step Program built
on them is tests/test_linear_moe_program.py's.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.kernels import gdn_step, gqa_decode
from paddle_tpu.models.reference import qwen3_next as reference
from paddle_tpu.obs import telemetry
from paddle_tpu.ops import linear_attention, registry
from paddle_tpu.ops import ssm


# -- (a) the op's forms -----------------------------------------------------------

def _rule_ins(rs, rows, length, key_heads=2, heads=4, dim=8, state=True):
    """(Q, K, V, G, Beta, State) as the op takes them."""
    return (jnp.asarray(rs.randn(rows, length, key_heads * dim), jnp.float32),
            jnp.asarray(rs.randn(rows, length, key_heads * dim), jnp.float32),
            jnp.asarray(rs.randn(rows, length, heads * dim), jnp.float32),
            -jnp.asarray(rs.uniform(1e-3, 0.6, (rows, length, heads)),
                         jnp.float32),
            jnp.asarray(rs.uniform(0.05, 0.95, (rows, length, heads)),
                        jnp.float32),
            jnp.asarray(0.3 * rs.randn(rows, heads, dim, dim) if state
                        else np.zeros((rows, heads, dim, dim)), jnp.float32))


def _rule(ins, lo=None, hi=None, state=None, **attrs):
    """One application of the op over positions lo..hi: (out, state)."""
    q, k, v, g, beta, s0 = ins
    cut = lambda t: t[:, lo:hi]
    return _applied(tuple(sorted(dict({"chunk": 64}, **attrs).items())))(
        cut(q), cut(k), cut(v), cut(g), cut(beta),
        s0 if state is None else state)


@functools.lru_cache(maxsize=None)
def _applied(attrs):
    """The op's kernel under one jit a set of attrs (a shape each)."""
    def apply(q, k, v, g, beta, state):
        out = registry.get_op_info("gated_delta_rule").kernel(
            None, {"Q": [q], "K": [k], "V": [v], "G": [g], "Beta": [beta],
                   "State": [state]}, dict(attrs))
        return out["Out"][0], out["StateOut"][0]
    return jax.jit(apply)


def _by_position(ins):
    """The recurrence in numpy, a position and a head at a time: what
    the reference's `delta_rule` is, with a state handed in.  A gate a
    key channel (g [rows, T, heads * dim]) decays row d of a head's
    state by its own exp(g[d])."""
    q, k, v, g, beta, state = (np.asarray(t, np.float64) for t in ins)
    rows, length, heads = beta.shape
    dim = state.shape[-1]
    # [rows, T, heads, dim, 1] a channel, [rows, T, heads, 1, 1] a head
    g = g.reshape(rows, length, heads, -1, 1)
    group = heads // (q.shape[-1] // dim)
    q, k = (t.reshape(rows, length, -1, dim) for t in (q, k))
    q = q / np.sqrt((q * q).sum(-1, keepdims=True) + 1e-6) / np.sqrt(dim)
    k = k / np.sqrt((k * k).sum(-1, keepdims=True) + 1e-6)
    v = v.reshape(rows, length, heads, dim)
    out = np.zeros_like(v)
    state = state.copy()
    for b in range(rows):
        for j in range(heads):
            s = state[b, j]
            for t in range(length):
                kt, qt = k[b, t, j // group], q[b, t, j // group]
                s = s * np.exp(g[b, t, j])      # [dim, 1] or [1, 1]
                s = s + np.outer(kt, beta[b, t, j] * (v[b, t, j] - s.T @ kt))
                out[b, t, j] = s.T @ qt
            state[b, j] = s
    return out.reshape(rows, length, -1), state


@pytest.mark.parametrize("length,chunk", [(1, 64), (64, 64), (128, 64),
                                          (75, 64), (13, 4), (3, 64)])
@pytest.mark.parametrize("state", [True, False])
def test_the_rule_is_the_recurrence_position_by_position(length, chunk,
                                                         state):
    """T = 1 (the step), whole chunks, a T that is no multiple of the
    chunk, from a state handed in and from zeros; two value heads a key
    head."""
    ins = _rule_ins(np.random.RandomState(length), 2, length, state=state)
    out, new = _rule(ins, chunk=chunk)
    want_out, want_state = _by_position(ins)
    np.testing.assert_allclose(np.asarray(out), want_out, atol=2e-5)
    np.testing.assert_allclose(np.asarray(new), want_state, atol=2e-5)


@pytest.mark.parametrize("first", [1, 5, 64, 70])
def test_a_block_then_steps_is_all_steps_is_one_block(first):
    ins = _rule_ins(np.random.RandomState(first), 2, 80)
    whole, whole_state = _rule(ins, chunk=16)
    out, state = _rule(ins, 0, first, chunk=16)
    outs, steps, walked = [out], [], ins[5]
    for t in range(80):
        if t >= first:
            out, state = _rule(ins, t, t + 1, state=state)
            outs.append(out)
        one, walked = _rule(ins, t, t + 1, state=walked)
        steps.append(one)
    for got in (jnp.concatenate(outs, axis=1),
                jnp.concatenate(steps, axis=1)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(whole),
                                   atol=2e-5)
    for got in (state, walked):
        np.testing.assert_allclose(np.asarray(got), np.asarray(whole_state),
                                   atol=2e-5)


def test_the_reference_walks_the_same_recurrence():
    rs = np.random.RandomState(4)
    q, k, v, g, beta, _ = _rule_ins(rs, 2, 9, state=False)
    split = lambda t, n: t.reshape(2, 9, n, 8)
    norm = lambda t: linear_attention.l2norm(split(t, 2))
    out, state = reference.delta_rule(
        {}, jnp.repeat(norm(q) / np.sqrt(8), 2, axis=2),
        jnp.repeat(norm(k), 2, axis=2), split(v, 4), g, beta)
    want_out, want_state = _by_position((q, k, v, g, beta,
                                         np.zeros((2, 4, 8, 8))))
    np.testing.assert_allclose(np.asarray(out).reshape(2, 9, -1), want_out,
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(state), want_state, atol=2e-5)


def _kernel_ins(rs, rows, key_heads, heads, dtype=jnp.float32):
    q, k = (linear_attention.l2norm(jnp.asarray(
        rs.randn(rows, key_heads, 128), jnp.float32)) for _ in range(2))
    return (q * 128 ** -0.5, k,
            jnp.asarray(rs.randn(rows, heads, 128), dtype),
            -jnp.asarray(rs.uniform(1e-3, 0.6, (rows, heads)), jnp.float32),
            jnp.asarray(rs.uniform(0.05, 0.95, (rows, heads)), jnp.float32),
            jnp.asarray(0.3 * rs.randn(rows, heads, 128, 128), jnp.float32))


# (rows, key heads, value heads, (rows, value heads) a grid step; None:
# the chooser's): one block of everything, blocks of 16 of 32 value
# heads (four grid steps: a block comes in, one is worked, one goes
# out), a value head a key head; several rows a grid step, a loop over
# them inside; all 32 heads a step; an odd number of rows; a batch of 1
_BLOCKS = [(2, 2, 4, None), (2, 16, 32, (1, 16)), (2, 2, 2, None),
           (4, 2, 4, (1, 4)), (4, 2, 4, (2, 4)), (4, 2, 4, (4, 4)),
           (2, 16, 32, (1, 32)), (3, 2, 4, None), (1, 2, 4, None)]


@pytest.mark.parametrize("rows,key_heads,heads,block", _BLOCKS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_step_kernel_is_the_plain_step(rows, key_heads, heads, block,
                                           dtype):
    """The kernel's body under the Pallas interpreter, at 128 x 128 a
    head, by the block of state a grid step takes."""
    ins = _kernel_ins(np.random.RandomState(heads), rows, key_heads, heads,
                      dtype)
    got, state = gdn_step.step(*ins, plain=None, block=block,
                               interpret=True)
    q, k, v, g, beta, s0 = ins
    want, want_state = linear_attention.recurrent(
        q[:, None], k[:, None], v[:, None].astype(jnp.float32), g[:, None],
        beta[:, None], s0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[:, 0]),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(state), np.asarray(want_state),
                               atol=2e-5)


@pytest.mark.parametrize("shape,block", [
    # qwen3next-decode-ep16's and ling3-decode-ep16's: all heads, and
    # the rows that fill the budget
    ((128, 32, 16), (4, 32)), ((128, 32, 32), (4, 32)),
    # rows the budget's four do not divide, and a batch of 1
    ((6, 32, 16), (3, 32)), ((3, 32, 16), (3, 32)), ((1, 32, 16), (1, 32)),
    # few heads: the rows make the block up
    ((4, 4, 2), (4, 4)), ((2, 2, 2), (2, 2)), ((128, 4, 2), (32, 4)),
    # more heads than the budget holds: whole key heads that tile the
    # sublanes, a row a step
    ((8, 256, 128), (1, 128)), ((8, 256, 256), (1, 128)),
])
def test_the_block_is_the_most_state_that_fits(shape, block):
    """`choose_block` from the shapes alone: (rows, value heads, key
    heads) -> (rows, value heads) a grid step, and the VMEM the call may
    take follows from it."""
    rows, heads, key_heads = shape
    assert gdn_step.choose_block(rows, heads, key_heads, 128, 128,
                                 jnp.float32) == block
    state = block[0] * block[1] * 128 * 128 * 4
    assert state <= gdn_step._STEP_BYTES
    # a block comes in, one is worked where it lies, one goes out
    assert 3 * state < gdn_step.vmem_limit(block) <= 3 * state + (8 << 20)


@pytest.mark.parametrize("why,shape", [
    ("grouped heads that are not 128 x 128",
     (2, 4, 2, 64, 128, jnp.float32)),
    ("a key that is no whole sublane tiles", (2, 4, 4, 12, 128, jnp.float32)),
    ("values that fill no lane blocks", (2, 4, 4, 96, 192, jnp.float32)),
    ("heads the pack does not divide", (2, 5, 5, 96, 192, jnp.float32, 2)),
    ("a row past a grid step's bytes", (2, 64, 64, 128, 384, jnp.float32)),
    ("a gate a key channel over a state that is not square",
     (2, 4, 4, 96, 192, jnp.float32, 2, True)),
    ("a state that is not float32", (2, 4, 2, 128, 128, jnp.bfloat16)),
    ("value heads that do not group", (2, 5, 2, 128, 128, jnp.float32)),
])
def test_the_step_kernel_refuses_what_it_does_not_take(why, shape):
    assert gdn_step.choose_block(*shape) is None


def test_the_ops_kernel_path_is_counted_and_is_its_plain_path():
    """At 128 x 128 a head the op asks for the kernel (on the CPU the
    kernel's plain stand-in runs): the same numbers as the recurrence,
    and the counter says which way."""
    rs = np.random.RandomState(6)
    ins = _rule_ins(rs, 2, 1, key_heads=2, heads=4, dim=128)
    before = telemetry.snapshot()
    out, state = _rule(ins)
    traced = telemetry.snapshot_delta(before)
    want_out, want_state = _by_position(ins)
    np.testing.assert_allclose(np.asarray(out), want_out, atol=2e-5)
    np.testing.assert_allclose(np.asarray(state), want_state, atol=2e-5)
    assert traced[_rule_lowering("step", "kernel", 0, 4)] == 1
    assert traced["recurrent_state_bytes_total{kind=delta}"] \
        == 4 * 128 * 128 * 4


def test_the_rule_has_no_gradient_and_says_so():
    assert registry.get_op_info("gated_delta_rule").stop_gradient_op
    with pytest.raises(NotImplementedError, match="forward only"):
        registry.get_op_info("gated_delta_rule").grad_kernel(None, {}, {})


def test_operands_that_do_not_fit_the_state_are_refused():
    q, k, v, g, beta, state = _rule_ins(np.random.RandomState(0), 2, 3)
    with pytest.raises(ValueError, match="gated_delta_rule"):
        _rule((q, k, v[..., :24], g, beta, state))


# -- (a') a gate a key channel (Kimi Delta Attention) ------------------------------

FLOOR = -5.0    # Ling-3.0-flash's `kda_lower_bound`


def _channel_ins(rs, rows, length, heads=4, dim=8, held=None, **kwargs):
    """`_rule_ins` with G [rows, T, heads * dim] in [FLOOR, 0), spread
    over five orders; `held` = (lo, hi): the positions whose every gate
    sits at the bound (the case the sub-blocks are for)."""
    q, k, v, _, beta, state = _rule_ins(rs, rows, length, key_heads=heads,
                                        heads=heads, dim=dim, **kwargs)
    g = FLOOR * jax.nn.sigmoid(jnp.asarray(
        rs.uniform(-10, 3, (rows, length, heads * dim)), jnp.float32))
    if held is not None:
        g = g.at[:, held[0]:held[1]].set(FLOOR)
    return q, k, v, g, beta, state


@pytest.mark.parametrize("length,chunk,sub,held", [
    (1, 64, 16, None), (64, 64, 16, None), (128, 64, 16, (64, 128)),
    (75, 64, 16, (0, 75)), (13, 4, 2, None), (3, 64, 16, None),
    (40, 16, 16, (0, 40)), (96, 32, 8, (10, 50))])
def test_the_channel_gated_rule_is_the_recurrence(length, chunk, sub, held):
    """The op under a gate a key channel: T = 1 (the step), whole
    chunks, a T that is no multiple of the chunk, every gate of a whole
    chunk held at the bound -5 (where the sub-block's right factor
    reaches e^75), against the recurrence position by position in
    float64."""
    ins = _channel_ins(np.random.RandomState(length), 2, length, held=held)
    out, new = _rule(ins, chunk=chunk, sub_chunk=sub)
    assert bool(jnp.isfinite(out).all()) and bool(jnp.isfinite(new).all())
    want_out, want_state = _by_position(ins)
    np.testing.assert_allclose(np.asarray(out), want_out, atol=2e-5)
    np.testing.assert_allclose(np.asarray(new), want_state, atol=2e-5)


def test_the_sub_block_is_what_keeps_the_bound_held_chunk_finite():
    """The same chunk as one sub-block of 64 positions: the right factor
    passes e^88 and the block form is no number; `sub_chunk` picks 16
    from the bound, and the whole chunk (one sub-block) for a gate that
    cannot fall below -1."""
    ins = _channel_ins(np.random.RandomState(0), 1, 64, held=(0, 64))
    out, _ = _rule(ins, chunk=64, sub_chunk=64)
    assert not bool(jnp.isfinite(out).all())
    assert linear_attention.sub_chunk(64, FLOOR) == 16
    assert linear_attention.sub_chunk(64, -1.0) == 64
    assert linear_attention.sub_chunk(8, FLOOR) == 8
    assert 15 * -FLOOR < 80 < 88.7      # e^75: inside float32


@pytest.mark.parametrize("length", [1, 7, 70])
def test_a_gate_constant_over_a_heads_channels_is_the_scalar_gate(length):
    """One op: G [rows, T, heads * dim] with a head's channels all alike
    gives what G [rows, T, heads] gives, step and block."""
    ins = _rule_ins(np.random.RandomState(length), 2, length)
    q, k, v, g, beta, state = ins
    wide = jnp.repeat(g, 8, axis=-1)
    want, want_state = _rule(ins, chunk=16)
    got, got_state = _rule((q, k, v, wide, beta, state), chunk=16,
                           sub_chunk=4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_state),
                               np.asarray(want_state), atol=2e-5)


@pytest.mark.parametrize("first", [1, 5, 64, 70])
def test_channel_gated_block_then_steps_is_one_block(first):
    ins = _channel_ins(np.random.RandomState(first), 2, 80, held=(20, 50))
    whole, whole_state = _rule(ins, chunk=16, sub_chunk=8)
    out, state = _rule(ins, 0, first, chunk=16, sub_chunk=8)
    outs = [out]
    for t in range(first, 80):
        out, state = _rule(ins, t, t + 1, state=state)
        outs.append(out)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, axis=1)),
                               np.asarray(whole), atol=2e-5)
    np.testing.assert_allclose(np.asarray(state), np.asarray(whole_state),
                               atol=2e-5)


@pytest.mark.parametrize("rows,heads,block", [
    (2, 4, None), (2, 32, (1, 16)), (4, 4, (1, 4)), (4, 4, (2, 4)),
    (4, 4, (4, 4)), (2, 32, (1, 32)), (3, 4, None), (1, 4, None)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_channel_gated_step_kernel_is_the_plain_step(rows, heads, block,
                                                         dtype):
    """The kernel's body under the Pallas interpreter with the decay a
    column, over `_BLOCKS`' kinds of block (None: the chooser's), 32
    heads ling3-decode-ep16's; some gates at the bound."""
    rs = np.random.RandomState(heads)
    q, k, v, _, beta, s0 = _kernel_ins(rs, rows, heads, heads, dtype)
    g = FLOOR * jax.nn.sigmoid(jnp.asarray(
        rs.uniform(-10, 3, (rows, heads, 128)), jnp.float32))
    g = g.at[:, 0].set(FLOOR)
    got, state = gdn_step.step(q, k, v, g, beta, s0, plain=None,
                               block=block, interpret=True)
    want, want_state = linear_attention.recurrent(
        q[:, None], k[:, None], v[:, None].astype(jnp.float32), g[:, None],
        beta[:, None], s0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[:, 0]),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(state), np.asarray(want_state),
                               atol=2e-5)


def test_the_channel_gate_is_counted_and_scoped():
    """The counter's `gate` label tells the two gates apart, step and
    block; the lowered step's scopes are `kda_*`."""
    rs = np.random.RandomState(8)
    before = telemetry.snapshot()
    _rule(_channel_ins(rs, 2, 1, dim=128))
    _rule(_channel_ins(rs, 2, 9), chunk=4, sub_chunk=2)
    traced = telemetry.snapshot_delta(before)
    assert traced[_rule_lowering("step", "kernel", 0, 4, "channel")] == 1
    assert traced[_rule_lowering("block", "plain", 4, 4, "channel",
                                 (8, 8))] == 1
    q, k, v, g, beta, state = _channel_ins(rs, 2, 1, dim=128)
    lowered = _applied((("chunk", 64),)).lower(
        q, k, v, g, beta, state).as_text(debug_info=True)
    assert "kda_state" in lowered and "gdn_state" not in lowered


def test_a_gate_of_neither_shape_is_refused_and_both_are_named():
    q, k, v, g, beta, state = _rule_ins(np.random.RandomState(0), 2, 3)
    with pytest.raises(ValueError, match="a gate a key channel"):
        _rule((q, k, v, jnp.repeat(g, 3, axis=-1), beta, state))


def _rule_lowering(form, path, chunk, heads, gate="head", dims=(128, 128)):
    return ("gated_delta_rule_lowerings_total{chunk=%d,form=%s,gate=%s,"
            "heads=%d,key_dim=%d,path=%s,state_dtype=float32,"
            "value_dim=%d}"
            % (chunk, form, gate, heads, dims[0], path, dims[1]))


# -- (a') beta in (0, 2), a state that is not square, heads side by side ----------

def _wide_ins(rs, rows, length, heads, key_dim, value_dim, state=True):
    """(Q, K, V, G, Beta, State) with beta drawn over (0, 2) (negative
    eigenvalues allowed: Olmo-Hybrid's) and a state of key_dim x
    value_dim a head, a key head a value head."""
    return (jnp.asarray(rs.randn(rows, length, heads * key_dim), jnp.float32),
            jnp.asarray(rs.randn(rows, length, heads * key_dim), jnp.float32),
            jnp.asarray(rs.randn(rows, length, heads * value_dim),
                        jnp.float32),
            -jnp.asarray(rs.uniform(1e-3, 0.6, (rows, length, heads)),
                         jnp.float32),
            jnp.asarray(rs.uniform(0.02, 1.98, (rows, length, heads)),
                        jnp.float32),
            jnp.asarray(0.3 * rs.randn(rows, heads, key_dim, value_dim)
                        if state else
                        np.zeros((rows, heads, key_dim, value_dim)),
                        jnp.float32))


def _normed(ins):
    """`recurrent`'s operands of the op's: q and k split, normed and
    scaled, v split."""
    q, k, v, g, beta, state = ins
    heads, key_dim = state.shape[1:3]
    split = lambda t: t.reshape(*t.shape[:2], heads, -1)
    return (linear_attention.l2norm(split(q)) * key_dim ** -0.5,
            linear_attention.l2norm(split(k)), split(v), g, beta, state)


# (heads, key_dim, value_dim, T, chunk): Olmo-Hybrid's head at the cell's
# chunk, whole chunks and a block that is no multiple of one; a small
# head, a short chunk
_WIDE = [(3, 96, 192, 128, 64), (3, 96, 192, 75, 64), (4, 8, 24, 13, 4),
         (4, 8, 24, 64, 16)]


@pytest.mark.parametrize("heads,key_dim,value_dim,length,chunk", _WIDE)
@pytest.mark.parametrize("state", [True, False])
def test_the_block_form_is_the_recurrence_at_beta_up_to_2(
        heads, key_dim, value_dim, length, chunk, state):
    """`chunked` against `recurrent` with beta over (0, 2): the entries
    of A in (I + A)^-1 are up to twice those at beta < 1 and nothing is
    clamped.  The tolerance: a position's output is a sum of up to
    `length` updates of O(1) values in float32, both forms at the
    highest precision, in different orders; 1e-4 of the largest entry
    (5e-5 was read at T = 128; at beta < 1 the same shapes read 2e-5)."""
    ins = _normed(_wide_ins(np.random.RandomState(length), 2, length, heads,
                            key_dim, value_dim, state))
    want, want_state = jax.jit(linear_attention.recurrent)(*ins)
    got, got_state = jax.jit(functools.partial(
        linear_attention.chunked, chunk=chunk))(*ins)
    for g, w in ((got, want), (got_state, want_state)):
        w = np.asarray(w)
        np.testing.assert_allclose(np.asarray(g), w,
                                   atol=1e-4 * np.abs(w).max())


@pytest.mark.parametrize("heads,key_dim,value_dim,pack", [
    (4, 8, 24, 2), (4, 8, 24, 4), (2, 96, 192, 2), (4, 8, 24, 1)])
@pytest.mark.parametrize("length", [1, 9])
def test_heads_side_by_side_are_the_heads_apart(heads, key_dim, value_dim,
                                                pack, length):
    """`state_pack`: the op over a state whose heads lie side by side
    (a step and a block, the plain path here) gives the op's output over
    the heads apart, and the state it hands on is that state side by
    side; the layout's round trip is the identity."""
    ins = _wide_ins(np.random.RandomState(pack), 2, length, heads, key_dim,
                    value_dim)
    want, want_state = _rule(ins, chunk=4)
    beside = gdn_step.pack_state(ins[5], pack)
    assert beside.shape == (2, heads // pack, key_dim, pack * value_dim)
    np.testing.assert_array_equal(
        np.asarray(gdn_step.unpack_state(beside, pack)), np.asarray(ins[5]))
    # unit u holds heads pack * u .. pack * u + pack - 1, a head after a
    # head along the lanes
    np.testing.assert_array_equal(
        np.asarray(beside[:, 0, :, value_dim * (pack - 1):]),
        np.asarray(ins[5][:, pack - 1]))
    got, got_state = _rule(ins, state=beside, chunk=4, state_pack=pack)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(gdn_step.unpack_state(got_state, pack)),
        np.asarray(want_state), atol=1e-6)


def _wide_kernel_ins(rs, rows, heads, key_dim, value_dim, dtype):
    q, k = (linear_attention.l2norm(jnp.asarray(
        rs.randn(rows, heads, key_dim), jnp.float32)) for _ in range(2))
    return (q * key_dim ** -0.5, k,
            jnp.asarray(rs.randn(rows, heads, value_dim), dtype),
            -jnp.asarray(rs.uniform(1e-3, 0.6, (rows, heads)), jnp.float32),
            jnp.asarray(rs.uniform(0.02, 1.98, (rows, heads)), jnp.float32),
            jnp.asarray(0.3 * rs.randn(rows, heads, key_dim, value_dim),
                        jnp.float32))


# (rows, heads, key_dim, value_dim, pack, (rows, heads) a grid step):
# Olmo-Hybrid's 30 heads of 96 x 192 in pairs, the chooser's block and a
# row a grid step (four grid steps: a block comes in, one is worked, one
# goes out); a small key; values padded to whole lane blocks, a head a
# unit; four heads side by side
_WIDE_BLOCKS = [(4, 30, 96, 192, 2, None), (4, 30, 96, 192, 2, (1, 30)),
                (2, 6, 8, 192, 2, None), (2, 6, 8, 192, 2, (1, 6)),
                (2, 4, 8, 256, 1, None), (3, 4, 16, 96, 4, None)]


@pytest.mark.parametrize("rows,heads,key_dim,value_dim,pack,block",
                         _WIDE_BLOCKS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_step_kernel_takes_a_state_that_is_not_square(
        rows, heads, key_dim, value_dim, pack, block, dtype):
    """The kernel's body under the Pallas interpreter over a state of
    key_dim x value_dim a head, `pack` heads side by side as it lies in
    HBM, beta over (0, 2): the plain step's output and state (2e-5, the
    square kernel's tolerance: the same four lines on the same values,
    the sums over the sublanes in another order), the state's layout
    round trip included."""
    ins = _wide_kernel_ins(np.random.RandomState(heads), rows, heads,
                           key_dim, value_dim, dtype)
    q, k, v, g, beta, s0 = ins
    got, state = gdn_step.step(q, k, v, g, beta,
                               gdn_step.pack_state(s0, pack), plain=None,
                               block=block, interpret=True, pack=pack)
    assert state.shape == (rows, heads // pack, key_dim, pack * value_dim)
    want, want_state = linear_attention.recurrent(
        q[:, None], k[:, None], v[:, None].astype(jnp.float32), g[:, None],
        beta[:, None], s0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[:, 0]),
                               atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(gdn_step.unpack_state(state, pack)),
        np.asarray(want_state), atol=2e-5)


@pytest.mark.parametrize("shape,block", [
    # olmohybrid-decode-pp4's: 4 rows of all 30 heads, 8.4 MiB
    ((128, 30, 30, 96, 192, jnp.float32, 2), (4, 30)),
    # rows the four do not divide; a batch of 1; the padded layout
    ((6, 30, 30, 96, 192, jnp.float32, 2), (3, 30)),
    ((1, 30, 30, 96, 192, jnp.float32, 2), (1, 30)),
    ((128, 30, 30, 96, 256, jnp.float32, 1), (2, 30)),
    # qwen3next-decode-ep16's and ling3-decode-ep16's, as they were
    ((128, 32, 16, 128, 128, jnp.float32), (4, 32)),
    ((128, 32, 32, 128, 128, jnp.float32, 1), (4, 32)),
])
def test_the_block_of_a_state_that_is_not_square(shape, block):
    """`choose_block` at a head that is not 128 x 128: all the heads of
    the most rows within `_WIDE_STEP_BYTES`; the square shapes' blocks
    are PR 64's."""
    assert gdn_step.choose_block(*shape) == block
    rows, heads = block
    state = rows * heads * shape[3] * shape[4] * 4
    assert state <= max(gdn_step._WIDE_STEP_BYTES, gdn_step._STEP_BYTES)
    assert 3 * state < gdn_step.vmem_limit(
        block, shape[3] * shape[4] * 4) <= 3 * state + (8 << 20)


def test_the_ops_wide_kernel_path_is_counted_by_its_shape():
    """At 96 x 192 a head with two heads side by side the op asks for
    the kernel (on the CPU its plain stand-in runs) and the counter says
    the head's shape; the heads apart (a state of 192 lanes) stay
    plain."""
    rs = np.random.RandomState(5)
    ins = _wide_ins(rs, 2, 1, 2, 96, 192)
    before = telemetry.snapshot()
    want, want_state = _rule(ins)
    got, state = _rule(ins, state=gdn_step.pack_state(ins[5], 2),
                       state_pack=2)
    traced = telemetry.snapshot_delta(before)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(gdn_step.unpack_state(state, 2)), np.asarray(want_state),
        atol=2e-5)
    assert traced[_rule_lowering("step", "kernel", 0, 2,
                                 dims=(96, 192))] == 1
    assert traced[_rule_lowering("step", "plain", 0, 2,
                                 dims=(96, 192))] == 1


# -- (b) the convolution that carries its tail -------------------------------------

def _conv(x, filt, tail=None, bias=None, activation="silu"):
    ins = {"X": [x], "Filter": [filt]}
    if bias is not None:
        ins["Bias"] = [bias]
    if tail is not None:
        ins["Tail"] = [tail]
    out = registry.get_op_info("causal_conv1d").kernel(
        None, ins, {"activation": activation})
    return out["Out"][0], out.get("TailOut", [None])[0]


@pytest.mark.parametrize("cuts", [[0], [0, 5], [0, 1, 2, 3, 4, 5, 6],
                                  [0, 2, 9, 10]])
@pytest.mark.parametrize("bias", [True, False])
def test_a_split_sequence_with_its_tail_is_the_unsplit_call(cuts, bias):
    rs = np.random.RandomState(len(cuts))
    x = jnp.asarray(rs.randn(2, 12, 6), jnp.float32)
    filt = jnp.asarray(rs.randn(6, 4), jnp.float32)
    b = jnp.asarray(rs.randn(6), jnp.float32)
    want, _ = _conv(x, filt, bias=b if bias else jnp.zeros(6))
    tail, outs = jnp.zeros((2, 3, 6)), []
    for lo, hi in zip(cuts, cuts[1:] + [12]):
        out, tail = _conv(x[:, lo:hi], filt, tail, b if bias else None)
        outs.append(out)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, axis=1)),
                               np.asarray(want), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(tail), np.asarray(x[:, -3:]))


def test_the_tail_keeps_its_own_type():
    x = jnp.ones((1, 2, 4), jnp.float32)
    _, tail = _conv(x, jnp.ones((4, 4)), jnp.zeros((1, 3, 4), jnp.bfloat16))
    assert tail.dtype == jnp.bfloat16 and tail.shape == (1, 3, 4)


def _conv_before(x, filt, bias, act):
    """`causal_conv1d`'s body as it was before it took a tail (commit
    984867f), verbatim."""
    pre = ssm._pre_activation(x, filt, bias)
    out = pre * jax.nn.sigmoid(pre) if act == "silu" else pre
    return out.astype(x.dtype)


@pytest.mark.parametrize("act", ["silu", ""])
def test_without_a_tail_the_convolution_lowers_as_it_did(act):
    """granite's convolution: the jaxpr of the op without `Tail` is,
    text for text, that of its body before this PR."""
    x = jnp.zeros((2, 16, 8), jnp.bfloat16)
    filt, bias = jnp.zeros((8, 4), jnp.bfloat16), jnp.zeros((8,), jnp.bfloat16)
    now = jax.make_jaxpr(lambda *a: registry.get_op_info(
        "causal_conv1d").kernel(None, {"X": [a[0]], "Filter": [a[1]],
                                       "Bias": [a[2]]},
                                {"activation": act})["Out"][0])(x, filt, bias)
    then = jax.make_jaxpr(lambda *a: _conv_before(*a, act))(x, filt, bias)
    assert str(now) == str(then)


def test_the_tailed_convolution_has_no_gradient_and_says_so():
    with pytest.raises(NotImplementedError, match="forward only"):
        registry.get_op_info("causal_conv1d").grad_kernel(
            None, {"Tail": [jnp.zeros((1, 3, 4))]}, {})


# -- (c) 256-wide heads, a quarter rotated, through the cache ---------------------

def _attend(q, k, v, caches, pos, heads, kv_heads):
    out = registry.get_op_info("cached_attention").kernel(
        None, {"Q": [q], "KNew": [k], "VNew": [v], "KCache": [caches[0]],
               "VCache": [caches[1]], "Position": [pos]},
        {"num_heads": heads, "num_kv_heads": kv_heads})
    return out["Out"][0], (out["KCacheOut"][0], out["VCacheOut"][0])


def _rotated(x, heads, positions, rotary):
    return registry.get_op_info("rope").kernel(
        None, {"X": [x], "Positions": [positions]},
        {"num_heads": heads, "theta": 1e7, "rotary_dim": rotary})["Out"][0]


def _plain_attention(q, k, v, heads, kv_heads):
    rows, seq, _ = q.shape
    dim = q.shape[-1] // heads
    qh = q.reshape(rows, seq, heads, dim)
    kh, vh = (np.repeat(np.asarray(t).reshape(rows, seq, kv_heads, dim),
                        heads // kv_heads, axis=2) for t in (k, v))
    s = np.einsum("bqhd,bkhd->bhqk", qh, kh) / np.sqrt(dim)
    s = np.where(np.tril(np.ones((seq, seq), bool)), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, vh).reshape(rows, seq, -1)


@pytest.mark.parametrize("slots,cuts,path", [
    (128, [0, 3] + list(range(4, 12)), "kernel"),   # a block, then steps
    (24, list(range(12)), "plain"),     # an extent the kernel does not tile
])
def test_wide_heads_partly_rotated_are_plain_attention(slots, cuts, path):
    """16 / 2 heads of 256 with 64 rotated, through `rope(rotary_dim=)`
    and `cached_attention`, against plain causal attention over the
    reference's rotation."""
    rs = np.random.RandomState(slots)
    heads, kv_heads, dim, seq, rows = 16, 2, 256, 12, 2
    q = jnp.asarray(rs.randn(rows, seq, heads * dim), jnp.float32)
    k, v = (jnp.asarray(rs.randn(rows, seq, kv_heads * dim), jnp.float32)
            for _ in range(2))
    positions = jnp.broadcast_to(jnp.arange(seq), (rows, seq))
    qr, kr = _rotated(q, heads, positions, 64), \
        _rotated(k, kv_heads, positions, 64)
    want_q, want_k = (np.asarray(reference.rope(
        t.reshape(rows, seq, n, dim), jnp.arange(seq), 1e7, 64)).reshape(
            rows, seq, -1) for t, n in ((q, heads), (k, kv_heads)))
    np.testing.assert_allclose(np.asarray(qr), want_q, atol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(qr).reshape(rows, seq, heads, dim)[..., 64:],
        np.asarray(q).reshape(rows, seq, heads, dim)[..., 64:])
    caches = [jnp.zeros((rows, kv_heads, slots, dim))] * 2
    before = telemetry.snapshot()
    outs = []
    for lo, hi in zip(cuts, cuts[1:] + [seq]):
        out, caches = _attend(qr[:, lo:hi], kr[:, lo:hi], v[:, lo:hi],
                              caches, jnp.full((rows,), lo, jnp.int32),
                              heads, kv_heads)
        outs.append(np.asarray(out))
    traced = telemetry.snapshot_delta(before)
    assert {key.split("path=")[1].split("}")[0].split(",")[0]
            for key in traced
            if key.startswith("window_attention_lowerings_total")} == {path}
    np.testing.assert_allclose(
        np.concatenate(outs, axis=1),
        _plain_attention(want_q, want_k, v, heads, kv_heads), atol=3e-5)


def test_the_chooser_takes_wide_heads_with_smaller_blocks():
    # the float32 accumulator and the operands are twice as wide at 256:
    # a float32 prefill block of a group of 8 takes half the slots a step
    assert gqa_decode.choose_block(1024, 1024, 2, 128) == 1024
    assert gqa_decode.choose_block(1024, 1024, 2, 256) == 1024
    assert gqa_decode.choose_block(1024, 1024, 4, 256) == 512
    assert gqa_decode.choose_block(2048, 2048, 2, 128) == 512
    assert gqa_decode.choose_block(2048, 2048, 2, 256) == 256
    assert gqa_decode.choose_block(1024, 8, 2, 256) == 1024
    assert gqa_decode.choose_block(1024, 8, 2, 192) == 0
