"""`ssd_scan` with its state handed in and on (ops/ssm.py `State` /
`StateOut`, kernels/ssd.py's `ssd_block_*`, `ssd_update`) against
the position-by-position float32 recurrence
(models/reference/granite_moe_hybrid.py): zeros in equals the stateless
op; a block after a block equals one block (a chunk's border inside a
block and a block's border apart); n steps equal a block; each against
the recurrence, output and the state handed on; the block kernel under
the Pallas interpreter against the plain path; what is refused; the
counters; and that training's Program is op for op what it was.  Decays
are drawn as the configuration's `ssm_init`, so that state really
crosses the borders.

Tiny sizes on the CPU, float32.
"""

import hashlib
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.kernels import ssd, ssd_step
from paddle_tpu.models.hybrid_program import build_granite_hybrid_program
from paddle_tpu.models.reference import granite_moe_hybrid as reference
from paddle_tpu.obs import telemetry
from paddle_tpu.ops import registry, ssm

# tests/test_hybrid_program.py's: the chunked scan adds up in another
# order than the recurrence, seen 1e-6 of the largest entry; a state
# dropped or a decay left out is off by a hundredth or more
RTOL = 2e-5

SCAN = dict(batch=2, heads=4, dim=8, state=16, chunk=8)


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.abs(got - want).max() / max(np.abs(want).max(), 1e-30)


def _inputs(seq, seed=0, s=SCAN):
    """The op's inputs over `seq` positions, the parameters drawn as
    `ssm_init` draws them: dt log-uniform in [1e-3, 1e-1], A = -U(1, 16):
    decays of 0.2 to 0.999 a position."""
    rs = np.random.RandomState(seed)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    dt = np.exp(rs.uniform(np.log(1e-3), np.log(1e-1), s["heads"]))
    return {
        "X": f32(rs.randn(s["batch"], seq, s["heads"] * s["dim"])),
        "Dt": f32(0.3 * rs.randn(s["batch"], seq, s["heads"])),
        "DtBias": f32(np.log(np.expm1(dt))),
        "ALog": f32(np.log(rs.uniform(1.0, 16.0, s["heads"]))),
        "B": f32(0.5 * rs.randn(s["batch"], seq, s["state"])),
        "C": f32(0.5 * rs.randn(s["batch"], seq, s["state"])),
        "D": f32(1.0 + 0.1 * rs.randn(s["heads"])),
    }


def _cut(ins, start, stop):
    return {k: v[:, start:stop] if v.ndim == 3 else v
            for k, v in ins.items()}


def _zeros(s=SCAN):
    return jnp.zeros((s["batch"], s["state"], s["heads"] * s["dim"]),
                     jnp.float32)


def _op(ins, state=None, s=SCAN):
    """(Y, StateOut) of the op with `state`, (Y, None) without."""
    info = registry.get_op_info("ssd_scan")
    attrs = {"num_heads": s["heads"], "chunk_size": s["chunk"]}
    fed = {k: [v] for k, v in ins.items()}
    if state is None:
        return info.kernel(None, fed, attrs)["Y"][0], None
    out = info.kernel(None, dict(fed, State=[state]), attrs)
    return out["Y"][0], out["StateOut"][0]


def _blocks(ins, cuts, state):
    """The op over consecutive blocks of `cuts` positions from `state`:
    (Y of all of them, the state after the last)."""
    ys, at = [], 0
    for length in cuts:
        y, state = _op(_cut(ins, at, at + length), state)
        ys.append(y)
        at += length
    return jnp.concatenate(ys, axis=1), state


def _sequential(ins, s=SCAN):
    """(y, the state after the last position a head at a time) of the
    reference's recurrence, from zeros."""
    batch, seq, _ = ins["X"].shape
    y, state = reference.recurrence(
        {}, ins["X"].reshape(batch, seq, s["heads"], s["dim"]),
        jax.nn.softplus(ins["Dt"] + ins["DtBias"]), -jnp.exp(ins["ALog"]),
        ins["B"], ins["C"], ins["D"])
    return y.reshape(ins["X"].shape), state


@pytest.fixture(scope="module")
def whole():
    ins = _inputs(24)
    y, state = _sequential(ins)
    return {"ins": ins, "y": y, "state": state}


def test_zeros_in_is_the_stateless_op(whole):
    stateless, _ = _op(whole["ins"])
    y, state = _op(whole["ins"], _zeros())
    np.testing.assert_array_equal(np.asarray(y), np.asarray(stateless))
    assert state.shape == _zeros().shape and state.dtype == jnp.float32
    assert _rel(ssm.heads_apart(state, SCAN["heads"]), whole["state"]) < RTOL


@pytest.mark.parametrize("cuts", [
    (24,), (8, 16), (16, 8), (8, 8, 8)], ids=lambda c: "+".join(map(str, c)))
def test_blocks_agree_with_the_recurrence(whole, cuts):
    """A chunk's border inside a block and a block's border apart."""
    y, state = _blocks(whole["ins"], cuts, _zeros())
    assert _rel(y, whole["y"]) < RTOL
    assert _rel(ssm.heads_apart(state, SCAN["heads"]), whole["state"]) < RTOL


@pytest.mark.parametrize("cuts", [
    (1,) * 24, (8,) + (1,) * 16, (16,) + (1,) * 8, (1,) * 8 + (16,)],
    ids=["steps", "8+steps", "16+steps", "steps+16"])
def test_steps_equal_a_block(whole, cuts):
    """A prompt as blocks and then a position a step, and steps alone:
    what the whole sequence gives, the state across the border."""
    y, state = _blocks(whole["ins"], cuts, _zeros())
    assert _rel(y, whole["y"]) < RTOL
    assert _rel(ssm.heads_apart(state, SCAN["heads"]), whole["state"]) < RTOL


def test_the_state_really_crosses_the_borders(whole):
    """A block that started from zeros again would be far off (a
    thousand times the tolerance; `D x`, which no state moves, is most
    of y under steps this small): the comparisons above do test the
    state handed in."""
    ins = whole["ins"]
    first, _ = _op(_cut(ins, 0, 16), _zeros())
    again, _ = _op(_cut(ins, 16, 24), _zeros())
    assert _rel(jnp.concatenate([first, again], axis=1), whole["y"]) > 0.02


# -- the kernels under the interpreter ------------------------------------------

BLOCK_CASES = {
    "4x32": dict(batch=2, seq=32, heads=4, dim=32, state=16, chunk=16),
    "4x64": dict(batch=1, seq=48, heads=4, dim=64, state=16, chunk=16),
    "2x128": dict(batch=1, seq=16, heads=2, dim=128, state=8, chunk=16),
}


def _kernel_operands(k, seed=3):
    rs = np.random.RandomState(seed)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    shape = (k["batch"], k["seq"])
    width = k["heads"] * k["dim"]
    x = f32(rs.randn(*shape, width))
    b, c = (f32(0.5 * rs.randn(*shape, k["state"])) for _ in range(2))
    dt = f32(np.exp(rs.uniform(np.log(1e-3), np.log(0.3),
                               shape + (k["heads"],))))
    a = dt * f32(-np.exp(rs.uniform(0, 2.7, k["heads"])))
    d_skip = f32(1.0 + 0.1 * rs.randn(k["heads"]))
    state = f32(rs.randn(k["batch"], k["state"], width))
    return (x, dt, a, b, c, d_skip), state


@pytest.mark.parametrize("case", list(BLOCK_CASES))
@pytest.mark.parametrize("what", ["y", "state"])
def test_the_block_kernel_agrees_with_the_plain_path(case, what):
    k = BLOCK_CASES[case]
    args, state = _kernel_operands(k)
    want = ssm.chunked_scan(*args, k["chunk"], state=state)
    got = ssd.fwd_kernels(*args, k["chunk"], interpret=True, entering=state)
    index = ("y", "state").index(what)
    assert got[index].shape == want[index].shape
    assert got[index].dtype == want[index].dtype
    assert _rel(got[index], want[index]) < RTOL


STEP_CASES = {
    # rows, heads x width: two heads a lane block, a head a lane block,
    # a head that fills none
    "16x64": dict(batch=2, heads=16, dim=64, state=128),
    "32x64": dict(batch=4, heads=32, dim=64, state=128),
    "8x128": dict(batch=2, heads=8, dim=128, state=128),
    "4x8": dict(batch=3, heads=4, dim=8, state=16),
}


@pytest.mark.parametrize("case", list(STEP_CASES))
def test_the_step_is_one_position_of_the_recurrence_from_its_state(case):
    """`ssd_update` over the state as it is carried, state entries by
    head lanes, against the reference's recurrence a head at a time from
    the same state."""
    k = STEP_CASES[case]
    (x, dt, a, b, c, d_skip), state = _kernel_operands(dict(k, seq=1))
    got_y, got_state = ssm.ssd_update(state, x[:, 0], dt[:, 0], a[:, 0],
                                      b[:, 0], c[:, 0], d_skip)
    rate = (a / dt)[0, 0]       # a = dt * A, A a head
    want_y, want_state = reference.recurrence(
        {}, x.reshape(k["batch"], 1, k["heads"], k["dim"]), dt, rate, b, c,
        d_skip, start=ssm.heads_apart(state, k["heads"]))
    assert got_state.shape == state.shape and got_state.dtype == state.dtype
    assert _rel(got_y, want_y.reshape(got_y.shape)) < RTOL
    assert _rel(ssm.heads_apart(got_state, k["heads"]), want_state) < RTOL


def test_the_plain_step_is_one_position_of_the_recurrence():
    ins = _inputs(1, seed=5)
    state = jnp.asarray(np.random.RandomState(6).randn(
        *_zeros().shape), jnp.float32)
    y, new = _op(ins, state)
    # the recurrence from zeros over one position, plus what the state
    # handed in gives: decayed, read by C
    s = SCAN
    dt = jax.nn.softplus(ins["Dt"] + ins["DtBias"])[:, 0]
    decay = jnp.exp(dt * -jnp.exp(ins["ALog"]))
    apart = ssm.heads_apart(state, s["heads"]) * decay[:, :, None, None]
    from_zero_y, from_zero = _sequential(ins)
    want_state = apart + from_zero
    want_y = from_zero_y[:, 0] + jnp.einsum(
        "bhpn,bn->bhp", apart, ins["C"][:, 0]).reshape(s["batch"], -1)
    assert _rel(y[:, 0], want_y) < RTOL
    assert _rel(ssm.heads_apart(new, s["heads"]), want_state) < RTOL


# the step kernel's body, copies and all (kernels/ssd_step.py): STEP_CASES
# at the block the shapes choose, the last block the only block; several
# grid steps, so that a block's way in, work and way out lie beside
# their neighbours'; 16 state entries, B and C padded to a lane block;
# 8, a block that comes in as one slice; the rows beside the state
KERNEL_CASES = {
    "16x64": (STEP_CASES["16x64"], None),
    "32x64": (STEP_CASES["32x64"], None),
    "8x128": (STEP_CASES["8x128"], None),
    "32x64-a-row-a-step": (STEP_CASES["32x64"], 1),
    "32x64-two-rows-a-step": (STEP_CASES["32x64"], 2),
    "6-rows-of-16-entries": (dict(batch=6, heads=4, dim=32, state=16), 2),
    "8-entries": (dict(batch=2, heads=2, dim=64, state=8), 1),
    # the rows beside the state a tile of 8 that four grid steps share,
    # a block's own 8, and all 12 where neither divides
    "16-rows-two-a-step": (dict(batch=16, heads=2, dim=64, state=8), 2),
    "16-rows-eight-a-step": (dict(batch=16, heads=2, dim=64, state=8), 8),
    "12-rows-four-a-step": (dict(batch=12, heads=2, dim=64, state=8), 4),
}


def _step_operands(k, seed=3):
    (x, dt, a, b, c, d_skip), state = _kernel_operands(dict(k, seq=1), seed)
    return state, x[:, 0], dt[:, 0], a[:, 0], b[:, 0], c[:, 0], d_skip


@pytest.mark.parametrize("case", list(KERNEL_CASES))
@pytest.mark.parametrize("against", ["ssd_update", "recurrence"])
def test_the_step_kernel_is_the_plain_step(case, against):
    k, block = KERNEL_CASES[case]
    at = _step_operands(k)
    got_y, got_state = ssd_step.step(*at, plain=None, block=block,
                                     interpret=True)
    if against == "ssd_update":
        want_y, want_state = ssm.ssd_update(*at)
    else:
        state, x, dt, a, b, c, d_skip = at
        want_y, apart = reference.recurrence(
            {}, x.reshape(k["batch"], 1, k["heads"], k["dim"]), dt[:, None],
            (a / dt)[0], b[:, None], c[:, None], d_skip,
            start=ssm.heads_apart(state, k["heads"]))
        want_y = want_y.reshape(got_y.shape)
        got_state = ssm.heads_apart(got_state, k["heads"])
        want_state = apart
    assert got_y.dtype == jnp.float32 and got_state.dtype == jnp.float32
    assert got_state.shape == want_state.shape
    assert _rel(got_y, want_y) < RTOL
    assert _rel(got_state, want_state) < RTOL


def test_the_step_kernel_hands_back_the_buffer_it_was_handed():
    """The state is the kernel's fifth operand and its second result,
    one buffer (`input_output_aliases`), and stays in HBM: no block of
    it is the pipeline's to move."""
    at = _step_operands(STEP_CASES["32x64"])
    text = str(jax.make_jaxpr(lambda *v: ssd_step.step(
        *v, plain=None, block=2, interpret=True))(*at))
    assert "name=ssd_step_r4_b2" in text
    assert "input_output_aliases=((4, 1),)" in text
    # the kernel's own arguments: the state in and the state out
    assert text.count("Ref<any>{f32[4,128,2048]}") >= 2
    assert "Ref<vmem>{f32[4,128,2048]}" not in text


@pytest.mark.parametrize("what,shape,dtype", [
    ("a-bfloat16-state", (2, 128, 1024), jnp.bfloat16),
    ("entries-off-the-sublane-tile", (2, 12, 1024), jnp.float32),
    ("a-width-off-the-lane-block", (3, 16, 32), jnp.float32),
    ("a-row-over-a-block", (2, 1024, 8192), jnp.float32)])
def test_the_step_kernel_refuses_what_it_cannot_tile(what, shape, dtype):
    assert ssd_step.choose_block(*shape, dtype) is None


@pytest.mark.parametrize("rows,held", [(64, 4), (6, 3), (7, 1), (1, 1)])
def test_a_block_of_the_step_kernel_divides_the_rows(rows, held):
    """Granite's row of 128 x 8192 float32 is a quarter of
    `_STEP_BYTES`."""
    assert ssd_step.choose_block(rows, 128, 8192, jnp.float32) == held


def test_the_step_kernel_refuses_rows_its_block_does_not_divide():
    at = _step_operands(STEP_CASES["32x64"])
    with pytest.raises(ValueError, match="no step the kernel takes"):
        ssd_step.step(*at, plain=None, block=3, interpret=True)
    with pytest.raises(ValueError, match="no step the kernel takes"):
        ssd_step.step(at[0].astype(jnp.bfloat16), *at[1:], plain=None,
                      interpret=True)


def _lowered_step(k):
    """(the op's outputs, what the counters say) of one step at k."""
    state, x, dt, a, b, c, d_skip = _step_operands(k)
    ins = {"X": x[:, None], "Dt": dt[:, None], "B": b[:, None],
           "C": c[:, None], "DtBias": jnp.zeros(k["heads"]),
           "ALog": jnp.zeros(k["heads"]), "D": d_skip, "State": state}
    before = telemetry.snapshot()
    out = registry.get_op_info("ssd_scan").kernel(
        None, {name: [value] for name, value in ins.items()},
        {"num_heads": k["heads"], "chunk_size": 8})
    return out, telemetry.snapshot_delta(before)


def test_a_step_lowers_the_kernel_where_its_shape_allows():
    """`path=kernel` at the shapes `choose_block` takes (on any platform
    but the TPU what is lowered in its place is `ssd_update`, and the
    numbers are `ssd_update`'s), `path=plain` at a width that is no whole
    lane block."""
    for case, path in (("16x64", "kernel"), ("4x8", "plain")):
        k = STEP_CASES[case]
        out, delta = _lowered_step(k)
        assert delta["ssd_scan_lowerings_total{chunk=0,form=step,heads=%d,"
                     "path=%s,state_dtype=float32}" % (k["heads"], path)] == 1
        width = k["heads"] * k["dim"]
        assert out["StateOut"][0].shape == (k["batch"], k["state"], width)
        assert out["Y"][0].shape == (k["batch"], 1, width)


@pytest.mark.parametrize("case", ["16x64", "4x8"])
def test_the_op_s_step_is_ssd_update_on_this_platform(case):
    """Through the op, kernel path or plain: one float32 update of the
    state handed in, bit for bit what `ssd_update` gives from the op's
    own dt and a."""
    k = STEP_CASES[case]
    out, _ = _lowered_step(k)
    state, x, dt_raw, _, b, c, d_skip = _step_operands(k)
    dt, a, _ = ssm._steps(dt_raw, jnp.zeros(k["heads"]),
                          jnp.zeros(k["heads"]))
    want_y, want_state = ssm.ssd_update(state, x, dt, a, b, c, d_skip)
    np.testing.assert_array_equal(np.asarray(out["StateOut"][0]),
                                  np.asarray(want_state))
    assert _rel(out["Y"][0][:, 0], want_y) < RTOL


# -- what is refused, shapes, counters -----------------------------------------

def _program(seq, chunk=8, with_state=True):
    s = SCAN
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        def data(name, shape):
            return fluid.layers.data(name=name, shape=shape,
                                     dtype="float32",
                                     append_batch_size=False)
        x = data("x", [s["batch"], seq, s["heads"] * s["dim"]])
        dt = data("dt", [s["batch"], seq, s["heads"]])
        b = data("b", [s["batch"], seq, s["state"]])
        c = data("c", [s["batch"], seq, s["state"]])
        state = data("state", list(_zeros().shape)) if with_state else None
        out = fluid.layers.ssd_scan(x, dt, b, c, s["heads"],
                                    chunk_size=chunk, state=state)
    return main, out


@pytest.mark.parametrize("seq", [1, 8, 16, -1])
def test_the_build_knows_every_shape(seq):
    _, (y, state) = _program(seq)
    assert tuple(y.shape) == (SCAN["batch"], seq, SCAN["heads"] * SCAN["dim"])
    assert tuple(state.shape) == tuple(_zeros().shape)
    assert str(state.dtype).endswith("float32") or "FP32" in str(state.dtype)


def test_a_block_off_the_chunk_is_an_error_at_build():
    with pytest.raises(ValueError, match="no multiple of the chunk"):
        _program(12)
    with pytest.raises(ValueError, match="no multiple of the chunk"):
        _program(1, with_state=False)


def test_a_block_off_the_chunk_is_an_error_at_lowering():
    with pytest.raises(ValueError, match="no multiple of the chunk"):
        _op(_inputs(5), _zeros())


def test_a_state_of_another_shape_or_type_is_refused():
    with pytest.raises(ValueError, match="State"):
        _op(_inputs(8), _zeros()[:, :8])
    with pytest.raises(ValueError, match="State"):
        _op(_inputs(8), _zeros().astype(jnp.bfloat16))


def test_the_carried_form_has_no_gradient():
    ins = _inputs(8)
    info = registry.get_op_info("ssd_scan")
    with pytest.raises(NotImplementedError, match="forward only"):
        info.grad_kernel(None, dict({k: [v] for k, v in ins.items()},
                                    State=[_zeros()]),
                         {"num_heads": 4, "chunk_size": 8})


def test_counters_say_what_was_lowered():
    before = telemetry.snapshot()
    _op(_inputs(8))
    _op(_inputs(16), _zeros())
    _op(_inputs(1), _zeros())
    delta = telemetry.snapshot_delta(before)
    for form, chunk in (("block", 8), ("step", 0)):
        assert delta["ssd_scan_lowerings_total{chunk=%d,form=%s,heads=4,"
                     "path=plain,state_dtype=float32}" % (chunk, form)] == 1
    # training's form carries no state: its kernels count it
    assert not [k for k in delta if "form=train" in k]
    # a row's state, 16 entries x 32 lanes of float32, handed on twice
    assert delta["recurrent_state_bytes_total{kind=ssd}"] == 2 * 16 * 32 * 4


# -- training's Program is what it was ---------------------------------------------

def _fingerprint(program):
    ops = [(op.type, sorted((k, repr(v)) for k, v in op.attrs.items()
                            if not k.startswith("op_")),
            sorted((k, len(v)) for k, v in op.inputs.items()),
            sorted((k, len(v)) for k, v in op.outputs.items()))
           for op in program.global_block().desc.ops]
    return len(ops), hashlib.sha256(
        json.dumps(ops, sort_keys=True).encode()).hexdigest()[:16]


def test_the_training_program_is_op_for_op_what_it_was():
    """Recorded on the parent commit (PR 70's tree), before `ssd_scan`
    and `_mamba_mixer` could carry a state: the same op types in the
    same order with the same attrs, inputs and outputs."""
    main, startup, loss, _ = build_granite_hybrid_program(
        1, 32, 97, layer_types=("mamba", "mamba", "attention"), chunk=8)
    with fluid.program_guard(main, startup):
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    assert _fingerprint(main) == (188, "6593f06096d0ff00")


def test_the_training_kernels_keep_their_names():
    """`granite-train-4k`'s readers find the scan's kernels by name."""
    k = dict(batch=1, seq=16, heads=2, dim=64, state=16, chunk=16)
    args, state = _kernel_operands(k)
    named = lambda fn, *a, **kw: str(jax.make_jaxpr(
        lambda *v: fn(*v, **kw))(*a))
    assert "ssd_fwd_c16_h2" in named(ssd.fwd_kernels, *args, chunk=16,
                                     interpret=True)
    assert "ssd_block_c16_h2" in named(
        lambda *v: ssd.fwd_kernels(*v[:-1], 16, interpret=True,
                                   entering=v[-1]), *args, state)
