"""The state-space layer's ops (ops/ssm.py, kernels/ssd.py,
`fluid.layers.ssd_scan` and `causal_conv1d`) and the granite hybrid
decoder built on them (models/hybrid_program.py) against the plain
float32 reference (models/reference/granite_hybrid.py), whose recurrence
walks the positions one by one: the scan over 1, 2 and 5 chunks with
decays near 1 and near 0, output and every gradient; the Mosaic kernels
under the Pallas interpreter against the plain chunked path; the
convolution, its gradient and its causality; grouped key/value heads and
the tied head; the whole model's loss and every parameter's gradient;
what stays float32 under bfloat16 compute; shapes without a trace, the
counters, the initializer; and that Ouro's and OLMoE's programs are
op for op what they were.

Tiny sizes on the CPU, float32 unless said.
"""

import hashlib
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid.param_attr import ParamAttr
from paddle_tpu.kernels import ssd
from paddle_tpu.models.hybrid_program import (build_granite_hybrid_program,
                                              granite_hybrid_param_names)
from paddle_tpu.models.looped_program import build_looped_program
from paddle_tpu.models.moe_program import build_olmoe_program
from paddle_tpu.models.reference import granite_hybrid as reference
from paddle_tpu.obs import telemetry
from paddle_tpu.ops import registry, ssm

# float32 on the CPU.  The chunked scan adds up in another order than the
# sequential recurrence (products over a chunk, exponentials of
# differences of sums instead of a running product of decays): seen 1e-6
# of the largest entry, forward and backward.  2e-5 is twenty times that
# and twenty times under one bfloat16 rounding (2^-9 = 2e-3); a decay
# left out, a chunk's state dropped or a gradient's term forgotten is off
# by a hundredth or more.
RTOL = 2e-5

SLOTS = ("X", "Dt", "DtBias", "ALog", "B", "C", "D")


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.abs(got - want).max() / max(np.abs(want).max(), 1e-30)


# -- the scan op against the sequential recurrence ---------------------------

SCAN = dict(batch=2, heads=4, dim=8, state=16, chunk=8)
DECAYS = {
    # dt A a step: exp of it is the share of the state that survives
    "near_1": (1e-3, 1e-2),     # 0.99 .. 0.999: the state crosses chunks
    "near_0": (2.0, 8.0),       # 0.14 .. 3e-4: it dies inside a chunk
    "mixed": (1e-3, 4.0),
}


def _scan_inputs(chunks, decay, seed=0):
    s = SCAN
    seq = chunks * s["chunk"]
    rs = np.random.RandomState(seed)
    lo, hi = DECAYS[decay]
    steps = np.exp(rs.uniform(np.log(lo), np.log(hi), s["heads"]))
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    return {
        "X": f32(rs.randn(s["batch"], seq, s["heads"] * s["dim"])),
        "Dt": f32(0.3 * rs.randn(s["batch"], seq, s["heads"])),
        # softplus(Dt + DtBias) * exp(ALog) is about `steps`
        "DtBias": f32(np.log(np.expm1(0.5)) * np.ones(s["heads"])),
        "ALog": f32(np.log(steps / 0.5)),
        "B": f32(0.5 * rs.randn(s["batch"], seq, s["state"])),
        "C": f32(0.5 * rs.randn(s["batch"], seq, s["state"])),
        "D": f32(1.0 + 0.1 * rs.randn(s["heads"])),
    }, f32(rs.randn(s["batch"], seq, s["heads"] * s["dim"]))


def _sequential(ins):
    s = SCAN
    batch, seq, _ = ins["X"].shape
    y = reference.recurrence(
        ins["X"].reshape(batch, seq, s["heads"], s["dim"]),
        jax.nn.softplus(ins["Dt"] + ins["DtBias"]), -jnp.exp(ins["ALog"]),
        ins["B"], ins["C"], ins["D"])
    return y.reshape(ins["X"].shape)


def _run_scan_op(ins, dy):
    info = registry.get_op_info("ssd_scan")
    attrs = {"num_heads": SCAN["heads"], "chunk_size": SCAN["chunk"]}
    out = info.kernel(None, {k: [v] for k, v in ins.items()}, attrs)
    grad_ins = {k: [v] for k, v in ins.items()}
    grad_ins.update({"O@Y": out["Y"], "O@States": out["States"],
                     "OG@Y": [dy]})
    grads = info.grad_kernel(None, grad_ins, attrs)
    return out["Y"][0], {s: grads[s + "@GRAD"][0] for s in SLOTS}


@pytest.fixture(scope="module", params=[
    (1, "mixed"), (2, "near_1"), (2, "near_0"), (5, "near_1"),
    (5, "near_0"), (5, "mixed")], ids=lambda p: "%d_chunks_%s" % p)
def scanned(request):
    chunks, decay = request.param
    ins, dy = _scan_inputs(chunks, decay)
    y, grads = _run_scan_op(ins, dy)
    want_y, vjp = jax.vjp(lambda *v: _sequential(dict(zip(SLOTS, v))),
                          *(ins[s] for s in SLOTS))
    return {"y": y, "grads": grads, "want_y": want_y,
            "want_grads": dict(zip(SLOTS, vjp(dy))), "ins": ins}


def test_scan_output_agrees_with_the_recurrence(scanned):
    assert _rel(scanned["y"], scanned["want_y"]) < RTOL


@pytest.mark.parametrize("slot", SLOTS)
def test_scan_gradient_agrees_with_the_recurrence(scanned, slot):
    got, want = scanned["grads"][slot], scanned["want_grads"][slot]
    assert got.shape == want.shape and got.dtype == want.dtype
    assert _rel(got, want) < RTOL


def test_the_state_really_crosses_chunks():
    """With decays near 1 most of what a late position reads was written
    chunks earlier: a scan that dropped the carried state would be far
    off, so the comparisons above do test it."""
    ins, _ = _scan_inputs(5, "near_1")
    s = SCAN
    want = _sequential(ins)
    own_chunk_only = jnp.concatenate([
        _sequential({k: (v[:, i * s["chunk"]:(i + 1) * s["chunk"]]
                         if v.ndim == 3 else v) for k, v in ins.items()})
        for i in range(5)], axis=1)
    assert _rel(own_chunk_only, want) > 0.2


# -- the Mosaic kernels under the interpreter --------------------------------

KERNEL_CASES = {
    # heads x width: 4 heads a step, 2 a step (the cell's), 1 a step
    "4x32": dict(batch=2, seq=32, heads=4, dim=32, state=16, chunk=16),
    "4x64": dict(batch=1, seq=48, heads=4, dim=64, state=16, chunk=16),
    "2x128": dict(batch=1, seq=32, heads=2, dim=128, state=8, chunk=16),
}


@pytest.fixture(scope="module", params=list(KERNEL_CASES))
def kernels_and_plain(request):
    k = KERNEL_CASES[request.param]
    rs = np.random.RandomState(3)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    shape = (k["batch"], k["seq"])
    x = f32(rs.randn(*shape, k["heads"] * k["dim"]))
    dy = f32(rs.randn(*shape, k["heads"] * k["dim"]))
    b, c = (f32(0.5 * rs.randn(*shape, k["state"])) for _ in range(2))
    dt = f32(np.exp(rs.uniform(np.log(1e-3), np.log(0.3),
                               shape + (k["heads"],))))
    a = dt * f32(-np.exp(rs.uniform(0, 2.7, k["heads"])))
    d_skip = f32(1.0 + 0.1 * rs.randn(k["heads"]))
    args = (x, dt, a, b, c, d_skip)
    y, states = ssm.chunked_scan(*args, k["chunk"])
    plain = ssm.chunked_scan_grad(*args, states, dy, k["chunk"])
    got_y, got_states = ssd.fwd_kernels(*args, k["chunk"], interpret=True)
    got = ssd.bwd_kernels(*args, states, dy, k["chunk"], interpret=True)
    return {"y": (got_y, y), "states": (got_states, states),
            **{name: (g, p) for name, g, p in zip(
                ("dx", "ddt", "da", "db", "dc", "dd"), got, plain)}}


@pytest.mark.parametrize("what", ["y", "states", "dx", "ddt", "da", "db",
                                  "dc", "dd"])
def test_kernels_agree_with_the_plain_path(kernels_and_plain, what):
    got, want = kernels_and_plain[what]
    assert got.shape == want.shape and got.dtype == want.dtype
    assert _rel(got, want) < RTOL


def test_heads_a_step_are_read_off_the_shape():
    assert ssd.heads_a_step(64 * 64, 64) == 2      # the cell's
    assert ssd.heads_a_step(4 * 32, 4) == 4
    assert ssd.heads_a_step(2 * 128, 2) == 1
    # widths that do not divide 128, and heads that do not fill a step,
    # take the plain path
    assert ssd.heads_a_step(4 * 96, 4) == 0
    assert ssd.heads_a_step(3 * 64, 3) == 0
    assert ssd.heads_a_step(32 * 8, 32) == 0


# -- shapes, errors, counters --------------------------------------------------

def _scan_program(seq, chunk):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        def data(name, width):
            return fluid.layers.data(name=name, shape=[2, seq, width],
                                     dtype="float32",
                                     append_batch_size=False)
        y = fluid.layers.ssd_scan(data("x", 32), data("dt", 4),
                                  data("b", 16), data("c", 16), 4,
                                  chunk_size=chunk)
    return main, startup, y


def test_a_sequence_off_the_chunk_is_an_error_at_build():
    with pytest.raises(ValueError, match="no multiple of the chunk"):
        _scan_program(20, 8)


def test_the_build_traces_nothing_and_knows_every_shape():
    before = telemetry.snapshot()
    main, _, y = _scan_program(24, 8)
    built = telemetry.snapshot_delta(before)
    assert not [k for k in built if k.startswith(("ssd_", "causal_conv1d"))]
    op = [o for o in main.global_block().desc.ops
          if o.type == "ssd_scan"][0]
    block = main.global_block()
    assert tuple(y.shape) == (2, 24, 32)
    states = block.var(op.output("States")[0])
    assert tuple(states.shape) == (2, 3, 16, 32)
    assert states.dtype == "float32"


def _lowerings(delta, prefix):
    return {k[len(prefix):]: v for k, v in delta.items()
            if k.startswith(prefix)}


def test_the_gradient_op_lowers_no_forward_scan():
    """One "fwd" for the op and one "bwd" for its gradient: a gradient
    that ran the forward again (`run_generic_grad`) would count a second
    "fwd"."""
    ins, dy = _scan_inputs(2, "mixed")
    before = telemetry.snapshot()
    _run_scan_op(ins, dy)
    counts = _lowerings(telemetry.snapshot_delta(before),
                        "ssd_lowerings_total")
    by_kernel = {k: sum(v for key, v in counts.items()
                        if "kernel=%s" % k in key) for k in ("fwd", "bwd")}
    assert by_kernel == {"fwd": 1, "bwd": 1}
    assert all("chunk=8" in k and "heads_per_step=" in k for k in counts)
    assert registry.get_op_info("ssd_scan").grad_kernel is not None
    assert registry.get_op_info("causal_conv1d").grad_kernel is not None


# -- the convolution -----------------------------------------------------------

CONV = dict(batch=2, seq=12, channels=10, width=4)


def _conv_inputs(seed=0):
    rs = np.random.RandomState(seed)
    c = CONV
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    return (f32(rs.randn(c["batch"], c["seq"], c["channels"])),
            f32(rs.randn(c["channels"], c["width"])),
            f32(rs.randn(c["channels"])),
            f32(rs.randn(c["batch"], c["seq"], c["channels"])))


def _conv_op(x, w, b, activation="silu"):
    info = registry.get_op_info("causal_conv1d")
    return info.kernel(None, {"X": [x], "Filter": [w], "Bias": [b]},
                       {"activation": activation})["Out"][0]


def test_convolution_agrees_with_four_shifted_adds():
    x, w, b, _ = _conv_inputs()
    np.testing.assert_allclose(_conv_op(x, w, b),
                               reference.causal_conv(x, w, b), atol=1e-6)
    # without the activation: the pre-activation itself
    silu_inverse_free = _conv_op(x, w, b, activation="")
    np.testing.assert_allclose(jax.nn.silu(silu_inverse_free),
                               reference.causal_conv(x, w, b), atol=1e-6)


@pytest.mark.parametrize("slot,index", [("X", 0), ("Filter", 1),
                                        ("Bias", 2)])
def test_convolution_gradient_is_the_references(slot, index):
    x, w, b, dy = _conv_inputs(1)
    info = registry.get_op_info("causal_conv1d")
    got = info.grad_kernel(
        None, {"X": [x], "Filter": [w], "Bias": [b], "OG@Out": [dy]},
        {"activation": "silu"})[slot + "@GRAD"][0]
    want = jax.vjp(reference.causal_conv, x, w, b)[1](dy)[index]
    assert got.shape == want.shape
    assert _rel(got, want) < RTOL


def test_position_t_does_not_read_t_plus_1():
    x, w, b, dy = _conv_inputs(2)
    t = 5
    later = x.at[:, t + 1:].add(1.0)
    np.testing.assert_array_equal(_conv_op(x, w, b)[:, :t + 1],
                                  _conv_op(later, w, b)[:, :t + 1])
    assert np.abs(_conv_op(x, w, b)[:, t + 1]
                  - _conv_op(later, w, b)[:, t + 1]).max() > 1e-3
    # and the gradient to x_t comes from the outputs t .. t + 3 only
    info = registry.get_op_info("causal_conv1d")
    ins = {"X": [x], "Filter": [w], "Bias": [b]}
    dx = lambda g: info.grad_kernel(None, dict(ins, **{"OG@Out": [g]}),
                                    {"activation": "silu"})["X@GRAD"][0]
    outside = dy.at[:, :t].add(1.0).at[:, t + CONV["width"]:].add(1.0)
    np.testing.assert_allclose(dx(dy)[:, t], dx(outside)[:, t], atol=1e-6)


def test_convolution_refuses_an_activation_it_does_not_know():
    x, w, b, _ = _conv_inputs()
    with pytest.raises(ValueError, match="activation"):
        _conv_op(x, w, b, activation="relu")


# -- the whole model -------------------------------------------------------------

LT = ("mamba", "mamba", "attention")
B, T, V = 2, 40, 97
SIZES = dict(d_model=32, d_ff=48, n_head=4, n_kv_head=2, mamba_heads=4,
             mamba_d_head=16, d_state=16, d_conv=4, chunk=8, sm_scale=0.2,
             embedding_multiplier=3.0, residual_multiplier=0.5,
             logits_scaling=2.0)
CFG = {"layer_types": LT, "mamba_n_heads": 4, "mamba_d_head": 16,
       "mamba_d_state": 16, "num_attention_heads": 4,
       "num_key_value_heads": 2, "attention_multiplier": 0.2,
       "embedding_multiplier": 3.0, "residual_multiplier": 0.5,
       "logits_scaling": 2.0, "rms_norm_eps": 1e-5}
NAMES = granite_hybrid_param_names(LT)
PARAMS = jax.tree_util.tree_leaves(NAMES)
# the program's attention is the flash kernel under the interpreter, its
# scan chunked: logits of size ~1.5 were seen to differ by 7e-7, the loss
# by 1e-7 of it, gradients by 2e-6 of each parameter's largest entry
FORWARD_ATOL = 1e-5
LOSS_RTOL = 2e-6


def _feeds(seed=0):
    tok = np.random.RandomState(seed).randint(0, V, (B, T + 1))
    return {"tokens": tok[:, :-1].astype("int64"),
            "targets": tok[:, 1:, None].astype("int64")}


def _start(startup, names, seed=3):
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    startup.random_seed = seed
    exe.run(startup, scope=scope)
    rs = np.random.RandomState(seed)
    for name in names:
        value = np.asarray(scope.get(name), np.float32)
        # norm scales, D and the convolution's bias moved off their
        # initial 1 and 0, so that one left out shows
        if value.ndim == 1 and not name.endswith(("a_log", "dt_bias")):
            value = value + 0.1 * rs.randn(*value.shape).astype("float32")
        scope.set(name, jnp.asarray(value))
    return exe, scope


@pytest.fixture(scope="module")
def trained_once():
    before = telemetry.snapshot()
    main, startup, loss, parts = build_granite_hybrid_program(
        B, T, V, layer_types=LT, **SIZES)
    with fluid.program_guard(main, startup):
        grads = dict((p.name, g) for p, g in
                     fluid.backward.append_backward(loss))
    built = telemetry.snapshot_delta(before)
    exe, scope = _start(startup, PARAMS)
    feeds = _feeds()
    out = exe.run(main, feed=feeds, scope=scope,
                  fetch_list=[loss, parts["logits"]]
                  + [grads[n] for n in PARAMS])
    lowered = telemetry.snapshot_delta(before)
    params = jax.tree_util.tree_map(scope.get, NAMES)
    jfeeds = {k: jnp.asarray(v) for k, v in feeds.items()}
    want_grads = jax.grad(lambda p: reference.loss(CFG, p, jfeeds))(params)
    return {
        "main": main, "built": built, "lowered": lowered,
        "loss": float(out[0].reshape(-1)[0]), "logits": out[1],
        "grads": dict(zip(PARAMS, out[2:])),
        "want_loss": float(reference.loss(CFG, params, jfeeds)),
        "want_logits": np.asarray(reference.logits(CFG, params,
                                                   jfeeds["tokens"])),
        "want_grads": dict(zip(PARAMS,
                               jax.tree_util.tree_leaves(want_grads))),
        "params": params, "feeds": jfeeds,
    }


def test_loss_and_logits_agree_with_the_reference(trained_once):
    assert trained_once["loss"] == pytest.approx(trained_once["want_loss"],
                                                 rel=LOSS_RTOL)
    np.testing.assert_allclose(trained_once["logits"],
                               trained_once["want_logits"],
                               atol=FORWARD_ATOL, rtol=0)
    # the last positions against the whole context, as the chip check
    last = reference.logits(CFG, trained_once["params"],
                            trained_once["feeds"]["tokens"], last=8)
    np.testing.assert_allclose(trained_once["logits"][:, -8:], last,
                               atol=FORWARD_ATOL, rtol=0)


@pytest.mark.parametrize("name", PARAMS)
def test_every_gradient_agrees_with_the_reference(trained_once, name):
    got, want = trained_once["grads"][name], trained_once["want_grads"][name]
    assert got.shape == want.shape
    assert np.abs(np.asarray(want)).max() > 0
    assert _rel(got, want) < RTOL


def test_the_program_has_every_parameter_the_reference_names(trained_once):
    built = {p.name for p in
             trained_once["main"].global_block().all_parameters()}
    assert built == set(PARAMS)
    assert "head.w" not in built      # the head is the embedding


def test_counters_say_what_was_lowered(trained_once):
    assert not [k for k in trained_once["built"]
                if k.startswith(("ssd_", "causal_conv1d"))]
    lowered = trained_once["lowered"]
    scans = _lowerings(lowered, "ssd_lowerings_total")
    by_kernel = {k: sum(v for key, v in scans.items()
                        if "kernel=%s" % k in key) for k in ("fwd", "bwd")}
    assert by_kernel == {"fwd": 2, "bwd": 2}     # two mamba layers
    assert lowered[
        "causal_conv1d_lowerings_total{activation=silu,width=4}"] == 2


def test_the_tied_matrix_gets_the_sum_of_two_gradients(trained_once):
    """`append_backward` adds the embedding's gradient
    (`lookup_table_grad`) and the head's (through `transpose_grad`) with
    a `sum` op; each alone is not the reference's gradient."""
    block = trained_once["main"].global_block()
    sums = [op for op in block.desc.ops if op.type == "sum"
            and op.output("Out")[0] == "embed.w@GRAD"]
    assert len(sums) == 1 and len(sums[0].input("X")) == 2
    makers = {op.type for op in block.desc.ops
              if set(sums[0].input("X")) & {n for names in
                                            op.outputs.values()
                                            for n in names}}
    assert makers == {"lookup_table_grad", "transpose_grad"}

    params, feeds = trained_once["params"], trained_once["feeds"]

    def loss_of(embed_in, embed_out):
        def logits(cfg, p, tokens):
            x, _ = reference.hidden(cfg, dict(p, embed=embed_in), tokens)
            return x @ embed_out.T / cfg["logits_scaling"]
        z = logits(CFG, params, feeds["tokens"])
        return jnp.mean(-jnp.take_along_axis(
            jax.nn.log_softmax(z, axis=-1),
            feeds["targets"].astype(jnp.int32), axis=-1))

    g_in, g_out = jax.grad(loss_of, argnums=(0, 1))(params["embed"],
                                                    params["embed"])
    got = trained_once["grads"]["embed.w"]
    assert _rel(got, g_in + g_out) < RTOL
    assert _rel(got, g_in) > 0.1 and _rel(got, g_out) > 0.1


# -- grouped key/value heads -----------------------------------------------------

def test_repeated_heads_are_the_references_indexed_ones():
    """One attention layer alone: the program repeats each key/value
    head for its group of query heads (reshape, expand, reshape), the
    reference indexes 2 heads from 4; wk's and wv's gradients are sums
    over a group."""
    lt = ("attention",)
    names = granite_hybrid_param_names(lt)
    leaves = jax.tree_util.tree_leaves(names)
    main, startup, loss, parts = build_granite_hybrid_program(
        B, T, V, layer_types=lt, **SIZES)
    ops = [op.type for op in main.global_block().desc.ops]
    assert ops.count("expand") == 2 and "rope" not in ops
    expand = [op for op in main.global_block().desc.ops
              if op.type == "expand"][0]
    assert expand.attrs["expand_times"] == [1, 1, 1, 2, 1]
    flash = [op for op in main.global_block().desc.ops
             if op.type == "flash_attention"][0]
    assert flash.attrs["sm_scale"] == pytest.approx(0.2)
    assert flash.attrs["num_heads"] == 4
    with fluid.program_guard(main, startup):
        grads = dict((p.name, g) for p, g in
                     fluid.backward.append_backward(loss))
    exe, scope = _start(startup, leaves)
    feeds = _feeds(1)
    block = names["blocks"][0]
    got = exe.run(main, feed=feeds, scope=scope, fetch_list=[
        parts["mixer_out"][0], grads[block["wk"]], grads[block["wv"]]])
    params = jax.tree_util.tree_map(scope.get, names)
    cfg = dict(CFG, layer_types=lt)
    jfeeds = {k: jnp.asarray(v) for k, v in feeds.items()}
    h = reference.rms_norm(
        3.0 * params["embed"][jfeeds["tokens"]], params["blocks"][0][
            "norm_1"], 1e-5)
    np.testing.assert_allclose(
        got[0], reference.attention_mixer(cfg, params["blocks"][0], h),
        atol=FORWARD_ATOL)
    assert tuple(got[1].shape) == (32, 2 * 8)
    want = jax.grad(lambda p: reference.loss(cfg, p, jfeeds))(params)
    for g, w in zip(got[1:], ("wk", "wv")):
        assert _rel(g, want["blocks"][0][w]) < RTOL


# -- bfloat16 compute --------------------------------------------------------------

def test_bfloat16_compute_keeps_the_scans_islands_float32():
    """Under `amp.enable_bf16` the projections, the convolution's result
    and Y are bfloat16; inside the scan op every exponential (the decays)
    and every cumulative sum is float32, every product takes bfloat16
    operands and adds up in float32, the carried states and the
    parameters' gradients are float32; the loss stays within a stated
    distance of the float32 reference's."""
    lt = ("mamba",)
    names = granite_hybrid_param_names(lt)
    leaves = jax.tree_util.tree_leaves(names)
    with fluid.amp.bf16_guard():
        main, startup, loss, _ = build_granite_hybrid_program(
            B, T, V, layer_types=lt, **SIZES)
        with fluid.program_guard(main, startup):
            grads = dict((p.name, g) for p, g in
                         fluid.backward.append_backward(loss))
        block = main.global_block()
        scan = [op for op in block.desc.ops if op.type == "ssd_scan"][0]
        exe, scope = _start(startup, leaves)
        feeds = _feeds(2)
        b0 = names["blocks"][0]
        fetched = exe.run(
            main, feed=feeds, scope=scope, return_numpy=False,
            fetch_list=[loss, scan.input("X")[0], scan.input("Dt")[0],
                        scan.output("Y")[0], scan.output("States")[0]]
            + [grads[b0[w]] for w in ("a_log", "d", "dt_bias", "in_proj")])
        got_loss, x, dt, y, states = fetched[:5]
        assert [t.dtype for t in (x, dt, y)] == [jnp.bfloat16] * 3
        assert states.dtype == jnp.float32
        assert all(g.dtype == jnp.float32 for g in fetched[5:])

        info = registry.get_op_info("ssd_scan")
        attrs = dict(scan.attrs)
        ins = {slot: [scope.get(n) if scope.find_var(n) is not None
                      else jnp.zeros(block.var(n).shape, block.var(n).dtype)]
               for slot, (n,) in scan.inputs.items()}
        ins["X"], ins["Dt"] = [x], [dt]
        for slot in ("B", "C"):
            ins[slot] = [jnp.zeros(block.var(scan.input(slot)[0]).shape,
                                   jnp.bfloat16)]

        def both(ins):
            out = info.kernel(None, ins, attrs)
            g = dict(ins)
            g.update({"O@Y": out["Y"], "O@States": out["States"],
                      "OG@Y": out["Y"]})
            return out, info.grad_kernel(None, g, attrs)

        jaxpr = jax.make_jaxpr(both)(ins)
    eqns = []

    def walk(j):
        for e in j.eqns:
            eqns.append(e)
            for v in e.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    inner = getattr(sub, "jaxpr", None)
                    if inner is not None:
                        walk(getattr(inner, "jaxpr", inner))

    walk(jaxpr.jaxpr)
    by_name = {}
    for e in eqns:
        by_name.setdefault(e.primitive.name, []).append(e)
    assert by_name["exp"] and by_name["cumsum"] and by_name["dot_general"]
    for name in ("exp", "expm1", "cumsum", "log1p", "logistic"):
        for e in by_name.get(name, []):
            assert e.invars[0].aval.dtype == jnp.float32, name
    for e in by_name["dot_general"]:
        assert {v.aval.dtype for v in e.invars} == {jnp.dtype(jnp.bfloat16)}
        assert e.outvars[0].aval.dtype == jnp.float32

    params = jax.tree_util.tree_map(scope.get, names)
    want = float(reference.loss(
        dict(CFG, layer_types=lt), params,
        {k: jnp.asarray(v) for k, v in feeds.items()}))
    # bfloat16 activations through one layer and a 97-way softmax: seen
    # 2e-4 of the loss; the reference in bfloat16 throughout is off by 2e-3
    assert float(np.asarray(got_loss).reshape(-1)[0]) == pytest.approx(
        want, rel=1e-3)


# -- the initializer ---------------------------------------------------------------

def test_a_log_and_dt_bias_are_drawn_on_a_log_scale():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        def data(name, width):
            return fluid.layers.data(name=name, shape=[1, 8, width],
                                     dtype="float32",
                                     append_batch_size=False)
        fluid.layers.ssd_scan(
            data("x", 4096), data("dt", 512), data("b", 4), data("c", 4),
            512, chunk_size=8, a_log_attr=ParamAttr(name="a_log"),
            d_attr=ParamAttr(name="d"), dt_bias_attr=ParamAttr(name="dtb"))
    scope = fluid.Scope()
    startup.random_seed = 5
    fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
    a = np.exp(np.asarray(scope.get("a_log"), np.float64))
    step = np.log1p(np.exp(np.asarray(scope.get("dtb"), np.float64)))
    assert a.min() >= 1.0 and a.max() <= 16.0
    assert 7.0 < a.mean() < 10.0                   # uniform on [1, 16]
    assert step.min() >= 1e-3 * 0.999 and step.max() <= 1e-1 * 1.001
    # log-uniform: the median is the geometric mean of the ends
    assert 0.007 < np.median(step) < 0.014
    np.testing.assert_array_equal(scope.get("d"), np.ones(512, "float32"))
    # decays a step between about 0.2 and 0.999
    decay = np.exp(-np.outer(step, a))
    assert decay.min() > 0.15 and decay.max() < 0.9995
    with pytest.raises(ValueError):
        fluid.initializer.LogScale(0.0, 1.0)
    with pytest.raises(ValueError):
        fluid.initializer.LogScale(1.0, 2.0, "uniform")


# -- Ouro's and OLMoE's programs are what they were -------------------------------

def _fingerprint(program):
    ops = [(op.type, sorted((k, repr(v)) for k, v in op.attrs.items()
                            if not k.startswith("op_")))
           for op in program.global_block().desc.ops]
    return len(ops), hashlib.sha256(
        json.dumps(ops, sort_keys=True).encode()).hexdigest()[:16]


# recorded on the parent commit (PR 30's tree), before
# `decoder_block.attention` gained `n_kv_head`, `sm_scale` and a `theta`
# that may be None: same op types in the same order with the same attrs,
# so neither cell's compile-cache key nor its step changes
RECORDED = {
    "ouro": (276, "20671a8da7683262"),
    "olmoe": (123, "53f24e5381977b9f"),
}


@pytest.mark.parametrize("model", ["ouro", "olmoe"])
def test_ouro_and_olmoe_programs_are_op_for_op_what_they_were(model):
    if model == "ouro":
        main, startup, loss, _ = build_looped_program(
            1, 32, 97, n_layer=2, n_loop=2, n_head=4, d_model=64,
            d_head=16, d_ff=96)
    else:
        main, startup, loss, _ = build_olmoe_program(
            1, 32, 97, n_layer=2, n_head=4, d_model=64, d_expert=32,
            n_experts=8, top_k=2)
    with fluid.program_guard(main, startup):
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    assert _fingerprint(main) == RECORDED[model]
    attention = [op for op in main.global_block().desc.ops
                 if op.type == "flash_attention"]
    assert attention and all(op.attrs["sm_scale"] == 0.0
                             for op in attention)
    assert "expand" not in [op.type for op in main.global_block().desc.ops]
