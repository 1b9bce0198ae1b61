"""The post-2023 decoder block and the looped model built from it
(models/looped_program.py) against the plain float32 reference
(models/reference/ouro.py): the `rms_norm` and `rope` ops, the logits and
gate probabilities of every pass, the loss, every parameter's gradient
(the test that one contribution per use of a shared weight is summed, not
one kept), and what sharing a parameter by name asks of the start-up
program, `append_backward` and the optimizer.

Tiny sizes on the CPU: 2 blocks x 3 passes, hidden 64, 4 heads of 16,
vocabulary 97, 32 tokens, seeded random weights (norm scales and the gate
moved off their initial 1 and 0, so that a scale or a gate left out
shows).
"""

import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid.param_attr import ParamAttr
from paddle_tpu.models.looped_program import (build_looped_program,
                                              looped_param_names)
from paddle_tpu.models.reference import ouro as reference
from paddle_tpu.models.transformer_program import transformer_program_feeds
from paddle_tpu.obs import telemetry

B, T, V, L, R, H, D, F = 2, 32, 97, 2, 3, 4, 64, 160
CFG = {"num_attention_heads": H, "rope_theta": 1000000, "rms_norm_eps": 1e-6,
       "total_ut_steps": R, "exit_entropy_beta": 0.1}
NAMES = looped_param_names(L)
PARAMS = jax.tree_util.tree_leaves(NAMES)

# float32 on the CPU.  The program's attention is the flash kernel under
# the interpreter (online softmax, another summation order than the dense
# reference's), its exit distribution is kept in logs, and six block
# applications carry the differences along: logits of size ~1 were seen
# to differ by 3e-6.  1e-5 is three times that and two hundred times
# under what one bfloat16 rounding (2^-9) of a logit would give.
FORWARD_ATOL = 1e-5
# gradients, as a share of each parameter's largest entry: seen 1e-6; a
# contribution of one pass left out is off by a third or more
GRAD_RTOL = 2e-5
# bfloat16 keeps 8 bits: one rounding is 2^-9 = 2e-3 of a value, and the
# ops below round their input and their output once each
BF16_RTOL = 1e-2


def _build():
    return build_looped_program(B, T, V, n_layer=L, n_loop=R, n_head=H,
                                d_model=D, d_ff=F)


def _start(startup, seed=3):
    """A scope after the start-up program, its vectors (norm scales,
    gate bias) moved off their initial values."""
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    startup.random_seed = seed
    exe.run(startup, scope=scope)
    rs = np.random.RandomState(seed)
    for name in PARAMS:
        value = np.asarray(scope.get(name))
        if value.ndim == 1:
            scope.set(name, jnp.asarray(
                value + 0.1 * rs.randn(*value.shape).astype("float32")))
    return exe, scope


@pytest.fixture(scope="module")
def trained_once():
    """The program run once in float32 beside the reference on the same
    weights and batch: the loss, each pass's logits and gate
    probabilities, every parameter's gradient."""
    main, startup, loss, passes = _build()
    with fluid.program_guard(main, startup):
        grads = dict((p.name, g) for p, g in
                     fluid.backward.append_backward(loss))
    exe, scope = _start(startup)
    feeds = transformer_program_feeds(B, T, V, seed=1)
    fetch = [loss] + passes["logits"] + passes["lambdas"] + \
        passes["exit_p"] + [grads[n] for n in PARAMS]
    out = exe.run(main, feed=feeds, fetch_list=fetch, scope=scope)
    params = jax.tree_util.tree_map(scope.get, NAMES)
    jfeeds = {k: jnp.asarray(v) for k, v in feeds.items()}
    terms = reference.loss_terms(CFG, params, jfeeds)
    want_logits, _ = reference.logits_and_gates(CFG, params,
                                                jfeeds["tokens"])
    want_grads = jax.grad(lambda p: reference.loss(CFG, p, jfeeds))(params)
    return {
        "main": main, "startup": startup,
        "loss": float(out[0][0]), "logits": out[1:1 + R],
        "lambdas": out[1 + R:1 + 2 * R], "exit_p": out[1 + 2 * R:1 + 3 * R],
        "grads": dict(zip(PARAMS, out[1 + 3 * R:])),
        "want": terms, "want_logits": want_logits,
        "want_grads": dict(zip(PARAMS,
                               jax.tree_util.tree_leaves(want_grads))),
    }


# -- the two ops against the reference's functions ---------------------------

def _op_program(op, low_precision):
    """x -> [cast to bfloat16 ->] op -> [cast back]; returns (main, out,
    the gradients of sum(out * probe) by x and by the op's parameter)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.create_parameter([B, T, D], "float32", attr="x")
        probe = fluid.layers.data(name="probe", shape=[B, T, D],
                                  dtype="float32", append_batch_size=False)
        pos = fluid.layers.data(name="pos", shape=[B, T], dtype="int64",
                                append_batch_size=False)
        h = fluid.layers.cast(x, "bfloat16") if low_precision else x
        if op == "rms_norm":
            h = fluid.layers.rms_norm(h, epsilon=1e-6,
                                      param_attr=ParamAttr(name="g"))
            wrt = [x, main.global_block().var("g")]
        else:
            h = fluid.layers.rope(h, pos, num_heads=H, theta=1e6)
            wrt = [x]
        out = fluid.layers.cast(h, "float32") if low_precision else h
        loss = fluid.layers.reduce_sum(out * probe)
        grads = fluid.backward.calc_gradient(loss, wrt)
    return main, startup, out, grads


@pytest.mark.parametrize("low_precision", [False, True],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("op", ["rms_norm", "rope"])
def test_op_agrees_with_the_reference_forward_and_gradient(op,
                                                           low_precision):
    rs = np.random.RandomState(11)
    x0 = rs.randn(B, T, D).astype("float32")
    g0 = (1 + 0.2 * rs.randn(D)).astype("float32")
    probe = rs.randn(B, T, D).astype("float32")
    pos = np.stack([np.arange(T), np.arange(5, 5 + T)]).astype("int64")
    main, startup, out, grads = _op_program(op, low_precision)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    scope.set("x", jnp.asarray(x0))
    if op == "rms_norm":
        scope.set("g", jnp.asarray(g0))
    got = exe.run(main, feed={"probe": probe, "pos": pos},
                  fetch_list=[out] + grads, scope=scope)

    def ref(x, g):
        if low_precision:
            x = x.astype(jnp.bfloat16).astype(jnp.float32)
        if op == "rms_norm":
            y = reference.rms_norm(x, g, 1e-6)
        else:
            y = reference.rope(x.reshape(B, T, H, D // H), jnp.asarray(pos),
                               1e6).reshape(B, T, D)
        if low_precision:
            y = y.astype(jnp.bfloat16).astype(jnp.float32)
        return y

    want = ref(jnp.asarray(x0), jnp.asarray(g0))
    want_grads = jax.grad(lambda x, g: jnp.sum(ref(x, g) * probe),
                          argnums=(0, 1))(jnp.asarray(x0), jnp.asarray(g0))
    # in float32 only the order of operations differs; in bfloat16 the
    # gradient passes one more rounding than the reference's straight-
    # through casts do
    rtol = BF16_RTOL if low_precision else 1e-5
    for g, w in zip(got, (want,) + want_grads):
        w = np.asarray(w)
        assert np.abs(np.asarray(g) - w).max() <= rtol * np.abs(w).max()


def test_rope_keeps_position_zero_and_each_pair_its_length():
    rs = np.random.RandomState(2)
    x0 = rs.randn(1, 4, D).astype("float32")
    pos = np.array([[0, 1, 7, 4095]], "int64")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[1, 4, D], dtype="float32",
                              append_batch_size=False)
        p = fluid.layers.data(name="pos", shape=[1, 4], dtype="int64",
                              append_batch_size=False)
        out = fluid.layers.rope(x, p, num_heads=H, theta=1e6)
    assert tuple(out.shape) == (1, 4, D) and out.dtype == "float32"
    got, = fluid.Executor(fluid.CPUPlace()).run(
        main, feed={"x": x0, "pos": pos}, fetch_list=[out])
    np.testing.assert_allclose(got[0, 0], x0[0, 0], atol=1e-7)
    half = D // H // 2
    pairs = lambda a: np.hypot(  # noqa: E731
        a.reshape(1, 4, H, 2, half)[..., 0, :],
        a.reshape(1, 4, H, 2, half)[..., 1, :])
    np.testing.assert_allclose(pairs(got), pairs(x0), rtol=1e-5)
    assert np.abs(got[0, 3] - x0[0, 3]).max() > 0.1


def test_the_ops_have_shape_rules_and_the_build_traces_neither():
    from paddle_tpu.ops import registry

    for op in ("rms_norm", "rope"):
        assert registry.get_op_info(op).infer_shape is not None
    main, _, out, _ = _op_program("rms_norm", low_precision=True)
    assert tuple(out.shape) == (B, T, D)
    norm = [od for od in main.global_block().desc.ops
            if od.type == "rms_norm"][0]
    y = main.global_block().var(norm.output("Y")[0])
    assert tuple(y.shape) == (B, T, D) and y.dtype == "bfloat16"


# -- the program against the reference ---------------------------------------

def test_the_loss_agrees_with_the_reference(trained_once):
    want = float(trained_once["want"]["loss"])
    assert abs(trained_once["loss"] - want) <= FORWARD_ATOL
    # and it is not the plain cross-entropy of any one pass
    for ce in trained_once["want"]["pass_ce"]:
        assert abs(float(ce) - want) > 10 * FORWARD_ATOL


@pytest.mark.parametrize("t", range(R))
def test_every_pass_gives_the_reference_logits(trained_once, t):
    got = trained_once["logits"][t]
    want = np.asarray(trained_once["want_logits"][t])
    assert got.shape == (B, T, V)
    assert np.abs(got - want).max() <= FORWARD_ATOL
    if t:
        # the passes differ: a loop that ran once would repeat itself
        assert np.abs(got - trained_once["logits"][t - 1]).max() > 1e-3


@pytest.mark.parametrize("t", range(R))
def test_every_pass_gives_the_reference_gate_and_exit_share(trained_once,
                                                            t):
    want = trained_once["want"]
    for got, ref in ((trained_once["lambdas"][t], want["lambdas"][t]),
                     (trained_once["exit_p"][t], want["p"][t])):
        assert np.abs(got.reshape(B, T) - np.asarray(ref)).max() \
            <= FORWARD_ATOL
    total = sum(p.reshape(B, T) for p in trained_once["exit_p"])
    np.testing.assert_allclose(total, 1.0, atol=1e-6)


@pytest.mark.parametrize("name", PARAMS)
def test_every_parameters_gradient_is_the_references(trained_once, name):
    got = np.asarray(trained_once["grads"][name])
    want = np.asarray(trained_once["want_grads"][name])
    assert got.shape == want.shape
    assert np.abs(want).max() > 0
    assert np.abs(got - want).max() <= GRAD_RTOL * np.abs(want).max()


def test_under_amp_the_program_stays_near_the_reference_and_trains():
    with fluid.amp.bf16_guard():
        main, startup, loss, _ = _build()
        with fluid.program_guard(main, startup):
            fluid.optimizer.Adam(learning_rate=1e-3, beta2=0.95) \
                .minimize(loss)
        exe, scope = _start(startup)
        # copies on the host: the steps below donate the scope's arrays
        params = jax.tree_util.tree_map(
            lambda n: np.asarray(scope.get(n)).astype("float32"), NAMES)
        feeds = transformer_program_feeds(B, T, V, seed=1)
        losses = [float(exe.run(main, feed=feeds, fetch_list=[loss],
                                scope=scope)[0][0]) for _ in range(6)]
    want = float(reference.loss(
        CFG, params, {k: jnp.asarray(v) for k, v in feeds.items()}))
    # bfloat16 products and activations under a float32 loss: each
    # logit carries a few 2^-9 roundings, the mean over 64 tokens less
    assert abs(losses[0] - want) <= BF16_RTOL * want
    assert abs(losses[0] - want) > 0        # it did compute in bfloat16
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


def test_a_saturated_gate_gives_a_finite_loss():
    """lambda = 1 to the last bit: p_t log p_t must be 0, not NaN."""
    main, startup, loss, passes = _build()
    exe, scope = _start(startup)
    gate_w, gate_b = NAMES["gate"]
    scope.set(gate_b, jnp.asarray([200.0], jnp.float32))
    feeds = transformer_program_feeds(B, T, V, seed=1)
    got = exe.run(main, feed=feeds, fetch_list=[loss] + passes["exit_p"],
                  scope=scope)
    assert np.isfinite(got[0]).all()
    np.testing.assert_allclose(got[1], 1.0)
    np.testing.assert_allclose(got[R], 0.0)


# -- a parameter shared by name ----------------------------------------------

def test_a_shared_parameter_is_declared_and_initialised_once(trained_once):
    main, startup = trained_once["main"], trained_once["startup"]
    params = main.global_block().all_parameters()
    assert sorted(p.name for p in params) == sorted(PARAMS)
    written = [n for od in startup.global_block().desc.ops
               for n in od.output_names()]
    assert sorted(written) == sorted(PARAMS)     # one initialiser each
    reads = {}
    for od in main.global_block().desc.ops:
        if od.type in ("mul", "rms_norm", "lookup_table"):
            for n in od.input_names():
                if n in PARAMS:
                    reads[n] = reads.get(n, 0) + 1
    assert reads.pop(NAMES["embed"]) == 1
    assert set(reads.values()) == {R}


def test_the_backward_sums_one_contribution_per_use(trained_once):
    ops = trained_once["main"].global_block().desc.ops
    sums = {od.output("Out")[0]: od.input("X") for od in ops
            if od.type == "sum"}
    for name in PARAMS:
        if name == NAMES["embed"]:
            assert name + "@GRAD" not in sums
            continue
        # the last pass's gate is not in the loss: one use fewer
        uses = R - 1 if name in NAMES["gate"] else R
        contribs = sums[name + "@GRAD"]
        assert len(contribs) == uses and len(set(contribs)) == uses
        written = [od for od in ops if od.type != "sum"
                   and set(od.output_names()) & set(contribs)]
        assert len(written) == uses


def test_append_backward_counts_the_shared_uses():
    main, startup, loss, _ = _build()
    with fluid.program_guard(main, startup):
        fluid.backward.append_backward(loss)
    label = "program_shared_parameter_uses{program=%d}" % main._cache_token
    # 11 per block and norm_f and the head R times, the gate's two R - 1
    assert telemetry.snapshot()[label] == (11 * L + 2) * R + 2 * (R - 1)

    plain, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(plain, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        fluid.backward.append_backward(
            fluid.layers.mean(x=fluid.layers.fc(input=x, size=3)))
    assert not [k for k in telemetry.snapshot()
                if k.startswith("program_shared_parameter_uses")
                and "=%d}" % plain._cache_token in k]


def test_adam_updates_a_shared_parameter_once_with_one_pair_of_moments():
    main, startup, loss, _ = _build()
    with fluid.program_guard(main, startup):
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    adam = [od for od in main.global_block().desc.ops if od.type == "adam"]
    assert sorted(od.input("Param")[0] for od in adam) == sorted(PARAMS)
    for slot in ("Moment1", "Moment2"):
        assert len({od.input(slot)[0] for od in adam}) == len(PARAMS)
    exe, scope = _start(startup)
    before = {n: np.asarray(scope.get(n)) for n in PARAMS}
    exe.run(main, feed=transformer_program_feeds(B, T, V, seed=1),
            fetch_list=[loss], scope=scope)
    moments = [n for n in scope.local_var_names() if "moment" in n]
    assert len(moments) == 2 * len(PARAMS)
    for n in PARAMS:
        step = np.abs(np.asarray(scope.get(n)) - before[n]).max()
        assert 0 < step <= 1.001e-3      # one Adam step of lr 1e-3, not R


def test_sharing_a_name_with_another_shape_is_an_error():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        h = fluid.layers.fc(input=x, size=8, bias_attr=False,
                            param_attr=ParamAttr(name="w"))
        fluid.layers.fc(input=h, size=8, bias_attr=False,
                        param_attr=ParamAttr(name="w"))
        with pytest.raises(ValueError, match="shared with shape"):
            fluid.layers.fc(input=h, size=4, bias_attr=False,
                            param_attr=ParamAttr(name="w"))
    inits = [od for od in startup.global_block().desc.ops
             if "w" in od.output_names()]
    assert len(inits) == 1


def test_four_contributions_to_one_gradient_keep_their_names_apart():
    """y = w(w(w(w x))): `@RENAME@` gives each use of w a gradient of
    its own and one `sum` adds the four."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[6], dtype="float32")
        h = x
        for _ in range(4):
            h = fluid.layers.fc(input=h, size=6, bias_attr=False,
                                param_attr=ParamAttr(name="w"))
        loss = fluid.layers.mean(x=h)
        (param, grad), = fluid.backward.append_backward(loss)
    ops = main.global_block().desc.ops
    total, = [od for od in ops if od.type == "sum"]
    assert total.output("Out") == [grad.name] == ["w@GRAD"]
    assert len(set(total.input("X"))) == 4
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    x0 = np.random.RandomState(0).randn(3, 6).astype("float32")
    got, = exe.run(main, feed={"x": x0}, fetch_list=[grad], scope=scope)
    w0 = jnp.asarray(scope.get("w"))
    want = jax.grad(lambda w: jnp.mean(jnp.asarray(x0) @ w @ w @ w @ w))(w0)
    np.testing.assert_allclose(got, np.asarray(want), rtol=1e-5, atol=1e-7)


# -- the benchmark's copy of the reference -----------------------------------

def test_the_benchmarks_copy_of_the_reference_gives_the_same_loss(
        trained_once):
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "reference", "ouro.py")
    spec = importlib.util.spec_from_file_location("bench_ref_ouro", path)
    copy = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(copy)
    rs = np.random.RandomState(4)
    shapes = jax.tree_util.tree_map(
        lambda n: trained_once["want_grads"][n].shape, NAMES)
    params = jax.tree_util.tree_map(
        lambda s: jnp.asarray(0.2 * rs.randn(*s), jnp.float32), shapes,
        is_leaf=lambda s: isinstance(s, tuple) and
        all(isinstance(d, int) for d in s))
    feeds = {k: jnp.asarray(v) for k, v in
             transformer_program_feeds(B, T, V, seed=9).items()}
    mine = reference.loss_terms(CFG, params, feeds)
    theirs = copy.loss_terms(CFG, params, feeds)
    assert float(mine["loss"]) == float(theirs["loss"])
    for a, b in zip(mine["pass_ce"], theirs["pass_ce"]):
        assert float(a) == float(b)


def test_the_flash_kernel_tiles_the_cells_shape_as_the_counter_says():
    """`[1, 16, 4096, 128]` causal in bfloat16, the shape `ouro-train-4k`
    runs 32 times a step: traced (not run), the kernel takes 1024
    queries by 512 keys with a head's K and V resident, and
    `flash_attention_lowerings_total` says so."""
    from paddle_tpu.kernels.flash_attention import flash_attention

    x = jax.ShapeDtypeStruct((1, 16, 4096, 128), jnp.bfloat16)
    out = jax.eval_shape(lambda q, k, v: flash_attention(q, k, v, None, True),
                         x, x, x)
    assert out.shape == x.shape and out.dtype == x.dtype
    counts = {k: v for k, v in telemetry.snapshot().items()
              if k.startswith("flash_attention_lowerings_total")}
    assert list(counts) == ["flash_attention_lowerings_total{block_k=512,"
                            "block_q=1024,heads_per_step=1,"
                            "kv_resident=true}"]
