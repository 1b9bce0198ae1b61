"""The flash_attention framework op (ops/attention.py).

The pallas online-softmax kernel (interpret mode on these CPU tests)
surfaces through the op registry and `fluid.layers.flash_attention`;
the reference's closest surface builds attention from composed ops
(python/paddle/v2/fluid/nets.py:338).  Checks: OpTest output + grad
against the dense reference, the fluid transformer program training
through ParallelTrainer on the 8-device mesh with ring sp engaged,
and ring-vs-dense gradient parity through the Program stack.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu.fluid as fluid
from paddle_tpu.kernels.flash_attention import reference_attention

from op_test import OpTest

RS = np.random.RandomState(5)


def _dense_ref(q, k, v, num_heads, causal):
    b, t, d = q.shape

    def heads(x):
        return x.reshape(b, t, num_heads, d // num_heads) \
                .transpose(0, 2, 1, 3)

    o = reference_attention(jnp.asarray(heads(q)), jnp.asarray(heads(k)),
                            jnp.asarray(heads(v)), None, causal)
    return np.asarray(o).transpose(0, 2, 1, 3).reshape(b, t, d)


FWD_LOWERINGS = "flash_attention_lowerings_total"
BWD_LOWERINGS = "flash_attention_bwd_lowerings_total"
GRAD_LOWERINGS = "flash_attention_grad_lowerings_total"


def _counters(prefix):
    from paddle_tpu.obs import telemetry

    return {k: v for k, v in telemetry.snapshot().items()
            if k.startswith(prefix)}


def _rose(prefix, before):
    """{counter: by how much} of the counters called `prefix...` that
    moved since `before = _counters(prefix)`."""
    return {k: v - before.get(k, 0) for k, v in _counters(prefix).items()
            if v != before.get(k, 0)}


class TestFlashAttentionOp(OpTest):
    op_type = "flash_attention"

    def test_causal_multihead(self):
        q = RS.randn(2, 8, 16).astype("float32")
        k = RS.randn(2, 8, 16).astype("float32")
        v = RS.randn(2, 8, 16).astype("float32")
        self.inputs = {"Q": q, "K": k, "V": v}
        self.attrs = {"num_heads": 4, "causal": True}
        self.outputs = {"Out": _dense_ref(q, k, v, 4, True)}
        self.check_output(atol=1e-5)
        # the f32 central-difference probe is noisy through softmax
        # (analytic grads match jax.grad of the dense reference to
        # 1e-7 — see the exact check below); loose numeric bound
        self.check_grad(["Q", "K", "V"], "Out", max_relative_error=0.15)

    def test_full_single_head(self):
        # mild scale keeps the softmax well-conditioned for the f32
        # central-difference probe (correctness itself is pinned by the
        # exact analytic-vs-jax.grad test below)
        q = (0.5 * RS.randn(2, 6, 8)).astype("float32")
        k = (0.5 * RS.randn(2, 6, 8)).astype("float32")
        v = RS.randn(2, 6, 8).astype("float32")
        self.inputs = {"Q": q, "K": k, "V": v}
        self.attrs = {"num_heads": 1, "causal": False}
        self.outputs = {"Out": _dense_ref(q, k, v, 1, False)}
        self.check_output(atol=1e-5)
        # the f32 central-difference probe is noisy through softmax
        # (analytic grads match jax.grad of the dense reference to
        # 1e-7 — see the exact check below); loose numeric bound
        self.check_grad(["Q", "K", "V"], "Out", max_relative_error=0.15)


def _train_transformer(sp_axis, mesh, feed_specs, steps=3,
                       sp_mode="ring"):
    """Build + train the fluid transformer; returns (losses, qkv-weight
    after training)."""
    from paddle_tpu.models.transformer_program import (
        build_transformer_program, transformer_program_feeds)
    from paddle_tpu.parallel import ParallelTrainer

    fluid.framework.reset_unique_name()
    B, T, V = 4, 16, 64
    main, startup, avg_loss, _ = build_transformer_program(
        B, T, V, n_layer=1, n_head=4, d_model=32, sp_axis=sp_axis,
        sp_mode=sp_mode)
    with fluid.program_guard(main, startup):
        fluid.optimizer.Momentum(learning_rate=0.05,
                                 momentum=0.9).minimize(avg_loss)
    trainer = ParallelTrainer(
        main, startup, ["tokens", "positions", "targets"],
        [avg_loss.name], mesh, feed_specs=feed_specs, seed=0)
    trainer.init()
    losses = []
    before = _counters(GRAD_LOWERINGS)
    for _ in range(steps):
        (l,) = trainer.step(transformer_program_feeds(B, T, V, seed=1))
        losses.append(float(np.asarray(l).reshape(-1)[0]))
    # which gradient the one attention op's got, whatever the number of
    # times the trainer traced its step
    residuals = {k[len(GRAD_LOWERINGS):] for k in _rose(GRAD_LOWERINGS,
                                                        before)}
    assert residuals == {"{residuals=recomputed}" if sp_axis
                         else "{residuals=saved}"}, residuals
    weight = sorted(n for n in trainer.state if n.startswith("fc_"))[0]
    return losses, np.asarray(trainer.state[weight]), trainer


def test_fluid_transformer_ring_sp_on_mesh():
    """The Program-stack transformer trains over dp×sp with ring
    attention, and the ring path computes the same losses/weights as
    the dense flash path on the same mesh (grad parity through
    training)."""
    devs = jax.devices()
    assert len(devs) >= 8, "conftest forces an 8-device CPU mesh"
    mesh = Mesh(np.array(devs[:8]).reshape(4, 2), ("dp", "sp"))
    specs = {"tokens": P("dp", "sp"), "positions": P("dp", "sp"),
             "targets": P("dp", "sp", None)}

    ring_losses, ring_w, trainer = _train_transformer("sp", mesh, specs)
    flat_losses, flat_w, _ = _train_transformer("", mesh, specs)

    assert all(np.isfinite(ring_losses)), ring_losses
    assert ring_losses[-1] < ring_losses[0], ring_losses
    # ring merge is online-softmax in f32: same math, mergewise order
    np.testing.assert_allclose(ring_losses, flat_losses, rtol=2e-5)
    np.testing.assert_allclose(ring_w, flat_w, rtol=2e-4, atol=2e-6)

    # momentum accumulators really drive the update (task: no
    # hand-rolled SGD in the sharded paths)
    vel = [n for n in trainer.state if "velocity" in n]
    assert vel and any(
        np.abs(np.asarray(trainer.state[n])).max() > 0 for n in vel)


def test_fluid_transformer_ulysses_sp_on_mesh():
    """The all-to-all (Ulysses) sequence-parallel mode computes the
    same training as the dense path too (heads trade places with the
    sequence shard; 4 heads / sp=2)."""
    devs = jax.devices()
    mesh = Mesh(np.array(devs[:8]).reshape(4, 2), ("dp", "sp"))
    specs = {"tokens": P("dp", "sp"), "positions": P("dp", "sp"),
             "targets": P("dp", "sp", None)}

    uly_losses, uly_w, _ = _train_transformer("sp", mesh, specs,
                                              sp_mode="ulysses")
    flat_losses, flat_w, _ = _train_transformer("", mesh, specs)

    assert all(np.isfinite(uly_losses)), uly_losses
    np.testing.assert_allclose(uly_losses, flat_losses, rtol=2e-5)
    np.testing.assert_allclose(uly_w, flat_w, rtol=2e-4, atol=2e-6)


def test_flash_attention_op_in_program_grads_vs_reference():
    """Program-stack grads of the op match jax.grad of the dense
    reference implementation."""
    B, T, D, H = 2, 8, 16, 2
    q0 = RS.randn(B, T, D).astype("float32")
    k0 = RS.randn(B, T, D).astype("float32")
    v0 = RS.randn(B, T, D).astype("float32")

    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        qp = fluid.layers.create_parameter([B, T, D], "float32")
        kp = fluid.layers.create_parameter([B, T, D], "float32")
        vp = fluid.layers.create_parameter([B, T, D], "float32")
        out = fluid.layers.flash_attention(qp, kp, vp, num_heads=H,
                                           causal=True)
        loss = fluid.layers.mean(x=out)
        grads = fluid.backward.calc_gradient(loss, [qp, kp, vp])

    from paddle_tpu.core.scope import Scope
    from paddle_tpu.fluid.executor import scope_guard, global_scope

    with scope_guard(Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        for var, val in ((qp, q0), (kp, k0), (vp, v0)):
            global_scope().set(var.name, jnp.asarray(val))
        got = exe.run(main, feed={}, fetch_list=grads)

    def heads(x):
        return x.reshape(B, T, H, D // H).transpose(0, 2, 1, 3)

    def ref_loss(q, k, v):
        o = reference_attention(heads(q), heads(k), heads(v), None, True)
        return jnp.mean(o.transpose(0, 2, 1, 3).reshape(B, T, D))

    want = jax.grad(ref_loss, argnums=(0, 1, 2))(
        jnp.asarray(q0), jnp.asarray(k0), jnp.asarray(v0))
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-6)


def test_block_size_in_a_program_is_honoured_and_its_absence_chooses():
    """A program whose op says block_size=128 still lowers with 128 x 128
    blocks; one that names none leaves the choice to the kernel (here
    the whole 256-long sequence).  The counter's labels say which, and
    that the op's two heads of 16 share a grid step."""
    B, T, D = 1, 256, 32
    x0 = RS.randn(B, T, D).astype("float32")

    def lowered_with(**layer_args):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[B, T, D],
                                  dtype="float32",
                                  append_batch_size=False)
            out = fluid.layers.flash_attention(x, x, x, num_heads=2,
                                               **layer_args)
        before = _counters(FWD_LOWERINGS)
        got, = fluid.Executor(fluid.CPUPlace()).run(
            main, feed={"x": x0}, fetch_list=[out])
        return np.asarray(got), _rose(FWD_LOWERINGS, before)

    named, delta = lowered_with(block_size=128)
    assert delta == {"flash_attention_lowerings_total{block_k=128,"
                     "block_q=128,heads_per_step=2,kv_resident=true}": 1}
    chosen, delta = lowered_with()
    assert delta == {"flash_attention_lowerings_total{block_k=256,"
                     "block_q=256,heads_per_step=2,kv_resident=true}": 1}
    np.testing.assert_allclose(named, chosen, atol=2e-5)
    np.testing.assert_allclose(named, _dense_ref(x0, x0, x0, 2, False),
                               atol=2e-5)


def test_block_size_reaches_the_backward_kernels_of_a_program():
    """The gradient of a program's op runs the backward kernel (one for
    a head this short), one count per lowering, at the block size the op
    names (the op's gradient hands it to the kernels' chooser); the
    gradients are dense attention's."""
    B, T, D, H = 1, 256, 32, 2
    x0 = (0.5 * RS.randn(B, T, D)).astype("float32")

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.create_parameter([B, T, D], "float32")
        out = fluid.layers.flash_attention(x, x, x, num_heads=H,
                                           causal=True, block_size=128)
        loss = fluid.layers.mean(x=out)
        grads = fluid.backward.calc_gradient(loss, [x])

    from paddle_tpu.core.scope import Scope
    from paddle_tpu.fluid.executor import scope_guard, global_scope

    before = _counters(BWD_LOWERINGS)
    with scope_guard(Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        global_scope().set(x.name, jnp.asarray(x0))
        got, = exe.run(main, feed={}, fetch_list=grads)
    assert _rose(BWD_LOWERINGS, before) == {
        "flash_attention_bwd_lowerings_total{block_k=128,block_q=128,"
        "heads_per_step=2,kernel=dq_dkv}": 1}

    def heads(x):
        return x.reshape(B, T, H, D // H).transpose(0, 2, 1, 3)

    def ref_loss(x):
        o = reference_attention(heads(x), heads(x), heads(x), None, True)
        return jnp.mean(o.transpose(0, 2, 1, 3).reshape(B, T, D))

    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(jax.grad(ref_loss)(
                                   jnp.asarray(x0))),
                               rtol=1e-4, atol=1e-7)


# -- the op hands the kernels what it holds -----------------------------------

def _equations(jaxpr, inside=()):
    """(equation, names of the jitted functions around it) of a jaxpr
    and of every jaxpr its equations hold, but for the kernels'
    bodies."""
    for eqn in jaxpr.eqns:
        yield eqn, inside
        if eqn.primitive.name == "pallas_call":
            continue
        within = inside + ((eqn.params["name"],)
                           if eqn.primitive.name == "jit" else ())
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub, within)


# gpt2m-train's and ouro-train-4k's (and olmoe-train-4k's) attention
CELL_SHAPES = [((8, 1024, 1024), 16, 2), ((1, 4096, 2048), 16, 1)]


@pytest.mark.parametrize("shape,heads,heads_per_step", CELL_SHAPES)
def test_no_head_is_transposed_around_the_kernels(shape, heads,
                                                  heads_per_step):
    """The op and its gradient at the cells' shapes, traced: Q, K, V,
    dOut and the results reach and leave the kernels as
    [batch, seq, heads * dim].  Outside the kernels nothing of that size
    is transposed (the one transpose is of the float32 row sums,
    [batch, seq, heads] -> [batch, heads, seq], a 64th or a 128th of an
    operand), and nothing stands behind an optimization barrier; the
    forward is one kernel and the backward one, named by the prefixes
    the benchmark's readers match."""
    from paddle_tpu.ops import registry

    info = registry.get_op_info("flash_attention")
    attrs = {"num_heads": heads, "causal": True}
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    lse = jax.ShapeDtypeStruct((shape[0], heads, shape[1]), jnp.float32)

    def forward(q, k, v):
        outs = info.kernel(None, {"Q": [q], "K": [k], "V": [v]}, attrs)
        return outs["Out"][0], outs["Lse"][0]

    def gradient(q, k, v, out, lse, dout):
        grads = info.grad_kernel(None, {
            "Q": [q], "K": [k], "V": [v], "O@Out": [out], "O@Lse": [lse],
            "OG@Out": [dout]}, attrs)
        return [grads[slot + "@GRAD"][0] for slot in ("Q", "K", "V")]

    for fn, args, prefix in (
            (forward, (x, x, x), "flash_attention_fwd"),
            (gradient, (x, x, x, x, lse, x), "flash_attention_bwd")):
        traced = jax.make_jaxpr(fn)(*args)
        assert [v.aval.shape for v in traced.jaxpr.outvars[:1]] == [shape]
        eqns = list(_equations(traced.jaxpr))
        names = [e.primitive.name for e, _ in eqns]
        assert "optimization_barrier" not in names
        assert [e.outvars[0].aval.shape for e, _ in eqns
                if e.primitive.name == "transpose"] \
            == [lse.shape] * (fn is gradient)
        # the kernel lowered for the TPU (its twin under the CPU's
        # interpreter sits in the other branch of the platform switch)
        kernels = [e.params["name"] for e, _ in eqns
                   if e.primitive.name == "pallas_call"
                   and not e.params["interpret"]]
        assert len(kernels) == 1 and kernels[0].startswith(prefix + "_q")
        assert kernels[0].endswith("_h%d" % heads_per_step)
        # operands enter the kernel as the op got them
        (kernel, inside), = [(e, inside) for e, inside in eqns
                             if e.primitive.name == "pallas_call"
                             and not e.params["interpret"]]
        assert [v.aval.shape for v in kernel.invars[:3]] == [shape] * 3
        assert inside[-1] == ("_fwd_kernels" if fn is forward
                              else "_bwd_kernels")


@pytest.mark.parametrize("shape,heads,heads_per_step", CELL_SHAPES)
def test_the_counters_say_how_many_heads_a_grid_step_holds(
        tmp_path, shape, heads, heads_per_step):
    """A program with the cell's attention and its gradient, built and
    its shapes inferred (nothing run): one forward and one backward
    lowering an op, under `heads_per_step` 2 for GPT-2's heads of 64 and
    1 for Ouro's of 128, none under "split"; `obs_dump` lists the label
    with the others."""
    from paddle_tpu.ops import registry
    from paddle_tpu.tools import obs_dump

    info = registry.get_op_info("flash_attention")
    attrs = {"num_heads": heads, "causal": True}
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)

    def step(q, k, v, dout):
        ins = {"Q": [q], "K": [k], "V": [v]}
        outs = info.kernel(None, ins, attrs)
        return info.grad_kernel(None, dict(
            ins, **{"O@Out": outs["Out"], "O@Lse": outs["Lse"],
                    "OG@Out": [dout]}), attrs)

    before = {p: _counters(p) for p in (FWD_LOWERINGS, BWD_LOWERINGS)}
    jax.eval_shape(step, x, x, x, x)
    for prefix in (FWD_LOWERINGS, BWD_LOWERINGS):
        (key, n), = _rose(prefix, before[prefix]).items()
        assert n == 1 and "heads_per_step=%d," % heads_per_step in key
    path = str(tmp_path / "metrics.prom")
    assert obs_dump.main(["--metrics-out", path]) == 0
    with open(path) as f:
        listed = [line for line in f if line.startswith(
            ("flash_attention_lowerings_total{",
             "flash_attention_bwd_lowerings_total{"))]
    assert len(listed) == 2
    assert all('heads_per_step="%d"' % heads_per_step in line
               for line in listed)


# -- the explicit gradient against the generic one ----------------------------

def _attention_program(x0, num_heads, weights, with_lse=True, **layer_args):
    """A program of one attention op a row of `x0` = (q, k, v) values,
    each op's result weighted by `weights` into the loss, so that every
    element of dOut differs; `with_lse=False` appends the op as a
    program built before it had `Lse` holds it, with `Out` alone.
    Returns (program, start-up program, parameters and their values,
    their gradients)."""
    main, startup = fluid.Program(), fluid.Program()
    total = None
    with fluid.program_guard(main, startup):
        w = fluid.layers.create_parameter(list(weights.shape), "float32")
        params = [(w, weights)]
        for qkv in x0:
            q, k, v = (fluid.layers.create_parameter(list(x.shape),
                                                     "float32")
                       for x in qkv)
            params += zip((q, k, v), qkv)
            if with_lse:
                out = fluid.layers.flash_attention(
                    q, k, v, num_heads=num_heads, **layer_args)
            else:
                helper = fluid.layer_helper.LayerHelper("flash_attention")
                out = helper.create_tmp_variable("float32")
                helper.append_op(
                    type="flash_attention",
                    inputs={"Q": [q], "K": [k], "V": [v]},
                    outputs={"Out": [out]},
                    attrs={"num_heads": num_heads,
                           "causal": layer_args.get("causal", False),
                           "block_size": layer_args.get("block_size") or 0})
            part = fluid.layers.mean(
                x=fluid.layers.elementwise_mul(x=out, y=w))
            total = part if total is None else total + part
        grads = fluid.backward.calc_gradient(
            total, [p for p, _ in params[1:]])
    return main, startup, params, grads


def _run_gradients(main, startup, params, grads):
    from paddle_tpu.core.scope import Scope
    from paddle_tpu.fluid.executor import scope_guard, global_scope

    with scope_guard(Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        for var, val in params:
            global_scope().set(var.name, jnp.asarray(val))
        return [np.asarray(g) for g in
                exe.run(main, feed={}, fetch_list=grads)]


def _reference_gradients(x0, num_heads, weights, causal):
    def heads(x):
        b, t, d = x.shape
        return x.reshape(b, t, num_heads, d // num_heads) \
                .transpose(0, 2, 1, 3)

    def loss(q, k, v):
        o = reference_attention(heads(q), heads(k), heads(v), None, causal)
        return jnp.mean(o.transpose(0, 2, 1, 3).reshape(q.shape) * weights)

    return [np.asarray(g) for qkv in x0 for g in jax.grad(
        loss, argnums=(0, 1, 2))(*map(jnp.asarray, qkv))]


def _qkv(n_ops, B, T, dim):
    return [tuple((0.5 * RS.randn(B, T, dim)).astype("float32")
                  for _ in range(3)) for _ in range(n_ops)]


@pytest.mark.parametrize("block_size", [None, 128])
@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize("causal", [False, True])
def test_the_explicit_gradient_is_the_generic_one(monkeypatch, causal,
                                                  head_dim, block_size):
    """The op's gradient from the statistics the forward saved runs the
    kernels the generic gradient (jax.vjp of the whole op, what the
    executor falls back on when an op registers none) runs on the
    values it recomputes: the same gradients to float32 rounding of
    the row sums, and dense attention's."""
    from paddle_tpu.ops import registry

    B, T, H = 1, 256, 2
    x0 = _qkv(1, B, T, H * head_dim)
    weights = RS.randn(B, T, H * head_dim).astype("float32")
    program = _attention_program(x0, H, weights, causal=causal,
                                 block_size=block_size)
    before = _counters(GRAD_LOWERINGS)
    explicit = _run_gradients(*program)
    assert _rose(GRAD_LOWERINGS, before) == {
        GRAD_LOWERINGS + "{residuals=saved}": 1}
    monkeypatch.setattr(registry.get_op_info("flash_attention"),
                        "grad_kernel", None)
    generic = _run_gradients(*_attention_program(
        x0, H, weights, causal=causal, block_size=block_size))
    want = _reference_gradients(x0, H, weights, causal)
    for got, same, dense in zip(explicit, generic, want):
        np.testing.assert_allclose(got, same, rtol=1e-6,
                                   atol=1e-6 * np.abs(same).max())
        np.testing.assert_allclose(got, dense, rtol=1e-4, atol=1e-6)


def test_a_step_lowers_the_forward_kernel_once_an_op():
    """A program of n attention ops and their gradients holds n forward
    kernels, not 2n: every gradient op reads what its forward op saved."""
    B, T, H, n = 1, 128, 2, 3
    x0 = _qkv(n, B, T, 32)
    weights = RS.randn(B, T, 32).astype("float32")
    program = _attention_program(x0, H, weights, causal=True)
    before = {p: _counters(p) for p in (FWD_LOWERINGS, BWD_LOWERINGS,
                                        GRAD_LOWERINGS)}
    got = _run_gradients(*program)
    assert sum(_rose(FWD_LOWERINGS, before[FWD_LOWERINGS]).values()) == n
    assert sum(_rose(BWD_LOWERINGS, before[BWD_LOWERINGS]).values()) == n
    assert _rose(GRAD_LOWERINGS, before[GRAD_LOWERINGS]) == {
        GRAD_LOWERINGS + "{residuals=saved}": n}
    for g, w in zip(got, _reference_gradients(x0, H, weights, True)):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("causal", [False, True])
def test_a_program_without_the_statistics_takes_the_generic_gradient(
        causal):
    """An op desc with `Out` alone, as a program built or saved before
    the op had `Lse` holds it: its gradient op has no `O@Lse`, runs the
    forward again through the generic gradient, says so, and gives the
    gradients of a program that has the output."""
    B, T, H = 1, 128, 2
    x0 = _qkv(1, B, T, 64)
    weights = RS.randn(B, T, 64).astype("float32")
    old = _attention_program(x0, H, weights, with_lse=False, causal=causal)
    grad_op, = [od for od in old[0].global_block().desc.ops
                if od.type == "flash_attention_grad"]
    assert "O@Lse" not in grad_op.inputs and "O@Out" in grad_op.inputs
    before = {p: _counters(p) for p in (FWD_LOWERINGS, GRAD_LOWERINGS)}
    got = _run_gradients(*old)
    assert sum(_rose(FWD_LOWERINGS, before[FWD_LOWERINGS]).values()) == 2
    assert _rose(GRAD_LOWERINGS, before[GRAD_LOWERINGS]) == {
        GRAD_LOWERINGS + "{residuals=recomputed}": 1}
    new = _run_gradients(*_attention_program(x0, H, weights,
                                             causal=causal))
    for g, same in zip(got, new):
        np.testing.assert_allclose(g, same, rtol=1e-6,
                                   atol=1e-6 * np.abs(same).max())


def test_the_statistics_are_float32_whatever_the_compute_type():
    """`Lse` has a static float32 [batch, heads, seq] meta at build time
    for bfloat16 operands too, and holds the rows' log-sum-exp."""
    B, T, H, dim = 2, 128, 4, 64
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[B, T, dim], dtype="bfloat16",
                              append_batch_size=False)
        out = fluid.layers.flash_attention(x, x, x, num_heads=H,
                                           causal=True)
    op, = [od for od in main.global_block().desc.ops
           if od.type == "flash_attention"]
    lse = main.global_block().var(op.output("Lse")[0])
    assert (tuple(lse.shape), lse.dtype, lse.stop_gradient) \
        == ((B, H, T), "float32", True)
    assert out.dtype == "bfloat16"
    x0 = jnp.asarray(0.5 * RS.randn(B, T, dim), jnp.bfloat16)
    got, = fluid.Executor(fluid.CPUPlace()).run(
        main, feed={"x": x0}, fetch_list=[lse])
    xh = x0.astype(jnp.float32).reshape(B, T, H, dim // H) \
           .transpose(0, 2, 1, 3)
    s = jnp.einsum("bhqd,bhkd->bhqk", xh, xh) * (dim // H) ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    assert np.asarray(got).dtype == np.float32
    np.testing.assert_allclose(got, jax.nn.logsumexp(s, axis=-1),
                               rtol=2e-2, atol=2e-2)
