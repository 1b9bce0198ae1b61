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
    for _ in range(steps):
        (l,) = trainer.step(transformer_program_feeds(B, T, V, seed=1))
        losses.append(float(np.asarray(l).reshape(-1)[0]))
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


def _flash_lowerings():
    from paddle_tpu.obs import telemetry

    return {k: v for k, v in telemetry.snapshot().items()
            if k.startswith("flash_attention_lowerings_total")}


def test_block_size_in_a_program_is_honoured_and_its_absence_chooses():
    """A program whose op says block_size=128 still lowers with 128 x 128
    blocks; one that names none leaves the choice to the kernel (here
    the whole 256-long sequence).  The counter's labels say which."""
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
        before = _flash_lowerings()
        got, = fluid.Executor(fluid.CPUPlace()).run(
            main, feed={"x": x0}, fetch_list=[out])
        after = _flash_lowerings()
        return np.asarray(got), {k: v - before.get(k, 0)
                                 for k, v in after.items()
                                 if v != before.get(k, 0)}

    named, delta = lowered_with(block_size=128)
    assert delta == {"flash_attention_lowerings_total{block_k=128,"
                     "block_q=128,kv_resident=true}": 1}
    chosen, delta = lowered_with()
    assert delta == {"flash_attention_lowerings_total{block_k=256,"
                     "block_q=256,kv_resident=true}": 1}
    np.testing.assert_allclose(named, chosen, atol=2e-5)
    np.testing.assert_allclose(named, _dense_ref(x0, x0, x0, 2, False),
                               atol=2e-5)


def test_block_size_reaches_the_backward_kernels_of_a_program():
    """The gradient of a program's op runs the backward kernel (one for
    a head this short), one count per lowering, at the block size the op
    names (the generic gradient differentiates the kernel's custom_vjp);
    the gradients are dense attention's."""
    from paddle_tpu.obs import telemetry

    B, T, D, H = 1, 256, 32, 2
    x0 = (0.5 * RS.randn(B, T, D)).astype("float32")

    def bwd_lowerings():
        return {k: v for k, v in telemetry.snapshot().items()
                if k.startswith("flash_attention_bwd_lowerings_total")}

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.create_parameter([B, T, D], "float32")
        out = fluid.layers.flash_attention(x, x, x, num_heads=H,
                                           causal=True, block_size=128)
        loss = fluid.layers.mean(x=out)
        grads = fluid.backward.calc_gradient(loss, [x])

    from paddle_tpu.core.scope import Scope
    from paddle_tpu.fluid.executor import scope_guard, global_scope

    before = bwd_lowerings()
    with scope_guard(Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        global_scope().set(x.name, jnp.asarray(x0))
        got, = exe.run(main, feed={}, fetch_list=grads)
    delta = {k: v - before.get(k, 0) for k, v in bwd_lowerings().items()
             if v != before.get(k, 0)}
    assert delta == {
        "flash_attention_bwd_lowerings_total{block_k=128,block_q=128,"
        "kernel=dq_dkv}": 1}

    def heads(x):
        return x.reshape(B, T, H, D // H).transpose(0, 2, 1, 3)

    def ref_loss(x):
        o = reference_attention(heads(x), heads(x), heads(x), None, True)
        return jnp.mean(o.transpose(0, 2, 1, 3).reshape(B, T, D))

    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(jax.grad(ref_loss)(
                                   jnp.asarray(x0))),
                               rtol=1e-4, atol=1e-7)
