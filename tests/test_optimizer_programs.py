"""What `Optimizer.minimize` leaves in a Program, and how it trains.

An optimizer declares its update rule (`fluid/optimizer.py`); `minimize`
appends one update op a parameter with that rule's accumulators beside
it, and the Executor compiles the block as it was built.  The op types
that advance a parameter are the one list `ops/optimizer_ops.UPDATE_OPS`,
which the sharding rules, the sharding analyzer and the diagram read.
"""

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.ops import registry as op_registry
from paddle_tpu.ops.optimizer_ops import UPDATE_OPS


def _build_convnet(optimizer_fn):
    """A small conv classifier with several same-shape and
    different-shape params, built in its own program pair."""
    main = fluid.Program()
    startup = fluid.Program()
    fluid.framework.reset_unique_name()
    with fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[1, 12, 12],
                                dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        h = fluid.layers.conv2d(input=img, num_filters=4, filter_size=3,
                                act="relu")
        h = fluid.layers.conv2d(input=h, num_filters=4, filter_size=3,
                                act="relu")
        h = fluid.layers.fc(input=h, size=10, act="softmax")
        loss = fluid.layers.mean(
            x=fluid.layers.cross_entropy(input=h, label=label))
        opt = optimizer_fn()
        ops, _ = opt.minimize(loss)
    return main, startup, loss, ops


def _train(main, startup, loss, steps=4, seed=3):
    from paddle_tpu.core.scope import Scope
    from paddle_tpu.fluid.executor import scope_guard, fetch_var

    with scope_guard(Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        rng = np.random.RandomState(seed)
        batch = {"img": rng.randn(8, 1, 12, 12).astype("float32"),
                 "label": rng.randint(0, 10, (8, 1)).astype("int64")}
        losses = [exe.run(main, feed=batch, fetch_list=[loss])[0]
                  for _ in range(steps)]
        params = {p.name: np.asarray(fetch_var(p.name))
                  for p in main.global_block().all_parameters()}
    return losses, params


OPTIMIZERS = {
    "sgd": lambda: fluid.optimizer.SGD(learning_rate=0.05),
    "momentum": lambda: fluid.optimizer.Momentum(learning_rate=0.05,
                                                 momentum=0.9),
    "adam": lambda: fluid.optimizer.Adam(learning_rate=0.01),
    "adagrad": lambda: fluid.optimizer.Adagrad(learning_rate=0.05),
    "rmsprop": lambda: fluid.optimizer.RMSProp(learning_rate=0.01),
    "adadelta": lambda: fluid.optimizer.Adadelta(),
}


@pytest.mark.parametrize("name", sorted(OPTIMIZERS))
def test_optimizer_trains_the_same_twice(name):
    """Two fresh builds of one net, each through `minimize` and an
    Executor of its own, train bit for bit alike, one update op a
    parameter, and the loss on the batch falls."""
    runs = []
    for _ in range(2):
        main, startup, loss, ops = _build_convnet(OPTIMIZERS[name])
        assert [op.type for op in ops] == [name] * 6
        runs.append(_train(main, startup, loss))
    (losses_a, params_a), (losses_b, params_b) = runs
    for la, lb in zip(losses_a, losses_b):
        assert np.array_equal(la, lb), (name, la, lb)
    assert params_a.keys() == params_b.keys()
    for pname in params_a:
        assert np.array_equal(params_a[pname], params_b[pname]), \
            (name, pname)
    assert float(losses_a[-1][0]) < float(losses_a[0][0]), losses_a


def test_two_adam_instances_never_share_a_group():
    """Two Adam instances in one program have distinct beta-pow
    variables and distinct moments: every op of one instance reads that
    instance's scalars and no accumulator of the other's."""
    main = fluid.Program()
    startup = fluid.Program()
    fluid.framework.reset_unique_name()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        h1 = fluid.layers.fc(input=x, size=4)
        h2 = fluid.layers.fc(input=x, size=4)
        loss1 = fluid.layers.mean(x=h1)
        loss2 = fluid.layers.mean(x=h2)
        ops1, _ = fluid.optimizer.Adam(learning_rate=0.01).minimize(loss1)
        ops2, _ = fluid.optimizer.Adam(learning_rate=0.01).minimize(loss2)

    def names(ops, slot):
        return {n for op in ops for n in op.desc.input(slot)}

    assert ops1 and ops2
    assert all(op.type == "adam" for op in ops1 + ops2)
    for slot in ("Beta1Pow", "Beta2Pow"):
        # every member of an instance reads the same beta-pow var
        assert len(names(ops1, slot)) == 1 and len(names(ops2, slot)) == 1
        assert names(ops1, slot).isdisjoint(names(ops2, slot))
    for slot in ("Moment1", "Moment2"):
        mine, theirs = names(ops1, slot), names(ops2, slot)
        assert len(mine) == len(ops1) and len(theirs) == len(ops2)
        assert mine.isdisjoint(theirs)
        # an accumulator carries its parameter's name
        for op in ops1 + ops2:
            param, = op.desc.input("Param")
            acc, = op.desc.input(slot)
            assert acc.startswith(param + "_moment"), (param, acc)


def test_one_optimizer_two_programs():
    """An optimizer instance reused across programs creates fresh state
    vars in each (regression: shared scalars were cached by name only)."""
    opt = fluid.optimizer.Adam(learning_rate=0.01)
    mains = []
    for _ in range(2):
        main = fluid.Program()
        startup = fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[4], dtype="float32")
            loss = fluid.layers.mean(x=fluid.layers.fc(input=x, size=4))
            opt.minimize(loss)
        mains.append(main)
    for main in mains:
        block = main.global_block()
        updates = [op for op in block.ops if op.type == "adam"]
        assert len(updates) == 2
        for op in block.ops:
            if op.type in ("adam", "scale"):
                for names in op.desc.inputs.values():
                    for n in names:
                        assert block.has_var_recursive(n), \
                            "%s reads %r not in its program" % (op.type, n)
        for op in updates:
            param, = op.desc.input("Param")
            for slot in ("Moment1", "Moment2"):
                acc, = op.desc.input(slot)
                assert acc.startswith(param + "_moment"), (param, acc)


# -- the one list of update-op types ------------------------------------------

@pytest.mark.parametrize("op", sorted(UPDATE_OPS))
def test_update_op_is_on_the_one_list(op):
    """A name on the list is a registered op that writes `ParamOut` in
    place and lets no gradient through."""
    assert op_registry.has_op(op)
    info = op_registry.get_op_info(op)
    assert "ParamOut" in info.in_place_outputs
    assert info.stop_gradient_op and info.grad_maker is None


def _optimizer_classes(base=fluid.optimizer.Optimizer):
    for cls in base.__subclasses__():
        yield cls
        yield from _optimizer_classes(cls)


def test_every_optimizer_class_updates_through_the_list():
    classes = list(_optimizer_classes())
    assert len(classes) >= 9
    for cls in classes:
        assert cls.op_type in UPDATE_OPS, cls
