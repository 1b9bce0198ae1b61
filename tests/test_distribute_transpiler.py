"""DistributeTranspiler tests (reference: tests/book_distribute/
notest_dist_fit_a_line.py pattern + test_split_var.py), run loopback in
one process plus a true multi-process run with TRAINING_ROLE env vars."""

import os
import subprocess
import sys

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.fluid as fluid
from paddle_tpu import native
from paddle_tpu.distributed import (DistributeTranspiler,
                                    split_dense_variable, run_pserver)
from paddle_tpu.ops.dist import ClientPool


class _Var:
    def __init__(self, name, shape):
        self.name = name
        self.shape = shape


def test_split_dense_variable():
    """reference: tests/test_split_var.py behavior."""
    vars = [_Var("a", (4000,)), _Var("b", (10,))]
    blocks = split_dense_variable(vars, pserver_count=3,
                                  min_block_size=1024)
    by_name = {}
    for name, bid, begin, size in blocks:
        by_name.setdefault(name, []).append((begin, size))
    # `a` split into >=2 blocks covering all 4000 elements
    total = sum(s for _, s in by_name["a"])
    assert total == 4000
    assert len(by_name["a"]) >= 2
    # small `b` stays whole
    assert by_name["b"] == [(0, 10)]


def _build_fit_a_line():
    x = fluid.layers.data(name="x", shape=[13], dtype="float32")
    y_predict = fluid.layers.fc(input=x, size=1, act=None)
    y = fluid.layers.data(name="y", shape=[1], dtype="float32")
    cost = fluid.layers.square_error_cost(input=y_predict, label=y)
    avg_cost = fluid.layers.mean(x=cost)
    opt = fluid.optimizer.SGD(learning_rate=0.01)
    optimize_ops, params_grads = opt.minimize(avg_cost)
    return x, y, avg_cost, optimize_ops, params_grads


def test_transpiled_training_loopback():
    """Trainer program with dist_send ops against an in-process C++
    pserver pair; loss must decrease as with local SGD."""
    servers = [native.ParameterServer(num_trainers=1, sync=True)
               for _ in range(2)]
    try:
        endpoints = ",".join("127.0.0.1:%d" % s.port for s in servers)
        x, y, avg_cost, optimize_ops, params_grads = _build_fit_a_line()
        t = DistributeTranspiler()
        t.transpile(optimize_ops=optimize_ops, params_grads=params_grads,
                    trainer_id=0, pservers=endpoints, trainers=1,
                    split_method=lambda vs, n: split_dense_variable(
                        vs, n, min_block_size=4))

        place = fluid.CPUPlace()
        exe = fluid.Executor(place)
        exe.run(fluid.default_startup_program())
        t.init_pservers()

        feeder = fluid.DataFeeder(place=place, feed_list=[x, y])
        reader = paddle.batch(paddle.dataset.uci_housing.train(),
                              batch_size=20)
        losses = []
        for pass_id in range(8):
            for data in reader():
                out, = exe.run(fluid.default_main_program(),
                               feed=feeder.feed(data),
                               fetch_list=[avg_cost])
                losses.append(float(np.asarray(out).reshape(-1)[0]))
        assert losses[-1] < losses[0], (losses[0], losses[-1])
        assert losses[-1] < 1.0, losses[-1]
        # both pservers participated
        assert all(s.num_updates() > 0 for s in servers)
    finally:
        ClientPool.reset()
        for s in servers:
            s.stop()


def test_transpiled_sparse_embedding():
    """lookup_table with is_sparse=True ships SelectedRows rows only."""
    server = native.ParameterServer(num_trainers=1, sync=True)
    try:
        words = fluid.layers.data(name="w", shape=[1], dtype="int64")
        emb = fluid.layers.embedding(input=words, size=[50, 8],
                                     is_sparse=True)
        label = fluid.layers.data(name="lbl", shape=[8], dtype="float32")
        cost = fluid.layers.mean(
            x=fluid.layers.square_error_cost(input=emb, label=label))
        opt = fluid.optimizer.SGD(learning_rate=0.5)
        optimize_ops, params_grads = opt.minimize(cost)

        t = DistributeTranspiler()
        t.transpile(optimize_ops=optimize_ops, params_grads=params_grads,
                    pservers="127.0.0.1:%d" % server.port, trainers=1)

        place = fluid.CPUPlace()
        exe = fluid.Executor(place)
        exe.run(fluid.default_startup_program())
        t.init_pservers()

        feeder = fluid.DataFeeder(place=place, feed_list=[words, label])
        rs = np.random.RandomState(0)
        ids = rs.randint(0, 50, size=(16, 1)).astype(np.int64)
        tgt = (ids.astype(np.float32) / 50.0).repeat(8, axis=1)
        feed = feeder.feed([(ids[i], tgt[i]) for i in range(16)])
        losses = []
        for _ in range(30):
            out, = exe.run(fluid.default_main_program(), feed=feed,
                           fetch_list=[cost])
            losses.append(float(np.asarray(out).reshape(-1)[0]))
        assert losses[-1] < losses[0] * 0.7, (losses[0], losses[-1])
    finally:
        ClientPool.reset()
        server.stop()


_DIST_SCRIPT = r'''
import os, sys
import numpy as np
import paddle_tpu as paddle
import paddle_tpu.fluid as fluid
from paddle_tpu.distributed import DistributeTranspiler, run_pserver
from paddle_tpu.ops.dist import ClientPool

role = os.environ["TRAINING_ROLE"]
endpoint = os.environ["PSERVER_ENDPOINT"]
trainers = int(os.environ["TRAINERS"])

if role == "PSERVER":
    s = run_pserver(endpoint, trainers=trainers, sync=True)
    sys.stdout.write("READY\n"); sys.stdout.flush()
    sys.stdin.readline()   # parent closes stdin to stop us
    s.stop()
    sys.exit(0)

x = fluid.layers.data(name="x", shape=[13], dtype="float32")
y_predict = fluid.layers.fc(input=x, size=1, act=None)
y = fluid.layers.data(name="y", shape=[1], dtype="float32")
cost = fluid.layers.square_error_cost(input=y_predict, label=y)
avg_cost = fluid.layers.mean(x=cost)
optimize_ops, params_grads = fluid.optimizer.SGD(
    learning_rate=0.01).minimize(avg_cost)

t = DistributeTranspiler()
t.transpile(optimize_ops=optimize_ops, params_grads=params_grads,
            trainer_id=int(os.environ["TRAINER_ID"]),
            pservers=endpoint, trainers=trainers)
place = fluid.CPUPlace()
exe = fluid.Executor(place)
exe.run(fluid.default_startup_program())
t.init_pservers()
feeder = fluid.DataFeeder(place=place, feed_list=[x, y])
reader = paddle.batch(paddle.dataset.uci_housing.train(), batch_size=20)
losses = []
for p in range(6):
    for data in reader():
        out, = exe.run(fluid.default_main_program(),
                       feed=feeder.feed(data), fetch_list=[avg_cost])
        losses.append(float(np.asarray(out).reshape(-1)[0]))
ClientPool.reset()
ok = losses[-1] < losses[0]
print("LOSS", losses[0], losses[-1], flush=True)
sys.exit(0 if ok else 1)
'''


def test_multiprocess_roles():
    """Full parity with the reference's env-var role selection
    (reference: notest_dist_fit_a_line.py TRAINING_ROLE=PSERVER/TRAINER):
    one pserver process, two synchronized trainer processes."""
    import socket

    with socket.socket() as sk:
        sk.bind(("127.0.0.1", 0))
        port = sk.getsockname()[1]
    endpoint = "127.0.0.1:%d" % port
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env_base = {**os.environ, "PYTHONPATH": repo,
                "JAX_PLATFORMS": "cpu",
                "PSERVER_ENDPOINT": endpoint, "TRAINERS": "2"}

    ps = subprocess.Popen(
        [sys.executable, "-c", _DIST_SCRIPT],
        env={**env_base, "TRAINING_ROLE": "PSERVER"},
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    assert ps.stdout.readline().strip() == "READY"

    trainers = [subprocess.Popen(
        [sys.executable, "-c", _DIST_SCRIPT],
        env={**env_base, "TRAINING_ROLE": "TRAINER",
             "TRAINER_ID": str(i)},
        stdout=subprocess.PIPE, text=True) for i in range(2)]
    rcs = [p.wait(timeout=240) for p in trainers]
    for p in trainers:
        print(p.stdout.read())
    ps.stdin.close()
    ps.wait(timeout=30)
    assert rcs == [0, 0], rcs


def test_async_sgd_convergence_and_staleness():
    """Async-SGD through the transpiler (reference:
    ParameterServer2.h asyncSGD:468): gradients apply immediately with
    no cross-trainer barrier, a staleness bound discards gradients
    computed against parameters >= N versions old
    (ParameterServer2.h:243), and training still converges."""
    server = native.ParameterServer(num_trainers=2, sync=False,
                                    async_lagged_threshold=4)
    try:
        endpoint = "127.0.0.1:%d" % server.port
        x, y, avg_cost, optimize_ops, params_grads = _build_fit_a_line()
        t = DistributeTranspiler()
        t.transpile(optimize_ops=optimize_ops, params_grads=params_grads,
                    pservers=endpoint, trainers=2, sync_mode=False)
        assert t.sync is False

        place = fluid.CPUPlace()
        exe = fluid.Executor(place)
        exe.run(fluid.default_startup_program())
        t.init_pservers()

        feeder = fluid.DataFeeder(place=place, feed_list=[x, y])
        reader = paddle.batch(paddle.dataset.uci_housing.train(),
                              batch_size=20)
        losses = []
        for _ in range(8):
            for data in reader():
                out, = exe.run(fluid.default_main_program(),
                               feed=feeder.feed(data),
                               fetch_list=[avg_cost])
                losses.append(float(np.asarray(out).reshape(-1)[0]))
        # async single-trainer traffic converges like sync
        assert losses[-1] < losses[0], (losses[0], losses[-1])
        assert server.num_updates() > 0
        assert server.num_lagged() == 0

        # deterministic staleness: a second client whose view of one
        # block is now 5+ versions behind gets its gradient discarded
        pname = next(iter(t.param_blocks))
        _ep, begin, size = t.param_blocks[pname][0]
        bname = "%s@%d" % (pname, begin)
        lagger = native.PServerClient("127.0.0.1", server.port)
        lagger.get_param(bname, size)          # records current version
        fresh = native.PServerClient("127.0.0.1", server.port)
        fresh.get_param(bname, size)
        for _ in range(5):                     # bump 5 versions
            fresh.send_grad(bname, np.zeros(size, np.float32))
        lagger.send_grad(bname, np.zeros(size, np.float32))
        assert not lagger.last_grad_applied    # discarded as stale
        assert server.num_lagged() >= 1
        # the stale trainer resynchronized: its next grad applies
        lagger.send_grad(bname, np.zeros(size, np.float32))
        assert lagger.last_grad_applied
        lagger.close()
        fresh.close()
    finally:
        ClientPool.reset()
        server.stop()


def test_lr_decay_warning():
    """An op writing the optimizer's LR var after transpile means the
    pserver's snapshotted LR goes stale — transpile must warn."""
    import warnings

    x, y, avg_cost, optimize_ops, params_grads = _build_fit_a_line()
    prog = fluid.default_main_program()
    block = prog.global_block()
    lr_name = optimize_ops[0].desc.input("LearningRate")[0]
    # simulate an LR-decay schedule: an op whose output is the LR var
    block.append_op(type="scale", inputs={"X": [block.var(lr_name)]},
                    outputs={"Out": [block.var(lr_name)]},
                    attrs={"scale": 0.9}, infer_shape=False)

    t = DistributeTranspiler()
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        t.transpile(optimize_ops=optimize_ops, params_grads=params_grads,
                    pservers="127.0.0.1:6174", trainers=1)
    assert any("learning-rate" in str(w.message) for w in rec), \
        [str(w.message) for w in rec]
