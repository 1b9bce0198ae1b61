"""Distributed training over the native C++ parameter server.

Spawns pservers + trainers on this host via the cluster launcher (the
reference's paddle.py/fabric flow), with the DistributeTranspiler
splitting the program into trainer/pserver halves:

    python examples/dist_pserver_fit_a_line.py

Role processes re-enter this file with TRAINING_ROLE set, exactly like
the reference's book_distribute scripts.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))  # run from anywhere in the checkout


import numpy as np


def run_trainer():
    import paddle_tpu as paddle
    import paddle_tpu.fluid as fluid
    from paddle_tpu.distributed import DistributeTranspiler

    x = fluid.layers.data(name="x", shape=[13], dtype="float32")
    y_pred = fluid.layers.fc(input=x, size=1)
    y = fluid.layers.data(name="y", shape=[1], dtype="float32")
    loss = fluid.layers.mean(
        x=fluid.layers.square_error_cost(input=y_pred, label=y))
    opt = fluid.optimizer.SGD(learning_rate=0.001)
    optimize_ops, params_grads = opt.minimize(loss)

    pservers = os.environ["PSERVERS"]
    trainer_id = int(os.environ.get("TRAINER_ID", "0"))
    trainers = int(os.environ.get("TRAINERS", "1"))
    sync = os.environ.get("PADDLE_SYNC", "1") == "1"

    # rewrites the main program in place: optimizer ops become
    # dist_send ops against the pserver endpoints
    t = DistributeTranspiler()
    t.transpile(optimize_ops=optimize_ops, params_grads=params_grads,
                trainer_id=trainer_id, pservers=pservers,
                trainers=trainers, sync=sync)

    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(fluid.default_startup_program())
    # every trainer: the first to arrive initialises the servers, and
    # all pull the same values, so none sends a gradient for a
    # parameter its server does not hold yet
    t.init_pservers()

    feeder = fluid.DataFeeder(place=fluid.TPUPlace(0), feed_list=[x, y])
    reader = paddle.batch(paddle.dataset.uci_housing.train(),
                          batch_size=20)
    for pass_id in range(3):
        costs = []
        for data in reader():
            out, = exe.run(feed=feeder.feed(data), fetch_list=[loss])
            costs.append(float(np.asarray(out).reshape(-1)[0]))
        print("trainer %d pass %d avg cost %.4f"
              % (trainer_id, pass_id, float(np.mean(costs))), flush=True)


def main():
    if os.environ.get("TRAINING_ROLE") == "TRAINER":
        run_trainer()
        return
    # parent: spawn 2 pservers + 2 trainers on loopback
    from paddle_tpu.tools.cluster_launch import launch

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))
    child_pythonpath = os.pathsep.join(
        p for p in (repo_root, os.environ.get("PYTHONPATH")) if p)
    ps_procs, tr_procs, _ = launch(
        [os.path.abspath(__file__)],
        pservers=["127.0.0.1:7164", "127.0.0.1:7165"],
        trainers=2, sync=True,
        # pservers import paddle_tpu via `python -c`, so the repo root
        # must reach them through the environment
        env={"JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu"),
             "PYTHONPATH": child_pythonpath})
    rc = 0
    for p in tr_procs:
        rc |= p.wait(timeout=600)
    for p in ps_procs:
        p.terminate()
    sys.exit(rc)


if __name__ == "__main__":
    main()
