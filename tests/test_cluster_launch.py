"""Cluster launcher: spawn pservers + trainers as real processes and
train distributed fit_a_line through the full role protocol
(reference: paddle/scripts/cluster_train launcher behavior)."""

import os
import socket
import subprocess
import sys
import textwrap

from paddle_tpu.tools.cluster_launch import launch

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TRAIN_SCRIPT = textwrap.dedent("""
    import os, sys
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.fluid as fluid
    from paddle_tpu.distributed import DistributeTranspiler
    from paddle_tpu.ops.dist import ClientPool

    x = fluid.layers.data(name="x", shape=[13], dtype="float32")
    yp = fluid.layers.fc(input=x, size=1)
    y = fluid.layers.data(name="y", shape=[1], dtype="float32")
    avg = fluid.layers.mean(
        x=fluid.layers.square_error_cost(input=yp, label=y))
    oops, pg = fluid.optimizer.SGD(learning_rate=0.01).minimize(avg)
    t = DistributeTranspiler()
    t.transpile(optimize_ops=oops, params_grads=pg,
                trainer_id=int(os.environ["TRAINER_ID"]),
                pservers=os.environ["PSERVERS"],
                trainers=int(os.environ["TRAINERS"]))
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    t.init_pservers()
    feeder = fluid.DataFeeder(place=fluid.CPUPlace(), feed_list=[x, y])
    rd = paddle.batch(paddle.dataset.uci_housing.train(), batch_size=20)
    losses = []
    for p in range(3):
        for d in rd():
            out, = exe.run(fluid.default_main_program(),
                           feed=feeder.feed(d), fetch_list=[avg])
            losses.append(float(np.asarray(out).reshape(-1)[0]))
    ClientPool.reset()
    sys.exit(0 if losses[-1] < losses[0] else 1)
""")


def test_cluster_launch_end_to_end(tmp_path):
    script = tmp_path / "train_dist.py"
    script.write_text(TRAIN_SCRIPT)
    ports = []
    for _ in range(2):
        with socket.socket() as sk:
            sk.bind(("127.0.0.1", 0))
            ports.append(sk.getsockname()[1])
    pservers = ["127.0.0.1:%d" % p for p in ports]

    ps_procs, tr_procs, _ = launch(
        [str(script)], pservers, trainers=2, sync=True,
        env={"PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu"})
    try:
        rcs = [p.wait(timeout=240) for p in tr_procs]
        assert rcs == [0, 0], rcs
    finally:
        import signal

        for p in ps_procs:
            p.send_signal(signal.SIGTERM)
        for p in ps_procs:
            p.wait(timeout=30)


def test_cluster_launch_remote_over_ssh(tmp_path):
    """--hosts mode really EXECUTES over the ssh transport (reference:
    cluster_train/paddle.py:33-104 runs remote commands, not prints).
    The transport here is a local ssh shim — same argv contract
    (`ssh host "shell command"`) with the hostname recorded so the test
    can assert per-host dispatch."""
    from paddle_tpu.tools.cluster_launch import launch_remote

    import shlex

    script = tmp_path / "train_dist.py"
    script.write_text(TRAIN_SCRIPT)
    hostlog = tmp_path / "hosts.log"
    shim = tmp_path / "fakessh"
    shim.write_text("#!/bin/bash\n"
                    "host=\"$1\"; shift\n"
                    "echo \"$host\" >> %s\n"
                    "exec bash -c \"$1\"\n" % shlex.quote(str(hostlog)))
    shim.chmod(0o755)

    # both staggered ports (base, base+1) must be free: reserve a pair
    sk1, sk2 = socket.socket(), socket.socket()
    try:
        while True:
            sk1.bind(("127.0.0.1", 0))
            port = sk1.getsockname()[1]
            try:
                sk2.bind(("127.0.0.1", port + 1))
                break
            except OSError:
                sk1.close()
                sk1 = socket.socket()
    finally:
        sk1.close()
        sk2.close()

    # two distinct loopback-resolvable "hosts"; port_step staggers the
    # pserver ports since both land on this machine
    from paddle_tpu.tools.cluster_launch import stop_remote

    ps_procs, tr_procs = launch_remote(
        [str(script)], hosts=["127.0.0.1", "localhost"],
        trainers_per_host=1, base_port=port, port_step=1, sync=True,
        python=sys.executable, ssh_cmd=(str(shim),), workdir=REPO,
        env={"PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu"})
    try:
        rcs = [p.wait(timeout=240) for p in tr_procs]
        assert rcs == [0, 0], rcs
        dispatched = hostlog.read_text().split()
        assert sorted(set(dispatched)) == ["127.0.0.1", "localhost"], \
            dispatched
    finally:
        for p in ps_procs:
            stop_remote(p)


ELASTIC_TRAIN_SCRIPT = TRAIN_SCRIPT.replace(
    'pservers=os.environ["PSERVERS"],',
    'pservers=",".join(__import__("paddle_tpu.distributed",'
    ' fromlist=["discover_pservers"]).discover_pservers()),')


def test_cluster_launch_elastic(tmp_path):
    """--elastic flow: launcher starts a master registry, pservers bind
    free ports and register slots, trainers DISCOVER the endpoints
    instead of reading a static list (reference: the etcd-driven
    go/pserver cluster bring-up)."""
    script = tmp_path / "train_dist_elastic.py"
    script.write_text(ELASTIC_TRAIN_SCRIPT)

    # endpoints are placeholders in elastic mode: only the count is used
    ps_procs, tr_procs, master = launch(
        [str(script)], ["x:0", "x:0"], trainers=2, sync=True,
        elastic=True,
        env={"PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu"})
    try:
        rcs = [p.wait(timeout=240) for p in tr_procs]
        assert rcs == [0, 0], rcs
    finally:
        import signal

        for p in ps_procs:
            p.send_signal(signal.SIGTERM)
        for p in ps_procs:
            p.wait(timeout=30)
        master.stop()
