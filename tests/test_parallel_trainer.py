"""ParallelTrainer grad/loss parity on the virtual 8-device CPU mesh.

The reference's analogous coverage is ParallelExecutor/parallel_do
tests asserting multi-device loss equals single-device loss
(reference: python/paddle/v2/fluid/tests/test_parallel_op.py pattern).
Here dp=8, dp=4 x mp=2, and a 1-device mesh must produce the same
losses and final parameters on identical data — XLA GSPMD collectives
replace NCCL allreduce, so parity proves the sharded step is the same
program.
"""

import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec as P

import paddle_tpu.fluid as fluid
from paddle_tpu.core.scope import Scope
from paddle_tpu.obs import flight as obs_flight
from paddle_tpu.obs import health as obs_health
from paddle_tpu.obs import telemetry as obs_tele
from paddle_tpu.obs import trace as obs_trace
from paddle_tpu.parallel import (make_mesh, ParallelTrainer, param_spec,
                                 batch_spec)
from paddle_tpu.spmd import SpmdTrainer

BATCH, DIM, HIDDEN, CLASSES = 16, 8, 1024, 4


def _build_mlp():
    # same var names for every build so state dicts are comparable
    fluid.framework.reset_unique_name()
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[BATCH, DIM],
                              dtype="float32", append_batch_size=False)
        label = fluid.layers.data(name="label", shape=[BATCH, 1],
                                  dtype="int64", append_batch_size=False)
        h = fluid.layers.fc(input=x, size=HIDDEN, act="relu")
        logits = fluid.layers.fc(input=h, size=CLASSES, act=None)
        loss = fluid.layers.softmax_with_cross_entropy(logits, label)
        avg = fluid.layers.mean(loss)
        fluid.optimizer.MomentumOptimizer(
            learning_rate=0.1, momentum=0.9).minimize(avg)
    return main, startup, avg


def _feeds(step):
    rs = np.random.RandomState(100 + step)
    return {
        "x": rs.rand(BATCH, DIM).astype(np.float32),
        "label": rs.randint(0, CLASSES, size=(BATCH, 1)).astype(np.int64),
    }


def _run(mesh, steps=4, zero_stage=0, return_trainer=False):
    main, startup, avg = _build_mlp()
    tr = ParallelTrainer(main, startup, feed_names=["x", "label"],
                         fetch_names=[avg.name], mesh=mesh,
                         zero_stage=zero_stage).init()
    losses = []
    for i in range(steps):
        (loss,) = tr.step(_feeds(i))
        losses.append(float(np.asarray(loss).reshape(-1)[0]))
    params = {n: np.asarray(v) for n, v in tr.state.items()}
    if return_trainer:
        return losses, params, tr
    return losses, params


def _assert_parity(a, b):
    losses_a, params_a = a
    losses_b, params_b = b
    np.testing.assert_allclose(losses_a, losses_b, rtol=2e-5, atol=1e-6)
    assert params_a.keys() == params_b.keys()
    for n in params_a:
        np.testing.assert_allclose(params_a[n], params_b[n],
                                   rtol=2e-4, atol=1e-5, err_msg=n)


def test_dp8_matches_single_device():
    single = _run(make_mesh(n_devices=1))
    dp8 = _run(make_mesh(n_devices=8))
    assert all(np.isfinite(single[0]))
    _assert_parity(dp8, single)


def test_dp8_trains_on_fixed_batch():
    main, startup, avg = _build_mlp()
    tr = ParallelTrainer(main, startup, feed_names=["x", "label"],
                         fetch_names=[avg.name],
                         mesh=make_mesh(n_devices=8)).init()
    feeds = _feeds(0)
    losses = [float(np.asarray(tr.step(feeds)[0]).reshape(-1)[0])
              for _ in range(6)]
    assert losses[-1] < losses[0], losses


def test_dp4_mp2_matches_single_device():
    single = _run(make_mesh(n_devices=1))
    dpmp = _run(make_mesh(n_devices=8, mp=2))
    _assert_parity(dpmp, single)

    # the hidden fc weight (DIM x HIDDEN) really is mp-sharded
    mesh = make_mesh(n_devices=8, mp=2)
    spec = param_spec("w", (DIM, HIDDEN), mesh)
    assert spec == P(None, "mp")


def test_param_spec_layouts():
    mesh = make_mesh(n_devices=8, mp=2)
    # big embedding table: rows (vocab) sharded
    assert param_spec("emb", (4096, 128), mesh) == P("mp", None)
    # wide fc: cols (output dim) sharded
    assert param_spec("fc_w", (256, 1024), mesh) == P(None, "mp")
    # small weights / biases / BN stats: replicated
    assert param_spec("fc_b", (64,), mesh) == P()
    assert param_spec("small_w", (32, 48), mesh) == P()
    assert param_spec("conv_w", (64, 3, 3, 3), mesh) == P()
    # mp absent or 1: everything replicated
    dp_only = make_mesh(n_devices=8, mp=1)
    assert param_spec("emb", (4096, 128), dp_only) == P()
    # odd cols not divisible by mp: falls back to row or replicated
    assert param_spec("w", (1024, 1023), mesh) == P("mp", None)


def test_batch_spec_layouts():
    mesh = make_mesh(n_devices=8, mp=2)
    assert batch_spec((16, 3, 32, 32), mesh) == P("dp")
    assert batch_spec((), mesh) == P()
    no_dp = make_mesh(n_devices=8, mp=2, axes=("x", "mp"))
    assert batch_spec((16, 4), no_dp) == P()


def test_parallel_do_shim_matches_plain_execution():
    """ParallelDo is a documented no-op under SPMD: the block must
    behave exactly as inline execution on a single device."""
    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    pd = fluid.layers.ParallelDo(places=None)
    with pd.do():
        xi = pd.read_input(x)
        pd.write_output(fluid.layers.scale(x=xi, scale=3.0))
    out = pd()

    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    xs = np.arange(8, dtype=np.float32).reshape(2, 4)
    res, = exe.run(fluid.default_main_program(), feed={"x": xs},
                   fetch_list=[out])
    np.testing.assert_allclose(np.asarray(res), xs * 3.0, rtol=1e-6)


def test_zero1_matches_single_device():
    """ZeRO-1 (dp-sharded optimizer state) is the same program: losses
    and final params match the unsharded single-device run, and the
    velocity accumulators really live sharded over dp."""
    from paddle_tpu.parallel.sharding import is_optimizer_state

    single = _run(make_mesh(n_devices=1))
    z_losses, z_params, tr = _run(make_mesh(n_devices=8), zero_stage=1,
                                  return_trainer=True)
    _assert_parity((z_losses, z_params), single)

    acc_names = [n for n in tr.state if is_optimizer_state(n)]
    assert acc_names, list(tr.state)
    sharded = [n for n in acc_names
               if "dp" in tuple(tr.state[n].sharding.spec)]
    # the big fc velocities shard; shape-[1] accumulators stay replicated
    assert sharded, {n: tr.state[n].sharding.spec for n in acc_names}


def test_zero1_with_mp_composes():
    single = _run(make_mesh(n_devices=1))
    zmp = _run(make_mesh(n_devices=8, mp=2), zero_stage=1)
    _assert_parity(zmp, single)


# -- one step in flight ------------------------------------------------------
# step() dispatches step N, then waits for step N-1 (nothing the user
# sets chooses this: a numerics monitor, a flight recorder or a step
# observer, which need the step's values inside the step, make it wait
# for its own fetches as it did before)


def _trainer(cls=ParallelTrainer, **kw):
    main, startup, avg = _build_mlp()
    return cls(main, startup, feed_names=["x", "label"],
               fetch_names=[avg.name], mesh=make_mesh(n_devices=4),
               **kw).init()


def _watch_waits(monkeypatch):
    """Every argument `jax.block_until_ready` is called with."""
    waited = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: waited.append(x) or real(x))
    return waited


def _wait_args():
    return [e["args"] for e in obs_trace.events()
            if e["ph"] == "X" and e["name"] == "parallel/wait"]


def test_step_waits_for_the_step_before_and_never_for_its_own(
        monkeypatch):
    tr = _trainer()
    waited = _watch_waits(monkeypatch)
    returned = []
    with obs_trace.tracing():
        for i in range(4):
            seen = len(waited)
            returned.append(tr.step(_feeds(i)))
            if i == 0:
                assert len(waited) == seen          # nothing to wait for
            else:
                assert len(waited) == seen + 1
                assert waited[-1] is returned[i - 1]
            assert all(w is not returned[i] for w in waited)
    assert _wait_args() == [{"for_step": i - 1, "own": 0}
                            for i in range(4)]


@pytest.mark.parametrize("who", ["monitor", "flight"])
def test_step_waits_for_its_own_fetches_when_someone_reads_them(
        monkeypatch, tmp_path, who):
    if who == "monitor":
        obs_health.enable()         # init() installs the monitor
    tr = _trainer()
    recorded = []
    if who == "flight":
        recorder = obs_flight.install(out_dir=str(tmp_path))
        real = recorder.record_step
        monkeypatch.setattr(
            recorder, "record_step",
            lambda trainer, step, **kw: recorded.append((step, kw))
            or real(trainer, step, **kw))
    waited = _watch_waits(monkeypatch)
    with obs_trace.tracing():
        for i in range(3):
            seen = len(waited)
            (loss,) = tr.step(_feeds(i))
            own = waited[seen]              # the first wait of the step
            assert own[0] is loss
            assert tr._in_flight is None
            value = float(np.asarray(loss).reshape(-1)[0])
            if who == "monitor":
                # the monitor's scalars ride on this step's fetches
                assert len(own) == 1 + len(tr._monitor.fetch_names)
                (seen_max,) = tr._monitor.last["max_abs"].values()
                assert seen_max == pytest.approx(abs(value), rel=1e-6)
            if who == "flight":
                step, rec = recorded[-1]
                assert step == i and len(recorded) == i + 1
                assert rec["loss"] == pytest.approx(value, rel=1e-6)
                assert rec["feeds"]["x"] == "float32[%d, %d]" % (BATCH, DIM)
    assert _wait_args() == [{"for_step": i, "own": 1} for i in range(3)]


def test_running_one_ahead_changes_no_bit():
    eager, ahead = _trainer(), _trainer()
    eager_losses, pending = [], []
    for i in range(8):
        (loss,) = eager.step(_feeds(i))
        eager_losses.append(np.asarray(loss))   # read before the next
        pending.append(ahead.step(_feeds(i))[0])
    for a, b in zip(eager_losses, pending):
        assert a.tobytes() == np.asarray(b).tobytes()
    assert eager.state.keys() == ahead.state.keys()
    for name in eager.state:
        assert np.asarray(eager.state[name]).tobytes() \
            == np.asarray(ahead.state[name]).tobytes(), name


def test_a_failed_wait_blames_the_step_waited_for(monkeypatch):
    tr = _trainer()
    small = {n: v[:8] for n, v in _feeds(0).items()}
    tr.step(small)
    crashes = []
    monkeypatch.setattr(
        obs_flight, "on_crash",
        lambda exc, origin="unknown", **ctx: crashes.append(
            (type(exc), origin, ctx)))

    def boom(x):
        raise RuntimeError("device halted")

    monkeypatch.setattr(jax, "block_until_ready", boom)
    with pytest.raises(RuntimeError, match="device halted"):
        tr.step(_feeds(1))
    (kind, origin, ctx), = crashes
    assert kind is RuntimeError and origin == "parallel/step"
    assert ctx["step"] == 0
    assert ctx["feeds"] == {"x": "float32[8, %d]" % DIM,
                            "label": "int32[8, 1]"}


@pytest.mark.parametrize("how", ["fetch_state", "dump_state_to",
                                 "save_checkpoint"])
def test_state_read_right_after_a_step_holds_that_step(tmp_path, how):
    settled = _trainer(SpmdTrainer)
    settled.step(_feeds(0))
    jax.block_until_ready(settled.state)
    want = {n: np.asarray(v) for n, v in settled.state.items()}

    tr = _trainer(SpmdTrainer)
    before = {n: np.asarray(v) for n, v in tr.state.items()}
    tr.step(_feeds(0))                      # returns with the step queued
    if how == "fetch_state":
        got = {n: tr.fetch_state(n) for n in want}
    elif how == "dump_state_to":
        scope = Scope()
        tr.dump_state_to(scope)
        got = {n: np.asarray(scope.get(n)) for n in want}
    else:
        tr.save_checkpoint(str(tmp_path), step=1)
        other = _trainer(SpmdTrainer)
        assert other.restore_checkpoint(str(tmp_path))["step"] == 1
        got = {n: np.asarray(v) for n, v in other.state.items()}
    assert any((got[n] != before[n]).any() for n in want)
    for n in want:
        assert got[n].tobytes() == want[n].tobytes(), n


@pytest.mark.parametrize("how", ["load_state_from",
                                 "restore_checkpoint"])
def test_a_restore_drops_the_step_in_flight(monkeypatch, tmp_path, how):
    tr = _trainer(SpmdTrainer)
    tr.step(_feeds(0))
    if how == "load_state_from":
        scope = Scope()
        tr.dump_state_to(scope)
    else:
        tr.save_checkpoint(str(tmp_path), step=1)
    stale = tr.step(_feeds(1))
    assert tr._in_flight[2] is stale
    if how == "load_state_from":
        tr.load_state_from(scope)
    else:
        tr.restore_checkpoint(str(tmp_path))
    assert tr._in_flight is None
    waited = _watch_waits(monkeypatch)
    with obs_trace.tracing():
        fresh = tr.step(_feeds(1))
    assert waited == []                     # no step before this one
    assert _wait_args() == [{"for_step": -1, "own": 0}]
    # the restored state is step 0's: the same step again, bit for bit
    assert np.asarray(fresh[0]).tobytes() == np.asarray(stale[0]).tobytes()
