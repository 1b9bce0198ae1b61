"""Chaos harness CLI: supervised training under injected failure.

    # the CI entry point (scripts/ci.sh, scripts/smoke.sh):
    python -m paddle_tpu.tools.chaos_cli --selftest

    # a custom chaos run (fault spec: point:kind[:after[:times]]):
    python -m paddle_tpu.tools.chaos_cli --epochs 3 --seed 11 \
        --faults reader/pump:io_error:5,supervisor/step:preempt:9

`--selftest` certifies the resilience contract end to end: an
MNIST-scale MLP classifier trains twice on the same seed — once
fault-free, once under chaos (one transient reader IOError, one real
SIGTERM preemption, one forced-nonfinite step) with the
`TrainingSupervisor` driving checkpoint/resume.  It asserts that

  * the supervised run completes despite all three faults,
  * its final parameters are IDENTICAL to the fault-free run's (the
    urgent checkpoint + batch-skip resume + nonfinite rollback
    reconstruct the exact trajectory),
  * the per-step loss trajectory matches step for step, and
  * `faults_injected_total{point,kind}` / `supervisor_restarts_total`
    confirm the faults actually fired and recovery actually ran —
    a chaos test that silently injected nothing proves nothing.

See docs/RESILIENCE.md for the fault-point catalogue and the
supervisor lifecycle.
"""

import argparse
import os
import sys
import tempfile


def parse_args(argv=None):
    p = argparse.ArgumentParser(prog="paddle_chaos")
    p.add_argument("--selftest", action="store_true",
                   help="chaos certification: supervised run with "
                        "injected faults must match a fault-free run")
    p.add_argument("--epochs", type=int, default=2)
    p.add_argument("--steps", type=int, default=8,
                   help="batches per epoch")
    p.add_argument("--batch", type=int, default=16)
    p.add_argument("--seed", type=int, default=7,
                   help="data/fault-plan seed")
    p.add_argument("--ckpt-every", type=int, default=1,
                   help="supervisor steps_per_checkpoint")
    p.add_argument("--max-restarts", type=int, default=5)
    p.add_argument("--faults", default=None,
                   help="comma list of point:kind[:after[:times]] "
                        "(default: the selftest trio)")
    p.add_argument("--ckpt-dir", default=None,
                   help="checkpoint directory (default: a tmpdir)")
    return p.parse_args(argv)


def _fresh_workspace():
    """Fresh default programs/scope so two runs in one process can't
    share state (the same reset the test suite does per test)."""
    from paddle_tpu.core import scope as scope_mod
    from paddle_tpu.fluid import framework
    from paddle_tpu.v2 import layer as v2_layer

    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    scope_mod._global_scope = scope_mod.Scope()
    v2_layer._reset_data_layers()


def _build_mnist_mlp():
    """MNIST-scale classifier on the v2 API: 64-dim class-templated
    synthetic images -> tanh MLP -> softmax over 10 digits."""
    import paddle_tpu.v2 as paddle

    paddle.init()
    img = paddle.layer.data(name="img",
                            type=paddle.data_type.dense_vector(64))
    label = paddle.layer.data(name="label",
                              type=paddle.data_type.integer_value(10))
    hidden = paddle.layer.fc(input=img, size=32,
                             act=paddle.activation.Tanh())
    pred = paddle.layer.fc(input=hidden, size=10,
                           act=paddle.activation.Softmax())
    cost = paddle.layer.classification_cost(input=pred, label=label)
    params = paddle.parameters.create(cost)
    sgd = paddle.trainer.SGD(
        cost=cost, parameters=params,
        update_equation=paddle.optimizer.Momentum(momentum=0.9,
                                                  learning_rate=0.05))
    return sgd


def _make_batches(args):
    from paddle_tpu.dataset.common import synthetic_images

    imgs, labels = synthetic_images(args.steps * args.batch, (64,), 10,
                                    seed=args.seed)
    return [
        [(imgs[i], int(labels[i]))
         for i in range(b * args.batch, (b + 1) * args.batch)]
        for b in range(args.steps)
    ]


def _final_params(sgd):
    import numpy as np

    from paddle_tpu.core.scope import global_scope
    from paddle_tpu.fluid.io import is_persistable

    out = {}
    for v in sgd._main_program.list_vars():
        if not is_persistable(v):
            continue
        val = global_scope().get(v.name)
        if val is not None:
            out[v.name] = np.array(val)
    return out


def _parse_fault_specs(text):
    specs = []
    for item in text.split(","):
        parts = item.strip().split(":")
        if len(parts) < 2:
            raise SystemExit("bad fault spec %r (want "
                             "point:kind[:after[:times]])" % item)
        point, kind = parts[0], parts[1]
        after = int(parts[2]) if len(parts) > 2 else 0
        times = int(parts[3]) if len(parts) > 3 else 1
        specs.append((point, kind, after, times))
    return specs


def _default_fault_specs(args):
    # one of each: a transient reader I/O error, a real SIGTERM
    # preemption, a forced-nonfinite step — placed inside epoch 0/1 so
    # every recovery path runs before the final checkpoint
    mid = max(2, args.steps // 2)
    return [
        ("supervisor/step", "preempt", mid, 1),
        ("supervisor/step", "nonfinite", mid + 2, 1),
        ("reader/pump", "io_error", args.steps + 2, 1),
    ]


def _supervised_run(args, chaos, ckpt_dir):
    """One full training run; returns (summary, losses-by-step,
    final-params, fired-fault-counts)."""
    from paddle_tpu.reader import host_prefetch
    from paddle_tpu.resilience import faults
    from paddle_tpu.resilience.supervisor import TrainingSupervisor

    _fresh_workspace()
    sgd = _build_mnist_mlp()
    batches = _make_batches(args)

    def reader():
        for b in batches:
            yield b

    if chaos:
        faults.enable(seed=args.seed)
        specs = (_parse_fault_specs(args.faults) if args.faults
                 else _default_fault_specs(args))
        for point, kind, after, times in specs:
            faults.inject(point, kind, after=after, times=times)
    try:
        sup = TrainingSupervisor(
            ckpt_dir, program=sgd._main_program,
            steps_per_checkpoint=args.ckpt_every,
            max_restarts=args.max_restarts)
        losses = {}
        summary = sup.run(
            sgd.step_runner(feeding={"img": 0, "label": 1}),
            host_prefetch(reader, depth=2), num_epochs=args.epochs,
            on_step=lambda step, loss: losses.__setitem__(step, loss))
        fired = faults.fired_counts()
    finally:
        faults.disable()
    return summary, losses, _final_params(sgd), fired


def selftest(args):
    import numpy as np

    from paddle_tpu.obs import telemetry as obs_tele

    workdir = tempfile.mkdtemp(prefix="paddle_chaos_")
    clean_sum, clean_loss, clean_params, _ = _supervised_run(
        args, chaos=False, ckpt_dir=os.path.join(workdir, "clean"))
    chaos_sum, chaos_loss, chaos_params, fired = _supervised_run(
        args, chaos=True, ckpt_dir=os.path.join(workdir, "chaos"))

    # every planned fault fired (a chaos run that injects nothing
    # certifies nothing)
    for point, kind, _, times in _default_fault_specs(args) \
            if not args.faults else _parse_fault_specs(args.faults):
        assert fired.get((point, kind), 0) >= 1, \
            "fault %s:%s never fired: %s" % (point, kind, fired)

    # the registry agrees: injections counted, restarts counted
    snap = obs_tele.snapshot()
    injected = sum(v for k, v in snap.items()
                   if k.startswith("faults_injected_total{"))
    restarts = sum(v for k, v in snap.items()
                   if k.startswith("supervisor_restarts_total"))
    assert injected >= 3, \
        "faults_injected_total says %d (<3):\n%s" % (injected, snap)
    assert restarts >= 2 and chaos_sum["restarts"] >= 2, \
        "expected >=2 supervisor restarts, got %s / registry %s" \
        % (chaos_sum, restarts)

    # the supervised chaos run reconstructed the exact trajectory
    assert clean_sum["steps"] == chaos_sum["steps"], (clean_sum,
                                                      chaos_sum)
    assert sorted(clean_loss) == sorted(chaos_loss)
    for step in clean_loss:
        assert abs(clean_loss[step] - chaos_loss[step]) < 1e-9, \
            "loss diverged at step %d: %.9g vs %.9g" \
            % (step, clean_loss[step], chaos_loss[step])
    # var names can differ across the two builds (unique_name counts
    # on); compare by sorted order — same architecture, same count
    ka, kb = sorted(clean_params), sorted(chaos_params)
    assert len(ka) == len(kb), (ka, kb)
    for a, b in zip(ka, kb):
        np.testing.assert_array_equal(
            clean_params[a], chaos_params[b],
            err_msg="final params diverged: %s vs %s" % (a, b))

    print("[chaos] selftest green: %d faults fired %s, %d supervisor "
          "restart(s), final params and %d-step loss trajectory "
          "IDENTICAL to the fault-free run (ckpts under %s)"
          % (injected,
             sorted("%s:%s=%d" % (p, k, n)
                    for (p, k), n in fired.items()),
             chaos_sum["restarts"], len(clean_loss), workdir),
          flush=True)
    return 0


def main(argv=None):
    args = parse_args(argv)
    # chaos runs must never contend for a real accelerator
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if args.selftest:
        return selftest(args)
    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="paddle_chaos_")
    summary, losses, _, fired = _supervised_run(
        args, chaos=True, ckpt_dir=ckpt_dir)
    print("[chaos] run complete: %s; faults fired: %s; final loss "
          "%.6g; checkpoints under %s"
          % (summary,
             sorted("%s:%s=%d" % (p, k, n)
                    for (p, k), n in fired.items()) or "none",
             losses[max(losses)] if losses else float("nan"),
             ckpt_dir), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
