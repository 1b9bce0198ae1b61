"""One process of tests/test_compile_cache.py: runs a group of entry
points once and prints one `RESULT {json}` line per entry point — its
fetches (as hex, compared bit for bit), what the program's two
compile-cache counters moved by, and what JAX itself reported to a
listener of this script's own.

    python tests/compile_cache_child.py <group> [--no-cache]
                                        [--changed] [--workdir DIR]

The caller owns the environment: JAX_PLATFORMS, the host device count,
JAX_COMPILATION_CACHE_DIR.  `--no-cache` never calls
`enable_compile_cache()`.
"""

import argparse
import json
import os
import sys

import numpy as np

JAX_EVENTS = {"/jax/compilation_cache/cache_hits": 0,
              "/jax/compilation_cache/cache_misses": 0}


def _count_jax_event(event, **_):
    if event in JAX_EVENTS:
        JAX_EVENTS[event] += 1


def _hex(values):
    return [np.asarray(v).tobytes().hex() for v in values]


# ---------------------------------------------------------------------------
# programs
# ---------------------------------------------------------------------------

def _fit_a_line(batch=8, scale=1.0):
    """Linear regression with Adam; `scale` is one op attribute."""
    import paddle_tpu.fluid as fluid

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[13], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        pred = fluid.layers.fc(input=fluid.layers.scale(x=x, scale=scale),
                               size=1)
        loss = fluid.layers.mean(
            x=fluid.layers.square_error_cost(input=pred, label=y))
        fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
    rs = np.random.RandomState(0)
    feed = {"x": rs.rand(batch, 13).astype(np.float32),
            "y": rs.rand(batch, 1).astype(np.float32)}
    return main, startup, loss, feed


def _train(steps=3, **kw):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.core.scope import Scope

    main, startup, loss, feed = _fit_a_line(**kw)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(Scope()):
        exe.run(startup)
        return [exe.run(main, feed=feed, fetch_list=[loss])[0]
                for _ in range(steps)]


# ---------------------------------------------------------------------------
# group "restart": the six entry points
# ---------------------------------------------------------------------------

def executor_f32(args):
    return {"fetches": _hex(_train())}


def executor_bf16(args):
    import paddle_tpu.fluid as fluid

    with fluid.amp.bf16_guard():
        return {"fetches": _hex(_train())}


def functional(args):
    import jax

    import paddle_tpu.fluid as fluid
    from paddle_tpu.core.scope import Scope
    from paddle_tpu.jit import FunctionalProgram, state_from_scope

    main, startup, loss, feed = _fit_a_line(scale=2.0)
    scope = Scope()
    fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
    fp = FunctionalProgram(main, ["x", "y"], [loss.name])
    state = {n: jax.numpy.asarray(v)
             for n, v in state_from_scope(fp, scope).items()}
    step = jax.jit(lambda s, f: fp(s, f), donate_argnums=(0,))
    losses = []
    for _ in range(3):
        (fetch,), state = step(state, feed)
        losses.append(fetch)
    return {"fetches": _hex(losses)}


def spmd(args):
    import jax

    import paddle_tpu.fluid as fluid
    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.spmd import SpmdTrainer

    fluid.framework.reset_unique_name()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[16, 8], dtype="float32",
                              append_batch_size=False)
        label = fluid.layers.data(name="label", shape=[16, 1],
                                  dtype="int64", append_batch_size=False)
        h = fluid.layers.fc(input=x, size=64, act="relu")
        logits = fluid.layers.fc(input=h, size=4, act=None)
        avg = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, label))
        fluid.optimizer.MomentumOptimizer(
            learning_rate=0.1, momentum=0.9).minimize(avg)
    trainer = SpmdTrainer(main, startup, feed_names=["x", "label"],
                          fetch_names=[avg.name],
                          mesh=make_mesh(n_devices=8),
                          zero_stage=1).init()
    rs = np.random.RandomState(1)
    feeds = {"x": rs.rand(16, 8).astype(np.float32),
             "label": rs.randint(0, 4, size=(16, 1)).astype(np.int64)}
    losses = [trainer.step(feeds)[0] for _ in range(3)]
    return {"fetches": _hex(losses), "devices": len(jax.devices())}


def engine(args):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.core.scope import Scope
    from paddle_tpu.serving import EngineConfig, InferenceEngine

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[64], dtype="float32")
        hidden = fluid.layers.fc(input=img, size=32, act="tanh")
        probs = fluid.layers.fc(input=hidden, size=10, act="softmax")
    scope = Scope()
    fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
    eng = InferenceEngine(main, ["img"], [probs], scope=scope,
                          config=EngineConfig(batch_buckets=[2, 4]))
    warmed = eng.warmup()
    out, = eng.run({"img": np.random.RandomState(2).rand(3, 64)
                    .astype(np.float32)})
    return {"fetches": _hex([out]), "buckets": warmed,
            "warmup": eng.last_warmup_stats}


def supervisor(args):
    """Cold: a fault-free supervised run (the reference losses) and
    one killed by a real SIGTERM mid-epoch under `--workdir`.  Warm:
    the rescheduled process — it resumes the killed run's checkpoint
    and finishes.  The cold process never resumes: what the resumed one runs is what an uninterrupted run
    compiled."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.core.scope import Scope
    from paddle_tpu.obs import telemetry as obs_tele
    from paddle_tpu.reader import host_prefetch
    from paddle_tpu.resilience import faults
    from paddle_tpu.resilience.supervisor import (Preempted,
                                                  TrainingSupervisor)

    rs = np.random.RandomState(7)
    batches = [{"x": rs.rand(8, 13).astype(np.float32),
                "y": rs.rand(8, 1).astype(np.float32)}
               for _ in range(6)]

    def reader():
        yield from batches

    def supervised(ckpt_dir, **kw):
        # a program of this entry's own: the cold process compiles it
        main, startup, loss, _ = _fit_a_line(scale=0.5)
        exe, scope = fluid.Executor(fluid.CPUPlace()), Scope()
        exe.run(startup, scope=scope)
        losses = {}

        def step(batch):
            with obs_tele.step("supervised", examples=8):
                return exe.run(main, feed=batch, fetch_list=[loss],
                               scope=scope)[0]

        summary = TrainingSupervisor(
            os.path.join(args.workdir, ckpt_dir), program=main,
            scope=scope, steps_per_checkpoint=1, **kw).run(
            step, host_prefetch(reader, depth=2), num_epochs=2,
            on_step=lambda step, loss: losses.__setitem__(
                step, float(loss).hex()))
        return summary, losses

    if not args.warm:
        _, losses = supervised("clean")
        faults.enable(seed=7)
        faults.inject("supervisor/step", "preempt", after=3, times=1)
        try:
            supervised("killed", on_preempt="raise")
        except Preempted:
            pass
        else:
            raise AssertionError("the preemption fault never fired")
        finally:
            faults.disable()
        return {"fetches": losses}
    summary, losses = supervised("killed")
    return {"fetches": losses, "steps": summary["steps"]}


# ---------------------------------------------------------------------------
# group "programs": four settings, each with a changed twin
# ---------------------------------------------------------------------------

def _with_flag(name, value):
    from paddle_tpu.utils import flags

    prev = flags.get_flag(name)
    flags.set_flag(name, value)
    try:
        return {"fetches": _hex(_train())}
    finally:
        flags.set_flag(name, prev)


def feed_shape(args):
    return {"fetches": _hex(_train(batch=16 if args.changed else 8))}


def op_attribute(args):
    return {"fetches": _hex(_train(scale=3.0 if args.changed else 1.0))}


def amp_flag(args):
    return _with_flag("amp_bf16", bool(args.changed))


def donation_flag(args):
    return _with_flag("donation", "off" if args.changed else "auto")


GROUPS = {
    "restart": [executor_f32, executor_bf16, functional, spmd, engine,
                supervisor],
    "programs": [feed_shape, op_attribute, amp_flag, donation_flag],
    "tiny": [executor_f32],
}


def main():
    p = argparse.ArgumentParser()
    p.add_argument("group", choices=sorted(GROUPS))
    p.add_argument("--no-cache", action="store_true")
    p.add_argument("--changed", action="store_true")
    p.add_argument("--warm", action="store_true")
    p.add_argument("--workdir", default=None)
    args = p.parse_args()

    import jax

    from paddle_tpu.obs import telemetry as obs_tele
    from paddle_tpu.utils.compile_cache import enable_compile_cache

    jax.monitoring.register_event_listener(_count_jax_event)
    if not args.no_cache:
        enable_compile_cache()
    for entry in GROUPS[args.group]:
        snap = obs_tele.snapshot()
        seen = dict(JAX_EVENTS)
        result = entry(args)
        delta = obs_tele.snapshot_delta(snap)
        result.update(
            entry=entry.__name__,
            hits=delta.get("compile_cache_hits_total", 0),
            misses=delta.get("compile_cache_misses_total", 0),
            jit_compiles=delta.get("executor_jit_traces_total", 0),
            jax_hits=JAX_EVENTS["/jax/compilation_cache/cache_hits"]
            - seen["/jax/compilation_cache/cache_hits"],
            jax_misses=JAX_EVENTS["/jax/compilation_cache/cache_misses"]
            - seen["/jax/compilation_cache/cache_misses"])
        print("RESULT " + json.dumps(result), flush=True)
    print("RESULT " + json.dumps({
        "entry": "process",
        "cache_dir": jax.config.jax_compilation_cache_dir}), flush=True)


if __name__ == "__main__":
    sys.exit(main())
