"""The program's own names in the profiler's trace and in the compiled
program: `obs.trace.span` as a `jax.profiler.TraceAnnotation` (recorded
with obs.trace *disabled*, nested with JAX's own host events, arguments
as stats), tracing that does not make the executor block, a
`jax.named_scope` per op type, and the jit phase counter.
"""

import glob
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.jit import FunctionalProgram, state_from_scope
from paddle_tpu.obs import telemetry as obs_tele
from paddle_tpu.obs import trace as obs_trace
from paddle_tpu.parallel import ParallelTrainer, make_mesh


def _mlp(batch=8, dim=4):
    fluid.framework.reset_unique_name()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[batch, dim],
                              dtype="float32", append_batch_size=False)
        label = fluid.layers.data(name="label", shape=[batch, 1],
                                  dtype="int64", append_batch_size=False)
        h = fluid.layers.fc(input=x, size=16, act="relu")
        logits = fluid.layers.fc(input=h, size=3)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, label))
        fluid.optimizer.MomentumOptimizer(
            learning_rate=0.1, momentum=0.9).minimize(loss)
    feeds = {"x": np.ones((batch, dim), np.float32),
             "label": np.zeros((batch, 1), np.int64)}
    return main, startup, loss, feeds


def _profile(tmp_path, body):
    """Run `body()` under a profiler session (python tracer off, as the
    benchmark traces) and return the host lines' events:
    [(name, start_ns, end_ns, stats)] per line that holds a span."""
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                       dict(ev.stats)) for ev in line.events]
            if any("/" in name for name, _, _, _ in events):
                lines.append(events)
    return lines


def _inside(events, outer, name):
    """The events called `name` that lie within `outer`'s interval."""
    return [ev for ev in events if ev[0] == name
            and outer[1] <= ev[1] and ev[2] <= outer[2]]


def test_executor_spans_reach_the_profiler_with_obs_trace_disabled(
        tmp_path):
    main, startup, loss, feeds = _mlp()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    exe.run(main, feed=feeds, fetch_list=[loss], scope=scope)   # compile
    assert not obs_trace.is_enabled()

    def body():
        for _ in range(2):
            exe.run(main, feed=feeds, fetch_list=[loss], scope=scope)

    line, = _profile(tmp_path, body)
    runs = [ev for ev in line if ev[0] == "executor/run"]
    assert len(runs) == 2
    for run in runs:
        assert run[3] == {"feeds": 2, "fetches": 1}
        assert len(_inside(line, run, "executor/feed")) == 1
        plan, = _inside(line, run, "executor/plan")
        assert plan[3] == {"miss": 0}
        assert len(_inside(line, run, "executor/fetch")) == 1
        segment, = _inside(line, run, "executor/segment")
        assert segment[3]["index"] == 0 and segment[3]["jit"] == 1
        assert segment[3]["segment"].startswith("jit_segment[0:")
        dispatch, = _inside(line, segment, "executor/dispatch")
        assert _inside(line, dispatch, "PjitFunction(segment_fn)")
    # nothing went to the in-memory sink
    assert obs_trace.events() == []


def test_trainer_step_is_an_enclosing_span_with_four_children(tmp_path):
    main, startup, loss, feeds = _mlp()
    trainer = ParallelTrainer(main, startup, feed_names=["x", "label"],
                              fetch_names=[loss.name],
                              mesh=make_mesh(n_devices=4)).init()
    trainer.step(feeds)                                         # compile
    lines = _profile(tmp_path, lambda: [trainer.step(feeds)
                                        for _ in range(2)])
    line, = [ln for ln in lines
             if any(ev[0] == "parallel/step" for ev in ln)]
    steps = [ev for ev in line if ev[0] == "parallel/step"]
    assert [s[3] for s in steps] == [{"step": 1}, {"step": 2}]
    for step in steps:
        children = [_inside(line, step, "parallel/" + what)
                    for what in ("prepare", "dispatch", "wait", "record")]
        assert [len(c) for c in children] == [1, 1, 1, 1]
        starts = [c[0][1] for c in children]
        assert starts == sorted(starts)
        assert _inside(line, children[1][0], "PjitFunction(step)")
        # one step in flight: the wait is for the step before
        assert children[2][0][3] == {"for_step": step[3]["step"] - 1,
                                     "own": 0}
    # the step's telemetry is still fed, once a step
    snap = obs_tele.snapshot()
    assert snap["trainer_steps_total{trainer=parallel}"] == 3
    assert snap["trainer_examples_total{trainer=parallel}"] == 3 * 8


@pytest.mark.parametrize("profiled,blocks", [(False, 0), (True, 1)])
def test_tracing_alone_does_not_make_the_executor_block(monkeypatch,
                                                        profiled, blocks):
    main, startup, loss, feeds = _mlp()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    exe.run(main, feed=feeds, fetch_list=[loss], scope=scope)
    calls = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: calls.append(1) or real(x))
    with obs_trace.tracing():
        if profiled:
            with fluid.profiler.profiler():
                exe.run(main, feed=feeds, fetch_list=[loss], scope=scope,
                        return_numpy=False)
        else:
            exe.run(main, feed=feeds, fetch_list=[loss], scope=scope,
                    return_numpy=False)
    assert len(calls) == blocks
    names = [e["name"] for e in obs_trace.events() if e["ph"] == "X"]
    assert names == ["executor/feed", "executor/plan", "executor/dispatch",
                     "executor/segment", "executor/run"]


def _op_names(fp, state, feeds):
    """The `op_name` paths of the compiled text of one functional step."""
    text = jax.jit(lambda s, f: fp(s, f)).lower(state, feeds) \
        .compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', text))


def test_compiled_program_names_each_op_type_and_pass():
    fluid.framework.reset_unique_name()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[2, 3, 8, 8],
                                dtype="float32", append_batch_size=False)
        label = fluid.layers.data(name="label", shape=[2, 1],
                                  dtype="int64", append_batch_size=False)
        conv = fluid.layers.conv2d(input=img, num_filters=4,
                                   filter_size=3, padding=1)
        bn = fluid.layers.batch_norm(input=conv, act="relu")
        logits = fluid.layers.fc(input=bn, size=3)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, label))
        fluid.optimizer.MomentumOptimizer(
            learning_rate=0.1, momentum=0.9).minimize(loss)
    scope = fluid.Scope()
    fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
    fp = FunctionalProgram(main, ["img", "label"], [loss.name])
    names = _op_names(fp, state_from_scope(fp, scope),
                      {"img": jnp.ones((2, 3, 8, 8), jnp.float32),
                       "label": jnp.zeros((2, 1), jnp.int32)})
    for scope_name in ("conv2d", "conv2d_grad", "batch_norm",
                       "batch_norm_grad", "momentum"):
        assert any("/%s/" % scope_name in n for n in names), scope_name


def test_compiled_flash_program_names_the_backward():
    from paddle_tpu.models.transformer_program import (
        build_transformer_program)

    fluid.framework.reset_unique_name()
    main, startup, loss, _ = build_transformer_program(
        1, 128, 32, n_layer=1, n_head=1, d_model=64, causal=True)
    with fluid.program_guard(main, startup):
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    scope = fluid.Scope()
    fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
    fp = FunctionalProgram(main, ["tokens", "positions", "targets"],
                           [loss.name])
    names = _op_names(fp, state_from_scope(fp, scope), {
        "tokens": jnp.zeros((1, 128), jnp.int32),
        "positions": jnp.zeros((1, 128), jnp.int32),
        "targets": jnp.zeros((1, 128, 1), jnp.int32)})
    assert any("/flash_attention/" in n for n in names)
    # the backward kernels lie under the grad op's scope; JAX wraps a
    # scope opened under a transformation in the transformation's name
    # ("jvp(flash_attention_bwd)")
    assert any(re.search(r"^[^/]*/flash_attention_grad/.*"
                         r"[/(]flash_attention_bwd[/)]", n) for n in names)


def test_jit_phases_are_counted_per_function():
    main, startup, loss, feeds = _mlp()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    exe.run(main, feed=feeds, fetch_list=[loss], scope=scope)
    snap = obs_tele.snapshot()
    phases = {p: snap.get("jit_phase_seconds_total{fun_name=segment_fn,"
                          "phase=%s}" % p, 0)
              for p in ("trace", "lower", "compile")}
    assert all(v > 0 for v in phases.values()), phases
    # a second run of the same shapes compiles nothing: no counter moves
    exe.run(main, feed=feeds, fetch_list=[loss], scope=scope)
    assert {k: v for k, v in obs_tele.snapshot_delta(snap).items()
            if k.startswith("jit_phase_seconds_total")} == {}
