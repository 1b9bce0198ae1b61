"""Executor program-cache keying: tokens must never alias across
program lifetimes (id() can be reused after GC; reference executors
key on the C++ ProgramDesc identity which has the same hazard)."""

import gc

import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import framework
from paddle_tpu.obs import telemetry as obs_tele
from paddle_tpu.utils import flags


def _build_and_run(exe, scale):
    """Fresh program computing x * scale; same topology/version for
    every scale so only the cache token distinguishes them."""
    prog = framework.Program()
    startup = framework.Program()
    with fluid.program_guard(prog, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        y = fluid.layers.scale(x=x, scale=float(scale))
    out, = exe.run(prog, feed={"x": np.ones((1, 4), np.float32)},
                   fetch_list=[y])
    return float(np.asarray(out).reshape(-1)[0])


def test_program_tokens_unique_across_gc():
    tokens = set()
    for _ in range(50):
        p = framework.Program()
        assert p._cache_token not in tokens
        tokens.add(p._cache_token)
        del p
        gc.collect()


def test_no_stale_cache_hit_after_program_rebuild():
    exe = fluid.Executor(fluid.CPUPlace())
    # interleave builds and drops so CPython is free to reuse object
    # ids; results must always track the live program's computation
    for scale in (2.0, 3.0, 5.0, 7.0):
        got = _build_and_run(exe, scale)
        assert got == scale, (got, scale)
        gc.collect()


def test_int64_feed_overflow_is_loud():
    """int64 feeds narrow to int32 (x64 off); out-of-range ids must
    raise instead of silently wrapping (embedding/beam id corruption)."""
    import pytest

    x = fluid.layers.data(name="ids", shape=[1], dtype="int64")
    y = fluid.layers.cast(x=x, dtype="float32")
    exe = fluid.Executor(fluid.CPUPlace())
    ok = exe.run(fluid.default_main_program(),
                 feed={"ids": np.array([[5]], np.int64)},
                 fetch_list=[y])
    assert float(np.asarray(ok[0]).reshape(-1)[0]) == 5.0
    with pytest.raises(OverflowError, match="int32 range"):
        exe.run(fluid.default_main_program(),
                feed={"ids": np.array([[2 ** 40]], np.int64)},
                fetch_list=[y])


def test_clone_gets_its_own_cache_slot():
    prog = framework.Program()
    startup = framework.Program()
    with fluid.program_guard(prog, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        fluid.layers.scale(x=x, scale=2.0)
    clone = prog.clone()
    assert clone._cache_token != prog._cache_token


# ---------------------------------------------------------------------------
# the attribution path: one lowering a segment, artifacts that outlive
# the flag, the plain path's numbers
# ---------------------------------------------------------------------------

def _tiny_train_program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        h = fluid.layers.fc(input=x, size=3)
        cost = fluid.layers.mean(x=h)
        fluid.optimizer.SGDOptimizer(learning_rate=0.1).minimize(cost)
    return main, startup, cost


def test_attribution_jit_path_lowers_each_segment_once(monkeypatch):
    """FLAGS_xla_cost_attribution on the plain jit path used to pay a
    second, throwaway lower().compile() per segment.  Count actual
    lowerings by counting kernel applications under trace: each
    lowering of a segment runs apply_op once per op."""
    from paddle_tpu.fluid import executor as executor_mod

    main, startup, cost = _tiny_train_program()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)

    calls = []
    real_apply = executor_mod.apply_op
    monkeypatch.setattr(executor_mod, "apply_op",
                        lambda ctx, od: (calls.append(od.type),
                                         real_apply(ctx, od))[1])
    flags.set_flag("xla_cost_attribution", True)
    try:
        traces0 = obs_tele.jit_trace_count()
        feed = {"x": np.ones((2, 4), np.float32)}
        for _ in range(3):
            exe.run(main, feed=feed, fetch_list=[cost], scope=scope)
    finally:
        flags.set_flag("xla_cost_attribution", False)
    n_ops = len(main.global_block().desc.ops)
    # ONE lowering total: apply_op ran exactly once per op, not twice
    assert len(calls) == n_ops, (len(calls), n_ops, calls)
    # and exactly one compile was counted for the single jit segment
    assert obs_tele.jit_trace_count() - traces0 == 1
    # the attribution landed (graceful skip allowed only if the
    # runtime exposes no analyses — CPU jax here exposes both)
    snap = obs_tele.snapshot()
    assert any(k.startswith("xla_flops{") for k in snap), \
        [k for k in snap if k.startswith("xla_")]


def test_attribution_artifacts_survive_flag_drop():
    """Segments warmed under force_attribution (serving warmup) must
    keep serving those signatures after the flag drops — no recompile
    on the first real request — while NEW signatures compile through
    the normal jit path."""
    from paddle_tpu.obs import health as obs_health

    main, startup, cost = _tiny_train_program()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
    feed2 = {"x": np.ones((2, 4), np.float32)}
    with obs_health.force_attribution():
        exe.run(main, feed=feed2, fetch_list=[cost], scope=scope)
    traces_warm = obs_tele.jit_trace_count()
    # same signature, flag off: served from the attribution artifact
    out1 = exe.run(main, feed=feed2, fetch_list=[cost], scope=scope)
    assert obs_tele.jit_trace_count() == traces_warm
    # new batch size, flag off: a fresh compile through the jit path
    exe.run(main, feed={"x": np.ones((5, 4), np.float32)},
            fetch_list=[cost], scope=scope)
    assert obs_tele.jit_trace_count() == traces_warm + 1
    assert np.isfinite(out1[0]).all()


def test_attribution_flag_flip_does_not_stall_warm_signatures(
        monkeypatch):
    """Enabling the flag on a LIVE process must not inline-recompile
    signatures already warm in the jit call cache (a multi-second
    stall per segment mid-training); only fresh builds attribute."""
    from paddle_tpu.fluid import executor as executor_mod

    main, startup, cost = _tiny_train_program()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
    feed = {"x": np.ones((2, 4), np.float32)}
    exe.run(main, feed=feed, fetch_list=[cost], scope=scope)  # warm
    traces_warm = obs_tele.jit_trace_count()

    calls = []
    real_apply = executor_mod.apply_op
    monkeypatch.setattr(executor_mod, "apply_op",
                        lambda ctx, od: (calls.append(od.type),
                                         real_apply(ctx, od))[1])
    flags.set_flag("xla_cost_attribution", True)
    try:
        exe.run(main, feed=feed, fetch_list=[cost], scope=scope)
    finally:
        flags.set_flag("xla_cost_attribution", False)
    # no lowering happened (no apply_op under trace), no compile
    assert not calls, calls
    assert obs_tele.jit_trace_count() == traces_warm


def test_attribution_numerics_match_plain_path():
    """The attribution AOT dispatch must be numerically identical to
    the plain jit path (same program, same seed, same feeds)."""
    def run(attr):
        main, startup, cost = _tiny_train_program()
        scope = fluid.Scope()
        exe = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(scope):
            exe.run(startup)
        flags.set_flag("xla_cost_attribution", attr)
        try:
            outs = []
            for _ in range(3):
                outs.append(exe.run(
                    main, feed={"x": np.ones((2, 4), np.float32)},
                    fetch_list=[cost], scope=scope)[0])
        finally:
            flags.set_flag("xla_cost_attribution", False)
        return np.concatenate(outs)

    np.testing.assert_array_equal(run(False), run(True))
