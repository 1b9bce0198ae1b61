"""Executor program-cache keying: tokens must never alias across
program lifetimes (id() can be reused after GC; reference executors
key on the C++ ProgramDesc identity which has the same hazard)."""

import gc
import re

import jax
import numpy as np
import pytest

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


# ---------------------------------------------------------------------------
# a Program is compiled as it was built: the plan's key, the ops a
# segment applies, and what is left to XLA
# ---------------------------------------------------------------------------

# flags read while a segment is traced, each with a value that is not
# its default: the plan of one setting must never serve another
TRACE_TIME_FLAGS = {"amp_bf16": True, "amp_bf16_act": False,
                    "bn_shifted_stats": True, "donation": "off"}


@pytest.mark.parametrize("flag", sorted(TRACE_TIME_FLAGS))
def test_trace_time_flag_keys_the_plan(flag):
    """A flip builds a new plan; the flip back hits the old one."""
    main, startup, cost = _tiny_train_program()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    feed = {"x": np.ones((2, 4), np.float32)}

    def step():
        exe.run(main, feed=feed, fetch_list=[cost], scope=scope)
        return next(reversed(exe._cache.values()))

    before = flags.get_flag(flag)
    try:
        first = step()
        plans = len(exe._cache)
        assert step() is first and len(exe._cache) == plans
        flags.set_flag(flag, TRACE_TIME_FLAGS[flag])
        assert flags.get_flag(flag) != before
        flipped = step()
        assert flipped is not first and len(exe._cache) == plans + 1
        flags.set_flag(flag, before)
        assert step() is first and len(exe._cache) == plans + 1
    finally:
        flags.set_flag(flag, before)


def test_executor_compiles_the_block_as_built(monkeypatch):
    """The ops the jitted segment applies are block 0's own, in order,
    and the Program the caller handed over is the one the plan holds,
    as it was."""
    from paddle_tpu.fluid import executor as executor_mod

    main, startup, cost = _tiny_train_program()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    built = [od.to_dict() for od in main.desc.block(0).ops]
    version = main.version

    applied = []
    real_apply = executor_mod.apply_op
    monkeypatch.setattr(executor_mod, "apply_op",
                        lambda ctx, od: (applied.append(od),
                                         real_apply(ctx, od))[1])
    exe.run(main, feed={"x": np.ones((2, 4), np.float32)},
            fetch_list=[cost], scope=scope)
    ops = main.desc.block(0).ops
    assert len(applied) == len(ops)
    assert all(a is b for a, b in zip(applied, ops))
    compiled = next(reversed(exe._cache.values()))
    assert compiled.program is main
    assert main.version == version
    assert [od.to_dict() for od in ops] == built


def _mul(x, w, name):
    """`mul` as a bare op: X [batch, 8] by W [8, 8] into `name`."""
    block = x.block
    out = block.create_var(name=name, dtype="float32", shape=[-1, 8])
    block.append_op(type="mul", inputs={"X": [x.name], "Y": [w.name]},
                    outputs={"Out": [out.name]},
                    attrs={"x_num_col_dims": 1, "y_num_col_dims": 1})
    return out


def _compiled_segment_text(build):
    """The optimized HLO of the one jitted segment of the forward
    program `build(x, w)` makes over `x` [4, 8] and `w` [8, 8], as the
    CPU backend compiles it: the executor's own jitted function,
    lowered for the arguments the executor called it with."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4, 8], dtype="float32",
                              append_batch_size=False)
        w = fluid.layers.create_parameter(shape=[8, 8], dtype="float32",
                                          name="w")
        fetch = build(x, w)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    feed = {"x": np.ones((4, 8), np.float32)}
    exe.run(main, feed=feed, fetch_list=[fetch], scope=scope)
    compiled = next(reversed(exe._cache.values()))
    assert list(compiled._jit_cache) == [0]
    jitted = compiled._jit_cache[0]
    fn, called_with = jitted["fn"], []

    def spy(*args):
        called_with.append(jax.tree_util.tree_map(
            lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype), args))
        return fn(*args)

    spy._cache_size = fn._cache_size
    jitted["fn"] = spy
    exe.run(main, feed=feed, fetch_list=[fetch], scope=scope)
    text = fn.lower(*called_with[0]).compile().as_text()
    # products the CPU backend hands to a library would hide from the
    # count below
    assert "custom-call" not in text, text
    return text


def _dots(text):
    return len(re.findall(r"\bdot\(", text))


def test_a_dead_product_is_not_compiled():
    """What a dead-op pass would drop from the Program, XLA drops from
    the executable: a `mul` no fetch reads leaves no `dot`."""
    def build(x, w):
        _mul(x, w, "dead")
        return fluid.layers.scale(x=x, scale=2.0)

    text = _compiled_segment_text(build)
    assert _dots(text) == 0, text
    assert "multiply(" in text


def test_a_product_written_twice_is_compiled_once():
    """Two `mul` ops over the same operands are one `dot`."""
    def build(x, w):
        return fluid.layers.elementwise_add(x=_mul(x, w, "a"),
                                            y=_mul(x, w, "b"))

    text = _compiled_segment_text(build)
    assert _dots(text) == 1, text


def test_a_static_shape_is_a_constant():
    """`shape` of a variable whose dims are static is a constant of the
    executable: nothing is computed at run time, the scaled input it
    was taken from not even read."""
    def build(x, w):
        y = fluid.layers.scale(x=x, scale=2.0)
        block = x.block
        dims = block.create_var(name="dims", dtype="int32", shape=[2])
        block.append_op(type="shape", inputs={"Input": [y.name]},
                        outputs={"Out": [dims.name]}, infer_shape=False)
        return dims

    text = _compiled_segment_text(build)
    entry = text[text.index("ENTRY"):]
    assert "constant({4, 8})" in entry, text
    assert "multiply(" not in text and "fusion(" not in entry, text
    assert re.findall(r"parameter\((\d+)\)", entry) == ["0"], text
