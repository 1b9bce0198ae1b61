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


# -- the op's instance inside its type ----------------------------------------

from paddle_tpu.fluid.executor import INSTANCE_SIGIL, op_instance  # noqa: E402


def _instances_under(names, op_type):
    """The instance components right after `op_type` in the paths."""
    found = set()
    for n in names:
        parts = n.split(";")[0].split("/")
        for i, part in enumerate(parts[:-1]):
            if re.sub(r"^(\w+\()*|\)*$", "", part) == op_type \
                    and parts[i + 1].startswith(INSTANCE_SIGIL):
                found.add(parts[i + 1])
    return found


def _conv_program():
    fluid.framework.reset_unique_name()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[2, 3, 8, 8],
                                dtype="float32", append_batch_size=False)
        label = fluid.layers.data(name="label", shape=[2, 1],
                                  dtype="int64", append_batch_size=False)
        conv = fluid.layers.conv2d(input=img, num_filters=4,
                                   filter_size=3, padding=1)
        conv = fluid.layers.conv2d(input=conv, num_filters=4,
                                   filter_size=1)
        bn = fluid.layers.batch_norm(input=conv, act="relu")
        logits = fluid.layers.fc(input=bn, size=3)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, label))
        fluid.optimizer.MomentumOptimizer(
            learning_rate=0.1, momentum=0.9).minimize(loss)
    feeds = {"img": jnp.ones((2, 3, 8, 8), jnp.float32),
             "label": jnp.zeros((2, 1), jnp.int32)}
    return main, startup, loss, feeds


def _compiled_names(main, startup, loss, feeds):
    scope = fluid.Scope()
    fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
    fp = FunctionalProgram(main, sorted(feeds), [loss.name])
    return _op_names(fp, state_from_scope(fp, scope), feeds)


def test_compiled_program_names_each_op_instance_under_its_type():
    main, startup, loss, feeds = _conv_program()
    names = _compiled_names(main, startup, loss, feeds)
    ops = main.global_block().desc.ops
    convs = [od for od in ops if od.type == "conv2d"]
    assert [op_instance(od) for od in convs] == \
        [INSTANCE_SIGIL + od.output("Output")[0] for od in convs]
    assert len(set(map(op_instance, convs))) == 2
    # the two convolutions are told apart, forward and backward, and an
    # op and its gradient share a name
    wanted = {op_instance(od) for od in convs}
    assert _instances_under(names, "conv2d") == wanted
    assert _instances_under(names, "conv2d_grad") == wanted
    grads = [od for od in ops if od.type == "conv2d_grad"]
    assert sorted(map(op_instance, grads)) == sorted(wanted)
    # the type stays the first component after the jit wrapper
    assert any(re.match(r"^jit\([^)]*\)/conv2d/%s[^/]+/" % INSTANCE_SIGIL, n)
               for n in names)
    # an optimizer op has its parameter's name
    updates = [od for od in ops if od.type == "momentum"]
    assert len(updates) == 8
    assert [op_instance(od) for od in updates] == \
        [INSTANCE_SIGIL + od.input("Param")[0] for od in updates]
    assert _instances_under(names, "momentum") == \
        set(map(op_instance, updates))


@pytest.mark.parametrize("op_type", [
    "conv2d", "batch_norm", "mul", "elementwise_add", "relu", "mean",
    "softmax_with_cross_entropy"])
def test_an_op_and_its_gradient_share_an_instance(op_type):
    main, _, _, _ = _conv_program()
    ops = main.global_block().desc.ops
    forward = sorted(op_instance(od) for od in ops if od.type == op_type)
    backward = sorted(op_instance(od) for od in ops
                      if od.type == op_type + "_grad")
    assert forward and forward == backward


def test_a_weight_applied_twice_gives_two_instances():
    from paddle_tpu.fluid.param_attr import ParamAttr

    fluid.framework.reset_unique_name()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8, 16], dtype="float32",
                              append_batch_size=False)
        h = x
        for _ in range(2):
            h = fluid.layers.fc(input=h, size=16, bias_attr=False,
                                param_attr=ParamAttr(name="shared.w"))
        loss = fluid.layers.mean(h)
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    ops = main.global_block().desc.ops
    products = [od for od in ops if od.type == "mul"]
    assert [od.input("Y") for od in products] == [["shared.w"]] * 2
    assert len({op_instance(od) for od in products}) == 2
    names = _compiled_names(main, startup, loss,
                            {"x": jnp.ones((8, 16), jnp.float32)})
    wanted = {op_instance(od) for od in products}
    assert _instances_under(names, "mul") == wanted
    assert _instances_under(names, "mul_grad") == wanted
    # one parameter, one update, named for it
    assert _instances_under(names, "sgd") == {INSTANCE_SIGIL + "shared.w"}


def test_an_op_in_a_sub_block_nests_under_the_op_that_holds_it():
    fluid.framework.reset_unique_name()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[1, 4], dtype="float32",
                              append_batch_size=False)
        acc = fluid.layers.fill_constant(shape=[1, 4], dtype="float32",
                                         value=0.0)
        i = fluid.layers.fill_constant(shape=[1], dtype="int64", value=0)
        limit = fluid.layers.fill_constant(shape=[1], dtype="int64",
                                           value=3)
        cond = fluid.layers.less_than(x=i, y=limit)
        loop = fluid.layers.While(cond=cond, max_steps=8)
        with loop.block():
            t = fluid.layers.scale(x=x, scale=3.0)
            fluid.layers.sums(input=[acc, t], out=acc)
            fluid.layers.increment(x=i, value=1, in_place=True)
            fluid.layers.less_than(x=i, y=limit, cond=cond)
        loss = fluid.layers.mean(x=acc)
    names = _compiled_names(main, startup, loss,
                            {"x": jnp.ones((1, 4), jnp.float32)})
    outer, = [od for od in main.global_block().desc.ops
              if od.type == "while"]
    inner = "scale/%s%s" % (INSTANCE_SIGIL, t.name)
    nested = [n for n in names if inner in n]
    assert nested
    for n in nested:
        assert re.match(r"^jit\([^)]*\)/while/%s/.*%s/"
                        % (re.escape(op_instance(outer)), re.escape(inner)),
                        n), n


def _scopes_the_kernels_open():
    """Every literal (or module constant) handed to `jax.named_scope`
    under paddle_tpu/, and every registered op type."""
    import paddle_tpu
    from paddle_tpu.ops import registry

    root = os.path.dirname(paddle_tpu.__file__)
    found = set(registry.registered_ops())
    for path in glob.glob(os.path.join(root, "**", "*.py"), recursive=True):
        with open(path) as f:
            text = f.read()
        found.update(re.findall(r'named_scope\(\s*"([^"]+)"', text))
        for const in re.findall(r"named_scope\(\s*([A-Z_]+)\s*\)", text):
            found.update(re.findall(r'^%s\s*=\s*"([^"]+)"' % const, text,
                                    re.M))
    return found


@pytest.mark.parametrize("first_output", [
    "conv2d_7.tmp_0", "scope/of/names/fc_0.tmp_1", "fc_0.w_0@GRAD",
    "a;b", "sum", "rope", "moe_route", "ssd_decay", "flash_attention_bwd",
    "pp_stage", "mul"])
def test_no_instance_breaks_a_path_or_equals_a_scope(first_output):
    from paddle_tpu.core.desc import OpDesc

    scopes = _scopes_the_kernels_open()
    assert {"moe_route", "ssd_decay", "flash_attention_bwd",
            "pp_stage"} <= scopes
    for od in (OpDesc("scale", {"X": ["x"]},
                      {"Out": ["@EMPTY@", first_output]}),
               OpDesc("scale_grad", {"X": ["x"], "O@Out": [first_output],
                                     "OG@Out": ["g"]}, {"X@GRAD": ["gx"]}),
               OpDesc("adam", {"Param": [first_output], "Grad": ["g"]},
                      {"ParamOut": [first_output]})):
        name = op_instance(od)
        assert name.startswith(INSTANCE_SIGIL) and len(name) > 1
        assert not set("/;@") & set(name)
        assert name not in scopes
        # what a reader's path parser makes of it: itself
        assert re.match(r"^\w+\((.*)\)$", name) is None
    # and no scope a kernel opens, and no op type, starts like one
    assert not [s for s in scopes if s.startswith(INSTANCE_SIGIL)]


def test_an_op_without_outputs_still_has_an_instance():
    from paddle_tpu.core.desc import OpDesc

    assert op_instance(OpDesc("print", {"In": ["x"]}, {})) == \
        INSTANCE_SIGIL + "x"
    assert op_instance(OpDesc("barrier", {}, {})) == \
        INSTANCE_SIGIL + "barrier"


# -- the decoding layer: spans around a call, scopes inside it ------------------

def _rnn_decoder(takes_block=False):
    """A `ProgramDecoder` over a one-layer recurrent step (start-up
    weights); with `takes_block` the token feed is declared
    `[batch, -1]` and the step reads the block's last token."""
    H, V = 8, 11
    fluid.framework.reset_unique_name()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        tok = fluid.layers.data(name="tok",
                                shape=[-1, -1] if takes_block else [-1],
                                dtype="int64", append_batch_size=False)
        h_in = fluid.layers.data(name="h_in", shape=[-1, H],
                                 dtype="float32", append_batch_size=False)
        last = tok
        if takes_block:
            # the block's columns, last first: [T, batch] -> [batch]
            last = fluid.layers.reduce_max(tok, dim=1)
        emb = fluid.layers.embedding(last, size=[V, 6])
        h_out = fluid.layers.fc(input=[emb, h_in], size=H, act="tanh")
        logits = fluid.layers.fc(input=h_out, size=V)
    scope = fluid.Scope()
    fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
    return fluid.ProgramDecoder(
        main.clone(for_test=True), token_name="tok",
        logits_name=logits.name, state_pairs=[("h_in", h_out.name)],
        scope=scope), H


def _decoder_calls(decoder, hidden, batch=3):
    """{mode: a call of it}, each with a state of `batch` rows."""
    def init():
        return {"h_in": np.zeros((batch, hidden), np.float32)}

    prompt = np.arange(batch * 5).reshape(batch, 5) % 7
    return {
        "greedy": lambda: decoder.greedy(
            bos=1, eos=0, max_len=6, init_state=init()),
        "greedy-prefill": lambda: decoder.greedy(
            bos=1, eos=0, max_len=6, init_state=init(), prompt=prompt),
        "sample": lambda: decoder.sample(
            bos=1, eos=0, max_len=4, init_state=init(), prompt=prompt),
        "beam": lambda: decoder.beam(
            beam_size=2, bos=1, eos=0, max_len=5, init_state=init()),
    }


@pytest.mark.parametrize("mode,prompt_len,max_len", [
    ("greedy", 0, 6), ("greedy-prefill", 5, 6), ("sample", 5, 4),
    ("beam", 0, 5)])
def test_decoder_spans_reach_the_profiler_with_obs_trace_disabled(
        tmp_path, mode, prompt_len, max_len):
    decoder, hidden = _rnn_decoder()
    call = _decoder_calls(decoder, hidden)[mode]
    assert not obs_trace.is_enabled()
    # the first call builds the program, the second finds it
    lines = _profile(tmp_path, lambda: (call(), call()))
    line, = [ln for ln in lines if any(ev[0] == "decode/call" for ev in ln)]
    calls = [ev for ev in line if ev[0] == "decode/call"]
    assert [c[3].pop("built") for c in calls] == [1, 0]
    first, second = (c[3].pop("call") for c in calls)
    assert second == first + 1
    for whole in calls:
        assert whole[3] == {"mode": mode, "batch": 3, "max_len": max_len,
                            "prompt_len": prompt_len, "block": 1}
        prep, = _inside(line, whole, "decode/prep")
        dispatch, = _inside(line, whole, "decode/dispatch")
        fetch, = _inside(line, whole, "decode/fetch")
        assert prep[3] == {"host_bytes": 3 * hidden * 4, "device_bytes": 0}
        assert dispatch[3] == {} and fetch[3] == {}
        assert prep[2] <= dispatch[1] and dispatch[2] <= fetch[1]
        # the jitted function stays a lambda, inside the dispatch
        assert _inside(line, dispatch, "PjitFunction(<lambda>)")
    assert obs_trace.events() == []


def test_a_block_taking_decoder_says_its_block_in_the_call_span(tmp_path):
    from paddle_tpu.models.decode import PREFILL_BLOCK

    decoder, hidden = _rnn_decoder(takes_block=True)
    assert decoder._takes_block
    call = _decoder_calls(decoder, hidden)["greedy-prefill"]
    lines = _profile(tmp_path, call)
    whole, = [ev for ln in lines for ev in ln if ev[0] == "decode/call"]
    assert whole[3]["block"] == PREFILL_BLOCK and whole[3]["built"] == 1


def test_decoder_spans_reach_the_memory_sink_while_obs_trace_is_on():
    decoder, hidden = _rnn_decoder()
    call = _decoder_calls(decoder, hidden)["greedy-prefill"]
    with obs_trace.tracing():
        call()
    spans = [e for e in obs_trace.events() if e["ph"] == "X"]
    assert [e["name"] for e in spans] == [
        "decode/prep", "decode/dispatch", "decode/fetch", "decode/call"]
    assert {e["cat"] for e in spans} == {"decoder"}
    assert spans[0]["args"] == {"host_bytes": 3 * hidden * 4,
                                "device_bytes": 0}
    assert spans[-1]["args"]["mode"] == "greedy-prefill"


def _lowered_names(decoder, hidden, mode):
    """The `op_name` paths of the text a decoder's call compiles to."""
    _decoder_calls(decoder, hidden)[mode]()
    (key, fn), = decoder._compiled.items()
    assert key[0] == mode
    state = {"h_in": jnp.zeros((3, hidden), jnp.float32)}
    extra = {"greedy": (), "beam": (),
             "greedy-prefill": (jnp.zeros((3, 5), jnp.int32),),
             "sample": (jnp.zeros((3, 5), jnp.int32),
                        jax.random.PRNGKey(0))}[mode]
    text = fn.lower(decoder._params, state, *extra).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', text))


@pytest.mark.parametrize("mode,prefills", [
    ("greedy", False), ("greedy-prefill", True), ("sample", True),
    ("beam", False)])
def test_a_lowered_call_names_its_prefill_and_its_steps(mode, prefills):
    decoder, hidden = _rnn_decoder()
    names = _lowered_names(decoder, hidden, mode)
    steps = [n for n in names if "/decode_steps/" in n]
    assert steps
    # the scope stands in front of the scan's own, the op's type and
    # instance behind them
    assert any(re.match(r"^jit\(<lambda>\)/decode_steps/while/body/.*"
                        r"/mul/%s[^/]+/" % INSTANCE_SIGIL, n)
               for n in steps), sorted(steps)[:5]
    prefill = [n for n in names if "/decode_prefill/" in n]
    assert bool(prefill) == prefills
    if prefills:
        # the first position runs outside the scan, the rest inside it
        assert any(re.match(r"^jit\(<lambda>\)/decode_prefill/mul/", n)
                   for n in prefill)
        assert any("/decode_prefill/while/body/" in n for n in prefill)
    assert not [n for n in names
                if "decode_prefill" in n and "decode_steps" in n]


def test_jit_phases_of_a_decoder_are_counted_under_its_lambda():
    decoder, hidden = _rnn_decoder()
    call = _decoder_calls(decoder, hidden)["greedy-prefill"]
    before = obs_tele.snapshot()
    call()
    moved = obs_tele.snapshot_delta(before)
    for phase in ("trace", "lower", "compile"):
        assert moved.get("jit_phase_seconds_total{fun_name=<lambda>,"
                         "phase=%s}" % phase, 0) > 0, moved
    # the second call compiles nothing
    before = obs_tele.snapshot()
    call()
    assert not [k for k in obs_tele.snapshot_delta(before)
                if k.startswith("jit_phase_seconds_total")]
