"""The start-up timeline `obs.trace` keeps itself: which events the
program's once-a-process work leaves there, under which parents, and
that a steady-state run, step or call leaves none; `startup_summary`'s
arithmetic; the list's bound and its life apart from `enable()`'s
buffer.  No test asserts a duration.
"""

import threading
import time

import numpy as np
import pytest

import paddle_tpu
import paddle_tpu.fluid as fluid
from paddle_tpu.obs import trace as obs_trace
from paddle_tpu.parallel import ParallelTrainer, make_mesh
from paddle_tpu.tools import obs_dump


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


@pytest.fixture(autouse=True)
def room_on_the_timeline(monkeypatch):
    """The list is one a process and bounded: a worker that ran other
    files first may have filled it.  Every test gets room of its own."""
    monkeypatch.setattr(obs_trace, "_STARTUP_MAX",
                        len(obs_trace.startup_events()) + 4096)


class _Since:
    """The events the timeline gained since this was made, each with
    its index in the whole list (what `parent` names)."""

    def __init__(self):
        self.mark = len(obs_trace.startup_events())

    def events(self):
        return list(enumerate(obs_trace.startup_events()))[self.mark:]

    def named(self, name):
        return [(i, ev) for i, ev in self.events() if ev["name"] == name]

    def one(self, name):
        (i, ev), = self.named(name)
        return i, ev


def _jit_phases(since, fun_name):
    return [(i, ev) for i, ev in since.events()
            if ev["name"].startswith("startup/jit_")
            and ev["args"]["fun_name"] == fun_name]


# -- the executor ---------------------------------------------------------------

def test_a_first_run_holds_its_plan_and_its_jit_phases():
    main, startup, loss, feeds = _mlp()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    since = _Since()
    exe.run(startup, scope=scope)
    run, first = since.one("startup/executor_first_run")
    assert first["args"]["place"] == "CPUPlace"
    assert first["args"]["plan_miss"] == 1 and first["args"]["traces"] >= 1
    _, plan = since.one("startup/executor_plan")
    assert plan["parent"] == run
    assert plan["args"]["ops"] == first["args"]["ops"] > 0
    assert plan["args"]["segments"] >= 1
    phases = _jit_phases(since, "segment_fn")
    assert {ev["name"] for _, ev in phases} == {
        "startup/jit_trace", "startup/jit_lower", "startup/jit_compile"}
    assert all(ev["parent"] == run for _, ev in phases)
    # an event knows when it ran and where
    assert all(ev["dur"] is not None and ev["t0"] <= time.perf_counter()
               and ev["tid"] == first["tid"]
               and ev["thread"] == threading.current_thread().name
               for _, ev in since.events())

    since = _Since()
    exe.run(main, feed=feeds, fetch_list=[loss], scope=scope)
    run, first = since.one("startup/executor_first_run")
    assert since.one("startup/executor_plan")[1]["parent"] == run
    assert _jit_phases(since, "segment_fn")


def test_a_second_run_of_the_same_program_appends_nothing():
    main, startup, loss, feeds = _mlp()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    exe.run(main, feed=feeds, fetch_list=[loss], scope=scope)
    since = _Since()
    for _ in range(3):
        exe.run(main, feed=feeds, fetch_list=[loss], scope=scope)
    exe.run(startup, scope=fluid.Scope())
    assert since.events() == []


def test_a_run_that_only_retraces_is_a_first_run_without_a_plan():
    fluid.framework.reset_unique_name()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[-1, 4], dtype="float32",
                              append_batch_size=False)
        y = fluid.layers.scale(x=x, scale=2.0)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(main, feed={"x": np.ones((2, 4), np.float32)}, fetch_list=[y])
    since = _Since()
    exe.run(main, feed={"x": np.ones((3, 4), np.float32)}, fetch_list=[y])
    run, first = since.one("startup/executor_first_run")
    assert first["args"]["plan_miss"] == 0 and first["args"]["traces"] == 1
    assert since.named("startup/executor_plan") == []
    assert all(ev["parent"] == run for _, ev in since.events()
               if ev["name"] != "startup/executor_first_run")


def test_the_optimizer_pass_holds_the_backward_pass():
    since = _Since()
    _mlp()
    opt, minimized = since.one("startup/program_optimize")
    _, backward = since.one("startup/program_backward")
    assert backward["parent"] == opt
    assert minimized["args"] == {"op_type": "momentum", "parameters": 4}
    assert 0 < backward["args"]["ops_before"] < backward["args"]["ops_after"]


# -- the decoder ----------------------------------------------------------------

def _rnn_decoder(host_weights=False):
    H, V = 8, 11
    fluid.framework.reset_unique_name()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        tok = fluid.layers.data(name="tok", shape=[-1], dtype="int64",
                                append_batch_size=False)
        h_in = fluid.layers.data(name="h_in", shape=[-1, H],
                                 dtype="float32", append_batch_size=False)
        emb = fluid.layers.embedding(tok, size=[V, 6])
        h_out = fluid.layers.fc(input=[emb, h_in], size=H, act="tanh")
        logits = fluid.layers.fc(input=h_out, size=V)
    scope = fluid.Scope()
    fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
    if host_weights:
        for name in scope.local_var_names():
            scope.set(name, np.asarray(scope.get(name)))
    return fluid.ProgramDecoder(
        main.clone(for_test=True), token_name="tok",
        logits_name=logits.name, state_pairs=[("h_in", h_out.name)],
        scope=scope), H


@pytest.mark.parametrize("host_weights", [False, True])
def test_a_decoder_init_holds_its_program_and_its_weights_way(host_weights):
    since = _Since()
    decoder, _ = _rnn_decoder(host_weights)
    init, _ = since.one("startup/decoder_init")
    assert since.one("startup/functional_program")[1]["parent"] == init
    _, placed = since.one("startup/state_place")
    assert placed["parent"] == init
    # weights the scope holds on the device are taken as they lie
    nbytes = sum(v.nbytes for v in decoder._params.values())
    assert placed["args"] == (
        {"arrays": len(decoder._params), "bytes": nbytes} if host_weights
        else {"arrays": 0, "bytes": 0})


def test_a_built_call_is_an_event_and_a_repeated_call_is_none():
    decoder, hidden = _rnn_decoder()
    prompt = np.arange(15).reshape(3, 5) % 7

    def call(max_len=6):
        return decoder.greedy(
            bos=1, eos=0, max_len=max_len, prompt=prompt,
            init_state={"h_in": np.zeros((3, hidden), np.float32)})

    since = _Since()
    call()
    build, built = since.one("startup/decoder_build")
    assert built["args"] == {"mode": "greedy-prefill", "batch": 3,
                             "prompt_len": 5, "max_len": 6}
    phases = _jit_phases(since, "<lambda>")
    assert {ev["name"] for _, ev in phases} == {
        "startup/jit_trace", "startup/jit_lower", "startup/jit_compile"}
    assert all(ev["parent"] == build for _, ev in phases)

    since = _Since()
    call()
    call()
    assert since.events() == []
    # another key is another program
    call(max_len=4)
    assert since.one("startup/decoder_build")[1]["args"]["max_len"] == 4


# -- the trainer ----------------------------------------------------------------

def test_a_trainer_init_and_its_first_step_and_no_other_step():
    main, startup, loss, feeds = _mlp()
    trainer = ParallelTrainer(main, startup, feed_names=sorted(feeds),
                              fetch_names=[loss.name],
                              mesh=make_mesh(n_devices=2, dp=2))
    since = _Since()
    trainer.init()
    init, inited = since.one("startup/trainer_init")
    assert inited["args"] == {"trainer": "ParallelTrainer"}
    run, first = since.one("startup/executor_first_run")
    assert first["parent"] == init and first["args"]["place"] == "CPUPlace"
    assert since.one("startup/executor_plan")[1]["parent"] == run
    assert since.one("startup/functional_program")[1]["parent"] == init
    _, placed = since.one("startup/state_place")
    assert placed["parent"] == init
    assert placed["args"] == {
        "arrays": len(trainer.state),
        "bytes": sum(v.nbytes for v in trainer.state.values())}
    assert since.named("startup/trainer_first_step") == []

    since = _Since()
    trainer.step(feeds)
    step, first = since.one("startup/trainer_first_step")
    assert first["args"] == {"step": 0} and first["parent"] == -1
    phases = _jit_phases(since, "step")
    assert {ev["name"] for _, ev in phases} == {
        "startup/jit_trace", "startup/jit_lower", "startup/jit_compile"}
    assert all(ev["parent"] == step for _, ev in phases)

    since = _Since()
    trainer.step(feeds)
    trainer.step(feeds)
    assert since.events() == []


# -- the package's import and a load from disk ----------------------------------

def test_the_import_begins_before_every_other_event():
    events = obs_trace.startup_events()
    first = events[0]
    assert first["name"] == "startup/import" and first["parent"] == -1
    assert all(first["t0"] <= ev["t0"] for ev in events)
    children = {ev["name"]: ev for ev in events if ev["parent"] == 0
                and ev["name"].startswith("startup/import_")}
    assert set(children) == {"startup/import_fluid", "startup/import_v2"}
    assert "fluid" in paddle_tpu.__all__


def test_a_load_from_disk_says_its_files_and_bytes(tmp_path):
    main, startup, loss, _ = _mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        fluid.io.save_persistables(exe, str(tmp_path), main)
        since = _Since()
        fluid.io.load_persistables(exe, str(tmp_path), main)
        _, loaded = since.one("startup/load")
        assert loaded["args"]["files"] > 0
        assert loaded["args"]["bytes"] >= sum(
            np.asarray(scope.get(p.name)).nbytes
            for p in main.global_block().all_parameters())
        since = _Since()
        fluid.io.load_params(exe, str(tmp_path), main)
        assert since.one("startup/load")[1]["args"]["files"] == 4

    fluid.framework.reset_unique_name()
    infer, infer_startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(infer, infer_startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        y = fluid.layers.fc(input=x, size=3)
    with fluid.scope_guard(fluid.Scope()):
        exe.run(infer_startup)
        fluid.io.save_inference_model(str(tmp_path / "m"), ["x"], [y], exe,
                                      infer)
        since = _Since()
        fluid.io.load_inference_model(str(tmp_path / "m"), exe)
    (outer, model), (_, weights) = since.named("startup/load")
    assert model["args"] == {"program": "__model__"}
    assert weights["parent"] == outer and weights["args"]["files"] == 2


# -- the summary ----------------------------------------------------------------

def _event(name, t0, dur, parent=-1, tid=1, **args):
    return {"name": name, "t0": t0, "dur": dur, "tid": tid,
            "thread": "t%d" % tid, "parent": parent, "args": args}


# one thread: an init that holds a first run that holds a plan and three
# phases (the outer event told last, as `emit_span` tells it), then a jit
# of the caller's own with a phase inside it, then a first step
TIMELINE = [
    _event("startup/trainer_init", 10.0, 6.0),
    _event("startup/executor_plan", 10.5, 0.5, parent=5),
    _event("startup/jit_trace", 11.0, 1.0, parent=5, fun_name="segment_fn"),
    _event("startup/jit_lower", 12.0, 0.5, parent=5, fun_name="segment_fn"),
    _event("startup/jit_compile", 12.5, 1.5, parent=5,
           fun_name="segment_fn"),
    _event("startup/executor_first_run", 10.25, 4.25, parent=0,
           place="CPUPlace"),
    _event("startup/jit_trace", 16.5, 0.25, parent=7, fun_name="inner"),
    _event("startup/jit_trace", 16.25, 1.0, fun_name="pool"),
    _event("startup/jit_compile", 17.25, 0.75, fun_name="pool"),
    _event("startup/trainer_first_step", 20.0, 3.0),
    _event("startup/jit_compile", 20.5, 2.0, parent=9, fun_name="step"),
]


def test_self_seconds_add_up_to_covered_on_one_thread():
    summary = obs_trace.startup_summary(events=TIMELINE)
    rows = summary["events"]
    assert rows["startup/trainer_init"] == {"calls": 1, "self_s": 1.75}
    assert rows["startup/executor_first_run"] == {"calls": 1,
                                                  "self_s": 0.75}
    assert rows["startup/executor_plan"] == {"calls": 1, "self_s": 0.5}
    assert rows["startup/jit_trace"] == {"calls": 1, "self_s": 1.0}
    assert rows["startup/jit_compile"] == {"calls": 2, "self_s": 3.5}
    assert rows["startup/trainer_first_step"]["self_s"] == 1.0
    # a jit of the caller's own, and a phase under it, are kept apart
    assert rows["startup/jit_trace (outside)"] == {"calls": 2,
                                                   "self_s": 1.0}
    assert rows["startup/jit_compile (outside)"]["self_s"] == 0.75
    inside = sum(row["self_s"] for name, row in rows.items()
                 if not name.endswith(obs_trace.OUTSIDE))
    assert inside == summary["covered"] == 9.0


@pytest.mark.parametrize("since,until,names,covered", [
    (None, 15.0, {"startup/trainer_init", "startup/executor_plan",
                  "startup/jit_trace", "startup/jit_lower",
                  "startup/jit_compile", "startup/executor_first_run"},
     5.0),                      # the init runs on past `until`
    (12.0, 17.0, {"startup/jit_lower", "startup/jit_compile",
                  "startup/jit_trace (outside)"}, 2.0),
    (20.0, None, {"startup/trainer_first_step", "startup/jit_compile"},
     3.0),
    (20.25, 20.5, set(), 0.0),  # `until` itself is outside
])
def test_since_and_until_choose_events_by_their_beginning(
        since, until, names, covered):
    summary = obs_trace.startup_summary(since=since, until=until,
                                        events=TIMELINE)
    assert set(summary["events"]) == names
    assert summary["covered"] == covered


def test_two_threads_that_overlap_are_covered_once():
    events = [_event("startup/decoder_init", 0.0, 4.0),
              _event("startup/load", 1.0, 5.0, tid=2)]
    summary = obs_trace.startup_summary(events=events)
    assert summary["covered"] == 6.0
    assert sum(r["self_s"] for r in summary["events"].values()) == 9.0


def test_the_live_timeline_adds_up_too():
    main, startup, loss, feeds = _mlp()
    began = time.perf_counter()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    exe.run(main, feed=feeds, fetch_list=[loss], scope=scope)
    summary = obs_trace.startup_summary(since=began)
    mine = threading.get_ident() & 0x7FFFFFFF
    assert all(ev["tid"] == mine for ev in obs_trace.startup_events()
               if ev["t0"] >= began)
    inside = sum(row["self_s"] for name, row in summary["events"].items()
                 if not name.endswith(obs_trace.OUTSIDE))
    # JAX's clock and the listener's differ by microseconds
    assert inside == pytest.approx(summary["covered"], abs=1e-3)
    assert summary["covered"] <= time.perf_counter() - began


# -- the list itself --------------------------------------------------------------

def test_enable_and_reset_leave_the_timeline_alone():
    before = obs_trace.startup_events()
    assert before
    obs_trace.enable(clear=True)
    try:
        with obs_trace.span("startup/kept", cat="startup", why="test"):
            with obs_trace.span("executor/inner", cat="executor"):
                pass
        obs_trace.reset()
        assert obs_trace.events() == []
    finally:
        obs_trace.disable()
    after = obs_trace.startup_events()
    assert after[:len(before)] == before
    assert after[-1]["name"] == "startup/kept"
    assert after[-1]["args"] == {"why": "test"}
    # it never was an event of the main buffer, and the export has it
    doc = obs_trace.to_chrome_trace()
    carried = obs_dump.startup_events_of(doc)
    assert [ev["name"] for ev in carried] == [ev["name"] for ev in after]
    assert [ev["parent"] for ev in carried] == [ev["parent"]
                                                for ev in after]
    assert "startup/kept" in obs_dump.render_startup()
    assert obs_dump.main(["--startup"]) == 0


def test_an_open_event_counts_up_to_now():
    with obs_trace.span("startup/still_open", cat="startup"):
        ev = obs_trace.startup_events()[-1]
        assert ev["name"] == "startup/still_open" and ev["dur"] is None
        rows = obs_trace.startup_summary(since=ev["t0"])["events"]
        assert rows["startup/still_open"]["calls"] == 1
    assert obs_trace.startup_events()[-1]["dur"] is not None


def test_a_full_list_drops_and_counts(monkeypatch):
    room = 2
    monkeypatch.setattr(obs_trace, "_STARTUP_MAX",
                        len(obs_trace.startup_events()) + room)
    dropped = obs_trace.startup_summary()["dropped"]
    with obs_trace.span("startup/a", cat="startup"):
        with obs_trace.span("startup/b", cat="startup"):
            with obs_trace.span("startup/c", cat="startup"):   # dropped
                obs_trace.emit_span("startup/d", time.perf_counter(), 0.0,
                                    cat="startup")             # dropped
    events = obs_trace.startup_events()
    assert [ev["name"] for ev in events[-room:]] == ["startup/a",
                                                     "startup/b"]
    assert obs_trace.startup_summary()["dropped"] == dropped + 2
    assert obs_trace.to_chrome_trace()["otherData"][
        "startup_dropped_events"] == dropped + 2
    # the list's last places are not for jit phases: a first run, told
    # at its end, finds room after a process's worth of compiles
    monkeypatch.setattr(obs_trace, "_STARTUP_MAX",
                        len(obs_trace.startup_events())
                        + obs_trace._STARTUP_KEPT_FREE)
    obs_trace.emit_span("startup/jit_compile", time.perf_counter(), 0.0,
                        cat="startup", args={"fun_name": "f"})
    assert obs_trace.startup_summary()["dropped"] == dropped + 3
    # what a dropped span holds hangs under the nearest event kept
    with obs_trace.span("startup/e", cat="startup"):
        pass
    assert obs_trace.startup_events()[-1]["parent"] == -1


def test_a_phase_told_inside_a_phase_is_folded_into_it():
    since = _Since()
    with obs_trace.span("startup/holder", cat="startup"):
        t0 = time.perf_counter()
        for name, began in (("inner", t0 + 2e-6), ("sibling", t0 + 4e-6),
                            ("outer", t0 + 1e-6)):
            obs_trace.emit_span("startup/jit_trace", began, 1e-6,
                                cat="startup", args={"fun_name": name})
        obs_trace.emit_span("startup/jit_lower", t0 + 8e-6, 1e-6,
                            cat="startup", args={"fun_name": "outer"})
        obs_trace.emit_span("startup/first", t0, 1e-5, cat="startup")
    holder, _ = since.one("startup/holder")
    first, ev = since.one("startup/first")
    assert ev["parent"] == holder
    kept = [(ev["name"], ev["args"]["fun_name"], ev["parent"])
            for _, ev in since.events() if "fun_name" in ev["args"]]
    assert kept == [("startup/jit_trace", "outer", first),
                    ("startup/jit_lower", "outer", first)]


def test_two_threads_keep_their_own_parents():
    ready, go = threading.Barrier(2), threading.Barrier(2)
    found = {}

    def worker(key):
        with obs_trace.span("startup/outer_" + key, cat="startup"):
            ready.wait(10)       # both outer events are open now
            with obs_trace.span("startup/inner_" + key, cat="startup"):
                go.wait(10)
            t0 = time.perf_counter()
            obs_trace.emit_span("startup/told_" + key, t0, 0.0,
                                cat="startup")
        found[key] = threading.get_ident() & 0x7FFFFFFF

    since = _Since()
    threads = [threading.Thread(target=worker, args=(k,), name="w" + k)
               for k in "ab"]
    for t in threads:
        t.start()
    for t in threads:
        t.join(20)
    for key in "ab":
        outer, ev = since.one("startup/outer_" + key)
        assert ev["parent"] == -1 and ev["tid"] == found[key]
        assert ev["thread"] == "w" + key
        for name in ("startup/inner_", "startup/told_"):
            _, child = since.one(name + key)
            assert child["parent"] == outer and child["tid"] == found[key]
