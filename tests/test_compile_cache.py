"""The one persistent compile cache: JAX's, placed by
`paddle_tpu/utils/compile_cache.py`, behind every entry point.

What is asked of it: a restarted process compiles nothing; a warm
cache never serves another program; a damaged entry costs a recompile
and no more; a process that never turns it on writes nothing; the two
counters the program publishes are what JAX reports; a restored
checkpoint runs the step that was compiled before it.  A cache only
shows between processes, so each scenario runs
`tests/compile_cache_child.py` twice (cold, then warm) against one
directory and the cases below read the JSON lines it prints — a dozen
process starts for the file, not one per case.
"""

import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.core.scope import Scope
from paddle_tpu.fluid import executor as executor_mod
from paddle_tpu.obs import telemetry as obs_tele

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHILD = os.path.join(REPO, "tests", "compile_cache_child.py")


def _child(*argv, cache_dir=None, checkout=REPO):
    """One run of the child script: {entry: its JSON line}.
    `cache_dir` None leaves JAX_COMPILATION_CACHE_DIR unset (the cache
    then goes under `checkout`, where the child imports paddle_tpu
    from)."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"
           and not k.startswith("FLAGS_")}
    env["PYTHONPATH"] = checkout
    if cache_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    # niced and held to two cores: a child left alone compiles on
    # every core, and the tests of other workers that wait on leases
    # and deadlines must not starve
    cores = ",".join(map(str, sorted(os.sched_getaffinity(0))[-2:]))
    proc = subprocess.run(["nice", "-n", "10", "taskset", "-c", cores,
                           sys.executable, CHILD, *argv], env=env,
                          text=True, capture_output=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    lines = [json.loads(line[len("RESULT "):])
             for line in proc.stdout.splitlines()
             if line.startswith("RESULT ")]
    return {line["entry"]: line for line in lines}


def _linked_checkout(root):
    """A directory that imports this checkout's paddle_tpu through a
    symlink: `enable_compile_cache()` places `.jax_cache` beside the
    package it was imported from, so this one's is the child's alone."""
    os.symlink(os.path.join(REPO, "paddle_tpu"),
               os.path.join(str(root), "paddle_tpu"))
    return str(root)


# ---------------------------------------------------------------------------
# a restart compiles nothing
# ---------------------------------------------------------------------------

RESTART_ENTRIES = ["executor_f32", "executor_bf16", "functional", "spmd",
                   "engine", "supervisor"]


@pytest.fixture(scope="module")
def restart(tmp_path_factory):
    """The six entry points in one process, then again in a second
    process against the same cache directory and checkpoints."""
    cache = tmp_path_factory.mktemp("restart_cache")
    work = tmp_path_factory.mktemp("restart_work")
    cold = _child("restart", "--workdir", str(work), cache_dir=cache)
    warm = _child("restart", "--warm", "--workdir", str(work),
                  cache_dir=cache)
    return cold, warm


@pytest.mark.parametrize("entry", RESTART_ENTRIES)
def test_restart_compiles_nothing(restart, entry):
    cold, warm = restart[0][entry], restart[1][entry]
    assert cold["misses"] > 0, cold
    assert warm["misses"] == 0 and warm["hits"] > 0, warm
    for run in (cold, warm):
        # the two counters move exactly when JAX says its cache hit or
        # missed (the child's own `jax.monitoring` listener)
        assert (run["hits"], run["misses"]) == \
            (run["jax_hits"], run["jax_misses"]), run
    if entry == "supervisor":
        # the resumed run replays the fault-free run's later steps
        assert warm["steps"] == len(cold["fetches"])
        assert 0 < len(warm["fetches"]) < len(cold["fetches"])
        for step, loss in warm["fetches"].items():
            assert loss == cold["fetches"][step], step
    else:
        assert warm["fetches"] == cold["fetches"]
    if entry == "spmd":
        assert cold["devices"] == warm["devices"] == 8
    if entry == "engine":
        assert warm["buckets"] == 2
        assert warm["warmup"]["pcache_hits"] > 0
        assert warm["warmup"]["pcache_misses"] == 0
        assert warm["warmup"]["jit_compiles"] == \
            cold["warmup"]["jit_compiles"]


def test_resumed_run_loads_what_the_cold_process_left(restart):
    """The registry's counters around the resumed run
    (`compile_cache_hits_total`, what the engine's warm-up stats read
    too): it loads executables, and no more of them than the cold
    process compiled or loaded itself."""
    cold, warm = restart[0]["supervisor"], restart[1]["supervisor"]
    assert 0 < warm["hits"] <= cold["misses"] + cold["hits"]


# ---------------------------------------------------------------------------
# a warm cache never serves another program
# ---------------------------------------------------------------------------

PROGRAM_ENTRIES = ["feed_shape", "op_attribute", "amp_flag",
                   "donation_flag"]


@pytest.fixture(scope="module")
def programs(tmp_path_factory):
    """Four settings of one training program fill a cache; a second
    process changes each setting against that cache; a third runs the
    changed settings with no cache at all, from a checkout of its own."""
    cache = tmp_path_factory.mktemp("programs_cache")
    alone = _linked_checkout(tmp_path_factory.mktemp("programs_alone"))
    base = _child("programs", cache_dir=cache)
    changed = _child("programs", "--changed", cache_dir=cache)
    uncached = _child("programs", "--changed", "--no-cache",
                      checkout=alone)
    return base, changed, uncached, alone


@pytest.mark.parametrize("entry", PROGRAM_ENTRIES)
def test_warm_cache_never_serves_another_program(programs, entry):
    base, changed, uncached, _ = programs
    assert changed[entry]["fetches"] == uncached[entry]["fetches"]
    if entry in ("feed_shape", "op_attribute", "amp_flag"):
        assert changed[entry]["fetches"] != base[entry]["fetches"]
    else:
        # donation is aliasing only
        assert changed[entry]["fetches"] == base[entry]["fetches"]
    assert changed[entry]["misses"] > 0, changed[entry]


def test_off_means_no_disk(programs):
    """A process that never calls `enable_compile_cache()` and has no
    JAX_COMPILATION_CACHE_DIR has no cache: no directory set, no hit
    or miss reported, nothing written beside its checkout."""
    _, _, uncached, alone = programs
    assert uncached["process"]["cache_dir"] is None
    for entry in PROGRAM_ENTRIES:
        got = uncached[entry]
        assert got["hits"] == got["misses"] == 0, got
        assert got["jax_hits"] == got["jax_misses"] == 0, got
        assert got["jit_compiles"] > 0, got
    assert os.listdir(alone) == ["paddle_tpu"]


# ---------------------------------------------------------------------------
# a damaged entry is not fatal
# ---------------------------------------------------------------------------

DAMAGE = {
    "garbage": lambda blob: b"not an executable" * 64,
    "truncated": lambda blob: blob[:len(blob) // 2],
    "empty": lambda blob: b"",
}


@pytest.fixture(scope="module")
def damaged(tmp_path_factory):
    """One cold run into `<checkout>/.jax_cache` (no environment
    variable: the default place), then for each kind of damage the
    entries restored in place (the path is part of the key), every one
    of them damaged, and a warm run."""
    checkout = _linked_checkout(tmp_path_factory.mktemp("damaged"))
    cache = os.path.join(checkout, ".jax_cache")
    keep = str(tmp_path_factory.mktemp("damaged_keep") / "entries")
    cold = _child("tiny", checkout=checkout)
    assert cold["process"]["cache_dir"] == cache
    shutil.copytree(cache, keep)
    entries = [n for n in os.listdir(keep) if n.endswith("-cache")]
    assert len(entries) == cold["executor_f32"]["misses"] > 0
    runs = {}
    for kind, damage in DAMAGE.items():
        shutil.rmtree(cache)
        shutil.copytree(keep, cache)
        for name in entries:
            path = os.path.join(cache, name)
            with open(path, "rb") as f:
                blob = f.read()
            with open(path, "wb") as f:
                f.write(damage(blob))
        runs[kind] = _child("tiny", checkout=checkout)["executor_f32"]
    return cold["executor_f32"], runs


@pytest.mark.parametrize("kind", sorted(DAMAGE))
def test_damaged_entry_is_not_fatal(damaged, kind):
    cold, runs = damaged
    assert runs[kind]["fetches"] == cold["fetches"]
    assert runs[kind]["hits"] == 0
    assert runs[kind]["misses"] == cold["misses"]


# ---------------------------------------------------------------------------
# what is left in this process
# ---------------------------------------------------------------------------

def test_values_signature():
    key = executor_mod._values_signature_key
    a = np.zeros((2, 3), np.float32)
    assert key([("a", a), ("b", a)]) == \
        key([("b", np.ones((2, 3), np.float32)), ("a", a)])
    assert key([("a", a)]) != key([("a", np.zeros((2, 4), np.float32))])
    assert key([("a", a)]) != key([("a", np.zeros((2, 3), np.int32))])


def test_restored_checkpoint_runs_the_compiled_step(tmp_path):
    """`load_checkpoint` leaves host arrays in the scope.  The executor
    places them as it places a feed, once, so the step after a restore
    is the program the steps before it ran: no retrace, the losses of
    a run that was never interrupted, device arrays in the scope."""
    import jax

    from paddle_tpu.fluid.checkpoint import (CheckpointSaver,
                                             load_checkpoint)

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[13], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        loss = fluid.layers.mean(x=fluid.layers.square_error_cost(
            input=fluid.layers.fc(input=x, size=1), label=y))
        fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
    rs = np.random.RandomState(3)
    feed = {"x": rs.rand(8, 13).astype(np.float32),
            "y": rs.rand(8, 1).astype(np.float32)}
    exe = fluid.Executor(fluid.CPUPlace())

    def steps(scope, n):
        return [exe.run(main, feed=feed, fetch_list=[loss],
                        scope=scope)[0].tobytes() for _ in range(n)]

    whole, scope = Scope(), Scope()
    exe.run(startup, scope=whole)
    exe.run(startup, scope=scope)
    expected = steps(whole, 5)
    assert steps(scope, 2) == expected[:2]
    saver = CheckpointSaver(str(tmp_path), main_program=main)
    saver.save(2, scope)
    saver.wait()
    restored = Scope()
    assert load_checkpoint(str(tmp_path), scope=restored) == 2
    names = restored.local_var_names()
    assert names and all(isinstance(restored.get(n), np.ndarray)
                         for n in names)
    snap = obs_tele.snapshot()
    assert steps(restored, 3) == expected[2:]
    assert obs_tele.snapshot_delta(snap).get(
        "executor_jit_traces_total", 0) == 0
    assert all(isinstance(restored.get(n), jax.Array) for n in names)


def _build_scale_program(scale=2.0):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        y = fluid.layers.scale(x=x, scale=scale)
        z = fluid.layers.scale(x=y, scale=3.0)
    return main, startup, z.name


class TestProgramCacheEviction:
    def test_eviction_bounds_the_cache_and_is_logged(self, monkeypatch,
                                                     caplog):
        import logging

        monkeypatch.setattr(executor_mod.Executor, "_CACHE_MAX", 1)
        exe = executor_mod.Executor(executor_mod.CPUPlace())
        x = np.arange(8, dtype=np.float32).reshape(2, 4)
        with executor_mod.scope_guard(Scope()), \
                caplog.at_level(logging.DEBUG,
                                logger="paddle_tpu.executor"):
            for scale in (2.0, 3.0):
                main, startup, fetch = _build_scale_program(scale)
                exe.run(startup)
                exe.run(main, feed={"x": x}, fetch_list=[fetch])
        assert len(exe._cache) == 1
        assert any("evicted program cache entry" in r.message
                   for r in caplog.records)


@pytest.mark.parametrize("gone", [
    # the home-made executable cache, gone with its flag, its option and
    # the mode it degraded donation to.  `pcache_hits` / `pcache_misses`
    # stay: they are keys of the engine's warm-up stats
    r"compile_cache_dir|use_pcache|effective_mode"
    r"|pcache(?!_hits|_misses)",
    # the measuring programs from before `benchmark/run.py`, gone with
    # the environment names that steered them
    r"\b(BENCH|MEGA)_[A-Z]|\bbench\.py\b|mega_bench|spmd[./]bench"
    r"|run_serving_bench|bench_decode",
], ids=["cache", "benches"])
def test_no_source_names_what_was_deleted(gone):
    gone = re.compile(gone)
    sources = [os.path.join(REPO, "chip_smoke.py"),
               os.path.join(REPO, "__graft_entry__.py")]
    for top in ("paddle_tpu", "scripts"):
        for root, _, names in os.walk(os.path.join(REPO, top)):
            sources += [os.path.join(root, n) for n in names
                        if n.endswith((".py", ".sh"))]
    assert len(sources) > 100
    found = []
    for path in sources:
        with open(path, encoding="utf-8") as f:
            found += ["%s:%d: %s" % (os.path.relpath(path, REPO), i,
                                     line.strip())
                      for i, line in enumerate(f, 1)
                      if gone.search(line)]
    assert not found, "\n".join(found)


def test_the_model_builder_reads_no_layout_from_the_environment(
        monkeypatch):
    """`__graft_entry__._build_model` builds the Program it is asked
    for, op for op, whatever a deleted knob says."""
    from __graft_entry__ import _build_model
    from paddle_tpu import models

    def op_types():
        main, _, _, _ = _build_model(models.lenet5, 4, 28, 10,
                                     with_loss=True, channels=1)
        return [op.type for op in main.global_block().ops]

    asked = op_types()
    monkeypatch.setenv("BENCH_LAYOUT", "NHWC")
    assert op_types() == asked
    assert "transpose" not in asked
