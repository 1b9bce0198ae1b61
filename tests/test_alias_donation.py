"""Alias & donation-safety analysis (analysis/alias.py, A0xx codes)
and its executor/trainer/audit wiring behind FLAGS_donation.

Donation is value-preserving: XLA reuses the donated input's buffer
for an output, so numerics across off/conservative/auto must be
BIT-identical on f32 — several tests below pin exactly that, on the
toy and on the models the benchmark's cells run at their smallest
sizes.  On the CPU backend donation is a silent no-op, so the tests
read the plan the executor applied rather than assert buffer deletion.
"""

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import analysis
from paddle_tpu.core.desc import OpDesc
from paddle_tpu.core.scope import Scope
from paddle_tpu.obs import mem as obs_mem
from paddle_tpu.tools.lint_cli import _build_two_segment
from paddle_tpu.tools.mem_cli import _build_adam_toy, _fork_adam_slot
from paddle_tpu.utils import flags


@pytest.fixture(autouse=True)
def _restore_donation_flag():
    old = flags.get_flag("donation")
    yield
    flags.set_flag("donation", old)


def _toy_feeds(n=4, d=64):
    rs = np.random.RandomState(0)
    return lambda: {"x": rs.randn(n, d).astype(np.float32)}


def _train_losses(main, startup, cost, steps=4, feeds=None):
    """Fresh Executor+Scope run; returns (per-step losses, final
    param values) for exact cross-mode comparison.  `feeds()` gives a
    step's feed dict (the toy's random batches when None)."""
    feeds = feeds or _toy_feeds()
    scope = Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup, scope=scope)
        losses = []
        for _ in range(steps):
            out, = exe.run(main, feed=feeds(), fetch_list=[cost],
                           scope=scope)
            losses.append(np.asarray(out).copy())
        params = {n: np.asarray(scope.get(n)).copy()
                  for n in main.global_block().vars
                  if scope.get(n) is not None}
    return losses, params, exe


# -- the plan ---------------------------------------------------------------

def test_mode_ladder_and_fingerprints():
    main, _startup, cost = _build_adam_toy()
    plans = {m: analysis.analyze_donation(main, fetches=[cost.name],
                                          mode=m)
             for m in ("off", "conservative", "auto")}
    auto = plans["auto"]
    assert not auto.report.errors
    nseg = len(auto.segments)
    assert any(auto.donate(i) for i in range(nseg))
    for i in range(nseg):
        assert plans["off"].donate(i) == ()
        assert set(plans["conservative"].donate(i)) \
            <= set(auto.donate(i))
    # the three modes can never share an executable
    fps = {m: p.fingerprint() for m, p in plans.items()}
    assert len(set(fps.values())) == 3, fps


def test_feed_is_never_widened():
    main, _startup, cost = _build_adam_toy()
    plan = analysis.analyze_donation(main, fetches=[cost.name],
                                     mode="auto")
    for i in range(len(plan.segments)):
        assert "x" not in plan.donate(i)
    # same result whether or not the caller names its feeds: a name
    # read before any def site is caller-owned regardless
    plan2 = analysis.analyze_donation(main, fetches=[cost.name],
                                      feeds=["x"], mode="auto")
    assert plan2.fingerprint() == plan.fingerprint()


def test_donation_mode_parsing():
    assert analysis.donation_mode("off") == "off"
    assert analysis.donation_mode("bogus") == "auto"
    from paddle_tpu.analysis.alias import state_donation

    flags.set_flag("donation", "off")
    assert state_donation() is False
    flags.set_flag("donation", "auto")
    assert state_donation() is True


# -- the A-codes ------------------------------------------------------------

def test_a001_forked_slot_and_audit_delta():
    main, _startup, cost = _build_adam_toy()
    forked = _fork_adam_slot(main)
    plan = analysis.analyze_donation(main, fetches=[cost.name],
                                     mode="auto")
    assert "A001" in plan.report.codes()
    broken = obs_mem.audit_donation(main, fetches=[cost.name],
                                    mode="auto")
    hits = [r for r in broken["reclaimable"] if r["name"] == forked]
    assert hits and hits[0].get("code") == "A001"
    assert broken["reclaimable_bytes"] > 0
    # FLAGS_donation=off surrenders exactly the donated bytes on top
    off = obs_mem.audit_donation(main, fetches=[cost.name],
                                 mode="off")
    assert not off["donated"]
    assert off["reclaimable_bytes"] == (broken["reclaimable_bytes"]
                                        + broken["donated_bytes"])


def test_a002_read_after_donation_via_stale_plan():
    main, _startup, hname, lname = _build_two_segment()
    plan = analysis.analyze_donation(main, fetches=[lname],
                                     mode="auto")
    assert any(hname in plan.widened(i)
               for i in range(len(plan.segments)))
    # mutate the program AFTER planning: a later op now reads the
    # donated buffer — verify() must refuse the stale plan
    main.desc.block(0).ops.append(
        OpDesc("scale", {"X": [hname]}, {"Out": ["__late__"]},
               {"scale": 2.0}))
    rep = plan.verify(main, fetches=[lname, "__late__"])
    assert "A002" in rep.codes()
    assert rep.errors


def test_a003_fetch_declines_widening():
    main, _startup, hname, lname = _build_two_segment()
    plan = analysis.analyze_donation(main, fetches=[lname, hname],
                                     mode="auto")
    assert "A003" in plan.report.codes()
    assert not any(hname in plan.widened(i)
                   for i in range(len(plan.segments)))


# -- executor wiring --------------------------------------------------------

def test_executor_applies_widened_plan():
    flags.set_flag("donation", "auto")
    main, startup, hname, lname = _build_two_segment()
    scope = Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup, scope=scope)
        exe.run(main, feed={"x": np.zeros((4, 16), np.float32)},
                fetch_list=[lname], scope=scope)
    cp = list(exe._cache.values())[-1]
    assert cp._donation["mode"] == "auto"
    muts = [j["mutated"] for j in cp._jit_cache.values()]
    assert any(hname in m for m in muts), muts


def _adam_toy():
    main, startup, cost = _build_adam_toy()
    return main, startup, cost, _toy_feeds()


def _resnet_cifar10():
    """`resnet50-train`'s family at its smallest depth (one block a
    group), 32x32 images, Momentum."""
    from paddle_tpu.models.image import resnet_cifar10

    fluid.framework.reset_unique_name()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        image = fluid.layers.data(name="image", shape=[3, 32, 32],
                                  dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        logits = resnet_cifar10(image, class_dim=10, depth=8)
        cost = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, label))
        fluid.optimizer.Momentum(learning_rate=0.01,
                                 momentum=0.9).minimize(cost)
    rs = np.random.RandomState(0)
    feed = {"image": rs.rand(4, 3, 32, 32).astype(np.float32),
            "label": rs.randint(0, 10, size=(4, 1)).astype(np.int64)}
    return main, startup, cost, lambda: feed


def _transformer():
    """`gpt2m-train`'s program (the `flash_attention` op, interpreted
    here) at tests/test_flash_attention_op.py's size, Adam."""
    from paddle_tpu.models.transformer_program import (
        build_transformer_program, transformer_program_feeds)

    fluid.framework.reset_unique_name()
    main, startup, cost, _ = build_transformer_program(
        4, 16, 64, n_layer=1, n_head=4, d_model=32)
    with fluid.program_guard(main, startup):
        fluid.optimizer.Adam(learning_rate=3e-4).minimize(cost)
    feed = transformer_program_feeds(4, 16, 64, seed=1)
    return main, startup, cost, lambda: feed


def _looped():
    """`ouro-train-4k`'s program at tests/test_looped_program.py's
    size: two blocks applied three times over shared weights, Adam."""
    from paddle_tpu.models.looped_program import build_looped_program
    from paddle_tpu.models.transformer_program import (
        transformer_program_feeds)

    fluid.framework.reset_unique_name()
    main, startup, cost, _ = build_looped_program(
        2, 32, 97, n_layer=2, n_loop=3, n_head=4, d_model=64, d_ff=160)
    with fluid.program_guard(main, startup):
        fluid.optimizer.Adam(learning_rate=3e-4).minimize(cost)
    feed = transformer_program_feeds(2, 32, 97, seed=1)
    return main, startup, cost, lambda: feed


@pytest.mark.parametrize("build", [_adam_toy, _resnet_cifar10,
                                   _transformer, _looped],
                         ids=lambda b: b.__name__.lstrip("_"))
def test_modes_bit_identical_f32(build):
    """The core safety property: donation never changes a value — on
    the toy and on the programs the benchmark's cells run.  `auto` is
    the mode those cells run, and here it stays `auto`."""
    runs = {}
    for mode in ("off", "conservative", "auto"):
        flags.set_flag("donation", mode)
        main, startup, cost, feeds = build()
        runs[mode] = _train_losses(main, startup, cost, steps=3,
                                   feeds=feeds)
    ref_losses, ref_params, _ = runs["off"]
    for mode in ("conservative", "auto"):
        losses, params, _ = runs[mode]
        for a, b in zip(ref_losses, losses):
            np.testing.assert_array_equal(a, b)
        for n, v in ref_params.items():
            np.testing.assert_array_equal(v, params[n])
    cp = list(runs["auto"][2]._cache.values())[-1]
    assert cp._donation["mode"] == "auto"
    if sum(1 for seg in cp._plan if seg["jit"]) > 1:
        assert any(cp._donation["widened"]), cp._donation


def test_donation_under_amp_bf16():
    """Satellite: under amp_bf16 the state dtypes take two steps to
    reach their fixed point (f32 -> bf16 -> f32 masters).  The
    donation plan must ride the re-traces: after the fixed point no
    segment traces again, and auto matches off bit-for-bit (same
    casts, donation is aliasing only)."""
    runs = {}
    for mode in ("off", "auto"):
        flags.set_flag("donation", mode)
        with fluid.amp.bf16_guard():
            main, startup, cost = _build_adam_toy()
            runs[mode] = _train_losses(main, startup, cost, steps=5)
    for a, b in zip(runs["off"][0], runs["auto"][0]):
        np.testing.assert_array_equal(a, b)
    # signature fixed point: at most 3 traces over 5 steps (f32 ->
    # bf16 transient -> steady); a donated-dtype mismatch against the
    # runtime signature would retrace on EVERY step (>= 5)
    _losses, _params, exe = runs["auto"]
    cp = list(exe._cache.values())[-1]
    assert cp._donation["mode"] == "auto"
    sizes = {i: j["fn"]._cache_size()
             for i, j in cp._jit_cache.items()}
    assert sizes and all(s <= 3 for s in sizes.values()), sizes


# -- audit ------------------------------------------------------------------

def test_audit_clean_toy_zero_reclaimable_under_auto():
    main, _startup, cost = _build_adam_toy()
    audit = obs_mem.audit_donation(main, fetches=[cost.name],
                                   mode="auto")
    assert audit["mode"] == "auto"
    assert audit["reclaimable_bytes"] == 0, audit["reclaimable"]
    assert audit["donated_bytes"] > 0
    # every reclaimable entry in ANY mode carries its explanation
    off = obs_mem.audit_donation(main, fetches=[cost.name],
                                 mode="off")
    assert off["reclaimable_bytes"] > 0
    for r in off["reclaimable"]:
        assert r["reason"]
