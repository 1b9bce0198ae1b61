"""paddle_tpu.obs.mem: static memory timeline vs XLA actuals, the
donation audit, OOM pre-flight/post-mortems and gauge retirement."""

import json
import os

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.core.scope import Scope
from paddle_tpu.obs import flight as obs_flight
from paddle_tpu.obs import health as obs_health
from paddle_tpu.obs import mem as obs_mem
from paddle_tpu.obs import registry as obs_registry
from paddle_tpu.utils import flags as pt_flags

# the pinned static-vs-XLA factor for the golden fixtures: the static
# liveness walk and XLA's buffer assignment must stay within 4x of
# each other on CPU (measured: lenet5 1.65, mlp 2.41 — XLA's temp
# arena holds fusion scratch the IR walk can't see, and the walk
# counts logical bytes, not padded layouts)
PINNED_FACTOR = 4.0


def _build_lenet5(batch=8):
    from paddle_tpu import models as zoo

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        image = fluid.layers.data(
            name="image", shape=[batch, 1, 28, 28], dtype="float32",
            append_batch_size=False)
        logits = zoo.lenet5(image, class_dim=10)
        label = fluid.layers.data(
            name="label", shape=[batch, 1], dtype="int64",
            append_batch_size=False)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, label))
        fluid.optimizer.MomentumOptimizer(
            learning_rate=0.01, momentum=0.9).minimize(loss)
    feeds = {"image": np.random.RandomState(0)
             .rand(batch, 1, 28, 28).astype("float32"),
             "label": np.random.RandomState(1)
             .randint(0, 10, (batch, 1)).astype("int64")}
    return main, startup, loss, feeds


def _build_mlp(batch=16):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[batch, 256],
                              dtype="float32",
                              append_batch_size=False)
        h = fluid.layers.fc(input=x, size=512, act="relu")
        h = fluid.layers.fc(input=h, size=256, act="relu")
        y = fluid.layers.fc(input=h, size=10)
        label = fluid.layers.data(name="label", shape=[batch, 1],
                                  dtype="int64",
                                  append_batch_size=False)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(y, label))
        fluid.optimizer.MomentumOptimizer(
            learning_rate=0.01, momentum=0.9).minimize(loss)
    feeds = {"x": np.random.RandomState(0)
             .rand(batch, 256).astype("float32"),
             "label": np.random.RandomState(1)
             .randint(0, 10, (batch, 1)).astype("int64")}
    return main, startup, loss, feeds


def _build_adam_toy():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[64], dtype="float32")
        h = fluid.layers.fc(input=x, size=32)
        cost = fluid.layers.mean(x=h)
        fluid.optimizer.AdamOptimizer(
            learning_rate=0.01).minimize(cost)
    return main, startup, cost


def _run_captured(main, startup, loss, feeds):
    scope = Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup, scope=scope)
        with obs_health.force_attribution():
            exe.run(main, feed=feeds, fetch_list=[loss], scope=scope)
    return exe, scope


# ---------------------------------------------------------------------------
# static timeline
# ---------------------------------------------------------------------------

def test_timeline_matches_peak_walk():
    """liveness_peak_bytes is the timeline's peak — one shared walk."""
    from paddle_tpu.analysis.dataflow import (liveness_peak_bytes,
                                              liveness_timeline)

    main, _startup, loss, _ = _build_lenet5()
    bd = main.desc.block(0)
    final = {n for n, vd in bd.vars.items() if vd.persistable}
    final.add(loss.name)

    def nbytes(name):
        vd = bd.vars.get(name)
        if vd is None or vd.persistable or vd.shape is None:
            return 0
        return int(np.prod([max(s, 1) for s in vd.shape])) * 4

    tl = liveness_timeline(bd.ops, nbytes, final, top_n=4)
    peak, peak_op = liveness_peak_bytes(bd.ops, nbytes, final)
    assert tl["peak_bytes"] == peak and tl["peak_op"] == peak_op
    assert len(tl["series"]) == len(bd.ops)
    assert max(tl["series"]) == peak
    # blamed buffers: sorted largest-first, all live at the peak, each
    # with a defining op at or before the peak
    sizes = [b["bytes"] for b in tl["top_buffers"]]
    assert sizes == sorted(sizes, reverse=True) and sizes[0] > 0
    for b in tl["top_buffers"]:
        assert b["def_op"] is None or b["def_op"] <= peak_op


def test_program_timeline_and_render():
    main, _startup, loss, _ = _build_lenet5()
    tl = obs_mem.program_timeline(main, fetches=[loss.name], top_n=5)
    assert tl["ops"] == len(main.desc.block(0).ops)
    assert tl["peak_bytes"] > 0 and tl["params_bytes"] > 0
    assert tl["total_peak_bytes"] == \
        tl["peak_bytes"] + tl["params_bytes"]
    assert tl["peak_op_type"] == tl["op_types"][tl["peak_op"]]
    text = obs_mem.render_timeline(tl)
    assert "<- peak" in text
    assert tl["top_buffers"][0]["name"] in text


def test_timeline_chrome_trace_counter_track(tmp_path):
    from paddle_tpu.tools.obs_dump import validate_chrome_trace

    main, _startup, loss, _ = _build_lenet5()
    tl = obs_mem.program_timeline(main, fetches=[loss.name])
    path = str(tmp_path / "mem_trace.json")
    obs_mem.timeline_chrome_trace(tl, path=path)
    events = validate_chrome_trace(path)
    counters = [ev for ev in events if ev["ph"] == "C"]
    assert len(counters) == tl["ops"]
    assert max(ev["args"]["live_bytes"] for ev in counters) \
        == tl["peak_bytes"]


# ---------------------------------------------------------------------------
# golden fixtures: static estimate vs XLA actuals (CPU backend)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("build", [_build_lenet5, _build_mlp],
                         ids=["lenet5", "mlp"])
def test_static_peak_within_pinned_factor_of_xla(build):
    main, startup, loss, feeds = build()
    _run_captured(main, startup, loss, feeds)
    rep = obs_mem.drift_report()
    joined = [r for r in rep["segments"] if r["ratio"]]
    assert joined, "executor registered no drift-joinable segments"
    for row in joined:
        assert 1.0 / PINNED_FACTOR <= row["ratio"] <= PINNED_FACTOR, \
            "segment %s: static %d vs xla %d (ratio %.3f) outside " \
            "the pinned %gx factor" % (
                row["segment"], row["static_peak_bytes"],
                row["xla_program_bytes"], row["ratio"], PINNED_FACTOR)
    # the join also published the ratio gauge per segment
    snap = {k: v for k, v in
            __import__("paddle_tpu.obs.telemetry",
                       fromlist=["snapshot"]).snapshot().items()
            if k.startswith("mem_estimate_ratio{")}
    assert snap, "mem_estimate_ratio gauge never published"


def test_store_dump_load_roundtrip(tmp_path):
    main, startup, loss, feeds = _build_lenet5()
    _run_captured(main, startup, loss, feeds)
    path = str(tmp_path / "store.json")
    obs_mem.dump_store(path)
    offline = obs_mem.drift_report(obs_mem.load_store(path))
    live = obs_mem.drift_report()
    assert offline["n"] == live["n"] > 0
    assert offline["median_ratio"] == live["median_ratio"]
    with pytest.raises(ValueError):
        bad = str(tmp_path / "bad.json")
        with open(bad, "w") as f:
            json.dump({"kind": "other"}, f)
        obs_mem.load_store(bad)


# ---------------------------------------------------------------------------
# donation audit
# ---------------------------------------------------------------------------

def test_donation_audit_clean_program():
    main, _startup, cost = _build_adam_toy()
    audit = obs_mem.audit_donation(main, fetches=[cost.name])
    assert audit["donated"] and audit["donated_bytes"] > 0
    assert not audit["reclaimable"]
    donated_names = {d["name"] for d in audit["donated"]}
    # the param and both Adam moments advance in place -> donated
    assert any(n.endswith("moment1_0") for n in donated_names)
    assert any(d["kind"] == "param" for d in audit["donated"])


def test_donation_audit_finds_forked_adam_slot():
    from paddle_tpu.core.desc import VarDesc

    main, _startup, cost = _build_adam_toy()
    bd = main.desc.block(0)
    forked = None
    for od in bd.ops:
        if od.type == "adam":
            forked = od.input("Moment1")[0]
            src = bd.vars[forked]
            fork = forked + "__fork"
            bd.vars[fork] = VarDesc(fork, src.type, src.dtype,
                                    src.shape, persistable=True)
            od.outputs["Moment1Out"] = [fork]
            break
    assert forked
    audit = obs_mem.audit_donation(main, fetches=[cost.name])
    hits = [r for r in audit["reclaimable"] if r["name"] == forked]
    assert hits and hits[0]["bytes"] > 0
    assert hits[0]["kind"] == "optimizer_state"
    assert "forks" in hits[0]["reason"]
    assert audit["reclaimable_bytes"] >= hits[0]["bytes"]
    text = obs_mem.render_audit(audit)
    assert "RECLAIM" in text and forked in text


def test_donation_audit_dropped_alias():
    """A declared in-place out slot missing from the op strands the
    input buffer — the 'dropped alias' class."""
    main, _startup, cost = _build_adam_toy()
    bd = main.desc.block(0)
    name = None
    for od in bd.ops:
        if od.type == "adam":
            name = od.input("Moment2")[0]
            del od.outputs["Moment2Out"]
            break
    audit = obs_mem.audit_donation(main, fetches=[cost.name])
    hits = [r for r in audit["reclaimable"] if r["name"] == name]
    assert hits and "absent" in hits[0]["reason"]


# ---------------------------------------------------------------------------
# OOM pre-flight + post-mortem
# ---------------------------------------------------------------------------

def test_oom_context_is_empty_for_non_oom():
    assert obs_mem.oom_context(ValueError("boom")) == {}
    assert obs_mem.is_oom(MemoryError("x"))
    assert obs_mem.is_oom(RuntimeError("RESOURCE_EXHAUSTED: hbm"))
    assert not obs_mem.is_oom(RuntimeError("shape mismatch"))


def test_preflight_budget_and_flight_bundle(tmp_path):
    main, startup, loss, feeds = _build_lenet5()
    tl = obs_mem.program_timeline(main, fetches=[loss.name], top_n=8)
    recorder = obs_flight.install(out_dir=str(tmp_path), capacity=8)
    scope = Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    prev = pt_flags.get_flag("mem_budget_gb")
    try:
        with fluid.scope_guard(scope):
            exe.run(startup, scope=scope)
            pt_flags.set_flag("mem_budget_gb", 1e-6)
            with pytest.raises(obs_mem.MemoryBudgetError) as ei:
                exe.run(main, feed=feeds, fetch_list=[loss],
                        scope=scope, use_program_cache=False)
        assert "RESOURCE_EXHAUSTED" in str(ei.value)
        assert ei.value.timeline is not None
        # a budget the program fits compiles fine
        pt_flags.set_flag("mem_budget_gb", 16.0)
        with fluid.scope_guard(scope):
            exe.run(main, feed=feeds, fetch_list=[loss], scope=scope)
    finally:
        pt_flags.set_flag("mem_budget_gb", prev)
        obs_flight.uninstall()
    bundle = recorder.last_bundle_path
    assert bundle and os.path.exists(bundle)
    with open(bundle) as f:
        doc = json.load(f)
    ooms = [n["oom"] for n in doc["notes"] if n.get("oom")]
    assert ooms, "flight bundle carries no oom note"
    # the bundle's top blamed buffer IS the static timeline's peak
    # resident (the acceptance contract)
    assert ooms[0]["top_buffers"][0]["name"] == \
        tl["top_buffers"][0]["name"]
    from paddle_tpu.tools.obs_dump import render_flight

    rendered = render_flight(bundle)
    assert "OOM post-mortem" in rendered
    assert tl["top_buffers"][0]["name"] in rendered


# ---------------------------------------------------------------------------
# gauge retirement on program-cache eviction (satellite fix)
# ---------------------------------------------------------------------------

def _segment_gauge_labels(name):
    fam = obs_registry.get_registry().gauge(name,
                                            labelnames=("segment",))
    return {dict(s.get("labels", {})).get("segment")
            for s in fam.samples()}


def test_segment_gauges_retired_on_eviction():
    main, startup, loss, feeds = _build_lenet5()
    scope = Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe._CACHE_MAX = 1  # instance override: evict on the 2nd program
    with fluid.scope_guard(scope):
        exe.run(startup, scope=scope)
        with obs_health.force_attribution():
            exe.run(main, feed=feeds, fetch_list=[loss], scope=scope)
    assert _segment_gauge_labels("mem_static_peak_bytes"), \
        "attribution run published no mem gauges"
    labels_before = _segment_gauge_labels("xla_temp_bytes")
    assert labels_before
    # a second distinct program evicts the first from the LRU
    main2, startup2, loss2, feeds2 = _build_mlp()
    scope2 = Scope()
    with fluid.scope_guard(scope2):
        exe.run(startup2, scope=scope2)
        exe.run(main2, feed=feeds2, fetch_list=[loss2], scope=scope2)
    # the lenet5 program's segment labels are gone from every
    # per-segment family (xla_* and mem_*), not frozen forever
    lenet_labels = {l for l in labels_before if "conv2d" in (l or "")}
    assert lenet_labels
    for fam in ("xla_temp_bytes", "xla_argument_bytes",
                "mem_static_peak_bytes", "mem_xla_program_bytes",
                "mem_estimate_ratio"):
        assert not (_segment_gauge_labels(fam) & lenet_labels), \
            "evicted segment labels still render in %s" % fam
    assert not (set(obs_mem.segments()) & lenet_labels)


def test_eviction_keeps_labels_shared_with_live_program():
    """Labels are shape-independent: evicting one of two structurally
    identical programs must NOT retire the survivor's gauges (it is
    warm and would never re-publish them)."""
    main_a, startup_a, loss_a, feeds = _build_lenet5()
    main_b, startup_b, loss_b, _ = _build_lenet5()
    init_exe = fluid.Executor(fluid.CPUPlace())  # keeps startups out
    exe = fluid.Executor(fluid.CPUPlace())       # of the tiny cache
    exe._CACHE_MAX = 1
    scope_a, scope_b = Scope(), Scope()
    with fluid.scope_guard(scope_a):
        init_exe.run(startup_a, scope=scope_a)
        with obs_health.force_attribution():
            exe.run(main_a, feed=feeds, fetch_list=[loss_a],
                    scope=scope_a)
    labels = _segment_gauge_labels("mem_static_peak_bytes")
    assert labels
    with fluid.scope_guard(scope_b):
        init_exe.run(startup_b, scope=scope_b)
        # identical structure -> identical labels; inserting B evicts
        # A, but B still owns every label
        exe.run(main_b, feed=feeds, fetch_list=[loss_b],
                scope=scope_b)
    assert _segment_gauge_labels("mem_static_peak_bytes") == labels
    assert _segment_gauge_labels("xla_temp_bytes") >= labels


# ---------------------------------------------------------------------------
# satellites: S005 blame + serving /healthz memory section
# ---------------------------------------------------------------------------

def test_s005_cites_top_peak_buffers():
    from paddle_tpu import analysis

    main, _startup, loss, _feeds = _build_mlp()
    plan = analysis.analyze_sharding(main, {"dp": 4, "mp": 2},
                                     fetches=[loss.name],
                                     hbm_gb=1e-6, publish=False)
    errs = [d for d in plan.report.errors if d.code == "S005"]
    assert errs
    top = plan.hbm_breakdown["top_buffers"]
    assert top and top[0]["bytes"] > 0
    # the message names WHICH activations to remat, not just totals
    assert "top resident" in errs[0].message
    assert top[0]["name"] in errs[0].message


def test_serving_healthz_memory_section():
    from paddle_tpu.fluid import io as fluid_io
    from paddle_tpu.serving import (EngineConfig, InferenceEngine,
                                    InferenceServer, ServerConfig)

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[8],
                                dtype="float32")
        probs = fluid.layers.fc(input=img, size=3, act="softmax")
    scope = Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
    program = fluid_io.prune_program(main, [probs])
    engine = InferenceEngine(program, ["img"], [probs], scope=scope,
                             config=EngineConfig(batch_buckets=[2, 4]))
    server = InferenceServer(engine, ServerConfig(port=0,
                                                  warmup=False))
    engine.warmup()
    body = server.health_signals()
    # CPU exposes no allocator stats, but warmup captured per-bucket
    # XLA bytes through the attribution artifacts
    assert "memory" in body, body
    buckets = body["memory"]["bucket_xla_bytes"]
    assert set(buckets) == {"2", "4"}
    assert all(v >= 0 for v in buckets.values())
    snap = {k: v for k, v in
            __import__("paddle_tpu.obs.telemetry",
                       fromlist=["snapshot"]).snapshot().items()
            if k.startswith("mem_bucket_xla_bytes{")}
    assert len(snap) == 2
