"""paddle_tpu.obs.comm: per-bucket comm spans, overlap-efficiency
truth, analytic-floor drift and cross-host merge (tools/comm_cli.py
`pcomm` is the operator surface; scripts/ci.sh runs its --selftest).
"""

import json
import os
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import native
from paddle_tpu.obs import comm as obs_comm
from paddle_tpu.obs import fleet as obs_fleet
from paddle_tpu.obs import flight as obs_flight
from paddle_tpu.obs import registry as obs_registry
from paddle_tpu.obs import trace as obs_trace
from paddle_tpu.parallel import make_mesh
from paddle_tpu.spmd import SpmdTrainer
from paddle_tpu.spmd import overlap as spmd_overlap
from paddle_tpu.tools.obs_dump import validate_chrome_trace

BATCH, DIM, HIDDEN, CLASSES = 16, 8, 1024, 4


def _build_mlp():
    # the test_spmd probe: big first layer, small head, so a KB-scale
    # bucket cap yields several buckets in reduce order
    fluid.framework.reset_unique_name()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[BATCH, DIM],
                              dtype="float32", append_batch_size=False)
        label = fluid.layers.data(name="label", shape=[BATCH, 1],
                                  dtype="int64", append_batch_size=False)
        h = fluid.layers.fc(input=x, size=HIDDEN, act="relu")
        logits = fluid.layers.fc(input=h, size=CLASSES, act=None)
        loss = fluid.layers.softmax_with_cross_entropy(logits, label)
        avg = fluid.layers.mean(loss)
        fluid.optimizer.MomentumOptimizer(
            learning_rate=0.1, momentum=0.9).minimize(avg)
    return main, startup, avg


def _feeds(step=0):
    rs = np.random.RandomState(100 + step)
    return {
        "x": rs.rand(BATCH, DIM).astype(np.float32),
        "label": rs.randint(0, CLASSES,
                            size=(BATCH, 1)).astype(np.int64),
    }


def _make_trainer(mesh, bucket_bytes):
    main, startup, avg = _build_mlp()
    return SpmdTrainer(main, startup, feed_names=["x", "label"],
                       fetch_names=[avg.name], mesh=mesh,
                       bucket_bytes=bucket_bytes).init()


@pytest.fixture(scope="module")
def overlap_setup():
    """ONE traced overlapped dp=8 trainer shared across this module:
    the schedule spans fire at jit-trace time only, so the trace runs
    once with tracing on and COPIES of the captured events/schedule/
    host-context survive the per-test `fresh_obs` reset (the trainer
    object itself is reused — recompiling it per test would blow the
    tier-1 budget)."""
    obs_trace.enable()
    obs_comm.reset()
    mesh = make_mesh(n_devices=8, dp=8)
    trainer = _make_trainer(mesh, 24 << 10)
    trainer.step(_feeds(0))
    assert trainer.step_mode == "overlap-dp", trainer.step_mode
    setup = {
        "trainer": trainer,
        "sched": obs_comm.last_schedule(),
        "events": [dict(e) for e in obs_trace.events()],
        "host_ctx": obs_flight.host_context(),
    }
    obs_trace.disable()
    obs_trace.reset()
    yield setup


# -- trace-time schedule spans ---------------------------------------------

def test_schedule_names_last_produced_first(overlap_setup):
    sched = overlap_setup["sched"]
    trainer = overlap_setup["trainer"]
    assert sched and sched["collective"] == "allreduce"
    assert sched["axis"] == "dp" and sched["mean"]
    assert sched["n_buckets"] >= 2
    assert sched["total_bytes"] == sum(b["bytes"]
                                       for b in sched["buckets"])
    # flattened bucket members in EXACTLY the last-produced-first
    # (DDP) order the program's reduce seam defines
    _split, grad_order = spmd_overlap._split_point(
        list(trainer.main_program.desc.block(0).ops))
    flat = [n for b in sched["buckets"] for n in b["names"]]
    want = [g for g in reversed(grad_order) if g in set(flat)]
    assert flat == want, (flat, want)


def test_span_nesting_bytes_labels_and_instants(overlap_setup):
    sched = overlap_setup["sched"]
    evs = overlap_setup["events"]
    parents = [e for e in evs
               if e.get("name") == "comm/bucketed_allreduce"]
    assert parents, [e.get("name") for e in evs]
    assert parents[0]["args"]["n_buckets"] == sched["n_buckets"]
    assert parents[0]["args"]["total_bytes"] == sched["total_bytes"]
    bspans = [e for e in evs if e.get("name") == "comm/bucket"]
    assert len(bspans) == sched["n_buckets"]
    for i, e in enumerate(bspans):
        assert e["args"]["bucket"] == i
        assert e["args"]["bytes"] == sched["buckets"][i]["bytes"] > 0
        assert e["args"]["names"] == len(sched["buckets"][i]["names"])
        assert e["args"]["first"] == sched["buckets"][i]["names"][0]
    launches = [e for e in evs
                if e.get("name") == "comm/bucket_launch"]
    completes = [e for e in evs
                 if e.get("name") == "comm/bucket_complete"]
    assert len(launches) == len(completes) == sched["n_buckets"]
    # the overlap schedule's seam marker fired inside the same trace
    assert any(e.get("name") == "comm/reduce_seam" for e in evs)


def test_record_schedule_counter_and_reset():
    obs_trace.enable()
    sched = obs_comm.record_schedule(
        "allreduce", "dp",
        [{"bucket": 0, "names": ["b@GRAD", "a@GRAD"], "bytes": 128},
         {"bucket": 1, "names": ["w@GRAD"], "bytes": 64}])
    assert obs_comm.last_schedule() is sched
    assert sched["n_buckets"] == 2 and sched["total_bytes"] == 192
    ctr = obs_registry.get_registry().counter(
        "comm_bucket_schedules_total", labelnames=("collective",))
    vals = {s["labels"]["collective"]: s["value"]
            for s in ctr.samples()}
    assert vals["allreduce"] == 1
    assert any(e.get("name") == "comm/schedule"
               for e in obs_trace.events())
    # span helpers nest one comm/bucket per bucket inside the parent
    with obs_comm.schedule_span(sched):
        for i in range(sched["n_buckets"]):
            with obs_comm.bucket_span(sched, i):
                pass
    evs = obs_trace.events()
    assert len([e for e in evs
                if e.get("name") == "comm/bucket"]) == 2
    assert len([e for e in evs
                if e.get("name") == "comm/bucket_launch"]) == 2
    obs_comm.reset()
    assert obs_comm.last_schedule() is None


# -- runtime truth + overlap split -----------------------------------------

def test_measure_trainer_comm_rows_and_metrics(overlap_setup):
    trainer = overlap_setup["trainer"]
    rep = obs_comm.measure_trainer_comm(trainer, reps=1)
    assert rep and rep["collective"] == "allreduce" and rep["n"] == 8
    assert len(rep["buckets"]) >= 2
    for r in rep["buckets"]:
        assert r["measured_s"] > 0 and r["pred_s"] > 0
        assert r["wire_bytes"] > r["bytes"]  # ring wire > payload
        assert r["ratio"] == r["measured_s"] / r["pred_s"]
    assert rep["measured_s"] == pytest.approx(
        sum(r["measured_s"] for r in rep["buckets"]))
    reg = obs_registry.get_registry()
    hist = reg.histogram("comm_collective_seconds",
                         labelnames=("collective", "bucket"))
    buckets_seen = {s["labels"]["bucket"] for s in hist.samples()
                    if s["labels"].get("collective") == "allreduce"}
    assert {str(r["bucket"]) for r in rep["buckets"]} <= buckets_seen
    ctr = reg.counter("comm_bytes_total", labelnames=("collective",))
    total = sum(s["value"] for s in ctr.samples()
                if s["labels"]["collective"] == "allreduce")
    assert total == rep["wire_bytes"]  # reps=1: one replay per bucket


def test_overlap_report_split_and_gauges(overlap_setup):
    trainer = overlap_setup["trainer"]
    bucket_report = obs_comm.measure_trainer_comm(trainer, reps=1)
    rep = obs_comm.overlap_report(trainer, _feeds(0), reps=1,
                                  bucket_report=bucket_report)
    assert rep["supported"] and rep["step_mode"] == "overlap-dp"
    assert rep["plan_fingerprint"] == trainer.plan.fingerprint()
    assert rep["bucket_bytes"] == 24 << 10
    assert rep["step_s"] > 0 and rep["compute_s"] > 0
    assert rep["comm_s"] == pytest.approx(bucket_report["measured_s"])
    assert rep["exposed_s"] >= 0
    assert 0.0 <= rep["overlap_efficiency"] <= 1.0
    # the split is internally consistent: exposed + hidden == comm
    # (unless everything was exposed and hidden clamped to 0)
    assert rep["exposed_s"] + rep["hidden_s"] \
        == pytest.approx(rep["comm_s"]) \
        or rep["exposed_s"] >= rep["comm_s"]
    reg = obs_registry.get_registry()
    (exposed,) = reg.gauge("comm_exposed_seconds").samples()
    assert exposed["value"] == pytest.approx(rep["exposed_s"],
                                             abs=1e-6)
    (eff,) = reg.gauge("overlap_efficiency").samples()
    assert eff["value"] == pytest.approx(rep["overlap_efficiency"],
                                         abs=1e-4)


def test_overlap_report_fallback_carries_no_exposed_s():
    # a dp=4,mp=2 mesh falls back to gspmd at init: the report is
    # refused WITHOUT an exposed_s, so a fallback run structurally
    # cannot enter the overlap-efficiency baseline
    trainer = _make_trainer(make_mesh(n_devices=8, dp=4, mp=2),
                            24 << 10)
    assert trainer.step_mode == "gspmd"
    rep = obs_comm.overlap_report(trainer, _feeds(0), reps=1)
    assert not rep["supported"]
    assert rep["overlap_fallback_reason"]
    assert rep["plan_fingerprint"] == trainer.plan.fingerprint()
    assert "exposed_s" not in rep and "overlap_efficiency" not in rep


def test_trainer_stamps_flight_host_context(overlap_setup):
    ctx = overlap_setup["host_ctx"]
    trainer = overlap_setup["trainer"]
    assert ctx.get("process_index") == 0
    assert ctx.get("mesh_axes", {}).get("dp") == 8
    assert ctx.get("plan_fingerprint") == trainer.plan.fingerprint()
    assert ctx.get("host")


# -- drift off the ring-cost floor -----------------------------------------

_BUCKET_REPORT = {
    "collective": "allreduce", "axis": "dp", "n": 8,
    "bucket_bytes": 1 << 10, "measured_s": 0.0035, "pred_s": 0.0015,
    "wire_bytes": 2625,
    "buckets": [
        {"bucket": 0, "names": ["b", "a"], "bytes": 1000,
         "wire_bytes": 1750, "pred_s": 0.001, "measured_s": 0.002,
         "ratio": 2.0},
        {"bucket": 1, "names": ["w"], "bytes": 500, "wire_bytes": 875,
         "pred_s": 0.0005, "measured_s": 0.0015, "ratio": 3.0},
    ],
}


def test_drift_report_rows_and_gauge():
    drift = obs_comm.drift_report(_BUCKET_REPORT)
    assert drift["n"] == 2 and drift["median_ratio"] == 2.5
    assert [r["bucket"] for r in drift["rows"]] == [0, 1]
    gauge = obs_registry.get_registry().gauge(
        "comm_estimate_ratio", labelnames=("bucket",))
    vals = {s["labels"]["bucket"]: s["value"]
            for s in gauge.samples()}
    assert vals == {"0": 2.0, "1": 3.0}
    assert obs_comm.drift_report(None)["n"] == 0


# -- span windows, clock exchange, cross-host merge ------------------------

def _fake_window(host, epoch_wall, n=3):
    return {"host": host, "ts": epoch_wall + 1.0,
            "epoch_wall": epoch_wall, "dropped": 0,
            "events": [{"name": "step", "cat": "paddle_tpu",
                        "ph": "X", "ts": 1000.0 * i, "dur": 500.0,
                        "tid": 0} for i in range(n)]}


def test_merge_windows_rebases_with_offsets():
    # hostB's wall clock runs 0.5s ahead; the estimated offset cancels
    # it, putting both hosts' epochs on the same corrected instant
    wa = _fake_window("hostA", 100.0)
    wb = _fake_window("hostB", 100.5)
    merged = obs_comm.merge_windows({"hostA": wa, "hostB": wb},
                                    {"hostB": 0.5})
    events = validate_chrome_trace(merged)
    names = {e["args"]["name"]: e["pid"] for e in events
             if e.get("name") == "process_name"}
    assert names == {"hostA": 1, "hostB": 2}
    assert merged["otherData"]["hosts"] == ["hostA", "hostB"]
    assert merged["otherData"]["clock_offsets"]["hostB"] == 0.5
    a_ts = sorted(e["ts"] for e in events
                  if e.get("ph") == "X" and e["pid"] == 1)
    b_ts = sorted(e["ts"] for e in events
                  if e.get("ph") == "X" and e["pid"] == 2)
    assert a_ts == b_ts  # fully cancelled
    # without the offset, hostB's track sits 0.5s (5e5 us) later
    plain = obs_comm.merge_windows({"hostA": wa, "hostB": wb})
    b_plain = sorted(e["ts"] for e in plain["traceEvents"]
                     if e.get("ph") == "X" and e["pid"] == 2)
    assert b_plain[0] - b_ts[0] == pytest.approx(5e5, abs=1.0)
    assert obs_comm.merge_windows({})["otherData"]["hosts"] == []


def test_span_window_payload_filters_and_anchors():
    obs_trace.enable()
    sched = obs_comm.record_schedule(
        "allreduce", "dp",
        [{"bucket": 0, "names": ["a@GRAD"], "bytes": 64}])
    with obs_comm.schedule_span(sched):
        with obs_comm.bucket_span(sched, 0):
            pass
    payload = obs_comm.span_window_payload(host="me", limit=16)
    assert payload["host"] == "me" and payload["events"]
    assert payload["ts"] > 0
    # epoch_wall anchors the trace epoch near (wall now - perf now
    # since epoch): sanity-bound it to the recent past.  ts is rounded
    # to ms, so it can land up to 0.5ms BEFORE epoch_wall when the
    # whole body ran faster than that
    assert -0.001 <= payload["ts"] - payload["epoch_wall"] < 3600
    assert all(e["ph"] in ("X", "i") for e in payload["events"])
    assert any(e["name"] == "comm/bucket" for e in payload["events"])


def test_clock_offset_recovery_over_lease_store():
    master = native.Master()
    addr = "127.0.0.1:%d" % master.port
    responder = None
    try:
        responder = obs_comm.ClockResponder(
            addr, host="skewed", poll_s=0.02, skew_s=0.25).start()
        offsets = obs_comm.estimate_clock_offsets(
            addr, ["skewed"], reps=2, timeout_s=5.0)
        off = offsets["skewed"]
        assert off is not None and abs(off - 0.25) < 0.2, offsets
        # a host with no responder yields None, not a hang
        silent = obs_comm.estimate_clock_offsets(
            addr, ["ghost"], reps=1, timeout_s=0.3)
        assert silent["ghost"] is None
    finally:
        if responder is not None:
            responder.stop()
        master.stop()


def test_span_push_collect_reporter_lease_and_age_gauge():
    obs_trace.enable()
    sched = obs_comm.record_schedule(
        "allreduce", "dp",
        [{"bucket": 0, "names": ["a@GRAD"], "bytes": 64}])
    with obs_comm.schedule_span(sched):
        with obs_comm.bucket_span(sched, 0):
            pass
    master = native.Master()
    addr = "127.0.0.1:%d" % master.port
    reporter = None
    try:
        # bare push: update is unregister + register (immutable lease)
        lease = obs_comm.push_span_window(addr, host="bare", limit=64)
        assert lease is not None
        lease2 = obs_comm.push_span_window(addr, host="bare",
                                           limit=64, lease_prev=lease)
        assert lease2 is not None
        # FleetReporter rides the span window beside its snapshot
        reporter = obs_fleet.FleetReporter(addr, host="ridden",
                                           interval_s=60.0,
                                           span_window=64)
        assert reporter.push_once()
        assert reporter._span_lease is not None
        windows = obs_comm.collect_span_windows(addr)
        assert {"bare", "ridden"} <= set(windows)
        assert windows["bare"]["events"]
        assert windows["ridden"]["epoch_wall"] > 0
        # the aggregator publishes per-host snapshot age ...
        agg = obs_fleet.FleetAggregator()
        assert agg.collect(addr) >= 1
        agg.stragglers()
        age = obs_registry.get_registry().gauge(
            "fleet_snapshot_age_seconds", labelnames=("host",))
        ages = {s["labels"]["host"]: s["value"]
                for s in age.samples()}
        assert "ridden" in ages and ages["ridden"] >= 0
        # ... and retires it (plus the span window) when the host
        # leaves the fleet
        reporter.stop(unregister=True)
        reporter = None
        agg.collect(addr)
        agg.stragglers()
        assert not any(s["labels"]["host"] == "ridden"
                       for s in age.samples())
        assert "ridden" not in obs_comm.collect_span_windows(addr)
    finally:
        if reporter is not None:
            reporter.stop(unregister=True)
        master.stop()


def test_fleet_snapshot_age_from_ingest():
    agg = obs_fleet.FleetAggregator()
    agg.ingest({"host": "old", "ts": time.time() - 7.0,
                "metrics": {}})
    agg.stragglers()
    age = obs_registry.get_registry().gauge(
        "fleet_snapshot_age_seconds", labelnames=("host",))
    ages = {s["labels"]["host"]: s["value"] for s in age.samples()}
    assert ages["old"] >= 6.5


# -- flight host context ---------------------------------------------------

def test_flight_host_context_merge_delete_and_dump(tmp_path):
    obs_flight.set_host_context(host="h3", process_index=3,
                                mesh_axes={"dp": 8})
    obs_flight.set_host_context(plan_fingerprint="fp9")
    ctx = obs_flight.host_context()
    assert ctx["process_index"] == 3 and ctx["plan_fingerprint"] \
        == "fp9"
    # None deletes a key
    obs_flight.set_host_context(plan_fingerprint=None)
    assert "plan_fingerprint" not in obs_flight.host_context()
    recorder = obs_flight.install(out_dir=str(tmp_path), capacity=4)
    try:
        bundle = recorder.dump(reason="test")
    finally:
        obs_flight.uninstall()
    with open(bundle) as f:
        doc = json.load(f)
    assert doc["host_context"]["host"] == "h3"
    assert doc["host_context"]["mesh_axes"] == {"dp": 8}
    # cleared context -> no host_context key at all
    obs_flight.clear_host_context()
    recorder = obs_flight.install(out_dir=str(tmp_path), capacity=4)
    try:
        bundle2 = recorder.dump(reason="test2")
    finally:
        obs_flight.uninstall()
    with open(bundle2) as f:
        assert "host_context" not in json.load(f)
