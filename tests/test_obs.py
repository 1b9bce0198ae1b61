"""paddle_tpu.obs: span tracer (nesting, threads, Chrome JSON schema),
labeled metrics registry, telemetry, executor/profiler back-compat,
and the unified serving /metrics surface.

Tier-1 (CPU): the observability layer must never change results — it
only watches — so these tests assert on the emitted events/metrics and
on the old profiler API staying intact underneath."""

import json
import threading

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.obs import registry as obs_registry
from paddle_tpu.obs import telemetry as obs_tele
from paddle_tpu.obs import trace as obs_trace
from paddle_tpu.tools.obs_dump import (validate_chrome_trace,
                                       validate_prometheus_text)


@pytest.fixture(autouse=True)
def _tracer_off_after():
    yield
    obs_trace.disable()
    obs_trace.reset()


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

def test_span_nesting_containment():
    with obs_trace.tracing():
        with obs_trace.span("outer", kind="test"):
            with obs_trace.span("inner"):
                pass
            with obs_trace.span("inner2"):
                pass
    events = [e for e in obs_trace.events() if e["ph"] == "X"]
    by_name = {e["name"]: e for e in events}
    assert set(by_name) == {"outer", "inner", "inner2"}
    outer, inner = by_name["outer"], by_name["inner"]
    assert outer["tid"] == inner["tid"]
    # children close before the parent, so containment holds
    for child in (inner, by_name["inner2"]):
        assert outer["ts"] <= child["ts"] + 1e-3
        assert child["ts"] + child["dur"] <= \
            outer["ts"] + outer["dur"] + 1e-3
    assert outer["args"] == {"kind": "test"}


def test_span_disabled_is_noop():
    assert not obs_trace.is_enabled()
    with obs_trace.span("ghost"):
        pass
    obs_trace.instant("ghost_i")
    assert obs_trace.events() == []


def test_span_set_args_and_instant():
    with obs_trace.tracing():
        with obs_trace.span("s") as sp:
            sp.set(batch=4, compiled=True)
        obs_trace.instant("moment", label="x")
    evs = obs_trace.events()
    sp = next(e for e in evs if e["name"] == "s")
    assert sp["args"] == {"batch": 4, "compiled": True}
    inst = next(e for e in evs if e["name"] == "moment")
    assert inst["ph"] == "i" and inst["args"] == {"label": "x"}


def test_tracer_thread_safety_and_tracks():
    n_threads, n_spans = 8, 50

    def worker(i):
        for j in range(n_spans):
            with obs_trace.span("w%d" % i, j=j):
                pass

    with obs_trace.tracing():
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    evs = obs_trace.events()
    spans = [e for e in evs if e["ph"] == "X"]
    assert len(spans) == n_threads * n_spans
    assert obs_trace.dropped_events() == 0
    # per-thread tracks: each worker's spans share one tid (the OS may
    # reuse idents of exited threads, so distinct-count can be < N);
    # every track announced itself with a thread_name meta row
    tids = {e["name"]: set() for e in spans}
    for e in spans:
        tids[e["name"]].add(e["tid"])
    assert all(len(s) == 1 for s in tids.values())
    all_tids = set().union(*tids.values())
    metas = [e for e in evs if e["ph"] == "M"
             and e["name"] == "thread_name"]
    assert {m["tid"] for m in metas} == all_tids


def test_tracer_buffer_bound_counts_drops():
    with obs_trace.tracing(max_events=10):
        for i in range(50):
            with obs_trace.span("s%d" % i):
                pass
        assert obs_trace.dropped_events() > 0
        doc = obs_trace.to_chrome_trace()
    assert doc["otherData"]["dropped_events"] > 0
    # the start-up timeline rides along, under a bound of its own
    assert len([e for e in doc["traceEvents"] if e["ph"] == "X"
                and e["cat"] != obs_trace.STARTUP]) <= 10
    # the guard-scoped bound must NOT leak: a later enable() (no
    # explicit bound) gets the previous cap back, not the tiny one —
    # otherwise every trace in the process silently drops events
    # after the tenth
    obs_trace.enable()
    try:
        for i in range(50):
            with obs_trace.span("t%d" % i):
                pass
        assert obs_trace.dropped_events() == 0
        assert len([e for e in obs_trace.events()
                    if e["ph"] == "X"]) == 50
    finally:
        obs_trace.disable()
        obs_trace.reset()


def test_chrome_trace_schema_and_file_round_trip(tmp_path):
    with obs_trace.tracing():
        with obs_trace.span("a"):
            with obs_trace.span("b"):
                pass
    path = str(tmp_path / "trace.json")
    doc = obs_trace.export_chrome_trace(path)
    validate_chrome_trace(doc)
    with open(path) as f:
        reloaded = json.load(f)
    events = validate_chrome_trace(reloaded)
    assert {"a", "b"} <= {e["name"] for e in events}


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_labeled_counter_render_and_identity():
    reg = obs_registry.MetricsRegistry()
    fam = reg.counter("widgets_total", "widgets", labelnames=("kind",))
    fam.labels(kind="a").inc(2)
    fam.labels(kind="b").inc()
    assert fam.labels(kind="a") is fam.labels(kind="a")
    assert reg.counter("widgets_total", labelnames=("kind",)) is fam
    text = reg.render_text()
    assert '# TYPE widgets_total counter' in text
    assert 'widgets_total{kind="a"} 2' in text
    assert 'widgets_total{kind="b"} 1' in text
    # a family is not directly incrementable; labels must match
    with pytest.raises(ValueError):
        fam.inc()
    with pytest.raises(ValueError):
        fam.labels(wrong="x")
    # name re-registration with different type/labels is an error
    with pytest.raises(ValueError):
        reg.gauge("widgets_total")


def test_registry_labeled_histogram_render():
    reg = obs_registry.MetricsRegistry()
    h = reg.histogram("lat_seconds", buckets=(0.1, 1.0),
                      labelnames=("stage",))
    h.labels(stage="pad").observe(0.05)
    h.labels(stage="pad").observe(0.5)
    text = reg.render_text()
    assert 'lat_seconds_bucket{stage="pad",le="0.1"} 1' in text
    assert 'lat_seconds_bucket{stage="pad",le="+Inf"} 2' in text
    assert 'lat_seconds_count{stage="pad"} 2' in text
    names = validate_prometheus_text(text)
    assert "lat_seconds_bucket" in names


def test_registry_groups_and_jsonl():
    root = obs_registry.MetricsRegistry()
    sub = obs_registry.MetricsRegistry()
    sub.counter("sub_total").inc(3)
    root.gauge("root_gauge").set(1.5)
    root.attach("grp", sub)
    text = root.render_text()
    assert "root_gauge 1.5" in text and "sub_total 3" in text
    samples = {s["name"]: s for s in root.to_dict()["metrics"]}
    assert samples["sub_total"]["group"] == "grp"
    for line in root.render_jsonl().strip().splitlines():
        json.loads(line)
    # replacing a mount drops the old sub-registry from the render
    root.attach("grp", obs_registry.MetricsRegistry())
    assert "sub_total" not in root.render_text()


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------

def test_telemetry_step_and_gauges():
    reg = obs_registry.get_registry()
    steps_before = reg.counter(
        "trainer_steps_total", labelnames=("trainer",)) \
        .labels(trainer="t_obs").value
    with obs_tele.step("t_obs", examples=32):
        pass
    fam = reg.counter("trainer_steps_total", labelnames=("trainer",))
    assert fam.labels(trainer="t_obs").value == steps_before + 1
    assert reg.counter("trainer_examples_total",
                       labelnames=("trainer",)) \
        .labels(trainer="t_obs").value >= 32
    assert reg.gauge("trainer_examples_per_sec",
                     labelnames=("trainer",)) \
        .labels(trainer="t_obs").value > 0
    obs_tele.set_gauge("trainer_grad_norm", 1.25, trainer="t_obs")
    assert reg.gauge("trainer_grad_norm", labelnames=("trainer",)) \
        .labels(trainer="t_obs").value == 1.25
    obs_tele.set_gauge("loss_scale", 2.0)
    assert reg.gauge("loss_scale").value == 2.0
    flat = obs_tele.snapshot()
    assert flat["trainer_steps_total{trainer=t_obs}"] >= 1


def test_telemetry_snapshot_delta_counters_vs_gauges():
    reg = obs_registry.get_registry()
    reg.counter("delta_total").inc(2)
    reg.gauge("delta_gauge").set(5)
    reg.histogram("delta_seconds").observe(0.2)
    before = obs_tele.snapshot()
    assert obs_tele.snapshot_delta(before) == {}   # nothing moved
    reg.counter("delta_total").inc(3)
    reg.gauge("delta_gauge").set(7)
    reg.histogram("delta_seconds").observe(0.3)
    reg.counter("delta_untouched_total").inc(0)    # new but at 0
    d = obs_tele.snapshot_delta(before)
    assert d["delta_total"] == 3                   # increment, not 5
    assert d["delta_gauge"] == 7                   # current value
    assert d["delta_seconds_count"] == 1
    assert abs(d["delta_seconds_sum"] - 0.3) < 1e-6
    assert "delta_untouched_total" not in d


def test_telemetry_snapshot_delta_gauge_disappears():
    """A gauge present in `before` but gone from the registry (reset,
    or a family child that no longer renders) must simply drop out of
    the delta — never KeyError, never report a phantom value."""
    reg = obs_registry.get_registry()
    reg.gauge("vanishing_gauge").set(3)
    reg.counter("surviving_total").inc(1)
    before = obs_tele.snapshot()
    assert before["vanishing_gauge"] == 3
    # a fresh registry: the gauge (and everything else) is gone
    obs_registry.reset_registry()
    reg2 = obs_registry.get_registry()
    reg2.counter("surviving_total").inc(5)
    d = obs_tele.snapshot_delta(before)
    assert "vanishing_gauge" not in d
    # the surviving counter diffs against the OLD snapshot's 1
    assert d["surviving_total"] == 4
    # and the degenerate case: delta against a gauge-only snapshot
    # over an empty registry is just empty
    obs_registry.reset_registry()
    assert obs_tele.snapshot_delta({"vanishing_gauge": 3}) == {}


def test_registry_concurrent_writers_exact_totals():
    """Counter/histogram increments from many threads (racing the
    labeled-family get-or-create path too) must land exactly; a
    concurrent render/snapshot must neither crash nor corrupt."""
    reg = obs_registry.get_registry()
    n_threads, n_iter = 8, 400
    errors = []

    def writer(tid):
        try:
            for i in range(n_iter):
                reg.counter("conc_total").inc()
                reg.counter("conc_labeled_total",
                            labelnames=("worker",)) \
                   .labels(worker="w%d" % (tid % 4)).inc()
                reg.histogram(
                    "conc_seconds",
                    buckets=(0.001, 0.01, 0.1)).observe(0.01 * (i % 3))
                reg.gauge("conc_gauge").set(i)
        except Exception as exc:  # noqa: BLE001 — surface in main
            errors.append(exc)

    def reader():
        try:
            for _ in range(50):
                text = reg.render_text()
                validate_prometheus_text(text)
                obs_tele.snapshot()
        except Exception as exc:  # noqa: BLE001
            errors.append(exc)

    threads = [threading.Thread(target=writer, args=(t,))
               for t in range(n_threads)] \
        + [threading.Thread(target=reader)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    assert reg.counter("conc_total").value == n_threads * n_iter
    fam = reg.counter("conc_labeled_total", labelnames=("worker",))
    assert sum(s["value"] for s in fam.samples()) == n_threads * n_iter
    hist = reg.histogram("conc_seconds", buckets=(0.001, 0.01, 0.1))
    assert hist.count == n_threads * n_iter
    # the final render is stable and parseable after the storm
    names = validate_prometheus_text(reg.render_text())
    assert "conc_total" in names and "conc_labeled_total" in names


def test_registry_histogram_count_below_interpolates():
    h = obs_registry.Histogram("lat", buckets=(0.01, 0.1, 1.0))
    assert h.fraction_below(0.05) == 1.0  # empty: nothing violates
    for v in (0.005, 0.05, 0.5, 5.0):     # one per bucket incl +Inf
        h.observe(v)
    assert h.count_below(0.01) == 1
    # halfway through the (0.01, 0.1] bucket: 1 full + 0.5 interp
    assert abs(h.count_below(0.055) - 1.5) < 1e-9
    assert h.count_below(1.0) == 3
    # beyond the largest finite bound: the +Inf bucket counts
    assert h.count_below(10.0) == 4
    assert abs(h.fraction_below(0.1) - 0.5) < 1e-9


def _tiny_program():
    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    h = fluid.layers.fc(input=x, size=3, act="relu")
    out = fluid.layers.mean(x=h)
    return x, out


def test_executor_telemetry_counts_runs_transfers_and_retraces():
    _, out = _tiny_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    runs0 = obs_registry.get_registry().counter(
        "executor_runs_total").value
    h2d0 = obs_tele.transfer_bytes("h2d")
    traces0 = obs_tele.jit_trace_count()
    exe.run(fluid.default_main_program(),
            feed={"x": np.ones((2, 4), np.float32)}, fetch_list=[out])
    assert obs_registry.get_registry().counter(
        "executor_runs_total").value > runs0
    assert obs_tele.transfer_bytes("h2d") - h2d0 >= 2 * 4 * 4
    traces_after_first = obs_tele.jit_trace_count()
    assert traces_after_first > traces0  # first call compiled
    # same shape again: no retrace counted
    exe.run(fluid.default_main_program(),
            feed={"x": np.ones((2, 4), np.float32)}, fetch_list=[out])
    assert obs_tele.jit_trace_count() == traces_after_first
    # new batch size: the jit specializes -> retrace detected even
    # though neither profiler nor tracing is enabled
    exe.run(fluid.default_main_program(),
            feed={"x": np.ones((5, 4), np.float32)}, fetch_list=[out])
    assert obs_tele.jit_trace_count() > traces_after_first


# ---------------------------------------------------------------------------
# executor/profiler integration + back-compat
# ---------------------------------------------------------------------------

def test_executor_spans_and_profiler_records_together():
    _, out = _tiny_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    feed = {"x": np.ones((2, 4), np.float32)}
    with obs_trace.tracing():
        with fluid.profiler.profiler():
            exe.run(fluid.default_main_program(), feed=feed,
                    fetch_list=[out])
            exe.run(fluid.default_main_program(), feed=feed,
                    fetch_list=[out], eager=True)
    # old API: the per-op/per-segment table still populates
    records = fluid.profiler.get_profile_records()
    assert any("jit_segment" in k for k in records)
    assert any("mul" in k or "matmul" in k for k in records)
    # new layer: the same activity produced trace spans
    events = validate_chrome_trace(obs_trace.to_chrome_trace())
    names = {e["name"] for e in events if e["ph"] == "X"}
    assert any(n.startswith("executor/run") for n in names)
    assert "executor/segment" in names
    assert any("mean" in n for n in names)  # eager op span
    # run spans contain their segment spans on the same thread; the
    # segment's label (the profiler table's row) is an argument
    runs = [e for e in events if e["ph"] == "X"
            and e["name"] == "executor/run"]
    segs = [e for e in events if e["ph"] == "X"
            and e["name"] == "executor/segment"]
    assert {s["args"]["segment"] for s in segs if s["args"]["jit"]} \
        <= set(records) | {k.split("/first")[0] for k in records}
    assert any(r["ts"] <= s["ts"] + 1e-3
               and s["ts"] + s["dur"] <= r["ts"] + r["dur"] + 1e-3
               and r["tid"] == s["tid"]
               for r in runs for s in segs)


def test_tracing_without_profiler_leaves_table_empty():
    _, out = _tiny_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    fluid.profiler.reset_profiler()
    with obs_trace.tracing():
        exe.run(fluid.default_main_program(),
                feed={"x": np.ones((2, 4), np.float32)},
                fetch_list=[out], eager=True)
    # spans recorded, but the profiler table stays untouched
    assert any(e["ph"] == "X" for e in obs_trace.events())
    assert fluid.profiler.get_profile_records() == {}


def test_profile_records_min_clamped_for_zero_call_entries():
    fluid.profiler.reset_profiler()
    # a defaultdict read (e.g. an aborted record_event path) creates a
    # zero-call entry; the exported table must not leak inf
    fluid.profiler._records["phantom"]  # noqa: B018 — touch creates it
    fluid.profiler.record("real", 0.5)
    records = fluid.profiler.get_profile_records()
    assert records["phantom"]["calls"] == 0
    assert records["phantom"]["min"] == 0.0
    assert records["real"]["min"] == 0.5
    fluid.profiler.reset_profiler()


def test_profiler_record_delegates_to_registry():
    before = obs_tele.snapshot().get(
        "profiler_event_calls_total{event=obs_delegate}", 0)
    fluid.profiler.record("obs_delegate", 0.01)
    flat = obs_tele.snapshot()
    assert flat["profiler_event_calls_total{event=obs_delegate}"] \
        == before + 1
    assert flat["profiler_event_seconds_total{event=obs_delegate}"] > 0


# ---------------------------------------------------------------------------
# serving shim: unified /metrics
# ---------------------------------------------------------------------------

def test_serving_metrics_render_is_unified():
    from paddle_tpu.serving.metrics import ServingMetrics

    metrics = ServingMetrics()
    metrics.requests_total.inc(2)
    obs_registry.get_registry().counter("executor_runs_total").inc(0)
    text = metrics.render_text()
    # old serving names preserved...
    assert "serving_requests_total 2" in text
    assert "serving_queue_seconds_count 0" in text
    # ...next to executor-side metrics from the shared registry
    assert "executor_runs_total" in text
    validate_prometheus_text(text)
    # the shim still mirrors stage latencies into the profiler table
    metrics.observe_stage("queue", 0.004)
    assert "serving/queue" in fluid.profiler.get_profile_records()


def test_obs_dump_cli_dump_modes(tmp_path):
    from paddle_tpu.tools import obs_dump

    # the registry is reset between tests (conftest fresh_obs); the
    # dump needs at least one sample of its own
    obs_registry.get_registry().counter("cli_dump_total").inc()
    with obs_trace.tracing():
        with obs_trace.span("cli_span"):
            pass
        trace_path = str(tmp_path / "t.json")
        metrics_path = str(tmp_path / "m.prom")
        rc = obs_dump.main(["--trace-out", trace_path,
                            "--metrics-out", metrics_path])
    assert rc == 0
    events = validate_chrome_trace(trace_path)
    assert any(e["name"] == "cli_span" for e in events)
    with open(metrics_path) as f:
        validate_prometheus_text(f.read())
    assert obs_dump.main(["--check", trace_path]) == 0
    jsonl_path = str(tmp_path / "m.jsonl")
    assert obs_dump.main(["--metrics-out", jsonl_path,
                          "--format", "jsonl"]) == 0
    with open(jsonl_path) as f:
        for line in f.read().strip().splitlines():
            json.loads(line)
