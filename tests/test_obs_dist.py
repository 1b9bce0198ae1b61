"""Request-scoped tracing + fleet aggregation (obs.context, obs.tail,
obs.fleet, registry exemplars, coordinator heartbeat telemetry) — the
distributed-observability layer (docs/OBSERVABILITY.md "Request
tracing & exemplars" / "Fleet aggregation & stragglers",
docs/SERVING.md request-id/traceparent contract)."""

import http.client
import json
import os
import threading
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.core.scope import Scope
from paddle_tpu.fluid import io as fluid_io
from paddle_tpu.obs import context as obs_context
from paddle_tpu.obs import fleet as obs_fleet
from paddle_tpu.obs import flight as obs_flight
from paddle_tpu.obs import registry as obs_registry
from paddle_tpu.obs import tail as obs_tail
from paddle_tpu.obs import telemetry as obs_tele
from paddle_tpu.resilience import faults as r_faults
from paddle_tpu.serving import (InferenceEngine, EngineConfig,
                                InferenceServer, ServerConfig)
from paddle_tpu.tools.obs_dump import (render_tail,
                                       validate_prometheus_text,
                                       validate_tail_dump)

TRACE_ID = "0af7651916cd43dd8448eb211c80319c"
PARENT_SPAN = "b7ad6b7169203331"
TRACEPARENT = "00-%s-%s-01" % (TRACE_ID, PARENT_SPAN)
# the injected-slow request gets its OWN trace id so exemplar/tail
# assertions can't be satisfied by the fast request
SLOW_TRACE_ID = "deadbeefcafe43dd8448eb211c80319c"
SLOW_TRACEPARENT = "00-%s-%s-01" % (SLOW_TRACE_ID, PARENT_SPAN)


# ---------------------------------------------------------------------------
# obs.context
# ---------------------------------------------------------------------------

def test_traceparent_parse_and_echo():
    ctx = obs_context.new_context(TRACEPARENT)
    assert ctx.trace_id == TRACE_ID
    assert ctx.parent_span_id == PARENT_SPAN
    assert ctx.span_id != PARENT_SPAN and len(ctx.span_id) == 16
    echo = ctx.traceparent()
    version, trace_id, span_id, flags = echo.split("-")
    assert (version, trace_id, span_id, flags) \
        == ("00", TRACE_ID, ctx.span_id, "01")


@pytest.mark.parametrize("header", [
    None, "", "garbage", "00-zz-yy-01",
    "00-" + "0" * 32 + "-" + PARENT_SPAN + "-01",   # all-zero trace
    "00-" + TRACE_ID + "-" + "0" * 16 + "-01",      # all-zero span
    "ff-" + TRACE_ID + "-" + PARENT_SPAN + "-01",   # reserved version
    "00-" + TRACE_ID[:30] + "-" + PARENT_SPAN + "-01",  # short trace
    # right length but not hex: int(x, 16) would accept '_' and '+'
    "00-" + TRACE_ID[:15] + "_" + TRACE_ID[16:] + "-" + PARENT_SPAN
    + "-01",
    "00-" + TRACE_ID + "-+" + PARENT_SPAN[1:] + "-01",
])
def test_malformed_traceparent_mints_fresh(header):
    assert obs_context.from_traceparent(header) is None
    ctx = obs_context.new_context(header)   # never fails the request
    assert len(ctx.trace_id) == 32 and ctx.parent_span_id is None


def test_span_nesting_and_cross_thread_record():
    ctx = obs_context.TraceContext()
    with obs_context.use(ctx):
        assert obs_context.current() is ctx
        with obs_context.span("outer"):
            with obs_context.span("inner"):
                pass
    assert obs_context.current() is None

    # worker thread: no binding, records against the carried ctx
    def worker():
        ctx.record("stage", time.time(), 0.001)

    t = threading.Thread(target=worker)
    t.start()
    t.join()

    roots = ctx.span_tree()
    by_name = {n["name"]: n for n in roots}
    # outer/inner nested; the cross-thread record roots at ctx.span_id
    # (no explicit root span recorded -> both are roots)
    assert "outer" in by_name and "stage" in by_name
    outer = by_name["outer"]
    assert [c["name"] for c in outer["children"]] == ["inner"]
    assert by_name["stage"]["parent_span_id"] == ctx.span_id


def test_context_span_list_is_bounded():
    ctx = obs_context.TraceContext(max_spans=4)
    for i in range(10):
        ctx.record("s%d" % i, time.time(), 0.0)
    assert len(ctx.span_records()) == 4
    assert ctx.dropped_spans == 6


# ---------------------------------------------------------------------------
# registry exemplars
# ---------------------------------------------------------------------------

def test_histogram_exemplar_lands_in_bucket_and_renders():
    reg = obs_registry.MetricsRegistry()
    h = reg.histogram("lat_seconds", buckets=(0.01, 0.1, 1.0))
    h.observe(0.005)                       # no exemplar
    h.observe(0.05, exemplar=TRACE_ID)     # le=0.1 bucket
    h.observe(5.0, exemplar={"trace_id": "beef"})  # +Inf bucket
    ex = h.exemplars()
    assert set(ex) == {"0.1", "+Inf"}
    assert ex["0.1"][0] == {"trace_id": TRACE_ID}
    assert ex["0.1"][1] == 0.05
    # exemplars are opt-in (OpenMetrics negotiation): the default
    # text-format render must stay stock-scraper-parseable
    plain = reg.render_text()
    assert " # " not in plain
    text = reg.render_text(exemplars=True)
    bucket_line = [l for l in text.splitlines()
                   if 'le="0.1"' in l][0]
    assert bucket_line.startswith('lat_seconds_bucket{le="0.1"} 2 # ')
    assert 'trace_id="%s"' % TRACE_ID in bucket_line
    # un-exemplared buckets render exactly as before
    assert 'lat_seconds_bucket{le="0.01"} 1\n' in text + "\n"
    # the validator understands the exemplar suffix
    names = validate_prometheus_text(text)
    assert "lat_seconds_bucket" in names


def test_exemplar_last_write_wins_per_bucket():
    h = obs_registry.Histogram("h", buckets=(1.0,))
    h.observe(0.5, exemplar="first")
    h.observe(0.7, exemplar="second")
    assert h.exemplars()["1"][0] == {"trace_id": "second"}


# ---------------------------------------------------------------------------
# obs.tail
# ---------------------------------------------------------------------------

def test_tail_recorder_classify_capture_and_bound(tmp_path):
    rec = obs_tail.TailRecorder(capacity=2, slow_ms=10.0)
    ctx = obs_context.TraceContext()
    ctx.record("serving/request", time.time(), 0.02,
               span_id=ctx.span_id, parent_span_id=None)
    assert rec.offer(ctx, 5.0, status=200) is None      # fast + ok
    assert rec.offer(ctx, 50.0, status=200) == "slow"
    assert rec.offer(ctx, 5.0, status=504) == "error"   # 5xx
    assert rec.offer(ctx, 50.0, status=500) == "error"  # error outranks
    records = rec.records()
    assert len(records) == 2                            # ring bound
    assert [r["reason"] for r in records] == ["error", "error"]
    fam = obs_registry.get_registry().counter(
        "tail_captured_total", labelnames=("reason",))
    assert fam.labels(reason="slow").value == 1
    assert fam.labels(reason="error").value == 2

    path = str(tmp_path / "tail.json")
    rec.dump(path)
    doc = validate_tail_dump(path)
    assert doc["evicted"] == 1 and doc["total_captured"] == 3
    rendered = render_tail(path)
    assert "serving/request" in rendered
    assert ctx.trace_id in rendered


def test_tail_module_level_offer_noop_without_recorder():
    assert obs_tail.get_recorder() is None
    assert obs_tail.offer(obs_context.TraceContext(), 1e9, 500) is None
    rec = obs_tail.install(capacity=4, slow_ms=None)
    assert obs_tail.offer(obs_context.TraceContext(), 1e9, 200) is None
    assert obs_tail.offer(obs_context.TraceContext(), 1.0, 500) \
        == "error"
    assert len(rec.records()) == 1


# ---------------------------------------------------------------------------
# serving loopback: the full request-tracing contract
# ---------------------------------------------------------------------------

def _tiny_server(tmp_path, **cfg_kw):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[8], dtype="float32")
        probs = fluid.layers.fc(input=img, size=3, act="softmax")
    scope = Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
    program = fluid_io.prune_program(main, [probs])
    engine = InferenceEngine(program, ["img"], [probs], scope=scope,
                             config=EngineConfig(batch_buckets=[2]))
    return InferenceServer(engine, ServerConfig(port=0, **cfg_kw))


def _post(host, port, payload, headers=None):
    conn = http.client.HTTPConnection(host, port, timeout=30)
    try:
        conn.request("POST", "/v1/infer", json.dumps(payload),
                     dict({"Content-Type": "application/json"},
                          **(headers or {})))
        resp = conn.getresponse()
        return (resp.status, json.loads(resp.read()),
                dict(resp.getheaders()))
    finally:
        conn.close()


def _get(host, port, path, headers=None):
    conn = http.client.HTTPConnection(host, port, timeout=30)
    try:
        conn.request("GET", path, headers=headers or {})
        resp = conn.getresponse()
        return (resp.status, resp.read().decode(),
                dict(resp.getheaders()))
    finally:
        conn.close()


def test_server_request_tracing_contract(tmp_path):
    """Acceptance: request_id + traceparent echo on every reply
    (success, 400, 503), the slow request's exemplar in /metrics, its
    span tree in /debug/tail, and the JSONL access log."""
    log_path = str(tmp_path / "access.jsonl")
    server = _tiny_server(tmp_path, tail_slow_ms=50.0,
                          access_log=log_path).start()
    host, port = server.address
    payload = {"inputs": {"img": [[0.5] * 8]}}
    try:
        # 200: request_id minted, caller's trace continued
        st, body, headers = _post(host, port, payload,
                                  {"traceparent": TRACEPARENT})
        assert st == 200 and body["request_id"]
        assert headers["traceparent"].split("-")[1] == TRACE_ID
        assert headers["x-request-id"] == body["request_id"]

        # injected slow path -> exemplar + tail capture
        plan = r_faults.enable(seed=0)
        plan.inject("serving/run", "latency", latency_s=0.12, times=1)
        try:
            st, slow_body, _ = _post(
                host, port, payload,
                {"traceparent": SLOW_TRACEPARENT})
            assert st == 200
        finally:
            r_faults.disable()

        # plain 0.0.4 scrape: parseable by stock Prometheus, NO
        # exemplar syntax; OpenMetrics-negotiated scrape carries the
        # exemplar with the slow request's trace id + # EOF
        _, plain, plain_headers = _get(host, port, "/metrics")
        validate_prometheus_text(plain)
        assert " # " not in plain
        assert plain_headers["Content-Type"].startswith("text/plain")
        _, text, om_headers = _get(
            host, port, "/metrics",
            {"Accept": "application/openmetrics-text"})
        validate_prometheus_text(text)
        assert om_headers["Content-Type"].startswith(
            "application/openmetrics-text")
        assert text.endswith("# EOF\n")
        assert any("serving_total_seconds_bucket" in line
                   and " # " in line and SLOW_TRACE_ID in line
                   for line in text.splitlines()), text
        # OM counter families drop the _total suffix in TYPE lines
        assert "# TYPE serving_requests counter" in text
        assert "serving_requests_total " in text
        assert "# TYPE serving_requests_total counter" in plain

        st, tail_text, _ = _get(host, port, "/debug/tail")
        doc = validate_tail_dump(json.loads(tail_text))
        assert st == 200 and len(doc["requests"]) == 1
        captured = doc["requests"][0]
        assert captured["reason"] == "slow"
        assert captured["trace_id"] == SLOW_TRACE_ID
        assert captured["request_id"] == slow_body["request_id"]
        names = set()

        def walk(nodes):
            for n in nodes:
                names.add(n["name"])
                walk(n["children"])

        walk(captured["spans"])
        assert {"serving/request", "serving/admission",
                "serving/queue_wait", "serving/batch_assemble",
                "serving/pad_bucket", "serving/device_execute",
                "serving/split_serialize"} <= names, names
        # the tree is rooted at the single request span
        roots = captured["spans"]
        assert len(roots) == 1 and roots[0]["name"] \
            == "serving/request"

        # 400: bad input still answers with a request_id
        st, body400, _ = _post(host, port, {"inputs": {}})
        assert st == 400 and body400["request_id"]

        # 503 draining: rejection body carries a request_id too, but
        # the drain shed must NOT churn the tail ring (it would evict
        # the pre-drain captures an operator wants)
        server.draining = True
        st, body503, _ = _post(host, port, payload)
        server.draining = False
        assert st == 503 and body503["request_id"]
        assert len(server.tail.records()) == 1
    finally:
        server.shutdown()

    lines = [json.loads(l) for l in open(log_path)]
    assert len(lines) == 4
    assert [l["status"] for l in lines] == [200, 200, 400, 503]
    ok = lines[0]
    assert ok["request_id"] and ok["trace_id"] == TRACE_ID
    assert ok["batch"] == 1 and ok["bucket"] == 2
    assert all(isinstance(l["latency_ms"], float) for l in lines)


def test_server_shed_429_not_tail_captured(tmp_path):
    """Sustained overload sheds 429s continuously; capturing their
    empty span trees would churn the bounded ring and evict the
    captures that matter (same contract as drain 503s)."""
    from paddle_tpu.serving.batcher import QueueFullError

    server = _tiny_server(tmp_path, tail_slow_ms=50.0).start()
    try:
        def full(*a, **kw):
            raise QueueFullError("admission queue full (64 waiting)")

        server.batcher.submit_and_wait = full
        status, body = server.handle_infer(
            {"inputs": {"img": [[0.5] * 8]}})
        assert status == 429 and body["request_id"]
        assert server.tail.records() == []
    finally:
        server.shutdown()


def test_start_fleet_reporter_rejects_conflicting_args():
    from paddle_tpu.distributed import coordinator as coord

    rep = obs_fleet.FleetReporter("127.0.0.1:1", host="a",
                                  interval_s=60.0)
    assert coord._fleet_reporter[0] is None
    coord._fleet_reporter[0] = rep
    try:
        # argless call (init_multihost's path) returns the running one
        assert coord.start_fleet_reporter() is rep
        assert coord.start_fleet_reporter(master="127.0.0.1:1",
                                          host="a") is rep
        with pytest.raises(RuntimeError):
            coord.start_fleet_reporter(master="other:2")
        with pytest.raises(RuntimeError):
            coord.start_fleet_reporter(host="b")
    finally:
        coord._fleet_reporter[0] = None


def test_server_no_access_log_by_default(tmp_path):
    server = _tiny_server(tmp_path).start()
    try:
        assert server._access_log is None
        # in-process callers (no HTTP) get the same contract
        status, body = server.handle_infer(
            {"inputs": {"img": [[0.5] * 8]}})
        assert status == 200 and body["request_id"]
    finally:
        server.shutdown()


# ---------------------------------------------------------------------------
# flight bundles name the active request
# ---------------------------------------------------------------------------

def test_flight_bundle_embeds_trace_context(tmp_path):
    rec = obs_flight.FlightRecorder(out_dir=str(tmp_path))
    ctx = obs_context.TraceContext()
    with obs_context.use(ctx):
        path = rec.dump(reason="test", exc=ValueError("boom"))
    doc = json.load(open(path))
    assert doc["trace_context"] == {"trace_id": ctx.trace_id,
                                    "span_id": ctx.span_id,
                                    "request_id": ctx.request_id}
    from paddle_tpu.tools.obs_dump import render_flight

    rendered = render_flight(path)
    assert ctx.request_id in rendered and ctx.trace_id in rendered

    # no context bound -> no trace_context key (pre-existing contract)
    path2 = rec.dump(reason="test2")
    assert "trace_context" not in json.load(open(path2))


# ---------------------------------------------------------------------------
# fleet aggregation
# ---------------------------------------------------------------------------

def _snap(host, step_s, n_steps, ts=1.0):
    return {"host": host, "ts": ts, "metrics": {
        "trainer_step_seconds{trainer=v2}_sum": step_s * n_steps,
        "trainer_step_seconds{trainer=v2}_count": n_steps,
        "executor_runs_total": n_steps}}


def test_fleet_aggregator_merge_and_straggler_gauges():
    agg = obs_fleet.FleetAggregator()
    agg.ingest(_snap("host0", 0.010, 10))
    agg.ingest(_snap("host1", 0.011, 10))
    agg.ingest(_snap("host2", 0.100, 10))   # the straggler
    report = agg.stragglers()
    assert report["flagged"] == ["host2"]
    assert report["step_ms"]["host2"] == pytest.approx(100.0)
    assert report["median_ms"] == pytest.approx(11.0)

    merged = agg.merged_samples()
    assert merged["executor_runs_total{host=host0}"] == 10
    assert "trainer_step_seconds{host=host2,trainer=v2}_sum" in merged

    reg = obs_registry.get_registry()
    straggler = reg.gauge("fleet_straggler", labelnames=("host",))
    assert straggler.labels(host="host2").value == 1
    assert straggler.labels(host="host0").value == 0
    assert reg.gauge("fleet_hosts").value == 3
    host_ms = reg.gauge("fleet_host_step_ms", labelnames=("host",))
    assert host_ms.labels(host="host2").value == pytest.approx(100.0)

    text = agg.render_text()
    assert "executor_runs_total{host=host1} 10" in text


def test_fleet_aggregator_newest_snapshot_wins_and_bad_ingest():
    agg = obs_fleet.FleetAggregator()
    agg.ingest(_snap("h", 0.2, 10, ts=2.0))
    agg.ingest(_snap("h", 0.1, 10, ts=1.0))   # older: ignored
    assert agg.step_times()["h"] == pytest.approx(200.0)
    with pytest.raises(ValueError):
        agg.ingest({"metrics": {}})            # no host
    # a host with no step data merges but never flags
    agg.ingest({"host": "idle", "ts": 3.0,
                "metrics": {"executor_runs_total": 1}})
    assert "idle" not in agg.step_times()
    assert agg.stragglers()["flagged"] == []   # single-step-host fleet


def test_fleet_reporter_push_collect_roundtrip():
    """Two workers push through a REAL master lease store; the
    aggregator pulls both, flags the inflated host, and a stopped
    reporter's snapshot disappears with its lease."""
    native = pytest.importorskip("paddle_tpu.native")
    master = native.Master()
    addr = "127.0.0.1:%d" % master.port
    try:
        # this process IS host "fast": run real (tiny) steps
        for _ in range(3):
            with obs_tele.step("fleet_test", examples=1):
                pass
        rep = obs_fleet.FleetReporter(addr, host="fast",
                                      interval_s=60.0)
        assert rep.push_once()
        # second push re-registers (update path)
        assert rep.push_once()

        # a corrupt push (valid JSON, not a dict) must be skipped,
        # not abort the collection
        bad_client = native.MasterClient("127.0.0.1", master.port)
        assert bad_client.register("/obs/bad", "42", 60000) is not None
        bad_client.close()

        agg = obs_fleet.FleetAggregator()
        agg.ingest(_snap("slow", 0.5, 4, ts=time.time()))
        assert agg.collect(addr) == 1
        assert set(agg.hosts()) == {"fast", "slow"}
        report = agg.stragglers()
        assert report["flagged"] == ["slow"], report

        rep.stop(unregister=True)
        agg2 = obs_fleet.FleetAggregator()
        assert agg2.collect(addr) == 0

        # dead-host expiry: the lease is gone, so a re-collect DROPS
        # the store-sourced host from the merged view (the directly
        # ingested one stays) and the re-publish retires its gauges
        assert agg.collect(addr) == 0
        assert agg.hosts() == ["slow"]
        agg.stragglers()
        host_ms = obs_registry.get_registry().gauge(
            "fleet_host_step_ms", labelnames=("host",))
        assert not any(s.get("labels", {}).get("host") == "fast"
                       for s in host_ms.samples())
        assert any(s.get("labels", {}).get("host") == "slow"
                   for s in host_ms.samples())
    finally:
        master.stop()


# ---------------------------------------------------------------------------
# coordinator heartbeat telemetry under injected faults
# ---------------------------------------------------------------------------

def test_service_lease_heartbeat_histogram_and_fault_survival():
    """Satellite acceptance: injected latency + io_error faults on the
    heartbeat path land in the new coordinator_heartbeat_seconds
    histogram / failure counter, and the lease SURVIVES the budgeted
    retry (the io_error is retried on a fresh connection within one
    beat, well inside the TTL)."""
    native = pytest.importorskip("paddle_tpu.native")
    from paddle_tpu.distributed import ElasticRegistry
    from paddle_tpu.distributed import coordinator as coordinator_mod

    ttl_ms = 600
    master = native.Master()
    lease = reg = None
    try:
        plan = r_faults.enable(seed=0)
        # beat 1 pays an injected 30ms stall; beat 2 an io_error
        lat = plan.inject("coordinator/heartbeat", "latency",
                          latency_s=0.03, times=1)
        ioe = plan.inject("coordinator/heartbeat", "io_error",
                          after=1, times=1)
        reg = ElasticRegistry("127.0.0.1", master.port)
        slot, lease = reg.register_pserver("h:1", 1, ttl_ms=ttl_ms)
        assert slot == 0
        # outlive several TTLs: both faults must have fired and been
        # absorbed without the lease lapsing
        deadline = time.time() + 10
        while (lat.fired < 1 or ioe.fired < 1) \
                and time.time() < deadline:
            time.sleep(0.02)
        time.sleep(ttl_ms / 1000.0 * 1.5)
        assert lat.fired == 1 and ioe.fired == 1
        assert not lease.lapsed
        assert reg.pservers() == {0: "h:1"}

        hist = obs_registry.get_registry().histogram(
            "coordinator_heartbeat_seconds",
            coordinator_mod.HEARTBEAT_SECONDS_BUCKETS)
        assert hist.count >= 3          # several beats landed
        assert hist.max >= 0.03         # the injected stall is visible
        failures = obs_registry.get_registry().counter(
            "coordinator_heartbeat_failures_total")
        assert failures.value == 1      # exactly the injected io_error
    finally:
        # the heartbeat thread MUST be joined before the master stops:
        # a keep-alive racing a dead master is undefined in the native
        # transport (same discipline as test_elastic_coordination)
        if lease is not None:
            lease.release()
        if reg is not None:
            reg.close()
        r_faults.disable()
        master.stop()

