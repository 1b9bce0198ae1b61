"""paddle_tpu.serving: bucketed compile cache, dynamic micro-batching,
backpressure, metrics, and the end-to-end HTTP server.

Tier-1 (CPU): bucket padding must be invisible to results, split/merge
must round-trip (incl. ragged LoD inputs), deadlines and queue bounds
must reject rather than hang, and two same-bucket requests must share
one compiled executable (measured via jit specialization counts, not
assumed)."""

import http.client
import json
import threading
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.core.ragged import RaggedTensor
from paddle_tpu.core.scope import Scope
from paddle_tpu.fluid import io as fluid_io
from paddle_tpu.serving import (
    InferenceEngine, EngineConfig, MicroBatcher, BatcherConfig,
    InferenceServer, ServerConfig, QueueFullError,
    DeadlineExceededError, ShuttingDownError)
from paddle_tpu.serving.metrics import ServingMetrics


# ---------------------------------------------------------------------------
# model builders
# ---------------------------------------------------------------------------

def _digits_model(tmp_path):
    """A recognize-digits-style MLP exported for inference (startup
    init only: serving correctness is about transport, not accuracy)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[64], dtype="float32")
        hidden = fluid.layers.fc(input=img, size=32, act="tanh")
        probs = fluid.layers.fc(input=hidden, size=10, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(Scope()):
        exe.run(startup)
        fluid_io.save_inference_model(
            str(tmp_path), ["img"], [probs], exe, main_program=main,
            bucket_hints={"batch_buckets": [2, 4, 8]})
    return str(tmp_path)


def _ragged_model():
    """A sequence model (lod_level-1 feed, sequence_pool) built in the
    default program; returns (program, feed_names, fetch_vars)."""
    x = fluid.layers.data(name="x", shape=[4], dtype="float32",
                          lod_level=1)
    pooled = fluid.layers.sequence_pool(input=x, pool_type="sum")
    logits = fluid.layers.fc(input=pooled, size=3)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    program = fluid_io.prune_program(fluid.default_main_program(),
                                     [logits])
    return program, ["x"], [logits]


def _rand_images(batch, seed=0):
    return np.random.RandomState(seed).rand(batch, 64).astype(
        np.float32)


# ---------------------------------------------------------------------------
# engine: bucket padding + compile cache
# ---------------------------------------------------------------------------

def test_bucket_padding_matches_direct_run(tmp_path):
    model_dir = _digits_model(tmp_path)
    engine = InferenceEngine.from_saved_model(model_dir)
    assert engine.config.batch_buckets == (2, 4, 8)  # export hints

    # direct executor run on the exact (unpadded) shape
    exe = fluid.Executor(fluid.CPUPlace())
    imgs = _rand_images(3)
    with fluid.scope_guard(engine.scope):
        want, = exe.run(engine.program, feed={"img": imgs},
                        fetch_list=engine.fetch_names,
                        scope=engine.scope)

    got, = engine.run({"img": imgs})
    assert got.shape == (3, 10)  # sliced back from the 4-bucket
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_bucket_for_rounding():
    cfg = EngineConfig(batch_buckets=[2, 4, 8])
    assert [cfg.bucket_for(b) for b in (1, 2, 3, 4, 7, 8)] == \
        [2, 2, 4, 4, 8, 8]
    assert cfg.bucket_for(9) == 16  # beyond top: multiples of 8
    assert cfg.bucket_for(17) == 24
    none_cfg = EngineConfig(batch_buckets=None)
    assert none_cfg.bucket_for(5) == 5


def test_no_recompile_across_same_bucket_requests(tmp_path):
    engine = InferenceEngine.from_saved_model(_digits_model(tmp_path))
    engine.warmup()
    traces_after_warmup = engine.trace_count()
    assert traces_after_warmup > 0

    # two requests with DIFFERENT true batches landing in one bucket
    timings = {}
    engine.run({"img": _rand_images(3, seed=1)}, timings=timings)
    assert timings["compiled"] is False
    engine.run({"img": _rand_images(4, seed=2)}, timings=timings)
    assert timings["compiled"] is False
    assert engine.trace_count() == traces_after_warmup


def test_cache_hit_miss_counters(tmp_path):
    metrics = ServingMetrics()
    engine = InferenceEngine.from_saved_model(_digits_model(tmp_path),
                                              metrics=metrics)
    engine.run({"img": _rand_images(2)})          # cold: compile
    assert metrics.cache_miss_total.value == 1
    engine.run({"img": _rand_images(1, seed=3)})  # same 2-bucket: hit
    assert metrics.cache_hit_total.value == 1
    assert metrics.cache_miss_total.value == 1


# ---------------------------------------------------------------------------
# batcher: split/merge, deadlines, backpressure
# ---------------------------------------------------------------------------

def test_microbatch_split_merge_round_trip(tmp_path):
    engine = InferenceEngine.from_saved_model(_digits_model(tmp_path))
    engine.warmup()
    batcher = MicroBatcher(
        engine, BatcherConfig(max_batch=8, max_wait_ms=100)).start()
    try:
        inputs = [_rand_images(b, seed=10 + b) for b in (1, 2, 3)]
        singles = [engine.run({"img": x})[0] for x in inputs]

        barrier = threading.Barrier(3)
        futures = [None] * 3

        def submit(i):
            barrier.wait()
            futures[i] = batcher.submit({"img": inputs[i]})

        threads = [threading.Thread(target=submit, args=(i,))
                   for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i, fut in enumerate(futures):
            got, = fut.result(timeout=30)
            assert got.shape == inputs[i].shape[:1] + (10,)
            np.testing.assert_allclose(got, singles[i], rtol=1e-5,
                                       atol=1e-6)
    finally:
        batcher.close()


def test_microbatch_ragged_round_trip():
    program, feed_names, fetch_vars = _ragged_model()
    engine = InferenceEngine(program, feed_names, fetch_vars,
                             config=EngineConfig(batch_buckets=[4],
                                                 token_bucket=16))
    seqs_a = [np.arange(8, dtype=np.float32).reshape(2, 4),
              np.ones((3, 4), np.float32)]
    seqs_b = [np.full((1, 4), 2.0, np.float32)]
    single_a, = engine.run({"x": seqs_a})
    single_b, = engine.run({"x": seqs_b})
    assert np.asarray(single_a).shape == (2, 3)
    assert np.asarray(single_b).shape == (1, 3)

    batcher = MicroBatcher(
        engine, BatcherConfig(max_batch=8, max_wait_ms=100)).start()
    try:
        barrier = threading.Barrier(2)
        futures = [None, None]

        def submit(i, seqs):
            barrier.wait()
            futures[i] = batcher.submit({"x": seqs})

        threads = [threading.Thread(target=submit, args=(0, seqs_a)),
                   threading.Thread(target=submit, args=(1, seqs_b))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        got_a, = futures[0].result(timeout=30)
        got_b, = futures[1].result(timeout=30)
        np.testing.assert_allclose(np.asarray(got_a),
                                   np.asarray(single_a), rtol=1e-5,
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(got_b),
                                   np.asarray(single_b), rtol=1e-5,
                                   atol=1e-6)
    finally:
        batcher.close()


def test_ragged_warmup_compiles_buckets():
    """warmup() must survive LoD feeds (per-row feature dims kept) and
    actually cover the smallest token shape of each batch bucket."""
    program, feed_names, fetch_vars = _ragged_model()
    engine = InferenceEngine(program, feed_names, fetch_vars,
                             config=EngineConfig(batch_buckets=[2, 4],
                                                 token_bucket=16))
    assert engine.warmup() == 2
    traces = engine.trace_count()
    # one-token sequences land exactly on the warmed shape: no retrace
    got, = engine.run({"x": [np.zeros((1, 4), np.float32),
                             np.ones((1, 4), np.float32)]})
    assert np.asarray(got).shape == (2, 3)
    assert engine.trace_count() == traces


class _SlowEngine:
    """Engine stand-in that blocks until released — makes queue-full
    and deadline states deterministic."""

    def __init__(self, release):
        self.feed_names = ["img"]
        self.fetch_names = ["out"]
        self._feed_meta = {"img": {"shape": [-1, 4],
                                   "dtype": np.dtype(np.float32),
                                   "lod_level": 0}}
        self.metrics = None
        self._release = release

    def batch_size(self, feeds):
        return int(np.asarray(feeds["img"]).shape[0])

    def run(self, feeds, timings=None):
        self._release.wait(timeout=30)
        return [np.asarray(feeds["img"])]


def test_deadline_exceeded_rejection():
    release = threading.Event()
    batcher = MicroBatcher(
        _SlowEngine(release),
        BatcherConfig(max_batch=1, max_wait_ms=0, queue_size=8)).start()
    try:
        # first request occupies the engine; the second's 20ms deadline
        # expires while it waits behind it
        blocker = batcher.submit({"img": np.zeros((1, 4), np.float32)})
        doomed = batcher.submit({"img": np.ones((1, 4), np.float32)},
                                timeout_ms=20)
        time.sleep(0.1)
        release.set()
        blocker.result(timeout=30)
        with pytest.raises(DeadlineExceededError):
            doomed.result(timeout=30)
    finally:
        batcher.close()


def test_queue_full_load_shedding():
    release = threading.Event()
    metrics = ServingMetrics()
    batcher = MicroBatcher(
        _SlowEngine(release),
        BatcherConfig(max_batch=1, max_wait_ms=0, queue_size=2),
        metrics=metrics).start()
    try:
        feeds = {"img": np.zeros((1, 4), np.float32)}
        futures = [batcher.submit(feeds)]  # occupies the engine
        # fill the admission queue, then overflow it
        admitted = 0
        with pytest.raises(QueueFullError):
            for _ in range(16):
                futures.append(batcher.submit(feeds))
                admitted += 1
        assert admitted <= 3  # 1 in-flight grace + queue_size
        assert metrics.rejected_queue_full.value >= 1
        release.set()
        for fut in futures:  # everything admitted still completes
            fut.result(timeout=30)
    finally:
        batcher.close()


def test_draining_rejects_new_submits():
    release = threading.Event()
    release.set()
    batcher = MicroBatcher(_SlowEngine(release), BatcherConfig()).start()
    batcher.close()
    with pytest.raises(ShuttingDownError):
        batcher.submit({"img": np.zeros((1, 4), np.float32)})


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_metrics_counters_monotonic(tmp_path):
    metrics = ServingMetrics()
    engine = InferenceEngine.from_saved_model(_digits_model(tmp_path),
                                              metrics=metrics)
    batcher = MicroBatcher(engine, BatcherConfig(max_wait_ms=0),
                           metrics=metrics).start()
    try:
        seen = []
        for i in range(4):
            batcher.submit_and_wait({"img": _rand_images(2, seed=i)})
            seen.append((metrics.requests_total.value,
                         metrics.responses_total.value,
                         metrics.cache_hit_total.value
                         + metrics.cache_miss_total.value,
                         metrics.total_seconds.count))
        for prev, cur in zip(seen, seen[1:]):
            assert all(c >= p for p, c in zip(prev, cur)), seen
        assert seen[-1][0] == seen[-1][1] == 4
        with pytest.raises(ValueError):
            metrics.requests_total.inc(-1)  # counters can't go down
    finally:
        batcher.close()


def test_metrics_render_text():
    metrics = ServingMetrics()
    metrics.requests_total.inc(3)
    metrics.batch_occupancy.observe(2)
    metrics.observe_stage("queue", 0.004)
    text = metrics.render_text()
    assert "serving_requests_total 3" in text
    assert 'serving_batch_occupancy_bucket{le="2"} 1' in text
    assert "serving_queue_seconds_count 1" in text
    # the profiler mirror row exists too
    from paddle_tpu.fluid import profiler

    assert "serving/queue" in profiler.get_profile_records()


# ---------------------------------------------------------------------------
# end-to-end HTTP server
# ---------------------------------------------------------------------------

def _post(host, port, path, payload, timeout=30):
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        conn.request("POST", path, json.dumps(payload),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())
    finally:
        conn.close()


def _get(host, port, path, timeout=30):
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, resp.read().decode()
    finally:
        conn.close()


def _metric_value(text, name):
    for line in text.splitlines():
        if line.startswith(name + " "):
            return float(line.split()[1])
    raise AssertionError("metric %s not in:\n%s" % (name, text))


def test_server_end_to_end_concurrent_clients(tmp_path):
    """Acceptance: N concurrent clients get correct per-request
    outputs, batch-occupancy > 1 lands in metrics, zero recompiles
    after warmup, and the server drains cleanly."""
    engine = InferenceEngine.from_saved_model(_digits_model(tmp_path))
    server = InferenceServer(engine, ServerConfig(
        port=0, max_batch=16, max_wait_ms=150, queue_size=32)).start()
    host, port = server.address
    try:
        traces_after_warmup = engine.trace_count()
        assert traces_after_warmup > 0  # warmup compiled the buckets
        # warmup compiles are startup cost, not traffic: the
        # request-path histograms/counters must still be zero
        assert server.metrics.compute_seconds.count == 0
        assert server.metrics.cache_miss_total.value == 0

        n_clients = 6
        inputs = [_rand_images(1, seed=20 + i) for i in range(n_clients)]
        singles = [engine.run({"img": x})[0] for x in inputs]
        barrier = threading.Barrier(n_clients)
        results = [None] * n_clients

        def client(i):
            barrier.wait()
            results[i] = _post(host, port, "/v1/infer",
                               {"inputs": {"img": inputs[i].tolist()}})

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

        fetch = engine.fetch_names[0]
        for i, (status, body) in enumerate(results):
            assert status == 200, body
            got = np.asarray(body["outputs"][fetch], np.float32)
            np.testing.assert_allclose(got, singles[i], rtol=1e-4,
                                       atol=1e-5)

        # zero recompiles: every request landed in a warmed bucket
        assert engine.trace_count() == traces_after_warmup

        status, text = _get(host, port, "/metrics")
        assert status == 200
        assert server.metrics.batch_occupancy.max > 1, \
            "no micro-batch coalesced >1 concurrent requests"
        assert _metric_value(text, "serving_responses_total") \
            >= n_clients
        # monotonic across scrapes
        status2, text2 = _get(host, port, "/metrics")
        assert _metric_value(text2, "serving_responses_total") >= \
            _metric_value(text, "serving_responses_total")

        status, body = _get(host, port, "/healthz")
        assert status == 200 and "ok" in body
    finally:
        server.shutdown()
    # drained cleanly: post-shutdown submits are refused, not hung
    with pytest.raises(ShuttingDownError):
        server.batcher.submit({"img": inputs[0]})


def test_server_queue_full_returns_429(tmp_path):
    release = threading.Event()
    engine = _SlowEngine(release)
    server = InferenceServer(engine, ServerConfig(
        port=0, max_batch=1, max_wait_ms=0, queue_size=1,
        warmup=False)).start()
    host, port = server.address
    try:
        payload = {"inputs": {"img": [[0.0] * 4]}}
        codes = [None] * 8
        threads = []

        def client(i):
            codes[i] = _post(host, port, "/v1/infer", payload)[0]

        for i in range(8):
            t = threading.Thread(target=client, args=(i,))
            t.start()
            threads.append(t)
        # the engine is blocked, so overflow shows up quickly
        deadline = time.monotonic() + 10
        while 429 not in codes and time.monotonic() < deadline:
            time.sleep(0.01)
        release.set()
        for t in threads:
            t.join(timeout=30)
        assert 429 in codes, codes  # load was shed, not queued
        assert 200 in codes, codes  # admitted work still answered
    finally:
        server.shutdown()


def test_server_deadline_returns_504(tmp_path):
    release = threading.Event()
    engine = _SlowEngine(release)
    server = InferenceServer(engine, ServerConfig(
        port=0, max_batch=1, max_wait_ms=0, queue_size=8,
        warmup=False)).start()
    host, port = server.address
    try:
        payload = {"inputs": {"img": [[0.0] * 4]}}
        statuses = {}

        def blocker():
            statuses["blocker"] = _post(host, port, "/v1/infer",
                                        payload)[0]

        def doomed():
            statuses["doomed"] = _post(
                host, port, "/v1/infer",
                dict(payload, timeout_ms=20))[0]

        tb = threading.Thread(target=blocker)
        tb.start()
        time.sleep(0.2)  # blocker is in the engine; queue the doomed one
        td = threading.Thread(target=doomed)
        td.start()
        time.sleep(0.2)
        release.set()
        tb.join(timeout=30)
        td.join(timeout=30)
        assert statuses["blocker"] == 200, statuses
        assert statuses["doomed"] == 504, statuses
    finally:
        server.shutdown()


def test_server_bad_request_and_draining(tmp_path):
    engine = InferenceEngine.from_saved_model(_digits_model(tmp_path))
    server = InferenceServer(engine, ServerConfig(
        port=0, warmup=False)).start()
    host, port = server.address
    try:
        status, body = _post(host, port, "/v1/infer", {"inputs": {}})
        assert status == 400 and "img" in body["error"]
        # wrong per-sample shape is rejected at admission (it must
        # never reach the batcher and poison a co-batched request)
        status, body = _post(host, port, "/v1/infer",
                             {"inputs": {"img": [[0.0] * 8]}})
        assert status == 400 and "shape" in body["error"]
        status, _ = _post(host, port, "/nope", {})
        assert status == 404
    finally:
        server.shutdown()
    assert server.draining
    status, body = server.handle_infer(
        {"inputs": {"img": [[0.0] * 64]}})
    assert status == 503


def _post_with_headers(host, port, path, payload, timeout=30):
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        conn.request("POST", path, json.dumps(payload),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read()), \
            dict(resp.getheaders())
    finally:
        conn.close()


def test_server_429_carries_retry_after():
    """A load-shed reply must advertise its backoff hint: closed-loop
    clients (and pload workers) re-offer shed work immediately
    otherwise (docs/SERVING.md backpressure contract)."""
    release = threading.Event()
    engine = _SlowEngine(release)
    server = InferenceServer(engine, ServerConfig(
        port=0, max_batch=1, max_wait_ms=0, queue_size=1,
        warmup=False, retry_after_s=2.0)).start()
    host, port = server.address
    try:
        payload = {"inputs": {"img": [[0.0] * 4]}}
        results = [None] * 8
        threads = []

        def client(i):
            results[i] = _post_with_headers(host, port, "/v1/infer",
                                            payload)

        for i in range(8):
            t = threading.Thread(target=client, args=(i,))
            t.start()
            threads.append(t)
        deadline = time.monotonic() + 10
        while not any(r and r[0] == 429 for r in results) \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        release.set()
        for t in threads:
            t.join(timeout=30)
        shed = [r for r in results if r and r[0] == 429]
        assert shed, [r and r[0] for r in results]
        for status, body, headers in shed:
            assert headers.get("Retry-After") == "2", headers
            assert body["request_id"]  # shed replies stay quotable
        ok = [r for r in results if r and r[0] == 200]
        assert ok and all("Retry-After" not in r[2] for r in ok)
    finally:
        server.shutdown()


def test_concurrent_load_statuses_complete():
    """The batcher deadline/504 path under concurrent submits: many
    producers against a slow engine must each get exactly one of
    200/429/504 — with a request_id — and no future may hang."""
    release = threading.Event()
    engine = _SlowEngine(release)
    server = InferenceServer(engine, ServerConfig(
        port=0, max_batch=2, max_wait_ms=0, queue_size=4,
        warmup=False))
    server.batcher.start()  # loopback: no HTTP listener needed
    try:
        n = 24
        results = [None] * n
        payload = {"inputs": {"img": [[0.0] * 4]}, "timeout_ms": 150}

        def producer(i):
            results[i] = server.handle_infer(dict(payload))

        threads = [threading.Thread(target=producer, args=(i,))
                   for i in range(n)]
        for t in threads:
            t.start()
        # the engine is blocked past every queued request's 150ms
        # deadline: queued work expires (504), overflow sheds (429),
        # the batch already in the engine completes (200)
        time.sleep(0.4)
        release.set()
        for t in threads:
            t.join(timeout=30)
        assert all(r is not None for r in results), \
            "a submit hung: %r" % [i for i, r in enumerate(results)
                                   if r is None]
        statuses = [status for status, _ in results]
        assert all(s in (200, 429, 504) for s in statuses), statuses
        for status, body in results:
            assert body.get("request_id"), (status, body)
        assert 200 in statuses, statuses   # admitted work answered
        assert 429 in statuses, statuses   # overflow shed
        assert 504 in statuses, statuses   # expired at dequeue
        # nothing left pending inside the batcher either
        assert server.metrics.queue_depth.value == 0
    finally:
        server.batcher.close()


def test_queue_depth_peak_high_watermark():
    """The peak gauge keeps the worst depth between scrapes — set on
    enqueue, dequeue AND the shed path — and a render resets the
    window to the live depth."""
    metrics = ServingMetrics()
    metrics.note_queue_depth(3)
    metrics.note_queue_depth(1)
    assert metrics.queue_depth.value == 1
    assert metrics.queue_depth_peak.value == 3
    text = metrics.render_text()
    assert "serving_queue_depth_peak 3" in text
    # the scrape carried the watermark out; the window restarts at
    # the live depth
    assert metrics.queue_depth_peak.value == 1
    assert "serving_queue_depth_peak 1" in metrics.render_text()

    # the shed path publishes the saturated depth (an overflowing
    # queue between enqueue/dequeue samples was formerly invisible)
    release = threading.Event()
    shed_metrics = ServingMetrics()
    batcher = MicroBatcher(
        _SlowEngine(release),
        BatcherConfig(max_batch=1, max_wait_ms=0, queue_size=2),
        metrics=shed_metrics).start()
    try:
        feeds = {"img": np.zeros((1, 4), np.float32)}
        futures = [batcher.submit(feeds)]
        with pytest.raises(QueueFullError):
            for _ in range(16):
                futures.append(batcher.submit(feeds))
        assert shed_metrics.queue_depth.value >= 2
        assert shed_metrics.queue_depth_peak.value >= 2
        release.set()
        for fut in futures:
            fut.result(timeout=30)
    finally:
        batcher.close()


# ---------------------------------------------------------------------------
# SLO burn
# ---------------------------------------------------------------------------

def test_slo_tracker_burn_windows():
    from paddle_tpu.serving.metrics import SLOTracker

    m = ServingMetrics()
    slo = SLOTracker(m, objective_ms=100.0, target=0.9, model="mdl")
    assert slo.update() == 0.0                   # no traffic yet
    for _ in range(8):
        m.total_seconds.observe(0.01)            # within objective
    for _ in range(2):
        m.total_seconds.observe(5.0)             # violations
    # 20% violating / 10% budget = burn 2x
    assert slo.update() == pytest.approx(2.0, rel=0.05)
    # next window: all good -> burn back to 0
    for _ in range(5):
        m.total_seconds.observe(0.01)
    assert slo.update() == pytest.approx(0.0, abs=1e-9)
    # gauge surfaced in the default registry, labeled by model
    from paddle_tpu.obs import registry as obs_registry

    fam = obs_registry.get_registry().gauge(
        "slo_burn_rate", labelnames=("model",))
    assert fam.labels(model="mdl").value == 0.0
    with pytest.raises(ValueError):
        SLOTracker(m, objective_ms=50, target=1.0)
    # objectives beyond the histogram's largest finite bucket are
    # unmeasurable (violations would land in +Inf and read as good)
    with pytest.raises(ValueError):
        SLOTracker(m, objective_ms=60_000)


def test_server_healthz_carries_slo_burn():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[4], dtype="float32")
        out = fluid.layers.fc(input=img, size=2)
    scope = Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
    program = fluid_io.prune_program(main, [out])
    engine = InferenceEngine(program, ["img"], [out], scope=scope,
                             config=EngineConfig(batch_buckets=[2]))
    server = InferenceServer(
        engine, ServerConfig(warmup=False, slo_ms=30_000,
                             slo_target=0.99, model_name="m0"))
    server.batcher.start()
    try:
        status, _ = server.handle_infer(
            {"inputs": {"img": np.zeros((1, 4)).tolist()}})
        assert status == 200
        health = server.health_signals()
    finally:
        server.batcher.close()
    assert health["slo"]["objective_ms"] == 30_000
    # generous objective: nothing burned
    assert health["slo_burn_rate"] == 0.0
    # without an SLO config the key stays absent (contract: opt-in)
    server2 = InferenceServer(engine, ServerConfig(warmup=False))
    assert "slo_burn_rate" not in server2.health_signals()
