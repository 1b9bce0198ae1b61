"""paddle_tpu.obs.load: traffic mix, arrival schedules, replay,
open-vs-closed-loop latency accounting (the coordinated-omission
asymmetry, demonstrated on a fake stalling target), report math and
the tail/exemplar joins.

Tier-1 (CPU, no real server — the loopback/HTTP integration is
`pload --selftest`'s job): schedules must be deterministic under
seed, replay must preserve gaps and batches, open-loop latency must
be measured from the SCHEDULE while closed-loop latency is measured
from the send."""

import json
import random
import threading
import time

import pytest

from paddle_tpu.obs import load as obs_load
from paddle_tpu.obs.registry import MetricsRegistry


# ---------------------------------------------------------------------------
# traffic mix + schedules
# ---------------------------------------------------------------------------

def test_mix_parse_weights_and_fractions():
    mix = obs_load.TrafficMix.parse("1:6,4:3,8:1")
    assert mix.weights == {1: 6.0, 4: 3.0, 8: 1.0}
    fr = mix.fractions()
    assert abs(sum(fr.values()) - 1.0) < 1e-12
    assert fr[1] == pytest.approx(0.6)
    # bare sizes weigh equally
    assert obs_load.TrafficMix.parse("1,4,8").fractions()[4] == \
        pytest.approx(1 / 3)
    with pytest.raises(ValueError):
        obs_load.TrafficMix.parse("0:1")
    with pytest.raises(ValueError):
        obs_load.TrafficMix({})


def test_mix_sample_matches_weights():
    mix = obs_load.TrafficMix.parse("1:3,4:1")
    rng = random.Random(0)
    draws = [mix.sample(rng) for _ in range(4000)]
    assert set(draws) == {1, 4}
    assert 0.70 < draws.count(1) / len(draws) < 0.80


def test_uniform_schedule_deterministic_spacing():
    sched = obs_load.build_schedule(100.0, n=50, arrival="uniform")
    assert len(sched) == 50 and sched[0][0] == 0.0
    gaps = [b[0] - a[0] for a, b in zip(sched, sched[1:])]
    assert all(abs(g - 0.01) < 1e-9 for g in gaps)
    # same seed -> identical schedule (batches included)
    again = obs_load.build_schedule(100.0, n=50, arrival="uniform")
    assert again == sched


def test_poisson_schedule_mean_gap():
    sched = obs_load.build_schedule(200.0, n=500, arrival="poisson",
                                    seed=1)
    gaps = [b[0] - a[0] for a, b in zip(sched, sched[1:])]
    mean = sum(gaps) / len(gaps)
    assert 1 / 200 * 0.7 < mean < 1 / 200 * 1.3
    assert obs_load.build_schedule(200.0, n=500, arrival="poisson",
                                   seed=1) == sched
    assert obs_load.build_schedule(200.0, n=500, arrival="poisson",
                                   seed=2) != sched


def test_phases_and_ramp_modulate_rate():
    phases = obs_load.parse_phases("5:400,6:100")
    assert phases == [(5.0, 400.0), (6.0, 100.0)]
    assert obs_load.rate_at(0.0, 100.0, phases=phases) == 100.0
    assert obs_load.rate_at(5.5, 100.0, phases=phases) == 400.0
    assert obs_load.rate_at(7.0, 100.0, phases=phases) == 100.0
    # linear ramp-in scales the base rate, floored at 5%
    assert obs_load.rate_at(1.0, 100.0, ramp_s=2.0) == \
        pytest.approx(50.0)
    assert obs_load.rate_at(0.0, 100.0, ramp_s=2.0) == \
        pytest.approx(5.0)
    assert obs_load.rate_at(3.0, 100.0, ramp_s=2.0) == 100.0
    # a burst phase thins the uniform gaps after its start
    sched = obs_load.build_schedule(
        10.0, duration_s=2.0, arrival="uniform",
        phases=[(1.0, 1000.0)])
    early = [t for t, _ in sched if t < 1.0]
    late = [t for t, _ in sched if t >= 1.0]
    assert len(late) > len(early) * 10


def test_schedule_needs_bound_and_valid_arrival():
    with pytest.raises(ValueError):
        obs_load.build_schedule(100.0)
    with pytest.raises(ValueError):
        obs_load.build_schedule(100.0, n=10, arrival="bursty")


# ---------------------------------------------------------------------------
# access-log replay
# ---------------------------------------------------------------------------

def test_access_log_replay_preserves_gaps_and_batches(tmp_path):
    entries = [
        {"t": 100.0, "batch": 2, "status": 200, "request_id": "a",
         "trace_id": "t" * 32, "latency_ms": 3.0, "bucket": 2},
        {"t": 100.5, "batch": 1, "status": 200, "request_id": "b",
         "trace_id": "u" * 32, "latency_ms": 2.0, "bucket": 1},
        {"t": 101.5, "batch": 4, "status": 429, "request_id": "c",
         "trace_id": "v" * 32, "latency_ms": 0.1, "bucket": 4},
    ]
    path = tmp_path / "access.jsonl"
    with open(path, "w") as f:
        f.write("not json, torn append\n")
        for e in entries:
            f.write(json.dumps(e) + "\n")
        f.write("\n")
    loaded = obs_load.load_access_log(str(path))
    assert [e["request_id"] for e in loaded] == ["a", "b", "c"]
    sched = obs_load.replay_schedule(loaded, speed=2.0)
    assert sched == [(0.0, 2), (0.25, 1), (0.75, 4)]
    with pytest.raises(ValueError):
        obs_load.replay_schedule(loaded, speed=0.0)
    # out-of-order logs are sorted by t before gap reconstruction
    loaded_rev = list(reversed(loaded))
    assert obs_load.replay_schedule(sorted(loaded_rev,
                                           key=lambda e: e["t"])) == \
        obs_load.replay_schedule(loaded)


# ---------------------------------------------------------------------------
# open vs closed loop: the omission asymmetry on a fake target
# ---------------------------------------------------------------------------

class _StallingTarget:
    """Fake target: one armed call stalls, everything else is fast.
    No server, no sockets — pure accounting test."""

    def __init__(self, stall_at=3, stall_s=0.2, fast_s=0.001):
        self.stall_at = stall_at
        self.stall_s = stall_s
        self.fast_s = fast_s
        self.calls = 0
        self._lock = threading.Lock()

    def infer(self, payload, ctx, timeout_s=None):
        with self._lock:
            self.calls += 1
            stall = self.calls == self.stall_at
        time.sleep(self.stall_s if stall else self.fast_s)
        return 200, {"request_id": ctx.request_id}, {}


def _payload(batch):
    return {"batch": batch}


def test_open_loop_measures_from_schedule():
    """With one sender, a 200ms stall delays every later scheduled
    arrival; open-loop latency (from the schedule) must show that
    backlog, and `service_ms` (send -> reply) must stay small for the
    non-stalled requests."""
    target = _StallingTarget(stall_at=3, stall_s=0.2)
    sched = [(i * 0.001, 1) for i in range(10)]
    report = obs_load.run_open_loop(
        target, sched, _payload, max_inflight=1,
        registry=MetricsRegistry(), slo_ms=100.0)
    assert report["mode"] == "open" and report["n"] == 10
    # over half the run sat behind the stall: p50 is already inflated
    assert report["percentiles_ms"]["p90_ms"] >= 100.0
    assert report["slo"]["violations"] >= 5
    worst = report["worst"][0]
    assert worst["latency_ms"] >= 150.0
    # the stall is backlog, not per-request service: at most the one
    # stalled call has a big service_ms
    slow_service = [w for w in report["worst"]
                    if w["service_ms"] >= 150.0]
    assert len(slow_service) <= 1


def test_closed_loop_hides_the_same_stall():
    target = _StallingTarget(stall_at=3, stall_s=0.2)
    report = obs_load.run_closed_loop(
        target, _payload, workers=1, n=10, seed=3,
        registry=MetricsRegistry(), slo_ms=100.0)
    assert report["mode"] == "closed" and report["n"] == 10
    # exactly one request observed the stall; the p50 stays clean and
    # only max carries it — the coordinated-omission trap
    assert report["max_ms"] >= 150.0
    assert report["percentiles_ms"]["p50_ms"] < 100.0
    assert report["slo"]["violations"] == 1


class _RetryAfterTarget:
    def __init__(self):
        self.calls = 0

    def infer(self, payload, ctx, timeout_s=None):
        self.calls += 1
        if self.calls == 1:
            return 429, {"error": "full",
                         "request_id": ctx.request_id}, \
                {"Retry-After": "0.01"}
        return 200, {"request_id": ctx.request_id}, {}


def test_closed_loop_honors_retry_after():
    target = _RetryAfterTarget()
    t0 = time.perf_counter()
    report = obs_load.run_closed_loop(
        target, _payload, workers=1, n=3,
        registry=MetricsRegistry())
    assert time.perf_counter() - t0 >= 0.01
    assert report["by_status"] == {"200": 2, "429": 1}
    shed = [w for w in report["worst"] if w["status"] == 429]
    assert shed and shed[0]["retry_after"] == "0.01"


def test_open_loop_latency_histogram_lands_in_registry():
    reg = MetricsRegistry()
    target = _StallingTarget(stall_at=99, stall_s=0.0, fast_s=0.0)
    sched = [(0.0, 1), (0.0, 2), (0.0, 2)]
    obs_load.run_open_loop(target, sched, _payload, max_inflight=2,
                           registry=reg)
    text = reg.render_text()
    assert 'load_latency_seconds_count{bucket="b2",status="200"} 2' \
        in text
    assert "load_offered_rps" in text and "load_inflight 0" in text


# ---------------------------------------------------------------------------
# report math
# ---------------------------------------------------------------------------

def _samples(lats, batch=1, status=200):
    return [{"batch": batch, "bucket": "b%d" % batch, "status": status,
             "latency_ms": float(v), "service_ms": float(v),
             "trace_id": "%032x" % i, "request_id": "req-%d" % i}
            for i, v in enumerate(lats)]


def test_report_percentiles_and_slo():
    report = obs_load.build_report(
        _samples(range(1, 101)), mode="open", wall_s=2.0, slo_ms=90.0,
        offered_rps=50.0)
    pct = report["percentiles_ms"]
    assert pct["p50_ms"] == 50.0 and pct["p90_ms"] == 90.0
    assert pct["p99_ms"] == 99.0 and pct["p99_9_ms"] == 100.0
    assert report["max_ms"] == 100.0
    assert report["achieved_rps"] == 50.0
    assert report["slo"] == {"slo_ms": 90.0, "attainment": 0.9,
                             "violations": 10}
    assert report["by_bucket"]["b1"]["n"] == 100
    assert [w["latency_ms"] for w in report["worst"]] == \
        [100.0, 99.0, 98.0, 97.0, 96.0]
    assert obs_load.percentile([], 99.0) is None
    with pytest.raises(ValueError):
        obs_load.build_report([None], mode="open", wall_s=1.0)


def test_format_report_mentions_the_tail():
    report = obs_load.build_report(_samples([1.0, 2.0, 300.0]),
                                   mode="open", wall_s=1.0,
                                   slo_ms=100.0)
    text = obs_load.format_report(report)
    assert "open loop: 3 requests" in text
    assert "p99 300.00" in text and "worst 300.00ms" in text
    assert "slo:" in text


# ---------------------------------------------------------------------------
# joins
# ---------------------------------------------------------------------------

def test_join_tail_matches_request_then_trace():
    report = obs_load.build_report(_samples([1.0, 2.0, 50.0]),
                                   mode="open", wall_s=1.0)
    worst = report["worst"][0]
    tail_doc = {"requests": [
        {"request_id": worst["request_id"], "trace_id": "nope",
         "reason": "slow", "latency_ms": 49.0, "status": 200,
         "spans": [{"name": "serving/request"}]},
    ]}
    assert obs_load.join_tail(report, tail_doc) == 1
    assert report["worst"][0]["tail"]["reason"] == "slow"
    assert report["worst"][0]["tail"]["spans"]
    # trace_id is the fallback join key
    report2 = obs_load.build_report(_samples([1.0, 2.0, 50.0]),
                                    mode="open", wall_s=1.0)
    w2 = report2["worst"][0]
    assert obs_load.join_tail(report2, {"requests": [
        {"request_id": "other", "trace_id": w2["trace_id"],
         "reason": "slow", "latency_ms": 48.0, "status": 200,
         "spans": []}]}) == 1
    assert obs_load.join_tail(report2, {"requests": []}) == 0


def test_parse_and_join_exemplars():
    text = "\n".join([
        "# TYPE serving_total_seconds histogram",
        'serving_total_seconds_bucket{le="0.05"} 7 '
        '# {trace_id="%s"} 0.021 1700000000.000' % ("ab" * 16),
        'serving_total_seconds_bucket{le="+Inf"} 8',
        "serving_total_seconds_count 8",
    ])
    ex = obs_load.parse_exemplars(text)
    assert list(ex) == ["ab" * 16]
    hit = ex["ab" * 16][0]
    assert hit["metric"] == "serving_total_seconds"
    assert hit["le"] == "0.05" and hit["value"] == pytest.approx(0.021)
    report = obs_load.build_report(
        [{"batch": 1, "bucket": "b1", "status": 200,
          "latency_ms": 21.0, "service_ms": 21.0,
          "trace_id": "ab" * 16, "request_id": "req-x"}],
        mode="open", wall_s=1.0)
    assert obs_load.join_exemplars(report, text) == 1
    assert report["worst"][0]["exemplars"][0]["le"] == "0.05"
