"""run.py end to end as a CPU rehearsal, on a tiny configuration and
workloads that live only under benchmark/tests/fixture and are found
through the same lookup by name, which is the proof that a new cell needs
new files and no edit: nothing under benchmark/ outside benchmark/tests/
knows them.  And BENCHMARK.json against the files it names.
"""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import BENCH_ROOT, CHECKOUT, Lookup

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixture")
RUN = os.path.join(BENCH_ROOT, "run.py")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
# what only a chip can say: none of it may appear in a rehearsal's line
DEVICE_METRICS = {"mfu", "mxu_roofline", "flash_fwd_roofline",
                  "device_idle_share", "hbm_peak_gib", "mxu_ms_per_step",
                  "nonmxu_ms_per_step", "functional_step_ms",
                  "executor_overhead_share", "collective_ms_per_step",
                  "collective_exposed_ms_per_step"}


def run_cell(workload, trace, env_update=None, seconds="1", timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_update or {})
    for key, value in list(env.items()):
        if value is None:
            del env[key]
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", workload, "--seed", "5",
         "--seconds", seconds, "--trace", str(trace),
         "--search-path", FIXTURE],
        cwd=CHECKOUT, env=env, capture_output=True, text=True,
        timeout=timeout)
    return proc


def last_line(proc):
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_untraced_rehearsal_prints_the_end_to_end_line():
    result = last_line(run_cell("gpt2-tiny-train", 0))
    assert set(result) == RESULT_KEYS
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"train_items_per_s", "setup_s"}
    assert result["metrics"]["train_items_per_s"]["unit"] == "items/s"
    assert result["metrics"]["setup_s"]["value"] > 0
    assert result["device"] == {"platform": "cpu", "kind": "cpu",
                                "count": 1, "memory_peak_bytes": 0}


def test_traced_rehearsal_prints_counters_and_no_device_metric():
    result = last_line(run_cell("gpt2-tiny-train", 1))
    assert set(result) == RESULT_KEYS       # no breakdown without a device
    assert result["correct"] is True
    assert result["device"]["platform"] == "cpu"
    assert "busy_s" not in result["device"]
    metrics = result["metrics"]
    assert not DEVICE_METRICS & set(metrics)
    assert metrics["compiles_in_window"] == {"value": 0, "unit": "count"}
    assert {"setup_compile_s", "setup_cache_misses"} <= set(metrics)


def test_serving_rehearsal_runs_the_generator_as_a_child():
    result = last_line(run_cell("resnet50-tiny-serve", 1, seconds="2"))
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 5
    assert {"gen_late_ms_p95", "serve_queue_ms_mean",
            "serve_batch_rows_mean", "serve_compute_ms_mean",
            "serve_wire_ms_mean", "compiles_in_window"} <= \
        set(result["metrics"])
    assert result["metrics"]["compiles_in_window"]["value"] == 0
    assert not DEVICE_METRICS & set(result["metrics"])


def test_four_virtual_devices_rehearse_the_spmd_driver():
    proc = run_cell("resnet50-tiny-train-dp4", 0, env_update={
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    result = last_line(proc)
    assert result["device"]["count"] == 4
    assert result["failed"] == 0
    assert "within" in proc.stdout and "check FAIL: loss" not in proc.stdout


def test_no_accelerator_and_no_word_for_the_cpu_is_an_error():
    proc = run_cell("gpt2-tiny-train", 0, env_update={"JAX_PLATFORMS": None})
    assert proc.returncode != 0
    assert "no accelerator" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_more_chips_than_the_host_has_is_an_error():
    proc = run_cell("resnet50-tiny-train-dp4", 0)
    assert proc.returncode != 0 and "needs 4 chip(s)" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_unknown_device_kind_is_an_error(tmp_path):
    """peaks.json is found by the same lookup, so a table without the
    device's kind can be put in front of it: the run has to fail."""
    from benchmark import harness

    (tmp_path / "peaks.json").write_text(json.dumps(
        {"source": "test", "devices": {}}))

    class Device:
        platform, device_kind = "tpu", "TPU v9 imaginary"

    import jax

    real = jax.devices
    jax.devices = lambda *a: [Device()]
    try:
        with pytest.raises(SystemExit) as err:
            harness.require_devices(1, Lookup([str(tmp_path)]))
    finally:
        jax.devices = real
    assert "not in peaks.json" in str(err.value)


def test_benchmark_json_names_files_that_exist_and_agree():
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    lookup = Lookup()
    assert bench["command"] == ["python3", "benchmark/run.py"]
    assert bench["paths"] == ["benchmark"]
    end_to_end = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in end_to_end
    cells = {w["name"] for w in bench["workloads"]}
    for cfg in bench["configs"]:
        with open(os.path.join(CHECKOUT, cfg["file"])) as f:
            on_disk = json.load(f)
        assert on_disk["source"] == cfg["source"]
        assert on_disk["reduced"] == cfg["reduced"]
        lookup.path("models", on_disk["builder"] + ".py")
        lookup.path("reference", on_disk["reference"] + ".py")
    for cell in bench["workloads"]:
        workload = lookup.json("workloads", cell["name"])
        assert workload["config"] == cell["config"]
        assert workload["chips"] == cell["chips"]
        assert workload["why"] == cell["why"] and len(cell["why"]) <= 200
        lookup.path("drivers", workload["driver"] + ".py")
    for metric in bench["per_layer"]:
        reader = lookup.module("layer_metrics", metric["name"])
        assert (reader.LAYER, reader.MOVES, reader.UNIT, reader.SOURCE) == \
            (metric["layer"], metric["moves"], metric["unit"],
             metric["source"])
        assert metric["moves"] in end_to_end
        assert set(metric.get("workloads", cells)) <= cells
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
