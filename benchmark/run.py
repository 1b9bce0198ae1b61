"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process that finds the cell's files by name (workloads/<name>.json,
the configuration, model builder, driver and reference it names), loads,
warms up only that cell's shapes, measures for `--seconds`, checks the
outputs, prints readable lines and then, as the last line of its
standard output, the one JSON object BENCHMARK.json's contract fixes.
With `--trace 0` the metrics are the cell's end-to-end metrics; with
`--trace 1` a few seconds more are run under the profiler and the
metrics are the per-layer ones, each from a reader of its own under
layer_metrics/.

The run is on the accelerator or it fails: no device whose kind is
missing from peaks.json, no fallback to the CPU.  JAX_PLATFORMS=cpu, set
in so many words, makes a rehearsal whose line names the CPU and carries
no device metric.
"""

import argparse
import json
import os
import sys
import time

PROCESS_START = time.perf_counter()
BREAKDOWN_ENTRIES = 10


def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--search-path", action="append", default=[],
                   help="a directory laid out like benchmark/, searched "
                        "first for configs, workloads, models, drivers, "
                        "references and layer metrics")
    return p.parse_args(argv)


def layer_metrics(run):
    """Every reader under layer_metrics/ that finds something to read."""
    metrics = {}
    for name in run.lookup.names("layer_metrics"):
        reader = run.lookup.module("layer_metrics", name)
        value = reader.read(run)
        if value is not None:
            metrics[name] = {"value": value, "unit": reader.UNIT}
    return metrics


def breakdown(run):
    """Where the traced window's device time and idle time went."""
    from benchmark.reduce import xplane

    trace = run.reduced
    first = min(trace.devices)
    ops = sorted(
        xplane.op_seconds(trace.devices[first], trace.window).items(),
        key=lambda item: -item[1][0])
    gaps = xplane.idle_gaps(trace, first)
    return {
        "device_ops": [["%s x%d [%s]" % (name, calls, category), seconds]
                       for (name, category), (seconds, calls)
                       in ops[:BREAKDOWN_ENTRIES]],
        "idle_gaps": [[n, s] for n, s in
                      gaps.most_common(BREAKDOWN_ENTRIES)],
    }


def main(argv=None):
    args = parse_args(argv)
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.dirname(here))
    from benchmark import harness

    lookup = harness.Lookup(args.search_path)
    workload = lookup.json("workloads", args.workload)
    workload["name"] = args.workload
    config = lookup.json("configs", workload["config"])
    clock = harness.SetupClock(PROCESS_START)

    with clock.phase("import"):
        import jax
        import paddle_tpu.fluid  # noqa: F401 — the system under test

    with clock.phase("devices"):
        devices, peaks = harness.require_devices(workload["chips"], lookup)
    first = devices[0]
    cache = harness.place_compile_cache()
    entries_before = harness.cache_entries(cache)
    print("jax %s platform=%s device_kind=%s devices=%d cell=%s seed=%d"
          % (jax.__version__, first.platform, first.device_kind,
             len(jax.devices()), args.workload, args.seed), flush=True)
    print("compile cache: %s (%d entries)" % (cache, entries_before),
          flush=True)

    run = harness.Run(workload, config, args.seed, args.seconds,
                      bool(args.trace), lookup, devices, peaks, clock,
                      harness.CompileClock())
    lookup.module("drivers", workload["driver"]).run(run)
    if run.window_start is None:
        raise SystemExit("benchmark: the driver measured no window")
    setup_s = clock.setup_s(run.window_start)

    device = {"platform": first.platform, "kind": first.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": harness.memory_peak_bytes(devices)}
    result = {"correct": bool(run.correct), "attempted": run.attempted,
              "failed": run.failed}
    if run.trace:
        from benchmark.reduce import xplane

        run.reduced = xplane.load(run.trace_dir)
        if run.reduced is not None:
            origin = run.reduced.window[0]
            run.reduced.spans.extend(
                (origin + s, origin + e, n) for s, e, n in run.host_spans)
        result["metrics"] = layer_metrics(run)
        if run.reduced is not None and run.reduced.devices:
            start, end = run.reduced.window
            device["busy_s"] = xplane.busy_seconds(run.reduced)
            device["window_s"] = end - start
            result["breakdown"] = breakdown(run)
    else:
        metrics = dict(run.end_to_end)
        metrics["setup_s"] = (setup_s, "s")
        result["metrics"] = {name: {"value": value, "unit": unit}
                             for name, (value, unit) in metrics.items()}
    result["device"] = device

    added = harness.cache_entries(cache) - entries_before
    print("compile cache: %d entries added, %d hit(s), %d miss(es) in "
          "the whole run" % (added, run.compiles.hits, run.compiles.misses),
          flush=True)
    for name, m in sorted(result["metrics"].items()):
        print("metric %-32s %s %s" % (name, m["value"], m["unit"]),
              flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
