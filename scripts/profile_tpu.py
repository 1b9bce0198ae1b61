"""Device-time profile of a bench model's train step.

Runs the same jitted step as bench.py under `jax.profiler.trace` and
aggregates on-device time by XLA `hlo_category` (the trace events carry
per-instruction category / FLOPs / bytes metadata), printing a table
like the reference's ParseEvents summary but at HLO granularity
(reference: paddle/platform/profiler.h:133-146).

Usage (from the repo root, on the TPU or CPU):
    python scripts/profile_tpu.py            # resnet50, batch 128
    BENCH_MODEL=vgg16 BENCH_BATCH=64 python scripts/profile_tpu.py

NOTE: the "is this leg compute/HBM/input/host bound" triage that used
to be read by hand off this table now lives in `pperf classify` and
the per-leg BENCH "perf" blob (paddle_tpu.obs.perf, docs/PERF.md);
this script remains the drill-down for per-HLO device time once the
classifier has named the bottleneck.
"""

import collections
import glob
import gzip
import json
import os
import sys
import tempfile


def aggregate_trace(trace_dir):
    paths = glob.glob(os.path.join(trace_dir, "**", "*.trace.json.gz"),
                      recursive=True)
    cat = collections.Counter()
    flops = collections.Counter()
    per_op = collections.defaultdict(collections.Counter)
    shapes = {}
    for p in paths:
        with gzip.open(p, "rt") as f:
            data = json.load(f)
        for ev in data.get("traceEvents", []):
            args = ev.get("args") or {}
            if ev.get("ph") != "X" or "hlo_category" not in args:
                continue
            dur = int(args.get("device_duration_ps", 0))
            c = args["hlo_category"]
            cat[c] += dur
            per_op[c][ev["name"]] += dur
            shapes.setdefault(ev["name"],
                              args.get("shape_with_layout", ""))
            try:
                flops[c] += float(args.get("model_flops") or 0)
            except (TypeError, ValueError):
                pass
    return cat, flops, per_op, shapes


def main():
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    steps = int(os.environ.get("PROFILE_STEPS", "10"))

    import jax
    import bench
    from paddle_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    model = os.environ.get("BENCH_MODEL", "resnet50")
    batch = int(os.environ.get("BENCH_BATCH", "128"))

    import paddle_tpu.fluid as fluid

    if os.environ.get("BENCH_AMP", "1") != "0":
        fluid.amp.enable_bf16()
    image_size = int(os.environ.get(
        "BENCH_IMAGE_SIZE", "32" if model == "smallnet" else "224"))
    class_dim = int(os.environ.get(
        "BENCH_CLASS_DIM", "10" if model == "smallnet" else "1000"))
    main_prog, startup, _, avg_loss = bench._build_image_model(
        model, batch, image_size, class_dim)

    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    dev = jax.devices()[0]
    step, state = bench.functional_step(
        main_prog, ["image", "label"], avg_loss.name, scope, dev)
    feeds = jax.device_put(
        bench._image_feeds(batch, image_size, class_dim), dev)

    for _ in range(3):
        fetches, state = step(state, feeds)
    jax.block_until_ready(fetches)

    trace_dir = tempfile.mkdtemp(prefix="paddle_tpu_profile_")
    with jax.profiler.trace(trace_dir):
        for _ in range(steps):
            fetches, state = step(state, feeds)
        jax.block_until_ready(fetches)

    cat, flops, per_op, shapes = aggregate_trace(trace_dir)
    total = sum(cat.values())
    if not total:
        print("no device events captured (trace dir: %s)" % trace_dir)
        return
    ms = 1.0 / (1e9 * steps)  # ps -> ms/step
    print("%s batch=%d: %.2f ms/step device time over %d steps"
          % (model, batch, total * ms, steps))
    print("%-26s %10s %7s %12s" % ("category", "ms/step", "%", "GFLOP/step"))
    for c, d in cat.most_common():
        print("%-26s %10.3f %6.1f%% %12.1f"
              % (c, d * ms, 100.0 * d / total, flops[c] / 1e9 / steps))
    print("\ntop instructions:")
    everything = collections.Counter()
    for c in per_op:
        everything.update(per_op[c])
    for name, d in everything.most_common(15):
        print("%10.3f ms/step  %-30s %s"
              % (d * ms, name[:30], shapes.get(name, "")[:60]))
    print("\ntrace: %s" % trace_dir)


if __name__ == "__main__":
    main()
