"""A serving cell: `tools/serve_cli.start_server` on a
`save_inference_model` export, offered an open-loop schedule of HTTP
requests at the workload's fixed rate by benchmark/loadgen.py, which runs
as a child process that never imports JAX.

The exported weights are the start-up program's, with one addition of the
benchmark's own: the batch-norm moving statistics are set to those the
plain reference measures on a seeded calibration batch, as a trained
model's are.  With the start-up values (mean 0, variance 1) activations
grow through the residual stages until the softmax is one-hot, and a
comparison of probabilities would compare nothing.
"""

import http.client
import json
import os
import re
import shutil
import subprocess
import sys
import time

import numpy as np

from benchmark import harness, loadgen

INFER_PATH = "/v1/infer"
HISTOGRAMS = ("serving_queue_seconds", "serving_compute_seconds",
              "serving_total_seconds", "serving_batch_rows")


class Served:
    """The running server and what the benchmark knows of its inputs."""

    def __init__(self, server, body_paths, body_images, sizes, want,
                 fetch_name, work):
        self.server = server
        self.body_paths, self.body_images = body_paths, body_images
        self.sizes = sizes              # request size -> body indices
        self.want = want                # reference probabilities by image
        self.fetch_name, self.work = fetch_name, work
        self.host, self.port = server.address


def start(run):
    """Export, start the server, encode the pool of bodies and compute
    the reference's answers.  All of it is set-up."""
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu.tools import serve_cli

    cfg, wl = run.config, run.workload
    if cfg["compute_dtype"] == "bfloat16":
        fluid.amp.enable_bf16()
    work = harness.work_dir(wl["name"])
    model_dir = os.path.join(work, "model")
    shutil.rmtree(model_dir, ignore_errors=True)
    reference = run.lookup.module("reference", cfg["reference"])
    model = run.lookup.module("models", cfg["builder"])

    with run.clock.phase("build"):
        built = model.build(cfg, None, train=False)
        built["startup"].random_seed = run.seed
    # the pool of distinct images the bodies are cut from: host arrays,
    # rounded to the three decimals the wire format carries
    rs = np.random.RandomState(run.seed)
    counts = {int(s): int(n) for s, n in wl["body_pool"].items()}
    n_images = sum(s * n for s, n in counts.items())
    shape = (cfg["channels"], cfg["image_size"], cfg["image_size"])
    images = np.round(rs.rand(n_images, *shape), 3)

    with run.clock.phase("startup"):
        exe = fluid.Executor(fluid.TPUPlace(0))
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(built["startup"])
    with run.clock.phase("reference"):
        names = built["param_names"]
        params = jax.tree_util.tree_map(scope.get, names)
        calibration = jax.random.uniform(
            jax.random.fold_in(jax.random.PRNGKey(run.seed), 0xCA11),
            (wl["calibration_batch"],) + shape)
        stats = jax.jit(lambda p, x: reference.batch_statistics(
            cfg, p, x))(params, calibration)
        for (_, _, mean_name, var_name), (mean, var) in zip(names["bn"],
                                                            stats):
            scope.set(mean_name, mean)
            scope.set(var_name, var)
        params = jax.tree_util.tree_map(scope.get, names)
        want = np.asarray(jax.jit(lambda p, x: reference.probabilities(
            cfg, p, x))(params, images.astype(np.float32)))
        del params, stats
    with run.clock.phase("export"):
        with fluid.scope_guard(scope):
            fluid.io.save_inference_model(
                model_dir, ["image"], [built["fetch"]], exe,
                main_program=built["main"])
        del scope

    with run.clock.phase("server"):
        buckets = wl["batch_buckets"]
        server = serve_cli.start_server(serve_cli.parse_args([
            "--model_dir", model_dir, "--port", "0",
            "--max_batch", str(wl["max_batch"]),
            "--batch_buckets", ",".join(map(str, buckets))]))
    try:
        engine = server.engine
        if engine.last_warmup_stats is None:
            raise RuntimeError("the server started without warming its "
                               "buckets")
        if engine.param_devices() != {run.devices[0]}:
            raise RuntimeError("engine parameters are on %s, not on %s"
                               % (engine.param_devices(), run.devices[0]))
        with run.clock.phase("bodies"):
            body_dir = os.path.join(work, "bodies")
            shutil.rmtree(body_dir, ignore_errors=True)
            os.makedirs(body_dir)
            body_paths, body_images, sizes = [], [], {}
            at = 0
            for size, n in sorted(counts.items()):
                for _ in range(n):
                    index = list(range(at, at + size))
                    at += size
                    path = os.path.join(body_dir,
                                        "%d.json" % len(body_paths))
                    with open(path, "w") as f:
                        f.write(json.dumps({"inputs": {
                            "image": images[index].tolist()}}))
                    sizes.setdefault(size, []).append(len(body_paths))
                    body_paths.append(path)
                    body_images.append(index)
        return Served(server, body_paths, body_images, sizes, want,
                      engine.fetch_names[0], work)
    except BaseException:
        server.shutdown()
        raise


def post(served, body_path):
    conn = http.client.HTTPConnection(served.host, served.port, timeout=300)
    try:
        with open(body_path, "rb") as f:
            conn.request("POST", INFER_PATH, f.read(),
                         {"Content-Type": "application/json"})
        resp = conn.getresponse()
        payload = json.loads(resp.read())
        if resp.status != 200:
            raise RuntimeError("POST answered %d: %r"
                               % (resp.status, payload))
        return np.asarray(payload["outputs"][served.fetch_name])
    finally:
        conn.close()


def scrape(served):
    """sum and count of the serving histograms, from GET /metrics."""
    conn = http.client.HTTPConnection(served.host, served.port, timeout=60)
    try:
        conn.request("GET", "/metrics")
        text = conn.getresponse().read().decode()
    finally:
        conn.close()
    out = {}
    for name in HISTOGRAMS:
        for part in ("sum", "count"):
            m = re.search(r"^%s_%s (\S+)$" % (name, part), text, re.M)
            out[name + "_" + part] = float(m.group(1)) if m else 0.0
    m = re.search(r"^serving_compile_cache_miss_total (\S+)$", text, re.M)
    out["compile_misses"] = float(m.group(1)) if m else 0.0
    return out


def offer(run, served, rate, seconds, seed, label, keep=0, on_go=None):
    """One open-loop window: the child offers a seeded schedule at `rate`
    for `seconds`; returns its report with the server's histograms
    differenced over the same window.  `on_go` is called at the instant
    the child is told to start."""
    wl = run.workload
    rng = np.random.RandomState(seed)
    schedule = [
        (offset, int(rng.choice(served.sizes[size])))
        for offset, size in loadgen.build_schedule(
            rate, seconds, wl["request_sizes"], seed,
            arrival=wl["arrival"], bursts=wl.get("bursts"))]
    kept = sorted(rng.choice(len(schedule), size=min(keep, len(schedule)),
                             replace=False).tolist())
    plan_path = os.path.join(served.work, "plan-%s.json" % label)
    report_path = os.path.join(served.work, "report-%s.json" % label)
    with open(plan_path, "w") as f:
        json.dump({"host": served.host, "port": served.port,
                   "path": INFER_PATH, "bodies": served.body_paths,
                   "schedule": schedule, "senders": wl["senders"],
                   "timeout_s": wl["timeout_s"], "keep": kept,
                   "report": report_path}, f)
    child = subprocess.Popen(
        [sys.executable, os.path.abspath(loadgen.__file__), plan_path],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    try:
        if child.stdout.readline().strip() != "ready":
            raise RuntimeError("the load generator did not start")
        before = (scrape(served), run.compiles.snapshot())
        if on_go is not None:
            on_go()
        child.stdin.write("go\n")
        child.stdin.flush()
        child.wait(timeout=seconds + wl["timeout_s"] + 60)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    if child.returncode != 0:
        raise RuntimeError("the load generator exited with %d"
                           % child.returncode)
    after = scrape(served)
    with open(report_path) as f:
        report = json.load(f)
    report["server"] = {k: after[k] - before[0][k] for k in after}
    report["compiles"] = run.compiles.since(before[1])["compiles"]
    report["schedule"] = schedule
    return report


def summarise(report):
    records = report["records"]
    ok = [r for r in records if r["status"] == 200]
    latency = sorted((r["done"] - r["due"]) * 1e3 for r in ok)
    late = sorted((r["sent"] - r["due"]) * 1e3 for r in records)
    service = [(r["done"] - r["sent"]) * 1e3 for r in ok]
    return {
        "attempted": len(records), "failed": len(records) - len(ok),
        "p50_ms": loadgen.percentile(latency, 50.0),
        "p95_ms": loadgen.percentile(latency, 95.0),
        "late_p95_ms": loadgen.percentile(late, 95.0),
        "service_mean_ms": float(np.mean(service)) if service else None,
        "seconds": report["end"] - report["t0"],
        "ok_rps": len(ok) / (report["end"] - report["t0"]),
    }


def check_setup(run, served):
    """Before the window, one request at a time so that nothing is
    coalesced: image 0 of a body is answered identically in two requests
    of different size that pad to the same bucket."""
    wl = run.workload
    buckets = sorted(wl["batch_buckets"])

    def bucket(n):
        return next(b for b in buckets if n <= b)

    by_bucket = {}
    for size in sorted(served.sizes):
        by_bucket.setdefault(bucket(size), []).append(size)
    checks = {}
    for b, same in by_bucket.items():
        if len(same) < 2:
            continue
        small, large = same[0], same[-1]
        body = served.body_paths[served.sizes[large][0]]
        with open(body) as f:
            rows = json.load(f)["inputs"]["image"]
        cut = os.path.join(served.work, "identity.json")
        with open(cut, "w") as f:
            f.write(json.dumps({"inputs": {"image": rows[:small]}}))
        a, c = post(served, cut)[0], post(served, body)[0]
        checks["image 0 answered identically in requests of %d and %d "
               "(bucket %d)" % (small, large, b)] = \
            bool(np.abs(a - c).max() < 1e-6)
    return checks


def check_answers(run, served, report):
    """The kept answers against the reference's inference-mode forward
    pass on the same images, and every row a distribution."""
    tol = run.config["reference_tolerance"]["prob_abs"]
    worst, rows_ok, n = 0.0, True, 0
    for rec in report["records"]:
        if "answer" not in rec:
            continue
        got = np.asarray(json.loads(rec["answer"])["outputs"]
                         [served.fetch_name])
        want = served.want[served.body_images[rec["body"]]]
        if got.shape != want.shape or not np.isfinite(got).all():
            return {"answers have the reference's shape and are finite":
                    False}
        worst = max(worst, float(np.abs(got - want).max()))
        rows_ok = rows_ok and bool(
            np.abs(got.sum(axis=1) - 1).max() < 2e-2)
        n += 1
    return {
        "%d kept answers within %.1e of the reference's probabilities "
        "(worst %.2e, largest probability %.2e)"
        % (n, tol, worst, float(served.want.max())): n > 0 and worst <= tol,
        "every row of them sums to 1": rows_ok,
    }


def run(run):
    wl = run.workload
    served = start(run)
    try:
        with run.clock.phase("checks"):
            checks = check_setup(run, served)
        setup = run.compiles.snapshot()
        report = offer(run, served, wl["rate_rps"], run.seconds, run.seed,
                       "window", keep=wl["checked_answers"],
                       on_go=run.start_window)
        s = summarise(report)
        facts = run.facts
        facts.update(
            generator=s, server=report["server"],
            compiles_in_window=report["compiles"]
            + int(report["server"]["compile_misses"]),
            setup_compile_s=setup["seconds"],
            setup_cache_misses=setup["misses"], chips=len(run.devices))
        print("window: %d requests in %.3f s, %d failed, p50 %.3f ms, "
              "p95 %.3f ms, generator late p95 %.3f ms, %.2f ok/s"
              % (s["attempted"], s["seconds"], s["failed"], s["p50_ms"],
                 s["p95_ms"], s["late_p95_ms"], s["ok_rps"]), flush=True)
        checks.update(check_answers(run, served, report))
        if run.trace:
            with run.tracing():
                origin = time.perf_counter()
                traced = offer(run, served, wl["rate_rps"],
                               wl.get("trace_seconds", 3.0), run.seed + 1,
                               "traced")
            run.host_spans = [
                (r["sent"] - origin, r["done"] - origin, "bench/request")
                for r in traced["records"]]
            facts["compiles_in_window"] += traced["compiles"] + int(
                traced["server"]["compile_misses"])
            t = summarise(traced)
            print("traced window: %d requests, p50 %.3f ms (untraced %.3f)"
                  % (t["attempted"], t["p50_ms"], s["p50_ms"]), flush=True)
        for text, ok in checks.items():
            print("check %s: %s" % ("ok  " if ok else "FAIL", text),
                  flush=True)
        run.correct = all(checks.values())
        run.attempted, run.failed = s["attempted"], s["failed"]
        run.end_to_end["serve_p50_ms"] = (s["p50_ms"], "ms")
        run.end_to_end["serve_p95_ms"] = (s["p95_ms"], "ms")
        facts["memory_peak_bytes"] = harness.memory_peak_bytes(run.devices)
    finally:
        served.server.shutdown()
