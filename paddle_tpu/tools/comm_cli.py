"""pcomm — collective/communication observability CLI
(paddle_tpu.obs.comm).

    # per-bucket comm truth + the overlap-efficiency split on a
    # simulated dp=8 mesh (JAX_PLATFORMS=cpu; virtual devices are
    # provisioned automatically)
    pcomm report [--dp 8] [--bucket-kb 24] [--reps 3] \\
                 [--trace-out comm_trace.json] [--json]

    # cross-host merge: pull every live /obsspan/* window from the
    # master's lease store (workers push them via
    # FleetReporter(span_window=N)), estimate per-host clock offsets
    # over the same store, emit ONE Perfetto trace with a process
    # track per host on a common timebase
    pcomm merge --master host:port --out merged_trace.json
    pcomm merge --windows w1.json w2.json --out merged_trace.json

    # the CI entry point (scripts/ci.sh, scripts/smoke.sh)
    pcomm --selftest

`--selftest` proves the loop on the 8-device simulated mesh: the
traced bucket schedule nests one `comm/bucket` span per bucket in
last-produced-first order with byte labels; `overlap_report` splits
step wall into exposed-vs-hidden comm against the reduction-elided
twin (and a gspmd-fallback trainer is refused WITHOUT an exposed_s);
and a real master lease store carries span windows + the NTP-style
clock exchange (a ClockResponder with 0.5s injected skew is recovered
and the merged trace re-bases by it, validating as a Chrome trace).
"""

import argparse
import json
import os
import sys
import tempfile


def parse_args(argv=None):
    p = argparse.ArgumentParser(prog="pcomm")
    p.add_argument("cmd", nargs="?", choices=["report", "merge"],
                   help="operator command (or use --selftest)")
    p.add_argument("--selftest", action="store_true",
                   help="spans + overlap split + cross-host merge "
                        "certification (CPU, 8 virtual devices)")
    # report
    p.add_argument("--dp", type=int, default=8,
                   help="report: data-parallel mesh width")
    p.add_argument("--bucket-kb", type=int, default=24,
                   help="report: ring-allreduce bucket size in KiB "
                        "(small enough that the probe MLP fills "
                        "several buckets)")
    p.add_argument("--reps", type=int, default=3,
                   help="report: timed repetitions per measurement")
    p.add_argument("--trace-out", default=None,
                   help="report: write this process's span trace "
                        "here (Chrome trace JSON)")
    # merge
    p.add_argument("--master", default=None,
                   help="merge: master host:port whose /obsspan/* "
                        "windows to pull")
    p.add_argument("--windows", nargs="*", default=None,
                   help="merge: span-window JSON files (offline "
                        "merge; skips the clock exchange)")
    p.add_argument("--out", default=None,
                   help="merge: merged trace path (default "
                        "comm_merged_trace.json)")
    p.add_argument("--no-clock-sync", action="store_true",
                   help="merge: skip the clock-offset exchange (rely "
                        "on host wall clocks)")
    p.add_argument("--clock-reps", type=int, default=3,
                   help="merge: ping/pong exchanges per host")
    p.add_argument("--clock-timeout", type=float, default=3.0,
                   help="merge: seconds to wait for each pong")
    p.add_argument("--json", action="store_true",
                   help="machine-readable output")
    return p.parse_args(argv)


def _ensure_virtual_devices(n=8):
    """Provision n virtual CPU devices BEFORE jax imports — the report
    and selftest paths need a real multi-device mesh with no
    accelerator attached."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=%d"
            % int(n)).strip()


# ---------------------------------------------------------------------------
# probe model (the test_spmd MLP recipe: big first layer, small head,
# so a KB-scale bucket cap yields several buckets in reduce order)
# ---------------------------------------------------------------------------

BATCH, DIM, HIDDEN, CLASSES = 16, 8, 1024, 4


def _build_mlp():
    import paddle_tpu.fluid as fluid

    fluid.framework.reset_unique_name()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[BATCH, DIM],
                              dtype="float32",
                              append_batch_size=False)
        label = fluid.layers.data(name="label", shape=[BATCH, 1],
                                  dtype="int64",
                                  append_batch_size=False)
        h = fluid.layers.fc(input=x, size=HIDDEN, act="relu")
        logits = fluid.layers.fc(input=h, size=CLASSES, act=None)
        loss = fluid.layers.softmax_with_cross_entropy(logits, label)
        avg = fluid.layers.mean(loss)
        fluid.optimizer.MomentumOptimizer(
            learning_rate=0.1, momentum=0.9).minimize(avg)
    return main, startup, avg


def _feeds(step=0):
    import numpy as np

    rs = np.random.RandomState(100 + step)
    return {
        "x": rs.rand(BATCH, DIM).astype(np.float32),
        "label": rs.randint(0, CLASSES,
                            size=(BATCH, 1)).astype(np.int64),
    }


def _make_trainer(mesh, bucket_bytes):
    from paddle_tpu.spmd import SpmdTrainer

    main, startup, avg = _build_mlp()
    return SpmdTrainer(main, startup, feed_names=["x", "label"],
                       fetch_names=[avg.name], mesh=mesh,
                       bucket_bytes=bucket_bytes).init()


# ---------------------------------------------------------------------------
# report
# ---------------------------------------------------------------------------

def _render_report(rep, bucket_report, drift):
    lines = []
    if not rep["supported"]:
        lines.append("overlap NOT measured: step_mode=%s (%s)"
                     % (rep["step_mode"],
                        rep["overlap_fallback_reason"]))
        return "\n".join(lines)
    lines.append("per-bucket ring truth (allreduce over %s, %d-way):"
                 % (bucket_report["axis"], bucket_report["n"]))
    lines.append("  %-7s %10s %10s %9s %9s %7s"
                 % ("bucket", "bytes", "wire", "pred ms",
                    "meas ms", "ratio"))
    for r in bucket_report["buckets"]:
        lines.append("  %-7d %10d %10d %9.3f %9.3f %7s"
                     % (r["bucket"], r["bytes"], r["wire_bytes"],
                        r["pred_s"] * 1e3, r["measured_s"] * 1e3,
                        "%.2f" % r["ratio"] if r["ratio"] else "-"))
    lines.append("overlap split over %d rep(s):" % rep["reps"])
    lines.append("  step %.3f ms = compute %.3f ms + exposed comm "
                 "%.3f ms" % (rep["step_s"] * 1e3,
                              rep["compute_s"] * 1e3,
                              rep["exposed_s"] * 1e3))
    eff = rep["overlap_efficiency"]
    lines.append("  standalone comm %.3f ms -> hidden %.3f ms "
                 "(overlap efficiency %s)"
                 % (rep["comm_s"] * 1e3, rep["hidden_s"] * 1e3,
                    "%.1f%%" % (eff * 100) if eff is not None
                    else "n/a"))
    if drift["median_ratio"]:
        lines.append("analytic-floor drift: median measured/pred "
                     "%.2f over %d bucket(s)"
                     % (drift["median_ratio"], drift["n"]))
    return "\n".join(lines)


def cmd_report(args):
    from paddle_tpu.obs import comm as obs_comm
    from paddle_tpu.obs import trace as obs_trace
    from paddle_tpu.parallel import make_mesh

    obs_trace.enable()
    mesh = make_mesh(n_devices=args.dp, dp=args.dp)
    trainer = _make_trainer(mesh, args.bucket_kb << 10)
    feeds = _feeds(0)
    trainer.step(feeds)                 # trace the bucket schedule
    bucket_report = obs_comm.measure_trainer_comm(trainer,
                                                  reps=args.reps)
    rep = obs_comm.overlap_report(trainer, feeds, reps=args.reps,
                                  bucket_report=bucket_report)
    drift = obs_comm.drift_report(bucket_report)
    if args.json:
        out = dict(rep)
        out.pop("spans", None)
        print(json.dumps({"overlap": out, "drift": drift},
                         sort_keys=True))
    else:
        print("[pcomm] mlp probe, dp=%d, bucket %d KiB:"
              % (args.dp, args.bucket_kb))
        print(_render_report(rep, bucket_report, drift))
    if args.trace_out:
        obs_trace.export_chrome_trace(args.trace_out)
        if not args.json:
            print("[pcomm] span trace written: %s" % args.trace_out)
    return 0 if rep["supported"] else 2


# ---------------------------------------------------------------------------
# merge
# ---------------------------------------------------------------------------

def cmd_merge(args):
    from paddle_tpu.obs import comm as obs_comm

    offsets = {}
    if args.windows:
        windows = {}
        for path in args.windows:
            with open(path) as f:
                payload = json.load(f)
            windows[payload.get("host") or path] = payload
    elif args.master:
        windows = obs_comm.collect_span_windows(args.master)
        if windows and not args.no_clock_sync:
            offsets = obs_comm.estimate_clock_offsets(
                args.master, sorted(windows), reps=args.clock_reps,
                timeout_s=args.clock_timeout)
    else:
        raise SystemExit("merge needs --master or --windows")
    merged = obs_comm.merge_windows(windows, offsets)
    out = args.out or "comm_merged_trace.json"
    tmp = out + ".tmp"
    with open(tmp, "w") as f:
        json.dump(merged, f, sort_keys=True)
    os.replace(tmp, out)
    hosts = merged["otherData"]["hosts"]
    if args.json:
        print(json.dumps({"out": out, "hosts": hosts,
                          "events": len(merged["traceEvents"]),
                          "clock_offsets":
                              merged["otherData"]["clock_offsets"]},
                         sort_keys=True))
    else:
        print("[pcomm] merged %d host track(s) (%s) into %s (%d "
              "events); clock offsets: %s"
              % (len(hosts), ", ".join(hosts) or "none", out,
                 len(merged["traceEvents"]),
                 {h: ("%.3fs" % o if o is not None else "?")
                  for h, o in
                  merged["otherData"]["clock_offsets"].items()}
                 or "skipped"))
    return 0 if hosts else 2


# ---------------------------------------------------------------------------
# selftest
# ---------------------------------------------------------------------------

def _selftest_spans_and_overlap(workdir):
    """Legs 1-2: traced schedule shape + the overlap-efficiency split
    (and the fallback trainer refused without an exposed_s)."""
    from paddle_tpu.obs import comm as obs_comm
    from paddle_tpu.obs import flight as obs_flight
    from paddle_tpu.obs import trace as obs_trace
    from paddle_tpu.parallel import make_mesh
    from paddle_tpu.spmd import overlap as spmd_overlap

    obs_trace.enable()
    mesh = make_mesh(n_devices=8, dp=8)
    trainer = _make_trainer(mesh, 24 << 10)
    feeds = _feeds(0)
    trainer.step(feeds)
    assert trainer.step_mode == "overlap-dp", trainer.step_mode

    # schedule shape: >= 2 buckets, flattened names in EXACTLY the
    # last-produced-first (DDP) order the program's seam defines
    sched = obs_comm.last_schedule()
    assert sched and sched["collective"] == "allreduce", sched
    assert sched["n_buckets"] >= 2, sched
    _split, grad_order = spmd_overlap._split_point(
        list(trainer.main_program.desc.block(0).ops))
    flat = [n for b in sched["buckets"] for n in b["names"]]
    want = [g for g in reversed(grad_order) if g in set(flat)]
    assert flat == want, (flat, want)

    # trace nesting: one parent, one comm/bucket span per bucket with
    # byte labels, bracketed by launch/complete instants, plus the
    # reduce seam marker from the overlap schedule
    evs = obs_trace.events()
    parents = [e for e in evs
               if e.get("name") == "comm/bucketed_allreduce"]
    assert parents and parents[0]["args"]["n_buckets"] \
        == sched["n_buckets"], parents
    bspans = [e for e in evs if e.get("name") == "comm/bucket"]
    assert len(bspans) == sched["n_buckets"], evs
    for e in bspans:
        assert e["args"]["bytes"] > 0 and e["args"]["names"] >= 1, e
    assert [e["args"]["first"] for e in bspans] \
        == [b["names"][0] for b in sched["buckets"]]
    launches = [e for e in evs if e.get("name") == "comm/bucket_launch"]
    completes = [e for e in evs
                 if e.get("name") == "comm/bucket_complete"]
    assert len(launches) == len(completes) == sched["n_buckets"]
    assert any(e.get("name") == "comm/reduce_seam" for e in evs)

    # overlap truth: the split is internally consistent and published
    bucket_report = obs_comm.measure_trainer_comm(trainer, reps=2)
    assert bucket_report and len(bucket_report["buckets"]) >= 2
    for r in bucket_report["buckets"]:
        assert r["measured_s"] > 0 and r["pred_s"] > 0, r
    rep = obs_comm.overlap_report(trainer, feeds, reps=2,
                                  bucket_report=bucket_report)
    assert rep["supported"] and rep["step_s"] > 0 \
        and rep["compute_s"] > 0 and rep["comm_s"] > 0, rep
    assert rep["exposed_s"] >= 0 \
        and 0.0 <= rep["overlap_efficiency"] <= 1.0, rep
    assert abs(rep["exposed_s"] + rep["hidden_s"] - rep["comm_s"]) \
        < 1e-9 or rep["exposed_s"] >= rep["comm_s"], rep

    # satellite: the trainer stamped this worker's identity for any
    # future flight bundle; a dump carries it
    ctx = obs_flight.host_context()
    assert ctx.get("process_index") == 0 \
        and ctx.get("mesh_axes", {}).get("dp") == 8 \
        and ctx.get("plan_fingerprint") \
        == trainer.plan.fingerprint(), ctx
    recorder = obs_flight.install(out_dir=workdir, capacity=8)
    try:
        bundle = recorder.dump(reason="pcomm-selftest")
    finally:
        obs_flight.uninstall()
    with open(bundle) as f:
        doc = json.load(f)
    assert doc["host_context"]["plan_fingerprint"] \
        == trainer.plan.fingerprint(), doc.get("host_context")

    # fallback trainer (dp=4,mp=2 mesh): overlap refused, and the
    # report carries NO exposed_s — it can never enter the overlap
    # baseline
    mesh2 = make_mesh(n_devices=8, dp=4, mp=2)
    trainer2 = _make_trainer(mesh2, 24 << 10)
    trainer2.step(feeds)
    assert trainer2.step_mode == "gspmd" \
        and trainer2.overlap_fallback_reason
    rep2 = obs_comm.overlap_report(trainer2, feeds, reps=2)
    assert not rep2["supported"] and "exposed_s" not in rep2 \
        and rep2["overlap_fallback_reason"], rep2
    return rep, bucket_report


def _selftest_merge(workdir):
    """Leg 3: span windows + clock exchange + merged trace over a
    REAL master lease store."""
    from paddle_tpu import native
    from paddle_tpu.obs import comm as obs_comm
    from paddle_tpu.obs import fleet as obs_fleet
    from paddle_tpu.obs import registry as obs_registry
    from paddle_tpu.tools.obs_dump import validate_chrome_trace

    master = native.Master()
    addr = "127.0.0.1:%d" % master.port
    responder = None
    reporter = None
    try:
        # hostA rides the FleetReporter (snapshot + span window in one
        # push); hostB is a bare push with a skewed clock responder
        reporter = obs_fleet.FleetReporter(addr, host="hostA",
                                           interval_s=60.0,
                                           span_window=256)
        assert reporter.push_once() \
            and reporter._span_lease is not None
        assert obs_comm.push_span_window(addr, host="hostB",
                                         limit=256) is not None
        responder = obs_comm.ClockResponder(addr, host="hostB",
                                            poll_s=0.02,
                                            skew_s=0.5).start()
        offsets = obs_comm.estimate_clock_offsets(
            addr, ["hostB"], reps=3, timeout_s=5.0)
        off = offsets["hostB"]
        assert off is not None and abs(off - 0.5) < 0.2, offsets

        windows = obs_comm.collect_span_windows(addr)
        assert {"hostA", "hostB"} <= set(windows), sorted(windows)
        for w in windows.values():
            assert w["events"] and w["epoch_wall"] > 0, w["host"]
        merged = obs_comm.merge_windows(windows, offsets)
        events = validate_chrome_trace(merged)
        names = {e["args"]["name"] for e in events
                 if e.get("name") == "process_name"}
        assert {"hostA", "hostB"} <= names, names
        assert merged["otherData"]["clock_offsets"]["hostB"] == off
        # the offset actually re-bases: hostA's events shift by ~the
        # recovered skew relative to an uncorrected merge
        plain = obs_comm.merge_windows(windows, None)
        pick = [e for e in merged["traceEvents"]
                if e["pid"] == 1 and e["ph"] == "X"][0]
        pick0 = [e for e in plain["traceEvents"]
                 if e["pid"] == 1 and e["ph"] == "X"][0]
        shift_s = (pick["ts"] - pick0["ts"]) / 1e6
        assert abs(shift_s - off) < 0.05, (shift_s, off)

        # satellite: the aggregator publishes per-host snapshot age
        # and retires it when the host's lease dies
        agg = obs_fleet.FleetAggregator()
        assert agg.collect(addr) >= 1
        agg.stragglers()
        age = obs_registry.get_registry().gauge(
            "fleet_snapshot_age_seconds", labelnames=("host",))
        ages = {s["labels"]["host"]: s["value"]
                for s in age.samples()}
        assert "hostA" in ages and ages["hostA"] >= 0, ages
        reporter.stop(unregister=True)
        reporter = None
        agg.collect(addr)
        agg.stragglers()
        assert not any(s["labels"]["host"] == "hostA"
                       for s in age.samples()), age.samples()
        assert "hostA" not in obs_comm.collect_span_windows(addr)
        return len(windows), off, len(events)
    finally:
        if responder is not None:
            responder.stop()
        if reporter is not None:
            reporter.stop(unregister=True)
        master.stop()


def selftest(args):
    import shutil

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    _ensure_virtual_devices(8)
    workdir = tempfile.mkdtemp(prefix="paddle_pcomm_")
    try:
        rep, bucket_report = _selftest_spans_and_overlap(workdir)
        n_hosts, off, n_events = _selftest_merge(workdir)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    print("[pcomm] selftest green: %d bucket(s) traced in reduce "
          "order, overlap split step %.2fms = compute %.2fms + "
          "exposed %.2fms (efficiency %.0f%%); %d host window(s) "
          "merged on a common timebase (%d events, recovered skew "
          "%.3fs)"
          % (len(bucket_report["buckets"]), rep["step_s"] * 1e3,
             rep["compute_s"] * 1e3, rep["exposed_s"] * 1e3,
             rep["overlap_efficiency"] * 100, n_hosts, n_events,
             off),
          flush=True)
    return 0


def main(argv=None):
    args = parse_args(argv)
    if args.selftest:
        return selftest(args)
    if args.cmd == "report":
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        _ensure_virtual_devices(max(8, args.dp))
        return cmd_report(args)
    if args.cmd == "merge":
        return cmd_merge(args)
    raise SystemExit("nothing to do: pass report|merge or --selftest")


if __name__ == "__main__":
    sys.exit(main())
