"""pmem — HBM memory observability CLI (paddle_tpu.obs.mem).

    # the static memory timeline of a model's training program:
    # per-op live bytes, peak op, top resident buffers blamed to
    # their defining ops (+ a Chrome-trace counter track)
    pmem timeline --model lenet5 --batch 128 [--trace-out mem.json]

    # static-vs-XLA drift: run one step under attribution (or join a
    # saved --store dump) and report actual/static per segment
    pmem drift --model lenet5
    pmem drift --store mem_store.json

    # buffer-donation audit: param/optimizer-state buffers that are
    # dead-after-use but NOT donated, with bytes reclaimable
    pmem audit --model lenet5

    # the CI entry point (scripts/ci.sh, scripts/smoke.sh)
    pmem --selftest

`--selftest` proves the whole loop on CPU: timeline render + counter
track (validated as Chrome trace JSON), a REAL lenet5 step whose
static peak joins XLA's `memory_analysis()` actuals into a drift
report, a donation audit that finds a deliberately-forked Adam moment
slot (and nothing on the clean program), and a forced-tiny-budget OOM
whose flight bundle carries
the same top blamed buffer the static timeline names.
"""

import argparse
import json
import os
import sys
import tempfile


def parse_args(argv=None):
    p = argparse.ArgumentParser(prog="pmem")
    p.add_argument("cmd", nargs="?",
                   choices=["timeline", "drift", "audit"],
                   help="operator command (or use --selftest)")
    p.add_argument("--selftest", action="store_true",
                   help="timeline + drift join + donation audit + "
                        "OOM flight-bundle certification (CPU)")
    p.add_argument("--model", default="lenet5",
                   help="model name (paddle_tpu.models.image_train)")
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--image-size", type=int, default=None)
    p.add_argument("--class-dim", type=int, default=None)
    p.add_argument("--bf16", action="store_true", default=True)
    p.add_argument("--f32", dest="bf16", action="store_false")
    p.add_argument("--top", type=int, default=8,
                   help="timeline: blamed buffers to list")
    p.add_argument("--trace-out", default=None,
                   help="timeline: write the Chrome-trace counter "
                        "track here (co-loadable with obs exports)")
    p.add_argument("--store", default=None,
                   help="drift: join a saved obs.mem store dump "
                        "instead of running a step in-process")
    p.add_argument("--store-out", default=None,
                   help="drift: also dump this process's capture "
                        "store for later offline joins")
    p.add_argument("--json", action="store_true",
                   help="machine-readable output")
    return p.parse_args(argv)


def _build_train(model, batch, image_size=None, class_dim=None):
    """(main, startup, loss_var): the models.image_train recipe."""
    from paddle_tpu.models import image_train

    try:
        build = image_train.builder(model, image_size, class_dim)
    except ValueError as e:
        raise SystemExit("pmem: %s" % e)
    main, startup, loss_name = build(batch)
    return main, startup, main.global_block().var(loss_name)


def _feeds(model, batch, image_size=None, class_dim=None):
    import numpy as np

    from paddle_tpu.models.image_train import MODELS

    spec = MODELS[model]
    size = int(image_size or spec["image_size"])
    classes = int(class_dim or spec["class_dim"])
    rs = np.random.RandomState(0)
    return {
        "image": rs.rand(batch, spec["channels"], size,
                         size).astype("float32"),
        "label": rs.randint(0, classes, (batch, 1)).astype("int64"),
    }


def _amp(bf16):
    import paddle_tpu.fluid as fluid

    if bf16:
        fluid.amp.enable_bf16()
    else:
        fluid.amp.disable_bf16()


# ---------------------------------------------------------------------------
# commands
# ---------------------------------------------------------------------------

def cmd_timeline(args):
    from paddle_tpu.obs import mem as obs_mem

    _amp(args.bf16)
    main, _startup, loss = _build_train(args.model, args.batch,
                                        args.image_size,
                                        args.class_dim)
    tl = obs_mem.program_timeline(main, fetches=[loss.name],
                                  top_n=args.top)
    if args.trace_out:
        obs_mem.timeline_chrome_trace(tl, path=args.trace_out)
    if args.json:
        print(json.dumps(tl, sort_keys=True))
    else:
        print("[pmem] %s batch %d (%s):"
              % (args.model, args.batch,
                 "bf16-act" if args.bf16 else "f32"))
        print(obs_mem.render_timeline(tl))
        if args.trace_out:
            print("[pmem] counter track written: %s (load next to an "
                  "obs_dump trace in Perfetto)" % args.trace_out)
    return 0


def cmd_audit(args):
    from paddle_tpu.obs import mem as obs_mem

    _amp(args.bf16)
    main, _startup, loss = _build_train(args.model, args.batch,
                                        args.image_size,
                                        args.class_dim)
    audit = obs_mem.audit_donation(main, fetches=[loss.name])
    if args.json:
        print(json.dumps(audit, sort_keys=True))
    else:
        print("[pmem] %s batch %d:" % (args.model, args.batch))
        print(obs_mem.render_audit(audit))
    return audit["reclaimable_bytes"] > 0 and 1 or 0


def _capture_one_step(args):
    """Run one real training step with attribution forced so the
    executor registers the static side and publish_compile_stats
    supplies the XLA side of every segment's drift join."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.obs import health as obs_health

    _amp(args.bf16)
    main, startup, loss = _build_train(args.model, args.batch,
                                       args.image_size,
                                       args.class_dim)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup, scope=scope)
        with obs_health.force_attribution():
            exe.run(main, feed=_feeds(args.model, args.batch,
                                      args.image_size,
                                      args.class_dim),
                    fetch_list=[loss], scope=scope)
    return main, loss


def cmd_drift(args):
    from paddle_tpu.obs import mem as obs_mem

    if args.store:
        store = obs_mem.load_store(args.store)
    else:
        _capture_one_step(args)
        store = None  # this process's live capture
    rep = obs_mem.drift_report(store)
    if args.store_out and not args.store:
        obs_mem.dump_store(args.store_out)
    if args.json:
        print(json.dumps(rep, sort_keys=True))
    else:
        print("[pmem] " + ("store %s" % args.store if args.store
                           else "%s batch %d, one captured step"
                           % (args.model, args.batch)))
        print(obs_mem.render_drift(rep))
    return 0 if rep["n"] else 2


# ---------------------------------------------------------------------------
# selftest
# ---------------------------------------------------------------------------

def _fork_adam_slot(program):
    """Deliberately break one Adam update's Moment1Out alias (the
    H003 fork class): the audit must name the stranded moment buffer
    as reclaimable."""
    from paddle_tpu.core.desc import VarDesc

    bd = program.desc.block(0)
    for od in bd.ops:
        if od.type == "adam":
            m1 = od.input("Moment1")[0]
            fork = m1 + "__fork"
            src = bd.vars[m1]
            bd.vars[fork] = VarDesc(fork, src.type, src.dtype,
                                    src.shape, persistable=True)
            od.outputs["Moment1Out"] = [fork]
            return m1
    raise AssertionError("no adam op to fork")


def _build_adam_toy():
    import paddle_tpu.fluid as fluid

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[64], dtype="float32")
        h = fluid.layers.fc(input=x, size=32)
        cost = fluid.layers.mean(x=h)
        fluid.optimizer.AdamOptimizer(
            learning_rate=0.01).minimize(cost)
    return main, startup, cost


def selftest(args):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu.obs import flight as obs_flight
    from paddle_tpu.obs import mem as obs_mem
    from paddle_tpu.tools import obs_dump
    from paddle_tpu.utils import flags as pt_flags

    workdir = tempfile.mkdtemp(prefix="paddle_pmem_")

    # --- leg 1: static timeline + counter-track export -----------------
    main, startup, loss = _build_train("lenet5", 8)
    tl = obs_mem.program_timeline(main, fetches=[loss.name], top_n=5)
    assert len(tl["series"]) == tl["ops"] and tl["ops"] > 0, tl
    assert tl["peak_bytes"] > 0 and tl["peak_op"] is not None, tl
    assert tl["top_buffers"], "no blamed buffers at the peak"
    assert tl["top_buffers"][0]["def_op_type"], tl["top_buffers"][0]
    rendered = obs_mem.render_timeline(tl)
    assert "<- peak" in rendered and "top buffers" in rendered
    trace_path = os.path.join(workdir, "mem_trace.json")
    obs_mem.timeline_chrome_trace(tl, path=trace_path)
    events = obs_dump.validate_chrome_trace(trace_path)
    assert any(ev["ph"] == "C" for ev in events), \
        "no counter events in the mem trace"

    # --- leg 2: drift join on a real captured step ---------------------
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    from paddle_tpu.obs import health as obs_health

    feeds = _feeds("lenet5", 8)
    with fluid.scope_guard(scope):
        exe.run(startup, scope=scope)
        with obs_health.force_attribution():
            exe.run(main, feed=feeds, fetch_list=[loss], scope=scope)
    rep = obs_mem.drift_report()
    joined = [r for r in rep["segments"] if r["ratio"]]
    assert joined, "no static-vs-XLA joined segments:\n%s" \
        % obs_mem.render_drift(rep)
    assert rep["median_ratio"] and rep["median_ratio"] > 0
    store_path = os.path.join(workdir, "mem_store.json")
    obs_mem.dump_store(store_path)
    offline = obs_mem.drift_report(obs_mem.load_store(store_path))
    assert offline["n"] == rep["n"], "offline store join drifted"

    # --- leg 3: donation audit — clean program, then a forked slot -----
    adam_main, _adam_startup, adam_cost = _build_adam_toy()
    clean = obs_mem.audit_donation(adam_main,
                                   fetches=[adam_cost.name])
    assert clean["donated"] and not clean["reclaimable"], \
        obs_mem.render_audit(clean)
    forked_name = _fork_adam_slot(adam_main)
    broken = obs_mem.audit_donation(adam_main,
                                    fetches=[adam_cost.name])
    hits = [r for r in broken["reclaimable"]
            if r["name"] == forked_name]
    assert hits and hits[0]["bytes"] > 0 \
        and hits[0]["kind"] == "optimizer_state", \
        obs_mem.render_audit(broken)
    # the refusal is explained, not just priced: the forked slot
    # carries its A-code (analysis/alias.py) in entry and rendering
    assert hits[0].get("code") == "A001", hits[0]
    assert "A001" in obs_mem.render_audit(broken)
    # the plan closes what the audit prices: flag off, every donated
    # buffer moves to reclaimable — the off/auto delta IS the win
    off = obs_mem.audit_donation(adam_main, fetches=[adam_cost.name],
                                 mode="off")
    assert not off["donated"], obs_mem.render_audit(off)
    assert off["reclaimable_bytes"] == (broken["reclaimable_bytes"]
                                        + broken["donated_bytes"]), \
        (off["reclaimable_bytes"], broken["reclaimable_bytes"],
         broken["donated_bytes"])

    # --- leg 4: forced-tiny-budget OOM -> flight bundle with blame -----
    recorder = obs_flight.install(out_dir=workdir, capacity=8)
    oom_scope = fluid.Scope()
    oom_exe = fluid.Executor(fluid.CPUPlace())
    budget_prev = pt_flags.get_flag("mem_budget_gb")
    try:
        with fluid.scope_guard(oom_scope):
            oom_exe.run(startup, scope=oom_scope)
            pt_flags.set_flag("mem_budget_gb", 1e-6)
            try:
                oom_exe.run(main, feed=feeds, fetch_list=[loss],
                            scope=oom_scope, use_program_cache=False)
                raise AssertionError("tiny mem budget did not trip "
                                     "the pre-flight")
            except obs_mem.MemoryBudgetError as exc:
                assert "RESOURCE_EXHAUSTED" in str(exc), exc
    finally:
        pt_flags.set_flag("mem_budget_gb", budget_prev)
        obs_flight.uninstall()
    bundle = recorder.last_bundle_path
    assert bundle and os.path.exists(bundle), "no OOM flight bundle"
    with open(bundle) as f:
        doc = json.load(f)
    oom_notes = [n["oom"] for n in doc.get("notes", [])
                 if n.get("oom")]
    assert oom_notes, "flight bundle carries no oom note"
    top = oom_notes[0]["top_buffers"]
    assert top and top[0]["name"] == tl["top_buffers"][0]["name"], \
        "bundle's top blamed buffer %r != static timeline's %r" \
        % (top and top[0]["name"], tl["top_buffers"][0]["name"])
    rendered_bundle = obs_dump.render_flight(bundle)
    assert "OOM post-mortem" in rendered_bundle

    print("[pmem] selftest green: timeline %d op(s) peak %.2f MiB at "
          "op %s (%s), counter track %d event(s); drift joined %d "
          "segment(s) median ratio %.3f; donation "
          "audit: clean program donates %d buffer(s), forked Adam "
          "slot %r flagged A001 with %.1f KiB reclaimable and "
          "FLAGS_donation=off surrenders the full delta; OOM bundle "
          "%s blames %r"
          % (tl["ops"], tl["peak_bytes"] / 2**20, tl["peak_op"],
             tl["peak_op_type"], len(events), rep["n"],
             rep["median_ratio"], len(clean["donated"]),
             forked_name, hits[0]["bytes"] / 1024.0, bundle,
             top[0]["name"]),
          flush=True)
    return 0


def main(argv=None):
    args = parse_args(argv)
    if args.selftest:
        return selftest(args)
    if args.cmd == "timeline":
        return cmd_timeline(args)
    if args.cmd == "drift":
        return cmd_drift(args)
    if args.cmd == "audit":
        return cmd_audit(args)
    raise SystemExit("nothing to do: pass timeline|drift|audit or "
                     "--selftest")


if __name__ == "__main__":
    sys.exit(main())
