"""HBM memory observability: static liveness timeline vs XLA actuals,
buffer-donation audit, OOM pre-flight/post-mortems.

The repo *estimates* HBM in the shard analyzer's S005 per-device
peaks, but until this module nothing ever checked those predictions
against what XLA actually allocates.  Five layers close the loop:

  * **static timeline** — `program_timeline(program, fetches)` runs
    the ONE shared liveness walk (`analysis.dataflow
    .liveness_timeline`, the same accounting S005 uses)
    and returns the per-op live-activation-bytes series with the
    top-N buffers resident at the peak, each blamed to its defining
    op.  `render_timeline` draws it, `timeline_chrome_trace` exports
    a Chrome-trace counter track ("ph": "C") co-loadable with the
    obs.trace exports (its timebase is synthetic — one µs per op
    index — so it loads as a profile shape, not wall time).
  * **actuals capture** — the executor registers each jit segment's
    static peak at first build (`register_segment_static`) and
    `obs.health.publish_compile_stats` forwards the segment's
    `compiled.memory_analysis()` numbers here
    (`on_compile_captured`), riding the SAME attribution AOT artifact
    that executes the step — no second compile.  Both land in
    `mem_*{segment=}` gauges plus `jax.local_devices()` live-bytes
    watermarks (`mem_device_*{device=}`; CPU backends report none —
    graceful).
  * **drift report** — `drift_report()` joins static peak vs XLA
    temp+output bytes per segment and publishes
    `mem_estimate_ratio{segment=}`.
  * **donation audit** — `audit_donation(program)` walks the
    registry's `in_place_outputs` declarations against the signature
    the executor will actually donate (`mutated = outputs ∩ reads`
    per jit segment) and reports param/optimizer-state buffers that
    are dead-after-use but NOT donated (forked slots, dropped
    aliases, updates stranded in non-jittable segments), with the
    bytes reclaimable — the measurement half of the buffer-donation
    work.
  * **OOM pre-flight + post-mortem** — `FLAGS_mem_budget_gb` makes
    the executor refuse to compile a program whose static peak busts
    the budget (`preflight` raises `MemoryBudgetError`, an honest
    pre-device RESOURCE_EXHAUSTED), and `oom_context(exc, program)`
    attaches the timeline's top blamed buffers + the last `mem_*`
    gauges to the PR 3 flight bundle for both the pre-flight error
    and a real device RESOURCE_EXHAUSTED (`obs_dump --flight`
    renders the blame table).

Import-cheap by design: fluid/analysis are imported lazily inside
functions, same contract as obs.health — `paddle_tpu.obs` stays free
of framework import cycles.  `tools/mem_cli.py` ("pmem") is the
operator surface; docs/OBSERVABILITY.md "Memory" has the runbook.
"""

import json
import os
import threading
import time

from . import registry as registry_mod
from . import telemetry as telemetry_mod

__all__ = ["program_timeline", "segment_static_peak",
           "render_timeline", "timeline_chrome_trace",
           "register_segment_static", "on_compile_captured",
           "retire_segments", "segments", "xla_program_bytes_total",
           "device_watermarks", "publish_device_watermarks",
           "record_bucket_bytes", "health_memory_section",
           "drift_report", "render_drift", "dump_store", "load_store",
           "audit_donation", "render_audit",
           "MemoryBudgetError", "preflight", "is_oom", "oom_context"]

GiB = float(1 << 30)
MiB = float(1 << 20)

_lock = threading.Lock()
# segment label -> {"static_peak_bytes", "static_peak_op",
#   "top_buffers", "xla": {...}} — the drift join's left and right
# sides, keyed exactly like the executor's xla_* gauges
_segments = {}
# serving bucket -> xla program bytes its warmup compiles added
_bucket_bytes = {}


def _reg():
    return registry_mod.get_registry()


def _seg_gauge(name, help_text):
    return _reg().gauge(name, help_text, labelnames=("segment",))


# ---------------------------------------------------------------------------
# static timeline
# ---------------------------------------------------------------------------

def _bf16_act_now():
    from ..utils import flags

    return bool(flags.get_flag("amp_bf16")
                and flags.get_flag("amp_bf16_act"))


def _byte_policies(bd, bf16_act=None):
    """(activation_bytes, persistable_bytes) name->bytes policies over
    one block's VarDescs: activations at amp element sizes (dynamic
    dims count 1 — a floor, same as S005), persistables at full
    storage size (masters stay f32)."""
    from ..fluid import analysis as fluid_analysis

    if bf16_act is None:
        bf16_act = _bf16_act_now()

    def act_bytes(name):
        vd = bd.vars.get(name)
        if vd is None or vd.persistable or vd.shape is None:
            return 0
        return fluid_analysis._numel(vd.shape) * \
            fluid_analysis._elem_bytes(str(vd.dtype), False, bf16_act)

    def persist_bytes(name):
        vd = bd.vars.get(name)
        if vd is None or not vd.persistable or vd.shape is None:
            return 0
        return fluid_analysis._numel(vd.shape) * \
            fluid_analysis._elem_bytes(str(vd.dtype), True, bf16_act)

    return act_bytes, persist_bytes


def program_timeline(program, fetches=None, top_n=8, bf16_act=None):
    """The static memory timeline of a Program's block 0: per-op live
    activation bytes (the liveness series), the constant
    params+state floor, and the top-N buffers resident at the peak
    blamed to their defining ops.  Pure IR walk — zero devices."""
    from ..analysis.dataflow import liveness_timeline

    desc = getattr(program, "desc", program)
    bd = desc.block(0)
    act_bytes, persist_bytes = _byte_policies(bd, bf16_act)
    final_live = {n for n, vd in bd.vars.items() if vd.persistable}
    final_live |= set(fetches or ())
    tl = liveness_timeline(bd.ops, act_bytes, final_live,
                           top_n=top_n)
    params = sum(persist_bytes(n) for n in bd.vars)
    peak_op = tl["peak_op"]
    return {
        "kind": "paddle_tpu.mem_timeline",
        "version": 1,
        "ops": len(bd.ops),
        "op_types": [od.type for od in bd.ops],
        "series": tl["series"],
        "peak_bytes": int(tl["peak_bytes"]),
        "peak_op": peak_op,
        "peak_op_type": (bd.ops[peak_op].type
                         if peak_op is not None else None),
        "params_bytes": int(params),
        "total_peak_bytes": int(params + tl["peak_bytes"]),
        "top_buffers": tl["top_buffers"],
    }


def segment_static_peak(op_descs, outputs, block_desc, top_n=5,
                        bf16_act=None):
    """Static live-activation peak over ONE executor jit segment's
    ops, with the segment's outputs as the final live set — the
    apples-to-apples comparand for that segment's XLA temp+output
    bytes (arguments live outside the walk, exactly like feeds)."""
    from ..analysis.dataflow import liveness_timeline

    act_bytes, _ = _byte_policies(block_desc, bf16_act)
    return liveness_timeline(op_descs, act_bytes, set(outputs or ()),
                             top_n=top_n)


def render_timeline(tl, width=48, max_rows=64):
    """ASCII render of a timeline: one bar per op (downsampled past
    `max_rows`), the peak row marked, then the blamed top buffers."""
    lines = ["memory timeline: %d op(s), params+state %.1f MiB, "
             "activation peak %.1f MiB at op %s (%s), total peak "
             "%.1f MiB"
             % (tl["ops"], tl["params_bytes"] / MiB,
                tl["peak_bytes"] / MiB, tl["peak_op"],
                tl["peak_op_type"], tl["total_peak_bytes"] / MiB)]
    series = tl["series"]
    if series:
        peak = max(max(series), 1)
        n = len(series)
        stride = max(1, -(-n // int(max_rows)))
        for start in range(0, n, stride):
            chunk = series[start:start + stride]
            val = max(chunk)
            bar = "#" * max(1, int(round(val / peak * width))) \
                if val else ""
            marker = " <- peak" if (tl["peak_op"] is not None
                                    and start <= tl["peak_op"]
                                    < start + stride) else ""
            label = ("op %d" % start if stride == 1
                     else "op %d-%d" % (start, start + len(chunk) - 1))
            lines.append("  %-12s %8.1f MiB |%-*s|%s"
                         % (label, val / MiB, width, bar, marker))
    if tl["top_buffers"]:
        lines.append("top buffers live at the peak:")
        for b in tl["top_buffers"]:
            lines.append("  %-44s %10.2f MiB  def op %-4s %s"
                         % (b["name"], b["bytes"] / MiB,
                            b["def_op"], b["def_op_type"] or "-"))
    return "\n".join(lines)


def timeline_chrome_trace(tl, path=None, name="mem_live_bytes"):
    """The timeline as a Chrome trace-event counter track ("ph": "C")
    plus one span per op, co-loadable with the obs.trace exports in
    Perfetto.  The timebase is SYNTHETIC — one µs per op
    index (a static walk has no wall clock) — so it reads as a
    profile shape next to the real tracks, not as wall time."""
    evs = [{"name": "process_name", "ph": "M", "pid": 3, "tid": 0,
            "args": {"name": "paddle_tpu.obs.mem (static, 1us/op)"}}]
    for i, val in enumerate(tl["series"]):
        evs.append({"name": name, "cat": "mem", "ph": "C", "pid": 3,
                    "tid": 1, "ts": float(i),
                    "args": {"live_bytes": int(val)}})
        evs.append({"name": tl["op_types"][i], "cat": "mem", "ph": "X",
                    "pid": 3, "tid": 1, "ts": float(i), "dur": 1.0,
                    "args": {"op_index": i, "live_bytes": int(val)}})
    doc = {"traceEvents": evs, "displayTimeUnit": "ms",
           "otherData": {"producer": "paddle_tpu.obs.mem",
                         "peak_bytes": int(tl["peak_bytes"]),
                         "peak_op": tl["peak_op"],
                         "params_bytes": int(tl["params_bytes"])}}
    if path:
        tmp = str(path) + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, str(path))
    return doc


# ---------------------------------------------------------------------------
# actuals capture (executor wiring)
# ---------------------------------------------------------------------------

def register_segment_static(segment, op_descs, outputs, block_desc):
    """Executor hook, first build of a jit segment under attribution:
    record the segment's static activation peak + blamed buffers and
    publish `mem_static_peak_bytes{segment=}`.  The later
    `on_compile_captured` call for the same label completes the
    drift join."""
    tl = segment_static_peak(op_descs, outputs, block_desc)
    entry = {"static_peak_bytes": int(tl["peak_bytes"]),
             "static_peak_op": tl["peak_op"],
             "top_buffers": tl["top_buffers"],
             "captured_at": time.time()}
    with _lock:
        _segments.setdefault(segment, {}).update(entry)
    _seg_gauge("mem_static_peak_bytes",
               "static liveness activation-peak bytes per compiled "
               "segment (obs.mem)") \
        .labels(segment=segment).set(entry["static_peak_bytes"])
    return entry


def on_compile_captured(segment, published):
    """obs.health hook: `published` is publish_compile_stats' dict of
    xla_* values for one compiled executable.  Stores the actuals
    side of the drift join, publishes `mem_xla_program_bytes` (temp +
    output — what the program itself allocates beyond its arguments)
    and, when the static side is already registered,
    `mem_estimate_ratio{segment=}` (XLA actual / static estimate)."""
    xla = {k: v for k, v in (published or {}).items()
           if k.startswith("xla_")}
    if not xla:
        return None
    program_bytes = int(xla.get("xla_temp_bytes", 0)
                        + xla.get("xla_output_bytes", 0))
    with _lock:
        entry = _segments.setdefault(segment, {})
        entry["xla"] = xla
        entry["xla_program_bytes"] = program_bytes
        entry["captured_at"] = time.time()
        static = entry.get("static_peak_bytes")
    _seg_gauge("mem_xla_program_bytes",
               "XLA temp+output bytes per compiled segment (what the "
               "program allocates beyond its arguments)") \
        .labels(segment=segment).set(program_bytes)
    if xla.get("xla_argument_bytes") is not None:
        _seg_gauge("mem_xla_argument_bytes",
                   "XLA argument bytes per compiled segment") \
            .labels(segment=segment) \
            .set(int(xla["xla_argument_bytes"]))
    if static:
        _seg_gauge("mem_estimate_ratio",
                   "XLA actual temp+output bytes / static "
                   "liveness-peak estimate per segment (1.0 = the "
                   "static model is exact)") \
            .labels(segment=segment) \
            .set(round(program_bytes / static, 6))
    publish_device_watermarks()
    return program_bytes


_SEG_GAUGES = ("mem_static_peak_bytes", "mem_xla_program_bytes",
               "mem_xla_argument_bytes", "mem_estimate_ratio")


def retire_segments(labels):
    """Drop per-segment mem_* gauge children and store entries for
    retired segments (program-cache LRU eviction): a long-lived
    serving process must not accumulate dead segment labels.  A label
    shared with a still-live program re-publishes on its next
    build."""
    reg = _reg()
    with _lock:
        for label in labels:
            _segments.pop(label, None)
    for name in _SEG_GAUGES:
        fam = reg.gauge(name, labelnames=("segment",))
        for label in labels:
            fam.remove(segment=label)


def segments():
    """Snapshot of the per-segment store (static + xla sides)."""
    with _lock:
        return {k: dict(v) for k, v in _segments.items()}


def xla_program_bytes_total():
    """Sum of captured XLA temp+output bytes across all live
    segments (the serving warmup's per-bucket delta base)."""
    with _lock:
        return sum(int(v.get("xla_program_bytes", 0))
                   for v in _segments.values())


def reset():
    """Clear the store (test isolation; gauges reset with the
    registry)."""
    with _lock:
        _segments.clear()
        _bucket_bytes.clear()


def device_watermarks():
    """{device: {"bytes_in_use", "peak_bytes_in_use", "bytes_limit"}}
    from `jax.local_devices()[*].memory_stats()`.  Backends without
    allocator stats (CPU) contribute nothing — graceful by
    contract."""
    out = {}
    try:
        import jax

        for dev in jax.local_devices():
            try:
                stats = dev.memory_stats()
            except Exception:
                stats = None
            if not stats:
                continue
            out[str(dev)] = {
                k: int(stats[src]) for k, src in
                (("bytes_in_use", "bytes_in_use"),
                 ("peak_bytes_in_use", "peak_bytes_in_use"),
                 ("bytes_limit", "bytes_limit"))
                if src in stats}
    except Exception:
        return {}
    return out


def publish_device_watermarks():
    """Publish the watermarks as `mem_device_*{device=}` gauges;
    returns the dict (empty on statless backends)."""
    marks = device_watermarks()
    if not marks:
        return marks
    reg = _reg()
    for dev, stats in marks.items():
        if "bytes_in_use" in stats:
            reg.gauge("mem_device_bytes_in_use",
                      "device allocator live bytes",
                      labelnames=("device",)) \
                .labels(device=dev).set(stats["bytes_in_use"])
        if "peak_bytes_in_use" in stats:
            reg.gauge("mem_device_peak_bytes",
                      "device allocator peak live bytes (high "
                      "watermark)", labelnames=("device",)) \
                .labels(device=dev).set(stats["peak_bytes_in_use"])
    return marks


def record_bucket_bytes(bucket, nbytes):
    """Serving warmup hook: the XLA temp+output footprint of one
    batch bucket's warmed executables, as
    `mem_bucket_xla_bytes{bucket=}` (the /healthz "memory" section
    reads these back).  The engine passes the store total measured
    right after the bucket's warmup — segment labels are
    shape-independent and each bucket recompiles every jittable
    segment, so at that instant the store IS the bucket's program."""
    nbytes = max(0, int(nbytes))
    with _lock:
        _bucket_bytes[str(bucket)] = nbytes
    _reg().gauge("mem_bucket_xla_bytes",
                 "XLA temp+output bytes of each serving batch "
                 "bucket's warmed executables",
                 labelnames=("bucket",)) \
        .labels(bucket=bucket).set(nbytes)
    return nbytes


def health_memory_section():
    """The serving /healthz "memory" block: per-bucket warmup bytes +
    device watermarks.  None when neither exists (nothing captured,
    CPU backend) so the endpoint contract stays opt-in."""
    with _lock:
        buckets = dict(_bucket_bytes)
    marks = device_watermarks()
    if not buckets and not marks:
        return None
    section = {}
    if buckets:
        section["bucket_xla_bytes"] = buckets
    if marks:
        section["device"] = marks
    return section


# ---------------------------------------------------------------------------
# drift report + calibration feed
# ---------------------------------------------------------------------------

def _median(vals):
    vals = sorted(vals)
    n = len(vals)
    if not n:
        return None
    if n % 2:
        return vals[n // 2]
    return (vals[n // 2 - 1] + vals[n // 2]) / 2.0


def drift_report(store=None):
    """Join static peak vs XLA actual per segment.  `store` defaults
    to this process's capture (`segments()`); pass a `load_store`
    dict for offline joins.  Segments with only one side are listed
    under "unjoined".  Publishes `mem_estimate_ratio{segment=}` for
    every joined row."""
    store = segments() if store is None else store
    rows, unjoined = [], []
    for segment in sorted(store):
        e = store[segment]
        static = e.get("static_peak_bytes")
        actual = e.get("xla_program_bytes")
        if static and actual is not None:
            ratio = round(actual / static, 6) if static else None
            rows.append({"segment": segment,
                         "static_peak_bytes": int(static),
                         "xla_program_bytes": int(actual),
                         "ratio": ratio,
                         "top_buffers": e.get("top_buffers", [])})
            if ratio is not None:
                _seg_gauge("mem_estimate_ratio",
                           "XLA actual temp+output bytes / static "
                           "liveness-peak estimate per segment (1.0 "
                           "= the static model is exact)") \
                    .labels(segment=segment).set(ratio)
        else:
            unjoined.append({"segment": segment,
                             "has_static": bool(static),
                             "has_actual": actual is not None})
    ratios = [r["ratio"] for r in rows if r["ratio"]]
    return {"kind": "paddle_tpu.mem_drift", "version": 1,
            "segments": rows, "unjoined": unjoined,
            "n": len(ratios), "median_ratio": _median(ratios),
            "device": device_watermarks() or None}


def render_drift(report):
    lines = ["memory drift: %d joined segment(s), %d unjoined, "
             "median actual/static ratio %s"
             % (len(report["segments"]), len(report["unjoined"]),
                ("%.3f" % report["median_ratio"])
                if report["median_ratio"] else "n/a")]
    lines.append("  %-44s %12s %12s %8s"
                 % ("segment", "static MiB", "xla MiB", "ratio"))
    for r in report["segments"]:
        lines.append("  %-44s %12.2f %12.2f %8s"
                     % (r["segment"],
                        r["static_peak_bytes"] / MiB,
                        r["xla_program_bytes"] / MiB,
                        ("%.3f" % r["ratio"]) if r["ratio"] else "-"))
    for u in report["unjoined"]:
        side = "static only" if u["has_static"] else "actual only"
        lines.append("  %-44s (%s — no join)" % (u["segment"], side))
    if report.get("device"):
        for dev, stats in sorted(report["device"].items()):
            lines.append("  device %s: %.1f MiB in use, peak %.1f MiB"
                         % (dev,
                            stats.get("bytes_in_use", 0) / MiB,
                            stats.get("peak_bytes_in_use", 0) / MiB))
    return "\n".join(lines)


def dump_store(path):
    """Persist this process's capture store for an offline
    `pmem drift --store` join (atomic write)."""
    doc = {"kind": "paddle_tpu.mem_store", "version": 1,
           "segments": segments(),
           "device": device_watermarks() or None,
           "created_at": time.time()}
    tmp = str(path) + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
    os.replace(tmp, str(path))
    return str(path)


def load_store(path):
    with open(path) as f:
        doc = json.load(f)
    if doc.get("kind") != "paddle_tpu.mem_store":
        raise ValueError("%s is not a pmem store dump (kind=%r)"
                         % (path, doc.get("kind")))
    return doc["segments"]


# ---------------------------------------------------------------------------
# donation audit
# ---------------------------------------------------------------------------

def audit_donation(program, fetches=(), mode=None):
    """Price the donation-safety analysis (`analysis/alias.py`): which
    param/optimizer-state buffers the executor's jit signature donates
    under the requested FLAGS_donation mode, and which dead-after-use
    buffers it does NOT — each reclaimable entry cross-linked to the
    A-code explaining the refusal (A001 forked/absent in-place slot,
    A004 update stranded in a non-jittable segment; code None only
    under mode=off, where the flag itself is the refusal).

    mode: "auto" | "conservative" | "off"; None reads FLAGS_donation.
    Returns {"donated": [...], "reclaimable": [...]} entries with
    name/bytes/op identity; `reclaimable_bytes` is the audit's
    headline number."""
    from ..analysis.alias import analyze_donation
    from ..fluid import analysis as fluid_analysis

    desc = getattr(program, "desc", program)
    bd = desc.block(0)
    bf16_act = _bf16_act_now()
    plan = analyze_donation(program, fetches=fetches, mode=mode)

    def full_bytes(name):
        vd = bd.vars.get(name)
        if vd is None or vd.shape is None:
            return 0
        return fluid_analysis._numel(vd.shape) * \
            fluid_analysis._elem_bytes(str(vd.dtype), True, bf16_act)

    def kind_of(name, slot):
        vd = bd.vars.get(name)
        if vd is not None and vd.is_parameter:
            return "param"
        if slot == "ParamOut":
            return "param"
        if vd is not None and vd.persistable:
            return "optimizer_state"
        return "activation"

    donated, reclaimable = [], []
    for e in plan.entries:
        if e["status"] not in ("donated", "reclaimable"):
            continue
        item = {"name": e["name"], "bytes": int(full_bytes(e["name"])),
                "op_index": e["op_index"], "op_type": e["op_type"],
                "slot": e["slot"],
                "kind": kind_of(e["name"], e["slot"])}
        if e["status"] == "donated":
            donated.append(item)
        else:
            item["reason"] = e["reason"]
            if e["code"]:
                item["code"] = e["code"]
            reclaimable.append(item)
    return {
        "kind": "paddle_tpu.mem_donation_audit", "version": 2,
        "ops": len(bd.ops), "jit_segments": sum(
            1 for s in plan.segments if s["jit"]),
        "mode": plan.mode,
        "widened": sorted(n for s in plan.segments
                          for n in s["widened"]),
        "donated": donated,
        "donated_bytes": sum(d["bytes"] for d in donated),
        "reclaimable": reclaimable,
        "reclaimable_bytes": sum(r["bytes"] for r in reclaimable),
    }


def render_audit(audit):
    lines = ["donation audit: %d op(s) in %d jit segment(s); "
             "%d buffer(s) donated (%.1f MiB), %d reclaimable "
             "(%.1f MiB)"
             % (audit["ops"], audit["jit_segments"],
                len(audit["donated"]), audit["donated_bytes"] / MiB,
                len(audit["reclaimable"]),
                audit["reclaimable_bytes"] / MiB)]
    for r in audit["reclaimable"]:
        lines.append("  RECLAIM %-36s %10.2f MiB  [%s] op %d %s/%s"
                     % (r["name"], r["bytes"] / MiB, r["kind"],
                        r["op_index"], r["op_type"], r["slot"]))
        lines.append("          %s%s"
                     % (("%s: " % r["code"]) if r.get("code") else "",
                        r["reason"]))
    if not audit["reclaimable"]:
        lines.append("  every dead-after-use param/state buffer is "
                     "donated — nothing to reclaim")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# OOM pre-flight + post-mortem
# ---------------------------------------------------------------------------

class MemoryBudgetError(MemoryError):
    """Raised by the pre-flight check (`FLAGS_mem_budget_gb`) before
    any compile: the honest, pre-device RESOURCE_EXHAUSTED.  Carries
    `.timeline` so the flight-bundle context never recomputes the
    walk."""

    def __init__(self, message, timeline=None, budget_gb=None):
        super().__init__(message)
        self.timeline = timeline
        self.budget_gb = budget_gb


def preflight(program, fetches, budget_gb):
    """Refuse a program whose static total peak (params + optimizer
    state + liveness activation peak) exceeds `budget_gb` GiB.  The
    error message names the top blamed buffers — the same table a
    real device OOM's flight bundle carries."""
    tl = program_timeline(program, fetches=fetches, top_n=8)
    total = tl["total_peak_bytes"]
    budget = float(budget_gb) * GiB
    if total <= budget:
        return tl
    top = "; ".join("%s %.1f MiB (op %s %s)"
                    % (b["name"], b["bytes"] / MiB, b["def_op"],
                       b["def_op_type"])
                    for b in tl["top_buffers"][:3])
    raise MemoryBudgetError(
        "RESOURCE_EXHAUSTED (pre-flight): static peak HBM %.3f GiB "
        "(params+state %.3f + activation peak %.3f at op %s %s) "
        "exceeds FLAGS_mem_budget_gb=%.3g%s"
        % (total / GiB, tl["params_bytes"] / GiB,
           tl["peak_bytes"] / GiB, tl["peak_op"], tl["peak_op_type"],
           float(budget_gb),
           "" if not top else " — top resident: " + top),
        timeline=tl, budget_gb=float(budget_gb))


def is_oom(exc):
    """True for device RESOURCE_EXHAUSTED errors and the pre-flight
    MemoryBudgetError — the class whose flight bundles carry the
    blamed-buffer table."""
    if isinstance(exc, MemoryBudgetError):
        return True
    if isinstance(exc, MemoryError):
        return True
    return "RESOURCE_EXHAUSTED" in str(exc)


def oom_context(exc, program=None, fetches=None):
    """Flight-bundle context for an OOM-class exception: `{}` for
    anything else (the executor splats this into `on_crash`, so the
    hot exception path stays one is_oom check).  The "oom" note
    carries the static timeline's top blamed buffers and the last
    mem_*/xla_* gauge values — the post-mortem names WHICH buffers
    were resident instead of just "out of memory"."""
    if not is_oom(exc):
        return {}
    tl = getattr(exc, "timeline", None)
    if tl is None and program is not None:
        try:
            tl = program_timeline(program, fetches=fetches, top_n=8)
        except Exception:
            tl = None
    gauges = {k: v for k, v in telemetry_mod.snapshot().items()
              if k.startswith(("mem_", "xla_"))}
    oom = {"reason": "resource_exhausted"}
    if tl is not None:
        oom.update({
            "static_peak_bytes": tl["peak_bytes"],
            "params_bytes": tl["params_bytes"],
            "total_peak_bytes": tl["total_peak_bytes"],
            "peak_op": tl["peak_op"],
            "peak_op_type": tl["peak_op_type"],
            "top_buffers": tl["top_buffers"],
        })
    if gauges:
        oom["mem_gauges"] = gauges
    marks = device_watermarks()
    if marks:
        oom["device"] = marks
    return {"oom": oom}
