"""Measure the top-K ranked candidates through bench.py.

The static model (tune/rank.py) earns nothing until it is checked
against hardware, but measuring the WHOLE space is exactly the pod
burn the tuner exists to avoid — so this module runs only the plan's
top-K survivors, each as one bench.py subprocess through the exact
path every other measurement takes: the AOT steady-state compile,
JAX's persistent compilation cache (bench.py turns it on; the children
share the directory `utils/compile_cache.py` names), and the
perf-history append.  Nothing bespoke to un-trust.

What one chip can measure of a multi-chip candidate is its per-device
proxy: bench runs the candidate's per-device batch slice
(`batch / dp`), its micro-batch split, and its pass pipeline —
the compute + overhead terms of the prediction.  The comm term stays
analytic until multi-chip legs exist (ROADMAP item 1); tune/fit.py
fits the correction on exactly the terms that were measured.

Every record lands in `perf_history.jsonl` with leg `ptune:<tag>` and
the stamped `"config"` blob, so the calibration join is a history
lookup, not filename archaeology.

Only `RankedPlan.ranked` entries can be measured: rejections never
carry a `bench_env`, and `measure_plan` walks the ranked list — the
selftest proves an injected S002-invalid mesh cannot reach here.
"""

import json
import os
import subprocess
import sys

__all__ = ["measure_plan", "measurement_env", "bench_path",
           "MeasureError"]


class MeasureError(RuntimeError):
    pass


def bench_path():
    """bench.py at the repo root (two levels above this package).
    Measuring needs the checkout; ranking deliberately does not."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    path = os.path.join(root, "bench.py")
    if not os.path.exists(path):
        raise MeasureError(
            "ptune measure drives the repo's bench.py, which is not "
            "next to this install (%s) — run from the repo checkout "
            "(`ptune plan`/`fit` work anywhere)" % path)
    return path


def _entries(plan, model=None):
    """Uniform (tag, config, bench_env, context) view over a
    RankedPlan or a loaded plan-JSON dict."""
    if hasattr(plan, "ranked") and not isinstance(plan, dict):
        model = model or plan.model
        return [(e.candidate.tag(), e.candidate.config(model),
                 e.candidate.bench_env(model))
                for e in plan.ranked], model, dict(plan.context)
    model = model or plan.get("model")
    return [(e["tag"], e["config"], dict(e["bench_env"]))
            for e in plan.get("ranked", ())], model, \
        dict(plan.get("context") or {})


def measurement_env(env_over, context, model, history=None, iters=2,
                    warmup=1, image_size=None, extra_env=None):
    """The full env overrides for one candidate's bench.py run.

    Starts from the candidate's own `bench_env` and replays the PLAN
    CONTEXT so the measured program is the one the ranking priced:
    BENCH_AMP follows the plan's `bf16_act` (an `--f32` plan must not
    be measured under bench's bf16 default), and the builder's
    image_size/class_dim knobs carry over unless overridden here.
    Relative history paths are absolutized against the CALLER's cwd —
    the bench subprocess runs from the repo root, and `ptune fit`
    later resolves the same path from the caller's cwd again."""
    env = dict(env_over)
    env.setdefault("BENCH_MODEL", model)
    env["BENCH_ITERS"] = str(iters)
    env["BENCH_WARMUP"] = str(warmup)
    if "bf16_act" in context:
        env["BENCH_AMP"] = "1" if context["bf16_act"] else "0"
    size = image_size or context.get("image_size")
    if size:
        env["BENCH_IMAGE_SIZE"] = str(size)
    if context.get("class_dim"):
        env["BENCH_CLASS_DIM"] = str(context["class_dim"])
    if history:
        env["BENCH_HISTORY"] = os.path.abspath(history)
    env.update(extra_env or {})
    return env


def _config_matches(expected, got, context):
    """The measured record's config blob must be the candidate point:
    bench's global batch is the candidate's per-device slice, and the
    AMP mode must match what the plan was ranked under."""
    if not isinstance(got, dict):
        return "record carries no config blob"
    checks = [
        ("mesh", expected["mesh"], got.get("mesh")),
        ("batch", expected["per_device_batch"], got.get("batch")),
        ("micro_batches", expected["micro_batches"],
         got.get("micro_batches")),
        ("pass_pipeline", expected["pass_pipeline"],
         got.get("pass_pipeline")),
    ]
    if "bf16_act" in context:
        checks.append(("amp_bf16", bool(context["bf16_act"]),
                       got.get("amp_bf16")))
    for name, want, have in checks:
        if want != have:
            return "config.%s mismatch: expected %r, measured %r" \
                % (name, want, have)
    return None


def measure_plan(plan, topk=3, history=None, iters=2, warmup=1,
                 model=None, image_size=None, extra_env=None,
                 timeout=900, echo=None):
    """Run bench.py on the plan's top-K ranked candidates.

    plan: a `RankedPlan` or a loaded plan-JSON dict.
    history: perf-history path the records append to (bench.py's
        default — `perf_history.jsonl` at the repo root — when None).
    extra_env: overrides applied last (the selftest pins
        JAX_PLATFORMS=cpu and tiny iters here).

    Returns a list of {"tag", "ok", "record" | "error"}; raises
    MeasureError only for setup problems (no bench.py) — one failed
    leg does not forfeit the rest.
    """
    bench = bench_path()
    entries, model, context = _entries(plan, model)
    if model is None:
        raise MeasureError("plan names no model and none was given")
    results = []
    for tag, config, env_over in entries[:int(topk)]:
        # ambient BENCH_*/FLAGS_compile_passes (a leftover A/B sweep
        # export, say) would silently measure a different program than
        # the one the plan ranked — scrub them; the candidate's env is
        # the only bench config (re-add knobs via extra_env if needed).
        env = {k: v for k, v in os.environ.items()
               if not k.startswith("BENCH_")
               and k != "FLAGS_compile_passes"}
        env.update(measurement_env(
            env_over, context, model, history=history, iters=iters,
            warmup=warmup, image_size=image_size,
            extra_env=extra_env))
        if echo:
            echo("[ptune] measuring %s (batch %s x mb %s)"
                 % (tag, env["BENCH_BATCH"], env["BENCH_MICRO_BATCH"]))
        try:
            proc = subprocess.run(
                [sys.executable, bench], cwd=os.path.dirname(bench),
                env=env, capture_output=True, text=True,
                timeout=timeout)
        except subprocess.TimeoutExpired:
            # one hung compile forfeits its leg, never the rest
            results.append({"tag": tag, "ok": False,
                            "error": "bench.py exceeded the %gs "
                            "budget" % timeout})
            continue
        if proc.returncode != 0:
            results.append({"tag": tag, "ok": False,
                            "error": "bench.py exit %d: %s"
                            % (proc.returncode,
                               proc.stderr.strip()[-500:])})
            continue
        try:
            record = json.loads(proc.stdout.strip().splitlines()[-1])
        except (ValueError, IndexError):
            results.append({"tag": tag, "ok": False,
                            "error": "bench.py emitted no JSON record: "
                            "%r" % proc.stdout[-200:]})
            continue
        mismatch = _config_matches(config, record.get("config"),
                                   context)
        if mismatch:
            results.append({"tag": tag, "ok": False, "record": record,
                            "error": mismatch})
            continue
        results.append({"tag": tag, "ok": True, "record": record})
    return results
