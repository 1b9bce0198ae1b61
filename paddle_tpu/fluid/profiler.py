"""Profiler: per-op timing tables and XLA trace hooks.

reference: paddle/platform/profiler.h:27-146 (RecordEvent around every op,
ParseEvents table) + python/paddle/v2/fluid/profiler.py.  The compiled
path profiles at segment granularity (XLA owns fusion); the eager executor
mode gives reference-style per-op attribution.  `profiler(...)` can also
start JAX's own trace for TensorBoard (`trace_dir=`), in which every
`obs.trace.span` of the program (`executor/run`, `executor/segment`, ...)
and every op's `jax.named_scope` appear beside the device's operations.
While the table is on, the executor blocks on each segment's outputs so
that a row is wall time; that is the only thing that makes it block.

Since the obs layer landed this module is the back-compat veneer over
`paddle_tpu.obs`: `record_event` is a span (it lands on the obs trace
timeline whenever tracing is on, independent of the profiler table
being enabled), and every `record()` also feeds the unified metrics
registry (`profiler_event_seconds_total` / `profiler_event_calls_total`
labeled by event), so the old per-op table and the new /metrics
surface can never disagree.
"""

import contextlib
import time
from collections import defaultdict

from ..obs import registry as obs_registry
from ..obs import trace as obs_trace

__all__ = ["profiler", "reset_profiler", "get_profile_records",
           "cuda_profiler", "tpu_profiler"]

_records = defaultdict(lambda: {"calls": 0, "total": 0.0,
                                "min": float("inf"), "max": 0.0})
_enabled = [False]


def is_enabled():
    return _enabled[0]


# cached (registry, seconds_family, calls_family): record() runs on
# the serving request path, so resolve the families once per registry
# instead of two locked get-or-creates per observation
_fam_cache = [None, None, None]


def _registry_families():
    reg = obs_registry.get_registry()
    if _fam_cache[0] is not reg:  # registry swapped (reset_registry)
        _fam_cache[1] = reg.counter(
            "profiler_event_seconds_total",
            "accumulated seconds per profiler event",
            labelnames=("event",))
        _fam_cache[2] = reg.counter(
            "profiler_event_calls_total",
            "call count per profiler event",
            labelnames=("event",))
        _fam_cache[0] = reg
    return _fam_cache[1], _fam_cache[2]


def record(name, seconds):
    r = _records[name]
    r["calls"] += 1
    r["total"] += seconds
    r["min"] = min(r["min"], seconds)
    r["max"] = max(r["max"], seconds)
    # the old API delegates to the new registry: the same observation
    # is scrapeable from the unified /metrics surface
    seconds_fam, calls_fam = _registry_families()
    seconds_fam.labels(event=name).inc(seconds)
    calls_fam.labels(event=name).inc()


@contextlib.contextmanager
def record_event(name):
    """Span-backed RecordEvent: feeds the per-op table when the
    profiler is enabled AND the obs trace timeline when tracing is on
    (either alone works)."""
    tracing = obs_trace.is_enabled()
    if not (_enabled[0] or tracing):
        yield
        return
    t0 = time.perf_counter()
    try:
        yield
    finally:
        dt = time.perf_counter() - t0
        if tracing:
            obs_trace.emit_span(name, t0, dt, cat="op")
        if _enabled[0]:
            record(name, dt)


def reset_profiler():
    _records.clear()


def get_profile_records():
    out = {}
    for k, v in _records.items():
        v = dict(v)
        if not v["calls"]:
            # a zero-call entry (e.g. created by a defaultdict read)
            # must not leak the `inf` sentinel — clamp like
            # _print_table does
            v["min"] = 0.0
        out[k] = v
    return out


def _print_table(sorted_key=None):
    rows = []
    for name, r in _records.items():
        rows.append((name, r["calls"], r["total"],
                     r["min"] if r["calls"] else 0.0, r["max"],
                     r["total"] / max(r["calls"], 1)))
    key_idx = {"calls": 1, "total": 2, "min": 3, "max": 4, "ave": 5}.get(
        sorted_key, 2)
    rows.sort(key=lambda x: -x[key_idx] if isinstance(x[key_idx], (int,
              float)) else 0)
    print("%-40s %8s %12s %12s %12s %12s" % (
        "Event", "Calls", "Total(s)", "Min(s)", "Max(s)", "Ave(s)"))
    for row in rows:
        print("%-40s %8d %12.6f %12.6f %12.6f %12.6f" % row)


@contextlib.contextmanager
def profiler(state="All", sorted_key=None, trace_dir=None):
    """reference: fluid/profiler.py profiler context manager."""
    _enabled[0] = True
    reset_profiler()
    jax_trace = None
    if trace_dir:
        import jax

        jax.profiler.start_trace(trace_dir)
        jax_trace = trace_dir
    try:
        yield
    finally:
        _enabled[0] = False
        if jax_trace:
            import jax

            jax.profiler.stop_trace()
        _print_table(sorted_key)


@contextlib.contextmanager
def cuda_profiler(output_file=None, output_mode=None, config=None):
    """Kept for API parity (reference: fluid/profiler.py:33); maps to a JAX
    device trace."""
    with profiler(trace_dir=None):
        yield


tpu_profiler = cuda_profiler
