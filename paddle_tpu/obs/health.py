"""Numerics health monitoring: jit-safe nonfinite detection, grad-norm
gauges, compile-time XLA memory/cost attribution, and a bisection tool
for non-finite jitted steps.

The reference executor's only numerics guard is the eager per-op
NaN/Inf scan (reference: executor.cc:29 FLAGS_check_nan_inf +
CheckTensorNANOrInf executor.cc:66-77) — and this port honors that
flag only on the eager path, so a jitted TPU step can go non-finite
silently.  This module closes the gap in three layers:

  * `NumericsMonitor` — appends on-device reductions to a Program
    (nan/inf counts via the `count_nonfinite` op, max-abs via
    abs+reduce_max, global grad norm via `fluid/clip.py`'s
    `append_global_norm` machinery).  The reductions ride the regular
    fetch path as a few extra scalars — jit-safe, fused by XLA into
    the step executable, and never forcing an early device->host sync
    mid-segment.  `record()` feeds them into registry
    counters/gauges: `numerics_nonfinite_total{tensor=...}`,
    `numerics_max_abs{tensor=...}`, `grad_global_norm`.
  * `locate_nonfinite(program, feed)` — replays the offending step
    EAGERLY with FLAGS_check_nan_inf set and returns the first op
    whose output went non-finite (op type, index, output var) — the
    bisection the eager-only flag almost gives us today.
  * `publish_compile_stats(segment, compiled)` —
    `compiled.memory_analysis()` / `cost_analysis()` capture at
    jit-build time (FLAGS_xla_cost_attribution), exported as
    per-segment-label gauges `xla_temp_bytes`, `xla_argument_bytes`,
    `xla_output_bytes`, `xla_flops`, `xla_bytes_accessed` — the
    per-kernel memory/FLOP attribution a TVM-style compiler report
    carries, so /metrics and BENCH artifacts show where HBM and FLOPs
    go.

Trainers check the module switch: `health.enable()` makes the v2 SGD
loop and the mesh-parallel trainer install a monitor automatically
(watching the cost/fetches plus every parameter gradient).  Everything
here only watches — results are never changed.

Import-cheap by design: fluid is imported lazily inside methods, so
`paddle_tpu.obs` stays free of framework import cycles.
"""

import threading

import numpy as np

from . import registry as registry_mod
from . import telemetry as telemetry_mod

__all__ = ["NumericsMonitor", "locate_nonfinite", "publish_compile_stats",
           "retire_compile_stats", "scan_outputs", "enable", "disable",
           "enabled", "force_attribution", "attribution_forced"]

_enabled = False

# one stable prefix so health vars are recognizable in program dumps
VAR_PREFIX = "health_"

# counting override for the xla_cost_attribution flag: surfaces that
# want attribution for a bounded window (serving warmup) nest this
# instead of flipping the process-global flag — concurrent warmups
# can't race each other's save/restore or leave the flag stuck
_attr_lock = threading.Lock()
_attr_forced = 0


class _ForcedAttribution:
    def __enter__(self):
        global _attr_forced
        with _attr_lock:
            _attr_forced += 1
        return self

    def __exit__(self, *exc):
        global _attr_forced
        with _attr_lock:
            _attr_forced -= 1
        return False


def force_attribution():
    """`with health.force_attribution(): ...` — XLA memory/cost
    capture is on for jit builds in the body regardless of
    FLAGS_xla_cost_attribution; nests and composes across threads."""
    return _ForcedAttribution()


def attribution_forced():
    return _attr_forced > 0


def enable():
    """Turn trainer-side numerics monitoring on: the v2 SGD loop and
    the mesh-parallel trainer install a NumericsMonitor on their next
    train/init."""
    global _enabled
    _enabled = True


def disable():
    global _enabled
    _enabled = False


def enabled():
    return _enabled


# ---------------------------------------------------------------------------
# NumericsMonitor
# ---------------------------------------------------------------------------

class NumericsMonitor:
    """Appends jit-safe numerics reductions to a Program and turns the
    fetched scalars into registry signals.

    Usage:
        mon = NumericsMonitor(program, tensors=[loss.name],
                              grads=None).install()   # None = discover
        outs = exe.run(program, feed=...,
                       fetch_list=user_fetches + mon.fetch_names)
        mon.record(dict(zip(mon.fetch_names, outs[len(user_fetches):])))

    tensors: Variables/names to watch (nonfinite count + max-abs each).
    grads:   grad Variables/names folded into ONE global-norm scalar
             (reusing fluid/clip.py's append_global_norm); None
             auto-discovers every parameter gradient written in block
             0; pass [] to skip the norm.
    loss_scaler: optional fluid.amp.LossScaler updated from the
             found-nonfinite signal on every record() (publishes the
             `amp_loss_scale` gauge).
    """

    def __init__(self, program, tensors=None, grads=None,
                 loss_scaler=None):
        self.program = program
        self.loss_scaler = loss_scaler
        self._tensors = [self._name_of(t) for t in (tensors or [])]
        self._grads = (None if grads is None
                       else [self._name_of(g) for g in grads])
        self._outputs = []   # (kind, tensor_label, out_var_name)
        self._installed = False
        self.last = None

    @staticmethod
    def _name_of(v):
        return v if isinstance(v, str) else v.name

    @classmethod
    def for_train_program(cls, program, cost=None, params_grads=None,
                          loss_scaler=None):
        """Monitor a training program: watch the cost, global-norm all
        known gradients (from params_grads when the caller has them,
        discovered from the block otherwise)."""
        grads = None
        if params_grads is not None:
            grads = [g for _, g in params_grads if g is not None]
        return cls(program, tensors=[cost] if cost is not None else [],
                   grads=grads, loss_scaler=loss_scaler)

    # -- program instrumentation --------------------------------------------
    def _discover_grads(self):
        from ..fluid import framework

        block = self.program.global_block()
        written = set()
        for od in block.desc.ops:
            for names in od.outputs.values():
                written.update(names)
        grads = []
        for name, var in block.vars.items():
            if isinstance(var, framework.Parameter) \
                    and name + "@GRAD" in written:
                grads.append(name + "@GRAD")
        return grads

    def install(self):
        """Append the reduction ops (idempotent).  Returns self."""
        if self._installed:
            return self
        from ..fluid import clip as clip_mod
        from ..fluid import framework

        block = self.program.global_block()
        for name in self._tensors:
            watched = block.var_recursive(name)
            cnt = block.create_var(
                name=framework.unique_name(VAR_PREFIX + "nonfinite"),
                dtype="int32", shape=(1,))
            block.append_op(type="count_nonfinite",
                            inputs={"X": [name]},
                            outputs={"Out": [cnt]})
            self._outputs.append(("nonfinite", name, cnt.name))
            absv = block.create_var(
                name=framework.unique_name(VAR_PREFIX + "abs"),
                dtype=watched.dtype, shape=watched.shape)
            block.append_op(type="abs", inputs={"X": [name]},
                            outputs={"Out": [absv]})
            mx = block.create_var(
                name=framework.unique_name(VAR_PREFIX + "maxabs"),
                dtype=watched.dtype, shape=(1,))
            block.append_op(type="reduce_max", inputs={"X": [absv]},
                            outputs={"Out": [mx]},
                            attrs={"reduce_all": True})
            self._outputs.append(("maxabs", name, mx.name))
        grads = self._grads if self._grads is not None \
            else self._discover_grads()
        for gname in grads:
            cnt = block.create_var(
                name=framework.unique_name(VAR_PREFIX + "nonfinite"),
                dtype="int32", shape=(1,))
            block.append_op(type="count_nonfinite",
                            inputs={"X": [gname]},
                            outputs={"Out": [cnt]})
            self._outputs.append(("nonfinite", gname, cnt.name))
        if grads:
            gnorm = clip_mod.append_global_norm(
                block, [block.var_recursive(g) for g in grads],
                prefix=VAR_PREFIX + "global_norm")
            self._outputs.append(("gnorm", None, gnorm.name))
        self._installed = True
        return self

    @property
    def fetch_names(self):
        """Monitor output var names to append to the fetch list."""
        return [vname for _, _, vname in self._outputs]

    # -- signal publishing ---------------------------------------------------
    def record(self, values):
        """Feed one step's fetched monitor scalars into the registry.
        `values`: dict name->value, or a sequence aligned with
        `fetch_names`.  Returns a summary dict (and remembers it as
        `.last`)."""
        if not isinstance(values, dict):
            values = dict(zip(self.fetch_names, values))
        reg = registry_mod.get_registry()
        fam = reg.counter(
            "numerics_nonfinite_total",
            "NaN/Inf elements observed in watched tensors",
            labelnames=("tensor",))
        summary = {"nonfinite": {}, "max_abs": {}}
        found = 0
        for kind, label, vname in self._outputs:
            val = values.get(vname)
            if val is None:
                continue
            scalar = np.asarray(val).reshape(-1)[0]
            if kind == "nonfinite":
                c = int(scalar)
                summary["nonfinite"][label] = c
                found += c
                # inc(0) still creates the child, so /metrics shows the
                # watched tensor at 0 instead of omitting it
                fam.labels(tensor=label).inc(c)
            elif kind == "maxabs":
                v = float(scalar)
                summary["max_abs"][label] = v
                reg.gauge("numerics_max_abs",
                          "max |x| of watched tensors (most recent "
                          "step)", labelnames=("tensor",)) \
                   .labels(tensor=label).set(v)
            else:
                v = float(scalar)
                summary["grad_global_norm"] = v
                telemetry_mod.set_gauge("grad_global_norm", v)
        summary["found_nonfinite"] = bool(found)
        if self.loss_scaler is not None:
            summary["loss_scale"] = self.loss_scaler.update(found > 0)
        self.last = summary
        return summary


# ---------------------------------------------------------------------------
# eager bisection
# ---------------------------------------------------------------------------

def _clone_scope(scope):
    """Flat copy of a scope chain into a fresh Scope, so the eager
    replay can't mutate the caller's persistable state (optimizer ops
    re-run during the replay)."""
    from ..core.scope import Scope

    clone = Scope()
    seen = set()
    s = scope
    while s is not None:
        for name in s.local_var_names():
            if name not in seen:
                seen.add(name)
                clone.set_local(name, s.get(name))
        s = s._parent
    return clone


def locate_nonfinite(program, feed, fetch_list=None, scope=None,
                     place=None, clone_scope=True):
    """Replay `program` EAGERLY with FLAGS_check_nan_inf set and return
    the first op producing a non-finite output, as a dict:

        {"op_type", "op_index", "output_slot", "var_name",
         "nonfinite_count", "message"}

    or None when the whole replay stays finite.  This is the bisection
    for jitted programs: the flag itself only guards the eager
    interpreter (see fluid/executor.py), so when a compiled step's
    loss goes NaN, hand the same feed here to get the offending op.

    The replay runs against a flat copy of `scope` by default
    (clone_scope=False replays in place, mutating optimizer state
    exactly like a real step would).  Flight-recorder crash dumps are
    suppressed for the replay — it is a diagnosis, not a crash.
    """
    from ..core.scope import global_scope
    from ..fluid import executor as executor_mod
    from ..utils import flags as flags_mod
    from . import flight as flight_mod

    scope = scope if scope is not None else global_scope()
    if clone_scope:
        scope = _clone_scope(scope)
    exe = executor_mod.Executor(place or executor_mod.CPUPlace())
    prev = flags_mod.get_flag("check_nan_inf")
    flags_mod.set_flag("check_nan_inf", True)
    try:
        with flight_mod.suppressed():
            exe.run(program, feed=dict(feed),
                    fetch_list=list(fetch_list or []), scope=scope,
                    eager=True, use_program_cache=False)
        return None
    except executor_mod.NonfiniteError as err:
        return {"op_type": err.op_type, "op_index": err.op_index,
                "output_slot": err.slot, "var_name": err.var_name,
                "nonfinite_count": err.nonfinite_count,
                "message": str(err)}
    finally:
        flags_mod.set_flag("check_nan_inf", prev)


# ---------------------------------------------------------------------------
# host-side output scanning (serving)
# ---------------------------------------------------------------------------

def scan_outputs(named_values):
    """Count NaN/Inf elements in already-materialized host values
    (serving fetch outputs) into `numerics_nonfinite_total{tensor=}`.
    Returns the total found.  Cheap relative to the JSON serialization
    the serving path does right after."""
    reg = registry_mod.get_registry()
    fam = reg.counter(
        "numerics_nonfinite_total",
        "NaN/Inf elements observed in watched tensors",
        labelnames=("tensor",))
    total = 0
    for name, val in named_values:
        arr = np.asarray(getattr(val, "values", val))
        if arr.dtype.kind not in "fc":
            continue
        bad = int(arr.size - np.isfinite(arr).sum())
        fam.labels(tensor=name).inc(bad)
        total += bad
    return total


# ---------------------------------------------------------------------------
# XLA memory/cost attribution
# ---------------------------------------------------------------------------

_MEMORY_GAUGES = (
    ("xla_temp_bytes", "temp_size_in_bytes",
     "XLA temp buffer bytes per compiled segment"),
    ("xla_argument_bytes", "argument_size_in_bytes",
     "XLA argument bytes per compiled segment"),
    ("xla_output_bytes", "output_size_in_bytes",
     "XLA output bytes per compiled segment"),
    ("xla_generated_code_bytes", "generated_code_size_in_bytes",
     "XLA generated code bytes per compiled segment"),
)

_COST_GAUGES = (
    ("xla_flops", "flops", "XLA-estimated FLOPs per compiled segment"),
    ("xla_bytes_accessed", "bytes accessed",
     "XLA-estimated bytes accessed per compiled segment"),
)


def publish_compile_stats(segment, compiled):
    """Capture `compiled.memory_analysis()` / `cost_analysis()` (a
    CompiledMemoryStats and one dict of properties, as jax 0.9.0
    returns them) into per-segment-label gauges.  Returns the dict of
    published values, or None when the executable reports neither."""
    reg = registry_mod.get_registry()
    published = {}
    ma = compiled.memory_analysis()
    if ma is not None:
        for gauge, attr, help_text in _MEMORY_GAUGES:
            v = getattr(ma, attr, None)
            if v is None:
                continue
            reg.gauge(gauge, help_text, labelnames=("segment",)) \
               .labels(segment=segment).set(int(v))
            published[gauge] = int(v)
    ca = compiled.cost_analysis() or {}
    for gauge, key, help_text in _COST_GAUGES:
        v = ca.get(key)
        if v is None:
            continue
        reg.gauge(gauge, help_text, labelnames=("segment",)) \
           .labels(segment=segment).set(float(v))
        published[gauge] = float(v)
    if published:
        # the memory-observability side of the same capture: obs.mem
        # stores the actuals for the static-vs-XLA drift join and the
        # mem_* gauges (same best-effort contract as everything here)
        from . import mem as mem_mod

        try:
            mem_mod.on_compile_captured(segment, published)
        except Exception:
            pass
    return published or None


def retire_compile_stats(segments):
    """Drop the per-segment xla_* gauge children for retired segment
    labels (the program-cache LRU eviction path; obs.mem retires its
    mem_* gauges through the same executor hook).  A label shared
    with a still-cached program re-publishes on its next build."""
    reg = registry_mod.get_registry()
    for gauge, _src, help_text in _MEMORY_GAUGES + _COST_GAUGES:
        fam = reg.gauge(gauge, help_text, labelnames=("segment",))
        for segment in segments:
            fam.remove(segment=segment)
