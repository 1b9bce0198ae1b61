"""Process flags with env bootstrap.

TPU-native equivalent of the reference's gflags tiers (reference:
paddle/utils/Flags.cpp:18-100 flag registry; python/paddle/v2/fluid/
__init__.py:89-96 `init_gflags(--tryfromenv=...)` pulling FLAGS_* from
the environment).  Flags registered here are read at runtime by the
executor (check_nan_inf, the dtype and donation policies) and trainers.
"""

import logging
import os

__all__ = ["DEFINE_flag", "get_flag", "set_flag", "parse_flags_from_env",
           "all_flags"]

_FLAGS = {}
_log = logging.getLogger("paddle_tpu")
# FLAGS_* variables no flag answers to, each named once a process
_unknown_told = set()


def _coerce(value, default):
    if isinstance(default, bool):
        if isinstance(value, str):
            return value.lower() in ("1", "true", "yes", "on")
        return bool(value)
    if isinstance(default, int):
        return int(value)
    if isinstance(default, float):
        return float(value)
    return value


def DEFINE_flag(name, default, help_str=""):
    _FLAGS[name] = {"value": default, "default": default,
                    "help": help_str}
    return default


def get_flag(name):
    return _FLAGS[name]["value"]


def set_flag(name, value):
    f = _FLAGS[name]
    f["value"] = _coerce(value, f["default"])


def all_flags():
    return {k: v["value"] for k, v in _FLAGS.items()}


def parse_flags_from_env(names=None):
    """Read FLAGS_<name> env vars (reference: the __init__.py:89-96
    `tryfromenv` bootstrap).  A FLAGS_<x> variable with no flag <x>
    (a typo, or a flag of another checkout) sets nothing and is named
    in a warning, so that it does not pass for one that took effect."""
    for name in (names or list(_FLAGS)):
        env = os.environ.get("FLAGS_" + name)
        if env is not None:
            set_flag(name, env)
    for var in sorted(os.environ):
        name = var.removeprefix("FLAGS_")
        if name != var and name not in _FLAGS \
                and var not in _unknown_told:
            _unknown_told.add(var)
            _log.warning("%s is set in the environment and paddle_tpu "
                         "defines no flag %r: it has no effect",
                         var, name)


# core flags (reference: executor.cc:28-31, Flags.cpp)
DEFINE_flag("check_nan_inf", False,
            "scan every op output for NaN/Inf in eager mode "
            "(reference: executor.cc:29)")
DEFINE_flag("amp_bf16", False,
            "cast MXU op operands (mul/matmul/conv) to bfloat16 with "
            "f32 accumulation (see fluid.amp)")
DEFINE_flag("bn_shifted_stats", False,
            "compute batch-norm statistics in the shifted one-pass form "
            "(cancellation-safe for pathological input scales, e.g. raw "
            "0-255 pixels into the first BN).  Default off: the "
            "per-channel shift subtract defeats XLA's multi-output "
            "reduce fusion, costing a full-size pass per BN (a TPU A/B "
            "of 2026-07-31, before the ledger, ResNet-50 b128: plain "
            "2471.1 vs shifted 2129.5 img/s); the plain E[x^2]-E[x]^2 "
            "form accumulates in f32 with a >=0 clamp, fine for "
            "normalized inputs")
DEFINE_flag("xla_cost_attribution", False,
            "capture per-segment XLA memory/cost analyses at jit-build "
            "time into xla_* registry gauges (obs/health.py).  Each "
            "segment's first build per signature goes through an AOT "
            "artifact that is both published and executed (executor."
            "_run_attr_aot) — one XLA compile, no throwaway capture "
            "compile.  Default off only because the flag changes the "
            "dispatch path (AOT call instead of jax.jit's) for "
            "segments it touched; serving warmup enables it, the "
            "surface whose /metrics consumes the attribution")
DEFINE_flag("mem_budget_gb", 0.0,
            "OOM pre-flight (obs/mem.py): before compiling a program, "
            "check its static peak-HBM estimate (params + optimizer "
            "state + liveness activation peak — the S005 accounting) "
            "against this many GiB and raise MemoryBudgetError naming "
            "the top blamed buffers instead of letting the device "
            "surface an opaque RESOURCE_EXHAUSTED; the failure routes "
            "through the flight recorder like a real OOM.  0 (default) "
            "disables")
DEFINE_flag("verify_program", False,
            "run paddle_tpu.analysis verification on every program "
            "before its FIRST compile (per executor + program "
            "version): structural + infer-shape re-derivation + "
            "write/alias hazards.  Error-severity findings raise "
            "ProgramVerificationError naming the op index and "
            "variable instead of surfacing as an opaque XLA trace "
            "error.  Default off: the full check re-derives every "
            "op's output meta through jax.eval_shape, a build-time "
            "cost that the surfaces opting into verification (tests, "
            "serving warmup, the proglint CLI) pay explicitly")
DEFINE_flag("verify_sharding", False,
            "run the paddle_tpu.analysis.shard SPMD analyzer at the "
            "parallel trust boundaries BEFORE any lowering: "
            "ParallelTrainer.init / make_parallel_step analyze the "
            "program against the mesh (S0xx codes, docs/ANALYSIS.md), "
            "and the pipeline/MoE schedule constructors check their "
            "axis layouts.  Error-severity findings raise "
            "ProgramVerificationError naming op index, var, and spec "
            "instead of surfacing minutes later as an XLA GSPMD "
            "error.  Default off: the multichip dryrun, tests, and "
            "proglint --mesh opt in explicitly")
DEFINE_flag("donation", "auto",
            "jit-segment buffer donation policy (analysis/alias.py). "
            "'conservative' donates the executor's classic "
            "outputs-intersect-reads set (in-place param/state "
            "updates); 'auto' (default) additionally donates every "
            "buffer the A0xx donation-safety analysis proves dead "
            "after its segment — and degrades itself to "
            "'conservative' when the analysis fails for any reason; "
            "'off' disables donation entirely (the numerics-baseline "
            "mode: donation is value-preserving, so off/auto must "
            "match bit-for-bit).  The mode is part of the executor's "
            "program-cache key — a flag flip can never serve a stale "
            "executable")
DEFINE_flag("amp_bf16_act", True,
            "when amp_bf16 is on, keep activations bfloat16 between ops "
            "instead of casting every MXU output back to f32 — halves "
            "HBM traffic on the elementwise/norm chains; statistics, "
            "losses, and master weights stay f32")

parse_flags_from_env()
