"""The full measurement suite: every leg of CONFIGS as its own
`python bench.py` process, one after the other.

A chip belongs to one process at a time, so this parent never imports
JAX or paddle_tpu: it only sets each leg's BENCH_*/FLAGS_* overrides and
waits for the child.  The legs share compiled code through JAX's
persistent compilation cache, which every bench.py puts in the same
place (JAX_COMPILATION_CACHE_DIR if the environment names one, else
<checkout>/.jax_cache — paddle_tpu/utils/compile_cache.py).

Config order = information value: the headline, then single-factor A/B
legs each pinning its flags EXPLICITLY relative to the default (a tag
must never rely on a default it means to vary), then batch/memory/layout
levers, the model suite, inference rows, and last googlenet.

Usage:  python scripts/mega_bench.py            # everything
        MEGA_CONFIGS=f32act,fused python ...    # subset
MEGA_LEG_TIMEOUT bounds one leg's wall clock (seconds, default 2400); a
leg that exceeds it is killed and counted as failed.  Each leg's record
is printed as it arrives and appended by bench.py to perf_history.jsonl
under the leg's name; the exit code is the number of legs that failed.
"""

import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CONFIGS = [
    # --- headline: the sweep-1 winner is now the flag default
    # (bf16 activations, unfused updates, plain one-pass BN stats),
    # plus the saved-stats backward fix — re-measure first ---
    ("default-b128", {}),
    # --- single-factor A/B legs vs that default (each pins only the
    # factor it varies; defaults cover the rest) ---
    ("f32act", {"BENCH_TAG": "f32act", "FLAGS_amp_bf16_act": "0"}),
    ("fused", {"BENCH_TAG": "fused", "FLAGS_fuse_optimizer": "1"}),
    ("bnshifted", {"BENCH_TAG": "bnshifted",
                   "FLAGS_bn_shifted_stats": "1"}),
    ("r3config", {"BENCH_TAG": "r3config", "FLAGS_amp_bf16_act": "0",
                  "FLAGS_fuse_optimizer": "0",
                  "FLAGS_bn_shifted_stats": "0"}),
    # --- batch/memory levers ---
    ("b256", {"BENCH_BATCH": "256"}),
    ("b256rcp8", {"BENCH_BATCH": "256", "BENCH_RECOMPUTE": "8"}),
    ("nhwc-b128", {"BENCH_LAYOUT": "NHWC"}),
    ("f32-b128", {"BENCH_AMP": "0"}),
    # --- cost-model-guided pass pipeline (compile/opt_passes.py):
    # auto_remat prices the HBM-bound b256 leg's activation peak
    # against the budget and rematerializes only when it busts ---
    ("opt-b256", {"BENCH_BATCH": "256",
                  "FLAGS_compile_passes": "default+auto_remat:stride=8"}),
    # --- device-prefetch input pipeline vs the input-bound verdict
    # (AlexNet 14% MFU): the A/B that measures the overlap win ---
    ("alexnet-pf2", {"BENCH_MODEL": "alexnet", "BENCH_PREFETCH": "2"}),
    # --- the model suite (BASELINE.md rows) ---
    ("vgg16", {"BENCH_MODEL": "vgg16"}),
    ("alexnet", {"BENCH_MODEL": "alexnet"}),
    ("lstm", {"BENCH_MODEL": "lstm", "BENCH_BATCH": "256",
              "BENCH_HIDDEN": "256"}),
    ("transformer", {"BENCH_MODEL": "transformer"}),
    # --- inference rows (IntelOptimizedPaddle.md:68-104) ---
    ("infer-resnet50", {"BENCH_MODEL": "resnet50",
                        "BENCH_MODE": "infer"}),
    # the layout+fuse pipeline applies to the inference clone (no
    # backward): NHWC accepted only on a predicted tiled-roofline win
    ("infer-resnet50-opt", {"BENCH_MODEL": "resnet50",
                            "BENCH_MODE": "infer",
                            "FLAGS_compile_passes":
                                "default+layout+fuse"}),
    ("infer-vgg19", {"BENCH_MODEL": "vgg19", "BENCH_MODE": "infer"}),
    ("infer-googlenet", {"BENCH_MODEL": "googlenet",
                         "BENCH_MODE": "infer"}),
    ("infer-alexnet", {"BENCH_MODEL": "alexnet",
                       "BENCH_MODE": "infer"}),
    # --- serving tail latency (obs/load.py): open-loop Poisson load
    # against a loopback server; the record's `latency` blob is what
    # `pperf gate --latency-tolerance` regresses on ---
    ("serving-slo", {"BENCH_SERVING": "1"}),
    # last: its ~1500-op inception graph is the one compile that never
    # finished (sweep 1, 2026-07-31: >40 min, killed)
    ("googlenet", {"BENCH_MODEL": "googlenet"}),
]

# set per leg and by nobody else: a value left over in the caller's
# environment must not leak into a leg that means the default
_MANAGED = ("BENCH_TAG", "BENCH_MODEL", "BENCH_MODE", "BENCH_BATCH",
            "BENCH_HIDDEN", "BENCH_RECOMPUTE", "BENCH_LAYOUT",
            "BENCH_AMP", "BENCH_LEG", "BENCH_MESH",
            "BENCH_MICRO_BATCH", "BENCH_PREFETCH", "BENCH_SERVING",
            "FLAGS_amp_bf16_act", "FLAGS_fuse_optimizer",
            "FLAGS_bn_shifted_stats", "FLAGS_compile_passes")


def run_leg(name, overrides, timeout):
    """One leg in a fresh bench.py process; True when it exited 0."""
    env = {k: v for k, v in os.environ.items() if k not in _MANAGED}
    env.update(overrides)
    env["BENCH_LEG"] = name  # names the leg in perf_history.jsonl
    t0 = time.perf_counter()
    try:
        rc = subprocess.run([sys.executable, "bench.py"], cwd=REPO,
                            env=env, timeout=timeout).returncode
    except subprocess.TimeoutExpired:
        rc = "killed after %ds" % timeout
    print("[mega] %s %s in %.0fs"
          % (name, "OK" if rc == 0 else "FAILED (%s)" % rc,
             time.perf_counter() - t0), file=sys.stderr, flush=True)
    return rc == 0


def main():
    subset = os.environ.get("MEGA_CONFIGS")
    names = subset.split(",") if subset else None
    timeout = float(os.environ.get("MEGA_LEG_TIMEOUT", "2400"))
    failed = []
    for name, overrides in CONFIGS:
        if names is not None and name not in names:
            continue
        print("[mega] --- %s ---" % name, file=sys.stderr, flush=True)
        if not run_leg(name, overrides, timeout):
            failed.append(name)
    print("[mega] done: %d failed %s" % (len(failed), failed),
          file=sys.stderr, flush=True)
    return len(failed)


if __name__ == "__main__":
    sys.exit(main())
