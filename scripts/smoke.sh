#!/usr/bin/env bash
# Pre-commit smoke gate: never snapshot a red HEAD again.
#   scripts/smoke.sh          -> import check + fast test subset (~1 min)
#   scripts/smoke.sh --full   -> import check + full suite
set -euo pipefail
cd "$(dirname "$0")/.."

# Force CPU unconditionally: a chip belongs to one process at a time and
# the gate must never be that process.
export JAX_PLATFORMS=cpu
export XLA_FLAGS="--xla_force_host_platform_device_count=8"

echo "[smoke] import paddle_tpu ..."
python -c "import paddle_tpu; import __graft_entry__; print('  ok:', len(paddle_tpu.ops.registry.registered_ops()), 'ops registered')"

echo "[smoke] serving selftest (server up, one request, /metrics, drain) ..."
timeout 300 python -m paddle_tpu.tools.serve_cli --selftest

echo "[smoke] obs selftest (traced train+serve, request tracing: traceparent/request_id/exemplar/tail ring, NaN health+flight loop, Perfetto JSON, unified /metrics) ..."
timeout 300 python -m paddle_tpu.tools.obs_dump --selftest

echo "[smoke] chaos selftest (injected I/O fault + preemption + nonfinite; auto-resume must match fault-free run) ..."
timeout 300 python -m paddle_tpu.tools.chaos_cli --selftest

echo "[smoke] pelastic selftest (view-change protocol + simulated-fleet shrink/grow + 2-worker SIGTERM chaos drill) ..."
timeout 600 python -m paddle_tpu.tools.elastic_cli --selftest

echo "[smoke] pload selftest (open vs closed loop omission gap, tail join, replay fidelity) ..."
timeout 300 python -m paddle_tpu.tools.load_cli --selftest

echo "[smoke] pmem selftest (memory timeline, drift join, A-coded donation audit + off/auto delta, OOM flight bundle) ..."
timeout 300 python -m paddle_tpu.tools.mem_cli --selftest

echo "[smoke] pcomm selftest (comm spans, overlap split, cross-host merge) ..."
timeout 300 python -m paddle_tpu.tools.comm_cli --selftest

echo "[smoke] proglint selftest (verifier + hazard detector + executor verify gate + sharding analyzer over the 4 dryrun meshes + donation A-code corruptions) ..."
timeout 300 python -m paddle_tpu.tools.lint_cli --selftest --mesh dp=4,mp=2

echo "[smoke] pshard selftest (rule precedence, plan round-trip, plan-driven SPMD step, sharded ckpt) ..."
timeout 300 python -m paddle_tpu.tools.shard_cli --selftest

echo "[smoke] pshard plan (lenet5 on dp=4,mp=2 zero1 — the reviewable layout artifact) ..."
_plan=$(mktemp)
timeout 300 python -m paddle_tpu.tools.shard_cli plan --model lenet5 \
    --mesh dp=4,mp=2 --batch 64 --zero-stage 1 --out "$_plan"
rm -f "$_plan"

echo "[smoke] dryrun_multichip(8) ..."
# The gate's copy of the driver dryrun, pinned to the virtual CPU mesh
# this script already exports.  timeout turns a bootstrap regression
# into a loud fail.
timeout 300 env JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python -c "import __graft_entry__; __graft_entry__.dryrun_multichip(8)"

if [[ "${1:-}" == "--full" ]]; then
  echo "[smoke] full test suite ..."
  python -m pytest tests/ -x -q
else
  echo "[smoke] fast subset ..."
  python -m pytest tests/test_math_ops.py tests/test_lod_machinery.py -x -q
  python -m pytest tests/ -q --collect-only >/dev/null
fi
echo "[smoke] green"
