#!/usr/bin/env bash
# CI pipeline (reference: the Travis + docker build flow,
# paddle/scripts/travis + docker/build.sh): style-ish checks, native
# build, full test suite, the driver's dry run, and a wheel.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
export XLA_FLAGS="--xla_force_host_platform_device_count=8"

echo "[ci] compile check (syntax across the tree) ..."
python -m compileall -q paddle_tpu tests examples __graft_entry__.py

echo "[ci] native runtime build ..."
make -C native

echo "[ci] full test suite (examples run for real, small shapes) ..."
# tier-1 includes tests/test_serving.py (engine/batcher/server, not
# slow-marked)
RUN_EXAMPLES=1 python -m pytest tests/ -q

echo "[ci] serving selftest (server up, one request, /metrics, drain) ..."
timeout 300 python -m paddle_tpu.tools.serve_cli --selftest

echo "[ci] obs selftest (traced train+serve, request tracing: traceparent/request_id/exemplar/tail ring, NaN health+flight loop, Perfetto JSON, unified /metrics) ..."
timeout 300 python -m paddle_tpu.tools.obs_dump --selftest

echo "[ci] chaos selftest (injected I/O fault + SIGTERM preemption + nonfinite step; supervised run must match fault-free params) ..."
timeout 300 python -m paddle_tpu.tools.chaos_cli --selftest

echo "[ci] pelastic selftest (two-phase view-change protocol over a real master with lease expiry, simulated-fleet dp 8->4->8 with densify restore, 2 real workers with one SIGTERM'd mid-step: shrink commit + shard-exact continue + rejoin grow) ..."
timeout 600 python -m paddle_tpu.tools.elastic_cli --selftest

echo "[ci] pload selftest (open-loop p99 surfaces an injected stall closed-loop hides, worst request joins its /debug/tail span tree, access-log replay reproduces count + bucket mix) ..."
timeout 300 python -m paddle_tpu.tools.load_cli --selftest

echo "[ci] pmem selftest (static timeline + counter track, static-vs-XLA drift join on lenet5, donation audit finds a forked Adam slot, forced-tiny-budget OOM flight bundle blames the peak buffer) ..."
timeout 300 python -m paddle_tpu.tools.mem_cli --selftest

echo "[ci] pcomm selftest (per-bucket comm spans in reduce order, overlap exposed-vs-hidden split, cross-host span merge with recovered clock skew) ..."
timeout 300 python -m paddle_tpu.tools.comm_cli --selftest

echo "[ci] pshard selftest (rule precedence, rules reshape the layout, plan save/load fingerprint-stable, plan-driven SPMD step on 8 devices, sharded checkpoint round-trip with zero densified vars) ..."
timeout 300 python -m paddle_tpu.tools.shard_cli --selftest

echo "[ci] pshard plan (zero-device layout build: the dp=4,mp=2 zero1 artifact must render and carry a comm floor) ..."
_plan=$(mktemp)
# all of the output is read before grep looks at it: `grep -q` leaves
# at its first match, and a writer that prints on gets a broken pipe,
# which pipefail then reports as this leg's failure
_rendered=$(timeout 300 python -m paddle_tpu.tools.shard_cli plan \
    --model lenet5 --mesh dp=4,mp=2 --batch 64 --zero-stage 1 \
    --out "$_plan")
grep -q "comm:" <<<"$_rendered" || {
    echo "[ci] pshard plan rendered no comm floor" >&2; exit 1; }
timeout 300 python -m paddle_tpu.tools.shard_cli show --plan "$_plan" \
    >/dev/null
rm -f "$_plan"

echo "[ci] proglint selftest (verifier corruptions + sharding analyzer: lenet5/golden clean on 4 dryrun meshes, seeded S-code corruptions) ..."
timeout 300 python -m paddle_tpu.tools.lint_cli --selftest --mesh dp=4,mp=2

echo "[ci] proglint golden fixtures (checked-in IR must be well-formed, not just pinned) ..."
timeout 300 python -m paddle_tpu.tools.lint_cli --golden --quiet

echo "[ci] proglint --mesh over the four dryrun mesh shapes (pinned IR must also SHARD clean) ..."
for mesh in dp=4,mp=2 dp=2,mp=2,sp=2 pp=4,dp=2 dp=2,ep=4; do
    timeout 300 python -m paddle_tpu.tools.lint_cli --golden --quiet \
        --mesh "$mesh"
done

echo "[ci] proglint --donation over golden fixtures (alias analysis must plan every pinned program with 0 errors) ..."
timeout 300 python -m paddle_tpu.tools.lint_cli --golden --quiet \
    --donation

echo "[ci] pmem audit under FLAGS_donation=auto (lenet5 must have 0 reclaimable bytes: everything provably donatable is donated or carries an A-code) ..."
timeout 300 env FLAGS_donation=auto python -m paddle_tpu.tools.mem_cli \
    audit --model lenet5 --json | python -c "
import json, sys
a = json.load(sys.stdin)
assert a['mode'] == 'auto', a['mode']
assert a['reclaimable_bytes'] == 0, \
    'lenet5 under auto left %d reclaimable bytes: %r' \
    % (a['reclaimable_bytes'], a['reclaimable'])
print('[ci] lenet5 donation audit: %d bytes donated, 0 reclaimable'
      % a['donated_bytes'])
"

echo "[ci] driver entry point ..."
# the dryrun is DEFINED on virtual CPU devices
timeout 900 python -c \
    "import __graft_entry__; __graft_entry__.dryrun_multichip(8)"

echo "[ci] wheel build ..."
# --no-build-isolation: build with the env's setuptools (works offline)
pip wheel --no-deps --no-build-isolation -w dist/ . >/dev/null
ls -l dist/*.whl

echo "[ci] green"
