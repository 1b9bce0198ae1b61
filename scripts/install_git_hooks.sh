#!/usr/bin/env bash
# Install the repo's git hooks: pre-push runs a fast gate (syntax +
# native build + the quick test subset); the full scripts/ci.sh gate
# runs in the workflow (.github/workflows/ci.yml) and can be run
# locally before a release.
set -euo pipefail
cd "$(dirname "$0")/.."

hook=.git/hooks/pre-push
cat > "$hook" <<'EOF'
#!/usr/bin/env bash
set -euo pipefail
echo "[pre-push] fast gate (scripts/ci.sh has the full one)"
export JAX_PLATFORMS=cpu
export XLA_FLAGS="--xla_force_host_platform_device_count=8"
python -m compileall -q paddle_tpu tests examples __graft_entry__.py
make -C native -q || make -C native
# the checked-in golden ProgramDescs must be well-formed IR, not just
# byte-stable: proglint walks each fixture through the full verifier,
# the SPMD analyzer under the default dryrun mesh, AND the donation
# alias analysis (a pinned program must always plan with 0 A errors)
python -m paddle_tpu.tools.lint_cli --golden --quiet --mesh dp=4,mp=2 \
    --donation
python -m pytest tests/test_math_ops.py tests/test_fit_a_line.py -q
EOF
chmod +x "$hook"
echo "installed $hook"
