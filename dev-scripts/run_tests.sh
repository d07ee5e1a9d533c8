#!/usr/bin/env bash
# CI entry point (reference repo's dev-scripts/ + travis analog).
# Runs the full suite on a virtual 8-device CPU mesh — no TPU required —
# then compile-checks the graft entry points the driver exercises.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
export XLA_FLAGS="--xla_force_host_platform_device_count=8"

python dev-scripts/check_reference_mount.py
# Fast tier in parallel (slow-marked tests deselected by pyproject addopts),
# then the slow tier (multi-process DCN seam + medium-scale integration)
# serially — its tests each spawn subprocesses / big arrays of their own.
python -m pytest tests/ -q -n auto "$@"
# Exit 5 = nothing collected (e.g. a -k filter matching no slow test) — fine.
python -m pytest tests/ -q -m slow "$@" || [ $? -eq 5 ]
python -c "import __graft_entry__ as g; g.entry(); g.dryrun_multichip(8)"
echo "ALL CHECKS PASSED"
