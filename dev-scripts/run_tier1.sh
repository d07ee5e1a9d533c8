#!/usr/bin/env bash
# Tier-1 verify: the ROADMAP.md "Tier-1 verify" command, verbatim.
# Keep this file and ROADMAP.md in lockstep — CI and humans run this
# wrapper; the ROADMAP line is the contract.
cd "$(dirname "$0")/.."

# Static-analysis gate first: pure AST, no JAX import, seconds repo-wide.
# Findings (or a reasonless suppression/baseline entry) fail the run
# before any test spins up. See docs/ANALYSIS.md. The project graph
# (PML012-016) is on; its summary cache makes the warm re-run cheap,
# and both runs are held to the documented wall-clock budget
# (cold <= 15 s, warm <= 3 s) so "lint finishes in seconds" stays a
# tested promise, not a docstring.
rm -f .photon-lint-cache.json
t0=$(date +%s%N)
python -m photon_ml_tpu.cli.lint photon_ml_tpu/ || exit $?
t1=$(date +%s%N)
python -m photon_ml_tpu.cli.lint photon_ml_tpu/ > /dev/null || exit $?
t2=$(date +%s%N)
cold_ms=$(( (t1 - t0) / 1000000 )); warm_ms=$(( (t2 - t1) / 1000000 ))
echo "photon-lint wall: cold ${cold_ms}ms (budget 15000), warm ${warm_ms}ms (budget 3000)"
if [ "$cold_ms" -gt 15000 ] || [ "$warm_ms" -gt 3000 ]; then
  echo "photon-lint exceeded its wall-clock budget" >&2; exit 1
fi

# The string-keyed seams cross into tests and dev-scripts (fault plans,
# metric needles, span assertions) — hold those trees to the
# whole-program rules against the package registries.
python -m photon_ml_tpu.cli.lint --no-baseline \
  --select PML012,PML013,PML014,PML015,PML016 tests dev-scripts || exit $?

set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=${PIPESTATUS[0]}; echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)

# Lockdep leg (docs/ANALYSIS.md "Static vs runtime"): re-run the
# lock-heaviest suites with the runtime validator armed
# (PHOTON_LOCKDEP=1 -> conftest arms utils/lockdep.py). Any observed
# lock-order inversion fails the leg; the merged .photon-lockdep.json
# dump is then reconciled against the static graph — a runtime-only
# edge means the resolver missed a real acquisition path and must be
# fixed (or carried as an explicit --allow-gap, mirrored in
# tests/test_lockdep.py KNOWN_GAPS). Static-only edges are coverage
# debt: reported, not failing.
if [ "$rc" -eq 0 ]; then
  rm -f .photon-lockdep.json
  timeout -k 10 600 env JAX_PLATFORMS=cpu PHOTON_LOCKDEP=1 \
    python -m pytest tests/test_fleet.py tests/test_publish.py \
    tests/test_serving_trace.py -q -m 'not slow' \
    -p no:cacheprovider -p no:xdist -p no:randomly; rc=$?
  if [ "$rc" -eq 0 ] && [ -f .photon-lockdep.json ]; then
    python - <<'PY'; rc=$?
import json, sys
doc = json.load(open(".photon-lockdep.json"))
inv = doc.get("inversions", [])
for i in inv:
    print(f"lockdep inversion: {i['edge']} (prior {i['prior']}) "
          f"at {i['witness']}", file=sys.stderr)
print(f"lockdep: {len(doc.get('nodes', []))} locks, "
      f"{len(doc.get('edges', []))} edges, {len(inv)} inversions, "
      f"{len(doc.get('blocking', []))} blocking-under-lock observations")
sys.exit(1 if inv else 0)
PY
  fi
  # Known gaps (mirrored in tests/test_lockdep.py KNOWN_GAPS): the
  # strict resolver refuses to type registry-returned metric handles
  # (mx.gauge(...).set(), counter(...).inc()), so their internal locks
  # appear only at runtime. Leaf-lock edges into obs/metrics primitives
  # are terminal — those locks guard one dict/float and call nothing.
  if [ "$rc" -eq 0 ] && [ -f .photon-lockdep.json ]; then
    python -m photon_ml_tpu.cli.lint --locks \
      --reconcile .photon-lockdep.json \
      --allow-gap 'photon_ml_tpu.serving.batcher.MicroBatcher._cond -> photon_ml_tpu.obs.metrics.Gauge._lock' \
      --allow-gap 'photon_ml_tpu.serving.service.ScoringService._lock -> photon_ml_tpu.obs.metrics.Counter._lock' \
      photon_ml_tpu/ || rc=$?
  fi
fi

# Trace smoke (docs/OBSERVABILITY.md): a tiny traced game_train run must
# yield a Chrome-loadable trace whose spans nest and whose bridged
# Start/Finish pairs all closed, then a second streamed run at
# --streaming dtype=int8 must tag every transfer counter/span with its
# dtype and hold the kernel-build count at warmup levels
# (docs/STREAMING.md "Quantized streaming"). Seconds on CPU; catches a
# broken observability layer before it reaches a 90-minute flagship run.
if [ "$rc" -eq 0 ]; then
  env JAX_PLATFORMS=cpu python dev-scripts/trace_smoke.py; rc=$?
fi

# Serving trace smoke (docs/SERVING.md): a tiny traced QPS run through
# the real HTTP path — request spans parent into flush spans and close,
# /slo parses, steady-state recompiles stay zero. Seconds on CPU.
if [ "$rc" -eq 0 ]; then
  env JAX_PLATFORMS=cpu python dev-scripts/serving_trace_smoke.py; rc=$?
fi

# Fleet smoke (docs/SERVING.md "Scaling out"): 2 subprocess replicas,
# SIGKILL one mid-traffic, assert bit-identical scores through the
# failure, shard re-home within deadline, degraded /healthz that
# clears, and moved photon_fleet_* counters. Seconds on CPU.
if [ "$rc" -eq 0 ]; then
  env JAX_PLATFORMS=cpu python dev-scripts/fleet_smoke.py; rc=$?
fi

# Elastic smoke (docs/SERVING.md "Elastic fleet"): a 2-replica fleet
# under a seeded hot-spot must split the hot shard + scale up within
# deadline, scores bit-identical throughout, and the elastic ledger
# rows + events render via photon-obs tail --elastic. Seconds on CPU.
if [ "$rc" -eq 0 ]; then
  env JAX_PLATFORMS=cpu python dev-scripts/elastic_smoke.py; rc=$?
fi

# Ledger smoke (docs/OBSERVABILITY.md "The run ledger"): a tiny fit
# must leave a CRC-committed, seq-contiguous run ledger whose
# run-vs-itself diff reports zero convergence regression. Seconds on CPU.
if [ "$rc" -eq 0 ]; then
  env JAX_PLATFORMS=cpu python dev-scripts/ledger_smoke.py; rc=$?
fi

# Solver race smoke (docs/STREAMING.md "Stochastic solvers"): the same
# tiny streamed fit under solver=lbfgs and solver=sdca — both converge,
# every accepted SDCA epoch carries a finite tightening duality-gap
# certificate, both curves reach a common target, and photon-obs diff
# across the two runs renders the gap-vs-wall overlay. Seconds on CPU.
if [ "$rc" -eq 0 ]; then
  env JAX_PLATFORMS=cpu python dev-scripts/solver_race_smoke.py; rc=$?
fi

# Publish smoke (docs/SERVING.md "Continuous publication"): a 2-replica
# fleet runs one refit->delta->canary->hot-swap cycle with cold-restart
# score parity, plus a rejected delta auto-rolled back; the publish
# ledger renders and photon_publish_* counters move. Seconds on CPU.
if [ "$rc" -eq 0 ]; then
  env JAX_PLATFORMS=cpu python dev-scripts/publish_smoke.py; rc=$?
fi

# Boot smoke (docs/SERVING.md "Sub-second restart"): publish a mapped
# generation, mmap-boot a subprocess replica from the generation root,
# assert bit-identical scores vs a cold npz boot, the
# photon_boot_seconds waterfall + generation gauge + compile-cache hits
# on /metrics, and a clean post-reader CRC verify. Seconds on CPU.
if [ "$rc" -eq 0 ]; then
  env JAX_PLATFORMS=cpu python dev-scripts/boot_smoke.py; rc=$?
fi

# Kernel-registry smoke (docs/KERNELS.md): every registered Pallas
# program runs through the interpreter on CPU and matches its XLA
# reference; an enabled kernel without a backend degrades LOUDLY
# (KernelFallback + counter); warm resolves are hits, never rebuilds;
# the kernel.resolve instants render via summarize --kernels. Seconds
# on CPU.
if [ "$rc" -eq 0 ]; then
  env JAX_PLATFORMS=cpu python dev-scripts/kernel_smoke.py; rc=$?
fi

# Fabric smoke (docs/STREAMING.md "Multi-host streaming"): a REAL
# 2-process jax.distributed CPU fit with the host-level fabric armed —
# chunk ranges shard over the two ranks, host partials meet in one
# cross-host allreduce per pass, coefficients match a single-process
# streamed oracle within the 5e-3 sharded-parity band, and the shared
# ledger carries a matching fabric_digest row per accepted iteration.
# Guarded: skips loudly (rc 0) if jax.distributed cannot init here.
# ~1-2 minutes on CPU.
if [ "$rc" -eq 0 ]; then
  env JAX_PLATFORMS=cpu python dev-scripts/fabric_smoke.py; rc=$?
fi
exit $rc
