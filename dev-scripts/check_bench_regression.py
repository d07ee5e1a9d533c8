#!/usr/bin/env python
"""Fail if the staging/ingest lines of a fresh bench tail regress >20%
vs a baseline bench tail (--baseline, required: the repository commits
none until the chip benchmark exists — see PERF.md).

The guarded lines are the host-side cold-fit costs the parallel
pipelines (photon_ml_tpu/game/staging.py + photon_ml_tpu/ingest,
docs/STAGING.md + docs/INGEST.md) exist to bound:

  staging_bucketing_seconds            build_bucketing at 10M/1M scale
  staging_projection_seconds           SERIAL whole-bucket projection
                                       (comparable across rounds)
  staging_seconds_10m_rows_1m_entities bucketing + serial projection
  sparse_re_staging_seconds            cold RE coordinate staging
  sparse_re_staging_warm_seconds       staging-cache warm restage

plus cross-line invariants computed within the fresh tail itself:

  - the parallel projection line (staging_projection_parallel_seconds)
    may never exceed the committed serial wall by more than the band;
  - the parallel ingest rate (ingest_records_per_sec) may never fall
    more than the band below the serial native rate measured in the
    SAME tail (parallelism must not regress the serial wall);
  - the columnar ingest cache's decode-layer warm speedup
    (ingest_warm_cache_speedup) must stay >= 5x, band-adjusted — the
    "warm restarts skip Avro decode" contract;
  - the ingestion overlap invariant: end_to_end_cold_fit_seconds <=
    1.15 x max(ingest_cold_seconds, staging_plus_fit_seconds).
    Enforced on hosts with >= 4 cores (where parallel decode can
    actually shrink the decode wall); reported-only on the 1-core CI
    box, the same caveat as the staging multi-worker scaling note.

plus the serving-sweep invariants when the fresh tail carries
dev-scripts/bench_serving.py's open-loop lines (docs/SERVING.md):

  - serving_sweep_recompiles must be 0 (steady state never recompiles);
  - serving_bench_vs_metrics_{request,latency}_delta <= 10% (the sweep
    and the serving scoreboard share provenance);
  - serving_p99_vs_qps_curve banded against the committed baseline at
    matching QPS levels, when the baseline has the curve.

plus the CONVERGENCE gate (docs/OBSERVABILITY.md "The run ledger"):

  - ``time_to_target_value_seconds`` (the flagships read it from their
    run ledgers — time to achieve 99% of the run's objective drop) is
    banded against the committed baseline when both carry it, so a
    regression in HOW FAST the objective falls fails CI even when
    wall-time totals still look fine;
  - ``--ledger FRESH_DIR --baseline-ledger BASE_DIR`` compares two run
    ledgers directly (photon-obs diff machinery): per-coordinate time
    to the common target value must stay within the band.

plus, with ``--metrics-dump METRICS.prom`` (a file written by
``game_train --metrics-dump`` / ``flagship_criteo_stream.py``), a
bench-vs-metrics consistency gate: bench lines that have a counter
counterpart in the photon-obs registry (transfer seconds/bytes, peak
in-flight chunks) must agree within 10% — a bench tail and a metrics
dump from the same run can no longer silently disagree
(docs/OBSERVABILITY.md).

Usage:
  check_bench_regression.py --fresh TAIL.json --baseline BASE.json
                            [--metrics-dump METRICS.prom]
  check_bench_regression.py --run-staging     --baseline BASE.json

--fresh takes either a raw bench.py stdout object ({"metric": ...,
"secondary": {...}}) or a bare section dict (the bench_fresh_host_suite
return value). --run-staging measures a fresh tail itself by running
bench.bench_fresh_host_suite in a subprocess (several minutes at the
10M-row design scale; this is the opt-in PML_CHECK_BENCH=1 step of
dev-scripts/run_tier1.sh). Exit 0 = within band, 1 = regression,
2 = usage/baseline error.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
TOLERANCE = 0.20
# Bench line ↔ photon-obs metric counterparts (the --metrics-dump gate).
# Fractions of a second of jitter between the perf_counter wall and the
# counter's accumulated device_put time are expected; 10% is the band.
METRIC_CROSSCHECKS = {
    "criteo_stream_transfer_seconds": "photon_transfer_seconds_total",
    "stream_transfer_seconds": "photon_transfer_seconds_total",
    "criteo_stream_transfer_gb": ("photon_transfer_bytes_total",
                                  1.0 / 2 ** 30),
    "criteo_stream_peak_inflight_chunks":
        "photon_stream_inflight_chunks_peak",
}
METRICS_TOLERANCE = 0.10
# Failure-window p99 may cost up to this over the sweep's own steady
# p99 when no committed baseline carries the line yet (detection +
# failover + cold re-homed cache, all inside the window).
FLEET_FAILURE_P99_FACTOR = 10.0
# The elastic Zipf-sweep acceptance (bench_serving.py --fleet
# --zipf-sweep; docs/SERVING.md "Elastic fleet"): with the control
# loop armed, the knee at the highest skew must retain >= this
# fraction of the zero-skew knee, and the steady p99 at the highest
# skew may cost at most this factor over zero-skew. Gated only where
# `zipf_sweep_valid` (>= 4 cores — a shared single core measures
# scheduling, not shard balance); the static map's collapse is the
# reported comparison line, never a gate.
ELASTIC_KNEE_RETENTION = 0.9
ELASTIC_P99_FACTOR = 2.0
# The publish arm's bands (bench_serving.py --publish): the swap-window
# p99 may cost this over the stream's own steady p99 (the swap holds
# the flush lock for the row writes + LRU invalidation, nothing more),
# and the swap wall itself is bounded absolutely — a row swap that
# takes a second has re-staged something, not swapped rows.
PUBLISH_SWAP_P99_FACTOR = 3.0
PUBLISH_SWAP_SECONDS_MAX = 1.0
# Quantized streaming (docs/STREAMING.md): int8 payload vs f32 at
# matching chunk config — the whole point of the representation — and
# the minimum device_put fraction of the pass wall for the int8-wall
# band to be a TRANSFER claim rather than a CPU-convert measurement.
INT8_BYTES_RATIO_MAX = 0.30
QUANT_TRANSFER_BOUND_FRACTION = 0.5
# Solver race (docs/STREAMING.md "Stochastic solvers"): the two final
# fits must rank test rows the same way — the stochastic path may trade
# wall clock, never accuracy (the established 5e-3 AUC parity band).
# The time ratio is hardware truth: SDCA's cheaper passes must win
# (≤ 1.0× band-adjusted) when the stream is transfer-bound; on a
# compute-bound CPU box the ratio is reported only, like the quant wall.
SOLVER_RACE_AUC_DELTA_MAX = 5e-3
SWEEP_AUC_DELTA_MAX = 5e-3
SWEEP_ITER2PLUS_SPEEDUP_MIN = 1.5
# Kernel registry sweep (docs/KERNELS.md): a fused Pallas program and
# its registered XLA reference compute the same math, so the sweep's
# relative parity delta is a correctness tripwire, not a tolerance —
# f32 accumulation-order noise sits orders below this band. Parity
# gates on EVERY tail (interpret mode runs the same program a TPU
# would); the fused-vs-XLA timing ratio gates only where the registry
# default was flipped ON (the committed "sweep showed a win" claim)
# AND the line is timing-valid (never in interpret mode).
KERNEL_PARITY_REL_MAX = 1e-3
GUARDED = [
    "staging_bucketing_seconds",
    "staging_projection_seconds",
    "staging_seconds_10m_rows_1m_entities",
    "sparse_re_staging_seconds",
    "sparse_re_staging_warm_seconds",
]


def _lines(obj: dict) -> dict:
    """Accept a raw bench stdout object or a bare section dict."""
    if "secondary" in obj and isinstance(obj["secondary"], dict):
        return obj["secondary"]
    if "parsed" in obj and isinstance(obj["parsed"], dict):
        return _lines(obj["parsed"])
    return obj


def _fresh_from_run() -> dict:
    # Same fresh-process discipline as bench.main(): device-runtime state
    # accumulated in a long-lived parent skews the host sorts ~3x.
    with tempfile.NamedTemporaryFile(mode="r", suffix=".json") as f:
        subprocess.run(
            [sys.executable, "-c",
             "import json, sys, bench;"
             " json.dump(bench.bench_fresh_host_suite(),"
             " open(sys.argv[1], 'w'))", f.name],
            cwd=REPO, check=True)
        return json.load(f)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    src = ap.add_mutually_exclusive_group(required=False)
    src.add_argument("--fresh", help="path to a fresh bench tail JSON")
    src.add_argument("--run-staging", action="store_true",
                     help="measure a fresh staging tail now (slow)")
    ap.add_argument("--baseline",
                    help="bench tail JSON to compare against, taken on "
                         "the same machine (required with --fresh and "
                         "--run-staging)")
    ap.add_argument("--tolerance", type=float, default=TOLERANCE,
                    help="allowed fractional regression (default 0.20)")
    ap.add_argument("--metrics-dump",
                    help="photon-obs Prometheus dump from the SAME run "
                         "as --fresh: bench lines with a metric "
                         "counterpart must agree within 10%%")
    ap.add_argument("--ledger",
                    help="fresh run-ledger directory: per-coordinate "
                         "time-to-target vs --baseline-ledger must stay "
                         "within the band (docs/OBSERVABILITY.md)")
    ap.add_argument("--baseline-ledger",
                    help="baseline run-ledger directory for --ledger")
    args = ap.parse_args()

    if bool(args.ledger) != bool(args.baseline_ledger):
        print("--ledger and --baseline-ledger go together")
        return 2
    if not args.fresh and not args.run_staging and not args.ledger:
        print("need --fresh, --run-staging, or a --ledger pair")
        return 2

    base = {}  # ledger-only invocation: no bench tail to gate
    if args.fresh or args.run_staging:
        if not args.baseline:
            print("--fresh and --run-staging need --baseline")
            return 2
        try:
            with open(args.baseline) as f:
                base = _lines(json.load(f))
        except (OSError, ValueError) as e:
            print(f"cannot load baseline {args.baseline}: {e}")
            return 2
    if args.fresh:
        try:
            with open(args.fresh) as f:
                fresh = _lines(json.load(f))
        except (OSError, ValueError) as e:
            print(f"cannot load fresh tail {args.fresh}: {e}")
            return 2
    elif args.run_staging:
        fresh = _lines(_fresh_from_run())
    else:
        fresh = {}  # ledger-only invocation: no bench tail to gate

    failures = []
    band = 1.0 + args.tolerance

    def _invalid(lines, key):
        """bench.py's load/calibration gate: a line marked ``_valid:
        false`` documents a contended environment — reported only, never
        a regression verdict in either direction."""
        if lines.get(f"{key}_valid") is False:
            return lines.get(f"{key}_invalid_reason", "gated invalid")
        return None

    for key in (GUARDED if fresh else ()):  # ledger-only: no bench tail
        if key not in base:
            continue  # line did not exist in that round
        if key not in fresh:
            failures.append(f"{key}: missing from fresh tail "
                            f"(baseline {base[key]})")
            continue
        b, v = float(base[key]), float(fresh[key])
        reason = _invalid(fresh, key) or _invalid(base, key)
        if reason is not None:
            print(f"{key}: fresh {v:g} vs baseline {b:g} INVALID "
                  f"(reported only: {reason})")
            continue
        verdict = "OK" if v <= b * band else "REGRESSION"
        print(f"{key}: fresh {v:g} vs baseline {b:g} "
              f"(limit {b * band:.3g}) {verdict}")
        if v > b * band:
            failures.append(f"{key}: {v:g} > {b * band:.3g} "
                            f"(baseline {b:g} +{args.tolerance:.0%})")
    par = fresh.get("staging_projection_parallel_seconds")
    serial_base = base.get("staging_projection_seconds")
    if par is not None and serial_base is not None:
        b, v = float(serial_base), float(par)
        verdict = "OK" if v <= b * band else "REGRESSION"
        print(f"staging_projection_parallel_seconds "
              f"(workers={fresh.get('staging_workers', '?')}): fresh "
              f"{v:g} vs serial baseline {b:g} (limit {b * band:.3g}) "
              f"{verdict}")
        if v > b * band:
            failures.append(
                f"staging_projection_parallel_seconds: {v:g} > "
                f"{b * band:.3g} — the parallel pipeline is slower than "
                f"the committed serial wall")

    # --- ingestion invariants (docs/INGEST.md), within the fresh tail ---
    par_rate = fresh.get("ingest_records_per_sec")
    serial_rate = fresh.get("avro_native_records_per_sec")
    if par_rate is not None and serial_rate is not None:
        floor = float(serial_rate) / band
        verdict = "OK" if float(par_rate) >= floor else "REGRESSION"
        print(f"ingest_records_per_sec "
              f"(workers={fresh.get('ingest_workers', '?')}): fresh "
              f"{par_rate:g} vs serial-native {serial_rate:g} "
              f"(floor {floor:.3g}) {verdict}")
        if float(par_rate) < floor:
            failures.append(
                f"ingest_records_per_sec: {par_rate:g} < {floor:.3g} — "
                f"parallel ingest is slower than the serial native wall")
    warm = fresh.get("ingest_warm_cache_speedup")
    if warm is not None:
        floor = 5.0 / band
        verdict = "OK" if float(warm) >= floor else "REGRESSION"
        print(f"ingest_warm_cache_speedup: fresh {warm:g}x vs the >= 5x "
              f"contract (floor {floor:.3g}x) {verdict}")
        if float(warm) < floor:
            failures.append(
                f"ingest_warm_cache_speedup: {warm:g}x < {floor:.3g}x — "
                f"the warm mmap path no longer beats decode >= 5x")
    e2e = fresh.get("end_to_end_cold_fit_seconds")
    t_ing = fresh.get("ingest_cold_seconds")
    t_fit = fresh.get("staging_plus_fit_seconds")
    if e2e is not None and t_ing is not None and t_fit is not None:
        limit = 1.15 * max(float(t_ing), float(t_fit))
        cores = int(fresh.get("ingest_bench_cores", 0))
        ok = float(e2e) <= limit
        enforced = cores >= 4
        verdict = ("OK" if ok else
                   "REGRESSION" if enforced else
                   "over limit (reported only: "
                   f"{cores}-core host cannot shrink the decode wall)")
        print(f"end_to_end_cold_fit_seconds: fresh {e2e:g} vs "
              f"1.15 x max(ingest {t_ing:g}, staging+fit {t_fit:g}) "
              f"= {limit:.3g} {verdict}")
        if enforced and not ok:
            failures.append(
                f"end_to_end_cold_fit_seconds: {e2e:g} > {limit:.3g} — "
                f"ingestion is serializing in front of the fit again")

    # --- streamed-pass invariants (docs/STREAMING.md), within the fresh
    # tail: pinning trades spare HBM for stream traffic, so the fully-
    # pinned pass may never be slower than the unpinned one beyond the
    # band (a violation means pinning went from a lever to a liability).
    curve = fresh.get("stream_pinned_fraction_curve")
    if isinstance(curve, dict) and "0" in curve and "100" in curve:
        t0, t100 = float(curve["0"]), float(curve["100"])
        limit = t0 * band
        verdict = "OK" if t100 <= limit else "REGRESSION"
        print(f"stream_pinned_fraction_curve: fully-pinned {t100:g}s vs "
              f"unpinned {t0:g}s (limit {limit:.3g}) {verdict}")
        if t100 > limit:
            failures.append(
                f"stream_pinned_fraction_curve: fully-pinned pass "
                f"{t100:g}s > {limit:.3g}s — pinning slows the stream")
    # --- quantized-streaming invariants (docs/STREAMING.md "Quantized
    # streaming"), within the fresh tail: the int8 chunk format is a
    # pure transfer-volume play, so its BYTES must land ≤ 0.30× f32 at
    # matching chunk config, the analytic byte sum must agree with the
    # photon_transfer_bytes_total measurement of the same pass within
    # 10% (shared provenance), warm passes must never compile, and —
    # when the pass is actually transfer-bound — the int8 wall may not
    # exceed the f32 band (on a compute-bound CPU box the wall line is
    # reported only, like the <4-core ingest overlap gate).
    q_bytes = fresh.get("stream_quant_bytes_per_pass")
    if isinstance(q_bytes, dict) and "float32" in q_bytes \
            and "int8" in q_bytes:
        ratio = float(q_bytes["int8"]) / max(float(q_bytes["float32"]),
                                             1.0)
        ok = ratio <= INT8_BYTES_RATIO_MAX
        print(f"stream_quant int8/f32 bytes: {ratio:.4f} (limit "
              f"{INT8_BYTES_RATIO_MAX:g}) {'OK' if ok else 'REGRESSION'}")
        if not ok:
            failures.append(
                f"stream_quant bytes ratio: int8 moves {ratio:.2f}x the "
                f"f32 payload (> {INT8_BYTES_RATIO_MAX:g}) — the "
                f"quantized layout stopped being a transfer win")
        q_meas = fresh.get("stream_quant_metric_bytes_per_pass") or {}
        for dt, analytic_b in q_bytes.items():
            meas = q_meas.get(dt)
            if meas is None:
                continue
            denom = max(abs(float(analytic_b)), abs(float(meas)), 1e-9)
            rel = abs(float(analytic_b) - float(meas)) / denom
            ok = rel <= METRICS_TOLERANCE
            print(f"stream_quant[{dt}]: analytic {analytic_b:g}B vs "
                  f"counter {meas:g}B (delta {rel:.1%}) "
                  f"{'OK' if ok else 'DISAGREEMENT'}")
            if not ok:
                failures.append(
                    f"stream_quant[{dt}]: analytic byte sum {analytic_b:g}"
                    f" disagrees with photon_transfer_bytes_total "
                    f"{meas:g} by {rel:.1%} (> "
                    f"{METRICS_TOLERANCE:.0%})")
        misses = fresh.get("stream_quant_warm_compile_misses")
        if misses is not None:
            ok = int(misses) == 0
            print(f"stream_quant_warm_compile_misses: {misses} "
                  f"(must be 0) {'OK' if ok else 'REGRESSION'}")
            if not ok:
                failures.append(
                    f"stream_quant_warm_compile_misses: {misses} — a "
                    f"warmed quantized stream recompiled (the dtype key "
                    f"broke the one-program-per-stream invariant)")
        t_f32 = fresh.get("stream_quant_f32_pass_seconds")
        t_int8 = fresh.get("stream_quant_int8_pass_seconds")
        frac = (fresh.get("stream_quant_transfer_fraction") or {}).get(
            "float32")
        if t_f32 is not None and t_int8 is not None:
            limit = float(t_f32) * band
            bound = (frac is not None
                     and float(frac) >= QUANT_TRANSFER_BOUND_FRACTION)
            ok = float(t_int8) <= limit
            verdict = ("OK" if ok else
                       "REGRESSION" if bound else
                       "over limit (reported only: pass is compute-"
                       f"bound, transfer fraction {frac})")
            print(f"stream_quant_int8_pass_seconds: {t_int8:g}s vs f32 "
                  f"{t_f32:g}s (limit {limit:.3g}) {verdict}")
            if bound and not ok:
                failures.append(
                    f"stream_quant_int8_pass_seconds: {t_int8:g}s > "
                    f"{limit:.3g}s on a transfer-bound pass — the "
                    f"quantized stream is slower than the f32 one")

    # --- solver-race invariants (docs/STREAMING.md "Stochastic
    # solvers"), within the fresh tail: both solvers must have REACHED
    # the common target (the harness raises otherwise, so a present line
    # with non-positive seconds means the ledger provenance broke), the
    # SDCA gap certificate must be finite and non-negative, and the two
    # final fits must agree on AUC. The wall ratio is printed with the
    # load/calibration validity stamp honored — reported either way,
    # never a verdict (which solver wins is a property of the box).
    t_lb = fresh.get("solver_time_to_target_seconds_lbfgs")
    t_sd = fresh.get("solver_time_to_target_seconds_sdca")
    if t_lb is not None and t_sd is not None:
        ok = (math.isfinite(float(t_lb)) and float(t_lb) > 0
              and math.isfinite(float(t_sd)) and float(t_sd) > 0)
        print(f"solver race time-to-target: lbfgs {t_lb:g}s, sdca "
              f"{t_sd:g}s {'OK' if ok else 'REGRESSION'}")
        if not ok:
            failures.append(
                f"solver race: non-finite/non-positive time-to-target "
                f"(lbfgs {t_lb!r}, sdca {t_sd!r}) — the ledger curves "
                f"no longer carry usable provenance")
        ratio = fresh.get("solver_race_ratio")
        reason = _invalid(fresh, "solver_race")
        if ratio is not None:
            frac = fresh.get("solver_race_transfer_fraction")
            bound = (reason is None and frac is not None
                     and float(frac) >= QUANT_TRANSFER_BOUND_FRACTION)
            ok = float(ratio) <= band
            verdict = ("OK" if ok else
                       "REGRESSION" if bound else
                       "over limit (reported only: "
                       + (reason or f"compute-bound box, transfer "
                                    f"fraction {frac}") + ")")
            print(f"solver_race_ratio: sdca/lbfgs {ratio:g}x "
                  f"(limit {band:.3g}x on a transfer-bound stream) "
                  f"{verdict}")
            if bound and not ok:
                failures.append(
                    f"solver_race_ratio: {ratio:g}x > {band:.3g}x on a "
                    f"transfer-bound stream — SDCA stopped paying for "
                    f"its passes")
        g = fresh.get("solver_race_final_gap_sdca")
        if g is not None:
            ok = math.isfinite(float(g)) and float(g) >= 0.0
            print(f"solver_race_final_gap_sdca: {g:g} "
                  f"{'OK' if ok else 'REGRESSION'}")
            if not ok:
                failures.append(
                    f"solver_race_final_gap_sdca: {g!r} — the duality-"
                    f"gap certificate went non-finite or negative")
        delta = fresh.get("solver_race_auc_delta")
        if delta is not None:
            ok = float(delta) <= SOLVER_RACE_AUC_DELTA_MAX
            print(f"solver_race_auc_delta: {delta:g} (limit "
                  f"{SOLVER_RACE_AUC_DELTA_MAX:g}) "
                  f"{'OK' if ok else 'REGRESSION'}")
            if not ok:
                failures.append(
                    f"solver_race_auc_delta: {delta:g} > "
                    f"{SOLVER_RACE_AUC_DELTA_MAX:g} — the stochastic "
                    f"fit no longer matches L-BFGS ranking quality")

    # --- dirty-gated sweeps (docs/SWEEPS.md) ----------------------------
    # bench_sweep's parity ladder and perf claims. Always gated:
    # gate=0 bit-identity (rung 1 — wrong, not slow, if it breaks),
    # the gated arm's AUC band, the gate=0 wall staying in band of the
    # ungated full path (the bare `--sweep` flag must cost nothing),
    # and the steady-state gated/full iteration ratio ≤ 1.0× band —
    # once the skip fraction saturates, a gated sweep dispatches almost
    # nothing, on any box. The iter2+ SUMMED speedup ≥ 1.5× is the
    # flagship acceptance reading and includes the gated arm's one-time
    # compacted-wave compiles, which on a small CPU box are the same
    # order as the solves — so it's a verdict only when the flagship
    # config ran (sweep_flagship), reported otherwise.
    bit = fresh.get("sweep_gate0_bit_identical")
    if bit is not None:
        print(f"sweep_gate0_bit_identical: {bit} "
              f"{'OK' if bit else 'REGRESSION'}")
        if not bit:
            failures.append(
                "sweep_gate0_bit_identical: false — gate=0 no longer "
                "reproduces the ungated descent bit-for-bit (parity "
                "ladder rung 1, SWEEPS.md)")
    delta = fresh.get("sweep_auc_delta")
    if delta is not None:
        ok = float(delta) <= SWEEP_AUC_DELTA_MAX
        print(f"sweep_auc_delta: {delta:g} (limit "
              f"{SWEEP_AUC_DELTA_MAX:g}) {'OK' if ok else 'REGRESSION'}")
        if not ok:
            failures.append(
                f"sweep_auc_delta: {delta:g} > {SWEEP_AUC_DELTA_MAX:g} "
                f"— the gated fit left the full-sweep quality band "
                f"despite the final full-sweep backstop")
    w_full = fresh.get("sweep_wall_seconds_full")
    w_g0 = fresh.get("sweep_wall_seconds_gate0")
    sweep_reason = _invalid(fresh, "sweep")
    if w_full is not None and w_g0 is not None:
        ok = float(w_g0) <= float(w_full) * band
        verdict = ("OK" if ok else
                   "REGRESSION" if sweep_reason is None else
                   f"over limit (reported only: {sweep_reason})")
        print(f"sweep gate=0 wall: {w_g0:g}s vs full {w_full:g}s "
              f"(limit {band:.3g}x) {verdict}")
        if sweep_reason is None and not ok:
            failures.append(
                f"sweep gate=0 wall: {w_g0:g}s > {band:.3g}x full "
                f"{w_full:g}s — the bare --sweep flag stopped being "
                f"free")
    sr = fresh.get("sweep_steady_ratio")
    if sr is not None:
        ok = float(sr) <= band
        verdict = ("OK" if ok else
                   "REGRESSION" if sweep_reason is None else
                   f"over limit (reported only: {sweep_reason})")
        print(f"sweep_steady_ratio: gated/full {sr:g}x steady-state "
              f"sweep (limit {band:.3g}x) {verdict}")
        if sweep_reason is None and not ok:
            failures.append(
                f"sweep_steady_ratio: {sr:g}x > {band:.3g}x — a "
                f"saturated-skip gated sweep costs more than a full "
                f"one; the gate is dispatching work it shouldn't")
    sp = fresh.get("sweep_iter2plus_speedup")
    if sp is not None:
        flagship = (bool(fresh.get("sweep_flagship"))
                    and sweep_reason is None)
        ok = float(sp) >= SWEEP_ITER2PLUS_SPEEDUP_MIN
        verdict = ("OK" if ok else
                   "REGRESSION" if flagship else
                   "under limit (reported only: "
                   + (sweep_reason or "non-flagship scale, compile-"
                                      "bound arms") + ")")
        print(f"sweep_iter2plus_speedup: full/gated {sp:g}x over "
              f"iterations >= 2 (limit {SWEEP_ITER2PLUS_SPEEDUP_MIN:g}x "
              f"at flagship scale) {verdict}")
        if flagship and not ok:
            failures.append(
                f"sweep_iter2plus_speedup: {sp:g}x < "
                f"{SWEEP_ITER2PLUS_SPEEDUP_MIN:g}x at flagship scale — "
                f"dirty-gated sweeps stopped paying for their waves")

    # --- kernel-registry invariants (docs/KERNELS.md) -------------------
    # bench_kernels' sweep lines. Two gates per kernel: the parity
    # delta (always — a fused program that disagrees with its XLA
    # reference is wrong, not slow), and the fused ≤ 1.0× XLA wall
    # (band-adjusted) for kernels whose registry default is ON — a
    # flipped default cites the sweep, so the sweep must keep showing
    # the win. Interpret-stamped lines (kernel_<name>_valid: false)
    # never produce a timing verdict.
    flipped = set(fresh.get("kernel_defaults_flipped") or [])
    for kname in fresh.get("kernel_sweep_kernels") or []:
        rel = fresh.get(f"kernel_{kname}_parity_rel")
        if rel is not None:
            ok = float(rel) <= KERNEL_PARITY_REL_MAX
            print(f"kernel_{kname}_parity_rel: {float(rel):.3g} (limit "
                  f"{KERNEL_PARITY_REL_MAX:g}) "
                  f"{'OK' if ok else 'REGRESSION'}")
            if not ok:
                failures.append(
                    f"kernel_{kname}_parity_rel: {float(rel):.3g} > "
                    f"{KERNEL_PARITY_REL_MAX:g} — the fused program "
                    f"disagrees with its XLA reference (wrong, not "
                    f"slow)")
        ratio = fresh.get(f"kernel_{kname}_ratio")
        if ratio is None:
            continue
        reason = _invalid(fresh, f"kernel_{kname}")
        if reason is not None:
            print(f"kernel_{kname}_ratio: {float(ratio):g}x INVALID "
                  f"(reported only: {reason})")
            continue
        if kname not in flipped:
            print(f"kernel_{kname}_ratio: {float(ratio):g}x (reported "
                  f"only: default off, no flip claim to hold)")
            continue
        ok = float(ratio) <= band
        print(f"kernel_{kname}_ratio: {float(ratio):g}x (limit "
              f"{band:.3g}x — default flipped ON) "
              f"{'OK' if ok else 'REGRESSION'}")
        if not ok:
            failures.append(
                f"kernel_{kname}_ratio: fused is {float(ratio):g}x the "
                f"XLA wall (> {band:.3g}x) but the registry default is "
                f"ON — the flip's sweep evidence no longer holds")

    # --- quantized device-LRU invariants (docs/SERVING.md "Quantized
    # device cache"): at a fixed HBM budget the int8 cache must hold
    # ≥ 2× the entities and its hit rate may never fall below f32's
    # (equal capacity utility is the floor; the win grows with skew).
    cache_sweep = fresh.get("serving_cache_dtype_sweep")
    if isinstance(cache_sweep, dict) and "float32" in cache_sweep \
            and "int8" in cache_sweep:
        cap_ratio = (cache_sweep["int8"]["capacity"]
                     / max(cache_sweep["float32"]["capacity"], 1))
        ok = cap_ratio >= 2.0
        print(f"serving int8 cache capacity ratio: {cap_ratio:.2f}x "
              f"(floor 2x) {'OK' if ok else 'REGRESSION'}")
        if not ok:
            failures.append(
                f"serving_cache_dtype_sweep: int8 holds only "
                f"{cap_ratio:.2f}x the f32 entities at equal bytes "
                f"(< 2x) — the quantized cache stopped paying")
        h32 = float(cache_sweep["float32"]["hit_rate"])
        h8 = float(cache_sweep["int8"]["hit_rate"])
        ok = h8 >= h32 - 1e-6
        print(f"serving int8 hit rate: {h8:.4f} vs f32 {h32:.4f} at "
              f"equal HBM {'OK' if ok else 'REGRESSION'}")
        if not ok:
            failures.append(
                f"serving_cache_dtype_sweep: int8 hit rate {h8:.4f} < "
                f"f32 {h32:.4f} at equal HBM budget — more capacity "
                f"must never cache worse")
        rec = fresh.get("serving_cache_sweep_recompiles")
        if rec is not None and int(rec) != 0:
            print(f"serving_cache_sweep_recompiles: {rec} REGRESSION")
            failures.append(
                f"serving_cache_sweep_recompiles: {rec} — the "
                f"quantized scorer recompiled in steady state")

    sh = fresh.get("stream_sharded_pass_seconds")
    single = fresh.get("stream_single_pass_seconds")
    devs = int(fresh.get("stream_sharded_devices", 0))
    if sh is not None and single is not None and devs == 1:
        # At D=1 the sharded composition is the same work + an identity
        # psum — it may not cost more than the band over the plain pass.
        limit = float(single) * band
        verdict = "OK" if float(sh) <= limit else "REGRESSION"
        print(f"stream_sharded_pass_seconds (D=1): {sh:g}s vs single "
              f"{single:g}s (limit {limit:.3g}) {verdict}")
        if float(sh) > limit:
            failures.append(
                f"stream_sharded_pass_seconds: {sh:g}s > {limit:.3g}s — "
                f"the sharded composition adds overhead at D=1")

    # --- serving invariants (docs/SERVING.md, ISSUE 8) ------------------
    # The open-loop sweep's own lines, gated within the fresh tail: the
    # sweep may never recompile in steady state, and the bench's request
    # counts / latency totals must agree with the serving scoreboard
    # (they share provenance). The p99 curve is banded against the
    # committed baseline at matching QPS levels when one exists.
    rec = fresh.get("serving_sweep_recompiles")
    if rec is not None:
        verdict = "OK" if int(rec) == 0 else "REGRESSION"
        print(f"serving_sweep_recompiles: {rec} (must be 0) {verdict}")
        if int(rec) != 0:
            failures.append(
                f"serving_sweep_recompiles: {rec} != 0 — the serving "
                f"sweep recompiled in steady state (bucketing broke)")
    for key in ("serving_bench_vs_metrics_request_delta",
                "serving_bench_vs_metrics_latency_delta"):
        delta = fresh.get(key)
        if delta is None:
            continue
        ok = float(delta) <= METRICS_TOLERANCE
        print(f"{key}: {float(delta):.1%} "
              f"(limit {METRICS_TOLERANCE:.0%}) "
              f"{'OK' if ok else 'DISAGREEMENT'}")
        if not ok:
            failures.append(
                f"{key}: bench and serving metrics disagree by "
                f"{float(delta):.1%} (> {METRICS_TOLERANCE:.0%}) — the "
                f"sweep and the scoreboard cannot both be right")
    fresh_curve = fresh.get("serving_p99_vs_qps_curve")
    base_curve = base.get("serving_p99_vs_qps_curve")
    if isinstance(fresh_curve, dict) and isinstance(base_curve, dict):
        for q in sorted(set(fresh_curve) & set(base_curve), key=float):
            if fresh_curve[q] is None or base_curve[q] is None:
                continue
            b, v = float(base_curve[q]), float(fresh_curve[q])
            verdict = "OK" if v <= b * band else "REGRESSION"
            print(f"serving_p99_vs_qps_curve[{q} qps]: fresh {v:g}ms vs "
                  f"baseline {b:g}ms (limit {b * band:.3g}) {verdict}")
            if v > b * band:
                failures.append(
                    f"serving_p99_vs_qps_curve[{q}]: {v:g}ms > "
                    f"{b * band:.3g}ms — serving p99 regressed at "
                    f"{q} qps")

    # --- fleet chaos invariants (docs/SERVING.md "Scaling out") ---------
    # The bench_serving.py --fleet sweep kills a replica mid-sweep; its
    # lines carry the chaos acceptance: the kill fired, every non-shed
    # request was served, scores match the single-process oracle, the
    # dead shard re-homed within the configured deadline, and p99 during
    # the failure window stays inside the band (vs the committed
    # baseline when it has the line, else vs the sweep's own steady p99
    # scaled by FLEET_FAILURE_P99_FACTOR — detection + failover may
    # cost that much at the tail, never more).
    rehome = fresh.get("fleet_rehome_seconds")
    if rehome is not None:
        ddl = float(fresh.get("fleet_rehome_deadline_s", 5.0))
        ok = float(rehome) <= ddl
        print(f"fleet_rehome_seconds: {rehome:g}s vs deadline {ddl:g}s "
              f"{'OK' if ok else 'REGRESSION'}")
        if not ok:
            failures.append(
                f"fleet_rehome_seconds: {rehome:g}s > {ddl:g}s — the "
                f"dead replica's shards re-homed too slowly")
        if fresh.get("fleet_kill_fired") is False:
            failures.append(
                "fleet_kill_fired: the injected replica_kill never "
                "fired — the chaos sweep measured nothing")
            print("fleet_kill_fired: False REGRESSION")
        unserved = fresh.get("fleet_unserved_total")
        if unserved is not None:
            ok = int(unserved) == 0
            print(f"fleet_unserved_total: {unserved} (must be 0) "
                  f"{'OK' if ok else 'REGRESSION'}")
            if not ok:
                failures.append(
                    f"fleet_unserved_total: {unserved} non-shed "
                    f"request(s) went unserved — the failover ladder "
                    f"dropped traffic")
        if fresh.get("fleet_parity_ok") is False:
            failures.append(
                f"fleet_parity_ok: "
                f"{fresh.get('fleet_parity_mismatches')} fleet "
                f"score(s) differ from the single-process oracle "
                f"(max |d| {fresh.get('fleet_parity_max_abs_diff')}) — "
                f"routed scoring is WRONG, not merely slow")
            print("fleet_parity_ok: False REGRESSION")
        p99_fail = fresh.get("fleet_p99_during_failure_ms")
        p99_steady = fresh.get("fleet_p99_steady_ms")
        base_fail = base.get("fleet_p99_during_failure_ms")
        if p99_fail is not None:
            if base_fail is not None:
                limit = float(base_fail) * band
                src = f"baseline {base_fail:g}ms +{args.tolerance:.0%}"
            elif p99_steady is not None:
                limit = float(p99_steady) * FLEET_FAILURE_P99_FACTOR
                src = (f"steady {p99_steady:g}ms x "
                       f"{FLEET_FAILURE_P99_FACTOR:g}")
            else:
                limit = None
            if limit is not None:
                ok = float(p99_fail) <= limit
                print(f"fleet_p99_during_failure_ms: {p99_fail:g}ms vs "
                      f"{src} (limit {limit:.3g}) "
                      f"{'OK' if ok else 'REGRESSION'}")
                if not ok:
                    failures.append(
                        f"fleet_p99_during_failure_ms: {p99_fail:g}ms "
                        f"> {limit:.3g}ms — the failure-window tail "
                        f"broke its band")

    # --- multi-host fabric invariants (bench.py bench_fabric;
    # docs/STREAMING.md "Multi-host streaming", docs/SERVING.md
    # "Multi-host fleet") — guarded on line presence (committed tails
    # predate the fabric). Correctness gates (D=1 bit-parity, unserved,
    # drill parity) hold regardless of validity; the re-home wall is
    # reported-only when the drill ran on a <4-core box
    # (fabric_rehome_valid: false).
    d1 = fresh.get("fabric_d1_parity_max_abs_diff")
    if d1 is not None:
        ok = float(d1) == 0.0
        print(f"fabric_d1_parity_max_abs_diff: {d1:g} (must be 0) "
              f"{'OK' if ok else 'REGRESSION'}")
        if not ok:
            failures.append(
                f"fabric_d1_parity_max_abs_diff: {d1:g} != 0 — the "
                f"W=1 fabric short-circuit must be BIT-identical to "
                f"the local stream, or single-host results stop "
                f"reproducing on the fabric path")
    fab_rehome = fresh.get("fabric_rehome_seconds")
    if fab_rehome is not None:
        fab_valid = fresh.get("fabric_rehome_valid") is not False
        if fresh.get("fabric_recovered") is False:
            failures.append(
                "fabric_recovered: the killed machine's replica never "
                "came back up — the cross-machine drill measured a "
                "fleet that did not recover")
            print("fabric_recovered: False REGRESSION")
        if fresh.get("fabric_crossed_machines") is False:
            failures.append(
                "fabric_crossed_machines: the respawn did not fail "
                "over to the surviving machine — whole-machine death "
                "is unhandled")
            print("fabric_crossed_machines: False REGRESSION")
        ddl = float(fresh.get("fabric_rehome_deadline_s", 5.0))
        ok = float(fab_rehome) <= ddl
        print(f"fabric_rehome_seconds: {fab_rehome:g}s vs deadline "
              f"{ddl:g}s "
              f"{'OK' if ok else 'REGRESSION' if fab_valid else 'reported-only (invalid)'}")
        if fab_valid and not ok:
            failures.append(
                f"fabric_rehome_seconds: {fab_rehome:g}s > {ddl:g}s — "
                f"cross-machine shard re-home broke its deadline")
        unserved = fresh.get("fabric_unserved_total")
        if unserved is not None:
            ok = int(unserved) == 0
            print(f"fabric_unserved_total: {unserved} (must be 0) "
                  f"{'OK' if ok else 'REGRESSION'}")
            if not ok:
                failures.append(
                    f"fabric_unserved_total: {unserved} request(s) "
                    f"went unserved through the whole-machine drill — "
                    f"the cross-machine failover dropped traffic")
        if fresh.get("fabric_drill_parity_ok") is False:
            failures.append(
                f"fabric_drill_parity_ok: "
                f"{fresh.get('fabric_drill_parity_mismatches')} drill "
                f"score(s) differ from the fleet's pre-drill bits — "
                f"remote re-homed scoring is WRONG, not merely slow")
            print("fabric_drill_parity_ok: False REGRESSION")

    # --- elastic Zipf-sweep invariants (docs/SERVING.md "Elastic
    # fleet"): knee QPS and steady p99 must HOLD as skew rises with
    # the control loop armed; the static map's degradation rides
    # alongside as the reported comparison line.
    zipf_knees = fresh.get("fleet_knee_vs_skew_curve")
    if isinstance(zipf_knees, dict) and len(zipf_knees) >= 2:
        zipf_valid = fresh.get("zipf_sweep_valid") is not False
        lo = min(zipf_knees, key=float)
        hi = max(zipf_knees, key=float)
        k_lo, k_hi = float(zipf_knees[lo]), float(zipf_knees[hi])
        floor = ELASTIC_KNEE_RETENTION * k_lo
        ok = k_hi >= floor
        verdict = ("OK" if ok else
                   "REGRESSION" if zipf_valid else
                   "under floor (reported only: "
                   f"{fresh.get('zipf_sweep_invalid_reason', 'gated')})")
        print(f"fleet_knee_vs_skew_curve: s={hi} knee {k_hi:g} qps vs "
              f"s={lo} knee {k_lo:g} qps (floor {floor:.3g}) {verdict}")
        if zipf_valid and not ok:
            failures.append(
                f"fleet_knee_vs_skew_curve: knee at s={hi} is "
                f"{k_hi:g} < {floor:.3g} qps "
                f"({ELASTIC_KNEE_RETENTION:g}x the s={lo} knee) — the "
                f"elastic fleet is losing its knee to skew")
        zipf_p99 = fresh.get("fleet_p99_vs_skew_curve") or {}
        p_lo, p_hi = zipf_p99.get(lo), zipf_p99.get(hi)
        if p_lo is not None and p_hi is not None:
            limit = float(p_lo) * ELASTIC_P99_FACTOR
            ok = float(p_hi) <= limit
            verdict = ("OK" if ok else
                       "REGRESSION" if zipf_valid else
                       "over limit (reported only)")
            print(f"fleet_p99_vs_skew_curve: s={hi} p99 {p_hi:g}ms vs "
                  f"s={lo} {p_lo:g}ms (limit {limit:.3g}) {verdict}")
            if zipf_valid and not ok:
                failures.append(
                    f"fleet_p99_vs_skew_curve: p99 at s={hi} is "
                    f"{p_hi:g}ms > {limit:.3g}ms — the elastic tail "
                    f"broke its skew band")
        st_knees = fresh.get("fleet_static_knee_vs_skew_curve") or {}
        if lo in st_knees and hi in st_knees and float(st_knees[lo]):
            st_ret = float(st_knees[hi]) / float(st_knees[lo])
            el_ret = k_hi / k_lo if k_lo else 0.0
            print(f"static-map comparison (reported): knee retention "
                  f"{st_ret:.2f}x static vs {el_ret:.2f}x elastic at "
                  f"s={hi}")

    # --- publish invariants (docs/SERVING.md "Continuous publication") --
    # The bench_serving.py --publish arm lands a refit→delta→hot-swap
    # mid-stream; its lines carry the zero-drop acceptance: the swap
    # wall is bounded, p99 inside the swap window stays within band of
    # the stream's own steady p99 (or the committed baseline's window
    # p99 when it has the line), no request goes unserved, and the swap
    # never recompiles.
    swap_s = fresh.get("publish_swap_seconds")
    if swap_s is not None:
        ok = float(swap_s) <= PUBLISH_SWAP_SECONDS_MAX
        print(f"publish_swap_seconds: {swap_s:g}s vs bound "
              f"{PUBLISH_SWAP_SECONDS_MAX:g}s "
              f"{'OK' if ok else 'REGRESSION'}")
        if not ok:
            failures.append(
                f"publish_swap_seconds: {swap_s:g}s > "
                f"{PUBLISH_SWAP_SECONDS_MAX:g}s — the hot swap is not "
                f"a row swap any more")
        pub_unserved = fresh.get("publish_unserved")
        if pub_unserved is not None:
            ok = int(pub_unserved) == 0
            print(f"publish_unserved: {pub_unserved} (must be 0) "
                  f"{'OK' if ok else 'REGRESSION'}")
            if not ok:
                failures.append(
                    f"publish_unserved: {pub_unserved} request(s) "
                    f"went unserved across the publish — the "
                    f"zero-drop contract is broken")
        recompiles = fresh.get("publish_sweep_recompiles")
        if recompiles is not None and int(recompiles) != 0:
            print(f"publish_sweep_recompiles: {recompiles} REGRESSION")
            failures.append(
                f"publish_sweep_recompiles: {recompiles} — a row swap "
                f"must never change a compiled program shape")
        p99_swap = fresh.get("publish_p99_swap_window_ms")
        p99_steady = fresh.get("publish_p99_steady_ms")
        base_swap = base.get("publish_p99_swap_window_ms")
        if p99_swap is not None:
            if base_swap is not None:
                limit = float(base_swap) * band
                src = f"baseline {base_swap:g}ms +{args.tolerance:.0%}"
            elif p99_steady is not None:
                limit = float(p99_steady) * PUBLISH_SWAP_P99_FACTOR
                src = (f"steady {p99_steady:g}ms x "
                       f"{PUBLISH_SWAP_P99_FACTOR:g}")
            else:
                limit = None
            if limit is not None:
                ok = float(p99_swap) <= limit
                print(f"publish_p99_swap_window_ms: {p99_swap:g}ms vs "
                      f"{src} (limit {limit:.3g}) "
                      f"{'OK' if ok else 'REGRESSION'}")
                if not ok:
                    failures.append(
                        f"publish_p99_swap_window_ms: {p99_swap:g}ms "
                        f"> {limit:.3g}ms — the swap window's tail "
                        f"broke its band")

    # --- restart gates (bench_serving.py --restart; docs/SERVING.md
    # "Sub-second restart") ----------------------------------------------
    # The mmap claim: a warm mmap-boot replica reaches traffic in at
    # most half the npz-boot wall (band-adjusted). On boxes under 4
    # cores the interpreter tail dominates both formats, so the ratio
    # is reported-only there (restart_valid=false, stamped by the
    # bench); the zero-drop leg (restart_unserved) gates everywhere.
    restart_mmap = fresh.get("replica_restart_seconds_mmap")
    restart_npz = fresh.get("replica_restart_seconds_npz")
    if restart_mmap is not None and restart_npz is not None:
        limit = 0.5 * float(restart_npz) * band
        if fresh.get("restart_valid") is False:
            print(f"replica_restart_seconds_mmap: {restart_mmap:g}s vs "
                  f"0.5x npz {restart_npz:g}s INVALID (reported only: "
                  f"{fresh.get('restart_invalid_reason', 'gated')})")
        else:
            ok = float(restart_mmap) <= limit
            print(f"replica_restart_seconds_mmap: {restart_mmap:g}s vs "
                  f"0.5x npz {restart_npz:g}s (limit {limit:.3g}) "
                  f"{'OK' if ok else 'REGRESSION'}")
            if not ok:
                failures.append(
                    f"replica_restart_seconds_mmap: {restart_mmap:g}s "
                    f"> {limit:.3g}s — the mmap boot no longer halves "
                    f"the restart wall")
        speedup = fresh.get("boot_map_load_speedup")
        if speedup is not None:
            print(f"boot_map_load_speedup: {speedup:g}x (model tier, "
                  f"in-process; reported)")
        r_unserved = fresh.get("restart_unserved")
        if r_unserved is not None:
            ok = int(r_unserved) == 0
            print(f"restart_unserved: {r_unserved} (must be 0) "
                  f"{'OK' if ok else 'REGRESSION'}")
            if not ok:
                failures.append(
                    f"restart_unserved: {r_unserved} request(s) went "
                    f"unserved across the kill+restart — retries must "
                    f"follow the re-home")
        if fresh.get("restart_parity_ok") is False:
            print("restart_parity_ok: False REGRESSION")
            failures.append(
                "restart_parity_ok: mmap-booted replica scores differ "
                "from the npz oracle — the formats must be "
                "bit-identical")

    # --- convergence gate (docs/OBSERVABILITY.md "The run ledger") ------
    # Time-to-target regressions fail CI even when wall totals look
    # fine: a fit that takes the same 90 minutes but reaches the target
    # objective half as fast has regressed in the way the papers'
    # convergence-vs-wall-clock curves actually measure.
    ttt_base = base.get("time_to_target_value_seconds")
    ttt_fresh = fresh.get("time_to_target_value_seconds")
    if ttt_base is not None and ttt_fresh is not None:
        b, v = float(ttt_base), float(ttt_fresh)
        verdict = "OK" if v <= b * band else "REGRESSION"
        print(f"time_to_target_value_seconds: fresh {v:g} vs baseline "
              f"{b:g} (limit {b * band:.3g}) {verdict}")
        if v > b * band:
            failures.append(
                f"time_to_target_value_seconds: {v:g} > {b * band:.3g} "
                f"— the objective falls slower than the committed round")
    if args.ledger:
        from photon_ml_tpu.obs.ledger import LedgerError, diff_ledgers

        try:
            # baseline-ledger is run A, fresh is run B: the gated ratio
            # is B's time to the common target over A's.
            d = diff_ledgers(args.baseline_ledger, args.ledger)
        except LedgerError as e:
            print(f"cannot diff ledgers: {e}")
            return 2
        gated = 0
        for coord, entry in d["coordinates"].items():
            ratio = entry.get("time_to_target_ratio")
            if ratio is None:
                continue
            gated += 1
            ok = ratio <= band
            print(f"ledger time-to-target[{coord}]: fresh/base "
                  f"{ratio:.2f}x (limit {band:.2f}x) "
                  f"{'OK' if ok else 'REGRESSION'}")
            if not ok:
                failures.append(
                    f"ledger time-to-target[{coord}]: {ratio:.2f}x > "
                    f"{band:.2f}x — convergence regressed "
                    f"(target {entry['target_value']:.6g})")
        if gated == 0:
            print("ledger diff: no coordinate with a comparable "
                  "time-to-target (nothing gated)")

    # --- bench ↔ metrics consistency (docs/OBSERVABILITY.md) ------------
    if args.metrics_dump:
        from photon_ml_tpu.obs.metrics import (metric_value,
                                               parse_prometheus_text)

        try:
            with open(args.metrics_dump) as f:
                parsed = parse_prometheus_text(f.read())
        except OSError as e:
            print(f"cannot load metrics dump {args.metrics_dump}: {e}")
            return 2
        checked = 0
        for bench_key, metric in METRIC_CROSSCHECKS.items():
            scale = 1.0
            if isinstance(metric, tuple):
                metric, scale = metric
            bench_v = fresh.get(bench_key)
            metric_v = metric_value(parsed, metric)
            if bench_v is None or metric_v is None:
                continue
            checked += 1
            metric_v *= scale
            denom = max(abs(float(bench_v)), abs(metric_v), 1e-9)
            rel = abs(float(bench_v) - metric_v) / denom
            ok = rel <= METRICS_TOLERANCE
            print(f"{bench_key}: bench {bench_v:g} vs metric {metric} "
                  f"{metric_v:g} (delta {rel:.1%}) "
                  f"{'OK' if ok else 'DISAGREEMENT'}")
            if not ok:
                failures.append(
                    f"{bench_key}: bench line {bench_v:g} disagrees "
                    f"with metric {metric} = {metric_v:g} by {rel:.1%} "
                    f"(> {METRICS_TOLERANCE:.0%}) — the bench tail and "
                    f"the metrics dump cannot both be right")
        if checked == 0:
            print("metrics dump: no overlapping bench/metric keys to "
                  "cross-check (nothing gated)")

    if failures:
        print(f"\n{len(failures)} staging regression(s) vs "
              f"{os.path.basename(args.baseline)}:")
        for f_ in failures:
            print(f"  - {f_}")
        return 1
    print("\nstaging/ingest bench lines within "
          f"{args.tolerance:.0%} of {os.path.basename(args.baseline)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
