"""CI solver-race smoke: L-BFGS and SDCA must both finish the SAME tiny
streamed fit, leave comparable ledger curves, and diff with the
duality-gap overlay (ISSUE 16 satellite: run_tier1.sh gains this step).

Asserts, in order:

1. two ``game_train`` runs over one dataset — the streamed fixed
   coordinate under ``solver=lbfgs`` (the DSL default) and under
   ``solver=sdca`` — both converge and write healthy ledgers;
2. the SDCA ledger's ``opt_iter`` rows are stamped
   ``opt=sdca-stream`` and EVERY accepted epoch carries a finite
   ``gap`` column whose trend is downward (first → last), the
   certificate contract of docs/STREAMING.md "Stochastic solvers";
3. both convergence curves reach a common target (the worse final
   value plus a relative band) — ``time_to_target`` is non-None for
   each;
4. ``photon-obs diff`` across the two runs gates the shared coordinate
   (a time-to-target ratio exists) and renders the
   "duality gap vs wall clock" overlay — the gap series must survive
   the full ledger → curves → diff → render pipeline.

Runs on CPU in seconds — wired into dev-scripts/run_tier1.sh after the
ledger smoke.
"""

import math
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _train_args(train_dir, out, solver):
    return [
        "--train", train_dir,
        "--coordinate", "name=fixed,type=fixed,shard=global",
        "--update-sequence", "fixed",
        "--opt-config", "fixed:optimizer=LBFGS,max_iter=40,reg=L2,"
                        "reg_weight=1.0",
        "--streaming", f"chunk_rows=128,num_hot=8,workers=2,"
                       f"solver={solver}",
        "--output-dir", out,
    ]


def main() -> int:
    import numpy as np

    from photon_ml_tpu.cli import game_train
    from photon_ml_tpu.cli.obs import render_diff
    from photon_ml_tpu.data import sparse as sp
    from photon_ml_tpu.data.game_data import from_sparse_batch
    from photon_ml_tpu.data.io import save_game_dataset
    from photon_ml_tpu.obs.ledger import (convergence_curves,
                                          diff_ledgers, read_rows,
                                          time_to_target, verify_ledger)

    with tempfile.TemporaryDirectory(prefix="pml_race_smoke_") as td:
        train_dir = os.path.join(td, "train")
        batch, _ = sp.synthetic_sparse(700, 64, 5, seed=11)
        save_game_dataset(from_sparse_batch(batch), train_dir)

        ledgers, curves, finals = {}, {}, {}
        for solver in ("lbfgs", "sdca"):
            out_dir = os.path.join(td, f"out-{solver}")
            game_train.run(game_train.build_parser().parse_args(
                _train_args(train_dir, out_dir, solver)))
            ledger_dir = os.path.join(out_dir, "ledger")
            problems = verify_ledger(ledger_dir)
            if problems:
                print(f"{solver} ledger verification FAILED:")
                for p in problems:
                    print(f"  - {p}")
                return 1
            rows, _ = read_rows(ledger_dir)
            by_coord = convergence_curves(rows)
            assert "fixed" in by_coord, \
                f"{solver}: no 'fixed' curve (have {sorted(by_coord)})"
            ledgers[solver] = ledger_dir
            curves[solver] = by_coord["fixed"]
            finals[solver] = curves[solver][-1]["value"]
            if solver == "sdca":
                opt_rows = [r for r in rows if r["kind"] == "opt_iter"]
                assert opt_rows and all(
                    r.get("opt") == "sdca-stream" for r in opt_rows), \
                    "sdca rows not stamped opt=sdca-stream"
                gaps = [r.get("gap") for r in opt_rows]
                assert all(g is not None and math.isfinite(g)
                           for g in gaps), \
                    f"non-finite/missing gap on an accepted epoch: {gaps}"
                assert gaps[-1] < gaps[0], \
                    f"gap certificate never tightened: {gaps[0]} -> " \
                    f"{gaps[-1]}"

        # (3) the race quantity: both curves reach the worse final.
        worst = max(finals.values())
        target = worst + 1e-6 * max(abs(worst), 1.0)
        tt = {s: time_to_target(curves[s], target) for s in curves}
        for s, hit in tt.items():
            assert hit is not None, \
                f"{s} never reached the common target {target}"

        # (4) cross-solver diff gates the coordinate and renders the
        # gap-vs-wall overlay (SDCA emits gap, L-BFGS never does — the
        # overlay must appear because ONE side carries the series).
        diff = diff_ledgers(ledgers["lbfgs"], ledgers["sdca"])
        entry = diff["coordinates"].get("fixed")
        assert entry is not None and \
            entry.get("time_to_target_ratio") is not None, \
            f"diff gated no time-to-target ratio: {entry}"
        rendered = render_diff(diff)
        assert "duality gap vs wall clock" in rendered, \
            "gap overlay missing from photon-obs diff output"
        print(rendered)
        print(f"solver race smoke ok: lbfgs {tt['lbfgs']['seconds']:.3f}s"
              f" / sdca {tt['sdca']['seconds']:.3f}s to target "
              f"{target:.6g}; sdca gap {np.round(gaps[0], 4)} -> "
              f"{np.round(gaps[-1], 6)} over {len(gaps)} epoch(s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
