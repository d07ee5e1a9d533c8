"""The dense fixed effect's solves, sweep by sweep, on the chip.

    python3 dev-scripts/exp_fixed_trials.py <out.json> <benchmark/run.py's arguments>

Runs ``benchmark/run.py`` as it is and keeps, before the run's ledger is
thrown away, the fixed-effect coordinate's ``opt_iter`` rows and every
``coordinate_update`` row, and reduces them to one line a sweep: the
update's seconds, its L-BFGS iterations, its ``evaluations`` (every trial
one where the search evaluates the objective, one an iteration under a
``LineOracle``) and, where the last row carries them, its trials. With
``--trace 1`` the traced sweep's solve loops too (``exp_lane_trials``).
``EXP_SKIP_CHECK=1`` skips the reference comparison (``correct`` then says
nothing). A builder's measuring aid (PERF.md section 6, PR 38): nothing of the
benchmark reads it.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.getcwd()
sys.path[:0] = [HERE, os.path.join(HERE, "benchmark"),
                os.path.join(HERE, "dev-scripts")]


def per_sweep(rows) -> list[dict]:
    """One dict an outer iteration: the fixed update's seconds and counts."""
    fixed = {r["coordinate"] for r in rows if r.get("kind") == "opt_iter"}
    out = {}
    for r in rows:
        it = r.get("outer_iteration")
        if it is None:
            continue
        row = out.setdefault(it, {"sweep": it, "trials": 0})
        if r.get("kind") == "coordinate_update":
            row.setdefault("update_s", {})[r["coordinate"]] = r.get("seconds")
        elif r.get("kind") == "opt_iter" and r.get("coordinate") in fixed:
            row["iterations"] = max(row.get("iterations", 0),
                                    int(r["iteration"]))
            row["trials"] += int(r.get("trials") or 0)
            if r.get("evaluations") is not None:
                row["evaluations"] = int(r["evaluations"])
    return [out[k] for k in sorted(out)]


def main(argv) -> int:
    out_path, argv = argv[0], argv[1:]
    import faults
    import trace_reduce

    run = faults.load_run()
    kept = {}
    if os.environ.get("EXP_SKIP_CHECK") == "1":
        real_schema = run.load_schema

        def load_schema(name):
            mod = real_schema(name)
            mod.check = lambda *a, **k: {}
            return mod

        run.load_schema = load_schema

    if "--trace" in argv and argv[argv.index("--trace") + 1] == "1":
        from exp_lane_trials import solve_loops
        real_reduce = trace_reduce.reduce

        def reduce(trace_dir, *a, **k):
            kept["loops"] = solve_loops(
                trace_reduce.load(trace_reduce.find_xplane(trace_dir)))
            return real_reduce(trace_dir, *a, **k)

        trace_reduce.reduce = reduce

    import importlib
    ledger = importlib.import_module("photon_ml_tpu.obs.ledger")
    real_rows = ledger.read_rows

    def read_rows(path, *a, **k):
        rows, rest = real_rows(path, *a, **k)
        kept["sweeps"] = per_sweep(rows)
        kept["opt_iter"] = [r for r in rows if r.get("kind") == "opt_iter"]
        return rows, rest

    ledger.read_rows = read_rows
    try:
        return run.main(argv)
    finally:
        with open(out_path, "w") as f:
            json.dump(kept, f, indent=1)
        for s in kept.get("sweeps", []):
            print("fixed-sweep " + json.dumps(s), file=sys.stderr)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
