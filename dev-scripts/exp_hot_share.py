"""The sweep that backs ``sparse_fixed._HOT_EIGHTHS_OF_FREE`` (ISSUE 32):
``python3 dev-scripts/exp_hot_share.py <eighths> <run.py's arguments>`` runs
one benchmark cell with the resident hot block offered that many eighths of
the device's free memory, in run.py's own process (as ``benchmark/faults.py``
holds a fault in place), and prints the ``fe_layout`` row and the
coordinate's staging phases beside the result line. The program has no
option for the share: it is one constant, and this script is the only thing
that ever sets it to anything else. PERF.md section 6, PR 32, has the table
this produced at 2M click-log rows on one v5e."""

from __future__ import annotations

import importlib.util
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv: list[str]) -> int:
    eighths = int(argv[0])
    sys.path.insert(0, ROOT)
    from photon_ml_tpu.game.coordinates import sparse_fixed
    from photon_ml_tpu.obs.ledger import RunLedger

    sparse_fixed._HOT_EIGHTHS_OF_FREE = eighths
    record = RunLedger.record

    def echo(self, kind, **fields):
        if kind == "fe_layout" or (
                kind == "phase" and str(fields.get("name")).startswith("fe.")):
            print(f"[exp_hot_share] eighths={eighths} {kind} {fields}",
                  file=sys.stderr, flush=True)
        return record(self, kind, **fields)

    RunLedger.record = echo
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(ROOT, "benchmark", "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    return run.main(argv[1:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
