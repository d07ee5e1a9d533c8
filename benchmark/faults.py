"""The faults a training cell can have, planted underneath the timed path:
``python3 benchmark/faults.py <fault> <run.py's arguments>`` runs one cell with
the fault in place (on the chip at the cell's own size, to read what each
compared number makes of it), and ``test_check.py`` drives the same plants at
a small size on the CPU. The benchmark's own runs never import this file.

The plants themselves belong to the cell's data schema
(``benchmark/schemas/<schema>.py``, ``faults``): name -> a function that
returns the context manager holding the fault in place.
"""

from __future__ import annotations

import importlib.util
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def load_run():
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(HERE, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def planted(fault: str, run, cell: str):
    """The context manager that holds ``fault`` in place for a run of
    ``cell``."""
    conf = run.load_cell(cell)["configuration"]
    faults = run.load_schema(conf.get("schema")).faults
    if fault not in faults:
        raise ValueError(f"unknown fault {fault!r}: schema {conf['schema']!r}"
                         f" has {sorted(faults)}")
    return faults[fault]()


if __name__ == "__main__":
    run = load_run()
    cell = sys.argv[sys.argv.index("--workload") + 1]
    with planted(sys.argv[1], run, cell):
        sys.exit(run.main(sys.argv[2:]))
