"""The comparison that decides ``correct``, shown to fail: its control and the
faults a training cell can have, at a size a test run can hold on the CPU.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/test_check.py -q

Each case drives ``run.main`` through a whole ``--rehearsal`` run (the look for
a chip skipped, everything else as on the chip) and reads ``correct`` from the
result line. The benchmark's own runs never run this file; the control's and
the faults' readings at the cells' own size are in PERF.md.
"""

from __future__ import annotations

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import faults  # noqa: E402

CELLS = ["ml20m-logistic.steady"]
ROWS = "60000"


@pytest.fixture(scope="module")
def run():
    return faults.load_run()


def result(run, capsys, cell, *extra):
    rc = run.main(["--workload", cell, "--seed", "2147483659", "--seconds",
                   "1", "--trace", "0", "--rehearsal", "--rows", ROWS,
                   *extra])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(run, capsys, cell):
    out = result(run, capsys, cell)
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["attempted"] == 3


@pytest.mark.parametrize("cell", CELLS)
def test_control_bfloat16_is_not_correct(run, capsys, cell):
    out = result(run, capsys, cell, "--control", "bfloat16")
    assert out["correct"] is False, out["compared"]
    over = [k for k, v in out["compared"].items() if v["value"] > v["limit"]]
    assert any(k.startswith("coef.") for k in over), out["compared"]


@pytest.mark.parametrize("cell", CELLS)
def test_unchanged_state_is_not_correct(run, capsys, cell):
    with faults.planted("unchanged", run, cell):
        out = result(run, capsys, cell)
    assert out["correct"] is False, out["compared"]
    assert out["compared"]["coef.per-user"]["value"] > 0.99


@pytest.mark.parametrize("cell", CELLS)
def test_half_the_batch_is_not_correct(run, capsys, cell):
    with faults.planted("half-batch", run, cell):
        out = result(run, capsys, cell)
    assert out["correct"] is False, out["compared"]
    assert out["compared"]["loss_1"]["value"] > 0.3


@pytest.mark.parametrize("cell", CELLS)
def test_stale_small_waves_are_not_correct(run, capsys, cell):
    with faults.planted("stale-small-waves", run, cell):
        out = result(run, capsys, cell)
    assert out["correct"] is False, out["compared"]
    over = [k for k, v in out["compared"].items() if v["value"] > v["limit"]]
    assert any(k.startswith("small.") for k in over), out["compared"]
