"""``python3 benchmark/run.py --selfcheck``: the yardstick checked on the CPU.

The trace reduction is held to a hand-made profile whose answers are worked
out below, and to the small trace recorded on the chip that is kept in
``benchmark/selfcheck/`` with what it was read as when recorded; the two
work-count functions are held to shapes worked by hand, and the generator's
activity curve to its anchors. No chip, no program.
"""

from __future__ import annotations

import json
import os
import sys
from types import SimpleNamespace as NS

import trace_reduce
import work

HERE = os.path.dirname(os.path.abspath(__file__))
SEQ = ["fixed", "per-user", "per-item"]


def close(a, b, rel=1e-9):
    return abs(a - b) <= rel * max(abs(a), abs(b), 1e-30)


def hand_made_profile():
    """One device, times in ns. Markers at 0 / 1000 / 3000 / 4000. Device
    operations: a [100,400) b [300,600) (overlapping: union 500 inside
    ``fixed``), c [900,1200) (100 in ``fixed``, 200 in ``per-user``),
    a [2000,2500) (``per-user``), d [3900,4500) (100 inside ``per-item``, the
    rest past the window). A second line that is not 'XLA Ops' is ignored."""
    ev = lambda name, s, d: NS(name=name, start_ns=s, duration_ns=d)  # noqa: E731
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[ev("a", 100, 300), ev("b", 300, 300),
                                   ev("c", 900, 300), ev("a", 2000, 500),
                                   ev("d", 3900, 600)]),
        NS(name="XLA Modules", events=[ev("jit_f", 0, 4000)])])
    host = NS(name="/host:CPU", lines=[NS(name="python3", events=[
        ev("bench.mark.start", 0, 1), ev("bench.mark.7.fixed", 1000, 1),
        ev("bench.mark.7.per-user", 3000, 1),
        ev("bench.mark.7.per-item", 4000, 1), ev("other", 5, 5)])])
    return NS(planes=[host, device])


def check_hand_made():
    r = trace_reduce.reduce_profile(hand_made_profile(), "bench.mark", SEQ)
    ns = 1e-9
    assert r["devices"] == 1 and r["events"] == 5, r
    assert close(r["window_s"], 4000 * ns), r
    assert close(r["busy_s"], (500 + 300 + 500 + 100) * ns), r
    busy = r["busy_by_coordinate_s"]
    assert close(busy["fixed"], 600 * ns), busy
    assert close(busy["per-user"], 700 * ns), busy
    assert close(busy["per-item"], 100 * ns), busy
    ops = dict(map(tuple, r["breakdown"]["device_ops"]))
    assert close(ops["a"], 800 * ns) and close(ops["d"], 100 * ns), ops
    gaps = r["breakdown"]["idle_gaps"]
    # longest gaps: [2500,3900) = 1400 starts in per-user; [1200,2000) = 800
    assert gaps[0][0] == "during per-user update" and close(
        gaps[0][1], 1400 * ns), gaps
    assert close(gaps[1][1], 800 * ns), gaps
    total_gap = sum(g[1] for g in gaps)
    assert close(total_gap + r["busy_s"], r["window_s"]), (total_gap, r)
    assert close(trace_reduce.union_s([(0, 2), (1, 3), (5, 6)])[0], 4)


def check_recorded():
    """The trace recorded on the chip (three small jitted programs between
    the harness's markers) still reads as it did when it was recorded."""
    path = os.path.join(HERE, "selfcheck", "tiny.xplane.pb")
    with open(os.path.join(HERE, "selfcheck", "tiny.expected.json")) as f:
        want = json.load(f)
    r = trace_reduce.reduce_profile(trace_reduce.load(path), "bench.mark",
                                    SEQ)
    for k in ("events", "devices"):
        assert r[k] == want[k], (k, r[k], want[k])
    for k in ("window_s", "busy_s"):
        assert close(r[k], want[k], 1e-6), (k, r[k], want[k])
    for c in SEQ:
        assert close(r["busy_by_coordinate_s"][c],
                     want["busy_by_coordinate_s"][c], 1e-6), c
    assert 0 < r["busy_s"] < r["window_s"]
    assert [n for n, _ in r["breakdown"]["device_ops"]] == [
        n for n, _ in want["breakdown"]["device_ops"]]


def check_work():
    # 1,000 rows x 32 features, 4 iterations: 5 evaluations, X read twice
    assert work.fe_pass_bytes(1000, 32, 4) == 5 * 2 * 1000 * 32 * 4 == 1280000
    assert work.solve_evaluations("logistic", 8, 25) == 26
    assert work.solve_evaluations("linear", 8, 25) == 9
    try:
        work.solve_evaluations("poisson", 8, 25)
    except ValueError:
        pass
    else:
        raise AssertionError("an unknown task was counted")
    # three entities of 2, 5 and 9 rows under a cap of 5 train on 2 + 5 + 5
    assert work.trained_rows([2, 5, 9], 5) == 12
    assert work.trained_rows([2, 5, 9], None) == 16
    # fixed: 5 evaluations x 4 x 1000 x 32 = 640,000, rescoring 64,000; a
    # table training on all 1000 rows: 26 x 4 x 1000 x 8 = 832,000, one on
    # 900 of them: 748,800; each rescoring all rows, 16,000
    want = 640000 + 64000 + 832000 + 748800 + 2 * 16000
    assert work.sweep_flops("logistic", 1000, 32, 4,
                            [(8, 1000), (8, 900)], 25) == want
    want = 640000 + 64000 + 9 * 4 * 8 * (1000 + 900) + 2 * 16000
    assert work.sweep_flops("linear", 1000, 32, 4,
                            [(8, 1000), (8, 900)], 25) == want


def check_activity():
    """The activity curve gives back the anchors it is laid through, sums to
    the rows asked for, and leaves no entity without a row."""
    import numpy as np
    import gen
    anchors = {"rows": 20000263, "min": 20, "q1": 35, "median": 68,
               "q3": 155, "max": 9254}
    full = gen.activity_counts(20000263, 138493, anchors)
    assert full.sum() == 20000263 and np.all(np.diff(full) >= 0)
    assert (full[0], full[-1]) == (20, 9254), (full[0], full[-1])
    assert list(np.quantile(full, [0.25, 0.5, 0.75])) == [35, 68, 155]
    half = gen.activity_counts(10000000, 138493, anchors)
    assert half.sum() == 10000000 and (half[0], half[-1]) == (10, 4627)
    # a long tail of one-row entities survives the cut with a row each
    tail = gen.activity_counts(5000, 1000, dict(
        anchors, rows=20000, min=1, q1=2, median=5, q3=15, max=900))
    assert tail.sum() == 5000 and tail.min() == 1


def main() -> int:
    for check in (check_hand_made, check_work, check_activity,
                  check_recorded):
        check()
        print(f"selfcheck {check.__name__}: ok", file=sys.stderr)
    print(json.dumps({"selfcheck": "ok"}))
    return 0
