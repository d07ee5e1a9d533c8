"""``python3 benchmark/run.py --selfcheck``: the yardstick checked on the CPU.

The trace reduction is held to a hand-made profile whose answers are worked
out below, and to the small trace recorded on the chip that is kept in
``benchmark/selfcheck/`` with what it was read as when recorded; every
configuration's schema resolves and exposes what ``run.SCHEMA`` lists, and runs
the checks it brings (``selfchecks``: work counts held to shapes worked by
hand, a generator's curve to its anchors). No chip, no program.
"""

from __future__ import annotations

import json
import os
import sys
from types import SimpleNamespace as NS

import trace_reduce

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


def main(schemas: dict) -> int:
    """``schemas``: configuration name -> its schema module, which ``run.py``
    has loaded and so held to its contract; the checks a schema brings
    (``selfchecks``) run once."""
    checks = [check_hand_made, check_recorded]
    for mod in {m.__name__: m for m in schemas.values()}.values():
        checks += getattr(mod, "selfchecks", ())
    for check in checks:
        check()
        print(f"selfcheck {check.__name__}: ok", file=sys.stderr)
    for conf, mod in schemas.items():
        print(f"selfcheck {conf}: {mod.__name__} ok", file=sys.stderr)
    print(json.dumps({"selfcheck": "ok"}))
    return 0
