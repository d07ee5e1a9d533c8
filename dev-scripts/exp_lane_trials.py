"""What a line-search trial of a bucket solve costs on the chip, class by class.

    python3 dev-scripts/exp_lane_trials.py <out.json> <benchmark/run.py's arguments>

Runs ``benchmark/run.py`` as it is (give it ``--trace 1``) and, before the
run's trace is thrown away, reduces the traced sweep's device line once
more: every outermost ``while`` on it is one wave's solve loop, the ``while``
inside it that takes most of its time its line search, and the times an operation
of that inner loop's body ran are the trials the wave paid for (under
``vmap`` a wave pays its slowest lane's at every iteration). Beside them the
``re_fit_wave`` rows of the traced sweep, so that a class's seconds a trial
can be held against its block's bytes. A builder's measuring aid (PERF.md
section 6, PR 36): nothing of the benchmark reads it.
"""

from __future__ import annotations

import collections
import json
import os
import sys

HERE = os.getcwd()
sys.path[:0] = [HERE, os.path.join(HERE, "benchmark")]


def solve_loops(profile,
                device_prefix: str = "/device:TPU:") -> list[dict]:
    """One dict for every outermost ``while`` of the first device's
    operations line."""
    import trace_reduce

    for plane in profile.planes:
        if not plane.name.startswith(device_prefix):
            continue
        for line in plane.lines:
            if line.name != trace_reduce.OPS_LINE:
                continue
            ops = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                          trace_reduce.short(ev.name)) for ev in line.events)
            return _nest(ops)
    return []


def _nest(ops) -> list[dict]:
    out, i, n = [], 0, len(ops)
    while i < n:
        s, e, name = ops[i]
        i += 1
        if "while" not in name.split(" ", 1)[0]:
            continue
        inside = []
        while i < n and ops[i][0] < e:
            inside.append(ops[i])
            i += 1
        inner = collections.defaultdict(list)
        for s1, e1, nm in inside:
            if "while" in nm.split(" ", 1)[0]:
                inner[nm].append((s1, e1))
        row = {"solve": name, "solve_s": (e - s) * 1e-9,
               "events": len(inside)}
        if inner:
            nm, spans = max(inner.items(),
                            key=lambda p: sum(b - a for a, b in p[1]))
            body = collections.Counter(
                op for s1, e1, op in inside
                if op != nm and any(a <= s1 and e1 <= b for a, b in spans))
            trips = max(body.values()) if body else 0
            search_s = sum(b - a for a, b in spans) * 1e-9
            # What an iteration costs beside its search: the operations
            # of the solve loop's own body, heaviest first.
            rest = collections.Counter()
            for s1, e1, op in inside:
                if op != nm and not any(a <= s1 and e1 <= b
                                        for a, b in spans):
                    rest[op] += (e1 - s1) * 1e-9
            row.update(search=nm, searches=len(spans), search_s=search_s,
                       trials=trips,
                       trial_ms=search_s / trips * 1e3 if trips else None,
                       rest_ops=rest.most_common(8))
        out.append(row)
    return out


def main(argv) -> int:
    out_path, argv = argv[0], argv[1:]
    import faults
    import trace_reduce

    run = faults.load_run()
    kept = {}
    real_reduce = trace_reduce.reduce

    def reduce(trace_dir, *a, **k):
        # The profiler is on for the traced sweep alone, so every solve
        # loop on the line is that sweep's.
        kept["loops"] = solve_loops(
            trace_reduce.load(trace_reduce.find_xplane(trace_dir)))
        return real_reduce(trace_dir, *a, **k)

    trace_reduce.reduce = reduce

    import importlib
    # ``photon_ml_tpu.obs.ledger`` the attribute is a function of that name
    ledger = importlib.import_module("photon_ml_tpu.obs.ledger")
    real_rows = ledger.read_rows

    def read_rows(path, *a, **k):
        rows, rest = real_rows(path, *a, **k)
        kept["waves"] = [r for r in rows if r.get("kind") == "re_fit_wave"]
        return rows, rest

    ledger.read_rows = read_rows
    try:
        return run.main(argv)
    finally:
        with open(out_path, "w") as f:
            json.dump(kept, f, indent=1)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
