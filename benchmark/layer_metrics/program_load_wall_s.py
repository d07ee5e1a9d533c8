"""``program_load_wall_s``: the wall set-up spends with a program on its way to
the device on any thread: the union over every thread of the ``program.load``
rows' trace, lower and compile intervals, with what lies between two steps of
one program (``setup_wall_s.load_spans``), inside set-up's interval
(``setup_wall_s.py``). ``phase_s.program_load`` sums the same rows' seconds,
and since the wave programs compile ahead on a thread each, that sum passes
the wall. The log names the three costliest programs by the union of their
own intervals, and how many programs were compiled or fetched on the fit
thread and how many elsewhere. ``None`` on a ledger without ``t0``."""

import sys

from setup_wall_s import fit_thread, load_spans, setup_end


def union(spans):
    total, reach = 0.0, None
    for a, b in sorted(spans):
        if reach is None or a > reach:
            total += b - a
            reach = b
        elif b > reach:
            total += b - reach
            reach = b
    return total


def read(name, ctx):
    rows = ctx["ledger_rows"]
    end = setup_end(rows, ctx["setup_sweeps"])
    if end is None:
        return None
    spans = [(max(a, 0.0), min(b, end), p, th)
             for a, b, p, th in load_spans(rows) if a < end]
    by_program = {}
    for a, b, p, _ in spans:
        by_program.setdefault(p, []).append((a, b))
    costliest = sorted(((union(s), p) for p, s in by_program.items()),
                       reverse=True)[:3]
    thread = fit_thread(rows)
    compiles = [r for r in rows if r.get("kind") == "phase"
                and r.get("name") == "program.load"
                and r.get("event") == "compile" and "t0" in r
                and float(r["t0"]) < end]
    mine = sum(r.get("thread") == thread for r in compiles)
    wall = union([(a, b) for a, b, _, _ in spans])
    print(f"program_load_wall_s: {wall:.6f} s of set-up's {end:.6f} "
          f"(the spans' sum {sum(b - a for a, b, _, _ in spans):.6f} s); "
          f"costliest: " + ", ".join(f"{p} {s:.6f} s" for s, p in costliest)
          + f"; programs compiled or fetched: {mine} on the fit thread, "
          f"{len(compiles) - mine} on others", file=sys.stderr)
    return wall
