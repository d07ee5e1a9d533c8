"""``setup_wall_s.<part>``: set-up's wall from inside the program, on the run
ledger's clock. Set-up runs from the ledger's opening (``t`` 0: ``fit`` opens
it first thing) to the ``t`` of the last ``coordinate_update`` row of the last
set-up sweep. Each instant of it goes to the innermost interval the fit thread
(the ``thread`` of the ``fit.digest`` row) has open there:

- ``staging``: ``fit.digest``, ``fit.coordinates`` and every other ``phase``
  row of the fit thread (those under them, and the transfers of the first
  update);
- ``program_load``: the fit thread's own ``program.load`` rows (trace, lower,
  compile, and a compile's cache fetch) and what lies between two steps of
  one program (``load_spans``);
- ``compile_wait``: ``re.compile_wait`` rows, the fit thread waiting on the
  wave programs compiled ahead;
- ``stage_wait``: ``re.stage_wait`` rows, the fit thread waiting on the
  stager's next shard;
- ``sweeps``: the set-up sweeps' ``coordinate_update`` intervals (``t0`` to
  ``t``) where nothing above is open inside them;
- ``other``: what remains, where the fit thread is inside none of these.

The six parts sum to the interval by construction. ``None`` only on a ledger
whose rows carry no ``t0`` (the parent's); a part that finds no row reads 0."""

import heapq
import re
import sys

PARTS = ("staging", "program_load", "compile_wait", "stage_wait", "sweeps",
         "other")
WAITS = {"re.compile_wait": "compile_wait", "re.stage_wait": "stage_wait"}
LOAD_STEPS = ("trace", "lower", "compile")
NEXT = {("trace", "lower"), ("lower", "compile")}


def program_name(program):
    """``jit(fit_bucket)``, the lower's and compile's name, is ``fit_bucket``,
    the trace's."""
    m = re.fullmatch(r"jit\((.*)\)", str(program))
    return m.group(1) if m else str(program)


def load_spans(rows):
    """(t0, t1, program, thread) of every program's load: its trace, lower
    and compile rows, and between two of them that follow each other on
    their thread (trace then lower, lower then compile of one program) the
    time JAX spends on that program and reports under no event."""
    by_thread = {}
    for r in rows:
        if (r.get("kind") == "phase" and r.get("name") == "program.load"
                and r.get("event") in LOAD_STEPS and "t0" in r):
            by_thread.setdefault(r.get("thread"), []).append(r)
    out = []
    for thread, steps in by_thread.items():
        steps.sort(key=lambda r: float(r["t0"]))
        for a, b in zip([None] + steps, steps):
            p = program_name(b.get("program"))
            out.append((float(b["t0"]), float(b["t"]), p, thread))
            if (a is not None and program_name(a.get("program")) == p
                    and (a["event"], b["event"]) in NEXT
                    and float(b["t0"]) > float(a["t"])):
                out.append((float(a["t"]), float(b["t0"]), p, thread))
    return out


def setup_end(rows, setup_sweeps):
    """``t`` of the last ``coordinate_update`` row of the last set-up sweep,
    or None; None too where no row carries ``t0``."""
    if not any("t0" in r for r in rows
               if r.get("kind") in ("phase", "coordinate_update")):
        return None
    ends = [float(r["t"]) for r in rows
            if r.get("kind") == "coordinate_update"
            and r.get("outer_iteration", -1) == setup_sweeps - 1]
    return max(ends) if ends else None


def fit_thread(rows):
    return next((r.get("thread") for r in rows if r.get("kind") == "phase"
                 and r.get("name") == "fit.digest"), None)


def part_of(row, thread, setup_sweeps):
    """The part a row's interval is, or None where it is not the fit
    thread's."""
    if row.get("kind") == "coordinate_update":
        return ("sweeps" if row.get("outer_iteration", -1) < setup_sweeps
                else None)
    if row.get("kind") != "phase" or row.get("thread") != thread:
        return None
    name = row.get("name")
    if name == "program.load":
        return "program_load"
    return WAITS.get(name, "staging")


def tile(spans, lo, hi):
    """Seconds of [lo, hi] by the innermost open span: ``spans`` are
    (t0, t1, part), the innermost open one is the latest started (the
    shortest where two start together); ``other`` where none is open."""
    out = dict.fromkeys(PARTS, 0.0)
    spans = sorted((max(a, lo), min(b, hi), p) for a, b, p in spans
                   if b > lo and a < hi and b > a)
    points = sorted({lo, hi} | {x for a, b, _ in spans for x in (a, b)})
    heap, i = [], 0
    for a, b in zip(points, points[1:]):
        while i < len(spans) and spans[i][0] <= a:
            s0, s1, p = spans[i]
            heapq.heappush(heap, (-s0, s1, p))
            i += 1
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        out[heap[0][2] if heap else "other"] += b - a
    return out


def tiling(rows, setup_sweeps):
    """(end of set-up, the fit thread, part -> seconds), or None."""
    end = setup_end(rows, setup_sweeps)
    thread = fit_thread(rows)
    if end is None or thread is None:
        return None
    spans = [(a, b, "program_load") for a, b, _, th in load_spans(rows)
             if th == thread]
    for r in rows:
        part = part_of(r, thread, setup_sweeps)
        if part is not None and "t0" in r:
            spans.append((float(r["t0"]), float(r["t"]), part))
    return end, thread, tile(spans, 0.0, end)


def read(name, ctx):
    part = name.split(".", 1)[1]
    got = tiling(ctx["ledger_rows"], ctx["setup_sweeps"])
    if got is None:
        return None
    end, thread, parts = got
    if part == PARTS[0]:
        print(f"setup_wall_s: set-up {end:.6f} s on the ledger's clock, on "
              f"thread {thread!r}: "
              + ", ".join(f"{p} {parts[p]:.6f}" for p in PARTS)
              + f"; the parts sum to {sum(parts.values()):.6f} s",
              file=sys.stderr)
    return parts[part]
