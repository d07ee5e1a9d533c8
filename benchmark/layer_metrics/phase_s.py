"""``phase_s.<x>``: seconds of set-up the program gives to one of its phases,
from the run ledger's ``phase`` rows written before the window opens:
``digest`` (``fit.digest``), ``bucketing`` (``re.bucketing``), ``host_stage``
(``re.host_stage``), ``transfer`` (``re.transfer`` and ``fe.transfer``), by
the name's last part; ``program_load``, the ``program.load`` rows' trace,
lower and compile steps (a ``cache_fetch`` lies inside its program's
``compile``). Host seconds: a transfer's are the enqueue's."""

import sys


def log_setup(rows, loads, end):
    """One log line: the program loads on either side of the window's
    opening (a late one names the update that recompiled), the seconds of
    set-up's own updates, and coordinate construction as a whole."""
    late = [r for r in loads if r["seq"] > end]
    recompiled = "".join(
        f"; {r.get('event')} of {r.get('program')} during "
        f"{r.get('coordinate')} of sweep {r.get('outer_iteration')}"
        for r in late[:5])
    updates = ", ".join(
        f"{r.get('coordinate')} {r.get('outer_iteration')} "
        f"{r.get('seconds')} s" for r in rows
        if r.get("kind") == "coordinate_update" and r["seq"] <= end)
    built = ", ".join(str(r.get("seconds")) for r in rows
                      if r.get("kind") == "phase"
                      and r.get("name") == "fit.coordinates")
    print(f"phase_s.program_load: {len(loads) - len(late)} program.load "
          f"steps in set-up, {len(late)} inside the window{recompiled}; "
          f"set-up's updates: {updates}; fit.coordinates {built} s",
          file=sys.stderr)


def read(name, ctx):
    part = name.split(".", 1)[1]
    rows = ctx["ledger_rows"]
    # set-up ends with the last update of its last sweep
    ends = [r["seq"] for r in rows if r.get("kind") == "coordinate_update"
            and r.get("outer_iteration", -1) == ctx["setup_sweeps"] - 1]
    phases = [r for r in rows if r.get("kind") == "phase"]
    if not ends or not phases:
        return None
    if part == "program_load":
        mine = [r for r in phases if r.get("name") == "program.load"
                and r.get("event") != "cache_fetch"]
        log_setup(rows, mine, max(ends))
    else:
        mine = [r for r in phases
                if str(r.get("name", "")).endswith("." + part)]
    mine = [r for r in mine if r["seq"] < max(ends)]
    return sum(float(r["seconds"]) for r in mine) if mine else None
