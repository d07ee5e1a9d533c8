"""L-BFGS iterations per fixed-effect update in the window, from the run
ledger's ``opt_iter`` rows (row 0 of an update is its starting value)."""


def iterations(ctx, sweep=None):
    """{outer iteration: L-BFGS iterations} of the fixed-effect coordinates'
    updates, over the window or for one sweep."""
    fixed = [c for c, v in ctx["cell"]["mix"]["coordinates"].items()
             if v["type"] == "fixed"]
    out = {}
    for r in ctx["ledger_rows"]:
        if r.get("kind") != "opt_iter" or r.get("coordinate") not in fixed:
            continue
        it = r["outer_iteration"]
        if it < ctx["setup_sweeps"] or (sweep is not None and it != sweep):
            continue
        out[it] = max(out.get(it, 0), int(r["iteration"]))
    return out


def read(name, ctx):
    its = iterations(ctx)
    return sum(its.values()) / len(its) if its else None
