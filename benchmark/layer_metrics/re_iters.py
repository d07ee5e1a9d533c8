"""``re_iters.<coordinate>``: L-BFGS iterations per fitted lane of that
random-effect coordinate over the window, from the solver's own counts on the
run ledger's ``re_fit_wave`` rows: sum of ``iters_sum`` over sum of
``entities_fit``."""


def window_waves(ctx, coordinate):
    """The window's ``re_fit_wave`` rows of one coordinate that carry the
    solver's counters (a program older than them writes none)."""
    return [r for r in ctx["ledger_rows"]
            if r.get("kind") == "re_fit_wave"
            and r.get("coordinate") == coordinate
            and r.get("outer_iteration", -1) >= ctx["setup_sweeps"]
            and r.get("iters_sum") is not None and r.get("entities_fit")]


def ratio(rows, num, den):
    """Sum of ``num(row)`` over sum of ``den(row)``; None with no rows or a
    zero denominator."""
    d = sum(den(r) for r in rows)
    return sum(num(r) for r in rows) / d if d else None


def read(name, ctx):
    return ratio(window_waves(ctx, name.split(".", 1)[1]),
                 lambda r: r["iters_sum"], lambda r: r["entities_fit"])
