"""``cg_steps.<coordinate>``: TRON's Hessian-vector products a solver
iteration over the window (one a conjugate-gradient step): for a table, Σ
``hvp_sum`` over Σ ``iters_sum`` of its ``re_fit_wave`` rows; for a fixed
effect, Σ ``hvps`` over the iterations of its ``opt_iter`` rows. A program
that counts no products, or a solver without them, reads nothing."""

from re_iters import ratio, window_waves


def read(name, ctx):
    coordinate = name.split(".", 1)[1]
    if ctx["cell"]["mix"]["coordinates"][coordinate]["type"] == "random":
        waves = window_waves(ctx, coordinate)
        if not waves or any(r.get("hvp_sum") is None for r in waves):
            return None
        v = ratio(waves, lambda r: r["hvp_sum"], lambda r: r["iters_sum"])
    else:
        rows = [r for r in ctx["ledger_rows"]
                if r.get("kind") == "opt_iter"
                and r.get("coordinate") == coordinate
                and r.get("outer_iteration", -1) >= ctx["setup_sweeps"]
                and r.get("iteration", 0) > 0]
        if not rows or any(r.get("hvps") is None for r in rows):
            return None
        v = ratio(rows, lambda r: r["hvps"], lambda r: 1)
    return v
