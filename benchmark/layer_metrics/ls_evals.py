"""``ls_evals.<coordinate>``: objective evaluations per L-BFGS iteration in
the window, line-search trials and each solve's starting evaluation
included. A random-effect coordinate: ``evals_sum`` over ``iters_sum`` of its
``re_fit_wave`` rows. A fixed-effect one: the ``evaluations`` its update's
last ``opt_iter`` row carries, over that row's ``iteration``."""

from re_iters import ratio, window_waves


def read(name, ctx):
    coordinate = name.split(".", 1)[1]
    kind = ctx["cell"]["mix"]["coordinates"].get(coordinate, {}).get("type")
    if kind != "fixed":
        return ratio(window_waves(ctx, coordinate),
                     lambda r: r["evals_sum"], lambda r: r["iters_sum"])
    return ratio([r for r in ctx["ledger_rows"]
                  if r.get("kind") == "opt_iter"
                  and r.get("coordinate") == coordinate
                  and r.get("outer_iteration", -1) >= ctx["setup_sweeps"]
                  and r.get("evaluations") is not None],
                 lambda r: r["evaluations"], lambda r: r["iteration"])
