"""``coef_nnz_share.<coordinate>``: of the columns some row touches, the
share whose coefficient is not zero where the traced sweep's update of that
fixed-effect coordinate ended: ``nnz`` of its last ``opt_iter`` row (an
OWL-QN solve writes it) over ``touched_columns`` of the last ``fe_layout``
row. A program that writes neither reads nothing."""


def read(name, ctx):
    coordinate = name.split(".", 1)[1]
    rows = [r for r in ctx["ledger_rows"]
            if r.get("kind") == "opt_iter"
            and r.get("coordinate") == coordinate
            and r.get("outer_iteration") == ctx.get("traced_sweep")
            and r.get("nnz") is not None]
    lay = [r for r in ctx["ledger_rows"] if r.get("kind") == "fe_layout"
           and r.get("touched_columns")]
    if not rows or not lay:
        return None
    last = max(rows, key=lambda r: r["iteration"])
    return 100.0 * last["nnz"] / lay[-1]["touched_columns"]
