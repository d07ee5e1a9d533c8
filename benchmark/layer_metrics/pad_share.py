"""``pad_share.<coordinate>``: the share of the rows the window's waves
dispatched (``lanes x cap``) that is bucket padding, not an entity's row:
1 - sum of ``rows_useful`` over sum of ``rows_padded``."""

from re_iters import ratio, window_waves


def read(name, ctx):
    v = ratio([r for r in window_waves(ctx, name.split(".", 1)[1])
               if r.get("rows_padded")],
              lambda r: r["rows_useful"], lambda r: r["rows_padded"])
    return None if v is None else 100.0 * (1.0 - v)
