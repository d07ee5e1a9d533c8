"""``width_pad_share.<coordinate>``: the share of the cells the window's
projected waves dispatched (``lanes x cap x d_active``) that no lane's own
rows x own active columns fill: 1 - sum of ``cols_useful`` over sum of
``cols_padded``. ``pad_share`` counts padded rows; this counts padded rows and
padded columns together. A program that does not count its columns reads
nothing."""

from re_iters import ratio, window_waves


def read(name, ctx):
    v = ratio([r for r in window_waves(ctx, name.split(".", 1)[1])
               if r.get("cols_padded")],
              lambda r: r["cols_useful"], lambda r: r["cols_padded"])
    return None if v is None else 100.0 * (1.0 - v)
