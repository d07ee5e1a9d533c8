"""``tron_cg_roofline``: TRON's conjugate-gradient loops against the HBM
roofline. Bytes the traced sweep's Hessian-vector products need whatever
implements them (the schema's ``bytes_needed("tron_cg", ctx)``: each product
two passes over the rows its solve owns, 4 B a cell) over the peak
bandwidth, over the device seconds under ``tron.cg`` (``tron_s``). Padding
rows and the lanes a wave waits for are in the seconds and not in the
bytes."""

import tron_s


def read(name, ctx):
    took = tron_s.seconds(ctx)
    need = ctx["schema"].bytes_needed("tron_cg", ctx) if took else None
    if not took or need is None:
        return None
    return 100.0 * need / ctx["peak"]["hbm_bytes_per_s"] / took
