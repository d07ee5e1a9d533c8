"""``fe_hvp_roofline``: the sparse fixed effect's Hessian-vector products
against the HBM roofline. Bytes the traced sweep's products need whatever
implements them (the schema's ``bytes_needed("fe_hvp", ctx)``: each product
two passes over the shard's non-zeros, X·v and Xᵀ(D·X·v), at 8 B a
non-zero) over the peak bandwidth, over the device seconds under ``fe.hvp``
(``fe_hvp_s``). A pass a product makes beyond those two is in the seconds
and not in the bytes."""

import fe_hvp_s


def read(name, ctx):
    took = fe_hvp_s.seconds(ctx)
    need = ctx["schema"].bytes_needed("fe_hvp", ctx) if took else None
    if not took or need is None:
        return None
    return 100.0 * need / ctx["peak"]["hbm_bytes_per_s"] / took
