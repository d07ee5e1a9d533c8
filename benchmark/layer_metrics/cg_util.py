"""``cg_util.<coordinate>``: of the Hessian-vector products the window's
vmapped TRON waves computed (every lane steps each CG loop until the wave's
slowest lane stops: ``hvp_wave``), the share the lanes needed
(``hvp_sum``). A program that counts no products reads nothing."""

from re_iters import ratio, window_waves


def read(name, ctx):
    waves = [r for r in window_waves(ctx, name.split(".", 1)[1])
             if r.get("hvp_wave")]
    v = ratio(waves, lambda r: r["hvp_sum"], lambda r: r["hvp_wave"])
    return None if v is None else 100.0 * v
