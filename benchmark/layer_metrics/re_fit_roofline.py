"""``re_fit_roofline``: the random-effect waves' solves against the HBM
roofline. Bytes the traced sweep's solves need whatever implements them (the
schema's ``bytes_needed("re_fit", ctx)``: a lane's evaluations x 2 passes x
its own rows x its own active columns x 4 B, from the wave rows' counters)
over the peak bandwidth, over the device seconds under ``re.solve``
(``re_solve_s``). Padding rows, padding columns and the lanes a wave waits
for are in the seconds and not in the bytes."""

import re_solve_s


def read(name, ctx):
    took = re_solve_s.seconds(ctx)
    need = ctx["schema"].bytes_needed("re_fit", ctx) if took else None
    if not took or need is None:
        return None
    return 100.0 * need / ctx["peak"]["hbm_bytes_per_s"] / took
