"""The dense fixed-effect pass against the HBM roofline: bytes the solve
needs (``work.fe_pass_bytes``) over the peak bandwidth, divided by the time
the device was busy inside the fixed update's host interval of the traced
sweep. Memory-bound: 4 FLOPs per 8 bytes."""

import work
from fe_iters import iterations


def read(name, ctx):
    tr = ctx["trace"]
    mix = ctx["cell"]["mix"]
    fixed = [c for c, v in mix["coordinates"].items() if v["type"] == "fixed"]
    its = iterations(ctx, ctx["traced_sweep"])
    if not tr or not fixed or not its:
        return None
    busy = sum(tr["busy_by_coordinate_s"].get(c, 0.0) for c in fixed)
    if busy <= 0:
        return None
    conf = ctx["cell"]["configuration"]
    need = work.fe_pass_bytes(conf["num_rows"], conf["global_features"],
                              its[ctx["traced_sweep"]])
    return 100.0 * need / ctx["peak"]["hbm_bytes_per_s"] / busy
