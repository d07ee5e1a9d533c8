"""The fixed-effect pass against the HBM roofline: bytes the solve needs (the
schema's ``bytes_needed("fe_pass", ctx)``) over the peak bandwidth, divided by
the time the device was busy inside the fixed update's host interval of the
traced sweep. Memory-bound: 4 FLOPs per 8 bytes on a dense shard."""


def read(name, ctx):
    tr = ctx["trace"]
    mix = ctx["cell"]["mix"]
    fixed = [c for c, v in mix["coordinates"].items() if v["type"] == "fixed"]
    if not tr or not fixed:
        return None
    busy = sum(tr["busy_by_coordinate_s"].get(c, 0.0) for c in fixed)
    need = ctx["schema"].bytes_needed(name[:-len("_roofline")], ctx)
    if busy <= 0 or need is None:
        return None
    return 100.0 * need / ctx["peak"]["hbm_bytes_per_s"] / busy
