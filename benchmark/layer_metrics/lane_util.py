"""``lane_util.<coordinate>``: of the lane-iterations the window's vmapped
waves ran (every lane steps until the wave's slowest has stopped:
``entities_fit x iters_max``), the share some lane needed (``iters_sum``)."""

from re_iters import ratio, window_waves


def read(name, ctx):
    v = ratio(window_waves(ctx, name.split(".", 1)[1]),
              lambda r: r["iters_sum"],
              lambda r: r["entities_fit"] * r["iters_max"])
    return None if v is None else 100.0 * v
