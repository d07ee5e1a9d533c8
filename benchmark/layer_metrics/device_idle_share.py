"""1 - (union of device-operation intervals) / (the traced sweep)."""


def read(name, ctx):
    tr = ctx["trace"]
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
