"""Host staging, program loading and the first sweeps' extra work: ``fit``
start to the end of set-up, less as many steady sweeps as set-up ran. A steady
sweep is the sum of its updates' ``train_seconds`` (mean over the window, the
traced sweep left out), so the profiler's own start and stop are not in it."""


def read(name, ctx):
    per_sweep = {}
    for u in ctx["updates"]:
        if (u["iteration"] >= ctx["setup_sweeps"]
                and u["iteration"] != ctx["traced_sweep"]):
            per_sweep[u["iteration"]] = (per_sweep.get(u["iteration"], 0.0)
                                         + u["train_seconds"])
    if not per_sweep:
        return None
    steady = sum(per_sweep.values()) / len(per_sweep)
    return (ctx["t_open"] - ctx["t_fit"]) - ctx["setup_sweeps"] * steady
