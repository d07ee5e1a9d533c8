"""The whole sweep's share of the chip's peak: FLOPs the traced sweep needs
(``work.sweep_flops``) over its seconds times the published bf16 peak. The
work is f32 and memory-bound, so this is small; it is the bound that stays
when a kernel's own roofline has gone silent."""

import gen
import work
from fe_iters import iterations


def read(name, ctx):
    tr = ctx["trace"]
    its = iterations(ctx, ctx["traced_sweep"])
    if not tr or not its:
        return None
    conf = ctx["cell"]["configuration"]
    settings = ctx["cell"]["settings"]
    tables = [(e["features"], work.trained_rows(
        gen.activity_counts(conf["num_rows"], e["count"], e["activity"]),
        settings.get("max_samples"))) for e in conf["entities"]]
    flops = work.sweep_flops(
        conf["task"], conf["num_rows"], conf["global_features"],
        its[ctx["traced_sweep"]], tables,
        int(settings["optimizer"]["max_iterations"]))
    chips = ctx["cell"]["chips"]
    return 100.0 * flops / (tr["window_s"] * chips
                            * ctx["peak"]["bf16_flops_per_s"])
