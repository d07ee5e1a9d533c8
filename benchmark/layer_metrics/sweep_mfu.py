"""The whole sweep's share of the chip's peak: FLOPs the traced sweep needs
(the schema's ``sweep_flops``) over its seconds times the published bf16 peak.
The work is f32 and memory-bound, so this is small; it is the bound that stays
when a kernel's own roofline has gone silent."""


def read(name, ctx):
    tr = ctx["trace"]
    flops = ctx["schema"].sweep_flops(ctx) if tr else None
    if flops is None:
        return None
    chips = ctx["cell"]["chips"]
    return 100.0 * flops / (tr["window_s"] * chips
                            * ctx["peak"]["bf16_flops_per_s"])
