"""``fe_hot_roofline`` / ``fe_cold_roofline``: one part of the sparse fixed
effect against the HBM roofline. Bytes the traced sweep's evaluations need
for that part's non-zeros whatever implements them (the schema's
``bytes_needed("fe_hot" | "fe_cold", ctx)``: evaluations x 2 passes x
non-zeros x 8 B) over the peak bandwidth, over the device seconds under the
part's scope (``sparse_s.hot`` / ``sparse_s.cold``). The rescoring pass after
the solve runs under the same scopes and is in the seconds, not in the
bytes."""

import sparse_s


def read(name, ctx):
    kernel = name[:-len("_roofline")]  # fe_hot | fe_cold
    took = sparse_s.seconds(ctx).get(kernel[3:])
    need = ctx["schema"].bytes_needed(kernel, ctx) if took else None
    if not took or need is None:
        return None
    return 100.0 * need / ctx["peak"]["hbm_bytes_per_s"] / took
