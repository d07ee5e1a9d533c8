"""``fe_vec_roofline``: the fixed-effect solver's own vector work against the
HBM roofline. Bytes the traced sweep's iterations need for their vectors of
``num_features`` float32 whatever implements them (the schema's
``bytes_needed("fe_vec", ctx)``: the two-loop recursion's reads of the
history, the new pair's writes, the pseudo-gradient, the orthant cut and each
trial's projected candidate) over the peak bandwidth, over the device seconds
under the program's scopes ``lbfgs.direction`` and ``owlqn.orthant``. A schema
that counts no such bytes, or a program without the scopes, reads nothing."""

import owlqn_s

SCOPES = ("lbfgs.direction", "owlqn.orthant")


def read(name, ctx):
    need = ctx["schema"].bytes_needed("fe_vec", ctx)
    took = owlqn_s.seconds_under(ctx, SCOPES) if need else None
    if not took:
        return None
    return 100.0 * need / ctx["peak"]["hbm_bytes_per_s"] / took
