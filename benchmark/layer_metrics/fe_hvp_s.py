"""``fe_hvp_s``: device seconds, inside the traced sweep, of the operations
under the program's scope ``fe.hvp``: the sparse fixed effect's
Hessian-vector products (TRON's conjugate-gradient steps over the resident
hybrid layout, ``ops/hybrid_sparse.hessian_vector``). A union of device
intervals, by ``owlqn_s.py``'s reduction. A program without the scope, or a
run without a trace, reads nothing."""

import owlqn_s

SCOPE = ("fe.hvp",)


def seconds(ctx):
    """Read once a run (kept on ``ctx``)."""
    if "_fe_hvp_s" not in ctx:
        ctx["_fe_hvp_s"] = owlqn_s.seconds_under(ctx, SCOPE)
    return ctx["_fe_hvp_s"]


def read(name, ctx):
    return seconds(ctx)
