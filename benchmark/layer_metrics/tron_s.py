"""``tron_s``: device seconds, inside the traced sweep, of the operations
under the program's scope ``tron.cg``: TRON's Steihaug conjugate-gradient
loops, each step a Hessian-vector product, the fixed effect's and every
table wave's. A union of device intervals, by ``owlqn_s.py``'s reduction. A
program without the scope, or a run without a trace, reads nothing."""

import owlqn_s

SCOPE = ("tron.cg",)


def seconds(ctx):
    """Read once a run (kept on ``ctx``)."""
    if "_tron_s" not in ctx:
        ctx["_tron_s"] = owlqn_s.seconds_under(ctx, SCOPE)
    return ctx["_tron_s"]


def read(name, ctx):
    return seconds(ctx)
