"""``re_solve_s``: device seconds, inside the traced sweep, of the operations
under the program's scope ``re.solve``: the vmapped masked-lane solves of the
random-effect waves (each lane's evaluations, line searches and two-loop
recursions; not the gathers and scatters around them). A union of device
intervals, by ``owlqn_s.py``'s reduction. A program without the scope, or a
run without a trace, reads nothing."""

import owlqn_s

SCOPE = ("re.solve",)


def seconds(ctx):
    """Read once a run (kept on ``ctx``)."""
    if "_re_solve_s" not in ctx:
        ctx["_re_solve_s"] = owlqn_s.seconds_under(ctx, SCOPE)
    return ctx["_re_solve_s"]


def read(name, ctx):
    return seconds(ctx)
