"""``scope_s.<group>``: device seconds, inside the traced sweep, under the
program's named scopes of that group (``scope_reduce.GROUPS``): the union of
the intervals of the device operations whose op-name path has the scope as a
component. The groups nest and overlap by design; not to be added up."""

import scope_reduce


def read(name, ctx):
    return scope_reduce.for_run(ctx).get("scope_s", {}).get(
        name.split(".", 1)[1])
