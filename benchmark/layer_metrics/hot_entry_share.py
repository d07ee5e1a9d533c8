"""``hot_entry_share``: of the non-zeros of the fixed effect's sparse shard,
the share the resident layout's dense hot block serves, from the last
``fe_layout`` row of the run ledger (``hot_entries`` over ``hot_entries`` +
``cold_entries``). A program that writes no such row reads nothing."""


def read(name, ctx):
    rows = [r for r in ctx["ledger_rows"] if r.get("kind") == "fe_layout"]
    if not rows:
        return None
    hot, cold = rows[-1].get("hot_entries"), rows[-1].get("cold_entries")
    if hot is None or cold is None or hot + cold <= 0:
        return None
    return 100.0 * hot / (hot + cold)
