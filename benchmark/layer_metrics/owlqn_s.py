"""``owlqn_s.orthant``: device seconds, inside the traced sweep, of the
operations under the program's scope ``owlqn.orthant``: OWL-QN's
pseudo-gradient, the orthant cut of its direction and the projection of each
trial point. A union of device intervals, as ``scope_s.*`` and ``sparse_s.*``
are (``sparse_s.py`` holds the same reduction for its two scopes alone). A
program without the scope reads nothing."""

import sys

import scope_reduce
import trace_reduce

SCOPES = {"orthant": ("owlqn.orthant",)}
MARK = "bench.mark"
DEVICE = "/device:TPU:"


def traced_sweep(ctx):
    """(parsed planes, start ns, end ns) of this run's traced sweep, parsed
    once a run (kept on ``ctx``); None where there is no trace."""
    if "_owlqn_trace" not in ctx:
        ctx["_owlqn_trace"] = _parse(ctx)
    return ctx["_owlqn_trace"]


def _parse(ctx):
    sweep = ctx.get("traced_sweep")
    if sweep is None or not ctx.get("trace"):
        return None
    try:
        with open(trace_reduce.find_xplane(ctx["trace_dir"]), "rb") as f:
            planes = scope_reduce.parse_xspace(f.read())
    except (OSError, ValueError) as e:
        print(f"owlqn_s: no trace to read: {e}", file=sys.stderr)
        return None
    marks = {}
    for p in planes:
        if p["name"].startswith(DEVICE):
            continue
        for line in p["lines"]:
            for s, _, mid in line["events"]:
                name = p["event_names"].get(mid, "")
                if name.startswith(MARK + "."):
                    marks[name[len(MARK) + 1:]] = s
    last = f"{sweep}.{ctx['cell']['mix']['update_sequence'][-1]}"
    if "start" not in marks or last not in marks:
        return None
    return planes, marks["start"], marks[last]


def seconds_under(ctx, scopes):
    """Device seconds of the traced sweep under any of ``scopes`` (a scope
    is matched inside a component of an operation's op-name path, as
    ``scope_reduce`` does); None where nothing ran under them."""
    traced = traced_sweep(ctx)
    if traced is None:
        return None
    planes, lo, hi = traced
    total, devices = 0.0, 0
    for p in planes:
        ops = [ev for ln in p["lines"] if ln["name"] == trace_reduce.OPS_LINE
               for ev in ln["events"]]
        if not p["name"].startswith(DEVICE) or not ops:
            continue
        devices += 1
        mine = {mid for mid, path in p["tf_op"].items()
                if any(scope in c for scope in scopes
                       for c in path.rsplit(":", 1)[0].split("/"))}
        total += trace_reduce.union_s(trace_reduce.clip(
            [(s, e) for s, e, mid in ops if mid in mine], lo, hi))[0]
    if not devices or not total:
        return None
    return total * 1e-9 / devices


def read(name, ctx):
    scopes = SCOPES.get(name.split(".", 1)[1])
    return seconds_under(ctx, scopes) if scopes else None
