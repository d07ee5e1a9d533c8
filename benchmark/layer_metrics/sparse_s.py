"""``sparse_s.hot`` / ``sparse_s.cold``: device seconds, inside the traced
sweep, of the operations under the program's scopes ``fe.hot`` (the resident
sparse layout's dense block: its two passes per evaluation) and ``fe.cold``
(the cold classes: the scatter-add of margins and the gather of the
gradient). Unions of device intervals, as ``scope_s.*`` are; the two do not
overlap. A program without the scopes reads nothing."""

import sys

import scope_reduce
import trace_reduce

SCOPES = {"hot": "fe.hot", "cold": "fe.cold"}
MARK = "bench.mark"
DEVICE = "/device:TPU:"


def seconds(ctx) -> dict:
    """{"hot": s, "cold": s} of this run's traced sweep, read once a run
    (kept on ``ctx``); {} where there is no trace or no such scope."""
    if "_sparse_s" not in ctx:
        ctx["_sparse_s"] = _read(ctx)
    return ctx["_sparse_s"]


def _read(ctx) -> dict:
    sweep = ctx.get("traced_sweep")
    if sweep is None or not ctx.get("trace"):
        return {}
    try:
        with open(trace_reduce.find_xplane(ctx["trace_dir"]), "rb") as f:
            planes = scope_reduce.parse_xspace(f.read())
    except (OSError, ValueError) as e:
        print(f"sparse_s: no trace to read: {e}", file=sys.stderr)
        return {}
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
        return {}
    lo, hi = marks["start"], marks[last]
    total, devices = {k: 0.0 for k in SCOPES}, 0
    for p in planes:
        ops = [ev for ln in p["lines"] if ln["name"] == trace_reduce.OPS_LINE
               for ev in ln["events"]]
        if not p["name"].startswith(DEVICE) or not ops:
            continue
        devices += 1
        for key, scope in SCOPES.items():
            # a scope is matched inside a path component, as scope_reduce
            # does: a transform may wrap the component
            mine = {mid for mid, path in p["tf_op"].items()
                    if any(scope in c for c in
                           path.rsplit(":", 1)[0].split("/"))}
            total[key] += trace_reduce.union_s(trace_reduce.clip(
                [(s, e) for s, e, mid in ops if mid in mine], lo, hi))[0]
    if not devices or not any(total.values()):
        return {}
    return {k: v * 1e-9 / devices for k, v in total.items()}


def read(name, ctx):
    return seconds(ctx).get(name.split(".", 1)[1])
