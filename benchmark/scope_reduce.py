"""Device time under the program's named scopes, from the raw profiler trace.

``trace_reduce.py`` gives device time to a coordinate by the host interval it
fell in. This module reads what ``jax.profiler.ProfileData`` does not hand
out: each device operation's op-name path, which XLA keeps as the ``tf_op``
stat of the event's *metadata* (``jit(fit_bucket)/re.solve/vmap(...)/while/
body/lbfgs.line_search/while:`` ...). The program writes that path with
``jax.named_scope`` (docs/OBSERVABILITY.md, "Scopes"); under ``vmap`` a scope
is printed wrapped by the transform, so a scope is matched inside a path
component, not against the whole component. A ``while`` has no path in the
v5e's trace; it takes the scopes its body's operations share.

The ``.xplane.pb`` is decoded here, by a wire decoder of the five messages
needed (XSpace, XPlane, XLine, XEvent, XEventMetadata with its XStats):
``tensorflow``'s ``xplane_pb2`` takes 11 s to import where it exists, and the
chip's machine need not have it.

``scope_s.<group>`` is the union of the device intervals, inside the traced
sweep, of the ``XLA Ops`` events whose path has a component of that group.
Unions, so nesting (``glm.value_grad`` inside ``lbfgs.line_search``, a
``while`` around its body) is not counted twice; the groups overlap by design
and are not to be added up.

Beside the metrics, ``for_run`` prints once to stderr: device seconds per fit
wave (the k-th execution of a bucket program inside a coordinate's interval
is wave k) beside that wave's ledger counters, the share of device-busy time
under no program scope, and the longest idle gaps with the innermost program
annotation open on the host as each began. Log lines, not metrics.
"""

from __future__ import annotations

import sys
import time

import trace_reduce

# metric suffix -> the scopes whose operations it unions
GROUPS = {
    "line_search": ("lbfgs.line_search",),
    "value_grad": ("glm.value_grad",),
    "direction": ("lbfgs.direction",),
    "gather_scatter": ("re.gather", "re.scatter"),
    "score": ("fe.score", "re.score"),
}
# every scope the program writes (docs/OBSERVABILITY.md): an operation under
# none of them is "unscoped"
SCOPES = ("fe.fit", "fe.score", "re.gather", "re.solve", "re.scatter",
          "re.score", "lbfgs.direction", "lbfgs.line_search",
          "glm.value_grad")
# the program's host annotations (obs.annotated), innermost last
ANNOTATIONS = ("descent.update", "fe.fit", "fe.score", "re.fit_wave",
               "re.score", "ledger.drain")
WAVE_PROGRAMS = ("jit_fit_bucket", "jit_fit_gated", "jit_fit_gram")
MODULES_LINE = "XLA Modules"


# -- the wire decoder --------------------------------------------------------

def _fields(buf):
    """(field number, wire type, value) of one protobuf message: an int for
    a varint or a fixed-width field, a memoryview for a length-delimited
    one."""
    i, n = 0, len(buf)
    while i < n:
        key = shift = 0
        while True:
            b = buf[i]
            i += 1
            key |= (b & 0x7F) << shift
            if b < 0x80:
                break
            shift += 7
        wire = key & 7
        if wire == 0:
            v = shift = 0
            while True:
                b = buf[i]
                i += 1
                v |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
        elif wire == 2:
            ln = shift = 0
            while True:
                b = buf[i]
                i += 1
                ln |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            v = buf[i:i + ln]
            i += ln
        elif wire == 1:
            v = int.from_bytes(buf[i:i + 8], "little")
            i += 8
        elif wire == 5:
            v = int.from_bytes(buf[i:i + 4], "little")
            i += 4
        else:
            raise ValueError(f"wire type {wire} is not in an xplane file")
        yield key >> 3, wire, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _map_entry(buf):
    key, value = 0, b""
    for f, _, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _event_metadata(buf, stat_names):
    """(name, {stat name: string value}) of one XEventMetadata; only string
    stats are kept (``tf_op`` is one)."""
    name, stats = "", {}
    for f, _, v in _fields(buf):
        if f == 2:
            name = _text(v)
        elif f == 5:
            sid, text = 0, None
            for g, _, u in _fields(v):
                if g == 1:
                    sid = u
                elif g == 5:
                    text = _text(u)
                elif g == 7:  # a string interned as a stat's name
                    text = stat_names.get(u)
            if text is not None:
                stats[stat_names.get(sid, sid)] = text
    return name, stats


def _line(buf, tick):
    name, t0_ns, events = "", 0, []
    for f, _, v in _fields(buf):
        if f == 2:
            name = _text(v)
        elif f == 3:
            t0_ns = v
        elif f == 4:
            if not len(events) % 4096:
                tick()
            mid = off = dur = 0
            for g, _, u in _fields(v):
                if g == 1:
                    mid = u
                elif g == 2:
                    off = u
                elif g == 3:
                    dur = u
            events.append((off, dur, mid))
    # an event's start as ProfileData gives it: the line's timestamp plus
    # the event's offset, in ns
    return {"name": name,
            "events": [(t0_ns + off / 1000.0, t0_ns + (off + dur) / 1000.0,
                        mid) for off, dur, mid in events]}


def parse_xspace(data, tick=lambda: None) -> list[dict]:
    """The planes of an ``.xplane.pb``: ``name``, ``lines`` (``name`` and
    ``events`` as (start ns, end ns, metadata id)), ``event_names`` and
    ``tf_op`` by metadata id."""
    planes = []
    for f, _, pb in _fields(memoryview(data)):
        if f != 1:
            continue
        name, lines, metas, stat_names = "", [], [], {}
        for g, _, v in _fields(pb):
            if g == 2:
                name = _text(v)
            elif g == 3:
                lines.append(v)
            elif g == 4:
                metas.append(v)
            elif g == 5:
                sid, sm = _map_entry(v)
                for h, _, u in _fields(sm):
                    if h == 2:
                        stat_names[sid] = _text(u)
        plane = {"name": name, "event_names": {}, "tf_op": {},
                 "lines": [_line(v, tick) for v in lines]}
        for v in metas:
            mid, mb = _map_entry(v)
            ev_name, stats = _event_metadata(mb, stat_names)
            plane["event_names"][mid] = ev_name
            if stats.get("tf_op"):
                plane["tf_op"][mid] = stats["tf_op"]
        planes.append(plane)
    return planes


# -- the reduction -----------------------------------------------------------

def scopes_of(path: str) -> frozenset:
    """The program scopes among the components of one ``tf_op`` path
    (``<op name path>:<op type>``)."""
    comps = path.rsplit(":", 1)[0].split("/")
    return frozenset(s for s in SCOPES if any(s in c for c in comps))


def scopes_by_operation(ops, paths) -> dict:
    """{metadata id: scopes} of one device's operations. An operation with
    a path has the scopes of its path. The v5e's trace gives a ``while`` no
    path, only the operations of its body: a pathless operation has the
    scopes that ALL the pathed operations running inside its intervals
    share (a line search's loop holds ``glm.value_grad`` and other work, and
    is owned by ``lbfgs.line_search``, which both carry)."""
    scopes = {mid: scopes_of(path) for mid, path in paths.items()}
    shared, open_now = {}, []  # open_now: (end, metadata id) of containers
    for s, e, mid in sorted(ops, key=lambda ev: (ev[0], -ev[1])):
        while open_now and open_now[-1][0] <= s:
            open_now.pop()
        if mid in scopes:
            for _, outer in open_now:
                had = shared.get(outer)
                shared[outer] = scopes[mid] if had is None \
                    else had & scopes[mid]
        else:
            open_now.append((e, mid))
    scopes.update(shared)
    return scopes


def reduce_planes(planes, mark: str, traced_sweep, sequence,
                  device_prefix: str = "/device:TPU:") -> dict:
    """``scope_s`` per group, the unscoped share of device-busy time (whole
    sweep and per coordinate), device seconds per wave program execution per
    coordinate, the ten longest idle gaps with the host annotation open as
    each began, and the ``while`` operations (by ``trace_reduce.short``
    name: one name may be several programs' loops) with their scopes."""
    ns = 1e-9
    marks, notes = {}, []
    for p in planes:
        if p["name"].startswith(device_prefix):
            continue
        for line in p["lines"]:
            for s, e, mid in line["events"]:
                name = p["event_names"].get(mid, "")
                if name.startswith(mark + "."):
                    marks[name[len(mark) + 1:]] = s
                elif name in ANNOTATIONS:
                    notes.append((s, e, name))
    want = ["start"] + [f"{traced_sweep}.{c}" for c in sequence]
    missing = [k for k in want if k not in marks]
    if missing:
        raise ValueError(f"the trace lacks the markers "
                         f"{[mark + '.' + k for k in missing]}: not the "
                         f"traced sweep's file")
    lo, hi = marks["start"], marks[f"{traced_sweep}.{sequence[-1]}"]
    bounds, edge = {}, lo
    for c in sequence:
        bounds[c] = (edge, marks[f"{traced_sweep}.{c}"])
        edge = bounds[c][1]

    devices = [p for p in planes if p["name"].startswith(device_prefix)
               and any(ln["name"] == trace_reduce.OPS_LINE and ln["events"]
                       for ln in p["lines"])]
    if not devices:
        raise ValueError(f"no plane {device_prefix}* with a line "
                         f"{trace_reduce.OPS_LINE!r} holds an event")
    k = len(devices)
    group_s = {g: 0.0 for g in GROUPS}
    busy = {"sweep": 0.0, **{c: 0.0 for c in sequence}}
    scoped = dict(busy)
    waves = {c: [] for c in sequence}
    whiles, gaps = {}, []
    for p in devices:
        ops = [ev for ln in p["lines"] if ln["name"] == trace_reduce.OPS_LINE
               for ev in ln["events"]]
        scopes = scopes_by_operation(ops, p["tf_op"])
        inside = trace_reduce.clip([(s, e) for s, e, _ in ops], lo, hi)
        total, merged = trace_reduce.union_s(inside)
        named = trace_reduce.union_s(trace_reduce.clip(
            [(s, e) for s, e, mid in ops if scopes.get(mid)], lo, hi))[1]
        busy["sweep"] += total * ns
        scoped["sweep"] += sum(e - s for s, e in named) * ns
        for c, (a, b) in bounds.items():
            busy[c] += trace_reduce.union_s(
                trace_reduce.clip(merged, a, b))[0] * ns
            scoped[c] += trace_reduce.union_s(
                trace_reduce.clip(named, a, b))[0] * ns
        for g, names in GROUPS.items():
            group_s[g] += trace_reduce.union_s(trace_reduce.clip(
                [(s, e) for s, e, mid in ops
                 if scopes.get(mid, frozenset()).intersection(names)],
                lo, hi))[0] * ns
        for s, e, mid in ops:
            name = p["event_names"].get(mid, "")
            if name.startswith("%while") and e > lo and s < hi:
                short = trace_reduce.short(name)
                w = whiles.setdefault(short, {"seconds": 0.0, "scopes": set()})
                w["seconds"] += (min(e, hi) - max(s, lo)) * ns / k
                w["scopes"] |= scopes.get(mid, frozenset())
        for ln in p["lines"]:
            if ln["name"] != MODULES_LINE:
                continue
            for s, e, mid in sorted(ln["events"]):
                if not p["event_names"].get(mid, "").startswith(
                        WAVE_PROGRAMS):
                    continue
                for c, (a, b) in bounds.items():
                    if a <= s < b:
                        waves[c].append((e - s) * ns)
        edges = [[lo, lo]] + merged + [[hi, hi]]
        for (_, e0), (s1, _) in zip(edges, edges[1:]):
            if s1 > e0:
                gaps.append((s1 - e0, e0))
    longest = []
    for length, t in sorted(gaps, reverse=True)[:10]:
        open_now = [(s, name) for s, e, name in notes if s <= t < e]
        longest.append({"seconds": length * ns,
                        "at_s": (t - lo) * ns,
                        "host": max(open_now)[1] if open_now else None})
    any_scope = any(scoped.values())
    return {
        "scope_s": {g: v / k for g, v in group_s.items() if v > 0},
        "busy_s": {c: v / k for c, v in busy.items()},
        "unscoped_share": {c: 1.0 - scoped[c] / busy[c]
                           for c in busy if busy[c] > 0 and any_scope},
        "wave_device_s": waves, "idle_gaps": longest,
        "whiles": {n: {"seconds": w["seconds"],
                       "scopes": sorted(w["scopes"])}
                   for n, w in whiles.items()},
    }


# -- the run's own trace -----------------------------------------------------

_RUN = {}  # traced sweep -> what for_run found (layer_reader re-executes a
#            reader's file per metric; this module is imported once)


def for_run(ctx, budget_s: float = 60.0, mark: str = "bench.mark") -> dict:
    """The reduction of this run's traced sweep, made once; ``{}`` where
    there is no traced sweep or its file cannot be read."""
    sweep = ctx.get("traced_sweep")
    if sweep is None or not ctx.get("trace"):
        return {}
    if sweep not in _RUN:
        t0 = time.monotonic()

        def tick():
            if time.monotonic() - t0 > budget_s:
                raise TimeoutError(f"scope reduction passed its budget of "
                                   f"{budget_s} s")

        try:
            with open(trace_reduce.find_xplane(ctx["trace_dir"]),
                      "rb") as f:
                planes = parse_xspace(f.read(), tick)
            tick()
            _RUN[sweep] = reduce_planes(
                planes, mark, sweep, ctx["cell"]["mix"]["update_sequence"])
        except (OSError, ValueError, TimeoutError) as e:
            print(f"scope_reduce: no scope metrics: {e}", file=sys.stderr)
            _RUN[sweep] = {}
        else:
            report(_RUN[sweep], ctx, time.monotonic() - t0)
    return _RUN[sweep]


def report(r: dict, ctx, seconds: float) -> None:
    """The log lines: waves, unscoped share, idle gaps, ``while`` owners."""
    out = [f"scope_reduce: the trace's second reading took {seconds:.2f} s"]
    rows = {}
    for row in ctx["ledger_rows"]:
        if (row.get("kind") == "re_fit_wave"
                and row.get("outer_iteration") == ctx["traced_sweep"]):
            rows.setdefault(row.get("coordinate"), []).append(row)
    for c, secs in r["wave_device_s"].items():
        for i, s in enumerate(secs):
            w = rows.get(c, [])[i] if i < len(rows.get(c, [])) else {}
            fit, its = w.get("entities_fit"), w.get("iters_sum")
            mean = (f"{its / fit:.2f}" if fit and its is not None else "-")
            pad = (f"{100 * (1 - w['rows_useful'] / w['rows_padded']):.1f}%"
                   if w.get("rows_padded") else "-")
            out.append(
                f"  wave {c} {i}: device {s:.4f} s, cap {w.get('cap', '-')}, "
                f"entities_fit {fit if fit is not None else '-'}, iters_max "
                f"{w.get('iters_max', '-')}, mean iterations {mean}, "
                f"lanes_at_cap {w.get('lanes_at_cap', '-')}, padding {pad}")
    for c, share in r["unscoped_share"].items():
        out.append(f"  device-busy time under no program scope, {c}: "
                   f"{100 * share:.2f}% of {r['busy_s'][c]:.4f} s")
    for g in r["idle_gaps"]:
        out.append(f"  idle gap {g['seconds'] * 1e3:.3f} ms at "
                   f"{g['at_s']:.4f} s, host in {g['host'] or 'no annotation'}")
    for name, w in sorted(r["whiles"].items(),
                          key=lambda p: -p[1]["seconds"])[:12]:
        out.append(f"  {name}: {w['seconds']:.4f} s under "
                   f"{', '.join(w['scopes']) or 'no scope'}")
    print("\n".join(out), file=sys.stderr, flush=True)
