"""From one profiler trace (``.xplane.pb``) to what the per-layer metrics read.

The harness traces one steady sweep and drops a zero-length host marker
(``jax.profiler.TraceAnnotation``) into the trace at every
``CoordinateUpdate``: ``<mark>.start`` when the profiler has started,
``<mark>.<iteration>.<coordinate>`` when that coordinate's update has ended.
Markers and device operations are on the trace's own clock, so device time is
attributed to a coordinate by the host interval it fell in; no name inside the
program is needed (it has no ``jax.named_scope`` yet).

``breakdown.device_ops`` sums each operation's own events; a ``while`` holds
the operations of its body, so the list is for ranking, not for adding up.

Read with ``jax.profiler.ProfileData`` and nothing else. The reduction holds to
a time budget of its own and raises ``TimeoutError`` past it.
"""

from __future__ import annotations

import glob
import os
import time

OPS_LINE = "XLA Ops"  # the device line that holds single operations


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def short(name: str) -> str:
    """An operation's name as XLA gave it, without the operand list:
    ``%while.299 = (f32[65536,8]{1,0:T(8,128)}, ...`` becomes
    ``%while.299 (f32[65536,8]``. The first result shape stays, since one
    program compiled for two bucket shapes gives two operations of one name."""
    head, _, rest = name.partition(" = ")
    shape = rest.split("{", 1)[0].split(" ", 1)[0]
    return f"{head} {shape}"[:96] if rest else head[:96]


def union_s(intervals) -> tuple[float, list]:
    """Total length of the union of ``(start, end)`` pairs, and the merged
    pairs in order."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def reduce_profile(profile, mark: str, sequence, budget_s: float = 60.0,
                   device_prefix: str = "/device:TPU:") -> dict:
    deadline = time.monotonic() + budget_s

    def tick():
        if time.monotonic() > deadline:
            raise TimeoutError(f"trace reduction passed its budget of "
                               f"{budget_s} s")

    marks, devices = {}, []
    for plane in profile.planes:
        if plane.name.startswith(device_prefix):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for i, ev in enumerate(line.events):
                    if not i % 4096:
                        tick()
                    ops.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name))
            if ops:
                devices.append(ops)
            continue
        for line in plane.lines:
            for i, ev in enumerate(line.events):
                if not i % 4096:
                    tick()
                if ev.name.startswith(mark):
                    marks[ev.name[len(mark) + 1:]] = ev.start_ns
    if "start" not in marks:
        raise ValueError(f"the trace holds no {mark}.start marker")
    ends = {k.split(".", 1)[1]: v for k, v in marks.items() if k != "start"}
    missing = [c for c in sequence if c not in ends]
    if missing:
        raise ValueError(f"the trace holds no marker for {missing}")
    if not devices:
        raise ValueError(f"no plane {device_prefix}* with a line "
                         f"{OPS_LINE!r} holds an event")
    lo, hi = marks["start"], ends[sequence[-1]]
    bounds, edge = {}, lo
    for c in sequence:
        bounds[c] = (edge, ends[c])
        edge = ends[c]

    ns = 1e-9
    busy, by_coord = 0.0, {c: 0.0 for c in sequence}
    by_name, gaps, n_events = {}, [], 0
    for ops in devices:
        tick()
        inside = [(max(s, lo), min(e, hi), name) for s, e, name in ops
                  if e > lo and s < hi]
        n_events += len(inside)
        total, merged = union_s([(s, e) for s, e, _ in inside])
        busy += total * ns
        for c, (a, b) in bounds.items():
            by_coord[c] += union_s(clip(merged, a, b))[0] * ns
        for s, e, name in inside:
            name = short(name)
            by_name[name] = by_name.get(name, 0.0) + (e - s) * ns
        edges = [[lo, lo]] + merged + [[hi, hi]]
        for (_, e0), (s1, _) in zip(edges, edges[1:]):
            if s1 > e0:
                where = next((c for c, (a, b) in bounds.items()
                              if a <= e0 < b), sequence[-1])
                gaps.append((where, (s1 - e0) * ns))
    k = len(devices)
    top_ops = sorted(by_name.items(), key=lambda p: -p[1])[:10]
    longest = sorted(gaps, key=lambda p: -p[1])[:10]
    return {
        "events": n_events, "devices": k,
        "window_s": (hi - lo) * ns, "busy_s": busy / k,
        "busy_by_coordinate_s": {c: v / k for c, v in by_coord.items()},
        "breakdown": {
            "device_ops": [[n, s / k] for n, s in top_ops],
            "idle_gaps": [[f"during {c} update", s] for c, s in longest]},
    }


def reduce(trace_dir: str, mark: str, sequence, budget_s: float = 60.0,
           device_prefix: str = "/device:TPU:") -> dict:
    return reduce_profile(load(find_xplane(trace_dir)), mark, list(sequence),
                          budget_s, device_prefix)

