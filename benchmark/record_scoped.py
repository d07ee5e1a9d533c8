"""Record ``benchmark/selfcheck/scoped.xplane.pb`` on the chip:

    python3 benchmark/record_scoped.py chiprun_out/scoped

A GLMix of a few hundred rows (one fixed effect, one random effect, the
program's own coordinates and descent loop, the harness's markers) is traced
for its third sweep. The profiler's file holds far more than a reader of
scopes needs (every operation's source stack, shapes, byte counts; the
runtime's own host threads), so what is kept is a slimmed copy, re-encoded
here with the fields ``scope_reduce.parse_xspace`` reads: the device planes'
``XLA Ops`` and ``XLA Modules`` lines, each event's metadata id, offset and
duration, each metadata's name (cut to 64 characters) and ``tf_op``, and the
host's markers and program annotations. The slimmed copy must reduce to
exactly what the full file reduces to; that reading is written beside it as
``scoped.expected.json``.

The encoder (``field``, ``plane``) also builds the hand-made profile of
``tests/test_inside_view.py``.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

import scope_reduce  # noqa: E402
import trace_reduce  # noqa: E402

MARK = "bench.mark"
SEQ = ["fixed", "per-user"]
SWEEPS = 3  # the last one is traced
NAME = 64  # characters kept of an operation's name: its first result shape


# -- a protobuf encoder for the messages parse_xspace reads -------------------

def varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def field(number: int, value) -> bytes:
    """One field: an int as a varint, a str or bytes length-delimited."""
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(number << 3 | 2) + varint(len(value)) + value


def plane(name: str, lines, metadata, event_stat: bytes = b"") -> bytes:
    """One XPlane. ``lines``: (name, timestamp ns, [(metadata id, offset
    ps, duration ps)]); ``metadata``: {id: (name, tf_op or None)};
    ``event_stat``: an encoded XStat put on every event (a reader of scopes
    has to step over those)."""
    out = field(2, name)
    for lname, t0, events in lines:
        body = field(2, lname) + field(3, t0)
        for mid, off, dur in events:
            ev = field(1, mid) + field(2, off) + field(3, dur)
            body += field(4, ev + (field(4, event_stat) if event_stat
                                   else b""))
        out += field(3, body)
    for mid, (ename, tf_op) in metadata.items():
        md = field(1, mid) + field(2, ename)
        if tf_op is not None:
            md += field(5, field(1, 1) + field(5, tf_op))
        out += field(4, field(1, mid) + field(2, md))
    # stat metadata 1 is ``tf_op``
    return out + field(5, field(1, 1) + field(2, field(1, 1)
                                              + field(2, "tf_op")))


def slim(planes, device_prefix: str) -> bytes:
    """The XSpace of what ``scope_reduce.reduce_planes`` reads of
    ``planes``."""
    out = b""
    for p in planes:
        device = p["name"].startswith(device_prefix)
        lines, used = [], set()
        for ln in p["lines"]:
            if device and ln["name"] not in (trace_reduce.OPS_LINE,
                                             scope_reduce.MODULES_LINE):
                continue
            events = [(mid, round(s * 1000), round((e - s) * 1000))
                      for s, e, mid in ln["events"]
                      if device
                      or p["event_names"].get(mid, "").startswith(MARK + ".")
                      or p["event_names"].get(mid) in scope_reduce.ANNOTATIONS]
            if events:
                lines.append((ln["name"], 0, events))
                used.update(mid for mid, _, _ in events)
        if lines:
            out += field(1, plane(p["name"], lines, {
                mid: (p["event_names"][mid][:NAME], p["tf_op"].get(mid))
                for mid in sorted(used)}))
    return out


def expected(r: dict) -> dict:
    return {"traced_sweep": SWEEPS - 1, "sequence": SEQ,
            "scope_s": r["scope_s"], "unscoped_share": r["unscoped_share"],
            "wave_device_s": r["wave_device_s"],
            "whiles": {n: w["scopes"] for n, w in r["whiles"].items()}}


# -- the recording ------------------------------------------------------------

def record(trace_dir: str) -> None:
    import jax
    import numpy as np

    from photon_ml_tpu.data import synthetic
    from photon_ml_tpu.data.game_data import from_synthetic
    from photon_ml_tpu.game import descent
    from photon_ml_tpu.game.coordinates import (FixedEffectCoordinate,
                                                RandomEffectCoordinate)
    from photon_ml_tpu.ops import losses
    from photon_ml_tpu.optim import OptimizerConfig
    from photon_ml_tpu.optim.problem import GLMOptimizationConfiguration
    from photon_ml_tpu.optim.regularization import (RegularizationContext,
                                                    RegularizationType)
    from photon_ml_tpu.parallel.mesh import make_mesh
    from photon_ml_tpu.types import TaskType
    from photon_ml_tpu.utils import events

    opt = GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(max_iterations=3, history_length=2,
                                  max_line_search_steps=4),
        regularization=RegularizationContext(RegularizationType.L2, 1.0))
    mesh = make_mesh(devices=jax.devices()[:1])
    ds = from_synthetic(synthetic.game_data(
        np.random.default_rng(26), n=384, d_global=4,
        re_specs={"userId": (12, 3)}))
    coords = {
        "fixed": FixedEffectCoordinate(ds, "global", losses.LOGISTIC, opt,
                                       mesh),
        # 9..16 rows an entity: one bucket class, so one bucket program
        "per-user": RandomEffectCoordinate(ds, "userId", "re_userId",
                                           losses.LOGISTIC, opt, mesh,
                                           lower_bound=9, upper_bound=16)}

    def on_update(event):
        if not isinstance(event, events.CoordinateUpdate):
            return
        last = event.coordinate == SEQ[-1]
        if event.iteration == SWEEPS - 1:
            with jax.profiler.TraceAnnotation(
                    f"{MARK}.{event.iteration}.{event.coordinate}"):
                pass
            if last:
                jax.profiler.stop_trace()
        elif last and event.iteration == SWEEPS - 2:
            options = jax.profiler.ProfileOptions()  # as benchmark/run.py
            options.python_tracer_level = 0
            options.enable_hlo_proto = False
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            with jax.profiler.TraceAnnotation(f"{MARK}.start"):
                pass

    events.default_emitter.register(on_update)
    try:
        descent.run(TaskType.LOGISTIC_REGRESSION, coords,
                    descent.CoordinateDescentConfig(SEQ, SWEEPS,
                                                    sync_updates=True))
    finally:
        events.default_emitter.unregister(on_update)


def main(argv) -> int:
    out_dir = argv[1] if len(argv) > 1 else "chiprun_out/scoped"
    os.makedirs(out_dir, exist_ok=True)
    import jax
    platform = jax.devices()[0].platform
    prefix = "/device:TPU:" if platform == "tpu" else "/host:CPU"
    with tempfile.TemporaryDirectory() as work:
        record(work)
        with open(trace_reduce.find_xplane(work), "rb") as f:
            raw = f.read()
    planes = scope_reduce.parse_xspace(raw)
    if platform != "tpu":
        print(f"recorded on {platform}: {len(raw)} bytes, "
              f"{sum(len(ln['events']) for p in planes for ln in p['lines'])}"
              f" events; only a TPU's trace has device planes to reduce")
        return 3
    full = scope_reduce.reduce_planes(planes, MARK, SWEEPS - 1, SEQ, prefix)
    small = slim(planes, prefix)
    again = scope_reduce.reduce_planes(scope_reduce.parse_xspace(small), MARK,
                                       SWEEPS - 1, SEQ, prefix)
    same = json.dumps(expected(full), sort_keys=True) == json.dumps(
        expected(again), sort_keys=True)
    with open(os.path.join(out_dir, "scoped.xplane.pb"), "wb") as f:
        f.write(small)
    with open(os.path.join(out_dir, "scoped.expected.json"), "w") as f:
        json.dump(expected(again), f, indent=1, sort_keys=True)
        f.write("\n")
    with open(os.path.join(out_dir, "scoped.paths.txt"), "w") as f:
        for p in planes:  # every op-name path, to read by hand
            for mid, path in sorted(p["tf_op"].items()):
                f.write(f"{p['event_names'][mid][:60]}\t{path}\n")
    print(json.dumps({"raw_bytes": len(raw), "slim_bytes": len(small),
                      "slim_reads_as_full": same,
                      "device": jax.devices()[0].device_kind,
                      "expected": expected(again)}))
    return 0 if same and len(small) < 100_000 else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv))
