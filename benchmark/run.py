"""One run of one benchmark cell: ``python3 benchmark/run.py --workload <cell>
--seed <n> --seconds <s> --trace <0|1>``.

The harness knows no cell, configuration, traffic mix or metric by name. It
reads ``BENCHMARK.json`` at the root of the checkout and finds by name:

- the cell's configuration file, ``configs[].file`` of its ``config``;
- the configuration's data schema, ``benchmark/schemas/<schema>.py`` where
  ``<schema>`` is the configuration file's ``"schema"``: the one module that
  knows the shape of the data (``SCHEMA`` below lists what it exposes);
- the traffic mix, ``benchmark/traffic/<traffic>.json``;
- the cell's own settings, ``benchmark/workloads/<cell>.json`` (optimiser,
  ``max_samples``, ``steady_sweep_s``);
- each per-layer metric's reader, ``benchmark/layer_metrics/<stem>.py`` where
  ``<stem>`` is the metric's name up to its first ``.``; it exposes
  ``read(name, ctx)`` and returns a number, or ``None`` when it finds nothing.

A run is one process and one ``fit`` of the schema's estimator on data made in
memory from ``--seed``. Set-up is process start to the end of descent sweep 2;
the window is sweeps 3 .. 2+N with N fixed beforehand from ``--seconds`` and
the cell's ``steady_sweep_s``; the output check (the schema's ``check``) runs
after the window has closed and the peak memory has been read. ``--rehearsal``
lets the same code run off the chip at a tiny size and marks its output as no
measurement.
"""

from __future__ import annotations

import time

_T0 = time.monotonic()  # process start, as near as Python can take it

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(HERE, "layer_metrics"))
sys.path.insert(0, os.path.join(HERE, "schemas"))

TRACE_BUDGET_S = 60.0  # the trace reduction's own time budget
MARK = "bench.mark"  # host-plane marker prefix, one per CoordinateUpdate
# What a schema module exposes; run.py and the metric readers call nothing
# else of it:
#   make(seed, conf)           the seeded data, plain numpy, nothing of the
#                              program imported
#   shrink(conf, rows)         the rehearsal's smaller configuration
#   dataset(data)              the program's dataset of that data
#   estimator(cell, mesh, sweeps, ledger_dir, feature_dtype)
#                              the object the window drives, built as
#                              cli/game_train.main builds it
#   model_arrays(model, mix)   the trained leaves as numpy
#   check(data, cell, served, ledger_rows, sweeps)
#                              the plain reference and the comparison: name ->
#                              {"value", "limit"}, limits from the
#                              configuration's check.limits
#   sweep_flops(ctx)           FLOPs the traced sweep needs, or None
#   bytes_needed(kernel, ctx)  bytes that kernel's work in the traced sweep
#                              needs, or None
#   faults                     name -> a function that returns the context
#                              manager planting that fault under the timed path
SCHEMA = ("make", "shrink", "dataset", "estimator", "model_arrays", "check",
          "sweep_flops", "bytes_needed", "faults")


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """Everything that belongs to one cell, found by the names in
    BENCHMARK.json."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    cell = dict(cells[name])
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cell["configuration"] = load_json(ROOT, conf["file"])
    cell["mix"] = load_json(HERE, "traffic", cell["traffic"] + ".json")
    cell["settings"] = load_json(HERE, "workloads", name + ".json")

    def mine(m):
        return "workloads" not in m or name in m["workloads"]

    cell["end_to_end"] = [m for m in bench["end_to_end"] if mine(m)]
    cell["per_layer"] = [m for m in bench["per_layer"] if mine(m)]
    return cell


def layer_reader(metric_name: str):
    stem = metric_name.split(".", 1)[0]
    path = os.path.join(HERE, "layer_metrics", stem + ".py")
    spec = importlib.util.spec_from_file_location(f"layer_metrics.{stem}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_schema(name):
    """The module ``benchmark/schemas/<name>.py``, held to ``SCHEMA``."""
    path = os.path.join(HERE, "schemas", f"{name}.py")
    if not os.path.exists(path):
        raise SystemExit(f"the configuration file's \"schema\" is {name!r}: "
                         f"there is no {path}")
    mod = importlib.import_module(name)
    missing = [k for k in SCHEMA if not hasattr(mod, k)]
    if missing:
        raise SystemExit(f"{path} lacks {', '.join(missing)}")
    return mod


class Recorder:
    """Listens to the program's ``CoordinateUpdate`` events on one monotonic
    clock; starts and stops the profiler around one steady sweep."""

    def __init__(self, sequence, setup_sweeps, sweeps, trace_dir, counts):
        self.sequence = list(sequence)
        self.setup_sweeps = setup_sweeps
        self.sweeps = sweeps
        self.trace_dir = trace_dir
        self.counts = counts
        self.updates = []  # dicts: iteration, coordinate, t, train_seconds
        self.window_counts = None  # compile counters as the window opens
        self.window_memory = None
        self.traced_sweep = None

    def __call__(self, event):
        from photon_ml_tpu.utils import events
        if not isinstance(event, events.CoordinateUpdate):
            return
        t = time.monotonic()
        import jax
        self.updates.append({"iteration": event.iteration,
                             "coordinate": event.coordinate, "t": t,
                             "train_seconds": event.train_seconds})
        last = event.coordinate == self.sequence[-1]
        if self.traced_sweep == event.iteration:
            with jax.profiler.TraceAnnotation(
                    f"{MARK}.{event.iteration}.{event.coordinate}"):
                pass
            if last:
                jax.profiler.stop_trace()
        if not last:
            return
        log(f"sweep {event.iteration + 1}/{self.sweeps} ended at "
            f"{t - _T0:.3f} s")
        if event.iteration == self.setup_sweeps - 1:
            self.window_counts = dict(self.counts)
            self.window_memory = device_memory()
        # The profiler is on for the window's second sweep only.
        if self.trace_dir and event.iteration == self.setup_sweeps:
            # Device operations and the harness's own markers; the Python
            # tracer would slow the host that dispatches the sweep.
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.enable_hlo_proto = False
            jax.profiler.start_trace(self.trace_dir,
                                     profiler_options=options)
            self.traced_sweep = event.iteration + 1
            with jax.profiler.TraceAnnotation(f"{MARK}.start"):
                pass

    def sweep_end(self, k: int) -> float:
        """Arrival of the last update of sweep ``k`` (1-based)."""
        return [u["t"] for u in self.updates
                if u["iteration"] == k - 1
                and u["coordinate"] == self.sequence[-1]][0]


def device_memory():
    import jax
    out = {"bytes_in_use": 0, "peak_bytes_in_use": 0}
    for d in jax.local_devices():
        s = d.memory_stats() or {}
        for k in out:
            out[k] = max(out[k], int(s.get(k, 0)))
    return out


def count_compiles():
    """Programs asked of the compiler and those found in the persistent
    cache (the rest were compiled), and functions traced, as JAX's own
    monitoring counts them."""
    import jax
    counts = {"requests": 0, "cache_hits": 0, "traces": 0}

    def on_event(event, **kw):
        if event.endswith("/compile_requests_use_cache"):
            counts["requests"] += 1
        elif event.endswith("/cache_hits"):
            counts["cache_hits"] += 1

    def on_duration(event, duration_secs, **kw):
        if event == "/jax/core/compile/jaxpr_trace_duration":
            counts["traces"] += 1

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    return counts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--rehearsal", action="store_true",
                    help="run off the chip; the output is no measurement")
    ap.add_argument("--rows", type=int, default=None,
                    help="rehearsal only: rows instead of the configuration's")
    ap.add_argument("--control", default=None, choices=("bfloat16",),
                    help="run the program's lower-precision path: the output "
                         "is the control's reading, no measurement")
    ap.add_argument("--selfcheck", action="store_true",
                    help="check the trace reduction and the work counts on "
                         "the CPU against benchmark/selfcheck/")
    args = ap.parse_args(argv)
    if args.selfcheck:
        import selfcheck
        return selfcheck.main({
            c["name"]: load_schema(load_json(ROOT, c["file"]).get("schema"))
            for c in load_json(ROOT, "BENCHMARK.json")["configs"]})
    if not args.workload:
        ap.error("--workload is required")
    cell = load_cell(args.workload)
    conf = cell["configuration"]
    mix = cell["mix"]
    schema = load_schema(conf.get("schema"))
    if args.rows is not None:
        if not args.rehearsal:
            ap.error("--rows is for --rehearsal only")
        cell["configuration"] = conf = schema.shrink(conf, args.rows)

    from photon_ml_tpu.utils.compile_cache import enable_compilation_cache
    cache_dir = enable_compilation_cache()
    import jax

    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    peaks = load_json(HERE, "peaks.json")
    if args.rehearsal:
        log("REHEARSAL: not a measurement; no number below is a device "
            "metric")
        peak = next(iter(peaks.values()))
    else:
        if platform == "cpu" or len(devices) < cell["chips"]:
            log(f"this cell needs {cell['chips']} accelerator chip(s); JAX "
                f"found {len(devices)} x {platform} ({kind})")
            return 3
        if kind not in peaks:
            log(f"device kind {kind!r} is not in benchmark/peaks.json")
            return 3
        peak = peaks[kind]
    counts = count_compiles()

    setup_sweeps = int(mix["setup_sweeps"])
    n_window = max(int(mix["min_window_sweeps"]),
                   math.ceil(args.seconds
                             / float(cell["settings"]["steady_sweep_s"])))
    sweeps = setup_sweeps + n_window

    t = time.monotonic()
    data = schema.make(args.seed, conf)
    gen_s = time.monotonic() - t
    log(f"generated the data of seed {args.seed} in {gen_s:.2f} s")

    from photon_ml_tpu.parallel.mesh import make_mesh
    from photon_ml_tpu.utils import events
    work = tempfile.mkdtemp(prefix="bench-")
    trace_dir = os.path.join(work, "trace") if args.trace else None
    est = schema.estimator(
        cell, make_mesh(devices=devices[:cell["chips"]]), sweeps,
        os.path.join(work, "ledger"), args.control or conf["storage_dtype"])
    rec = Recorder(mix["update_sequence"], setup_sweeps, sweeps, trace_dir,
                   counts)
    events.default_emitter.register(rec)
    t_fit = time.monotonic()
    try:
        result = est.fit(schema.dataset(data),
                         locked_coordinates=set(mix["locked_coordinates"])
                         or None)[0]
    finally:
        events.default_emitter.unregister(rec)
    t_done = time.monotonic()

    t_open, t_close = rec.sweep_end(setup_sweeps), rec.sweep_end(sweeps)
    window_s = t_close - t_open
    in_window = {k: counts[k] - rec.window_counts[k] for k in counts}
    memory = device_memory()
    log(f"set-up {t_open - _T0:.3f} s (generate {gen_s:.2f}, fit began at "
        f"{t_fit - _T0:.2f}, sweeps 1..2 end at {rec.sweep_end(1) - _T0:.2f} "
        f"and {t_open - _T0:.2f}); window "
        f"{window_s:.3f} s over {n_window} sweeps; fit returned "
        f"{t_done - t_close:.3f} s after the window closed")
    compiled = rec.window_counts["requests"] - rec.window_counts["cache_hits"]
    log(f"programs during set-up: {rec.window_counts['requests']} asked, "
        f"{rec.window_counts['cache_hits']} found in {cache_dir}, "
        f"{compiled} compiled; during the window: {in_window['requests']} "
        f"asked (compiled or loaded), {in_window['traces']} traced")
    log(f"device memory: {rec.window_memory} as the window opened, {memory} "
        f"after it")

    from photon_ml_tpu.obs.ledger import read_rows
    ledger_rows, _ = read_rows(os.path.join(work, "ledger"))
    served = schema.model_arrays(result.model, mix)
    # Free the program's state before the reference takes the device.
    del est, result
    gc.collect()
    jax.clear_caches()

    ctx = {
        "cell": cell, "peak": peak, "updates": rec.updates,
        "ledger_rows": ledger_rows, "t_fit": t_fit, "t_open": t_open,
        "t_close": t_close, "n_window": n_window,
        "setup_sweeps": setup_sweeps, "traced_sweep": rec.traced_sweep,
        "trace": None, "trace_dir": trace_dir, "schema": schema,
    }
    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": memory["peak_bytes_in_use"]}
    out = {"correct": False, "attempted": n_window, "failed": 0,
           "metrics": {}, "device": device}
    if args.trace:
        import trace_reduce
        t = time.monotonic()
        try:
            ctx["trace"] = trace_reduce.reduce(
                trace_dir, MARK, mix["update_sequence"], TRACE_BUDGET_S)
        except (ValueError, TimeoutError) as e:
            log(f"the trace could not be reduced: {e}")
            if not args.rehearsal:
                return 4
        else:
            log(f"trace reduced in {time.monotonic() - t:.2f} s: "
                f"{ctx['trace']['events']} device events")
        if ctx["trace"]:
            device["busy_s"] = ctx["trace"]["busy_s"]
            device["window_s"] = ctx["trace"]["window_s"]
            out["breakdown"] = ctx["trace"]["breakdown"]
        metrics = cell["per_layer"]
    else:
        metrics = cell["end_to_end"]
    values = {"setup_s": t_open - _T0, "sweep_s": window_s / n_window}
    for m in metrics:
        v = (values.get(m["name"]) if not args.trace
             else layer_reader(m["name"])(m["name"], ctx))
        if v is not None:
            out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    shutil.rmtree(work, ignore_errors=True)

    t = time.monotonic()
    numbers = schema.check(data, cell, served, ledger_rows, sweeps)
    log(f"reference and comparison took {time.monotonic() - t:.2f} s")
    over = [k for k, v in numbers.items() if not v["value"] <= v["limit"]]
    late = in_window["requests"]
    if late:
        log(f"NOT CORRECT: {late} program(s) compiled or loaded inside the "
            f"window")
    out["correct"] = not over and not late
    out["failed"] = 0 if out["correct"] else n_window
    if args.rehearsal or args.control:
        out["not_a_measurement"] = ("rehearsal" if args.rehearsal
                                    else f"control {args.control}")
    out["window"] = {"sweeps": n_window, "seconds": window_s,
                     "compiled_in_setup": compiled,
                     "loaded_in_setup": rec.window_counts["cache_hits"],
                     "asked_in_window": in_window["requests"]}
    out["compared"] = numbers
    for k, v in numbers.items():
        log(f"compared {k}: {v['value']:.6g} (limit {v['limit']:.6g})"
            f"{'  <-- over' if k in over else ''}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
