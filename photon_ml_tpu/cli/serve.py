"""Online scoring service driver.

Reference parity: none — the reference stops at batch scoring
(GameScoringDriver); this driver is the serving half the ROADMAP's
"heavy traffic" north star needs. Loads a trained GameModel once, keeps it
resident (photon_ml_tpu/serving/), and answers JSON-over-HTTP scoring
requests at low latency with micro-batching and a metrics endpoint.

Quickstart (docs/SERVING.md):

    photon-game-serve --model-dir out/best --port 8080
    curl -s localhost:8080/score -d '{"requests": [{"features": \
        {"global": [0.1, ...]}, "entity_ids": {"userId": 7}}]}'
    curl -s localhost:8080/metrics
"""

from __future__ import annotations

import argparse
import json
import logging
import os

from photon_ml_tpu.models import io as model_io
from photon_ml_tpu.parallel.mesh import device_summary
from photon_ml_tpu.serving.service import ScoringService, make_http_server
from photon_ml_tpu.utils.compile_cache import enable_compilation_cache
from photon_ml_tpu.utils.logging import setup_logging

logger = logging.getLogger("photon_ml_tpu.cli")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--model-dir", required=True, help="GameModel directory")
    p.add_argument("--model-format", default="NPZ",
                   choices=["NPZ", "AVRO"],
                   help="AVRO loads a best-avro directory through "
                        "--feature-index-dir (same contract as game_score)")
    p.add_argument("--feature-index-dir",
                   help="REQUIRED with --model-format AVRO: the training "
                        "run's saved index maps")
    p.add_argument("--entity-vocabs",
                   help="entity-vocabs.json mapping raw entity keys to "
                        "vocabulary rows; lets requests carry raw string "
                        "ids. Auto-discovered beside --feature-index-dir "
                        "when present")
    p.add_argument("--as-mean", action="store_true",
                   help="serve probabilities/rates (inverse link) instead "
                        "of raw linear scores")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8080,
                   help="0 picks a free port (printed at startup)")
    p.add_argument("--max-batch", type=int, default=64,
                   help="micro-batch flush size (also the largest padded "
                        "batch shape)")
    p.add_argument("--max-wait-ms", type=float, default=2.0,
                   help="max time a queued request waits for batch-mates")
    p.add_argument("--cache-entities", type=int, default=4096,
                   help="per-coordinate LRU device cache capacity "
                        "(random-effect rows)")
    p.add_argument("--cache-dtype", default="float32",
                   choices=["float32", "int8"],
                   help="device-LRU storage dtype: int8 (symmetric "
                        "per-row quantization, dequantized in the "
                        "scoring gather) caches ~4x the entities per "
                        "HBM byte at a sub-1e-2 score perturbation "
                        "(docs/SERVING.md \"Quantized device cache\")")
    p.add_argument("--store-shards", type=int, default=8,
                   help="hash shards of the host-resident random-effect "
                        "store (mapped boots keep the generation's "
                        "tables whole and gather directly — the shard "
                        "count then only names the future RPC seam)")
    p.add_argument("--boot-warmup", action="store_true",
                   help="touch every power-of-two bucket shape before "
                        "serving, so the first real request never pays "
                        "a compile; with the persistent compilation "
                        "cache warm these are disk hits "
                        "(photon_compile_cache_hits_total) — the "
                        "boot.warmup phase of docs/SERVING.md "
                        "\"Sub-second restart\"")
    p.add_argument("--max-queue", type=int, default=None,
                   help="admission-control bound on queued requests "
                        "(default 16×max-batch); overflow sheds with "
                        "HTTP 503 instead of buffering unboundedly "
                        "(docs/ROBUSTNESS.md)")
    p.add_argument("--request-deadline-s", type=float, default=30.0,
                   help="per-request deadline: a request still queued "
                        "past this fails fast with 504 instead of "
                        "waiting forever (0 disables)")
    p.add_argument("--slo-window-s", type=float, default=60.0,
                   help="sliding window of the /slo tracker (latency "
                        "percentiles + error-budget burn)")
    p.add_argument("--slo-availability", type=float, default=0.999,
                   help="availability objective: shed/deadline/5xx "
                        "burn the 1-objective error budget")
    p.add_argument("--slo-latency-ms", type=float, default=None,
                   help="optional latency objective: answered requests "
                        "slower than this also burn error budget")
    p.add_argument("--trace-out",
                   help="write a Chrome trace-event JSON of the serving "
                        "session at shutdown (request spans with queue/"
                        "assemble/device/respond attribution children "
                        "parented into their flush spans) — written "
                        "from a finally, so a crashed server keeps its "
                        "timeline; render with `photon-obs summarize "
                        "--serving` (docs/OBSERVABILITY.md)")
    p.add_argument("--metrics-dump",
                   help="write the full /metrics exposition (serving "
                        "scoreboard + cross-stack registry) to this "
                        "file at shutdown, also from a finally — the "
                        "game_train --metrics-dump parity flag")
    # -- fleet-replica plumbing (serving/fleet.py spawns these) ----------
    p.add_argument("--ready-file",
                   help="after binding, atomically write {pid, host, "
                        "port} JSON here — the supervisor's handshake "
                        "for --port 0 replicas (no port-allocation "
                        "race, no pipe to overflow)")
    p.add_argument("--replica-id", type=int, default=None,
                   help="this server's stable fleet index: fault site "
                        "fleet.replica_flush fires with it, logs carry "
                        "it (set by the fleet supervisor)")
    p.add_argument("--fault-plan",
                   help="JSON FaultPlan installed at startup — the "
                        "game_train --fault-plan parity flag; how "
                        "fleet chaos drills reach inside a replica "
                        "(docs/ROBUSTNESS.md)")
    return p


def load_model(args):
    """Load (model, entity_vocabs, boot_meta) per the driver's format
    flags. ``boot_meta`` is ``{"generation": g, "model_version": v}``
    for a mapped/generation boot and ``{}`` for the classic layouts —
    layout auto-detection (photon_ml_tpu/boot) means a ``--model-dir``
    pointing at a generation root boots the CURRENT generation with the
    corruption fallback ladder, with zero new flags."""
    from photon_ml_tpu import boot

    vocabs = None
    if args.entity_vocabs:
        with open(args.entity_vocabs) as f:
            vocabs = json.load(f)
    kind, path, _ = boot.resolve_model_path(args.model_dir)
    if kind == "generations" and args.model_format != "AVRO":
        model, marker, gen = boot.GenerationStore(path).load_current()
        logger.info("mapped boot: generation gen-%06d (model_version "
                    "%d) of %s", gen, int(marker.get("model_version", 0)),
                    path)
        return model, vocabs, {"generation": gen,
                               "model_version":
                                   int(marker.get("model_version", 0))}
    if kind == "mapped" and args.model_format != "AVRO":
        model, marker = boot.load_mapped_model(path)
        return model, vocabs, {"generation": marker.get("generation"),
                               "model_version":
                                   int(marker.get("model_version", 0))}
    if args.model_format == "AVRO":
        from photon_ml_tpu.avro.model_io import (load_game_model_avro,
                                                 load_index_maps)

        if not args.feature_index_dir:
            raise ValueError(
                "--model-format AVRO needs --feature-index-dir (the "
                "model's feature space)")
        imaps = load_index_maps(args.feature_index_dir)
        if vocabs is None:
            vocab_path = os.path.join(
                os.path.dirname(args.feature_index_dir.rstrip("/")),
                "entity-vocabs.json")
            if os.path.exists(vocab_path):
                with open(vocab_path) as f:
                    vocabs = json.load(f)
        return load_game_model_avro(args.model_dir, imaps,
                                    entity_vocabs=vocabs), vocabs, {}
    # host=True: random-effect tables go straight to the host store —
    # never staged through device memory on the way in.
    return model_io.load_game_model(args.model_dir, host=True,
                                    mapped=False), vocabs, {}


def _boot_phase_gauges(phases: dict[str, float],
                       generation) -> None:
    """``photon_boot_seconds{phase=...}`` + ``photon_model_generation``
    — the restart tail as numbers, not a log line (one None check when
    metrics are off)."""
    from photon_ml_tpu import obs

    mx = obs.metrics()
    if mx is None:
        return
    for phase, seconds in phases.items():
        mx.gauge("photon_boot_seconds", phase=phase).set(seconds)
    if generation is not None:
        mx.gauge("photon_model_generation").set(float(generation))


def create_server(args):
    """Build the resident service + bound HTTP server (not yet serving).

    Split from ``main`` so tests and embedding callers can drive the
    server loop themselves; returns (server, service).

    Construction is attributed as a ``serving.boot`` span with
    ``boot.map`` (model load — an mmap for generation/mapped layouts, a
    parse for npz), ``boot.compile`` (service + program construction)
    and ``boot.warmup`` (bucket-shape touches, ``--boot-warmup``)
    children — recorded AFTER the fact via ``record_complete`` so the
    service's own lifecycle span (the ScoringStart/Finish bridge pair,
    which outlives boot by the whole serving session) never nests
    inside a boot phase (docs/SERVING.md "Sub-second restart")."""
    import time as _time

    from photon_ml_tpu import obs

    if getattr(args, "fault_plan", None):
        from photon_ml_tpu import faults as flt

        with open(args.fault_plan) as f:
            flt.install(flt.FaultPlan.from_json(f.read()))
        logger.warning("fault plan %s ARMED in this server",
                       args.fault_plan)
    marks = {}

    def _phase(name, t0, e0):
        marks[name] = (e0, _time.perf_counter() - t0)

    t_boot, e_boot = _time.perf_counter(), _time.time_ns()
    enable_compilation_cache()
    device = device_summary()
    # A lone server scores on the first device only, by design.
    logger.info("running on platform=%s device_kind=%s devices=%d "
                "(scoring uses one)", device["platform"], device["kind"],
                device["count"])
    t0, e0 = _time.perf_counter(), _time.time_ns()
    model, vocabs, boot_meta = load_model(args)
    _phase("boot.map", t0, e0)
    t0, e0 = _time.perf_counter(), _time.time_ns()
    service = ScoringService(
        model, as_mean=args.as_mean, max_batch=args.max_batch,
        max_wait_ms=args.max_wait_ms,
        cache_entities=args.cache_entities,
        cache_dtype=getattr(args, "cache_dtype", "float32"),
        store_shards=args.store_shards, entity_vocabs=vocabs,
        max_queue=args.max_queue,
        request_deadline_s=(args.request_deadline_s or None),
        slo_window_s=getattr(args, "slo_window_s", 60.0),
        slo_availability=getattr(args, "slo_availability", 0.999),
        slo_latency_ms=getattr(args, "slo_latency_ms", None),
        replica_id=getattr(args, "replica_id", None),
        initial_version=int(boot_meta.get("model_version", 0) or 0),
        boot_generation=boot_meta.get("generation"))
    _phase("boot.compile", t0, e0)
    if getattr(args, "boot_warmup", False):
        t0, e0 = _time.perf_counter(), _time.time_ns()
        shapes = service.warmup()
        _phase("boot.warmup", t0, e0)
        logger.info("boot warmup: %d bucket shape(s) in %.3fs", shapes,
                    marks["boot.warmup"][1])
    total = _time.perf_counter() - t_boot
    tr = obs.tracer()
    if tr is not None:
        bid = tr.record_complete("serving.boot", cat="serving",
                                 t0_epoch_ns=e_boot, dur_s=total,
                                 generation=boot_meta.get("generation"))
        for name, (e0, dur) in marks.items():
            tr.record_complete(name, cat="serving", t0_epoch_ns=e0,
                               dur_s=dur, parent=bid)
    phases = {"map": marks["boot.map"][1],
              "compile": marks["boot.compile"][1],
              "warmup": marks.get("boot.warmup", (0, 0.0))[1],
              "total": total}
    t_map, t_compile, t_warm = (phases["map"], phases["compile"],
                                phases["warmup"])
    _boot_phase_gauges(phases, boot_meta.get("generation"))
    logger.info("boot: map %.3fs, compile %.3fs, warmup %.3fs "
                "(generation %s)", t_map, t_compile, t_warm,
                boot_meta.get("generation"))
    server = make_http_server(service, host=args.host, port=args.port)
    if getattr(args, "ready_file", None):
        # Atomic: the supervisor polling this file must never read a
        # torn write (same tmp+rename discipline as every commit point).
        host, port = server.server_address[:2]
        tmp = args.ready_file + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"pid": os.getpid(), "host": host, "port": port}, f)
        os.replace(tmp, args.ready_file)
    return server, service


def _dump_observability(service, trace_out, metrics_dump) -> None:
    """Shutdown/crash dump path (runs in a ``finally``): a served session
    keeps its timeline and scoreboard even when the server dies — the
    crash is exactly when you want them (game_train parity)."""
    from photon_ml_tpu import obs

    if trace_out:
        obs.dump_trace(trace_out)
        logger.info("wrote trace %s (render with `photon-obs summarize "
                    "--serving`)", trace_out)
    if metrics_dump:
        tmp = metrics_dump + ".tmp"
        with open(tmp, "w") as f:
            f.write(service.metrics_text())
        os.replace(tmp, metrics_dump)
        logger.info("wrote metrics %s", metrics_dump)


def run(args) -> None:
    setup_logging()
    trace_out = getattr(args, "trace_out", None)
    metrics_dump = getattr(args, "metrics_dump", None)
    if trace_out or metrics_dump:
        from photon_ml_tpu import obs

        # Metrics ride along with tracing (the request-span path needs
        # the tracer; the /metrics registry append needs the registry).
        obs.enable(trace=bool(trace_out), metrics=True)
    server, service = create_server(args)
    host, port = server.server_address[:2]
    logger.info("serving %s on http://%s:%d (POST /score, GET /metrics, "
                "GET /slo)", args.model_dir, host, port)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        logger.info("shutting down")
    finally:
        server.server_close()
        service.close()
        if trace_out or metrics_dump:
            from photon_ml_tpu import obs

            try:
                _dump_observability(service, trace_out, metrics_dump)
            finally:
                obs.disable()


def main(argv=None):
    run(build_parser().parse_args(argv))


if __name__ == "__main__":
    main()
