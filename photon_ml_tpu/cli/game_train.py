"""GAME training driver.

Reference parity: photon-client ``cli/game/training/GameTrainingDriver.
scala`` + ``cli/game/GameDriver.scala`` — parse params, read train/validation
data, run GameEstimator.fit over the regularization grid, select the best
model by the primary validation evaluator, write model + summary. Supports
warm start (``--model-input-dir``) and partial retraining
(``--locked-coordinates``).

Coordinate specs use the same mini-DSL style as the reference's config
strings, e.g.:

    --coordinate "name=fixed,type=fixed,shard=global"
    --coordinate "name=per-user,type=random,shard=re_userId,re=userId,min_samples=2"
    --opt-config "fixed:optimizer=LBFGS,reg=L2,reg_weight=1.0"
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import logging
import os
import time

from photon_ml_tpu.api.configs import (CoordinateConfiguration,
                                       FactoredRandomEffectDataConfiguration,
                                       FixedEffectDataConfiguration,
                                       RandomEffectDataConfiguration,
                                       parse_ingest_config, parse_kv,
                                       parse_optimizer_config,
                                       parse_staging_config,
                                       parse_streaming_config)
from photon_ml_tpu.api.estimator import GameEstimator
from photon_ml_tpu.data.io import load_game_dataset
from photon_ml_tpu.data.validators import (DataValidationLevel,
                                           validate_game_dataset)
from photon_ml_tpu.models import io as model_io
from photon_ml_tpu.optim.problem import GLMOptimizationConfiguration
from photon_ml_tpu.parallel.mesh import device_summary, make_mesh
from photon_ml_tpu.types import TaskType
from photon_ml_tpu.utils.compile_cache import enable_compilation_cache
from photon_ml_tpu.utils.logging import setup_logging

logger = logging.getLogger("photon_ml_tpu.cli")


def parse_coordinate(spec: str) -> tuple[str, dict]:
    kv = parse_kv(spec)
    if "name" not in kv or "type" not in kv or "shard" not in kv:
        raise ValueError(f"coordinate spec needs name/type/shard: {spec!r}")
    return kv.pop("name"), kv


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--train", required=True,
                   help="training data: a GameDataset directory "
                        "(data/io.py format) or a LIBSVM text FILE "
                        "(loaded as one sparse 'global' shard — the "
                        "Criteo-style fixed-effect-only configuration)")
    p.add_argument("--validation")
    p.add_argument("--task", default="LOGISTIC_REGRESSION",
                   choices=[t.value for t in TaskType])
    p.add_argument("--coordinate", action="append", required=True,
                   help="coordinate spec (repeatable): name=,type=fixed|"
                        "random|factored,shard=[,re=,min_samples=,"
                        "max_samples=,projector=NONE|INDEX_MAP|RANDOM,"
                        "projected_dim=,features_to_samples_ratio=,"
                        "subspace=auto|true|false (keep the trained "
                        "random-effect model in per-entity subspace form),"
                        "rank=,alternations=,hybrid=,dtype=]")
    p.add_argument("--opt-config", action="append", default=[],
                   help="'<coordinate>:<optimizer mini-DSL>' (repeatable)")
    p.add_argument("--update-sequence", required=True,
                   help="comma-separated coordinate order")
    p.add_argument("--iterations", type=int, default=1)
    p.add_argument("--evaluators", default="",
                   help="comma-separated, first is primary (e.g. AUC,AUC@userId)")
    p.add_argument("--reg-weight-grid", default=[],
                   help="'<coordinate>:w1,w2,...' (repeatable)",
                   action="append")
    p.add_argument("--model-input-dir", help="warm-start GameModel directory")
    p.add_argument("--locked-coordinates", default="",
                   help="comma-separated coordinates to keep fixed")
    p.add_argument("--output-dir", required=True)
    p.add_argument("--output-mode", default="BEST", choices=["BEST", "ALL"])
    p.add_argument("--checkpoint", action=argparse.BooleanOptionalAction,
                   default=True,
                   help="checkpoint descent progress under "
                        "<output-dir>/checkpoints after every coordinate "
                        "update (--no-checkpoint disables)")
    p.add_argument("--resume", action="store_true",
                   help="resume from an existing checkpoint directory "
                        "instead of starting fresh")
    p.add_argument("--tuning", default="NONE",
                   choices=["NONE", "RANDOM", "BAYESIAN"],
                   help="hyperparameter-tuning mode: search per-coordinate "
                        "regularization weights after the grid sweep "
                        "(reference: GameTrainingDriver hyperParameterTuning)")
    p.add_argument("--tuning-iters", type=int, default=10,
                   help="number of tuning trials")
    p.add_argument("--tuning-range", default="1e-4:1e4",
                   help="lo:hi regularization-weight search range "
                        "(log scale)")
    p.add_argument("--profile-dir",
                   help="capture a jax.profiler trace of the fit into this "
                        "directory (TensorBoard/Perfetto viewable)")
    p.add_argument("--data-validation", default="VALIDATE_FULL",
                   choices=[v.value for v in DataValidationLevel],
                   help="input sanity checks (reference DataValidators: "
                        "task-valid labels, finite features/offsets, "
                        "non-negative weights)")
    p.add_argument("--avro-feature-shard", action="append", default=[],
                   help='Avro-input shard spec '
                        '"name=global,bags=features[+moreBags],'
                        'intercept=true,sparse=false" (repeatable). Any '
                        "spec switches --train/--validation to Avro "
                        "container files/directories, the reference's "
                        "AvroDataReader flow")
    p.add_argument("--avro-re-types", default="",
                   help="comma-separated random-effect id keys read from "
                        "the Avro records' metadataMap")
    p.add_argument("--feature-index-dir",
                   help="saved index maps (<shard>.json) freezing the "
                        "feature space (reference PalDB feature maps); "
                        "built from the data when omitted")
    p.add_argument("--date-range",
                   help="yyyyMMdd-yyyyMMdd or ISO a:b — expand --train as "
                        "daily partitions <root>/yyyy/mm/dd (reference "
                        "inputDataDateRange)")
    p.add_argument("--model-output-format", default="NPZ",
                   choices=["NPZ", "AVRO", "BOTH"],
                   help="AVRO additionally writes the reference's "
                        "BayesianLinearModelAvro layout under "
                        "<output-dir>/best-avro, together with the index "
                        "maps and entity vocabularies needed to reload it "
                        "(requires Avro input via --avro-feature-shard)")
    p.add_argument("--distributed", action="store_true",
                   help="join the multi-host world before building the "
                        "mesh (JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES "
                        "/ JAX_PROCESS_ID; automatic on Cloud TPU). "
                        "Recovery from a lost host is restart + --resume.")
    p.add_argument("--fabric", action="store_true",
                   help="arm the host-level DCN fabric for streamed "
                        "fixed-effect fits (PHOTON_FABRIC_WORLD / "
                        "PHOTON_FABRIC_RANK / PHOTON_FABRIC_COORDINATOR; "
                        "docs/STREAMING.md \"Multi-host streaming\"): "
                        "chunk ranges shard over hosts, host partials "
                        "meet in one cross-host allreduce per pass, and "
                        "every accepted iteration exchanges cross-rank "
                        "digests. Composes with --distributed; the mesh "
                        "then spans LOCAL devices only.")
    p.add_argument("--staging-cache-dir",
                   help="persist projected random-effect staging artifacts "
                        "here, keyed by dataset content digest — a re-run "
                        "on the same data memory-maps the staged blocks "
                        "(shard-granular: a killed run resumes with "
                        "partial credit) instead of re-paying the "
                        "projection pass")
    p.add_argument("--staging",
                   help="parallel staging pipeline knobs, "
                        "'workers=8,mode=thread|process,depth=10,"
                        "shard_entities=65536,retries=2,backoff=0.05,"
                        "straggler=30' (docs/STAGING.md, "
                        "docs/ROBUSTNESS.md); default: one worker per "
                        "host core, thread mode, depth=workers+2")
    p.add_argument("--ingest",
                   help="parallel Avro ingestion knobs, "
                        "'workers=8,mode=thread|process,depth=2,"
                        "chunk_records=65536' (docs/INGEST.md); applies "
                        "to Avro inputs (--avro-feature-shard). Default: "
                        "one decode worker per host core, thread mode")
    p.add_argument("--streaming", nargs="?", const="",
                   help="route sparse fixed-effect coordinates onto the "
                        "row-streamed path (docs/STREAMING.md): the shard "
                        "stages into host-resident chunks, chunk ranges "
                        "partition over the mesh's data axis, and every "
                        "L-BFGS value/gradient streams each device's "
                        "range with psum-merged partials — n bounded by "
                        "host RAM, not HBM; the fit checkpoints mid-"
                        "optimization. Optional mini-DSL "
                        "'chunk_rows=262144,num_hot=512,"
                        "dtype=float32|bfloat16|int8,depth=2,pin=0,"
                        "workers=8,solver=lbfgs|sdca|sgd' (bare "
                        "--streaming takes every default; dtype=int8 "
                        "quarters the streamed bytes — symmetric "
                        "per-column quantization with f32 accumulation; "
                        "solver=sdca|sgd runs the duality-gap-certified "
                        "stochastic solvers over the same chunk feed, "
                        "docs/STREAMING.md)")
    p.add_argument("--ingest-cache-dir",
                   help="persist decoded Avro columns here (columnar "
                        "mmap ingest cache, keyed by file identity + "
                        "decode plan) — a re-run on the same inputs "
                        "memory-maps columns instead of re-decoding "
                        "Avro, and a killed run resumes with per-chunk "
                        "partial credit (docs/INGEST.md)")
    p.add_argument("--fault-plan",
                   help="TESTING ONLY: install a deterministic "
                        "fault-injection plan (photon_ml_tpu/faults "
                        "FaultPlan JSON) for this run — the chaos "
                        "suite's process-level kill/corruption drills "
                        "drive the trainer through this flag "
                        "(docs/ROBUSTNESS.md)")
    p.add_argument("--trace-out",
                   help="write a Chrome trace-event JSON of this run "
                        "(photon-obs span tracing: lifecycle scopes, "
                        "streamed passes, chunk transfers, checkpoint "
                        "writes) — load in chrome://tracing or "
                        "ui.perfetto.dev, or render with `photon-obs "
                        "summarize` (docs/OBSERVABILITY.md). Off by "
                        "default: the instrumentation then costs one "
                        "None check per site")
    p.add_argument("--metrics-dump",
                   help="write the cross-stack metrics registry "
                        "(transfer bytes/seconds, compile-cache misses, "
                        "peak in-flight chunks, retry/recovery counters) "
                        "as Prometheus text at exit — the batch-run "
                        "form of the serving /metrics endpoint "
                        "(docs/OBSERVABILITY.md)")
    p.add_argument("--ledger-dir", default=None,
                   help="run-ledger directory (docs/OBSERVABILITY.md "
                        "\"The run ledger\"): manifest + append-as-"
                        "produced per-iteration convergence telemetry, "
                        "committed under the atomic-marker/CRC "
                        "discipline so a crashed run keeps its curve. "
                        "Default: <output-dir>/ledger; pass '' to "
                        "disable. A fresh run replaces a stale ledger; "
                        "--resume validates run identity and APPENDS. "
                        "Inspect with `photon-obs tail/diff/verify`")
    p.add_argument("--watchdog", nargs="?", const="",
                   help="arm the convergence watchdogs "
                        "(obs/watchdog.py): 'nan=raise|warn|stop|off,"
                        "stall=K[:action],divergence=F[:action],"
                        "slow_iter=F[:action]'. Bare --watchdog arms "
                        "the NaN detector (raise). Off by default at "
                        "one None check per optimizer iteration")
    return p


def _arm_observability(args, stack, is_primary, est) -> None:
    """Install the run ledger + convergence watchdogs for the span of
    the fit/tuning phase (docs/OBSERVABILITY.md "The run ledger").

    The ledger defaults ON (``<output-dir>/ledger``; ``--ledger-dir ''``
    disables): every ``game_train`` run leaves its convergence curve on
    disk. A fresh run replaces a stale ledger (exactly the checkpoint
    cleanup discipline); ``--resume`` appends after descent validates
    run identity against the checkpoint fingerprint. Rank 0 only — one
    writer per shared filesystem; close() runs via the stack so a
    crashed fit keeps its curve prefix.
    """
    from photon_ml_tpu import obs

    spec = getattr(args, "watchdog", None)
    if spec is not None:
        prev_wd = obs.set_watchdog(obs.parse_watchdog_config(spec))
        stack.callback(obs.set_watchdog, prev_wd)
    ledger_dir = getattr(args, "ledger_dir", None)
    if ledger_dir is None:
        ledger_dir = os.path.join(args.output_dir, "ledger")
    if not ledger_dir or not is_primary:
        return
    if not getattr(args, "resume", False) and os.path.exists(ledger_dir):
        import shutil

        logger.info("fresh run: removing stale run ledger at %s",
                    ledger_dir)
        shutil.rmtree(ledger_dir)
    led = obs.RunLedger.resume(ledger_dir,
                               manifest=est.ledger_manifest())
    prev_led = obs.set_ledger(led)

    def _close(exc_type, exc, tb):
        led.close(status="ok" if exc_type is None else "error")
        obs.set_ledger(prev_led)
        return False

    stack.push(_close)


def _load_dataset(path: str, num_features=None):
    """GameDataset directory, or a LIBSVM file → sparse 'global' shard."""
    if os.path.isdir(path):
        return load_game_dataset(path)
    from photon_ml_tpu.data.game_data import from_sparse_batch
    from photon_ml_tpu.data.libsvm import read_libsvm
    from photon_ml_tpu.data.sparse import from_libsvm

    data = read_libsvm(path, dense=False, num_features=num_features)
    return from_sparse_batch(from_libsvm(data))


def _parse_avro_shards(specs):
    """--avro-feature-shard mini-DSL → {shard: FeatureShardConfig}."""
    from photon_ml_tpu.avro.data_reader import FeatureShardConfig

    out = {}
    for spec in specs:
        kv = parse_kv(spec)
        if "name" not in kv:
            raise ValueError(f"avro shard spec needs name=: {spec!r}")
        out[kv["name"]] = FeatureShardConfig(
            feature_bags=tuple(
                b for b in kv.get("bags", "features").split("+") if b),
            has_intercept=kv.get("intercept", "true").lower() == "true",
            sparse=kv.get("sparse", "false").lower() == "true")
    return out


def _ingest_config(args):
    """--ingest / --ingest-cache-dir → IngestConfig (None when neither
    flag is set: the reader then uses its defaults)."""
    from photon_ml_tpu.ingest import IngestConfig

    cfg = (parse_ingest_config(args.ingest)
           if getattr(args, "ingest", None) else None)
    if getattr(args, "ingest_cache_dir", None):
        cfg = dataclasses.replace(cfg or IngestConfig(),
                                  cache_dir=args.ingest_cache_dir)
    return cfg


def _load_avro_inputs(args):
    """The reference GameTrainingDriver flow: feature maps → AvroDataReader
    → (train, validation) GameDatasets sharing one feature space."""
    from photon_ml_tpu.avro.data_reader import AvroDataReader
    from photon_ml_tpu.avro.model_io import load_index_maps
    from photon_ml_tpu.utils.ranges import (DateRange,
                                            input_paths_within_date_range)

    ingest_cfg = _ingest_config(args)
    shard_cfgs = _parse_avro_shards(args.avro_feature_shard)
    re_types = [t for t in args.avro_re_types.split(",") if t]
    index_maps = (load_index_maps(args.feature_index_dir)
                  if args.feature_index_dir else None)
    train_paths = [args.train]
    if args.date_range:
        train_paths = input_paths_within_date_range(
            args.train, DateRange.parse(args.date_range))
        if not train_paths:
            raise ValueError(
                f"no daily partitions under {args.train} within "
                f"{args.date_range}")
        logger.info("date range %s: %d daily partitions", args.date_range,
                    len(train_paths))
    reader = AvroDataReader()
    train, meta = reader.read(train_paths, shard_cfgs,
                              random_effect_types=re_types,
                              index_maps=index_maps, ingest=ingest_cfg)
    validation = None
    if args.validation:
        # Frozen feature space + entity vocabulary from training
        # (reference: validation reads through the same index maps).
        # Unseen validation entities are routine (new users appear every
        # day) — they get rows past the frozen range and score with the
        # fixed effect only, the reference's unseen-entity semantics.
        validation, val_meta = reader.read(
            args.validation, shard_cfgs, random_effect_types=re_types,
            index_maps=meta.index_maps, entity_vocabs=meta.entity_vocabs,
            allow_unseen_entities=True, ingest=ingest_cfg)
        for t in re_types:
            unseen = (len(val_meta.entity_vocabs[t])
                      - len(meta.entity_vocabs[t]))
            if unseen:
                logger.info(
                    "validation has %d unseen %s entities (scored with "
                    "the fixed effect only)", unseen, t)
    return train, validation, meta


def _sync_global_devices_or_skip(tag: str) -> None:
    """``multihost_utils.sync_global_devices`` where the backend can,
    a loud skip where it cannot.

    The barrier is a device collective, and the CPU backend cannot run
    multi-process collectives at all ("Multiprocess computations aren't
    implemented"). On that backend the sync seam degrades to a logged
    no-op: the checkpoint-cleanup race it guards is a real-filesystem
    concern that CPU multi-process runs (localhost test worlds) do not
    actually have. On every other backend a failed barrier raises — a
    silently skipped barrier where one was needed would be
    resuming-from-wrong-state by another name.
    """
    import jax

    if jax.default_backend() == "cpu":
        logger.warning(
            "SKIPPING sync_global_devices(%r): the CPU backend has no "
            "multi-process collectives — ranks proceed unbarriered "
            "(safe for localhost test worlds; use a real accelerator "
            "backend for shared-filesystem runs)", tag)
        return
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices(tag)


def _disarm_fabric() -> None:
    """Release the process-wide fabric (run bracket: an in-process
    caller — tests, the smoke drivers — must not leak an armed comm or
    a bound coordinator socket into the next run)."""
    from photon_ml_tpu.fabric import runtime as fabric_runtime

    comm = fabric_runtime.active()
    if comm is not None:
        fabric_runtime.install(None)
        comm.close()


def run(args) -> dict:
    """Driver entry: observability bracket around the real run (the
    trace/metrics dumps happen in a ``finally`` so a crashed fit still
    leaves its timeline on disk — the crash is exactly when you want
    it)."""
    trace_out = getattr(args, "trace_out", None)
    metrics_dump = getattr(args, "metrics_dump", None)
    if trace_out or metrics_dump:
        from photon_ml_tpu import obs

        obs.enable(trace=bool(trace_out), metrics=True,
                   spill=(trace_out + ".spill") if trace_out else None)
        try:
            with obs.span("game_train", cat="driver"):
                return _run(args)
        finally:
            import jax

            if jax.process_index() == 0:
                # One writer on a shared checkpoint/output filesystem.
                if trace_out:
                    obs.dump_trace(trace_out)
                    logger.info("wrote trace %s (chrome://tracing, "
                                "ui.perfetto.dev, or `photon-obs "
                                "summarize`)", trace_out)
                if metrics_dump:
                    obs.dump_metrics(metrics_dump)
                    logger.info("wrote metrics %s", metrics_dump)
            obs.disable()
            _disarm_fabric()
    try:
        return _run(args)
    finally:
        _disarm_fabric()


def _run(args) -> dict:
    setup_logging()
    enable_compilation_cache()
    if getattr(args, "fault_plan", None):
        from photon_ml_tpu import faults

        with open(args.fault_plan) as f:
            faults.install(faults.FaultPlan.from_json(f.read()))
        logger.warning("fault injection ACTIVE from %s — this run will "
                       "deliberately fail in the planned ways",
                       args.fault_plan)
    t0 = time.perf_counter()  # duration base (PML004)
    task = TaskType(args.task)
    if (args.model_output_format in ("AVRO", "BOTH")
            and not args.avro_feature_shard):
        # Fail at argument time, not after an hours-long fit.
        raise ValueError(
            "--model-output-format AVRO needs Avro input "
            "(--avro-feature-shard) to supply feature index maps and "
            "entity vocabularies")
    avro_meta = None
    if args.avro_feature_shard:
        train, validation, avro_meta = _load_avro_inputs(args)
    else:
        for flag, value in (("--date-range", args.date_range),
                            ("--avro-re-types", args.avro_re_types),
                            ("--feature-index-dir",
                             args.feature_index_dir),
                            ("--ingest", getattr(args, "ingest", None)),
                            ("--ingest-cache-dir",
                             getattr(args, "ingest_cache_dir", None))):
            if value:
                raise ValueError(
                    f"{flag} applies to Avro inputs "
                    f"(--avro-feature-shard)")
        train = _load_dataset(args.train)
        validation = None
        if args.validation:
            nf = None
            if not os.path.isdir(args.validation):
                # LIBSVM validation must share the training feature space —
                # whatever form training was loaded from.
                if "global" not in train.feature_shards:
                    raise ValueError(
                        "LIBSVM validation requires a 'global' feature "
                        "shard in the training data")
                nf = train.shard_dim("global")
            validation = _load_dataset(args.validation, num_features=nf)
    vlevel = DataValidationLevel(args.data_validation)
    validate_game_dataset(task, train, level=vlevel)
    if validation is not None:
        validate_game_dataset(task, validation, level=vlevel)

    opt_by_coord: dict[str, GLMOptimizationConfiguration] = {}
    for spec in args.opt_config:
        cid, _, dsl = spec.partition(":")
        opt_by_coord[cid.strip()] = parse_optimizer_config(dsl)

    grid_by_coord: dict[str, tuple[float, ...]] = {}
    for spec in args.reg_weight_grid:
        if not spec:
            continue
        cid, _, ws = spec.partition(":")
        grid_by_coord[cid.strip()] = tuple(
            float(w) for w in ws.split(",") if w)

    locked = {c for c in args.locked_coordinates.split(",") if c}
    coordinates: dict[str, CoordinateConfiguration] = {}
    for spec in args.coordinate:
        name, kv = parse_coordinate(spec)
        if kv["type"] == "fixed":
            hybrid_kv = kv.get("hybrid", "auto").lower()
            if hybrid_kv not in ("auto", "true", "false"):
                raise ValueError(
                    f"hybrid= must be auto, true, or false "
                    f"(got {hybrid_kv!r})")
            data = FixedEffectDataConfiguration(
                kv["shard"],
                feature_sharded=kv.get("feature_sharded",
                                       "false").lower() == "true",
                feature_dtype=kv.get("dtype", "float32"),
                hybrid=(None if hybrid_kv == "auto"
                        else hybrid_kv == "true"))
        elif kv["type"] == "random":
            sub_kv = kv.get("subspace", "auto").lower()
            if sub_kv not in ("auto", "true", "false"):
                raise ValueError(
                    f"subspace= must be auto, true, or false "
                    f"(got {sub_kv!r})")
            kv["subspace"] = sub_kv
            data = RandomEffectDataConfiguration(
                random_effect_type=kv["re"],
                feature_shard_id=kv["shard"],
                active_data_lower_bound=int(kv.get("min_samples", 1)),
                active_data_upper_bound=(int(kv["max_samples"])
                                         if "max_samples" in kv else None),
                projector=kv.get("projector", "NONE").upper(),
                projected_dimension=(int(kv["projected_dim"])
                                     if "projected_dim" in kv else None),
                features_to_samples_ratio=(
                    float(kv["features_to_samples_ratio"])
                    if "features_to_samples_ratio" in kv else None),
                subspace_model=(
                    None if kv.get("subspace", "auto") == "auto"
                    else kv["subspace"] == "true"),
                feature_dtype=kv.get("dtype", "float32"))
        elif kv["type"] == "factored":
            data = FactoredRandomEffectDataConfiguration(
                random_effect_type=kv["re"],
                feature_shard_id=kv["shard"],
                rank=int(kv.get("rank", 4)),
                alternations=int(kv.get("alternations", 2)),
                active_data_lower_bound=int(kv.get("min_samples", 1)),
                active_data_upper_bound=(int(kv["max_samples"])
                                         if "max_samples" in kv else None))
        else:
            raise ValueError(f"unknown coordinate type {kv['type']!r}")
        opt = opt_by_coord.get(name, GLMOptimizationConfiguration())
        grid = grid_by_coord.get(name, ())
        # Locked coordinates are never retrained, so tuning/grids don't
        # apply to them — don't demand a regularizer for them.
        if ((grid or (args.tuning != "NONE" and name not in locked))
                and opt.regularization.reg_type.value == "NONE"):
            # A reg-weight grid / tuning sweep over a NONE-regularized
            # coordinate silently fits the identical model at every point.
            raise ValueError(
                f"coordinate {name!r} has regularization NONE; "
                f"--reg-weight-grid/--tuning need an --opt-config with "
                f"reg=L1|L2|ELASTIC_NET for it")
        coordinates[name] = CoordinateConfiguration(
            data=data, optimization=opt, reg_weight_grid=grid)

    evaluators = [e for e in args.evaluators.split(",") if e]
    if args.tuning != "NONE" and (not args.validation or not evaluators):
        # Fail at argument time, not after an hours-long grid sweep.
        raise ValueError("--tuning requires --validation and --evaluators")
    fabric_comm = None
    if getattr(args, "fabric", False):
        # Arm the process-wide fabric BEFORE the estimator stages any
        # streamed coordinate (fabric/runtime.py). The mesh goes LOCAL:
        # cross-host traffic rides the FabricComm allreduce, never an
        # XLA collective (unimplemented on CPU process groups).
        from photon_ml_tpu.fabric import runtime as fabric_runtime

        fabric_comm = fabric_runtime.comm_from_env()
        if fabric_comm is None:
            raise ValueError(
                "--fabric needs PHOTON_FABRIC_WORLD >= 2 plus "
                "PHOTON_FABRIC_RANK / PHOTON_FABRIC_COORDINATOR in the "
                "environment (fabric/runtime.comm_from_env)")
        fabric_runtime.install(fabric_comm)
        logger.info("fabric armed: rank %d/%d (coordinator %s:%d)",
                    fabric_comm.rank, fabric_comm.world,
                    *fabric_comm.coordinator)
    est = GameEstimator(
        task=task,
        coordinates=coordinates,
        update_sequence=[c for c in args.update_sequence.split(",") if c],
        mesh=make_mesh(distributed=getattr(args, "distributed", False),
                       local=fabric_comm is not None),
        descent_iterations=args.iterations,
        validation_evaluators=evaluators,
        staging_cache_dir=args.staging_cache_dir,
        staging=(parse_staging_config(args.staging)
                 if getattr(args, "staging", None) else None),
        ingest=_ingest_config(args) if args.avro_feature_shard else None,
        streaming=(parse_streaming_config(args.streaming)
                   if getattr(args, "streaming", None) is not None
                   else None))
    device = device_summary()
    logger.info("running on platform=%s device_kind=%s devices=%d",
                device["platform"], device["kind"], device["count"])

    initial_models = None
    if args.model_input_dir:
        initial_models = dict(
            model_io.load_game_model(args.model_input_dir).models)

    # Multi-host: every process runs the same device program, but only the
    # primary touches shared files (checkpoint cleanup, model/summary
    # output). Checkpoint LOADS happen on every rank (identical control
    # flow needs identical resume state — checkpoint_dir must be a shared
    # filesystem); SAVES are rank-0-only inside CheckpointManager.
    import jax
    is_primary = jax.process_index() == 0 and (
        fabric_comm is None or fabric_comm.rank == 0)

    if getattr(args, "resume", False) and not getattr(args, "checkpoint", True):
        raise ValueError("--resume requires checkpointing; "
                         "drop --no-checkpoint")
    checkpoint_dir = None
    if getattr(args, "checkpoint", True):
        checkpoint_dir = os.path.join(args.output_dir, "checkpoints")
        if (is_primary and not getattr(args, "resume", False)
                and os.path.exists(checkpoint_dir)):
            # Fresh run: stale checkpoints must not silently short-circuit
            # training (resume is an explicit opt-in).
            import shutil
            logger.info("fresh run: removing stale checkpoints at %s",
                        checkpoint_dir)
            shutil.rmtree(checkpoint_dir)
        if jax.process_count() > 1:
            # All ranks load checkpoints inside fit; none may read before
            # rank 0's cleanup above lands on the shared filesystem.
            _sync_global_devices_or_skip("checkpoint-cleanup")

    from photon_ml_tpu.utils.logging import profile_trace

    with contextlib.ExitStack() as obs_stack:
        _arm_observability(args, obs_stack, is_primary, est)
        from photon_ml_tpu import obs

        led = obs.ledger()
        ledger_info = (None if led is None else
                       {"dir": led.directory,
                        "run_id": led.manifest.get("run_id")})

        with profile_trace(getattr(args, "profile_dir", None)):
            results = est.fit(train, validation,
                              initial_models=initial_models,
                              locked_coordinates=locked or None,
                              checkpoint_dir=checkpoint_dir)

        tuning_summary = None
        if args.tuning != "NONE":
            # Reference: GameTrainingDriver's hyperparameter-tuning mode
            # — the grid results seed the search as prior observations,
            # then RANDOM / BAYESIAN (GP + expected improvement) trials
            # refine the per-coordinate regularization weights on the
            # validation metric. The search runs INSIDE the ledger
            # scope: per-trial rows land in the same run ledger.
            from photon_ml_tpu.hyperparameter.evaluation import \
                GameEvaluationFunction
            from photon_ml_tpu.hyperparameter.search import (
                GaussianProcessSearch, RandomSearch)
            from photon_ml_tpu.utils.ranges import DoubleRange

            lo, _, hi = args.tuning_range.partition(":")
            evalfn = GameEvaluationFunction(
                est, train, validation,
                coordinate_ids=[c for c in est.update_sequence
                                if c not in locked],
                reg_weight_range=DoubleRange(float(lo), float(hi)),
                initial_models=initial_models,
                locked_coordinates=locked or None)
            dims = evalfn.dimensions()
            searcher_cls = (GaussianProcessSearch
                            if args.tuning == "BAYESIAN" else RandomSearch)
            searcher = searcher_cls(dims, evalfn)
            priors = evalfn.observations_from_results(results)
            search = searcher.find_with_priors(args.tuning_iters, priors)
            best_trial = evalfn.best_trial()
            if (best_trial is not None
                    and best_trial[0] <= search.best_value + 1e-12):
                # The winning trial's model was already trained during
                # the search — reuse it instead of refitting an
                # (n+1)-th time.
                results = results + best_trial[2]
            # else: the winner is a grid prior, already in `results`.
            tuning_summary = {
                "mode": args.tuning,
                "iterations": args.tuning_iters,
                "best_config": search.best_config(dims),
                "trials": [
                    {"point": {d.name: float(p)
                               for d, p in zip(dims, o.point)},
                     "objective": float(o.value)}
                    for o in search.observations],
            }

    best = est.select_best_model(results)

    os.makedirs(args.output_dir, exist_ok=True)
    if is_primary:
        if args.output_mode == "ALL":
            for i, r in enumerate(results):
                model_io.save_game_model(
                    r.model, os.path.join(args.output_dir, f"model-{i}"))
        if args.model_output_format in ("NPZ", "BOTH"):
            model_io.save_game_model(best.model,
                                     os.path.join(args.output_dir, "best"))
        if args.model_output_format in ("AVRO", "BOTH"):
            from photon_ml_tpu.avro.model_io import (save_game_model_avro,
                                                     save_index_maps)

            avro_dir = os.path.join(args.output_dir, "best-avro")
            save_game_model_avro(
                best.model, avro_dir, avro_meta.index_maps,
                entity_vocabs=avro_meta.entity_vocabs)
            # Make the directory self-contained: reloading needs the same
            # index maps and vocabularies that wrote it, not a re-read of
            # the training data.
            save_index_maps(avro_meta.index_maps,
                            os.path.join(avro_dir, "index-maps"))
            with open(os.path.join(avro_dir, "entity-vocabs.json"),
                      "w") as f:
                json.dump(avro_meta.entity_vocabs, f)
    summary = {
        "task": task.value,
        "device": device,
        # Byte-level fingerprint of the selected model: two runs (or two
        # DCN ranks) trained the SAME model iff these agree — a far
        # sharper probe than any rounded metric (VERDICT Weak #6).
        "model_digest": model_io.game_model_digest(best.model),
        "candidates": [
            {"configs": {
                c: {"reg_type": o.regularization.reg_type.value,
                    "reg_weight": o.regularization.reg_weight}
                for c, o in r.configs.items()},
             "metrics": r.evaluation.metrics if r.evaluation else None}
            for r in results],
        "best_metrics": (best.evaluation.metrics if best.evaluation else None),
        "tuning": tuning_summary,
        # Provenance pointer: summary and convergence curve are the
        # same run (photon-obs tail/diff on this directory).
        "ledger": ledger_info,
        "wall_seconds": time.perf_counter() - t0,
    }
    if is_primary:
        with open(os.path.join(args.output_dir, "summary.json"), "w") as f:
            json.dump(summary, f, indent=2)
        logger.info("wrote %s", args.output_dir)
    return summary


def main(argv=None):
    run(build_parser().parse_args(argv))


if __name__ == "__main__":
    main()
