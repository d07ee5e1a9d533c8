"""GameEstimator: the training front door.

Reference parity: photon-api ``estimators/GameEstimator.scala`` — builds
per-coordinate datasets/coordinates from the input data, runs
``CoordinateDescent`` once per GameOptimizationConfiguration (the
regularization-weight grid), evaluates each candidate on validation data,
and exposes best-model selection
(``fit(data, validationData, configs) → Seq[(GameModel, EvaluationResults,
config)]``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import logging
import os
from typing import Optional

import jax.numpy as jnp

from photon_ml_tpu import obs
from photon_ml_tpu.api.configs import (CoordinateConfiguration,
                                       FactoredRandomEffectDataConfiguration,
                                       FixedEffectDataConfiguration,
                                       IngestConfig,
                                       RandomEffectDataConfiguration,
                                       StagingConfig, StreamingConfig)
from photon_ml_tpu.data.game_data import GameDataset, SparseShard
from photon_ml_tpu.evaluation import evaluators as ev
from photon_ml_tpu.game import descent
from photon_ml_tpu.game.coordinates import (FixedEffectCoordinate,
                                            RandomEffectCoordinate,
                                            SparseFixedEffectCoordinate)
from photon_ml_tpu.game.factored import FactoredRandomEffectCoordinate
from photon_ml_tpu.game.models import GameModel
from photon_ml_tpu.normalization import NormalizationContext
from photon_ml_tpu.ops import losses as losses_mod
from photon_ml_tpu.optim.problem import (GLMOptimizationConfiguration,
                                         VarianceComputationType)
from photon_ml_tpu.types import TaskType

logger = logging.getLogger("photon_ml_tpu.api")


@dataclasses.dataclass
class GameResult:
    model: GameModel
    evaluation: Optional[ev.EvaluationResults]
    configs: dict[str, GLMOptimizationConfiguration]




class GameEstimator:
    """Train GAME models over a device mesh (reference: GameEstimator)."""

    def __init__(
        self,
        task: TaskType,
        coordinates: dict[str, CoordinateConfiguration],
        update_sequence: list[str],
        mesh,
        descent_iterations: int = 1,
        validation_evaluators: Optional[list[str]] = None,
        normalization: Optional[dict[str, NormalizationContext]] = None,
        compute_variances_at_end: bool = True,
        staging_cache_dir: Optional[str] = None,
        staging: Optional[StagingConfig] = None,
        ingest: Optional[IngestConfig] = None,
        streaming: Optional[StreamingConfig] = None,
        trace=None,
        ledger_dir: Optional[str] = None,
        watchdog=None,
    ):
        self.task = TaskType(task)
        self.coordinate_configs = coordinates
        self.update_sequence = update_sequence
        self.mesh = mesh
        self.descent_iterations = descent_iterations
        self.validation_evaluators = validation_evaluators or []
        self.normalization = normalization or {}
        self.compute_variances_at_end = compute_variances_at_end
        # Disk cache for projected random-effect staging artifacts
        # (game/staging_cache.py): a warm re-fit of the same dataset in a
        # fresh process memory-maps the staged blocks instead of re-paying
        # the projection pass.
        self.staging_cache_dir = staging_cache_dir
        # Parallel staging pipeline knobs (game/staging.py), shared by
        # every projected random-effect coordinate this estimator builds.
        self.staging = staging
        # Parallel Avro ingestion knobs (photon_ml_tpu/ingest): the
        # estimator consumes already-materialized GameDatasets, so this is
        # the configuration surface for the drivers that read Avro on its
        # behalf (game_train wires --ingest / --ingest-cache-dir through
        # here and into AvroDataReader.read).
        self.ingest = ingest
        # Row-streamed fixed effects (docs/STREAMING.md): when set, every
        # sparse fixed-effect coordinate routes onto the streamed path —
        # chunk ranges sharded over the mesh's data axis, psum-merged
        # partials, n bounded by host RAM instead of HBM.
        self.streaming = streaming
        # Span tracing (docs/OBSERVABILITY.md): an obs.Tracer instance
        # activated for the duration of each fit() — library users get
        # the same timeline `game_train --trace-out` produces, without
        # going through the CLI. None (the default) costs nothing.
        self.trace = trace
        # Run ledger (docs/OBSERVABILITY.md "The run ledger"): when set,
        # each fit() writes convergence telemetry under this directory —
        # manifest + append-as-produced per-iteration rows. Reuses an
        # already-active ledger (the game_train driver's, a tuning
        # trial's parent) instead of opening a second one.
        self.ledger_dir = ledger_dir
        # Convergence watchdogs (obs/watchdog.py): a WatchdogConfig
        # armed for the duration of fit(). None (default) = every
        # optimizer site pays one None check.
        self.watchdog = watchdog
        self.loss = losses_mod.loss_for_task(self.task)
        # (cache key, coords) of the last fit — lets repeated fits on the
        # SAME dataset (hyperparameter tuning trials) swap optimization
        # configs instead of re-running bucketing + device staging. A shared
        # mutable holder, not a plain attribute: tuning fits shallow-copied
        # estimators, and the copies must feed the same cache. The cached
        # coordinates keep the dataset alive, so id() keys are stable.
        self._coord_cache: dict[str, tuple[tuple, dict]] = {}

    # -- coordinate construction ------------------------------------------

    def _build_coordinates(
        self,
        dataset: GameDataset,
        opt_configs: dict[str, GLMOptimizationConfiguration],
    ) -> dict[str, object]:
        coords: dict[str, object] = {}
        streamed: list[str] = []
        # A random effect over a sparse shard stages through the pipelined
        # projector: its blocks reach the device as the first sweep consumes
        # them, after every coordinate is built. Such coordinates are built
        # first, so that a resident sparse fixed effect can size its hot
        # block around the bytes they declare; the update sequence, not this
        # order, decides who trains when.
        def stages_at_once(item):
            data = item[1].data
            return not (isinstance(data, RandomEffectDataConfiguration)
                        and data.projector.upper() != "RANDOM"
                        and (data.projector.upper() == "INDEX_MAP"
                             or data.features_to_samples_ratio is not None
                             or isinstance(dataset.feature_shards[
                                 data.feature_shard_id], SparseShard)))

        for cid, cc in sorted(self.coordinate_configs.items(),
                              key=stages_at_once):
            opt = opt_configs[cid]
            if isinstance(cc.data, FixedEffectDataConfiguration):
                shard = dataset.feature_shards[cc.data.feature_shard_id]
                if isinstance(shard, SparseShard):
                    if cc.data.feature_shard_id in self.normalization:
                        raise ValueError(
                            f"normalization is not supported on sparse "
                            f"shard {cc.data.feature_shard_id!r}")
                    if self.streaming is not None:
                        if cc.data.feature_sharded:
                            raise ValueError(
                                f"coordinate {cid!r}: streaming and "
                                f"feature_sharded are mutually exclusive "
                                f"— the streamed path shards ROWS over "
                                f"the data axis (docs/STREAMING.md)")
                        from photon_ml_tpu.game.coordinates import \
                            StreamingSparseFixedEffectCoordinate

                        coords[cid] = \
                            StreamingSparseFixedEffectCoordinate.stage(
                                dataset, cc.data.feature_shard_id,
                                self.loss, opt, self.mesh, self.streaming,
                                default_dtype=cc.data.feature_dtype)
                        streamed.append(cid)
                        continue
                    coords[cid] = SparseFixedEffectCoordinate(
                        dataset, cc.data.feature_shard_id, self.loss, opt,
                        self.mesh,
                        feature_sharded=cc.data.feature_sharded,
                        hybrid=cc.data.hybrid,
                        feature_dtype=cc.data.feature_dtype,
                        deferred_bytes=sum(
                            c.deferred_device_bytes()
                            for c in coords.values()
                            if isinstance(c, RandomEffectCoordinate)))
                    continue
                coords[cid] = FixedEffectCoordinate(
                    dataset, cc.data.feature_shard_id, self.loss, opt,
                    self.mesh,
                    norm=self.normalization.get(cc.data.feature_shard_id,
                                                NormalizationContext()),
                    feature_dtype=cc.data.feature_dtype)
            elif isinstance(cc.data, RandomEffectDataConfiguration):
                if cc.data.projector.upper() == "RANDOM":
                    # Gaussian random projection = a factored coordinate
                    # with a frozen seeded projection matrix
                    # (ProjectionMatrixBroadcast parity).
                    if cc.data.feature_shard_id in self.normalization:
                        raise ValueError(
                            f"normalization is not supported with "
                            f"projector=RANDOM on shard "
                            f"{cc.data.feature_shard_id!r}")
                    coords[cid] = FactoredRandomEffectCoordinate(
                        dataset, cc.data.random_effect_type,
                        cc.data.feature_shard_id, self.loss, opt, self.mesh,
                        rank=cc.data.projected_dimension,
                        learn_projection=False,
                        lower_bound=cc.data.active_data_lower_bound,
                        upper_bound=cc.data.active_data_upper_bound)
                    continue
                coords[cid] = RandomEffectCoordinate(
                    dataset, cc.data.random_effect_type,
                    cc.data.feature_shard_id, self.loss, opt, self.mesh,
                    lower_bound=cc.data.active_data_lower_bound,
                    upper_bound=cc.data.active_data_upper_bound,
                    norm=self.normalization.get(cc.data.feature_shard_id,
                                                NormalizationContext()),
                    projection=cc.data.projector.upper() == "INDEX_MAP",
                    features_to_samples_ratio=(
                        cc.data.features_to_samples_ratio),
                    subspace_model=cc.data.subspace_model,
                    staging_cache_dir=self.staging_cache_dir,
                    feature_dtype=cc.data.feature_dtype,
                    staging=self.staging)
            elif isinstance(cc.data, FactoredRandomEffectDataConfiguration):
                if cc.data.feature_shard_id in self.normalization:
                    raise ValueError(
                        f"normalization is not supported on factored "
                        f"random-effect shard "
                        f"{cc.data.feature_shard_id!r} (the latent space "
                        f"has no per-feature transform)")
                coords[cid] = FactoredRandomEffectCoordinate(
                    dataset, cc.data.random_effect_type,
                    cc.data.feature_shard_id, self.loss, opt, self.mesh,
                    rank=cc.data.rank,
                    alternations=cc.data.alternations,
                    lower_bound=cc.data.active_data_lower_bound,
                    upper_bound=cc.data.active_data_upper_bound)
            else:  # pragma: no cover
                raise TypeError(type(cc.data))
        if self.streaming is not None and not streamed:
            # A streaming config that routes nothing is a silent no-op
            # pretending to be the biggest-config engine — fail loud.
            raise ValueError(
                "streaming=... was set but no coordinate routed onto the "
                "streamed path: it applies to FIXED-effect coordinates "
                "over SPARSE shards (docs/STREAMING.md)")
        return {cid: coords[cid] for cid in self.coordinate_configs}

    # -- evaluation --------------------------------------------------------

    @staticmethod
    def _stage_dataset(dataset: GameDataset) -> GameDataset:
        """Device-resident copy of a dataset for repeated scoring.

        Validation scoring runs once per coordinate-descent step; with
        host numpy shards every ``jnp.asarray`` inside the score/evaluate
        paths would re-upload the whole validation set each step. Staging
        once per fit makes those conversions no-ops — per-step validation
        then adds no host→device traffic at all.
        """
        from photon_ml_tpu.data.prefetch import stage_dataset

        return stage_dataset(dataset)

    def _evaluate(self, model: GameModel, dataset: GameDataset
                  ) -> Optional[ev.EvaluationResults]:
        if not self.validation_evaluators:
            return None
        scores = model.score(dataset)
        return ev.evaluation_suite(
            self.validation_evaluators, scores,
            dataset.response, dataset.weights,
            group_ids_by_column=dict(dataset.entity_ids),
            num_groups_by_column=dict(dataset.num_entities))

    # -- fit ---------------------------------------------------------------

    def fit(
        self,
        data: GameDataset,
        validation_data: Optional[GameDataset] = None,
        initial_models: Optional[dict] = None,
        locked_coordinates: Optional[set[str]] = None,
        checkpoint_dir: Optional[str] = None,
    ) -> list[GameResult]:
        """Train one GAME model per point of the regularization grid.

        Returns one GameResult per grid combination (cartesian product of
        each coordinate's ``reg_weight_grid``), mirroring the reference's
        Seq[GameOptimizationConfiguration] loop.

        With ``checkpoint_dir`` set, each grid point checkpoints its
        coordinate-descent progress under ``<checkpoint_dir>/grid-<i>`` and
        a rerun with the same arguments resumes mid-descent (SURVEY.md §5
        failure-recovery: the Spark-lineage replacement).

        With ``GameEstimator(trace=...)`` set, the whole fit runs under
        that tracer (an ``estimator.fit`` root span; staging, descent
        updates, streamed passes and checkpoint writes nest below it) —
        dump it afterwards with ``trace.dump(path)``.

        With ``GameEstimator(ledger_dir=...)`` set, the fit records a
        run ledger there (resume-appending when one with the same run
        identity already exists); ``GameEstimator(watchdog=...)`` arms
        the convergence watchdogs for the duration
        (docs/OBSERVABILITY.md "The run ledger").
        """
        with contextlib.ExitStack() as stack:
            if self.watchdog is not None:
                prev_wd = obs.set_watchdog(self.watchdog)
                stack.callback(obs.set_watchdog, prev_wd)
            if self.ledger_dir and obs.ledger() is None:
                import jax

                if jax.process_index() == 0:
                    # Open (or resume-append) this fit's run ledger —
                    # unless the driver already installed one, which
                    # every row then lands in (the tuning-trial case).
                    # One writer per shared filesystem: rank 0 only.
                    led = obs.RunLedger.resume(
                        self.ledger_dir, manifest=self.ledger_manifest())
                    prev_led = obs.set_ledger(led)
                    stack.callback(obs.set_ledger, prev_led)

                    # Closed via the stack even when the fit raises — a
                    # crashed fit keeps its curve prefix, stamped with
                    # how it ended.
                    def _close(exc_type, exc, tb, _led=led):
                        _led.close(status="ok" if exc_type is None
                                   else "error")
                        return False

                    stack.push(_close)
            if obs.ledger() is not None:
                # This fit's or the driver's: its ``program.load`` rows
                # come from one process-wide listener.
                obs.record_program_loads()
            if self.trace is None:
                return self._fit(data, validation_data, initial_models,
                                 locked_coordinates, checkpoint_dir)
            stack.enter_context(obs.activated(trace_obj=self.trace))
            stack.enter_context(
                obs.span("estimator.fit", cat="driver",
                         coordinates=list(self.coordinate_configs)))
            return self._fit(data, validation_data, initial_models,
                             locked_coordinates, checkpoint_dir)

    def ledger_manifest(self) -> dict:
        """Creator-side run-ledger manifest: the configuration this
        estimator can describe up front (game_train reuses it when the
        DRIVER owns the ledger). Run IDENTITY (dataset digest etc.) is
        stamped by descent.run's fingerprint machinery at the first
        update."""
        from photon_ml_tpu.obs.ledger import build_manifest

        config = {
            "task": self.task.value,
            "update_sequence": list(self.update_sequence),
            "iterations": self.descent_iterations,
            "coordinates": {
                cid: {"data": descent._jsonable(cc.data),
                      "optimization": descent._jsonable(cc.optimization),
                      "reg_weight_grid": list(cc.reg_weight_grid)}
                for cid, cc in self.coordinate_configs.items()},
            "streaming": descent._jsonable(self.streaming),
            "normalization": {
                s: descent.normalization_digest(ctx)
                for s, ctx in self.normalization.items()},
        }
        return build_manifest(
            config=config, mesh_shape=dict(self.mesh.shape))

    def _fit(
        self,
        data: GameDataset,
        validation_data: Optional[GameDataset] = None,
        initial_models: Optional[dict] = None,
        locked_coordinates: Optional[set[str]] = None,
        checkpoint_dir: Optional[str] = None,
    ) -> list[GameResult]:
        from photon_ml_tpu.game.checkpoint import CheckpointManager

        if validation_data is not None:
            # Grouped evaluators index per-entity ids against each
            # dataset's own vocabulary; scoring gathers RE rows by id. Both
            # are silently wrong if validation was read with a different
            # vocabulary than training (reference: shared PalDB index maps
            # guarantee this; here it must be asserted).
            for t, n_train in data.num_entities.items():
                n_val = validation_data.num_entities.get(t)
                if n_val is None:
                    continue
                # Provenance tokens (AvroDataReader attaches them) settle
                # alignment exactly: validation's BASE vocabulary must be
                # training's FINAL one — a true extension passes whatever
                # the sizes, an independently-built vocabulary fails even
                # at identical size (counts cannot tell those apart).
                tr_tok = data.vocab_tokens.get(t)
                va_tok = validation_data.vocab_tokens.get(t)
                if tr_tok is not None and va_tok is not None:
                    # Aligned iff validation's vocabulary IS training's
                    # final one (content-identical — e.g. a subset() split)
                    # or extends it (base == training's final).
                    if tr_tok[1] not in va_tok:
                        raise ValueError(
                            f"validation entity vocabulary for {t!r} was "
                            f"not derived from the training vocabulary "
                            f"(provenance mismatch): entity ids would "
                            f"silently misalign. Read validation with the "
                            f"training vocabularies (AvroDataReader "
                            f"entity_vocabs=meta.entity_vocabs, "
                            f"allow_unseen_entities=True)")
                    continue
                # No tokens (hand-built datasets): fall back to counts.
                # An EXTENSION of the training vocabulary is legal
                # (allow_unseen_entities: unseen ids get rows past the
                # frozen range and score with zero RE contribution); a
                # smaller/reshuffled vocabulary is silent id misalignment.
                if n_val < n_train:
                    raise ValueError(
                        f"validation entity vocabulary for {t!r} has size "
                        f"{n_val} < training {n_train}; read validation "
                        f"with the training vocabularies "
                        f"(AvroDataReader entity_vocabs=...)")
                if n_val > n_train:
                    # Counts cannot distinguish a true extension from an
                    # unrelated larger vocabulary — make the assumption
                    # loud so an independently-built validation set is
                    # noticed (ids 0..n_train-1 MUST mean the same
                    # entities in both datasets).
                    logger.warning(
                        "validation %s vocabulary (%d) extends training "
                        "(%d): assuming shared ids for the first %d "
                        "entities — unseen ones score with zero "
                        "random-effect contribution. Read validation with "
                        "the training vocabularies "
                        "(allow_unseen_entities=True) to guarantee this.",
                        t, n_val, n_train, n_train)

        if validation_data is not None and self.validation_evaluators:
            # Without evaluators validation_data is only consulted for the
            # vocabulary checks above — don't hold it in device memory.
            validation_data = self._stage_dataset(validation_data)

        cids = list(self.coordinate_configs)
        grids = [self.coordinate_configs[c].expand_grid() for c in cids]
        results: list[GameResult] = []
        base_coords: Optional[dict[str, object]] = None
        for grid_index, combo in enumerate(itertools.product(*grids)):
            opt_configs = dict(zip(cids, combo))
            if base_coords is None:
                # Coordinates (bucketing, device staging) are built ONCE;
                # later grid points — and later fit() calls on the same
                # dataset, e.g. tuning trials — swap only the optimization
                # config (reference: datasets built once, configs looped).
                # Key everything that shapes coordinate construction: the
                # dataset CONTENT (descent._dataset_digest — so a fresh
                # dataset object with identical content hits the cache,
                # and a same-id object rebuilt with different content
                # cannot poison it), per-coordinate data configs, the task
                # (picks the loss), and the normalization array contents.
                # The digest is memoized on the dataset object, so arrays
                # mutated IN PLACE on a previously-fitted dataset are
                # still not detected — datasets remain immutable by
                # contract once fitted.
                cache_key = (
                    descent._dataset_digest(data),
                    # Metadata the array digest cannot see but that shapes
                    # construction: entity-table sizes (bucketing, model
                    # row counts) and intercept columns (reg masks).
                    tuple(sorted(data.num_entities.items())),
                    tuple(sorted(data.intercept_index.items())),
                    self.task,
                    tuple(sorted(
                        (s, descent.normalization_digest(ctx))
                        for s, ctx in self.normalization.items())),
                    tuple((cid, self.coordinate_configs[cid].data)
                          for cid in cids),
                    # Streaming reshapes coordinate construction (chunked
                    # staging vs device-resident) without touching the
                    # data configs above.
                    self.streaming)
                cached = self._coord_cache.get("last")
                if cached is not None and cached[0] == cache_key:
                    base_coords = {
                        cid: cached[1][cid]
                        .with_optimization_config(opt_configs[cid])
                        for cid in cids}
                else:
                    with obs.phase("fit.coordinates"):
                        base_coords = self._build_coordinates(data,
                                                              opt_configs)
                self._coord_cache["last"] = (cache_key, base_coords)
                coords = base_coords
            else:
                coords = {cid: base_coords[cid].with_optimization_config(
                    opt_configs[cid]) for cid in cids}
            val_fn = None
            if validation_data is not None and self.validation_evaluators:
                def val_fn(m, _vd=validation_data):
                    return self._evaluate(m, _vd).metrics
            manager = (CheckpointManager(
                os.path.join(checkpoint_dir, f"grid-{grid_index}"))
                if checkpoint_dir else None)
            led = obs.ledger()
            bound = (led.bound(grid=grid_index) if led is not None
                     else contextlib.nullcontext())
            with bound:
                # pml: allow[PML012] grid-search outer loop: each call is an ENTIRE coordinate-descent fit; its per-update materialization (validation, checkpoint) amortizes over minutes of device work
                model, history = descent.run(
                    self.task, coords,
                    descent.CoordinateDescentConfig(
                        self.update_sequence, self.descent_iterations),
                    initial_models=initial_models,
                    locked_coordinates=locked_coordinates,
                    validation_fn=val_fn,
                    checkpoint_manager=manager)
            model = self._finalize_variances(model, coords, data)
            evaluation = (self._evaluate(model, validation_data)
                          if validation_data is not None else None)
            logger.info("GAME fit done for %s: %s",
                        {c: o.regularization.reg_weight
                         for c, o in opt_configs.items()},
                        evaluation.metrics if evaluation else "")
            results.append(GameResult(model=model, evaluation=evaluation,
                                      configs=opt_configs))
        return results

    def _finalize_variances(self, model: GameModel, coords, data: GameDataset
                            ) -> GameModel:
        """Compute per-coordinate coefficient variances at the optimum
        (reference: variance computation happens once after training)."""
        if not self.compute_variances_at_end:
            return model
        any_requested = any(
            VarianceComputationType(c.optimization.variance_computation)
            != VarianceComputationType.NONE
            for c in self.coordinate_configs.values())
        if not any_requested:
            return model
        scores = {cid: coords[cid].score(m)
                  for cid, m in model.models.items()}
        total = jnp.asarray(data.offsets) + sum(scores.values())
        models = dict(model.models)
        for cid, m in model.models.items():
            offsets = total - scores[cid]
            models[cid] = coords[cid].compute_model_variances(m, offsets)
        return dataclasses.replace(model, models=models)

    def select_best_model(self, results: list[GameResult]) -> GameResult:
        """Pick by the primary validation evaluator (reference:
        GameEstimator/driver best-model selection)."""
        best = None
        for r in results:
            if best is None:
                best = r
            elif (r.evaluation is not None
                  and r.evaluation.better_than(best.evaluation)):
                best = r
        if best is None:
            raise ValueError("no results to select from")
        return best
