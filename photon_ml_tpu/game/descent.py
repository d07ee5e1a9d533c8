"""Block coordinate descent: the GAME training loop.

Reference parity: photon-api ``algorithm/CoordinateDescent.scala`` — for
each iteration, for each coordinate in the update sequence: subtract the
coordinate's current scores from the residual, train it against the
remaining offsets, add its new scores back; track per-iteration validation
metrics; support locked (pretrained, partial-retraining) coordinates.

TPU-first notes: coordinates are trained SEQUENTIALLY by design (the block
residual dependency — SURVEY.md §2.5 P4: no pipeline parallelism exists in
this workload); the parallelism is inside each coordinate (data-parallel
psum for fixed effects, vmapped entity blocks for random effects). Score
bookkeeping is elementwise adds on stable-order (n,) device arrays instead
of the reference's outer-join RDD arithmetic (CoordinateDataScores +/-).
"""

from __future__ import annotations

import contextlib
import dataclasses
import enum
import hashlib
import logging
import threading
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu import obs
from photon_ml_tpu.game.models import CoordinateModel, GameModel
from photon_ml_tpu.types import TaskType
from photon_ml_tpu.utils import events as ev_mod

logger = logging.getLogger("photon_ml_tpu.game")


@dataclasses.dataclass
class CoordinateDescentConfig:
    """Update sequence + outer iterations (reference: GameTrainingDriver
    params ``coordinateUpdateSequence`` / ``coordinateDescentIterations``)."""

    update_sequence: list[str]
    iterations: int = 1
    # Per-update dispatch-stream barrier: None = auto (estimate the
    # enqueue-held scratch in bytes and sync when it could plausibly
    # exhaust HBM), True/False = force. See the gate in run().
    sync_updates: Optional[bool] = None


@dataclasses.dataclass
class CoordinateDescentHistory:
    """Per-(iteration, coordinate) timing and validation records."""

    records: list[dict] = dataclasses.field(default_factory=list)


def run(
    task: TaskType,
    coordinates: dict[str, object],
    config: CoordinateDescentConfig,
    *,
    initial_models: Optional[dict[str, CoordinateModel]] = None,
    locked_coordinates: Optional[set[str]] = None,
    validation_fn: Optional[Callable[[GameModel], dict]] = None,
    checkpoint_manager=None,
) -> tuple[GameModel, CoordinateDescentHistory]:
    """Run block coordinate descent (reference: CoordinateDescent.run).

    ``coordinates`` maps coordinate id → Fixed/RandomEffectCoordinate (all
    sharing one GameDataset's example order). ``locked_coordinates`` are
    scored but never retrained (reference partial retraining).
    ``validation_fn`` is called after each coordinate update with the
    current GameModel (reference: per-iteration EvaluationSuite logging).

    ``checkpoint_manager`` (game/checkpoint.py) persists models + progress
    after every coordinate update and, when an existing checkpoint is found
    under its directory, resumes from it: already-completed (iteration,
    coordinate) updates are skipped and the checkpointed models replace the
    warm starts. Restart state is models + a linear step counter + the
    (n,) residual score total. Restoring the saved total (instead of
    re-summing per-coordinate scores, which changes the f32 accumulation
    order) makes a resumed run BIT-exact with an uninterrupted one; the
    restored total is validated against the re-summed one and discarded if
    they disagree beyond accumulation noise (a kill between the model and
    residual writes can leave a newer model directory with older
    residuals — re-summation is always consistent with the model files).
    """
    seq = list(config.update_sequence)
    unknown = [c for c in seq if c not in coordinates]
    if unknown:
        raise ValueError(f"update sequence references unknown coordinates "
                         f"{unknown}")
    locked = set(locked_coordinates or ())
    for c in locked:
        if initial_models is None or c not in initial_models:
            raise ValueError(f"locked coordinate {c!r} needs an initial model")

    some = coordinates[seq[0]]
    n = some.dataset.num_rows

    # Set-up before the first update: identity, resume, the initial
    # models and their scores (programs loaded and run in it)
    with obs.phase("descent.init"):
        led = obs.ledger()
        fingerprint = None
        resume = None
        if checkpoint_manager is not None or led is not None:
            fingerprint = _fingerprint(task, coordinates, seq, config,
                                       locked, n)
        if led is not None:
            # Stamp (or validate, on a --resume append) the run ledger's
            # identity from the SAME fingerprint machinery the checkpoint
            # trusts — a ledger never silently continues a different run's
            # curve (obs/ledger.py).
            led.bind_fingerprint(fingerprint)
        if checkpoint_manager is not None:
            resume = checkpoint_manager.load(expected_fingerprint=fingerprint)
        history = CoordinateDescentHistory()
        done_steps = 0
        if resume is not None:
            initial_models = {**(initial_models or {}), **resume.models}
            done_steps = resume.done_steps
            history.records = list(resume.records)
            logger.info("resuming coordinate descent from checkpoint: "
                        "%d updates already done", done_steps)
            if resume.complete:
                return (GameModel(task=task, models=dict(resume.models)),
                        history)
            # Fast-forward per-coordinate down-sampling RNGs past the completed
            # train calls so the remaining steps draw the SAME subsamples as an
            # uninterrupted run would have.
            completed: dict[str, int] = {}
            for rec in resume.records:
                completed[rec["coordinate"]] = \
                    completed.get(rec["coordinate"], 0) + 1
            for cid, k in completed.items():
                advance = getattr(coordinates.get(cid),
                                  "advance_down_sampling", None)
                if advance is not None:
                    advance(k)

        models: dict[str, CoordinateModel] = {}
        scores: dict[str, jnp.ndarray] = {}
        base = jnp.asarray(some.dataset.offsets)
        total = jnp.zeros((n,), jnp.float32)

        # At scale, synchronize the dispatch stream once per coordinate
        # update. JAX enqueues every fit/score program ahead of execution, and
        # the runtime holds each queued program's output and scratch buffers
        # from ENQUEUE time — a full un-synced descent sweep at 19M rows
        # reproducibly exhausts HBM even though the same programs run fine
        # back-to-back with a barrier between them (and the resident arrays
        # total only a few GB). The barrier costs one host-device round trip
        # per coordinate update, so it is gated on an ESTIMATE of the scratch a
        # fully un-synced descent would hold: per queued update, O(n) score
        # outputs plus working buffers scaling with the coordinate's feature
        # dim (capped — sparse/tiled formulations never materialize n×d), for
        # every update the whole descent enqueues. Small configs keep full
        # dispatch pipelining; config.sync_updates forces either way.
        if config.sync_updates is not None:
            sync_updates = bool(config.sync_updates)
        else:
            # The byte estimate only ever ADDS protection beyond the empirical
            # n >= 4.2M row floor (where the 19M OOM was reproduced): the
            # estimate undercounts RE training scratch, so it must not be able
            # to turn the barrier OFF in the regime the floor covers.
            est_bytes = 0
            for cid in seq:
                dim = int(getattr(coordinates[cid], "dim", 8) or 8)
                est_bytes += n * 4 * (2 + min(dim, 4096))
            est_bytes *= max(1, config.iterations)
            sync_updates = n >= (1 << 22) or est_bytes >= (1 << 30)

        def _sync(x):
            if sync_updates:
                jax.block_until_ready(x)

        # Initialize models (warm starts / checkpoint state) and their scores.
        for cid in seq:
            coord = coordinates[cid]
            if initial_models and cid in initial_models:
                # Cross-type warm starts (full-rank ↔ factored random
                # effects) convert here so scoring and training see the
                # coordinate's own model type.
                adapt = getattr(coord, "adapt_initial", None)
                models[cid] = (adapt(initial_models[cid]) if adapt
                               else initial_models[cid])
            else:
                models[cid] = coord.initial_model()
            s = coord.score(models[cid])
            scores[cid] = s
            total = total + s
            _sync(total)

        if resume is not None and resume.residual_total is not None:
            restored = np.asarray(resume.residual_total)
            # Benign mismatch vs the fresh sum is f32 accumulation-order noise
            # (~1e-6); a kill between the model-dir and residual writes leaves
            # a step-sized gap instead. Restore only in the former case — the
            # fresh sum is always consistent with the model files.
            if restored.shape == total.shape and np.allclose(
                    np.asarray(total), restored, rtol=1e-5, atol=1e-5):
                total = jnp.asarray(restored)
            else:
                logger.warning(
                    "checkpoint residuals disagree with re-summed scores; "
                    "using the re-summed total (resume stays correct but is "
                    "no longer bit-exact)")

    emitter = ev_mod.default_emitter
    emitter.emit(ev_mod.TrainingStart(
        task=TaskType(task).value, update_sequence=tuple(seq),
        iterations=config.iterations))

    step = 0
    try:
        for it in range(config.iterations):
            for cid in seq:
                if cid in locked:
                    continue
                step += 1
                if step <= done_steps:
                    continue  # already covered by the checkpoint
                coord = coordinates[cid]
                t0 = time.monotonic()
                start = time.perf_counter()
                # Ledger context: every telemetry row the update's
                # optimizer produces (live opt_iter rows, compiled
                # spills, RE waves) carries which coordinate/step it
                # belongs to.
                bound = (led.bound(coordinate=cid, outer_iteration=it,
                                   step=step)
                         if led is not None
                         else contextlib.nullcontext())
                # One span per coordinate update — the descent
                # waterfall's unit; the coordinate's own spans (streamed
                # passes, fit waves, checkpoint writes) nest under it.
                with bound, obs.annotated("descent.update", cat="train",
                                          iteration=it, coordinate=cid,
                                          step=step):
                    if checkpoint_manager is not None:
                        # Streamed coordinates checkpoint INSIDE the
                        # update too (their fit is the multi-hour unit at
                        # flagship scale): bind this step's stream-state
                        # directory so a kill mid-L-BFGS resumes
                        # mid-optimization.
                        bind = getattr(coord, "bind_step_checkpoint",
                                       None)
                        if bind is not None:
                            bind(checkpoint_manager.stream_dir(step),
                                 step)
                    # Residual offsets: everything except this
                    # coordinate.
                    offsets = base + total - scores[cid]
                    model = coord.train_model(offsets,
                                              initial=models[cid])
                    new_scores = coord.score(model)
                    total = total + new_scores - scores[cid]
                    scores[cid] = new_scores
                    models[cid] = model
                    _sync(total)
                    if led is not None:
                        # What the coordinate counted inside its programs
                        # (fit-wave solver counters) is read here, once,
                        # behind the barrier: never between dispatches.
                        with obs.annotated("ledger.drain", cat="train"):
                            led.drain()
                elapsed = time.monotonic() - t0
                rec = {"iteration": it, "coordinate": cid,
                       "train_seconds": elapsed}
                if validation_fn is not None:
                    rec["validation"] = validation_fn(
                        GameModel(task=task, models=dict(models)))
                logger.info("CD iter %d coordinate %s: %.2fs %s", it, cid,
                            elapsed, rec.get("validation", ""))
                history.records.append(rec)
                emitter.emit(ev_mod.CoordinateUpdate(
                    iteration=it, coordinate=cid, train_seconds=elapsed,
                    validation=rec.get("validation")))
                if led is not None:
                    led.record("coordinate_update", coordinate=cid,
                               outer_iteration=it, step=step,
                               seconds=round(elapsed, 6),
                               validation=rec.get("validation"),
                               t0=led.clock(start),
                               thread=threading.current_thread().name)
                if checkpoint_manager is not None:
                    checkpoint_manager.save(
                        task, models, done_steps=step,
                        records=history.records, fingerprint=fingerprint,
                        # pml: allow[PML001] checkpoint persistence NEEDS the
                        # host copy, once per coordinate update (seconds of
                        # device work), and _sync already drained the stream
                        updated=[cid], residual_total=np.asarray(total))
                    # The step committed: its mid-step stream state is
                    # stale (a later resume starts AFTER this step).
                    clear = getattr(coord, "clear_step_checkpoint", None)
                    if clear is not None:
                        clear()
    finally:
        # Balanced lifecycle (PML007): a raise mid-descent must still
        # close the training scope for listeners tracking it.
        emitter.emit(ev_mod.TrainingFinish(task=TaskType(task).value,
                                           total_updates=step))
    if checkpoint_manager is not None:
        checkpoint_manager.save(task, models, done_steps=step,
                                records=history.records, complete=True,
                                fingerprint=fingerprint,
                                residual_total=np.asarray(total))
    return GameModel(task=task, models=models), history


def _dataset_digest(ds) -> str:
    """Content digest of a GameDataset (responses, offsets, weights,
    feature shards, entity assignments) — anything that changes the
    training objectives. Memoized on the dataset object: at Criteo scale
    this is a full pass over tens of GB, and a reg-weight grid would
    otherwise repeat it once per grid point. (Datasets are treated as
    immutable throughout — see the estimator's coordinate-cache contract.)
    """
    cached = getattr(ds, "_content_digest", None)
    if cached is not None:
        return cached
    h = hashlib.sha1()

    def _feed(arr):
        _feed_array(h, arr)

    with obs.phase("fit.digest"):
        for arr in (ds.response, ds.offsets, ds.weights):
            _feed(arr)
        for sid in sorted(ds.feature_shards):
            shard = ds.feature_shards[sid]
            if hasattr(shard, "indices"):  # SparseShard
                _feed(shard.indices)
                _feed(shard.values)
            else:
                _feed(shard)
        for re_type in sorted(ds.entity_ids):
            _feed(ds.entity_ids[re_type])
    digest = h.hexdigest()
    try:
        ds._content_digest = digest
    except (AttributeError, TypeError):
        pass  # frozen/slotted datasets: just recompute next time
    return digest


def _feed_array(h, arr) -> None:
    """The ONE array-content hashing convention (None gets a marker so
    (None, x) never collides with (x, None)) — shared by the dataset
    digest, the checkpoint fingerprint, and normalization_digest."""
    if arr is None:
        h.update(b"\x00none")
    else:
        h.update(np.ascontiguousarray(np.asarray(arr)).tobytes())


def normalization_digest(ctx) -> str:
    """Content digest of a NormalizationContext — pairs with
    ``_dataset_digest`` as the estimator's coordinate-cache key."""
    h = hashlib.sha1()
    _feed_array(h, ctx.factors)
    _feed_array(h, ctx.shifts)
    h.update(repr(ctx.intercept_index).encode())
    return h.hexdigest()


def _jsonable(obj):
    """Dataclass/enum tree → plain JSON-comparable values."""
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return {f.name: _jsonable(getattr(obj, f.name))
                for f in dataclasses.fields(obj)}
    if isinstance(obj, enum.Enum):
        return obj.value
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    return obj


def _fingerprint(task, coordinates, seq, config, locked, n) -> dict:
    """What a checkpoint must agree on to be resumable: anything that
    changes the sequence of training steps or their objectives — the FULL
    per-coordinate optimization config (tolerance, elastic-net alpha, …),
    the loop shape, and a digest of the training responses/offsets/weights
    (num_rows alone cannot tell two datasets apart)."""
    per_coord = {}
    for cid in seq:
        c = getattr(coordinates[cid], "config", None)
        per_coord[cid] = {
            "config": _jsonable(c) if c is not None else None,
            "down_sampling_seed": getattr(
                coordinates[cid], "_down_sampling_seed", None),
        }
    ds = coordinates[seq[0]].dataset
    h = hashlib.sha1()
    h.update(_dataset_digest(ds).encode())
    for cid in seq:
        norm = getattr(coordinates[cid], "norm", None)
        if norm is not None:
            _feed_array(h, getattr(norm, "factors", None))
            _feed_array(h, getattr(norm, "shifts", None))
    return {
        "task": TaskType(task).value,
        "sequence": list(seq),
        "iterations": int(config.iterations),
        "locked": sorted(locked),
        "num_rows": int(n),
        "data_digest": h.hexdigest(),
        "coordinates": per_coord,
    }
