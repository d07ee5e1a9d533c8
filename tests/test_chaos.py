"""Chaos suite: deterministic fault injection against the hardened
layers (photon_ml_tpu/faults + docs/ROBUSTNESS.md).

The contract under test, for EVERY fault class (worker crash, straggler,
corrupt cache shard, corrupt checkpoint artifact, transient I/O,
scoring-thread death, queue overload):

    recover with results BIT-IDENTICAL to the unfaulted run,
    or degrade fast with a DEFINED error + an incremented metric —
    never hang, never silently return wrong results.

Every fault is seeded and addressed by (site, occurrence/index), so a
failing test replays exactly.
"""

import json
import logging
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from photon_ml_tpu import faults
from photon_ml_tpu.data.game_data import GameDataset, SparseShard
from photon_ml_tpu.game import buckets as bkt
from photon_ml_tpu.game import staging as stg
from photon_ml_tpu.game import staging_cache
from photon_ml_tpu.game.checkpoint import CheckpointManager
from photon_ml_tpu.utils import events as ev

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))


@pytest.fixture(autouse=True)
def _no_leftover_plan():
    """A chaos test must never leak its plan into the next test."""
    yield
    faults.install(None)


# ---------------------------------------------------------------- injector


def test_fault_plan_addressing_and_determinism(tmp_path):
    plan = faults.FaultPlan(specs=(
        faults.FaultSpec(site="s", occurrences=(1, 3)),
        faults.FaultSpec(site="t", indices=(7,), max_fires=1),
    ), seed=5)
    inj = faults.FaultInjector(plan)
    inj.fire("s")  # occurrence 0: no fault
    with pytest.raises(faults.InjectedFault):
        inj.fire("s")  # occurrence 1: fires
    inj.fire("s")
    with pytest.raises(faults.InjectedFault):
        inj.fire("s")  # occurrence 3: fires
    inj.fire("t", index=3)  # wrong index: no fault
    with pytest.raises(faults.InjectedFault):
        inj.fire("t", index=7)
    inj.fire("t", index=7)  # max_fires=1 spent
    assert inj.fires("s") == 2 and inj.fires("t") == 1

    # JSON round trip (the game_train --fault-plan surface).
    restored = faults.FaultPlan.from_json(plan.to_json())
    assert restored == plan

    # Deterministic corruption: same plan, same site → same bytes.
    blobs = []
    for run in range(2):
        p = tmp_path / f"f{run}"
        p.write_bytes(b"\x00" * 256)
        inj = faults.FaultInjector(faults.FaultPlan(
            specs=(faults.FaultSpec(site="c", kind="corrupt"),), seed=9))
        assert inj.corrupt_file("c", str(p))
        blobs.append(p.read_bytes())
    assert blobs[0] == blobs[1] and blobs[0] != b"\x00" * 256


def test_inactive_injector_is_a_noop():
    assert faults.active() is None
    faults.fire("anything", index=3)  # must not raise


# ------------------------------------------------------- staging fixtures


def _skewed_dataset(n_entities=24, d=32, nnz=3, seed=0):
    """Small skewed GAME dataset → several capacity buckets, each wide
    enough to split into multiple 8-lane staging shards."""
    rng = np.random.default_rng(seed)
    counts = rng.integers(2, 21, n_entities)
    ids = np.repeat(np.arange(n_entities, dtype=np.int32), counts)
    rng.shuffle(ids)
    n = ids.shape[0]
    idx = np.sort(rng.integers(0, d - 1, (n, nnz)).astype(np.int32), axis=1)
    dup = np.zeros_like(idx, bool)
    dup[:, 1:] = idx[:, 1:] == idx[:, :-1]
    vals = rng.normal(size=(n, nnz)).astype(np.float32)
    idx[dup] = d
    vals[dup] = 0.0
    idx = np.concatenate([idx, np.full((n, 1), d - 1, np.int32)], axis=1)
    vals = np.concatenate([vals, np.ones((n, 1), np.float32)], axis=1)
    return GameDataset(
        response=rng.integers(0, 2, n).astype(np.float32),
        offsets=np.zeros(n, np.float32),
        weights=np.ones(n, np.float32),
        feature_shards={"re": SparseShard(idx, vals, d)},
        entity_ids={"userId": ids}, num_entities={"userId": n_entities},
        intercept_index={"re": d - 1})


def _stager(ds, config, cache_dir=None, cache_key=None, emitter=None):
    bucketing = bkt.build_bucketing(np.asarray(ds.entity_ids["userId"]),
                                    ds.num_entities["userId"])
    return stg.ProjectionStager(
        bucketing=bucketing, X=ds.feature_shards["re"],
        response=np.asarray(ds.response),
        weights=np.asarray(ds.weights),
        intercept_index=ds.intercept_index.get("re"),
        config=config, cache_dir=cache_dir, cache_key=cache_key,
        label="userId:re", emitter=emitter or ev.EventEmitter())


def _drain(stager):
    got = list(stager.shards())
    stager.join()
    return got


def _assert_bytes_equal(got, want):
    assert len(got) == len(want)
    for tg, tw in zip(got, want):
        assert len(tg) == len(tw)
        for ag, aw in zip(tg, tw):
            ag, aw = np.asarray(ag), np.asarray(aw)
            assert ag.dtype == aw.dtype and ag.shape == aw.shape
            assert ag.tobytes() == aw.tobytes()


def _unfaulted_shards(ds, **cfg_kw):
    return _drain(_stager(ds, stg.StagingConfig(**cfg_kw)))


# --------------------------------------------- staging: crash fault class


def test_staging_worker_crash_retries_bit_identical():
    """A crashed shard task (thread mode) walks the bounded-retry rung
    and the recovered output is byte-identical to the unfaulted run."""
    ds = _skewed_dataset(seed=1)
    want = _unfaulted_shards(ds, workers=2, shard_entities=8)
    plan = faults.FaultPlan(specs=(
        faults.FaultSpec(site="staging.phase_b", indices=(1,),
                         max_fires=1),
        faults.FaultSpec(site="staging.phase_a", indices=(0,),
                         max_fires=1, exc="InjectedIOError"),
    ))
    emitter = ev.EventEmitter()
    seen = []
    emitter.register(seen.append)
    with faults.installed(plan) as inj:
        stager = _stager(ds, stg.StagingConfig(
            workers=2, shard_entities=8, retry_backoff_s=0.01),
            emitter=emitter)
        got = _drain(stager)
    assert inj.fires() == 2
    assert stager.fault_stats["retries"] == 2
    retries = [e for e in seen if isinstance(e, ev.StagingRetry)]
    assert {e.index for e in retries} == {0, 1}
    _assert_bytes_equal(got, want)


def test_staging_retries_exhausted_fails_with_defined_error():
    """A deterministically-failing shard exhausts its budget and fails
    FAST with the real error on that shard's future — no hang."""
    ds = _skewed_dataset(seed=2)
    plan = faults.FaultPlan(specs=(
        faults.FaultSpec(site="staging.phase_b", indices=(0,)),))
    with faults.installed(plan):
        # Depth > shard count: the consumer exits on the failure, so the
        # depth bound must not gate the remaining (successful) shards.
        stager = _stager(ds, stg.StagingConfig(
            workers=2, shard_entities=8, max_retries=1,
            retry_backoff_s=0.01, pipeline_depth=64))
        t0 = time.monotonic()
        with pytest.raises(faults.InjectedFault):
            list(stager.shards())
        assert time.monotonic() - t0 < 30.0
        stager.join()
    assert stager.fault_stats["retries"] == 1


def test_staging_process_worker_sigkill_quarantine_serial_restage():
    """THE Snap-ML executor-loss scenario: a process-pool worker is
    SIGKILLed mid-task; the broken pool is quarantined and every
    remaining shard re-stages serially, byte-identical."""
    ds = _skewed_dataset(seed=3)
    want = _unfaulted_shards(ds, workers=2, shard_entities=8)
    plan = faults.FaultPlan(specs=(
        faults.FaultSpec(site="staging.phase_b", kind="kill",
                         occurrences=(0,), scope="worker"),))
    with faults.installed(plan):
        stager = _stager(ds, stg.StagingConfig(
            workers=2, mode="process", shard_entities=8,
            retry_backoff_s=0.01))
        got = _drain(stager)
    assert stager.fault_stats["quarantined"]
    assert stager.fault_stats["serial_restages"] >= 1
    _assert_bytes_equal(got, want)


def test_staging_straggler_deadline_degrades_not_stalls():
    """A shard that sleeps past the straggler deadline is re-staged
    serially; the consumer finishes LONG before the sleeper wakes, the
    late result is discarded, and the bytes are identical."""
    ds = _skewed_dataset(seed=4)
    want = _unfaulted_shards(ds, workers=2, shard_entities=8)
    sleep_s = 4.0
    plan = faults.FaultPlan(specs=(
        faults.FaultSpec(site="staging.phase_b", kind="sleep",
                         seconds=sleep_s, indices=(0,), max_fires=1),))
    emitter = ev.EventEmitter()
    seen = []
    emitter.register(seen.append)
    t0 = time.monotonic()
    with faults.installed(plan):
        stager = _stager(ds, stg.StagingConfig(
            workers=2, shard_entities=8, straggler_timeout_s=0.2),
            emitter=emitter)
        got = _drain(stager)
    assert time.monotonic() - t0 < sleep_s - 0.5  # didn't wait it out
    assert stager.fault_stats["stragglers"] == 1
    stragglers = [e for e in seen if isinstance(e, ev.StagingStraggler)]
    assert len(stragglers) == 1 and stragglers[0].index == 0
    _assert_bytes_equal(got, want)


# ------------------------------------------- staging cache: corrupt + I/O


def test_corrupt_cache_shard_detected_by_crc_and_restaged(tmp_path):
    """Injected bit rot in one cached shard file (valid npy header, wrong
    bytes) is caught by the commit marker's CRC; exactly that shard
    restages and the merged output is byte-identical."""
    ds = _skewed_dataset(seed=5)
    cache = str(tmp_path / "stage")
    cfg = stg.StagingConfig(workers=2, shard_entities=8)
    plan = faults.FaultPlan(specs=(
        faults.FaultSpec(site="staging_cache.shard_file", kind="corrupt",
                         indices=(1,), max_fires=1),), seed=11)
    with faults.installed(plan) as inj:
        cold = _drain(_stager(ds, cfg, cache_dir=cache, cache_key="k"))
    assert inj.fires() == 1
    # The corrupted shard still has its .ok marker yet must not load.
    assert staging_cache.load_shard(cache, "k", 1) is None
    assert staging_cache.load_shard(cache, "k", 0) is not None
    emitter = ev.EventEmitter()
    seen = []
    emitter.register(seen.append)
    warm = _stager(ds, cfg, cache_dir=cache, cache_key="k",
                   emitter=emitter)
    got = _drain(warm)
    staged = [e for e in seen if isinstance(e, ev.StagingShard)
              and e.source == "staged"]
    assert [e.index for e in staged] == [1]  # partial credit preserved
    _assert_bytes_equal(got, cold)


def test_transient_cache_load_error_degrades_to_miss(tmp_path):
    """A transient I/O error while probing the cache is a per-shard miss
    (restage), never a crash."""
    ds = _skewed_dataset(seed=6)
    cache = str(tmp_path / "stage")
    cfg = stg.StagingConfig(workers=2, shard_entities=8)
    cold = _drain(_stager(ds, cfg, cache_dir=cache, cache_key="k"))
    plan = faults.FaultPlan(specs=(
        faults.FaultSpec(site="staging_cache.load_shard",
                         exc="InjectedIOError", occurrences=(0,),
                         max_fires=1),))
    with faults.installed(plan):
        got = _drain(_stager(ds, cfg, cache_dir=cache, cache_key="k"))
    _assert_bytes_equal(got, cold)


# ----------------------------------------------------- checkpoint faults


def _tiny_models(rng, d_global=5, d_re=3, entities=6):
    import jax.numpy as jnp

    from photon_ml_tpu.game.models import FixedEffectModel, RandomEffectModel
    from photon_ml_tpu.models.coefficients import Coefficients

    return {
        "fixed": FixedEffectModel("global", Coefficients(
            jnp.asarray(rng.normal(size=d_global).astype(np.float32)))),
        "per-user": RandomEffectModel(
            "userId", "re_userId",
            jnp.asarray(rng.normal(size=(entities, d_re)
                                   ).astype(np.float32))),
    }


def _save_two_generations(mgr, task, models_g1, models_g2):
    mgr.save(task, models_g1, done_steps=1, records=[{"s": 1}],
             fingerprint={"f": 1},
             residual_total=np.arange(4, dtype=np.float32))
    mgr.save(task, models_g2, done_steps=2, records=[{"s": 1}, {"s": 2}],
             fingerprint={"f": 1}, updated=["per-user"],
             residual_total=np.arange(4, dtype=np.float32) + 1)


def _flip_bytes(path, off=64, n=16):
    with open(path, "r+b") as f:
        f.seek(off)
        blob = f.read(n)
        f.seek(off)
        f.write(bytes(b ^ 0xFF for b in blob))


def test_corrupt_checkpoint_artifact_recovers_prev_generation(rng, tmp_path):
    """Bit rot in the newest generation's coefficients fails its CRC;
    load falls back to generation N-1, emits CheckpointRecovered, and the
    recovered residuals are generation N-1's (bit-exact resume basis)."""
    from photon_ml_tpu.types import TaskType

    task = TaskType.LOGISTIC_REGRESSION
    mgr = CheckpointManager(str(tmp_path))
    g1, g2 = _tiny_models(rng), _tiny_models(rng)
    _save_two_generations(mgr, task, g1, g2)
    victim = os.path.join(
        str(tmp_path), "model", "random-effect", "per-user",
        "coefficients.npz")
    _flip_bytes(victim)
    seen = []
    ev.default_emitter.register(seen.append)
    try:
        state = CheckpointManager(str(tmp_path)).load(
            expected_fingerprint={"f": 1})
    finally:
        ev.default_emitter.unregister(seen.append)
    assert state is not None and state.recovered
    assert state.done_steps == 1  # generation N-1
    recovered = [e for e in seen if isinstance(e, ev.CheckpointRecovered)]
    assert len(recovered) == 1 and recovered[0].done_steps == 1
    assert "per-user" in recovered[0].reason
    # The restored table is generation 1's, byte for byte.
    np.testing.assert_array_equal(
        np.asarray(state.models["per-user"].means),
        np.asarray(g1["per-user"].means))
    np.testing.assert_array_equal(state.residual_total,
                                  np.arange(4, dtype=np.float32))


def test_corrupt_state_json_recovers_prev_generation(rng, tmp_path):
    from photon_ml_tpu.types import TaskType

    task = TaskType.LOGISTIC_REGRESSION
    mgr = CheckpointManager(str(tmp_path))
    _save_two_generations(mgr, task, _tiny_models(rng), _tiny_models(rng))
    with open(os.path.join(str(tmp_path), "state.json"), "w") as f:
        f.write("{ not json")
    state = CheckpointManager(str(tmp_path)).load()
    assert state is not None and state.recovered
    assert state.done_steps == 1


def test_both_generations_corrupt_trains_from_scratch(rng, tmp_path,
                                                      caplog):
    """Corruption beyond recovery DEGRADES (None → fresh training) with
    a loud log — never an exception, never silently wrong state."""
    from photon_ml_tpu.types import TaskType

    task = TaskType.LOGISTIC_REGRESSION
    mgr = CheckpointManager(str(tmp_path))
    _save_two_generations(mgr, task, _tiny_models(rng), _tiny_models(rng))
    victim = os.path.join(str(tmp_path), "model", "random-effect",
                          "per-user", "coefficients.npz")
    _flip_bytes(victim)
    _flip_bytes(victim + ".prev")
    with caplog.at_level(logging.ERROR, logger="photon_ml_tpu.game"):
        state = CheckpointManager(str(tmp_path)).load()
    assert state is None
    assert any("training from scratch" in r.message for r in caplog.records)


def test_injected_checkpoint_corruption_detected(rng, tmp_path):
    """The injector's corrupt fault at the checkpoint.artifact site is
    caught on load exactly like real bit rot."""
    from photon_ml_tpu.types import TaskType

    task = TaskType.LOGISTIC_REGRESSION
    mgr = CheckpointManager(str(tmp_path))
    g1 = _tiny_models(rng)
    mgr.save(task, g1, done_steps=1, records=[], fingerprint=None)
    plan = faults.FaultPlan(specs=(
        faults.FaultSpec(site="checkpoint.artifact", kind="corrupt",
                         max_fires=1),), seed=3)
    with faults.installed(plan) as inj:
        mgr.save(task, _tiny_models(rng), done_steps=2, records=[],
                 fingerprint=None, updated=["fixed"])
    assert inj.fires() == 1
    state = CheckpointManager(str(tmp_path)).load()
    assert state is not None and state.recovered and state.done_steps == 1


def test_clean_checkpoint_loads_unrecovered(rng, tmp_path):
    from photon_ml_tpu.types import TaskType

    task = TaskType.LOGISTIC_REGRESSION
    mgr = CheckpointManager(str(tmp_path))
    _save_two_generations(mgr, task, _tiny_models(rng), _tiny_models(rng))
    state = CheckpointManager(str(tmp_path)).load(
        expected_fingerprint={"f": 1})
    assert state is not None and not state.recovered
    assert state.done_steps == 2


def test_descent_resume_after_corruption_matches_clean_run(mesh):
    """End to end: a descent checkpointed per step, its newest artifact
    corrupted, then resumed — recovery retrains the lost step and the
    final coefficients are IDENTICAL to an uninterrupted run."""
    import tempfile

    from photon_ml_tpu.game import descent
    from photon_ml_tpu.game.coordinates import RandomEffectCoordinate
    from photon_ml_tpu.ops import losses
    from photon_ml_tpu.optim import OptimizerConfig
    from photon_ml_tpu.optim.problem import GLMOptimizationConfiguration
    from photon_ml_tpu.optim.regularization import (RegularizationContext,
                                                    RegularizationType)
    from photon_ml_tpu.types import TaskType

    ds = _skewed_dataset(seed=7)
    opt = GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(max_iterations=25, tolerance=1e-7),
        regularization=RegularizationContext(RegularizationType.L2, 1.0))
    cfg = descent.CoordinateDescentConfig(["per-user"], iterations=2)

    def _coord():
        return RandomEffectCoordinate(
            ds, "userId", "re", losses.LOGISTIC, opt, mesh,
            staging=stg.StagingConfig(workers=2, shard_entities=8))

    clean_model, _ = descent.run(
        TaskType.LOGISTIC_REGRESSION, {"per-user": _coord()}, cfg)
    want = np.asarray(clean_model.models["per-user"].means)

    with tempfile.TemporaryDirectory() as ckpt_dir:
        mgr = CheckpointManager(ckpt_dir)
        descent.run(TaskType.LOGISTIC_REGRESSION, {"per-user": _coord()},
                    cfg, checkpoint_manager=mgr)
        # Corrupt the newest committed coefficients (step 2's write).
        _flip_bytes(os.path.join(ckpt_dir, "model", "random-effect",
                                 "per-user", "coefficients.npz"))
        resumed, _ = descent.run(
            TaskType.LOGISTIC_REGRESSION, {"per-user": _coord()}, cfg,
            checkpoint_manager=CheckpointManager(ckpt_dir))
    got = np.asarray(resumed.models["per-user"].means)
    np.testing.assert_array_equal(got, want)


# ------------------------------------------------------- serving faults


def _service(rng, **kw):
    import jax.numpy as jnp

    from photon_ml_tpu.game.models import (FixedEffectModel, GameModel,
                                           RandomEffectModel)
    from photon_ml_tpu.models.coefficients import Coefficients
    from photon_ml_tpu.serving import ScoringService
    from photon_ml_tpu.types import TaskType

    model = GameModel(task=TaskType.LOGISTIC_REGRESSION, models={
        "fixed": FixedEffectModel("global", Coefficients(
            jnp.asarray(rng.normal(size=4).astype(np.float32)))),
        "per-user": RandomEffectModel(
            "userId", "re_userId",
            jnp.asarray(rng.normal(size=(8, 3)).astype(np.float32))),
    })
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_wait_ms", 1.0)
    kw.setdefault("emitter", ev.EventEmitter())
    return ScoringService(model, **kw)


def _request(rng, uid=0):
    from photon_ml_tpu.serving import ScoringRequest

    return ScoringRequest(
        features={"global": rng.normal(size=4).astype(np.float32),
                  "re_userId": rng.normal(size=3).astype(np.float32)},
        entity_ids={"userId": int(rng.integers(0, 8))}, uid=uid)


def test_scoring_thread_death_fails_fast_and_recovers(rng):
    """The scoring-thread-death fault class: a BaseException in the
    flush kills the worker; pending futures fail FAST with BatcherDied
    (not a hang), the worker restarts, and the next request scores."""
    from photon_ml_tpu.serving import BatcherDied

    plan = faults.FaultPlan(specs=(
        faults.FaultSpec(site="serving.flush", kind="thread_death",
                         occurrences=(0,), max_fires=1),))
    with faults.installed(plan):
        with _service(rng) as svc:
            f = svc.submit(_request(rng))
            with pytest.raises(BatcherDied):
                f.result(timeout=30)
            assert svc.metrics.recoveries_total == 1
            assert svc.batcher.restarts == 1
            # The restarted worker serves (unfaulted: max_fires spent).
            ok = svc.submit(_request(rng, uid=1))
            assert np.isfinite(float(ok.result(timeout=30)))


def test_flush_error_fails_batch_and_keeps_serving(rng):
    plan = faults.FaultPlan(specs=(
        faults.FaultSpec(site="serving.flush", occurrences=(0,),
                         max_fires=1),))
    with faults.installed(plan):
        with _service(rng) as svc:
            f = svc.submit(_request(rng))
            with pytest.raises(faults.InjectedFault):
                f.result(timeout=30)
            assert svc.metrics.flush_errors_total == 1
            assert svc.batcher.restarts == 0  # Exception ≠ thread death
            ok = svc.submit(_request(rng, uid=1))
            assert np.isfinite(float(ok.result(timeout=30)))


def test_flush_length_mismatch_fails_defined_not_hang():
    """A flush returning too few scores fails the whole batch with a
    defined error — pre-hardening, the unzipped tail hung forever."""
    from photon_ml_tpu.serving import MicroBatcher

    batcher = MicroBatcher(lambda entries: [1.0] * (len(entries) - 1),
                           max_batch=2, max_wait_ms=1.0)
    try:
        f1, f2 = batcher.submit("a"), batcher.submit("b")
        with pytest.raises(RuntimeError, match="scores"):
            f1.result(timeout=30)
        with pytest.raises(RuntimeError, match="scores"):
            f2.result(timeout=30)
    finally:
        batcher.close()


def test_queue_admission_control_sheds(rng):
    """Overload degrades by SHEDDING (defined error + metric), not by
    unbounded buffering: with the worker stalled, submits past max_queue
    raise BatcherQueueFull."""
    from photon_ml_tpu.serving import BatcherQueueFull

    plan = faults.FaultPlan(specs=(
        faults.FaultSpec(site="serving.flush", kind="sleep", seconds=1.0,
                         occurrences=(0,), max_fires=1),))
    with faults.installed(plan):
        with _service(rng, max_batch=1, max_wait_ms=0.0,
                      max_queue=2) as svc:
            first = svc.submit(_request(rng))  # occupies the worker
            shed = None
            fs = []
            for k in range(8):  # queue capacity 2 → must shed by here
                try:
                    fs.append(svc.submit(_request(rng, uid=k + 1)))
                except BatcherQueueFull as exc:
                    shed = exc
                    break
            assert shed is not None, "queue never filled"
            assert svc.metrics.shed_total >= 1
            # Everything admitted still resolves (scored after the stall).
            assert np.isfinite(float(first.result(timeout=30)))
            for f in fs:
                f.result(timeout=30)


def test_request_deadline_expires_in_queue_with_metric(rng):
    """Queued requests whose deadline passes while the worker is stalled
    fail with DeadlineExceeded + metric — their futures NEVER hang."""
    from photon_ml_tpu.serving import DeadlineExceeded

    plan = faults.FaultPlan(specs=(
        faults.FaultSpec(site="serving.flush", kind="sleep", seconds=1.0,
                         occurrences=(0,), max_fires=1),))
    with faults.installed(plan):
        with _service(rng, max_batch=1, max_wait_ms=0.0,
                      request_deadline_s=0.15) as svc:
            first = svc.submit(_request(rng))  # stalls the worker 1s
            late = [svc.submit(_request(rng, uid=k + 1)) for k in range(3)]
            assert np.isfinite(float(first.result(timeout=30)))
            for f in late:
                with pytest.raises(DeadlineExceeded):
                    f.result(timeout=30)
            assert svc.metrics.deadline_exceeded_total == 3


def test_store_fetch_transient_error_retried(rng):
    plan = faults.FaultPlan(specs=(
        faults.FaultSpec(site="serving.fetch", exc="InjectedIOError",
                         occurrences=(0,), max_fires=1),))
    with faults.installed(plan):
        with _service(rng) as svc:
            f = svc.submit(_request(rng))
            assert np.isfinite(float(f.result(timeout=30)))
            assert svc.metrics.retries_total >= 1


def test_http_error_bodies_and_metrics(rng):
    """Malformed JSON → 400 JSON body; scoring error → 500 JSON body;
    unknown path → 404 — all counted, none resetting the connection."""
    import urllib.error
    import urllib.request

    from photon_ml_tpu.serving import make_http_server

    def _post(url, body: bytes):
        req = urllib.request.Request(
            url, data=body, headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=30) as resp:
                return resp.status, json.loads(resp.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())

    plan = faults.FaultPlan(specs=(
        faults.FaultSpec(site="serving.flush", occurrences=(1,),
                         max_fires=1),))
    with faults.installed(plan):
        with _service(rng) as svc:
            server = make_http_server(svc, port=0)
            import threading

            t = threading.Thread(target=server.serve_forever, daemon=True)
            t.start()
            try:
                url = (f"http://127.0.0.1:{server.server_address[1]}")
                code, body = _post(url + "/score", b"{ not json")
                assert code == 400 and "error" in body
                code, body = _post(url + "/score", b"{}")
                assert code == 400 and "error" in body
                code, body = _post(url + "/nope", b"{}")
                assert code == 404 and "error" in body
                # Valid request → 200 with scores (flush occurrence 0).
                ok = json.dumps({"requests": [
                    {"features": {"global": [0.1] * 4}, "uid": 1}]})
                code, body = _post(url + "/score", ok.encode())
                assert code == 200 and len(body["scores"]) == 1
                # Injected scoring failure (occurrence 1) → 500 JSON.
                code, body = _post(url + "/score", ok.encode())
                assert code == 500 and "error" in body
                text = svc.metrics_text()
                assert 'photon_serving_http_errors_total{code="400"} 2' \
                    in text
                assert 'photon_serving_http_errors_total{code="500"} 1' \
                    in text
            finally:
                server.shutdown()
                server.server_close()


# ---------------------------------- driver SIGKILL → .ok-marker resume


@pytest.fixture(scope="module")
def mesh():
    from photon_ml_tpu.parallel.mesh import make_mesh

    return make_mesh()


def _train_args(train_dir, out, cache):
    return [
        "--train", train_dir,
        "--coordinate", "name=per-user,type=random,shard=re_userId,"
                        "re=userId,projector=INDEX_MAP",
        "--update-sequence", "per-user",
        "--iterations", "1",
        "--opt-config", "per-user:optimizer=LBFGS,reg=L2,reg_weight=1.0",
        "--output-dir", out,
        "--staging-cache-dir", cache,
        "--staging", "workers=2,shard_entities=8",
        "--no-checkpoint",
    ]


def test_driver_sigkill_resumes_from_ok_markers_bit_identical(tmp_path):
    """The satellite drill: the training driver is SIGKILLed mid-staging
    (via the injector, through ``--fault-plan``); the rerun resumes from
    the per-shard ``.ok`` markers with partial credit and the final
    coefficients are bit-identical to a never-killed run."""
    from photon_ml_tpu.cli import game_train
    from photon_ml_tpu.data import synthetic
    from photon_ml_tpu.data.game_data import from_synthetic
    from photon_ml_tpu.data.io import save_game_dataset

    rng = np.random.default_rng(0)
    syn = synthetic.game_data(rng, n=700, d_global=4,
                              re_specs={"userId": (40, 3)})
    ds = from_synthetic(syn)
    train_dir = str(tmp_path / "train")
    save_game_dataset(ds, train_dir)
    cache = str(tmp_path / "stage-cache")

    # Phase 1 (subprocess): SIGKILL the driver at the 3rd shard commit.
    plan = faults.FaultPlan(specs=(
        faults.FaultSpec(site="staging_cache.save_shard", kind="kill",
                         occurrences=(2,)),))
    plan_path = str(tmp_path / "plan.json")
    with open(plan_path, "w") as f:
        f.write(plan.to_json())
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "PYTHONPATH": REPO + (os.pathsep + env["PYTHONPATH"]
                                      if env.get("PYTHONPATH") else "")})
    log_path = str(tmp_path / "phase1.log")
    with open(log_path, "w") as log:
        proc = subprocess.run(
            [sys.executable, "-m", "photon_ml_tpu.cli.game_train"]
            + _train_args(train_dir, str(tmp_path / "out-killed"), cache)
            + ["--fault-plan", plan_path],
            env=env, cwd=REPO, stdout=log, stderr=subprocess.STDOUT,
            timeout=600)
    assert proc.returncode == -9, (
        f"driver survived the SIGKILL plan (rc={proc.returncode}):\n"
        + open(log_path).read()[-3000:])
    # Partial credit on disk: only COMMITTED shards have .ok markers (a
    # concurrent save mid-write when the kill landed has none; 1 or 2
    # committed depending on that race, never 3+ — the kill fired at the
    # 3rd save's entry).
    entries = os.listdir(cache)
    assert len(entries) == 1
    markers = [f for f in os.listdir(os.path.join(cache, entries[0]))
               if f.endswith(".ok")]
    assert 1 <= len(markers) <= 2, markers
    assert not os.path.exists(
        os.path.join(cache, entries[0], "meta.json"))

    # Phase 2 (in-process): rerun resumes from the markers...
    seen = []
    ev.default_emitter.register(seen.append)
    try:
        game_train.run(game_train.build_parser().parse_args(
            _train_args(train_dir, str(tmp_path / "out-resumed"), cache)))
    finally:
        ev.default_emitter.unregister(seen.append)
    starts = [e for e in seen if isinstance(e, ev.StagingStart)]
    assert starts and starts[0].cached_shards == len(markers)
    assert starts[0].num_shards > len(markers)  # the rest restaged

    # ...and a never-faulted run from scratch matches bit for bit.
    game_train.run(game_train.build_parser().parse_args(
        _train_args(train_dir, str(tmp_path / "out-clean"),
                    str(tmp_path / "fresh-cache"))))
    a = np.load(os.path.join(str(tmp_path), "out-resumed", "best",
                             "random-effect", "per-user",
                             "coefficients.npz"))
    b = np.load(os.path.join(str(tmp_path), "out-clean", "best",
                             "random-effect", "per-user",
                             "coefficients.npz"))
    assert sorted(a.files) == sorted(b.files)
    for k in a.files:
        np.testing.assert_array_equal(a[k], b[k])


# ------------------------------- streamed fixed effect (docs/STREAMING.md)


def _stream_fixture():
    """Tiny streamed coordinate over a 2-device mesh (shared shapes with
    tests/test_stream_dist.py)."""
    import jax

    from photon_ml_tpu.data import sparse as sp
    from photon_ml_tpu.data.game_data import from_sparse_batch
    from photon_ml_tpu.game.coordinates import \
        StreamingSparseFixedEffectCoordinate
    from photon_ml_tpu.ops import losses
    from photon_ml_tpu.ops import streaming_sparse as ss
    from photon_ml_tpu.optim import OptimizerConfig
    from photon_ml_tpu.optim.problem import GLMOptimizationConfiguration
    from photon_ml_tpu.optim.regularization import (RegularizationContext,
                                                    RegularizationType)
    from photon_ml_tpu.parallel.mesh import make_mesh

    batch, _ = sp.synthetic_sparse(700, 96, 5, seed=3)
    ds = from_sparse_batch(batch)

    def chunks():
        for lo in range(0, 700, 64):
            hi = min(lo + 64, 700)
            yield sp.SparseBatch(
                indices=np.asarray(batch.indices)[lo:hi],
                values=np.asarray(batch.values)[lo:hi],
                labels=np.asarray(batch.labels)[lo:hi],
                weights=np.asarray(batch.weights)[lo:hi],
                offsets=np.zeros(hi - lo, np.float32),
                num_features=batch.num_features)

    chunked = ss.build_chunked(chunks(), batch.num_features, 64, num_hot=16)
    cfg = GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(max_iterations=10, tolerance=1e-9),
        regularization=RegularizationContext(RegularizationType.L2, 1.0))
    mesh = make_mesh(num_data=2, devices=jax.devices()[:2])

    def make_coord():
        return StreamingSparseFixedEffectCoordinate(
            ds, chunked, "global", losses.LOGISTIC, cfg, mesh=mesh)

    return make_coord, chunked, ss, losses


def test_stream_transfer_transient_fault_retries_bit_identical():
    """One injected chunk-transfer failure mid-pass: the bounded-retry
    ladder re-transfers and the pass result is bit-identical to the
    unfaulted one (a transfer is idempotent)."""
    make_coord, chunked, ss, losses = _stream_fixture()
    import jax

    from photon_ml_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(num_data=2, devices=jax.devices()[:2])
    vg = ss.ShardedChunkStream(chunked, mesh).value_and_gradient(
        losses.LOGISTIC)
    w = np.zeros(96, np.float32)
    v0, g0 = vg(w)
    plan = faults.FaultPlan(specs=(faults.FaultSpec(
        site="stream.chunk_transfer", kind="raise", occurrences=(2,),
        max_fires=1),))
    with faults.installed(plan) as inj:
        v1, g1 = vg(w)
    assert inj.fires("stream.chunk_transfer") == 1
    assert float(v0) == float(v1)
    np.testing.assert_array_equal(np.asarray(g0), np.asarray(g1))


def test_stream_transfer_retries_exhausted_fail_defined():
    """A persistently failing transfer exhausts the bounded retries and
    raises the injected error — a lost chunk must never silently drop
    out of the objective."""
    make_coord, chunked, ss, losses = _stream_fixture()
    import jax

    from photon_ml_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(num_data=2, devices=jax.devices()[:2])
    vg = ss.ShardedChunkStream(chunked, mesh).value_and_gradient(
        losses.LOGISTIC)
    plan = faults.FaultPlan(specs=(faults.FaultSpec(
        site="stream.chunk_transfer", kind="raise", indices=(1,)),))
    with faults.installed(plan) as inj:
        with pytest.raises(faults.InjectedFault):
            vg(np.zeros(96, np.float32))
    # Initial attempt + the full retry budget, then the loud failure.
    assert inj.fires("stream.chunk_transfer") == \
        ss.TRANSFER_MAX_RETRIES + 1


def test_stream_checkpoint_corruption_recovers_prev_generation(tmp_path):
    """Injected bit rot on the newest stream-state npz: load() detects
    the CRC mismatch, falls back to the previous committed generation
    (CheckpointRecovered event), and the resumed fit still lands on
    bit-identical coefficients (it just re-runs the torn iteration)."""
    make_coord, *_ = _stream_fixture()
    clean = make_coord()
    clean.bind_step_checkpoint(str(tmp_path / "clean"), 1)
    off = np.zeros(700, np.float32)
    w_clean = np.asarray(clean.train_model(off).coefficients.means)

    victim = make_coord()
    victim.bind_step_checkpoint(str(tmp_path / "victim"), 1)
    # Corrupt the 5th snapshot's bytes AFTER its CRC was recorded, then
    # kill the fit at the 6th write — resume sees a bad newest
    # generation and must fall back one.
    plan = faults.FaultPlan(specs=(
        faults.FaultSpec(site="stream.checkpoint_artifact", kind="corrupt",
                         occurrences=(4,)),
        faults.FaultSpec(site="stream.checkpoint_write", kind="raise",
                         occurrences=(5,)),
    ))
    with faults.installed(plan) as inj:
        with pytest.raises(faults.InjectedFault):
            victim.train_model(off)
    assert inj.fires("stream.checkpoint_artifact") == 1
    seen = []
    ev.default_emitter.register(seen.append)
    try:
        w_resumed = np.asarray(victim.train_model(off).coefficients.means)
    finally:
        ev.default_emitter.unregister(seen.append)
    recovered = [e for e in seen if isinstance(e, ev.CheckpointRecovered)]
    assert recovered and recovered[0].directory == str(tmp_path / "victim")
    np.testing.assert_array_equal(w_resumed, w_clean)


def _stream_train_args(train_dir, out):
    return [
        "--train", train_dir,
        "--coordinate", "name=fixed,type=fixed,shard=global",
        "--update-sequence", "fixed",
        "--opt-config", "fixed:optimizer=LBFGS,reg=L2,reg_weight=1.0",
        "--streaming", "chunk_rows=128,num_hot=8,workers=2",
        "--output-dir", out,
    ]


def test_driver_sigkill_mid_lbfgs_resumes_bit_identical(tmp_path):
    """The flagship drill (ISSUE 6 acceptance): the training driver is
    SIGKILLed MID-L-BFGS on the streamed fixed effect (via
    ``--fault-plan`` at the 5th stream-state write); ``--resume`` picks
    up mid-optimization from the StreamingStateStore and the final
    coefficients are bit-identical to a never-killed run."""
    from photon_ml_tpu.cli import game_train
    from photon_ml_tpu.data import sparse as sp
    from photon_ml_tpu.data.game_data import from_sparse_batch
    from photon_ml_tpu.data.io import save_game_dataset

    batch, _ = sp.synthetic_sparse(700, 64, 5, seed=11)
    ds = from_sparse_batch(batch)
    train_dir = str(tmp_path / "train")
    save_game_dataset(ds, train_dir)

    plan = faults.FaultPlan(specs=(faults.FaultSpec(
        site="stream.checkpoint_write", kind="kill", occurrences=(4,)),))
    plan_path = str(tmp_path / "plan.json")
    with open(plan_path, "w") as f:
        f.write(plan.to_json())
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "PYTHONPATH": REPO + (os.pathsep + env["PYTHONPATH"]
                                      if env.get("PYTHONPATH") else "")})
    out_killed = str(tmp_path / "out-killed")
    log_path = str(tmp_path / "phase1.log")
    with open(log_path, "w") as log:
        proc = subprocess.run(
            [sys.executable, "-m", "photon_ml_tpu.cli.game_train"]
            + _stream_train_args(train_dir, out_killed)
            + ["--fault-plan", plan_path],
            env=env, cwd=REPO, stdout=log, stderr=subprocess.STDOUT,
            timeout=600)
    assert proc.returncode == -9, (
        f"driver survived the SIGKILL plan (rc={proc.returncode}):\n"
        + open(log_path).read()[-3000:])
    ckpt = os.path.join(out_killed, "checkpoints", "grid-0")
    stream_dirs = [d for d in os.listdir(ckpt)
                   if d.startswith("stream-step")]
    assert stream_dirs, "no mid-step stream state survived the kill"

    # Phase 2 (in-process): --resume continues MID-optimization...
    game_train.run(game_train.build_parser().parse_args(
        _stream_train_args(train_dir, out_killed) + ["--resume"]))

    # ...and matches a never-killed run bit for bit.
    out_clean = str(tmp_path / "out-clean")
    game_train.run(game_train.build_parser().parse_args(
        _stream_train_args(train_dir, out_clean)))
    a = np.load(os.path.join(out_killed, "best", "fixed-effect", "fixed",
                             "coefficients.npz"))
    b = np.load(os.path.join(out_clean, "best", "fixed-effect", "fixed",
                             "coefficients.npz"))
    assert sorted(a.files) == sorted(b.files)
    for k in a.files:
        np.testing.assert_array_equal(a[k], b[k])


def _sdca_train_args(train_dir, out):
    return [
        "--train", train_dir,
        "--coordinate", "name=fixed,type=fixed,shard=global",
        "--update-sequence", "fixed",
        "--opt-config", "fixed:optimizer=LBFGS,max_iter=40,reg=L2,"
                        "reg_weight=1.0",
        "--streaming", "chunk_rows=128,num_hot=8,workers=2,solver=sdca",
        "--output-dir", out,
    ]


def test_driver_sigkill_mid_sdca_epoch_resumes_bit_identical(tmp_path):
    """The photon-gap drill (ISSUE 16 acceptance): the training driver is
    SIGKILLed MID-SDCA-EPOCH (``--fault-plan`` at an ``opt.dual_update``
    chunk seam inside epoch 2); ``--resume`` reloads the last epoch
    boundary's (w, α) snapshot and the final coefficients are
    bit-identical to a never-killed run — the dual vector survives the
    crash, not just w."""
    from photon_ml_tpu.cli import game_train
    from photon_ml_tpu.data import sparse as sp
    from photon_ml_tpu.data.game_data import from_sparse_batch
    from photon_ml_tpu.data.io import save_game_dataset

    batch, _ = sp.synthetic_sparse(700, 64, 5, seed=11)
    ds = from_sparse_batch(batch)
    train_dir = str(tmp_path / "train")
    save_game_dataset(ds, train_dir)

    # 700 rows / 128-row chunks → 6 dual updates per epoch; occurrence 8
    # lands on epoch 2's third chunk — epoch 1's snapshot (w AND α) is on
    # disk, epoch 2 is torn.
    plan = faults.FaultPlan(specs=(faults.FaultSpec(
        site="opt.dual_update", kind="kill", occurrences=(8,)),))
    plan_path = str(tmp_path / "plan.json")
    with open(plan_path, "w") as f:
        f.write(plan.to_json())
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "PYTHONPATH": REPO + (os.pathsep + env["PYTHONPATH"]
                                      if env.get("PYTHONPATH") else "")})
    out_killed = str(tmp_path / "out-killed")
    log_path = str(tmp_path / "phase1.log")
    with open(log_path, "w") as log:
        proc = subprocess.run(
            [sys.executable, "-m", "photon_ml_tpu.cli.game_train"]
            + _sdca_train_args(train_dir, out_killed)
            + ["--fault-plan", plan_path],
            env=env, cwd=REPO, stdout=log, stderr=subprocess.STDOUT,
            timeout=600)
    assert proc.returncode == -9, (
        f"driver survived the SIGKILL plan (rc={proc.returncode}):\n"
        + open(log_path).read()[-3000:])
    ckpt = os.path.join(out_killed, "checkpoints", "grid-0")
    stream_dirs = [d for d in os.listdir(ckpt)
                   if d.startswith("stream-step")]
    assert stream_dirs, "no mid-fit stochastic state survived the kill"

    # Phase 2 (in-process): --resume reloads (w, α) and replays the
    # remaining epochs...
    game_train.run(game_train.build_parser().parse_args(
        _sdca_train_args(train_dir, out_killed) + ["--resume"]))

    # ...and matches a never-killed run bit for bit.
    out_clean = str(tmp_path / "out-clean")
    game_train.run(game_train.build_parser().parse_args(
        _sdca_train_args(train_dir, out_clean)))
    a = np.load(os.path.join(out_killed, "best", "fixed-effect", "fixed",
                             "coefficients.npz"))
    b = np.load(os.path.join(out_clean, "best", "fixed-effect", "fixed",
                             "coefficients.npz"))
    assert sorted(a.files) == sorted(b.files)
    for k in a.files:
        np.testing.assert_array_equal(a[k], b[k])
