"""Parallel, pipelined random-effect staging (game/staging.py).

The contract under test is EXACTNESS: the worker count, pool mode,
shard size, and pipeline handoff are execution choices — the staged
bytes, the column maps, the staging-cache contents, and the final GAME
coefficients must be identical to the serial whole-bucket build, bit for
bit. Plus the pipeline mechanics themselves: shard-granular cache
partial credit, lifecycle events, and the config surface.
"""

import os

import numpy as np
import pytest

from photon_ml_tpu.data.game_data import GameDataset, SparseShard
from photon_ml_tpu.game import buckets as bkt
from photon_ml_tpu.game import projector as prj
from photon_ml_tpu.game import staging as stg
from photon_ml_tpu.game import staging_cache
from photon_ml_tpu.ops import losses
from photon_ml_tpu.optim import OptimizerConfig
from photon_ml_tpu.optim.problem import GLMOptimizationConfiguration
from photon_ml_tpu.optim.regularization import (RegularizationContext,
                                                RegularizationType)
from photon_ml_tpu.parallel.mesh import make_mesh
from photon_ml_tpu.utils import events as ev


@pytest.fixture(scope="module")
def mesh():
    return make_mesh()


def _opt(max_iter=40):
    return GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(max_iterations=max_iter, tolerance=1e-8),
        regularization=RegularizationContext(RegularizationType.L2, 1.0))


def _skewed_dataset(n_entities=40, d=48, nnz=4, seed=0, intercept=True,
                    dense=False):
    """Entities with 2–40 examples → several capacity buckets, each wide
    enough to split into multiple 8-lane staging shards."""
    rng = np.random.default_rng(seed)
    counts = rng.integers(2, 41, n_entities)
    ids = np.repeat(np.arange(n_entities, dtype=np.int32), counts)
    rng.shuffle(ids)
    n = ids.shape[0]
    d_draw = d - 1 if intercept else d
    idx = np.sort(rng.integers(0, d_draw, (n, nnz)).astype(np.int32),
                  axis=1)
    dup = np.zeros_like(idx, bool)
    dup[:, 1:] = idx[:, 1:] == idx[:, :-1]
    vals = rng.normal(size=(n, nnz)).astype(np.float32)
    idx[dup] = d
    vals[dup] = 0.0
    if intercept:
        idx = np.concatenate([idx, np.full((n, 1), d - 1, np.int32)],
                             axis=1)
        vals = np.concatenate([vals, np.ones((n, 1), np.float32)], axis=1)
    shard = SparseShard(idx, vals, d)
    if dense:
        X = np.zeros((n, d), np.float32)
        valid = idx < d
        np.add.at(X, (np.broadcast_to(np.arange(n)[:, None],
                                      idx.shape)[valid], idx[valid]),
                  vals[valid])
        shard = X
    y = rng.integers(0, 2, n).astype(np.float32)
    w = rng.uniform(0.5, 1.5, n).astype(np.float32)
    ds = GameDataset(
        response=y, offsets=np.zeros(n, np.float32), weights=w,
        feature_shards={"re": shard}, entity_ids={"userId": ids},
        num_entities={"userId": n_entities},
        intercept_index={"re": d - 1} if intercept else {})
    return ds


def _serial_host_tuples(bucketing, X, ii, response, weights, ratio=None,
                        f_full=None, s_full=None):
    """The pre-pipeline whole-bucket staging, verbatim — the reference
    the sharded pipeline must reproduce bit for bit."""
    coo = prj.shard_coo(X)
    trips = prj.all_bucket_triplets(bucketing.buckets, X, coo)
    out = []
    for b, trip in zip(bucketing.buckets, trips):
        proj = prj.build_bucket_projection(
            b, X, ii, labels=response if ratio is not None else None,
            features_to_samples_ratio=ratio, triplets=trip)
        Xb = prj.gather_projected_features(b, proj, X, triplets=trip)
        (yb,) = bkt.gather_bucket_arrays(b, response)
        wb = bkt.bucket_weights(b, weights)
        tup = [Xb, yb, wb, b.example_idx.astype(np.int32),
               b.entity_rows, proj.cols]
        if f_full is not None or s_full is not None:
            f_p, s_p = prj.project_norm_arrays(proj, f_full, s_full)
            if f_full is not None:
                tup.append(f_p)
            if s_full is not None:
                tup.append(s_p)
        out.append(tuple(tup))
    return out


def _drain(stager):
    got = list(stager.shards())
    stager.join()
    return got


def _merge_by_bucket(plan, shards, num_buckets):
    """Concatenate shard tuples back into whole-bucket tuples."""
    merged = []
    for bi in range(num_buckets):
        parts = [t for (b, lo, hi), t in zip(plan, shards) if b == bi]
        merged.append(tuple(
            np.concatenate([np.asarray(p[j]) for p in parts])
            for j in range(len(parts[0]))))
    return merged


def _assert_bytes_equal(got, want):
    assert len(got) == len(want)
    for tg, tw in zip(got, want):
        assert len(tg) == len(tw)
        for ag, aw in zip(tg, tw):
            ag, aw = np.asarray(ag), np.asarray(aw)
            assert ag.dtype == aw.dtype and ag.shape == aw.shape
            assert ag.tobytes() == aw.tobytes()


def _stager(ds, config, cache_dir=None, cache_key=None, ratio=None,
            f_full=None, s_full=None, emitter=None, subspace=False):
    ii = ds.intercept_index.get("re")
    bucketing = bkt.build_bucketing(np.asarray(ds.entity_ids["userId"]),
                                    ds.num_entities["userId"])
    return bucketing, stg.ProjectionStager(
        bucketing=bucketing, X=ds.feature_shards["re"],
        response=np.asarray(ds.response),
        weights=np.asarray(ds.weights), intercept_index=ii,
        features_to_samples_ratio=ratio, factors=f_full, shifts=s_full,
        config=config, cache_dir=cache_dir, cache_key=cache_key,
        expect_subspace=subspace, label="userId:re",
        emitter=emitter or ev.EventEmitter())


# ------------------------------------------------------------------ parity


@pytest.mark.parametrize("workers", [1, 2, 8])
def test_staged_shards_bit_identical_to_serial(workers):
    """THE acceptance property: staged buckets and projections from the
    sharded W-worker pipeline are byte-identical to the whole-bucket
    serial build."""
    ds = _skewed_dataset()
    cfg = stg.StagingConfig(workers=workers, shard_entities=8)
    bucketing, stager = _stager(ds, cfg)
    shards = _drain(stager)
    merged = _merge_by_bucket(stager.plan, shards,
                              len(bucketing.buckets))
    want = _serial_host_tuples(
        bucketing, ds.feature_shards["re"],
        ds.intercept_index.get("re"),
        np.asarray(ds.response), np.asarray(ds.weights))
    _assert_bytes_equal(merged, want)


def test_process_mode_bit_identical_to_thread(monkeypatch):
    """The process-pool fallback ships work by pickle yet produces the
    same bytes (content never depends on the pool) — and its workers
    never initialise a JAX backend: on the chip a worker that did would
    ask for a device its parent already holds. The workers inherit a
    platform name no backend answers to, so one that tried would fail
    its task and show up as a retry."""
    ds = _skewed_dataset(n_entities=16, seed=3)
    _, t_stager = _stager(ds, stg.StagingConfig(workers=2,
                                                shard_entities=8))
    t_shards = _drain(t_stager)
    monkeypatch.setenv("JAX_PLATFORMS", "no-such-platform")
    emitter = ev.EventEmitter()
    seen = []
    emitter.register(seen.append)
    _, p_stager = _stager(ds, stg.StagingConfig(workers=2, mode="process",
                                                shard_entities=8),
                          emitter=emitter)
    p_shards = _drain(p_stager)
    _assert_bytes_equal(t_shards, p_shards)
    assert not [e for e in seen if isinstance(e, ev.StagingRetry)]


def test_dense_shard_with_normalization_parity():
    """Dense projected staging with factor+shift normalization: the
    per-shard norm projections and dense gathers merge exactly."""
    ds = _skewed_dataset(dense=True, seed=5)
    d = ds.feature_shards["re"].shape[1]
    rng = np.random.default_rng(0)
    f_full = rng.uniform(0.5, 2.0, d).astype(np.float32)
    s_full = rng.normal(size=d).astype(np.float32)
    cfg = stg.StagingConfig(workers=4, shard_entities=8)
    bucketing, stager = _stager(ds, cfg, f_full=f_full, s_full=s_full)
    merged = _merge_by_bucket(stager.plan, _drain(stager),
                              len(bucketing.buckets))
    want = _serial_host_tuples(
        bucketing, ds.feature_shards["re"],
        ds.intercept_index.get("re"), np.asarray(ds.response),
        np.asarray(ds.weights), f_full=f_full, s_full=s_full)
    _assert_bytes_equal(merged, want)


def test_pearson_ratio_path_bit_identical(rng):
    """The Pearson feature cap (stable-sorted moment sums) shards
    exactly too — the one staging stage where fp accumulation order
    could have diverged."""
    ds = _skewed_dataset(seed=7)
    ratio = 0.6
    cfg = stg.StagingConfig(workers=4, shard_entities=8)
    bucketing, stager = _stager(ds, cfg, ratio=ratio)
    merged = _merge_by_bucket(stager.plan, _drain(stager),
                              len(bucketing.buckets))
    want = _serial_host_tuples(
        bucketing, ds.feature_shards["re"],
        ds.intercept_index.get("re"), np.asarray(ds.response),
        np.asarray(ds.weights), ratio=ratio)
    _assert_bytes_equal(merged, want)


@pytest.mark.parametrize("workers", [1, 8])
def test_project_buckets_matches_per_bucket_build(workers):
    """The projection-only helper (the bench's measurement target) ==
    build_bucket_projection per bucket."""
    ds = _skewed_dataset(seed=2)
    X = ds.feature_shards["re"]
    ids = np.asarray(ds.entity_ids["userId"])
    b = bkt.build_bucketing(ids, ds.num_entities["userId"])
    ii = ds.intercept_index.get("re")
    got = stg.project_buckets(
        b, X, intercept_index=ii,
        config=stg.StagingConfig(workers=workers, shard_entities=8))
    for bucket, proj in zip(b.buckets, got):
        want = prj.build_bucket_projection(bucket, X, ii)
        assert proj.d_active == want.d_active
        np.testing.assert_array_equal(proj.cols, want.cols)


# ------------------------------------------------------- cache round trips


def test_cache_roundtrip_bit_identical(tmp_path):
    ds = _skewed_dataset(seed=11)
    cfg = stg.StagingConfig(workers=4, shard_entities=8)
    cache = str(tmp_path / "stage")
    _, cold = _stager(ds, cfg, cache_dir=cache, cache_key="k1")
    cold_shards = _drain(cold)
    emitter = ev.EventEmitter()
    seen = []
    emitter.register(seen.append)
    _, warm = _stager(ds, cfg, cache_dir=cache, cache_key="k1",
                      emitter=emitter)
    warm_shards = _drain(warm)
    assert all(e.source == "cache" for e in seen
               if isinstance(e, ev.StagingShard))
    _assert_bytes_equal(cold_shards, warm_shards)


def test_cache_partial_invalidation_restages_only_missing(tmp_path):
    """Shard-granular credit: corrupt ONE shard and only that shard
    restages — and the merged output is still byte-identical."""
    ds = _skewed_dataset(seed=13)
    cfg = stg.StagingConfig(workers=2, shard_entities=8)
    cache = str(tmp_path / "stage")
    _, cold = _stager(ds, cfg, cache_dir=cache, cache_key="k1")
    cold_shards = _drain(cold)
    assert len(cold_shards) > 2
    # Truncate one shard's arrays (the .ok marker survives — load must
    # still reject it on the unreadable array files).
    victim = 1
    entry = os.path.join(cache, "k1")
    for f in os.listdir(entry):
        if f.startswith(f"s{victim}_"):
            open(os.path.join(entry, f), "wb").close()
    assert staging_cache.load_shard(cache, "k1", victim) is None
    emitter = ev.EventEmitter()
    seen = []
    emitter.register(seen.append)
    _, again = _stager(ds, cfg, cache_dir=cache, cache_key="k1",
                       emitter=emitter)
    again_shards = _drain(again)
    staged = [e for e in seen if isinstance(e, ev.StagingShard)
              and e.source == "staged"]
    assert [e.index for e in staged] == [victim]
    _assert_bytes_equal(again_shards, cold_shards)
    # ...and the restage healed the entry on disk.
    assert staging_cache.load_shard(cache, "k1", victim) is not None


def test_cache_write_as_produced_without_full_drain(tmp_path):
    """Shards persist as they are produced — a consumer that stops early
    (killed run) still leaves the consumed prefix on disk."""
    ds = _skewed_dataset(seed=17)
    cfg = stg.StagingConfig(workers=1, shard_entities=8,
                            pipeline_depth=1)
    cache = str(tmp_path / "stage")
    _, stager = _stager(ds, cfg, cache_dir=cache, cache_key="k1")
    it = stager.shards()
    next(it)  # consume ONE shard, abandon the rest
    it.close()
    # The write trails the handoff (consumer latency comes first) by one
    # np.save; poll briefly rather than flake.
    import time

    deadline = time.monotonic() + 10.0
    while (staging_cache.load_shard(cache, "k1", 0) is None
           and time.monotonic() < deadline):
        time.sleep(0.02)
    assert staging_cache.load_shard(cache, "k1", 0) is not None
    # The abandoned entry is partial: no completion record.
    assert staging_cache.load(cache, "k1") is None


# ------------------------------------------------------ pipelined descent


def test_pipelined_descent_matches_barrier_exactly(mesh):
    """Final GAME coefficients from the lazily-consumed pipeline ==
    the fully-staged barrier path, bit for bit (same device programs in
    the same order — the handoff changes WHEN staging happens, never
    what is staged)."""
    from photon_ml_tpu.game import descent
    from photon_ml_tpu.game.coordinates import RandomEffectCoordinate
    from photon_ml_tpu.types import TaskType

    ds = _skewed_dataset(seed=19)
    cfg = _opt()
    results = {}
    for name, barrier in (("pipelined", False), ("barrier", True)):
        coord = RandomEffectCoordinate(
            ds, "userId", "re", losses.LOGISTIC, cfg, mesh,
            staging=stg.StagingConfig(workers=4, shard_entities=8))
        if barrier:
            coord.wait_staged()
        model, _ = descent.run(
            TaskType.LOGISTIC_REGRESSION, {"per-user": coord},
            descent.CoordinateDescentConfig(["per-user"], iterations=2))
        m = model.models["per-user"]
        results[name] = (np.asarray(m.means),
                         np.asarray(coord.score(m)))
    np.testing.assert_array_equal(results["pipelined"][0],
                                  results["barrier"][0])
    np.testing.assert_array_equal(results["pipelined"][1],
                                  results["barrier"][1])


def test_coordinate_staging_workers_invariant(mesh):
    """Through the coordinate front door: trained models identical for
    1 vs 8 staging workers (staged device arrays are the same bytes)."""
    from photon_ml_tpu.game.coordinates import RandomEffectCoordinate

    ds = _skewed_dataset(seed=23)
    off = np.zeros(ds.num_rows, np.float32)
    means = {}
    for workers in (1, 8):
        c = RandomEffectCoordinate(
            ds, "userId", "re", losses.LOGISTIC, _opt(), mesh,
            staging=stg.StagingConfig(workers=workers, shard_entities=8))
        means[workers] = np.asarray(c.train_model(off).means)
    np.testing.assert_array_equal(means[1], means[8])


# -------------------------------------------------------- events & config


def test_staging_events_lifecycle():
    ds = _skewed_dataset(n_entities=12, seed=29)
    emitter = ev.EventEmitter()
    seen = []
    emitter.register(seen.append)
    _, stager = _stager(ds, stg.StagingConfig(workers=2,
                                              shard_entities=8),
                        emitter=emitter)
    _drain(stager)
    kinds = [type(e).__name__ for e in seen]
    assert kinds[0] == "StagingStart"
    assert kinds.count("StagingFinish") == 1
    shard_events = [e for e in seen if isinstance(e, ev.StagingShard)]
    assert len(shard_events) == stager.num_shards
    start = next(e for e in seen if isinstance(e, ev.StagingStart))
    assert start.workers == 2 and start.mode == "thread"
    fin = next(e for e in seen if isinstance(e, ev.StagingFinish))
    assert fin.num_shards == stager.num_shards


def test_staging_config_validation_and_parse():
    from photon_ml_tpu.api.configs import parse_staging_config

    cfg = parse_staging_config("workers=8,depth=4,shard_entities=1024")
    assert cfg.workers == 8 and cfg.pipeline_depth == 4
    assert cfg.shard_entities == 1024 and cfg.mode == "thread"
    assert parse_staging_config("mode=process").mode == "process"
    with pytest.raises(ValueError, match="mode"):
        stg.StagingConfig(mode="fibers")
    with pytest.raises(ValueError, match="workers"):
        stg.StagingConfig(workers=0)
    with pytest.raises(ValueError, match="unknown staging keys"):
        parse_staging_config("wrokers=8")


def test_cli_staging_flag_round_trip():
    from photon_ml_tpu.cli import game_train

    args = game_train.build_parser().parse_args([
        "--train", "x", "--coordinate", "name=f,type=fixed,shard=global",
        "--update-sequence", "f", "--output-dir", "o",
        "--staging", "workers=2,mode=thread,depth=3"])
    from photon_ml_tpu.api.configs import parse_staging_config

    cfg = parse_staging_config(args.staging)
    assert cfg.workers == 2 and cfg.pipeline_depth == 3


def test_plan_shards_respects_pad_and_covers_every_lane():
    ds = _skewed_dataset(seed=31)
    b = bkt.build_bucketing(np.asarray(ds.entity_ids["userId"]),
                            ds.num_entities["userId"])
    plan = stg.plan_shards(b, shard_entities=10)  # rounds up to pad=8
    for bi, lo, hi in plan:
        assert lo % b.entity_pad_multiple == 0
        assert hi <= b.buckets[bi].num_entities
    for bi, bucket in enumerate(b.buckets):
        covered = sorted((lo, hi) for bj, lo, hi in plan if bj == bi)
        assert covered[0][0] == 0
        assert covered[-1][1] == bucket.num_entities
        for (_, h1), (l2, _) in zip(covered, covered[1:]):
            assert h1 == l2
