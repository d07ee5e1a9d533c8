"""GAME engine tests: bucketing, coordinates, coordinate descent.

Mirrors the reference's integration tests (SURVEY.md §4):
``RandomEffectDatasetIntegTest`` (active/passive split, grouping),
``CoordinateDescentIntegTest`` / ``GameEstimatorIntegTest`` (mixed-effect
fits improve over fixed-only; AUC thresholds on synthetic data).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.data import synthetic
from photon_ml_tpu.data.batch import LabeledBatch
from photon_ml_tpu.data.game_data import GameDataset, from_synthetic
from photon_ml_tpu.evaluation import evaluators as ev
from photon_ml_tpu.game import buckets as bkt
from photon_ml_tpu.game import descent
from photon_ml_tpu.game.coordinates import (FixedEffectCoordinate,
                                            RandomEffectCoordinate)
from photon_ml_tpu.ops import losses
from photon_ml_tpu.optim import OptimizerConfig
from photon_ml_tpu.optim import problem as local_problem
from photon_ml_tpu.optim.problem import GLMOptimizationConfiguration
from photon_ml_tpu.optim.regularization import (RegularizationContext,
                                                RegularizationType)
from photon_ml_tpu.parallel.mesh import make_mesh
from photon_ml_tpu.types import TaskType


@pytest.fixture(scope="module")
def mesh():
    return make_mesh()


def _game_config(l2=1.0, max_iter=60):
    return GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(max_iterations=max_iter, tolerance=1e-7),
        regularization=RegularizationContext(RegularizationType.L2, l2))


# ------------------------------------------------------------------ bucketing


def test_bucketing_covers_all_kept_entities(rng):
    ids = rng.integers(0, 50, size=400).astype(np.int32)
    b = bkt.build_bucketing(ids, 50, lower_bound=1)
    seen = set()
    for bucket in b.buckets:
        live = bucket.entity_rows >= 0
        for row, cnt, ex in zip(bucket.entity_rows[live],
                                bucket.counts[live],
                                bucket.example_idx[live]):
            assert row not in seen
            seen.add(row)
            got = ex[ex >= 0]
            assert len(got) == cnt
            assert np.all(ids[got] == row)
    assert seen == set(np.unique(ids))
    assert b.trained_entities.sum() == len(seen)


def test_bucketing_lower_bound_drops_small_entities(rng):
    ids = np.concatenate([np.zeros(20, np.int32), np.ones(2, np.int32),
                          np.full(5, 2, np.int32)])
    b = bkt.build_bucketing(ids, 3, lower_bound=5)
    assert bool(b.trained_entities[0]) and bool(b.trained_entities[2])
    assert not bool(b.trained_entities[1])
    assert b.num_passive_only_entities == 1
    assert b.num_passive_examples == 2


def test_bucketing_upper_bound_caps_samples(rng):
    ids = np.zeros(100, np.int32)
    b = bkt.build_bucketing(ids, 1, upper_bound=16, rng=rng)
    bucket = b.buckets[0]
    assert bucket.counts[0] == 16
    assert bucket.capacity == 16
    assert b.num_passive_examples == 84


def test_bucketing_matches_per_entity_reference(rng):
    """The vectorized builder (one padded gather per capacity class; no
    per-entity Python loops — round-2 verdict: staging at 10⁶ entities)
    must reproduce the straightforward per-entity construction exactly,
    including deterministic capping and padding."""
    def reference(ids, num_entities, lower_bound, upper_bound):
        order = np.argsort(ids, kind="stable")
        uniq, starts, counts = np.unique(ids[order], return_index=True,
                                         return_counts=True)
        capped = (counts if upper_bound is None
                  else np.minimum(counts, upper_bound))
        keep = counts >= max(1, lower_bound)
        caps = np.maximum(8, np.array([bkt._next_pow2(int(c))
                                       for c in capped]))
        out = {}
        for cap in np.unique(caps[keep]):
            sel = np.where(keep & (caps == cap))[0]
            pad_e = ((len(sel) + 7) // 8) * 8
            ex = np.full((pad_e, int(cap)), -1, np.int64)
            rows = np.full((pad_e,), -1, np.int32)
            for i, u in enumerate(sel):
                c = int(capped[u])
                ex[i, :c] = order[starts[u]: starts[u] + c]
                rows[i] = uniq[u]
            out[int(cap)] = (rows, ex)
        return out

    for trial in range(5):
        n = int(rng.integers(50, 2000))
        E = int(rng.integers(3, 60))
        ids = rng.integers(0, E, size=n).astype(np.int32)
        lb = int(rng.integers(1, 4))
        ub = None if trial % 2 else int(rng.integers(4, 40))
        got = bkt.build_bucketing(ids, E, lower_bound=lb, upper_bound=ub)
        want = reference(ids, E, lb, ub)
        assert {b.capacity for b in got.buckets} == set(want)
        for b in got.buckets:
            rows, ex = want[b.capacity]
            np.testing.assert_array_equal(b.entity_rows, rows)
            np.testing.assert_array_equal(b.example_idx, ex)


def test_bucket_weights_zero_padding(rng):
    ids = rng.integers(0, 7, size=60).astype(np.int32)
    b = bkt.build_bucketing(ids, 7)
    w = rng.uniform(0.5, 1.5, size=60).astype(np.float32)
    for bucket in b.buckets:
        wb = bkt.bucket_weights(bucket, w)
        assert np.all(wb[bucket.example_idx < 0] == 0.0)
        live = bucket.example_idx >= 0
        np.testing.assert_allclose(wb[live], w[bucket.example_idx[live]])


# ---------------------------------------------------------------- coordinates


def _tiny_game(rng, n=1500, seed_skew=1.1):
    syn = synthetic.game_data(
        rng, n=n, d_global=8,
        re_specs={"userId": (40, 4), "itemId": (25, 3)},
        entity_skew=seed_skew)
    return from_synthetic(syn)


def test_random_effect_bucketed_equals_per_entity_loop(rng, mesh):
    """THE key equivalence: vmapped bucket solves == independent solves."""
    ds = _tiny_game(rng, n=800)
    cfg = _game_config()
    coord = RandomEffectCoordinate(ds, "userId", "re_userId", losses.LOGISTIC,
                                   cfg, mesh)
    offsets = jnp.asarray(ds.offsets)
    model = coord.train_model(offsets)
    W = np.asarray(model.means)

    ids = ds.entity_ids["userId"]
    X = ds.feature_shards["re_userId"]
    for e in np.unique(ids)[:10]:
        m = ids == e
        batch = LabeledBatch.build(X[m], ds.response[m], ds.weights[m],
                                   np.asarray(offsets)[m])
        coef, _ = local_problem.run(
            losses.LOGISTIC, batch, cfg,
            intercept_index=ds.intercept_index["re_userId"])
        np.testing.assert_allclose(W[e], coef.means, rtol=2e-2, atol=2e-2)


def test_random_effect_untrained_entities_score_zero(rng, mesh):
    ds = _tiny_game(rng, n=300)
    cfg = _game_config()
    coord = RandomEffectCoordinate(ds, "userId", "re_userId", losses.LOGISTIC,
                                   cfg, mesh, lower_bound=10)
    model = coord.train_model(jnp.asarray(ds.offsets))
    W = np.asarray(model.means)
    untrained = ~coord.bucketing.trained_entities
    assert untrained.any()  # skewed data: some users have <10 samples
    assert np.all(W[untrained] == 0.0)
    # Scores for examples of untrained entities are exactly 0.
    s = np.asarray(coord.score(model))
    mask = untrained[ds.entity_ids["userId"]]
    assert np.all(s[mask] == 0.0)


def test_squared_loss_bucket_solve_matches_ridge_closed_form(rng, mesh):
    """Squared loss + L2 makes every entity's problem a ridge regression:
    the iterative vmapped bucket solve must land on the normal equations'
    solution (X^T W X + lam*mask)^-1 X^T W (y - o), worked here in float64
    numpy per entity — offsets, weights and the unpenalized intercept
    included."""
    ds = _tiny_game(rng, n=900)
    ds.response = rng.normal(size=ds.num_rows).astype(np.float32)
    ds.weights = rng.uniform(0.5, 2.0, ds.num_rows).astype(np.float32)
    lam = 0.5
    coord = RandomEffectCoordinate(ds, "userId", "re_userId", losses.SQUARED,
                                   _game_config(l2=lam, max_iter=80), mesh)
    offsets = rng.normal(scale=0.3, size=ds.num_rows).astype(np.float32)
    W = np.asarray(coord.train_model(jnp.asarray(offsets)).means)

    ids = ds.entity_ids["userId"]
    X = np.asarray(ds.feature_shards["re_userId"], np.float64)
    mask = np.ones(X.shape[1])
    if ds.intercept_index["re_userId"] is not None:
        mask[ds.intercept_index["re_userId"]] = 0.0
    trained = np.flatnonzero(coord.bucketing.trained_entities)
    assert len(trained) >= 20
    for e in trained:
        m = ids == e
        Xe, we = X[m], ds.weights[m].astype(np.float64)
        A = Xe.T @ (we[:, None] * Xe) + lam * np.diag(mask)
        rhs = Xe.T @ (we * (ds.response[m] - offsets[m]))
        np.testing.assert_allclose(W[e], np.linalg.solve(A, rhs),
                                   rtol=2e-3, atol=2e-3, err_msg=str(e))


def test_fixed_effect_coordinate_trains_and_scores(rng, mesh):
    ds = _tiny_game(rng, n=1000)
    coord = FixedEffectCoordinate(ds, "global", losses.LOGISTIC,
                                  _game_config(), mesh)
    model = coord.train_model(jnp.asarray(ds.offsets))
    s = np.asarray(coord.score(model))
    assert s.shape == (1000,)
    a = float(ev.auc(jnp.asarray(s), jnp.asarray(ds.response)))
    assert a > 0.6  # global effects alone predict something


# ----------------------------------------------------------- coordinate descent


def _build_coordinates(ds, mesh, l2_fixed=1.0, l2_re=1.0):
    return {
        "fixed": FixedEffectCoordinate(ds, "global", losses.LOGISTIC,
                                       _game_config(l2_fixed), mesh),
        "per-user": RandomEffectCoordinate(ds, "userId", "re_userId",
                                           losses.LOGISTIC,
                                           _game_config(l2_re), mesh),
        "per-item": RandomEffectCoordinate(ds, "itemId", "re_itemId",
                                           losses.LOGISTIC,
                                           _game_config(l2_re), mesh),
    }


def test_coordinate_descent_improves_auc(rng, mesh):
    ds = _tiny_game(rng, n=2000)
    coords = _build_coordinates(ds, mesh)
    y = jnp.asarray(ds.response)

    # Fixed-effect-only baseline:
    fixed_only, _ = descent.run(
        TaskType.LOGISTIC_REGRESSION, coords,
        descent.CoordinateDescentConfig(["fixed"], iterations=1))
    auc_fixed = float(ev.auc(fixed_only.score(ds), y))

    full, hist = descent.run(
        TaskType.LOGISTIC_REGRESSION, coords,
        descent.CoordinateDescentConfig(["fixed", "per-user", "per-item"],
                                        iterations=2))
    auc_full = float(ev.auc(full.score(ds), y))
    # Random effects must add real lift on per-entity data (GLMix claim).
    assert auc_full > auc_fixed + 0.03, (auc_fixed, auc_full)
    assert len(hist.records) == 6


def test_coordinate_descent_iterations_converge(rng, mesh):
    ds = _tiny_game(rng, n=1200)
    coords = _build_coordinates(ds, mesh)
    vals = []
    model, hist = descent.run(
        TaskType.LOGISTIC_REGRESSION, coords,
        descent.CoordinateDescentConfig(["fixed", "per-user"], iterations=3),
        validation_fn=lambda m: {
            "auc": float(ev.auc(m.score(ds), jnp.asarray(ds.response)))})
    aucs = [r["validation"]["auc"] for r in hist.records]
    # Later sweeps shouldn't degrade the training AUC materially.
    assert aucs[-1] >= aucs[0] - 1e-3


def test_warm_start_and_locked_coordinates(rng, mesh):
    ds = _tiny_game(rng, n=900)
    coords = _build_coordinates(ds, mesh)
    cfg = descent.CoordinateDescentConfig(["fixed", "per-user"], iterations=1)
    model1, _ = descent.run(TaskType.LOGISTIC_REGRESSION, coords, cfg)

    # Warm start: reuse model1's coordinates as initial models.
    model2, _ = descent.run(TaskType.LOGISTIC_REGRESSION, coords, cfg,
                            initial_models=dict(model1.models))
    y = jnp.asarray(ds.response)
    assert float(ev.auc(model2.score(ds), y)) >= float(
        ev.auc(model1.score(ds), y)) - 5e-3

    # Locked: the fixed coordinate must come back bit-identical.
    model3, _ = descent.run(
        TaskType.LOGISTIC_REGRESSION, coords, cfg,
        initial_models=dict(model1.models), locked_coordinates={"fixed"})
    np.testing.assert_array_equal(
        np.asarray(model3.models["fixed"].coefficients.means),
        np.asarray(model1.models["fixed"].coefficients.means))

    # Locked without an initial model is an error.
    with pytest.raises(ValueError):
        descent.run(TaskType.LOGISTIC_REGRESSION, coords, cfg,
                    locked_coordinates={"fixed"})


def test_descent_rejects_unknown_coordinate(rng, mesh):
    ds = _tiny_game(rng, n=300)
    coords = _build_coordinates(ds, mesh)
    with pytest.raises(ValueError):
        descent.run(TaskType.LOGISTIC_REGRESSION, coords,
                    descent.CoordinateDescentConfig(["nope"], iterations=1))


def test_fixed_effect_with_normalization_scores_raw_space(rng, mesh):
    """Regression: GAME models must hold ORIGINAL-space coefficients so that
    GameModel.score / transformer / saved models agree with the training-time
    (transformed-space) margins."""
    from photon_ml_tpu.normalization import NormalizationType, build_normalization

    ds = _tiny_game(rng, n=800)
    X = ds.feature_shards["global"]
    norm = build_normalization(
        NormalizationType.STANDARDIZATION, means=X.mean(0), variances=X.var(0),
        intercept_index=ds.intercept_index["global"])
    coord = FixedEffectCoordinate(ds, "global", losses.LOGISTIC,
                                  _game_config(), mesh, norm=norm)
    model = coord.train_model(jnp.asarray(ds.offsets))
    s_coord = np.asarray(coord.score(model))
    s_model = np.asarray(model.score(ds))  # plain X @ w path
    np.testing.assert_allclose(s_coord, s_model, rtol=1e-4, atol=1e-4)
    # And training with normalization on ill-scaled features actually works:
    a = float(ev.auc(jnp.asarray(s_model), jnp.asarray(ds.response)))
    assert a > 0.6


def test_descent_sync_updates_knob_is_behavior_neutral(rng, mesh):
    """config.sync_updates (auto/forced-on/forced-off) changes only the
    dispatch-stream barrier, never the trained model."""
    ds = _tiny_game(rng, n=600)
    coords = _build_coordinates(ds, mesh)
    outs = []
    for sync in (None, True, False):
        cfg = descent.CoordinateDescentConfig(["fixed", "per-user"],
                                              iterations=2,
                                              sync_updates=sync)
        model, _ = descent.run(TaskType.LOGISTIC_REGRESSION, coords, cfg)
        outs.append(np.asarray(model.models["fixed"].coefficients.means))
    assert np.allclose(outs[0], outs[1])
    assert np.allclose(outs[0], outs[2])
