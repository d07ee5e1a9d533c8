"""Sparse GAME end-to-end (BASELINE config 5, the Criteo regime).

Coverage:
- SparseFixedEffectCoordinate fit == dense FixedEffectCoordinate fit on the
  same (densified) data — the sparse objective is exact, not approximate.
- Full GameEstimator fit over a sparse shard on the 8-device mesh,
  including the feature-sharded (model-axis) configuration and the
  regularization grid.
- Pallas scatter kernel == XLA scatter (interpret mode on CPU).
- Sparse dataset save/load round trip through the CLI's container format.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.api.configs import (CoordinateConfiguration,
                                       FixedEffectDataConfiguration)
from photon_ml_tpu.api.estimator import GameEstimator
from photon_ml_tpu.data import sparse as sp
from photon_ml_tpu.data.game_data import (GameDataset, SparseShard,
                                          from_sparse_batch)
from photon_ml_tpu.data.io import load_game_dataset, save_game_dataset
from photon_ml_tpu.game.coordinates import (FixedEffectCoordinate,
                                            RandomEffectCoordinate,
                                            SparseFixedEffectCoordinate)
from photon_ml_tpu.ops import losses
from photon_ml_tpu.optim import OptimizerConfig
from photon_ml_tpu.optim.problem import (GLMOptimizationConfiguration,
                                         VarianceComputationType)
from photon_ml_tpu.optim.regularization import (RegularizationContext,
                                                RegularizationType)
from photon_ml_tpu.parallel.mesh import make_mesh
from photon_ml_tpu.types import TaskType


@pytest.fixture(scope="module")
def mesh():
    return make_mesh()


def _sparse_data(n=1024, d=64, nnz=6, seed=0):
    batch, w_true = sp.synthetic_sparse(n, d, nnz, seed=seed, zipf=False)
    return batch, w_true


def _densify(batch) -> np.ndarray:
    n, d = batch.num_rows, batch.num_features
    X = np.zeros((n, d + 1), np.float32)
    rows = np.repeat(np.arange(n), batch.max_nnz)
    np.add.at(X, (rows, np.asarray(batch.indices).reshape(-1)),
              np.asarray(batch.values).reshape(-1))
    return X[:, :d]


def _opt(l2=1.0, max_iter=80):
    return GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(max_iterations=max_iter, tolerance=1e-8),
        regularization=RegularizationContext(RegularizationType.L2, l2))


def test_sparse_coordinate_matches_dense(mesh):
    batch, _ = _sparse_data()
    sparse_ds = from_sparse_batch(batch)
    dense_ds = dataclasses.replace(
        sparse_ds, feature_shards={"global": _densify(batch)})
    cfg = _opt()
    dense = FixedEffectCoordinate(
        dense_ds, "global", losses.LOGISTIC, cfg, mesh)
    sparse = SparseFixedEffectCoordinate(
        sparse_ds, "global", losses.LOGISTIC, cfg, mesh)
    off = np.zeros(batch.num_rows, np.float32)
    m_dense = dense.train_model(off)
    m_sparse = sparse.train_model(off)
    np.testing.assert_allclose(
        np.asarray(m_sparse.coefficients.means),
        np.asarray(m_dense.coefficients.means), rtol=1e-3, atol=1e-4)
    # Scores agree too (gather margins == matmul margins).
    np.testing.assert_allclose(np.asarray(sparse.score(m_sparse)),
                               np.asarray(dense.score(m_sparse)),
                               rtol=1e-4, atol=1e-4)


def test_sparse_coordinate_feature_sharded_matches(mesh):
    batch, _ = _sparse_data(d=67)  # not a multiple of the model axis
    ds = from_sparse_batch(batch)
    cfg = _opt()
    # hybrid=False pins the replicated ELL formulation so this compares
    # the SAME objective evaluation with and without the model-axis
    # sharding (the hybrid layout sums in a different order).
    plain = SparseFixedEffectCoordinate(
        ds, "global", losses.LOGISTIC, cfg, mesh, hybrid=False)
    sharded = SparseFixedEffectCoordinate(
        ds, "global", losses.LOGISTIC, cfg, mesh, feature_sharded=True)
    off = np.zeros(batch.num_rows, np.float32)
    w_a = np.asarray(plain.train_model(off).coefficients.means)
    w_b = np.asarray(sharded.train_model(off).coefficients.means)
    assert w_a.shape == w_b.shape == (67,)
    np.testing.assert_allclose(w_a, w_b, rtol=1e-3, atol=1e-4)


def test_sparse_game_estimator_end_to_end(mesh):
    batch, _ = sp.synthetic_sparse(2048, 64, 16, seed=0, zipf=False,
                                   noise=0.1)
    ds = from_sparse_batch(batch)
    cc = {"fixed": CoordinateConfiguration(
        data=FixedEffectDataConfiguration("global"),
        optimization=_opt(),
        reg_weight_grid=(0.1, 1.0))}
    est = GameEstimator(TaskType.LOGISTIC_REGRESSION, cc, ["fixed"], mesh,
                        validation_evaluators=["AUC"])
    results = est.fit(ds, validation_data=ds)
    assert len(results) == 2
    best = est.select_best_model(results)
    assert best.evaluation.metrics["AUC"] > 0.7


def test_sparse_variances_simple(mesh):
    batch, _ = _sparse_data(n=512, d=24)
    ds = from_sparse_batch(batch)
    cfg = dataclasses.replace(
        _opt(), variance_computation=VarianceComputationType.SIMPLE)
    coord = SparseFixedEffectCoordinate(
        ds, "global", losses.LOGISTIC, cfg, mesh)
    off = np.zeros(batch.num_rows, np.float32)
    model = coord.train_model(off)
    model = coord.compute_model_variances(model, off)
    var = np.asarray(model.coefficients.variances)
    assert var.shape == (24,)
    assert np.all(var > 0)
    # Cross-check against the densified Hessian diagonal.
    X = _densify(batch)
    z = X @ np.asarray(model.coefficients.means)
    p = 1.0 / (1.0 + np.exp(-z))
    diag = (X * X * (p * (1 - p))[:, None]).sum(0) + 1.0  # + l2
    np.testing.assert_allclose(var, 1.0 / diag, rtol=2e-2, atol=1e-5)


def _sparse_re_data(n=2048, d=96, num_entities=24, nnz=5, seed=3,
                    intercept=True):
    """Sparse random-effect dataset with planted per-entity effects.

    Returns (sparse GameDataset, densified GameDataset) over one shard
    ``re`` keyed by ``userId``; labels depend on entity-specific weights so
    the random effect is identifiable.
    """
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, num_entities, n).astype(np.int32)
    idx = np.sort(rng.integers(0, d - 1 if intercept else d,
                               (n, nnz)).astype(np.int32), axis=1)
    # Canonicalize (ELL contract): duplicate columns become padding.
    dup = np.zeros_like(idx, bool)
    dup[:, 1:] = idx[:, 1:] == idx[:, :-1]
    vals = rng.normal(size=(n, nnz)).astype(np.float32)
    idx[dup] = d
    vals[dup] = 0.0
    if intercept:
        idx = np.concatenate([idx, np.full((n, 1), d - 1, np.int32)], axis=1)
        vals = np.concatenate([vals, np.ones((n, 1), np.float32)], axis=1)
    W_true = rng.normal(size=(num_entities, d)).astype(np.float32)
    margin = np.einsum(
        "nk,nk->n", vals,
        np.where(idx < d, W_true[ids[:, None], np.minimum(idx, d - 1)], 0.0))
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-margin))).astype(np.float32)
    shard = SparseShard(indices=idx, values=vals, num_features=d)
    base = dict(
        response=y, offsets=np.zeros(n, np.float32),
        weights=np.ones(n, np.float32),
        entity_ids={"userId": ids}, num_entities={"userId": num_entities},
        intercept_index={"re": d - 1 if intercept else None})
    sparse_ds = GameDataset(feature_shards={"re": shard}, **base)
    X = np.zeros((n, d), np.float32)
    np.add.at(X, (np.repeat(np.arange(n), idx.shape[1]),
                  np.minimum(idx, d - 1).reshape(-1)),
              np.where(idx < d, vals, 0.0).reshape(-1))
    dense_ds = GameDataset(feature_shards={"re": X}, **base)
    return sparse_ds, dense_ds


def test_sparse_random_effect_matches_densified_projection(mesh):
    """Sparse RE staging is exact: same fit as the dense projected path."""
    sparse_ds, dense_ds = _sparse_re_data()
    cfg = dataclasses.replace(
        _opt(), variance_computation=VarianceComputationType.SIMPLE)
    c_sparse = RandomEffectCoordinate(
        sparse_ds, "userId", "re", losses.LOGISTIC, cfg, mesh)
    assert c_sparse.projection  # implied by the sparse shard
    c_dense = RandomEffectCoordinate(
        dense_ds, "userId", "re", losses.LOGISTIC, cfg, mesh,
        projection=True)
    off = np.zeros(sparse_ds.num_rows, np.float32)
    m_sparse = c_sparse.train_model(off)
    m_dense = c_dense.train_model(off)
    np.testing.assert_allclose(np.asarray(m_sparse.means),
                               np.asarray(m_dense.means),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(c_sparse.score(m_sparse)),
                               np.asarray(c_dense.score(m_dense)),
                               rtol=1e-4, atol=1e-5)
    v_sparse = c_sparse.compute_model_variances(m_sparse, off)
    v_dense = c_dense.compute_model_variances(m_dense, off)
    np.testing.assert_allclose(np.asarray(v_sparse.variances),
                               np.asarray(v_dense.variances),
                               rtol=1e-4, atol=1e-6)
    # Model-level scoring agrees too (the CLI/validation path).
    np.testing.assert_allclose(np.asarray(m_sparse.score(sparse_ds)),
                               np.asarray(m_dense.score(dense_ds)),
                               rtol=1e-4, atol=1e-5)


def test_sparse_random_effect_pearson_ratio_matches_densified(mesh):
    """features_to_samples_ratio filters identically on sparse and dense."""
    sparse_ds, dense_ds = _sparse_re_data(n=1024, d=48, num_entities=8,
                                          seed=11)
    kw = dict(features_to_samples_ratio=0.2)
    c_sparse = RandomEffectCoordinate(
        sparse_ds, "userId", "re", losses.LOGISTIC, _opt(), mesh, **kw)
    c_dense = RandomEffectCoordinate(
        dense_ds, "userId", "re", losses.LOGISTIC, _opt(), mesh, **kw)
    off = np.zeros(sparse_ds.num_rows, np.float32)
    np.testing.assert_allclose(
        np.asarray(c_sparse.train_model(off).means),
        np.asarray(c_dense.train_model(off).means), rtol=1e-4, atol=1e-5)


def test_sparse_random_effect_large_d_never_densifies(mesh):
    """A d=100k sparse RE shard fits without the (n, d) dense matrix ever
    existing (it would be 1.6 GB here; the buckets stage at d_active ≤
    a few hundred) and recovers planted per-entity structure."""
    rng = np.random.default_rng(7)
    n, d, E, nnz = 4096, 100_000, 48, 6
    ids = rng.integers(0, E, n).astype(np.int32)
    # Each entity draws features from its own small column pool, so active
    # sets stay small and the planted effect is learnable.
    pools = rng.integers(0, d, (E, 64)).astype(np.int32)
    idx = np.sort(pools[ids[:, None],
                        rng.integers(0, 64, (n, nnz))], axis=1)
    dup = np.zeros_like(idx, bool)
    dup[:, 1:] = idx[:, 1:] == idx[:, :-1]
    vals = rng.normal(size=(n, nnz)).astype(np.float32)
    idx[dup] = d
    vals[dup] = 0.0
    w_pool = rng.normal(size=(E, 64)).astype(np.float32)
    margin = np.zeros(n, np.float32)
    for k in range(nnz):
        live = idx[:, k] < d
        match = pools[ids] == idx[:, k][:, None]  # (n, 64)
        coef = np.where(match, w_pool[ids], 0.0).sum(1)
        margin += np.where(live, vals[:, k] * coef, 0.0)
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-margin))).astype(np.float32)
    ds = GameDataset(
        response=y, offsets=np.zeros(n, np.float32),
        weights=np.ones(n, np.float32),
        feature_shards={"re": SparseShard(indices=idx, values=vals,
                                          num_features=d)},
        entity_ids={"userId": ids}, num_entities={"userId": E},
        intercept_index={})
    coord = RandomEffectCoordinate(ds, "userId", "re", losses.LOGISTIC,
                                   _opt(l2=0.3, max_iter=40), mesh)
    for arrays in coord._bucket_data:
        assert arrays[0].shape[-1] <= 1024  # staged width ≪ d
    model = coord.train_model(np.zeros(n, np.float32))
    s = np.asarray(coord.score(model))
    auc_num = (s[y > 0][:, None] > s[y == 0][None, :]).mean()
    assert auc_num > 0.8
    W = np.asarray(model.means)
    # Coefficients only on (a subset of) each entity's active columns.
    for e in range(0, E, 7):
        active = np.unique(idx[(ids == e)][idx[ids == e] < d])
        nz = np.flatnonzero(W[e])
        assert np.isin(nz, active).all()


def test_sparse_random_effect_rejects_normalization(mesh):
    from photon_ml_tpu.normalization import NormalizationContext

    sparse_ds, _ = _sparse_re_data(n=256, d=16, num_entities=4)
    with pytest.raises(ValueError, match="normalization"):
        RandomEffectCoordinate(
            sparse_ds, "userId", "re", losses.LOGISTIC, _opt(), mesh,
            norm=NormalizationContext(
                factors=np.ones(16, np.float32),
                intercept_index=15))


def test_sparse_random_effect_through_estimator(mesh):
    from photon_ml_tpu.api.configs import RandomEffectDataConfiguration

    sparse_ds, _ = _sparse_re_data(n=2048, d=64, num_entities=16, seed=5)
    cc = {"per-user": CoordinateConfiguration(
        data=RandomEffectDataConfiguration("userId", "re"),
        optimization=_opt())}
    est = GameEstimator(TaskType.LOGISTIC_REGRESSION, cc, ["per-user"],
                        mesh, validation_evaluators=["AUC"])
    results = est.fit(sparse_ds, validation_data=sparse_ds)
    assert results[0].evaluation.metrics["AUC"] > 0.75


@pytest.fixture(scope="module")
def mesh1():
    """Single-device mesh: the hybrid fast path's regime."""
    import jax
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))


def _intercepted(batch):
    """Append an all-ones intercept column (id = d) to an ELL batch."""
    d = batch.num_features
    idx = np.concatenate(
        [np.asarray(batch.indices),
         np.full((batch.num_rows, 1), d, np.int32)], axis=1)
    vals = np.concatenate(
        [np.asarray(batch.values),
         np.ones((batch.num_rows, 1), np.float32)], axis=1)
    return dataclasses.replace(batch, indices=idx, values=vals,
                               num_features=d + 1)


def _ell_objective(batch, w, l2=0.0, l1=0.0, intercept=None,
                   weights=None):
    """Reference regularized objective evaluated through the ELL ops
    (both layouts must minimize this same function)."""
    from photon_ml_tpu.ops import sparse_aggregators as sagg

    b = batch if weights is None else dataclasses.replace(
        batch, weights=weights)
    v, _ = sagg.value_and_gradient(losses.LOGISTIC, jnp.asarray(w), b)
    mask = np.ones(len(w), np.float32)
    if intercept is not None:
        mask[intercept] = 0.0
    return (float(v) + 0.5 * l2 * float(np.sum((w * mask) ** 2))
            + l1 * float(np.sum(np.abs(w * mask))))


def test_hybrid_coordinate_matches_ell(mesh1):
    """The hybrid hot/cold layout minimizes the SAME objective as the ELL
    pipeline (values equal at both solutions; coefficients agree up to
    optimizer path sensitivity) and the SIMPLE variance computation is
    exact at a shared model."""
    batch, _ = sp.synthetic_sparse(2048, 256, 8, seed=4)  # zipf head
    batch = _intercepted(batch)
    ds = from_sparse_batch(batch)
    ds = dataclasses.replace(ds, intercept_index={"global": 256})
    cfg = dataclasses.replace(
        _opt(), variance_computation=VarianceComputationType.SIMPLE)
    ell = SparseFixedEffectCoordinate(
        ds, "global", losses.LOGISTIC, cfg, mesh1, hybrid=False)
    hyb = SparseFixedEffectCoordinate(
        ds, "global", losses.LOGISTIC, cfg, mesh1)
    assert hyb.hybrid and not ell.hybrid
    off = np.zeros(batch.num_rows, np.float32)
    m_ell = ell.train_model(off)
    m_hyb = hyb.train_model(off)
    w_e = np.asarray(m_ell.coefficients.means)
    w_h = np.asarray(m_hyb.coefficients.means)
    f_e = _ell_objective(batch, w_e, l2=1.0, intercept=256)
    f_h = _ell_objective(batch, w_h, l2=1.0, intercept=256)
    assert abs(f_e - f_h) < 1e-5 * abs(f_e), (f_e, f_h)
    np.testing.assert_allclose(w_h, w_e, rtol=0.1, atol=1e-3)
    # Scores at the SAME model agree exactly (scoring-path equivalence).
    np.testing.assert_allclose(np.asarray(hyb.score(m_ell)),
                               np.asarray(ell.score(m_ell)),
                               rtol=1e-4, atol=1e-4)
    # Variances at the SAME model: exact path equivalence.
    v_ell = ell.compute_model_variances(m_ell, off)
    v_hyb = hyb.compute_model_variances(m_ell, off)
    np.testing.assert_allclose(
        np.asarray(v_hyb.coefficients.variances),
        np.asarray(v_ell.coefficients.variances), rtol=1e-4, atol=1e-7)


def test_hybrid_matches_ell_owlqn_l1(mesh1):
    """L1/OWL-QN in the permuted space: the intercept's exemption follows
    the permutation and both layouts reach the same L1 objective."""
    from photon_ml_tpu.optim import OptimizerType

    batch, _ = sp.synthetic_sparse(1024, 128, 6, seed=6)
    batch = _intercepted(batch)
    ds = from_sparse_batch(batch)
    ds = dataclasses.replace(ds, intercept_index={"global": 128})
    cfg = GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(optimizer_type=OptimizerType.OWLQN,
                                  max_iterations=80, tolerance=1e-8),
        regularization=RegularizationContext(RegularizationType.L1, 0.5))
    off = np.zeros(batch.num_rows, np.float32)
    w_ell = np.asarray(SparseFixedEffectCoordinate(
        ds, "global", losses.LOGISTIC, cfg, mesh1,
        hybrid=False).train_model(off).coefficients.means)
    w_hyb = np.asarray(SparseFixedEffectCoordinate(
        ds, "global", losses.LOGISTIC, cfg, mesh1,
        hybrid=True).train_model(off).coefficients.means)
    f_e = _ell_objective(batch, w_ell, l1=0.5, intercept=128)
    f_h = _ell_objective(batch, w_hyb, l1=0.5, intercept=128)
    assert abs(f_e - f_h) < 1e-4 * abs(f_e), (f_e, f_h)
    # L1 actually sparsified (sanity that the orthant path ran).
    assert (np.abs(w_hyb) < 1e-8).sum() > 0


def test_hybrid_down_sampling_matches_ell(mesh1):
    """Weight-masked down-sampling == the ELL path's row-gathered subsets
    (same seed ⇒ same draws ⇒ identical subsampled objective)."""
    batch, _ = sp.synthetic_sparse(2048, 64, 6, seed=7)
    ds = from_sparse_batch(batch)
    cfg = dataclasses.replace(_opt(), down_sampling_rate=0.5)
    off = np.zeros(batch.num_rows, np.float32)
    coords = {
        name: SparseFixedEffectCoordinate(
            ds, "global", losses.LOGISTIC, cfg, mesh1, hybrid=h,
            down_sampling_seed=9)
        for name, h in (("ell", False), ("hyb", True))}
    w = {k: np.asarray(c.train_model(off).coefficients.means)
         for k, c in coords.items()}
    # Reconstruct the draw both coordinates made (same seed, same order).
    from photon_ml_tpu.game.sampling import binary_classification_down_sample
    idx, mult = binary_classification_down_sample(
        np.random.default_rng(9), ds.response, 0.5)
    w_mask = np.zeros(ds.num_rows, np.float32)
    w_mask[idx] = np.asarray(ds.weights)[idx] * np.asarray(mult)
    f_e = _ell_objective(batch, w["ell"], l2=1.0, weights=jnp.asarray(w_mask))
    f_h = _ell_objective(batch, w["hyb"], l2=1.0, weights=jnp.asarray(w_mask))
    assert abs(f_e - f_h) < 1e-5 * abs(f_e), (f_e, f_h)


def test_hybrid_auto_selection(mesh, mesh1):
    """auto: hybrid whenever coefficients replicate — single-device uses
    the single layout, a sharded data axis the HybridShards composition;
    only feature_sharded (no replicated permuted space) keeps ELL."""
    batch, _ = _sparse_data(n=256, d=32)
    ds = from_sparse_batch(batch)
    c1 = SparseFixedEffectCoordinate(
        ds, "global", losses.LOGISTIC, _opt(), mesh1)
    assert c1.hybrid and not c1._hybrid_sharded
    c8 = SparseFixedEffectCoordinate(
        ds, "global", losses.LOGISTIC, _opt(), mesh)
    assert c8.hybrid and c8._hybrid_sharded
    assert not SparseFixedEffectCoordinate(
        ds, "global", losses.LOGISTIC, _opt(), mesh,
        feature_sharded=True).hybrid
    with pytest.raises(ValueError, match="feature_sharded"):
        SparseFixedEffectCoordinate(
            ds, "global", losses.LOGISTIC, _opt(), mesh1,
            feature_sharded=True, hybrid=True)


def test_hybrid_sharded_matches_ell(mesh, mesh1):
    """The data-sharded hybrid composition (HybridShards) minimizes the
    SAME objective as the ELL pipeline and the single-device hybrid
    layout, with exact scoring/variance path equivalence — the P3
    composition the single-shard layout could not cover."""
    batch, _ = sp.synthetic_sparse(2049, 256, 8, seed=4)  # odd: pad rows
    batch = _intercepted(batch)
    ds = from_sparse_batch(batch)
    ds = dataclasses.replace(ds, intercept_index={"global": 256})
    cfg = dataclasses.replace(
        _opt(), variance_computation=VarianceComputationType.SIMPLE)
    ell = SparseFixedEffectCoordinate(
        ds, "global", losses.LOGISTIC, cfg, mesh, hybrid=False)
    hyb = SparseFixedEffectCoordinate(
        ds, "global", losses.LOGISTIC, cfg, mesh)
    one = SparseFixedEffectCoordinate(
        ds, "global", losses.LOGISTIC, cfg, mesh1)
    assert hyb.hybrid and hyb._hybrid_sharded
    off = np.zeros(batch.num_rows, np.float32)
    m_ell = ell.train_model(off)
    m_hyb = hyb.train_model(off)
    w_e = np.asarray(m_ell.coefficients.means)
    w_h = np.asarray(m_hyb.coefficients.means)
    f_e = _ell_objective(batch, w_e, l2=1.0, intercept=256)
    f_h = _ell_objective(batch, w_h, l2=1.0, intercept=256)
    assert abs(f_e - f_h) < 1e-5 * abs(f_e), (f_e, f_h)
    np.testing.assert_allclose(w_h, w_e, rtol=0.1, atol=1e-3)
    # Scores at the SAME model: all three layouts agree exactly.
    np.testing.assert_allclose(np.asarray(hyb.score(m_ell)),
                               np.asarray(ell.score(m_ell)),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(hyb.score(m_ell)),
                               np.asarray(one.score(m_ell)),
                               rtol=1e-4, atol=1e-4)
    # Variances at the SAME model: exact path equivalence.
    v_ell = ell.compute_model_variances(m_ell, off)
    v_hyb = hyb.compute_model_variances(m_ell, off)
    np.testing.assert_allclose(
        np.asarray(v_hyb.coefficients.variances),
        np.asarray(v_ell.coefficients.variances), rtol=1e-4, atol=1e-7)


def test_hybrid_sharded_objective_is_exact(mesh):
    """Raw value/gradient/margins of the sharded hybrid objective equal
    the single-device hybrid layout's and the ELL shard_map pipeline's at
    an arbitrary w — the composition is exact, not approximate."""
    import jax.numpy as jnp

    from photon_ml_tpu.ops import hybrid_sparse as hs
    from photon_ml_tpu.parallel import sparse_objective as sobj
    from photon_ml_tpu.parallel import sparse_problem as spp

    batch, _ = sp.synthetic_sparse(1023, 300, 8, seed=0, zipf=True)
    d = batch.num_features
    w = np.random.default_rng(1).normal(size=d).astype(np.float32)

    hb = hs.build_hybrid(batch)
    shb = spp.shard_hybrid(hs.build_hybrid_shards(batch, 8), mesh)
    v1, g1 = hs.value_and_gradient(
        losses.LOGISTIC, hs.to_permuted_space(hb, jnp.asarray(w)), hb)
    g1 = np.asarray(hs.to_original_space(hb, g1))
    w8p = jnp.asarray(w)[shb.perm]
    v8, g8 = sobj.make_hybrid_value_and_gradient(
        losses.LOGISTIC, mesh, shb)(w8p)
    g8 = np.asarray(g8)[np.asarray(shb.inv_perm)]
    vE, gE = sobj.make_value_and_gradient(
        losses.LOGISTIC, mesh, spp.shard_sparse_batch(batch, mesh))(
        jnp.asarray(w))
    assert abs(float(v1) - float(v8)) < 1e-5 * abs(float(v1))
    assert abs(float(vE) - float(v8)) < 1e-5 * abs(float(vE))
    np.testing.assert_allclose(g8, g1, rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(g8, np.asarray(gE), rtol=1e-3, atol=1e-4)
    m1 = np.asarray(hs.margins(hb, hs.to_permuted_space(hb, jnp.asarray(w))))
    m8 = np.asarray(sobj.make_hybrid_margins(mesh, shb)(w8p))[:batch.num_rows]
    np.testing.assert_allclose(m8, m1, rtol=1e-4, atol=1e-5)


def test_hybrid_sharded_down_sampling_matches(mesh, mesh1):
    """Same seed ⇒ same subsampled objective across the sharded and
    single-device hybrid layouts (flat padded row order == original row
    order, so the weight mask lands on the same rows)."""
    batch, _ = sp.synthetic_sparse(2048, 64, 6, seed=7)
    ds = from_sparse_batch(batch)
    cfg = dataclasses.replace(_opt(), down_sampling_rate=0.5)
    off = np.zeros(batch.num_rows, np.float32)
    w = {}
    for name, m in (("one", mesh1), ("sharded", mesh)):
        w[name] = np.asarray(SparseFixedEffectCoordinate(
            ds, "global", losses.LOGISTIC, cfg, m,
            down_sampling_seed=9).train_model(off).coefficients.means)
    from photon_ml_tpu.game.sampling import binary_classification_down_sample
    idx, mult = binary_classification_down_sample(
        np.random.default_rng(9), ds.response, 0.5)
    w_mask = np.zeros(ds.num_rows, np.float32)
    w_mask[idx] = np.asarray(ds.weights)[idx] * np.asarray(mult)
    f_1 = _ell_objective(batch, w["one"], l2=1.0, weights=jnp.asarray(w_mask))
    f_8 = _ell_objective(batch, w["sharded"], l2=1.0,
                         weights=jnp.asarray(w_mask))
    assert abs(f_1 - f_8) < 1e-5 * abs(f_1), (f_1, f_8)


def test_hybrid_layout_roundtrip():
    """build_hybrid partitions every nonzero exactly once and the permuted
    margins/gradient match a dense reference."""
    import jax.numpy as jnp

    from photon_ml_tpu.ops import hybrid_sparse as hs

    rng = np.random.default_rng(11)
    batch, _ = sp.synthetic_sparse(512, 96, 5, seed=11)
    hb = hs.build_hybrid(batch, hot_threshold=20)
    X = _densify(batch)
    w = rng.normal(size=96).astype(np.float32)
    wp = hs.to_permuted_space(hb, jnp.asarray(w))
    np.testing.assert_allclose(
        np.asarray(hs.to_original_space(hb, wp)), w, rtol=0, atol=0)
    z = np.asarray(hs.margins(hb, wp))
    np.testing.assert_allclose(z, X @ w, rtol=1e-4, atol=1e-4)
    r = rng.normal(size=512).astype(np.float32)
    from photon_ml_tpu.ops.hybrid_sparse import row_gradient
    g = np.asarray(hs.to_original_space(hb, row_gradient(hb, jnp.asarray(r))))
    np.testing.assert_allclose(g, r @ X, rtol=1e-3, atol=1e-3)


def test_pallas_scatter_matches_xla():
    from photon_ml_tpu.ops.pallas_sparse import scatter_rowterm

    rng = np.random.default_rng(1)
    n, k, d = 333, 7, 200  # non-tile-aligned everywhere
    idx = rng.integers(0, d + 1, (n, k)).astype(np.int32)
    rv = rng.normal(size=(n, k)).astype(np.float32)
    rv[idx == d] = 0.0
    ref = np.zeros(d + 1, np.float32)
    np.add.at(ref, idx.reshape(-1), rv.reshape(-1))
    out = np.asarray(scatter_rowterm(idx, rv, d, interpret=True))
    np.testing.assert_allclose(out, ref[:d], rtol=1e-5, atol=1e-5)


def test_sparse_dataset_roundtrip(tmp_path):
    batch, _ = _sparse_data(n=128, d=32)
    ds = from_sparse_batch(batch)
    save_game_dataset(ds, str(tmp_path / "ds"))
    back = load_game_dataset(str(tmp_path / "ds"))
    shard = back.feature_shards["global"]
    assert isinstance(shard, SparseShard)
    assert shard.num_features == 32
    np.testing.assert_array_equal(shard.indices,
                                  ds.feature_shards["global"].indices)
    np.testing.assert_allclose(shard.values,
                               ds.feature_shards["global"].values)


def test_sparse_subset():
    batch, _ = _sparse_data(n=100, d=16)
    ds = from_sparse_batch(batch)
    sub = ds.subset(np.arange(10))
    assert sub.feature_shards["global"].indices.shape[0] == 10
    assert sub.shard_dim("global") == 16


def test_avro_reader_sparse_shard(tmp_path):
    """AvroDataReader with FeatureShardConfig(sparse=True) builds an ELL
    SparseShard identical in content to the dense read."""
    from photon_ml_tpu.avro import schemas
    from photon_ml_tpu.avro.container import write_records
    from photon_ml_tpu.avro.data_reader import (AvroDataReader,
                                                FeatureShardConfig)

    rng = np.random.default_rng(4)
    recs = []
    for i in range(50):
        feats = [{"name": f"f{j}", "term": "", "value": float(v)}
                 for j, v in zip(rng.choice(20, size=5, replace=False),
                                 rng.normal(size=5))]
        # one duplicated feature to exercise accumulation
        feats.append(dict(feats[0]))
        recs.append({"uid": f"u{i}", "label": float(i % 2),
                     "features": feats})
    path = str(tmp_path / "d.avro")
    write_records(path, schemas.TRAINING_EXAMPLE_AVRO, recs)

    reader = AvroDataReader()
    dense_ds, meta = reader.read(
        path, {"g": FeatureShardConfig(("features",), has_intercept=True)})
    sparse_ds, _ = reader.read(
        path, {"g": FeatureShardConfig(("features",), has_intercept=True,
                                       sparse=True)},
        index_maps=meta.index_maps)

    shard = sparse_ds.feature_shards["g"]
    assert isinstance(shard, SparseShard)
    # Densify the ELL and compare against the dense read exactly.
    n, d = shard.shape
    dense_from_sparse = np.zeros((n, d + 1), np.float32)
    rows = np.repeat(np.arange(n), shard.indices.shape[1])
    np.add.at(dense_from_sparse, (rows, shard.indices.reshape(-1)),
              shard.values.reshape(-1))
    np.testing.assert_allclose(dense_from_sparse[:, :d],
                               dense_ds.feature_shards["g"], rtol=1e-6)
    # Canonical rows: no duplicate indices (dups accumulated at read).
    for i in range(n):
        real = shard.indices[i][shard.indices[i] < d]
        assert len(real) == len(set(real.tolist()))


def test_game_train_accepts_libsvm_file(rng, tmp_path):
    """The training driver takes a LIBSVM file directly as a sparse
    fixed-effect-only dataset (Criteo-style ingestion shortcut)."""
    import json
    import os

    from photon_ml_tpu.cli import game_train
    from photon_ml_tpu.data.libsvm import write_libsvm

    X = (rng.normal(size=(600, 20)) *
         (rng.random((600, 20)) < 0.4)).astype(np.float32)
    w = rng.normal(size=20)
    y = np.where(rng.uniform(size=600) < 1 / (1 + np.exp(-X @ w)), 1, -1)
    tr = str(tmp_path / "tr.txt")
    va = str(tmp_path / "va.txt")
    write_libsvm(tr, X[:480], y[:480])
    write_libsvm(va, X[480:], y[480:])
    out = str(tmp_path / "out")
    summary = game_train.run(game_train.build_parser().parse_args([
        "--train", tr, "--validation", va,
        "--coordinate", "name=fixed,type=fixed,shard=global",
        "--update-sequence", "fixed", "--evaluators", "AUC",
        "--output-dir", out,
    ]))
    assert summary["best_metrics"]["AUC"] > 0.7


def test_staging_cache_roundtrip(mesh, tmp_path):
    """Warm staging (digest-keyed disk cache) skips the projection pass
    and reproduces the cold coordinate exactly — staged arrays, trained
    model, scores, and the subspace join tables."""
    from photon_ml_tpu.utils import events as ev

    sparse_ds, _ = _sparse_re_data()
    cfg = _opt()
    cache = str(tmp_path / "stage")
    seen = []
    ev.default_emitter.register(seen.append)
    try:
        kw = dict(staging_cache_dir=cache, subspace_model=True)
        cold = RandomEffectCoordinate(sparse_ds, "userId", "re",
                                      losses.LOGISTIC, cfg, mesh,
                                      **kw).wait_staged()
        n_staged = sum(1 for e in seen
                       if isinstance(e, ev.StagingShard)
                       and e.source == "staged")
        assert n_staged > 0
        seen.clear()
        warm = RandomEffectCoordinate(sparse_ds, "userId", "re",
                                      losses.LOGISTIC, cfg, mesh,
                                      **kw).wait_staged()
        # No projection work on the warm path: every shard a cache hit.
        shard_events = [e for e in seen if isinstance(e, ev.StagingShard)]
        assert shard_events and all(e.source == "cache"
                                    for e in shard_events)
    finally:
        ev.default_emitter.unregister(seen.append)
    assert len(warm._bucket_data) == len(cold._bucket_data)
    for tc, tw in zip(cold._bucket_data, warm._bucket_data):
        assert len(tc) == len(tw)
        for ac, aw in zip(tc, tw):
            np.testing.assert_array_equal(np.asarray(ac), np.asarray(aw))
    np.testing.assert_array_equal(cold.subspace_cols, warm.subspace_cols)
    np.testing.assert_array_equal(np.asarray(cold._sp_flatpos),
                                  np.asarray(warm._sp_flatpos))
    off = np.zeros(sparse_ds.num_rows, np.float32)
    m_cold = cold.train_model(off)
    m_warm = warm.train_model(off)
    np.testing.assert_array_equal(np.asarray(m_cold.means),
                                  np.asarray(m_warm.means))
    np.testing.assert_array_equal(np.asarray(cold.score(m_cold)),
                                  np.asarray(warm.score(m_warm)))


def test_staging_cache_keys_on_content(mesh, tmp_path):
    """Different data or staging params never hit the same cache entry."""
    from photon_ml_tpu.game import staging_cache

    sparse_ds, _ = _sparse_re_data()
    other_ds, _ = _sparse_re_data(seed=5)
    cache = str(tmp_path / "stage")
    cfg = _opt()
    c1 = RandomEffectCoordinate(sparse_ds, "userId", "re", losses.LOGISTIC,
                                cfg, mesh, staging_cache_dir=cache)
    c2 = RandomEffectCoordinate(other_ds, "userId", "re", losses.LOGISTIC,
                                cfg, mesh, staging_cache_dir=cache)
    c3 = RandomEffectCoordinate(sparse_ds, "userId", "re", losses.LOGISTIC,
                                cfg, mesh, staging_cache_dir=cache,
                                upper_bound=2)
    keys = {c._staging_cache_key for c in (c1, c2, c3)}
    assert len(keys) == 3
    # A corrupt entry is a miss, not an error: truncate every array file
    # (once the pipeline's background cache writes have landed).
    import os
    c1.wait_staged()
    entry = os.path.join(cache, c1._staging_cache_key)
    for f in os.listdir(entry):
        if f.endswith(".npy"):
            open(os.path.join(entry, f), "wb").close()
    assert staging_cache.load(cache, c1._staging_cache_key) is None
    c1b = RandomEffectCoordinate(sparse_ds, "userId", "re", losses.LOGISTIC,
                                 cfg, mesh, staging_cache_dir=cache)
    off = np.zeros(sparse_ds.num_rows, np.float32)
    np.testing.assert_allclose(
        np.asarray(c1b.train_model(off).means),
        np.asarray(c1.train_model(off).means), rtol=1e-5, atol=1e-6)
    # ...and the restage REPLACED the poisoned entry (no permanent miss).
    c1b.wait_staged()
    assert staging_cache.load(cache, c1._staging_cache_key) is not None


def test_random_effect_bf16_feature_storage(mesh):
    """bf16 bucket-block storage reproduces the f32 per-entity solves to
    bf16 tolerance, on both the projected (sparse) and dense RE paths,
    with equal AUC on planted effects (the dense fixed path's contract:
    storage shrinks, accumulation stays f32)."""
    sparse_ds, dense_ds = _sparse_re_data()
    cfg = _opt()
    off = np.zeros(sparse_ds.num_rows, np.float32)
    y = np.asarray(sparse_ds.response)
    from photon_ml_tpu.evaluation import evaluators as ev

    for ds_, proj in ((sparse_ds, True), (dense_ds, False)):
        c32 = RandomEffectCoordinate(ds_, "userId", "re", losses.LOGISTIC,
                                     cfg, mesh, projection=proj)
        c16 = RandomEffectCoordinate(ds_, "userId", "re", losses.LOGISTIC,
                                     cfg, mesh, projection=proj,
                                     feature_dtype="bfloat16").wait_staged()
        assert c16._bucket_data[0][0].dtype == jnp.bfloat16
        m32 = c32.train_model(off)
        m16 = c16.train_model(off)
        w32, w16 = np.asarray(m32.means), np.asarray(m16.means)
        # bf16 storage: ~1e-2 relative coefficient deltas are expected.
        np.testing.assert_allclose(w16, w32, rtol=0.3, atol=0.05)
        a32 = float(ev.auc(jnp.asarray(np.asarray(c32.score(m32))),
                           jnp.asarray(y)))
        a16 = float(ev.auc(jnp.asarray(np.asarray(c16.score(m16))),
                           jnp.asarray(y)))
        assert a16 > a32 - 0.01, (proj, a16, a32)

    with pytest.raises(ValueError, match="feature_dtype"):
        RandomEffectCoordinate(sparse_ds, "userId", "re", losses.LOGISTIC,
                               cfg, mesh, feature_dtype="int8")
