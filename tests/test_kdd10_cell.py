"""The KDD Cup 2010 cell, program side, on the CPU: a shard of unit-length
rows of varying length is real-valued, so the resident layout holds its hot
columns as float32 values beside the cold classes (``hot_storage``); the
layout's Hessian-vector product is the dense float64 Xᵀ·D·X·v over both
parts, and its two parts (the rows' curvature, then the product at it) are
that product to the bit; the sparse coordinate's TRON solve reaches the
reference's minimiser of the same logistic objective; its ``opt_iter`` rows
carry each iteration's products and curvatures, the crossings they cost
and ``floor_stop``; and the hot block's budget reckons TRON's own vectors,
not L-BFGS's.

Values, shapes and counts only, never a time.
"""

from __future__ import annotations

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu import obs
from photon_ml_tpu.data.game_data import GameDataset, SparseShard
from photon_ml_tpu.data.sparse import SparseBatch
from photon_ml_tpu.game.coordinates import SparseFixedEffectCoordinate
from photon_ml_tpu.game.coordinates import sparse_fixed
from photon_ml_tpu.obs.ledger import RunLedger, read_rows
from photon_ml_tpu.ops import hybrid_sparse as hs
from photon_ml_tpu.ops import losses
from photon_ml_tpu.optim import OptimizerConfig, OptimizerType
from photon_ml_tpu.optim.problem import GLMOptimizationConfiguration
from photon_ml_tpu.optim.regularization import (RegularizationContext,
                                                RegularizationType)
from photon_ml_tpu.parallel.mesh import make_mesh

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
for _p in (os.path.join(REPO, "benchmark"),
           os.path.join(REPO, "benchmark", "schemas")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import game_kdd10  # noqa: E402  (benchmark/schemas/game_kdd10.py)
import kdd10_reference  # noqa: E402  (benchmark/kdd10_reference.py)

L2 = RegularizationContext(RegularizationType.L2, 1.0)


@pytest.fixture(scope="module")
def one():
    """A mesh of one device: the one-shard hybrid layout the cell runs."""
    return make_mesh(devices=jax.devices()[:1])


@pytest.fixture(scope="module")
def data():
    """The schema's own generator at its selfcheck size: 2,000 rows of 8 to
    64 non-zeros, 36.35 on average, over 5,720 columns."""
    return game_kdd10.make(4100000023, game_kdd10._tiny())


def _one_valued(data):
    """The same rows with every live value 1/sqrt(36): one value a column."""
    live = data.indices < data.num_features
    return np.where(live, np.float32(1 / 6), 0).astype(np.float32)


def _batch(data, values=None):
    n = data.response.shape[0]
    return SparseBatch(
        indices=data.indices,
        values=data.values if values is None else values,
        labels=data.response, weights=np.ones(n, np.float32),
        offsets=np.zeros(n, np.float32), num_features=data.num_features)


def _dense(data) -> np.ndarray:
    X = np.zeros((data.indices.shape[0], data.num_features + 1))
    np.add.at(X, (np.arange(X.shape[0])[:, None], data.indices), data.values)
    return X[:, :-1]


@pytest.mark.parametrize("one_valued, storage", [(False, "float32"),
                                                 (True, "count8")])
def test_unit_length_rows_keep_a_float32_block(data, one_valued, storage):
    """Rows scaled to their own length give every hot column several
    values, so ``_count_hot`` refuses the count block; the same rows with
    one value keep it."""
    values = _one_valued(data) if one_valued else None
    hb = hs.build_hybrid(_batch(data, values), hot_block_bytes=None)
    assert hs.hot_storage(hb) == storage
    assert hb.num_hot > 0 and hb.entries[1] > 0
    assert hb.entries[0] + hb.entries[1] == round(2000 * 36.35)


@pytest.mark.parametrize("hot_threshold", [8, 40])
def test_hessian_vector_is_the_dense_float64_product(data, hot_threshold):
    """Σ l''·(x·v)·x over both parts of the layout, in permuted space,
    against Xᵀ·D·X·v in float64 at a point with margins of both signs."""
    rng = np.random.default_rng(hot_threshold)
    hb = hs.build_hybrid(_batch(data), hot_threshold=hot_threshold)
    assert hs.hot_storage(hb) == "float32" and hb.num_hot > 0
    assert len(hb.cold_rowids) > 0
    X = _dense(data)
    w = rng.normal(size=data.num_features)
    v = rng.normal(size=data.num_features)
    p = 1 / (1 + np.exp(-(X @ w)))
    want = X.T @ (p * (1 - p) * (X @ v))
    got = hs.to_original_space(hb, hs.hessian_vector(
        losses.LOGISTIC,
        hs.to_permuted_space(hb, jnp.asarray(w, jnp.float32)),
        hs.to_permuted_space(hb, jnp.asarray(v, jnp.float32)), hb))
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4,
                               atol=2e-4 * np.abs(want).max())


@pytest.mark.parametrize("hot_threshold", [8, 40, None])
def test_the_product_at_the_curvature_is_the_product(data, hot_threshold):
    """``hessian_vector_at(curvature(w), v)``, the two parts TRON takes a
    CG solve's products in, is the dense float64 Xᵀ·D·X·v and, to the bit,
    ``hessian_vector(w, v)``: over both parts of the layout and with no hot
    block at all (a threshold above every column's count)."""
    rng = np.random.default_rng(7)
    if hot_threshold is None:
        hb = hs.build_hybrid(_batch(data), hot_threshold=10 ** 6)
        assert hb.num_hot == 0
    else:
        hb = hs.build_hybrid(_batch(data), hot_threshold=hot_threshold)
        assert hs.hot_storage(hb) == "float32" and hb.num_hot > 0
    assert len(hb.cold_rowids) > 0
    hb = dataclasses.replace(hb, offsets=jnp.asarray(
        rng.normal(size=data.response.shape[0]), jnp.float32))
    X = _dense(data)
    w = rng.normal(size=data.num_features)
    v = rng.normal(size=data.num_features)
    p = 1 / (1 + np.exp(-(X @ w + np.asarray(hb.offsets, np.float64))))
    want = X.T @ (p * (1 - p) * (X @ v))
    w_p = hs.to_permuted_space(hb, jnp.asarray(w, jnp.float32))
    v_p = hs.to_permuted_space(hb, jnp.asarray(v, jnp.float32))
    split = jax.jit(lambda w, v, hb: hs.hessian_vector_at(
        hs.curvature(losses.LOGISTIC, w, hb), v, hb))(w_p, v_p, hb)
    whole = jax.jit(lambda w, v, hb: hs.hessian_vector(
        losses.LOGISTIC, w, v, hb))(w_p, v_p, hb)
    np.testing.assert_array_equal(np.asarray(split), np.asarray(whole))
    got = hs.to_original_space(hb, split)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4,
                               atol=2e-4 * np.abs(want).max())


def _dataset(data) -> GameDataset:
    n = data.response.shape[0]
    return GameDataset(
        response=data.response, offsets=np.zeros(n, np.float32),
        weights=np.ones(n, np.float32),
        feature_shards={"global": SparseShard(data.indices, data.values,
                                              data.num_features)},
        entity_ids={}, num_entities={}, intercept_index={})


def _tron(max_iterations=25):
    return GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(optimizer_type=OptimizerType.TRON,
                                  max_iterations=max_iterations),
        regularization=L2)


def _rows(tmp_path, train):
    d = str(tmp_path / "ledger")
    led = RunLedger.create(d)
    obs.set_ledger(led)
    try:
        out = train()
    finally:
        obs.set_ledger(None)
        led.close()
    rows, problems = read_rows(d)
    assert problems == []
    return out, rows


@pytest.fixture(scope="module")
def fitted(data, one, tmp_path_factory):
    """The coordinate staged and fitted once from zeros, under a ledger."""
    def train():
        coord = SparseFixedEffectCoordinate(_dataset(data), "global",
                                            losses.LOGISTIC, _tron(), one)
        return coord.train_model(jnp.zeros(data.response.shape[0],
                                           jnp.float32))
    return _rows(tmp_path_factory.mktemp("fit"), train)


def test_the_tron_fit_reaches_the_references_minimiser(data, fitted):
    """The sparse coordinate's TRON solve against the reference's truncated
    Newton on the same rows and L2 weight: the objective to float32's
    resolution of it, the coefficients to a part in a thousand."""
    model, _ = fitted
    block = kdd10_reference._FixedBlock(
        data, 1.0, kdd10_reference.field_starts(game_kdd10._tiny()))
    off = block.offsets(np.zeros(data.response.shape[0]))
    w, _, _ = block.solve(off, block.zeros, None)
    ref = block.full(w, data.num_features)
    got = np.asarray(model.coefficients.means)
    f_ref = block.value(off, jnp.asarray(ref[block.columns]))
    f_got = block.value(off, jnp.asarray(got[block.columns]))
    block.pool.shutdown()
    assert abs(f_got - f_ref) <= 1e-6 * f_ref
    assert np.linalg.norm(got - ref) <= 1e-3 * np.linalg.norm(ref)
    untouched = np.ones(data.num_features, bool)
    untouched[block.columns] = False
    assert not got[untouched].any()  # no row, no gradient: exactly 0


def _count(jaxpr, name):
    """Equations of primitive ``name`` in ``jaxpr`` and every sub-jaxpr."""
    n = 0
    for e in jaxpr.eqns:
        n += e.primitive.name == name
        for v in e.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += _count(sub, name)
    return n


def _loops(jaxpr, depth=0):
    for e in jaxpr.eqns:
        if e.primitive.name == "while":
            yield depth, e
        for v in e.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _loops(sub, depth + (e.primitive.name
                                                    == "while"))


def test_the_cg_loop_crosses_the_cold_entries_twice_a_product(data, one):
    """The fit's Steihaug loop (TRON's inner ``while``) holds the product at
    the curvature and no more: its scatter-adds and gathers are
    ``hessian_vector_at``'s, one scatter fewer than ``hessian_vector``'s,
    whose margins at w the loop no longer recomputes each step."""
    coord = SparseFixedEffectCoordinate(_dataset(data), "global",
                                        losses.LOGISTIC, _tron(), one)
    hb, n = coord._staged, data.response.shape[0]
    jaxpr = jax.make_jaxpr(coord._fit)(hb, jnp.zeros(n, jnp.float32),
                                       jnp.zeros(coord.dim, jnp.float32))
    cg = [e for depth, e in _loops(jaxpr.jaxpr) if depth == 1]
    assert len(cg) == 1
    body = cg[0].params["body_jaxpr"].jaxpr
    w = jnp.zeros(hb.num_features, jnp.float32)
    d2 = jnp.ones(n, jnp.float32)
    at = jax.make_jaxpr(lambda d2, v: hs.hessian_vector_at(d2, v, hb))(
        d2, w).jaxpr
    whole = jax.make_jaxpr(lambda w, v: hs.hessian_vector(
        losses.LOGISTIC, w, v, hb))(w, w).jaxpr
    for prim in ("scatter-add", "gather"):
        assert _count(body, prim) == _count(at, prim) > 0, prim
    assert _count(whole, "scatter-add") == _count(at, "scatter-add") + 1


def test_tron_rows_carry_products_crossings_and_the_floor(fitted):
    """Every ``opt_iter`` row of the solve carries its products (0 at the
    start), the curvatures its CG solve computed (one an iteration, none at
    the start) and the passes over the shard they cost: two for the
    starting evaluation, then two for the step's evaluation, one a
    curvature and two a product; the last row ``evaluations`` and
    ``floor_stop``; the layout row says the block holds float32 values."""
    _, rows = fitted
    its = [r for r in rows if r["kind"] == "opt_iter"]
    assert its and its[0]["iteration"] == 0 and its[0]["hvps"] == 0
    assert all(r["opt"] == "tron" for r in its)
    assert all(r["hvps"] >= 1 for r in its[1:])
    assert [r["curvatures"] for r in its] == [0] + [1] * (len(its) - 1)
    assert [r["crossings"] for r in its] == [2] + [
        2 + r["curvatures"] + 2 * r["hvps"] for r in its[1:]]
    assert its[-1]["evaluations"] == its[-1]["iteration"] + 1
    assert isinstance(its[-1]["floor_stop"], bool)
    assert all("floor_stop" not in r for r in its[:-1])
    assert all("trials" not in r for r in its)
    lay = [r for r in rows if r["kind"] == "fe_layout"]
    assert lay and lay[-1]["hot_storage"] == "float32"


@pytest.mark.parametrize("optimizer, vectors", [
    (OptimizerType.TRON, 9 + 2), (OptimizerType.LBFGS, 2 * 10 + 17 + 2),
    (OptimizerType.OWLQN, 2 * 10 + 24 + 2)])
def test_the_budget_reckons_the_solvers_own_vectors(optimizer, vectors):
    """TRON keeps no history: at the cell's 20,216,830 columns its solve is
    reckoned at its own measured scratch, a quarter of L-BFGS's."""
    d = 20_216_830
    reg = (RegularizationContext(RegularizationType.L1, 1.0)
           if optimizer == OptimizerType.OWLQN else L2)
    config = GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(optimizer_type=optimizer),
        regularization=reg)
    assert sparse_fixed.solver_state_bytes(d, config) == 4 * d * vectors
