"""ISSUE 34 on the CPU: where every hot column of a shard holds one float32
value, the resident hot block is int8 counts and a float32 scale a column;
``float32(count) * scale`` is the float32 block bit for bit, the passes are
the float32 layout's to rounding, the same bytes hold four times the
columns, and a shard that fails the rule builds the parent's leaves and
traces the parent's programs."""

from __future__ import annotations

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from photon_ml_tpu import obs
from photon_ml_tpu.data.game_data import GameDataset, SparseShard
from photon_ml_tpu.data.sparse import SparseBatch
from photon_ml_tpu.game.coordinates import SparseFixedEffectCoordinate
from photon_ml_tpu.game.models import FixedEffectModel
from photon_ml_tpu.models.coefficients import Coefficients
from photon_ml_tpu.obs.ledger import read_rows
from photon_ml_tpu.ops import hybrid_sparse as hs
from photon_ml_tpu.ops import losses
from photon_ml_tpu.optim import OptimizerConfig, OptimizerType
from photon_ml_tpu.optim.problem import GLMOptimizationConfiguration
from photon_ml_tpu.optim.regularization import (RegularizationContext,
                                                RegularizationType)
from photon_ml_tpu.parallel.mesh import make_mesh

N, D, FIELDS = 3000, 4000, 12
S = np.float32(1.0 / np.sqrt(FIELDS))


@pytest.fixture(autouse=True)
def _clean_obs_state():
    yield
    obs.set_ledger(None)
    obs.disable()


def one_valued(seed=0, n=N, d=D, fields=FIELDS, value=S, labels="binary"):
    """A click log in small: ``fields`` one-hot fields, each a Zipf draw
    over its own run of the columns, every value the one float32 ``value``;
    two fields share the first run, so some rows name a column twice."""
    rng = np.random.default_rng(seed)
    width = d // fields
    base = width * np.arange(fields)
    base[1] = 0
    idx = (base + rng.zipf(1.3, size=(n, fields)) % width).astype(np.int32)
    y = (rng.random(n) < 0.3 if labels == "binary"
         else rng.poisson(0.4, n)).astype(np.float32)
    return SparseBatch(indices=idx,
                       values=np.full((n, fields), value, np.float32),
                       labels=y, weights=np.ones(n, np.float32),
                       offsets=np.zeros(n, np.float32), num_features=d)


def as_float32(batch, **kw):
    """The same shard as the parent lays it out: the rule refused."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(hs, "_count_hot", lambda *a: (None, None, 0))
        return hs.build_hybrid(batch, **kw)


def with_values(batch, values):
    return dataclasses.replace(batch, values=values)


# -- the block ----------------------------------------------------------------

def per_column_scales(batch):
    """Every column its own float32 value (still one value a column)."""
    rng = np.random.default_rng(5)
    s = rng.uniform(0.1, 2.0, batch.num_features + 1).astype(np.float32)
    return with_values(batch, s[np.asarray(batch.indices)])


def a_triple_that_sums_exactly(batch):
    """Three slots of every row on one column, value 0.25: 3 x 0.25 is the
    sequential float32 sum."""
    idx = np.asarray(batch.indices).copy()
    idx[:, :3] = 7
    return dataclasses.replace(
        batch, indices=idx, values=np.full(idx.shape, 0.25, np.float32))


@pytest.mark.parametrize("shard", [
    pytest.param(lambda b: b, id="one-value-for-all"),
    pytest.param(per_column_scales, id="a-value-of-its-own-a-column"),
    pytest.param(a_triple_that_sums_exactly, id="a-count-of-three"),
])
def test_the_count_block_rebuilds_the_float32_block_bit_for_bit(shard):
    batch = shard(one_valued())
    hb = hs.build_hybrid(batch)
    hf = as_float32(batch)
    assert hs.hot_storage(hb) == "count8" and hs.hot_storage(hf) == "float32"
    assert hb.X_hot.dtype == jnp.int8 and hb.hot_scale.dtype == jnp.float32
    assert hb.num_hot == hf.num_hot > 100 and hf.hot_scale is None
    counts = np.asarray(hb.X_hot)
    assert counts.max() >= 2  # a row that names a column twice is in it
    rebuilt = counts.astype(np.float32) * np.asarray(hb.hot_scale)
    assert rebuilt.dtype == np.float32
    np.testing.assert_array_equal(rebuilt, np.asarray(hf.X_hot))
    # and nothing else of the layout moved
    assert hb.entries == hf.entries and hb.class_lens == hf.class_lens
    for a, b in zip(jax.tree.leaves(hb.cold_vals),
                    jax.tree.leaves(hf.cold_vals)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert hb.hot_plan == (hf.num_hot, hb.num_hot)


def test_every_product_of_the_margins_is_the_float32_block_s_to_the_bit():
    """``scale`` is folded into the coefficients first: count * (s * w) is
    the float32 block's (count * s) * w for the counts 1 and 2, every bit.
    (The sums agree to rounding only: a CPU's dot contracts multiply and
    add, which rounds s * w in one and not in the other.)"""
    batch = one_valued()
    hb, hf = hs.build_hybrid(batch), as_float32(batch)
    w = np.random.default_rng(1).standard_normal(hb.num_hot).astype(
        np.float32)
    counts = np.asarray(hb.X_hot)
    assert set(np.unique(counts)) == {0, 1, 2}
    ours = counts.astype(np.float32) * (np.asarray(hb.hot_scale) * w)
    np.testing.assert_array_equal(ours, np.asarray(hf.X_hot) * w)
    z = hs._hot_matvec(hb.X_hot, jnp.asarray(w), hb.hot_scale)
    zf = hs._hot_matvec(hf.X_hot, jnp.asarray(w))
    assert z.dtype == jnp.float32
    exact = np.asarray(hf.X_hot, np.float64) @ w.astype(np.float64)
    tol = 4e-7 * np.abs(np.asarray(hf.X_hot, np.float64)) @ np.abs(w)
    assert np.all(np.abs(np.asarray(z) - exact) <= tol + 1e-12)
    assert np.all(np.abs(np.asarray(zf) - exact) <= tol + 1e-12)


PASSES = {
    "margins": lambda loss, hb, w, v: hs.margins(hb, w),
    "row_gradient": lambda loss, hb, w, v: hs.row_gradient(
        hb, jnp.cos(jnp.arange(hb.labels.shape[0], dtype=jnp.float32))),
    "value_and_gradient": lambda loss, hb, w, v: jnp.concatenate(
        [x.reshape(-1) for x in hs.value_and_gradient(loss, w, hb)]),
    "hessian_vector": lambda loss, hb, w, v: hs.hessian_vector(
        loss, w, v, hb),
    "hessian_vector_at": lambda loss, hb, w, v: hs.hessian_vector_at(
        hs.curvature(loss, w, hb), v, hb),
    "hessian_diagonal": lambda loss, hb, w, v: hs.hessian_diagonal(
        loss, w, hb),
}


@pytest.mark.parametrize("name", sorted(PASSES))
@pytest.mark.parametrize("loss", ["logistic", "poisson"])
def test_a_pass_equals_the_float32_layout_s_to_float32_rounding(name, loss):
    batch = one_valued(2, labels="binary" if loss == "logistic" else "counts")
    hb, hf = hs.build_hybrid(batch), as_float32(batch)
    assert hs.hot_storage(hb) == "count8" and hb.entries[0] > hb.entries[1]
    rng = np.random.default_rng(3)
    w = jnp.asarray(0.3 * rng.standard_normal(D), jnp.float32)
    v = jnp.asarray(rng.standard_normal(D), jnp.float32)
    fn = PASSES[name]
    got = np.asarray(jax.jit(fn, static_argnums=0)(
        losses.get_loss(loss), hb, w, v))
    want = np.asarray(jax.jit(fn, static_argnums=0)(
        losses.get_loss(loss), hf, w, v))
    assert got.dtype == np.float32
    np.testing.assert_allclose(got, want, rtol=2e-5,
                               atol=2e-6 * np.abs(want).max())


# -- the rule -------------------------------------------------------------------

def one_real_valued_hot_column(batch):
    vals = np.asarray(batch.values).copy()
    idx = np.asarray(batch.indices)
    hot = np.bincount(idx.reshape(-1)).argmax()
    rows, slots = np.nonzero(idx == hot)
    vals[rows[0], slots[0]] = np.float32(0.5)
    return with_values(batch, vals)


def a_cell_named_128_times(batch):
    """200 slots a row, 128 of them on one column in every row."""
    n = 64
    rng = np.random.default_rng(9)
    idx = (1 + rng.integers(0, 500, size=(n, 200))).astype(np.int32)
    idx[:, :128] = 0
    return SparseBatch(indices=idx, values=np.full(idx.shape, 0.25,
                                                   np.float32),
                       labels=batch.labels[:n], weights=batch.weights[:n],
                       offsets=batch.offsets[:n], num_features=501)


def six_that_do_not_sum_exactly(batch):
    """Six values of 1 / sqrt(12) summed one after the other in float32 are
    not float32(6) * s (up to five are)."""
    summed = np.float32(0)
    for _ in range(6):
        summed = np.float32(summed + S)
    assert summed != np.float32(6) * S
    idx = np.asarray(batch.indices).copy()
    idx[:5, :6] = 7
    return dataclasses.replace(batch, indices=idx)


@pytest.mark.parametrize("shard, dtype", [
    pytest.param(one_real_valued_hot_column, jnp.float32,
                 id="one-real-valued-hot-column"),
    pytest.param(a_cell_named_128_times, jnp.float32,
                 id="a-multiplicity-over-127"),
    pytest.param(six_that_do_not_sum_exactly, jnp.float32,
                 id="a-count-off-its-sequential-sum"),
    pytest.param(lambda b: b, jnp.bfloat16, id="bfloat16-storage"),
])
def test_a_shard_that_fails_the_rule_builds_the_parent_s_leaves(shard, dtype):
    batch = shard(one_valued())
    kw = dict(feature_dtype=dtype, hot_threshold=8)
    hb = hs.build_hybrid(batch, **kw)
    parent = as_float32(batch, **kw)
    assert hb.hot_scale is None and hb.num_hot == parent.num_hot > 0
    assert hb.X_hot.dtype == dtype
    assert hs.hot_storage(hb) == np.dtype(dtype).name
    got, want = jax.tree.leaves(hb), jax.tree.leaves(parent)
    assert len(got) == len(want)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    if dtype == jnp.float32:  # the first column that failed says why
        assert 0 <= hb.hot_plan[1] < hb.num_hot
    # and the programs traced over it are the parent's, to the letter
    w = jnp.zeros((batch.num_features,), jnp.float32)

    def program(hb):
        return jax.jit(lambda hb, w: hs.value_and_gradient(
            losses.LOGISTIC, w, hb)).lower(hb, w).as_text()

    assert program(hb) == program(parent)


def test_real_valued_fixtures_keep_their_float32_block():
    from photon_ml_tpu.data import sparse as sp_data

    batch, _ = sp_data.synthetic_sparse(4096, 512, 8, seed=3)
    hb = hs.build_hybrid(batch)
    assert hs.hot_storage(hb) == "float32" and hb.hot_scale is None
    assert hb.num_hot > 0 and hb.hot_plan == (hb.num_hot, 0)


# -- the plan -------------------------------------------------------------------

@pytest.mark.parametrize("rows, budget, candidates, k4, k1", [
    pytest.param(2_000_000, 8_454_654_464, 4115, 1024, 4096,
                 id="criteo-2M-rows"),  # 4,227 fit; max_hot binds
    pytest.param(3_000_000, 3_423_500_948, 1356, 256, 1024,
                 id="kdd12-3M-rows"),  # 1,141 fit: whole tiles
    pytest.param(2_000_000, 8_454_654_464, 9000, 1024, 4096,
                 id="more-candidates-than-max-hot"),
    pytest.param(1_000_000, 1_200_000_000, 9000, 256, 1152,
                 id="the-tiles-round-each-plan"),  # 300 and 1,199 fit
    pytest.param(1000, 4 * 1000 * 130, 700, 128, 512, id="a-small-shard"),
])
def test_the_same_bytes_hold_four_times_the_columns(rows, budget, candidates,
                                                    k4, k1):
    counts = np.zeros(20_000, np.int64)
    counts[:candidates] = rows
    f32 = hs.plan_resident_hot(counts, rows, jnp.float32,
                               hot_block_bytes=budget)
    thr = hs._default_hot_threshold(rows, jnp.float32)
    cnt = hs.plan_resident_hot(counts, rows, "int8", thr,
                               hot_block_bytes=budget)
    assert (f32, cnt) == (k4, k1)
    assert cnt % 128 == 0 and 4 * f32 - 128 <= cnt <= min(4096, candidates)
    # a byte a cell and a float32 scale a column, inside the bytes offered
    assert cnt * (rows + 4) <= budget


def test_under_a_byte_budget_the_layout_is_four_times_as_wide():
    batch = one_valued(4, n=1000, d=1024, fields=48)
    budget = 4 * 1000 * 130  # 130 float32 columns: one tile
    hb = hs.build_hybrid(batch, hot_block_bytes=budget)
    hf = as_float32(batch, hot_block_bytes=budget)
    assert (hf.num_hot, hb.num_hot) == (128, 512)
    assert hb.hot_plan == (128, 512)
    held = int(np.asarray(hb.X_hot).nbytes + np.asarray(hb.hot_scale).nbytes)
    # the bytes offered hold both: the float32 block's and 2,048 more
    assert int(np.asarray(hf.X_hot).nbytes) < held == 512 * 1004 <= budget
    assert hb.entries[0] > hf.entries[0]
    assert sum(hb.entries) == sum(hf.entries)
    # a real-valued shard under the same bytes keeps the float32 plan
    rng = np.random.default_rng(0)
    real = with_values(batch, rng.uniform(0.5, 1.5, (1000, 48)).astype(
        np.float32))
    hr = hs.build_hybrid(real, hot_block_bytes=budget)
    assert hr.num_hot == 128 and hs.hot_storage(hr) == "float32"
    assert hr.hot_plan == (128, 0)


# -- through the coordinate -----------------------------------------------------

def _dataset(batch):
    return GameDataset(
        response=np.asarray(batch.labels), offsets=np.zeros(
            batch.labels.shape[0], np.float32),
        weights=np.asarray(batch.weights),
        feature_shards={"global": SparseShard(
            np.asarray(batch.indices), np.asarray(batch.values),
            batch.num_features)},
        entity_ids={}, num_entities={}, intercept_index={})


def _objective(batch, loss, w, l1, l2):
    """The objective in float64, from the rows themselves."""
    w = np.asarray(w, np.float64)
    idx, val = np.asarray(batch.indices), np.asarray(batch.values, np.float64)
    z = (w[idx] * val).sum(axis=1)
    y = np.asarray(batch.labels, np.float64)
    data = (np.logaddexp(0, z) - y * z if loss == "logistic"
            else np.exp(z) - y * z)
    return data.sum() + 0.5 * l2 * (w * w).sum() + l1 * np.abs(w).sum()


@pytest.mark.parametrize("kind, reg, loss", [
    pytest.param(OptimizerType.LBFGS, RegularizationType.L2, "logistic",
                 id="lbfgs-l2-logistic"),
    pytest.param(OptimizerType.OWLQN, RegularizationType.L1, "poisson",
                 id="owlqn-l1-poisson"),
])
def test_a_fit_reaches_the_float32_layout_s_objective(kind, reg, loss,
                                                      monkeypatch):
    batch = one_valued(6, labels="binary" if loss == "logistic" else "counts")
    config = GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(optimizer_type=kind, max_iterations=300,
                                  tolerance=1e-10),
        regularization=RegularizationContext(reg, 0.5))
    mesh = make_mesh(devices=jax.devices()[:1])

    def fitted():
        coord = SparseFixedEffectCoordinate(
            _dataset(batch), "global", losses.get_loss(loss), config, mesh)
        model = coord.train_model(jnp.zeros((N,), jnp.float32))
        return coord, np.asarray(model.coefficients.means)

    coord, w = fitted()
    assert hs.hot_storage(coord._staged) == "count8"
    assert coord._staged.X_hot.dtype == jnp.int8
    monkeypatch.setattr(hs, "_count_hot", lambda *a: (None, None, 0))
    coord32, w32 = fitted()
    assert hs.hot_storage(coord32._staged) == "float32"
    l1 = 0.5 if reg == RegularizationType.L1 else 0.0
    l2 = 0.5 if reg == RegularizationType.L2 else 0.0
    f, f32 = (_objective(batch, loss, x, l1, l2) for x in (w, w32))
    assert f == pytest.approx(f32, rel=1e-6)
    np.testing.assert_allclose(coord.score(coord.initial_model()), 0.0)
    model = FixedEffectModel(shard_id="global",
                             coefficients=Coefficients(jnp.asarray(w32)))
    np.testing.assert_allclose(coord.score(model), coord32.score(model),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("shard, storage", [
    pytest.param(lambda b: b, "count8", id="one-valued"),
    pytest.param(one_real_valued_hot_column, "float32", id="real-valued"),
])
def test_the_layout_row_carries_the_storage_and_its_plan(shard, storage,
                                                         tmp_path,
                                                         monkeypatch):
    from photon_ml_tpu.game.coordinates import sparse_fixed

    batch = shard(one_valued(4, n=1000, d=1024, fields=48))
    budget = 4 * 1000 * 130
    monkeypatch.setattr(sparse_fixed, "hot_block_budget",
                        lambda mesh, solver_bytes=0, deferred_bytes=0: budget)
    led = obs.RunLedger.create(str(tmp_path))
    obs.set_ledger(led)
    SparseFixedEffectCoordinate(
        _dataset(batch), "global", losses.LOGISTIC,
        GLMOptimizationConfiguration(
            optimizer=OptimizerConfig(max_iterations=5),
            regularization=RegularizationContext(RegularizationType.L2, 1.0)),
        make_mesh(devices=jax.devices()[:1]))
    obs.set_ledger(None)
    led.close()
    lay, = [r for r in read_rows(str(tmp_path))[0]
            if r.get("kind") == "fe_layout"]
    assert lay["hot_storage"] == storage
    assert lay["hot_columns_f32"] == 128
    assert lay["hot_entries"] + lay["cold_entries"] == 1000 * 48
    if storage == "count8":
        assert lay["num_hot"] == lay["hot_exact_candidates"] == 512
        assert lay["hot_bytes"] == 512 * 1000 + 512 * 4 <= budget
    else:  # the first column that failed the rule: the heaviest
        assert lay["num_hot"] == 128 and lay["hot_exact_candidates"] == 0
        assert lay["hot_bytes"] == 128 * 1000 * 4
