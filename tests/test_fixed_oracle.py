"""The dense fixed effect's mesh ``LineOracle`` (ISSUE 38): the psum objective
taken apart equals the objective evaluated, ``parallel/problem.run`` through
it reaches the evaluation path's optimum on 8 devices and on 1, the fixed
fit's line search reads no feature array, and the fits that take no oracle
(OWL-QN, elastic net, TRON, ``run_grid``) build none.

Everything here runs on the 8-device CPU mesh of conftest.py: values,
shapes and counts, never a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu import obs
from photon_ml_tpu.data.batch import LabeledBatch
from photon_ml_tpu.game.coordinates import FixedEffectCoordinate
from photon_ml_tpu.normalization import (NormalizationContext,
                                         NormalizationType,
                                         build_normalization)
from photon_ml_tpu.obs.ledger import RunLedger, read_rows
from photon_ml_tpu.ops import aggregators as agg
from photon_ml_tpu.ops import losses
from photon_ml_tpu.optim import OptimizerConfig, OptimizerType, with_l2
from photon_ml_tpu.optim.problem import GLMOptimizationConfiguration
from photon_ml_tpu.optim.regularization import (RegularizationContext,
                                                RegularizationType,
                                                intercept_mask)
from photon_ml_tpu.parallel import objective as dobj
from photon_ml_tpu.parallel import problem as dist_problem
from photon_ml_tpu.parallel.mesh import make_mesh, shard_batch
from tests.test_lane_oracle import _dense_game, _loops

LOSSES = {"logistic": losses.LOGISTIC, "poisson": losses.POISSON}
REGS = {"l2": RegularizationContext(RegularizationType.L2, 0.7),
        "none": RegularizationContext(RegularizationType.NONE)}
D, N, PAD = 6, 203, 9  # the last PAD rows are padding; column D − 1 intercept
ALPHAS = (0.0, 0.3, 1.0, 2.5)


@pytest.fixture(scope="module")
def mesh():
    return make_mesh()


def _norm(kind: str) -> NormalizationContext:
    if kind == "identity":
        return NormalizationContext()
    rng = np.random.default_rng(5)
    return build_normalization(
        NormalizationType.STANDARDIZATION,
        means=rng.normal(scale=0.5, size=D),
        variances=rng.uniform(0.5, 3.0, size=D), intercept_index=D - 1)


def _batch(loss_name: str, seed: int, mesh) -> LabeledBatch:
    """Data offsets, uneven weights, and ``PAD`` zero-weight rows holding
    what would overflow a Poisson margin if anything read them; sharded
    over the mesh, which pads the rows to a multiple of its data axis."""
    rng = np.random.default_rng(seed)
    X = rng.normal(scale=0.6, size=(N, D)).astype(np.float32)
    X[:, -1] = 1.0
    z = X @ rng.normal(scale=0.5, size=D)
    if loss_name == "logistic":
        y = (rng.uniform(size=N) < 1 / (1 + np.exp(-z))).astype(np.float32)
    else:
        y = rng.poisson(np.exp(np.clip(z, -3, 2))).astype(np.float32)
    w = rng.uniform(0.5, 2.0, size=N).astype(np.float32)
    o = rng.normal(scale=0.3, size=N).astype(np.float32)
    X[-PAD:], y[-PAD:], w[-PAD:], o[-PAD:] = 100.0, 3.0, 0.0, 80.0
    return shard_batch(LabeledBatch.build(X, y, w, o), mesh)


def _point(seed):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(scale=0.3, size=D), jnp.float32),
            jnp.asarray(rng.normal(scale=0.4, size=D), jnp.float32))


@pytest.mark.parametrize("reg", sorted(REGS))
@pytest.mark.parametrize("norm", ["identity", "standardized"])
@pytest.mark.parametrize("loss_name", sorted(LOSSES))
def test_mesh_oracle_equals_evaluation(mesh, loss_name, norm, reg):
    """For each α, the oracle's (f, φ′) along w + αd and its accepted
    (f, g, margins) are the psum objective's evaluation at w + αd."""
    batch = _batch(loss_name, 11, mesh)
    loss, ctx, rc = LOSSES[loss_name], _norm(norm), REGS[reg]
    vg = with_l2(dobj.make_value_and_gradient(loss, mesh, batch, ctx),
                 rc.l2_weight(), jnp.asarray(intercept_mask(D, D - 1)))
    line = dobj.make_line_oracle(loss, mesh, batch, ctx, rc, D - 1, D)

    @jax.jit
    def walk(w, d):
        f0, g0, carry = line.start(w)
        ray = line.along(carry, w, d)
        got = [(f0, g0, carry[0])]
        want = [(*vg(w), agg.margins(batch, w, ctx))]
        for alpha in ALPHAS:
            alpha = jnp.asarray(alpha, jnp.float32)
            f, g = vg(w + alpha * d)
            f_at, g_at, (z_at, _) = line.accept(ray, alpha)
            got.append((*line.trial(ray, alpha), f_at, g_at, z_at))
            want.append((f, jnp.dot(g, d), f, g,
                         agg.margins(batch, w + alpha * d, ctx)))
        return got, want

    got, want = walk(*_point(3))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                                   atol=2e-5)
    # start computes what the first evaluation computed
    assert np.asarray(got[0][0]) == np.asarray(want[0][0])
    n = batch.num_rows
    for f_trial, _, f_at, _, z in got[1:]:
        # the accepted point's value is its trial's, to the bit
        assert np.asarray(f_trial) == np.asarray(f_at)
        # padding rows, the data's and the mesh's, keep margin 0
        assert np.all(np.asarray(z)[N - PAD:] == 0.0) and z.shape == (n,)
    assert got[0][2].sharding.spec == jax.sharding.PartitionSpec("data")


def _config(optimizer=OptimizerType.LBFGS, reg=RegularizationType.L2,
            weight=1.0, max_iterations=20, tolerance=1e-7):
    return GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(optimizer_type=optimizer,
                                  max_iterations=max_iterations,
                                  tolerance=tolerance),
        regularization=RegularizationContext(reg, weight))


@pytest.mark.parametrize("devices", [8, 1])
@pytest.mark.parametrize("loss_name", sorted(LOSSES))
def test_run_through_the_oracle_reaches_the_evaluation_optimum(
        monkeypatch, loss_name, devices):
    """The same optimum either way; ``evaluations`` counts pairs of passes
    under the oracle (the first evaluation and one an iteration), whatever
    the trials."""
    m = make_mesh(devices=jax.devices()[:devices])
    batch = _batch(loss_name, 7, m)
    cfg = _config(max_iterations=60, tolerance=1e-7)

    def fit():  # traced anew on each call
        return jax.jit(lambda b: dist_problem.run(
            LOSSES[loss_name], b, m, cfg, norm=_norm("standardized"),
            intercept_index=D - 1, already_sharded=True))(batch)

    coef_oracle, asked = fit()
    monkeypatch.setattr(dist_problem, "takes_line_oracle", lambda c: False)
    coef_eval, plain = fit()
    np.testing.assert_allclose(coef_oracle.means, coef_eval.means, rtol=2e-3,
                               atol=2e-3)
    np.testing.assert_allclose(asked.value, plain.value, rtol=1e-5)
    its = int(asked.iterations)
    assert its > 0 and int(asked.evaluations) == its + 1
    assert int(asked.trials) >= its  # a search tries at least once
    # every trial an evaluation: the count the parent's path keeps
    assert int(plain.evaluations) == int(plain.trials) + 1


# -- the fixed coordinate's programs ------------------------------------------

def _fit_jaxpr(coord):
    ds = coord.dataset
    return jax.make_jaxpr(coord._fit)(
        coord._staged, jnp.asarray(ds.offsets),
        jnp.zeros((coord.dim,), jnp.float32))


def _search_reads(coord):
    """For each line search of the fit program (a ``while`` inside the
    solve's ``while``): whether any operand has the feature block's shape,
    or its feature-major view's (``parallel/objective._feature_major``)."""
    block = coord._staged.features.shape
    searches = [e for depth, e in _loops(_fit_jaxpr(coord).jaxpr)
                if depth == 1]
    assert searches
    return [any(getattr(v.aval, "shape", None) in (block, block[::-1])
                for v in e.invars)
            for e in searches]


def test_fixed_fit_line_search_reads_no_feature(mesh, monkeypatch):
    ds = _dense_game()
    coord = FixedEffectCoordinate(ds, "global", losses.LOGISTIC, _config(),
                                  mesh)
    assert _search_reads(coord) == [False]
    # the parent's path reads the block at every trial
    monkeypatch.setattr(dist_problem, "takes_line_oracle", lambda c: False)
    assert _search_reads(coord.with_optimization_config(_config())) == [True]


def _fit_with_rows(coord, tmp_path, name):
    d = str(tmp_path / name)
    led = RunLedger.create(d)
    obs.set_ledger(led)
    try:
        w = np.asarray(coord.train_model(
            jnp.asarray(coord.dataset.offsets)).coefficients.means)
    finally:
        obs.set_ledger(None)
        led.close()
    rows, problems = read_rows(d)
    assert problems == []
    return w, [r for r in rows if r["kind"] == "opt_iter"]


def test_coordinate_rows_count_passes_and_trials(mesh, monkeypatch, tmp_path):
    """The oracle's last row: ``evaluations`` 1 + iterations
    (``ls_evals.fixed`` reads 1 + 1/``fe_iters``) and the solve's
    ``trials`` beside it; the same model as the evaluation path's, whose
    rows are the parent's."""
    ds = _dense_game()
    cfg = _config(max_iterations=40, tolerance=1e-8)
    w, rows = _fit_with_rows(
        FixedEffectCoordinate(ds, "global", losses.LOGISTIC, cfg, mesh),
        tmp_path, "oracle")
    its = rows[-1]["iteration"]
    assert its > 0 and rows[-1]["evaluations"] == its + 1
    assert rows[-1]["trials"] >= its
    assert all("trials" not in r for r in rows[:-1])
    monkeypatch.setattr(dist_problem, "takes_line_oracle", lambda c: False)
    # run to its cap, so that its searches meet float32's floor and try
    # more than once whatever the order of the passes' sums
    w_eval, rows_eval = _fit_with_rows(
        FixedEffectCoordinate(ds, "global", losses.LOGISTIC,
                              _config(max_iterations=40, tolerance=0.0),
                              mesh),
        tmp_path, "eval")
    np.testing.assert_allclose(w, w_eval, rtol=1e-2, atol=1e-3)
    assert all("trials" not in r for r in rows_eval)
    evals = rows_eval[-1]["evaluations"]
    assert evals > rows_eval[-1]["iteration"] + 1  # a trial an evaluation


# -- the fits that take no oracle ---------------------------------------------

@pytest.fixture
def no_oracle(monkeypatch):
    def refuse(*a, **k):
        raise AssertionError("an oracle was built for a fit that takes none")

    monkeypatch.setattr(dobj, "make_line_oracle", refuse)


@pytest.mark.parametrize("name", ["owlqn", "elastic-net", "tron"])
def test_owlqn_and_tron_fits_build_no_oracle(mesh, no_oracle, name,
                                             tmp_path):
    """They evaluate the objective as they did: no oracle is made for
    them, and their rows carry no ``trials``."""
    cfg = {"owlqn": _config(reg=RegularizationType.L1, weight=0.5),
           "elastic-net": _config(reg=RegularizationType.ELASTIC_NET,
                                  weight=0.5),
           "tron": _config(OptimizerType.TRON)}[name]
    w, rows = _fit_with_rows(
        FixedEffectCoordinate(_dense_game(), "global", losses.LOGISTIC, cfg,
                              mesh), tmp_path, name)
    assert np.all(np.isfinite(w)) and rows[-1]["iteration"] > 0
    assert all("trials" not in r for r in rows)


def test_run_grid_builds_no_oracle(mesh, no_oracle):
    coord = FixedEffectCoordinate(_dense_game(), "global", losses.LOGISTIC,
                                  _config(), mesh)
    coefs = dist_problem.run_grid(
        losses.LOGISTIC, coord._staged, mesh, _config(), [0.1, 1.0],
        already_sharded=True)[0]
    assert np.all(np.isfinite(np.asarray(jax.tree.leaves(coefs)[0])))
