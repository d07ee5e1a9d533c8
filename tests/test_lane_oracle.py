"""The lane solver's ``LineOracle`` (ISSUE 36): the dense objective taken
apart equals the objective evaluated, the bucket programs' line searches
read no feature array, and a wave fitted through the oracle agrees with one
whose every trial is an evaluation.

Everything here runs on the CPU: values, shapes and counts, never a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu import obs
from photon_ml_tpu.data import synthetic
from photon_ml_tpu.data.batch import LabeledBatch
from photon_ml_tpu.data.game_data import from_synthetic
from photon_ml_tpu.game.coordinates import RandomEffectCoordinate
from photon_ml_tpu.game.coordinates import random_effect as re_mod
from photon_ml_tpu.normalization import (NormalizationContext,
                                         NormalizationType,
                                         build_normalization)
from photon_ml_tpu.obs.ledger import RunLedger, read_rows
from photon_ml_tpu.ops import aggregators as agg
from photon_ml_tpu.ops import losses
from photon_ml_tpu.optim import (OptimizerConfig, OptimizerType,
                                 minimize_lbfgs)
from photon_ml_tpu.optim.problem import (GLMOptimizationConfiguration,
                                         make_line_oracle, make_objective)
from photon_ml_tpu.optim.regularization import (RegularizationContext,
                                                RegularizationType)
from photon_ml_tpu.parallel.mesh import make_mesh

LOSSES = {"logistic": losses.LOGISTIC, "poisson": losses.POISSON,
          "squared": losses.SQUARED}
D, N, PAD = 6, 40, 8  # the last PAD rows are padding; column D − 1 intercept
L2 = RegularizationContext(RegularizationType.L2, 0.7)
ALPHAS = (0.0, 0.3, 1.0, 2.5)


def _norm(kind: str) -> NormalizationContext:
    if kind == "identity":
        return NormalizationContext()
    rng = np.random.default_rng(5)
    return build_normalization(
        NormalizationType.STANDARDIZATION if kind == "standardized"
        else NormalizationType.SCALE_WITH_STANDARD_DEVIATION,
        means=rng.normal(scale=0.5, size=D),
        variances=rng.uniform(0.5, 3.0, size=D), intercept_index=D - 1)


def _batch(loss_name: str, seed: int) -> LabeledBatch:
    """A lane's block: data offsets, uneven weights, and ``PAD`` zero-weight
    rows holding what would overflow a Poisson margin if anything read it."""
    rng = np.random.default_rng(seed)
    X = rng.normal(scale=0.6, size=(N, D)).astype(np.float32)
    X[:, -1] = 1.0
    z = X @ rng.normal(scale=0.5, size=D)
    if loss_name == "logistic":
        y = (rng.uniform(size=N) < 1 / (1 + np.exp(-z))).astype(np.float32)
    elif loss_name == "poisson":
        y = rng.poisson(np.exp(np.clip(z, -3, 2))).astype(np.float32)
    else:
        y = (z + rng.normal(size=N)).astype(np.float32)
    w = rng.uniform(0.5, 2.0, size=N).astype(np.float32)
    o = rng.normal(scale=0.3, size=N).astype(np.float32)
    X[-PAD:], y[-PAD:], w[-PAD:], o[-PAD:] = 100.0, 3.0, 0.0, 80.0
    return LabeledBatch.build(X, y, w, o)


def _problem(loss_name, batch, norm):
    return LOSSES[loss_name], batch, norm, L2, D - 1, D


def _point(seed):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(scale=0.3, size=D), jnp.float32),
            jnp.asarray(rng.normal(scale=0.4, size=D), jnp.float32))


def _walk(problem, w, d):
    """(oracle's readings, evaluation's readings) along w + αd: for each α
    the trial's (f, slope) and the accepted (f, g, margins)."""
    vg = make_objective(*problem)[0]
    line = make_line_oracle(*problem)
    f0, g0, carry = line.start(w)
    ray = line.along(carry, w, d)
    got, want = [(f0, g0, carry[0])], [(*vg(w), agg.margins(problem[1], w,
                                                              problem[2]))]
    for alpha in ALPHAS:
        alpha = jnp.asarray(alpha, jnp.float32)
        f, g = vg(w + alpha * d)
        f_at, g_at, (z_at, _) = line.accept(ray, alpha)
        got.append((*line.trial(ray, alpha), f_at, g_at, z_at))
        want.append((f, jnp.dot(g, d), f, g,
                     agg.margins(problem[1], w + alpha * d, problem[2])))
    return got, want


def _close(got, want):
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                                   atol=2e-5)


@pytest.mark.parametrize("norm", ["identity", "scaled", "standardized"])
@pytest.mark.parametrize("loss_name", sorted(LOSSES))
def test_oracle_equals_evaluation(loss_name, norm):
    problem = _problem(loss_name, _batch(loss_name, 11), _norm(norm))
    got, want = _walk(problem, *_point(3))
    _close(got, want)
    for f_trial, _, f_at, _, z in got[1:]:
        # the accepted point's value is its trial's, to the bit
        assert np.asarray(f_trial) == np.asarray(f_at)
        # padding rows keep margin 0 whatever the step
        assert np.all(np.asarray(z)[-PAD:] == 0.0)


@pytest.mark.parametrize("norm", ["identity", "standardized"])
@pytest.mark.parametrize("loss_name", sorted(LOSSES))
def test_oracle_equals_evaluation_under_vmap(loss_name, norm):
    """Lanes of different data, points and directions, and (as the
    projected path has it) a normalisation of their own."""
    lanes = 5
    batches = jax.tree.map(lambda *a: jnp.stack(a),
                           *[_batch(loss_name, 20 + k) for k in range(lanes)])
    ws, ds = (jnp.stack(a) for a in zip(*[_point(40 + k)
                                          for k in range(lanes)]))
    ctx = _norm(norm)
    scale = jnp.linspace(1.0, 2.0, lanes)[:, None]

    def lane_norm(k):
        if ctx.is_identity:
            return ctx
        return NormalizationContext(ctx.factors * k, ctx.shifts,
                                    ctx.intercept_index)

    def lane(batch, w, d, k):
        return _walk(_problem(loss_name, batch, lane_norm(k)), w, d)

    got, want = jax.vmap(lane)(batches, ws, ds, scale)
    _close(got, want)
    one, _ = lane(jax.tree.map(lambda a: a[2], batches), ws[2], ds[2],
                  scale[2])
    _close(jax.tree.map(lambda a: a[2], got), one)


@pytest.mark.parametrize("loss_name", sorted(LOSSES))
def test_minimize_through_the_oracle(loss_name):
    """The same optimum either way; ``evaluations`` counts pairs of passes
    under the oracle and ``trials`` the trials in both."""
    problem = _problem(loss_name, _batch(loss_name, 7), _norm("standardized"))
    vg = make_objective(*problem)[0]
    cfg = OptimizerConfig(max_iterations=40, tolerance=1e-6)
    w0 = jnp.zeros((D,), jnp.float32)
    plain = minimize_lbfgs(vg, w0, cfg)
    asked = minimize_lbfgs(vg, w0, cfg, line=make_line_oracle(*problem))
    np.testing.assert_allclose(asked.w, plain.w, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(asked.value, plain.value, rtol=1e-5)
    assert int(asked.evaluations) == int(asked.iterations) + 1
    assert int(asked.trials) >= int(asked.iterations) > 0
    assert int(plain.trials) == int(plain.evaluations) - 1


# -- the bucket programs -------------------------------------------------------

def _opt(optimizer=OptimizerType.LBFGS, reg=RegularizationType.L2,
         max_iterations=60, tolerance=1e-7):
    return GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(optimizer_type=optimizer,
                                  max_iterations=max_iterations,
                                  tolerance=tolerance),
        regularization=RegularizationContext(reg, 1.0))


@pytest.fixture(scope="module")
def mesh():
    return make_mesh()


def _dense_game(n=800):
    """tests/test_game.py's ``_tiny_game``."""
    return from_synthetic(synthetic.game_data(
        np.random.default_rng(12345), n=n, d_global=8,
        re_specs={"userId": (40, 4), "itemId": (25, 3)}, entity_skew=1.1))


def _sparse_entity_game(n=900, ne=30, d=16):
    """tests/test_projection.py's: an entity touches 3 columns of its own
    and the intercept."""
    rng = np.random.default_rng(12345)
    ds = from_synthetic(synthetic.game_data(
        rng, n=n, d_global=6, re_specs={"userId": (ne, d)}))
    X = ds.feature_shards["re_userId"].copy()
    ids = ds.entity_ids["userId"]
    for e in range(ne):
        mask = np.zeros(d, bool)
        mask[rng.choice(d - 1, size=3, replace=False)] = mask[d - 1] = True
        X[ids == e] = np.where(mask[None, :], X[ids == e], 0.0)
    ds.feature_shards["re_userId"] = X
    return ds


def _inside_view_game():
    """tests/test_inside_view.py's ``game``."""
    return from_synthetic(synthetic.game_data(
        np.random.default_rng(7), n=640, d_global=4,
        re_specs={"userId": (40, 3)}))


def _table(ds, mesh, opt=None, **kind):
    if kind.pop("standardized", False):
        X = ds.feature_shards["re_userId"]
        kind["norm"] = build_normalization(
            NormalizationType.SCALE_WITH_STANDARD_DEVIATION,
            variances=X.var(0) + 0.1,
            intercept_index=ds.intercept_index["re_userId"])
    return RandomEffectCoordinate(ds, "userId", "re_userId", losses.LOGISTIC,
                                  opt or _opt(), mesh, **kind)


def _first_wave(coord):
    """The first staged wave's program arguments."""
    coord.wait_staged()
    arrays = coord._bucket_data[0]
    W = coord._prepare_table(None)
    offsets = jnp.zeros((coord.dataset.num_rows,), jnp.float32)
    return (W, offsets, *arrays), arrays[0].shape


def _loops(jaxpr, depth=0):
    """(depth, eqn) of every ``while`` of a jaxpr, loops inside loops
    deeper."""
    for eqn in jaxpr.eqns:
        inner = depth + (eqn.primitive.name == "while")
        if eqn.primitive.name == "while":
            yield depth, eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _loops(sub, inner)


def _search_reads(coord):
    """For each line search of the wave program (a ``while`` inside the
    solve's ``while``): whether any operand has the block's shape."""
    args, block = _first_wave(coord)
    assert len(block) == 3
    jaxpr = jax.make_jaxpr(coord._fit_bucket.jitted)(*args)
    searches = [e for depth, e in _loops(jaxpr.jaxpr) if depth == 1]
    assert searches
    return [any(getattr(v.aval, "shape", None) == block for v in e.invars)
            for e in searches]


@pytest.mark.parametrize("kind", [
    {}, {"projection": True}, {"projection": True, "standardized": True},
    {"projection": True, "subspace_model": True}],
    ids=["dense", "projected", "projected-normalised", "subspace"])
def test_line_search_loop_reads_no_feature(mesh, kind):
    ds = _sparse_entity_game() if kind else _dense_game()
    coord = _table(ds, mesh, **kind)
    assert coord._line_oracle
    assert _search_reads(coord) == [False]


@pytest.mark.parametrize("opt", [
    _opt(reg=RegularizationType.L1),
    _opt(reg=RegularizationType.ELASTIC_NET),
    _opt(optimizer=OptimizerType.TRON)], ids=["l1", "elastic-net", "tron"])
@pytest.mark.parametrize("kind", [{}, {"projection": True}],
                         ids=["dense", "projected"])
def test_owlqn_and_tron_tables_build_no_oracle(mesh, monkeypatch, opt, kind):
    """They keep the path they had: no oracle is made for them, OWL-QN's
    trials evaluate the objective over the block and TRON's inner loop is
    its conjugate gradients'."""
    def refuse(*a, **k):
        raise AssertionError("an oracle was built for a lane that takes "
                             "none")

    monkeypatch.setattr(re_mod, "make_line_oracle", refuse)
    ds = _sparse_entity_game() if kind else _dense_game()
    coord = _table(ds, mesh, opt, **kind)
    assert not coord._line_oracle
    assert all(_search_reads(coord))
    coord.train_model(jnp.asarray(ds.offsets))


def _fit_with_rows(coord, offsets, tmp_path, name):
    d = str(tmp_path / name)
    led = RunLedger.create(d)
    obs.set_ledger(led)
    try:
        W = np.asarray(coord.train_model(offsets).means)
    finally:
        obs.set_ledger(None)
        led.close()
    rows, problems = read_rows(d)
    assert problems == []
    return W, [r for r in rows if r["kind"] == "re_fit_wave"]


@pytest.mark.parametrize("case", ["game", "game-squared", "projection",
                                  "projection-normalised", "inside-view",
                                  "inside-view-subspace"])
def test_wave_through_the_oracle_agrees_with_evaluation(mesh, monkeypatch,
                                                       tmp_path, case):
    """The fixtures of test_game.py, test_projection.py and
    test_inside_view.py, fitted through the oracle and with every trial an
    evaluation (the parent's path): the same rows within the solver's
    tolerance, and the wave rows say which it was."""
    kind, opt, loss = {}, _opt(), losses.LOGISTIC
    if case.startswith("game"):
        ds = _dense_game()
        if case == "game-squared":
            loss = losses.SQUARED
            ds.response = np.random.default_rng(3).normal(
                size=ds.num_rows).astype(np.float32)
    elif case.startswith("projection"):
        ds = _sparse_entity_game()
        kind = {"projection": True,
                "standardized": case == "projection-normalised"}
        opt = _opt(max_iterations=80, tolerance=1e-8)
    else:
        ds = _inside_view_game()
        opt = _opt(max_iterations=6)
        if case == "inside-view-subspace":
            kind = {"projection": True, "subspace_model": True}
    offsets = jnp.asarray(ds.offsets)

    def build():
        if "standardized" in kind:
            return _table(ds, mesh, opt, **kind)
        return RandomEffectCoordinate(ds, "userId", "re_userId", loss, opt,
                                      mesh, **kind)

    W_oracle, rows = _fit_with_rows(build(), offsets, tmp_path, "oracle")
    monkeypatch.setattr(RandomEffectCoordinate, "_line_oracle",
                        property(lambda self: False))
    W_eval, rows_eval = _fit_with_rows(build(), offsets, tmp_path, "eval")
    # The stopping rule is a relative decrease of the value, which holds a
    # flat direction's coefficient to less than it holds the value.
    tol = 2e-2 if opt.optimizer.max_iterations == 6 else 2e-3
    np.testing.assert_allclose(W_oracle, W_eval, rtol=5 * tol, atol=tol)
    assert rows and len(rows) == len(rows_eval)
    for r, e in zip(rows, rows_eval):
        assert r["line"] == "oracle" and e["line"] == "evaluation"
        assert r["evals_sum"] == r["iters_sum"] + r["entities_fit"]
        assert r["trials_sum"] >= r["iters_sum"]
        # every trial an evaluation: the count the rows held before
        assert e["evals_sum"] == e["trials_sum"] + e["entities_fit"]
