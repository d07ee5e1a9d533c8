"""The Yahoo! Music cell, program side, on the CPU: TRON's lanes solve a
quadratic to its normal equations under ``vmap``, its Hessian-vector
products are counted as they run (``hvps``, ``hvp_history``), a wave's rows
carry what its lanes needed and what the wave computed (``hvp_sum``,
``hvp_wave``), the fixed effect's rows carry each iteration's products, the
dense fixed effect's TRON program holds no tiled copy of X, a solve ends at
its objective's float32 floor (``floor_stop``, ``floor_sum``) and not on a
rejection it can resolve, and the benchmark's item -> artist map is a
function.

Values, shapes and counts only, never a time.
"""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import io_callback

from photon_ml_tpu import obs
from photon_ml_tpu.data import synthetic
from photon_ml_tpu.data.batch import LabeledBatch
from photon_ml_tpu.data.game_data import from_synthetic
from photon_ml_tpu.game.coordinates import (FixedEffectCoordinate,
                                            RandomEffectCoordinate)
from photon_ml_tpu.game.coordinates import random_effect as re_mod
from photon_ml_tpu.normalization import NormalizationContext
from photon_ml_tpu.obs.ledger import RunLedger, read_rows
from photon_ml_tpu.ops import losses
from photon_ml_tpu.optim import (OptimizerConfig, OptimizerType,
                                 minimize_lbfgs, tron)
from photon_ml_tpu.optim.problem import (GLMOptimizationConfiguration,
                                         make_objective)
from photon_ml_tpu.optim.regularization import (RegularizationContext,
                                                RegularizationType)
from photon_ml_tpu.parallel.mesh import make_mesh
from tests.test_optimizers import _logistic_problem

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
for _p in (os.path.join(REPO, "benchmark"),
           os.path.join(REPO, "benchmark", "schemas")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import game_music  # noqa: E402  (benchmark/schemas/game_music.py)

D = 8  # a table's width in the cell: 7 slopes and the intercept
L2 = RegularizationContext(RegularizationType.L2, 1.0)


def _config(max_iterations=25, tolerance=1e-7):
    return OptimizerConfig(optimizer_type=OptimizerType.TRON,
                           max_iterations=max_iterations, tolerance=tolerance)


def _lanes(lanes: int, rows: int, seed: int, same: bool = False):
    """(X, y, w, o) of ``lanes`` squared-loss blocks on a 0-100 scale, the
    intercept last, a lane's tail rows padding (weight 0); every lane the
    same block where ``same``."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(lanes, rows, D)).astype(np.float32)
    X[..., -1] = 1.0
    y = np.clip(50 + 20 * rng.normal(size=(lanes, rows)), 0, 100)
    w = (np.arange(rows)[None, :] < rng.integers(
        2, rows + 1, size=(lanes, 1))).astype(np.float32)
    o = rng.normal(scale=5.0, size=(lanes, rows))
    if same:
        X, y, w, o = (np.broadcast_to(a[:1], a.shape) for a in (X, y, w, o))
    return tuple(jnp.asarray(a, jnp.float32) for a in (X, y, w, o))


def _solve(X, y, w, o, config, hvp_seen=None):
    batch = LabeledBatch(X, y, w, o)
    vg, hvp, _ = make_objective(losses.SQUARED, batch, NormalizationContext(),
                                L2, D - 1, D)
    if hvp_seen is not None:
        def hvp(w_, v, _hvp=hvp):  # counts every product as it runs
            io_callback(hvp_seen, None, ordered=True)
            return _hvp(w_, v)
    return tron.minimize(vg, hvp, jnp.zeros((D,), jnp.float32), config)


def _normal_equations(X, y, w, o):
    """The block minimum in float64: (Σ x xᵀ + λ M) β = Σ x (y − o)."""
    X, y, w, o = (np.asarray(a, np.float64) for a in (X, y, w, o))
    M = np.eye(D)
    M[-1, -1] = 0.0
    return np.linalg.solve((X * w[:, None]).T @ X + M,
                           (X * w[:, None]).T @ (y - o))


@pytest.mark.parametrize("lanes,rows,seed", [(16, 12, 0), (40, 3, 1),
                                             (5, 200, 2)])
def test_vmapped_tron_lanes_reach_the_normal_equations(lanes, rows, seed):
    blocks = _lanes(lanes, rows, seed)
    res = jax.jit(jax.vmap(lambda *b: _solve(*b, _config())))(*blocks)
    for k in range(lanes):
        want = _normal_equations(*(np.asarray(a[k]) for a in blocks))
        np.testing.assert_allclose(np.asarray(res.w[k]), want,
                                   rtol=1e-3, atol=1e-3 * np.abs(want).max())
    # every iteration a conjugate-gradient solve of at least one product
    its = np.asarray(res.iterations)
    hist = np.asarray(res.hvp_history)
    assert (hist[:, 0] == 0).all()
    for k in range(lanes):
        assert (hist[k, 1:its[k] + 1] >= 1).all()
        assert (hist[k, its[k] + 1:] == 0).all()
    np.testing.assert_array_equal(hist.sum(axis=1), np.asarray(res.hvps))


@pytest.mark.parametrize("rows,seed,tolerance", [(12, 3, 1e-7), (60, 4, 1e-7),
                                                 (60, 5, 0.0)])
def test_hvps_on_a_solve_equal_its_cg_steps(rows, seed, tolerance):
    """The products a solve reports are the ones its CG loops ran: each run
    of the product is counted from inside the compiled program."""
    seen = []
    X, y, w, o = (a[0] for a in _lanes(1, rows, seed))
    res = jax.jit(lambda *b: _solve(
        *b, _config(tolerance=tolerance),
        hvp_seen=lambda: seen.append(1)))(X, y, w, o)
    jax.block_until_ready(res)
    assert int(res.hvps) == len(seen) > 0
    assert int(np.asarray(res.hvp_history).sum()) == len(seen)
    assert int(res.evaluations) == int(res.iterations) + 1


@pytest.mark.parametrize("same", [True, False], ids=["agree", "differ"])
def test_a_wave_computes_at_least_what_its_lanes_need(same):
    """``hvp_wave`` (lanes × Σ over iterations of the longest lane's CG
    steps) is at least ``hvp_sum`` (the lanes' own), and equal where every
    lane takes the same steps; a padding lane counts in the first only."""
    lanes = 12
    blocks = _lanes(lanes, 10, 6, same=same)
    res = jax.jit(jax.vmap(lambda *b: _solve(*b, _config())))(*blocks)
    live = jnp.arange(lanes)
    stats = dict(zip(re_mod._WAVE_STATS, map(int, re_mod._wave_stats(
        live, res.iterations, res.evaluations,
        jnp.zeros_like(res.iterations), res.hvp_history, 25))))
    assert stats["hvp_sum"] == int(res.hvps.sum())
    hist = np.asarray(res.hvp_history)
    assert stats["hvp_wave"] == lanes * int(hist.max(axis=0).sum())
    assert stats["hvp_wave"] >= stats["hvp_sum"] > 0
    assert (stats["hvp_wave"] == stats["hvp_sum"]) is same
    # one lane of padding: its products are computed, not needed
    padded = jnp.where(live == 0, -1, live)
    pad = dict(zip(re_mod._WAVE_STATS, map(int, re_mod._wave_stats(
        padded, res.iterations, res.evaluations,
        jnp.zeros_like(res.iterations), res.hvp_history, 25))))
    assert pad["hvp_wave"] == stats["hvp_wave"]
    assert pad["hvp_sum"] == stats["hvp_sum"] - int(res.hvps[0])


def test_a_solver_without_products_counts_none():
    live = jnp.arange(4)
    its = jnp.full((4,), 3)
    stats = dict(zip(re_mod._WAVE_STATS, map(int, re_mod._wave_stats(
        live, its, its + 1, its, None, 25))))
    assert (stats["hvp_sum"], stats["hvp_wave"]) == (0, 0)
    assert stats["iters_sum"] == 12 and stats["trials_sum"] == 12


# -- float32's floor -----------------------------------------------------------

def _last_accepted(res):
    """The last iteration whose step was accepted: a rejected step leaves
    the value history where it was, an accepted one lowers it."""
    vh = np.asarray(res.value_history)[:int(res.iterations) + 1]
    moved = np.flatnonzero(vh[1:] != vh[:-1]) + 1
    return int(moved[-1]) if moved.size else 0


@pytest.mark.parametrize("rows,seed,tolerance", [
    (2000, 0, 1e-7), (3000, 1, 1e-7), (4000, 4, 1e-7), (2000, 2, 0.0),
    (4000, 3, 0.0)])
def test_a_solve_ends_at_its_objectives_float32_floor(rows, seed, tolerance):
    """A block of 0-100 ratings whose float32 objective is 4e5-9e5: after
    its Newton steps the next one's decrease is under the objective's last
    place, so it is rejected and the solve ends there, converged (under
    ``tolerance`` 0 nothing else could end it but the radius's collapse,
    which reads ``failed``) and at the block minimum all the same."""
    X, y, w, o = (a[0] for a in _lanes(1, rows, seed))
    w = jnp.ones_like(w)
    res = jax.jit(lambda *b: _solve(*b, _config(tolerance=tolerance)))(
        X, y, w, o)
    assert 1e5 < float(res.value) < 1e6
    assert bool(res.floor_stop) and bool(res.converged)
    assert int(res.iterations) <= _last_accepted(res) + 1
    want = _normal_equations(X, y, w, o)
    np.testing.assert_allclose(np.asarray(res.w), want, rtol=1e-3,
                               atol=1e-3 * np.abs(want).max())


def test_each_lane_of_a_wave_stops_at_its_own_floor():
    """Lanes whose objectives lie 1e1 to 1e11 apart: each ends on the
    rejection its own objective cannot resolve, ``floor_sum`` counts the
    live ones, and the wave ends far under the iteration cap."""
    lanes = 6
    X, y, w, o = _lanes(lanes, 300, 7)
    scale = jnp.logspace(-2, 3, lanes)[:, None]
    w, y, o = jnp.ones_like(w), y * scale, o * scale
    res = jax.jit(jax.vmap(lambda *b: _solve(*b, _config(tolerance=0.0))))(
        X, y, w, o)
    f = np.asarray(res.value)
    assert f.max() / f.min() > 1e9
    assert np.asarray(res.floor_stop).all() and np.asarray(res.converged).all()
    for k in range(lanes):
        lane = jax.tree.map(lambda a: a[k], res)
        assert int(lane.iterations) <= _last_accepted(lane) + 1
        want = _normal_equations(X[k], y[k], w[k], o[k])
        np.testing.assert_allclose(np.asarray(lane.w), want, rtol=1e-3,
                                   atol=1e-3 * np.abs(want).max())
    live = jnp.arange(lanes)
    args = (res.iterations, res.evaluations, jnp.zeros_like(res.iterations),
            res.hvp_history, 25, res.floor_stop)
    stats = dict(zip(re_mod._WAVE_STATS, map(int, re_mod._wave_stats(
        live, *args))))
    assert stats["floor_sum"] == lanes
    assert stats["iters_max"] < 25 and stats["lanes_at_cap"] == 0
    padded = dict(zip(re_mod._WAVE_STATS, map(int, re_mod._wave_stats(
        jnp.where(live == 0, -1, live), *args))))
    assert padded["floor_sum"] == lanes - 1


def test_resolvable_rejections_do_not_end_a_logistic_solve(rng):
    """Started far from its optimum, a logistic solve's model overshoots
    and early steps are rejected with decreases far above the floor: the
    solve goes on past them to the optimum of
    ``test_tron_logistic_matches_scipy_and_lbfgs``."""
    vg, hvp, w_ref, _ = _logistic_problem(rng)
    cfg = OptimizerConfig(max_iterations=100, tolerance=1e-9)
    res = tron.minimize(vg, hvp, jnp.full((8,), 5.0), cfg)
    vh = np.asarray(res.value_history)[:int(res.iterations) + 1]
    rejected = np.flatnonzero(vh[1:] == vh[:-1]) + 1
    first = int(rejected[0])
    assert first < _last_accepted(res)  # accepted steps after it
    eps = np.finfo(np.float32).eps
    assert vh[first] - vh[-1] > 1e4 * eps * vh[first]  # far above the floor
    assert bool(res.converged)
    np.testing.assert_allclose(res.w, w_ref, rtol=2e-2, atol=2e-2)
    lbfgs = minimize_lbfgs(vg, jnp.zeros(8), cfg)
    np.testing.assert_allclose(res.w, lbfgs.w, rtol=2e-2, atol=2e-2)


# -- the coordinates' rows ----------------------------------------------------

@pytest.fixture(scope="module")
def mesh():
    return make_mesh()


def _game(n=1200):
    return from_synthetic(synthetic.game_data(
        np.random.default_rng(2011), n=n, d_global=8,
        re_specs={"userId": (60, 4)}, entity_skew=1.1))


def _opt(optimizer):
    return GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(optimizer_type=optimizer, max_iterations=25),
        regularization=L2)


def _rows(tmp_path, name, train):
    d = str(tmp_path / name)
    led = RunLedger.create(d)
    obs.set_ledger(led)
    try:
        train()
    finally:
        obs.set_ledger(None)
        led.close()
    rows, problems = read_rows(d)
    assert problems == []
    return rows


@pytest.mark.parametrize("optimizer", [OptimizerType.TRON,
                                       OptimizerType.LBFGS])
def test_wave_rows_carry_the_products(mesh, tmp_path, optimizer):
    ds = _game()
    coord = RandomEffectCoordinate(ds, "userId", "re_userId", losses.SQUARED,
                                   _opt(optimizer), mesh)
    waves = [r for r in _rows(tmp_path, "waves", lambda: coord.train_model(
        jnp.asarray(ds.offsets))) if r["kind"] == "re_fit_wave"]
    assert waves
    for r in waves:
        if optimizer == OptimizerType.TRON:
            assert r["hvp_wave"] >= r["hvp_sum"] >= r["iters_sum"] > 0
            assert r["hvp_wave"] % r["lanes"] == 0
        else:
            assert (r["hvp_sum"], r["hvp_wave"]) == (0, 0)


@pytest.mark.parametrize("optimizer", [OptimizerType.TRON,
                                       OptimizerType.LBFGS])
def test_fixed_rows_carry_each_iterations_products(mesh, tmp_path,
                                                   optimizer):
    ds = _game()
    coord = FixedEffectCoordinate(ds, "global", losses.SQUARED,
                                  _opt(optimizer), mesh)
    rows = [r for r in _rows(tmp_path, "fixed", lambda: coord.train_model(
        jnp.asarray(ds.offsets))) if r["kind"] == "opt_iter"]
    assert rows and rows[0]["iteration"] == 0
    if optimizer == OptimizerType.TRON:
        assert rows[0]["hvps"] == 0
        assert all(r["hvps"] >= 1 for r in rows[1:])
        assert rows[-1]["evaluations"] == rows[-1]["iteration"] + 1
    else:
        assert all("hvps" not in r for r in rows)


@pytest.mark.parametrize("optimizer", [OptimizerType.TRON,
                                       OptimizerType.LBFGS])
def test_rows_say_which_solves_ended_at_the_floor(mesh, tmp_path, optimizer):
    """Under TRON the fixed effect's last ``opt_iter`` row carries
    ``floor_stop`` and every wave row ``floor_sum`` (at most its live
    lanes); under L-BFGS the rows carry no ``floor_stop`` and ``floor_sum``
    reads 0."""
    ds = _game()
    fixed = FixedEffectCoordinate(ds, "global", losses.SQUARED,
                                  _opt(optimizer), mesh)
    table = RandomEffectCoordinate(ds, "userId", "re_userId", losses.SQUARED,
                                   _opt(optimizer), mesh)

    def train():
        fixed.train_model(jnp.asarray(ds.offsets))
        table.train_model(jnp.asarray(ds.offsets))

    rows = _rows(tmp_path, "floor", train)
    opt_rows = [r for r in rows if r["kind"] == "opt_iter"]
    waves = [r for r in rows if r["kind"] == "re_fit_wave"]
    assert opt_rows and waves
    assert all("floor_stop" not in r for r in opt_rows[:-1])
    if optimizer == OptimizerType.TRON:
        assert isinstance(opt_rows[-1]["floor_stop"], bool)
        assert all(0 <= r["floor_sum"] <= r["entities_fit"] for r in waves)
        assert sum(r["floor_sum"] for r in waves) > 0
    else:
        assert "floor_stop" not in opt_rows[-1]
        assert all(r["floor_sum"] == 0 for r in waves)


def test_tron_fixed_program_holds_no_row_major_copy_of_x(mesh):
    """TRON's CG runs inside its outer loop, so the feature block is an
    operand of a nested loop: it goes in feature-major (``(d, n)``, the
    bytes the TPU already holds), never as the ``(n, d)`` array whose
    row-major tiling padded 32 columns to 128 (PERF.md section 6)."""
    ds = _game()
    coord = FixedEffectCoordinate(ds, "global", losses.SQUARED,
                                  _opt(OptimizerType.TRON), mesh)
    shape = coord._staged.features.shape
    jaxpr = jax.make_jaxpr(coord._fit)(
        coord._staged, jnp.asarray(ds.offsets),
        jnp.zeros((coord.dim,), jnp.float32))

    def loops(j, depth=0):
        for e in j.eqns:
            if e.primitive.name == "while":
                yield depth, e
            for v in e.params.values():
                for sub in v if isinstance(v, (list, tuple)) else [v]:
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        yield from loops(sub, depth + (
                            e.primitive.name == "while"))

    cg = [e for depth, e in loops(jaxpr.jaxpr) if depth == 1]
    assert cg
    for e in cg:
        shapes = [getattr(v.aval, "shape", None) for v in e.invars]
        assert shape not in shapes and shape[::-1] in shapes
    # and the numbers are the evaluation path's: the same minimum
    w = np.asarray(coord.train_model(jnp.asarray(ds.offsets))
                   .coefficients.means)
    lbfgs = FixedEffectCoordinate(ds, "global", losses.SQUARED,
                                  _opt(OptimizerType.LBFGS), mesh)
    np.testing.assert_allclose(w, np.asarray(lbfgs.train_model(
        jnp.asarray(ds.offsets)).coefficients.means), rtol=1e-3, atol=1e-3)


# -- the benchmark's item -> artist map ---------------------------------------

TAXONOMY = {"tracks": 500, "albums": 90, "artists": 28, "genres": 4}


@pytest.mark.parametrize("seed", [1, 2900000011, 2**31 + 17])
def test_the_item_artist_map_is_a_function(seed):
    conf = {"items": TAXONOMY,
            "assumed_generator": {"artist_zipf_exponent": 1.0}}
    a = game_music.artist_of_items(np.random.default_rng(seed), conf)
    assert a.shape == (622,) and a.dtype == np.int32
    assert (a[:590] >= 0).all() and (a[:590] < 28).all()
    np.testing.assert_array_equal(a[590:618], np.arange(28))
    assert (a[618:] == 28).all()  # genres: the no-artist id
    again = game_music.artist_of_items(np.random.default_rng(seed), conf)
    np.testing.assert_array_equal(a, again)  # the seed's own function
    # skewed: the head artist holds several times its even share
    assert np.bincount(a[:590]).max() > 3 * 590 / 28
