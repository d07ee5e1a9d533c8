"""Optimizer convergence tests vs closed forms and scipy.

Mirrors photon-lib ``LBFGSTest`` / ``TRONTest`` / ``OWLQNTest`` (SURVEY.md
§4): convergence on quadratics and known GLM solutions, optimizer
cross-checks (LBFGS and TRON reach the same optimum), OWL-QN sparsity.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.optimize

from photon_ml_tpu.data.batch import LabeledBatch
from photon_ml_tpu.ops import aggregators as agg
from photon_ml_tpu.ops import losses
from photon_ml_tpu.optim import lbfgs
from photon_ml_tpu.optim import (OptimizerConfig, OptimizerType,
                                 l1_weights_vector, minimize_lbfgs,
                                 minimize_owlqn, minimize_tron, optimize,
                                 with_l2, with_l2_hvp)


def _quadratic(d, rng):
    A = rng.normal(size=(d, d))
    A = A @ A.T + d * np.eye(d)  # SPD, well-conditioned
    b = rng.normal(size=d)
    A_j, b_j = jnp.asarray(A, jnp.float32), jnp.asarray(b, jnp.float32)

    def vg(w):
        return 0.5 * w @ A_j @ w - b_j @ w, A_j @ w - b_j

    def hvp(w, v):
        return A_j @ v

    w_star = np.linalg.solve(A, b)
    return vg, hvp, w_star


def _logistic_problem(rng, n=200, d=8, l2=0.1):
    X = rng.normal(size=(n, d)).astype(np.float32)
    X[:, -1] = 1.0
    w_true = rng.normal(size=d)
    p = 1.0 / (1.0 + np.exp(-(X @ w_true)))
    y = (rng.uniform(size=n) < p).astype(np.float32)
    batch = LabeledBatch.build(X, y)

    def vg(w):
        return agg.value_and_gradient(losses.LOGISTIC, w, batch)

    def hvp(w, v):
        return agg.hessian_vector(losses.LOGISTIC, w, v, batch)

    vg_l2 = with_l2(vg, l2)
    hvp_l2 = with_l2_hvp(hvp, l2)

    # scipy ground truth (f64)
    def f_np(w):
        z = X.astype(np.float64) @ w
        return (np.logaddexp(0, z) - y * z).sum() + 0.5 * l2 * (w @ w)

    res = scipy.optimize.minimize(f_np, np.zeros(d), method="L-BFGS-B",
                                  jac=lambda w: X.T.astype(np.float64) @ (
                                      1/(1+np.exp(-(X @ w))) - y) + l2 * w,
                                  options={"gtol": 1e-10})
    return vg_l2, hvp_l2, res.x, batch


def test_lbfgs_quadratic(rng):
    vg, _, w_star = _quadratic(10, rng)
    out = jax.jit(lambda w0: minimize_lbfgs(vg, w0, OptimizerConfig(
        max_iterations=100, tolerance=1e-10)))(jnp.zeros(10))
    assert bool(out.converged)
    np.testing.assert_allclose(out.w, w_star, rtol=1e-3, atol=1e-3)


def test_tron_quadratic(rng):
    vg, hvp, w_star = _quadratic(10, rng)
    # f32: the gradient floor sits around 1e-4 relative; 1e-6 is achievable
    # via the value criterion, 1e-10 is not (stall would be reported failed).
    out = jax.jit(lambda w0: minimize_tron(vg, hvp, w0, OptimizerConfig(
        max_iterations=50, tolerance=1e-6)))(jnp.zeros(10))
    assert bool(out.converged)
    np.testing.assert_allclose(out.w, w_star, rtol=1e-3, atol=1e-3)


def test_lbfgs_logistic_matches_scipy(rng):
    vg, _, w_ref, _ = _logistic_problem(rng)
    out = minimize_lbfgs(vg, jnp.zeros(8), OptimizerConfig(
        max_iterations=200, tolerance=1e-9))
    np.testing.assert_allclose(out.w, w_ref, rtol=2e-2, atol=2e-2)


def test_tron_logistic_matches_scipy_and_lbfgs(rng):
    vg, hvp, w_ref, _ = _logistic_problem(rng)
    cfg = OptimizerConfig(max_iterations=100, tolerance=1e-9)
    out_t = minimize_tron(vg, hvp, jnp.zeros(8), cfg)
    out_l = minimize_lbfgs(vg, jnp.zeros(8), cfg)
    np.testing.assert_allclose(out_t.w, w_ref, rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(out_t.w, out_l.w, rtol=2e-2, atol=2e-2)


def test_linear_regression_exact_solution(rng):
    n, d = 100, 6
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (X @ rng.normal(size=d) + 0.01 * rng.normal(size=n)).astype(np.float32)
    batch = LabeledBatch.build(X, y)
    vg = lambda w: agg.value_and_gradient(losses.SQUARED, w, batch)
    w_ols = np.linalg.lstsq(X, y, rcond=None)[0]
    out = minimize_lbfgs(vg, jnp.zeros(d), OptimizerConfig(
        max_iterations=200, tolerance=1e-10))
    np.testing.assert_allclose(out.w, w_ols, rtol=1e-2, atol=1e-2)


def test_poisson_regression_converges(rng):
    n, d = 300, 5
    X = (rng.normal(size=(n, d)) * 0.3).astype(np.float32)
    w_true = rng.normal(size=d) * 0.5
    lam = np.exp(X @ w_true)
    y = rng.poisson(lam).astype(np.float32)
    batch = LabeledBatch.build(X, y)
    vg = with_l2(lambda w: agg.value_and_gradient(losses.POISSON, w, batch), 1e-3)
    out = minimize_lbfgs(vg, jnp.zeros(d), OptimizerConfig(
        max_iterations=200, tolerance=1e-9))
    assert bool(out.converged)
    assert float(out.grad_norm) < 1e-3 * max(1.0, float(out.value))
    # Recovered rates close-ish to truth
    np.testing.assert_allclose(out.w, w_true, atol=0.3)


def test_owlqn_produces_sparsity_and_matches_scipy(rng):
    n, d = 250, 12
    X = rng.normal(size=(n, d)).astype(np.float32)
    w_true = np.zeros(d); w_true[:3] = [2.0, -1.5, 1.0]
    y = (X @ w_true + 0.1 * rng.normal(size=n)).astype(np.float32)
    batch = LabeledBatch.build(X, y)
    l1 = 25.0
    vg = lambda w: agg.value_and_gradient(losses.SQUARED, w, batch)
    l1w = jnp.full((d,), l1)
    out = minimize_owlqn(vg, jnp.zeros(d), l1w, OptimizerConfig(
        max_iterations=300, tolerance=1e-10))

    # scipy reference on the L1 problem via smooth reformulation (w = p - q).
    def f_np(wpq):
        p, q = wpq[:d], wpq[d:]
        w = p - q
        r = X.astype(np.float64) @ w - y
        return 0.5 * (r @ r) + l1 * (p.sum() + q.sum())

    def g_np(wpq):
        p, q = wpq[:d], wpq[d:]
        g = X.T.astype(np.float64) @ (X.astype(np.float64) @ (p - q) - y)
        return np.concatenate([g + l1, -g + l1])

    res = scipy.optimize.minimize(
        f_np, np.zeros(2 * d), jac=g_np, method="L-BFGS-B",
        bounds=[(0, None)] * (2 * d), options={"ftol": 1e-14, "gtol": 1e-10})
    w_ref = res.x[:d] - res.x[d:]
    np.testing.assert_allclose(out.w, w_ref, rtol=5e-2, atol=5e-2)
    # True zeros stay (numerically) zero.
    assert np.all(np.abs(np.asarray(out.w)[np.abs(w_ref) < 1e-8]) < 1e-6)


def test_owlqn_exact_zeros(rng):
    """OWL-QN's orthant projection must yield EXACT zeros, not small values."""
    n, d = 100, 8
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (X[:, 0] * 2.0 + 0.05 * rng.normal(size=n)).astype(np.float32)
    batch = LabeledBatch.build(X, y)
    vg = lambda w: agg.value_and_gradient(losses.SQUARED, w, batch)
    out = minimize_owlqn(vg, jnp.zeros(d), jnp.full((d,), 40.0),
                         OptimizerConfig(max_iterations=200, tolerance=1e-10))
    w = np.asarray(out.w)
    assert np.sum(w == 0.0) >= d - 3  # hard zeros from projection


def test_vmapped_lbfgs_matches_individual(rng):
    """The random-effect regime: batched independent solves under vmap."""
    E, n, d = 5, 40, 4
    Xs = rng.normal(size=(E, n, d)).astype(np.float32)
    ws = rng.normal(size=(E, d)).astype(np.float32)
    ys = np.stack([
        (rng.uniform(size=n) < 1/(1+np.exp(-(Xs[i] @ ws[i])))).astype(np.float32)
        for i in range(E)])
    batches = LabeledBatch.build(Xs, ys,
                                 weights=np.ones((E, n), np.float32),
                                 offsets=np.zeros((E, n), np.float32))
    cfg = OptimizerConfig(max_iterations=100, tolerance=1e-8)

    def solve(bb, w0):
        vg = with_l2(lambda w: agg.value_and_gradient(losses.LOGISTIC, w, bb),
                     0.1)
        return minimize_lbfgs(vg, w0, cfg)

    outs = jax.jit(jax.vmap(solve))(batches, jnp.zeros((E, d)))
    for i in range(E):
        b_i = jax.tree.map(lambda a: a[i], batches)
        out_i = solve(b_i, jnp.zeros(d))
        np.testing.assert_allclose(outs.w[i], out_i.w, rtol=5e-3, atol=5e-3)
        assert bool(outs.converged[i])


def test_vmapped_tron_matches_individual(rng):
    E, n, d = 4, 30, 3
    Xs = rng.normal(size=(E, n, d)).astype(np.float32)
    ys = rng.normal(size=(E, n)).astype(np.float32)
    batches = LabeledBatch.build(Xs, ys,
                                 weights=np.ones((E, n), np.float32),
                                 offsets=np.zeros((E, n), np.float32))
    cfg = OptimizerConfig(max_iterations=50, tolerance=1e-9)

    def solve(bb, w0):
        vg = with_l2(lambda w: agg.value_and_gradient(losses.SQUARED, w, bb), 0.01)
        hvp = with_l2_hvp(
            lambda w, v: agg.hessian_vector(losses.SQUARED, w, v, bb), 0.01)
        return minimize_tron(vg, hvp, w0, cfg)

    outs = jax.jit(jax.vmap(solve))(batches, jnp.zeros((E, d)))
    for i in range(E):
        b_i = jax.tree.map(lambda a: a[i], batches)
        out_i = solve(b_i, jnp.zeros(d))
        np.testing.assert_allclose(outs.w[i], out_i.w, rtol=5e-3, atol=5e-3)


def _split_logistic(X, y, weights, l2=0.1):
    """A logistic objective's (vg, hvp, curvature, hvp_at): the product at
    w, and the same product in TRON's two parts, the rows' curvature at w
    and the product at it."""
    X_j, y_j, wt = (jnp.asarray(a, jnp.float32) for a in (X, y, weights))

    def vg(w):
        z = X_j @ w
        l, dl = losses.LOGISTIC.loss_and_dz(z, y_j)
        return (jnp.sum(wt * l) + 0.5 * l2 * jnp.dot(w, w),
                X_j.T @ (wt * dl) + l2 * w)

    def curvature(w):
        return wt * losses.LOGISTIC.d2z(X_j @ w, y_j)

    def hvp_at(c, v):
        return X_j.T @ (c * (X_j @ v)) + l2 * v

    return vg, (lambda w, v: hvp_at(curvature(w), v)), curvature, hvp_at


def _same_solve(split, plain):
    for name in ("w", "value", "iterations", "value_history",
                 "grad_norm_history", "hvps", "hvp_history"):
        np.testing.assert_array_equal(np.asarray(getattr(split, name)),
                                      np.asarray(getattr(plain, name)),
                                      err_msg=name)


def test_tron_with_a_curvature_solves_as_without(rng):
    """The CG solve's curvature computed once before its loop takes the
    steps the product at w takes, to the bit; the result counts one
    curvature an iteration whose CG ran, and none without a curvature."""
    n, d = 200, 8
    X = rng.normal(size=(n, d))
    X[:, -1] = 1.0
    y = (rng.uniform(size=n) < 0.4).astype(np.float32)
    vg, hvp, curvature, hvp_at = _split_logistic(X, y, np.ones(n))
    cfg = OptimizerConfig(max_iterations=30, tolerance=1e-9)
    plain = jax.jit(lambda w0: minimize_tron(vg, hvp, w0, cfg))(
        jnp.zeros(d))
    split = jax.jit(lambda w0: minimize_tron(
        vg, hvp_at, w0, cfg, curvature=curvature))(jnp.zeros(d))
    _same_solve(split, plain)
    it = int(plain.iterations)
    ran = (np.asarray(plain.hvp_history) > 0).astype(np.int32)
    assert it >= 2 and ran.sum() == it
    np.testing.assert_array_equal(np.asarray(split.curvature_history), ran)
    assert int(split.curvatures) == it
    assert plain.curvatures is None and plain.curvature_history is None


def test_vmapped_tron_with_a_curvature_leaves_an_idle_lane_alone(rng):
    """Lanes solved under ``vmap`` through the split take the plain
    product's steps; a lane that starts converged (every weight 0, so its
    gradient is 0) computes no curvature, takes no product and keeps its
    starting point."""
    E, n, d = 3, 60, 4
    Xs = rng.normal(size=(E, n, d))
    ys = (rng.uniform(size=(E, n)) < 0.5).astype(np.float32)
    wts = np.ones((E, n))
    wts[1] = 0.0
    cfg = OptimizerConfig(max_iterations=30, tolerance=1e-9)

    def solve(X, y, wt, split):
        vg, hvp, curvature, hvp_at = _split_logistic(X, y, wt)
        if split:
            return minimize_tron(vg, hvp_at, jnp.zeros(d), cfg,
                                 curvature=curvature)
        return minimize_tron(vg, hvp, jnp.zeros(d), cfg)

    outs = [jax.jit(jax.vmap(lambda X, y, wt, s=s: solve(X, y, wt, s)))(
        jnp.asarray(Xs), jnp.asarray(ys), jnp.asarray(wts))
        for s in (True, False)]
    split, plain = outs
    _same_solve(split, plain)
    its = np.asarray(plain.iterations)
    assert its[1] == 0 and (its[[0, 2]] >= 2).all()
    np.testing.assert_array_equal(np.asarray(split.curvatures), its)
    assert int(split.hvps[1]) == 0
    assert not np.asarray(split.curvature_history[1]).any()
    np.testing.assert_array_equal(np.asarray(split.w[1]), np.zeros(d))


def test_history_tracking(rng):
    vg, _, _ = _quadratic(6, rng)
    out = minimize_lbfgs(vg, jnp.zeros(6), OptimizerConfig(
        max_iterations=50, tolerance=1e-10))
    it = int(out.iterations)
    vh = np.asarray(out.value_history)
    assert np.all(np.isfinite(vh[:it + 1]))
    assert np.all(np.isnan(vh[it + 1:]))
    # Values are non-increasing (monotone line search).
    assert np.all(np.diff(vh[:it + 1]) <= 1e-5)


def test_factory_dispatch_and_validation(rng):
    vg, hvp, _ = _quadratic(4, rng)
    cfg = OptimizerConfig(optimizer_type=OptimizerType.TRON, tolerance=1e-6)
    with pytest.raises(ValueError):
        optimize(vg, jnp.zeros(4), cfg)  # TRON without hvp
    out = optimize(vg, jnp.zeros(4), cfg, hvp=hvp)
    assert bool(out.converged)
    with pytest.raises(ValueError):
        optimize(vg, jnp.zeros(4),
                 OptimizerConfig(optimizer_type=OptimizerType.OWLQN))


def _ill_conditioned_quadratic(d, rng, cond=1e4):
    """SPD quadratic with eigenvalues log-spaced over ``cond``."""
    Q, _ = np.linalg.qr(rng.normal(size=(d, d)))
    eig = np.logspace(0, np.log10(cond), d)
    A = (Q * eig) @ Q.T
    b = rng.normal(size=d)
    A_j = jnp.asarray(A, jnp.float32)
    b_j = jnp.asarray(b, jnp.float32)

    def vg(w):
        return 0.5 * w @ A_j @ w - b_j @ w, A_j @ w - b_j

    def f_np(w):
        return 0.5 * w @ A @ w - b @ w

    def g_np(w):
        return A @ w - b

    return vg, f_np, g_np, np.linalg.solve(A, b)


def test_strong_wolfe_iteration_parity_vs_scipy(rng):
    """Strong-Wolfe L-BFGS should take a comparable number of iterations to
    scipy's L-BFGS-B on an ill-conditioned quadratic (breeze
    StrongWolfeLineSearch parity check: Armijo-only backtracking degrades
    badly here)."""
    d = 20
    vg, f_np, g_np, w_star = _ill_conditioned_quadratic(d, rng)
    ref = scipy.optimize.minimize(
        f_np, np.zeros(d), jac=g_np, method="L-BFGS-B",
        options={"gtol": 1e-8, "maxiter": 500})
    out = minimize_lbfgs(vg, jnp.zeros(d), OptimizerConfig(
        max_iterations=500, tolerance=1e-8))
    assert bool(out.converged)
    # f32 floor: compare against the f64 optimum loosely, iterations tightly.
    np.testing.assert_allclose(out.w, w_star, rtol=5e-2, atol=5e-2)
    assert int(out.iterations) <= 2 * ref.nit + 10


def test_strong_wolfe_conditions_hold_on_accepted_steps(rng):
    """The accepted step must satisfy BOTH strong-Wolfe conditions (which
    imply s^T y > 0) — checked directly on single optimizer steps from
    several random starts, conditions evaluated on the step s = w1 − w0
    (scale-invariant in the direction)."""
    d = 12
    vg, _, _, _ = _ill_conditioned_quadratic(d, rng)
    cfg = OptimizerConfig(max_iterations=1, tolerance=1e-12)
    c1, c2 = cfg.wolfe_c1, cfg.wolfe_c2
    for _ in range(5):
        w0 = jnp.asarray(rng.normal(size=d), jnp.float32)
        f0, g0 = vg(w0)
        out = minimize_lbfgs(vg, w0, cfg)
        s = np.asarray(out.w) - np.asarray(w0)
        assert np.linalg.norm(s) > 0  # a step was taken
        f1, g1 = vg(out.w)
        dg0 = float(np.asarray(g0) @ s)  # α·φ'(0) < 0
        dg1 = float(np.asarray(g1) @ s)  # α·φ'(α)
        assert dg0 < 0
        # Sufficient decrease: f(w1) ≤ f(w0) + c1·g0ᵀs  (small f32 slack).
        assert float(f1) <= float(f0) + c1 * dg0 + 1e-4 * abs(float(f0))
        # Strong curvature: |g1ᵀs| ≤ c2·|g0ᵀs| → implies sᵀy > 0.
        assert abs(dg1) <= c2 * abs(dg0) * (1 + 1e-3)
        assert float(np.asarray(g1 - g0) @ s) > 0  # sᵀy > 0


def test_wolfe_logistic_fewer_evals_than_tolerance_budget(rng):
    """The Wolfe search should not regress iteration counts on the standard
    logistic problem (guard against unit-step Armijo being replaced by
    something slower in the common well-scaled case)."""
    vg, _, w_ref, _ = _logistic_problem(rng)
    out = minimize_lbfgs(vg, jnp.zeros(8), OptimizerConfig(
        max_iterations=200, tolerance=1e-9))
    np.testing.assert_allclose(out.w, w_ref, rtol=2e-2, atol=2e-2)
    assert int(out.iterations) < 60


# -- the age-ordered history (ISSUE 27) ----------------------------------------

def _two_loop_lists(g, pairs):
    """Nocedal & Wright, Numerical Optimization, Algorithm 7.4, over a plain
    Python list of (s, y) pairs, oldest first, in float64: the reference
    ``lbfgs._two_loop`` is held to."""
    q = np.array(g, np.float64)
    rhos = [1.0 / (y @ s) for s, y in pairs]
    alphas = [0.0] * len(pairs)
    for i in reversed(range(len(pairs))):
        s, y = pairs[i]
        alphas[i] = rhos[i] * (s @ q)
        q = q - alphas[i] * y
    if pairs:
        s, y = pairs[-1]
        q = (s @ y) / (y @ y) * q
    for i, (s, y) in enumerate(pairs):
        beta = rhos[i] * (y @ q)
        q = q + s * (alphas[i] - beta)
    return q


@pytest.mark.parametrize("d", [8, 32])
@pytest.mark.parametrize("accepted", [0, 1, 3, 9, 10, 14],
                         ids=lambda k: f"pairs{k}")
def test_two_loop_matches_nocedal_wright(accepted, d):
    """``count`` in {0, 1, 3, m−1, m} and eviction (14 accepted pairs, the 4
    oldest gone), with a rejected pair between every two accepted ones, which
    must leave the buffers as they are."""
    m = 10
    rng = np.random.default_rng(100 * d + accepted)
    A = rng.normal(size=(d, d))
    A = A @ A.T / d + np.eye(d)  # SPD: every pair has sᵀy > 0
    s_hist = jnp.zeros((m, d), jnp.float32)
    y_hist = jnp.zeros((m, d), jnp.float32)
    rho = jnp.zeros((m,), jnp.float32)
    pairs = []
    for _ in range(accepted):
        s = rng.normal(size=d).astype(np.float32)
        y = (A @ s).astype(np.float32)
        pairs.append((s.astype(np.float64), y.astype(np.float64)))
        for good, scale in ((True, 1.0), (False, 7.0)):
            good = jnp.asarray(good)
            s_hist = lbfgs._push(s_hist, jnp.asarray(scale * s), good)
            y_hist = lbfgs._push(y_hist, jnp.asarray(scale * y), good)
            rho = lbfgs._push(rho, jnp.asarray(scale / (s @ y), jnp.float32),
                              good)
    count = jnp.asarray(min(accepted, m), jnp.int32)
    # slot 0 holds the newest pair, slot a the pair of age a
    for age, (s, _) in enumerate(reversed(pairs[-m:])):
        np.testing.assert_array_equal(s_hist[age], s.astype(np.float32))
    g = rng.normal(size=d).astype(np.float32)
    got = lbfgs._two_loop(jnp.asarray(g), s_hist, y_hist, rho, count)
    want = _two_loop_lists(g, pairs[-m:])
    np.testing.assert_allclose(got, want, rtol=2e-4,
                               atol=2e-5 * np.abs(want).max())


def _lane_problems(rng, d):
    """Six per-entity problems that differ in every way a circular history
    needed a per-lane index for. Lane k minimizes

        logistic(X_k, y_k; w) + ½·l2_k·|w|² + c_k·w + ½·relu(−c_k·w − T)²

    0, 1: well-scaled logistic fits, which converge in a few iterations,
       each at its own;
    2: features scaled over three decades: runs past m = 10 iterations, so
       it evicts pairs;
    3: all-zero features and w0 = 0: the gradient is zero, converged at w0;
    4: nothing but the linear term: y = 0 in every pair, so every pair is
       rejected (sᵀy ≤ eps) and the lane runs to the iteration cap;
    5: linear until c·w < −T, curved from there on: rejects its first pairs,
       accepts the later ones.
    """
    E, n = 6, 40
    X = rng.normal(size=(E, n, d)).astype(np.float32)
    X[2] *= np.logspace(-1.5, 1.5, d).astype(np.float32)
    w_true = rng.normal(size=(E, d))
    y = (rng.uniform(size=(E, n))
         < 1 / (1 + np.exp(-np.einsum("end,ed->en", X, w_true)))
         ).astype(np.float32)
    X[3:] = 0.0
    l2 = np.array([0.1, 1.0, 1e-3, 1.0, 0.0, 0.0], np.float32)
    c = np.zeros((E, d), np.float32)
    c[4] = rng.normal(size=d)
    c[5] = rng.normal(size=d)
    c[4:] /= np.linalg.norm(c[4:], axis=1, keepdims=True)
    bend = np.array([0, 0, 0, 0, 0, 1], np.float32)
    return tuple(jnp.asarray(a) for a in (X, y, l2, c, bend))


def _lane_objective(threshold, X, y, l2, c, bend):
    batch = LabeledBatch.build(X, y)

    def vg(w):
        f, g = agg.value_and_gradient(losses.LOGISTIC, w, batch)
        over = bend * jnp.maximum(-jnp.dot(c, w) - threshold, 0.0)
        return (f + 0.5 * l2 * jnp.dot(w, w) + jnp.dot(c, w)
                + 0.5 * over * over,
                g + l2 * w + c - over * c)
    return vg


@pytest.mark.parametrize("owlqn", [False, True], ids=["lbfgs", "owlqn"])
def test_vmapped_lanes_that_differ_match_individual_solves(owlqn):
    """The vmapped machine gives each lane the iterate, the iteration count
    and the evaluation count of the same problem solved alone (extends
    ``test_vmapped_lbfgs_matches_individual``). The strong-Wolfe search
    doubles its step 24 times before it gives up on lane 5's linear stretch,
    OWL-QN's Armijo search takes unit steps: hence the two thresholds. The
    tolerance stops every lane well above f32's rounding, where a batched
    and an unbatched reduction's last bits would decide a trial."""
    d = 8
    cfg = OptimizerConfig(max_iterations=30, tolerance=1e-4)
    data = _lane_problems(np.random.default_rng(2027), d)
    threshold = 3.5 if owlqn else 2e7
    l1 = jnp.full((d,), 0.05) if owlqn else None

    def solve(*lane):
        return lbfgs.minimize(_lane_objective(threshold, *lane),
                              jnp.zeros((d,), jnp.float32), cfg,
                              l1_weights=l1)

    outs = jax.jit(jax.vmap(solve))(*data)
    alone = jax.jit(solve)
    for k in range(6):
        one = alone(*(a[k] for a in data))
        assert int(outs.iterations[k]) == int(one.iterations), k
        assert int(outs.evaluations[k]) == int(one.evaluations), k
        assert bool(outs.converged[k]) == bool(one.converged), k
        np.testing.assert_allclose(outs.w[k], one.w, rtol=1e-4, atol=1e-5,
                                   err_msg=f"lane {k}")
    its = [int(i) for i in outs.iterations]
    evals = [int(e) for e in outs.evaluations]
    assert its[0] != its[1] and 0 < its[0] < 30 and 0 < its[1] < 30, its
    assert its[2] > cfg.history_length, its  # evicts
    assert its[3] == 0 and evals[3] == 1, (its, evals)  # converged at w0
    assert its[4] == 30, its  # never a curvature pair, never converges
    if not owlqn:  # every search of the linear lane runs out of trials
        assert evals[4] == 1 + 30 * cfg.max_line_search_steps, evals
    assert 1 < its[5] < 30 and bool(outs.converged[5]), its


@pytest.mark.parametrize("owlqn", [False, True], ids=["lbfgs", "owlqn"])
def test_vmapped_solve_compiles_without_gather_or_scatter(owlqn, rng):
    """A per-lane index (a ring's head, ``history.at[it]``) lowers to a
    gather or scatter with one index per lane, which a TPU walks lane by
    lane (PERF.md §6, PR 27). The whole result is kept, the histories
    included, so nothing is dead code."""
    E, n, d = 16, 12, 8
    X = jnp.asarray(rng.normal(size=(E, n, d)), jnp.float32)
    y = jnp.asarray(rng.uniform(size=(E, n)) < 0.5, jnp.float32)
    cfg = OptimizerConfig(max_iterations=25, tolerance=1e-7)

    def solve(X, y, w0):
        vg = with_l2(lambda w: agg.value_and_gradient(
            losses.LOGISTIC, w, LabeledBatch.build(X, y)), 1.0)
        return lbfgs.minimize(
            vg, w0, cfg, l1_weights=jnp.full((d,), 0.1) if owlqn else None)

    lowered = jax.jit(jax.vmap(solve)).lower(X, y, jnp.zeros((E, d)))
    for text in (lowered.as_text(), lowered.compile().as_text()):
        found = re.findall(
            r"(?:stablehlo\.|[\]})] )(gather|scatter|dynamic_slice|"
            r"dynamic-slice|dynamic_update_slice|dynamic-update-slice)\b",
            text)
        assert found == [], found


# -- OWL-QN's ValueOracle, and the folded ring history (ISSUE 33) -----------

def _poisson_l1_problem(rng, n=400, d=60):
    """A sparse-ish Poisson regression with row offsets, as an objective
    and as the pieces a ``ValueOracle`` is made of."""
    X = jnp.asarray((rng.uniform(size=(n, d)) < 0.15) * rng.normal(
        size=(n, d)) * 0.5, jnp.float32)
    w_true = np.where(rng.uniform(size=d) < 0.3, rng.normal(size=d), 0.0
                      ) * min(1.0, (60 / d) ** 0.5)
    off = jnp.asarray(np.log(rng.geometric(0.6, size=n)), jnp.float32)
    y = jnp.asarray(rng.poisson(np.exp(np.asarray(off) + np.asarray(X)
                                       @ w_true - 1.0)), jnp.float32)
    calls = {"gradient": 0}

    def value_at(z):
        return jnp.sum(jnp.exp(z) - y * z)

    def vg(w):
        calls["gradient"] += 1
        z = X @ w + off
        return value_at(z), X.T @ (jnp.exp(z) - y)

    def start(w):
        z = X @ w + off
        return value_at(z), X.T @ (jnp.exp(z) - y), z

    def trial(w):
        z = X @ w + off
        return value_at(z), z

    def accept(w, z):
        return X.T @ (jnp.exp(z) - y)

    return vg, lbfgs.ValueOracle(start, trial, accept), d, calls


@pytest.mark.parametrize("ring", [False, True], ids=["aged", "ring"])
def test_owlqn_through_its_oracle_gives_the_value_and_grad_path_s_iterates(
        ring, rng, monkeypatch):
    """A trial's value from one pass, the gradient once at the accepted
    point: the same iterates as an evaluation a trial, to rounding, and
    ``evaluations`` still counts the values taken, 1 + the trials."""
    if ring:
        monkeypatch.setattr(lbfgs, "_RING_BYTES", 1)
    vg, oracle, d, _ = _poisson_l1_problem(rng)
    cfg = OptimizerConfig(max_iterations=40, tolerance=1e-9)
    l1 = jnp.full((d,), 0.5)
    plain = minimize_owlqn(vg, jnp.zeros(d), l1, cfg)
    asked = minimize_owlqn(vg, jnp.zeros(d), l1, cfg, line=oracle)
    assert int(asked.iterations) == int(plain.iterations) > 5
    np.testing.assert_allclose(asked.value_history, plain.value_history,
                               rtol=1e-6, equal_nan=True)
    np.testing.assert_allclose(asked.w, plain.w, rtol=1e-4, atol=1e-6)
    np.testing.assert_array_equal(asked.trials_history, plain.trials_history)
    np.testing.assert_array_equal(asked.nnz_history, plain.nnz_history)
    trials = np.asarray(asked.trials_history)
    its = int(asked.iterations)
    assert trials[0] == 0 and (trials[1:its + 1] >= 1).all()
    assert (trials[its + 1:] == -1).all()
    assert int(asked.evaluations) == 1 + trials[1:its + 1].sum()
    nnz = np.asarray(asked.nnz_history)
    assert nnz[0] == 0 and nnz[its] == int(np.sum(np.asarray(asked.w) != 0))
    assert 0 < nnz[its] < d  # L1 pruned some and kept some


def test_the_oracle_spares_every_trial_its_gradient(rng):
    """Traced once, so Python-side counts are counts of what the program
    holds: with the oracle the objective's own value_and_grad is never
    called."""
    vg, oracle, d, calls = _poisson_l1_problem(rng)
    cfg = OptimizerConfig(max_iterations=10)
    minimize_owlqn(vg, jnp.zeros(d), jnp.full((d,), 0.5), cfg, line=oracle)
    assert calls["gradient"] == 0
    minimize_owlqn(vg, jnp.zeros(d), jnp.full((d,), 0.5), cfg)
    assert calls["gradient"] == 2  # the start, and the line search's body


def test_each_optimizer_refuses_the_other_s_oracle(rng):
    vg, oracle, d, _ = _poisson_l1_problem(rng)
    line = lbfgs.LineOracle(oracle.start, None, None, None)
    with pytest.raises(ValueError, match="OWL-QN takes a ValueOracle"):
        lbfgs.minimize(vg, jnp.zeros(d), l1_weights=jnp.ones(d), line=line)
    with pytest.raises(ValueError, match="L-BFGS takes a LineOracle"):
        lbfgs.minimize(vg, jnp.zeros(d), line=oracle)
    with pytest.raises(ValueError, match="ValueOracle"):
        optimize(vg, jnp.zeros(d),
                 OptimizerConfig(optimizer_type=OptimizerType.OWLQN),
                 l1_weights=jnp.ones(d), line=line)


@pytest.mark.parametrize("owlqn", [False, True], ids=["lbfgs", "owlqn"])
def test_the_folded_ring_history_gives_the_aged_history_s_iterates(
        owlqn, rng, monkeypatch):
    """From ``_RING_BYTES`` up the solve runs on (rows, 1024) vectors with
    its history written in place; d here is no multiple of the fold, so
    pad coordinates exist and have to stay inert."""
    vg, _, d, _ = _poisson_l1_problem(rng, n=1000, d=1500)
    cfg = OptimizerConfig(max_iterations=30, tolerance=1e-9)
    l1 = jnp.full((d,), 0.5) if owlqn else None
    smooth = vg if owlqn else with_l2(vg, 1.0)
    aged = lbfgs.minimize(smooth, jnp.zeros(d), cfg, l1_weights=l1)
    monkeypatch.setattr(lbfgs, "_RING_BYTES", 4 * 10 * d)
    ring = lbfgs.minimize(smooth, jnp.zeros(d), cfg, l1_weights=l1)
    assert ring.w.shape == (d,)
    assert int(ring.iterations) == int(aged.iterations) > 10  # wraps m = 10
    np.testing.assert_allclose(ring.value_history, aged.value_history,
                               rtol=2e-5, equal_nan=True)
    np.testing.assert_allclose(ring.w, aged.w, rtol=2e-3, atol=2e-4)
    if owlqn:
        assert int(np.sum(np.asarray(ring.w) == 0)) > 0


def test_a_small_or_batched_solve_keeps_the_aged_history(rng):
    """The ring is for one large unbatched solve: every shape a cell's
    vmapped bucket solves or its 2**20-column fixed effect has stays under
    ``_RING_BYTES`` (their compiled programs do not change)."""
    assert 10 * (1 << 20) * 4 < lbfgs._RING_BYTES <= 10 * 54_686_452 * 4
    folded = lbfgs._fold(jnp.arange(3000, dtype=jnp.float32))
    assert folded.shape == (8, 1024) and float(folded.sum()) == 3000 * 2999 / 2


def test_a_trial_that_overflows_fails_alone(rng):
    """Poisson: ``exp`` of a long step's margins is inf in float32. The
    trial is refused and the step halved; under vmap a lane's inf stays in
    its lane."""
    X = jnp.asarray(rng.normal(size=(2, 50, 4)), jnp.float32)
    y = jnp.asarray(rng.poisson(2.0, size=(2, 50)), jnp.float32)
    off = jnp.stack([jnp.zeros(50), jnp.full((50,), 80.0)])  # lane 1: e^80

    def solve(X, y, off):
        def vg(w):
            z = X @ w + off
            return jnp.sum(jnp.exp(z) - y * z), X.T @ (jnp.exp(z) - y)
        return lbfgs.minimize(with_l2(vg, 1.0), jnp.zeros(4),
                              OptimizerConfig(max_iterations=50))

    outs = jax.vmap(solve)(X, y, off)
    alone = solve(X[0], y[0], off[0])
    assert np.isfinite(np.asarray(outs.w)).all()
    np.testing.assert_allclose(outs.w[0], alone.w, rtol=1e-5, atol=1e-6)
    assert np.isfinite(float(outs.value[1]))
    assert float(outs.value[1]) <= float(outs.value_history[1][0])


def test_a_tolerance_of_zero_runs_the_iteration_cap(rng):
    """``tolerance`` 0 makes no convergence test: the cap is the budget. Any
    other tolerance is the test it was."""
    vg, _, w_star = _quadratic(6, rng)
    cfg = OptimizerConfig(max_iterations=12)
    stopped = minimize_lbfgs(vg, jnp.zeros(6), cfg)
    assert int(stopped.iterations) < 12 and bool(stopped.converged)
    capped = minimize_lbfgs(vg, jnp.zeros(6), dataclasses.replace(
        cfg, tolerance=0.0))
    assert int(capped.iterations) == 12 and not bool(capped.converged)
    np.testing.assert_allclose(capped.w, w_star, atol=1e-4)
    assert float(capped.value) <= float(stopped.value) + 1e-6
