"""TRON: Trust-Region Newton with conjugate-gradient inner solves.

Reference parity: photon-lib ``optimization/TRON.scala`` — itself a port of
LIBLINEAR's tron.cpp (Lin, Weng & Keerthi 2008): an outer trust-region loop
whose step comes from a Steihaug conjugate-gradient solve of H·s = −g using
Hessian-VECTOR products only (H is never materialized), truncated at the
trust-region boundary.

TPU-first design: both loops are ``lax.while_loop``s compiled into one XLA
program; each CG iteration costs exactly one Hessian-vector product — one
fused matmul pair (+ one psum when distributed), the analogue of the
reference's one ``treeAggregate(HessianVectorAggregator)`` per CG step.
Masked updates make the machine vmappable for per-entity solves, like
photon_ml_tpu/optim/lbfgs.py.

Every product of one CG solve is taken at the same ``w``, so a caller may
hand the product over in two parts: ``curvature(w) -> c``, computed once a
solve before its loop, and ``hvp(c, v)`` each step (for a GLM, ``c`` is the
rows' curvature l''(z(w)) and a product is Xᵀ(c ⊙ X·v): one pass over X
fewer a product). Without ``curvature``, ``c`` is ``w`` itself and the
program is the one the plain ``hvp(w, v)`` traces to.

The products are most of a solve, so the result counts them: ``hvps``, and
``hvp_history[k]`` the CG steps of outer iteration k (0 at the start and
past the end). Under ``vmap`` the CG loop runs until its slowest lane's
residual is small, so a wave computes Σₖ maxₗ ``hvp_history[l, k]``
products in every lane; a lane that has converged runs no CG step of its
own and so does not lengthen the loop. Under a ``curvature``, the result
also counts the curvatures computed: ``curvatures``, and
``curvature_history[k]`` 1 where outer iteration k's CG ran. The Steihaug
loop and its curvature run under the scope ``tron.cg``.

A solve also ends at the objective's own resolution: a rejected step whose
predicted decrease is within a few units in the last place of ``f``
(``floor_stop``). The radius then shrinks and every later step is a
shorter piece of the same CG path, so no later step's decrease could be
resolved either; the solve is converged to the precision of its dtype.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from photon_ml_tpu.optim.common import (Curvature, Hvp, OptResult,
                                        OptimizerConfig, ValueAndGrad,
                                        check_convergence, masked_update,
                                        scoped)

Array = jax.Array

# LIBLINEAR trust-region constants (tron.cpp).
_ETA0, _ETA1, _ETA2 = 1e-4, 0.25, 0.75
_SIGMA1, _SIGMA2, _SIGMA3 = 0.25, 0.5, 4.0
# A rejected step predicted to lower ``f`` by at most this many times
# eps·|f| ends the solve. From the float32 rows of a Yahoo! Music rehearsal
# (20,000 rows): the fixed effect's first rejected steps predict 0.004-0.04
# of eps·|f|; in the tables, steps predicted under eps·|f| read an actual
# decrease within ±1.5 eps·|f| nine times in ten where rejected, and up to
# 2.8 where accepted: the rounding of a lane's sum. Eight covers it, with
# room for the longer sums of a full-size block.
_FLOOR_ULPS = 8.0


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class TronResult(OptResult):
    """``OptResult`` and ``floor_stop``: bool, the solve ended on a rejected
    step whose predicted decrease its objective could not resolve (and not
    on the gradient or the value test). The other solvers' results have no
    such field."""

    floor_stop: Optional[Array] = None
    # Under a ``curvature``: int32, the curvatures the CG solves computed
    # (one a solve), and (max_iterations + 1,) int32, those of each
    # iteration (0 at the start and past the end); None without one.
    curvatures: Optional[Array] = None
    curvature_history: Optional[Array] = None


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class _TronState:
    w: Array
    f: Array
    g: Array
    delta: Array  # trust-region radius
    it: Array
    converged: Array
    failed: Array  # trust region collapsed before convergence
    g0_norm: Array
    value_history: Array
    grad_norm_history: Array
    hvps: Array  # Hessian-vector products taken, all iterations
    hvp_history: Array  # (max_iter + 1,) int32: the CG steps of each
    floor_stop: Array  # ended on a rejected step float32 could not resolve
    # (max_iter + 1,) int32 under a ``curvature``: 1 where CG ran; else None
    curvature_history: Optional[Array]


@scoped("tron.cg")
def _cg_steihaug(hvp, w, g, delta, max_cg, tol_cg, idle, curvature=None):
    """Truncated CG: approximately solve H s = −g within ‖s‖ ≤ delta.

    Returns (s, sHs, gs, steps) where sHs = sᵀHs and gs = gᵀs, the pieces
    needed for the model-decrease computation, and steps the CG iterations
    taken, one Hessian-vector product ``hvp(c, p)`` each, at ``c =
    curvature(w)`` computed once before the loop (``w`` without one).
    ``idle`` (a converged lane of a vmapped solve, whose result is
    discarded) takes none.
    """
    c = w if curvature is None else curvature(w)
    s0 = jnp.zeros_like(g)
    r0 = -g  # residual = -g - H s, s=0
    p0 = r0
    rr0 = jnp.dot(r0, r0)
    cg_tol = tol_cg * jnp.sqrt(rr0)

    def cond(st):
        s, r, p, rr, i, done = st
        return (~done) & (i < max_cg) & (jnp.sqrt(rr) > cg_tol)

    def body(st):
        s, r, p, rr, i, done = st
        hp = hvp(c, p)
        php = jnp.dot(p, hp)
        # Negative curvature or tiny curvature → step to the boundary.
        alpha = rr / jnp.maximum(php, 1e-30)
        s_next = s + alpha * p
        over = (php <= 0.0) | (jnp.linalg.norm(s_next) >= delta)

        # Boundary step: find tau >= 0 with ‖s + tau p‖ = delta.
        ss, sp, pp = jnp.dot(s, s), jnp.dot(s, p), jnp.dot(p, p)
        disc = jnp.sqrt(jnp.maximum(sp * sp + pp * (delta * delta - ss), 0.0))
        tau = (disc - sp) / jnp.maximum(pp, 1e-30)
        s_bound = s + tau * p

        s_new = jnp.where(over, s_bound, s_next)
        r_new = r - jnp.where(over, tau, alpha) * hp
        rr_new = jnp.dot(r_new, r_new)
        beta = rr_new / jnp.maximum(rr, 1e-30)
        p_new = r_new + beta * p
        return (s_new, r_new, p_new, rr_new, i + 1, done | over)

    st = (s0, r0, p0, rr0, jnp.asarray(0, jnp.int32), jnp.asarray(idle))
    s, r, p, rr, i, done = lax.while_loop(cond, body, st)
    sHs = jnp.dot(s, -g - r)  # H s = -g - r by the residual invariant
    gs = jnp.dot(g, s)
    return s, sHs, gs, i


def minimize(
    value_and_grad: ValueAndGrad,
    hvp: Hvp,
    w0: Array,
    config: OptimizerConfig = OptimizerConfig(),
    curvature: Optional[Curvature] = None,
) -> TronResult:
    """Trust-region Newton minimization of a twice-differentiable objective.

    ``hvp(w, v)`` is H·v at ``w``; with ``curvature``, ``hvp(c, v)`` is H·v
    at the point whose ``curvature(w)`` is ``c`` (the module's docstring)."""
    max_iter = config.max_iterations

    f0, g0 = value_and_grad(w0)
    g0_norm = jnp.linalg.norm(g0)
    vh = jnp.full((max_iter + 1,), jnp.nan, jnp.float32).at[0].set(
        f0.astype(jnp.float32))
    gh = jnp.full((max_iter + 1,), jnp.nan, jnp.float32).at[0].set(
        g0_norm.astype(jnp.float32))

    init = _TronState(
        w=w0, f=f0, g=g0,
        delta=g0_norm,  # LIBLINEAR: initial radius = ‖g0‖
        it=jnp.asarray(0, jnp.int32),
        converged=g0_norm <= config.tolerance,
        failed=jnp.asarray(False),
        g0_norm=g0_norm,
        value_history=vh, grad_norm_history=gh,
        hvps=jnp.asarray(0, jnp.int32),
        hvp_history=jnp.zeros((max_iter + 1,), jnp.int32),
        floor_stop=jnp.asarray(False),
        curvature_history=(None if curvature is None else
                           jnp.zeros((max_iter + 1,), jnp.int32)),
    )
    floor_rel = _FLOOR_ULPS * jnp.finfo(f0.dtype).eps

    def body(state: _TronState) -> _TronState:
        s, sHs, gs, steps = _cg_steihaug(
            hvp, state.w, state.g, state.delta, config.max_cg_iterations, 0.1,
            state.converged, curvature)
        prered = -(gs + 0.5 * sHs)  # predicted decrease of the quadratic model
        w_new = state.w + s
        f_new, g_new = value_and_grad(w_new)
        actred = state.f - f_new
        snorm = jnp.linalg.norm(s)

        # Radius update (LIBLINEAR tron.cpp rules, simplified alpha=1 form).
        ratio = actred / jnp.maximum(prered, 1e-30)
        delta = state.delta
        delta = jnp.where(
            ratio < _ETA0, _SIGMA1 * jnp.minimum(delta, snorm),
            jnp.where(
                ratio < _ETA1, jnp.maximum(_SIGMA1 * delta, _SIGMA2 * snorm),
                jnp.where(
                    ratio < _ETA2, delta,  # acceptable step: keep radius
                    jnp.maximum(delta, _SIGMA3 * snorm))))

        accept = (actred > _ETA0 * prered) & jnp.isfinite(f_new)
        w_acc = jnp.where(accept, w_new, state.w)
        f_acc = jnp.where(accept, f_new, state.f)
        g_acc = jnp.where(accept, g_new, state.g)

        gnorm = jnp.linalg.norm(g_acc)
        it = state.it + 1
        # Value-based convergence only counts on accepted steps (a rejected
        # step trivially has Δf = 0); gradient-based convergence is valid at
        # the current iterate regardless of acceptance.
        grad_conv = gnorm <= config.tolerance * jnp.maximum(state.g0_norm, 1.0)
        conv = grad_conv | (accept & check_convergence(
            f_acc, state.f, gnorm, state.g0_norm, config.tolerance))
        # A rejected step whose predicted decrease the objective cannot
        # resolve: every later one is shorter (the module's docstring).
        floor = ~accept & (prered <= floor_rel * jnp.abs(state.f))
        # A collapsed radius with the gradient still large is a true stall.
        stalled = delta < 1e-12

        vh = state.value_history.at[it].set(f_acc.astype(jnp.float32))
        gh = state.grad_norm_history.at[it].set(gnorm.astype(jnp.float32))

        new_state = _TronState(
            w=w_acc, f=f_acc, g=g_acc, delta=delta, it=it,
            converged=state.converged | conv | stalled | floor,
            failed=state.failed | (stalled & ~conv & ~floor),
            g0_norm=state.g0_norm,
            value_history=vh, grad_norm_history=gh,
            hvps=state.hvps + steps,
            hvp_history=state.hvp_history.at[it].set(steps),
            floor_stop=state.floor_stop | (floor & ~conv),
            # A converged lane's count is frozen with the rest of its state.
            curvature_history=(None if curvature is None else
                               state.curvature_history.at[it].set(1)),
        )
        return masked_update(state.converged, new_state, state)

    def cond(state: _TronState):
        return (~state.converged) & (state.it < max_iter)

    final = lax.while_loop(cond, body, init)
    return TronResult(
        w=final.w,
        value=final.f,
        grad_norm=jnp.linalg.norm(final.g),
        iterations=final.it,
        evaluations=final.it + 1,  # the starting point, then one a step
        converged=final.converged & ~final.failed,
        value_history=final.value_history,
        grad_norm_history=final.grad_norm_history,
        hvps=final.hvps,
        hvp_history=final.hvp_history,
        floor_stop=final.floor_stop,
        curvatures=(None if curvature is None else
                    jnp.sum(final.curvature_history)),
        curvature_history=final.curvature_history,
    )
