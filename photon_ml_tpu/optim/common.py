"""Shared optimizer scaffolding: configs, results, convergence, tracking.

Reference parity: photon-lib ``optimization/Optimizer.scala``,
``OptimizerConfig.scala``, ``OptimizerType.scala``,
``OptimizationStatesTracker.scala`` / ``OptimizerState.scala``.

TPU-first design: optimizers are pure functions ``(objective, w0) → OptResult``
compiled as ``lax.while_loop`` state machines with static shapes. Two
requirements shape everything here (SURVEY.md §7):

1. **vmap-ability** — the same optimizer must run as one big fixed-effect
   solve AND as thousands of per-entity random-effect solves batched under
   ``vmap``. Under vmap, ``while_loop`` keeps stepping until every lane's
   cond is false, and *done lanes keep executing the body*; therefore every
   state update is masked with the per-lane ``converged`` flag so finished
   lanes are frozen rather than perturbed.
2. **fixed-shape history** — per-iteration (value, grad-norm) history is
   recorded into preallocated ``max_iterations``-length buffers (the
   ``OptimizationStatesTracker`` analogue), NaN-padded past convergence.
"""

from __future__ import annotations

import dataclasses
import enum
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

Array = jax.Array

# objective(w) -> (value, grad). Regularization is folded in by the caller
# (see photon_ml_tpu/optim/regularization.py).
ValueAndGrad = Callable[[Array], tuple[Array, Array]]
# hvp(w, v) -> H·v for TRON; under a curvature, hvp(curvature(w), v).
Hvp = Callable[[Array, Array], Array]
# curvature(w) -> what every product of one CG solve at w shares.
Curvature = Callable[[Array], Array]


class OptimizerType(enum.Enum):
    LBFGS = "LBFGS"
    OWLQN = "OWLQN"
    TRON = "TRON"
    # Stochastic solvers — streamed path only (optim/stochastic.py):
    # duality-gap-certified dual coordinate ascent and its primal
    # mini-batch fallback. ``optimize()`` rejects them (there is no
    # compiled device-resident variant); the streamed coordinate
    # dispatches them behind the minimize_streaming contract.
    SDCA = "SDCA"
    SGD = "SGD"


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    """Reference parity: OptimizerConfig (type, maxIter, tolerance)."""

    optimizer_type: OptimizerType = OptimizerType.LBFGS
    max_iterations: int = 100
    # Relative tolerance of ``check_convergence``; 0 asks for no such test:
    # the iteration cap is the solve's budget.
    tolerance: float = 1e-7
    # L-BFGS/OWL-QN history length (Breeze default m=10).
    history_length: int = 10
    # Max line-search / inner-CG steps (static bounds for while_loops).
    max_line_search_steps: int = 25
    max_cg_iterations: int = 20
    # Strong-Wolfe constants (Breeze StrongWolfeLineSearch defaults):
    # sufficient decrease c1, curvature c2.
    wolfe_c1: float = 1e-4
    wolfe_c2: float = 0.9


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class OptResult:
    """Final state + per-iteration history (OptimizationStatesTracker)."""

    w: Array
    value: Array
    grad_norm: Array
    iterations: Array  # int32, iterations actually executed
    # int32: objective evaluations actually executed (value-and-gradient
    # calls, the starting one and every line-search trial included)
    evaluations: Array
    converged: Array  # bool
    value_history: Array  # (max_iterations + 1,), NaN past the end
    grad_norm_history: Array  # (max_iterations + 1,), NaN past the end
    # OWL-QN only: (max_iterations + 1,) int32, −1 past the end: the trials
    # of each iteration's line search (0 at the start) and the non-zero
    # coefficients of the iterate it ended on
    trials_history: Optional[Array] = None
    nnz_history: Optional[Array] = None
    # L-BFGS and OWL-QN: int32, the trials of all the solve's line searches,
    # whatever a trial cost (under a LineOracle no pass over the data, so
    # ``evaluations`` no longer holds them)
    trials: Optional[Array] = None
    # TRON only: int32, the Hessian-vector products of its CG solves (one a
    # CG step), and (max_iterations + 1,) int32, those of each iteration
    # (0 at the start and past the end)
    hvps: Optional[Array] = None
    hvp_history: Optional[Array] = None


def scoped(name: str, fn: Optional[Callable] = None) -> Callable:
    """``fn`` with every operation it traces under ``jax.named_scope(name)``
    — the device-side names of docs/OBSERVABILITY.md's scope vocabulary;
    with ``fn`` left out, a decorator. Compile-time metadata only: the
    program, its name and its results are unchanged."""
    if fn is None:
        return functools.partial(scoped, name)

    @functools.wraps(fn)
    def wrapped(*args):
        with jax.named_scope(name):
            return fn(*args)
    return wrapped


def masked_update(converged: Array, new, old):
    """Freeze a pytree once this lane has converged (vmap safety)."""
    def _sel(n, o):
        c = jnp.reshape(converged, converged.shape + (1,) * (n.ndim - converged.ndim))
        return jnp.where(c, o, n)
    return jax.tree.map(_sel, new, old)


def check_convergence(
    value: Array,
    prev_value: Array,
    grad_norm: Array,
    initial_grad_norm: Array,
    tolerance: float,
) -> Array:
    """Photon/Breeze-style convergence: relative gradient norm OR relative
    objective-change below tolerance.

    Reference parity: Optimizer.scala convergence checks
    (``relativeTolerance`` on both loss delta and gradient norm).

    A ``tolerance`` of 0 makes no test (the same program as before for any
    other): the solve runs to its iteration cap, or until a line search
    fails. A float32 objective of 7e5 resolves 0.06, so whether a warm
    step's change passes 1e-7 of it is rounding's to say, and a solve then
    takes 5 iterations or 25 by the flip of a coin (PERF.md section 6, PR
    33): a job that budgets its sweeps by iterations says so with 0.
    """
    if tolerance <= 0.0:
        return jnp.asarray(False)
    grad_ok = grad_norm <= tolerance * jnp.maximum(initial_grad_norm, 1.0)
    val_ok = jnp.abs(value - prev_value) <= tolerance * jnp.maximum(
        jnp.abs(prev_value), 1e-12)
    return grad_ok | val_ok
