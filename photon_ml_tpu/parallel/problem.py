"""Distributed GLM optimization problem: the fixed-effect training path.

Reference parity: photon-api ``optimization/DistributedOptimizationProblem.
scala`` — binds (optimizer, distributed objective, regularization, variance
mode) and runs the full L-BFGS/TRON/OWL-QN fit over the cluster. Here the
"cluster" is a device mesh and the entire fit is one jit-compiled program:
the optimizer's while_loop body contains the psum-reduced objective, so a
whole training run is a single XLA executable with zero host round-trips.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from photon_ml_tpu.data.batch import LabeledBatch
from photon_ml_tpu.models.coefficients import Coefficients
from photon_ml_tpu.normalization import NormalizationContext
from photon_ml_tpu.ops.losses import PointwiseLoss
from photon_ml_tpu.optim import (OptResult, OptimizerType,
                                 l1_weights_vector, optimize, with_l2,
                                 with_l2_hvp)
from photon_ml_tpu.optim.common import scoped
from photon_ml_tpu.optim.problem import (GLMOptimizationConfiguration,
                                         VarianceComputationType,
                                         resolve_optimizer_config,
                                         takes_line_oracle,
                                         variances_from_diagonal,
                                         variances_from_matrix)
from photon_ml_tpu.optim.regularization import intercept_mask
from photon_ml_tpu.parallel import objective as dobj
from photon_ml_tpu.parallel.mesh import shard_batch

Array = jax.Array


def run(
    loss: PointwiseLoss,
    batch: LabeledBatch,
    mesh: Mesh,
    config: GLMOptimizationConfiguration,
    initial: Optional[Coefficients] = None,
    norm: NormalizationContext = NormalizationContext(),
    intercept_index: Optional[int] = None,
    already_sharded: bool = False,
) -> tuple[Coefficients, OptResult]:
    """Fit one GLM over the mesh (DistributedOptimizationProblem.run).

    Plain L-BFGS takes the objective apart as a ``LineOracle``
    (``objective.make_line_oracle``): its line search then reads rows, and
    an iteration is one pair of passes over X whatever its trials. OWL-QN
    and TRON evaluate the objective as before."""
    if not already_sharded:
        batch = shard_batch(batch, mesh)
    dim = batch.dim
    mask = jnp.asarray(intercept_mask(dim, intercept_index))
    reg = config.regularization
    l2 = reg.l2_weight()

    vg = scoped("glm.value_grad", with_l2(
        dobj.make_value_and_gradient(loss, mesh, batch, norm), l2, mask))
    hvp = with_l2_hvp(dobj.make_hvp(loss, mesh, batch, norm), l2, mask)

    l1 = reg.l1_weight()
    l1w = l1_weights_vector(l1, dim, intercept_index) if l1 > 0.0 else None
    opt_cfg = resolve_optimizer_config(config.optimizer, l1w is not None)

    line = None
    if takes_line_oracle(config):
        line = dobj.make_line_oracle(loss, mesh, batch, norm, reg,
                                     intercept_index, dim)

    w0 = initial.means if initial is not None else jnp.zeros(
        (dim,), batch.features.dtype)
    result = optimize(vg, w0, opt_cfg, hvp=hvp, l1_weights=l1w, line=line)

    variances = None
    kind = VarianceComputationType(config.variance_computation)
    if kind == VarianceComputationType.SIMPLE:
        variances = variances_from_diagonal(
            dobj.make_hessian_diagonal(loss, mesh, batch, norm)(result.w),
            l2, mask)
    elif kind == VarianceComputationType.FULL:
        variances = variances_from_matrix(
            dobj.make_hessian_matrix(loss, mesh, batch, norm)(result.w),
            l2, mask)

    return Coefficients(means=result.w, variances=variances), result


def run_grid(
    loss: PointwiseLoss,
    batch: LabeledBatch,
    mesh: Mesh,
    config: GLMOptimizationConfiguration,
    lambdas,
    initial: Optional[Coefficients] = None,
    norm: NormalizationContext = NormalizationContext(),
    intercept_index: Optional[int] = None,
    already_sharded: bool = False,
) -> tuple[Array, OptResult]:
    """Fit the SAME GLM at every L2 weight in ``lambdas`` as ONE compiled
    program — the whole solver ``vmap``-ped over the regularization axis
    (SURVEY §2.5 P5's optional vmap-over-λ; the reference loops its
    reg-weight grid sequentially through Spark jobs).

    Returns ``(W, results)`` with ``W`` of shape (len(lambdas), dim) and a
    stacked OptResult (per-λ leaves). L2/NONE regularization with
    L-BFGS/TRON only — L1 grids (OWL-QN's per-λ orthant sets) and variance
    computation stay on the sequential :func:`run` path.
    """
    reg = config.regularization
    if reg.l1_weight() > 0.0:
        raise ValueError("run_grid handles L2/NONE grids; L1 grids use "
                         "sequential run() (OWL-QN per-λ orthant sets)")
    if OptimizerType(config.optimizer.optimizer_type) == OptimizerType.OWLQN:
        raise ValueError("run_grid supports L-BFGS/TRON; OWL-QN exists for "
                         "L1 objectives, which run_grid does not handle")
    if VarianceComputationType(config.variance_computation) != \
            VarianceComputationType.NONE:
        raise ValueError("run_grid does not compute variances; evaluate "
                         "them per selected model via run()")
    if not already_sharded:
        batch = shard_batch(batch, mesh)
    dim = batch.dim
    mask = jnp.asarray(intercept_mask(dim, intercept_index))
    base_vg = dobj.make_value_and_gradient(loss, mesh, batch, norm)
    base_hvp = dobj.make_hvp(loss, mesh, batch, norm)
    opt_cfg = resolve_optimizer_config(config.optimizer, False)
    w0 = initial.means if initial is not None else jnp.zeros(
        (dim,), batch.features.dtype)

    def solve(lam):
        # λ is a traced vmap lane — fold it inline (with_l2's zero-weight
        # shortcut cannot branch on a tracer).
        def vg(w):
            f, g = base_vg(w)
            wm = w * mask
            return f + 0.5 * lam * jnp.sum(wm * wm), g + lam * wm

        def hvp(w, v):
            return base_hvp(w, v) + lam * (v * mask)

        return optimize(vg, w0, opt_cfg, hvp=hvp)

    results = jax.vmap(solve)(jnp.asarray(lambdas, jnp.float32))
    return results.w, results
