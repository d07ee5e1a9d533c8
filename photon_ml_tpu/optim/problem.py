"""GLM optimization problems: bind loss + data + regularization + optimizer.

Reference parity: photon-api ``optimization/
GeneralizedLinearOptimizationProblem.scala`` /
``SingleNodeOptimizationProblem.scala`` (the per-entity local solve) and the
config bundles in photon-lib ``optimization/game/
GLMOptimizationConfiguration.scala``. The distributed twin lives in
photon_ml_tpu/parallel/objective.py.

Variance computation (reference ``computeVariances``,
``VarianceComputationType``): SIMPLE = 1/diag(H); FULL = diag(H⁻¹).
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Optional

import jax
import jax.numpy as jnp

from photon_ml_tpu.data.batch import LabeledBatch
from photon_ml_tpu.models.coefficients import Coefficients
from photon_ml_tpu.normalization import NormalizationContext
from photon_ml_tpu.ops import aggregators as agg
from photon_ml_tpu.ops.losses import PointwiseLoss
from photon_ml_tpu.optim import (OptimizerConfig, OptimizerType, OptResult,
                                 RegularizationContext, l1_weights_vector,
                                 optimize, with_l2, with_l2_hvp)
from photon_ml_tpu.optim.common import scoped
from photon_ml_tpu.optim.regularization import intercept_mask

Array = jax.Array


class VarianceComputationType(enum.Enum):
    NONE = "NONE"
    SIMPLE = "SIMPLE"  # 1 / diag(H)
    FULL = "FULL"  # diag(H^-1) — materializes H, small d only


@dataclasses.dataclass(frozen=True)
class GLMOptimizationConfiguration:
    """(optimizer, regularization, variance) bundle for one coordinate.

    Reference parity: GLMOptimizationConfiguration.scala.
    """

    optimizer: OptimizerConfig = OptimizerConfig()
    regularization: RegularizationContext = RegularizationContext()
    variance_computation: VarianceComputationType = VarianceComputationType.NONE
    # Down-sampling rate for this coordinate (1.0 = off); applied by the
    # coordinate, not here (reference: DownSampler).
    down_sampling_rate: float = 1.0


def resolve_optimizer_config(
    opt_cfg: OptimizerConfig, has_l1: bool
) -> OptimizerConfig:
    """L1/elastic-net silently selects OWL-QN (reference behavior)."""
    if has_l1 and OptimizerType(opt_cfg.optimizer_type) == OptimizerType.LBFGS:
        return dataclasses.replace(opt_cfg, optimizer_type=OptimizerType.OWLQN)
    return opt_cfg


def variances_from_diagonal(diag: Array, l2: float, reg_mask: Array) -> Array:
    """SIMPLE variances: elementwise 1/(diag(H) + λ·mask)."""
    return 1.0 / jnp.maximum(diag + l2 * reg_mask, 1e-12)


def variances_from_matrix(H: Array, l2: float, reg_mask: Array) -> Array:
    """FULL variances: diag(H⁻¹) with the L2 term on the diagonal."""
    dim = H.shape[-1]
    eye = jnp.eye(dim, dtype=H.dtype)
    H = H + jnp.diag(l2 * reg_mask) + 1e-9 * eye
    return jnp.diagonal(jnp.linalg.solve(H, eye))


def make_objective(
    loss: PointwiseLoss,
    batch: LabeledBatch,
    norm: NormalizationContext,
    reg: RegularizationContext,
    intercept_index: Optional[int],
    dim: int,
):
    """Build (value_and_grad, hvp, l1_weights) for a local batch."""
    mask = jnp.asarray(intercept_mask(dim, intercept_index))

    def vg(w: Array):
        return agg.value_and_gradient(loss, w, batch, norm)

    def hvp(w: Array, v: Array):
        return agg.hessian_vector(loss, w, v, batch, norm)

    l2 = reg.l2_weight()
    vg = scoped("glm.value_grad", with_l2(vg, l2, mask))
    hvp = with_l2_hvp(hvp, l2, mask)
    l1 = reg.l1_weight()
    l1_weights = (l1_weights_vector(l1, dim, intercept_index)
                  if l1 > 0.0 else None)
    return vg, hvp, l1_weights


def run(
    loss: PointwiseLoss,
    batch: LabeledBatch,
    config: GLMOptimizationConfiguration,
    initial: Optional[Coefficients] = None,
    norm: NormalizationContext = NormalizationContext(),
    intercept_index: Optional[int] = None,
) -> tuple[Coefficients, OptResult]:
    """Solve one GLM on one local batch (SingleNodeOptimizationProblem.run).

    Pure and jit/vmap-compatible given fixed shapes; the vmapped form is the
    random-effect per-entity path.
    """
    dim = batch.dim
    w0 = initial.means if initial is not None else jnp.zeros(
        (dim,), batch.features.dtype)
    vg, hvp, l1w = make_objective(loss, batch, norm, config.regularization,
                                  intercept_index, dim)
    opt_cfg = resolve_optimizer_config(config.optimizer, l1w is not None)
    result = optimize(vg, w0, opt_cfg, hvp=hvp, l1_weights=l1w)
    variances = compute_variances(loss, result.w, batch, norm,
                                  config.variance_computation,
                                  config.regularization, intercept_index)
    return Coefficients(means=result.w, variances=variances), result


def compute_variances(
    loss: PointwiseLoss,
    w: Array,
    batch: LabeledBatch,
    norm: NormalizationContext,
    kind: VarianceComputationType,
    reg: RegularizationContext,
    intercept_index: Optional[int],
) -> Optional[Array]:
    """Coefficient variance estimates from the Hessian at the optimum.

    Reference parity: GeneralizedLinearOptimizationProblem.computeVariances:
    SIMPLE → elementwise 1/diag(H); FULL → diag(H⁻¹). L2 contributes λ to
    regularized diagonal entries.
    """
    kind = VarianceComputationType(kind)
    if kind == VarianceComputationType.NONE:
        return None
    l2 = reg.l2_weight()
    mask = jnp.asarray(intercept_mask(w.shape[-1], intercept_index))
    if kind == VarianceComputationType.SIMPLE:
        return variances_from_diagonal(
            agg.hessian_diagonal(loss, w, batch, norm), l2, mask)
    return variances_from_matrix(
        agg.hessian_matrix(loss, w, batch, norm), l2, mask)
