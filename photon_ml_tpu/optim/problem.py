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
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from photon_ml_tpu.data.batch import LabeledBatch
from photon_ml_tpu.models.coefficients import Coefficients
from photon_ml_tpu.normalization import NormalizationContext
from photon_ml_tpu.ops import aggregators as agg
from photon_ml_tpu.ops.losses import PointwiseLoss
from photon_ml_tpu.optim import (LineOracle, OptimizerConfig, OptimizerType,
                                 OptResult, RegularizationContext,
                                 l1_weights_vector, optimize, with_l2,
                                 with_l2_hvp)
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


def make_line_oracle(
    loss: PointwiseLoss,
    batch: LabeledBatch,
    norm: NormalizationContext,
    reg: RegularizationContext,
    intercept_index: Optional[int],
    dim: int,
    total: Callable[[Array], Array] = lambda x: x,
) -> LineOracle:
    """``make_objective``'s smooth objective, Σ wᵢ·l(zᵢ, yᵢ) + ½·λ‖w∘mask‖²
    with z = X'·w + offset, taken apart for L-BFGS's line search
    (optim/lbfgs.py). An evaluation is two passes over ``batch.features``,
    and under ``vmap`` a wave of bucket solves pays its slowest lane's
    trials at every iteration. Along w + αd the margins are z + α·(X'·d)
    and ‖(w + αd)∘mask‖² a quadratic in α: ``along`` crosses the block once
    for X'·d (the same affine map as the margins' without the offset, so
    factors and shifts stay linear in d) and takes the quadratic's two dot
    products; a trial then reads rows (margins, labels, weights) and
    scalars, no feature and no coefficient; ``accept`` crosses once more
    for the gradient. Zero-weight rows keep margin 0 and weight 0, as
    ``agg.margins`` and ``agg._masked`` hold them.

    What is carried from point to point is the margins and that squared
    norm, both as the line gave them: the value ``accept`` reports is then
    the accepted trial's own to the bit, and the next search's φ(0) equals
    it, as they do where every trial is an evaluation. The stopping rule
    compares consecutive values at float32's last place, where a value
    recomputed another way (the L2 term from the coefficients) sits a
    unit apart from its trial's.

    ``total`` sums over every row of the objective what ``batch``'s rows
    give (the value, the gradient, the slope): nothing to do where
    ``batch`` holds them all, a ``psum`` where it is one shard of them
    (``parallel/objective.make_line_oracle``). The L2 terms are added
    after it, once, on coefficients every shard holds whole."""
    mask = jnp.asarray(intercept_mask(dim, intercept_index))
    l2 = reg.l2_weight()
    live = batch.weights > 0.0

    def at(z, ww):
        """(f, the rows' weighted dl/dz) at margins z of a point whose
        masked squared norm is ww."""
        l, dl = loss.loss_and_dz(z, batch.labels)
        f = total(jnp.sum(agg._masked(batch.weights, l), axis=-1))
        return (f + 0.5 * l2 * ww if l2 else f,
                agg._masked(batch.weights, dl))

    def gradient(r, w):
        g = total(norm.pullback_gradient(agg._tmatvec(batch.features, r),
                                         jnp.sum(r, axis=-1)))
        return g + l2 * (w * mask) if l2 else g

    def masked_dot(a, b):
        return jnp.sum((a * mask) * (b * mask), axis=-1)

    def step(ray, alpha):
        """The carry at w + αd."""
        z, u, _, _, quad = ray
        if not l2:
            return z + alpha * u, None
        ww, wd, dd = quad
        return z + alpha * u, ww + alpha * (2.0 * wd + alpha * dd)

    @scoped("glm.value_grad")
    def start(w):
        carry = agg.margins(batch, w, norm), masked_dot(w, w) if l2 else None
        f, r = at(*carry)
        return f, gradient(r, w), carry

    @scoped("glm.value_grad")
    def along(carry, w, d):
        z, ww = carry
        d_eff, shift = norm.effective_coefficients(d)
        u = jnp.where(live, agg._matvec(batch.features, d_eff)
                      + jnp.expand_dims(shift, -1), 0.0)
        quad = (ww, masked_dot(w, d), masked_dot(d, d)) if l2 else None
        return z, u, w, d, quad

    @scoped("glm.value_grad")
    def trial(ray, alpha):
        _, u, _, _, quad = ray
        f, r = at(*step(ray, alpha))
        slope = total(jnp.sum(r * u, axis=-1))
        if l2:
            _, wd, dd = quad
            slope = slope + l2 * (wd + alpha * dd)
        return f, slope

    @scoped("glm.value_grad")
    def accept(ray, alpha):
        _, _, w, d, _ = ray
        carry = step(ray, alpha)
        f, r = at(*carry)
        return f, gradient(r, w + alpha * d), carry

    return LineOracle(start, along, trial, accept)


def takes_line_oracle(config: GLMOptimizationConfiguration) -> bool:
    """Whether an L-BFGS solve of ``config`` goes through a ``LineOracle``:
    plain L-BFGS does; OWL-QN's trial points leave the line (an L1 weight)
    and TRON has no line search. What a coordinate can see of its solve,
    and all it decides by."""
    return (config.regularization.l1_weight() == 0.0
            and OptimizerType(config.optimizer.optimizer_type)
            == OptimizerType.LBFGS)


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
