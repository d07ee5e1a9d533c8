"""Regularization contexts and objective wrappers.

Reference parity: photon-lib ``optimization/RegularizationContext.scala`` /
``RegularizationType.scala`` — NONE, L1, L2, ELASTIC_NET with mixing weight
alpha: l1 = alpha*lambda, l2 = (1-alpha)*lambda. L2 is folded into the smooth
objective's value/gradient/Hessian; L1 is handled by OWL-QN's pseudo-gradient
(never differentiated).

The ``reg_mask`` vector excludes coordinates from regularization — the
reference excludes the intercept (OWLQN.scala: L1 weight 0 for intercept).
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


class RegularizationType(enum.Enum):
    NONE = "NONE"
    L1 = "L1"
    L2 = "L2"
    ELASTIC_NET = "ELASTIC_NET"


@dataclasses.dataclass(frozen=True)
class RegularizationContext:
    reg_type: RegularizationType = RegularizationType.NONE
    reg_weight: float = 0.0
    # Elastic-net mixing: l1 = alpha * weight, l2 = (1 - alpha) * weight.
    elastic_net_alpha: float = 0.5

    def l1_weight(self) -> float:
        t = RegularizationType(self.reg_type)
        if t == RegularizationType.L1:
            return self.reg_weight
        if t == RegularizationType.ELASTIC_NET:
            return self.elastic_net_alpha * self.reg_weight
        return 0.0

    def l2_weight(self) -> float:
        t = RegularizationType(self.reg_type)
        if t == RegularizationType.L2:
            return self.reg_weight
        if t == RegularizationType.ELASTIC_NET:
            return (1.0 - self.elastic_net_alpha) * self.reg_weight
        return 0.0


def intercept_mask(dim: int, intercept_index: Optional[int]) -> np.ndarray:
    """1.0 for regularized coordinates, 0.0 for the intercept."""
    mask = np.ones((dim,), np.float32)
    if intercept_index is not None:
        mask[intercept_index] = 0.0
    return mask


def with_l2(
    value_and_grad: Callable[[Array], tuple[Array, Array]],
    l2_weight: float,
    reg_mask: Optional[Array] = None,
) -> Callable[[Array], tuple[Array, Array]]:
    """Fold 0.5·λ‖w∘mask‖² into a smooth objective."""
    if l2_weight == 0.0:
        return value_and_grad

    def wrapped(w: Array) -> tuple[Array, Array]:
        f, g = value_and_grad(w)
        wm = w if reg_mask is None else w * reg_mask
        f = f + 0.5 * l2_weight * jnp.sum(wm * wm, axis=-1)
        g = g + l2_weight * wm
        return f, g

    return wrapped


def with_l2_value(
    value_fn: Callable[[Array], Array],
    l2_weight: float,
    reg_mask: Optional[Array] = None,
) -> Callable[[Array], Array]:
    """Value-only companion of :func:`with_l2` — for streamed line-search
    probes where the gradient pass is deferred to acceptance."""
    if l2_weight == 0.0:
        return value_fn

    def wrapped(w: Array) -> Array:
        wm = w if reg_mask is None else w * reg_mask
        return value_fn(w) + 0.5 * l2_weight * jnp.sum(wm * wm, axis=-1)

    return wrapped


def with_l2_hvp(
    hvp: Callable[[Array, Array], Array],
    l2_weight: float,
    reg_mask: Optional[Array] = None,
) -> Callable[[Array, Array], Array]:
    """``hvp`` plus the L2 term's H·v, λ·(v∘mask). The term reads ``v``
    alone, so the first argument (``w``, or TRON's curvature) passes
    through untouched."""
    if l2_weight == 0.0:
        return hvp

    def wrapped(w: Array, v: Array) -> Array:
        hv = hvp(w, v)
        vm = v if reg_mask is None else v * reg_mask
        return hv + l2_weight * vm

    return wrapped


def l1_weights_vector(
    l1_weight: float, dim: int, intercept_index: Optional[int],
    dtype=jnp.float32,
) -> Array:
    """Per-coordinate L1 weights for OWL-QN (intercept excluded)."""
    return jnp.asarray(l1_weight * intercept_mask(dim, intercept_index),
                       dtype=dtype)
