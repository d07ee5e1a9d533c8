"""Optimizers: L-BFGS, OWL-QN, TRON as compiled state machines.

Reference parity: photon-lib ``optimization/`` — ``Optimizer.scala``,
``OptimizerFactory.scala``, ``LBFGS.scala``, ``OWLQN.scala``, ``TRON.scala``.
"""

from __future__ import annotations

from typing import Optional

import jax

from photon_ml_tpu.optim import lbfgs as _lbfgs
from photon_ml_tpu.optim import tron as _tron
from photon_ml_tpu.optim.common import (Curvature, Hvp, OptResult,
                                        OptimizerConfig, OptimizerType,
                                        ValueAndGrad)
from photon_ml_tpu.optim.regularization import (RegularizationContext,
                                                RegularizationType,
                                                intercept_mask,
                                                l1_weights_vector, with_l2,
                                                with_l2_hvp)

Array = jax.Array

LineOracle = _lbfgs.LineOracle
ValueOracle = _lbfgs.ValueOracle
minimize_lbfgs = _lbfgs.minimize
minimize_owlqn = _lbfgs.minimize_owlqn
minimize_tron = _tron.minimize


def optimize(
    value_and_grad: ValueAndGrad,
    w0: Array,
    config: OptimizerConfig,
    *,
    hvp: Optional[Hvp] = None,
    l1_weights: Optional[Array] = None,
    line: "Optional[LineOracle | ValueOracle]" = None,
    curvature: Optional[Curvature] = None,
) -> OptResult:
    """Dispatch on OptimizerType (reference: OptimizerFactory.scala).

    ``value_and_grad`` must already include any L2 term (use ``with_l2``);
    ``l1_weights`` routes to OWL-QN; TRON additionally needs ``hvp``.
    ``line`` is the same objective taken apart for the line search
    (optim/lbfgs.py): a ``LineOracle`` for L-BFGS, a ``ValueOracle`` for
    OWL-QN, and ``minimize`` refuses the one under the other; TRON has no
    line search and does not ask it. ``curvature`` is TRON's alone: with it
    ``hvp`` takes what it returns in place of ``w`` (optim/tron.py).
    """
    t = OptimizerType(config.optimizer_type)
    if t == OptimizerType.LBFGS:
        if l1_weights is not None:
            raise ValueError("L1 regularization requires OWLQN, not LBFGS")
        return minimize_lbfgs(value_and_grad, w0, config, line=line)
    if t == OptimizerType.OWLQN:
        if l1_weights is None:
            raise ValueError("OWLQN requires l1_weights (else use LBFGS)")
        return minimize_owlqn(value_and_grad, w0, l1_weights, config,
                              line=line)
    if t == OptimizerType.TRON:
        if hvp is None:
            raise ValueError("TRON requires a Hessian-vector product (hvp)")
        if l1_weights is not None:
            raise ValueError("TRON does not support L1 (reference parity)")
        return minimize_tron(value_and_grad, hvp, w0, config,
                             curvature=curvature)
    if t in (OptimizerType.SDCA, OptimizerType.SGD):
        raise ValueError(
            f"{t.value} is a streamed-path stochastic solver (it needs "
            f"the chunk feed for its per-row/per-chunk updates) — use "
            f"the streaming coordinate (GameEstimator(streaming=...) / "
            f"game_train --streaming solver={t.value.lower()}), not "
            f"optimize()")
    raise ValueError(t)  # pragma: no cover


__all__ = [
    "OptResult", "OptimizerConfig", "OptimizerType", "ValueAndGrad", "Hvp",
    "LineOracle", "ValueOracle",
    "RegularizationContext", "RegularizationType",
    "minimize_lbfgs", "minimize_owlqn", "minimize_tron", "optimize",
    "with_l2", "with_l2_hvp", "l1_weights_vector", "intercept_mask",
]
