"""Sparse distributed GLM fit: the Criteo-path optimization problem.

Reference parity: optimization/DistributedOptimizationProblem.scala bound to
a sparse DistributedGLMLossFunction. Same optimizer state machines as the
dense path (L-BFGS / OWL-QN / TRON run on the dense (d,) coefficient
vector); only the objective evaluation is sparse. With
``feature_sharded=True`` the coefficient dimension is padded to a multiple
of the mesh's ``model`` axis and every optimizer array (w, grads, L-BFGS
history) carries that sharding — XLA partitions the two-loop recursion's
dots and axpys automatically.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from photon_ml_tpu.data.sparse import SparseBatch
from photon_ml_tpu.models.coefficients import Coefficients
from photon_ml_tpu.ops.losses import PointwiseLoss
from photon_ml_tpu.optim import (LineOracle, OptResult, ValueOracle,
                                 l1_weights_vector, optimize, with_l2,
                                 with_l2_hvp)
from photon_ml_tpu.optim.common import scoped
from photon_ml_tpu.optim.problem import (GLMOptimizationConfiguration,
                                         VarianceComputationType,
                                         resolve_optimizer_config,
                                         variances_from_diagonal)
from photon_ml_tpu.optim.regularization import intercept_mask
from photon_ml_tpu.parallel import sparse_objective as sobj
from photon_ml_tpu.parallel.mesh import (DATA_AXIS, MODEL_AXIS,
                                         pad_to_multiple)

Array = jax.Array


def shard_sparse_batch(batch: SparseBatch, mesh: Mesh) -> SparseBatch:
    """Pad rows to the data-axis size and place shards on devices."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    k = mesh.shape[DATA_AXIS]
    padded = batch.pad_to(pad_to_multiple(batch.num_rows, k))
    return jax.device_put(
        padded,
        jax.tree.map(
            lambda leaf: NamedSharding(
                mesh, P(DATA_AXIS, *(None,) * (np.ndim(leaf) - 1))),
            padded))


def _pad_features(batch: SparseBatch, d_pad: int) -> SparseBatch:
    """Re-point ELL padding slots at the new one-past-end sentinel.

    Uses jnp ops so an already device-placed batch keeps its sharding
    (np.asarray here would pull shards back to host and silently drop the
    row sharding the caller paid for).
    """
    if d_pad == batch.num_features:
        return batch
    xp = jnp if isinstance(batch.indices, jax.Array) else np
    idx = xp.where(batch.indices == batch.num_features, d_pad,
                   batch.indices).astype(xp.int32)
    return SparseBatch(
        indices=idx, values=batch.values, labels=batch.labels,
        weights=batch.weights, offsets=batch.offsets, num_features=d_pad)


def _hybrid_line(loss: PointwiseLoss, hb, l2: float, mask: Array,
                 owlqn: bool = False) -> "LineOracle | ValueOracle":
    """``run_hybrid``'s objective, Σ w·l(z) + ½·λ‖w∘mask‖² with z = offsets
    + X·w, for the line search. An evaluation here is two crossings of
    the cold classes (0.33 s at 2M rows of click logs against 11 ms for the
    hot block, PERF.md section 5), and a search takes one to a dozen trials
    as the data fall: along w + αd the margins are z + α·(X·d), so X·d is
    crossed once, a trial reads rows and columns and no feature, and the
    gradient is crossed once at the point accepted. Every iteration then
    costs one evaluation's passes, on every data set. OWL-QN's trial points
    π(w + αd) leave that line (``owlqn``): a trial crosses once for its own
    margins and reads its value off the rows, and the gradient is crossed
    once from the accepted trial's margins, so an iteration costs its
    trials + 1 crossings where an evaluation a trial cost twice as many."""
    from photon_ml_tpu.ops import hybrid_sparse as hybrid

    def l2_terms(w):
        if l2 == 0.0:
            return 0.0, 0.0
        wm = w * mask
        return 0.5 * l2 * jnp.sum(wm * wm, axis=-1), l2 * wm

    def at(z, w):
        """(f, row terms, the L2 term's gradient) at margins z of w."""
        value, r = hybrid.row_terms(loss, hb, z)
        reg, reg_grad = l2_terms(w)
        return value + reg, r, reg_grad

    @scoped("glm.value_grad")
    def start(w):
        z = hybrid.margins(hb, w)
        f, r, reg_grad = at(z, w)
        return f, hybrid.row_gradient(hb, r) + reg_grad, z

    if owlqn:
        @scoped("glm.value_grad")
        def value(w):
            z = hybrid.margins(hb, w)
            return at(z, w)[0], z

        @scoped("glm.value_grad")
        def gradient(w, z):
            _, r, reg_grad = at(z, w)
            return hybrid.row_gradient(hb, r) + reg_grad

        return ValueOracle(start, value, gradient)

    @scoped("glm.value_grad")
    def along(z, w, d):
        return z, hybrid.products(hb, d), w, d

    @scoped("glm.value_grad")
    def trial(ray, alpha):
        z, u, w, d = ray
        f, r, reg_grad = at(z + alpha * u, w + alpha * d)
        return f, jnp.dot(r, u) + (jnp.dot(reg_grad, d) if l2 else 0.0)

    @scoped("glm.value_grad")
    def accept(ray, alpha):
        z, u, w, d = ray
        z = z + alpha * u
        f, r, reg_grad = at(z, w + alpha * d)
        return f, hybrid.row_gradient(hb, r) + reg_grad, z

    return LineOracle(start, along, trial, accept)


def run_hybrid(
    loss: PointwiseLoss,
    hb,
    config: GLMOptimizationConfiguration,
    initial: Optional[Coefficients] = None,
    intercept_index_permuted: Optional[int] = None,
) -> tuple[Coefficients, OptResult]:
    """Fit one GLM over a HybridSparseBatch (ops/hybrid_sparse.py) —
    the single-device Criteo fast path.

    The whole solve runs in the hybrid layout's PERMUTED feature space
    (count-descending relabeling): the L2 fold, L1 weights, intercept
    mask, optimizer state, and variance diagonal all live there, and only
    the returned Coefficients are mapped back. L2/L1 are permutation-
    equivariant, so this is exact. ``intercept_index_permuted`` is the
    intercept's PERMUTED column (callers map it once at staging).
    """
    from photon_ml_tpu.ops import hybrid_sparse as hybrid

    dim = hb.num_features
    # Made on the device: as a host array the mask is a constant of the
    # program, 219 MB of it at 54.7M columns.
    mask = jnp.ones((dim,), jnp.float32)
    if intercept_index_permuted is not None:
        mask = mask.at[intercept_index_permuted].set(0.0)
    reg = config.regularization
    l2 = reg.l2_weight()

    vg = scoped("glm.value_grad", with_l2(
        lambda w: hybrid.value_and_gradient(loss, w, hb), l2, mask))
    # TRON takes its products at the rows' curvature, computed once a CG
    # solve, so a product makes two passes over the non-zeros, not three.
    hvp = with_l2_hvp(
        lambda d2, v: hybrid.hessian_vector_at(d2, v, hb), l2, mask)

    l1 = reg.l1_weight()
    l1w = l1 * mask if l1 > 0.0 else None
    opt_cfg = resolve_optimizer_config(config.optimizer, l1w is not None)

    if initial is not None:
        w0 = hybrid.to_permuted_space(hb, jnp.asarray(initial.means))
    else:
        w0 = jnp.zeros((dim,), jnp.float32)

    result = optimize(vg, w0, opt_cfg, hvp=hvp, l1_weights=l1w,
                      line=_hybrid_line(loss, hb, l2, mask, l1w is not None),
                      curvature=lambda w: hybrid.curvature(loss, w, hb))

    variances = None
    kind = VarianceComputationType(config.variance_computation)
    if kind == VarianceComputationType.SIMPLE:
        diag = hybrid.hessian_diagonal(loss, result.w, hb)
        variances = hybrid.to_original_space(
            hb, variances_from_diagonal(diag, l2, mask))
    elif kind == VarianceComputationType.FULL:
        raise NotImplementedError(
            "FULL variance needs the dense d×d Hessian — not available at "
            "sparse/Criteo scale (use SIMPLE, as the reference does)")

    means = hybrid.to_original_space(hb, result.w)
    return Coefficients(means=means, variances=variances), result


def shard_hybrid(shb, mesh: Mesh):
    """Place a HybridShards on the mesh: data arrays' leading shard axis
    over ``data``, the permutation tables replicated."""
    import dataclasses as dc

    from jax.sharding import NamedSharding, PartitionSpec as P

    def put_data(leaf):
        return jax.device_put(leaf, NamedSharding(
            mesh, P(DATA_AXIS, *(None,) * (np.ndim(leaf) - 1))))

    rep = NamedSharding(mesh, P())
    return dc.replace(
        shb,
        X_hot=put_data(shb.X_hot),
        cold_rowids=tuple(put_data(a) for a in shb.cold_rowids),
        cold_vals=tuple(put_data(a) for a in shb.cold_vals),
        labels=put_data(shb.labels),
        weights=put_data(shb.weights),
        offsets=put_data(shb.offsets),
        perm=jax.device_put(shb.perm, rep),
        inv_perm=jax.device_put(shb.inv_perm, rep),
    )


def run_hybrid_sharded(
    loss: PointwiseLoss,
    shb,
    mesh: Mesh,
    config: GLMOptimizationConfiguration,
    initial: Optional[Coefficients] = None,
    intercept_index_permuted: Optional[int] = None,
) -> tuple[Coefficients, OptResult]:
    """Fit one GLM over a HybridShards — the multi-device Criteo fast path.

    Identical contract to ``run_hybrid``: the whole solve lives in the
    GLOBAL permuted feature space (replicated w; the shard_map objectives
    psum per-shard hot/cold aggregates over ``data``), and only the
    returned Coefficients map back to original column order.
    """
    from photon_ml_tpu.parallel import sparse_objective as sobj_mod

    dim = shb.num_features
    mask = jnp.asarray(intercept_mask(dim, intercept_index_permuted))
    reg = config.regularization
    l2 = reg.l2_weight()

    vg = scoped("glm.value_grad", with_l2(
        sobj_mod.make_hybrid_value_and_gradient(loss, mesh, shb), l2, mask))
    hvp = with_l2_hvp(
        sobj_mod.make_hybrid_hvp(loss, mesh, shb), l2, mask)

    l1 = reg.l1_weight()
    l1w = (jnp.asarray(
        l1 * intercept_mask(dim, intercept_index_permuted))
        if l1 > 0.0 else None)
    opt_cfg = resolve_optimizer_config(config.optimizer, l1w is not None)

    if initial is not None:
        w0 = jnp.asarray(initial.means)[shb.perm]
    else:
        w0 = jnp.zeros((dim,), jnp.float32)

    result = optimize(vg, w0, opt_cfg, hvp=hvp, l1_weights=l1w)

    variances = None
    kind = VarianceComputationType(config.variance_computation)
    if kind == VarianceComputationType.SIMPLE:
        diag = sobj_mod.make_hybrid_hessian_diagonal(
            loss, mesh, shb)(result.w)
        variances = variances_from_diagonal(diag, l2, mask)[shb.inv_perm]
    elif kind == VarianceComputationType.FULL:
        raise NotImplementedError(
            "FULL variance needs the dense d×d Hessian — not available at "
            "sparse/Criteo scale (use SIMPLE, as the reference does)")

    means = result.w[shb.inv_perm]
    return Coefficients(means=means, variances=variances), result


def run(
    loss: PointwiseLoss,
    batch: SparseBatch,
    mesh: Mesh,
    config: GLMOptimizationConfiguration,
    initial: Optional[Coefficients] = None,
    intercept_index: Optional[int] = None,
    feature_sharded: bool = False,
    already_sharded: bool = False,
) -> tuple[Coefficients, OptResult]:
    """Fit one sparse GLM over the mesh; returns original-dim coefficients."""
    dim = batch.num_features
    d_pad = dim
    if feature_sharded:
        d_pad = pad_to_multiple(dim, mesh.shape[MODEL_AXIS])
        batch = _pad_features(batch, d_pad)
    if not already_sharded:
        batch = shard_sparse_batch(batch, mesh)

    mask = np.zeros(d_pad, np.float32)
    mask[:dim] = intercept_mask(dim, intercept_index)
    mask = jnp.asarray(mask)
    reg = config.regularization
    l2 = reg.l2_weight()

    vg = scoped("glm.value_grad", with_l2(
        sobj.make_value_and_gradient(loss, mesh, batch, feature_sharded),
        l2, mask))
    hvp = with_l2_hvp(
        sobj.make_hvp(loss, mesh, batch, feature_sharded), l2, mask)

    l1 = reg.l1_weight()
    if l1 > 0.0:
        # Host-built (jit-safe: no device array ever crosses back to np).
        l1w = np.zeros(d_pad, np.float32)
        l1w[:dim] = l1 * intercept_mask(dim, intercept_index)
        l1w = jnp.asarray(l1w)
    else:
        l1w = None
    opt_cfg = resolve_optimizer_config(config.optimizer, l1w is not None)

    if initial is not None:
        w0 = jnp.zeros((d_pad,), jnp.float32).at[:dim].set(initial.means)
    else:
        w0 = jnp.zeros((d_pad,), jnp.float32)
    if feature_sharded:
        from jax.sharding import NamedSharding, PartitionSpec as P
        w0 = jax.device_put(w0, NamedSharding(mesh, P(MODEL_AXIS)))

    result = optimize(vg, w0, opt_cfg, hvp=hvp, l1_weights=l1w)

    variances = None
    kind = VarianceComputationType(config.variance_computation)
    if kind == VarianceComputationType.SIMPLE:
        diag = sobj.make_hessian_diagonal(loss, mesh, batch,
                                          feature_sharded)(result.w)
        variances = variances_from_diagonal(diag, l2, mask)[:dim]
    elif kind == VarianceComputationType.FULL:
        raise NotImplementedError(
            "FULL variance needs the dense d×d Hessian — not available at "
            "sparse/Criteo scale (use SIMPLE, as the reference does)")

    means = result.w[:dim]
    return Coefficients(means=means, variances=variances), result
