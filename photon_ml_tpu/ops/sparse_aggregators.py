"""Fused sparse (ELL) GLM aggregates: value/gradient, H·v, Hessian diag.

Reference parity: the same ``ValueAndGradientAggregator`` /
``HessianVectorAggregator`` contracts as ops/aggregators.py, but over sparse
batches — the reference's per-example loop over sparse Breeze vectors
(axpy into a dense gradient) becomes, per device:

    margins:  gather  w_pad[indices] · values, summed over slots
    gradient: scatter-add of (weight · dl) ⊗ values back into w-shape

The coefficient vector is padded with one trailing zero slot so ELL padding
(slot index == d) gathers 0 and scatters into a discarded column — no masks
anywhere in the hot path. Zero-weight (padded) ROWS are handled by the
weight mask exactly as in the dense aggregators.

Scatter-adds lower to XLA's sort+segment machinery on TPU; for small and
moderate coefficient dimensions the Pallas compare+accumulate kernel
(ops/kernels/ell_scatter.py, registry name ``ell_scatter``) wins — it is
O(d·nnz), so XLA's scatter takes over for large d. The dimension policy
below picks the CANDIDATE; whether the Pallas program actually runs is
the kernel registry's call (flag + backend): the flag's default holds on
the TPU backend only, a flag forced on where nothing can run the program
raises, and a registry-level degradation — an injected ``kernel.launch``
fault — is LOUD (KernelFallback event + counter). Set ``USE_PALLAS`` to force
either path past the dimension policy (tests, benchmarks).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from photon_ml_tpu.data.sparse import SparseBatch
from photon_ml_tpu.ops.losses import PointwiseLoss

Array = jax.Array

# None = auto: Pallas kernel on TPU when dim <= _PALLAS_DIM_MAX, else XLA
# scatter. True/False force one path (tests, benchmarks).
USE_PALLAS: Optional[bool] = None
_PALLAS_DIM_MAX = 2048


def _w_padded(means: Array) -> Array:
    """(d,) -> (d+1,) with a zero sentinel slot for ELL padding."""
    return jnp.concatenate([means, jnp.zeros((1,), means.dtype)])


def ell_matvec(indices: Array, values: Array, means: Array) -> Array:
    """(n,) X @ w for ELL rows — THE sentinel gather-dot; every consumer
    of the ELL layout (objectives, model scoring) goes through here so the
    padding contract lives in one place."""
    w_pad = _w_padded(means)
    return jnp.sum(values * w_pad[indices], axis=-1)


def margins(batch: SparseBatch, means: Array) -> Array:
    """(n,) margins wᵀx + offset via slot gather."""
    return ell_matvec(batch.indices, batch.values, means) + batch.offsets


def _masked(weights: Array, term: Array) -> Array:
    return jnp.where(weights > 0.0, weights * term, 0.0)


def _scatter_rowterm(batch: SparseBatch, r: Array, dim: int) -> Array:
    """Σ_i r_i · x_i as a scatter-add of r ⊗ values into (d,).

    Dimension policy (is the O(d·nnz) kernel even a candidate?) lives
    here; backend policy (flag, TPU vs interpret vs loud XLA fallback)
    is the registry's. When the candidate check or the flag says XLA,
    the inline ``.at[].add`` runs untouched — zero registry traffic, so
    a flag-off process is byte-identical to the pre-registry tree."""
    upd = r[..., None] * batch.values
    use_pallas = USE_PALLAS
    if use_pallas is None or use_pallas:
        from photon_ml_tpu.ops import kernels
        reg = kernels.registry()
        if use_pallas is None:
            use_pallas = (dim <= _PALLAS_DIM_MAX
                          and reg.enabled("ell_scatter"))
        if use_pallas:
            return reg.resolve("ell_scatter")(batch.indices, upd, dim)
    flat = batch.indices.reshape(-1)
    return jnp.zeros((dim + 1,), upd.dtype).at[flat].add(
        upd.reshape(-1))[:dim]


def value_and_gradient(
    loss: PointwiseLoss,
    means: Array,
    batch: SparseBatch,
) -> tuple[Array, Array]:
    """(Σ w·l, Σ w·dl·x) — fused pass, one gather + one scatter."""
    z = margins(batch, means)
    l, dl = loss.loss_and_dz(z, batch.labels)
    value = jnp.sum(_masked(batch.weights, l), axis=-1)
    r = _masked(batch.weights, dl)
    return value, _scatter_rowterm(batch, r, batch.num_features)


def hessian_vector(
    loss: PointwiseLoss,
    means: Array,
    v: Array,
    batch: SparseBatch,
) -> Array:
    """Σ w·d2l·(x·v)·x — TRON's H·v without materializing H."""
    z = margins(batch, means)
    d2 = loss.d2z(z, batch.labels)
    v_pad = _w_padded(v)
    xv = jnp.sum(batch.values * v_pad[batch.indices], axis=-1)
    r = _masked(batch.weights, d2) * xv
    return _scatter_rowterm(batch, r, batch.num_features)


def hessian_diagonal(
    loss: PointwiseLoss,
    means: Array,
    batch: SparseBatch,
) -> Array:
    """diag(H) = Σ w·d2l·x² (SIMPLE variance mode)."""
    z = margins(batch, means)
    d2 = loss.d2z(z, batch.labels)
    r = _masked(batch.weights, d2)
    sq = SparseBatch(
        indices=batch.indices, values=batch.values * batch.values,
        labels=batch.labels, weights=batch.weights, offsets=batch.offsets,
        num_features=batch.num_features)
    return _scatter_rowterm(sq, r, batch.num_features)


def scores(batch: SparseBatch, means: Array,
           offsets: Optional[Array] = None) -> Array:
    s = margins(batch, means) - batch.offsets
    return s if offsets is None else s + offsets
