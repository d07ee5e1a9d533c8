"""Serving score fusion (registry name ``serving_score``).

One random-effect coordinate's contribution to a serving flush is the
chain gather → int8 dequant → row-dot → per-row scale
(serving/service.py ``_build_score_fn``):

    rows = cache[slots]                         # (n, d) gather, int8
    out  = einsum("nd,nd->n", mat, rows.f32)    # dequantized dot
    out *= scale[slots]                         # per-row dequant scale

As separate XLA programs the gathered rows round-trip HBM as f32 —
4 bytes/element for codes the cache stores at 1 — and at million-entity
stores that f32 materialization is the p99 and device-capacity tax the
int8 cache was built to avoid. The fused program (docs/KERNELS.md memory
diagram) gathers each code row straight into VMEM via scalar-prefetch
block indexing, upcasts in registers, reduces, and applies the scale in
the same grid step: the only HBM traffic is the int8 row read and one
f32 scalar write per example.

Grid: one step per batch row. ``slots`` rides
``PrefetchScalarGridSpec``, so the cache BlockSpec's index_map addresses
block (slots[i], 0, 0) — the gather IS the block schedule, not an op.
Every operand is addressed through an (rows, 1, d) view, for the reason
ops/kernels/re_rows.py gives: Mosaic tiles a block's last two dims, so a
one-row block carries its row id on a leading dim. The per-row dequant
scale is gathered by XLA beforehand — (n,) floats — and rides scalar
prefetch beside the slots, so the kernel reads it as one SMEM scalar.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from photon_ml_tpu.ops.kernels.re_rows import _row_view

Array = jax.Array


def _score_kernel(slots_ref, scale_ref, mat_ref, row_ref, out_ref):
    del slots_ref  # consumed by the index maps, not the body
    acc = jnp.sum(mat_ref[...] * row_ref[...].astype(jnp.float32),
                  axis=1, keepdims=True)
    out_ref[...] = acc * scale_ref[pl.program_id(0)]


def score_rows_pallas(mat: Array, slots: Array, cache: Array,
                      scale: Array | None,
                      interpret: bool = False) -> Array:
    """(n,) Σ_d mat[i,d]·dequant(cache[slots[i],d]) in one program.

    ``mat``: (n, d) f32 features. ``slots``: (n,) int32 cache rows (the
    service guarantees in-range: unknown entities resolve to the
    fallback slot). ``cache``: (E, d) int8 codes or f32 rows. ``scale``:
    (E,) f32 per-row dequant scales, or None for f32 caches (the
    fallback slot's scale is 0, so it dequantizes to exactly zero — same
    contract as the XLA chain)."""
    n = mat.shape[0]
    mat3 = _row_view(jnp.asarray(mat, jnp.float32))
    cache3 = _row_view(cache)
    row_block = (None, 1, mat3.shape[2])
    slots = jnp.clip(jnp.asarray(slots, jnp.int32), 0,
                     cache.shape[0] - 1)
    if scale is None:
        # f32 cache: fold a unit scale so both modes share one program
        # (×1.0 is bit-exact).
        row_scale = jnp.ones((n,), jnp.float32)
    else:
        row_scale = jnp.asarray(scale, jnp.float32)[slots]
    out = pl.pallas_call(
        _score_kernel,
        out_shape=jax.ShapeDtypeStruct((n, 1, 1), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n,),
            in_specs=[
                pl.BlockSpec(row_block, lambda i, s, sc: (i, 0, 0)),
                pl.BlockSpec(row_block, lambda i, s, sc: (s[i], 0, 0)),
            ],
            out_specs=pl.BlockSpec((None, 1, 1),
                                   lambda i, s, sc: (i, 0, 0)),
        ),
        interpret=interpret,
    )(slots, row_scale, mat3, cache3)
    return out[:, 0, 0]


def score_rows_xla(mat: Array, slots: Array, cache: Array,
                   scale: Array | None) -> Array:
    """The unfused chain exactly as ``_build_score_fn`` inlines it —
    gather, f32 einsum, one per-row scale multiply (x·(s·q) = s·(x·q),
    exact algebra)."""
    rows = cache[slots]
    out = jnp.einsum("nd,nd->n", mat, rows.astype(jnp.float32))
    if scale is not None:
        out = out * scale[slots]
    return out
