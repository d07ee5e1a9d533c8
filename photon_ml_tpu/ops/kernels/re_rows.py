"""RE bucket-solve gather/scatter fusion (registry names
``re_gather_rows`` / ``re_scatter_rows``).

A random-effect bucket wave (game/coordinates/random_effect.py
``_build_fits``) brackets its vmapped per-entity solves with two row
moves over the (num_entities+1, d) coefficient table:

    w0    = W[max(rows, 0)]                      # warm-start gather
    W'    = W.at[safe].set(w_fit, mode="drop")   # fitted-row scatter

XLA compiles each into its own gather/scatter program with the moved
rows staged through HBM between programs. These Pallas programs make
each move ONE grid schedule: the bucket's row ids ride scalar prefetch,
so the table BlockSpec's index_map addresses block (rows[i], 0, 0)
directly — the row id IS the block address, and each row crosses HBM
exactly once. The table is addressed through an (E, 1, d) view: Mosaic
tiles the LAST TWO dims of a block (8 sublanes x 128 lanes for f32) and
accepts a block only when those dims are tile multiples or span the
array, so a one-row block must carry the row id on a leading, untiled
dim and span the trailing (1, d) whole. The scatter aliases the table
in place (``input_output_aliases``),
so untouched rows are preserved without rewriting the table — the same
donation contract the XLA ``.at[].set`` path gets from
``donate_argnums``.

Both are pure data movement — no arithmetic — so parity with the XLA
path is BIT-exact by construction, which is what lets the refit
bit-identity invariant (docs/STREAMING.md) survive a backend flip.

Padding lanes (row id −1, ``mode="drop"`` on the XLA side) cannot be
"dropped" by a block schedule — every grid step writes somewhere — so
the wrapper redirects them at a valid target row and makes the write
content-identical (the target row's own incoming value), turning "drop"
into "write the same bytes twice": order-independent, hence race-free
even though redirected lanes collide with the real write.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from photon_ml_tpu.ops.kernels.ell_scatter import _pad_axis

Array = jax.Array

_LANE = 128


def _row_view(a: Array) -> Array:
    """(E, d) → (E, 1, d_pad): lanes padded to 128, one row per block."""
    return _pad_axis(a, _LANE, 1, 0)[:, None, :]


def _copy_kernel(rows_ref, src_ref, out_ref):
    del rows_ref  # consumed by the index maps, not the body
    out_ref[...] = src_ref[...]


def _scatter_kernel(rows_ref, vals_ref, w_ref, out_ref):
    # The table rides along only for the aliasing (out IS w_ref's
    # buffer); each grid step overwrites its target row with the lane's
    # values — redirected padding lanes write duplicate bytes.
    del rows_ref, w_ref
    out_ref[...] = vals_ref[...]


def gather_rows_pallas(W: Array, rows: Array,
                       interpret: bool = False) -> Array:
    """(B, d) W[max(rows, 0)] — the warm-start gather. Padding lanes
    (row id −1) read row 0, exactly like the XLA ``jnp.maximum`` path
    (the vmapped solve ignores those lanes; the clamp just keeps the
    read in-bounds)."""
    b = rows.shape[0]
    d = W.shape[1]
    w3 = _row_view(W)
    row_block = (None, 1, w3.shape[2])
    rr = jnp.maximum(jnp.asarray(rows, jnp.int32), 0)
    out = pl.pallas_call(
        _copy_kernel,
        out_shape=jax.ShapeDtypeStruct((b,) + w3.shape[1:], W.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b,),
            in_specs=[pl.BlockSpec(row_block, lambda i, r: (r[i], 0, 0))],
            out_specs=pl.BlockSpec(row_block, lambda i, r: (i, 0, 0)),
        ),
        interpret=interpret,
    )(rr, w3)
    return out[:, 0, :d]


def gather_rows_xla(W: Array, rows: Array) -> Array:
    return W[jnp.maximum(rows, 0)]


def scatter_rows_pallas(W: Array, rows: Array, vals: Array,
                        interpret: bool = False) -> Array:
    """W with vals[i] written at rows[i] (rows[i] < 0 dropped);
    untouched rows preserved via in-place aliasing.

    Invalid lanes are redirected at the lane holding the LARGEST row id
    (guaranteed valid when any lane is) and carry that lane's values, so
    the redirected write duplicates a real write byte-for-byte. When the
    whole wave is padding, they instead rewrite row 0 with its own
    current contents — a no-op scatter either way."""
    d = W.shape[1]
    w3 = _row_view(W)
    v3 = _row_view(jnp.asarray(vals, W.dtype))
    row_block = (None, 1, w3.shape[2])
    rows = jnp.asarray(rows, jnp.int32)
    valid = rows >= 0
    i_star = jnp.argmax(rows)  # lane of the largest (hence valid) row id
    row_star = jnp.maximum(rows[i_star], 0)
    any_valid = jnp.any(valid)
    safe_vals = jnp.where(any_valid, v3[i_star], w3[row_star])
    rows_fix = jnp.where(valid, rows, row_star)
    vals_fix = jnp.where(valid[:, None, None], v3, safe_vals[None])
    out = pl.pallas_call(
        _scatter_kernel,
        out_shape=jax.ShapeDtypeStruct(w3.shape, W.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows.shape[0],),
            in_specs=[
                pl.BlockSpec(row_block, lambda i, r: (i, 0, 0)),
                pl.BlockSpec(row_block, lambda i, r: (r[i], 0, 0)),
            ],
            out_specs=pl.BlockSpec(row_block, lambda i, r: (r[i], 0, 0)),
        ),
        # Operand indices count the scalar-prefetch arg: 0=rows_fix,
        # 1=vals_fix, 2=w3 → alias the TABLE into the output.
        input_output_aliases={2: 0},
        interpret=interpret,
    )(rows_fix, vals_fix, w3)
    return out[:, 0, :d]


def scatter_rows_xla(W: Array, rows: Array, vals: Array) -> Array:
    W = jnp.asarray(W)
    safe = jnp.where(jnp.asarray(rows) >= 0, rows, W.shape[0])
    return W.at[safe].set(jnp.asarray(vals, W.dtype), mode="drop")
