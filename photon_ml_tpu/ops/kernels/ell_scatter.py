"""ELL scatter-add kernel (registry name ``ell_scatter``).

The Pallas program moved here verbatim from ops/pallas_sparse.py when
the kernel registry landed (that module is now a compatibility shim over
this one); the algorithm and tile choices are unchanged — see the kernel
docstring. What this module adds is the registry contract: the XLA
reference closure (`scatter_rowterm_xla`, the exact ``.at[].add``
sort+segment path ops/sparse_aggregators.py used to inline) lives NEXT
to the Pallas program, so parity tests and the fallback ladder compare
two implementations with one signature.

Memory shape (docs/KERNELS.md): XLA lowers the scatter to sort + segment
sum — materializing sorted (n·k,) index/value copies in HBM; the Pallas
program streams each (row, col) tile through VMEM once and contracts a
one-hot compare in registers, O(d·nnz) compute but zero intermediate HBM
traffic. Its speed against XLA is not measured on the current chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array

# Column tile = one lane register width; row tile amortizes grid overhead.
_COL_TILE = 128
_ROW_TILE = 256


def _kernel(idx_ref, rv_ref, out_ref, *, col_tile: int):
    """Grid (d_tiles, n_tiles); n is the accumulation (minor) dimension.

    Per cell: unrolled loop over the ELL slots, each a vectorized
    compare + select + add on a (row_tile, col_tile) register block —
    no unaligned reshapes (Mosaic rejects flattening (R, k) ELL blocks),
    same multiply-accumulate count as the explicit one-hot matmul.
    """
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    idx = idx_ref[...]  # (row_tile, max_nnz) int32
    rv = rv_ref[...]  # (row_tile, max_nnz) f32
    rows = idx.shape[0]
    d0 = pl.program_id(0) * col_tile
    cols = d0 + jax.lax.broadcasted_iota(jnp.int32, (rows, col_tile), 1)
    acc = jnp.zeros((rows, col_tile), jnp.float32)
    for k in range(idx.shape[1]):
        acc += jnp.where(idx[:, k:k + 1] == cols, rv[:, k:k + 1], 0.0)
    out_ref[...] += jnp.sum(acc, axis=0, keepdims=True)


def _pad_axis(x, mult, axis, fill):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill)


def scatter_rowterm_pallas(indices: Array, rowterm_values: Array, dim: int,
                           interpret: bool = False) -> Array:
    """Σᵢ Σₖ rv[i,k] · e(indices[i,k]) into shape (dim,).

    ``indices``: (n, max_nnz) int32 ELL indices (padding == any id ≥ dim).
    ``rowterm_values``: (n, max_nnz) f32, typically r[:, None] * values.
    """
    n_tiles_d = -(-dim // _COL_TILE)
    d_pad = n_tiles_d * _COL_TILE
    # Padding rows use an index ≥ d_pad so they match no column tile.
    idx = _pad_axis(jnp.asarray(indices, jnp.int32), _ROW_TILE, 0, d_pad)
    rv = _pad_axis(jnp.asarray(rowterm_values, jnp.float32), _ROW_TILE, 0,
                   0.0)
    n_tiles_r = idx.shape[0] // _ROW_TILE
    # Under shard_map the output varies over the same mesh axes as the
    # inputs (each shard scatters its local rows); propagate the vma so
    # jax's check_vma accepts the kernel.
    vma = jax.typeof(idx).vma | jax.typeof(rv).vma
    out_aval = jax.ShapeDtypeStruct((1, d_pad), jnp.float32, vma=vma)
    out = pl.pallas_call(
        functools.partial(_kernel, col_tile=_COL_TILE),
        out_shape=out_aval,
        grid=(n_tiles_d, n_tiles_r),
        in_specs=[
            pl.BlockSpec((_ROW_TILE, idx.shape[1]), lambda i, j: (j, 0)),
            pl.BlockSpec((_ROW_TILE, rv.shape[1]), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((1, _COL_TILE), lambda i, j: (0, i)),
        interpret=interpret,
    )(idx, rv)
    return out[0, :dim]


def scatter_rowterm_xla(indices: Array, rowterm_values: Array,
                        dim: int) -> Array:
    """The XLA reference: flatten + ``.at[].add`` into a (dim+1,) table
    whose sentinel column absorbs ELL padding — byte-for-byte the path
    ops/sparse_aggregators.py ran before the registry, so a fallback is
    a policy change, not a numerics change."""
    upd = jnp.asarray(rowterm_values, jnp.float32)
    flat = jnp.asarray(indices, jnp.int32).reshape(-1)
    # Padding indices (== dim by the ELL contract) land on the sentinel
    # column and are sliced off; anything beyond is dropped by XLA's
    # scatter semantics — either way padding contributes nothing.
    return jnp.zeros((dim + 1,), upd.dtype).at[flat].add(
        upd.reshape(-1))[:dim]
