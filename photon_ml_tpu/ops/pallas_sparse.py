"""Compatibility shim: the Pallas ELL scatter moved to ops/kernels/.

The kernel registry (ops/kernels/registry.py, docs/KERNELS.md) owns
every Pallas program now — the scatter that used to live here is
ops/kernels/ell_scatter.py (registry name ``ell_scatter``), unchanged
tile-for-tile. This module keeps the original import path and the
original jitted ``scatter_rowterm(indices, rowterm_values, dim,
interpret=)`` signature for its one remaining caller (a parity test);
production dispatch goes through the registry via
ops/sparse_aggregators.py, which is where the flag/fallback policy
lives. Calling this wrapper is an EXPLICIT request for the Pallas
program (a parity fixture) — no flag, no fallback.
"""

from __future__ import annotations

import functools

import jax

from photon_ml_tpu.ops.kernels.ell_scatter import (  # noqa: F401
    _COL_TILE, _ROW_TILE, scatter_rowterm_pallas, scatter_rowterm_xla)

Array = jax.Array


@functools.partial(jax.jit, static_argnames=("dim", "interpret"))
def scatter_rowterm(indices: Array, rowterm_values: Array, dim: int,
                    interpret: bool = False) -> Array:
    """Σᵢ Σₖ rv[i,k] · e(indices[i,k]) into shape (dim,) — see
    ops/kernels/ell_scatter.py for the kernel."""
    return scatter_rowterm_pallas(indices, rowterm_values, dim,
                                  interpret=interpret)
