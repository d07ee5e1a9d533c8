"""Round-4 verdict item 6: the fused cold-path kernel experiment.

The hybrid layout's cold GRADIENT crossing is currently two HBM passes:
one fused XLA gather ``r[rowids]`` (random) materializing the gathered
stream, then padded row-sums (contiguous). A fused Pallas kernel does
both in one pass — the residual vector lives in VMEM (n=131k f32 =
512 KB), each (column-tile, L) block gathers its row values in-register
and reduces immediately, so the gathered intermediate never exists in
HBM. If the wall is random-access ELEMENT RATE (the round-3 analysis:
~0.14 Gelem/s XLA gather, ~0.84 Gelem/s Mosaic vreg shuffles), fusion
buys little; if it is the intermediate's bandwidth, it buys up to ~2×
on the crossing. This script measures both formulations on the bench
config (n=131k, d=1M, nnz=32 — BASELINE config 5's shape) and prints a
JSON verdict for PARITY.

    python dev-scripts/exp_cold_gather.py [--json] [--interpret]

VMEM bound: the fused kernel needs the full (n,) residual resident per
grid cell, so it applies when n ≤ ~2M f32 rows (16 MB VMEM) — the
device-resident hybrid regime. The streamed 100M-row path keeps chunks
at 10M rows (40 MB), out of VMEM reach: its crossing stays XLA.
"""
import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from photon_ml_tpu.utils.compile_cache import enable_compilation_cache

enable_compilation_cache()

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_C_TILE = 512


def _fused_kernel(r_ref, rows_ref, vals_ref, out_ref):
    """One (C_TILE, L) block: gather r by rowid in-register, multiply by
    the stored values, reduce over L — gathered stream never leaves
    VMEM. The residual lives as a (n_pad/128, 128) VMEM table; Mosaic
    supports 2D gathers only, so the flat rowid splits into (sublane,
    lane) coordinates."""
    r = r_ref[...]  # (n_pad // 128, 128) residual table
    idx = rows_ref[...]  # (C_TILE, L) int32, pad rows -> the zero slot
    gathered = r[idx >> 7, idx & 127]
    out_ref[...] = jnp.sum(gathered * vals_ref[...], axis=1)


def fused_cold_grad(r2d, rows, vals, interpret=False):
    """(C,) per-class gradient slice via the fused Pallas pass.
    ``r2d``: (n_pad/128, 128) residual with r2d.flat[n] == 0 (pad slot).
    """
    C, L = rows.shape
    c_pad = (-C) % _C_TILE
    n = r2d.shape[0] * 128 - 128  # flat pad slots live in the last row
    if c_pad:
        rows = jnp.pad(rows, ((0, c_pad), (0, 0)), constant_values=n)
        vals = jnp.pad(vals, ((0, c_pad), (0, 0)))
    out = pl.pallas_call(
        _fused_kernel,
        out_shape=jax.ShapeDtypeStruct((rows.shape[0],), jnp.float32),
        grid=(rows.shape[0] // _C_TILE,),
        in_specs=[
            pl.BlockSpec(r2d.shape, lambda i: (0, 0)),  # whole residual
            pl.BlockSpec((_C_TILE, L), lambda i: (i, 0)),
            pl.BlockSpec((_C_TILE, L), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((_C_TILE,), lambda i: (i,)),
        interpret=interpret,
    )(r2d, rows, vals)
    return out[:C]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=1 << 17)
    ap.add_argument("--d", type=int, default=1_000_000)
    ap.add_argument("--nnz", type=int, default=32)
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--interpret", action="store_true",
                    help="interpret the kernel (the CPU, small --n)")
    args = ap.parse_args()

    from photon_ml_tpu.data import sparse as sp
    from photon_ml_tpu.ops import hybrid_sparse as hs

    def log(m):
        print(f"[cold-gather {time.strftime('%H:%M:%S')}] {m}",
              file=sys.stderr, flush=True)

    batch, _ = sp.synthetic_sparse(args.n, args.d, args.nnz, seed=2)
    hb = hs.build_hybrid(batch)
    n = args.n
    cold_nnz = hb.entries[1]
    log(f"hybrid: {hb.num_hot} hot cols, {len(hb.cold_rowids)} cold "
        f"classes, {cold_nnz:,} cold nnz "
        f"(shapes {[tuple(r.shape) for r in hb.cold_rowids]})")

    rng = np.random.default_rng(0)
    r = jnp.asarray(rng.normal(size=n).astype(np.float32))
    # (n_pad/128, 128) table; flat slot n (the ELL pad sentinel) reads 0.
    flat_pad = (-(n + 1)) % 128 + 1
    r2d = jnp.concatenate(
        [r, jnp.zeros((flat_pad,), jnp.float32)]).reshape(-1, 128)

    # Baseline: the current two-pass XLA formulation, all classes: the
    # cold gradient, (present,).
    @jax.jit
    def xla_cold(rr):
        return hs._cold_grad(hb, rr, hb.cold_vals)[0]

    # Fused: one pallas_call per class (same per-class decomposition). A
    # class's row is one chunk of a column (ops/hybrid_sparse.py), a class
    # of L < 128 is held (L, C), and the chunk sums become the columns' as
    # in ``_cold_grad``: the top chunks' by concatenation, the remainder
    # chunks' by one scatter-add over ``chunk_cols``.
    @jax.jit
    def pallas_cold(rr2d):
        tops, rems = [], []
        for L, n_rems, rows, vals in zip(hb.class_lens, hb.class_rems,
                                         hb.cold_rowids, hb.cold_vals):
            if L < hs._LANES:
                rows, vals = rows.T, vals.T
            sums = fused_cold_grad(rr2d, rows, vals, args.interpret)
            rems.append(sums[:n_rems])
            tops.append(sums[n_rems:])
        return jnp.concatenate(tops).at[hb.chunk_cols].add(
            jnp.concatenate(rems))

    def timed(f, x, iters):
        o = f(x)
        jax.block_until_ready(o)
        t0 = time.perf_counter()
        for _ in range(iters):
            o = f(x)
        jax.block_until_ready(o)
        return (time.perf_counter() - t0) / iters

    out = {"cold_nnz": cold_nnz}

    # Baseline: element rate of the current two-pass crossing (anchors
    # the documented random-access wall).
    g_x = np.asarray(xla_cold(r))
    dt = min(timed(xla_cold, r, 30) for _ in range(3))
    out["cold_grad_xla_two_pass_us"] = round(dt * 1e6, 1)
    out["cold_grad_xla_gelem_per_sec"] = round(cold_nnz / dt / 1e9, 3)
    log(f"xla_two_pass: {dt * 1e6:.0f} us "
        f"({cold_nnz / dt / 1e9:.3f} Gelem/s over {cold_nnz:,} cold nnz)")

    try:
        g_p = np.asarray(pallas_cold(r2d))
    except Exception as e:  # lowering failure IS a result — record it
        msg = f"{type(e).__name__}: {str(e)[:300]}"
        log(f"fused kernel failed to lower/run: {msg}")
        # Mosaic's gather rule (jax 0.9, lowering.py _gather_lowering_rule)
        # asserts indices.shape == operand.shape + (1,): take-along-axis
        # patterns only — arbitrary-address VMEM gather is not
        # expressible, so the fused formulation cannot lower. Together
        # with the round-3 routing measurements (vreg butterfly
        # permutations ~0.84 Gelem/s, landing within 1.1x of plain
        # scatter when composed into full formulations), this closes the
        # experiment: the two remaining random crossings stay on XLA's
        # gather/scatter, and their element rate is the documented wall.
        out["fused_cold_gather"] = "unsupported"
        out["error"] = msg
        print(json.dumps(out) if args.json else
              "\n".join(f"{k}: {v}" for k, v in out.items()))
        return
    np.testing.assert_allclose(g_p, g_x, rtol=1e-5, atol=1e-4)
    log("parity OK")
    dt = min(timed(pallas_cold, r2d, 30) for _ in range(3))
    out["cold_grad_pallas_fused_us"] = round(dt * 1e6, 1)
    out["cold_grad_pallas_gelem_per_sec"] = round(cold_nnz / dt / 1e9, 3)
    out["speedup_fused_vs_xla"] = round(
        out["cold_grad_xla_two_pass_us"] / out["cold_grad_pallas_fused_us"],
        2)
    print(json.dumps(out) if args.json else
          "\n".join(f"{k}: {v}" for k, v in out.items()))


if __name__ == "__main__":
    main()
