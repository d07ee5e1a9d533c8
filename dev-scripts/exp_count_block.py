"""ISSUE 34: what bounds a pass over the count block, HBM or the convert?

On the chip, alone: the two products of a (rows, columns) int8 count block
in the forms ``ops/hybrid_sparse.py`` could take, beside the float32 block
of the same bytes. Prints ms a pass, GB/s by the block's bytes and ns a cell.

    python dev-scripts/exp_count_block.py [rows] [count columns]

``vpu``: X.astype(float32) @ (scale * w) and (r @ X.astype(float32)) * scale
as the layout writes them (the compiler makes one multiply-reduce fusion of
each, the convert inside). ``mxu``: the counts converted to bfloat16 (exact:
they are small integers) against the vector split into three bfloat16 parts
meant to add up to it exactly, accumulated in float32.

Read on one v5e (PERF.md section 6, PR 34): HBM bounds every form. 2,000,000
x 4096 int8: vpu 11.14 / 10.89 ms a pass (736 / 752 GB/s), mxu 11.75 / 11.02
ms, the float32 block of the same bytes (1024 columns) 10.88 / 10.88 ms;
3,000,000 x 1024: vpu 4.97 / 4.12 ms, mxu 4.61 / 4.42 ms, float32 (256
columns) 4.12 / 4.11 ms; no form holds scratch. The mxu form is no faster
and, as the chip's compiler lowers it, NOT exact: it read 2e-3 off the vpu
form, bfloat16's own step, as if the two lower parts were lost (the cause
was not found: compiled for a described v5e the split keeps both of its
subtractions, and the dot takes the int8 block itself as its operand). The
layout keeps the vpu form.
"""
import sys
import time

import jax
import jax.numpy as jnp

rows = int(sys.argv[1]) if len(sys.argv) > 1 else 2_000_000
k8 = int(sys.argv[2]) if len(sys.argv) > 2 else 4096
k32 = k8 // 4
REPS = 20


def pattern(dtype, k):
    """A block with ~3% of its cells 1 and a few 2, made on the device."""
    i = jax.lax.broadcasted_iota(jnp.int32, (rows, k), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (rows, k), 1)
    h = (i * 31 + j * 17) % 97
    return ((h < 3).astype(jnp.int32) + (h == 0)).astype(dtype)


def split3(v):
    """Three bfloat16 vectors whose float32 sum is ``v`` to the bit."""
    hi = v.astype(jnp.bfloat16)
    rest = v - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return jnp.stack([hi, mid, lo])


def mv_vpu(X, w, s):
    return X.astype(jnp.float32) @ (s * w)


def rmv_vpu(X, r, s):
    return (r @ X.astype(jnp.float32)) * s


def mv_mxu(X, w, s):
    return jnp.einsum("nk,pk->np", X.astype(jnp.bfloat16), split3(s * w),
                      preferred_element_type=jnp.float32).sum(axis=1)


def rmv_mxu(X, r, s):
    return jnp.einsum("pn,nk->pk", split3(r), X.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32).sum(axis=0) * s


def mv_f32(X, w, s):
    return X @ w


def rmv_f32(X, r, s):
    return r @ X


def timed(name, fn, X, v, s):
    f = jax.jit(fn)
    c = f.lower(X, v, s).compile()
    temp = c.memory_analysis().temp_size_in_bytes
    out = f(X, v, s).block_until_ready()
    t = time.perf_counter()
    for _ in range(REPS):
        out = f(X, v, s)
    out.block_until_ready()
    ms = (time.perf_counter() - t) / REPS * 1e3
    print(f"{name:9s} {X.dtype.name:8s} {X.shape}: {ms:8.3f} ms a pass, "
          f"{X.nbytes / ms / 1e6:6.1f} GB/s, {ms * 1e6 / X.size:.4f} ns a "
          f"cell, scratch {temp} B", flush=True)
    return out


print(jax.devices()[0].device_kind, rows, k8, flush=True)
key = jax.random.PRNGKey(0)
X8 = jax.jit(lambda: pattern(jnp.int8, k8))().block_until_ready()
w8 = jax.random.normal(key, (k8,), jnp.float32)
s8 = jnp.full((k8,), 39 ** -0.5, jnp.float32)
r = jax.random.normal(key, (rows,), jnp.float32)
z_vpu = timed("mv_vpu", mv_vpu, X8, w8, s8)
g_vpu = timed("rmv_vpu", rmv_vpu, X8, r, s8)
z_mxu = timed("mv_mxu", mv_mxu, X8, w8, s8)
g_mxu = timed("rmv_mxu", rmv_mxu, X8, r, s8)
print("mxu against vpu, largest difference over largest value: margins "
      f"{float(jnp.abs(z_mxu - z_vpu).max() / jnp.abs(z_vpu).max()):.3e}, "
      f"gradient {float(jnp.abs(g_mxu - g_vpu).max() / jnp.abs(g_vpu).max()):.3e}",
      flush=True)
del X8, z_vpu, g_vpu, z_mxu, g_mxu
X32 = jax.jit(lambda: pattern(jnp.float32, k32))().block_until_ready()
timed("mv_f32", mv_f32, X32, w8[:k32], s8[:k32])
timed("rmv_f32", rmv_f32, X32, r, s8[:k32])
