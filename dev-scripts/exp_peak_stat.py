"""Does memory_stats' peak see a jitted program's scratch? A loop-carried 2.19 GB buffer made inside jit."""
import jax, jax.numpy as jnp
d = jax.devices()[0]
def stats():
    s = d.memory_stats(); return s["bytes_in_use"], s["peak_bytes_in_use"], s.get("largest_alloc_size"), s.get("bytes_limit")
print("start", stats(), sorted(d.memory_stats()))
@jax.jit
def f(x):
    big = jnp.zeros((10, 53408, 1024), jnp.float32) + x
    def body(i, b):
        return jax.lax.dynamic_update_index_in_dim(b, b[(i + 1) % 10] + 1.0, i % 10, 0)
    return jax.lax.fori_loop(0, 30, body, big).sum()
c = f.lower(jnp.float32(1.0)).compile()
print("compiled temp", c.memory_analysis().temp_size_in_bytes)
print(float(f(jnp.float32(1.0)))); print("after temp-only program", stats())
y = jnp.zeros((53408, 1024), jnp.float32) + 1; y.block_until_ready(); print("after a 219 MB array", stats())
