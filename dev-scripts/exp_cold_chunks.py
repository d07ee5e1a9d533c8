"""ISSUE 30: what the cold part's two crossings cost at the Criteo cell's own
size, chunked against padded, and the chunk map whole against remainder-only.

On the chip, one process: the cell's rows from a seed (the benchmark's
generator), the layout the library builds (top chunks by slice, remainder
chunks through ``chunk_cols``), the same layout with EVERY chunk through a
map (built here, not a library path), and, with ``--parent DIR``, the padded
classes of another checkout's ``hybrid_sparse.py``. The hot block is never
placed on the device: each pass is timed over the cold classes alone.

    python dev-scripts/exp_cold_chunks.py [--seed N] [--rows N] [--parent DIR]
                                          [--by-class]

Prints one JSON object and writes it to ``chiprun_out/exp_cold_chunks.json``.
Seconds are medians of ``--runs`` calls after one warm-up, device-blocked.
"""
import argparse
import dataclasses
import functools
import importlib.util
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
for _p in ("benchmark", os.path.join("benchmark", "schemas")):
    sys.path.insert(0, os.path.join(REPO, _p))

import numpy as np

import jax
import jax.numpy as jnp


def timed(fn, args, runs):
    """Median seconds of ``runs`` blocked calls after a warm-up, and the
    result."""
    fn = jax.jit(fn)
    result = jax.block_until_ready(fn(*args))
    out = []
    for _ in range(runs):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append(time.perf_counter() - t0)
    return float(np.median(out)), np.asarray(result)


def cold_only(hb):
    """The layout without its hot block, on the device."""
    n = int(np.asarray(hb.labels).shape[0])
    return jax.device_put(dataclasses.replace(
        hb, X_hot=np.zeros((n, 0), np.float32), num_hot=0))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=2200000033)
    ap.add_argument("--rows", type=int, default=None)
    ap.add_argument("--runs", type=int, default=5)
    ap.add_argument("--parent", default=None,
                    help="a checkout whose padded layout is timed beside")
    ap.add_argument("--by-class", action="store_true",
                    help="each class's share of the two passes alone")
    args = ap.parse_args()

    import game_criteo
    from photon_ml_tpu.data.sparse import SparseBatch
    from photon_ml_tpu.game.coordinates.sparse_fixed import hot_block_budget
    from photon_ml_tpu.ops import hybrid_sparse as hs
    from photon_ml_tpu.parallel.mesh import make_mesh

    with open(os.path.join(REPO, "benchmark", "configs",
                           "glmix-criteo-1m-logistic.json")) as f:
        conf = json.load(f)
    if args.rows:
        conf = game_criteo.shrink(conf, args.rows)
    data = game_criteo.make(args.seed, conf)
    n = int(data.response.shape[0])
    batch = SparseBatch(
        indices=data.indices, values=data.values, labels=data.response,
        weights=np.ones(n, np.float32), offsets=np.zeros(n, np.float32),
        num_features=data.num_features)
    budget = hot_block_budget(make_mesh(devices=jax.devices()[:1]))
    dev = jax.devices()[0]
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "seed": args.seed, "rows": n, "hot_block_bytes": budget}

    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=data.num_features), jnp.float32)
    r = jnp.asarray(rng.normal(size=n), jnp.float32)

    def passes(mod, hb, tag):
        margins_s, z = timed(mod.margins, (hb, w), args.runs)
        gradient_s, g = timed(mod.row_gradient, (hb, r), args.runs)
        out[tag] = {"margins_s": margins_s, "row_gradient_s": gradient_s}
        return z, g

    t0 = time.perf_counter()
    host = hs.build_hybrid(batch, device=False, hot_block_bytes=budget)
    out["host_build_s"] = time.perf_counter() - t0
    slots = sum(int(a.size) for a in host.cold_rowids)
    out["layout"] = {
        "num_hot": host.num_hot, "cold_entries": host.entries[1],
        "cold_slots": slots, "cold_classes": len(host.class_lens),
        "cold_chunks": sum(int(a.size) // L for a, L in zip(
            host.cold_rowids, host.class_lens)),
        "remainder_chunks": int(host.chunk_cols.size)}
    hb = cold_only(host)
    z_b, g_b = passes(hs, hb, "remainder_map")

    # Every chunk through a map: the same rows, every class all remainder.
    present = sum(int(a.size) // L - rems for a, L, rems in zip(
        host.cold_rowids, host.class_lens, host.class_rems))
    maps, off = [], 0
    chunk_cols = np.asarray(host.chunk_cols)
    for start, L, rems, rows in zip(host.class_starts, host.class_lens,
                                    host.class_rems, host.cold_rowids):
        tops = rows.size // L - rems
        maps.append(np.concatenate([chunk_cols[off: off + rems],
                                    start + np.arange(tops, dtype=np.int32)]))
        off += rems
    full = jnp.asarray(np.concatenate(maps).astype(np.int32))
    flat_rows = jnp.concatenate([a.reshape(-1) for a in hb.cold_rowids])

    # Arrays go in as arguments: a closed-over one is baked into the program.
    def margins_full(w, full, flat_rows, cold_vals):
        w_chunk, parts, at = w[full], [], 0
        for L, vals in zip(hb.class_lens, cold_vals):
            C = vals.size // L
            w_c = w_chunk[at: at + C]
            parts.append(((w_c[None, :] if L < 128 else w_c[:, None])
                          * vals).reshape(-1))
            at += C
        return jnp.zeros((n + 1,), jnp.float32).at[flat_rows].add(
            jnp.concatenate(parts))[:n]

    def gradient_full(r, full, flat_rows, cold_vals):
        r_pad = jnp.concatenate([r, jnp.zeros((1,), r.dtype)])
        gathered, sums, at = r_pad[flat_rows], [], 0
        for L, vals in zip(hb.class_lens, cold_vals):
            blk = gathered[at: at + vals.size].reshape(vals.shape)
            sums.append(jnp.sum(blk * vals, axis=0 if L < 128 else 1))
            at += vals.size
        return jnp.zeros((present,), jnp.float32).at[full].add(
            jnp.concatenate(sums))

    rest = (full, flat_rows, hb.cold_vals)
    margins_s, z_a = timed(margins_full, (w, *rest), args.runs)
    gradient_s, g_a = timed(gradient_full, (r, *rest), args.runs)
    out["full_map"] = {
        "margins_s": margins_s, "row_gradient_s": gradient_s,
        "agrees": bool(
            np.allclose(z_a, z_b, rtol=1e-5, atol=1e-5)
            and np.allclose(g_a, g_b[:present], rtol=1e-4, atol=1e-4))}

    # The two crossings alone, and what a promise about the indices buys.
    prods = jnp.asarray(rng.normal(size=slots), jnp.float32)
    r_pad = jnp.concatenate([r, jnp.zeros((1,), r.dtype)])
    for tag, mode in (("default", None), ("promised", "promise_in_bounds")):
        out["crossing_" + tag] = {
            "gather_s": timed(
                lambda x, rows: x.at[rows].get(mode=mode),
                (r_pad, flat_rows), args.runs)[0],
            "scatter_add_s": timed(
                lambda p, rows: jnp.zeros((n + 1,), jnp.float32).at[
                    rows].add(p, mode=mode), (prods, flat_rows),
                args.runs)[0]}

    # A class alone, in the orientation it is held: its gather and sums, and
    # its products scattered by row (the same coefficient for every chunk).
    if args.by_class:
        def class_gradient(r_pad, rows, vals, axis):
            got = r_pad[rows.reshape(-1)].reshape(vals.shape)
            return jnp.sum(got * vals, axis=axis)

        def class_margins(rows, vals):
            return jnp.zeros((n + 1,), jnp.float32).at[
                rows.reshape(-1)].add((0.5 * vals).reshape(-1))

        out["by_class"] = [
            {"held": list(vals.shape), "slots": int(vals.size),
             "gradient_s": timed(
                 functools.partial(class_gradient, axis=int(L >= 128)),
                 (r_pad, rows, vals), args.runs)[0],
             "margins_s": timed(class_margins, (rows, vals), args.runs)[0]}
            for L, rows, vals in zip(hb.class_lens, hb.cold_rowids,
                                     hb.cold_vals)]
    del hb, full, flat_rows, prods, rest

    if args.parent:
        spec = importlib.util.spec_from_file_location(
            "hybrid_sparse_parent", os.path.join(
                args.parent, "photon_ml_tpu", "ops", "hybrid_sparse.py"))
        parent = importlib.util.module_from_spec(spec)
        sys.modules[spec.name] = parent
        spec.loader.exec_module(parent)
        host = parent.build_hybrid(batch, device=False,
                                   hot_block_bytes=budget)
        out["padded_layout"] = {
            "cold_slots": sum(int(a.size) for a in host.cold_rowids),
            "cold_classes": len(host.class_lens)}
        z_p, g_p = passes(parent, cold_only(host), "padded")
        out["padded"]["agrees"] = bool(
            np.allclose(z_p, z_b, rtol=1e-5, atol=1e-5)
            and np.allclose(g_p, g_b, rtol=1e-4, atol=1e-4))

    text = json.dumps(out, indent=1)
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "exp_cold_chunks.json"),
              "w") as f:
        f.write(text)
    print(text)


if __name__ == "__main__":
    main()
