"""The plain reference of ``game_music``: GLMix under the squared loss, every
block minimised in closed form from its normal equations.

The model and the descent are ``reference.py``'s (its docstring states them):
block coordinate descent, each coordinate in turn minimising
Σ ½ (x·w + offset − y)² over the rows it trains on plus ``λ/2 |w|²`` on every
coefficient but the intercept, the other coordinates' scores as offsets,
``max_samples`` by ``reference.capped_training_rows``. Under the squared loss
a block's minimum solves (Σ x xᵀ + λ M) w = Σ x (y − offset), so this file
solves that once a block where ``reference.py`` takes damped Newton steps
clipped at 4 a coefficient, several of which a 0-100 rating needs, and stops
when its determined entities have stopped moving, leaving the one-row ones
where the clip left them. Whole-batch float32 ``jax.numpy`` at ``highest``
precision, summed in blocks of rows; an entity's normal equations are
summed by ``segment_sum`` and solved by an 8-wide Cholesky factorisation
written out over (entities,) vectors. It shares no solver code with the
program's TRON and imports nothing of the program; what is compared and how
is ``reference.compare``.
"""

from __future__ import annotations

import numpy as np

import reference

BLOCK_ROWS = reference.BLOCK_ROWS


def _fns(lam: float):
    """The jitted pieces; row-wise arrays feature-major, ``(features,
    rows)``, as ``reference._fns`` keeps them."""
    import jax
    import jax.numpy as jnp

    def reg_mask(d):
        return jnp.ones((d,), jnp.float32).at[d - 1].set(0.0)

    @jax.jit
    def fixed_value_grad(Xt, y, off, w):
        mask = reg_mask(w.shape[0])
        r = w @ Xt + off - y
        return (0.5 * jnp.sum(r * r) + 0.5 * lam * jnp.sum((w * mask) ** 2),
                Xt @ r + lam * mask * w)

    @jax.jit
    def fixed_solve(Xt, y, off):
        H = Xt @ Xt.T + jnp.diag(lam * reg_mask(Xt.shape[0]))
        return jnp.linalg.solve(H, Xt @ (y - off))

    def blocks(n):
        return [(a, min(a + BLOCK_ROWS, n)) for a in range(0, n, BLOCK_ROWS)]

    def spd_solve(h, g, d):
        """x with H x = g for every entity at once (``h[i][j]``, i <= j, and
        ``g[i]`` are (entities,) vectors)."""
        L = [[None] * d for _ in range(d)]
        for j in range(d):
            s = h[j][j] - sum(L[j][k] ** 2 for k in range(j))
            L[j][j] = jnp.sqrt(jnp.maximum(s, 1e-20))
            for i in range(j + 1, d):
                L[i][j] = (h[j][i] - sum(L[i][k] * L[j][k]
                                         for k in range(j))) / L[j][j]
        z = [None] * d
        for i in range(d):
            z[i] = (g[i] - sum(L[i][k] * z[k] for k in range(i))) / L[i][i]
        x = [None] * d
        for i in reversed(range(d)):
            x[i] = (z[i] - sum(L[k][i] * x[k]
                               for k in range(i + 1, d))) / L[i][i]
        return jnp.stack(x)

    @jax.jit
    def entity_solve(Xt, y, off, train, ids, Wt):
        """Every entity's block minimum at once (``Wt`` gives the table's
        shape only), and the intercept curvature: its training rows."""
        d, E = Wt.shape
        mask = reg_mask(d)
        pairs = [(i, j) for i in range(d) for j in range(i, d)]
        total = 0.0
        for a, b in blocks(y.shape[0]):
            x = Xt[:, a:b]
            t = train[a:b]
            cols = jnp.concatenate([x * (t * (y[a:b] - off[a:b])),
                                    jnp.stack([t * x[i] * x[j]
                                               for i, j in pairs])])
            total = total + jax.ops.segment_sum(cols.T, ids[a:b],
                                                num_segments=E)
        sums = total.T
        h = [[None] * d for _ in range(d)]
        for k, (i, j) in enumerate(pairs):
            h[i][j] = sums[d + k] + (lam * mask[i] if i == j else 0.0)
        return spd_solve(h, list(sums[:d]), d), sums[d + len(pairs) - 1]

    @jax.jit
    def entity_score(Xt, ids, Wt):
        return jnp.concatenate([
            jnp.sum(Xt[:, a:b] * Wt[:, ids[a:b]], axis=0)
            for a, b in blocks(ids.shape[0])])

    @jax.jit
    def masked_loss(m, y, mask):
        return jnp.sum(mask * 0.5 * (m - y) ** 2)

    return (fixed_value_grad, fixed_solve, entity_solve, entity_score,
            masked_loss)


def train(data, mix: dict, settings: dict, sweeps: int, served: dict) -> dict:
    """``reference.train``'s descent and returns, each block solved once."""
    import jax
    import jax.numpy as jnp

    (fixed_value_grad, fixed_solve, entity_solve, entity_score,
     masked_loss) = _fns(float(settings["optimizer"]["reg_weight"]))
    seq = [c for c in mix["update_sequence"]
           if c not in mix["locked_coordinates"]]
    y = jnp.asarray(data.response)
    n = data.num_rows
    Xt, ids, rows, model, score = {}, {}, {}, {}, {}
    for cid in mix["update_sequence"]:
        c = mix["coordinates"][cid]
        shard = c["shard"] if c["type"] == "fixed" else "re_" + c["entity"]
        Xt[cid] = jnp.asarray(np.ascontiguousarray(data.shards[shard].T))
        if c["type"] == "fixed":
            model[cid] = jnp.zeros((Xt[cid].shape[0],), jnp.float32)
        else:
            ent = c["entity"]
            ids[cid] = jnp.asarray(data.entity_ids[ent])
            rows[cid] = jnp.asarray(reference.capped_training_rows(
                data.entity_ids[ent], data.num_entities[ent],
                settings.get("max_samples")))
            model[cid] = jnp.zeros((Xt[cid].shape[0],
                                    data.num_entities[ent]), jnp.float32)
        score[cid] = jnp.zeros((n,), jnp.float32)
    values, grad_norms, curvature = [], [], {}
    with jax.default_matmul_precision("highest"):
        for _ in range(sweeps):
            for cid in seq:
                off = sum(score[c] for c in score if c != cid)
                if mix["coordinates"][cid]["type"] == "fixed":
                    f, g = fixed_value_grad(Xt[cid], y, off, model[cid])
                    values.append(float(f))
                    grad_norms.append(float(jnp.linalg.norm(g)))
                    model[cid] = fixed_solve(Xt[cid], y, off)
                    score[cid] = model[cid] @ Xt[cid]
                else:
                    model[cid], curvature[cid] = entity_solve(
                        Xt[cid], y, off, rows[cid], ids[cid], model[cid])
                    score[cid] = entity_score(Xt[cid], ids[cid], model[cid])

        def margins(leaves):
            return sum(leaves[c] @ Xt[c] if leaves[c].ndim == 1
                       else entity_score(Xt[c], ids[c], leaves[c])
                       for c in mix["update_sequence"])

        mine = margins(model)
        theirs = margins({c: jnp.asarray(np.asarray(
            served[c], np.float32).T) for c in model})
        small = {}
        for cid, curv in curvature.items():
            loose = curv < reference.DETERMINED
            mask = rows[cid] * loose[ids[cid]]
            small[cid] = {"reference": float(masked_loss(mine, y, mask)),
                          "program": float(masked_loss(theirs, y, mask)),
                          "rows": float(jnp.sum(mask)),
                          "trained_rows": float(jnp.sum(rows[cid])),
                          "entities": int(jnp.sum(loose)),
                          "of": int(loose.shape[0])}
    return {"small": small,
            "model": {c: np.asarray(v.T if v.ndim == 2 else v)
                      for c, v in model.items()},
            "values": values, "grad_norms": grad_norms,
            "curvature": {c: np.asarray(v) for c, v in curvature.items()}}


def check(data, cell: dict, served: dict, ledger_rows, sweeps: int) -> dict:
    """name -> {"value", "limit"}, as ``reference.check`` gives them."""
    ref = train(data, cell["mix"], cell["settings"], sweeps, served)
    got = reference.compare(ref, served, ledger_rows, cell["mix"])
    out = {}
    for name, limit in cell["configuration"]["check"]["limits"].items():
        v = got.get(name, float("inf"))
        out[name] = {"value": v if np.isfinite(v) else 1e30, "limit": limit}
    return out
