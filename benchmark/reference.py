"""The plain GLMix reference, and the comparison that decides ``correct``.

GLMix / GAME (Zhang et al., KDD 2016): the score of a row is the sum of a
fixed effect ``x_g . w`` and one random effect per entity column,
``x_e . W_e[id]``. Training is block coordinate descent: each coordinate in
turn minimises the summed loss over the rows it trains on, with every other
coordinate's score as a fixed offset, plus ``lambda/2 |w|^2`` on every
coefficient except the intercept (the last column).

This file states that in whole-batch float32 ``jax.numpy`` at ``highest``
matmul precision, summed in blocks of rows so that it fits: no buckets, no
vmapped lanes, no warm-start tables, no kernels. Each block is minimised by damped Newton steps with step halving,
not by the program's L-BFGS, so the two share no solver code; where both
converge they reach the same block minimum. It imports nothing of the program
and takes nothing the program made except what is compared.

Two facts of the configuration are followed to the letter, because they define
which rows a block trains on:

- ``max_samples``: an entity with more rows keeps a random subset of that
  many for training (all its rows are still scored). The subset is the
  documented one: ``numpy.random.default_rng(0)``, entities in ascending id,
  ``choice(count, max_samples, replace=False)`` over the entity's rows in input
  order.
- an entity whose rows carry one label has no finite optimum for its
  unregularised intercept: the program stops at its iteration cap wherever it
  is, the reference where its steps are clipped. Their rows' losses are
  ~exp(-|margin|) either way, so the losses compared do not see them; the
  coefficient comparison leaves them out by a rule on the reference's own
  curvature (``DETERMINED``), not by name.

What is compared (``check``), each against a limit kept in the configuration's
file under ``check.limits`` with the readings it was set from in PERF.md:

- ``loss_k``, k = 1..3: the whole training objective after sweep k, which the
  program reports as the fixed effect's starting L-BFGS value in sweep k+1
  (run ledger): relative gap to the reference's.
- ``grad0``: the norm of the first gradient as the optimizer gets it (fixed
  effect, sweep 1, iteration 0): relative gap of norms.
- ``coef.<coordinate>``: the trained leaf after the last sweep,
  ``|prog - ref| / |ref|`` (Frobenius; determined entities only for the
  random-effect tables). A norm of the difference, stricter than a gap of
  norms, because it is what the lower-precision control moves.
- ``small.<coordinate>``: what ``coef`` leaves out of a table. The summed
  loss over the training rows of the entities that are not determined, under
  the program's last model and under the reference's: relative gap. A
  few-row entity's coefficients are loose but the loss it reaches is not, so
  a fault in the waves of the smallest buckets shows here. ``check`` prints
  how many entities and rows each of the two numbers covers.
"""

from __future__ import annotations

import sys

import numpy as np

DETERMINED = 2.0  # least intercept curvature (sum of l'' over the entity's
#                   training rows) for a table row to be compared
DAMPING = 1e-6
STEP_CLIP = 4.0
NEWTON_STEPS = 25
BLOCK_ROWS = 2_000_000  # rows summed per block, so that the products fit


def capped_training_rows(ids: np.ndarray, num_entities: int, cap,
                         seed: int = 0) -> np.ndarray:
    """(n,) float32, 1.0 on the rows an entity trains on (see the module
    docstring for the rule)."""
    n = ids.shape[0]
    train = np.ones(n, np.float32)
    if cap is None:
        return train
    counts = np.bincount(ids, minlength=num_entities)
    over = np.flatnonzero(counts > cap)
    if not over.size:
        return train
    order = np.argsort(ids, kind="stable")
    starts = np.cumsum(counts) - counts
    rng = np.random.default_rng(seed)
    for u in over:
        rows = order[starts[u]:starts[u] + counts[u]]
        train[rows] = 0.0
        train[rows[rng.choice(counts[u], size=int(cap), replace=False)]] = 1.0
    return train


def _fns(task: str, lam: float):
    """The jitted pieces, closed over the loss. Row-wise arrays are kept
    feature-major, ``(features, rows)``: a ``(rows, 8)`` float32 array is
    padded sixteenfold in the chip's tiled memory, a ``(8, rows)`` one is
    not."""
    import jax
    import jax.numpy as jnp

    if task == "logistic":
        def loss(m, y):
            return jnp.logaddexp(0.0, m) - y * m

        def d1(m, y):
            return jax.nn.sigmoid(m) - y

        def d2(m, y):
            s = jax.nn.sigmoid(m)
            return s * (1.0 - s)
    elif task == "linear":
        def loss(m, y):
            return 0.5 * (m - y) ** 2

        def d1(m, y):
            return m - y

        def d2(m, y):
            return jnp.ones_like(m)
    else:
        raise ValueError(task)

    def reg_mask(d):
        return jnp.ones((d,), jnp.float32).at[d - 1].set(0.0)

    @jax.jit
    def fixed_value(Xt, y, off, w):
        m = w @ Xt + off
        return jnp.sum(loss(m, y)) + 0.5 * lam * jnp.sum(
            (w * reg_mask(w.shape[0])) ** 2)

    @jax.jit
    def fixed_grad_hess(Xt, y, off, w):
        mask = reg_mask(w.shape[0])
        m = w @ Xt + off
        g = Xt @ d1(m, y) + lam * mask * w
        H = (Xt * d2(m, y)) @ Xt.T + jnp.diag(lam * mask)
        return g, H

    def blocks(n):
        return [(a, min(a + BLOCK_ROWS, n)) for a in range(0, n, BLOCK_ROWS)]

    def entity_sums(cols_of, ids, E, n):
        """Per-entity sums of row-wise columns, block of rows by block:
        ``cols_of(a, b)`` gives the (k, b-a) columns of rows a..b."""
        total = 0.0
        for a, b in blocks(n):
            total = total + jax.ops.segment_sum(cols_of(a, b).T, ids[a:b],
                                                num_segments=E)
        return total.T  # (k, E)

    def spd_solve(h, g, d):
        """x with H x = g for every entity at once, by an 8-wide Cholesky
        factorisation written out over (entities,) vectors. ``h[i][j]``, i<=j,
        and ``g[i]`` are such vectors."""
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
    def entity_step(Xt, y, off, train, ids, Wt):
        """One damped Newton step of every entity's block at once, with
        per-entity step halving; returns the new table, the largest step
        taken by a determined entity, and the intercept curvature."""
        d, E = Wt.shape
        n = y.shape[0]
        mask = reg_mask(d)[:, None]
        pairs = [(i, j) for i in range(d) for j in range(i, d)]

        def grad_and_hessian(a, b):
            x = Xt[:, a:b]
            m = jnp.sum(x * Wt[:, ids[a:b]], axis=0) + off[a:b]
            r = train[a:b] * d1(m, y[a:b])
            c = train[a:b] * d2(m, y[a:b])
            return jnp.concatenate(
                [x * r, jnp.stack([c * x[i] * x[j] for i, j in pairs])])

        sums = entity_sums(grad_and_hessian, ids, E, n)
        g = sums[:d] + lam * mask * Wt
        h = [[None] * d for _ in range(d)]
        for k, (i, j) in enumerate(pairs):
            h[i][j] = sums[d + k] + (lam * mask[i, 0] + DAMPING
                                     if i == j else 0.0)
        curvature = sums[d + len(pairs) - 1]  # the intercept's own l'' sum
        step = spd_solve(h, list(g), d)
        big = jnp.max(jnp.abs(step), axis=0, keepdims=True)
        step = step * jnp.minimum(1.0, STEP_CLIP / jnp.maximum(big, 1e-30))
        trials = (0.0, 0.25, 0.5, 1.0)

        def values(a, b):
            x = Xt[:, a:b]
            return jnp.stack([
                train[a:b] * loss(
                    jnp.sum(x * (Wt - t * step)[:, ids[a:b]], axis=0)
                    + off[a:b], y[a:b]) for t in trials])

        f = entity_sums(values, ids, E, n) + jnp.stack([
            0.5 * lam * jnp.sum(((Wt - t * step) * mask) ** 2, axis=0)
            for t in trials])
        taken = jnp.zeros((1, E), jnp.float32)
        for k, t in enumerate(trials[1:], 1):  # the longest that is not uphill
            taken = jnp.where(f[k] <= f[0], t, taken)
        moved = jnp.max(jnp.where(curvature >= DETERMINED,
                                  jnp.abs(taken * step), 0.0))
        return Wt - taken * step, moved, curvature

    @jax.jit
    def entity_score(Xt, ids, Wt):
        return jnp.concatenate([
            jnp.sum(Xt[:, a:b] * Wt[:, ids[a:b]], axis=0)
            for a, b in blocks(ids.shape[0])])

    @jax.jit
    def masked_loss(m, y, mask):
        return jnp.sum(mask * loss(m, y))

    return fixed_value, fixed_grad_hess, entity_step, entity_score, masked_loss


def train(data, mix: dict, settings: dict, sweeps: int, served: dict) -> dict:
    """Block coordinate descent over ``sweeps`` sweeps. Returns the trained
    leaves, the objective and first-gradient norm at the start of each
    fixed-effect update, each table's intercept curvature, and for each table
    the loss over its undetermined entities' training rows under the
    reference's model and under ``served``, the program's."""
    import jax
    import jax.numpy as jnp

    lam = float(settings["optimizer"]["reg_weight"])
    (fixed_value, fixed_grad_hess, entity_step, entity_score,
     masked_loss) = _fns(data.task, lam)
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
            rows[cid] = jnp.asarray(capped_training_rows(
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
                    w = model[cid]
                    f = fixed_value(Xt[cid], y, off, w)
                    g, H = fixed_grad_hess(Xt[cid], y, off, w)
                    values.append(float(f))
                    grad_norms.append(float(jnp.linalg.norm(g)))
                    for _ in range(NEWTON_STEPS):
                        step = jnp.linalg.solve(H, g)
                        t = 1.0
                        while t > 1e-3:
                            f_new = fixed_value(Xt[cid], y, off, w - t * step)
                            if float(f_new) <= float(f):
                                break
                            t *= 0.5
                        else:
                            break
                        w, f = w - t * step, f_new
                        if float(jnp.max(jnp.abs(t * step))) < 1e-7:
                            break
                        g, H = fixed_grad_hess(Xt[cid], y, off, w)
                    model[cid] = w
                    score[cid] = w @ Xt[cid]
                else:
                    Wt = model[cid]
                    for _ in range(NEWTON_STEPS):
                        Wt, moved, curv = entity_step(
                            Xt[cid], y, off, rows[cid], ids[cid], Wt)
                        if float(moved) < 1e-6:
                            break
                    model[cid], curvature[cid] = Wt, curv
                    score[cid] = entity_score(Xt[cid], ids[cid], Wt)

        def margins(leaves):
            return sum(leaves[c] @ Xt[c] if leaves[c].ndim == 1
                       else entity_score(Xt[c], ids[c], leaves[c])
                       for c in mix["update_sequence"])

        mine = margins(model)
        theirs = margins({c: jnp.asarray(np.asarray(
            served[c], np.float32).T) for c in model})
        small = {}
        for cid, curv in curvature.items():
            loose = curv < DETERMINED
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


def program_readings(ledger_rows, mix) -> tuple[dict, dict]:
    """{outer iteration: starting value} and {outer iteration: starting
    gradient norm} of the first fixed-effect coordinate's updates, as the
    program's run ledger has them."""
    fixed = [c for c in mix["update_sequence"]
             if mix["coordinates"][c]["type"] == "fixed"][0]
    values, norms = {}, {}
    for r in ledger_rows:
        if (r.get("kind") == "opt_iter" and r.get("coordinate") == fixed
                and r.get("iteration") == 0):
            values[r["outer_iteration"]] = r["value"]
            norms[r["outer_iteration"]] = r["grad_norm"]
    return values, norms


def compare(ref: dict, served: dict, ledger_rows, mix: dict) -> dict:
    """The numbers compared, without their limits; prints what the two
    numbers of each table cover."""
    values, norms = program_readings(ledger_rows, mix)
    out = {}
    for k in (1, 2, 3):
        # the objective after sweep k is the starting value of sweep k+1
        if k in values and k < len(ref["values"]):
            out[f"loss_{k}"] = abs(values[k] - ref["values"][k]) / abs(
                ref["values"][k])
    if 0 in norms:
        out["grad0"] = abs(norms[0] - ref["grad_norms"][0]) / ref[
            "grad_norms"][0]
    for cid, leaf in ref["model"].items():
        prog = served[cid]
        if leaf.ndim == 2:
            keep = ref["curvature"][cid] >= DETERMINED
            leaf, prog = leaf[keep], prog[keep]
        out[f"coef.{cid}"] = float(np.linalg.norm(prog - leaf)
                                   / max(np.linalg.norm(leaf), 1e-30))
    for cid, s in ref["small"].items():
        # a table with no undetermined entity has nothing left to cover
        out[f"small.{cid}"] = (abs(s["program"] - s["reference"])
                               / s["reference"] if s["rows"] else 0.0)
        print(f"coverage {cid}: coef compares {s['of'] - s['entities']} of "
              f"{s['of']} entities holding "
              f"{100 * (1 - s['rows'] / s['trained_rows']):.2f}% of its "
              f"training rows; small covers the other {s['entities']} "
              f"entities, {s['rows']:.0f} rows (loss {s['program']:.6g} "
              f"against the reference's {s['reference']:.6g})",
              file=sys.stderr, flush=True)
    return out


def check(data, cell: dict, served: dict, ledger_rows, sweeps: int) -> dict:
    """name -> {"value", "limit"} for every number compared. A number the
    run could not read counts as over its limit."""
    ref = train(data, cell["mix"], cell["settings"], sweeps, served)
    got = compare(ref, served, ledger_rows, cell["mix"])
    limits = cell["configuration"]["check"]["limits"]
    out = {}
    for name, limit in limits.items():
        v = got.get(name, float("inf"))
        out[name] = {"value": v if np.isfinite(v) else 1e30, "limit": limit}
    return out
