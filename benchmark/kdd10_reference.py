"""The plain reference of the schema ``game_kdd10``, and the comparison that
decides ``correct`` there.

The model is GLMix's (see ``reference.py``): a row's score is a fixed effect
over its sparse features, ``sum_j v_j w[c_j]``, plus one random effect a
student, ``x . W[student]``, and training is block coordinate descent under
the logistic loss and ``lambda/2 |.|^2`` (every fixed coefficient; every
table column but the intercept, the last). This file states the fixed block
in whole-batch float32 ``jax.numpy`` over the rows as they are given, in ELL
form (blocks of rows of like length, each as wide as its longest row), over
the columns some row touches (relabelled in column order, so that the
solver's vectors leave out the columns no row has, whose minimiser is 0):
margins by ``take``, the gradient and the Hessian's products by
``segment_sum``; no hot block, no classes, no permuted space, no trust
region. It is minimised by a truncated Newton
method: conjugate gradients on Hessian-vector products, preconditioned by
the Hessian's diagonal and one coarse term, then a step halved until the
objective does not rise (``criteo_reference.py``'s method, over rows of
varying length). The rows are held in a few blocks on the host's CPU
backend, one thread each. The table's blocks are ``reference.py``'s damped
Newton steps, used as they stand. Nothing of the program is imported, and
nothing it made is taken except what is compared; no solver code is shared
with the program's TRON.

What is compared (``check``), each against ``check.limits`` of the
configuration's file, is ``criteo_reference.py``'s set: ``grad0`` (the first
gradient's norm, summed in float64 here), ``loss_k`` (the objective after
sweep k), ``coef.fixed`` / ``small.fixed`` (determined columns by
coefficient, the rest by the loss they reach) and ``coef.per-student`` /
``small.per-student`` (the table at the program's own fixed effect).
TRON stops by its own rule, so unlike the Criteo cell's truncated L-BFGS
every one of these is a distance to the block's minimum at float32's
resolution, not a solver's slack.
"""

from __future__ import annotations

import sys
import time

import numpy as np

import criteo_reference
import reference as dense_reference

DETERMINED = dense_reference.DETERMINED
NEWTON_STEPS = 30
CG_STEPS = 30  # at most, in one Newton step
CG_RTOL_MIN = 3e-2  # the tightest residual a Newton step's system is solved to
BLOCKS = 12  # blocks of rows, and threads, of the fixed block
STEP_TOL = 1e-5  # the solve stops when no coefficient moves by more,
DECREASE_TOL = 1e-7  # or after a step predicted to lower f by less than this
#                      share of it, a tenth of where the program's TRON
#                      stops (8 units of float32's 1.2e-7 of f). Solved this
#                      far, the fixed block stands within 1.5e-6 of f and
#                      7e-4 of its coefficients of the same solve to 1e-4
#                      residuals (CPU runs at 100,000 rows)
BASE = 8  # fields on every row, one column each: the coarse term's


def _fixed_fns(lam: float, d: int):
    """The fixed block's jitted pieces, each over one block of rows.
    ``idx`` and ``val`` are slot-major, ``(slots, rows)``; a pad slot names
    column ``d`` and holds 0."""
    import jax
    import jax.numpy as jnp

    def margins(idx, val, w):
        return jnp.sum(val * jnp.take(jnp.append(w, 0.0), idx), axis=0)

    def rowterm(idx, val, r):
        return jax.ops.segment_sum((val * r[None, :]).reshape(-1),
                                   idx.reshape(-1), num_segments=d + 1)[:d]

    @jax.jit
    def value(idx, val, y, off, w):
        """The block's rows' losses, summed on the host: a float32 sum of
        40,000 positive terms one after another drifts by up to a part in
        a thousand, more than the differences ``loss_k`` reads."""
        m = margins(idx, val, w) + off
        return jnp.logaddexp(0.0, m) - y * m

    @jax.jit
    def grad_curv(idx, val, y, off, w):
        """The block's part of the gradient, its rows' curvatures l'', its
        part of the Hessian's diagonal, and its sum of l'' v^2 over the
        rows (v: the row's value on each of its eight row fields)."""
        p = jax.nn.sigmoid(margins(idx, val, w) + off)
        c = p * (1.0 - p)
        return (rowterm(idx, val, p - y), c, rowterm(idx, val * val, c),
                jnp.sum(c * val[0] * val[0]))

    @jax.jit
    def hvp(idx, val, c, v):
        return rowterm(idx, val, c * margins(idx, val, v))

    @jax.jit
    def precondition(r, diag, field_of, b):
        """The Hessian's diagonal plus one coarse term. Every row has one
        non-zero in each of the eight row fields, of the row's own value
        v_i, so X maps each such field's indicator vector z_f to the same
        (v_i): on their span the Hessian is b 1 1' + lam diag(columns of f)
        with b = sum_i l''_i v_i^2, inverted in closed form
        (Sherman-Morrison), as ``criteo_reference`` does for its 39 fields.
        Any positive definite preconditioner gives the same solution; this
        one gives it in fewer passes."""
        there = (diag > 0) & (field_of < BASE)
        f = jnp.where(there, field_of, 0)
        cols = jax.ops.segment_sum(jnp.where(there, 1.0, 0.0), f,
                                   num_segments=BASE)
        a = 1.0 / (lam * jnp.maximum(cols, 1.0))
        t = jax.ops.segment_sum(jnp.where(there, r, 0.0), f,
                                num_segments=BASE)
        coarse = a * t - a * (b * jnp.sum(a * t) / (1.0 + b * jnp.sum(a)))
        return r / (diag + lam) + jnp.where(there, coarse[f], 0.0)

    return value, grad_curv, hvp, jax.jit(margins), precondition


class _FixedBlock:
    """sum loss(X w + off) + lam/2 |w|^2 over all rows and the touched
    columns, held as ``BLOCKS`` blocks of rows on the host device and
    evaluated by as many threads. ``columns`` maps a touched column's own
    index to its original one."""

    def __init__(self, data, lam: float, field_starts: np.ndarray):
        import jax
        import jax.numpy as jnp
        from concurrent.futures import ThreadPoolExecutor

        D = int(data.num_features)
        live = data.indices < D
        self.columns, local = np.unique(data.indices[live],
                                        return_inverse=True)
        self.d = d = int(self.columns.size)
        # each row's live slots first, the rows in order of their length, so
        # that a block of rows is as wide as its longest row and no wider
        first = np.argsort(~live, axis=1, kind="stable")
        idx = np.full(data.indices.shape, d, np.int32)
        idx[live] = local
        idx = np.take_along_axis(idx, first, axis=1)
        val = np.take_along_axis(data.values, first, axis=1)
        length = live.sum(axis=1)
        self.order = np.argsort(length, kind="stable")
        self.lam = lam
        n = idx.shape[0]
        self.host = host = criteo_reference._host()
        (self._value, self._grad_curv, self._hvp, self._margins,
         self._precondition) = _fixed_fns(lam, d)
        edges = np.linspace(0, n, BLOCKS + 1).astype(np.int64)
        self.bounds = list(zip(edges[:-1].tolist(), edges[1:].tolist()))

        def put(a):
            return jax.device_put(np.ascontiguousarray(a), host)

        self.blocks = []
        for a, b in self.bounds:
            rows = self.order[a:b]
            wide = int(length[rows].max(initial=1))
            self.blocks.append((put(idx[rows, :wide].T),
                                put(val[rows, :wide].T),
                                put(data.response[rows])))
        self.pool = ThreadPoolExecutor(BLOCKS)
        self.field_of = put((np.searchsorted(
            field_starts, self.columns, side="right") - 1).astype(np.int32))
        self.passes = 0
        self.zeros = jax.device_put(jnp.zeros((d,), jnp.float32), host)

    def each(self, fn):
        """``fn(k, idx, val, y)`` of every block, a thread each."""
        import jax
        self.passes += 1
        return list(self.pool.map(
            lambda k: jax.block_until_ready(fn(k, *self.blocks[k])),
            range(BLOCKS)))

    def offsets(self, off):
        """(n,) offsets, from wherever they are, as the blocks' own."""
        import jax
        off = np.asarray(off, np.float32)[self.order]
        return [jax.device_put(off[a:b], self.host) for a, b in self.bounds]

    def full(self, w, num_features: int) -> np.ndarray:
        """A vector over the touched columns, laid out over all of them."""
        out = np.zeros(num_features, np.float32)
        out[self.columns] = np.asarray(w)
        return out

    def value(self, off, w):
        """The objective, its sums in float64."""
        parts = self.each(lambda k, idx, val, y: self._value(
            idx, val, y, off[k], w))
        w = np.asarray(w, np.float64)
        return float(sum(np.asarray(p, np.float64).sum() for p in parts)
                     + 0.5 * self.lam * (w @ w))

    def grad_curv(self, off, w):
        parts = self.each(lambda k, idx, val, y: self._grad_curv(
            idx, val, y, off[k], w))
        g = sum(p[0] for p in parts) + self.lam * w
        return (g, [p[1] for p in parts], sum(p[2] for p in parts),
                sum(float(p[3]) for p in parts))

    def margins(self, w) -> np.ndarray:
        out = np.empty(self.order.size, np.float32)
        out[self.order] = np.concatenate([np.asarray(m) for m in self.each(
            lambda k, idx, val, y: self._margins(idx, val, w))])
        return out

    def newton_direction(self, c, g, diag, b, rtol):
        """H s = g by preconditioned conjugate gradients, H = X' C X + lam,
        to a residual of ``rtol`` |g|."""
        import jax.numpy as jnp

        def pre(r):
            return self._precondition(r, diag, self.field_of, b)

        x, r = self.zeros, g
        z = pre(r)
        p, rz = z, float(r @ z)
        stop = (rtol * float(jnp.linalg.norm(g))) ** 2
        for _ in range(CG_STEPS):
            if float(r @ r) <= stop:
                break
            hp = sum(self.each(lambda k, idx, val, y: self._hvp(
                idx, val, c[k], p))) + self.lam * p
            a = rz / float(p @ hp)
            x, r = x + a * p, r - a * hp
            z = pre(r)
            rz, rz_old = float(r @ z), rz
            p = z + (rz / rz_old) * p
        return x

    def solve(self, off, w, scale):
        """The block's minimiser from ``w`` by truncated Newton steps; the
        objective and the gradient's norm at the start. ``scale`` is the
        norm of the run's first gradient: a step's system is solved to the
        residual min(0.1, sqrt(|g| / scale)) |g| (Eisenstat and Walker)."""
        import jax.numpy as jnp
        f = start = self.value(off, w)
        g, c, diag, b = self.grad_curv(off, w)
        norm0 = float(jnp.linalg.norm(g))
        for _ in range(NEWTON_STEPS):
            gn = float(jnp.linalg.norm(g))
            rtol = min(0.1, max(CG_RTOL_MIN, (gn / (scale or norm0)) ** 0.5))
            step = self.newton_direction(c, g, diag, b, rtol)
            last = 0.5 * float(g @ step) <= DECREASE_TOL * abs(f)
            t = 1.0
            while t > 1e-3:
                f_new = self.value(off, w - t * step)
                if f_new <= f:
                    break
                t *= 0.5
            else:
                break
            w, f = w - t * step, f_new
            if last or float(jnp.max(jnp.abs(t * step))) < STEP_TOL:
                break
            g, c, diag, b = self.grad_curv(off, w)
        return w, start, norm0


def first_gradient_norm(data) -> float:
    """|X' (1/2 - y)|, the gradient where every coefficient is 0 and there
    are no offsets, summed in float64 over the live entries (a float32
    ``segment_sum`` adds a column's terms one after another and drifts by
    more than the float32 sums of the program's two parts do)."""
    D = int(data.num_features)
    live = data.indices < D
    r = 0.5 - data.response.astype(np.float64)
    g = np.bincount(
        data.indices[live], minlength=D,
        weights=(data.values.astype(np.float64) * r[:, None])[live])
    return float(np.linalg.norm(g))


def field_starts(conf: dict) -> np.ndarray:
    """The first column of every field, the row fields first."""
    cards = [int(f["cardinality"])
             for f in conf["fields"] + conf["kc_fields"]]
    return np.concatenate([[0], np.cumsum(cards)[:-1]]).astype(np.int64)


def train(data, cell: dict, sweeps: int, served: dict) -> dict:
    """Block coordinate descent over ``sweeps`` sweeps, the fixed effect then
    the table, as ``criteo_reference.train``. Returns the trained leaves,
    the objective and the norm of the gradient at the start of each fixed
    update, both curvatures, and the losses ``small.*`` compares."""
    import jax
    import jax.numpy as jnp

    mix, settings = cell["mix"], cell["settings"]
    fixed, table = mix["update_sequence"]
    blocks = settings["optimizers"]
    lam = float(blocks[fixed]["reg_weight"])
    if (float(blocks[table]["reg_weight"]) != lam or any(
            o["regularization"] != "L2" for o in blocks.values())):
        raise SystemExit("kdd10_reference takes one L2 weight for both "
                         "coordinates")
    _, _, entity_step, entity_score, masked_loss = dense_reference._fns(
        "logistic", lam)
    assert (mix["coordinates"][fixed]["type"], mix["coordinates"][table][
        "type"]) == ("fixed", "random") and not mix["locked_coordinates"]
    block = _FixedBlock(data, lam, field_starts(cell["configuration"]))
    y = jnp.asarray(data.response)
    n = y.shape[0]
    Xt = jnp.asarray(np.ascontiguousarray(data.table.T))
    ids = jnp.asarray(data.entity_ids)
    rows = jnp.asarray(dense_reference.capped_training_rows(
        data.entity_ids, data.num_entities, settings.get("max_samples")))
    w = block.zeros
    Wt = jnp.zeros((Xt.shape[0], data.num_entities), jnp.float32)
    s_fixed = jnp.zeros((n,), jnp.float32)
    s_table = jnp.zeros((n,), jnp.float32)
    values, grad_norms, work = [], [], []
    with jax.default_matmul_precision("highest"):
        for _ in range(sweeps):
            t0, before = time.monotonic(), block.passes
            w, f, norm = block.solve(block.offsets(s_table), w,
                                     grad_norms[0] if grad_norms else None)
            values.append(f)
            grad_norms.append(norm if grad_norms
                              else first_gradient_norm(data))
            s_fixed = jnp.asarray(block.margins(w))
            t1 = time.monotonic()
            for steps in range(1, dense_reference.NEWTON_STEPS + 1):
                Wt, moved, curv = entity_step(Xt, y, s_fixed, rows, ids, Wt)
                if float(moved) < 1e-6:
                    break
            s_table = entity_score(Xt, ids, Wt)
            work.append((block.passes - before, t1 - t0, steps,
                         time.monotonic() - t1))
        print(f"reference: the fixed block on {block.host} over "
              f"{block.d} touched columns; per sweep (its passes over the "
              "rows, seconds, table steps, seconds): "
              + ", ".join(f"({p}, {a:.1f}, {s}, {b:.1f})"
                          for p, a, s, b in work), file=sys.stderr, flush=True)

        # what small.* compares
        _, _, diag, _ = block.grad_curv(block.offsets(s_table), w)
        diag = np.asarray(diag)
        w = np.asarray(w)
        w_prog = np.asarray(served[fixed], np.float32)
        w_theirs = w_prog[block.columns]
        ones = jnp.ones((n,), jnp.float32)
        swapped = jax.device_put(np.where(diag < DETERMINED, w_theirs, w),
                                 block.host)
        small = {fixed: {
            "reference": float(masked_loss(s_fixed + s_table, y, ones)),
            "program": float(masked_loss(
                jnp.asarray(block.margins(swapped)) + s_table, y, ones)),
            "loose": int(np.sum(diag < DETERMINED))}}
        # The table, at the program's own fixed effect: its block's
        # minimiser for those offsets, from the reference's last table.
        s_theirs = jnp.asarray(block.margins(jax.device_put(
            w_theirs, block.host)))
        for _ in range(dense_reference.NEWTON_STEPS):
            Wt, moved, curv = entity_step(Xt, y, s_theirs, rows, ids, Wt)
            if float(moved) < 1e-6:
                break
        theirs = s_theirs + entity_score(
            Xt, ids, jnp.asarray(np.asarray(served[table], np.float32).T))
        loose = curv < DETERMINED
        mask = rows * loose[ids]
        small[table] = {
            "reference": float(masked_loss(
                s_theirs + entity_score(Xt, ids, Wt), y, mask)),
            "program": float(masked_loss(theirs, y, mask)),
            "loose": int(jnp.sum(loose))}
    block.pool.shutdown()
    # The columns no row touches: 0 in the reference's minimiser, and a
    # curvature of 0, so they are compared by neither coef nor small.
    untouched = np.ones(int(data.num_features), bool)
    untouched[block.columns] = False
    return {"small": small, "values": values, "grad_norms": grad_norms,
            "model": {fixed: w, table: np.asarray(Wt.T)},
            "columns": {fixed: block.columns},
            "curvature": {fixed: diag, table: np.asarray(curv)},
            "untouched": {fixed: float(np.abs(w_prog[untouched]).max(
                initial=0.0))}}


def compare(ref: dict, served: dict, ledger_rows, mix: dict) -> dict:
    """The numbers compared, without their limits: ``criteo_reference``'s,
    the fixed effect's over its touched columns."""
    cols = ref["columns"]
    theirs = {cid: (np.asarray(leaf, np.float32)[cols[cid]] if cid in cols
                    else leaf) for cid, leaf in served.items()}
    out = criteo_reference.compare(ref, theirs, ledger_rows, mix)
    for cid, v in ref["untouched"].items():
        print(f"coverage {cid}: the columns no row touches hold at most "
              f"{v:.3g} in the program's model", file=sys.stderr, flush=True)
    return out


def check(data, cell: dict, served: dict, ledger_rows, sweeps: int) -> dict:
    """name -> {"value", "limit"} for every number compared. A number the
    run could not read counts as over its limit. The rehearsal's cap as the
    schema's estimator sets it."""
    import game_music
    cell = dict(cell, settings=game_music._settings(cell))
    ref = train(data, cell, sweeps, served)
    got = compare(ref, served, ledger_rows, cell["mix"])
    out = {}
    for name, limit in cell["configuration"]["check"]["limits"].items():
        v = got.get(name, float("inf"))
        out[name] = {"value": v if np.isfinite(v) else 1e30, "limit": limit}
    return out
