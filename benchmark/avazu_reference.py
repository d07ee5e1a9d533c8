"""The plain reference of the schema ``game_avazu``, and the comparison that
decides ``correct`` there.

The model is GAME's (see ``reference.py``): a row's score is a fixed effect
over its 22 hashed fields, ``sum_f v_f w[c_f]``, plus its publisher's effect
over the sparse shard ``re_ad``, ``sum_k u_k W[publisher, t_k]``; training is
block coordinate descent under ``lambda/2 |.|^2`` (every fixed coefficient;
every table column but the intercept, the last).

The fixed block is ``criteo_reference.py``'s (ELL margins by ``take``, sums
by ``segment_sum``, truncated Newton with preconditioned conjugate gradients,
on the host's CPU backend), stopped on the objective (``solve_fixed``).

The table is stated as **one ELL block over crossed columns**: the column of
a non-zero is ``(publisher, column)``, so the block is a plain sparse GLM of
``publishers x columns`` coefficients (those no row names drop out) and a
random effect's objective, separable by publisher under L2, needs no
projection, bucket, lane or column map to state. It is minimised by truncated
Newton steps: conjugate gradients on Hessian-vector products preconditioned
by the Hessian's diagonal, every scalar of the iteration (step lengths, the
stopping test, the step halving) taken **per publisher** by ``segment_sum``
over the crossed columns, which is the separability used and nothing else.
It trains on the rows ``reference.py``'s documented ``max_samples`` rule
keeps (``capped_training_rows``) and scores all rows. Nothing of the program
is imported, and nothing it made is taken except what is compared.

What is compared (``check``), each against ``check.limits`` of the
configuration's file: ``loss_1..3``, ``grad0``, ``coef.fixed`` and
``small.fixed`` as ``criteo_reference.py`` defines them, and for the table,
at the program's own fixed effect (that file says why):

- ``coef.<table>``: ``|prog - ref| / |ref|`` over the crossed columns of the
  publishers whose intercept curvature in the reference is at least
  ``DETERMINED``;
- ``small.<table>``: the summed loss over the training rows of the other
  publishers under the program's table against the reference's: relative
  gap;
- ``capped.<table>``: ``small``'s gap over the publishers ``max_samples``
  caps (0 where it caps none): the summed loss over the rows the documented
  rule keeps of them, under the program's table against the reference's.
  The reference's table is that loss's minimiser (but for L2), so a program
  that trained them on the same rows stands a second-order step above it
  however early its solve stopped, and one that trained them on other rows
  stands a sampling error above it, which the whole table's norms would
  dilute and the capped publishers' flat directions would drown;
- ``rows.<table>``: the rows the program trained the table on (the
  ``rows_useful`` of its first sweep's ``re_fit_wave`` rows) less the rows
  the documented rule keeps, in absolute value. Its limit is 0: the size of
  the subset is exact whatever the solves' tolerance, where ``capped`` holds
  its membership only as far as that tolerance resolves a sampling error;
- ``offspace.<table>``: the largest |coefficient| the program returns on a
  column its publisher's training rows never name: what a wrong column map
  or scatter writes. Its limit is 0.
"""

from __future__ import annotations

import sys
import time

import numpy as np

import criteo_reference
import reference as dense_reference

DETERMINED = dense_reference.DETERMINED
capped_training_rows = dense_reference.capped_training_rows
NEWTON_STEPS = 30
CG_RTOL = (1e-2, 0.25)  # the tightest and the loosest residual of a step
# A solve stops when no determined coefficient moves by more than its
# tolerance, and a Newton step's system is given at most so many conjugate
# gradient steps. Along the path the table only has to hand the fixed block
# its offsets (what is compared there is the objective, to parts in a
# thousand: an error of 1e-2 in a coefficient is second order in it); the
# last solve is the one the program's table is held to.
PATH = (1e-2, 20)
LAST = (1e-4, 40)
OBJECTIVE_TOL = 1e-5  # the fixed block's solve stops when a step lowers the
#                       objective by less of it
STEP_CLIP = dense_reference.STEP_CLIP
DAMPING = dense_reference.DAMPING
TRIALS = (0.0, 0.25, 0.5, 1.0)
BLOCKS = 12  # blocks of rows, and threads, of the table block


def _table_fns(d: int, E: int):
    """The table block's jitted pieces, each over one block of rows; ``idx``
    and ``val`` are slot-major, ``(slots, rows)``, ``t`` the rows' training
    mask, ``ids`` their publishers."""
    import jax
    import jax.numpy as jnp

    def margins(idx, val, w):
        return jnp.sum(val * jnp.take(w, idx), axis=0)

    def rowterm(idx, val, r):
        return jax.ops.segment_sum((val * r[None, :]).reshape(-1),
                                   idx.reshape(-1), num_segments=d)

    @jax.jit
    def grad_curv(idx, val, y, t, off, w):
        p = jax.nn.sigmoid(margins(idx, val, w) + off)
        c = t * p * (1.0 - p)
        return rowterm(idx, val, t * (p - y)), c, rowterm(idx, val * val, c)

    @jax.jit
    def hvp(idx, val, c, v):
        return rowterm(idx, val, c * margins(idx, val, v))

    @jax.jit
    def trial_losses(idx, val, y, t, ids, off, w, step):
        """(trials, E): every publisher's summed training loss at ``w -
        trial * step``."""
        m, s = margins(idx, val, w) + off, margins(idx, val, step)
        return jnp.stack([jax.ops.segment_sum(
            t * (jnp.logaddexp(0.0, m - k * s) - y * (m - k * s)), ids,
            num_segments=E) for k in TRIALS])

    @jax.jit
    def masked_loss(idx, val, y, mask, off, w):
        m = margins(idx, val, w) + off
        return jnp.sum(mask * (jnp.logaddexp(0.0, m) - y * m))

    return grad_curv, hvp, trial_losses, masked_loss, jax.jit(margins)


class _TableBlock:
    """sum_train loss(U W + off) + lam/2 |W without intercepts|^2 over the
    crossed columns some row names, held as ``BLOCKS`` blocks of rows on the
    host device and evaluated by as many threads."""

    def __init__(self, data, lam: float, train: np.ndarray):
        import jax
        import jax.numpy as jnp
        from concurrent.futures import ThreadPoolExecutor

        n, slots = data.table_indices.shape
        self.E, self.D = int(data.num_entities), int(data.table_features)
        self.host = host = criteo_reference._host()
        # The rows are held publisher by publisher (a stable sort), so that
        # a block of rows names one run of the crossed columns: the same
        # sums, read and written near each other.
        self.order = order = np.argsort(data.entity_ids, kind="stable")
        ids = data.entity_ids[order]
        table_indices = data.table_indices[order]
        train = np.asarray(train, np.float32)[order]
        key = (ids.astype(np.int64) * self.D)[:, None] + table_indices
        seen = np.zeros(self.E * self.D, bool)
        seen[key] = True
        self.crossed = np.flatnonzero(seen)  # (d,) publisher * D + column
        self.d = d = int(self.crossed.size)
        position = np.cumsum(seen, dtype=np.int64) - 1
        idx = position[key].astype(np.int32)
        trained = np.zeros(self.E * self.D, bool)
        trained[key[train > 0]] = True
        self.offspace = ~trained  # (E * D,) no training row names it
        self.bounds = [(int(a), int(b)) for a, b in zip(
            np.linspace(0, n, BLOCKS + 1)[:-1],
            np.linspace(0, n, BLOCKS + 1)[1:])]

        def put(a):
            return jax.device_put(np.ascontiguousarray(a), host)

        values, response = data.table_values[order], data.response[order]
        self.blocks = [(put(idx[a:b].T), put(values[a:b].T),
                        put(response[a:b]), put(train[a:b]),
                        put(ids[a:b])) for a, b in self.bounds]
        self.ent = put((self.crossed // self.D).astype(np.int32))
        column = self.crossed % self.D
        self.lam = put(np.where(column == self.D - 1, 0.0, lam
                                ).astype(np.float32))
        # every publisher's intercept among the crossed columns (d where it
        # has no row: a zero is gathered there)
        at = np.full(self.E, d, np.int64)
        at[(self.crossed // self.D)[column == self.D - 1]] = np.flatnonzero(
            column == self.D - 1)
        self.intercept_at = put(at.astype(np.int32))
        # the preconditioner's groups: a publisher's columns slot by slot
        # (a slot is a field of the shard, the last one the intercept)
        slot_of = np.zeros(d, np.int64)
        for k in range(slots):
            slot_of[idx[:, k]] = k
        group = (self.crossed // self.D) * slots + slot_of
        self.slots = slots
        self.group = put(group.astype(np.int32))
        self.group_columns = put(np.bincount(
            group, minlength=self.E * slots).astype(np.float32))
        self.u = float(data.table_values[0, 0])
        self._precondition = jax.jit(self._coarse)
        (self._grad_curv, self._hvp, self._trial_losses, self._masked_loss,
         self._margins) = _table_fns(d, self.E)
        self.pool = ThreadPoolExecutor(BLOCKS)
        self.passes = 0
        self.zeros = jax.device_put(jnp.zeros((d,), jnp.float32), host)
        self._by_entity = jax.jit(lambda v: jax.ops.segment_sum(
            v, self.ent, num_segments=self.E))
        self._max_by_entity = jax.jit(lambda v: jax.ops.segment_max(
            v, self.ent, num_segments=self.E))

    def _coarse(self, r, M, lam, curvature):
        """The Hessian's diagonal plus one coarse term a publisher. Every
        row of a publisher has one non-zero of value u in each field and the
        intercept's 1, so on the span of its fields' indicator vectors z_f
        and its intercept the Hessian is b q q' + lam diag(columns of f)
        with q = (u, ..., u, 1), b the sum of its rows' curvatures and no
        lam on the intercept: the data are flat along z_f - u z_0, where
        only lam holds, and the diagonal alone leaves those directions to
        the iteration. The coarse system is inverted in closed form
        (Sherman-Morrison, the limit of no penalty on the intercept taken
        by hand): x_f = (t_f - u t_0) / (lam n_f), x_0 = t_0 (1 / b + S) -
        T, with t = Z' r, S = u^2 sum 1 / (lam n_f), T = u sum t_f / (lam
        n_f). Any positive definite preconditioner gives the same solution;
        this one gives it in fewer passes."""
        import jax
        import jax.numpy as jnp
        E, k = self.E, self.slots
        t = jax.ops.segment_sum(r, self.group, num_segments=E * k
                                ).reshape(E, k)
        n = self.group_columns.reshape(E, k)
        inv = jnp.where(n[:, :-1] > 0, 1.0 / (lam * jnp.maximum(
            n[:, :-1], 1.0)), 0.0)
        t0 = t[:, -1:]
        S = self.u ** 2 * jnp.sum(inv, axis=1, keepdims=True)
        T = self.u * jnp.sum(t[:, :-1] * inv, axis=1, keepdims=True)
        b = jnp.maximum(curvature[:, None], DAMPING)
        x = jnp.concatenate([(t[:, :-1] - self.u * t0) * inv,
                             t0 * (1.0 / b + S) - T], axis=1)
        return r / M + x.reshape(-1)[self.group]

    def each(self, fn):
        import jax
        self.passes += 1
        return list(self.pool.map(
            lambda k: jax.block_until_ready(fn(k, *self.blocks[k])),
            range(BLOCKS)))

    def offsets(self, off):
        """(n,) offsets in the rows' input order, as the blocks' own."""
        import jax
        off = np.asarray(off, np.float32)[self.order]
        return [jax.device_put(off[a:b], self.host) for a, b in self.bounds]

    def margins(self, w) -> np.ndarray:
        """Every row's score, in the rows' input order."""
        out = np.empty(self.order.shape[0], np.float32)
        out[self.order] = np.concatenate([np.asarray(m) for m in self.each(
            lambda k, idx, val, y, t, ids: self._margins(idx, val, w))])
        return out

    def masked_loss(self, mask, off, w) -> float:
        mask = self.offsets(mask)
        return float(sum(float(v) for v in self.each(
            lambda k, idx, val, y, t, ids: self._masked_loss(
                idx, val, y, mask[k], off[k], w))))

    def take(self, table: np.ndarray):
        """A ``(publishers, columns)`` table's coefficients on the crossed
        columns."""
        import jax
        return jax.device_put(np.asarray(table, np.float32).reshape(-1)[
            self.crossed], self.host)

    def grad_curv(self, off, w):
        import jax.numpy as jnp
        parts = self.each(lambda k, idx, val, y, t, ids: self._grad_curv(
            idx, val, y, t, off[k], w))
        diag = sum(p[2] for p in parts)
        curvature = jnp.concatenate([diag, jnp.zeros((1,))])[
            self.intercept_at]
        return (sum(p[0] for p in parts) + self.lam * w,
                [p[1] for p in parts], diag, curvature)

    def newton_direction(self, c, g, diag, rtol, curvature, cg_steps):
        """H s = g for every publisher at once by preconditioned conjugate
        gradients, each publisher with its own step lengths and its own
        stopping test (a residual of ``rtol`` |g| of its own); the
        iteration ends when no determined publisher is left in it."""
        import jax.numpy as jnp
        seg, ent = self._by_entity, self.ent
        determined = curvature >= DETERMINED
        M = diag + self.lam + DAMPING
        lam = float(jnp.max(self.lam))

        def pre(r):
            return self._precondition(r, M, lam, curvature)

        x, r = self.zeros, g
        z = pre(r)
        p, rz = z, seg(r * z)
        stop = jnp.square(rtol) * seg(g * g)
        for _ in range(cg_steps):
            live = seg(r * r) > stop
            if not bool(jnp.any(live & determined)):
                break
            hp = sum(self.each(lambda k, idx, val, y, t, ids: self._hvp(
                idx, val, c[k], p))) + (self.lam + DAMPING) * p
            php = seg(p * hp)
            a = jnp.where(live & (php > 0), rz / jnp.maximum(php, 1e-30), 0.0)
            x, r = x + a[ent] * p, r - a[ent] * hp
            z = pre(r)
            rz, rz_old = seg(r * z), rz
            b = jnp.where(live, rz / jnp.maximum(rz_old, 1e-30), 0.0)
            p = z + b[ent] * p
        return x

    def solve(self, off, w, scale, rule=PATH):
        """The block's minimiser from ``w`` by truncated Newton steps, every
        publisher halving its own step until its own objective does not
        rise; the intercept curvatures at the last point, and the first
        gradient's per-publisher norms (``scale`` of the later solves: a
        step's system is solved to the residual sqrt(|g| / scale) |g| of
        each publisher, within ``CG_RTOL``)."""
        import jax.numpy as jnp
        seg, ent = self._by_entity, self.ent
        tol, cg_steps = rule
        first = None
        for _ in range(NEWTON_STEPS):
            g, c, diag, curvature = self.grad_curv(off, w)
            norm = jnp.sqrt(seg(g * g))
            if first is None:
                first = norm
            rtol = jnp.clip(jnp.sqrt(norm / jnp.maximum(
                first if scale is None else scale, 1e-30)), *CG_RTOL)
            step = self.newton_direction(c, g, diag, rtol, curvature,
                                         cg_steps)
            big = self._max_by_entity(jnp.abs(step))
            step = step * jnp.minimum(1.0, STEP_CLIP / jnp.maximum(
                big, 1e-30))[ent]
            f = sum(self.each(
                lambda k, idx, val, y, t, ids: self._trial_losses(
                    idx, val, y, t, ids, off[k], w, step))) + jnp.stack([
                        0.5 * seg(self.lam * jnp.square(w - k * step))
                        for k in TRIALS])
            taken = jnp.zeros((self.E,), jnp.float32)
            for k, trial in enumerate(TRIALS[1:], 1):
                taken = jnp.where(f[k] <= f[0], trial, taken)
            w = w - taken[ent] * step
            moved = jnp.max(jnp.where((curvature >= DETERMINED)[ent],
                                      jnp.abs(taken[ent] * step), 0.0))
            if float(moved) < tol:
                break
        return w, curvature, first


def solve_fixed(block, off, w, scale):
    """``criteo_reference._FixedBlock``'s truncated Newton steps under
    another stopping rule: the solve ends when a step lowers the objective by
    less than ``OBJECTIVE_TOL`` of it. A site row's three app fields all
    hold their null value and conversely, so six of the heaviest columns
    span two directions and the rest of their span is flat but for lam: that
    file's rule (no coefficient moves by 1e-4) follows those directions for
    hundreds of passes that move the objective by 1e-7 of it (seed 5, 2M
    rows: 1.3e-5 after 70 passes, then 5.6e-6, 2.2e-6, 1.3e-6 at 41 passes
    a step), and what is compared of the fixed effect is the objective and
    coefficients that a sound program holds to a few per cent. Returns the
    new point, the objective and the gradient's norm at the start."""
    import jax.numpy as jnp
    f = start = block.value(off, w)
    g, c, diag = block.grad_curv(off, w)
    norm0 = float(jnp.linalg.norm(g))
    for _ in range(criteo_reference.NEWTON_STEPS):
        gn = float(jnp.linalg.norm(g))
        rtol = min(0.1, max(criteo_reference.CG_RTOL_MIN,
                            (gn / (scale or norm0)) ** 0.5))
        step = block.newton_direction(c, g, diag, rtol)
        t = 1.0
        while t > 1e-3:
            f_new = block.value(off, w - t * step)
            if f_new <= f:
                break
            t *= 0.5
        else:
            break
        w, lowered, f = w - t * step, f - f_new, f_new
        if lowered < OBJECTIVE_TOL * abs(f):
            break
        g, c, diag = block.grad_curv(off, w)
    return w, start, norm0


def train(data, mix: dict, settings: dict, sweeps: int, served: dict) -> dict:
    """Block coordinate descent over ``sweeps`` sweeps, the fixed effect then
    the table; what ``compare`` needs of it."""
    import jax
    import jax.numpy as jnp

    fixed, table = mix["update_sequence"]
    assert (mix["coordinates"][fixed]["type"], mix["coordinates"][table][
        "type"]) == ("fixed", "random") and not mix["locked_coordinates"]
    opts = settings["optimizers"]
    cap = settings.get("max_samples")
    train_rows = capped_training_rows(data.entity_ids, data.num_entities, cap)
    block = criteo_reference._FixedBlock(data, float(opts[fixed][
        "reg_weight"]))
    tab = _TableBlock(data, float(opts[table]["reg_weight"]), train_rows)
    y = jnp.asarray(data.response)
    n = y.shape[0]
    w, W = block.zeros, tab.zeros
    s_table = np.zeros((n,), np.float32)
    values, grad_norms, work, scale = [], [], [], None
    with jax.default_matmul_precision("highest"):
        for sweep in range(sweeps):
            t0, before = time.monotonic(), (block.passes, tab.passes)
            w, f, norm = solve_fixed(block, block.offsets(s_table), w,
                                     grad_norms[0] if grad_norms else None)
            values.append(f)
            grad_norms.append(norm if grad_norms
                              else criteo_reference.first_gradient_norm(data))
            s_fixed = block.margins(w)
            t1 = time.monotonic()
            if sweep < sweeps - 1:
                # the last sweep's table is solved below, once, at the
                # program's fixed effect
                W, _, first = tab.solve(tab.offsets(s_fixed), W, scale)
                scale = first if scale is None else scale
                s_table = tab.margins(W)
            work.append((block.passes - before[0], t1 - t0,
                         tab.passes - before[1], time.monotonic() - t1))
        print(f"reference: both blocks on {block.host}, the table over "
              f"{tab.d} crossed columns; per sweep (the fixed block's "
              "passes over the rows, seconds, the table's, seconds): "
              + ", ".join(f"({p}, {a:.1f}, {q}, {b:.1f})"
                          for p, a, q, b in work), file=sys.stderr, flush=True)

        # small.fixed, as criteo_reference.py reads it
        _, _, diag = block.grad_curv(block.offsets(s_table), w)
        diag, w = np.asarray(diag), np.asarray(w)
        w_prog = np.asarray(served[fixed], np.float32)

        def whole_loss(s):
            m = jnp.asarray(s)
            return float(jnp.sum(jnp.logaddexp(0.0, m) - y * m))

        swapped = jax.device_put(np.where(diag < DETERMINED, w_prog, w),
                                 block.host)
        small = {fixed: {
            "reference": whole_loss(s_fixed + s_table),
            "program": whole_loss(block.margins(swapped) + s_table),
            "loose": int(np.sum(diag < DETERMINED))}}
        # The table, at the program's own fixed effect: its block's
        # minimiser for those offsets, from the reference's last table.
        theirs = tab.offsets(block.margins(jax.device_put(w_prog,
                                                          block.host)))
        t2, before = time.monotonic(), tab.passes
        W, curv, _ = tab.solve(theirs, W, scale, LAST)
        print(f"reference: the table at the program's fixed effect, "
              f"{tab.passes - before} passes, {time.monotonic() - t2:.1f} s",
              file=sys.stderr, flush=True)
        served_table = np.asarray(served[table], np.float32)
        W_prog = tab.take(served_table)
        curv = np.asarray(curv)
        loose = curv < DETERMINED
        mask = train_rows * loose[data.entity_ids]
        small[table] = {
            "reference": tab.masked_loss(mask, theirs, W),
            "program": tab.masked_loss(mask, theirs, W_prog),
            "loose": int(loose.sum())}
        counts = np.bincount(data.entity_ids, minlength=data.num_entities)
        over = (counts > cap if cap is not None
                else np.zeros_like(counts, bool))
        mask = train_rows * over[data.entity_ids]
        capped = {"reference": tab.masked_loss(mask, theirs, W),
                  "program": tab.masked_loss(mask, theirs, W_prog),
                  "publishers": int(over.sum()), "rows": int(mask.sum())}
    block.pool.shutdown()
    tab.pool.shutdown()
    return {"small": small, "values": values, "grad_norms": grad_norms,
            "model": {fixed: w, table: np.asarray(W)},
            "program": {fixed: w_prog, table: np.asarray(W_prog)},
            "curvature": {fixed: diag,
                          table: curv[np.asarray(tab.ent)]},
            "capped": capped, "training_rows": int(train_rows.sum()),
            "offspace": float(np.max(np.abs(served_table.reshape(-1)[
                tab.offspace]), initial=0.0))}


def _gap(prog, ref, keep) -> float:
    return float(np.linalg.norm(prog[keep] - ref[keep])
                 / max(np.linalg.norm(ref[keep]), 1e-30))


def compare(ref: dict, ledger_rows, mix: dict) -> dict:
    """The numbers compared, without their limits; prints what each pair of
    numbers covers."""
    values, norms = dense_reference.program_readings(ledger_rows, mix)
    table = mix["update_sequence"][1]
    out = {}
    for k in (1, 2, 3):
        if k in values and k < len(ref["values"]):
            out[f"loss_{k}"] = abs(values[k] - ref["values"][k]) / abs(
                ref["values"][k])
    if 0 in norms:
        out["grad0"] = abs(norms[0] - ref["grad_norms"][0]) / ref[
            "grad_norms"][0]
    for cid, leaf in ref["model"].items():
        keep = ref["curvature"][cid] >= DETERMINED
        out[f"coef.{cid}"] = _gap(ref["program"][cid], leaf, keep)
        s = ref["small"][cid]
        out[f"small.{cid}"] = (abs(s["program"] - s["reference"])
                               / s["reference"] if s["loose"] else 0.0)
        print(f"coverage {cid}: coef compares {int(keep.sum())} of "
              f"{keep.size} coefficients; small covers {s['loose']} (loss "
              f"{s['program']:.6g} against the reference's "
              f"{s['reference']:.6g})", file=sys.stderr, flush=True)
    c = ref["capped"]
    out[f"capped.{table}"] = (abs(c["program"] - c["reference"])
                              / c["reference"] if c["publishers"] else 0.0)
    fitted = [r["rows_useful"] for r in ledger_rows
              if r.get("kind") == "re_fit_wave"
              and r.get("coordinate") == table
              and r.get("outer_iteration") == 0
              and r.get("rows_useful") is not None]
    if fitted:
        out[f"rows.{table}"] = float(abs(sum(fitted) - ref["training_rows"]))
    out[f"offspace.{table}"] = ref["offspace"]
    print(f"coverage {table}: capped covers the {c['rows']} training rows of "
          f"the {c['publishers']} publishers max_samples caps (loss "
          f"{c['program']:.6g} against the reference's "
          f"{c['reference']:.6g})", file=sys.stderr, flush=True)
    return out


def check(data, cell: dict, served: dict, ledger_rows, sweeps: int) -> dict:
    """name -> {"value", "limit"} for every number compared. A number the
    run could not read counts as over its limit."""
    ref = train(data, cell["mix"], cell["settings"], sweeps, served)
    got = compare(ref, ledger_rows, cell["mix"])
    out = {}
    for name, limit in cell["configuration"]["check"]["limits"].items():
        v = got.get(name, float("inf"))
        out[name] = {"value": v if np.isfinite(v) else 1e30, "limit": limit}
    return out
