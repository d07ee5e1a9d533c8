"""The plain reference of the schema ``game_criteo``, and the comparison that
decides ``correct`` there.

The model is GAME's (see ``reference.py``): a row's score is a fixed effect
over its hashed fields, ``sum_f v_f w[c_f]``, plus one random effect,
``x . W[id]``, and training is block coordinate descent under
``lambda/2 |.|^2`` (every fixed coefficient; every table column but the
intercept, the last). This file states the fixed block in whole-batch float32
``jax.numpy`` over the rows as they are given, in ELL form: margins by
``take``, the gradient and the Hessian's products by ``segment_sum``; no hot
block, no classes, no permuted space, no L-BFGS. It is minimised by a
truncated Newton method: conjugate gradients on Hessian-vector products,
preconditioned by the Hessian's diagonal and one coarse term, then a step
that is halved until the objective does not rise. The rows are held in a few
blocks on the host's CPU backend, one thread each (``_host`` says why). The table's blocks are ``reference.py``'s damped
Newton steps, used as they stand. Nothing of the program is imported, and
nothing it made is taken except what is compared.

What is compared (``check``), each against ``check.limits`` of the
configuration's file:

- ``grad0``: the norm of the first gradient as the optimizer gets it (fixed
  effect, sweep 1, iteration 0, every coefficient 0): relative gap of norms.
  It holds hot block, cold classes, rows and weights to float32.
- ``loss_k``, k = 1..3: the whole objective after sweep k (the program's is
  the fixed effect's starting value in sweep k+1), relative gap to the
  reference's. The reference minimises each block; the program gives the
  fixed block its iteration cap, which at 2**20 columns leaves it short of
  the block's minimum in every sweep, so this gap is the solver's slack,
  one-sided and alike from seed to seed: its limit says how short a sound
  program may stop, and a program that trains on other rows or another
  objective is far outside it.
- ``coef.fixed``: ``|prog - ref| / |ref|`` over the determined columns, those
  whose data curvature in the reference (the sum of ``l'' v^2`` over the
  column's rows) is at least ``DETERMINED``; the same slack, in the
  coefficients. The others lean on the L2 term and are compared by what
  they do:
- ``small.fixed``: the summed loss over all rows of the reference's model
  with the program's coefficients in the columns that are not determined,
  against the reference's own: relative gap.
- ``coef.<table>`` and ``small.<table>``: the table against its block's
  minimiser **at the program's own fixed effect** (the reference's damped
  Newton steps on the offsets ``X w_program``), determined entities by
  coefficient, the others by the loss their rows reach, as ``reference.py``
  splits them. The table's solves stop by their own rule, so this pair does
  not depend on the path: it holds the table's solver, its features and the
  fixed effect's scores to float32, where the tables of two fixed effects
  that stand a fifth apart would only repeat that fifth.
"""

from __future__ import annotations

import sys
import time

import numpy as np

import reference as dense_reference

DETERMINED = dense_reference.DETERMINED
NEWTON_STEPS = 30
CG_STEPS = 40  # at most, in one Newton step
CG_RTOL_MIN = 1e-3  # the tightest residual a Newton step's system is solved to
BLOCKS = 8  # blocks of rows, and threads, of the fixed block
STEP_TOL = 1e-4  # the solve stops when no coefficient moves by more


def _host():
    """The device the fixed block runs on: the host's own CPU backend where
    the process has one beside the accelerator. A v5e runs ``take`` and
    ``segment_sum`` at 0.14 G elements/s, 2.1 s a pass over 2.5M rows, and
    the host's cores, a block of rows each, the same pass in 0.2 s (my chip
    run, PR 29): the fixed block's ~150 passes fit the run only there."""
    import jax
    try:
        return jax.devices("cpu")[0]
    except RuntimeError:
        return jax.devices()[0]


def _fixed_fns(lam: float, d: int, fields: int):
    """The fixed block's jitted pieces, each over one block of rows.
    ``idx`` and ``val`` are field-major, ``(fields, rows)``."""
    import jax
    import jax.numpy as jnp

    def margins(idx, val, w):
        return jnp.sum(val * jnp.take(w, idx), axis=0)

    def rowterm(idx, val, r):
        return jax.ops.segment_sum((val * r[None, :]).reshape(-1),
                                   idx.reshape(-1), num_segments=d)

    @jax.jit
    def value(idx, val, y, off, w):
        m = margins(idx, val, w) + off
        return jnp.sum(jnp.logaddexp(0.0, m) - y * m)

    @jax.jit
    def grad_curv(idx, val, y, off, w):
        """The block's part of the gradient, its rows' curvatures l'', and
        its part of the Hessian's diagonal."""
        p = jax.nn.sigmoid(margins(idx, val, w) + off)
        c = p * (1.0 - p)
        return rowterm(idx, val, p - y), c, rowterm(idx, val * val, c)

    @jax.jit
    def hvp(idx, val, c, v):
        return rowterm(idx, val, c * margins(idx, val, v))

    @jax.jit
    def precondition(r, diag, field_of, curv_sum, v2):
        """The Hessian's diagonal plus one coarse term. A row has one
        non-zero of value v in every field, so X maps each field's indicator
        vector z_f to the same v 1: the data are flat along z_f - z_g, where
        only lam holds, and the diagonal alone would leave those 38
        directions to the iteration. On the span of the z_f (``field_of``:
        the field most of a column's entries come from) the Hessian is
        v^2 sum(l'') 1 1' + lam diag(columns of f), inverted in closed form
        (Sherman-Morrison). Any positive definite preconditioner gives the
        same solution; this one gives it in fewer passes."""
        there = diag > 0
        cols = jax.ops.segment_sum(jnp.where(there, 1.0, 0.0), field_of,
                                   num_segments=fields)
        a = 1.0 / (lam * jnp.maximum(cols, 1.0))
        b = curv_sum * v2
        t = jax.ops.segment_sum(jnp.where(there, r, 0.0), field_of,
                                num_segments=fields)
        coarse = a * t - a * (b * jnp.sum(a * t) / (1.0 + b * jnp.sum(a)))
        return r / (diag + lam) + jnp.where(there, coarse[field_of], 0.0)

    return value, grad_curv, hvp, jax.jit(margins), precondition


class _FixedBlock:
    """sum loss(X w + off) + lam/2 |w|^2 over all rows, held as ``BLOCKS``
    blocks of rows on the host device and evaluated by as many threads."""

    def __init__(self, data, lam: float):
        import jax
        import jax.numpy as jnp
        from concurrent.futures import ThreadPoolExecutor

        self.lam, self.d = lam, int(data.num_features)
        n, fields = data.indices.shape
        self.host = host = _host()
        (self._value, self._grad_curv, self._hvp, self._margins,
         self._precondition) = _fixed_fns(lam, self.d, fields)
        self.bounds = [(int(a), int(b)) for a, b in zip(
            np.linspace(0, n, BLOCKS + 1)[:-1],
            np.linspace(0, n, BLOCKS + 1)[1:])]

        def put(a):
            return jax.device_put(np.ascontiguousarray(a), host)

        self.blocks = [(put(data.indices[a:b].T), put(data.values[a:b].T),
                        put(data.response[a:b])) for a, b in self.bounds]
        self.pool = ThreadPoolExecutor(BLOCKS)
        # the field most of a column's entries come from (the
        # preconditioner's)
        per_field = np.bincount(
            (data.indices.astype(np.int64) * fields + np.arange(fields)
             ).reshape(-1), minlength=self.d * fields).reshape(self.d, fields)
        self.field_of = put(per_field.argmax(axis=1).astype(np.int32))
        self.v2 = float(np.mean(np.square(data.values[:1000],
                                          dtype=np.float64)))
        self.passes = 0
        self.zeros = jax.device_put(jnp.zeros((self.d,), jnp.float32), host)

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
        off = np.asarray(off, np.float32)
        return [jax.device_put(off[a:b], self.host) for a, b in self.bounds]

    def value(self, off, w):
        parts = self.each(lambda k, idx, val, y: self._value(
            idx, val, y, off[k], w))
        return float(sum(float(p) for p in parts)
                     + 0.5 * self.lam * float(w @ w))

    def grad_curv(self, off, w):
        parts = self.each(lambda k, idx, val, y: self._grad_curv(
            idx, val, y, off[k], w))
        g = sum(p[0] for p in parts) + self.lam * w
        return g, [p[1] for p in parts], sum(p[2] for p in parts)

    def margins(self, w) -> np.ndarray:
        return np.concatenate([np.asarray(m) for m in self.each(
            lambda k, idx, val, y: self._margins(idx, val, w))])

    def newton_direction(self, c, g, diag, rtol):
        """H s = g by preconditioned conjugate gradients, H = X' C X + lam,
        to a residual of ``rtol`` |g|; the passes it took."""
        import jax.numpy as jnp
        curv_sum = sum(float(jnp.sum(ck)) for ck in c)

        def pre(r):
            return self._precondition(r, diag, self.field_of, curv_sum,
                                      self.v2)

        x, r = self.zeros, g
        z = pre(r)
        p, rz = z, float(r @ z)
        stop = (rtol * float(jnp.linalg.norm(g))) ** 2
        for k in range(CG_STEPS):
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
        residual min(0.1, sqrt(|g| / scale)) |g| (Eisenstat and Walker), so
        the steps far from the minimiser are cheap and the last ones tight.
        """
        import jax.numpy as jnp
        f = start = self.value(off, w)
        g, c, diag = self.grad_curv(off, w)
        norm0 = float(jnp.linalg.norm(g))
        for _ in range(NEWTON_STEPS):
            gn = float(jnp.linalg.norm(g))
            rtol = min(0.1, max(CG_RTOL_MIN, (gn / (scale or norm0)) ** 0.5))
            step = self.newton_direction(c, g, diag, rtol)
            t = 1.0
            while t > 1e-3:
                f_new = self.value(off, w - t * step)
                if f_new <= f:
                    break
                t *= 0.5
            else:
                break
            w, f = w - t * step, f_new
            if float(jnp.max(jnp.abs(t * step))) < STEP_TOL:
                break
            g, c, diag = self.grad_curv(off, w)
        return w, start, norm0


def first_gradient_norm(data) -> float:
    """|X' (1/2 - y)|, the gradient where every coefficient is 0 and there
    are no offsets, summed in float64. The blocks' float32 ``segment_sum``
    adds a column's terms one after another, and here they are all +-v/2
    with v = 1/sqrt(39): once a column's partial sum passes 128 each term is
    rounded to the sum's grid, where v/2 reads 1.75e-4 too large (its next
    bits are a run of ones), so the float32 norm comes out 1.46e-4 high at
    2M rows, which the chip's own tree-shaped sums do not (my chip runs, PR
    29: seven seeds, 1.38-1.59e-4). That is as much as bf16 storage of v
    moves the same number, which this number exists to read. The solver's
    own passes keep float32: a gradient 1e-4 off moves the block's minimum
    by 1e-4, far under what is compared."""
    r = 0.5 - data.response.astype(np.float64)
    g = np.bincount(
        data.indices.reshape(-1), minlength=int(data.num_features),
        weights=(data.values.astype(np.float64) * r[:, None]).reshape(-1))
    return float(np.linalg.norm(g))


def train(data, mix: dict, settings: dict, sweeps: int, served: dict) -> dict:
    """Block coordinate descent over ``sweeps`` sweeps, the fixed effect then
    the table. Returns the trained leaves, the objective and the norm of the
    gradient at the start of each fixed update, both curvatures, and the
    losses ``small.*`` compares."""
    import jax
    import jax.numpy as jnp

    lam = float(settings["optimizer"]["reg_weight"])
    _, _, entity_step, entity_score, masked_loss = dense_reference._fns(
        "logistic", lam)
    fixed, table = mix["update_sequence"]
    assert (mix["coordinates"][fixed]["type"], mix["coordinates"][table][
        "type"]) == ("fixed", "random") and not mix["locked_coordinates"]
    block = _FixedBlock(data, lam)
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
        print(f"reference: the fixed block on {block.host}; per sweep (its "
              "passes over the rows, seconds, table steps, seconds): "
              + ", ".join(f"({p}, {a:.1f}, {s}, {b:.1f})"
                          for p, a, s, b in work), file=sys.stderr, flush=True)

        # what small.* compares
        _, _, diag = block.grad_curv(block.offsets(s_table), w)
        diag = np.asarray(diag)
        w = np.asarray(w)
        w_prog = np.asarray(served[fixed], np.float32)
        ones = jnp.ones((n,), jnp.float32)
        swapped = jax.device_put(np.where(diag < DETERMINED, w_prog, w),
                                 block.host)
        small = {fixed: {
            "reference": float(masked_loss(s_fixed + s_table, y, ones)),
            "program": float(masked_loss(
                jnp.asarray(block.margins(swapped)) + s_table, y, ones)),
            "loose": int(np.sum(diag < DETERMINED))}}
        # The table, at the program's own fixed effect (the module
        # docstring says why): its block's minimiser for those offsets,
        # from the reference's last table.
        s_theirs = jnp.asarray(block.margins(jax.device_put(w_prog,
                                                            block.host)))
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
    return {"small": small, "values": values, "grad_norms": grad_norms,
            "model": {fixed: w, table: np.asarray(Wt.T)},
            "curvature": {fixed: diag, table: np.asarray(curv)}}


def compare(ref: dict, served: dict, ledger_rows, mix: dict) -> dict:
    """The numbers compared, without their limits; prints what each pair of
    numbers covers."""
    values, norms = dense_reference.program_readings(ledger_rows, mix)
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
        prog = np.asarray(served[cid], np.float32)
        out[f"coef.{cid}"] = float(
            np.linalg.norm(prog[keep] - leaf[keep])
            / max(np.linalg.norm(leaf[keep]), 1e-30))
        s = ref["small"][cid]
        out[f"small.{cid}"] = (abs(s["program"] - s["reference"])
                               / s["reference"] if s["loose"] else 0.0)
        print(f"coverage {cid}: coef compares {int(keep.sum())} of "
              f"{keep.size}; small covers {s['loose']} (loss "
              f"{s['program']:.6g} against the reference's "
              f"{s['reference']:.6g})", file=sys.stderr, flush=True)
    return out


def check(data, cell: dict, served: dict, ledger_rows, sweeps: int) -> dict:
    """name -> {"value", "limit"} for every number compared. A number the
    run could not read counts as over its limit."""
    ref = train(data, cell["mix"], cell["settings"], sweeps, served)
    got = compare(ref, served, ledger_rows, cell["mix"])
    out = {}
    for name, limit in cell["configuration"]["check"]["limits"].items():
        v = got.get(name, float("inf"))
        out[name] = {"value": v if np.isfinite(v) else 1e30, "limit": limit}
    return out
