"""The plain reference of the schema ``game_kdd12``, and the comparison that
decides ``correct`` there.

The model is GAME's with a Poisson response: a row's click count is Poisson
with mean ``exp(offset + sum_f v w[c_f] + x . W[id])``, the offset the log
of the row's impressions, and training is block coordinate descent: the
fixed block under ``lambda |w|_1`` on every coefficient, each advertiser's
block under ``lambda/2 |.|^2`` on every table column but the intercept (the
last). This file states the fixed block in whole-batch float32 ``jax.numpy``
over the rows as they are given, in ELL form, on the columns some row
touches only (a column no row touches has gradient 0 and stays exactly 0
under L1): margins by ``take``, the gradient by ``segment_sum``; no hot
block, no classes, no permuted space. It is minimised by an accelerated
proximal gradient method, not by the program's OWL-QN: a gradient step in
the metric of the Hessian's own majorizer, soft-thresholded, with momentum
that is dropped whenever the objective rises, and a step scale that is
raised until the quadratic model bounds the objective. No pseudo-gradient,
no orthant, no quasi-Newton pairs: the two share no solver idea. Each row
has one non-zero of value v in each of k fields, so x x' <= k diag(x^2) and
the Hessian X' diag(mu) X is bounded by k v^2 times the columns' sums of mu:
with v = 1/sqrt(k), by those sums themselves. The rows are held in a few
blocks on the host's CPU backend, a thread each (``criteo_reference._host``
says why). The advertisers' blocks are damped Newton steps on the Poisson
loss, ``reference.py``'s written out for another loss (that file knows two
losses and is not edited here). Nothing of the program is imported, and
nothing it made is taken except what is compared.

What is compared (``check``), each against ``check.limits`` of the
configuration's file:

- ``grad0``: the norm of the first gradient as the optimizer gets it (fixed
  effect, sweep 1, iteration 0, every coefficient 0). Under L1 that is the
  pseudo-gradient: the smooth gradient X'(mu - y), mu = impressions, shrunk
  towards 0 by lambda. Against the same in float64: relative gap of norms. It
  holds the offsets, ``exp``, the hot block, the cold classes, rows and
  weights to float32.
- ``loss_k``, k = 1..3: the fixed block's whole objective, L1 term included,
  after sweep k (the program's is the fixed effect's starting value in sweep
  k+1), relative gap to the reference's. The reference minimises each block;
  the program gives the fixed block its iteration cap, so this gap is the
  solver's slack, one-sided.
- ``coef.fixed``: ``|prog - ref| / |ref|`` over the determined columns, those
  whose data curvature in the reference (the sum of ``mu v^2`` over the
  column's rows) is at least ``DETERMINED``.
- ``small.fixed``: the summed loss over all rows of the reference's model
  with the program's coefficients in the columns that are not determined,
  against the reference's own: relative gap.
- ``zeros.fixed``: the columns that had to be exactly 0.0 in the program's
  model and are not: every column no row touches, and, where the program's
  count of non-zero coefficients lies outside the band ``check.nnz_band``
  times the reference's count, the columns it lies outside by. Limit 0.
- ``coef.<table>`` and ``small.<table>``: the table against its block's
  minimiser at the program's own fixed effect, as ``criteo_reference`` does
  and for its reason.
"""

from __future__ import annotations

import sys
import time

import numpy as np

import reference as dense_reference

DETERMINED = dense_reference.DETERMINED
DAMPING = dense_reference.DAMPING
STEP_CLIP = dense_reference.STEP_CLIP
NEWTON_STEPS = 30
BLOCKS = 8  # blocks of rows, and threads, of the fixed block
APG_STEPS = 100  # at most, in one solve of the fixed block
VALUE_TOL = 2e-7  # the solve stops when a step lowers the objective by less,
#                   relative: float32 sums of millions resolve no more
SCALE_MIN = 0.05  # the least step scale: the metric bounds the Hessian at 1
TABLE_TOL = 1e-4  # the table's steps stop under it: float32 sums of exp
#                   leave a determined entity's Newton step 2-4e-5 of noise


def _host():
    """The host's own CPU backend where the process has one beside the
    accelerator (``criteo_reference._host``: a v5e runs ``take`` and
    ``segment_sum`` ten times slower than the host's cores)."""
    import jax
    try:
        return jax.devices("cpu")[0]
    except RuntimeError:
        return jax.devices()[0]


def _fixed_fns(columns: int, v: float):
    """The fixed block's jitted pieces, each over one block of rows.
    ``idx`` is field-major, ``(fields, rows)``, over the touched columns."""
    import jax
    import jax.numpy as jnp

    def margins(idx, w):
        return v * jnp.sum(jnp.take(w, idx), axis=0)

    @jax.jit
    def value(idx, y, off, w):
        m = margins(idx, w) + off
        return jnp.sum(jnp.exp(m) - y * m)

    @jax.jit
    def value_grad_curv(idx, y, off, w):
        """The block's summed loss, its part of the gradient, and its part
        of the columns' sums of mu (the metric; times v^2, the Hessian's
        diagonal)."""
        m = margins(idx, w) + off
        mu = jnp.exp(m)
        rows = jnp.stack([mu - y, mu], axis=1)  # (rows, 2)
        sums = jax.ops.segment_sum(
            jnp.broadcast_to(rows[None], idx.shape + (2,)).reshape(-1, 2),
            idx.reshape(-1), num_segments=columns)
        return jnp.sum(mu - y * m), v * sums[:, 0], sums[:, 1]

    return value, value_grad_curv, jax.jit(margins)


class _FixedBlock:
    """sum(exp(m) - y m) + lam |w|_1 over all rows, m = X w + off, on the
    touched columns, held as ``BLOCKS`` blocks of rows on the host device
    and evaluated by as many threads."""

    def __init__(self, data, lam: float):
        import jax
        import jax.numpy as jnp
        from concurrent.futures import ThreadPoolExecutor

        n, fields = data.indices.shape
        self.lam, self.d = lam, int(data.num_features)
        self.v = float(data.values[0, 0])
        assert np.all(data.values == data.values[0, 0])
        present = np.bincount(data.indices.reshape(-1),
                              minlength=self.d) > 0
        self.touched = np.flatnonzero(present)
        remap = np.cumsum(present, dtype=np.int64) - 1
        idx = remap[data.indices].astype(np.int32)
        self.host = host = _host()
        self._value, self._vgc, self._margins = _fixed_fns(
            self.touched.size, self.v)
        self.bounds = [(int(a), int(b)) for a, b in zip(
            np.linspace(0, n, BLOCKS + 1)[:-1],
            np.linspace(0, n, BLOCKS + 1)[1:])]

        def put(a):
            return jax.device_put(np.ascontiguousarray(a), host)

        self.blocks = [(put(idx[a:b].T), put(data.response[a:b]))
                       for a, b in self.bounds]
        self.pool = ThreadPoolExecutor(BLOCKS)
        self.passes = 0
        self.zeros = jax.device_put(
            jnp.zeros((self.touched.size,), jnp.float32), host)

    def each(self, fn):
        """``fn(k, idx, y)`` of every block, a thread each."""
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

    def smooth(self, off, w) -> float:
        return float(sum(float(p) for p in self.each(
            lambda k, idx, y: self._value(idx, y, off[k], w))))

    def value_grad_curv(self, off, w):
        parts = self.each(lambda k, idx, y: self._vgc(idx, y, off[k], w))
        return (float(sum(float(p[0]) for p in parts)),
                sum(p[1] for p in parts), sum(p[2] for p in parts))

    def l1(self, w) -> float:
        import jax.numpy as jnp
        return self.lam * float(jnp.sum(jnp.abs(w)))

    def margins(self, w) -> np.ndarray:
        return np.concatenate([np.asarray(m) for m in self.each(
            lambda k, idx, y: self._margins(idx, w))])

    def everywhere(self, w) -> np.ndarray:
        """The touched columns' coefficients in all ``num_features``."""
        out = np.zeros(self.d, np.float32)
        out[self.touched] = np.asarray(w)
        return out

    def solve(self, off, w):
        """The block's minimiser from ``w``; the whole objective at the
        start. Accelerated proximal gradient steps in the metric ``scale``
        times the columns' sums of mu at the point the gradient is taken."""
        import jax.numpy as jnp
        lam = self.lam
        f, _, _ = self.value_grad_curv(off, w)
        start = total = f + self.l1(w)
        lead, t, scale = w, 1.0, 1.0
        for _ in range(APG_STEPS):
            f_lead, g, metric = self.value_grad_curv(off, lead)
            while True:
                m = scale * metric + 1e-12
                new = lead - g / m
                new = jnp.sign(new) * jnp.maximum(jnp.abs(new) - lam / m, 0.0)
                f_new = self.smooth(off, new)
                step = new - lead
                model = f_lead + float(g @ step) + 0.5 * float(
                    (m * step) @ step)
                if np.isfinite(f_new) and f_new <= model + 1e-6 * abs(model):
                    break
                scale *= 2.0
            total_new = f_new + self.l1(new)
            if total_new > total and lead is not w:
                lead, t = w, 1.0  # the momentum overshot: a plain step
                continue
            gained = total - total_new
            t_new = 0.5 * (1.0 + (1.0 + 4.0 * t * t) ** 0.5)
            lead = new + ((t - 1.0) / t_new) * (new - w)
            w, t, total = new, t_new, total_new
            scale = max(SCALE_MIN, 0.7 * scale)
            if gained < VALUE_TOL * abs(total):
                break
        return w, start


def first_gradient_norm(data, lam: float) -> float:
    """The norm of the pseudo-gradient where every coefficient is 0: the
    smooth gradient X'(impressions - clicks), summed in float64, with each
    component shrunk towards 0 by lambda (what OWL-QN searches along at 0).
    ``criteo_reference.first_gradient_norm`` says why float64."""
    r = np.exp(data.offsets.astype(np.float64)) - data.response.astype(
        np.float64)
    g = np.bincount(
        data.indices.reshape(-1), minlength=int(data.num_features),
        weights=(data.values.astype(np.float64) * r[:, None]).reshape(-1))
    return float(np.linalg.norm(np.sign(g) * np.maximum(np.abs(g) - lam, 0)))


def _table_fns(lam: float):
    """``reference._fns``'s pieces for one table under the Poisson loss:
    every advertiser's damped Newton step at once, with step halving."""
    import jax
    import jax.numpy as jnp

    def loss(m, y):
        return jnp.exp(m) - y * m

    def blocks(n):
        rows = dense_reference.BLOCK_ROWS
        return [(a, min(a + rows, n)) for a in range(0, n, rows)]

    def entity_sums(cols_of, ids, E, n):
        total = 0.0
        for a, b in blocks(n):
            total = total + jax.ops.segment_sum(cols_of(a, b).T, ids[a:b],
                                                num_segments=E)
        return total.T  # (k, E)

    def spd_solve(h, g, d):
        """x with H x = g for every entity at once: a Cholesky
        factorisation written out over (entities,) vectors."""
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
        """One damped Newton step of every entity's block; the new table,
        the largest step taken by a determined entity, and the intercept
        curvature. A step is the longest of 1, 1/2 .. 1/16 of the Newton
        step that is not uphill (``exp`` makes a long one overflow)."""
        d, E = Wt.shape
        n = y.shape[0]
        mask = jnp.ones((d, 1), jnp.float32).at[d - 1].set(0.0)
        pairs = [(i, j) for i in range(d) for j in range(i, d)]

        def grad_and_hessian(a, b):
            x = Xt[:, a:b]
            mu = jnp.exp(jnp.sum(x * Wt[:, ids[a:b]], axis=0) + off[a:b])
            r = train[a:b] * (mu - y[a:b])
            c = train[a:b] * mu
            return jnp.concatenate(
                [x * r, jnp.stack([c * x[i] * x[j] for i, j in pairs])])

        sums = entity_sums(grad_and_hessian, ids, E, n)
        g = sums[:d] + lam * mask * Wt
        h = [[None] * d for _ in range(d)]
        for k, (i, j) in enumerate(pairs):
            h[i][j] = sums[d + k] + (lam * mask[i, 0] + DAMPING
                                     if i == j else 0.0)
        curvature = sums[d + len(pairs) - 1]  # the intercept's own mu sum
        step = spd_solve(h, list(g), d)
        big = jnp.max(jnp.abs(step), axis=0, keepdims=True)
        step = step * jnp.minimum(1.0, STEP_CLIP / jnp.maximum(big, 1e-30))
        trials = (0.0, 0.0625, 0.125, 0.25, 0.5, 1.0)

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
        for k, t in enumerate(trials[1:], 1):
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

    return entity_step, entity_score, masked_loss


def train(data, mix: dict, settings: dict, sweeps: int, served: dict) -> dict:
    """Block coordinate descent over ``sweeps`` sweeps, the fixed effect then
    the table. Returns the trained leaves, the objective at the start of
    each fixed update and the first gradient's norm, both curvatures, the
    count of non-zero coefficients after each sweep, and the losses
    ``small.*`` compares."""
    import jax
    import jax.numpy as jnp

    fixed, table = mix["update_sequence"]
    assert (mix["coordinates"][fixed]["type"], mix["coordinates"][table][
        "type"]) == ("fixed", "random") and not mix["locked_coordinates"]
    opts = settings["optimizers"]
    assert (opts[fixed]["regularization"], opts[table]["regularization"]
            ) == ("L1", "L2")
    lam = float(opts[fixed]["reg_weight"])
    entity_step, entity_score, masked_loss = _table_fns(
        float(opts[table]["reg_weight"]))
    block = _FixedBlock(data, lam)
    y = jnp.asarray(data.response)
    base = jnp.asarray(data.offsets)
    n = y.shape[0]
    Xt = jnp.asarray(np.ascontiguousarray(data.table.T))
    ids = jnp.asarray(data.entity_ids)
    rows = jnp.asarray(dense_reference.capped_training_rows(
        data.entity_ids, data.num_entities, settings.get("max_samples")))
    w = block.zeros
    Wt = jnp.zeros((Xt.shape[0], data.num_entities), jnp.float32)
    s_fixed = jnp.zeros((n,), jnp.float32)
    s_table = jnp.zeros((n,), jnp.float32)
    values, nnz, work = [], [], []

    def table_solve(Wt, off):
        for steps in range(1, NEWTON_STEPS + 1):
            Wt, moved, curv = entity_step(Xt, y, off, rows, ids, Wt)
            if float(moved) < TABLE_TOL:
                break
        return Wt, curv, steps

    with jax.default_matmul_precision("highest"):
        for _ in range(sweeps):
            t0, before = time.monotonic(), block.passes
            w, f = block.solve(block.offsets(base + s_table), w)
            values.append(f)
            nnz.append(int(jnp.sum(w != 0.0)))
            s_fixed = jnp.asarray(block.margins(w))
            t1 = time.monotonic()
            Wt, _, steps = table_solve(Wt, base + s_fixed)
            s_table = entity_score(Xt, ids, Wt)
            work.append((block.passes - before, t1 - t0, steps,
                         time.monotonic() - t1))
        print(f"reference: the fixed block on {block.host}, "
              f"{block.touched.size} touched columns; per sweep (its passes "
              "over the rows, seconds, table steps, seconds): "
              + ", ".join(f"({p}, {a:.1f}, {s}, {b:.1f})"
                          for p, a, s, b in work)
              + f"; non-zero after each sweep {nnz}",
              file=sys.stderr, flush=True)

        # what small.* compares
        _, _, mu_sums = block.value_grad_curv(
            block.offsets(base + s_table), w)
        curv_fixed = np.zeros(block.d, np.float32)
        curv_fixed[block.touched] = np.asarray(mu_sums) * block.v ** 2
        w_all = block.everywhere(w)
        w_prog = np.asarray(served[fixed], np.float32)
        ones = jnp.ones((n,), jnp.float32)
        loose = curv_fixed[block.touched] < DETERMINED
        swapped = jax.device_put(np.where(
            loose, w_prog[block.touched], np.asarray(w)), block.host)
        small = {fixed: {
            "reference": float(masked_loss(base + s_fixed + s_table, y,
                                           ones)),
            "program": float(masked_loss(
                base + jnp.asarray(block.margins(swapped)) + s_table, y,
                ones)),
            "loose": int(loose.sum())}}
        # The table, at the program's own fixed effect: its block's
        # minimiser for those offsets, from the reference's last table.
        s_theirs = base + jnp.asarray(block.margins(jax.device_put(
            w_prog[block.touched], block.host)))
        Wt, curv, _ = table_solve(Wt, s_theirs)
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
    untouched = np.ones(block.d, bool)
    untouched[block.touched] = False
    return {"small": small, "values": values, "nnz": nnz,
            "grad_norms": [first_gradient_norm(data, lam)],
            "untouched": untouched,
            "model": {fixed: w_all, table: np.asarray(Wt.T)},
            "curvature": {fixed: curv_fixed, table: np.asarray(curv)}}


def compare(ref: dict, served: dict, ledger_rows, mix: dict,
            nnz_band) -> dict:
    """The numbers compared, without their limits; prints what each pair of
    numbers covers."""
    values, norms = dense_reference.program_readings(ledger_rows, mix)
    fixed = mix["update_sequence"][0]
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
    prog = np.asarray(served[fixed])
    stray = int(np.count_nonzero(prog[ref["untouched"]]))
    mine, theirs = int(np.count_nonzero(prog)), ref["nnz"][-1]
    lo, hi = (float(b) * theirs for b in nnz_band)
    outside = max(0.0, lo - mine, mine - hi)
    out[f"zeros.{fixed}"] = float(stray + np.ceil(outside))
    print(f"coverage zeros.{fixed}: {stray} of {int(ref['untouched'].sum())} "
          f"untouched columns are not 0.0; {mine} non-zero coefficients "
          f"against the reference's {theirs} (band {lo:.0f} to {hi:.0f})",
          file=sys.stderr, flush=True)
    return out


def check(data, cell: dict, served: dict, ledger_rows, sweeps: int) -> dict:
    """name -> {"value", "limit"} for every number compared. A number the
    run could not read counts as over its limit."""
    ref = train(data, cell["mix"], cell["settings"], sweeps, served)
    conf = cell["configuration"]["check"]
    got = compare(ref, served, ledger_rows, cell["mix"], conf["nnz_band"])
    out = {}
    for name, limit in conf["limits"].items():
        v = got.get(name, float("inf"))
        out[name] = {"value": v if np.isfinite(v) else 1e30, "limit": limit}
    return out
