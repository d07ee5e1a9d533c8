"""L-BFGS and OWL-QN as jit/vmap-compatible ``lax.while_loop`` programs.

Reference parity: photon-lib ``optimization/LBFGS.scala`` (wraps
``breeze.optimize.LBFGS``, m=10 history, line search) and ``OWLQN.scala``
(wraps ``breeze.optimize.OWLQN``: L1 via orthant-wise QN with per-coordinate
L1 weights, intercept excluded).

TPU-first design (SURVEY.md §7 step 2): instead of wrapping a host-side
optimization library, the whole optimizer is a single compiled state machine:

- fixed-shape (m, d) history buffers kept in AGE order (slot 0 the newest
  pair, slot a the pair of age a; an accepted pair shifts the rest down by
  one and the oldest falls off) and a two-loop recursion unrolled over the
  static age — no dynamic index anywhere in the state machine. A circular
  buffer would need a ``head``, and under vmap the head is per lane (lanes
  accept and reject pairs on their own), so every ``buf[head - j]`` lowers
  to a gather and every ``buf.at[head].set`` to a scatter with one index per
  lane, which the TPU walks lane by lane: 56% of a GLMix sweep (PERF.md §6,
  PR 27);
- strong-Wolfe line search (Breeze ``StrongWolfeLineSearch`` parity) as a
  bounded bisection-with-expansion inner ``while_loop`` — each trial costs
  one fused objective evaluation = one psum when the objective is
  distributed; OWL-QN uses backtracking Armijo on the projected point
  (orthant projection makes the Wolfe curvature condition ill-defined);
- every state update is masked by the per-lane ``converged`` flag so the
  SAME machine runs vmapped over thousands of padded per-entity problems
  (the random-effect regime, reference ``SingleNodeOptimizationProblem``)
  with lanes freezing as they individually converge;
- OWL-QN is the same machine with pseudo-gradients, orthant projection of
  the direction and the post-step point, and the L1 term added to the
  line-search objective.

- where a pass over the data is what an evaluation costs and the objective
  meets the data through margins alone (a GLM), the caller hands over a
  ``LineOracle``: a line search then makes one margins pass for the
  direction and one gradient pass at the point it accepts, and its trials
  none, so an iteration costs the same whatever the search needed. Two
  callers do: the resident sparse fixed effect
  (``parallel/sparse_problem._hybrid_line``) and the vmapped bucket solves
  of a random-effect table (``optim/problem.make_line_oracle``), where a
  wave pays its slowest lane's trials at every iteration. OWL-QN's
  trial points are projected onto an orthant and leave the line, so its
  oracle is a ``ValueOracle``: a trial makes the one pass its value needs,
  and the gradient's pass is made once, at the point accepted;
- where one ``(m, d)`` history buffer is ``_RING_BYTES`` or more (a lane of
  a vmapped solve never is), the solve runs on vectors folded to
  ``(rows, 1024)`` and keeps the history as a ring written in place: the
  device tiles an ``(m, d)`` array's m up to 16 rows, and the age-ordered
  push rebuilds it, so at 54.7M columns that form asks for 18 GB and the
  compiler refuses it (PERF.md section 6, PR 33).

OWL-QN follows Andrew & Gao (2007), as Breeze's implementation does.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

from photon_ml_tpu.optim.common import (OptResult, OptimizerConfig,
                                        ValueAndGrad, check_convergence,
                                        masked_update)

Array = jax.Array

_EPS = 1e-10


@dataclasses.dataclass(frozen=True)
class LineOracle:
    """A smooth objective f(w) = h(z(w)) + reg(w) with margins z affine in w,
    taken apart so that a line search crosses the data twice however many
    trials it makes: z(w + αd) = z(w) + α·z'(d).

    ``start(w)``            → (f, g, carry): the first evaluation; ``carry``
                              is whatever of it later lines reuse (the margins)
    ``along(carry, w, d)``  → ray: one pass, the direction's margins
    ``trial(ray, α)``       → (f, dφ/dα) at w + αd: no pass over the data
    ``accept(ray, α)``      → (f, g, carry) at w + αd: one gradient pass
    """

    start: Callable[[Array], tuple[Array, Array, Any]]
    along: Callable[[Any, Array, Array], Any]
    trial: Callable[[Any, Array], tuple[Array, Array]]
    accept: Callable[[Any, Array], tuple[Array, Array, Any]]


@dataclasses.dataclass(frozen=True)
class ValueOracle:
    """The same smooth objective for OWL-QN, whose trial points π(w + αd)
    are projected onto an orthant: not a straight line, so each trial needs
    its own margins, but only the point accepted needs a gradient.

    ``start(w)``          → (f, g, carry): the first evaluation, two passes;
                            ``carry`` is what ``accept`` reuses (the margins)
    ``trial(w)``          → (f, carry) at w: one pass
    ``accept(w, carry)``  → g at w, from its trial's carry: one pass
    """

    start: Callable[[Array], tuple[Array, Array, Any]]
    trial: Callable[[Array], tuple[Array, Any]]
    accept: Callable[[Array, Any], Array]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class _LBFGSState:
    w: Array
    f: Array
    g: Array  # gradient of the SMOOTH part
    s_hist: Array  # (m, d)
    y_hist: Array  # (m, d)
    rho: Array  # (m,)
    count: Array  # int32: number of valid pairs (slots 0 … count−1)
    it: Array  # int32
    evals: Array  # int32: value_and_grad calls so far, trials included
    #               (under a LineOracle: pairs of passes over the data)
    trials: Array  # int32: line-search trials so far, whatever each cost
    converged: Array  # bool
    failed: Array  # bool: line search stalled
    g0_norm: Array
    value_history: Array
    grad_norm_history: Array
    carry: Any = None  # an oracle's, at w (no leaf without one)
    head: Any = None  # int32, ring history only: the slot written next
    # OWL-QN only, int32, −1 past the end: a line search's trials and the
    # non-zero coefficients of the iterate it ended on
    trials_history: Any = None
    nnz_history: Any = None


# One history buffer of this many bytes or more is kept as a ring of folded
# rows (module docstring); 2**28 is d = 6.7M at m = 10, over any lane of a
# vmapped solve and any cell's d but the one that needs it.
_RING_BYTES = 1 << 28
_FOLD = (8, 1024)  # a folded vector is whole (8, 128) tiles: rows, lanes


def _fold(x: Array) -> Array:
    """A (d,) vector as (rows, 1024), zero-padded to whole tiles. Pad
    coordinates have gradient 0 and L1 weight 0 and stay 0."""
    rows, lanes = _FOLD
    r = -(-x.shape[0] // (rows * lanes)) * rows
    return jnp.pad(x, (0, r * lanes - x.shape[0])).reshape(r, lanes)


def _dot(a: Array, b: Array) -> Array:
    return jnp.dot(a, b) if a.ndim == 1 else jnp.sum(a * b)


def _two_loop(g, s_hist, y_hist, rho, count, head=None):
    """Two-loop recursion: returns d ≈ H⁻¹ g (descent dir is −d).

    The history is in age order (slot 0 newest) and ``count`` only masks,
    so every index is a Python int and the 2m steps unroll into slices.
    A ``fori_loop`` over the (unbatched) age gives the same numbers; on the
    chip it compiled no sooner, loaded 5 s later and ran the sweep 1%
    faster (PERF.md §6, PR 27), so the plain form stays.

    With ``head`` the two buffers are rings (the pair of age a in slot
    head − 1 − a): the one dynamic index is unbatched there, a slice.
    """
    m = s_hist.shape[0]
    valid = [a < count for a in range(m)]

    def aged(buf, a):
        if head is None:
            return buf[a]
        return lax.dynamic_index_in_dim(buf, (head - 1 - a) % m, 0, False)

    q = g
    alphas = []
    for a in range(m):  # newest → oldest
        alpha = jnp.where(valid[a], rho[a] * _dot(aged(s_hist, a), q), 0.0)
        q = q - alpha * aged(y_hist, a)
        alphas.append(alpha)

    sy = _dot(aged(s_hist, 0), aged(y_hist, 0))
    yy = _dot(aged(y_hist, 0), aged(y_hist, 0))
    gamma = jnp.where(count > 0, sy / jnp.maximum(yy, _EPS), 1.0)
    r = gamma * q

    for a in reversed(range(m)):  # oldest → newest
        b = rho[a] * _dot(aged(y_hist, a), r)
        r = r + jnp.where(valid[a], alphas[a] - b, 0.0) * aged(s_hist, a)
    return r


def _write(buf: Array, row: Array, head: Array, good_pair: Array) -> Array:
    """The ring's ``_push``: an accepted pair's ``row`` into slot ``head``,
    in place; a rejected one writes the slot's own row back."""
    old = lax.dynamic_index_in_dim(buf, head, 0, False)
    return lax.dynamic_update_index_in_dim(
        buf, jnp.where(good_pair, row, old), head, 0)


def _push(buf: Array, row: Array, good_pair: Array) -> Array:
    """An accepted pair's ``row`` into slot 0 and every other one a slot
    older (the oldest falls off); a rejected pair leaves ``buf`` as it is."""
    return jnp.where(good_pair, jnp.concatenate([row[None], buf[:-1]]), buf)


def _project_orthant(x: Array, orthant: Array) -> Array:
    """Zero coordinates whose sign disagrees with the orthant."""
    return jnp.where(jnp.sign(x) == orthant, x, 0.0)


def _pseudo_gradient(w: Array, g: Array, l1: Array) -> Array:
    """OWL-QN pseudo-gradient of f(w) + Σ l1ⱼ|wⱼ| (Andrew & Gao 2007)."""
    right = g + l1
    left = g - l1
    pg_zero = jnp.where(left > 0.0, left, jnp.where(right < 0.0, right, 0.0))
    return jnp.where(w > 0.0, g + l1, jnp.where(w < 0.0, g - l1, pg_zero))


def minimize(
    value_and_grad: ValueAndGrad,
    w0: Array,
    config: OptimizerConfig = OptimizerConfig(),
    l1_weights: Optional[Array] = None,
    line: "Optional[LineOracle | ValueOracle]" = None,
) -> OptResult:
    """Minimize f(w) (+ Σ l1ⱼ|wⱼ| when ``l1_weights`` given → OWL-QN).

    ``value_and_grad`` must be the SMOOTH part only; the L1 term is handled
    by pseudo-gradients / orthant projection, never differentiated. With
    ``line`` (the same smooth objective taken apart: a ``LineOracle`` for
    L-BFGS, a ``ValueOracle`` for OWL-QN, whose trial points leave the
    line) nothing else evaluates it. Under a ``LineOracle``
    ``evaluations`` counts pairs of passes over the data: the first
    evaluation and one an iteration. Under OWL-QN it counts objective
    values taken, 1 + Σ trials, with or without the oracle, which only
    spares each trial its gradient. ``trials`` counts the line searches'
    trials in every case.
    """
    m = config.history_length
    max_iter = config.max_iterations
    is_owlqn = l1_weights is not None
    dtype = w0.dtype
    d = w0.shape[-1]
    wanted = ValueOracle if is_owlqn else LineOracle
    if line is not None and not isinstance(line, wanted):
        raise ValueError(
            f"{'OWL-QN' if is_owlqn else 'L-BFGS'} takes a "
            f"{wanted.__name__}, not a {type(line).__name__}: OWL-QN's "
            f"trial points are projected and leave the straight line a "
            f"LineOracle needs")

    # A large unbatched solve runs folded, its history a ring (module
    # docstring): the objective still sees (d,) vectors.
    ring = w0.ndim == 1 and m * d * dtype.itemsize >= _RING_BYTES
    if ring:
        def unfold(x):
            return x.reshape(-1)[:d]

        w0 = _fold(w0)
        if is_owlqn:
            l1_weights = _fold(l1_weights)
        smooth = value_and_grad

        def value_and_grad(w):
            f, g = smooth(unfold(w))
            return f, _fold(g)

        if line is not None:
            flat = line

            def start(w):
                f, g, carry = flat.start(unfold(w))
                return f, _fold(g), carry

            def accept(ray, alpha):
                f, g, carry = flat.accept(ray, alpha)
                return f, _fold(g), carry

            line = ValueOracle(
                start, lambda w: flat.trial(unfold(w)),
                lambda w, carry: _fold(flat.accept(unfold(w), carry))
            ) if is_owlqn else LineOracle(
                start, lambda carry, w, v: flat.along(
                    carry, unfold(w), unfold(v)), flat.trial, accept)

    def total_value(f_smooth: Array, w: Array) -> Array:
        if not is_owlqn:
            return f_smooth
        return f_smooth + jnp.sum(l1_weights * jnp.abs(w))

    def search_gradient(w: Array, g: Array) -> Array:
        """The gradient driving direction + convergence (pg for OWL-QN)."""
        if not is_owlqn:
            return g
        with jax.named_scope("owlqn.orthant"):
            return _pseudo_gradient(w, g, l1_weights)

    carry0 = None
    if line is None:
        f0, g0 = value_and_grad(w0)
    else:
        f0, g0, carry0 = line.start(w0)
    ft0 = total_value(f0, w0)
    sg0 = search_gradient(w0, g0)
    g0_norm = jnp.linalg.norm(sg0)

    hist_shape = (m,) + w0.shape
    steps = jnp.arange(max_iter + 1)

    def record(hist, it, value):
        """``hist.at[it].set(value)`` as a mask: ``it`` is per lane under
        vmap, where an indexed write is a scatter."""
        return jnp.where(steps == it, value.astype(hist.dtype), hist)

    nans = jnp.full((max_iter + 1,), jnp.nan, jnp.float32)
    vh = record(nans, 0, ft0)
    gh = record(nans, 0, g0_norm)
    th = nh = None
    if is_owlqn:
        none = jnp.full((max_iter + 1,), -1, jnp.int32)
        th = record(none, 0, jnp.asarray(0))
        nh = record(none, 0, jnp.sum(w0 != 0.0))

    init = _LBFGSState(
        w=w0, f=f0, g=g0,
        s_hist=jnp.zeros(hist_shape, dtype), y_hist=jnp.zeros(hist_shape, dtype),
        rho=jnp.zeros((m,), dtype),
        count=jnp.asarray(0, jnp.int32),
        it=jnp.asarray(0, jnp.int32),
        evals=jnp.asarray(1, jnp.int32),  # the evaluation at w0
        trials=jnp.asarray(0, jnp.int32),
        converged=g0_norm <= config.tolerance,
        failed=jnp.asarray(False),
        g0_norm=g0_norm,
        value_history=vh, grad_norm_history=gh, carry=carry0,
        head=jnp.asarray(0, jnp.int32) if ring else None,
        trials_history=th, nnz_history=nh,
    )

    def line_search_owlqn(w, ft, sg, direction, carry):
        """Backtracking Armijo on the TOTAL objective; returns the new
        point, the trials it took and the oracle's carry there.

        OWL-QN only: the trial point is projected onto the orthant defined
        by sign(w) (or sign(−pg) at zeros) before evaluation, which makes
        the Wolfe curvature condition ill-defined — so Armijo it stays
        (Andrew & Gao 2007 use backtracking too). What is kept of a trial
        is its gradient or, under a ``ValueOracle``, its carry: the
        gradient is then taken once, at the point the search ends on (the
        one it started from where no trial met Armijo).
        """
        with jax.named_scope("owlqn.orthant"):
            orthant = jnp.where(w != 0.0, jnp.sign(w), jnp.sign(-sg))

        def ls_cond(st):
            alpha, steps, done, *_ = st
            return (~done) & (steps < config.max_line_search_steps)

        def ls_body(st):
            alpha, steps, done, best_w, best_f, kept = st
            with jax.named_scope("owlqn.orthant"):
                cand = _project_orthant(w + alpha * direction, orthant)
            f_new, keep = (value_and_grad if line is None
                           else line.trial)(cand)
            ft_new = total_value(f_new, cand)
            # Armijo with the projected displacement (OWL-QN form).
            decrease = _dot(sg, cand - w)
            ok = jnp.isfinite(ft_new) & (ft_new <= ft + config.wolfe_c1 * decrease)
            best_w = jnp.where(ok, cand, best_w)
            best_f = jnp.where(ok, f_new, best_f)
            kept = jax.tree.map(lambda new, old: jnp.where(ok, new, old),
                                keep, kept)
            return (alpha * 0.5, steps + 1, ok, best_w, best_f, kept)

        init_alpha = jnp.asarray(1.0, dtype)
        st = (init_alpha, jnp.asarray(0, jnp.int32), jnp.asarray(False),
              w, jnp.asarray(jnp.inf, dtype), sg if line is None else carry)
        with jax.named_scope("lbfgs.line_search"):
            _, steps, ok, new_w, new_f, kept = lax.while_loop(
                ls_cond, ls_body, st)
            if line is None:
                return ok, new_w, new_f, kept, steps, None
            return ok, new_w, new_f, line.accept(new_w, kept), steps, kept

    def line_search_wolfe(w, ft, sg, direction):
        """Strong-Wolfe line search as a bounded bisection-with-expansion.

        Reference parity: breeze ``StrongWolfeLineSearch`` driven by
        ``optimization/LBFGS.scala``. Instead of Breeze's host-side
        bracket-and-zoom recursion this is one fixed-bound ``while_loop``
        maintaining a bracket [a, b] (b = ∞ until an upper bound is seen):

        - Armijo fails, or slope already ≥ +c2·|φ'(0)| (overshot)  → b = α
        - Armijo holds but slope < c2·φ'(0) (still descending hard) → a = α
        - Armijo holds and |φ'(α)| ≤ −c2·φ'(0)                      → accept

        Next trial: 2α while unbracketed, else the midpoint. One fused
        value+grad per trial (one psum when distributed), vmap-safe: under
        vmap, JAX's while_loop batching select-freezes finished lanes.
        Guarantees sᵀy > 0 for accepted points, so every step yields a
        valid curvature pair. On budget exhaustion falls back to the best
        Armijo-satisfying point seen (the sy > eps gate below discards its
        pair if curvature is bad). Also returns the trials it took: one
        objective evaluation each.
        """
        c1 = config.wolfe_c1
        c2 = config.wolfe_c2
        dg0 = _dot(sg, direction)  # φ'(0) < 0 for descent directions
        inf = jnp.asarray(jnp.inf, dtype)

        def ls_cond(st):
            _, _, _, steps, done, *_ = st
            return (~done) & (steps < config.max_line_search_steps)

        def ls_body(st):
            a, b, alpha, steps, done, has_pt, res_w, res_f, res_g = st
            cand = w + alpha * direction
            f_new, g_new = value_and_grad(cand)
            dg_new = _dot(g_new, direction)
            armijo = jnp.isfinite(f_new) & (f_new <= ft + c1 * alpha * dg0)
            strong = armijo & (jnp.abs(dg_new) <= -c2 * dg0)
            curv_low = dg_new < c2 * dg0
            # Record: a strong point always wins; otherwise keep the best
            # (lowest-f) Armijo point as the exhaustion fallback. res_f
            # starts at f(w), and any Armijo point is below that.
            take = strong | (armijo & (f_new < res_f))
            res_w = jnp.where(take, cand, res_w)
            res_f = jnp.where(take, f_new, res_f)
            res_g = jnp.where(take, g_new, res_g)
            grow = armijo & curv_low & ~strong
            a2 = jnp.where(grow, alpha, a)
            b2 = jnp.where(~strong & ~grow, alpha, b)
            alpha2 = jnp.where(grow & ~jnp.isfinite(b2),
                               2.0 * alpha, 0.5 * (a2 + b2))
            return (a2, b2, alpha2, steps + 1, strong, has_pt | armijo,
                    res_w, res_f, res_g)

        st = (jnp.asarray(0.0, dtype), inf, jnp.asarray(1.0, dtype),
              jnp.asarray(0, jnp.int32), jnp.asarray(False),
              jnp.asarray(False), w, ft, sg)
        with jax.named_scope("lbfgs.line_search"):
            (_, _, _, steps, done, has_pt,
             new_w, new_f, new_g) = lax.while_loop(ls_cond, ls_body, st)
        return done | has_pt, new_w, new_f, new_g, steps

    def line_search_along(w, ft, sg, direction, carry):
        """``line_search_wolfe``'s bracket over a ``LineOracle``: the same
        tests on the same two numbers of a trial, (f, φ'), which here cost
        no pass over the data; what is kept of a trial is its α, and the
        gradient is taken once, at the α the search ends on (0 where no
        trial met Armijo: the point it started from). One pair of passes
        whatever the trials it reports."""
        c1 = config.wolfe_c1
        c2 = config.wolfe_c2
        dg0 = _dot(sg, direction)
        inf = jnp.asarray(jnp.inf, dtype)

        def ls_cond(st):
            _, _, _, steps, done, *_ = st
            return (~done) & (steps < config.max_line_search_steps)

        def ls_body(st):
            a, b, alpha, steps, done, has_pt, res_alpha, res_f = st
            f_new, dg_new = line.trial(ray, alpha)
            armijo = jnp.isfinite(f_new) & (f_new <= ft + c1 * alpha * dg0)
            strong = armijo & (jnp.abs(dg_new) <= -c2 * dg0)
            curv_low = dg_new < c2 * dg0
            take = strong | (armijo & (f_new < res_f))
            res_alpha = jnp.where(take, alpha, res_alpha)
            res_f = jnp.where(take, f_new, res_f)
            grow = armijo & curv_low & ~strong
            a2 = jnp.where(grow, alpha, a)
            b2 = jnp.where(~strong & ~grow, alpha, b)
            alpha2 = jnp.where(grow & ~jnp.isfinite(b2),
                               2.0 * alpha, 0.5 * (a2 + b2))
            return (a2, b2, alpha2, steps + 1, strong, has_pt | armijo,
                    res_alpha, res_f)

        zero = jnp.asarray(0.0, dtype)
        st = (zero, inf, jnp.asarray(1.0, dtype), jnp.asarray(0, jnp.int32),
              jnp.asarray(False), jnp.asarray(False), zero, ft)
        with jax.named_scope("lbfgs.line_search"):
            ray = line.along(carry, w, direction)
            _, _, _, steps, done, has_pt, alpha, _ = lax.while_loop(
                ls_cond, ls_body, st)
            new_f, new_g, new_carry = line.accept(ray, alpha)
        return (done | has_pt, w + alpha * direction, new_f, new_g, steps,
                new_carry)

    def body(state: _LBFGSState) -> _LBFGSState:
        sg = search_gradient(state.w, state.g)
        with jax.named_scope("lbfgs.direction"):
            d_dir = -_two_loop(sg, state.s_hist, state.y_hist, state.rho,
                               state.count, state.head)
            if is_owlqn:
                # Constrain the direction to the descent orthant of −pg.
                with jax.named_scope("owlqn.orthant"):
                    d_dir = jnp.where(d_dir * (-sg) > 0.0, d_dir, 0.0)
            # Safeguard: fall back to steepest descent on non-descent
            # directions.
            descent = _dot(sg, d_dir) < 0.0
            d_dir = jnp.where(descent, d_dir, -sg)
            # First iteration: scale like Breeze (step ~ 1/‖g‖ effect) to
            # avoid wild first steps on poorly scaled problems.
            first = state.count == 0
            d_dir = jnp.where(
                first, d_dir / jnp.maximum(jnp.linalg.norm(d_dir), 1.0),
                d_dir)

        ft = total_value(state.f, state.w)
        carry = None
        if is_owlqn:
            ok, new_w, new_f, new_g, trials, carry = line_search_owlqn(
                state.w, ft, sg, d_dir, state.carry)
        elif line is None:
            ok, new_w, new_f, new_g, trials = line_search_wolfe(
                state.w, ft, sg, d_dir)
        else:
            ok, new_w, new_f, new_g, trials, carry = line_search_along(
                state.w, ft, sg, d_dir, state.carry)
        # What the search cost in evaluations: every trial one, but under a
        # LineOracle one pair of passes whatever the trials.
        asked = trials if is_owlqn or line is None else 1

        with jax.named_scope("lbfgs.direction"):  # the history update
            s = new_w - state.w
            y = new_g - state.g
            sy = _dot(s, y)
            good_pair = ok & (sy > _EPS)
            new_count = jnp.where(good_pair,
                                  jnp.minimum(state.count + 1, m),
                                  state.count)
            head = None
            if ring:
                # In place, so this lane's own ``converged`` gates the
                # write and ``masked_update`` below leaves the rings out.
                good_pair = good_pair & ~state.converged
                s_hist = _write(state.s_hist, s, state.head, good_pair)
                y_hist = _write(state.y_hist, y, state.head, good_pair)
                head = jnp.where(good_pair, (state.head + 1) % m, state.head)
            else:
                s_hist = _push(state.s_hist, s, good_pair)
                y_hist = _push(state.y_hist, y, good_pair)
            rho = _push(state.rho, 1.0 / jnp.maximum(sy, _EPS), good_pair)

        new_sg = search_gradient(new_w, new_g)
        new_gnorm = jnp.linalg.norm(new_sg)
        ft_new = total_value(new_f, new_w)
        it = state.it + 1
        conv = ok & check_convergence(ft_new, ft, new_gnorm, state.g0_norm,
                                      config.tolerance)
        failed = ~ok  # line search exhausted: stop (stalled)

        vh = record(state.value_history, it, jnp.where(ok, ft_new, ft))
        gh = record(state.grad_norm_history, it,
                    jnp.where(ok, new_gnorm, jnp.linalg.norm(sg)))
        th = nh = None
        if is_owlqn:
            th = record(state.trials_history, it, trials)
            nh = record(state.nnz_history, it, jnp.sum(
                jnp.where(ok, new_w, state.w) != 0.0))

        new_state = _LBFGSState(
            w=jnp.where(ok, new_w, state.w),
            f=jnp.where(ok, new_f, state.f),
            g=jnp.where(ok, new_g, state.g),
            s_hist=s_hist, y_hist=y_hist, rho=rho,
            count=new_count,
            it=it,
            evals=state.evals + asked,
            trials=state.trials + trials,
            converged=state.converged | conv | failed,
            failed=state.failed | failed,
            g0_norm=state.g0_norm,
            value_history=vh, grad_norm_history=gh,
            carry=jax.tree.map(lambda new, old: jnp.where(ok, new, old),
                               carry, state.carry),
            head=head, trials_history=th, nnz_history=nh,
        )
        # vmap safety: freeze lanes that were already converged (history
        # buffers included — body still executes for them).
        frozen = masked_update(state.converged, new_state, state)
        if ring:
            frozen = dataclasses.replace(frozen, s_hist=s_hist, y_hist=y_hist)
        return frozen

    def cond(state: _LBFGSState):
        return (~state.converged) & (state.it < max_iter)

    final = lax.while_loop(cond, body, init)
    sg_final = search_gradient(final.w, final.g)
    return OptResult(
        w=unfold(final.w) if ring else final.w,
        value=total_value(final.f, final.w),
        grad_norm=jnp.linalg.norm(sg_final),
        iterations=final.it,
        evaluations=final.evals,
        trials=final.trials,
        converged=final.converged & ~final.failed,
        value_history=final.value_history,
        grad_norm_history=final.grad_norm_history,
        trials_history=final.trials_history,
        nnz_history=final.nnz_history,
    )


def minimize_owlqn(
    value_and_grad: ValueAndGrad,
    w0: Array,
    l1_weights: Array,
    config: OptimizerConfig = OptimizerConfig(),
    line: Optional[ValueOracle] = None,
) -> OptResult:
    """OWL-QN: minimize smooth f(w) + Σⱼ l1ⱼ |wⱼ|.

    Reference parity: photon-lib ``optimization/OWLQN.scala``.
    """
    return minimize(value_and_grad, w0, config, l1_weights=l1_weights,
                    line=line)
