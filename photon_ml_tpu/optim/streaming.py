"""Host-driven L-BFGS for row-streamed objectives.

Reference parity: photon-api's distributed fits are DRIVER-loop
optimization — Breeze L-BFGS iterates on the Spark driver, and every
value/gradient is one cluster pass (``DistributedGLMLossFunction`` →
``treeAggregate``). The compiled optimizer in ``optim/lbfgs.py`` is the
right shape when the data is device-resident (the whole solve is one XLA
program, vmappable for per-entity lanes), but a row-STREAMED objective
(``ops/streaming_sparse.py``) is a Python loop over chunk dispatches and
cannot be traced into a ``lax.while_loop``. This module is the
driver-loop counterpart: the two-loop recursion and vector math stay on
device (jitted helpers over (d,)-vectors — history for d=1M, m=10 is
40 MB), the iteration control runs in Python, and each objective
evaluation streams the chunks once.

Line search is backtracking Armijo (not strong Wolfe): each probe costs a
FULL pass over the data, and Armijo accepts in 1–2 probes from the
well-scaled L-BFGS direction where the bracket/bisect Wolfe machine
budgets for ~10. Curvature pairs that fail s·y > 0 are skipped (standard
damping), preserving a positive-definite inverse-Hessian model; parity
with the compiled strong-Wolfe L-BFGS is pinned by test on shared small
problems (tests/test_streaming.py).

L1/OWL-QN (``l1_weights``): the same driver loop runs Andrew & Gao's
orthant-wise scheme, mirroring the compiled ``minimize_owlqn``
(optim/lbfgs.py) — the PSEUDO-gradient drives the two-loop direction and
the convergence norm, every probe is projected onto the orthant of the
current point (sign(w), or sign(−pg) at zeros), Armijo tests the TOTAL
objective with the projected displacement ``pg·(cand − w)``, and
curvature pairs come from the RAW smooth gradients. The streamed
``value_and_grad``/``value_only`` stay the smooth part only; the L1 term
is added host-side at the probe barrier (the value is already synced
there) and is never differentiated.
"""

from __future__ import annotations

import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu import faults as flt
from photon_ml_tpu import obs
from photon_ml_tpu.obs.ledger import transfer_totals
from photon_ml_tpu.obs.watchdog import ConvergenceWatchdog
from photon_ml_tpu.optim.common import OptResult, OptimizerConfig
from photon_ml_tpu.optim.lbfgs import _project_orthant, _pseudo_gradient

Array = jax.Array


@jax.jit
def _two_loop(grad: Array, s_stack: Array, y_stack: Array,
              rho: Array, m: Array) -> Array:
    """Standard L-BFGS two-loop recursion over a fixed-size (M, d)
    history ring; entries past ``m`` (the live count) are masked out.
    Newest pair is at index m-1."""
    M = s_stack.shape[0]

    def bwd(i, carry):
        q, alpha = carry
        j = m - 1 - i  # newest → oldest; j < 0 once i >= m (dead lanes)
        live = j >= 0
        jc = jnp.maximum(j, 0)
        a = jnp.where(live, rho[jc] * jnp.dot(s_stack[jc], q), 0.0)
        q = q - a * y_stack[jc]  # a == 0 on dead lanes
        return q, jnp.where(live, alpha.at[jc].set(a), alpha)

    q, alpha = jax.lax.fori_loop(
        0, M, bwd, (grad, jnp.zeros((M,), jnp.float32)))
    # Initial Hessian scaling γ = s·y / y·y from the newest pair.
    newest = jnp.maximum(m - 1, 0)
    sy = jnp.dot(s_stack[newest], y_stack[newest])
    yy = jnp.dot(y_stack[newest], y_stack[newest])
    gamma = jnp.where((m > 0) & (yy > 0), sy / jnp.maximum(yy, 1e-30), 1.0)
    r = gamma * q

    def fwd(j, r):
        # Oldest → newest. Ring slots ≥ m hold zeros (s/y/rho/alpha), so
        # dead lanes contribute exactly 0 with no masking needed.
        beta = rho[j] * jnp.dot(y_stack[j], r)
        return r + (alpha[j] - beta) * s_stack[j]

    return -jax.lax.fori_loop(0, M, fwd, r)


@jax.jit
def _shift_in(stack: Array, v: Array, m: Array) -> Array:
    """Append ``v`` at ring position m (or shift left when full)."""
    M = stack.shape[0]
    full = m >= M
    shifted = jnp.where(full, jnp.roll(stack, -1, axis=0), stack)
    idx = jnp.where(full, M - 1, m)
    return shifted.at[idx].set(v)


def snapshot_state(w, g, s_stack, y_stack, rho, m_host, it, fv, gn_prev,
                   f0, gn0, vals, gns) -> dict:
    """Host-side snapshot of the FULL driver-loop state at an iteration
    boundary — everything the loop reads before its next streamed pass.
    Plain numpy (f32 exact), so a save→load→resume round trip replays
    the remaining iterations BIT-identically to an uninterrupted run
    (the objective itself is deterministic: fixed chunk order per
    device, fixed merge order)."""
    return {
        "w": np.asarray(w), "g": np.asarray(g),
        "s_stack": np.asarray(s_stack), "y_stack": np.asarray(y_stack),
        "rho": np.asarray(rho), "m": np.int32(m_host),
        "it": np.int32(it), "fv": np.float32(fv),
        "gn_prev": np.float32(gn_prev), "f0": np.float32(f0),
        "gn0": np.float32(gn0), "vals": np.asarray(vals),
        "gns": np.asarray(gns),
    }


def minimize_streaming(
    value_and_grad: Callable[[Array], tuple[Array, Array]],
    w0: Array,
    config: OptimizerConfig,
    log: Callable[[str], None] = lambda m: None,
    value_only: Optional[Callable[[Array], Array]] = None,
    checkpoint_save: Optional[Callable[[dict], None]] = None,
    resume_state: Optional[dict] = None,
    l1_weights: Optional[Array] = None,
    on_accept: Optional[Callable[[int, Array, float, float], None]] = None,
) -> OptResult:
    """Driver-loop L-BFGS: minimize a host-driven (value, grad) callable.

    ``value_and_grad`` is called once per iteration plus once per
    line-search probe; everything it returns stays on device until the
    final host read of the convergence scalars (one small sync per
    iteration — the stream itself is the dominant cost by orders of
    magnitude).

    ``value_only``, when given, is a cheaper streamed pass computing just
    the objective value; Armijo probes then use it — only the VALUE gates
    acceptance — and the gradient pass runs once per iteration, on the
    accepted point (ADVICE r5: without this, every backtracking probe
    paid the full gradient stream only to discard it). Probe cost per
    iteration drops from ``k·cost(vg)`` to ``k·cost(v) + cost(vg)``; on
    the hybrid-sparse chunk kernels the gradient half (hot rmatvec +
    per-slot cold scatter-adds) dominates compute, so cost(v) ≪
    cost(vg) and the win grows with every backtrack.

    ``checkpoint_save``, when given, is called at the end of every
    accepted iteration with a :func:`snapshot_state` dict; passing a
    saved snapshot back as ``resume_state`` restarts the loop at the
    NEXT iteration with bit-identical state (the crash-resume seam of
    the streamed fixed-effect coordinate — game/checkpoint.py's
    StreamingStateStore persists the snapshots). A resumed call skips
    the initial value/gradient pass entirely: the snapshot carries it.

    ``l1_weights``, when given, switches the loop to OWL-QN (module
    docstring) — ``value_and_grad``/``value_only`` must stay the SMOOTH
    part only; the L1 term is never differentiated.

    ``on_accept``, when given, runs once per ACCEPTED iteration with
    ``(it, w, value, grad_norm)``, after the ledger row and the
    checkpoint write — the fabric's cross-rank digest exchange hooks
    here (fabric/stream.py), so a ``RankDivergence`` raised from the
    hook still leaves a resumable snapshot and a flushed curve point
    behind, exactly like a watchdog verdict.

    Telemetry (docs/OBSERVABILITY.md "The run ledger"): when a run
    ledger is active (``obs.ledger()``), every accepted iteration
    records an ``opt_iter`` row LIVE — value, gradient norm, step,
    probe/pass counts, per-iteration wall seconds, cumulative transfer
    counters. When a watchdog config is installed
    (``obs.watchdog_config()``), the same per-iteration stream feeds a
    :class:`ConvergenceWatchdog` — NaN/stall/divergence/slow-iteration
    become a loud event plus a defined error or early stop. Both are
    off by default at one None check here.
    """
    d = int(w0.shape[0])
    M = config.history_length
    max_it = config.max_iterations
    led = obs.ledger()
    wd_cfg = obs.watchdog_config()
    wd = (ConvergenceWatchdog(wd_cfg) if wd_cfg is not None else None)
    l1 = (None if l1_weights is None
          else jnp.asarray(l1_weights, jnp.float32))
    opt_name = "lbfgs-stream" if l1 is None else "owlqn-stream"

    def _sgrad(w, g):
        """Gradient driving direction + convergence (pg under L1)."""
        return g if l1 is None else _pseudo_gradient(w, g, l1)

    def _l1_term(w) -> float:
        if l1 is None:
            return 0.0
        return float(jnp.sum(l1 * jnp.abs(w)))

    v_passes = g_passes = 0  # streamed passes, cumulative this call
    if resume_state is not None:
        st = resume_state
        if st["s_stack"].shape != (M, d) or st["w"].shape != (d,):
            raise ValueError(
                f"resume state shape mismatch: saved history "
                f"{st['s_stack'].shape} / w {st['w'].shape}, expected "
                f"({M}, {d}) / ({d},) — the checkpoint was written under "
                f"a different optimizer configuration")
        w = jnp.asarray(st["w"], jnp.float32)
        g = jnp.asarray(st["g"], jnp.float32)
        s_stack = jnp.asarray(st["s_stack"], jnp.float32)
        y_stack = jnp.asarray(st["y_stack"], jnp.float32)
        rho = jnp.asarray(st["rho"], jnp.float32)
        m_host = int(st["m"])
        m = jnp.asarray(m_host, jnp.int32)
        f0, gn0 = float(st["f0"]), float(st["gn0"])
        fv, gn_prev = float(st["fv"]), float(st["gn_prev"])
        start_it = int(st["it"]) + 1
        vals = np.full((max_it + 1,), np.nan, np.float32)
        gns = np.full((max_it + 1,), np.nan, np.float32)
        k = min(st["vals"].shape[0], max_it + 1)
        vals[:k], gns[:k] = st["vals"][:k], st["gns"][:k]
        sg = _sgrad(w, g)  # snapshot carries the RAW gradient
        log(f"resuming streamed L-BFGS at iteration {start_it} "
            f"(f={fv:.6g})")
    else:
        w = jnp.asarray(w0, jnp.float32)
        with obs.span("lbfgs.initial_pass", cat="optim"):
            f, g = value_and_grad(w)
        g_passes += 1
        sg = _sgrad(w, g)
        f0 = float(f) + _l1_term(w)
        gn0 = float(jnp.linalg.norm(sg))
        s_stack = jnp.zeros((M, d), jnp.float32)
        y_stack = jnp.zeros((M, d), jnp.float32)
        rho = jnp.zeros((M,), jnp.float32)
        m = jnp.zeros((), jnp.int32)
        m_host = 0  # host mirror of m — step-size branch must not sync
        vals = np.full((max_it + 1,), np.nan, np.float32)
        gns = np.full((max_it + 1,), np.nan, np.float32)
        vals[0], gns[0] = f0, gn0
        fv, gn_prev = f0, gn0
        start_it = 1
    converged = False
    it = start_it - 1
    for it in range(start_it, max_it + 1):
        t_iter = time.perf_counter()
        v0_passes, g0_passes = v_passes, g_passes
        # One span per driver-loop iteration (docs/OBSERVABILITY.md):
        # streamed passes, probes, and the checkpoint write all nest
        # under it, so the trace waterfall reads as the optimizer ran.
        with obs.span("lbfgs.iteration", cat="optim", it=it):
            direction = _two_loop(sg, s_stack, y_stack, rho, m)
            # pml: allow[PML001] direction-validity guard is a host branch by design; one scalar read per iteration vs a full data pass
            dg = float(jnp.dot(direction, sg))
            if not np.isfinite(dg) or dg >= 0.0:
                # pml: allow[PML001] steepest-descent fallback needs the host scalar for the same Armijo branch; rare path
                direction, dg = -sg, -float(jnp.dot(sg, sg))
            # First iteration: steepest descent scaled to unit step
            # length (Breeze's determineStepSize init); later γ-scaling
            # makes 1.0 the natural trial step.
            step = 1.0 if m_host > 0 else min(1.0,
                                              1.0 / max(gn_prev, 1e-12))
            # OWL-QN probes live in the orthant of the CURRENT point
            # (sign(w); sign(−pg) at zeros) — fixed across backtracks.
            orthant = (None if l1 is None else
                       jnp.where(w != 0.0, jnp.sign(w), jnp.sign(-sg)))
            accepted = False
            for probe in range(config.max_line_search_steps):
                w_try = w + step * direction
                if orthant is not None:
                    w_try = _project_orthant(w_try, orthant)
                with obs.span("lbfgs.probe", cat="optim", it=it,
                              probe=probe, step=step):
                    if value_only is None:
                        f_try, g_try = value_and_grad(w_try)
                        g_passes += 1
                        # pml: allow[PML001] Armijo probe is a BY-DESIGN barrier: the host decides accept/backtrack on this value (ISSUE 3)
                        f_try_h = float(f_try)
                    else:
                        v_passes += 1
                        # pml: allow[PML001] Armijo probe barrier, value-only pass (same by-design host decision as above)
                        f_try_h = float(value_only(w_try))
                f_try_h += _l1_term(w_try)  # total objective under L1
                # Watchdog chaos seam (docs/ROBUSTNESS.md): a "nan"
                # fault spec here is the injected form of a numerically
                # sick objective.
                f_try_h = flt.poison_scalar(flt.sites.STREAM_OBJECTIVE, f_try_h)
                if l1 is None:
                    decrease = step * dg
                else:
                    # Armijo with the projected displacement (the
                    # orthant projection breaks the step·dg identity).
                    # pml: allow[PML001] same by-design probe barrier — one scalar per probe
                    decrease = float(jnp.dot(sg, w_try - w))
                if np.isfinite(f_try_h) and \
                        f_try_h <= fv + config.wolfe_c1 * decrease:
                    accepted = True
                    break
                step *= 0.5
            if not accepted:
                if wd is not None:
                    # A line search that died on NON-FINITE probes is
                    # the NaN failure shape — loud, defined (a finite
                    # failed search stays the optimizer's own stop).
                    wd.on_line_search_failure(f_try_h, it)
                log(f"iter {it}: line search failed (f={fv:.6g}); "
                    f"stopping")
                break
            if value_only is not None:
                # Gradient pass only on acceptance (the curvature pair
                # and the next direction need it; rejected probes never
                # did).
                _, g_try = value_and_grad(w_try)
                g_passes += 1
            s = w_try - w
            y = g_try - g  # RAW smooth gradients (OWL-QN included)
            # pml: allow[PML001] curvature-damping skip is a host branch; one scalar per accepted step
            sy = float(jnp.dot(s, y))
            if sy > 1e-10:
                s_stack = _shift_in(s_stack, s, m)
                y_stack = _shift_in(y_stack, y, m)
                rho = _shift_in(rho[:, None], jnp.full((1,), 1.0 / sy,
                                                       jnp.float32),
                                m)[:, 0]
                m = jnp.minimum(m + 1, M)
                m_host = min(m_host + 1, M)
            w, g = w_try, g_try
            sg = _sgrad(w, g)
            f_prev, fv = fv, f_try_h
            # pml: allow[PML001] convergence test runs on host once per iteration; the streamed pass dominates by orders of magnitude
            gn = float(jnp.linalg.norm(sg))
            vals[it], gns[it] = fv, gn
            log(f"iter {it}: f={fv:.6g} |g|={gn:.3g} step={step:.3g}")
            if led is not None:
                # Append-as-produced: a SIGKILL one iteration later
                # still leaves this point on the curve (the ledger's
                # whole reason to exist).
                led.record("opt_iter", opt=opt_name, iteration=it,
                           value=fv, grad_norm=gn, step=step,
                           probes=probe + 1,
                           value_passes=v_passes - v0_passes,
                           grad_passes=g_passes - g0_passes,
                           seconds=round(time.perf_counter() - t_iter, 6),
                           **transfer_totals())
            if checkpoint_save is not None:
                # Iteration boundary = the resume point: everything the
                # next iteration reads goes into the snapshot (gn_prev is
                # the gn just computed — the value the next iteration
                # would see).
                checkpoint_save(snapshot_state(
                    w, g, s_stack, y_stack, rho, m_host, it, fv, gn, f0,
                    gn0, vals, gns))
            if on_accept is not None:
                # After the checkpoint write (same rationale as the
                # watchdog below): a divergence raised here leaves a
                # resumable snapshot + a flushed ledger row behind.
                on_accept(it, w, fv, gn)
            if wd is not None:
                # After the checkpoint write: a "raise" verdict still
                # leaves a resumable snapshot + a flushed ledger row.
                if wd.observe(it, fv, gn,
                              time.perf_counter() - t_iter) == "stop":
                    log(f"iter {it}: watchdog early stop")
                    break
            if gn <= config.tolerance * max(gn0, 1.0) or \
                    abs(fv - f_prev) <= config.tolerance * max(abs(f_prev),
                                                               1e-12):
                converged = True
                break
            gn_prev = gn

    return OptResult(
        w=w,
        value=jnp.asarray(fv, jnp.float32),
        grad_norm=jnp.asarray(gns[it] if not np.isnan(gns[it]) else gn_prev,
                              jnp.float32),
        iterations=jnp.asarray(it, jnp.int32),
        evaluations=jnp.asarray(v_passes + g_passes, jnp.int32),
        converged=jnp.asarray(converged),
        value_history=jnp.asarray(vals),
        grad_norm_history=jnp.asarray(gns),
    )
