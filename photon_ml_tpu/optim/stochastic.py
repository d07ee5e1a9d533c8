"""Host-driven stochastic solvers (SDCA + mini-batch SGD) for the
row-streamed objective — the duality gap as a first-class subsystem.

Snap ML and the GPU duality-gap work (PAPERS.md) fit Criteo-scale GLMs
several times faster than batch L-BFGS to a given AUC with stochastic
DUAL coordinate ascent, using the duality gap (optim/gap.py) both as a
principled stopping certificate and as an importance signal for what
stays resident on the accelerator. This module is that solver family
behind the exact :func:`optim.streaming.minimize_streaming` driver
contract: the same ``ChunkedHybrid`` chunk feed, the same
checkpoint/resume snapshot discipline (the dual vector α rides in the
snapshot beside w), the same watchdog arming and fault sites, the same
``opt_iter`` ledger rows — plus a ``gap`` column, the
``photon_opt_duality_gap`` gauge, and a gap-gated stop.

**SDCA** (``solver="sdca"``): one epoch visits every chunk in global
order; within a chunk the rows update SEQUENTIALLY (a ``lax.fori_loop``
inside one jitted per-chunk kernel — dual coordinate ascent is
inherently sequential; Snap ML's asynchronous parallel variant is out of
scope), each row taking the exact single-coordinate dual step
(``gap.sdca_delta``) and applying w ← w + (Δα/λ)·xᵢ so w ≡ w(α) holds
after every row — the invariant the gap identity rests on. The dual
vector α is HOST-resident (device residency would double the stream's
HBM footprint); each chunk's slice rides to the device beside the chunk
and comes home with the per-chunk gap partials. The epoch-end gap is
EXACT (not estimated): conj/α·offset partials accumulate during the
dual pass, the loss side is the epoch-end value pass, and the pieces
assemble per ``gap.assemble_gap`` — with the partial reduction grouped
by ``gap.reduce_gap_partials`` so a 1-device reduction is bit-identical
to the plain chunk-order sum.

**SGD** (``solver="sgd"``, and the fallback for losses without a cheap
conjugate — poisson, smoothed hinge): one epoch takes one
``w ← w − η_t·(C·g_chunk + λ·w)`` step per chunk (C = num_chunks makes
the chunk gradient an unbiased estimate of the full one) with the
classic λ-strong-convexity schedule η_t = 1/(λ(t + t₀)), t₀ = C; the
epoch-end (value, gradient) pass prices convergence and the gap column
carries the primal surrogate ‖∇P‖²/(2λ) (``gap.sgd_gap_surrogate``).

**Gap-driven residency**: ``pin_budget`` chunks stay pinned on device
through ``ops/chunk_sampler.GapChunkSampler`` — after each SDCA epoch
the pin set re-ranks by per-chunk gap contribution (the DuHL pattern),
so the chunks with convergence progress left in them stop paying the
transfer wall. Residency never changes chunk order, so results are
bit-identical for every pin set.

Warm starts: SDCA maintains w ≡ (1/λ)Σαᵢxᵢ and an arbitrary w₀ has no
α representation — a nonzero warm start is IGNORED (logged) and the
ascent starts at (w, α) = 0, unless ``resume_state`` carries a
snapshotted (w, α) pair. SGD warm-starts normally.
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
from photon_ml_tpu.ops import streaming_sparse as ss
from photon_ml_tpu.ops.chunk_sampler import GapChunkSampler
from photon_ml_tpu.ops.losses import PointwiseLoss
from photon_ml_tpu.optim import gap as gap_mod
from photon_ml_tpu.optim.common import OptResult, OptimizerConfig

Array = jax.Array

STOCHASTIC_SOLVERS = ("sdca", "sgd")

# Per-(loss, storage dtype) jitted SDCA chunk kernels — the same
# one-program-per-stream accounting as the value/gradient kernel caches
# in ops/streaming_sparse.py.
_SDCA_KERNELS: dict = {}


def _sdca_kernel(loss: PointwiseLoss, dtype: str):
    """One jitted per-chunk dual pass: (w_pad, α_chunk, offsets, λ,
    chunk) → (w_pad′, α_chunk′, [conj_sum, α·offset_sum, gap_sum]).

    Rows update sequentially (``fori_loop``); every per-row gather and
    scatter is 1-D over (H,) / (k,) slices, so the chunk-scale layout
    rules of ops/streaming_sparse.py (no (n, k)-shaped index operands)
    are never in play. int8 chunks dequantize per row — codes × scale
    gathers, f32 accumulation, no dense f32 block materialized."""
    key = (loss.name, dtype)
    f = _SDCA_KERNELS.get(key)
    if f is not None:
        ss._count_kernel_hit("stream_sdca_dual", dtype)
        return f
    ss._count_kernel_build("stream_sdca_dual", dtype)
    delta_fn = gap_mod.sdca_delta(loss.name)
    conj_fn = gap_mod.conjugate_term(loss.name)

    @jax.jit
    def f(w_pad: Array, alpha: Array, offsets: Array, lam: Array,
          ch: ss.CanonicalChunk):
        quantized = ch.cold_scale is not None

        def body(i, carry):
            w_pad, alpha, conj_s, aoff_s, gap_s = carry
            cc = ch.cold_cols[i]
            if quantized:
                xh = ch.X_hot[i].astype(jnp.float32) * ch.hot_scale
                cv = ch.cold_vals[i].astype(jnp.float32) * \
                    ch.cold_scale[cc]
            else:
                xh = ch.X_hot[i].astype(jnp.float32)
                cv = ch.cold_vals[i].astype(jnp.float32)
            o = offsets[i]
            y = ch.labels[i]
            wgt = ch.weights[i]
            a = alpha[i]
            # Margin + row norm from the hot row and the cold ELL row
            # (pad/hot-inert cold slots carry value 0 and the sentinel
            # column, so they contribute exactly 0 to both).
            z = o + jnp.dot(xh, w_pad[ch.hot_cols]) + \
                jnp.sum(w_pad[cc] * cv)
            xsq = jnp.dot(xh, xh) + jnp.sum(cv * cv)
            d_a = delta_fn(z, y, wgt, a, xsq, lam)
            a_new = a + d_a
            # w ≡ w(α): the dual step lands on w immediately. Sentinel
            # scatters (hot pad columns, cold pad slots) add exact 0.
            scale = d_a / lam
            w_pad = w_pad.at[ch.hot_cols].add(scale * xh)
            w_pad = w_pad.at[cc].add(scale * cv)
            alpha = alpha.at[i].set(a_new)
            cj = conj_fn(a_new, y, wgt)
            li, _ = loss.loss_and_dz(z, y)
            # Per-row Fenchel–Young term (≥ 0): the DuHL importance
            # signal, summed per chunk. Clamped at 0 against f32 noise.
            gap_i = jnp.where(wgt > 0.0, wgt * li + cj + a_new * z, 0.0)
            return (w_pad, alpha, conj_s + cj, aoff_s + a_new * o,
                    gap_s + jnp.maximum(gap_i, 0.0))

        zero = jnp.zeros((), jnp.float32)
        w_pad, alpha, conj_s, aoff_s, gap_s = jax.lax.fori_loop(
            0, ch.labels.shape[0], body,
            (w_pad, alpha, zero, zero, zero))
        return w_pad, alpha, jnp.stack([conj_s, aoff_s, gap_s])

    _SDCA_KERNELS[key] = f
    return f


# SGD step-norm trust radius: poisson/smoothed-hinge gradients are not
# Lipschitz-bounded (exp(z) grows without bound), so a raw 1/(λ(t+t₀))
# schedule can overshoot into overflow on the very first epoch. Clipping
# the STEP norm to R/t keeps every update bounded (total travel grows
# only like log t — the normalized-gradient-descent stabilization) while
# leaving the schedule untouched once iterates reach the region where
# steps are naturally small. Deterministic in (w, t), so a snapshot
# resume replays it exactly.
_SGD_TRUST_RADIUS = 1.0


@jax.jit
def _sgd_step(w: Array, g_chunk: Array, eta: Array, lam: Array,
              scale: Array, mask: Array, radius: Array) -> Array:
    """One mini-batch step: w − η·(C·g_chunk + λ·(w∘mask)) — the chunk
    gradient scaled by C = num_chunks is an unbiased estimate of the
    full data gradient of the SUM objective — with the step norm clipped
    to ``radius`` (= ``_SGD_TRUST_RADIUS``/t)."""
    step = eta * (scale * g_chunk + lam * (w * mask))
    norm = jnp.linalg.norm(step)
    clip = jnp.minimum(1.0, radius / jnp.maximum(norm, 1e-30))
    return w - clip * step


def snapshot_stochastic(w, alpha, it, fv, gap, f0, gap0, vals, gaps,
                        t_step) -> dict:
    """Host-side snapshot of the full stochastic driver state at an
    epoch boundary — the α vector rides beside w, so a save→load→resume
    round trip replays the remaining epochs BIT-identically to an
    uninterrupted run (chunk order and the within-chunk row order are
    fixed; residency never changes either). Plain numpy, keyed like
    optim/streaming.snapshot_state ("it" included — the checkpoint
    store's span reads it)."""
    return {
        "w": np.asarray(w), "alpha": np.asarray(alpha),
        "it": np.int32(it), "fv": np.float32(fv),
        "gap": np.float32(gap), "f0": np.float32(f0),
        "gap0": np.float32(gap0), "vals": np.asarray(vals),
        "gns": np.asarray(gaps), "t": np.int32(t_step),
    }


def minimize_stochastic(
    value_and_grad: Callable[[Array], tuple[Array, Array]],
    w0: Array,
    config: OptimizerConfig,
    *,
    chunked: ss.ChunkedHybrid,
    loss: PointwiseLoss,
    l2_weight: float,
    solver: str = "sdca",
    offsets: Optional[Array] = None,
    reg_mask: Optional[Array] = None,
    log: Callable[[str], None] = lambda m: None,
    value_only: Optional[Callable[[Array], Array]] = None,
    checkpoint_save: Optional[Callable[[dict], None]] = None,
    resume_state: Optional[dict] = None,
    prefetch_depth: int = 2,
    pin_budget: int = 0,
    num_devices: int = 1,
) -> OptResult:
    """Driver-loop stochastic solve behind the ``minimize_streaming``
    contract: same return type, same checkpoint/resume discipline, same
    telemetry sites.

    ``value_and_grad``/``value_only`` are the L2-WRAPPED streamed
    callables the coordinate already builds (``with_l2`` /
    ``with_l2_value``); ``l2_weight`` must match the λ folded into them
    — SDCA reads it for the dual step and the gap assembly, SGD for the
    step schedule and the gap surrogate. ``offsets`` is the full
    (padded_n,) residual array sliced per chunk for the dual pass (the
    wrapped callables close over their own copy).

    One ``opt_iter`` ledger row per ACCEPTED epoch carries ``gap``
    (finite, monotone-trending for SDCA); the ``photon_opt_duality_gap``
    gauge tracks it live; an armed watchdog gets both the standard
    ``observe`` feed and the gap gate (``observe_gap`` — ``gap <= tol``
    stops, non-finite raises). Convergence is gap-gated:
    ``gap <= config.tolerance · max(|f|, 1)``.

    ``num_devices`` fixes the GROUPING of the per-chunk gap-partial
    reduction (``gap.reduce_gap_partials``) so the certificate a D-device
    run reports is reproducible; the dual pass itself streams on the
    default device (sequential by nature).
    """
    if solver not in STOCHASTIC_SOLVERS:
        raise ValueError(f"unknown stochastic solver {solver!r}; "
                         f"expected one of {STOCHASTIC_SOLVERS}")
    if l2_weight <= 0.0:
        raise ValueError(
            f"stochastic solvers need l2_weight > 0 (the dual step, the "
            f"step schedule, and the gap certificate all rest on strong "
            f"convexity), got {l2_weight}")
    if solver == "sdca":
        if loss.name not in gap_mod.CONJUGATE_LOSSES:
            raise ValueError(
                f"sdca needs a loss with a cheap conjugate (have "
                f"{loss.name!r}, supported "
                f"{sorted(gap_mod.CONJUGATE_LOSSES)}); use solver='sgd'")
        if reg_mask is not None and \
                not bool(np.all(np.asarray(reg_mask) == 1.0)):
            raise ValueError(
                "sdca requires every coordinate regularized (w ≡ "
                "(1/λ)Σαᵢxᵢ has no unregularized analogue); drop the "
                "intercept exclusion or use solver='sgd'")

    d = int(w0.shape[0])
    rows = chunked.chunk_rows
    num_chunks = chunked.num_chunks
    padded_n = num_chunks * rows
    max_it = config.max_iterations
    led = obs.ledger()
    wd_cfg = obs.watchdog_config()
    wd = (ConvergenceWatchdog(wd_cfg) if wd_cfg is not None else None)
    mx = obs.metrics()
    v = (value_only if value_only is not None
         else (lambda w: value_and_grad(w)[0]))
    lam = jnp.asarray(l2_weight, jnp.float32)
    mask = (jnp.ones((d,), jnp.float32) if reg_mask is None
            else jnp.asarray(reg_mask, jnp.float32))
    dtype = ss.chunk_dtype(chunked.chunks[0])
    sampler = GapChunkSampler(chunked, pin_budget)
    t_step = 0  # SGD step counter (cumulative, rides the snapshot)
    t0_sched = num_chunks

    vals = np.full((max_it + 1,), np.nan, np.float32)
    gaps = np.full((max_it + 1,), np.nan, np.float32)
    if resume_state is not None:
        st = resume_state
        if st["w"].shape != (d,) or st["alpha"].shape != (padded_n,):
            raise ValueError(
                f"resume state shape mismatch: saved w {st['w'].shape} "
                f"/ alpha {st['alpha'].shape}, expected ({d},) / "
                f"({padded_n},) — the checkpoint was written under a "
                f"different configuration")
        w = jnp.asarray(st["w"], jnp.float32)
        alpha = np.array(st["alpha"], np.float32)
        fv, gap = float(st["fv"]), float(st["gap"])
        f0, gap0 = float(st["f0"]), float(st["gap0"])
        t_step = int(st["t"])
        start_it = int(st["it"]) + 1
        k = min(st["vals"].shape[0], max_it + 1)
        vals[:k], gaps[:k] = st["vals"][:k], st["gns"][:k]
        log(f"resuming streamed {solver} at epoch {start_it} "
            f"(f={fv:.6g}, gap={gap:.3g})")
    else:
        alpha = np.zeros((padded_n,), np.float32)
        if solver == "sdca":
            if bool(jnp.any(jnp.asarray(w0) != 0.0)):
                log("sdca ignores the warm start (w has no dual "
                    "representation); starting from (w, alpha) = 0")
            w = jnp.zeros((d,), jnp.float32)
            with obs.span("stochastic.initial_pass", cat="optim",
                          solver=solver):
                fv = float(v(w))
            # At (w, α) = (0, 0) the conjugate and α·offset sums vanish
            # (φ*(0) = 0 for both conjugate losses with {0,1}/real
            # labels), so gap₀ = P(0) exactly.
            gap = fv
        else:
            w = jnp.asarray(w0, jnp.float32)
            with obs.span("stochastic.initial_pass", cat="optim",
                          solver=solver):
                f_init, g_init = value_and_grad(w)
            fv = float(f_init)
            gap = gap_mod.sgd_gap_surrogate(
                float(jnp.linalg.norm(g_init)), l2_weight)
        f0, gap0 = fv, gap
        vals[0], gaps[0] = fv, gap
        start_it = 1

    w_pad = jnp.concatenate([w, jnp.zeros((1,), jnp.float32)])
    kernel = (_sdca_kernel(loss, dtype) if solver == "sdca" else None)
    vg_kernel = (ss._chunk_value_grad(loss, dtype) if solver == "sgd"
                 else None)
    scale_c = jnp.asarray(float(num_chunks), jnp.float32)

    converged = False
    it = start_it - 1
    try:
        for it in range(start_it, max_it + 1):
            t_iter = time.perf_counter()
            with obs.span("stochastic.epoch", cat="optim", it=it,
                          solver=solver):
                gn = None
                if solver == "sdca":
                    parts_rows = []
                    for i, ch, streamed in sampler.stream(prefetch_depth):
                        # Chaos seam (docs/ROBUSTNESS.md): the per-chunk
                        # stochastic update — a kill here must resume
                        # from the LAST epoch boundary's (w, α) snapshot
                        # to bit-identical coefficients.
                        flt.fire(flt.sites.OPT_DUAL_UPDATE, index=i)
                        off = ss._offsets_for(chunked, offsets, i, ch)
                        a_dev = jnp.asarray(alpha[i * rows:(i + 1) * rows])
                        w_pad, a_new, parts = kernel(w_pad, a_dev, off,
                                                     lam, ch)
                        # Same enqueue-scratch barrier as every streamed
                        # pass (ops/streaming_sparse.py).
                        jax.block_until_ready(w_pad)
                        # pml: allow[PML001] α is HOST-resident by design (a device-resident (padded_n,) dual would double the stream's HBM footprint); the chunk slice + (3,) partials ride home behind the per-chunk barrier
                        alpha[i * rows:(i + 1) * rows] = np.asarray(a_new)
                        # pml: allow[PML001] same by-design per-chunk copy as the α slice above
                        parts_rows.append(np.asarray(parts))
                        if streamed:
                            ss._delete_chunk(ch)
                    ss._collect_after_pass(chunked)
                    w = w_pad[:d]
                    # pml: allow[PML001] epoch-boundary value read is the BY-DESIGN host decision point (the gap assembly + convergence gate), one scalar per epoch
                    fv = float(v(w))
                    parts_arr = np.stack(parts_rows)
                    conj_sum = gap_mod.reduce_gap_partials(
                        parts_arr[:, 0], num_devices)
                    aoff_sum = gap_mod.reduce_gap_partials(
                        parts_arr[:, 1], num_devices)
                    # pml: allow[PML001] ‖w‖² closes the gap identity on host once per epoch
                    w_sq = float(jnp.dot(w, w))
                    gap = gap_mod.assemble_gap(fv, conj_sum, aoff_sum,
                                               l2_weight, w_sq)
                    sampler.update(parts_arr[:, 2])
                    v_passes, g_passes, dual_passes = 1, 0, 1
                else:
                    for i, ch, streamed in sampler.stream(prefetch_depth):
                        flt.fire(flt.sites.OPT_DUAL_UPDATE, index=i)
                        off = ss._offsets_for(chunked, offsets, i, ch)
                        _, g_chunk = vg_kernel(w, off, ch)
                        t_step += 1
                        eta = jnp.asarray(
                            1.0 / (l2_weight * (t_step + t0_sched)),
                            jnp.float32)
                        radius = jnp.asarray(
                            _SGD_TRUST_RADIUS / t_step, jnp.float32)
                        w = _sgd_step(w, g_chunk, eta, lam, scale_c,
                                      mask, radius)
                        jax.block_until_ready(w)
                        if streamed:
                            ss._delete_chunk(ch)
                    ss._collect_after_pass(chunked)
                    f_ep, g_ep = value_and_grad(w)
                    # pml: allow[PML001] epoch-boundary convergence read, one pair of scalars per epoch
                    fv = float(f_ep)
                    # Host f64 norm: early poisson iterates can carry
                    # per-row exp(z) gradients whose f32 sum-of-squares
                    # overflows to inf even though every element is
                    # finite.
                    # pml: allow[PML001] same epoch-boundary read as fv above
                    gn = float(np.linalg.norm(np.asarray(g_ep, np.float64)))
                    gap = gap_mod.sgd_gap_surrogate(gn, l2_weight)
                    w_pad = jnp.concatenate([w, jnp.zeros((1,),
                                                          jnp.float32)])
                    v_passes, g_passes, dual_passes = 0, 2, 0
                # Watchdog chaos seam (docs/ROBUSTNESS.md): a "nan"
                # fault spec here is the injected form of a numerically
                # sick gap certificate.
                gap = flt.poison_scalar(flt.sites.OPT_GAP_CHECK, gap)
                if mx is not None:
                    mx.gauge("photon_opt_duality_gap").set(gap)
                vals[it], gaps[it] = fv, gap
                seconds = time.perf_counter() - t_iter
                log(f"epoch {it}: f={fv:.6g} gap={gap:.3g} "
                    f"[{solver}]")
                if led is not None:
                    # Append-as-produced, exactly like the L-BFGS rows —
                    # a SIGKILL one epoch later still leaves this point
                    # (and its gap) on the curve.
                    led.record("opt_iter", opt=f"{solver}-stream",
                               iteration=it, value=fv,
                               grad_norm=(gn if gn is not None else gap),
                               gap=gap, value_passes=v_passes,
                               grad_passes=g_passes,
                               dual_passes=dual_passes,
                               seconds=round(seconds, 6),
                               **transfer_totals())
                if checkpoint_save is not None:
                    # Epoch boundary = the resume point; w AND α go in.
                    checkpoint_save(snapshot_stochastic(
                        w, alpha, it, fv, gap, f0, gap0, vals, gaps,
                        t_step))
                if wd is not None:
                    # After the checkpoint write (a "raise" verdict
                    # still leaves a resumable snapshot), the standard
                    # feed first, then the gap gate.
                    if wd.observe(it, fv, gap, seconds) == "stop":
                        log(f"epoch {it}: watchdog early stop")
                        break
                    if wd.observe_gap(it, gap) == "stop":
                        log(f"epoch {it}: duality gap "
                            f"{gap:.3g} <= watchdog tolerance — stopping")
                        break
                elif not np.isfinite(gap):
                    # No watchdog armed: a non-finite certificate still
                    # must not spin the remaining epochs.
                    log(f"epoch {it}: non-finite gap ({gap!r}); "
                        f"stopping")
                    break
                if gap <= config.tolerance * max(abs(fv), 1.0):
                    converged = True
                    break
    finally:
        sampler.release()

    return OptResult(
        w=w,
        value=jnp.asarray(fv, jnp.float32),
        # The gap IS the convergence certificate of the stochastic path;
        # it rides the grad_norm slots of the shared result type.
        grad_norm=jnp.asarray(gap, jnp.float32),
        iterations=jnp.asarray(it, jnp.int32),
        # One full-objective pass per epoch this call ran, and the
        # starting one unless the call resumed past it.
        evaluations=jnp.asarray(
            it - start_it + 1 + (resume_state is None), jnp.int32),
        converged=jnp.asarray(converged),
        value_history=jnp.asarray(vals),
        grad_norm_history=jnp.asarray(gaps),
    )
