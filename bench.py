"""Benchmark: GLM training throughput on the current accelerator.

Primary BASELINE.json metric — **GLM gradient-step samples/sec/chip** on the
fixed-effect data-parallel path (the reference's
``DistributedGLMLossFunction.treeAggregate`` hot loop as one fused
jit-compiled objective) — plus, as secondaries: a FULL jitted L-BFGS
iteration (value+grad + two-loop + strong-Wolfe line search) and TRON
iteration with donated buffers, the sparse/Criteo gradient step (1M-feature
ELL), the Pallas-vs-XLA scatter comparison, and the GAME coordinate-descent
sweep.

Measurement discipline: every timing chains iterations through a data
dependency and forces ONE host read-back at the end, at two different
iteration counts; the reported per-step time is the SLOPE
(t_big − t_small)/(iters_big − iters_small), which cancels the constant
dispatch and read-back cost and cannot be fooled by asynchronous
dispatch. Achieved FLOP/s and bytes/s
are printed next to samples/sec so the numbers can be audited against peak
(v5e: ~197 bf16 TFLOP/s, ~0.8 TB/s HBM).

``vs_baseline``: the reference publishes no numbers (BASELINE.md), so the
ratio is against an in-process numpy CPU implementation of the same fused
value+gradient pass.

Prints ONE JSON line.
"""

import json
import os
import sys
import time

import numpy as np

from photon_ml_tpu.utils.compile_cache import enable_compilation_cache

enable_compilation_cache()


def _progress(msg: str) -> None:
    """Stderr progress marker (stdout stays one JSON line). Cold compiles
    can take minutes in total; without these markers a slow run is
    indistinguishable from a hung one."""
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


# --- host-line validity gating (BENCH r3–r5: a recurring ~4× builder-vs-
# driver spread on host-staging lines was surfaced but never DETECTED —
# the 1.5×-spread contention guard catches jitter, not sustained load).
# Two gates, both recorded per line so a committed JSON self-describes:
#   * load average at measurement start above LOAD_GATE (the r05 driver
#     capture ran at 0.83 on this 1-core box and measured 4× slow);
#   * the calibration micro-workload (run once per fresh-host suite)
#     exceeding CALIBRATION_GATE × the committed clean-box reference —
#     sustained background load that a momentary loadavg can miss.
# An invalid line still reports its number, but carries ``<key>_valid:
# false`` + the reason; check_bench_regression treats it as
# reported-only.

# Min-of-5 of _calibration_workload on this 1-core CI box, measured
# near-idle (load ~0.2). Machine-specific by construction — re-measure
# when the fleet changes.
HOST_CALIBRATION_REF_S = 0.34
LOAD_GATE = 0.75
CALIBRATION_GATE = 1.5

_HOST_CAL = {"factor": None}


def _calibration_workload():
    """Fixed, allocation-light, sort-dominated — the same instruction
    mix as the staging host sections it calibrates for."""
    rng = np.random.default_rng(1234)
    a = rng.integers(0, 1 << 30, size=2_000_000)
    for _ in range(3):
        a = np.sort(a, kind="stable")[::-1].copy()


def host_calibration(out):
    """Run the calibration micro-workload and record the host's current
    speed factor vs the committed clean reference; later ``_host_line``
    calls gate their validity on it."""
    lo, samples, _ = _host_timed(_calibration_workload, n=3,
                                 label="host_calibration")
    factor = lo / HOST_CALIBRATION_REF_S
    _HOST_CAL["factor"] = factor
    out["host_calibration_seconds"] = round(lo, 3)
    out["host_calibration_samples"] = samples
    out["host_calibration_factor"] = round(factor, 2)
    if factor > CALIBRATION_GATE:
        _progress(f"WARNING host calibration {lo:.2f}s is {factor:.1f}x "
                  f"the clean-box reference {HOST_CALIBRATION_REF_S}s — "
                  "host lines in this capture will be marked invalid")
    return factor


def _host_timed(section, n=3, label=""):
    """Min-of-N timing for a HOST-side section with a contention guard.

    Device dispatch jitter doesn't apply to host work, but a 1-core box does: a
    background thread (device-runtime housekeeping, another process) can
    inflate a single run 3-5× — the round-4 driver capture recorded the
    10M-row projection pass at 52 s where its standalone time is ~11 s.
    Min of N ≥ 3 runs is the contention-robust estimator; ALL samples and
    the 1-min load average are returned so a committed JSON shows when a
    capture was dirty instead of silently blessing one roll.

    Returns (min_seconds, samples, contended) — ``contended`` is True when
    the spread exceeds 1.5× the minimum, i.e. the min itself may still be
    inflated and the line should not be quoted as a clean measurement.
    """
    times = []
    for _ in range(n):
        t0 = time.perf_counter()
        section()
        times.append(time.perf_counter() - t0)
    lo, hi = min(times), max(times)
    contended = hi > 1.5 * lo + 0.05
    if contended:
        _progress(f"WARNING {label or 'host section'}: timing spread "
                  f"{lo:.2f}-{hi:.2f}s across {n} runs, load "
                  f"{os.getloadavg()[0]:.2f} — host contended; even the "
                  "min may be inflated")
    return lo, [round(t, 3) for t in times], contended


def _host_line(out, key, section, n=3):
    """Record one host-side bench line: ``key`` = min of n runs,
    ``key_samples`` = every run, ``key_contended`` only when dirty, and
    ``key_valid: false`` + reason when a load/calibration gate fired
    (the line then documents the environment instead of polluting the
    cross-round trajectory)."""
    load = os.getloadavg()[0]
    lo, samples, contended = _host_timed(section, n=n, label=key)
    out[key] = round(lo, 2)
    out[f"{key}_samples"] = samples
    if contended:
        out[f"{key}_contended"] = True
    reasons = []
    if load > LOAD_GATE:
        reasons.append(f"load_avg_1m {load:.2f} > {LOAD_GATE}")
    factor = _HOST_CAL.get("factor")
    if factor is not None and factor > CALIBRATION_GATE:
        reasons.append(f"host calibration {factor:.1f}x the clean-box "
                       f"reference")
    if reasons:
        out[f"{key}_valid"] = False
        out[f"{key}_invalid_reason"] = "; ".join(reasons)
    return lo


def _cold_line(out, key, section, warm_n=2):
    """One-time staging cost: the FIRST run in this (fresh) process is
    the number — min-of-N would report the warm re-run instead (observed
    5–30× smaller: allocator/page-cache warm-up dominates these
    allocation-heavy sections). Warm re-runs are recorded alongside for
    contrast (``key_warm``); run this only from a fresh subprocess, where
    'first' genuinely means cold."""
    t0 = time.perf_counter()
    section()
    cold = time.perf_counter() - t0
    warm = []
    for _ in range(warm_n):
        t0 = time.perf_counter()
        section()
        warm.append(time.perf_counter() - t0)
    out[key] = round(cold, 2)
    out[f"{key}_samples"] = [round(cold, 3)] + [round(w, 3) for w in warm]
    out[f"{key}_warm"] = round(min(warm), 2)
    return cold


def _numpy_value_grad(X, y, w):
    z = X @ w
    p = 1.0 / (1.0 + np.exp(-z))
    l = np.logaddexp(0.0, z) - y * z
    r = p - y
    return l.sum(), X.T @ r


def _slope(run, iters_small, iters_large):
    """Per-iteration seconds via the dependency-chain slope method.

    The span must be wide enough that (iters_large − iters_small) × step
    time dwarfs dispatch jitter — callers pick spans per workload.
    Each endpoint takes the MIN of 5 runs: dispatch delay is additive and
    heavy-tailed, so
    the minimum is the contention-robust estimator of the true cost;
    medians let one bad tail at either endpoint swing the difference.
    """
    run(iters_small)  # warm-up / compile
    t_small = min(run(iters_small) for _ in range(5))
    t_large = min(run(iters_large) for _ in range(5))
    return max(t_large - t_small, 1e-9) / (iters_large - iters_small)


def bench_gradient_step(n=1 << 19, d=256):
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.data.batch import LabeledBatch
    from photon_ml_tpu.ops import aggregators as agg
    from photon_ml_tpu.ops import losses

    rng = np.random.default_rng(0)
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = rng.integers(0, 2, size=n).astype(np.float32)

    step = jax.jit(lambda ww, bb: agg.value_and_gradient(
        losses.LOGISTIC, ww, bb))

    def make_run(batch):
        def run(iters):
            w = jnp.zeros((d,), jnp.float32)
            t0 = time.perf_counter()
            for _ in range(iters):
                _, g = step(w, batch)
                w = w - 1e-9 * g  # chain: next step depends on this one
            np.asarray(w)  # force the whole chain
            return time.perf_counter() - t0
        return run

    dt = _slope(make_run(jax.device_put(LabeledBatch.build(X, y))), 20, 220)
    # bf16 feature storage: halves the streamed bytes, f32 MXU accumulation.
    # The bf16 step is ~2x faster, so the span doubles to keep the timed
    # window the same length relative to dispatch jitter.
    dt16 = _slope(make_run(jax.device_put(
        LabeledBatch.build(X, y, feature_dtype=jnp.bfloat16))), 20, 420)
    samples_per_sec = n / dt
    flops = 4.0 * n * d  # X@w and X.T@r, 2nd each
    bytes_moved = 2.0 * 4 * n * d  # X streamed twice (f32)

    # CPU numpy baseline (subsampled for time):
    n_cpu = min(n, 1 << 16)
    Xc, yc, wc = X[:n_cpu], y[:n_cpu], np.zeros(d, np.float32)
    _numpy_value_grad(Xc, yc, wc)
    t0 = time.perf_counter()
    reps = 3
    for _ in range(reps):
        _numpy_value_grad(Xc, yc, wc)
    cpu_dt = (time.perf_counter() - t0) / reps
    return {
        "samples_per_sec": samples_per_sec,
        "bf16_samples_per_sec": n / dt16,
        "achieved_gflops": flops / dt / 1e9,
        "achieved_gbytes_per_sec": bytes_moved / dt / 1e9,
        "cpu_numpy_samples_per_sec": n_cpu / cpu_dt,
    }


def bench_optimizer_steps(n=1 << 17, d=256):
    """Per-iteration cost of the FULL compiled optimizers (value+grad +
    history update + line search / CG), donated warm start.

    The problem is a deliberately ill-conditioned logistic fit and the
    tolerance is negative (convergence checks can never fire), so every
    requested iteration actually executes; the slope denominator uses the
    EXECUTED iteration counts reported by the solver, guarding against
    early line-search stalls silently zeroing the measurement.
    """
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.data.batch import LabeledBatch
    from photon_ml_tpu.ops import aggregators as agg
    from photon_ml_tpu.ops import losses
    from photon_ml_tpu.optim import (OptimizerConfig, minimize_lbfgs,
                                     minimize_tron, with_l2, with_l2_hvp)

    rng = np.random.default_rng(1)
    X = rng.normal(size=(n, d)).astype(np.float32)
    X *= np.logspace(0, 3, d, dtype=np.float32)  # condition ~1e6 in X'X
    w_true = rng.normal(size=d) / np.logspace(0, 3, d)
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-(X @ w_true)))).astype(
        np.float32)
    batch = jax.device_put(LabeledBatch.build(X, y))
    vg = with_l2(lambda w: agg.value_and_gradient(losses.LOGISTIC, w, batch),
                 1e-3)
    hvp = with_l2_hvp(
        lambda w, v: agg.hessian_vector(losses.LOGISTIC, w, v, batch), 1e-3)

    out = {}
    for name, solver in (
        ("lbfgs", lambda w0, k: minimize_lbfgs(
            vg, w0, OptimizerConfig(max_iterations=k, tolerance=-1.0))),
        ("tron", lambda w0, k: minimize_tron(
            vg, hvp, w0, OptimizerConfig(max_iterations=k, tolerance=-1.0,
                                         max_cg_iterations=10))),
    ):
        jitted = {}

        def run(iters, _solver=solver, _jitted=jitted):
            if iters not in _jitted:
                _jitted[iters] = jax.jit(
                    lambda w0, _k=iters: (
                        lambda r: (r.w, r.iterations))(_solver(w0, _k)),
                    donate_argnums=0)
            t0 = time.perf_counter()
            w, it = _jitted[iters](jnp.zeros((d,), jnp.float32))
            np.asarray(w)
            return time.perf_counter() - t0, int(it)

        # Spans wide enough that the timed difference (Δiters × step time:
        # ~200 ms for both solvers) dwarfs heavy-tailed dispatch jitter;
        # the while_loop body compiles once regardless
        # of the iteration bound, so wide spans cost only run time.
        spans = {"lbfgs": (10, 510), "tron": (8, 64)}[name]
        k_small, k_large = spans
        run(k_small)  # warm-up / compile BOTH programs before timing
        run(k_large)
        t_small, e_small = min(run(k_small) for _ in range(5))
        t_large, e_large = min(run(k_large) for _ in range(5))
        executed = max(e_large - e_small, 1)
        out[f"{name}_iteration_ms"] = max(t_large - t_small, 0.0) \
            / executed * 1e3
        out[f"{name}_executed_iterations"] = (e_small, e_large)
    return out


def bench_sparse(n=1 << 17, d=1_000_000, nnz=32):
    """Criteo-regime sparse gradient step (BASELINE config 5).

    Three layouts of the SAME objective: the ELL gather/scatter pipeline
    (the multi-chip shard_map path), and the hybrid hot-dense/cold-class
    layout (ops/hybrid_sparse.py — the single-chip default) in f32 and
    bf16. The ELL figure documents the XLA random-access wall the hybrid
    split exists to avoid.
    """
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.data import sparse as sp
    from photon_ml_tpu.ops import hybrid_sparse as hs
    from photon_ml_tpu.ops import losses, sparse_aggregators as sagg

    batch, _ = sp.synthetic_sparse(n, d, nnz, seed=2)
    out = {}

    b_dev = jax.device_put(batch)
    ell_step = jax.jit(lambda ww, bb: sagg.value_and_gradient(
        losses.LOGISTIC, ww, bb))

    def run_ell(iters):
        w = jnp.zeros((d,), jnp.float32)
        t0 = time.perf_counter()
        for _ in range(iters):
            _, g = ell_step(w, b_dev)
            w = w - 1e-9 * g
        np.asarray(w[:8])
        return time.perf_counter() - t0

    dt_ell = _slope(run_ell, 3, 23)
    out["sparse_ell_samples_per_sec"] = round(n / dt_ell)

    hyb_step = jax.jit(lambda ww, hb: hs.value_and_gradient(
        losses.LOGISTIC, ww, hb))
    for name, dtype in (("", jnp.float32), ("bf16_", jnp.bfloat16)):
        # Staging cost is measured COLD in bench_fresh_host_suite (a
        # fresh subprocess) — timing it here, mid-device-phase in a warm
        # process, produced the 11.65→37.04→20.09 swings of rounds 3–4.
        hb = hs.build_hybrid(batch, feature_dtype=dtype)
        if not name:
            out["sparse_hybrid_hot_cols"] = hb.num_hot

        def run_hyb(iters, _hb=hb):
            w = jnp.zeros((d,), jnp.float32)
            t0 = time.perf_counter()
            for _ in range(iters):
                _, g = hyb_step(w, _hb)
                w = w - 1e-9 * g
            np.asarray(w[:8])
            return time.perf_counter() - t0

        dt = _slope(run_hyb, 3, 23)
        out[f"sparse_{name}samples_per_sec"] = n / dt
        out[f"sparse_{name}gnnz_per_sec"] = n * nnz / dt / 1e9

    # The data-parallel composition of the hybrid layout (HybridShards +
    # shard_map psum) on this chip's 1-device mesh: demonstrates the
    # multi-device code path runs at the single-layout rate (the psum is
    # a no-op at S=1; per-shard work is identical).
    from photon_ml_tpu.parallel import sparse_objective as sobj
    from photon_ml_tpu.parallel import sparse_problem as spp
    from photon_ml_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(num_data=1, devices=jax.devices()[:1])
    shb = spp.shard_hybrid(hs.build_hybrid_shards(batch, 1), mesh)
    # The staged batch is a jit ARGUMENT (a closed-over device array would
    # bake the ~GB hot block into the executable as a constant).
    shard_vg = jax.jit(lambda ww, sb: sobj.make_hybrid_value_and_gradient(
        losses.LOGISTIC, mesh, sb)(ww))

    def run_shard(iters):
        w = jnp.zeros((d,), jnp.float32)
        t0 = time.perf_counter()
        for _ in range(iters):
            _, g = shard_vg(w, shb)
            w = w - 1e-9 * g
        np.asarray(w[:8])
        return time.perf_counter() - t0

    dt_sh = _slope(run_shard, 3, 23)
    out["sparse_hybrid_sharded_samples_per_sec"] = round(n / dt_sh)
    return out


def _sparse_re_inputs(n=100_000, d=200_000, num_entities=1000, nnz=8):
    """Shared dataset+config for the sparse-RE fit bench and the cold
    staging line (same shapes so both describe the same workload)."""
    from photon_ml_tpu.data.game_data import GameDataset, SparseShard
    from photon_ml_tpu.optim import OptimizerConfig
    from photon_ml_tpu.optim.problem import GLMOptimizationConfiguration
    from photon_ml_tpu.optim.regularization import (RegularizationContext,
                                                    RegularizationType)

    rng = np.random.default_rng(3)
    ids = rng.integers(0, num_entities, n).astype(np.int32)
    pools = rng.integers(0, d, (num_entities, 64)).astype(np.int32)
    idx = np.sort(pools[ids[:, None], rng.integers(0, 64, (n, nnz))],
                  axis=1)
    dup = np.zeros_like(idx, bool)
    dup[:, 1:] = idx[:, 1:] == idx[:, :-1]
    vals = rng.normal(size=(n, nnz)).astype(np.float32)
    idx[dup] = d
    vals[dup] = 0.0
    y = (rng.random(n) < 0.5).astype(np.float32)
    ds = GameDataset(
        response=y, offsets=np.zeros(n, np.float32),
        weights=np.ones(n, np.float32),
        feature_shards={"re": SparseShard(idx, vals, d)},
        entity_ids={"userId": ids}, num_entities={"userId": num_entities},
        intercept_index={})
    cfg = GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(max_iterations=15, tolerance=1e-7),
        regularization=RegularizationContext(RegularizationType.L2, 1.0))
    return ds, cfg


def bench_sparse_random_effect(n=100_000, d=200_000, num_entities=1000,
                               nnz=8):
    """Sparse random-effect fit at large d (SURVEY §2.1 sparse RE):
    steady-state per-train_model time (staging is measured cold in
    bench_fresh_host_suite)."""
    from photon_ml_tpu.game.coordinates import RandomEffectCoordinate
    from photon_ml_tpu.ops import losses
    from photon_ml_tpu.parallel.mesh import make_mesh

    ds, cfg = _sparse_re_inputs(n, d, num_entities, nnz)
    import shutil
    import tempfile

    # Staging cost is measured COLD in bench_fresh_host_suite (fresh
    # subprocess); here the coordinate is just built for the fit timing.
    res: dict = {}
    coord = RandomEffectCoordinate(ds, "userId", "re", losses.LOGISTIC,
                                   cfg, make_mesh()).wait_staged()
    # Staged data, not compiled programs.
    cache_dir = tempfile.mkdtemp(prefix="pml_staged_")
    try:
        RandomEffectCoordinate(ds, "userId", "re", losses.LOGISTIC,
                               cfg, make_mesh(),
                               staging_cache_dir=cache_dir
                               ).wait_staged()  # populates
        # Warm path: a fresh coordinate on the same data memory-maps the
        # staged blocks from the digest-keyed cache instead of re-running
        # the projection pass. wait_staged() = the staging barrier (the
        # pipeline otherwise defers shard loads to the first fit).
        _host_line(res, "sparse_re_staging_warm_seconds",
                   lambda: RandomEffectCoordinate(
                       ds, "userId", "re", losses.LOGISTIC, cfg,
                       make_mesh(),
                       staging_cache_dir=cache_dir).wait_staged())
        # bf16 bucket-block storage: halves the staged blocks' HBM, f32 MXU
        # accumulation (same contract as the dense fixed path). The f32
        # staging cache is dtype-independent (cast happens after load), so
        # reuse it rather than re-paying the projection pass.
        coord16 = RandomEffectCoordinate(ds, "userId", "re",
                                         losses.LOGISTIC, cfg, make_mesh(),
                                         staging_cache_dir=cache_dir,
                                         feature_dtype="bfloat16"
                                         ).wait_staged()
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)
    off = np.zeros(n, np.float32)

    def make_run(c):
        def run(iters):
            t0 = time.perf_counter()
            model = None
            for _ in range(iters):
                model = c.train_model(off, initial=model)
            np.asarray(model.means[:1])
            return time.perf_counter() - t0
        return run

    dt = _slope(make_run(coord), 1, 4)
    dt16 = _slope(make_run(coord16), 1, 4)
    res.update({
        "sparse_re_fit_seconds": round(dt, 3),
        "sparse_re_bf16_fit_seconds": round(dt16, 3),
        "sparse_re_config": f"n={n} d={d} entities={num_entities}",
    })
    return res


def bench_host_staging(n=10_000_000, num_entities=1_000_000, d=1_000_000,
                       nnz=8):
    """Host-side staging at the design-target scale (round-2 verdict:
    unmeasured): build_bucketing + per-entity subspace projection for a
    random effect over 10M rows, 1M entities, d=1M sparse features —
    all-numpy work that happens once per fit, before any device step.

    ``staging_projection_seconds`` stays the SERIAL whole-bucket pass
    (comparable across bench rounds); the ``*_parallel_*`` lines measure
    the sharded worker-pool pipeline (game/staging.py) at
    min(8, host cores) workers — the projection-wall fix, targeted at
    ≥4× on an 8-core host with byte-identical staged arrays (asserted in
    tests/test_staging_parallel.py)."""
    from photon_ml_tpu.data.game_data import SparseShard
    from photon_ml_tpu.game import staging as stg
    from photon_ml_tpu.game.buckets import build_bucketing
    from photon_ml_tpu.game.projector import (all_bucket_triplets,
                                              build_bucket_projection,
                                              shard_coo)

    rng = np.random.default_rng(11)
    ids = rng.integers(0, num_entities, n).astype(np.int32)
    idx = np.sort(rng.integers(0, d, (n, nnz)).astype(np.int32), axis=1)
    dup = np.zeros_like(idx, bool)
    dup[:, 1:] = idx[:, 1:] == idx[:, :-1]
    vals = rng.normal(size=(n, nnz)).astype(np.float32)
    idx[dup] = d
    vals[dup] = 0.0
    shard = SparseShard(idx, vals, d)

    out: dict = {"staging_load_avg_1m": round(os.getloadavg()[0], 2)}
    # Calibration FIRST: every _host_line below gates its validity on it.
    host_calibration(out)
    bucketing = build_bucketing(ids, num_entities)  # warm result for below

    def _bucketing():
        build_bucketing(ids, num_entities)

    def _projection():
        coo = shard_coo(shard)
        trips = all_bucket_triplets(bucketing.buckets, shard, coo)
        for bk, trip in zip(bucketing.buckets, trips):
            build_bucket_projection(bk, shard, None, triplets=trip)

    workers = min(8, os.cpu_count() or 1)

    def _projection_parallel():
        stg.project_buckets(bucketing, shard, intercept_index=None,
                            config=stg.StagingConfig(workers=workers))

    tb = _host_line(out, "staging_bucketing_seconds", _bucketing)
    tp = _host_line(out, "staging_projection_seconds", _projection)
    tpp = _host_line(out, "staging_projection_parallel_seconds",
                     _projection_parallel)
    out["staging_workers"] = workers
    out["staging_parallel_speedup"] = round(tp / max(tpp, 1e-9), 2)
    out["staging_parallel_efficiency"] = round(
        tp / max(tpp, 1e-9) / workers, 3)
    out["staging_seconds_10m_rows_1m_entities"] = round(tb + tp, 2)
    return out


def bench_ingest_cold_fit(n=20_000, nnz=20, entities=1000):
    """End-to-end cold fit through the ingestion layer: Avro file →
    block-parallel ingest (photon_ml_tpu/ingest) → random-effect
    coordinate staging → per-entity fits, against its standalone
    components. The overlap invariant the regression gate checks
    (dev-scripts/check_bench_regression.py):

        end_to_end_cold_fit_seconds <= 1.15 x max(ingest, staging+fit)

    With parallel decode the serial-decode wall stops serializing in
    front of the fit — demonstrable only where cores exist to fan the
    decode over, so the gate enforces on >= 4-core hosts and reports
    on this 1-core CI box (docs/INGEST.md, same caveat as the staging
    multi-worker scaling note in docs/STAGING.md). The warm line runs
    the same flow against a populated ingest cache."""
    import shutil
    import tempfile

    import jax

    from photon_ml_tpu import ingest as ing
    from photon_ml_tpu.avro import schemas
    from photon_ml_tpu.avro.container import DataFileWriter
    from photon_ml_tpu.avro.data_reader import (AvroDataReader,
                                                FeatureShardConfig)
    from photon_ml_tpu.game.coordinates import RandomEffectCoordinate
    from photon_ml_tpu.ops import losses
    from photon_ml_tpu.optim import OptimizerConfig
    from photon_ml_tpu.optim.problem import GLMOptimizationConfiguration
    from photon_ml_tpu.optim.regularization import (RegularizationContext,
                                                    RegularizationType)
    from photon_ml_tpu.parallel.mesh import make_mesh

    rng = np.random.default_rng(13)
    # Dense low-d shard: decode (nnz varints/doubles per record)
    # dominates the fold and the per-entity solves stay light — the
    # decode-bound side of the pipeline, where the ingestion layer is
    # the wall being measured.
    recs = [{
        "uid": i, "label": float(rng.integers(0, 2)),
        "weight": 1.0, "offset": 0.0,
        "features": [{"name": f"x{rng.integers(0, 32)}", "term": "",
                      "value": float(rng.normal())} for _ in range(nnz)],
        "metadataMap": {"userId": f"u{rng.integers(0, entities)}"},
    } for i in range(n)]
    td = tempfile.mkdtemp(prefix="pml_ingest_bench_")
    out: dict = {}
    try:
        p = os.path.join(td, "train.avro")
        with DataFileWriter(p, schemas.TRAINING_EXAMPLE_AVRO,
                            codec="deflate", block_records=1024) as w:
            for r in recs:
                w.append(r)
        cfgs = {"re": FeatureShardConfig(("features",), True)}
        workers = min(8, os.cpu_count() or 1)
        mesh = make_mesh()
        opt = GLMOptimizationConfiguration(
            optimizer=OptimizerConfig(max_iterations=15, tolerance=1e-7),
            regularization=RegularizationContext(
                RegularizationType.L2, 1.0))
        off = np.zeros(n, np.float32)

        def read(cfg):
            return AvroDataReader().read(
                p, cfgs, random_effect_types=["userId"], ingest=cfg)[0]

        def fit(ds):
            c = RandomEffectCoordinate(ds, "userId", "re",
                                       losses.LOGISTIC, opt, mesh)
            jax.block_until_ready(c.train_model(off).means)

        # Warm the jit caches first: a compile inside a timed region
        # would swamp every comparison below.
        ds0 = read(ing.IngestConfig(workers=1, chunk_records=1 << 30))
        fit(ds0)

        # Standalone components: the serial-decode reference (the wall
        # the parallel pipeline attacks) and staging+fit on resident data.
        t_ingest = _host_line(
            out, "ingest_cold_seconds",
            lambda: read(ing.IngestConfig(workers=1,
                                          chunk_records=1 << 30)))
        t_fit = _host_line(out, "staging_plus_fit_seconds",
                           lambda: fit(ds0))
        # The pipelined end-to-end flow (parallel decode feeding the
        # coordinate).
        par = ing.IngestConfig(workers=workers, chunk_records=2048)
        t_e2e = _host_line(out, "end_to_end_cold_fit_seconds",
                           lambda: fit(read(par)))
        out["end_to_end_overlap_ratio"] = round(
            t_e2e / max(max(t_ingest, t_fit), 1e-9), 3)
        # Warm restart: same flow against a populated ingest cache.
        cache = os.path.join(td, "icache")
        warm_cfg = ing.IngestConfig(workers=workers, chunk_records=2048,
                                    cache_dir=cache)
        fit(read(warm_cfg))  # populate
        t_warm = _host_line(out, "end_to_end_warm_fit_seconds",
                            lambda: fit(read(warm_cfg)))
        out["end_to_end_warm_speedup"] = round(
            t_e2e / max(t_warm, 1e-9), 2)
        out["ingest_bench_cores"] = os.cpu_count() or 1
    finally:
        shutil.rmtree(td, ignore_errors=True)
    return out


def bench_fresh_host_suite():
    """Everything that must be measured in a FRESH process, in one
    subprocess pass: the 10M-row staging (min-of-3 — its host sorts
    dominate, cold ≈ warm) and the COLD one-time staging lines (hybrid
    build, sparse-RE coordinate construction — allocation-heavy sections
    whose warm re-runs measure 5–30× faster, so min-of-N would misreport
    them; see _cold_line)."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.data import sparse as sp
    from photon_ml_tpu.game.coordinates import RandomEffectCoordinate
    from photon_ml_tpu.ops import hybrid_sparse as hs
    from photon_ml_tpu.ops import losses
    from photon_ml_tpu.parallel.mesh import make_mesh

    out = bench_host_staging()

    batch, _ = sp.synthetic_sparse(1 << 17, 1_000_000, 32, seed=2)
    # block: device_put is async — staging "done" means the blocks are
    # resident, not merely enqueued.
    _cold_line(out, "sparse_hybrid_staging_seconds",
               lambda: jax.block_until_ready(
                   hs.build_hybrid(batch, feature_dtype=jnp.float32)))

    ds, cfg = _sparse_re_inputs()
    _cold_line(out, "sparse_re_staging_seconds",
               lambda: RandomEffectCoordinate(
                   ds, "userId", "re", losses.LOGISTIC, cfg,
                   make_mesh()).wait_staged())

    # Pipelined handoff overlap (sparse-RE config): the barrier path
    # stages everything then fits; the pipelined path lets the first
    # train_model consume shards while later ones still project.
    # overlap_efficiency = hidden staging time / hideable staging time
    # (1.0 = staging fully behind the fits; ~0 on a 1-core host where
    # producer and consumer share the core).
    off = np.zeros(ds.num_rows, np.float32)
    # Warm the jit caches first: the fit kernels compile once per process
    # (several seconds), and a compile inside either timed region would
    # swamp the staging/fit overlap being measured.
    warm = RandomEffectCoordinate(ds, "userId", "re", losses.LOGISTIC,
                                  cfg, make_mesh())
    jax.block_until_ready(warm.train_model(off).means)
    t0 = time.perf_counter()
    c_bar = RandomEffectCoordinate(ds, "userId", "re", losses.LOGISTIC,
                                   cfg, make_mesh()).wait_staged()
    t_stage = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(c_bar.train_model(off).means)
    t_fit = time.perf_counter() - t0
    t0 = time.perf_counter()
    c_pipe = RandomEffectCoordinate(ds, "userId", "re", losses.LOGISTIC,
                                    cfg, make_mesh())
    jax.block_until_ready(c_pipe.train_model(off).means)
    t_pipe = time.perf_counter() - t0
    out["staging_pipeline_barrier_seconds"] = round(t_stage + t_fit, 3)
    out["staging_pipeline_overlapped_seconds"] = round(t_pipe, 3)
    out["staging_overlap_efficiency"] = round(min(1.0, max(
        0.0, t_stage + t_fit - t_pipe) / max(min(t_stage, t_fit), 1e-9)), 3)

    from photon_ml_tpu.avro import native_decode

    if native_decode.native_available():
        # Ingestion layer in the same fresh process (decode rates +
        # cache lines, then the end-to-end cold-fit overlap invariant) —
        # dev-scripts/check_bench_regression.py reads these from the
        # --run-staging tail.
        out.update(bench_avro_ingest())
        out.update(bench_ingest_cold_fit())
    return out


def _require_tpu(section: str) -> None:
    """A kernel timing comes from the chip or is not made."""
    import jax

    if jax.default_backend() != "tpu":
        raise RuntimeError(
            f"{section} times compiled Pallas programs and needs the TPU "
            f"backend; this is {jax.default_backend()!r}")


def bench_pallas_scatter(n=1 << 17, k=32, d=512):
    """Pallas compare+accumulate scatter vs XLA sort/segment scatter at the
    moderate-d regime the kernel targets. Fails off the TPU: the Mosaic
    kernel doesn't lower elsewhere, and an interpreter time is not a
    device number."""
    import jax
    import jax.numpy as jnp

    _require_tpu("bench_pallas_scatter")

    from photon_ml_tpu.ops.pallas_sparse import scatter_rowterm

    rng = np.random.default_rng(3)
    idx = jnp.asarray(rng.integers(0, d, (n, k)).astype(np.int32))
    rv = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32))

    xla = jax.jit(
        lambda i, v: jnp.zeros((d + 1,), jnp.float32)
        .at[i.reshape(-1)].add(v.reshape(-1))[:d])

    out = {}
    for name, f in (("pallas", lambda i, v: scatter_rowterm(i, v, d)),
                    ("xla", xla)):
        def run(iters, _f=f):
            v = rv
            t0 = time.perf_counter()
            for _ in range(iters):
                o = _f(idx, v)
                v = rv * (1.0 + 1e-20 * o[0])  # chain
            np.asarray(o[:4])
            return time.perf_counter() - t0

        out[f"scatter_{name}_d{d}_us"] = _slope(run, 5, 45) * 1e6
    return out


def bench_kernels():
    """Fused-vs-XLA sweep over every registry kernel (docs/KERNELS.md
    "The sweep workflow") — the evidence a registry default flip must
    cite. For each kernel in ops/kernels/ the sweep times the Pallas
    program against its registered XLA reference at the bench shapes
    and computes the parity delta between the two.

    Fails off the TPU: there the Pallas programs only run through the
    interpreter, whose wall is not a device number (tier-1 and
    dev-scripts/kernel_smoke.py hold interpret-mode parity instead).
    Parity deltas are computed next to every timing.

    ``kernel_defaults_flipped`` carries the kernels whose registered
    default is ON — the committed claim "the sweep showed a win here" —
    which is exactly the set check_bench_regression.py holds to the
    fused ≤ 1.0× XLA band on timing-valid tails."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.ops import kernels as K

    _require_tpu("bench_kernels")
    reg = K.registry()
    rng = np.random.default_rng(7)

    # ell_scatter: the streamed RE rowterm scatter (moderate d).
    n_sc, k_sc, d_sc = 1 << 17, 32, 512
    idx = jnp.asarray(rng.integers(0, d_sc, (n_sc, k_sc)).astype(np.int32))
    rv = jnp.asarray(rng.normal(size=(n_sc, k_sc)).astype(np.float32))

    # serving_score: gather -> int8 dequant -> einsum -> per-row scale.
    n_sv, d_sv, e_sv = 4096, 512, 8192
    mat = jnp.asarray(rng.normal(size=(n_sv, d_sv)).astype(np.float32))
    slots = jnp.asarray(rng.integers(0, e_sv, (n_sv,)).astype(np.int32))
    cache = jnp.asarray(
        rng.integers(-127, 128, (e_sv, d_sv)).astype(np.int8))
    scl = jnp.asarray(rng.uniform(1e-3, 2.0, (e_sv,)).astype(np.float32))

    # stream_margins / stream_rmatvec: the int8 hot-dense matvec pair.
    n_st, h_st = 1 << 15, 4096
    X_hot = jnp.asarray(
        rng.integers(-127, 128, (n_st, h_st)).astype(np.int8))
    w_hot = jnp.asarray(rng.normal(size=(h_st,)).astype(np.float32))
    base = jnp.asarray(rng.normal(size=(n_st,)).astype(np.float32))
    resid = jnp.asarray(rng.normal(size=(n_st,)).astype(np.float32))

    # re_gather_rows / re_scatter_rows: bucket-solve row traffic, with
    # invalid (-1) lanes in the final ragged wave. Rows are UNIQUE
    # within the wave (the bucket-solve contract) — with duplicates the
    # two backends' last-writer orders legitimately diverge.
    e_re, d_re, b_re = 8192, 256, 2048
    W = jnp.asarray(rng.normal(size=(e_re, d_re)).astype(np.float32))
    rows_np = rng.permutation(e_re)[:b_re].astype(np.int32)
    rows_np[:: max(b_re // 8, 1)] = -1
    rows = jnp.asarray(rows_np)
    vals = jnp.asarray(rng.normal(size=(b_re, d_re)).astype(np.float32))

    from photon_ml_tpu.ops.kernels import (ell_scatter, re_rows,
                                           serving_score, stream_fused)

    # (name, pallas(*arrays), xla(*arrays), arrays, chain_idx) —
    # chain_idx names the float operand the dependency
    # chain perturbs so asynchronous dispatch can't pipeline the timed
    # loop.
    cases = [
        ("ell_scatter",
         lambda i, v: ell_scatter.scatter_rowterm_pallas(i, v, d_sc),
         lambda i, v: ell_scatter.scatter_rowterm_xla(i, v, d_sc),
         (idx, rv), 1),
        ("serving_score", serving_score.score_rows_pallas,
         serving_score.score_rows_xla, (mat, slots, cache, scl), 0),
        ("stream_margins", stream_fused.hot_margins_pallas,
         stream_fused.hot_margins_xla, (X_hot, w_hot, base), 1),
        ("stream_rmatvec", stream_fused.hot_rmatvec_pallas,
         stream_fused.hot_rmatvec_xla, (X_hot, resid), 1),
        ("re_gather_rows", re_rows.gather_rows_pallas,
         re_rows.gather_rows_xla, (W, rows), 0),
        ("re_scatter_rows", re_rows.scatter_rows_pallas,
         re_rows.scatter_rows_xla, (W, rows, vals), 2),
    ]

    out = {
        "kernel_sweep_backend": jax.default_backend(),
        "kernel_sweep_kernels": [c[0] for c in cases],
        "kernel_defaults_flipped": [n for n in reg.names()
                                    if reg.get(n).default_on],
    }

    for name, pallas_fn, xla_fn, arrays, ci in cases:
        _progress(f"kernel sweep: {name}")
        variants = (
            ("pallas", jax.jit(lambda *a, _f=pallas_fn: _f(*a))),
            ("xla", jax.jit(lambda *a, _f=xla_fn: _f(*a))),
        )
        results = {}
        for backend, f in variants:
            def run(iters, _f=f, _arrays=arrays, _ci=ci):
                a = list(_arrays)
                t0 = time.perf_counter()
                for _ in range(iters):
                    o = _f(*a)
                    a[_ci] = a[_ci] * (1.0 + 1e-20
                                       * o.ravel()[0].astype(jnp.float32)
                                       .astype(a[_ci].dtype))
                np.asarray(o.ravel()[:1])
                return time.perf_counter() - t0

            results[backend] = np.asarray(f(*arrays), np.float64)  # warm
            out[f"kernel_{name}_{backend}_us"] = round(
                _slope(run, 5, 45) * 1e6, 1)
        out[f"kernel_{name}_ratio"] = round(
            out[f"kernel_{name}_pallas_us"]
            / max(out[f"kernel_{name}_xla_us"], 1e-9), 3)
        delta = float(np.max(np.abs(results["pallas"] - results["xla"])))
        ref = float(np.max(np.abs(results["xla"])))
        out[f"kernel_{name}_parity_delta"] = delta
        out[f"kernel_{name}_parity_rel"] = delta / max(ref, 1e-9)
    return out


def bench_avro_ingest(n=20_000, nnz=20):
    """Ingestion layer (docs/INGEST.md): native block decoder vs the
    pure-Python codec through AvroDataReader.read, the block-parallel
    pipeline at min(8, cores) decode workers, and the columnar mmap
    ingest cache — cold decode vs warm mmap load at the DECODE layer
    (the work the cache eliminates; the fold runs identically on both
    paths)."""
    import os
    import tempfile

    from photon_ml_tpu import ingest as ing
    from photon_ml_tpu.avro import native_decode, schemas
    from photon_ml_tpu.avro.container import DataFileWriter
    from photon_ml_tpu.avro.data_reader import (AvroDataReader,
                                                FeatureShardConfig)

    if not native_decode.native_available():
        return {}
    rng = np.random.default_rng(7)
    recs = [{
        "uid": i, "label": float(rng.integers(0, 2)),
        "weight": 1.0, "offset": 0.0,
        "features": [{"name": f"f{rng.integers(0, 500)}", "term": "t",
                      "value": float(rng.normal())} for _ in range(nnz)],
        "metadataMap": {"userId": f"u{rng.integers(0, 500)}"},
    } for i in range(n)]
    cfgs = {"global": FeatureShardConfig(("features",), True, sparse=True)}
    workers = min(8, os.cpu_count() or 1)
    out = {"ingest_workers": workers}
    with tempfile.TemporaryDirectory() as td:
        p = os.path.join(td, "ingest.avro")
        # 1024-record blocks so the parallel pipeline has boundaries to
        # split at (chunks cover whole blocks).
        with DataFileWriter(p, schemas.TRAINING_EXAMPLE_AVRO,
                            codec="deflate", block_records=1024) as w:
            for r in recs:
                w.append(r)

        # Full-read rates: serial native (the round-comparable line),
        # pure Python, and the block-parallel pipeline.
        serial_cfg = ing.IngestConfig(workers=1, chunk_records=1 << 30)
        par_cfg = ing.IngestConfig(workers=workers, chunk_records=2048)
        for name, kwargs in (
                ("native", {"ingest": serial_cfg}),
                ("python", {"use_native": False}),
                ("parallel", {"ingest": par_cfg})):
            lo, samples, contended = _host_timed(
                lambda _kw=kwargs: AvroDataReader().read(
                    p, cfgs, random_effect_types=["userId"], **_kw),
                label=f"avro_{name}")
            key = ("ingest" if name == "parallel" else f"avro_{name}")
            out[f"{key}_records_per_sec"] = round(n / lo)
            out[f"{key}_seconds_samples"] = samples
            if contended:
                out[f"{key}_contended"] = True
        out["ingest_parallel_speedup"] = round(
            out["ingest_records_per_sec"]
            / out["avro_native_records_per_sec"], 2)

        # Decode-layer cache comparison: drain the pipeline without the
        # fold — cold = native block decode, warm = CRC-verified mmap
        # load of the columnar cache (what a warm restart actually runs
        # instead of Avro decode).
        fb = ing.scan_file(p)
        fields = AvroDataReader().fields
        captures = {
            fields.response: (native_decode.CAP_RESPONSE, 0),
            fields.offset: (native_decode.CAP_OFFSET, 0),
            fields.weight: (native_decode.CAP_WEIGHT, 0),
            fields.uid: (native_decode.CAP_UID, 0),
            fields.metadata: (native_decode.CAP_META, 0),
            "features": (native_decode.CAP_BAG, 0),
        }
        plan = native_decode.compile_plan(fb.schema, captures)
        chunks = ing.plan_chunks([fb], 16384)

        def drain(cfg, key=None):
            pipe = ing.IngestPipeline(chunks, [plan], 1, cfg,
                                      cache_key=key)
            for _ in pipe.chunks():
                pass

        t_cold = _host_line(out, "ingest_cold_decode_seconds",
                            lambda: drain(ing.IngestConfig(workers=1)))
        cache_cfg = ing.IngestConfig(
            workers=1, cache_dir=os.path.join(td, "icache"))
        cache_key = ing.ingest_key([fb], captures, 1,
                                   cache_cfg.chunk_records)
        drain(cache_cfg, cache_key)  # populate
        t_warm = _host_line(out, "ingest_warm_cache_seconds",
                            lambda: drain(cache_cfg, cache_key))
        out["ingest_warm_cache_speedup"] = round(
            t_cold / max(t_warm, 1e-9), 2)
    return out


def bench_stream_pinned(n=1 << 15, d=4096, nnz=16, chunk_rows=1 << 12):
    """``pin_chunks`` pinned-fraction scaling curve (ROADMAP item 4): the
    n=100M streamed sweep is ~95% host→device transfer, and pinning is
    the first untried lever — each pinned chunk is stream traffic saved
    on EVERY objective evaluation, so seconds-per-pass should fall
    roughly linearly in the pinned fraction on a transfer-bound pass.
    Sweeps 0/25/50/100% of chunks pinned (stream_pinned_fraction_curve)
    plus the sharded composition at every local device
    (stream_sharded_pass_seconds — D=1 on a single-chip box; the psum
    merge is then an identity, so the line doubles as its overhead
    check)."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.data import sparse as sp
    from photon_ml_tpu.ops import losses
    from photon_ml_tpu.ops import streaming_sparse as ss
    from photon_ml_tpu.parallel.mesh import make_mesh

    batch, _ = sp.synthetic_sparse(n, d, nnz, seed=5)

    def chunks():
        for lo in range(0, n, chunk_rows):
            hi = min(lo + chunk_rows, n)
            yield sp.SparseBatch(
                indices=np.asarray(batch.indices)[lo:hi],
                values=np.asarray(batch.values)[lo:hi],
                labels=np.asarray(batch.labels)[lo:hi],
                weights=np.asarray(batch.weights)[lo:hi],
                offsets=np.asarray(batch.offsets)[lo:hi],
                num_features=d)

    chunked = ss.build_chunked(chunks(), d, chunk_rows, num_hot=256)
    out: dict = {
        "stream_pass_config": f"n={n} d={d} chunks={chunked.num_chunks}",
    }
    w0 = jnp.zeros((d,), jnp.float32)

    def make_run(vg):
        def run(iters):
            w = w0
            t0 = time.perf_counter()
            for _ in range(iters):
                _, g = vg(w)
                w = w - 1e-9 * g  # chain: next pass depends on this one
            np.asarray(w[:8])
            return time.perf_counter() - t0
        return run

    curve = {}
    for frac in (0.0, 0.25, 0.5, 1.0):
        count = int(round(frac * chunked.num_chunks))
        pinned = ss.pin_chunks(chunked, count)
        vg = ss.make_value_and_gradient(losses.LOGISTIC, chunked,
                                        pinned=pinned)
        curve[str(int(frac * 100))] = round(_slope(make_run(vg), 2, 8), 4)
    out["stream_pinned_fraction_curve"] = curve
    out["stream_pinned_fraction_speedup"] = round(
        curve["0"] / max(curve["100"], 1e-9), 2)

    mesh = make_mesh()
    sharded = ss.ShardedChunkStream(chunked, mesh)
    out["stream_sharded_devices"] = sharded.num_devices
    out["stream_sharded_pass_seconds"] = round(
        _slope(make_run(sharded.value_and_gradient(losses.LOGISTIC)),
               2, 8), 4)
    out["stream_single_pass_seconds"] = curve["0"]
    return out


def bench_stream_quant(n=1 << 15, d=4096, nnz=16, chunk_rows=1 << 12,
                       num_hot=512):
    """The pinned×quantized scaling matrix (ROADMAP item 3's transfer
    lever): the streamed pass is transfer-bound, so its wall should
    track the storage dtype's payload bytes. Stages the SAME rows at
    f32/bf16/int8, measures pass seconds at 0%% and 100%% pinned per
    dtype (``stream_quant_matrix_seconds``), and records each dtype's
    analytic payload per pass next to the ``photon_transfer_bytes_total``
    counter's measurement of one pass (``stream_quant_metric_bytes_per_
    pass`` — bench line and metric share provenance, the ≤10%% cross-
    check check_bench_regression.py gates). ``num_hot=512`` at nnz=16
    makes the hot block the payload bulk — the flagship regime, where
    int8 lands ≤0.30× f32. Also counts kernel builds during the timed
    (post-warmup) passes: must be ZERO (the kernel caches grow a dtype
    key, not extra steady-state compiles)."""
    import jax.numpy as jnp

    from photon_ml_tpu import obs
    from photon_ml_tpu.data import sparse as sp
    from photon_ml_tpu.ops import losses
    from photon_ml_tpu.ops import streaming_sparse as ss

    batch, _ = sp.synthetic_sparse(n, d, nnz, seed=7)

    def chunks():
        for lo in range(0, n, chunk_rows):
            hi = min(lo + chunk_rows, n)
            yield sp.SparseBatch(
                indices=np.asarray(batch.indices)[lo:hi],
                values=np.asarray(batch.values)[lo:hi],
                labels=np.asarray(batch.labels)[lo:hi],
                weights=np.asarray(batch.weights)[lo:hi],
                offsets=np.asarray(batch.offsets)[lo:hi],
                num_features=d)

    w0 = jnp.zeros((d,), jnp.float32)

    def make_run(vg):
        def run(iters):
            w = w0
            t0 = time.perf_counter()
            for _ in range(iters):
                _, g = vg(w)
                w = w - 1e-9 * g  # chain: next pass depends on this one
            np.asarray(w[:8])
            return time.perf_counter() - t0
        return run

    out: dict = {
        "stream_quant_config": f"n={n} d={d} nnz={nnz} "
                               f"chunk_rows={chunk_rows} "
                               f"num_hot={num_hot}",
    }
    matrix: dict = {}
    analytic: dict = {}
    measured: dict = {}
    transfer_frac: dict = {}
    warm_builds = 0.0
    # Metrics on for the byte provenance; restored to off afterwards so
    # the accounting never perturbs the other bench phases.
    _, mx = obs.enable(trace=False, metrics=True)
    try:
        for dtype in ("float32", "bfloat16", "int8"):
            chunked = ss.build_chunked(chunks(), d, chunk_rows,
                                       num_hot=num_hot,
                                       feature_dtype=dtype)
            analytic[dtype] = int(
                sum(ss._chunk_nbytes(ch) for ch in chunked.chunks))
            vg = ss.make_value_and_gradient(losses.LOGISTIC, chunked)
            make_run(vg)(1)  # warm-up: compile + first pass
            counters = obs.parse_prometheus_text(mx.render_text())
            bytes0 = obs.metric_value(
                counters, "photon_transfer_bytes_total", default=0.0)
            secs0 = obs.metric_value(
                counters, "photon_transfer_seconds_total", default=0.0)
            builds0 = obs.metric_value(
                counters, "photon_compile_cache_misses_total",
                default=0.0)
            pass_wall = make_run(vg)(1)  # ONE measured pass (counters)
            counters = obs.parse_prometheus_text(mx.render_text())
            measured[dtype] = int(obs.metric_value(
                counters, "photon_transfer_bytes_total",
                default=0.0) - bytes0)
            transfer_frac[dtype] = round(
                (obs.metric_value(counters,
                                  "photon_transfer_seconds_total",
                                  default=0.0) - secs0)
                / max(pass_wall, 1e-9), 4)
            cells = {}
            for frac, key in ((0.0, "0"), (1.0, "100")):
                pinned = ss.pin_chunks(
                    chunked, int(round(frac * chunked.num_chunks)))
                vg_p = ss.make_value_and_gradient(losses.LOGISTIC,
                                                  chunked, pinned=pinned)
                cells[key] = round(_slope(make_run(vg_p), 2, 8), 4)
            matrix[dtype] = cells
            counters = obs.parse_prometheus_text(mx.render_text())
            warm_builds += obs.metric_value(
                counters, "photon_compile_cache_misses_total",
                default=0.0) - builds0
    finally:
        obs.disable()
    out["stream_quant_matrix_seconds"] = matrix
    out["stream_quant_bytes_per_pass"] = analytic
    out["stream_quant_metric_bytes_per_pass"] = measured
    # device_put seconds / pass wall per dtype: the wall band below is
    # only a quantization claim when the pass is actually transfer-bound
    # (on a CPU box the "transfer" is a host-side copy and the pass is
    # compute-bound — check_bench_regression reports instead of gating).
    out["stream_quant_transfer_fraction"] = transfer_frac
    out["stream_quant_int8_bytes_ratio_vs_f32"] = round(
        analytic["int8"] / max(analytic["float32"], 1), 4)
    out["stream_quant_f32_pass_seconds"] = matrix["float32"]["0"]
    out["stream_quant_int8_pass_seconds"] = matrix["int8"]["0"]
    out["stream_quant_warm_compile_misses"] = int(warm_builds)
    return out


def bench_solver_race(n=1 << 15, d=4096, nnz=16, chunk_rows=1 << 12,
                      sdca_epochs=40, lbfgs_iters=40):
    """SDCA vs L-BFGS time-to-target on ONE streamed logistic fit
    (docs/STREAMING.md "Stochastic solvers"). Both solvers consume the
    same ``ChunkedHybrid`` feed with a run ledger armed; the curves come
    from ledger provenance (``convergence_curves`` over the recorded
    ``opt_iter`` rows), the common target is the WORSE final value of
    the two plus a small relative band, and ``time_to_target`` reads
    each curve from its own start. The two final fits must also agree on
    AUC — the stochastic path is not allowed to buy wall clock with
    accuracy. Single runs, wall-clock sensitive: the line carries the
    standard load/calibration validity stamp (``solver_race_valid:
    false`` on a contended box — reported, never silently gated)."""
    import tempfile

    import jax.numpy as jnp

    from photon_ml_tpu import obs
    from photon_ml_tpu.data import sparse as sp
    from photon_ml_tpu.evaluation.evaluators import auc
    from photon_ml_tpu.obs.ledger import (RunLedger, convergence_curves,
                                          read_rows, time_to_target)
    from photon_ml_tpu.ops import losses
    from photon_ml_tpu.ops import streaming_sparse as ss
    from photon_ml_tpu.optim import OptimizerConfig
    from photon_ml_tpu.optim.stochastic import minimize_stochastic
    from photon_ml_tpu.optim.streaming import minimize_streaming

    load = os.getloadavg()[0]
    batch, _ = sp.synthetic_sparse(n, d, nnz, seed=5)
    # λ sized like the flagship sweeps (λ̄ = λ/n = 1e-4): strong enough
    # convexity for the SDCA rate to bite within the epoch budget,
    # weak enough that the fit is non-trivial.
    l2 = 1e-4 * n

    def chunks():
        for lo in range(0, n, chunk_rows):
            hi = min(lo + chunk_rows, n)
            yield sp.SparseBatch(
                indices=np.asarray(batch.indices)[lo:hi],
                values=np.asarray(batch.values)[lo:hi],
                labels=np.asarray(batch.labels)[lo:hi],
                weights=np.asarray(batch.weights)[lo:hi],
                offsets=np.asarray(batch.offsets)[lo:hi],
                num_features=d)

    chunked = ss.build_chunked(chunks(), d, chunk_rows, num_hot=256)
    vg_stream = ss.make_value_and_gradient(losses.LOGISTIC, chunked)
    v_stream = ss.make_value_only(losses.LOGISTIC, chunked)

    def vg(w):
        f, g = vg_stream(w)
        return f + 0.5 * l2 * jnp.sum(w * w), g + l2 * w

    def v(w):
        return v_stream(w) + 0.5 * l2 * jnp.sum(w * w)

    w0 = jnp.zeros((d,), jnp.float32)
    out: dict = {
        "solver_race_config":
            f"n={n} d={d} chunks={chunked.num_chunks} l2={l2:g}",
    }
    results: dict = {}
    curves: dict = {}
    walls: dict = {}
    transfer: dict = {}
    _, mx = obs.enable(trace=False, metrics=True)
    try:
        with tempfile.TemporaryDirectory(prefix="pml_race_") as td:
            for solver in ("lbfgs", "sdca"):
                led_dir = os.path.join(td, solver)
                led = RunLedger.resume(led_dir)
                prev = obs.set_ledger(led)
                counters = obs.parse_prometheus_text(mx.render_text())
                secs0 = obs.metric_value(
                    counters, "photon_transfer_seconds_total", default=0.0)
                t0 = time.perf_counter()
                try:
                    if solver == "lbfgs":
                        r = minimize_streaming(
                            vg, w0,
                            OptimizerConfig(max_iterations=lbfgs_iters,
                                            tolerance=1e-8),
                            value_only=v)
                    else:
                        r = minimize_stochastic(
                            vg, w0,
                            OptimizerConfig(max_iterations=sdca_epochs,
                                            tolerance=1e-5),
                            chunked=chunked, loss=losses.LOGISTIC,
                            l2_weight=l2, solver="sdca", value_only=v)
                finally:
                    walls[solver] = time.perf_counter() - t0
                    obs.set_ledger(prev)
                    led.close()
                counters = obs.parse_prometheus_text(mx.render_text())
                transfer[solver] = obs.metric_value(
                    counters, "photon_transfer_seconds_total",
                    default=0.0) - secs0
                rows, problems = read_rows(led_dir)
                if problems:
                    raise RuntimeError(f"race ledger {solver}: {problems}")
                curves[solver] = convergence_curves(rows)["(run)"]
                results[solver] = r
    finally:
        obs.disable()
    # device_put seconds / combined race wall: the ≤1.0x ratio gate in
    # check_bench_regression.py is only an SDCA-pays-off claim when the
    # stream is actually transfer-bound (on a CPU box the pass is
    # compute-bound and the ratio is reported only).
    out["solver_race_transfer_fraction"] = round(
        sum(transfer.values()) / max(sum(walls.values()), 1e-9), 4)

    finals = {s: float(results[s].value) for s in results}
    # Worse of the two finals, padded: BOTH curves reach it by
    # construction, so neither time_to_target can come back None.
    worst = max(finals.values())
    target = worst + 1e-4 * max(abs(worst), 1.0)
    tt = {s: time_to_target(curves[s], target) for s in curves}
    out["solver_race_target_value"] = round(target, 6)
    for s in ("lbfgs", "sdca"):
        out[f"solver_time_to_target_seconds_{s}"] = round(
            tt[s]["seconds"], 4)
        out[f"solver_race_passes_{s}"] = tt[s]["passes"]
        out[f"solver_race_final_value_{s}"] = round(finals[s], 6)
    out["solver_race_ratio"] = round(
        out["solver_time_to_target_seconds_sdca"]
        / max(out["solver_time_to_target_seconds_lbfgs"], 1e-9), 3)
    out["solver_race_final_gap_sdca"] = float(results["sdca"].grad_norm)

    # AUC of each final fit, scored sparsely: pad w with one zero so the
    # sentinel column (== d) contributes nothing to the margin.
    labels = jnp.asarray(np.asarray(batch.labels))
    idx = np.asarray(batch.indices)
    vals = np.asarray(batch.values, np.float64)
    for s in ("lbfgs", "sdca"):
        w_pad = np.append(np.asarray(results[s].w, np.float64), 0.0)
        margins = (w_pad[idx] * vals).sum(axis=1)
        out[f"solver_race_auc_{s}"] = round(
            float(auc(jnp.asarray(margins, jnp.float32), labels)), 5)
    out["solver_race_auc_delta"] = round(
        abs(out["solver_race_auc_sdca"] - out["solver_race_auc_lbfgs"]), 5)

    # Trimmed curves for the round-over-round record: [seconds-from-
    # start, value, gap] per accepted iteration/epoch, ≤ 24 points.
    for s in ("lbfgs", "sdca"):
        pts = curves[s]
        t0 = pts[0]["t"]
        stride = max(1, (len(pts) + 23) // 24)
        kept = pts[::stride] + ([pts[-1]] if (len(pts) - 1) % stride else [])
        out[f"solver_race_curve_{s}"] = [
            [round(p["t"] - t0, 4), round(p["value"], 6),
             (round(p["gap"], 8) if p.get("gap") is not None else None)]
            for p in kept]

    reasons = []
    if load > LOAD_GATE:
        reasons.append(f"load_avg_1m {load:.2f} > {LOAD_GATE}")
    factor = _HOST_CAL.get("factor")
    if factor is not None and factor > CALIBRATION_GATE:
        reasons.append(f"host calibration {factor:.1f}x the clean-box "
                       f"reference")
    if reasons:
        out["solver_race_valid"] = False
        out["solver_race_invalid_reason"] = "; ".join(reasons)
    return out


def _fabric_chunked(n, d, nnz, chunk_rows, num_hot):
    from photon_ml_tpu.data import sparse as sp
    from photon_ml_tpu.ops import streaming_sparse as ss

    batch, _ = sp.synthetic_sparse(n, d, nnz, seed=7)

    def chunks():
        for lo in range(0, n, chunk_rows):
            hi = min(lo + chunk_rows, n)
            yield sp.SparseBatch(
                indices=np.asarray(batch.indices)[lo:hi],
                values=np.asarray(batch.values)[lo:hi],
                labels=np.asarray(batch.labels)[lo:hi],
                weights=np.asarray(batch.weights)[lo:hi],
                offsets=np.asarray(batch.offsets)[lo:hi],
                num_features=d)

    return ss.build_chunked(chunks(), d, chunk_rows, num_hot=num_hot)


def _fabric_rehome_drill(out):
    """Cross-machine re-home window (docs/SERVING.md "Multi-host
    fleet"): 2 machine agents + a 2-replica remote fleet, whole-machine
    SIGKILL under live traffic. Lines: the fleet's own shard re-home
    window (``fabric_rehome_seconds``, gated <= its deadline), the full
    cross-machine respawn wall (reported), unserved + client failures
    (gated == 0), and drill-score parity vs the fleet's pre-drill bits.
    On a <4-core box agents + replicas + fleet + driver share cores and
    the walls measure scheduler contention — stamped invalid, gates
    become reported-only."""
    import signal
    import subprocess
    import tempfile
    import threading
    import urllib.request

    import jax.numpy as jnp

    from photon_ml_tpu.fabric.transport import (RemoteTransport,
                                                local_tpu_chips)

    if local_tpu_chips():
        # This parent has touched JAX and holds the chips; the agents'
        # replica processes would each need one and get none.
        raise RuntimeError(
            "the fabric re-home drill starts replica processes from a "
            "parent that holds the TPU: refused on a TPU host (run it "
            "with JAX_PLATFORMS=cpu)")
    from photon_ml_tpu.game.models import (FixedEffectModel, GameModel,
                                           RandomEffectModel)
    from photon_ml_tpu.models import io as model_io
    from photon_ml_tpu.models.coefficients import Coefficients
    from photon_ml_tpu.serving.fleet import (ServingFleet,
                                             make_fleet_http_server)
    from photon_ml_tpu.types import TaskType

    ents, dg, dr = 32, 6, 4
    rng = np.random.default_rng(11)
    model = GameModel(task=TaskType.LOGISTIC_REGRESSION, models={
        "fixed": FixedEffectModel("global", Coefficients(
            jnp.asarray(rng.normal(size=dg).astype(np.float32)))),
        "per-user": RandomEffectModel(
            "userId", "re_userId",
            jnp.asarray(rng.normal(size=(ents, dr)).astype(np.float32))),
    })
    objs = []
    req_rng = np.random.default_rng(5)
    for i in range(12):
        objs.append({
            "features": {
                "global": req_rng.normal(size=dg).astype(
                    np.float32).tolist(),
                "re_userId": req_rng.normal(size=dr).astype(
                    np.float32).tolist()},
            "entity_ids": {"userId": int(i % ents)}, "uid": i})

    def post_one(url, obj):
        body = json.dumps({"requests": [obj]}).encode()
        req = urllib.request.Request(
            url + "/score", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30.0) as resp:
            return np.float32(json.loads(resp.read())["scores"][0])

    def start_agent(workdir, name):
        os.makedirs(workdir, exist_ok=True)
        ready = os.path.join(workdir, "agent.ready")
        env = dict(os.environ)
        repo = os.path.dirname(os.path.abspath(__file__))
        env["PYTHONPATH"] = (repo + os.pathsep + env["PYTHONPATH"]
                             if env.get("PYTHONPATH") else repo)
        with open(os.path.join(workdir, "agent.log"), "ab") as log_f:
            proc = subprocess.Popen(
                [sys.executable, "-m", "photon_ml_tpu.fabric.agent",
                 "--workdir", workdir, "--machine", name,
                 "--host", "127.0.0.1", "--port", "0",
                 "--ready-file", ready],
                stdout=log_f, stderr=subprocess.STDOUT, env=env,
                start_new_session=True)
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                raise RuntimeError(
                    f"agent {name} exited rc={proc.returncode}")
            if os.path.exists(ready):
                try:
                    with open(ready) as f:
                        info = json.load(f)
                    return proc, f"http://127.0.0.1:{int(info['port'])}"
                except (OSError, ValueError):
                    pass  # torn read mid-write; poll again
            time.sleep(0.05)
        raise RuntimeError(f"agent {name} not ready before its deadline")

    def kill_machine(proc):
        try:
            os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
        except (OSError, ProcessLookupError):
            pass
        try:
            proc.wait(timeout=5.0)
        except subprocess.TimeoutExpired:
            pass

    agents, server, fleet = [], None, None
    rehome_deadline_s = 5.0
    with tempfile.TemporaryDirectory(prefix="pml_bench_fabric_") as td:
        model_dir = os.path.join(td, "model")
        model_io.save_game_model(model, model_dir)
        try:
            agents = [start_agent(os.path.join(td, f"m{m}"), f"m{m}")
                      for m in range(2)]
            fleet = ServingFleet(
                replica_args=["--model-dir", model_dir,
                              "--max-wait-ms", "0.5"],
                num_replicas=2, workdir=os.path.join(td, "work"),
                probe_interval_s=0.1, heartbeat_deadline_s=1.0,
                rehome_deadline_s=rehome_deadline_s,
                retry_backoff_s=0.4, retries=4)
            fleet.supervisor.transport = RemoteTransport(
                [u for _, u in agents], fleet._replica_argv,
                timeout_s=2.0)
            fleet.start()
            server = make_fleet_http_server(fleet, port=0)
            threading.Thread(target=server.serve_forever,
                             daemon=True).start()
            url = f"http://127.0.0.1:{server.server_address[1]}"
            expected = np.asarray([post_one(url, o) for o in objs],
                                  np.float32)
            before = fleet.metrics.snapshot()
            stop = threading.Event()
            failures, served = [], []

            def scorer():
                i = 0
                while not stop.is_set():
                    obj = objs[i % len(objs)]
                    try:
                        served.append((i % len(objs), post_one(url, obj)))
                    except Exception as e:  # noqa: BLE001 drill verdict
                        failures.append((i, repr(e)))
                    i += 1
                    time.sleep(0.05)

            t = threading.Thread(target=scorer, daemon=True)
            t.start()
            try:
                time.sleep(0.5)  # traffic flowing on both replicas
                t0 = time.monotonic()
                kill_machine(agents[0][0])  # machine 0 is GONE
                # First the supervisor must NOTICE (probe/heartbeat
                # deadline) — polling for "recovered" straight away
                # would read the pre-death state as a 0-second drill.
                deadline = time.monotonic() + 30.0
                noticed = False
                while time.monotonic() < deadline:
                    if (fleet._degraded or fleet.supervisor.states()
                            != {0: "up", 1: "up"}):
                        noticed = True
                        break
                    time.sleep(0.05)
                detect_s = time.monotonic() - t0
                deadline = time.monotonic() + 120.0
                while time.monotonic() < deadline:
                    if (fleet.supervisor.states() == {0: "up", 1: "up"}
                            and not fleet._degraded):
                        break
                    time.sleep(0.2)
                recovery_s = time.monotonic() - t0
                recovered = noticed and (
                    fleet.supervisor.states() == {0: "up", 1: "up"}
                    and not fleet._degraded)
                time.sleep(0.5)  # a post-recovery traffic tail
            finally:
                stop.set()
                t.join(timeout=60.0)
            after = fleet.metrics.snapshot()
            handle = fleet.supervisor.replicas[0]
            mismatches = sum(1 for idx, s in served
                             if s != expected[idx])
            out["fabric_rehome_seconds"] = round(
                after["rehome_seconds_max"], 3)
            out["fabric_rehome_deadline_s"] = rehome_deadline_s
            out["fabric_detect_seconds"] = round(detect_s, 3)
            out["fabric_recovery_seconds"] = round(recovery_s, 3)
            out["fabric_recovered"] = recovered
            out["fabric_crossed_machines"] = (
                handle.machine == agents[1][1])
            out["fabric_unserved_total"] = int(
                after["unserved_total"] - before["unserved_total"]
                + len(failures))
            out["fabric_drill_requests"] = len(served)
            out["fabric_drill_parity_ok"] = mismatches == 0
            out["fabric_drill_parity_mismatches"] = mismatches
        finally:
            if server is not None:
                server.shutdown()
                server.server_close()
            if fleet is not None:
                fleet.close()
            for proc, _ in agents:
                kill_machine(proc)


def bench_fabric(n=1 << 14, d=2048, nnz=16, chunk_rows=1 << 11,
                 passes=12):
    """Multi-host fabric lines (docs/STREAMING.md "Multi-host
    streaming"; gated by check_bench_regression.py):

    - ``fabric_d1_parity_max_abs_diff`` — the W=1 short-circuit's
      (value, gradient, margins) vs the local chunked stream; REQUIRED
      exactly 0.0 (single-group runs must be BIT-identical, or every
      single-host result becomes un-reproducible on the fabric path);
    - ``fabric_dcn_allreduce_ms_per_pass`` / ``_bytes_per_pass`` — a
      2-rank world (threaded hosts, real sockets) streaming the shared
      pass; the per-round DCN wall and wire bytes come from the
      fabric's own counters, so the line cross-checks the ONE-allreduce
      -per-pass design invariant (``fabric_dcn_rounds_per_pass``);
    - the cross-machine re-home drill lines (see
      ``_fabric_rehome_drill``), validity-stamped on <4-core boxes.

    Standalone (``python bench.py bench_fabric``): the drill spawns
    agents + replica subprocesses, which would contend with the device
    phases if run inside the full sweep."""
    import jax.numpy as jnp

    from photon_ml_tpu import obs
    from photon_ml_tpu.fabric.collective import FabricComm
    from photon_ml_tpu.fabric.stream import FabricChunkStream
    from photon_ml_tpu.obs.metrics import MetricsRegistry
    from photon_ml_tpu.ops import losses
    from photon_ml_tpu.ops import streaming_sparse as ss

    chunked = _fabric_chunked(n, d, nnz, chunk_rows, num_hot=64)
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=d).astype(np.float32))
    off = jnp.zeros((chunked.num_chunks * chunked.chunk_rows,))
    out: dict = {
        "fabric_pass_config":
            f"n={n} d={d} chunks={chunked.num_chunks}",
    }

    # --- D=1 single-group bit-parity (the gate) ------------------------
    comm = FabricComm(0, 1)
    try:
        fs = FabricChunkStream(chunked, comm)
        v_f, g_f = fs.value_and_gradient(losses.LOGISTIC)(w, off)
        m_f = np.asarray(fs.margins(w))
    finally:
        comm.close()
    v_l, g_l = ss.make_value_and_gradient(losses.LOGISTIC, chunked)(w, off)
    m_l = np.asarray(ss.margins_chunked(chunked, w))
    out["fabric_d1_parity_max_abs_diff"] = float(max(
        abs(float(v_f) - float(v_l)),
        float(np.max(np.abs(np.asarray(g_f) - np.asarray(g_l)))),
        float(np.max(np.abs(m_f - m_l)))))

    # --- 2-rank DCN allreduce wall per pass ----------------------------
    mx = MetricsRegistry()
    with obs.activated(metrics_obj=mx):
        comms = [FabricComm(0, 2, timeout_s=120.0)]
        comms.append(FabricComm(1, 2, coordinator=comms[0].coordinator,
                                timeout_s=120.0))
        walls = [None, None]

        def host(rank):
            fs = FabricChunkStream(chunked, comms[rank])
            vg = fs.value_and_gradient(losses.LOGISTIC)
            vg(w, off)  # warm both ranks' compiled pass
            t0 = time.perf_counter()
            for _ in range(passes):
                v, _g = vg(w, off)
            float(v)
            walls[rank] = time.perf_counter() - t0

        import threading
        threads = [threading.Thread(target=host, args=(r,), daemon=True)
                   for r in (0, 1)]
        try:
            for t in threads:
                t.start()
        finally:
            for t in threads:
                t.join(600.0)
        for c in comms:
            c.close()
    if any(wl is None for wl in walls):
        raise RuntimeError("a fabric rank never finished its passes")
    snap = mx.snapshot()
    rounds = snap.get('photon_fabric_allreduce_total{op="allreduce"}', 0)
    dcn_s = snap.get("photon_fabric_allreduce_seconds_total", 0.0)
    wire = snap.get("photon_fabric_bytes_total", 0)
    out["fabric_world"] = 2
    out["fabric_passes"] = passes
    # rounds counts per-rank completions: world x (warmup + passes).
    out["fabric_dcn_rounds_per_pass"] = round(
        rounds / (2 * (passes + 1)), 3)
    out["fabric_dcn_allreduce_ms_per_pass"] = round(
        1e3 * dcn_s / max(rounds, 1), 4)
    out["fabric_dcn_bytes_per_pass"] = round(wire / max(rounds, 1))
    out["fabric_pass_seconds"] = round(max(walls) / passes, 4)

    # --- the cross-machine drill (validity-stamped) --------------------
    _progress("fabric: cross-machine re-home drill (2 agents, "
              "whole-machine SIGKILL)")
    _fabric_rehome_drill(out)
    cores = os.cpu_count() or 1
    if cores < 4:
        out["fabric_rehome_valid"] = False
        out["fabric_rehome_invalid_reason"] = (
            f"{cores} cores < 4 — agents, replicas, fleet, and driver "
            f"share cores; the drill walls measure scheduler "
            f"contention, not re-home")
    return out


def bench_game_iteration(n=100_000, n_users=2000, n_items=500):
    """One GAME coordinate-descent sweep (fixed + per-user + per-item),
    steady-state, by the slope between 1- and 6-iteration runs."""
    from photon_ml_tpu.data import synthetic
    from photon_ml_tpu.data.game_data import from_synthetic
    from photon_ml_tpu.game import descent
    from photon_ml_tpu.game.coordinates import (FixedEffectCoordinate,
                                                RandomEffectCoordinate)
    from photon_ml_tpu.ops import losses
    from photon_ml_tpu.optim import OptimizerConfig
    from photon_ml_tpu.optim.problem import GLMOptimizationConfiguration
    from photon_ml_tpu.optim.regularization import (RegularizationContext,
                                                    RegularizationType)
    from photon_ml_tpu.parallel.mesh import make_mesh
    from photon_ml_tpu.types import TaskType

    rng = np.random.default_rng(0)
    ds = from_synthetic(synthetic.game_data(
        rng, n=n, d_global=32,
        re_specs={"userId": (n_users, 8), "itemId": (n_items, 8)}))
    mesh = make_mesh()
    cfg = GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(max_iterations=25, tolerance=1e-7),
        regularization=RegularizationContext(RegularizationType.L2, 1.0))
    coords = {
        "fixed": FixedEffectCoordinate(ds, "global", losses.LOGISTIC, cfg,
                                       mesh),
        "per-user": RandomEffectCoordinate(ds, "userId", "re_userId",
                                           losses.LOGISTIC, cfg, mesh),
        "per-item": RandomEffectCoordinate(ds, "itemId", "re_itemId",
                                           losses.LOGISTIC, cfg, mesh),
    }
    seq = ["fixed", "per-user", "per-item"]

    def run(iters):
        cd = descent.CoordinateDescentConfig(seq, iterations=iters)
        t0 = time.perf_counter()
        model, _ = descent.run(TaskType.LOGISTIC_REGRESSION, coords, cd)
        np.asarray(model.models["fixed"].coefficients.means)
        np.asarray(model.models["per-user"].means[:1])
        return time.perf_counter() - t0

    # Wide span: each sweep is ~40-150 ms steady-state, so a (1, 11)
    # separation keeps per-dispatch jitter out of the
    # reported per-iteration figure.
    return _slope(run, 1, 11)


def bench_game_20m():
    """North-star MovieLens-20M-shaped CD sweep (BASELINE config 4) —
    gated behind PML_BENCH_20M=1: generation + staging + the timed descents
    add ~10+ minutes, too slow for every capture. The measurement itself
    lives in dev-scripts/flagship_movielens.py (shared, min-of-3 slope)."""
    import importlib.util
    import os

    if os.environ.get("PML_BENCH_20M") != "1":
        return {}
    spec = importlib.util.spec_from_file_location(
        "flagship_movielens",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "dev-scripts", "flagship_movielens.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # bf16 feature storage is the validated flagship configuration (the
    # f32 blocks pack ~2x the HBM; see dev-scripts/flagship_movielens.py).
    out = mod.run_flagship(feature_dtype="bfloat16", log=_progress)
    return {k: v for k, v in out.items()
            if k in ("game_cd_iteration_seconds_20m",
                     "flagship_validation_auc",
                     "flagship_first_descent_seconds")}


def bench_sweep(n=200_000, n_users=5_000, d_re=4, iterations=12,
                theta=0.05, grad_tol=0.05):
    """Full vs gate=0 vs dirty-gated GAME coordinate descent
    (docs/SWEEPS.md). Three arms over the SAME synthetic dataset, each
    with a run ledger armed:

    * ``full``  — HEAD's full-sweep descent (``sweep=None``).
    * ``gate0`` — ``--sweep`` with theta=0, grad_tol=0: must be
      BIT-identical to ``full`` and its wall inside the band (the
      normalization claim has a measured shape).
    * ``gated`` — the perf claim: outer iterations >= 2 refit only
      dirty entities, so their summed random-effect update wall drops;
      the final AUC must stay inside the 5e-3 band.

    Two perf lines, different claims:

    * ``sweep_steady_ratio`` — gated/full STEADY-state random-effect
      iteration wall (min ``train_seconds`` over outer iterations >= 2,
      backstop excluded). Once the skip fraction saturates, a gated
      sweep dispatches (almost) nothing — this is the per-sweep cost
      the flagship run pays for most of its iterations, and the gated
      <= 1.0x band gate in check_bench_regression.py reads it.
    * ``sweep_iter2plus_speedup`` — full/gated SUMMED random-effect
      ``train_seconds`` over outer iterations >= 2 (warm-up sweep
      excluded — full in both arms by construction; the final backstop
      stays in as part of the gated cost). This includes the gated
      arm's one-time compacted-wave program compiles, which on a CPU
      bench box are the same order as the solves themselves — so the
      >= 1.5x acceptance reading is gated only at flagship scale
      (``sweep_flagship``), where minutes-long sweeps dwarf compiles;
      at default scale it is reported only, like the quant wall.

    The skip-fraction curve and the refit/skipped counters come from
    the same ledger/metrics provenance the estimator emits in
    production. Flagship 10M-row/1M-entity scale rides behind
    PML_BENCH_SWEEP_10M=1 (generation + staging add tens of minutes);
    the default config keeps the same shape at capture-every-round
    cost."""
    import tempfile

    import jax.numpy as jnp

    from photon_ml_tpu import obs
    from photon_ml_tpu.data import synthetic
    from photon_ml_tpu.data.game_data import from_synthetic
    from photon_ml_tpu.evaluation.evaluators import auc
    from photon_ml_tpu.game import descent
    from photon_ml_tpu.game.coordinates import (FixedEffectCoordinate,
                                                RandomEffectCoordinate)
    from photon_ml_tpu.game.sweep import SweepConfig
    from photon_ml_tpu.obs.ledger import (RunLedger, fit_wave_summary,
                                          read_rows)
    from photon_ml_tpu.ops import losses
    from photon_ml_tpu.optim import OptimizerConfig
    from photon_ml_tpu.optim.problem import GLMOptimizationConfiguration
    from photon_ml_tpu.optim.regularization import (RegularizationContext,
                                                    RegularizationType)
    from photon_ml_tpu.parallel.mesh import make_mesh
    from photon_ml_tpu.types import TaskType

    flagship = os.environ.get("PML_BENCH_SWEEP_10M") == "1"
    if flagship:
        n, n_users = 10_000_000, 1_000_000

    load = os.getloadavg()[0]
    rng = np.random.default_rng(11)
    ds = from_synthetic(synthetic.game_data(
        rng, n=n, d_global=16, re_specs={"userId": (n_users, d_re)}))
    mesh = make_mesh()
    opt = GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(max_iterations=40, tolerance=1e-7),
        regularization=RegularizationContext(RegularizationType.L2, 1.0))
    seq = ["fixed", "per-user"]
    cd = descent.CoordinateDescentConfig(seq, iterations=iterations)
    y = jnp.asarray(ds.response)
    arms = {
        "full": None,
        "gate0": SweepConfig(),
        "gated": SweepConfig(theta=theta, grad_tol=grad_tol),
    }
    out: dict = {
        "sweep_config": f"n={n} users={n_users} d_re={d_re} "
                        f"iters={iterations} theta={theta:g} "
                        f"grad_tol={grad_tol:g}",
        "sweep_flagship": flagship,
    }
    models: dict = {}
    waves: dict = {}
    steady: dict = {}
    # Warm-up: one short ungated descent on throwaway coordinates so
    # the shared full-sweep programs compile before any arm's clock
    # starts — otherwise whichever arm runs first eats every compile
    # and the full-vs-gate0 wall comparison measures XLA, not descent.
    descent.run(TaskType.LOGISTIC_REGRESSION, {
        "fixed": FixedEffectCoordinate(ds, "global", losses.LOGISTIC,
                                       opt, mesh),
        "per-user": RandomEffectCoordinate(ds, "userId", "re_userId",
                                           losses.LOGISTIC, opt, mesh),
    }, descent.CoordinateDescentConfig(seq, iterations=2))
    _, mx = obs.enable(trace=False, metrics=True)
    try:
        with tempfile.TemporaryDirectory(prefix="pml_sweep_") as td:
            for arm, sweep in arms.items():
                # Fresh coordinates per arm: staged buckets and jitted
                # programs must not leak between arms (the full arm's
                # compiles are part of its own first iteration, same as
                # the gated arm's compacted-wave compiles are part of
                # its).
                coords = {
                    "fixed": FixedEffectCoordinate(
                        ds, "global", losses.LOGISTIC, opt, mesh),
                    "per-user": RandomEffectCoordinate(
                        ds, "userId", "re_userId", losses.LOGISTIC,
                        opt, mesh),
                }
                led_dir = os.path.join(td, arm)
                led = RunLedger.resume(led_dir)
                prev = obs.set_ledger(led)
                t0 = time.perf_counter()
                try:
                    model, hist = descent.run(
                        TaskType.LOGISTIC_REGRESSION, coords, cd,
                        sweep=sweep)
                finally:
                    out[f"sweep_wall_seconds_{arm}"] = round(
                        time.perf_counter() - t0, 3)
                    obs.set_ledger(prev)
                    led.close()
                models[arm] = model
                rows, problems = read_rows(led_dir)
                if problems:
                    raise RuntimeError(f"sweep ledger {arm}: {problems}")
                waves[arm] = fit_wave_summary(rows).get("per-user", [])
                re_wall = {}
                for rec in hist.records:
                    if rec["coordinate"] == "per-user":
                        re_wall[rec["iteration"]] = rec["train_seconds"]
                out[f"sweep_re_wall_iter2plus_{arm}"] = round(
                    sum(s for it, s in re_wall.items() if it >= 1), 3)
                steady[arm] = round(min(
                    (s for it, s in re_wall.items()
                     if 1 <= it < iterations - 1), default=0.0), 4)
                out[f"sweep_re_steady_iter_seconds_{arm}"] = steady[arm]
                out[f"sweep_auc_{arm}"] = round(
                    float(auc(model.score(ds), y)), 5)
                _progress(f"sweep arm {arm}: "
                          f"{out[f'sweep_wall_seconds_{arm}']}s, auc "
                          f"{out[f'sweep_auc_{arm}']}")
        snap = mx.snapshot()
    finally:
        obs.disable()

    out["sweep_iter2plus_speedup"] = round(
        out["sweep_re_wall_iter2plus_full"]
        / max(out["sweep_re_wall_iter2plus_gated"], 1e-9), 3)
    out["sweep_steady_ratio"] = round(
        steady["gated"] / max(steady["full"], 1e-9), 4)
    out["sweep_auc_delta"] = round(
        abs(out["sweep_auc_gated"] - out["sweep_auc_full"]), 5)
    out["sweep_gate0_bit_identical"] = bool(
        np.array_equal(np.asarray(models["full"].models["per-user"].means),
                       np.asarray(models["gate0"].models["per-user"].means))
        and np.array_equal(
            np.asarray(models["full"].models["fixed"].coefficients.means),
            np.asarray(models["gate0"].models["fixed"].coefficients.means)))
    out["sweep_gated_coeff_max_delta"] = round(float(np.max(np.abs(
        np.asarray(models["gated"].models["per-user"].means)
        - np.asarray(models["full"].models["per-user"].means)))), 6)
    # Skip fraction per outer iteration, from the gated arm's ledger
    # provenance (the photon-obs diff overlay reads the same rows).
    out["sweep_skip_fraction_curve"] = [
        round(e["entities_skipped"]
              / max(e["entities_fit"] + e["entities_skipped"], 1), 4)
        for e in waves["gated"]]
    out["sweep_entities_refit_total"] = int(sum(
        v for k, v in snap.items()
        if k.startswith("photon_re_entities_refit_total")))
    out["sweep_entities_skipped_total"] = int(sum(
        v for k, v in snap.items()
        if k.startswith("photon_re_entities_skipped_total")))

    reasons = []
    if load > LOAD_GATE:
        reasons.append(f"load_avg_1m {load:.2f} > {LOAD_GATE}")
    factor = _HOST_CAL.get("factor")
    if factor is not None and factor > CALIBRATION_GATE:
        reasons.append(f"host calibration {factor:.1f}x the clean-box "
                       f"reference")
    if reasons:
        out["sweep_valid"] = False
        out["sweep_invalid_reason"] = "; ".join(reasons)
    return out


def bench_criteo_stream():
    """Criteo row-axis streamed fit (n=100M, d=1M, E=1M) — gated behind
    PML_BENCH_CRITEO=1: the run takes over an hour (generation + fresh
    remote compiles + a streamed descent). The measurement lives in
    dev-scripts/flagship_criteo_stream.py; committed numbers in
    docs/PARITY.md "Criteo row axis"."""
    import importlib.util
    import os

    if os.environ.get("PML_BENCH_CRITEO") != "1":
        return {}
    spec = importlib.util.spec_from_file_location(
        "flagship_criteo_stream",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "dev-scripts", "flagship_criteo_stream.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = mod.run_criteo_stream(log=_progress)
    return {k: v for k, v in out.items() if k.startswith("criteo_stream")}


def _staging_in_subprocess():
    """bench_host_staging in a FRESH python process. In-process, the pass
    measures 10-11 s standalone but 39-46 s after the full device-phase
    sequence has run (reproduced in two full captures; a single prior small
    phase does NOT trigger it) — some accumulation of device-runtime state
    interferes with the host-side sorts. A subprocess gives the host
    benchmark the clean environment its number is supposed to describe."""
    import subprocess
    import tempfile

    # stderr passes through: the child runs ~15 s with no other progress
    # marker, and on failure its traceback must reach the bench log. The
    # result comes back via a temp file, not stdout — stray prints from
    # the child's import chain must not corrupt the JSON.
    with tempfile.NamedTemporaryFile(mode="r", suffix=".json") as f:
        subprocess.run(
            [sys.executable, "-c",
             "import json, sys, bench;"
             " json.dump(bench.bench_fresh_host_suite(),"
             " open(sys.argv[1], 'w'))", f.name],
            cwd=os.path.dirname(os.path.abspath(__file__)), check=True)
        return json.load(f)


def main():
    # Host-side staging FIRST, and it must stay first: the child fits on
    # the device, a chip belongs to one process, and this parent has not
    # touched JAX yet — after its first device op the child could get no
    # chip. (Also: after the device phases run, even a fresh subprocess
    # measures ~3x slow on a 1-core box — the parent's device-runtime
    # background threads compete for the core.)
    _progress("host staging at 10M rows / 1M entities (subprocess)")
    staging = _staging_in_subprocess()
    _progress("gradient step")
    grad = bench_gradient_step()
    _progress("optimizer iterations")
    opt = bench_optimizer_steps()
    _progress("sparse 1M-feature step")
    sparse = bench_sparse()
    _progress("sparse random effect")
    sparse_re = bench_sparse_random_effect()
    _progress("streamed pass: pinned-fraction curve + sharded merge")
    stream = bench_stream_pinned()
    _progress("streamed pass: pinned x quantized dtype matrix")
    stream_quant = bench_stream_quant()
    _progress("solver race: sdca vs l-bfgs time-to-target")
    race = bench_solver_race()
    _progress("pallas scatter")
    scatter = bench_pallas_scatter()  # raises off the TPU
    _progress("kernel registry sweep: fused vs xla")
    ksweep = bench_kernels()  # raises off the TPU
    # Avro ingestion lines ride the fresh-host subprocess suite above
    # (bench_avro_ingest + bench_ingest_cold_fit inside
    # bench_fresh_host_suite) — host-side work measured in a clean
    # process, same discipline as staging.
    _progress("GAME coordinate-descent sweep")
    game_iter_s = bench_game_iteration()
    _progress("dirty-gated sweeps: full vs gate0 vs gated")
    sweep = bench_sweep()
    game_20m = bench_game_20m()  # {} unless PML_BENCH_20M=1
    criteo = bench_criteo_stream()  # {} unless PML_BENCH_CRITEO=1
    _progress("done")
    print(json.dumps({
        "metric": "glm_gradient_step_samples_per_sec_per_chip",
        "value": round(grad["samples_per_sec"]),
        "unit": "samples/sec/chip",
        "vs_baseline": round(grad["samples_per_sec"]
                             / grad["cpu_numpy_samples_per_sec"], 3),
        "secondary": {
            "bf16_samples_per_sec": round(grad["bf16_samples_per_sec"]),
            "achieved_gflops": round(grad["achieved_gflops"], 1),
            "achieved_gbytes_per_sec": round(
                grad["achieved_gbytes_per_sec"], 1),
            "lbfgs_full_iteration_ms": round(opt["lbfgs_iteration_ms"], 3),
            "tron_full_iteration_ms": round(opt["tron_iteration_ms"], 3),
            "sparse_1m_feature_samples_per_sec": round(
                sparse["sparse_samples_per_sec"]),
            "sparse_gnnz_per_sec": round(sparse["sparse_gnnz_per_sec"], 3),
            "sparse_bf16_samples_per_sec": round(
                sparse["sparse_bf16_samples_per_sec"]),
            "sparse_ell_samples_per_sec":
                sparse["sparse_ell_samples_per_sec"],
            "sparse_hybrid_hot_cols": sparse["sparse_hybrid_hot_cols"],
            "sparse_hybrid_sharded_samples_per_sec":
                sparse["sparse_hybrid_sharded_samples_per_sec"],
            **sparse_re,
            **stream,
            **stream_quant,
            **race,
            **staging,
            **{key: round(v, 1) for key, v in scatter.items()},
            **ksweep,
            "game_cd_iteration_seconds": round(game_iter_s, 3),
            **sweep,
            **game_20m,
            **criteo,
            "cpu_numpy_baseline_samples_per_sec": round(
                grad["cpu_numpy_samples_per_sec"]),
            "timing_method": "dependency-chain slope",
        },
    }))


if __name__ == "__main__":
    # ``python bench.py bench_kernels`` (or any other bench_* function)
    # runs one section and prints its JSON — the sweep workflow in
    # docs/KERNELS.md commits these objects as flip evidence.
    if len(sys.argv) > 1:
        fn = globals().get(sys.argv[1])
        if not (sys.argv[1].startswith("bench_") and callable(fn)):
            print(f"unknown bench section {sys.argv[1]!r} (want one of "
                  f"{sorted(k for k in globals() if k.startswith('bench_'))})",
                  file=sys.stderr)
            sys.exit(2)
        print(json.dumps(fn()))
    else:
        main()
