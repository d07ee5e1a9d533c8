"""Criteo's row axis on one chip: a streamed n=100M GAME fit.

Round-4 verdict item 2 (SURVEY §6 config 5, §7 step 9): d=1M and E=1M
were demonstrated, but the largest committed row axis was 10–20M.
"1TB-scale" means n in the hundreds of millions, STREAMED — no formulation
that materializes an O(n × anything) device block can hold it. This run:

  * generates a Criteo-shaped synthetic in fixed-size chunks (planted
    fixed-effect weights over d=1M Zipf-popular columns + planted
    per-entity effects over E=1M entity feature pools);
  * stages each chunk once into the host-resident hybrid hot/cold layout
    (ops/streaming_sparse.build_chunked — peak host beyond the staged
    output is ONE chunk);
  * trains block coordinate descent with the row-STREAMED fixed effect
    (every L-BFGS value/gradient double-buffers chunks through the chip —
    the TPU-native DistributedGLMLossFunction treeAggregate pass) plus the
    device-resident sparse random effect (per-entity subspace buckets);
  * reports staging seconds, per-sweep seconds, train AUC vs the planted
    truth, and the host's peak RSS (the flat-memory claim, measured).

    python dev-scripts/flagship_criteo_stream.py \
        [--rows 100000000] [--chunk-rows 5000000] [--pin-gb 2.0] [--json]

Defaults need ~35 GB host RAM (staged chunks + RE arrays) and one 16 GB
chip (bf16 feature storage on both coordinates). Smaller sanity run:
``--rows 2000000 --chunk-rows 500000 --entities 20000``.
"""
import argparse
import json
import os
import resource
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from photon_ml_tpu import obs
from photon_ml_tpu.utils.compile_cache import enable_compilation_cache

enable_compilation_cache()


def _rss_gb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6


def run_criteo_stream(n_rows=100_000_000, d=1_000_000, n_entities=1_000_000,
                      nnz_fe=8, nnz_re=4, chunk_rows=5_000_000,
                      hot_block_gb=1.25, pin_gb=2.0, iterations=2,
                      fe_opt_iters=12, seed=11, checkpoint_dir=None,
                      dtype="int8", log=lambda m: None):
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.data.game_data import GameDataset, SparseShard
    from photon_ml_tpu.data.sparse import SparseBatch
    from photon_ml_tpu.evaluation.evaluators import auc
    from photon_ml_tpu.game import descent
    from photon_ml_tpu.game.coordinates import (
        RandomEffectCoordinate, StreamingSparseFixedEffectCoordinate)
    from photon_ml_tpu.ops import losses
    from photon_ml_tpu.ops import streaming_sparse as ss
    from photon_ml_tpu.optim import OptimizerConfig
    from photon_ml_tpu.optim.problem import GLMOptimizationConfiguration
    from photon_ml_tpu.optim.regularization import (RegularizationContext,
                                                    RegularizationType)
    from photon_ml_tpu.parallel.mesh import make_mesh
    from photon_ml_tpu.types import TaskType

    n_chunks = (n_rows + chunk_rows - 1) // chunk_rows
    rng0 = np.random.default_rng(seed)

    # Planted truths (small: O(d) + O(E)): fixed-effect weights over the
    # full column space; per-entity coefficients over 16-column pools.
    w_true = (rng0.normal(size=d) * 0.7).astype(np.float32)
    pools = rng0.integers(0, d, size=(n_entities, 16)).astype(np.int32)
    beta = (rng0.normal(size=(n_entities, 16)) * 0.8).astype(np.float32)

    # Zipf-ish fixed-effect column popularity via inverse-CDF sampling
    # (u^a maps uniforms onto a power-law rank distribution).
    zipf_a = 6.0

    # RE arrays accumulate across chunks (O(n) host, written once).
    re_idx = np.empty((n_rows, nnz_re), np.int32)
    re_val = np.empty((n_rows, nnz_re), np.float32)
    ids_all = np.empty((n_rows,), np.int32)
    y_all = np.empty((n_rows,), np.float32)

    def gen_chunks():
        for c in range(n_chunks):
            rng = np.random.default_rng(seed + 1000 + c)
            lo = c * chunk_rows
            hi = min(lo + chunk_rows, n_rows)
            m = hi - lo
            # Fixed-effect features: Zipf-popular columns, dedup via the
            # pad slot (index d, value 0) like every sparse source here.
            u = rng.random((m, nnz_fe))
            fe_idx = np.minimum((d * u ** zipf_a).astype(np.int64),
                                d - 1).astype(np.int32)
            fe_idx.sort(axis=1)
            dup = np.zeros_like(fe_idx, bool)
            dup[:, 1:] = fe_idx[:, 1:] == fe_idx[:, :-1]
            fe_val = rng.normal(size=(m, nnz_fe)).astype(np.float32)
            margin = np.einsum("ij,ij->i", np.where(dup, 0.0, fe_val),
                               w_true[fe_idx]).astype(np.float32)
            fe_idx[dup] = d
            fe_val[dup] = 0.0
            # Random-effect features from each row's entity pool.
            ids = rng.integers(0, n_entities, size=m).astype(np.int32)
            slot = rng.integers(0, 16, size=(m, nnz_re))
            ridx = np.sort(pools[ids[:, None], slot], axis=1)
            rdup = np.zeros_like(ridx, bool)
            rdup[:, 1:] = ridx[:, 1:] == ridx[:, :-1]
            rval = rng.normal(size=(m, nnz_re)).astype(np.float32)
            margin += np.einsum(
                "ij,ij->i", np.where(rdup, 0.0, rval),
                beta[ids[:, None], slot]).astype(np.float32)
            ridx[rdup] = d
            rval[rdup] = 0.0
            y = (rng.random(m) < 1.0 / (1.0 + np.exp(-margin))).astype(
                np.float32)
            re_idx[lo:hi], re_val[lo:hi] = ridx, rval
            ids_all[lo:hi], y_all[lo:hi] = ids, y
            yield SparseBatch(
                indices=fe_idx, values=fe_val, labels=y,
                weights=np.ones(m, np.float32),
                offsets=np.zeros(m, np.float32),  # streaming contract
                num_features=d)

    # int8 chunk storage is the DEFAULT (docs/STREAMING.md "Quantized
    # streaming"): the pass is transfer-bound and the multi-seed AUC
    # parity anchor (docs/PARITY.md) shows quantization does not move
    # model quality at flagship shape — so the ~4x-smaller stream is
    # free. --dtype float32|bfloat16 reproduces the older anchors.
    num_hot = ss.plan_num_hot(chunk_rows, int(hot_block_gb * 2 ** 30),
                              dtype)
    log(f"{n_rows:,} rows in {n_chunks} chunks; num_hot={num_hot} "
        f"({dtype} chunk storage)")
    t0 = time.perf_counter()
    with obs.span("flagship.fe_staging", cat="stage", chunks=n_chunks):
        chunked = ss.build_chunked(gen_chunks(), d, chunk_rows,
                                   num_hot=num_hot,
                                   feature_dtype=dtype, log=log)
    fe_staging = time.perf_counter() - t0
    log(f"FE chunk staging {fe_staging:.1f}s; host peak {_rss_gb():.1f} GB")

    ds = GameDataset(
        response=y_all, offsets=np.zeros(n_rows, np.float32),
        weights=np.ones(n_rows, np.float32),
        feature_shards={"re": SparseShard(re_idx, re_val, d)},
        entity_ids={"userId": ids_all},
        num_entities={"userId": n_entities},
        intercept_index={})
    cfg = GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(max_iterations=12, tolerance=1e-6),
        regularization=RegularizationContext(RegularizationType.L2, 1.0))
    # FE iterations are the wall-clock knob at streamed scale (one
    # iteration ≈ one full pass over the stream).
    fe_cfg = GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(max_iterations=fe_opt_iters,
                                  tolerance=1e-6),
        regularization=RegularizationContext(RegularizationType.L2, 1.0))

    # Pin as many leading chunks as the HBM budget allows: each pinned
    # chunk is stream traffic saved on EVERY objective evaluation.
    chunk_bytes = sum(
        a.nbytes for a in jax.tree.leaves(chunked.chunks[0]))
    pin = min(chunked.num_chunks,
              int(pin_gb * 2 ** 30 / max(chunk_bytes, 1)))
    log(f"chunk ≈ {chunk_bytes / 2**30:.2f} GiB on device; pinning {pin} "
        f"of {chunked.num_chunks} chunks (budget {pin_gb} GiB)")
    # Sharded over the data axis (docs/STREAMING.md): one chip streams
    # everything on a 1-device host (bit-identical to the mesh-less
    # path); a multi-chip host partitions the chunk ranges and psum-
    # merges partials automatically. pin is PER DEVICE under a mesh.
    fe_coord = StreamingSparseFixedEffectCoordinate(
        ds, chunked, "global", losses.LOGISTIC, fe_cfg,
        pin_device_chunks=pin, mesh=make_mesh(),
        log=lambda m: log(f"  [fe-lbfgs] {m}"))
    # Opt-in staging cache (set PML_CRITEO_STAGING_CACHE=/path): a
    # crash-rerun then skips the ~20-minute host projection pass
    # (digest-keyed; safe across identical generations). Opt-in because
    # the cache holds the FULL f32 staged buckets — tens of GB at 100M
    # rows — and a tmpfs-backed default would eat host RAM silently.
    cache_dir = os.environ.get("PML_CRITEO_STAGING_CACHE") or None
    t0 = time.perf_counter()
    with obs.span("flagship.re_staging", cat="stage"):
        re_coord = RandomEffectCoordinate(
            ds, "userId", "re", losses.LOGISTIC, cfg, make_mesh(),
            lower_bound=2, upper_bound=65536, feature_dtype="bfloat16",
            staging_cache_dir=cache_dir)
    re_staging = time.perf_counter() - t0
    log(f"RE staging {re_staging:.1f}s; host peak {_rss_gb():.1f} GB")

    coords = {"fixed": fe_coord, "per-user": re_coord}
    # Crash-resume for the ~90-minute fit (the round-5 run lost its
    # trained model to a TPU-worker crash): descent-level checkpoints
    # plus mid-L-BFGS stream state (docs/STREAMING.md) — a rerun with
    # the same --checkpoint-dir resumes instead of retraining.
    manager = None
    if checkpoint_dir:
        from photon_ml_tpu.game.checkpoint import CheckpointManager

        manager = CheckpointManager(checkpoint_dir)
        log(f"checkpointing descent + mid-L-BFGS state under "
            f"{checkpoint_dir}")
    t0 = time.perf_counter()
    with obs.span("flagship.descent", cat="train",
                  iterations=iterations):
        model, hist = descent.run(
            TaskType.LOGISTIC_REGRESSION, coords,
            descent.CoordinateDescentConfig(["fixed", "per-user"],
                                            iterations=iterations),
            checkpoint_manager=manager)
    descent_s = time.perf_counter() - t0
    per_update = {r["coordinate"]: r["train_seconds"]
                  for r in hist.records[-2:]}  # last sweep's updates
    log(f"{iterations}-sweep descent {descent_s:.1f}s "
        f"(last sweep per-coordinate {per_update})")

    log("scoring (streamed FE + RE)")
    with obs.span("flagship.scoring", cat="score"):
        scores = fe_coord.score(model.models["fixed"]) + \
            re_coord.score(model.models["per-user"])
        train_auc = float(auc(scores, jnp.asarray(y_all)))
    log(f"train AUC vs planted effects: {train_auc:.4f}; "
        f"host peak {_rss_gb():.1f} GB")
    out = {
        "criteo_stream_rows": n_rows,
        "criteo_stream_chunks": n_chunks,
        "criteo_stream_fe_staging_seconds": round(fe_staging, 1),
        "criteo_stream_re_staging_seconds": round(re_staging, 1),
        "criteo_stream_descent_seconds": round(descent_s, 1),
        "criteo_stream_last_sweep_seconds": {
            k: round(v, 1) for k, v in per_update.items()},
        "criteo_stream_train_auc": round(train_auc, 4),
        # 6 decimals: the dtype-parity anchor (docs/PARITY.md) quotes
        # this as a measurement series, the round-6-verdict discipline.
        "criteo_stream_train_auc_6d": round(train_auc, 6),
        "criteo_stream_dtype": dtype,
        "criteo_stream_seed": seed,
        "criteo_stream_host_peak_gb": round(_rss_gb(), 1),
    }
    # Transfer attribution from the device_put accounting wrapper — the
    # measured replacement for the "~95% host→device" hand subtraction
    # (VERDICT Weak #3). Bench line and metric share PROVENANCE: this
    # JSON line IS the counter.
    mx = obs.metrics()
    if mx is not None:
        parsed = obs.parse_prometheus_text(mx.render_text())
        t_xfer = obs.metric_value(
            parsed, "photon_transfer_seconds_total") or 0.0
        b_xfer = obs.metric_value(
            parsed, "photon_transfer_bytes_total") or 0.0
        out["criteo_stream_transfer_seconds"] = round(t_xfer, 1)
        out["criteo_stream_transfer_gb"] = round(b_xfer / 2 ** 30, 2)
        if descent_s > 0:
            out["criteo_stream_transfer_fraction"] = round(
                t_xfer / descent_s, 4)
        out["criteo_stream_peak_inflight_chunks"] = int(
            obs.metric_value(parsed,
                             "photon_stream_inflight_chunks_peak") or 0)
    led = obs.ledger()
    if led is not None:
        # Time-to-target READ FROM the run ledger (ISSUE 9 satellite):
        # the bench line and the convergence curve share provenance.
        from photon_ml_tpu.obs.ledger import (convergence_curves,
                                              read_rows,
                                              time_to_fraction)

        led.flush()
        rows, _ = read_rows(led.directory)
        curve = convergence_curves(rows).get("fixed")
        tt = time_to_fraction(curve) if curve else None
        if tt is not None:
            out["time_to_target_value_seconds"] = round(tt["seconds"], 3)
            out["time_to_target_value"] = round(tt["target_value"], 6)
            out["time_to_target_passes"] = tt["passes"]
        out["criteo_stream_ledger_dir"] = led.directory
        out["criteo_stream_run_id"] = led.manifest.get("run_id")
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=100_000_000)
    ap.add_argument("--features", type=int, default=1_000_000)
    ap.add_argument("--entities", type=int, default=1_000_000)
    ap.add_argument("--chunk-rows", type=int, default=5_000_000)
    ap.add_argument("--hot-gb", type=float, default=None,
                    help="per-chunk hot-block byte budget (default: the "
                         "run_criteo_stream default scaled by "
                         "chunk_rows/10M, so the TOTAL hot bytes and the "
                         "per-evaluation stream stay constant across "
                         "chunk sizes)")
    ap.add_argument("--pin-gb", type=float, default=2.0)
    ap.add_argument("--iterations", type=int, default=2)
    ap.add_argument("--fe-iters", type=int, default=12,
                    help="FE L-BFGS iterations (each is a full pass "
                         "over the stream)")
    ap.add_argument("--dtype", default="int8",
                    choices=["float32", "bfloat16", "int8"],
                    help="chunk storage dtype of the streamed fixed "
                         "effect (default int8 — symmetric per-column "
                         "quantization with f32 accumulation quarters "
                         "the transfer-bound stream; AUC parity "
                         "anchored multi-seed in docs/PARITY.md)")
    ap.add_argument("--seed", type=int, default=11,
                    help="data-generation seed (dtype_parity.py sweeps "
                         "this so the int8 anchor is multi-seed)")
    ap.add_argument("--checkpoint-dir",
                    help="persist descent + mid-L-BFGS stream state "
                         "here (docs/STREAMING.md); a rerun with the "
                         "same dir resumes the ~90-min fit instead of "
                         "retraining after a crash")
    ap.add_argument("--trace-out", default="criteo-stream-trace.json",
                    help="span-trace output (tracing is ON by default "
                         "for the flagship — this run is exactly the "
                         "one whose time accounting matters; pass '' "
                         "to disable). Render with `photon-obs "
                         "summarize` (docs/OBSERVABILITY.md)")
    ap.add_argument("--metrics-dump", default=None,
                    help="Prometheus-text metrics output (default: "
                         "<trace-out>.prom when tracing is on)")
    ap.add_argument("--ledger-dir", default="criteo-stream-ledger",
                    help="run-ledger directory (ON by default — the "
                         "flagship's convergence curve is exactly the "
                         "evidence the papers report; pass '' to "
                         "disable). A crash-rerun with the same dir "
                         "APPENDS after identity validation; inspect "
                         "live with `photon-obs tail` "
                         "(docs/OBSERVABILITY.md)")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()

    def log(m):
        print(f"[criteo-stream {time.strftime('%H:%M:%S')}] {m}",
              file=sys.stderr, flush=True)

    # One source of truth for the hot budget: the function default is
    # per-10M-row-chunk; scale it so total hot bytes are chunk-size
    # invariant unless the caller overrides explicitly.
    hot_gb = (args.hot_gb if args.hot_gb is not None
              else 1.25 * args.chunk_rows / 10_000_000)
    trace_out = args.trace_out or None
    metrics_dump = args.metrics_dump or (
        trace_out + ".prom" if trace_out else None)
    if trace_out or metrics_dump:
        obs.enable(trace=bool(trace_out), metrics=True,
                   spill=(trace_out + ".spill") if trace_out else None)
    led = None
    if args.ledger_dir:
        # Run ledger by default (resume-appending — the crash-rerun
        # story matches --checkpoint-dir): the fit's convergence curve
        # survives any exit, `photon-obs tail` watches it live.
        from photon_ml_tpu.obs.ledger import build_manifest

        led = obs.RunLedger.resume(args.ledger_dir, manifest=build_manifest(
            config={"flagship": "criteo_stream", "rows": args.rows,
                    "features": args.features, "entities": args.entities,
                    "chunk_rows": args.chunk_rows, "pin_gb": args.pin_gb,
                    "iterations": args.iterations,
                    "fe_iters": args.fe_iters, "dtype": args.dtype,
                    "seed": args.seed}))
        obs.set_ledger(led)
        log(f"run ledger -> {args.ledger_dir} (photon-obs tail "
            f"{args.ledger_dir})")
    status = "error"
    try:
        out = run_criteo_stream(
            n_rows=args.rows, d=args.features, n_entities=args.entities,
            chunk_rows=args.chunk_rows, hot_block_gb=hot_gb,
            pin_gb=args.pin_gb, iterations=args.iterations,
            fe_opt_iters=args.fe_iters, seed=args.seed,
            dtype=args.dtype,
            checkpoint_dir=args.checkpoint_dir, log=log)
        status = "ok"
    finally:
        # Dump in a finally: a crashed flagship leaves its timeline —
        # the round-5 run lost exactly this evidence to a worker crash.
        if led is not None:
            led.close(status=status)
            obs.set_ledger(None)
        if trace_out:
            obs.dump_trace(trace_out)
            log(f"trace -> {trace_out} (photon-obs summarize "
                f"{trace_out})")
        if metrics_dump:
            obs.dump_metrics(metrics_dump)
            log(f"metrics -> {metrics_dump}")
    if args.json:
        print(json.dumps(out))
    else:
        for k, v in out.items():
            print(f"{k}: {v}")


if __name__ == "__main__":
    main()
