#!/usr/bin/env python
"""Serving bench: synthetic traffic against a resident ScoringService.

Two modes, both driving Zipf-skewed request traffic (realistic per-user
activity — the same skew the training bucketing exploits) through the
full serving path: micro-batcher → shape-bucketed jitted scorer → LRU
random-effect cache.

**Open-loop target-QPS sweep (default).** Closed-loop clients can never
see saturation: when the service slows down, so do they (coordinated
omission). The sweep instead dispatches constant-arrival traffic at each
target rate — arrival i is scheduled at ``t0 + i/qps`` regardless of how
the service is doing, latency is measured from the SCHEDULED arrival,
and admission-control sheds count against the level. Emits one BENCH
line: ``serving_saturation_knee_qps`` with the full
``serving_p99_vs_qps_curve``, per-stage attribution fractions
(queue wait / assemble / device score / respond), and a bench-vs-metrics
cross-check — the bench's request counts and latency totals must agree
with the serving scoreboard within 10% (docs/OBSERVABILITY.md).

**Closed-loop (--closed-loop).** The original bench: N client threads,
submit→result round trips; still the right tool for steady-state
latency floors.

    JAX_PLATFORMS=cpu python dev-scripts/bench_serving.py
    JAX_PLATFORMS=cpu python dev-scripts/bench_serving.py --closed-loop

Both report steady-state recompiles, which must be ZERO (warmup owns
every bucket shape).
"""

from __future__ import annotations

import argparse
import concurrent.futures
import json
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# Dispatcher lateness beyond this marks an arrival "late" (the open-loop
# validity signal: a dispatcher that cannot keep schedule is measuring
# itself, not the service).
_LATE_S = 0.005


def build_parser():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--num-entities", type=int, default=20000)
    p.add_argument("--d-global", type=int, default=32)
    p.add_argument("--d-re", type=int, default=16)
    p.add_argument("--cache-entities", type=int, default=2048)
    p.add_argument("--max-batch", type=int, default=64)
    p.add_argument("--max-wait-ms", type=float, default=1.0)
    p.add_argument("--entity-skew", type=float, default=1.2,
                   help="Zipf exponent of the entity draw")
    p.add_argument("--unseen-frac", type=float, default=0.02,
                   help="fraction of requests with unknown entities")
    p.add_argument("--seed", type=int, default=0)
    # -- open-loop sweep (default mode) ------------------------------------
    p.add_argument("--qps", default="50,100,200,400,800",
                   help="comma-separated target-QPS levels of the "
                        "open-loop sweep (ascending)")
    p.add_argument("--seconds-per-level", type=float, default=2.0,
                   help="constant-arrival dispatch duration per level")
    p.add_argument("--drain-timeout-s", type=float, default=30.0,
                   help="max wait for a level's in-flight requests")
    # -- closed-loop mode ---------------------------------------------------
    p.add_argument("--closed-loop", action="store_true",
                   help="run the original closed-loop client bench "
                        "instead of the open-loop sweep")
    p.add_argument("--clients", type=int, default=8,
                   help="closed-loop client threads")
    p.add_argument("--requests-per-client", type=int, default=400)
    # -- fleet chaos sweep (docs/SERVING.md "Scaling out") -------------------
    p.add_argument("--fleet", action="store_true",
                   help="run the Zipf sweep against a REPLICATED fleet "
                        "(subprocess replicas + entity-affinity router) "
                        "and SIGKILL one replica mid-sweep through a "
                        "--fault-plan; reports fleet_rehome_seconds and "
                        "p99 inside vs outside the failure window")
    p.add_argument("--fleet-replicas", type=int, default=2)
    p.add_argument("--fleet-num-shards", type=int, default=None)
    p.add_argument("--fleet-kill-replica", type=int, default=1,
                   help="which replica the injected replica_kill targets")
    p.add_argument("--fleet-kill-at-flush", type=int, default=40,
                   help="the doomed replica dies at this flush "
                        "occurrence (deterministic fault addressing; "
                        "lands early in the sweep, after warmup)")
    p.add_argument("--fleet-rehome-deadline-s", type=float, default=5.0)
    p.add_argument("--fleet-hedge-after-ms", type=float, default=50.0)
    p.add_argument("--fleet-qps", default="40,80",
                   help="target-QPS levels of the fleet sweep (smaller "
                        "than the single-process sweep: every request "
                        "crosses one more HTTP hop)")
    # -- Zipf-skew sweep (docs/SERVING.md "Elastic fleet") -------------------
    p.add_argument("--zipf-sweep", action="store_true",
                   help="sweep the ELASTIC fleet across Zipf exponents "
                        "(with --fleet): at each skew, find the "
                        "saturation knee + steady p99 with the elastic "
                        "control loop armed, and measure the STATIC "
                        "map's degradation alongside — the acceptance "
                        "claim is knee retention as the head "
                        "concentrates (fleet_knee_vs_skew_curve, "
                        "fleet_p99_vs_skew_curve)")
    p.add_argument("--zipf-skews", default="0.0,0.6,0.9,1.2",
                   help="comma-separated Zipf exponents of the skew "
                        "sweep")
    p.add_argument("--zipf-qps", default="30,60,90",
                   help="target-QPS levels probed per skew (ascending)")
    p.add_argument("--zipf-seconds-per-level", type=float, default=2.0)
    p.add_argument("--zipf-static-baseline", dest="zipf_static",
                   action="store_true", default=True,
                   help="also measure the static-map baseline per skew")
    p.add_argument("--no-zipf-static-baseline", dest="zipf_static",
                   action="store_false")
    # -- publish arm (docs/SERVING.md "Continuous publication") --------------
    p.add_argument("--publish", action="store_true",
                   help="measure a live delta publish: open-loop "
                        "constant-QPS traffic with a refit→delta→"
                        "hot-swap landing mid-stream; reports "
                        "publish_swap_seconds, p99 inside the swap "
                        "window vs steady state, and unserved counts "
                        "(must be zero — the zero-drop contract)")
    p.add_argument("--publish-qps", type=float, default=150.0)
    p.add_argument("--publish-seconds", type=float, default=4.0,
                   help="open-loop dispatch duration; the swap lands at "
                        "the half-way mark")
    p.add_argument("--publish-dirty-entities", type=int, default=48,
                   help="entities refit into the published delta (the "
                        "hottest ones — their rows are device-cached, "
                        "so the swap exercises LRU invalidation)")
    p.add_argument("--publish-tuples-per-entity", type=int, default=4)
    # -- restart arm (docs/SERVING.md "Sub-second restart") ------------------
    p.add_argument("--restart", action="store_true",
                   help="measure the replica-restart tail: kill a warm "
                        "replica and measure spawn → first scored "
                        "request for an npz boot vs an mmap generation "
                        "boot (replica_restart_seconds_{npz,mmap}), "
                        "plus the in-process model-load walls and a "
                        "rehome-under-restart p99 leg through a "
                        "2-replica mmap-booted fleet (unserved must be "
                        "0)")
    p.add_argument("--restart-entities", type=int, default=200_000,
                   help="entity-table rows of the restart-arm model "
                        "(large enough that parse-vs-mmap dominates "
                        "the model phase)")
    p.add_argument("--restart-probe-requests", type=int, default=32,
                   help="single-request probes scored after each boot "
                        "(parity + ready-to-traffic confirmation)")
    p.add_argument("--restart-traffic-requests", type=int, default=240,
                   help="requests streamed through the 2-replica fleet "
                        "while one replica is killed and restarts")
    # -- quantized-cache sweep (docs/SERVING.md "Quantized device cache") ----
    p.add_argument("--cache-sweep", action="store_true",
                   help="sweep the device-LRU storage dtype at a FIXED "
                        "device-byte budget: f32 vs int8 caches sized to "
                        "the same HBM spend, one open-loop level each — "
                        "int8 holds ~4x the entities, so hit rate rises "
                        "and p99 falls at equal budget")
    p.add_argument("--cache-budget-kb", type=float, default=8.0,
                   help="device bytes per coordinate the sweep holds "
                        "fixed across dtypes (cache table + int8 scale "
                        "vector); small enough by default that the Zipf "
                        "working set OVERFLOWS the f32 cache — the "
                        "regime where quadrupled capacity moves the "
                        "hit rate")
    p.add_argument("--cache-sweep-qps", type=float, default=200.0)
    p.add_argument("--cache-sweep-seconds", type=float, default=5.0)
    return p


def build_model(args):
    import jax.numpy as jnp

    from photon_ml_tpu.game.models import (FixedEffectModel, GameModel,
                                           RandomEffectModel)
    from photon_ml_tpu.models.coefficients import Coefficients
    from photon_ml_tpu.types import TaskType

    rng = np.random.default_rng(args.seed)
    E, dg, dr = args.num_entities, args.d_global, args.d_re
    return GameModel(task=TaskType.LOGISTIC_REGRESSION, models={
        "fixed": FixedEffectModel("global", Coefficients(
            jnp.asarray(rng.normal(size=dg).astype(np.float32)))),
        "per-user": RandomEffectModel(
            "userId", "re_userId",
            jnp.asarray((rng.normal(size=(E, dr)) * 0.5
                         ).astype(np.float32))),
    })


def build_service(args):
    from photon_ml_tpu.serving import ScoringService
    from photon_ml_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    model = build_model(args)
    t0 = time.perf_counter()
    service = ScoringService(
        model, max_batch=args.max_batch, max_wait_ms=args.max_wait_ms,
        cache_entities=args.cache_entities)
    return service, time.perf_counter() - t0


def make_request_factory(args):
    from photon_ml_tpu.serving import ScoringRequest

    E, dg, dr = args.num_entities, args.d_global, args.d_re
    p = 1.0 / np.arange(1, E + 1) ** args.entity_skew
    p /= p.sum()

    def make_request(r):
        if r.random() < args.unseen_frac:
            eid = E + int(r.integers(0, 1000))
        else:
            eid = int(r.choice(E, p=p))
        return ScoringRequest(
            features={"global": r.normal(size=dg).astype(np.float32),
                      "re_userId": r.normal(size=dr).astype(np.float32)},
            entity_ids={"userId": eid})

    return make_request


def warmup(service, make_request, args):
    """Touch every bucket shape so steady state owns its programs: the
    direct score() path compiles the same per-bucket programs the
    batcher path runs, plus one batcher round trip for its seam."""
    warm_rng = np.random.default_rng(args.seed + 99)
    n = 1
    while n <= args.max_batch:
        service.score([make_request(warm_rng) for _ in range(n)])
        n *= 2
    service.submit(make_request(warm_rng)).result(timeout=60)


# -- open-loop sweep ---------------------------------------------------------


def run_open_loop_level(service, make_request, qps, seconds, seed,
                        drain_timeout_s):
    """One constant-arrival level; returns the level's scoreboard."""
    from photon_ml_tpu.serving import BatcherQueueFull, DeadlineExceeded

    rng = np.random.default_rng(seed)
    n = max(1, int(round(qps * seconds)))
    requests = [make_request(rng) for _ in range(n)]
    period = 1.0 / qps
    lock = threading.Lock()
    done = threading.Event()
    state = {"lat_open": [], "lat_submit": [], "deadline": 0, "error": 0,
             "completed": 0, "dispatched": 0, "t_last_done": 0.0}
    shed = late = 0

    def _make_cb(t_sched, t_submit):
        def _cb(fut):
            t_end = time.perf_counter()
            exc = fut.exception()
            with lock:
                state["completed"] += 1
                state["t_last_done"] = max(state["t_last_done"], t_end)
                if exc is None:
                    state["lat_open"].append(t_end - t_sched)
                    state["lat_submit"].append(t_end - t_submit)
                elif isinstance(exc, DeadlineExceeded):
                    state["deadline"] += 1
                else:
                    state["error"] += 1
                if state["completed"] == state["dispatched"] \
                        and done.is_set():
                    drained.set()
        return _cb

    drained = threading.Event()
    t0 = time.perf_counter()
    for i, req in enumerate(requests):
        t_sched = t0 + i * period
        delay = t_sched - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        t_submit = time.perf_counter()
        if t_submit - t_sched > _LATE_S:
            late += 1
        try:
            fut = service.submit(req)
        except BatcherQueueFull:
            shed += 1
            continue
        with lock:
            state["dispatched"] += 1
        fut.add_done_callback(_make_cb(t_sched, t_submit))
    done.set()
    with lock:  # either this recheck or a later callback sets drained
        if state["completed"] == state["dispatched"]:
            drained.set()
    drained.wait(timeout=drain_timeout_s)
    elapsed = max(state["t_last_done"], time.perf_counter()) - t0
    lat = np.asarray(state["lat_open"]) * 1e3
    ok = len(state["lat_open"])
    return {
        "target_qps": qps,
        "offered": n,
        "ok": ok,
        "shed": shed,
        "deadline_exceeded": state["deadline"],
        "errors": state["error"],
        "late_arrivals": late,
        "achieved_qps": round(ok / elapsed, 1) if elapsed > 0 else 0.0,
        "p50_ms": round(float(np.percentile(lat, 50)), 4) if ok else None,
        "p95_ms": round(float(np.percentile(lat, 95)), 4) if ok else None,
        "p99_ms": round(float(np.percentile(lat, 99)), 4) if ok else None,
        "lat_submit_sum_s": float(np.sum(state["lat_submit"])),
    }


def find_knee(levels):
    """The saturation knee: the highest target-QPS level the service
    sustained — <1% of offered load shed/expired AND achieved ≥90% of
    the target rate. Returns (knee_qps, saturated): ``saturated`` False
    means every level was sustained (the knee is beyond the sweep)."""
    knee = None
    saturated = False
    for lv in levels:
        bad_frac = (lv["shed"] + lv["deadline_exceeded"]
                    + lv["errors"]) / max(lv["offered"], 1)
        sustained = (bad_frac <= 0.01
                     and lv["achieved_qps"] >= 0.9 * lv["target_qps"])
        if sustained:
            knee = lv["target_qps"]
        else:
            saturated = True
            break
    if knee is None:  # even the lowest level fell over
        knee = 0.0
    return knee, saturated


def run_sweep(args, service, make_request, load_seconds):
    qps_levels = [float(q) for q in str(args.qps).split(",") if q]
    warmup(service, make_request, args)
    snap0 = service.metrics.snapshot()
    levels = []
    for i, qps in enumerate(qps_levels):
        lv = run_open_loop_level(service, make_request, qps,
                                 args.seconds_per_level,
                                 args.seed + 7000 + i,
                                 args.drain_timeout_s)
        levels.append(lv)
        print(f"[sweep] target {qps:g} qps: achieved "
              f"{lv['achieved_qps']:g}, p99 "
              f"{lv['p99_ms']}ms, shed {lv['shed']}", file=sys.stderr)
    snap1 = service.metrics.snapshot()
    knee, saturated = find_knee(levels)

    # Bench ↔ scoreboard cross-check (shared provenance): the bench's
    # completed-request count and summed submit→result latency must
    # agree with the serving metrics' deltas over the same window.
    bench_ok = sum(lv["ok"] for lv in levels)
    obs_ok = (snap1["request_latency"]["count"]
              - snap0["request_latency"]["count"])
    bench_lat_s = sum(lv["lat_submit_sum_s"] for lv in levels)
    obs_lat_s = (snap1["request_latency_sum_seconds"]
                 - snap0["request_latency_sum_seconds"])
    req_delta = (abs(bench_ok - obs_ok)
                 / max(bench_ok, obs_ok, 1))
    lat_delta = (abs(bench_lat_s - obs_lat_s)
                 / max(abs(bench_lat_s), abs(obs_lat_s), 1e-9))

    stage0, stage1 = (snap0["stage_seconds_total"],
                      snap1["stage_seconds_total"])
    stage_s = {k: stage1[k] - stage0[k] for k in stage1}
    stage_total = sum(stage_s.values()) or 1.0

    curve = {f"{lv['target_qps']:g}": lv["p99_ms"] for lv in levels}
    secondary = {
        "serving_p99_vs_qps_curve": curve,
        "serving_p50_vs_qps_curve": {
            f"{lv['target_qps']:g}": lv["p50_ms"] for lv in levels},
        "serving_achieved_qps_curve": {
            f"{lv['target_qps']:g}": lv["achieved_qps"]
            for lv in levels},
        "serving_shed_per_level": {
            f"{lv['target_qps']:g}": lv["shed"] for lv in levels},
        "serving_knee_saturated": saturated,
        "serving_sweep_levels": levels,
        "serving_sweep_recompiles":
            snap1["compiles_total"] - snap0["compiles_total"],
        "serving_bench_requests": bench_ok,
        "serving_obs_requests": obs_ok,
        "serving_bench_vs_metrics_request_delta": round(req_delta, 4),
        "serving_bench_latency_total_s": round(bench_lat_s, 4),
        "serving_obs_latency_total_s": round(obs_lat_s, 4),
        "serving_bench_vs_metrics_latency_delta": round(lat_delta, 4),
        "serving_queue_depth_peak": snap1["queue_depth_peak"],
        "model_load_seconds": round(load_seconds, 3),
        "seconds_per_level": args.seconds_per_level,
        "max_batch": args.max_batch,
        "max_wait_ms": args.max_wait_ms,
        "cache_entities": args.cache_entities,
        "num_entities": args.num_entities,
        "config": f"E={args.num_entities} d_global={args.d_global} "
                  f"d_re={args.d_re} skew={args.entity_skew} "
                  f"open-loop",
    }
    for stage, s in stage_s.items():
        secondary[f"serving_stage_fraction_{stage}"] = \
            round(s / stage_total, 4)
    out = {
        "metric": "serving_saturation_knee_qps",
        "value": knee,
        "unit": "qps",
        "secondary": secondary,
    }
    if secondary["serving_sweep_recompiles"] != 0:
        print("WARNING: the sweep recompiled — bucketing is broken",
              file=sys.stderr)
    if max(req_delta, lat_delta) > 0.10:
        print(f"WARNING: bench and serving metrics disagree "
              f"(requests {req_delta:.1%}, latency {lat_delta:.1%}) — "
              f"they share provenance and cannot both be right",
              file=sys.stderr)
    return out


# -- closed-loop (the original bench) ----------------------------------------


def run_closed_loop(args, service, make_request, load_seconds):
    def client(cid, count, record):
        r = np.random.default_rng(args.seed + 1000 + cid)
        reqs = [make_request(r) for _ in range(count)]
        for req in reqs:
            t = time.perf_counter()
            service.submit(req).result(timeout=60)
            if record is not None:
                record.append(time.perf_counter() - t)

    # Warmup: touch every bucket shape (lone requests through the deadline
    # path + full concurrent batches) so steady state owns its programs.
    warm_rng = np.random.default_rng(args.seed + 99)
    for n in (1, 2, 4, 8):
        for req in [make_request(warm_rng) for _ in range(n)]:
            service.submit(req)
        time.sleep(0.05)
    with concurrent.futures.ThreadPoolExecutor(args.clients) as ex:
        list(ex.map(lambda c: client(c, 40, None), range(args.clients)))
    compiles_after_warmup = service.metrics.snapshot()["compiles_total"]
    rows_after_warmup = service.metrics.snapshot()["rows_total"]

    # Measured steady-state phase.
    latencies: list[float] = []
    t0 = time.perf_counter()
    threads = [threading.Thread(
        target=client, args=(c, args.requests_per_client, latencies))
        for c in range(args.clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0

    snap = service.metrics.snapshot()
    lat = np.asarray(latencies) * 1e3
    total = len(latencies)
    out = {
        "metric": "serving_p99_latency_ms",
        "value": round(float(np.percentile(lat, 99)), 4),
        "unit": "ms",
        "secondary": {
            "p50_latency_ms": round(float(np.percentile(lat, 50)), 4),
            "p95_latency_ms": round(float(np.percentile(lat, 95)), 4),
            "mean_latency_ms": round(float(lat.mean()), 4),
            "throughput_rows_per_sec": round(total / wall, 1),
            "steady_state_seconds": round(wall, 3),
            "steady_state_requests": total,
            "batch_fill_ratio": round(snap["batch_fill_ratio"], 4),
            "re_cache_hit_rate": round(
                snap["re_cache"]["per-user"]["hit_rate"], 4),
            "re_cache_evictions": snap["re_cache"]["per-user"]["evictions"],
            "unseen_rows": snap["re_cache"]["per-user"]["unseen"],
            "compiles_total": snap["compiles_total"],
            "steady_state_recompiles":
                snap["compiles_total"] - compiles_after_warmup,
            "warmup_rows": rows_after_warmup,
            "model_load_seconds": round(load_seconds, 3),
            "clients": args.clients,
            "max_batch": args.max_batch,
            "max_wait_ms": args.max_wait_ms,
            "cache_entities": args.cache_entities,
            "num_entities": args.num_entities,
            "config": f"E={args.num_entities} d_global={args.d_global} "
                      f"d_re={args.d_re} skew={args.entity_skew}",
        },
    }
    if out["secondary"]["steady_state_recompiles"] != 0:
        print("WARNING: steady state recompiled — bucketing is broken",
              file=sys.stderr)
    return out


# -- publish arm (continuous publication under load) -------------------------


def run_publish(args):
    """One open-loop constant-QPS stream with a refit→delta→hot-swap
    landing at the half-way mark: the bench form of the zero-drop
    contract. What it holds: the swap wall is
    bounded, p99 inside the swap window stays within band of steady
    state, and NOT ONE request goes unserved."""
    import tempfile

    from photon_ml_tpu.game.refit import RefitBatch, refit_rows
    from photon_ml_tpu.serving import (BatcherQueueFull,
                                       DeadlineExceeded, DeltaStore,
                                       ScoringService)
    from photon_ml_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    model = build_model(args)
    t_load0 = time.perf_counter()
    service = ScoringService(
        model, max_batch=args.max_batch, max_wait_ms=args.max_wait_ms,
        cache_entities=args.cache_entities)
    load_seconds = time.perf_counter() - t_load0
    make_request = make_request_factory(args)
    warmup(service, make_request, args)
    compiles_after_warmup = service.metrics.snapshot()["compiles_total"]

    # Cut the delta through the real path: logged tuples for the
    # hottest entities (device-cached under Zipf — the swap must
    # invalidate live slots), per-entity refit, versioned artifact.
    rng = np.random.default_rng(args.seed + 17)
    k = min(args.publish_dirty_entities, args.num_entities)
    per = max(1, args.publish_tuples_per_entity)
    ids = np.repeat(np.arange(k, dtype=np.int64), per)
    n = ids.shape[0]
    batch = RefitBatch(
        "userId", "re_userId", ids,
        rng.normal(size=(n, args.d_re)).astype(np.float32),
        (rng.random(n) < 0.5).astype(np.float32),
        (rng.normal(size=n) * 0.3).astype(np.float32))
    t_refit0 = time.perf_counter()
    dirty, rows, refit_stats = refit_rows(model, "per-user", batch)
    refit_seconds = time.perf_counter() - t_refit0
    store = DeltaStore(tempfile.mkdtemp(prefix="photon-publish-bench-"))
    delta = store.write({"per-user": (dirty, rows)})

    qps = args.publish_qps
    total = max(1, int(round(qps * args.publish_seconds)))
    period = 1.0 / qps
    reqs = [make_request(rng) for _ in range(total)]
    swap = {"t0": None, "t1": None}

    def _swap():
        swap["t0"] = time.perf_counter()
        service.apply_delta(store.read(delta.version))
        swap["t1"] = time.perf_counter()

    timer = threading.Timer(args.publish_seconds / 2.0, _swap)
    lock = threading.Lock()
    records = []  # (t_sched, latency_s | None, kind)
    drained = threading.Event()
    state = {"dispatched": 0, "completed": 0, "done": False}

    def _cb(t_sched):
        def _inner(fut):
            t_end = time.perf_counter()
            exc = fut.exception()
            with lock:
                state["completed"] += 1
                if exc is None:
                    records.append((t_sched, t_end - t_sched, "ok"))
                elif isinstance(exc, DeadlineExceeded):
                    records.append((t_sched, None, "deadline"))
                else:
                    records.append((t_sched, None, "error"))
                if state["done"] and \
                        state["completed"] == state["dispatched"]:
                    drained.set()
        return _inner

    shed = 0
    timer.start()
    t0 = time.perf_counter()
    try:
        for i, req in enumerate(reqs):
            t_sched = t0 + i * period
            delay = t_sched - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            try:
                fut = service.submit(req)
            except BatcherQueueFull:
                with lock:
                    records.append((t_sched, None, "shed"))
                shed += 1
                continue
            with lock:
                state["dispatched"] += 1
            fut.add_done_callback(_cb(t_sched))
        with lock:
            state["done"] = True
            if state["completed"] == state["dispatched"]:
                drained.set()
        drained.wait(timeout=args.drain_timeout_s)
        timer.join()
    finally:
        timer.cancel()
        snap = service.metrics.snapshot()
        service.close()
    if swap["t1"] is None:
        raise RuntimeError("the swap never ran — raise "
                           "--publish-seconds")
    swap_seconds = swap["t1"] - swap["t0"]
    # The swap window, padded a batcher flush either side: requests
    # scheduled here felt the swap (if anything did).
    pad = max(0.05, 4 * args.max_wait_ms / 1e3)
    w0, w1 = swap["t0"] - pad, swap["t1"] + pad
    lat_in = [l for t, l, kind in records
              if kind == "ok" and w0 <= t <= w1]
    lat_out = [l for t, l, kind in records
               if kind == "ok" and not w0 <= t <= w1]
    unserved = sum(1 for _, _, kind in records
                   if kind in ("deadline", "error"))

    def _p99(xs):
        return (round(float(np.percentile(np.asarray(xs) * 1e3, 99)), 4)
                if xs else None)

    out = {
        "metric": "publish_swap_seconds",
        "value": round(swap_seconds, 6),
        "unit": "s",
        "secondary": {
            "publish_qps": qps,
            "publish_requests_offered": total,
            "publish_ok": len(lat_in) + len(lat_out),
            "publish_shed": shed,
            "publish_unserved": unserved,
            "publish_rows_swapped": int(delta.num_rows),
            "publish_dirty_entities": int(dirty.shape[0]),
            "publish_refit_seconds": round(refit_seconds, 4),
            "publish_refit_groups": refit_stats["groups"],
            "publish_applied_version": snap["model_version"],
            "publish_invalidated_slots_possible": int(k),
            "publish_swap_window_s": round(w1 - w0, 4),
            "publish_requests_in_swap_window": len(lat_in),
            "publish_p99_steady_ms": _p99(lat_out),
            "publish_p99_swap_window_ms": _p99(lat_in),
            "publish_p50_steady_ms": (round(float(np.percentile(
                np.asarray(lat_out) * 1e3, 50)), 4) if lat_out
                else None),
            # A swap must never recompile: the score program is a
            # function of the cache TABLES, not the rows in them.
            "publish_sweep_recompiles":
                snap["compiles_total"] - compiles_after_warmup,
            "model_load_seconds": round(load_seconds, 3),
            "config": f"E={args.num_entities} d_re={args.d_re} "
                      f"skew={args.entity_skew} publish open-loop",
        },
    }
    if unserved:
        print(f"WARNING: {unserved} request(s) went unserved across "
              f"the publish — the zero-drop contract is broken",
              file=sys.stderr)
    return out


# -- fleet chaos sweep -------------------------------------------------------


def _fleet_request_objs(args, n, seed):
    """Deterministic Zipf request stream as JSON-ready /score objects."""
    rng = np.random.default_rng(seed)
    E, dg, dr = args.num_entities, args.d_global, args.d_re
    p = 1.0 / np.arange(1, E + 1) ** args.entity_skew
    p /= p.sum()
    objs = []
    for i in range(n):
        if rng.random() < args.unseen_frac:
            eid = E + int(rng.integers(0, 1000))
        else:
            eid = int(rng.choice(E, p=p))
        objs.append({
            "features": {
                "global": rng.normal(size=dg).astype(
                    np.float32).tolist(),
                "re_userId": rng.normal(size=dr).astype(
                    np.float32).tolist()},
            "entity_ids": {"userId": eid},
            "uid": i,
        })
    return objs


def _post_score(url, obj, timeout_s=30.0):
    import urllib.request

    body = json.dumps({"requests": [obj]}).encode()
    req = urllib.request.Request(
        url + "/score", data=body,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout_s) as resp:
        return json.loads(resp.read())


def run_fleet(args, load_seconds_unused=None):
    """The open-loop Zipf sweep against a real replicated fleet, with a
    deterministic replica SIGKILL mid-sweep (``--fault-plan`` semantics:
    the plan is written to the fleet workdir and armed inside every
    replica). Reports the re-home window, p99 inside vs outside the
    failure window, and request-level parity against the in-process
    single-process ScoringService — the chaos acceptance line.
    """
    import tempfile
    import urllib.error
    import urllib.request

    from photon_ml_tpu import faults as flt
    from photon_ml_tpu.models import io as model_io
    from photon_ml_tpu.serving import ScoringRequest, ScoringService
    from photon_ml_tpu.serving.fleet import (ServingFleet,
                                             make_fleet_http_server)
    from photon_ml_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    model = build_model(args)
    workdir = tempfile.mkdtemp(prefix="photon-fleet-bench-")
    model_dir = os.path.join(workdir, "model")
    model_io.save_game_model(model, model_dir)

    # The kill, addressed deterministically: the doomed replica dies at
    # its --fleet-kill-at-flush'th flush (warmup flushes count — same
    # plan, same traffic, same death every run).
    plan = flt.FaultPlan(specs=(flt.FaultSpec(
        site="fleet.replica_flush", kind="replica_kill",
        indices=(args.fleet_kill_replica,),
        occurrences=(args.fleet_kill_at_flush,)),))
    plan_path = os.path.join(workdir, "fault-plan.json")
    with open(plan_path, "w") as f:
        f.write(plan.to_json())

    qps_levels = [float(q) for q in str(args.fleet_qps).split(",") if q]
    n_total = sum(max(1, int(round(q * args.seconds_per_level)))
                  for q in qps_levels)
    objs = _fleet_request_objs(args, n_total, args.seed + 31)

    # Local oracle: the single-process service scores the same stream;
    # fleet scores must be bit-identical (PR 1 parity, fleet edition).
    oracle_service = ScoringService(
        model, max_batch=args.max_batch, max_wait_ms=args.max_wait_ms,
        cache_entities=args.cache_entities)
    oracle_reqs = [ScoringRequest(
        features={k: np.asarray(v, np.float32)
                  for k, v in o["features"].items()},
        entity_ids=o["entity_ids"]) for o in objs]
    expected = np.asarray(oracle_service.score(oracle_reqs), np.float32)
    oracle_service.close()

    t_load0 = time.perf_counter()
    fleet = ServingFleet(
        replica_args=["--model-dir", model_dir,
                      "--max-batch", str(args.max_batch),
                      "--max-wait-ms", str(args.max_wait_ms),
                      "--cache-entities", str(args.cache_entities)],
        num_replicas=args.fleet_replicas,
        workdir=os.path.join(workdir, "fleet"),
        num_shards=args.fleet_num_shards,
        hedge_after_s=args.fleet_hedge_after_ms / 1e3,
        probe_interval_s=0.1, heartbeat_deadline_s=1.0,
        rehome_deadline_s=args.fleet_rehome_deadline_s,
        fault_plan_file=plan_path)
    fleet.start()
    server = make_fleet_http_server(fleet, port=0)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{server.server_address[1]}"

    # Degraded-window sampler: the failure window the p99 split uses is
    # OBSERVED (healthz flips), not assumed from the kill address.
    samples = []
    sampling = threading.Event()
    sampling.set()

    def _sample():
        while sampling.is_set():
            try:
                with urllib.request.urlopen(url + "/healthz",
                                            timeout=2.0) as r:
                    hz = json.loads(r.read())
                samples.append((time.perf_counter(),
                                bool(hz.get("degraded"))))
            except (OSError, ValueError):
                samples.append((time.perf_counter(), True))
            time.sleep(0.05)

    sampler = threading.Thread(target=_sample, daemon=True)

    results = []  # (idx, t_sched, latency_s | None, kind, score | None)
    res_lock = threading.Lock()

    def _one(idx, obj, t_sched):
        try:
            payload = _post_score(url, obj)
            t_end = time.perf_counter()
            with res_lock:
                results.append((idx, t_sched, t_end - t_sched, "ok",
                                float(payload["scores"][0])))
        except urllib.error.HTTPError as e:
            kind = "shed" if e.code == 503 else "error"
            with res_lock:
                results.append((idx, t_sched, None, kind, None))
        except (OSError, ValueError):
            with res_lock:
                results.append((idx, t_sched, None, "error", None))

    try:
        # Warmup: one request per shard-ish so both replicas own their
        # bucket-1 program before the clock starts.
        for i in range(2 * args.fleet_replicas):
            _post_score(url, objs[i % len(objs)], timeout_s=60.0)
        sampler.start()
        import concurrent.futures as cf

        pool = cf.ThreadPoolExecutor(max_workers=64)
        try:
            cursor = 0
            futs = []
            t_bench0 = time.perf_counter()
            for qps in qps_levels:
                n = max(1, int(round(qps * args.seconds_per_level)))
                period = 1.0 / qps
                t0 = time.perf_counter()
                for i in range(n):
                    t_sched = t0 + i * period
                    delay = t_sched - time.perf_counter()
                    if delay > 0:
                        time.sleep(delay)
                    obj = objs[cursor]
                    futs.append(pool.submit(_one, cursor, obj, t_sched))
                    cursor += 1
                print(f"[fleet] level {qps:g} qps dispatched",
                      file=sys.stderr)
            cf.wait(futs, timeout=args.drain_timeout_s)
        finally:
            pool.shutdown(wait=False)
        # Let the restart land so the degraded window closes on tape.
        deadline = time.perf_counter() + 30.0
        while time.perf_counter() < deadline:
            if samples and not samples[-1][1]:
                degr = [t for t, d in samples if d]
                if degr and samples[-1][0] > degr[-1]:
                    break
            if not any(d for _, d in samples):
                break
            time.sleep(0.1)
        sampling.clear()
        snap = fleet.metrics.snapshot()
        states = fleet.supervisor.states()
    finally:
        sampling.clear()
        server.shutdown()
        server.server_close()
        fleet.close()

    # Failure window: first degraded sample → first healthy sample
    # after it (padded one sampler period back — the kill predates its
    # first observation).
    degraded_ts = [t for t, d in samples if d]
    if degraded_ts:
        w0 = degraded_ts[0] - 0.1
        later_ok = [t for t, d in samples if not d and t > degraded_ts[-1]]
        w1 = later_ok[0] if later_ok else (degraded_ts[-1] + 0.1)
    else:
        w0 = w1 = None

    # Parity: the repo's cross-batch-shape tolerance (PR 1's parity
    # tests, tests/test_serving.py): XLA reduces different padded batch
    # shapes in different orders, so one-at-a-time vs coalesced flushes
    # agree to rtol 1e-5 / atol 1e-6, and BIT-identity holds only for
    # matching flush shapes — tests/test_fleet.py pins the bit-level
    # contract under controlled concurrency; the bench gates the
    # tolerance form over live coalescing traffic.
    lat_in, lat_out = [], []
    mismatches = bit_mismatches = 0
    checked = 0
    max_abs = 0.0
    shed = errors = 0
    for idx, t_sched, lat, kind, score in results:
        if kind == "shed":
            shed += 1
            continue
        if kind == "error":
            errors += 1
            continue
        checked += 1
        d = abs(float(np.float32(score)) - float(expected[idx]))
        max_abs = max(max_abs, d)
        if np.float32(score) != expected[idx]:
            bit_mismatches += 1
        if d > 1e-6 + 1e-5 * abs(float(expected[idx])):
            mismatches += 1
        if w0 is not None and w0 <= t_sched <= w1:
            lat_in.append(lat)
        else:
            lat_out.append(lat)

    def _p99(xs):
        return (round(float(np.percentile(np.asarray(xs) * 1e3, 99)), 4)
                if xs else None)

    kill_fired = snap["replica_deaths_total"] > 0
    out = {
        "metric": "fleet_rehome_seconds",
        "value": round(snap["rehome_seconds_last"], 6),
        "unit": "s",
        "secondary": {
            "fleet_replicas": args.fleet_replicas,
            "fleet_num_shards": fleet.num_shards,
            "fleet_qps_levels": qps_levels,
            "fleet_requests_offered": n_total,
            "fleet_ok": checked,
            "fleet_shed": shed,
            "fleet_errors": errors,
            "fleet_unserved_total": snap["unserved_total"],
            "fleet_kill_fired": kill_fired,
            "fleet_kill_replica": args.fleet_kill_replica,
            "fleet_kill_at_flush": args.fleet_kill_at_flush,
            "fleet_replica_deaths": snap["replica_deaths_total"],
            "fleet_replica_restarts": snap["replica_restarts_total"],
            "fleet_rehomes": snap["rehomes_total"],
            "fleet_rehome_seconds": round(
                snap["rehome_seconds_last"], 6),
            "fleet_rehome_deadline_s": args.fleet_rehome_deadline_s,
            "fleet_rehome_deadline_misses":
                snap["rehome_deadline_misses_total"],
            "fleet_hedges": snap["hedges_total"],
            "fleet_hedge_wins": snap["hedge_wins_total"],
            "fleet_forward_retries": snap["forward_retries_total"],
            "fleet_p99_steady_ms": _p99(lat_out),
            "fleet_p50_steady_ms": (round(float(np.percentile(
                np.asarray(lat_out) * 1e3, 50)), 4) if lat_out
                else None),
            "fleet_p99_during_failure_ms": _p99(lat_in),
            "fleet_requests_in_failure_window": len(lat_in),
            "fleet_degraded_window_s": (round(w1 - w0, 3)
                                        if w0 is not None else 0.0),
            "fleet_parity_checked": checked,
            "fleet_parity_mismatches": mismatches,
            "fleet_parity_max_abs_diff": max_abs,
            "fleet_parity_ok": mismatches == 0,
            "fleet_parity_bit_mismatches": bit_mismatches,
            "fleet_replica_states_final": {str(k): v
                                           for k, v in states.items()},
            "config": f"E={args.num_entities} d_global={args.d_global} "
                      f"d_re={args.d_re} skew={args.entity_skew} "
                      f"fleet open-loop",
        },
    }
    if not kill_fired:
        print("WARNING: the injected replica_kill never fired — raise "
              "traffic or lower --fleet-kill-at-flush", file=sys.stderr)
    if mismatches:
        print(f"WARNING: {mismatches} fleet scores differ from the "
              f"single-process oracle beyond the cross-shape tolerance "
              f"(max |d| {max_abs:g}) — the parity contract is broken",
              file=sys.stderr)
    return out


# -- Zipf-skew sweep (elastic vs static map) ---------------------------------


def _fleet_open_loop_level(url, objs, qps, seconds, drain_timeout_s):
    """One constant-arrival level against a fleet front door; returns
    a level dict in the find_knee shape (latency measured from the
    SCHEDULED arrival — no coordinated omission)."""
    import concurrent.futures as cf
    import urllib.error

    n = max(1, int(round(qps * seconds)))
    lock = threading.Lock()
    state = {"lat": [], "shed": 0, "errors": 0, "t_last": 0.0}

    def _one(obj, t_sched):
        try:
            _post_score(url, obj, timeout_s=30.0)
            t_end = time.perf_counter()
            with lock:
                state["lat"].append(t_end - t_sched)
                state["t_last"] = max(state["t_last"], t_end)
        except urllib.error.HTTPError as e:
            with lock:
                if e.code == 503:
                    state["shed"] += 1
                else:
                    state["errors"] += 1
        except (OSError, ValueError):
            with lock:
                state["errors"] += 1

    pool = cf.ThreadPoolExecutor(max_workers=64)
    futs = []
    period = 1.0 / qps
    t0 = time.perf_counter()
    try:
        for i in range(n):
            t_sched = t0 + i * period
            delay = t_sched - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            futs.append(pool.submit(_one, objs[i % len(objs)], t_sched))
        cf.wait(futs, timeout=drain_timeout_s)
    finally:
        pool.shutdown(wait=False)
    elapsed = max(state["t_last"], time.perf_counter()) - t0
    lat = np.asarray(state["lat"]) * 1e3
    ok = len(state["lat"])
    return {
        "target_qps": qps,
        "offered": n,
        "ok": ok,
        "shed": state["shed"],
        "deadline_exceeded": 0,
        "errors": state["errors"],
        "achieved_qps": round(ok / elapsed, 1) if elapsed > 0 else 0.0,
        "p50_ms": round(float(np.percentile(lat, 50)), 4) if ok else None,
        "p99_ms": round(float(np.percentile(lat, 99)), 4) if ok else None,
    }


def _zipf_leg(args, model_dir, workdir, skew, elastic_cfg, tag):
    """One (skew, map-mode) leg: a fresh 2-replica fleet swept over the
    ascending QPS levels; returns (knee, p99@lowest level, evidence)."""
    import argparse as _argparse

    from photon_ml_tpu.serving.fleet import (ServingFleet,
                                             make_fleet_http_server)

    leg_args = _argparse.Namespace(**vars(args))
    leg_args.entity_skew = skew
    qps_levels = [float(q) for q in str(args.zipf_qps).split(",") if q]
    n_objs = int(max(qps_levels) * args.zipf_seconds_per_level) + 64
    objs = _fleet_request_objs(leg_args, n_objs,
                               args.seed + int(skew * 100))
    fleet = ServingFleet(
        replica_args=["--model-dir", model_dir,
                      "--max-batch", str(args.max_batch),
                      "--max-wait-ms", str(args.max_wait_ms),
                      "--cache-entities", str(args.cache_entities)],
        num_replicas=args.fleet_replicas,
        workdir=os.path.join(workdir, tag),
        num_shards=args.fleet_num_shards,
        probe_interval_s=0.1, heartbeat_deadline_s=2.0,
        elastic=elastic_cfg)
    server = None
    try:
        fleet.start()
        server = make_fleet_http_server(fleet, port=0)
        threading.Thread(target=server.serve_forever,
                         daemon=True).start()
        url = f"http://127.0.0.1:{server.server_address[1]}"
        for i in range(2 * args.fleet_replicas):  # warm both programs
            _post_score(url, objs[i % len(objs)], timeout_s=60.0)
        levels = []
        for qps in qps_levels:
            lv = _fleet_open_loop_level(
                url, objs, qps, args.zipf_seconds_per_level,
                args.drain_timeout_s)
            levels.append(lv)
            print(f"[zipf {tag}] s={skew:g} target {qps:g} qps: "
                  f"achieved {lv['achieved_qps']:g}, p99 "
                  f"{lv['p99_ms']}ms, shed {lv['shed']}",
                  file=sys.stderr)
        knee, _saturated = find_knee(levels)
        snap = fleet.metrics.snapshot()
        return knee, levels[0]["p99_ms"], {
            "levels": levels,
            "splits": snap["splits_total"],
            "migrations": snap["migrations_total"],
            "scale_ups": snap["scale_ups_total"],
            "final_replicas": len(fleet.shard_map.live()),
            "final_shards": len(fleet.shard_map.shards()),
        }
    finally:
        if server is not None:
            server.shutdown()
            server.server_close()
        fleet.close()


def run_zipf_sweep(args):
    """The acceptance sweep of ROADMAP item 2: with the elastic loop
    armed, knee QPS and steady p99 must hold as Zipf skew rises (the
    static map's degradation is measured alongside as the comparison
    line). The acceptance: knee at the highest
    skew >= 0.9x the knee at zero skew, p99 in band; on boxes under 4
    cores the fleet shares one core and the knee measures scheduling,
    so the reading is marked (`zipf_sweep_valid: false` — the
    restart-arm discipline)."""
    import tempfile

    from photon_ml_tpu.models import io as model_io
    from photon_ml_tpu.serving import ElasticConfig
    from photon_ml_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    model = build_model(args)
    workdir = tempfile.mkdtemp(prefix="photon-zipf-bench-")
    model_dir = os.path.join(workdir, "model")
    model_io.save_game_model(model, model_dir)
    skews = [float(s) for s in str(args.zipf_skews).split(",") if s]
    elastic_cfg = ElasticConfig(
        interval_s=0.25, heat_window_s=5.0, split_factor=2.0,
        min_heat_requests=16, scale_up_heat_frac=0.6,
        hysteresis_ticks=2, cooldown_s=2.0,
        max_replicas=args.fleet_replicas + 2,
        min_replicas=args.fleet_replicas)

    knees, p99s, evidence = {}, {}, {}
    static_knees, static_p99s = {}, {}
    for skew in skews:
        k, p, ev_ = _zipf_leg(args, model_dir, workdir, skew,
                              elastic_cfg, f"elastic-s{skew:g}")
        knees[f"{skew:g}"] = k
        p99s[f"{skew:g}"] = p
        evidence[f"{skew:g}"] = ev_
    if args.zipf_static:
        for skew in skews:
            k, p, _ = _zipf_leg(args, model_dir, workdir, skew, None,
                                f"static-s{skew:g}")
            static_knees[f"{skew:g}"] = k
            static_p99s[f"{skew:g}"] = p

    lo, hi = f"{min(skews):g}", f"{max(skews):g}"
    retention = (knees[hi] / knees[lo]) if knees.get(lo) else 0.0
    valid = (os.cpu_count() or 1) >= 4
    secondary = {
        "fleet_knee_vs_skew_curve": knees,
        "fleet_p99_vs_skew_curve": p99s,
        "fleet_static_knee_vs_skew_curve": static_knees,
        "fleet_static_p99_vs_skew_curve": static_p99s,
        "fleet_zipf_evidence": evidence,
        "fleet_zipf_qps_levels": str(args.zipf_qps),
        "zipf_sweep_valid": valid,
        "config": f"E={args.num_entities} d_global={args.d_global} "
                  f"d_re={args.d_re} replicas={args.fleet_replicas} "
                  f"skews={args.zipf_skews} open-loop "
                  f"cores={os.cpu_count()}",
    }
    if not valid:
        secondary["zipf_sweep_invalid_reason"] = (
            "box has < 4 cores: the replicas share one core, so the "
            "knee measures scheduling, not shard balance; gates "
            "reported-only")
    if retention < 0.9:
        print(f"WARNING: elastic knee retention {retention:.2f}x at "
              f"s={hi} vs s={lo} — the elastic fleet is losing its "
              f"knee to skew", file=sys.stderr)
    return {
        "metric": "fleet_knee_retention_at_max_skew",
        "value": round(retention, 4),
        "unit": "x",
        "secondary": secondary,
    }


# -- restart arm -------------------------------------------------------------


def _spawn_replica(model_dir, workdir, tag, probe_objs, max_batch):
    """Spawn one ``photon-game-serve`` subprocess over ``model_dir`` and
    wait until it SCORES (ready file → healthz → first /score answers);
    returns (proc, url, ready_to_traffic_seconds). The replica runs with
    ``--boot-warmup`` and a live metrics registry so its
    photon_boot_seconds phase gauges are readable at /metrics."""
    import subprocess
    import urllib.request

    import photon_ml_tpu

    ready = os.path.join(workdir, f"{tag}.ready")
    if os.path.exists(ready):
        os.unlink(ready)
    pkg_root = os.path.dirname(os.path.dirname(
        os.path.abspath(photon_ml_tpu.__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = (pkg_root + os.pathsep + env["PYTHONPATH"]
                         if env.get("PYTHONPATH") else pkg_root)
    log_f = open(os.path.join(workdir, f"{tag}.log"), "ab")
    t0 = time.perf_counter()
    try:
        proc = subprocess.Popen(
            [sys.executable, "-m", "photon_ml_tpu.cli.serve",
             "--model-dir", model_dir, "--port", "0",
             "--max-batch", str(max_batch), "--boot-warmup",
             "--metrics-dump", os.path.join(workdir, f"{tag}.prom"),
             "--ready-file", ready],
            stdout=log_f, stderr=subprocess.STDOUT, env=env)
    finally:
        log_f.close()
    deadline = time.perf_counter() + 300.0
    info = None
    while time.perf_counter() < deadline:
        if proc.poll() is not None:
            raise RuntimeError(
                f"{tag} replica exited rc={proc.returncode} before "
                f"ready (see {workdir}/{tag}.log)")
        if os.path.exists(ready):
            try:
                with open(ready) as f:
                    info = json.load(f)
                break
            except (OSError, ValueError):
                pass
        time.sleep(0.01)
    if info is None:
        raise RuntimeError(f"{tag} replica never wrote its ready file")
    url = f"http://127.0.0.1:{int(info['port'])}"
    while time.perf_counter() < deadline:
        try:
            _post_score(url, probe_objs[0], timeout_s=10.0)
            break
        except OSError:
            time.sleep(0.01)
    else:
        raise RuntimeError(f"{tag} replica never answered /score")
    return proc, url, time.perf_counter() - t0


def _replica_boot_phases(url):
    """photon_boot_seconds{phase=...} off a live replica's /metrics."""
    import urllib.request

    try:
        with urllib.request.urlopen(url + "/metrics",
                                    timeout=10.0) as resp:
            text = resp.read().decode()
    except OSError:
        return {}
    out = {}
    for line in text.splitlines():
        if line.startswith("photon_boot_seconds{phase="):
            phase = line.split('"')[1]
            out[phase] = float(line.rsplit(" ", 1)[1])
    return out


def run_restart(args):
    """npz-boot vs mmap-boot ready-to-traffic walls + the
    rehome-under-restart leg (docs/SERVING.md "Sub-second restart").

    Each format boots twice: the first (cold) spawn warms the OS page
    cache and the persistent XLA compilation cache, the second (warm —
    the restart a production fleet actually pays) is the BENCH wall.
    ``restart_valid`` gates the 0.5× claim to boxes with >= 4 cores:
    on the 1-core CI box the interpreter tail dominates both formats
    and the ratio measures scheduling, not the model tier."""
    import signal
    import tempfile

    from photon_ml_tpu import boot
    from photon_ml_tpu.models import io as model_io
    from photon_ml_tpu.serving import ScoringRequest, ScoringService
    from photon_ml_tpu.serving.fleet import (ServingFleet,
                                             make_fleet_http_server)
    from photon_ml_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    args.num_entities = args.restart_entities
    model = build_model(args)
    workdir = tempfile.mkdtemp(prefix="photon-restart-bench-")
    npz_dir = os.path.join(workdir, "model-npz")
    gen_root = os.path.join(workdir, "model-gens")
    model_io.save_game_model(model, npz_dir)
    boot.GenerationStore(gen_root).publish(model)

    # In-process model-load walls: the parse-vs-mmap claim isolated
    # from interpreter/JAX startup (valid at any core count).
    t0 = time.perf_counter()
    model_io.load_game_model(npz_dir, host=True, mapped=False)
    load_npz = time.perf_counter() - t0
    t0 = time.perf_counter()
    boot.GenerationStore(gen_root).load_current()
    load_mmap = time.perf_counter() - t0

    probe_objs = _fleet_request_objs(args, args.restart_probe_requests,
                                     args.seed + 77)
    oracle = ScoringService(model, max_batch=args.max_batch,
                            max_wait_ms=args.max_wait_ms)
    expected = np.asarray([float(oracle.score([ScoringRequest(
        features={k: np.asarray(v, np.float32)
                  for k, v in o["features"].items()},
        entity_ids=o["entity_ids"])])[0]) for o in probe_objs],
        np.float32)
    oracle.close()

    walls = {}
    parity_ok = True
    for tag, model_dir in (("npz", npz_dir), ("mmap", gen_root)):
        for leg in ("cold", "warm"):
            proc, url, wall = _spawn_replica(
                model_dir, workdir, f"{tag}-{leg}", probe_objs,
                args.max_batch)
            try:
                if leg == "warm":
                    got = np.asarray(
                        [float(_post_score(url, o)["scores"][0])
                         for o in probe_objs], np.float32)
                    parity_ok = parity_ok and np.array_equal(got,
                                                             expected)
                    walls[f"{tag}_phases"] = _replica_boot_phases(url)
                walls[f"{tag}_{leg}"] = wall
            finally:
                proc.send_signal(signal.SIGKILL)
                proc.wait(timeout=30)
            print(f"[restart] {tag} {leg}: ready-to-traffic "
                  f"{wall:.3f}s", file=sys.stderr)

    # Rehome-under-restart: a 2-replica mmap-booted fleet, one replica
    # SIGKILLed mid-stream — every request must still answer (retries
    # follow the re-home), and the p99 over the stream is the tail a
    # restart actually costs traffic.
    fleet = ServingFleet(
        replica_args=["--model-dir", gen_root,
                      "--max-batch", str(args.max_batch),
                      "--max-wait-ms", str(args.max_wait_ms)],
        num_replicas=2, workdir=os.path.join(workdir, "fleet"),
        probe_interval_s=0.1, heartbeat_deadline_s=1.0,
        rehome_deadline_s=args.fleet_rehome_deadline_s)
    server = None
    unserved = 0
    lat = []
    try:
        fleet.start()
        server = make_fleet_http_server(fleet, port=0)
        threading.Thread(target=server.serve_forever,
                         daemon=True).start()
        url = f"http://127.0.0.1:{server.server_address[1]}"
        n = args.restart_traffic_requests
        objs = _fleet_request_objs(args, n, args.seed + 79)
        kill_at = n // 3
        for i, obj in enumerate(objs):
            if i == kill_at:
                handle = fleet.supervisor.replicas[1]
                if handle.proc is not None:
                    os.kill(handle.proc.pid, signal.SIGKILL)
            t0 = time.perf_counter()
            try:
                _post_score(url, obj, timeout_s=60.0)
                lat.append((time.perf_counter() - t0) * 1e3)
            except OSError:
                unserved += 1
        boot_metrics = {
            h.replica_id: round(h.boot_seconds, 3)
            for h in fleet.supervisor.replicas}
    finally:
        if server is not None:
            server.shutdown()
            server.server_close()
        fleet.close()

    lat.sort()
    p99 = lat[min(len(lat) - 1, int(0.99 * len(lat)))] if lat else 0.0
    valid = (os.cpu_count() or 1) >= 4
    secondary = {
        "replica_restart_seconds_npz": round(walls["npz_warm"], 3),
        "replica_restart_seconds_mmap": round(walls["mmap_warm"], 3),
        "replica_restart_cold_seconds_npz": round(walls["npz_cold"], 3),
        "replica_restart_cold_seconds_mmap": round(walls["mmap_cold"],
                                                   3),
        "replica_restart_ratio": round(
            walls["mmap_warm"] / max(walls["npz_warm"], 1e-9), 3),
        "replica_boot_phases_npz": walls.get("npz_phases", {}),
        "replica_boot_phases_mmap": walls.get("mmap_phases", {}),
        "boot_model_load_seconds_npz": round(load_npz, 4),
        "boot_model_load_seconds_mmap": round(load_mmap, 4),
        "boot_map_load_speedup": round(load_npz / max(load_mmap, 1e-9),
                                       2),
        "restart_rehome_p99_ms": round(p99, 2),
        "restart_unserved": unserved,
        "restart_parity_ok": bool(parity_ok),
        "restart_fleet_boot_seconds": boot_metrics,
        "restart_valid": valid,
        "config": f"E={args.restart_entities} d_re={args.d_re} "
                  f"probes={args.restart_probe_requests} "
                  f"traffic={args.restart_traffic_requests} "
                  f"cores={os.cpu_count()}",
    }
    if not valid:
        secondary["restart_invalid_reason"] = (
            "box has < 4 cores: interpreter startup dominates both "
            "boots; ratio gate reported-only")
    return {
        "metric": "replica_restart_seconds_mmap",
        "value": secondary["replica_restart_seconds_mmap"],
        "unit": "s",
        "secondary": secondary,
    }


def run_cache_sweep(args):
    """f32-vs-int8 device LRU at a FIXED HBM budget (ROADMAP item 3's
    serving half): capacity per dtype = budget // row bytes (f32: 4·d;
    int8: d + 4 — table row + its scale slot), so the int8 cache holds
    ~4× the entities of the f32 one on the same spend. One open-loop
    constant-arrival level per dtype over the SAME Zipf draw; the
    hit-rate → p99 movement at equal bytes is the BENCH claim
    (``serving_cache_dtype_sweep``: int8 capacity ≥ 2× f32, int8 hit
    rate ≥ f32's)."""
    from photon_ml_tpu.serving import ScoringService
    from photon_ml_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    model = build_model(args)
    make_request = make_request_factory(args)
    budget = int(args.cache_budget_kb * 1024)
    row_bytes = {"float32": args.d_re * 4, "int8": args.d_re + 4}
    sweep = {}
    for dtype in ("float32", "int8"):
        capacity = max(args.max_batch, budget // row_bytes[dtype])
        service = ScoringService(
            model, max_batch=args.max_batch,
            max_wait_ms=args.max_wait_ms, cache_entities=capacity,
            cache_dtype=dtype)
        try:
            warmup(service, make_request, args)
            snap0 = service.metrics.snapshot()
            lv = run_open_loop_level(service, make_request,
                                     args.cache_sweep_qps,
                                     args.cache_sweep_seconds,
                                     args.seed + 31, args.drain_timeout_s)
            snap1 = service.metrics.snapshot()
            cache0 = snap0["re_cache"]["per-user"]
            cache1 = snap1["re_cache"]["per-user"]
            hits = cache1["hits"] - cache0["hits"]
            misses = cache1["misses"] - cache0["misses"]
            sweep[dtype] = {
                "capacity": int(service.store.random[0].capacity),
                "device_bytes": service.store.device_cache_bytes(),
                "hit_rate": round(hits / max(hits + misses, 1), 4),
                "p99_ms": lv["p99_ms"],
                "p50_ms": lv["p50_ms"],
                "ok": lv["ok"],
                "recompiles": (snap1["compiles_total"]
                               - snap0["compiles_total"]),
            }
            print(f"[cache-sweep] {dtype}: capacity "
                  f"{sweep[dtype]['capacity']}, hit rate "
                  f"{sweep[dtype]['hit_rate']:.1%}, p99 "
                  f"{sweep[dtype]['p99_ms']}ms", file=sys.stderr)
        finally:
            service.close()
    secondary = {
        "serving_cache_dtype_sweep": sweep,
        "serving_cache_sweep_budget_bytes": budget,
        "serving_int8_cache_capacity_ratio": round(
            sweep["int8"]["capacity"]
            / max(sweep["float32"]["capacity"], 1), 2),
        "serving_int8_hit_rate": sweep["int8"]["hit_rate"],
        "serving_f32_hit_rate": sweep["float32"]["hit_rate"],
        "serving_cache_sweep_recompiles": (
            sweep["float32"]["recompiles"] + sweep["int8"]["recompiles"]),
        "config": f"E={args.num_entities} d_re={args.d_re} "
                  f"skew={args.entity_skew} budget="
                  f"{args.cache_budget_kb:g}KiB "
                  f"qps={args.cache_sweep_qps:g} open-loop",
    }
    return {
        "metric": "serving_int8_cache_capacity_ratio",
        "value": secondary["serving_int8_cache_capacity_ratio"],
        "unit": "x",
        "secondary": secondary,
    }


def _refuse_fleet_mode_on_tpu_host(mode):
    """--fleet, --zipf-sweep and --restart score an in-process oracle —
    this parent initialises a JAX backend and so holds every chip — and
    then start replica processes that each need one. A chip belongs to
    one process, so on a TPU host those modes do not run until the
    oracle moves out of the parent."""
    from photon_ml_tpu.fabric.transport import local_tpu_chips

    chips = local_tpu_chips()
    if chips:
        raise SystemExit(
            f"bench_serving {mode}: refused on a TPU host ({len(chips)} "
            f"chip(s)): this process scores the oracle itself, would "
            f"hold the chips, and its replica processes could get none "
            f"— run it with JAX_PLATFORMS=cpu for counts and parity")


def main(argv=None):
    args = build_parser().parse_args(argv)
    for mode in ("restart", "zipf_sweep", "fleet"):
        if getattr(args, mode):
            _refuse_fleet_mode_on_tpu_host("--" + mode.replace("_", "-"))
    if args.restart:
        out = run_restart(args)
        json.dump(out, sys.stdout)
        print()
        return 0
    if args.cache_sweep:
        out = run_cache_sweep(args)
        json.dump(out, sys.stdout)
        print()
        return 0
    if args.publish:
        out = run_publish(args)
        json.dump(out, sys.stdout)
        print()
        return 0
    if args.zipf_sweep:
        out = run_zipf_sweep(args)
        json.dump(out, sys.stdout)
        print()
        return 0
    if args.fleet:
        out = run_fleet(args)
        json.dump(out, sys.stdout)
        print()
        return 0
    service, load_seconds = build_service(args)
    try:
        if args.closed_loop:
            out = run_closed_loop(args, service, make_request_factory(args),
                                  load_seconds)
        else:
            out = run_sweep(args, service, make_request_factory(args),
                            load_seconds)
    finally:
        service.close()
    json.dump(out, sys.stdout)
    print()
    return 0


if __name__ == "__main__":
    sys.exit(main())
