#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

One process, the normal entry points, one model at full width:

1. every kernel in the registry, switched on, compiled for the TPU and
   compared with its XLA closure;
2. ``photon_ml_tpu.cli.game_train`` (its ``main``, the argv a user types)
   trains BASELINE config 4 — logistic GLMix, a dense global fixed effect
   plus per-user and per-item random effects at MovieLens-20M's entity
   widths (138,493 users, 26,744 items; 32 global features, 8 per entity;
   ``max_samples=65536``) — for two descent iterations with validation
   AUC. Width is not cut; the row count is: ~2M seeded synthetic rows
   with planted effects instead of 20M ratings, weights random from the
   seed;
3. ``photon_ml_tpu.cli.serve.create_server`` serves the model directory
   that run wrote, on a thread, and answers a few dozen HTTP ``/score``
   requests of mixed batch sizes, some for entities it never saw.

It checks, by the repo's own means, that the objective is finite and does
not rise between iterations, that validation AUC clears a floor against
the planted effects, that served scores equal a plain-NumPy score of the
same rows from the saved coefficients, that an unseen entity scores as the
fixed effect alone, that nothing fell back or compiled behind the
server's back, and — with several devices — that the data is spread over
all of them. Any failed check is an exception and a non-zero exit.

    python chip_smoke.py                 # on the chip; the last stdout line
                                         # is {"ok": true, "device": {...}}
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearsal
                                         # tiny, interpreted kernels; prints
                                         # REHEARSAL (cpu), never the pass line

Without ``--rehearsal`` the script exits non-zero before any work unless
the platform is ``tpu``. It needs no file git ignores and no network, and
starts no other process.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import dataclasses
import json
import os
import shutil
import sys
import tempfile
import threading
import time
import urllib.request

import numpy as np

SEED = 2026
D_GLOBAL, D_ENTITY = 32, 8
SEQUENCE = ("fixed", "per-user", "per-item")


@dataclasses.dataclass(frozen=True)
class Size:
    rows: int
    users: int
    items: int
    auc_floor: float
    http_requests: int


# MovieLens-20M's entity widths (BASELINE.json config 4); rows cut 10x.
FULL = Size(rows=2_000_000, users=138_493, items=26_744, auc_floor=0.80,
            http_requests=36)
TINY = Size(rows=4_000, users=96, items=48, auc_floor=0.65,
            http_requests=12)


def leg(name: str, t0: float) -> None:
    print(f"LEG {name}: ok ({time.monotonic() - t0:.1f}s)", flush=True)


# ---------------------------------------------------------------- kernels

def kernel_cases(rng, full: bool) -> dict:
    """name -> (arrays, static tail, exact?) for every registered kernel,
    at TPU shapes (small ones for the interpreter). ``exact`` cases hold integers and power-of-two scales,
    so any summation order gives the same bits."""
    import jax.numpy as jnp

    def pick(big, small):
        return big if full else small

    n_sc, k_sc, d_sc = pick((1 << 17, 32, 512), (512, 8, 256))
    n_sv, d_sv, e_sv = pick((4096, 512, 8192), (32, 128, 64))
    n_st, h_st = pick((1 << 15, 4096), (256, 512))
    e_re, d_re, b_re = pick((8192, 256, 2048), (64, 64, 16))

    def f32(a):
        return jnp.asarray(np.asarray(a, np.float32))

    slots = jnp.asarray(rng.integers(0, e_sv, n_sv).astype(np.int32))
    codes = jnp.asarray(
        rng.integers(-127, 128, (e_sv, d_sv)).astype(np.int8))
    X_hot = jnp.asarray(
        rng.integers(-127, 128, (n_st, h_st)).astype(np.int8))
    # Rows are unique within a wave (the bucket-solve contract) and the
    # ragged tail carries invalid (-1) lanes.
    rows = rng.permutation(e_re)[:b_re].astype(np.int32)
    rows[:: max(b_re // 8, 1)] = -1
    W = f32(rng.normal(size=(e_re, d_re)))
    return {
        "ell_scatter": ((
            jnp.asarray(rng.integers(0, d_sc, (n_sc, k_sc))
                        .astype(np.int32)),
            f32(rng.normal(size=(n_sc, k_sc)))), (d_sc,), False),
        "serving_score": ((
            f32(rng.normal(size=(n_sv, d_sv))), slots, codes,
            f32(rng.uniform(1e-3, 2.0, e_sv))), (), False),
        "serving_score/f32-rows": ((
            f32(rng.integers(-8, 9, (n_sv, d_sv))), slots,
            f32(rng.integers(-127, 128, (e_sv, d_sv)))), (None,), True),
        "stream_margins": ((
            X_hot, f32(rng.normal(size=h_st)),
            f32(rng.normal(size=n_st))), (), False),
        "stream_rmatvec": ((X_hot, f32(rng.normal(size=n_st))), (), False),
        "re_gather_rows": ((W, jnp.asarray(rows)), (), True),
        "re_scatter_rows": ((
            W, jnp.asarray(rows), f32(rng.normal(size=(b_re, d_re)))),
            (), True),
    }


def kernel_leg(on_tpu: bool) -> None:
    """Resolve every registered kernel through the registry's own seam
    with its flag forced on — compiled on the TPU, interpreted only in a
    rehearsal — and hold it to its XLA closure."""
    import jax

    from photon_ml_tpu.ops import kernels

    reg = kernels.registry()
    cases = kernel_cases(np.random.default_rng(SEED), full=on_tpu)
    covered = {name.split("/")[0] for name in cases}
    assert covered == set(reg.names()), (
        f"kernel cases {sorted(covered)} out of step with the registry "
        f"{reg.names()}")
    if not on_tpu:
        reg.force_interpret()
    try:
        for case, (arrays, tail, exact) in cases.items():
            name = case.split("/")[0]
            reg.set_enabled(name, True)
            resolved = reg.resolve(name)
            assert resolved.backend == "pallas", resolved
            assert resolved.interpret == (not on_tpu), resolved
            got = np.asarray(jax.jit(
                lambda *a, _f=resolved.fn: _f(*a, *tail))(*arrays))
            want = np.asarray(jax.jit(
                lambda *a, _f=reg.get(name).xla_fn: _f(*a, *tail))(*arrays))
            assert got.shape == want.shape and np.all(np.isfinite(got))
            if exact:
                assert np.array_equal(got, want), f"{case}: bits differ"
                band = "bit-exact"
            else:
                rel = (float(np.max(np.abs(got - want)))
                       / max(float(np.max(np.abs(want))), 1e-9))
                assert rel <= 1e-3, f"{case}: relative delta {rel:.3g}"
                band = f"rel {rel:.2g} <= 1e-3"
            how = "compiled" if on_tpu else "interpreted"
            print(f"  kernel {case}: pallas {how}, {band}", flush=True)
    finally:
        reg.reset()


# ------------------------------------------------------------------- data

def write_data(size: Size, workdir: str):
    """Seeded GLMix data with planted effects, through the dataset format
    the drivers read. Returns the held-out split (the serving leg scores
    rows of it)."""
    from photon_ml_tpu.data import synthetic
    from photon_ml_tpu.data.game_data import from_synthetic
    from photon_ml_tpu.data.io import save_game_dataset

    syn = synthetic.game_data(
        np.random.default_rng(SEED), n=size.rows, d_global=D_GLOBAL,
        re_specs={"userId": (size.users, D_ENTITY),
                  "itemId": (size.items, D_ENTITY)},
        task="logistic")
    ds = from_synthetic(syn)
    n_val = max(size.rows // 20, 1)
    val = ds.subset(np.arange(size.rows - n_val, size.rows))
    save_game_dataset(ds.subset(np.arange(size.rows - n_val)),
                      os.path.join(workdir, "train"))
    save_game_dataset(val, os.path.join(workdir, "val"))
    return val


# ------------------------------------------------------------------ train

def placement_snapshot() -> dict:
    """Where every live device array sits, plus each device's bytes in
    use. Taken from an event listener mid-run, so it only records: the
    emitter detaches a listener that raises, and a check that fails must
    fail the run."""
    import jax

    stats = [d.memory_stats() for d in jax.devices()]
    return {
        "arrays": [(a.shape, len(a.sharding.device_set),
                    a.sharding.is_fully_replicated)
                   for a in jax.live_arrays()],
        "bytes_in_use": (None if all(s is None for s in stats)
                         else [int(s["bytes_in_use"]) for s in stats]),
    }


def check_spread(size: Size, snapshot: dict, devices: int) -> str:
    """With several devices: the fixed-effect batch and the staged
    random-effect blocks are sharded over all of them, each trained
    random-effect table is placed on all of them, and every device holds
    bytes (nothing piled up on device 0)."""
    if devices == 1:
        return "one device"
    arrays = snapshot["arrays"]
    batch = [a for a in arrays if len(a[0]) == 2
             and a[0][1] == D_GLOBAL and a[0][0] >= size.rows // 2]
    blocks = [a for a in arrays if len(a[0]) == 3 and a[0][2] == D_ENTITY]
    assert batch and blocks, (len(batch), len(blocks))
    for shape, n, replicated in batch + blocks:
        assert n == devices and not replicated, (
            f"{shape} is not sharded over all {devices} devices "
            f"(on {n}, replicated={replicated})")
    for e in (size.users, size.items):
        placed = [n for shape, n, _ in arrays if shape == (e, D_ENTITY)]
        assert devices in placed, (
            f"no ({e}, {D_ENTITY}) table on all {devices} devices: "
            f"{placed}")
    held = snapshot["bytes_in_use"]
    if held is None:
        held = "not reported by this backend"
    else:
        assert all(b > 0 for b in held), f"a device holds nothing: {held}"
    return (f"{len(batch)} batch and {len(blocks)} block arrays sharded, "
            f"both tables placed, over {devices} devices; bytes in use "
            f"{held}")


def train_leg(size: Size, workdir: str, platform: str) -> dict:
    from photon_ml_tpu.cli import game_train
    from photon_ml_tpu.obs.ledger import read_rows, verify_ledger
    from photon_ml_tpu.utils import events

    out = os.path.join(workdir, "out")
    t0 = time.monotonic()
    updates = []  # (iteration, coordinate, seconds since t0, validation)
    snapshots = []

    def on_event(event):
        if isinstance(event, events.CoordinateUpdate):
            updates.append((event.iteration, event.coordinate,
                            time.monotonic() - t0, event.validation))
            if (event.iteration, event.coordinate) == (0, SEQUENCE[-1]):
                snapshots.append(placement_snapshot())

    opt = "optimizer=LBFGS,max_iter=25,reg=L2,reg_weight=1.0"
    argv = [
        "--train", os.path.join(workdir, "train"),
        "--validation", os.path.join(workdir, "val"),
        "--task", "LOGISTIC_REGRESSION",
        "--coordinate", "name=fixed,type=fixed,shard=global",
        "--coordinate", "name=per-user,type=random,shard=re_userId,"
                        "re=userId,max_samples=65536",
        "--coordinate", "name=per-item,type=random,shard=re_itemId,"
                        "re=itemId,max_samples=65536",
        "--update-sequence", ",".join(SEQUENCE),
        "--iterations", "2", "--evaluators", "AUC",
        "--output-dir", out,
        "--metrics-dump", os.path.join(workdir, "train-metrics.prom"),
    ]
    for cid in SEQUENCE:
        argv += ["--opt-config", f"{cid}:{opt}"]
    events.default_emitter.register(on_event)
    try:
        game_train.main(argv)
    finally:
        events.default_emitter.unregister(on_event)

    with open(os.path.join(out, "summary.json")) as f:
        summary = json.load(f)
    assert summary["device"]["platform"] == platform, summary["device"]
    assert [(i, c) for i, c, _, _ in updates] == [
        (i, c) for i in (0, 1) for c in SEQUENCE], updates

    # The objective, from the run ledger: the fixed effect's L-BFGS value
    # is the whole training loss given the other coordinates' scores.
    assert verify_ledger(summary["ledger"]["dir"]) == []
    rows, _ = read_rows(summary["ledger"]["dir"])
    values = [(r["outer_iteration"], r["value"]) for r in rows
              if r.get("kind") == "opt_iter" and r["coordinate"] == "fixed"]
    assert values and all(np.isfinite(v) for _, v in values), values
    final = {it: v for it, v in values}  # last row of each iteration
    assert final[1] <= final[0], (
        f"objective rose between iterations: {final[0]} -> {final[1]}")

    auc = summary["best_metrics"]["AUC"]
    assert abs(auc - updates[-1][3]["AUC"]) < 1e-6, (auc, updates[-1])
    assert auc >= size.auc_floor, (
        f"validation AUC {auc:.4f} under the floor {size.auc_floor}")
    first = updates[len(SEQUENCE) - 1][2]
    print(f"  objective (fixed effect, end of iteration): "
          f"{final[0]:.6g} -> {final[1]:.6g}; validation AUC {auc:.4f} "
          f"(floor {size.auc_floor})", flush=True)
    print(f"  seconds to first trained iteration {first:.1f} (load, "
          f"staging and compilation included), second iteration "
          f"{updates[-1][2] - first:.1f}", flush=True)
    placement = check_spread(size, snapshots[0], summary["device"]["count"])
    print(f"  placement: {placement}", flush=True)
    return {"auc": auc, "model_digest": summary["model_digest"],
            "model_dir": os.path.join(out, "best")}


# ------------------------------------------------------------------ serve

def saved_means(model_dir: str) -> dict:
    """The coefficients as the trainer wrote them, read with NumPy."""
    out = {}
    for kind, cid in (("fixed-effect", "fixed"),
                      ("random-effect", "per-user"),
                      ("random-effect", "per-item")):
        with np.load(os.path.join(model_dir, kind, cid,
                                  "coefficients.npz")) as z:
            out[cid] = z["means"].astype(np.float64)
    return out


def numpy_scores(means: dict, val, rows: np.ndarray,
                 unseen: np.ndarray) -> np.ndarray:
    """The offline reference: plain NumPy over the saved coefficients.
    ``unseen`` rows get the fixed effect alone."""
    score = (val.feature_shards["global"][rows].astype(np.float64)
             @ means["fixed"] + val.offsets[rows])
    for cid, re_type in (("per-user", "userId"), ("per-item", "itemId")):
        x = val.feature_shards[f"re_{re_type}"][rows].astype(np.float64)
        term = np.einsum("nd,nd->n", x,
                         means[cid][val.entity_ids[re_type][rows]])
        score = score + np.where(unseen, 0.0, term)
    return score


def serve_leg(size: Size, val, model_dir: str, compiled: list) -> None:
    from photon_ml_tpu import obs
    from photon_ml_tpu.cli import serve

    rng = np.random.default_rng(SEED + 1)
    obs.enable(trace=False, metrics=True)  # what serve.run does for /metrics
    server, service = serve.create_server(serve.build_parser().parse_args([
        "--model-dir", model_dir, "--port", "0", "--max-batch", "64",
        "--boot-warmup"]))
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    url = f"http://127.0.0.1:{server.server_address[1]}"

    def get(path):
        with urllib.request.urlopen(url + path, timeout=60) as resp:
            return resp.read().decode()

    def metric(name):
        return obs.metric_value(obs.parse_prometheus_text(get("/metrics")),
                                name, default=0.0)

    def post(rows, unseen):
        body = {"requests": [{
            "features": {sid: np.asarray(shard[r]).tolist()
                         for sid, shard in val.feature_shards.items()},
            # An id past the table and a missing id: both unseen.
            "entity_ids": ({"userId": size.users + 7 + int(r)} if u else
                           {k: int(v[r])
                            for k, v in val.entity_ids.items()}),
            "offset": float(val.offsets[r]), "uid": int(r)}
            for r, u in zip(rows, unseen)]}
        req = urllib.request.Request(url + "/score",
                                     data=json.dumps(body).encode())
        with urllib.request.urlopen(req, timeout=120) as resp:
            reply = json.loads(resp.read())
        assert reply["uids"] == [int(r) for r in rows]
        return np.asarray(reply["scores"])

    try:
        warm_compiles = metric("photon_serving_compiles_total")
        warm_programs = len(compiled)
        batches = []
        for k in range(size.http_requests):
            n = (1, 2, 3, 5, 8, 13, 21, 34, 55, 64)[k % 10]
            rows = rng.integers(0, val.num_rows, n)
            # Every third request mixes in unseen entities; request 0 is
            # one unseen row alone.
            unseen = (rng.uniform(size=n) < 0.3 if k % 3 == 0
                      else np.zeros(n, bool))
            if k == 0:
                unseen[:] = True
            batches.append((rows, unseen))
        half = len(batches) // 2
        served = [post(*b) for b in batches[:half]]
        with concurrent.futures.ThreadPoolExecutor(8) as pool:
            served += list(pool.map(lambda b: post(*b), batches[half:]))
        means = saved_means(model_dir)
        worst = 0.0
        for (rows, unseen), got in zip(batches, served):
            want = numpy_scores(means, val, rows, unseen)
            assert got.shape == want.shape and np.all(np.isfinite(got))
            # f32 on the device against f64 on the host: rtol 1e-3, with
            # the same absolute floor for scores that cancel to near 0.
            np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)
            worst = max(worst, float(np.max(np.abs(got - want))))
        rows, _ = batches[0]  # the unseen entity: the fixed effect alone
        fixed_only = (val.feature_shards["global"][rows].astype(np.float64)
                      @ means["fixed"] + val.offsets[rows])
        np.testing.assert_allclose(served[0], fixed_only, rtol=1e-3,
                                   atol=1e-3)
        assert json.loads(get("/healthz"))["status"] == "ok"
        assert metric("photon_serving_flush_errors_total") == 0
        assert metric("photon_kernel_fallbacks_total") == 0
        assert metric("photon_serving_compiles_total") == warm_compiles, (
            "a scoring bucket compiled after warm-up")
        late = [p for p in compiled[warm_programs:] if "score" in p]
        assert not late, f"scoring programs compiled after warm-up: {late}"
        scored = sum(len(r) for r, _ in batches)
        print(f"  {len(batches)} HTTP requests, {scored} rows "
              f"({int(sum(u.sum() for _, u in batches))} unseen): max "
              f"|served - numpy| {worst:.3g}; flush errors 0, kernel "
              f"fallbacks 0, {int(warm_compiles)} bucket programs, none "
              f"after warm-up", flush=True)
    finally:
        server.shutdown()
        server.server_close()
        service.close()
        thread.join(timeout=30)
        obs.disable()


# ------------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny size on JAX_PLATFORMS=cpu with interpreted "
                         "kernels: checks the script, proves nothing "
                         "about the chip, never prints the pass line")
    ap.add_argument("--workdir",
                    help="keep the trained model, logs and summaries here "
                         "(default: a temporary directory, removed)")
    args = ap.parse_args(argv)

    # The package first: alone in a directory this fails here, before JAX
    # is asked for a device.
    from photon_ml_tpu.utils import events
    from photon_ml_tpu.utils.compile_cache import enable_compilation_cache

    import jax
    import jaxlib

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"jax {jax.__version__} jaxlib {jaxlib.__version__} python "
          f"{sys.version.split()[0]}: platform={device['platform']} "
          f"device_kind={device['kind']} devices={device['count']}",
          flush=True)
    on_tpu = device["platform"] == "tpu"
    if args.rehearsal:
        if device["platform"] != "cpu":
            sys.exit("chip_smoke: --rehearsal is for JAX_PLATFORMS=cpu; "
                     f"this is {device['platform']}")
        print("REHEARSAL (cpu): tiny size, interpreted kernels — not a "
              "chip result", flush=True)
    elif not on_tpu:
        sys.exit(f"chip_smoke: platform is {device['platform']!r}, not "
                 f"'tpu' — nothing was run (--rehearsal checks the "
                 f"script on the CPU)")
    size = FULL if on_tpu else TINY

    cache_dir = enable_compilation_cache()
    entries_before = set(os.listdir(cache_dir))
    compiled: list[str] = []  # every program handed to the backend
    cache_events = {"compile_requests_use_cache": 0, "cache_hits": 0}

    def on_duration(event, duration_secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(str(kw.get("fun_name")))

    def on_cache_event(event, **kw):
        key = event.rsplit("/", 1)[-1]
        if key in cache_events:
            cache_events[key] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_cache_event)
    fallbacks = []

    def on_fallback(event):
        if isinstance(event, events.KernelFallback):
            fallbacks.append(event)

    events.default_emitter.register(on_fallback)

    workdir = args.workdir or tempfile.mkdtemp(prefix="chip_smoke_")
    os.makedirs(workdir, exist_ok=True)
    t_start = time.monotonic()
    try:
        t0 = time.monotonic()
        kernel_leg(on_tpu)
        leg("kernels", t0)

        t0 = time.monotonic()
        val = write_data(size, workdir)
        leg(f"data ({size.rows:,} rows, {size.users:,} users x "
            f"{size.items:,} items, seed {SEED})", t0)

        t0 = time.monotonic()
        trained = train_leg(size, workdir, device["platform"])
        leg("train (cli.game_train)", t0)

        t0 = time.monotonic()
        serve_leg(size, val, trained["model_dir"], compiled)
        leg("serve (cli.serve)", t0)

        assert not fallbacks, f"kernels fell back: {fallbacks}"
        gained = len(set(os.listdir(cache_dir)) - entries_before)
        hits = cache_events["cache_hits"]
        asked = cache_events["compile_requests_use_cache"]
        print(f"LEG compile cache: {cache_dir} gained {gained} entries; "
              f"{asked} programs asked of it, {hits} loaded, "
              f"{asked - hits} compiled", flush=True)
        facts = {"device": device, "jax": jax.__version__,
                 "rows": size.rows, "users": size.users,
                 "items": size.items, "auc": trained["auc"],
                 "model_digest": trained["model_digest"],
                 "compiled": asked - hits, "cache_hits": hits,
                 "wall_seconds": round(time.monotonic() - t_start, 1)}
        print("FACTS " + json.dumps(facts), flush=True)
        if args.workdir:
            with open(os.path.join(workdir, "facts.json"), "w") as f:
                json.dump(facts, f, indent=1)
    finally:
        events.default_emitter.unregister(on_fallback)
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_cache_event)
        if args.workdir:
            # The data is regenerable from the seed; the model stays.
            for split in ("train", "val"):
                shutil.rmtree(os.path.join(workdir, split),
                              ignore_errors=True)
        else:
            shutil.rmtree(workdir, ignore_errors=True)

    if on_tpu:
        print(json.dumps({"ok": True, "device": device}), flush=True)
    else:
        print("REHEARSAL (cpu): every leg ran; run without --rehearsal on "
              "the chip for a result", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
