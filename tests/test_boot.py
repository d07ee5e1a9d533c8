"""photon-boot: mmap model artifacts, atomic generation swap, and
device-elastic resume (ISSUE 14; docs/SERVING.md "Sub-second restart",
docs/STREAMING.md "Elastic resume").

The contracts under test:

* the mapped format is BYTE-identical to the npz layout (digest
  equality, not a tolerance), across every coordinate-model type;
* a mapped boot is zero-copy (the host store keeps the mmap tables
  whole) and serves the same bits as an npz boot — single service and
  through a real subprocess fleet;
* publication is atomic (a SIGKILL in the torn window leaves the
  previous generation current and servable byte-identically), rollback
  is a re-point, and post-CRC bit rot falls back one generation with a
  loud ``BootRecovered`` event;
* compaction of a committed DeltaStore chain equals replaying it,
  bit for bit, and refuses gapped chains;
* a streamed L-BFGS checkpoint written at D devices resumes at D′ ≠ D
  (``game_train --resume`` across forced device counts) within the
  established sharded-parity tolerance, while genuinely incompatible
  snapshots are still rejected.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from photon_ml_tpu import faults
from photon_ml_tpu.utils import events as ev

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))


@pytest.fixture(autouse=True)
def _no_leftover_plan():
    yield
    faults.install(None)


def _full_model(rng, E=40, d=8, A=3, rank=2):
    """One GameModel exercising every persisted coordinate type."""
    import jax.numpy as jnp

    from photon_ml_tpu.game.factored import FactoredRandomEffectModel
    from photon_ml_tpu.game.models import (FixedEffectModel, GameModel,
                                           RandomEffectModel,
                                           SubspaceRandomEffectModel)
    from photon_ml_tpu.models.coefficients import Coefficients
    from photon_ml_tpu.types import TaskType

    cols = np.sort(rng.integers(0, d, size=(E, A)).astype(np.int32),
                   axis=1)
    return GameModel(task=TaskType.LOGISTIC_REGRESSION, models={
        "fixed": FixedEffectModel("global", Coefficients(
            jnp.asarray(rng.normal(size=d).astype(np.float32)),
            jnp.asarray(rng.random(d).astype(np.float32)))),
        "per-user": RandomEffectModel(
            "userId", "re", jnp.asarray(
                rng.normal(size=(E, d)).astype(np.float32))),
        "per-song": SubspaceRandomEffectModel(
            "songId", "re", d, jnp.asarray(cols),
            jnp.asarray(rng.normal(size=(E, A)).astype(np.float32))),
        "per-artist": FactoredRandomEffectModel(
            "artistId", "re",
            projection=jnp.asarray(
                rng.normal(size=(rank, d)).astype(np.float32)),
            factors=jnp.asarray(
                rng.normal(size=(E, rank)).astype(np.float32))),
    })


def _serving_model(rng, E=64, dg=6, dr=4):
    import jax.numpy as jnp

    from photon_ml_tpu.game.models import (FixedEffectModel, GameModel,
                                           RandomEffectModel)
    from photon_ml_tpu.models.coefficients import Coefficients
    from photon_ml_tpu.types import TaskType

    return GameModel(task=TaskType.LOGISTIC_REGRESSION, models={
        "fixed": FixedEffectModel("global", Coefficients(
            jnp.asarray(rng.normal(size=dg).astype(np.float32)))),
        "per-user": RandomEffectModel(
            "userId", "re_userId", jnp.asarray(
                rng.normal(size=(E, dr)).astype(np.float32))),
    })


def _requests(rng, n, E=64, dg=6, dr=4):
    from photon_ml_tpu.serving import ScoringRequest

    return [ScoringRequest(
        features={"global": rng.normal(size=dg).astype(np.float32),
                  "re_userId": rng.normal(size=dr).astype(np.float32)},
        entity_ids={"userId": int(i % E)}) for i in range(n)]


# ------------------------------------------------------------ map format


def test_map_roundtrip_bit_parity_all_types(tmp_path):
    """Mapped write→load digests BYTE-identical to the in-memory model
    and the npz layout, for all four coordinate-model types; loaded
    tables are read-only mmap views."""
    from photon_ml_tpu import boot
    from photon_ml_tpu.models import io as model_io

    model = _full_model(np.random.default_rng(0))
    npz_dir = str(tmp_path / "npz")
    map_dir = str(tmp_path / "mapped")
    model_io.save_game_model(model, npz_dir)
    boot.write_mapped_model(model, map_dir)

    d_mem = model_io.game_model_digest(model)
    assert model_io.game_model_digest(
        model_io.load_game_model(npz_dir, host=True,
                                 mapped=False)) == d_mem
    loaded, marker = boot.load_mapped_model(map_dir)
    assert model_io.game_model_digest(loaded) == d_mem
    for cid in ("per-user", "per-song", "per-artist"):
        m = loaded.models[cid]
        arr = getattr(m, "means", None)
        if arr is None:
            arr = m.factors
        assert boot.is_mapped_array(arr)
        assert not np.asarray(arr).flags.writeable


def test_load_game_model_mapped_routing(tmp_path):
    """`mapped=True` prefers the map layout, FALLS BACK to npz when the
    directory has none; `mapped=None` auto-detects; `mapped=False`
    forces npz."""
    from photon_ml_tpu import boot
    from photon_ml_tpu.models import io as model_io

    model = _full_model(np.random.default_rng(1))
    d_mem = model_io.game_model_digest(model)
    npz_dir = str(tmp_path / "npz")
    map_dir = str(tmp_path / "mapped")
    model_io.save_game_model(model, npz_dir)
    boot.write_mapped_model(model, map_dir)

    # npz-only dir + mapped=True → npz fallback, same bytes.
    assert model_io.game_model_digest(model_io.load_game_model(
        npz_dir, host=True, mapped=True)) == d_mem
    # map dir auto-detected without any flag.
    auto = model_io.load_game_model(map_dir)
    assert model_io.game_model_digest(auto) == d_mem
    assert boot.is_mapped_array(auto.models["per-user"].means)


def test_mapped_store_zero_copy_and_scores_bit_identical():
    """A mapped boot takes the direct (no partition copy) host-store
    path and serves the same bits as the npz boot."""
    import tempfile

    from photon_ml_tpu import boot
    from photon_ml_tpu.serving import ScoringService

    rng = np.random.default_rng(2)
    model = _serving_model(rng)
    td = tempfile.mkdtemp(prefix="pml_boot_")
    map_dir = os.path.join(td, "mapped")
    boot.write_mapped_model(model, map_dir)
    mapped, _ = boot.load_mapped_model(map_dir)

    reqs = _requests(rng, 24)
    s_npz = ScoringService(model)
    expected = s_npz.score(reqs)
    s_npz.close()

    s_map = ScoringService(mapped)
    try:
        st = s_map.store.random[0].store
        assert st.mapped, "mapped model should take the direct path"
        got = s_map.score(reqs)
    finally:
        s_map.close()
    np.testing.assert_array_equal(got, expected)


def test_mapped_swap_rows_overlay_and_delta_rollback(tmp_path):
    """Row hot-swap on a mapped store lands in the overlay (the on-disk
    artifact stays pristine) and apply_delta/rollback_to stay exact."""
    from photon_ml_tpu import boot
    from photon_ml_tpu.models import io as model_io
    from photon_ml_tpu.serving.model_store import ResidentModelStore
    from photon_ml_tpu.serving.publish import DeltaStore

    rng = np.random.default_rng(3)
    model = _serving_model(rng)
    map_dir = str(tmp_path / "mapped")
    boot.write_mapped_model(model, map_dir)
    mapped, _ = boot.load_mapped_model(map_dir)
    store = ResidentModelStore(mapped)
    base_rows = store.random[0].store.fetch(np.arange(8, dtype=np.int64))

    ds = DeltaStore(str(tmp_path / "pub"))
    delta = ds.write({"per-user": (
        np.array([1, 5], np.int64),
        rng.normal(size=(2, 4)).astype(np.float32))})
    store.apply_delta(delta)
    got = store.random[0].store.fetch(np.arange(8, dtype=np.int64))
    exp = base_rows.copy()
    exp[1], exp[5] = delta.rows["per-user"][1]
    np.testing.assert_array_equal(got, exp)
    # The committed artifact on disk never mutated (swap = overlay).
    refetched, _ = boot.load_mapped_model(map_dir)
    assert model_io.game_model_digest(refetched) == \
        model_io.game_model_digest(model)
    # Rollback restores the pre-delta bytes exactly.
    store.rollback_to(0)
    np.testing.assert_array_equal(
        store.random[0].store.fetch(np.arange(8, dtype=np.int64)),
        base_rows)


# ----------------------------------------------------------- generations


def test_generation_publish_retention_and_rollback(tmp_path):
    from photon_ml_tpu import boot
    from photon_ml_tpu.models import io as model_io

    model = _serving_model(np.random.default_rng(4))
    gs = boot.GenerationStore(str(tmp_path / "gens"))
    assert gs.versions() == []
    v1, _ = gs.publish(model)
    v2, _ = gs.publish(model)
    v3, _ = gs.publish(model)
    assert (v1, v2, v3) == (1, 2, 3)
    # Two-generation retention: gen-1 pruned, current = newest.
    assert gs.versions() == [2, 3]
    assert gs.current_version() == 3
    # Rollback is a re-point; the rolled-to generation loads clean.
    assert gs.rollback() == 2
    m, marker, gen = gs.load_current()
    assert gen == 2
    assert model_io.game_model_digest(m) == \
        model_io.game_model_digest(model)
    # The pointed-at generation survives the next publish's pruning.
    gs.publish(model)
    assert 4 in gs.versions()


def test_torn_publish_invisible_under_sigkill(tmp_path):
    """SIGKILL in the torn window (blobs committed, directory marker
    not — `boot.map_write` occurrence 1): the half-written generation
    is invisible, gen-1 stays current and serves byte-identically, and
    a clean re-publish commits the same number."""
    from photon_ml_tpu import boot
    from photon_ml_tpu.models import io as model_io

    rng = np.random.default_rng(5)
    model = _serving_model(rng)
    gs = boot.GenerationStore(str(tmp_path / "gens"))
    gs.publish(model)
    d1 = model_io.game_model_digest(gs.load_current()[0])

    model2 = _serving_model(np.random.default_rng(6))
    npz2 = str(tmp_path / "model2")
    model_io.save_game_model(model2, npz2)
    plan = faults.FaultPlan(specs=(faults.FaultSpec(
        site="boot.map_write", kind="kill", occurrences=(1,)),))
    plan_path = str(tmp_path / "plan.json")
    with open(plan_path, "w") as f:
        f.write(plan.to_json())
    driver = (
        "import sys, json\n"
        "from photon_ml_tpu import faults, boot\n"
        "from photon_ml_tpu.models import io as model_io\n"
        f"with open({plan_path!r}) as f:\n"
        "    faults.install(faults.FaultPlan.from_json(f.read()))\n"
        f"m = model_io.load_game_model({npz2!r}, host=True)\n"
        f"boot.GenerationStore({str(tmp_path / 'gens')!r}).publish(m)\n")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + (os.pathsep + env["PYTHONPATH"]
                                if env.get("PYTHONPATH") else "")
    proc = subprocess.run([sys.executable, "-c", driver], env=env,
                          cwd=REPO, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == -9, \
        f"publisher survived the kill plan (rc={proc.returncode}):\n" \
        f"{proc.stderr[-2000:]}"

    gs2 = boot.GenerationStore(str(tmp_path / "gens"))
    # The torn gen-2 has blobs but no marker: not a committed version.
    assert gs2.versions() == [1]
    assert os.path.isdir(str(tmp_path / "gens" / "gen-000002"))
    m, _, gen = gs2.load_current()
    assert gen == 1
    assert model_io.game_model_digest(m) == d1
    # A clean re-publish commits the number the torn attempt burned.
    v, _ = gs2.publish(model2)
    assert v == 2
    assert model_io.game_model_digest(gs2.load_current()[0]) == \
        model_io.game_model_digest(model2)


def test_blob_rot_falls_back_one_generation_with_event(tmp_path):
    """Post-CRC bit rot in the CURRENT generation's blob: load_current
    detects the CRC mismatch, boots the PREVIOUS generation, and says
    so loudly (BootRecovered). Both generations rotten → the defined
    GenerationError."""
    from photon_ml_tpu import boot
    from photon_ml_tpu.models import io as model_io

    g1_model = _serving_model(np.random.default_rng(7))
    g2_model = _serving_model(np.random.default_rng(8))
    gs = boot.GenerationStore(str(tmp_path / "gens"))
    gs.publish(g1_model)
    # gen-2's per-user blob rots AFTER its CRC was recorded (the
    # corrupt hook sits post-checksum by construction).
    plan = faults.FaultPlan(specs=(faults.FaultSpec(
        site="boot.map_open", kind="corrupt", occurrences=(1,)),))
    with faults.installed(plan) as inj:
        gs.publish(g2_model)
    assert inj.fires("boot.map_open") == 1

    seen = []
    ev.default_emitter.register(seen.append)
    try:
        m, _, gen = gs.load_current()
    finally:
        ev.default_emitter.unregister(seen.append)
    assert gen == 1
    assert model_io.game_model_digest(m) == \
        model_io.game_model_digest(g1_model)
    recovered = [e for e in seen if isinstance(e, ev.BootRecovered)]
    assert recovered and recovered[0].from_version == 2 \
        and recovered[0].to_version == 1

    # Rot gen-1 too: the ladder ends in a refusal, never a guess.
    blob = str(tmp_path / "gens" / "gen-000001" / "blobs"
               / "per-user.bin")
    with open(blob, "r+b") as f:
        f.seek(8)
        f.write(b"\xff\xff\xff\xff")
    with pytest.raises(boot.GenerationError):
        gs.load_current()


def test_compaction_equals_delta_replay_bit_identical(tmp_path):
    """Folding a committed delta chain into the next generation equals
    replaying the chain onto a booted store, byte for byte — and the
    compacted generation records the folded model_version so a booted
    replica skips the chain."""
    from photon_ml_tpu import boot
    from photon_ml_tpu.serving.model_store import ResidentModelStore
    from photon_ml_tpu.serving.publish import DeltaStore

    rng = np.random.default_rng(9)
    model = _serving_model(rng)
    gs = boot.GenerationStore(str(tmp_path / "gens"))
    gs.publish(model)
    ds = DeltaStore(str(tmp_path / "pub"))
    deltas = [ds.write({"per-user": (
        np.sort(rng.choice(64, size=5, replace=False)).astype(np.int64),
        rng.normal(size=(5, 4)).astype(np.float32))}) for _ in range(3)]

    gen, _ = gs.compact(ds)
    assert gen == 2
    compacted, marker, _ = gs.load_current()
    assert marker["model_version"] == 3
    assert marker["deltas_folded"] == [1, 2, 3]

    replayed = ResidentModelStore(model)
    for d in deltas:
        replayed.apply_delta(d)
    all_ids = np.arange(64, dtype=np.int64)
    np.testing.assert_array_equal(
        ResidentModelStore(compacted).random[0].store.fetch(all_ids),
        replayed.random[0].store.fetch(all_ids))
    # Idempotent: nothing newer to fold.
    assert gs.compact(ds) is None
    # A booted service starts at the folded version: only NEWER deltas
    # apply (the chain-order check holds at the folded base).
    d4 = ds.write({"per-user": (np.array([0], np.int64),
                                rng.normal(size=(1, 4)).astype(
                                    np.float32))})
    store = ResidentModelStore(compacted, initial_version=3)
    assert store.version == 3
    store.apply_delta(d4)
    assert store.version == 4


def test_compaction_refuses_gapped_chain(tmp_path):
    """A retracted/missing delta mid-chain must refuse to fold — an
    artifact with a silent hole would serve wrong rows forever."""
    from photon_ml_tpu import boot
    from photon_ml_tpu.serving.publish import DeltaStore

    rng = np.random.default_rng(10)
    gs = boot.GenerationStore(str(tmp_path / "gens"))
    gs.publish(_serving_model(rng))
    ds = DeltaStore(str(tmp_path / "pub"))
    for _ in range(3):
        ds.write({"per-user": (np.array([1], np.int64),
                               rng.normal(size=(1, 4)).astype(
                                   np.float32))})
    ds.retract(2)
    with pytest.raises(boot.GenerationError, match="gaps"):
        gs.compact(ds)


# ------------------------------------------------- fleet + observability


def test_mmap_booted_fleet_serves_bit_identical(tmp_path):
    """A 2-replica fleet whose replicas mmap-boot the generation root
    answers bit-identically to the single-process npz oracle — the
    PR 1 parity discipline through the boot layer."""
    from photon_ml_tpu import boot
    from photon_ml_tpu.serving import ScoringService
    from photon_ml_tpu.serving.fleet import ServingFleet

    rng = np.random.default_rng(11)
    model = _serving_model(rng)
    gen_root = str(tmp_path / "gens")
    boot.GenerationStore(gen_root).publish(model)

    reqs = _requests(rng, 10)
    objs = [{"features": {k: np.asarray(v).tolist()
                          for k, v in r.features.items()},
             "entity_ids": r.entity_ids, "uid": i}
            for i, r in enumerate(reqs)]
    oracle = ScoringService(model, max_wait_ms=0.5)
    expected = np.asarray([float(oracle.submit(r).result(timeout=60))
                           for r in reqs], np.float32)
    oracle.close()

    fleet = ServingFleet(
        replica_args=["--model-dir", gen_root, "--max-wait-ms", "0.5"],
        num_replicas=2, workdir=str(tmp_path / "fleet"),
        probe_interval_s=0.1, heartbeat_deadline_s=2.0)
    try:
        fleet.start()
        # Replicas booted the generation (visible on their /healthz).
        hz = fleet._replica_get_json(0, "/healthz")
        assert hz["generation"] == 1, hz
        got = np.asarray(
            [float(fleet.score([o])["scores"][0]) for o in objs],
            np.float32)
    finally:
        fleet.close()
    np.testing.assert_array_equal(got, expected)


def test_boot_span_and_gauges(tmp_path):
    """cli/serve.create_server attributes the restart tail: a
    serving.boot span with map/compile/warmup children, the
    photon_boot_seconds{phase} gauges, and the model-generation
    gauge."""
    from photon_ml_tpu import boot, obs
    from photon_ml_tpu.cli import serve as serve_cli

    model = _serving_model(np.random.default_rng(12))
    gen_root = str(tmp_path / "gens")
    boot.GenerationStore(gen_root).publish(model)

    tracer = obs.Tracer()
    registry = obs.MetricsRegistry()
    with obs.activated(tracer, registry):
        args = serve_cli.build_parser().parse_args(
            ["--model-dir", gen_root, "--port", "0", "--boot-warmup",
             "--max-batch", "4"])
        server, service = serve_cli.create_server(args)
        server.server_close()
        service.close()
    snap = registry.snapshot()
    for phase in ("map", "compile", "warmup", "total"):
        key = f'photon_boot_seconds{{phase="{phase}"}}'
        assert key in snap and snap[key] >= 0.0, sorted(snap)
    assert snap["photon_model_generation"] == 1.0
    # Warmup re-ran owned shapes at least once → hits, not silence.
    hits = [v for k, v in snap.items()
            if k.startswith("photon_compile_cache_hits_total")]
    assert hits and sum(hits) >= 1
    trace = tracer.chrome_trace()
    names = [e["name"] for e in trace["traceEvents"]
             if e.get("ph") == "X"]
    assert "serving.boot" in names
    for child in ("boot.map", "boot.compile", "boot.warmup"):
        assert child in names, names


def test_summarize_serving_renders_boot_waterfall():
    """photon-obs summarize --serving: the boot span + children render
    as a waterfall (stdlib path, hand-built trace)."""
    from photon_ml_tpu.cli.obs import (render_serving_summary,
                                       summarize_serving)

    def span(name, sid, ts, dur, parent=None):
        args = {"span_id": sid}
        if parent is not None:
            args["parent_id"] = parent
        return {"ph": "X", "name": name, "ts": ts, "dur": dur,
                "cat": "serving", "args": args}

    trace = {"traceEvents": [
        span("serving.boot", 1, 0.0, 900e3),
        span("boot.map", 2, 10.0, 100e3, parent=1),
        span("boot.compile", 3, 110e3, 500e3, parent=1),
        span("boot.warmup", 4, 620e3, 250e3, parent=1),
    ]}
    summary = summarize_serving(trace)
    assert summary["boot"]["total_ms"] == pytest.approx(900.0)
    assert [p["phase"] for p in summary["boot"]["phases"]] == \
        ["boot.map", "boot.compile", "boot.warmup"]
    text = render_serving_summary(summary)
    assert "boot waterfall" in text and "boot.compile" in text


# ------------------------------------------------- device-elastic resume


def test_stream_snapshot_rejects_incompatible_fingerprint(tmp_path):
    """Elasticity never weakens the fingerprint: a snapshot from a
    different objective/config is still discarded, and a shape-
    incompatible history ring still raises."""
    from photon_ml_tpu.game.checkpoint import StreamingStateStore
    from photon_ml_tpu.optim.common import OptimizerConfig
    from photon_ml_tpu.optim.streaming import minimize_streaming

    snap = {"w": np.zeros(4, np.float32), "g": np.zeros(4, np.float32),
            "s_stack": np.zeros((2, 4), np.float32),
            "y_stack": np.zeros((2, 4), np.float32),
            "rho": np.zeros(2, np.float32), "m": np.int32(0),
            "it": np.int32(2), "fv": np.float32(1.0),
            "gn_prev": np.float32(1.0), "f0": np.float32(2.0),
            "gn0": np.float32(1.0), "vals": np.zeros(4, np.float32),
            "gns": np.zeros(4, np.float32)}
    store = StreamingStateStore(str(tmp_path / "ss"))
    store.save(snap, fingerprint={"dim": 4, "step": 1},
               environment={"num_devices": 1})
    # Device count is NOT identity: a different environment loads fine.
    assert store.load(expected_fingerprint={"dim": 4, "step": 1},
                      environment={"num_devices": 2}) is not None
    # A different fingerprint IS: discarded.
    assert store.load(
        expected_fingerprint={"dim": 8, "step": 1}) is None
    # A history ring from another optimizer config: defined rejection.
    with pytest.raises(ValueError, match="resume state shape mismatch"):
        minimize_streaming(
            lambda w: (np.float32(0.0), w), np.zeros(8, np.float32),
            OptimizerConfig(history_length=2, max_iterations=3),
            resume_state=snap)


def _elastic_env(devices: int) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = \
        f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = REPO + (os.pathsep + env["PYTHONPATH"]
                                if env.get("PYTHONPATH") else "")
    return env


def _elastic_train_argv(train_dir, out):
    return [sys.executable, "-m", "photon_ml_tpu.cli.game_train",
            "--train", train_dir,
            "--coordinate", "name=fixed,type=fixed,shard=global",
            "--update-sequence", "fixed",
            "--opt-config",
            "fixed:optimizer=LBFGS,reg=L2,reg_weight=1.0",
            "--streaming", "chunk_rows=128,num_hot=8,workers=2",
            "--output-dir", out]


def _run_train(argv, env, log_path, expect_kill=False):
    with open(log_path, "w") as log:
        proc = subprocess.run(argv, env=env, cwd=REPO, stdout=log,
                              stderr=subprocess.STDOUT, timeout=600)
    if expect_kill:
        assert proc.returncode == -9, (
            f"driver survived its kill plan (rc={proc.returncode}):\n"
            + open(log_path).read()[-3000:])
    else:
        assert proc.returncode == 0, (
            f"game_train failed (rc={proc.returncode}):\n"
            + open(log_path).read()[-3000:])


def test_elastic_resume_d1_d2_d1_within_parity_tolerance(tmp_path):
    """THE elastic drill (ISSUE 14 acceptance): a streamed L-BFGS fit
    checkpointed at D=1 is SIGKILLed, resumes at D=2 (chunk ranges
    re-shard), is killed again, finishes back at D=1 — and the final
    coefficients agree with a never-killed D=1 run within the
    established sharded-parity tolerance (the D-vs-1 accumulation-order
    band the stream-dist suite pins)."""
    from photon_ml_tpu.data import sparse as sp
    from photon_ml_tpu.data.game_data import from_sparse_batch
    from photon_ml_tpu.data.io import save_game_dataset

    batch, _ = sp.synthetic_sparse(600, 48, 5, seed=21)
    ds = from_sparse_batch(batch)
    train_dir = str(tmp_path / "train")
    save_game_dataset(ds, train_dir)

    def plan_file(occurrence: int) -> str:
        plan = faults.FaultPlan(specs=(faults.FaultSpec(
            site="stream.checkpoint_write", kind="kill",
            occurrences=(occurrence,)),))
        path = str(tmp_path / f"plan-{occurrence}.json")
        with open(path, "w") as f:
            f.write(plan.to_json())
        return path

    out = str(tmp_path / "out-elastic")
    # Phase 1: D=1, killed at the 4th mid-step snapshot.
    _run_train(_elastic_train_argv(train_dir, out)
               + ["--fault-plan", plan_file(3)],
               _elastic_env(1), str(tmp_path / "p1.log"),
               expect_kill=True)
    ckpt = os.path.join(out, "checkpoints", "grid-0")
    assert any(d.startswith("stream-step")
               for d in os.listdir(ckpt)), \
        "no mid-step stream state survived the kill"
    # Phase 2: ELASTIC resume at D=2, killed again mid-optimization.
    _run_train(_elastic_train_argv(train_dir, out)
               + ["--resume", "--fault-plan", plan_file(1)],
               _elastic_env(2), str(tmp_path / "p2.log"),
               expect_kill=True)
    # Phase 3: back to D=1, runs to completion.
    _run_train(_elastic_train_argv(train_dir, out) + ["--resume"],
               _elastic_env(1), str(tmp_path / "p3.log"))

    # Oracle: one clean never-killed D=1 run.
    out_clean = str(tmp_path / "out-clean")
    _run_train(_elastic_train_argv(train_dir, out_clean),
               _elastic_env(1), str(tmp_path / "clean.log"))

    a = np.load(os.path.join(out, "best", "fixed-effect", "fixed",
                             "coefficients.npz"))["means"]
    b = np.load(os.path.join(out_clean, "best", "fixed-effect",
                             "fixed", "coefficients.npz"))["means"]
    # The established sharded-parity band (tests/test_stream_dist.py's
    # full-descent D-vs-1 tolerance).
    np.testing.assert_allclose(a, b, atol=5e-3, rtol=0)
    # The elastic resume actually happened (loud by contract; the
    # warning is only emitted AFTER a snapshot passed the fingerprint
    # and was accepted under a different device environment).
    p2_log = open(str(tmp_path / "p2.log")).read()
    assert "ELASTIC resume" in p2_log, p2_log[-2000:]
