"""photon-sweep tests: dirty-gated incremental coordinate descent
(game/sweep.py + RandomEffectCoordinate.train_model_gated, docs/SWEEPS.md).

The parity ladder under test:

1. ``gate=0`` (theta=0, grad_tol=0 — the bare ``--sweep`` default) is
   BIT-IDENTICAL to an ungated run: coefficients and the checkpointed
   residual total, across all four random-effect model types (dense,
   projected, subspace, factored-in-sequence).
2. Gated runs land inside the repo's 5e-3 coefficient band with the
   mandatory final full sweep as the backstop — and actually skip
   entities in between (the perf claim has a visible shape: ledger
   ``re_fit_wave`` rows and the refit/skipped counters).
3. A killed gated run resumes BIT-IDENTICAL to an unkilled gated run —
   in-process (KeyboardInterrupt mid-descent) and end-to-end (SIGKILL
   via ``--fault-plan`` at the ``sweep.gate_state`` seam, rerun with
   ``--resume``).
"""

import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu import faults, obs
from photon_ml_tpu.api.configs import (CoordinateConfiguration,
                                       FixedEffectDataConfiguration,
                                       RandomEffectDataConfiguration,
                                       parse_sweep_config)
from photon_ml_tpu.api.estimator import GameEstimator
from photon_ml_tpu.data import synthetic
from photon_ml_tpu.data.game_data import from_synthetic
from photon_ml_tpu.game import descent
from photon_ml_tpu.game import sweep as swp
from photon_ml_tpu.game.checkpoint import CheckpointManager
from photon_ml_tpu.game.coordinates import (FixedEffectCoordinate,
                                            RandomEffectCoordinate)
from photon_ml_tpu.game.factored import FactoredRandomEffectCoordinate
from photon_ml_tpu.obs.ledger import RunLedger, fit_wave_summary, read_rows
from photon_ml_tpu.ops import losses
from photon_ml_tpu.optim import OptimizerConfig
from photon_ml_tpu.optim.problem import GLMOptimizationConfiguration
from photon_ml_tpu.optim.regularization import (RegularizationContext,
                                                RegularizationType)
from photon_ml_tpu.parallel.mesh import make_mesh
from photon_ml_tpu.types import TaskType

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))


@pytest.fixture(scope="module")
def mesh():
    return make_mesh()


@pytest.fixture(autouse=True)
def _clean_obs_state():
    yield
    obs.set_ledger(None)
    obs.disable()
    faults.install(None)


def _opt(l2=1.0, max_iter=40):
    return GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(max_iterations=max_iter, tolerance=1e-7),
        regularization=RegularizationContext(RegularizationType.L2, l2))


def _game(rng, n=600, users=30, d_re=3):
    syn = synthetic.game_data(rng, n=n, d_global=4,
                              re_specs={"userId": (users, d_re)})
    return from_synthetic(syn)


# ------------------------------------------------------------------- units


def test_sweep_config_validation_and_gate_zero():
    assert swp.SweepConfig().gate_zero
    assert swp.SweepConfig(grad_tol=1e-4).gate_zero is False
    assert swp.SweepConfig(theta=1e-3).gate_zero is False
    with pytest.raises(ValueError, match="theta"):
        swp.SweepConfig(theta=-1.0)
    with pytest.raises(ValueError, match="grad_tol"):
        swp.SweepConfig(grad_tol=-1e-9)
    with pytest.raises(ValueError, match="min_sweeps_full"):
        swp.SweepConfig(min_sweeps_full=0)


def test_next_pow2_and_compact_lanes():
    assert [swp.next_pow2(k) for k in (1, 2, 3, 4, 5, 63, 64, 65)] == \
        [1, 2, 4, 4, 8, 64, 64, 128]
    # Floored at the entity pad multiple, capped at the tuple's lanes.
    assert swp.compact_lanes(3, 8, 256) == 8
    assert swp.compact_lanes(9, 8, 256) == 16
    assert swp.compact_lanes(200, 8, 256) == 256
    assert swp.compact_lanes(0, 8, 256) == 8


def test_parse_sweep_config():
    assert parse_sweep_config("") == swp.SweepConfig()
    got = parse_sweep_config(
        "theta=1e-3,grad_tol=1e-4,min_sweeps_full=2,final_full=false,"
        "gram=true")
    assert got == swp.SweepConfig(theta=1e-3, grad_tol=1e-4,
                                  min_sweeps_full=2,
                                  final_full_sweep=False, gram=True)
    with pytest.raises(ValueError, match="unknown"):
        parse_sweep_config("thet=1")
    with pytest.raises(ValueError):
        parse_sweep_config("final_full=maybe")


def test_gate_and_advance_semantics():
    """Drift accumulates across skipped sweeps; grad evidence defaults to
    always-dirty; untrained entities never gate in."""
    ids = np.array([0, 0, 1, 1, 2, 2], np.int32)
    st = swp.CoordinateSweepState(3, ids, scale=np.full(3, 2.0),
                                  trained=np.array([True, True, False]))
    cfg = swp.SweepConfig(theta=0.1, grad_tol=1e-3)
    o0 = jnp.zeros(6, jnp.float32)
    st.advance(o0)  # full sweep: off_ref = o0
    # No solver evidence yet (+inf grad norms) -> every TRAINED entity
    # is dirty regardless of drift.
    dirty, drift = st.gate(o0, cfg)
    np.testing.assert_array_equal(np.asarray(dirty), [True, True, False])
    np.testing.assert_array_equal(np.asarray(drift), 0.0)
    st.grad_norms = jnp.zeros(3, jnp.float32)  # converged evidence
    # Entity 1's rows drift past theta*scale = 0.2; entity 0 stays clean.
    o1 = jnp.asarray(np.array([0.01, 0.0, 0.5, 0.25, 9.0, 9.0],
                              np.float32))
    dirty, drift = st.gate(o1, cfg)
    np.testing.assert_array_equal(np.asarray(dirty), [False, True, False])
    np.testing.assert_allclose(np.asarray(drift), [0.01, 0.75, 18.0])
    # Advance moves ONLY dirty entities' references: entity 0 keeps
    # accumulating the 0.01 it already drifted.
    st.advance(o1, dirty)
    o2 = jnp.asarray(np.array([0.15, 0.1, 0.5, 0.25, 9.0, 9.0],
                              np.float32))
    dirty2, drift2 = st.gate(o2, cfg)
    np.testing.assert_allclose(np.asarray(drift2), [0.25, 0.0, 18.0])
    np.testing.assert_array_equal(np.asarray(dirty2),
                                  [True, False, False])
    # Checkpoint round-trip restores the evidence exactly.
    fresh = swp.CoordinateSweepState(3, ids, scale=np.full(3, 2.0),
                                     trained=np.array([True, True, False]))
    fresh.restore(st.to_arrays())
    np.testing.assert_array_equal(np.asarray(fresh.grad_norms),
                                  np.asarray(st.grad_norms))
    np.testing.assert_array_equal(np.asarray(fresh.off_ref),
                                  np.asarray(st.off_ref))


def test_fit_wave_summary_aggregates_per_iteration():
    rows = [
        {"kind": "re_fit_wave", "coordinate": "per-user",
         "outer_iteration": 0, "wave": 0, "seconds": 0.5,
         "entities_fit": 8, "entities_skipped": 0, "drift_p99": 0.0},
        {"kind": "re_fit_wave", "coordinate": "per-user",
         "outer_iteration": 0, "wave": 1, "seconds": 0.25,
         "entities_fit": 4, "entities_skipped": 0, "drift_p99": 0.0},
        {"kind": "re_fit_wave", "coordinate": "per-user",
         "outer_iteration": 1, "wave": 0, "seconds": 0.1,
         "entities_fit": 2, "entities_skipped": 10, "drift_p99": 3e-4},
        {"kind": "opt_iter", "coordinate": "per-user"},
    ]
    got = fit_wave_summary(rows)
    assert list(got) == ["per-user"]
    it0, it1 = got["per-user"]
    assert it0["entities_fit"] == 12 and it0["waves"] == 2
    assert it1["entities_skipped"] == 10 and it1["drift_p99"] == 3e-4


# --------------------------------------- rung 1: gate=0 bit-identity


def _variant_coordinates(variant, ds, mesh):
    """fixed + one per-user coordinate of the requested model type."""
    if variant in ("projected", "subspace"):
        opt = _opt()
        cc = {
            "fixed": CoordinateConfiguration(
                data=FixedEffectDataConfiguration("global"),
                optimization=opt),
            "per-user": CoordinateConfiguration(
                data=RandomEffectDataConfiguration(
                    "userId", "re_userId", projector="INDEX_MAP",
                    subspace_model=(variant == "subspace")),
                optimization=opt),
        }
        est = GameEstimator(TaskType.LOGISTIC_REGRESSION, cc,
                            ["fixed", "per-user"], mesh)
        return est._build_coordinates(
            ds, {cid: c.optimization for cid, c in cc.items()})
    coords = {"fixed": FixedEffectCoordinate(ds, "global", losses.LOGISTIC,
                                             _opt(), mesh)}
    if variant == "dense":
        coords["per-user"] = RandomEffectCoordinate(
            ds, "userId", "re_userId", losses.LOGISTIC, _opt(), mesh)
    else:  # factored: no make_sweep_state -> always takes the full path
        coords["per-user"] = FactoredRandomEffectCoordinate(
            ds, "userId", "re_userId", losses.LOGISTIC, _opt(), mesh,
            rank=2, alternations=1)
    return coords


def _ckpt_arrays(directory):
    """Every committed coefficients.npz + residuals.npz, flattened."""
    out = {}
    for root, _, files in os.walk(os.path.join(directory, "model")):
        for f in files:
            if f == "coefficients.npz":
                with np.load(os.path.join(root, f)) as z:
                    for k in z.files:
                        out[f"{os.path.basename(root)}/{k}"] = z[k]
    with np.load(os.path.join(directory, "residuals.npz")) as z:
        out["residual_total"] = z["total"]
    return out


@pytest.mark.parametrize("variant",
                         ["dense", "projected", "subspace", "factored"])
def test_gate_zero_is_bit_identical(rng, mesh, tmp_path, variant):
    """Rung 1: theta=0, grad_tol=0 runs HEAD's full-sweep expressions —
    bit-equal coefficients AND residual total, per model type."""
    ds = _game(rng, n=500, users=20)
    cfg = descent.CoordinateDescentConfig(["fixed", "per-user"],
                                          iterations=3)
    _, a_dir = _run(variant, ds, mesh, cfg, tmp_path, "a", sweep=None)
    _, b_dir = _run(variant, ds, mesh, cfg, tmp_path, "b",
                    sweep=swp.SweepConfig())  # gate=0
    a, b = _ckpt_arrays(a_dir), _ckpt_arrays(b_dir)
    assert sorted(a) == sorted(b)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)


def _run(variant, ds, mesh, cfg, tmp_path, name, sweep):
    d = str(tmp_path / name)
    model, _ = descent.run(TaskType.LOGISTIC_REGRESSION,
                           _variant_coordinates(variant, ds, mesh), cfg,
                           checkpoint_manager=CheckpointManager(d),
                           sweep=sweep)
    return model, d


# ------------------------------ rung 2: gated band + visible skipping


def test_gated_run_skips_and_stays_in_band(rng, mesh, tmp_path):
    """Gated sweeps actually skip entities on iterations >= 2 (ledger
    rows + counters move), the final-full-sweep backstop refits every
    entity, and the final model lands in the 5e-3 band of a full run."""
    ds = _game(rng, n=800, users=30)
    cfg = descent.CoordinateDescentConfig(["fixed", "per-user"],
                                          iterations=4)
    coords = _variant_coordinates("dense", ds, mesh)
    ref, _ = descent.run(TaskType.LOGISTIC_REGRESSION, dict(coords), cfg)

    obs.enable(trace=False)
    led = RunLedger.resume(str(tmp_path / "ledger"))
    obs.set_ledger(led)
    try:
        got, _ = descent.run(
            TaskType.LOGISTIC_REGRESSION,
            _variant_coordinates("dense", ds, mesh), cfg,
            sweep=swp.SweepConfig(theta=0.05, grad_tol=0.05))
    finally:
        led.close()
        obs.set_ledger(None)

    np.testing.assert_allclose(np.asarray(got.models["per-user"].means),
                               np.asarray(ref.models["per-user"].means),
                               atol=5e-3, rtol=5e-3)

    rows, problems = read_rows(str(tmp_path / "ledger"))
    assert problems == []
    waves = [r for r in rows if r.get("kind") == "re_fit_wave"]
    assert waves, "gated run recorded no re_fit_wave rows"
    by_iter = {}
    for r in waves:
        it = r["outer_iteration"]
        by_iter.setdefault(it, [0, 0])
        by_iter[it][0] += r["entities_fit"]
        by_iter[it][1] += r["entities_skipped"]
    trained = int(coords["per-user"].bucketing.trained_entities.sum())
    # Warm-up sweep (min_sweeps_full=1) and the final backstop are full.
    assert by_iter[0] == [trained, 0]
    assert by_iter[3] == [trained, 0]
    skipped = sum(by_iter[it][1] for it in (1, 2))
    assert skipped > 0, f"gate never engaged: {by_iter}"
    assert all(f + s == trained for f, s in by_iter.values())
    # The counters tell the same story.
    snap = obs.metrics().snapshot()
    skip_keys = [k for k in snap
                 if k.startswith("photon_re_entities_skipped_total")]
    refit_keys = [k for k in snap
                  if k.startswith("photon_re_entities_refit_total")]
    assert skip_keys and sum(snap[k] for k in skip_keys) == skipped
    assert sum(snap[k] for k in refit_keys) == \
        sum(by_iter[it][0] for it in by_iter)
    # And the photon-obs diff aggregation reads them back.
    summary = fit_wave_summary(rows)
    assert [e["entities_skipped"] for e in summary["per-user"]] == \
        [by_iter[it][1] for it in sorted(by_iter)]


def test_gated_delta_matches_full_rescore(rng, mesh):
    """Coordinate-level: the scatter-added score delta equals the full
    score diff, and a second gated sweep under barely-moved offsets
    skips most entities."""
    ds = _game(rng, n=600, users=25)
    coord = RandomEffectCoordinate(ds, "userId", "re_userId",
                                   losses.LOGISTIC, _opt(), mesh)
    state = coord.make_sweep_state()
    cfg = swp.SweepConfig(theta=1e-3, grad_tol=1e-4)
    offsets = jnp.asarray(ds.offsets)
    model, delta, stats = coord.train_model_gated(
        offsets, state=state, config=cfg, force_full=True)
    assert delta is not None
    np.testing.assert_allclose(np.asarray(delta),
                               np.asarray(coord.score(model)),
                               atol=1e-4, rtol=1e-4)
    trained = int(coord.bucketing.trained_entities.sum())
    assert stats["entities_fit"] == trained
    # Offsets barely move -> the gate keeps converged entities out.
    model2, delta2, stats2 = coord.train_model_gated(
        offsets + 1e-6, state=state, config=cfg, initial=model)
    assert stats2["entities_fit"] + stats2["entities_skipped"] == trained
    assert stats2["entities_skipped"] > 0
    # Skipped entities' rows carry EXACTLY zero delta.
    refit_rows = np.zeros(ds.num_rows, bool)
    d2 = np.asarray(delta2)
    W1 = np.asarray(model.means)
    W2 = np.asarray(model2.means)
    changed = np.flatnonzero(np.any(W1 != W2, axis=1))
    refit_rows = np.isin(ds.entity_ids["userId"], changed)
    assert np.all(d2[~refit_rows] == 0.0)


# ----------------------------------------- satellite: Gram reuse


def test_gram_solver_parity_and_cache(rng, mesh):
    """Squared-loss + L2: the cached normal-equation solve matches the
    iterative solver inside the coefficient band, reuses the SAME Gram
    blocks across sweeps, and silently falls back when ineligible."""
    ds = _game(rng, n=700, users=24)
    ds.response = rng.normal(size=ds.num_rows).astype(np.float32)
    opt = _opt(l2=0.5, max_iter=80)
    coord = RandomEffectCoordinate(ds, "userId", "re_userId",
                                   losses.SQUARED, opt, mesh)
    assert coord._gram_eligible()
    state = coord.make_sweep_state()
    gcfg = swp.SweepConfig(theta=1e-3, grad_tol=1e-4, gram=True)
    offsets = jnp.asarray(ds.offsets)
    gram_model, _, _ = coord.train_model_gated(
        offsets, state=state, config=gcfg, force_full=True)
    it_model = coord.train_model(offsets)
    np.testing.assert_allclose(np.asarray(gram_model.means),
                               np.asarray(it_model.means),
                               atol=5e-3, rtol=5e-3)
    # The cache holds one block set per staged tuple and a second sweep
    # reuses it bit-for-bit.
    assert coord._gram_cache
    cached = {w: np.asarray(G) for w, G in coord._gram_cache.items()}
    coord.train_model_gated(offsets + 1e-4, state=state, config=gcfg,
                            initial=gram_model)
    for w, G in coord._gram_cache.items():
        np.testing.assert_array_equal(np.asarray(G), cached[w])
    # Ineligible without the ridge term (singular normal matrix for
    # entities with fewer samples than features) and for non-squared
    # losses — the gated path then runs the iterative solver.
    assert not RandomEffectCoordinate(
        ds, "userId", "re_userId", losses.SQUARED, _opt(l2=0.0),
        mesh)._gram_eligible()
    assert not RandomEffectCoordinate(
        ds, "userId", "re_userId", losses.LOGISTIC, opt,
        mesh)._gram_eligible()


def test_gram_descent_band(rng, mesh):
    ds = _game(rng, n=500, users=20)
    ds.response = rng.normal(size=ds.num_rows).astype(np.float32)
    cfg = descent.CoordinateDescentConfig(["fixed", "per-user"],
                                          iterations=3)

    def coords():
        return {
            "fixed": FixedEffectCoordinate(ds, "global", losses.SQUARED,
                                           _opt(l2=0.5), mesh),
            "per-user": RandomEffectCoordinate(ds, "userId", "re_userId",
                                               losses.SQUARED,
                                               _opt(l2=0.5), mesh),
        }

    ref, _ = descent.run(TaskType.LINEAR_REGRESSION, coords(), cfg)
    got, _ = descent.run(TaskType.LINEAR_REGRESSION, coords(), cfg,
                         sweep=swp.SweepConfig(theta=1e-3, grad_tol=1e-4,
                                               gram=True))
    np.testing.assert_allclose(np.asarray(got.models["per-user"].means),
                               np.asarray(ref.models["per-user"].means),
                               atol=5e-3, rtol=5e-3)


# ------------------------- rung 3: checkpointed gated resume


class _GatedKill:
    """Proxy a coordinate; raise after ``allow`` gated train calls."""

    def __init__(self, inner, allow):
        self._inner = inner
        self._allow = allow
        self.calls = 0

    def train_model_gated(self, offsets, **kw):
        self.calls += 1
        if self.calls > self._allow:
            raise KeyboardInterrupt("simulated kill")
        return self._inner.train_model_gated(offsets, **kw)

    def __getattr__(self, name):
        return getattr(self._inner, name)


def test_gated_kill_and_resume_bit_identical(rng, mesh, tmp_path):
    """Rung 3 in-process: the dirty-set evidence rides the checkpoint
    (sweep/<cid>.npz), so a gated run killed mid-descent resumes taking
    the SAME skip decisions and lands bit-identical to an unkilled gated
    run."""
    ds = _game(rng, n=600, users=20)
    cfg = descent.CoordinateDescentConfig(["fixed", "per-user"],
                                          iterations=4)
    sweep = swp.SweepConfig(theta=1e-3, grad_tol=1e-4)

    ref, ref_dir = _run("dense", ds, mesh, cfg, tmp_path, "ref",
                        sweep=sweep)
    assert os.path.exists(os.path.join(ref_dir, "sweep", "per-user.npz"))

    manager = CheckpointManager(str(tmp_path / "killed"))
    killed = _variant_coordinates("dense", ds, mesh)
    killed["per-user"] = _GatedKill(killed["per-user"], allow=2)
    with pytest.raises(KeyboardInterrupt):
        descent.run(TaskType.LOGISTIC_REGRESSION, killed, cfg,
                    checkpoint_manager=manager, sweep=sweep)
    state = manager.load()
    assert state is not None and not state.complete
    assert "per-user" in (state.sweep_states or {})

    resumed, _ = descent.run(TaskType.LOGISTIC_REGRESSION,
                             _variant_coordinates("dense", ds, mesh), cfg,
                             checkpoint_manager=manager, sweep=sweep)
    np.testing.assert_array_equal(
        np.asarray(resumed.models["per-user"].means),
        np.asarray(ref.models["per-user"].means))
    np.testing.assert_array_equal(
        np.asarray(resumed.models["fixed"].coefficients.means),
        np.asarray(ref.models["fixed"].coefficients.means))


def test_unreadable_sweep_artifact_degrades_to_full_sweep(rng, mesh,
                                                          tmp_path):
    """A corrupt sweep/<cid>.npz must not fail the resume: the
    coordinate re-tracks from a forced full sweep (correct, just less
    incremental)."""
    ds = _game(rng, n=400, users=15)
    cfg = descent.CoordinateDescentConfig(["fixed", "per-user"],
                                          iterations=3)
    sweep = swp.SweepConfig(theta=1e-3, grad_tol=1e-4)
    manager = CheckpointManager(str(tmp_path / "ckpt"))
    killed = _variant_coordinates("dense", ds, mesh)
    killed["per-user"] = _GatedKill(killed["per-user"], allow=1)
    with pytest.raises(KeyboardInterrupt):
        descent.run(TaskType.LOGISTIC_REGRESSION, killed, cfg,
                    checkpoint_manager=manager, sweep=sweep)
    art = os.path.join(str(tmp_path / "ckpt"), "sweep", "per-user.npz")
    with open(art, "wb") as f:
        f.write(b"not an npz")
    model, _ = descent.run(TaskType.LOGISTIC_REGRESSION,
                           _variant_coordinates("dense", ds, mesh), cfg,
                           checkpoint_manager=manager, sweep=sweep)
    ref, _ = descent.run(TaskType.LOGISTIC_REGRESSION,
                         _variant_coordinates("dense", ds, mesh), cfg,
                         sweep=sweep)
    np.testing.assert_allclose(np.asarray(model.models["per-user"].means),
                               np.asarray(ref.models["per-user"].means),
                               atol=5e-3, rtol=5e-3)


# ------------------- rung 3 end-to-end: SIGKILL at sweep.gate_state


def _sweep_train_args(train_dir, out, cache):
    return [
        "--train", train_dir,
        "--coordinate", "name=fixed,type=fixed,shard=global",
        "--coordinate", "name=per-user,type=random,shard=re_userId,"
                        "re=userId",
        "--update-sequence", "fixed,per-user",
        "--iterations", "4",
        "--opt-config", "per-user:optimizer=LBFGS,reg=L2,reg_weight=1.0",
        "--sweep", "theta=0.05,grad_tol=0.05",
        "--output-dir", out,
        "--staging-cache-dir", cache,
        "--staging", "workers=2,shard_entities=8",
    ]


def test_sweep_sigkill_resume_bit_identical(tmp_path):
    """The chaos drill (docs/ROBUSTNESS.md ``sweep.gate_state``): the
    driver is SIGKILLed at the dirty-set checkpoint seam mid-run; the
    ``--resume`` rerun continues from the last committed generation and
    the final coefficients are bit-identical to a never-killed gated
    run."""
    from photon_ml_tpu.data.io import save_game_dataset

    rng = np.random.default_rng(0)
    ds = _game(rng, n=600, users=25)
    train_dir = str(tmp_path / "train")
    save_game_dataset(ds, train_dir)
    out = str(tmp_path / "out-killed")

    # The site fires once per checkpointed gated save; the 5th firing
    # lands mid-run (4 iterations x 2 coordinates = 8 saves).
    plan = faults.FaultPlan(specs=(
        faults.FaultSpec(site="sweep.gate_state", kind="kill",
                         occurrences=(4,)),))
    plan_path = str(tmp_path / "plan.json")
    with open(plan_path, "w") as f:
        f.write(plan.to_json())
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "PYTHONPATH": REPO + (os.pathsep + env["PYTHONPATH"]
                                      if env.get("PYTHONPATH") else "")})
    log_path = str(tmp_path / "phase1.log")
    with open(log_path, "w") as log:
        proc = subprocess.run(
            [sys.executable, "-m", "photon_ml_tpu.cli.game_train"]
            + _sweep_train_args(train_dir, out,
                                str(tmp_path / "cache"))
            + ["--fault-plan", plan_path],
            env=env, cwd=REPO, stdout=log, stderr=subprocess.STDOUT,
            timeout=600)
    assert proc.returncode == -9, (
        f"driver survived the SIGKILL plan (rc={proc.returncode}):\n"
        + open(log_path).read()[-3000:])
    # The kill landed before the generation's commit point: a committed
    # earlier generation with sweep state is on disk.
    ckpt = os.path.join(out, "checkpoints", "grid-0")
    assert os.path.exists(os.path.join(ckpt, "state.json"))
    assert os.path.exists(os.path.join(ckpt, "sweep", "per-user.npz"))

    log_path2 = str(tmp_path / "phase2.log")
    with open(log_path2, "w") as log:
        proc = subprocess.run(
            [sys.executable, "-m", "photon_ml_tpu.cli.game_train"]
            + _sweep_train_args(train_dir, out,
                                str(tmp_path / "cache"))
            + ["--resume"],
            env=env, cwd=REPO, stdout=log, stderr=subprocess.STDOUT,
            timeout=600)
    assert proc.returncode == 0, open(log_path2).read()[-3000:]

    out_clean = str(tmp_path / "out-clean")
    log_path3 = str(tmp_path / "phase3.log")
    with open(log_path3, "w") as log:
        proc = subprocess.run(
            [sys.executable, "-m", "photon_ml_tpu.cli.game_train"]
            + _sweep_train_args(train_dir, out_clean,
                                str(tmp_path / "cache2")),
            env=env, cwd=REPO, stdout=log, stderr=subprocess.STDOUT,
            timeout=600)
    assert proc.returncode == 0, open(log_path3).read()[-3000:]

    for rel in (os.path.join("best", "random-effect", "per-user",
                             "coefficients.npz"),
                os.path.join("best", "fixed-effect", "fixed",
                             "coefficients.npz")):
        a = np.load(os.path.join(out, rel))
        b = np.load(os.path.join(out_clean, rel))
        assert sorted(a.files) == sorted(b.files)
        for k in a.files:
            np.testing.assert_array_equal(a[k], b[k], err_msg=rel)
