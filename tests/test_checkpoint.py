"""Checkpoint/restart tests for coordinate descent (SURVEY.md §5 failure
recovery — the Spark-lineage replacement).

Kill-and-resume: a descent killed mid-run and restarted from its checkpoint
must produce the same final model as an uninterrupted run. The checkpoint
persists the (n,) residual score total, so resume continues the exact f32
accumulation chain of the interrupted run (tolerances below predate that
and are now conservative; checkpoints without residuals fall back to fresh
summation, which is same-model-correct but not bit-exact).
"""

import json
import os

import jax
import numpy as np
import pytest

from photon_ml_tpu.api.configs import (CoordinateConfiguration,
                                       FixedEffectDataConfiguration,
                                       RandomEffectDataConfiguration)
from photon_ml_tpu.api.estimator import GameEstimator
from photon_ml_tpu.data import synthetic
from photon_ml_tpu.data.game_data import from_synthetic
from photon_ml_tpu.game import checkpoint as ckpt_mod
from photon_ml_tpu.game import descent
from photon_ml_tpu.game.checkpoint import CheckpointManager
from photon_ml_tpu.game.coordinates import (FixedEffectCoordinate,
                                            RandomEffectCoordinate)
from photon_ml_tpu.game.factored import FactoredRandomEffectCoordinate
from photon_ml_tpu.game.staging_cache import file_crc32
from photon_ml_tpu.ops import losses
from photon_ml_tpu.optim import OptimizerConfig
from photon_ml_tpu.optim.problem import GLMOptimizationConfiguration
from photon_ml_tpu.optim.regularization import (RegularizationContext,
                                                RegularizationType)
from photon_ml_tpu.parallel.mesh import make_mesh
from photon_ml_tpu.types import TaskType

VARIANTS = ["dense", "projected", "subspace", "factored"]


@pytest.fixture(scope="module")
def mesh():
    return make_mesh()


def _setup(rng, mesh):
    syn = synthetic.game_data(rng, n=600, d_global=6,
                              re_specs={"userId": (12, 3)})
    ds = from_synthetic(syn)
    opt = GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(max_iterations=40, tolerance=1e-7))
    cc = {
        "fixed": CoordinateConfiguration(
            data=FixedEffectDataConfiguration("global"), optimization=opt),
        "per-user": CoordinateConfiguration(
            data=RandomEffectDataConfiguration("userId", "re_userId"),
            optimization=opt),
    }
    est = GameEstimator(TaskType.LOGISTIC_REGRESSION, cc,
                        ["fixed", "per-user"], mesh, descent_iterations=2)
    coords = est._build_coordinates(
        ds, {cid: c.optimization for cid, c in cc.items()})
    cfg = descent.CoordinateDescentConfig(["fixed", "per-user"], iterations=2)
    return est, coords, cfg


class _KillSwitch:
    """Proxy a coordinate; raise after ``allow`` train_model calls."""

    def __init__(self, inner, allow):
        self._inner = inner
        self._allow = allow
        self.calls = 0

    def train_model(self, offsets, initial=None):
        self.calls += 1
        if self.calls > self._allow:
            raise KeyboardInterrupt("simulated kill")
        return self._inner.train_model(offsets, initial=initial)

    def __getattr__(self, name):
        return getattr(self._inner, name)


def _model_arrays(model):
    out = {}
    for cid, m in model.models.items():
        if hasattr(m, "factors"):  # factored: compare implied (E, d) table
            out[cid] = np.asarray(m.to_random_effect_model().means)
        elif hasattr(m, "means"):
            out[cid] = np.asarray(m.means)
        else:
            out[cid] = np.asarray(m.coefficients.means)
    return out


def test_kill_and_resume_matches_uninterrupted(rng, mesh, tmp_path):
    est, coords, cfg = _setup(rng, mesh)
    task = est.task

    # Ground truth: uninterrupted run, no checkpointing.
    clean_model, clean_hist = descent.run(task, coords, cfg)

    # Interrupted run: kill during the 3rd coordinate update (of 4).
    manager = CheckpointManager(str(tmp_path / "ckpt"))
    killed = dict(coords)
    killed["fixed"] = _KillSwitch(coords["fixed"], allow=1)
    with pytest.raises(KeyboardInterrupt):
        descent.run(task, killed, cfg, checkpoint_manager=manager)
    state = manager.load()
    assert state is not None and not state.complete
    assert state.done_steps == 2  # iter-0 fixed + iter-0 per-user

    # Resume with pristine coordinates and the same manager.
    resumed_model, resumed_hist = descent.run(
        task, coords, cfg, checkpoint_manager=manager)
    assert len(resumed_hist.records) == len(clean_hist.records)

    clean = _model_arrays(clean_model)
    resumed = _model_arrays(resumed_model)
    for cid in clean:
        np.testing.assert_allclose(resumed[cid], clean[cid],
                                   rtol=1e-4, atol=1e-5)

    # The final checkpoint is marked complete…
    final = manager.load()
    assert final.complete and final.done_steps == 4
    # …and a THIRD run short-circuits entirely (no training calls).
    counter = _KillSwitch(coords["fixed"], allow=0)
    third = dict(coords)
    third["fixed"] = counter
    again_model, _ = descent.run(task, third, cfg,
                                 checkpoint_manager=manager)
    assert counter.calls == 0
    for cid, arr in _model_arrays(again_model).items():
        np.testing.assert_allclose(arr, resumed[cid], rtol=1e-6)


def test_checkpoint_save_is_atomic_over_existing(rng, mesh, tmp_path):
    est, coords, cfg = _setup(rng, mesh)
    manager = CheckpointManager(str(tmp_path / "ckpt"))
    model, hist = descent.run(est.task, coords, cfg,
                              checkpoint_manager=manager)
    first = manager.load()
    # Overwrite with a later state: the directory swap must leave a
    # readable checkpoint (no partial writes), and reflect the new state.
    manager.save(est.task, model.models, done_steps=99,
                 records=hist.records, complete=True)
    second = manager.load()
    assert second.done_steps == 99
    assert set(second.models) == set(first.models)


def test_estimator_checkpoint_dir_resumes_grid(rng, mesh, tmp_path):
    syn = synthetic.game_data(rng, n=400, d_global=5, re_specs={})
    ds = from_synthetic(syn)
    opt = GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(max_iterations=30, tolerance=1e-7))
    cc = {"fixed": CoordinateConfiguration(
        data=FixedEffectDataConfiguration("global"), optimization=opt,
        reg_weight_grid=(0.1, 10.0))}
    est = GameEstimator(TaskType.LOGISTIC_REGRESSION, cc, ["fixed"], mesh)
    r1 = est.fit(ds, checkpoint_dir=str(tmp_path / "ck"))
    assert (tmp_path / "ck" / "grid-0").exists()
    assert (tmp_path / "ck" / "grid-1").exists()
    # Second fit resumes every grid point from its complete checkpoint.
    r2 = est.fit(ds, checkpoint_dir=str(tmp_path / "ck"))
    for a, b in zip(r1, r2):
        for cid in a.model.models:
            np.testing.assert_allclose(
                np.asarray(a.model.models[cid].coefficients.means),
                np.asarray(b.model.models[cid].coefficients.means),
                rtol=1e-6)


@pytest.mark.parametrize("change", ["iterations", "gated_parent"])
def test_checkpoint_discarded_on_config_change(rng, mesh, tmp_path,
                                               monkeypatch, change):
    """A checkpoint written under a different configuration must be
    discarded (retrain), not silently resumed as the wrong result.

    ``gated_parent``: the checkpoint a dirty-gated run of an older
    release left behind (a ``sweep`` key in its fingerprint, a CRC-vouched
    ``sweep/<cid>.npz`` beside the models). Nothing knows that layout any
    more, so it is discarded whole, never half-read."""
    est, coords, cfg = _setup(rng, mesh)
    ckpt = str(tmp_path / "ckpt")
    manager = CheckpointManager(ckpt)
    descent.run(est.task, coords, cfg, checkpoint_manager=manager)
    assert manager.load().complete

    cfg2 = cfg
    if change == "iterations":
        # Same coords, different iteration count -> fingerprint mismatch.
        cfg2 = descent.CoordinateDescentConfig(["fixed", "per-user"],
                                               iterations=1)
    else:
        os.makedirs(os.path.join(ckpt, "sweep"))
        art = os.path.join(ckpt, "sweep", "per-user.npz")
        with open(art, "wb") as f:
            f.write(b"not an npz: whoever opens this fails")
        with open(os.path.join(ckpt, "state.json")) as f:
            state = json.load(f)
        state["fingerprint"]["sweep"] = {
            "theta": 1e-3, "grad_tol": 1e-4, "min_sweeps_full": 1,
            "final_full_sweep": True, "gram": False}
        state["artifacts"]["sweep/per-user.npz"] = file_crc32(art)
        with open(os.path.join(ckpt, "state.json"), "w") as f:
            json.dump(state, f)
        manager = CheckpointManager(ckpt)  # a new process
    opened = []
    real_load = np.load

    def spy_load(path, *a, **kw):
        opened.append(str(path))
        return real_load(path, *a, **kw)

    monkeypatch.setattr(ckpt_mod.np, "load", spy_load)
    counter = _KillSwitch(coords["fixed"], allow=10)
    coords2 = dict(coords)
    coords2["fixed"] = counter
    descent.run(est.task, coords2, cfg2, checkpoint_manager=manager)
    # it retrained instead of short-circuiting
    assert counter.calls == cfg2.iterations
    assert not [p for p in opened if "sweep" in p]
    final = manager.load()
    assert final.complete and "sweep" not in final.fingerprint
    with open(os.path.join(ckpt, "state.json")) as f:
        assert not [a for a in json.load(f)["artifacts"]
                    if a.startswith("sweep/")]


def test_kill_and_resume_with_down_sampling(rng, mesh, tmp_path):
    """Resume must fast-forward the down-sampling RNG so remaining steps
    subsample exactly as the uninterrupted run would."""
    syn = synthetic.game_data(rng, n=800, d_global=6, re_specs={})
    ds = from_synthetic(syn)
    opt = GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(max_iterations=40, tolerance=1e-7),
        down_sampling_rate=0.5)
    cc = {"fixed": CoordinateConfiguration(
        data=FixedEffectDataConfiguration("global"), optimization=opt)}
    est = GameEstimator(TaskType.LOGISTIC_REGRESSION, cc, ["fixed"], mesh,
                        descent_iterations=3)
    cfg = descent.CoordinateDescentConfig(["fixed"], iterations=3)

    coords = est._build_coordinates(ds, {"fixed": opt})
    clean_model, _ = descent.run(est.task, coords, cfg)

    coords2 = est._build_coordinates(ds, {"fixed": opt})
    manager = CheckpointManager(str(tmp_path / "ckpt"))
    killed = dict(coords2)
    killed["fixed"] = _KillSwitch(coords2["fixed"], allow=2)
    with pytest.raises(KeyboardInterrupt):
        descent.run(est.task, killed, cfg, checkpoint_manager=manager)

    coords3 = est._build_coordinates(ds, {"fixed": opt})
    resumed_model, _ = descent.run(est.task, coords3, cfg,
                                   checkpoint_manager=manager)
    np.testing.assert_allclose(
        np.asarray(resumed_model.models["fixed"].coefficients.means),
        np.asarray(clean_model.models["fixed"].coefficients.means),
        rtol=1e-4, atol=1e-5)


def test_kill_and_resume_with_factored_coordinate(rng, mesh, tmp_path):
    """The checkpoint machinery is coordinate-type agnostic: a factored
    coordinate's (projection, factors) state survives kill-and-resume and
    reproduces the uninterrupted model."""
    from photon_ml_tpu.api.configs import (
        FactoredRandomEffectDataConfiguration)
    from photon_ml_tpu.game.factored import FactoredRandomEffectModel

    syn = synthetic.game_data(rng, n=600, d_global=6,
                              re_specs={"userId": (12, 6)})
    ds = from_synthetic(syn)
    opt = GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(max_iterations=30, tolerance=1e-7))
    cc = {
        "fixed": CoordinateConfiguration(
            data=FixedEffectDataConfiguration("global"), optimization=opt),
        "mf": CoordinateConfiguration(
            data=FactoredRandomEffectDataConfiguration(
                "userId", "re_userId", rank=2, alternations=1),
            optimization=opt),
    }
    est = GameEstimator(TaskType.LOGISTIC_REGRESSION, cc, ["fixed", "mf"],
                        mesh, descent_iterations=2)
    coords = est._build_coordinates(
        ds, {cid: c.optimization for cid, c in cc.items()})
    cfg = descent.CoordinateDescentConfig(["fixed", "mf"], iterations=2)

    ref_model, _ = descent.run(TaskType.LOGISTIC_REGRESSION, dict(coords),
                               cfg)
    ref = _model_arrays(ref_model)

    ckpt_dir = str(tmp_path / "ckpt")
    killed = dict(coords)
    killed["mf"] = _KillSwitch(coords["mf"], allow=1)
    with pytest.raises(KeyboardInterrupt):
        descent.run(TaskType.LOGISTIC_REGRESSION, killed, cfg,
                    checkpoint_manager=CheckpointManager(ckpt_dir))
    model, _ = descent.run(TaskType.LOGISTIC_REGRESSION, dict(coords), cfg,
                           checkpoint_manager=CheckpointManager(ckpt_dir))
    assert isinstance(model.models["mf"], FactoredRandomEffectModel)
    got = _model_arrays(model)
    for cid in ref:
        np.testing.assert_allclose(got[cid], ref[cid], rtol=1e-3,
                                   atol=1e-4)


def test_kill_and_resume_with_subspace_coordinate(rng, mesh, tmp_path):
    """A SubspaceRandomEffectModel's (cols, means) state survives
    kill-and-resume and reproduces the uninterrupted model.

    Parity is approximate by construction: the resumed run rebuilds its
    residuals by re-scoring the checkpoint-roundtripped models, so the
    retrained solves see ~1e-5-perturbed offsets that logistic curvature
    amplifies into ~1e-4-scale coefficient differences (observed
    flipping a tighter tolerance on a sum-order-only change in the
    scoring kernel). L2 regularization keeps the per-entity solves
    well-posed (unregularized 12-entity logistic is separable);
    tolerances admit the roundtrip, not solver drift."""
    from photon_ml_tpu.data.game_data import GameDataset, SparseShard
    from photon_ml_tpu.optim.regularization import (RegularizationContext,
                                                    RegularizationType)
    from photon_ml_tpu.game.models import SubspaceRandomEffectModel

    n, d, E, nnz = 900, 64, 12, 4
    ids = rng.integers(0, E, n).astype(np.int32)
    idx = np.sort(rng.integers(0, d, (n, nnz)).astype(np.int32), axis=1)
    dup = np.zeros_like(idx, bool)
    dup[:, 1:] = idx[:, 1:] == idx[:, :-1]
    vals = rng.normal(size=(n, nnz)).astype(np.float32)
    idx[dup] = d
    vals[dup] = 0.0
    y = rng.integers(0, 2, n).astype(np.float32)
    ds = GameDataset(
        response=y, offsets=np.zeros(n, np.float32),
        weights=np.ones(n, np.float32),
        feature_shards={"global": rng.normal(size=(n, 5)).astype(
            np.float32), "re": SparseShard(idx, vals, d)},
        entity_ids={"userId": ids}, num_entities={"userId": E},
        intercept_index={})
    opt = GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(max_iterations=30, tolerance=1e-7),
        regularization=RegularizationContext(RegularizationType.L2, 1.0))
    cc = {
        "fixed": CoordinateConfiguration(
            data=FixedEffectDataConfiguration("global"), optimization=opt),
        "per-user": CoordinateConfiguration(
            data=RandomEffectDataConfiguration(
                "userId", "re", projector="INDEX_MAP",
                subspace_model=True),
            optimization=opt),
    }
    est = GameEstimator(TaskType.LOGISTIC_REGRESSION, cc,
                        ["fixed", "per-user"], mesh, descent_iterations=2)
    coords = est._build_coordinates(
        ds, {cid: c.optimization for cid, c in cc.items()})
    cfg = descent.CoordinateDescentConfig(["fixed", "per-user"],
                                          iterations=2)

    ref_model, _ = descent.run(TaskType.LOGISTIC_REGRESSION, dict(coords),
                               cfg)
    ref = _model_arrays(ref_model)

    ckpt_dir = str(tmp_path / "ckpt")
    killed = dict(coords)
    killed["per-user"] = _KillSwitch(coords["per-user"], allow=1)
    with pytest.raises(KeyboardInterrupt):
        descent.run(TaskType.LOGISTIC_REGRESSION, killed, cfg,
                    checkpoint_manager=CheckpointManager(ckpt_dir))
    model, _ = descent.run(TaskType.LOGISTIC_REGRESSION, dict(coords), cfg,
                           checkpoint_manager=CheckpointManager(ckpt_dir))
    m = model.models["per-user"]
    assert isinstance(m, SubspaceRandomEffectModel)
    np.testing.assert_array_equal(
        np.asarray(m.cols), np.asarray(ref_model.models["per-user"].cols))
    got = _model_arrays(model)
    for cid in ref:
        np.testing.assert_allclose(got[cid], ref[cid], rtol=1e-3,
                                   atol=1e-3)


# -- bit-reproducibility per model type ---------------------------------------

def _opt(l2=1.0, max_iter=40):
    return GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(max_iterations=max_iter, tolerance=1e-7),
        regularization=RegularizationContext(RegularizationType.L2, l2))


def _game(rng, n=500, users=20, d_re=3):
    syn = synthetic.game_data(rng, n=n, d_global=4,
                              re_specs={"userId": (users, d_re)})
    return from_synthetic(syn)


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
    else:
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


def _run(variant, ds, mesh, directory=None):
    """One three-sweep descent on fresh coordinates; its model, and the
    checkpoint it committed when given a directory."""
    cfg = descent.CoordinateDescentConfig(["fixed", "per-user"],
                                          iterations=3, sync_updates=True)
    manager = None if directory is None else CheckpointManager(directory)
    model, _ = descent.run(TaskType.LOGISTIC_REGRESSION,
                           _variant_coordinates(variant, ds, mesh), cfg,
                           checkpoint_manager=manager)
    return model


@pytest.mark.parametrize("variant", VARIANTS)
def test_descent_is_bit_reproducible(rng, mesh, tmp_path, variant):
    """Two runs of one descent: bit-equal coefficients AND residual total,
    per model type. Every "equal to the last digit" of a parent-and-change
    comparison (PERF.md) rests on this."""
    ds = _game(rng)
    a_dir, b_dir = str(tmp_path / "a"), str(tmp_path / "b")
    _run(variant, ds, mesh, a_dir)
    _run(variant, ds, mesh, b_dir)
    a, b = _ckpt_arrays(a_dir), _ckpt_arrays(b_dir)
    assert sorted(a) == sorted(b) and len(a) >= 3
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)


@pytest.mark.parametrize("variant", VARIANTS)
def test_checkpoint_manager_leaves_the_iterates_alone(rng, mesh, tmp_path,
                                                      monkeypatch, variant):
    """Checkpointing observes a descent and never steers it: with and
    without a manager the models are bit-equal, and the committed
    ``residuals.npz`` is the live score total (what makes a resume
    bit-exact)."""
    ds = _game(rng)
    synced = []  # the descent's barrier after each update holds the total
    real = jax.block_until_ready

    def spy_barrier(x):
        synced.append(x)
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", spy_barrier)

    def live_total():
        total = [x for x in synced
                 if getattr(x, "shape", None) == (ds.num_rows,)][-1]
        del synced[:]
        return np.asarray(total)

    bare = _run(variant, ds, mesh)
    bare_total = live_total()
    kept = _run(variant, ds, mesh, str(tmp_path / "ckpt"))
    kept_total = live_total()
    want, got = _model_arrays(bare), _model_arrays(kept)
    for cid in want:
        np.testing.assert_array_equal(got[cid], want[cid], err_msg=cid)
    np.testing.assert_array_equal(kept_total, bare_total)
    np.testing.assert_array_equal(
        _ckpt_arrays(str(tmp_path / "ckpt"))["residual_total"], bare_total)


def test_kill_and_resume_with_projected_coordinate(rng, mesh, tmp_path):
    """The projected (E, d) table — rows rewritten through per-entity
    column maps — survives kill-and-resume: the restored residual total
    continues the interrupted run's accumulation chain, so the resumed
    model is the uninterrupted one bit for bit."""
    ds = _game(rng)
    cfg = descent.CoordinateDescentConfig(["fixed", "per-user"],
                                          iterations=3)
    coords = _variant_coordinates("projected", ds, mesh)
    assert coords["per-user"].projection and not coords["per-user"].subspace
    ref = _model_arrays(descent.run(TaskType.LOGISTIC_REGRESSION,
                                    dict(coords), cfg)[0])

    ckpt_dir = str(tmp_path / "ckpt")
    killed = dict(coords)
    killed["per-user"] = _KillSwitch(coords["per-user"], allow=1)
    with pytest.raises(KeyboardInterrupt):
        descent.run(TaskType.LOGISTIC_REGRESSION, killed, cfg,
                    checkpoint_manager=CheckpointManager(ckpt_dir))
    state = CheckpointManager(ckpt_dir).load()
    assert not state.complete and state.done_steps == 3
    model, hist = descent.run(TaskType.LOGISTIC_REGRESSION, dict(coords),
                              cfg,
                              checkpoint_manager=CheckpointManager(ckpt_dir))
    assert len(hist.records) == 6
    got = _model_arrays(model)
    for cid in ref:
        np.testing.assert_array_equal(got[cid], ref[cid], err_msg=cid)
