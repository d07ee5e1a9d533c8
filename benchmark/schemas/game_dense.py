"""The schema ``game_dense``: a GLMix whose every feature shard is a dense
``(n, d)`` float32 matrix with the intercept in its last column: one fixed
effect over the shard ``global`` and one random effect per entity column over
``re_<entity>``; logistic or squared loss.

What ``run.py`` and the metric readers ask of a schema (``run.SCHEMA``), this
module answers from the files that stand beside ``run.py``: the generator
``gen.py``, the plain reference ``reference.py`` and the work counts
``work.py``, none of which imports the program. Only ``dataset``,
``estimator``, ``model_arrays`` and the faults touch the program.
"""

from __future__ import annotations

import contextlib
import sys

import numpy as np

import gen
import reference
import work

make = gen.make
check = reference.check

TASKS = {"logistic": "LOGISTIC_REGRESSION", "linear": "LINEAR_REGRESSION"}
SMALL_ROWS = 16  # the fault ``stale-small-waves`` leaves such buckets stale


def shrink(conf: dict, rows: int) -> dict:
    """The rehearsal's configuration: fewer entities with the source's
    activity each, scaled to the rows."""
    few = [max(8, min(e["count"], rows // 20)) for e in conf["entities"]]
    return dict(conf, num_rows=rows, entities=[
        dict(e, count=k, activity=dict(
            e["activity"], rows=e["activity"]["rows"] * k / e["count"]))
        for e, k in zip(conf["entities"], few)])


def dataset(data):
    from photon_ml_tpu.data.game_data import GameDataset

    n = data.num_rows
    return GameDataset(
        response=data.response, offsets=np.zeros(n, np.float32),
        weights=np.ones(n, np.float32), feature_shards=dict(data.shards),
        entity_ids=dict(data.entity_ids),
        num_entities=dict(data.num_entities),
        intercept_index={k: v.shape[1] - 1 for k, v in data.shards.items()})


def estimator(cell: dict, mesh, sweeps: int, ledger_dir: str,
              feature_dtype: str):
    """The object the window drives, built as ``cli/game_train.main`` builds
    it, from the cell's files alone."""
    from photon_ml_tpu.api.configs import (CoordinateConfiguration,
                                           FixedEffectDataConfiguration,
                                           RandomEffectDataConfiguration)
    from photon_ml_tpu.api.estimator import GameEstimator
    from photon_ml_tpu.optim import (OptimizerConfig, OptimizerType,
                                     RegularizationContext)
    from photon_ml_tpu.optim.problem import GLMOptimizationConfiguration
    from photon_ml_tpu.optim.regularization import RegularizationType

    o = cell["settings"]["optimizer"]
    opt = GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(
            optimizer_type=OptimizerType(o["optimizer"]),
            max_iterations=int(o["max_iterations"])),
        regularization=RegularizationContext(
            reg_type=RegularizationType(o["regularization"]),
            reg_weight=float(o["reg_weight"])))
    coords = {}
    for cid, c in cell["mix"]["coordinates"].items():
        if c["type"] == "fixed":
            data = FixedEffectDataConfiguration(
                c["shard"], feature_dtype=feature_dtype)
        else:
            data = RandomEffectDataConfiguration(
                random_effect_type=c["entity"],
                feature_shard_id="re_" + c["entity"],
                active_data_upper_bound=cell["settings"].get("max_samples"),
                feature_dtype=feature_dtype)
        coords[cid] = CoordinateConfiguration(data=data, optimization=opt)
    task = cell["configuration"]["task"]
    if task not in TASKS:  # gen.py, reference.py and work.py know these two
        raise SystemExit(f"unknown task {task!r}: a new task needs its loss "
                         f"in gen.py, reference.py and work.py")
    return GameEstimator(
        task=TASKS[task], coordinates=coords,
        update_sequence=list(cell["mix"]["update_sequence"]), mesh=mesh,
        descent_iterations=sweeps, validation_evaluators=None,
        compute_variances_at_end=False, ledger_dir=ledger_dir)


def model_arrays(model, mix: dict) -> dict:
    """The trained model as plain numpy, one leaf per coordinate."""
    out = {}
    for cid, c in mix["coordinates"].items():
        m = model.models[cid]
        out[cid] = np.asarray(m.coefficients.means if c["type"] == "fixed"
                              else m.means, np.float32)
    return out


# -- the work the traced sweep needs ------------------------------------------

def _fixed_iterations(ctx):
    """L-BFGS iterations of the fixed effect's update in the traced sweep."""
    from fe_iters import iterations  # benchmark/layer_metrics/fe_iters.py
    return iterations(ctx, ctx["traced_sweep"]).get(ctx["traced_sweep"])


def sweep_flops(ctx):
    its = _fixed_iterations(ctx)
    if its is None:
        return None
    conf = ctx["cell"]["configuration"]
    settings = ctx["cell"]["settings"]
    tables = [(e["features"], work.trained_rows(
        gen.activity_counts(conf["num_rows"], e["count"], e["activity"]),
        settings.get("max_samples"))) for e in conf["entities"]]
    return work.sweep_flops(
        conf["task"], conf["num_rows"], conf["global_features"], its, tables,
        int(settings["optimizer"]["max_iterations"]))


def bytes_needed(kernel: str, ctx):
    its = _fixed_iterations(ctx)
    if kernel != "fe_pass" or its is None:
        return None
    conf = ctx["cell"]["configuration"]
    return work.fe_pass_bytes(conf["num_rows"], conf["global_features"], its)


# -- the faults a cell of this schema can have --------------------------------

@contextlib.contextmanager
def _patched(holder, name: str, broken):
    sound = getattr(holder, name)
    setattr(holder, name, broken)
    try:
        yield
    finally:
        setattr(holder, name, sound)


def _unchanged():
    """A random-effect update that returns its state unchanged."""
    from photon_ml_tpu.game.coordinates import RandomEffectCoordinate

    def broken(self, offsets, initial=None):
        return initial if initial is not None else self.initial_model()
    return _patched(RandomEffectCoordinate, "train_model", broken)


def _stale_small_waves():
    """The waves of the smallest buckets (entities of at most ``SMALL_ROWS``
    rows) are fitted in the first sweep and left as they are in every later
    one: the fault ``small.<coordinate>`` exists to catch, since
    ``coef.<coordinate>`` compares few of those entities."""
    from photon_ml_tpu.game.coordinates import RandomEffectCoordinate
    train_model = RandomEffectCoordinate.train_model

    def broken(self, offsets, initial=None):
        sweep = self.__dict__.get("_fault_sweeps", 0)
        self._fault_sweeps = sweep + 1
        fit = self._fit_bucket
        if sweep:  # Xb is (lanes, rows, features); a wave's stats may be None
            self._fit_bucket = lambda W, off, Xb, *rest: (
                (W, None) if Xb.shape[1] <= SMALL_ROWS
                else fit(W, off, Xb, *rest))
        try:
            return train_model(self, offsets, initial)
        finally:
            self._fit_bucket = fit
    return _patched(RandomEffectCoordinate, "train_model", broken)


def _half_batch():
    """Half of the rows left out of training (weight 0), the objective taken
    over the rest."""
    sound = dataset

    def broken(data):
        ds = sound(data)
        ds.weights = np.where(np.arange(ds.num_rows) % 2, 0.0, 1.0
                              ).astype(np.float32)
        return ds
    return _patched(sys.modules[__name__], "dataset", broken)


faults = {"unchanged": _unchanged, "half-batch": _half_batch,
          "stale-small-waves": _stale_small_waves}


# -- run.py --selfcheck -------------------------------------------------------

def check_work():
    # 1,000 rows x 32 features, 4 iterations: 5 evaluations, X read twice
    assert work.fe_pass_bytes(1000, 32, 4) == 5 * 2 * 1000 * 32 * 4 == 1280000
    assert work.solve_evaluations("logistic", 8, 25) == 26
    assert work.solve_evaluations("linear", 8, 25) == 9
    try:
        work.solve_evaluations("poisson", 8, 25)
    except ValueError:
        pass
    else:
        raise AssertionError("an unknown task was counted")
    # three entities of 2, 5 and 9 rows under a cap of 5 train on 2 + 5 + 5
    assert work.trained_rows([2, 5, 9], 5) == 12
    assert work.trained_rows([2, 5, 9], None) == 16
    # fixed: 5 evaluations x 4 x 1000 x 32 = 640,000, rescoring 64,000; a
    # table training on all 1000 rows: 26 x 4 x 1000 x 8 = 832,000, one on
    # 900 of them: 748,800; each rescoring all rows, 16,000
    want = 640000 + 64000 + 832000 + 748800 + 2 * 16000
    assert work.sweep_flops("logistic", 1000, 32, 4,
                            [(8, 1000), (8, 900)], 25) == want
    want = 640000 + 64000 + 9 * 4 * 8 * (1000 + 900) + 2 * 16000
    assert work.sweep_flops("linear", 1000, 32, 4,
                            [(8, 1000), (8, 900)], 25) == want


def check_activity():
    """The activity curve gives back the anchors it is laid through, sums to
    the rows asked for, and leaves no entity without a row."""
    anchors = {"rows": 20000263, "min": 20, "q1": 35, "median": 68,
               "q3": 155, "max": 9254}
    full = gen.activity_counts(20000263, 138493, anchors)
    assert full.sum() == 20000263 and np.all(np.diff(full) >= 0)
    assert (full[0], full[-1]) == (20, 9254), (full[0], full[-1])
    assert list(np.quantile(full, [0.25, 0.5, 0.75])) == [35, 68, 155]
    half = gen.activity_counts(10000000, 138493, anchors)
    assert half.sum() == 10000000 and (half[0], half[-1]) == (10, 4627)
    # a long tail of one-row entities survives the cut with a row each
    tail = gen.activity_counts(5000, 1000, dict(
        anchors, rows=20000, min=1, q1=2, median=5, q3=15, max=900))
    assert tail.sum() == 5000 and tail.min() == 1


selfchecks = (check_work, check_activity)
