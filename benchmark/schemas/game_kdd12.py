"""The schema ``game_kdd12``: GAME Poisson click counts on KDD Cup 2012 track
2's search-advertising rows. Every row is an aggregate of sessions with a
click count, an impression count and one id in each of 11 fields; each
(field, id) is its own column, the fields laid end to end over
``num_features`` columns (no hashing), one non-zero a field with the value
1/sqrt(11), held in ELL form (the program's ``SparseShard``). The response is
the click count, the data's offset log(impressions). The fixed effect reads
that shard under L1; one random effect keyed on AdvertiserID reads a dense
shard of the slot's depth and position (each one-hot), (depth − position) /
depth, and the intercept in the last column, under L2.

The generator and the work counts are below; the plain reference and the
comparison are ``benchmark/kdd12_reference.py``. None of them imports the
program; only ``dataset``, ``estimator`` (with its guard) and the faults touch
it. The Zipf draw is ``game_criteo``'s, the leaves ``game_dense``'s.
"""

from __future__ import annotations

import dataclasses
import sys

import numpy as np

import game_criteo
import game_dense
import kdd12_reference

model_arrays = game_dense.model_arrays
check = kdd12_reference.check

TASK = "POISSON_REGRESSION"
_MADE = {}  # of the data ``make`` made last, for the guard in ``estimator``,
#             which gets no data: the touched columns' counts, and the rows


@dataclasses.dataclass
class Data:
    indices: np.ndarray  # (n, fields) int32: a field's offset + the id's rank
    values: np.ndarray  # (n, fields) float32
    num_features: int
    entity: str
    entity_ids: np.ndarray  # (n,) int32
    num_entities: int
    table: np.ndarray  # (n, 8) float32, last column 1.0
    response: np.ndarray  # (n,) float32 click counts
    offsets: np.ndarray  # (n,) float32 log(impressions)


# -- the generator ------------------------------------------------------------

def field_offsets(conf: dict) -> np.ndarray:
    """The first column of every field, and the width past the last: the
    fields laid end to end have to fill ``num_features`` exactly."""
    cards = [int(f["cardinality"]) for f in conf["fields"]]
    starts = np.concatenate([[0], np.cumsum(cards)]).astype(np.int64)
    if int(starts[-1]) != int(conf["num_features"]):
        raise SystemExit(f"the fields hold {int(starts[-1])} ids and "
                         f"num_features is {conf['num_features']}")
    return starts


def slot_features(depth: np.ndarray, position: np.ndarray) -> np.ndarray:
    """The table's dense shard: depth and position one-hot (1..3 each),
    (depth − position) / depth, and the intercept last."""
    n = depth.shape[0]
    x = np.zeros((n, 8), np.float32)
    x[np.arange(n), depth - 1] = 1.0
    x[np.arange(n), 3 + position - 1] = 1.0
    x[:, 6] = (depth - position) / depth
    x[:, 7] = 1.0
    return x


def make(seed: int, conf: dict) -> Data:
    rng = np.random.default_rng(int(seed))
    n, d = int(conf["num_rows"]), int(conf["num_features"])
    gen, ent = conf["assumed_generator"], conf["entity"]
    starts = field_offsets(conf)
    fields = len(conf["fields"])
    depth = 1 + rng.choice(3, size=n, p=gen["depth_shares"])
    position = 1 + (rng.random(n) * depth).astype(np.int64)
    indices = np.empty((n, fields), np.int32)
    ids = None
    for f, field in enumerate(conf["fields"]):
        if field["name"] == "Depth":
            rank = depth - 1
        elif field["name"] == "Position":
            rank = position - 1
        else:
            rank = game_criteo.zipf_ranks(rng, n, int(field["cardinality"]),
                                          float(gen["zipf_exponent"]))
        indices[:, f] = starts[f] + rank
        if f == int(ent["field_index"]):
            ids = rank.astype(np.int32)
    values = np.full((n, fields), 1.0 / np.sqrt(fields), np.float32)
    table = slot_features(depth, position)
    impressions = np.maximum(1.0, np.floor(np.exp(
        float(gen["impression_log_sd"]) * rng.standard_normal(n))))
    # planted effects: a sparse fixed effect, dense advertiser rows
    planted = rng.random(d) < float(gen["planted_fixed_share"])
    w = np.zeros(d, np.float32)
    w[planted] = float(gen["planted_fixed_sd"]) * rng.standard_normal(
        int(planted.sum()))
    W = float(gen["planted_slope_sd"]) * rng.standard_normal(
        (int(ent["count"]), table.shape[1]))
    W[:, -1] = float(gen["planted_intercept_sd"]) * rng.standard_normal(
        int(ent["count"]))
    eta = (np.log(float(gen["base_click_rate"]))
           + w[indices].sum(axis=1, dtype=np.float64) / np.sqrt(fields)
           + np.einsum("nd,nd->n", table, W[ids]))
    clicks = rng.poisson(impressions * np.exp(eta))
    counts = np.bincount(indices.reshape(-1), minlength=d)
    _MADE.update(counts=counts[counts > 0], rows=n, columns=d)
    return Data(indices, values, d, ent["name"], ids, int(ent["count"]),
                table, clicks.astype(np.float32),
                np.log(impressions).astype(np.float32))


def shrink(conf: dict, rows: int) -> dict:
    """The rehearsal's configuration: fewer rows, and as many ids a field
    and advertisers as those rows can fill (a twentieth of them at most);
    the fields stay, laid end to end over as many columns as they then
    hold."""
    few = max(8, rows // 20)
    fields = [dict(f, cardinality=min(int(f["cardinality"]), few))
              for f in conf["fields"]]
    ent = conf["entity"]
    return dict(
        conf, num_rows=rows, fields=fields,
        num_features=sum(f["cardinality"] for f in fields),
        entity=dict(ent, count=fields[int(ent["field_index"])]["cardinality"]))


def dataset(data: Data):
    from photon_ml_tpu.data.game_data import GameDataset, SparseShard

    n = data.response.shape[0]
    return GameDataset(
        response=data.response, offsets=data.offsets,
        weights=np.ones(n, np.float32),
        feature_shards={
            "global": SparseShard(data.indices, data.values,
                                  data.num_features),
            "re_" + data.entity: data.table},
        entity_ids={data.entity: data.entity_ids},
        num_entities={data.entity: data.num_entities},
        intercept_index={"re_" + data.entity: data.table.shape[1] - 1})


# -- the estimator, behind its guard ------------------------------------------

# What the fixed effect's solve holds beside the rows, in vectors of d, where
# the program does not say (``sparse_fixed.solver_state_bytes``): the least a
# compiled OWL-QN of history m has been seen to ask for at this width, 2m of
# history and 24 more (the TPU compiler's own count for a described v5e,
# PERF.md section 6, PR 33), and the layout's two permutations. A program
# that keeps its history as one (m, d) array asks for more still.
SOLVER_VECTORS = 24 + 2


def _optimization(o: dict):
    from photon_ml_tpu.optim import (OptimizerConfig, OptimizerType,
                                     RegularizationContext)
    from photon_ml_tpu.optim.problem import GLMOptimizationConfiguration
    from photon_ml_tpu.optim.regularization import RegularizationType

    keys = {"tolerance": float(o["tolerance"])} if "tolerance" in o else {}
    return GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(
            optimizer_type=OptimizerType(o["optimizer"]),
            max_iterations=int(o["max_iterations"]),
            history_length=int(o.get("history_length", 10)), **keys),
        regularization=RegularizationContext(
            reg_type=RegularizationType(o["regularization"]),
            reg_weight=float(o["reg_weight"])))


def resident_plan(mesh, feature_dtype: str, config) -> dict:
    """What the program at hand would hold on one device of ``mesh`` for
    the fixed effect of the data made last, reckoned from its own planner
    before anything is allocated: the solve's vectors, the hot block's
    columns and bytes."""
    import jax.numpy as jnp

    from photon_ml_tpu.game.coordinates import sparse_fixed
    from photon_ml_tpu.ops import hybrid_sparse as hs

    counts, n, d = _MADE["counts"], _MADE["rows"], _MADE["columns"]
    dt = jnp.bfloat16 if feature_dtype == "bfloat16" else jnp.float32
    reckons = hasattr(sparse_fixed, "solver_state_bytes")
    if reckons:
        solver = sparse_fixed.solver_state_bytes(d, config)
        budget = sparse_fixed.hot_block_budget(mesh, solver)
    else:
        solver = 4 * d * (2 * config.optimizer.history_length
                          + SOLVER_VECTORS)
        budget = sparse_fixed.hot_block_budget(mesh)
    k = hs.plan_resident_hot(counts, n, dt, hot_block_bytes=budget)
    stats = mesh.devices.flat[0].memory_stats() or {}
    return {"reckons": reckons, "solver_bytes": int(solver),
            "num_hot": int(k),
            "hot_bytes": int(k) * n * (2 if dt == jnp.bfloat16 else 4),
            "device_bytes": int(stats.get("bytes_limit", 0))}


def estimator(cell: dict, mesh, sweeps: int, ledger_dir: str,
              feature_dtype: str):
    """The object the window drives, built as ``cli/game_train.main`` builds
    it, each coordinate with the optimisation block of its own
    (``settings["optimizers"]``), after the guard: where the solve's vectors
    and the hot block this program would allocate pass the device, exit with
    a plain message before the host or the device holds any of it."""
    from photon_ml_tpu.api.configs import (CoordinateConfiguration,
                                           FixedEffectDataConfiguration,
                                           RandomEffectDataConfiguration)
    from photon_ml_tpu.api.estimator import GameEstimator

    opts = {cid: _optimization(o)
            for cid, o in cell["settings"]["optimizers"].items()}
    coords = {}
    for cid, c in cell["mix"]["coordinates"].items():
        if c["type"] == "fixed":
            plan = resident_plan(mesh, feature_dtype, opts[cid])
            need = plan["solver_bytes"] + plan["hot_bytes"]
            if plan["device_bytes"] and need > plan["device_bytes"]:
                raise SystemExit(
                    f"game_kdd12: at {_MADE['columns']} columns this "
                    f"program's fixed-effect solve holds "
                    f"{plan['solver_bytes']} bytes of coefficient vectors"
                    f"{'' if plan['reckons'] else ' which its planner does not reckon'}"
                    f", and its resident layout would add a hot block of "
                    f"{plan['num_hot']} columns x {_MADE['rows']} rows = "
                    f"{plan['hot_bytes']} bytes, on a device of "
                    f"{plan['device_bytes']} bytes: it cannot hold this "
                    f"configuration (the block's budget has to reckon the "
                    f"solver)")
            data = FixedEffectDataConfiguration(
                c["shard"], feature_dtype=feature_dtype)
        else:
            data = RandomEffectDataConfiguration(
                random_effect_type=c["entity"],
                feature_shard_id="re_" + c["entity"],
                active_data_upper_bound=cell["settings"].get("max_samples"),
                feature_dtype=feature_dtype)
        coords[cid] = CoordinateConfiguration(data=data,
                                              optimization=opts[cid])
    if cell["configuration"]["task"] != "poisson":
        raise SystemExit("game_kdd12 knows the task poisson only")
    return GameEstimator(
        task=TASK, coordinates=coords,
        update_sequence=list(cell["mix"]["update_sequence"]), mesh=mesh,
        descent_iterations=sweeps, validation_evaluators=None,
        compute_variances_at_end=False, ledger_dir=ledger_dir)


# -- the work the traced sweep needs ------------------------------------------

def _traced_fixed(ctx):
    """The ``opt_iter`` rows of the fixed effect's update in the traced
    sweep, in order; None without them."""
    rows = [r for r in ctx["ledger_rows"]
            if r.get("kind") == "opt_iter" and r.get("coordinate") == "fixed"
            and r.get("outer_iteration") == ctx["traced_sweep"]]
    return sorted(rows, key=lambda r: r["iteration"]) or None


def _crossings(rows):
    """Passes over the shard's non-zeros the solve made, from the program's
    ``crossings`` an iteration; None where it writes none."""
    if rows is None or any(r.get("crossings") is None for r in rows):
        return None
    return sum(int(r["crossings"]) for r in rows)


def bytes_needed(kernel: str, ctx):
    """Bytes the fixed effect's solve of the traced sweep has to move,
    whatever implements it. ``fe_pass`` / ``fe_hot`` / ``fe_cold``: a pass
    reads a 4-byte index and a 4-byte value per non-zero, over all of a
    row's non-zeros or those the program's layout gives that part, times
    the passes the program counted (``crossings``: two for the first
    evaluation, then a trial each and one for the accepted point's
    gradient). ``fe_vec``: the solver's own vectors of ``num_features``
    float32: an iteration with p pairs of history reads each of the 2p
    vectors twice (the two loops) and writes a new s and y; the
    pseudo-gradient reads w and g and writes one; the orthant cut reads the
    direction and the pseudo-gradient and writes one; every trial reads w
    and the direction and writes the projected candidate."""
    rows = _traced_fixed(ctx)
    if rows is None:
        return None
    conf = ctx["cell"]["configuration"]
    if kernel == "fe_vec":
        if any(r.get("trials") is None for r in rows):
            return None
        m = int(ctx["cell"]["settings"]["optimizers"]["fixed"].get(
            "history_length", 10))
        vectors = sum(4 * min(int(r["iteration"]) - 1, m) + 2 + 3 + 3
                      + 3 * int(r["trials"]) for r in rows[1:])
        return vectors * int(conf["num_features"]) * 4
    crossed = _crossings(rows)
    if crossed is None:
        return None
    if kernel == "fe_pass":
        entries = int(conf["num_rows"]) * len(conf["fields"])
    elif kernel in ("fe_hot", "fe_cold"):
        lay = game_criteo.layout(ctx)
        if lay is None:
            return None
        entries = int(lay[kernel[3:] + "_entries"])
    else:
        return None
    return crossed * entries * (4 + 4)


def sweep_flops(ctx):
    """FLOPs the traced sweep needs: a multiply and an add per non-zero in
    each pass of the fixed effect the program counted and in one pass to
    rescore; the table's solves at the iteration cap over the rows, as
    ``work.sweep_flops`` counts a dense table."""
    crossed = _crossings(_traced_fixed(ctx))
    if crossed is None:
        return None
    conf = ctx["cell"]["configuration"]
    n, fields = int(conf["num_rows"]), len(conf["fields"])
    cap = int(ctx["cell"]["settings"]["optimizers"]["per-advertiser"][
        "max_iterations"])
    width = int(conf["entity"]["features"])
    return ((crossed + 1) * 2 * n * fields
            + (cap + 1) * 4 * n * width + 2 * n * width)


# -- the faults a cell of this schema can have --------------------------------

def _dataset_fault(spoil):
    """``dataset`` with ``spoil(ds)`` applied to what it returns."""
    sound = dataset

    def broken(data):
        ds = sound(data)
        spoil(ds)
        return ds
    return game_dense._patched(sys.modules[__name__], "dataset", broken)


def _half_batch():
    """Half of the rows left out of training (weight 0)."""
    def spoil(ds):
        ds.weights = np.where(np.arange(ds.num_rows) % 2, 0.0, 1.0
                              ).astype(np.float32)
    return _dataset_fault(spoil)


def _offsets_dropped():
    """The data's offsets zeroed under the timed path: clicks fitted as if
    every row had one impression."""
    def spoil(ds):
        ds.offsets = np.zeros_like(ds.offsets)
    return _dataset_fault(spoil)


def _l1_as_l2():
    """The fixed effect run under L2 of the same weight (and so under
    L-BFGS): nothing is pruned."""
    sound = _optimization

    def broken(o):
        return sound(dict(o, regularization="L2", optimizer="LBFGS")
                     if o["regularization"] == "L1" else o)
    return game_dense._patched(sys.modules[__name__], "_optimization",
                               broken)


faults = {"half-batch": _half_batch,
          "cold-dropped": game_criteo.faults["cold-dropped"],
          "offsets-dropped": _offsets_dropped, "l1-as-l2": _l1_as_l2}


# -- run.py --selfcheck -------------------------------------------------------

def check_generator():
    """The fields fill the width exactly, a row has one column in each
    field's own range, position never passes depth, and the counts are
    Poisson in the impressions."""
    conf = {"num_rows": 20000, "num_features": 1000 + 50 + 3 + 3 + 18,
            "fields": [{"name": "QueryID", "cardinality": 1000},
                       {"name": "AdvertiserID", "cardinality": 50},
                       {"name": "Depth", "cardinality": 3},
                       {"name": "Position", "cardinality": 3},
                       {"name": "Profile", "cardinality": 18}],
            "entity": {"name": "AdvertiserID", "field_index": 1,
                       "count": 50, "features": 8},
            "assumed_generator": {
                "zipf_exponent": 1.1, "depth_shares": [0.3, 0.4, 0.3],
                "impression_log_sd": 1.2, "planted_fixed_share": 0.1,
                "planted_fixed_sd": 1.0, "planted_slope_sd": 0.1,
                "planted_intercept_sd": 0.3, "base_click_rate": 0.04}}
    data = make(5, conf)
    starts = field_offsets(conf)
    for f in range(5):
        col = data.indices[:, f]
        assert col.min() >= starts[f] and col.max() < starts[f + 1], f
    depth = data.indices[:, 2] - starts[2]
    position = data.indices[:, 3] - starts[3]
    assert (position <= depth).all() and depth.max() == 2
    assert np.array_equal(data.table[:, 6], (
        (depth - position) / (depth + 1.0)).astype(np.float32))
    assert (data.table[:, :3].sum(1) == 1).all() and (data.table[:, 7] == 1
                                                      ).all()
    assert np.array_equal(data.entity_ids, data.indices[:, 1] - starts[1])
    imp = np.exp(data.offsets.astype(np.float64))
    assert 0.6 < np.mean(imp < 1.5) < 0.8 and imp.max() > 20
    rate = data.response.sum() / imp.sum()
    assert 0.02 < rate < 0.08, rate
    try:
        field_offsets(dict(conf, num_features=conf["num_features"] + 1))
    except SystemExit:
        pass
    else:
        raise AssertionError("a width the fields do not fill was taken")


def check_work():
    ctx = {"cell": {"configuration": {
        "num_rows": 1000, "num_features": 5000,
        "fields": [{}] * 11, "entity": {"features": 8}},
        "settings": {"optimizers": {
            "fixed": {"max_iterations": 25},
            "per-advertiser": {"max_iterations": 25}}}},
        "traced_sweep": 3,
        "ledger_rows": [
            {"kind": "fe_layout", "hot_entries": 6000, "cold_entries": 5000},
            {"kind": "opt_iter", "coordinate": "fixed", "outer_iteration": 3,
             "iteration": 0, "trials": 0, "crossings": 2},
            {"kind": "opt_iter", "coordinate": "fixed", "outer_iteration": 3,
             "iteration": 1, "trials": 1, "crossings": 2},
            {"kind": "opt_iter", "coordinate": "fixed", "outer_iteration": 3,
             "iteration": 2, "trials": 3, "crossings": 4}]}
    assert bytes_needed("fe_pass", ctx) == 8 * 11000 * 8
    assert bytes_needed("fe_hot", ctx) == 8 * 6000 * 8
    assert bytes_needed("fe_cold", ctx) == 8 * 5000 * 8
    # iteration 1: no pair yet, 8 + 3 vectors; iteration 2: one pair, 4 more
    # reads, 8 + 9 vectors
    assert bytes_needed("fe_vec", ctx) == (11 + 4 + 17) * 5000 * 4
    assert bytes_needed("other", ctx) is None
    assert sweep_flops(ctx) == (9 * 2 * 11000 + 26 * 4 * 8000 + 2 * 8000)
    for r in ctx["ledger_rows"][1:]:  # a program that counts no crossings
        del r["crossings"]
    assert bytes_needed("fe_pass", ctx) is None and sweep_flops(ctx) is None
    ctx["ledger_rows"] = ctx["ledger_rows"][:1]
    assert bytes_needed("fe_vec", ctx) is None


selfchecks = (check_generator, check_work)
