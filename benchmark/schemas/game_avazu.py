"""The schema ``game_avazu``: GLMix click prediction on Avazu's mobile-ad rows.
Every row has a click label and one value in each of 22 fields. A row's
traffic is a site's or an app's: on a site row the three app fields hold
their null value (id 0) and conversely, and the **publisher**, the entity, is
the site of a site row and the app of an app row. The fixed effect reads the
shard ``global``: all 22 fields, each hashed into ``hashed_features`` columns
(``game_criteo.hashed``) with the value 1/sqrt(22), in ELL form. One random
effect keyed on the publisher reads the *sparse* shard ``re_ad``: the 13 ad
and context fields one-hot, laid end to end with no hashing, value
1/sqrt(13), and the intercept (value 1) in the last column; the program
solves each publisher in the subspace of the columns it has seen.

The generator and the work counts are below; the plain reference and the
comparison are ``benchmark/avazu_reference.py``. None of them imports the
program; only ``dataset``, ``estimator`` (with its guard) and the faults touch
it. The hash and the Zipf draw are ``game_criteo``'s, the estimator's
optimisation blocks ``game_kdd12``'s, the leaves ``game_dense``'s.
"""

from __future__ import annotations

import dataclasses
import inspect
import sys

import numpy as np

import avazu_reference
import game_criteo
import game_dense
import game_kdd12

model_arrays = game_dense.model_arrays

TASK = "LOGISTIC_REGRESSION"
SIDES = ("site", "app")
# The share of a device's ``bytes_limit`` a job may reckon to hold: the size
# rule of the configuration's file, and the guard's.
DEVICE_SHARE = 0.85
_MADE = {}  # of the data ``make`` made last, for the guard in ``estimator``,
#             which gets no data: the hashed columns' counts, the rows, each
#             publisher's rows and active table columns


@dataclasses.dataclass
class Data:
    indices: np.ndarray  # (n, 22) int32 hashed columns of ``global``
    values: np.ndarray  # (n, 22) float32
    num_features: int
    entity: str
    entity_ids: np.ndarray  # (n,) int32 publisher
    num_entities: int
    table_indices: np.ndarray  # (n, 14) int32 columns of ``re_ad``
    table_values: np.ndarray  # (n, 14) float32, the last slot the intercept
    table_features: int
    response: np.ndarray  # (n,) float32


# -- the generator ------------------------------------------------------------

def table_offsets(conf: dict) -> tuple[list, np.ndarray]:
    """The fields of ``re_ad`` in the order they are laid, and the first
    column of each with the intercept's column last: they fill the table's
    ``features`` exactly."""
    fields = [f for f in conf["fields"] if f.get("table")]
    starts = np.concatenate([[0], np.cumsum(
        [int(f["cardinality"]) for f in fields])]).astype(np.int64)
    if int(starts[-1]) + 1 != int(conf["entity"]["features"]):
        raise SystemExit(f"the table's fields hold {int(starts[-1])} columns "
                         f"and an intercept; entity.features is "
                         f"{conf['entity']['features']}")
    return fields, starts


def publishers(conf: dict) -> tuple[int, int]:
    """(sites, apps) that can be a publisher: every id of the side's id
    field but its null value."""
    card = {f["name"]: int(f["cardinality"]) for f in conf["fields"]}
    sites, apps = card["site_id"] - 1, card["app_id"] - 1
    if sites + apps != int(conf["entity"]["count"]):
        raise SystemExit(f"site_id and app_id hold {sites} + {apps} "
                         f"publishers; entity.count is "
                         f"{conf['entity']['count']}")
    return sites, apps


def pool_ranks(rng, sizes: np.ndarray, exponent: float) -> np.ndarray:
    """One Zipf rank a row in [0, sizes[i]): ``game_criteo.zipf_ranks`` with
    a cardinality of the row's own."""
    top = sizes.astype(np.float64) + 1.0
    p = 1.0 - exponent
    x = (1.0 + rng.random(sizes.shape[0]) * (top ** p - 1.0)) ** (1.0 / p)
    return np.minimum(x.astype(np.int64) - 1, sizes - 1)


def pool_sizes(rows: np.ndarray, conf: dict) -> np.ndarray:
    """How many C14 values a publisher of ``rows`` rows shows: rows **
    ``pool_exponent``, rounded up, at least 2, at most the field's."""
    gen = conf["assumed_generator"]
    card = {f["name"]: int(f["cardinality"]) for f in conf["fields"]}
    return np.clip(np.ceil(rows.astype(np.float64)
                           ** float(gen["pool_exponent"])).astype(np.int64),
                   2, card[gen["pooled_field"]])


def make(seed: int, conf: dict) -> Data:
    rng = np.random.default_rng(int(seed))
    n, d = int(conf["num_rows"]), int(conf["hashed_features"])
    gen, ent = conf["assumed_generator"], conf["entity"]
    expo = float(gen["zipf_exponent"])
    card = {f["name"]: int(f["cardinality"]) for f in conf["fields"]}
    sites, apps = publishers(conf)
    E = int(ent["count"])
    site_row = rng.random(n) < float(conf["site_row_share"])
    # the publisher: a Zipf draw over its side's ids
    pub = np.where(site_row, game_criteo.zipf_ranks(rng, n, sites, expo),
                   sites + game_criteo.zipf_ranks(rng, n, apps, expo)
                   ).astype(np.int32)
    rows_of = np.bincount(pub, minlength=E)
    value = {}
    for f in conf["fields"]:
        name, side = f["name"], f.get("side")
        if name == gen["pooled_field"]:
            # a publisher's ads are its own: a pool of values starting at a
            # seeded offset, the rank within the pool a Zipf draw
            offset = rng.integers(0, card[name], size=E)
            rank = pool_ranks(rng, pool_sizes(rows_of, conf)[pub], expo)
            value[name] = (offset[pub] + rank) % card[name]
        elif name == gen["derived_field"]:
            # in the data each C14 value has one C17
            value[name] = (value[gen["pooled_field"]] * card[name]
                           // card[gen["pooled_field"]])
        elif name in ("site_id", "app_id"):
            mine = site_row if name == "site_id" else ~site_row
            first = 0 if name == "site_id" else sites
            value[name] = np.where(mine, 1 + pub - first, 0)
        elif side in SIDES:
            # a side's other fields: null (0) on the other side's rows
            mine = site_row if side == "site" else ~site_row
            value[name] = np.where(mine, 1 + game_criteo.zipf_ranks(
                rng, n, card[name] - 1, expo), 0)
        else:
            value[name] = game_criteo.zipf_ranks(rng, n, card[name], expo)
    fields = len(conf["fields"])
    indices = np.empty((n, fields), np.int32)
    for k, f in enumerate(conf["fields"]):
        indices[:, k] = game_criteo.hashed(k, value[f["name"]], d)
    values = np.full((n, fields), 1.0 / np.sqrt(fields), np.float32)
    tfields, starts = table_offsets(conf)
    D = int(ent["features"])
    tidx = np.empty((n, len(tfields) + 1), np.int32)
    for k, f in enumerate(tfields):
        tidx[:, k] = starts[k] + value[f["name"]]
    tidx[:, -1] = D - 1
    tval = np.full(tidx.shape, 1.0 / np.sqrt(len(tfields)), np.float32)
    tval[:, -1] = 1.0
    # planted effects
    w = float(gen["planted_fixed_sd"]) * rng.standard_normal(d)
    W = rng.standard_normal((E, D), dtype=np.float32)
    W *= np.float32(gen["planted_slope_sd"])
    W[:, -1] = float(gen["planted_intercept_sd"]) * rng.standard_normal(E)
    logits = (float(gen["planted_bias"])
              + w[indices].sum(axis=1) / np.sqrt(fields)
              + np.einsum("nk,nk->n", W[pub[:, None], tidx], tval))
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-logits))).astype(np.float32)
    seen = np.zeros(E * D, bool)
    seen[(pub.astype(np.int64) * D)[:, None] + tidx] = True
    _MADE.update(counts=np.bincount(indices.reshape(-1), minlength=d), rows=n,
                 entity_rows=rows_of,
                 entity_active=seen.reshape(E, D).sum(axis=1),
                 table_features=D)
    return Data(indices, values, d, ent["name"], pub, E, tidx, tval, D, y)


def shrink(conf: dict, rows: int) -> dict:
    """The rehearsal's configuration: fewer rows, and as many ids a field
    as those rows can fill (a twentieth of them at most, a fortieth for the
    two publisher fields); the fields, their hashing and the table's layout
    stay, the table's columns being what its fields then hold."""
    few = max(8, rows // 20)
    fields = [dict(f, cardinality=min(
        int(f["cardinality"]),
        few // 2 if f["name"] in ("site_id", "app_id") else few))
        for f in conf["fields"]]
    card = {f["name"]: f["cardinality"] for f in fields}
    return dict(conf, num_rows=rows, fields=fields, entity=dict(
        conf["entity"], count=card["site_id"] + card["app_id"] - 2,
        features=1 + sum(f["cardinality"] for f in fields if f.get("table"))),
        rehearsal_rows_of=int(conf["num_rows"]))


def _settings(cell: dict) -> dict:
    """The cell's settings; in a rehearsal ``max_samples`` shrunk as the
    rows are, so that it binds on the heaviest publishers there too."""
    settings, conf = cell["settings"], cell["configuration"]
    of = conf.get("rehearsal_rows_of")
    if of is None or settings.get("max_samples") is None:
        return settings
    return dict(settings, max_samples=max(8, int(
        settings["max_samples"]) * int(conf["num_rows"]) // int(of)))


def check(data, cell: dict, served: dict, ledger_rows, sweeps: int) -> dict:
    return avazu_reference.check(data, dict(cell, settings=_settings(cell)),
                                 served, ledger_rows, sweeps)


def dataset(data: Data):
    from photon_ml_tpu.data.game_data import GameDataset, SparseShard

    n = data.response.shape[0]
    shard = "re_" + data.entity
    return GameDataset(
        response=data.response, offsets=np.zeros(n, np.float32),
        weights=np.ones(n, np.float32),
        feature_shards={
            "global": SparseShard(data.indices, data.values,
                                  data.num_features),
            shard: SparseShard(data.table_indices, data.table_values,
                               data.table_features)},
        entity_ids={data.entity: data.entity_ids},
        num_entities={data.entity: data.num_entities},
        intercept_index={shard: data.table_features - 1})


# -- the estimator, behind its guard ------------------------------------------

def table_plan(max_samples, feature_dtype: str) -> dict:
    """What the program's projected staging would hold on the device for
    the table of the data made last, by the program's own rules and before
    anything is allocated: a class a power-of-two row capacity
    (``buckets``), its lanes a multiple of 8, its width the power of two
    over the most active columns any of its publishers has
    (``projector.projection_width``); a block is lanes x capacity x width
    cells, and labels, weights and row ids beside it. A publisher's active
    columns are counted over all its rows, so a capped one's are an upper
    bound."""
    from photon_ml_tpu.game import buckets as bkt
    from photon_ml_tpu.game import projector as prj

    rows, active = _MADE["entity_rows"], _MADE["entity_active"]
    live = np.flatnonzero(rows)
    kept = rows[live] if max_samples is None else np.minimum(
        rows[live], int(max_samples))
    caps = np.array([max(8, bkt._next_pow2(int(c))) for c in kept])
    cell = 2 if feature_dtype == "bfloat16" else 4
    classes, staged, useful = [], 0, 0
    for cap in np.unique(caps):
        sel = caps == cap
        lanes = -(-int(sel.sum()) // 8) * 8
        width = prj.projection_width(active[live][sel],
                                     int(_MADE["table_features"]))
        staged += lanes * int(cap) * (width * cell + 12) + lanes * (
            4 + 4 * width)
        useful += int((kept[sel] * active[live][sel]).sum()) * cell
        classes.append((int(cap), lanes, int(width)))
    return {"classes": classes, "staged_bytes": staged,
            "useful_bytes": useful,
            "capped": int((rows[live] > kept).sum())}


def resident_plan(mesh, feature_dtype: str, config, table_bytes: int) -> dict:
    """What the program at hand would give the fixed effect's hot block on
    one device of ``mesh`` for the data made last, from its own planner: a
    program whose budget takes the bytes the job's other coordinates will
    stage after it (``deferred_bytes``) is told the table's."""
    import jax.numpy as jnp

    from photon_ml_tpu.game.coordinates import sparse_fixed
    from photon_ml_tpu.ops import hybrid_sparse as hs

    counts, n = _MADE["counts"], _MADE["rows"]
    dt = jnp.bfloat16 if feature_dtype == "bfloat16" else jnp.float32
    solver = sparse_fixed.solver_state_bytes(counts.shape[0], config)
    reckons = "deferred_bytes" in inspect.signature(
        sparse_fixed.hot_block_budget).parameters
    budget = (sparse_fixed.hot_block_budget(mesh, solver,
                                            deferred_bytes=table_bytes)
              if reckons else sparse_fixed.hot_block_budget(mesh, solver))
    k = hs.plan_resident_hot(counts, n, dt, hot_block_bytes=budget)
    stats = mesh.devices.flat[0].memory_stats() or {}
    return {"reckons": reckons, "solver_bytes": int(solver),
            "num_hot": int(k),
            "hot_bytes": int(k) * n * (2 if dt == jnp.bfloat16 else 4),
            "in_use": int(stats.get("bytes_in_use", 0)),
            "device_bytes": int(stats.get("bytes_limit", 0))}


def estimator(cell: dict, mesh, sweeps: int, ledger_dir: str,
              feature_dtype: str):
    """The object the window drives, built as ``cli/game_train.main`` builds
    it (``game_kdd12``'s, each coordinate with the optimisation block of its
    own), after the guard: where the table's staged blocks and the hot block
    this program would allocate beside them pass ``DEVICE_SHARE`` of the
    device, exit with one plain line before the host or the device holds
    any of it."""
    from photon_ml_tpu.api.configs import (CoordinateConfiguration,
                                           FixedEffectDataConfiguration,
                                           RandomEffectDataConfiguration)
    from photon_ml_tpu.api.estimator import GameEstimator

    settings = _settings(cell)
    opts = {cid: game_kdd12._optimization(o)
            for cid, o in settings["optimizers"].items()}
    table = table_plan(settings.get("max_samples"), feature_dtype)
    coords = {}
    for cid, c in cell["mix"]["coordinates"].items():
        if c["type"] == "fixed":
            plan = resident_plan(mesh, feature_dtype, opts[cid],
                                 table["staged_bytes"])
            need = (plan["in_use"] + plan["solver_bytes"] + plan["hot_bytes"]
                    + table["staged_bytes"])
            if plan["device_bytes"] and need > DEVICE_SHARE * plan[
                    "device_bytes"]:
                raise SystemExit(
                    f"game_avazu: this program would stage "
                    f"{table['staged_bytes']} bytes of projected table "
                    f"blocks ({len(table['classes'])} classes up to "
                    f"{table['classes'][-1]}) after a hot block of "
                    f"{plan['num_hot']} columns x {_MADE['rows']} rows = "
                    f"{plan['hot_bytes']} bytes whose budget "
                    f"{'reckons' if plan['reckons'] else 'does not reckon'} "
                    f"them: {need} bytes on a device of "
                    f"{plan['device_bytes']}, over {DEVICE_SHARE:.0%} of "
                    f"it: it cannot hold this configuration")
            data = FixedEffectDataConfiguration(
                c["shard"], feature_dtype=feature_dtype)
        else:
            data = RandomEffectDataConfiguration(
                random_effect_type=c["entity"],
                feature_shard_id="re_" + c["entity"],
                active_data_upper_bound=settings.get("max_samples"),
                feature_dtype=feature_dtype)
        coords[cid] = CoordinateConfiguration(data=data,
                                              optimization=opts[cid])
    if cell["configuration"]["task"] != "logistic":
        raise SystemExit("game_avazu knows the task logistic only")
    return GameEstimator(
        task=TASK, coordinates=coords,
        update_sequence=list(cell["mix"]["update_sequence"]), mesh=mesh,
        descent_iterations=sweeps, validation_evaluators=None,
        compute_variances_at_end=False, ledger_dir=ledger_dir)


# -- the work the traced sweep needs ------------------------------------------

def _table(ctx) -> str:
    return [cid for cid, c in ctx["cell"]["mix"]["coordinates"].items()
            if c["type"] == "random"][0]


def traced_waves(ctx):
    """The ``re_fit_wave`` rows of the table's update in the traced sweep
    that count their columns; None where the program writes none."""
    rows = [r for r in ctx["ledger_rows"]
            if r.get("kind") == "re_fit_wave"
            and r.get("coordinate") == _table(ctx)
            and r.get("outer_iteration") == ctx["traced_sweep"]
            and r.get("cols_useful") is not None and r.get("entities_fit")]
    return rows or None


def bytes_needed(kernel: str, ctx):
    """``fe_pass`` / ``fe_hot`` / ``fe_cold``: as ``game_criteo`` counts
    them, over 22 fields. ``re_fit``: bytes the table's solves of the traced
    sweep have to read whatever implements them: a lane's evaluation makes
    two passes (margins, gradient) over its rows x active columns at 4 B a
    cell; a wave's lanes take ``evals_sum / entities_fit`` evaluations each
    on average, over ``cols_useful`` cells in all."""
    conf = ctx["cell"]["configuration"]
    if kernel == "re_fit":
        waves = traced_waves(ctx)
        if waves is None:
            return None
        return int(sum(r["evals_sum"] / r["entities_fit"] * 2
                       * r["cols_useful"] * 4 for r in waves))
    solved = game_criteo._traced_fixed(ctx)
    if solved is None:
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
    return solved[1] * 2 * entries * (4 + 4)


def sweep_flops(ctx):
    """FLOPs the traced sweep needs: per evaluation of the fixed effect a
    multiply and an add per non-zero in each of two passes, and one pass to
    rescore; the table's solves as the program counted them where it counts
    its columns (evaluations x 2 passes x 2 x useful cells), else at the
    iteration cap over every row's 14 non-zeros; its rescoring pass."""
    solved = game_criteo._traced_fixed(ctx)
    if solved is None:
        return None
    conf = ctx["cell"]["configuration"]
    n, fields = int(conf["num_rows"]), len(conf["fields"])
    slots = 1 + sum(1 for f in conf["fields"] if f.get("table"))
    waves = traced_waves(ctx)
    if waves is None:
        cap = int(ctx["cell"]["settings"]["optimizers"][_table(ctx)][
            "max_iterations"])
        table = (cap + 1) * 4 * n * slots
    else:
        table = sum(r["evals_sum"] / r["entities_fit"] * 4 * r["cols_useful"]
                    for r in waves)
    return int(solved[1] * 4 * n * fields + 2 * n * fields + table
               + 2 * n * slots)


# -- the faults a cell of this schema can have --------------------------------

def _half_batch():
    """Half of the rows left out of training (weight 0)."""
    def spoil(ds):
        ds.weights = np.where(np.arange(ds.num_rows) % 2, 0.0, 1.0
                              ).astype(np.float32)
    sound = dataset

    def broken(data):
        ds = sound(data)
        spoil(ds)
        return ds
    return game_dense._patched(sys.modules[__name__], "dataset", broken)


def _projection_truncated():
    """Every lane's column map cut to half of its class's width before the
    features are laid out: the columns past it are never trained."""
    from photon_ml_tpu.game import staging as stg

    sound = stg._phase_b

    def broken(task, cols, d_active, ctx=None):
        cols = np.array(cols, copy=True)
        cols[:, int(d_active) // 2:] = -1
        return sound(task, cols, d_active, ctx)
    return game_dense._patched(stg, "_phase_b", broken)


def _cap_ignored():
    """The reference's subset rule switched off: it trains every publisher
    on all of its rows where the program keeps ``max_samples`` of them."""
    def all_rows(ids, num_entities, cap, seed=0):
        return np.ones(ids.shape[0], np.float32)
    return game_dense._patched(avazu_reference, "capped_training_rows",
                               all_rows)


faults = {"half-batch": _half_batch,
          "cold-dropped": game_criteo.faults["cold-dropped"],
          "projection-truncated": _projection_truncated,
          "cap-ignored": _cap_ignored}


# -- run.py --selfcheck -------------------------------------------------------

def _small_conf() -> dict:
    fields = [("hour", 24, None, True), ("C1", 7, None, True),
              ("site_id", 41, "site", False), ("site_domain", 30, "site",
                                               False),
              ("app_id", 61, "app", False), ("app_category", 9, "app", False),
              ("device_ip", 5000, "device", False),
              ("C14", 300, None, True), ("C17", 50, None, True)]
    return {"num_rows": 30000, "hashed_features": 1 << 16,
            "site_row_share": 0.64,
            "fields": [dict(name=n, cardinality=c, side=s, table=t)
                       for n, c, s, t in fields],
            "entity": {"name": "publisher", "count": 100,
                       "features": 24 + 7 + 300 + 50 + 1},
            "assumed_generator": {
                "zipf_exponent": 1.1, "pooled_field": "C14",
                "derived_field": "C17", "pool_exponent": 0.5,
                "planted_fixed_sd": 1.0, "planted_slope_sd": 0.3,
                "planted_intercept_sd": 0.5, "planted_bias": -1.6}}


def check_generator():
    """The site/app nulls, the publisher of either side, the pool sizes, C17
    as a function of C14, and the table's layout."""
    conf = _small_conf()
    data = make(11, conf)
    d = conf["hashed_features"]
    n = conf["num_rows"]
    names = [f["name"] for f in conf["fields"]]
    pub = data.entity_ids
    site = pub < 40
    assert 0.6 < site.mean() < 0.68, site.mean()
    # a site row's app fields hash their null value, and conversely
    for name, mine in (("app_id", ~site), ("app_category", ~site),
                       ("site_id", site), ("site_domain", site)):
        k = names.index(name)
        null = game_criteo.hashed(k, np.zeros(1, np.int64), d)[0]
        assert (data.indices[~mine, k] == null).all(), name
        assert (data.indices[mine, k] != null).mean() > 0.99, name
    # the publisher is the side's id: site s is id 1 + s of site_id
    k = names.index("site_id")
    assert np.array_equal(data.indices[site, k], game_criteo.hashed(
        k, 1 + pub[site].astype(np.int64), d))
    k = names.index("app_id")
    assert np.array_equal(data.indices[~site, k], game_criteo.hashed(
        k, 1 + pub[~site].astype(np.int64) - 40, d))
    # the table: hour, C1, C14, C17 laid end to end, the intercept last
    tfields, starts = table_offsets(conf)
    assert [f["name"] for f in tfields] == ["hour", "C1", "C14", "C17"]
    assert list(starts) == [0, 24, 31, 331, 381]
    t = data.table_indices
    assert (t[:, -1] == 381).all() and (data.table_values[:, -1] == 1).all()
    assert np.allclose(data.table_values[:, :-1], 0.5)
    for j in range(4):
        assert t[:, j].min() >= starts[j] and t[:, j].max() < starts[j + 1]
    c14, c17 = t[:, 2] - 31, t[:, 3] - 331
    assert np.array_equal(c17, c14 * 50 // 300)
    # a publisher shows at most ceil(sqrt(rows)) values of C14, in a run of
    # the ring from its offset
    rows = np.bincount(pub, minlength=100)
    assert np.array_equal(pool_sizes(rows, conf), np.clip(
        np.ceil(np.sqrt(rows)).astype(np.int64), 2, 300))
    for e in (int(np.argmax(rows)), int(np.argmax(rows[40:])) + 40):
        seen = np.unique(c14[pub == e])
        assert seen.size <= pool_sizes(rows[e:e + 1], conf)[0], e
        assert seen.size > 0.5 * np.sqrt(rows[e]), (e, seen.size)
    assert np.array_equal(_MADE["entity_rows"], rows)
    head = int(np.argmax(rows))
    assert _MADE["entity_active"][head] == np.unique(t[pub == head]).size
    assert 0.1 < data.response.mean() < 0.35, data.response.mean()
    assert n == data.response.shape[0]
    try:
        table_offsets(dict(conf, entity=dict(conf["entity"], features=400)))
    except SystemExit:
        pass
    else:
        raise AssertionError("a width the table's fields do not fill passed")


def check_work():
    conf = dict(_small_conf(), num_rows=1000)
    ctx = {"cell": {"configuration": conf,
                    "mix": {"coordinates": {
                        "fixed": {"type": "fixed"},
                        "per-publisher": {"type": "random"}}},
                    "settings": {"optimizers": {
                        "per-publisher": {"max_iterations": 25}}}},
           "traced_sweep": 3,
           "ledger_rows": [
               {"kind": "fe_layout", "hot_entries": 6000,
                "cold_entries": 3000},
               {"kind": "opt_iter", "coordinate": "fixed",
                "outer_iteration": 3, "iteration": 0},
               {"kind": "opt_iter", "coordinate": "fixed",
                "outer_iteration": 3, "iteration": 4, "evaluations": 5},
               # two lanes of 10 and 6 rows with 5 and 3 active columns took
               # 12 evaluations between them: 6 each on average over 68 cells
               {"kind": "re_fit_wave", "coordinate": "per-publisher",
                "outer_iteration": 3, "entities_fit": 2, "evals_sum": 12,
                "cols_useful": 10 * 5 + 6 * 3, "cols_padded": 8 * 16 * 8},
               {"kind": "re_fit_wave", "coordinate": "per-publisher",
                "outer_iteration": 3, "entities_fit": 1, "evals_sum": 4,
                "cols_useful": 100, "cols_padded": 8 * 64 * 8},
               {"kind": "re_fit_wave", "coordinate": "per-publisher",
                "outer_iteration": 2, "entities_fit": 1, "evals_sum": 4,
                "cols_useful": 100, "cols_padded": 8 * 64 * 8}]}
    assert bytes_needed("re_fit", ctx) == (6 * 68 + 4 * 100) * 2 * 4
    assert bytes_needed("fe_pass", ctx) == 5 * 2 * 9000 * 8
    assert bytes_needed("fe_hot", ctx) == 5 * 2 * 6000 * 8
    assert bytes_needed("fe_cold", ctx) == 5 * 2 * 3000 * 8
    assert bytes_needed("other", ctx) is None
    assert sweep_flops(ctx) == (5 * 4 * 9000 + 2 * 9000
                                + (6 * 68 + 4 * 100) * 4 + 2 * 1000 * 5)
    # a program that does not count its columns: the table at its cap
    for r in ctx["ledger_rows"][3:]:
        del r["cols_useful"]
    assert bytes_needed("re_fit", ctx) is None
    assert sweep_flops(ctx) == (5 * 4 * 9000 + 2 * 9000
                                + 26 * 4 * 1000 * 5 + 2 * 1000 * 5)


selfchecks = (check_generator, check_work)
