"""The schema ``game_music``: GLMix rating regression at the shape of KDD Cup
2011 track 1, the Yahoo! Music data set (Dror, Koenigstein, Koren & Weimer,
JMLR W&CP 18, 2012): a rating on a 0-100 scale, one fixed effect over the
dense shard ``global`` and three random effects, per user, per item and per
artist of the item, each over a dense shard ``re_<entity>`` with the
intercept in its last column; the squared loss, every coordinate solved by
TRON.

The artist is a function of the item (the data set's item taxonomy: tracks
and albums belong to an artist, an artist item is its own artist, a genre
item has none and maps to one "no artist" id), so the artist table's rows
are the union of its items' rows. Which row belongs to which user and item,
and which artist a track or album belongs to, is drawn from the seed; how
many rows each user and each item has is not (``activity_counts``), so every
seed has the same user and item buckets.

The generator, the work counts and the faults are below. The plain
reference is ``benchmark/music_reference.py``: ``reference.py``'s block
descent and comparison, each block solved once from its normal equations,
given the one L2 weight every block of the cell states. The data are
``gen.Data``, so ``game_dense``'s dataset and leaves serve.
"""

from __future__ import annotations

import os
import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np
from scipy.special import ndtri

import game_criteo
import game_dense
import game_kdd12
import gen
import music_reference

model_arrays = game_dense.model_arrays
CHUNKS = gen.CHUNKS
TASK = "LINEAR_REGRESSION"


# -- the generator ------------------------------------------------------------

def activity_counts(n: int, entities: int, activity: dict) -> np.ndarray:
    """Rows of each entity, ascending: ``n`` apportioned along the source's
    activity. The curve (the benchmark's: the source publishes its means,
    not its quartiles) is ``floor`` plus a log-normal of log-sd ``log_sd``
    taken at u = (k + 1/2) / entities and scaled so that it sums to the
    published total ``rows``, i.e. through the published mean. Scaled by
    ``n`` over that total and rounded by largest remainder (ties to the
    lower rank), every entity keeping a row, as ``gen.activity_counts``
    apportions. No seed."""
    z = ndtri((np.arange(entities) + 0.5) / entities)
    floor = float(activity["floor"])
    tail = np.exp(float(activity["log_sd"]) * z)
    share = floor + tail * ((float(activity["rows"]) - floor * entities)
                            / tail.sum())
    over = np.maximum(share * (n / share.sum()) - 1.0, 0.0)
    over *= (n - entities) / over.sum()
    counts = np.floor(over).astype(np.int64)
    order = np.argsort(-(over - counts), kind="stable")
    counts[order[:n - entities - int(counts.sum())]] += 1
    return counts + 1


def artist_of_items(rng, conf: dict) -> np.ndarray:
    """(items,) int32, the artist id of every item id: the items are laid
    out tracks, albums, artist items, genre items; a track or album belongs
    to an artist drawn Zipf-skewed (the head artist at a seeded id), the
    k-th artist item is artist k, a genre item maps to the "no artist" id,
    the last one."""
    kinds = conf["items"]
    artists = int(kinds["artists"])
    owned = int(kinds["tracks"]) + int(kinds["albums"])
    rank = game_criteo.zipf_ranks(rng, owned, artists,
                                  float(conf["assumed_generator"][
                                      "artist_zipf_exponent"]))
    return np.concatenate([
        rng.permutation(artists)[rank], np.arange(artists),
        np.full(int(kinds["genres"]), artists)]).astype(np.int32)


def make(seed: int, conf: dict) -> gen.Data:
    """The dataset of one configuration file, drawn from ``seed``: rows
    filled in ``CHUNKS`` ranges from child streams, as ``gen.make`` does."""
    rng = np.random.default_rng(int(seed))
    n = int(conf["num_rows"])
    g = conf["assumed_generator"]
    user, item, artist = conf["entities"]
    if artist.get("of") != item["name"] or sum(
            conf["items"].values()) != int(item["count"]) or int(
            artist["count"]) != int(conf["items"]["artists"]) + 1:
        raise SystemExit("game_music: the item taxonomy does not add up to "
                         "the item and artist counts")
    ids = {}
    for ent in (user, item):
        counts = rng.permutation(activity_counts(
            n, int(ent["count"]), ent["activity"]))
        ids[ent["name"]] = rng.permutation(np.repeat(
            np.arange(int(ent["count"]), dtype=np.int32), counts))
    ids[artist["name"]] = artist_of_items(rng, conf)[ids[item["name"]]]
    ents = [user, item, artist]
    dims = {"global": int(conf["global_features"]),
            **{f"re_{e['name']}": int(e["features"]) for e in ents}}
    planted = {"global": (float(g["planted_fixed_sd"]) * rng.standard_normal(
        dims["global"])).astype(np.float32)}
    planted["global"][-1] = float(g["planted_bias"])
    for e in ents:
        w = float(g["planted_slope_sd"]) * rng.standard_normal(
            (int(e["count"]), int(e["features"])))
        w[:, -1] = float(g["planted_intercept_sd"]) * rng.standard_normal(
            int(e["count"]))
        planted[f"re_{e['name']}"] = w.astype(np.float32)
    shards = {k: np.empty((n, d), np.float32) for k, d in dims.items()}
    y = np.empty(n, np.float32)
    lo, hi = (float(v) for v in g["rating_range"])
    edges = np.linspace(0, n, CHUNKS + 1).astype(np.int64)

    def fill(job):
        r, a, b = job
        score = np.zeros(b - a, np.float32)
        for k, d in dims.items():
            x = r.standard_normal(size=(b - a, d), dtype=np.float32)
            x[:, -1] = 1.0
            score += (x @ planted[k] if k == "global" else np.einsum(
                "nd,nd->n", x, planted[k][ids[k[3:]][a:b]]))
            shards[k][a:b] = x
        y[a:b] = np.clip(score + float(g["noise_sd"]) * r.standard_normal(
            b - a, dtype=np.float32), lo, hi)

    jobs = list(zip(rng.spawn(CHUNKS), edges[:-1], edges[1:]))
    with ThreadPoolExecutor(min(CHUNKS, os.cpu_count() or 1)) as pool:
        list(pool.map(fill, jobs))
    return gen.Data(task="linear", shards=shards, entity_ids=ids,
                    num_entities={e["name"]: int(e["count"]) for e in ents},
                    response=y)


def shrink(conf: dict, rows: int) -> dict:
    """The rehearsal's configuration: the rows, the users, the item kinds
    and the artists cut in one proportion (each kind keeping a few), the
    activity curves and the taxonomy's rules kept; ``max_samples`` follows
    the rows in ``_settings``, so that it binds on the heaviest artists
    there too."""
    f = rows / float(conf["num_rows"])
    kinds = {k: max(2, round(int(v) * f)) for k, v in conf["items"].items()}
    user, item, artist = conf["entities"]

    def fewer(e, count):  # the same mean rows an entity
        return dict(e, count=count, activity=dict(
            e["activity"], rows=e["activity"]["rows"] * count / e["count"]))
    return dict(
        conf, num_rows=rows, items=kinds,
        rehearsal_rows_of=int(conf["num_rows"]),
        entities=[fewer(user, max(8, round(int(user["count"]) * f))),
                  fewer(item, sum(kinds.values())),
                  dict(artist, count=kinds["artists"] + 1)])


def dataset(data: gen.Data):
    return game_dense.dataset(data)


# -- the estimator ------------------------------------------------------------

def _settings(cell: dict) -> dict:
    """The cell's settings; in a rehearsal ``max_samples`` shrunk as the
    rows are (``game_avazu``'s rule)."""
    settings, conf = cell["settings"], cell["configuration"]
    of = conf.get("rehearsal_rows_of")
    if of is None or settings.get("max_samples") is None:
        return settings
    return dict(settings, max_samples=max(8, int(
        settings["max_samples"]) * int(conf["num_rows"]) // int(of)))


def estimator(cell: dict, mesh, sweeps: int, ledger_dir: str,
              feature_dtype: str):
    """The object the window drives, built as ``cli/game_train.main`` builds
    it, each coordinate with the optimisation block of its own
    (``settings["optimizers"]``, ``game_kdd12``'s reading of one)."""
    from photon_ml_tpu.api.configs import (CoordinateConfiguration,
                                           FixedEffectDataConfiguration,
                                           RandomEffectDataConfiguration)
    from photon_ml_tpu.api.estimator import GameEstimator

    settings = _settings(cell)
    coords = {}
    for cid, c in cell["mix"]["coordinates"].items():
        if c["type"] == "fixed":
            data = FixedEffectDataConfiguration(
                c["shard"], feature_dtype=feature_dtype)
        else:
            data = RandomEffectDataConfiguration(
                random_effect_type=c["entity"],
                feature_shard_id="re_" + c["entity"],
                active_data_upper_bound=settings.get("max_samples"),
                feature_dtype=feature_dtype)
        coords[cid] = CoordinateConfiguration(
            data=data,
            optimization=game_kdd12._optimization(
                settings["optimizers"][cid]))
    if cell["configuration"]["task"] != "linear":
        raise SystemExit("game_music knows the task linear only")
    return GameEstimator(
        task=TASK, coordinates=coords,
        update_sequence=list(cell["mix"]["update_sequence"]), mesh=mesh,
        descent_iterations=sweeps, validation_evaluators=None,
        compute_variances_at_end=False, ledger_dir=ledger_dir)


def check(data, cell: dict, served: dict, ledger_rows, sweeps: int) -> dict:
    """``music_reference.check``: every block of the cell states one L2
    weight (none on the intercepts), which the reference takes as its one;
    the rehearsal's cap as ``estimator`` sets it."""
    settings = _settings(cell)
    blocks = settings["optimizers"]
    weights = {float(o["reg_weight"]) for o in blocks.values()}
    if len(weights) != 1 or any(o["regularization"] != "L2"
                                for o in blocks.values()):
        raise SystemExit("game_music's reference takes one L2 weight for "
                         "every coordinate")
    return music_reference.check(data, dict(cell, settings=dict(
        settings, optimizer={"reg_weight": weights.pop()})), served,
        ledger_rows, sweeps)


# -- the work the traced sweep needs ------------------------------------------

def _fixed_passes(ctx):
    """(evaluations, Hessian-vector products) of the fixed effect's solve in
    the traced sweep, from its ``opt_iter`` rows; None where the program
    counts no products."""
    rows = game_kdd12._traced_fixed(ctx)
    if rows is None or any(r.get("hvps") is None for r in rows):
        return None
    return int(rows[-1]["evaluations"]), sum(int(r["hvps"]) for r in rows)


def traced_waves(ctx):
    """The ``re_fit_wave`` rows of the traced sweep, every table's, that
    count their products; None where the program writes none."""
    rows = [r for r in ctx["ledger_rows"]
            if r.get("kind") == "re_fit_wave"
            and r.get("outer_iteration") == ctx["traced_sweep"]
            and r.get("entities_fit")]
    if not rows or any(r.get("hvp_sum") is None for r in rows):
        return None
    return rows


def _widths(ctx) -> dict:
    """coordinate -> the width of the shard it reads."""
    conf = ctx["cell"]["configuration"]
    width = {f"re_{e['name']}": int(e["features"]) for e in conf["entities"]}
    width["global"] = int(conf["global_features"])
    return {cid: width[c["shard"] if c["type"] == "fixed"
                       else "re_" + c["entity"]]
            for cid, c in ctx["cell"]["mix"]["coordinates"].items()}


def _lane_cells(r) -> float:
    """A wave's per-lane share of work in cells: its live lanes' own rows
    over the lanes, so that x (evaluations or products) x this is what the
    lanes' own rows fill, on the average lane."""
    return r["rows_useful"] / r["entities_fit"]


def bytes_needed(kernel: str, ctx):
    """Bytes the traced sweep's solves have to read, whatever implements
    them, at 4 B a cell and two passes over the rows a product or an
    evaluation (margins X·w, gradient Xᵀr; X·v and Xᵀ(D·X·v)).
    ``fe_pass``: the fixed effect's evaluations plus its products over all
    rows of ``global``. ``tron_cg``: the products alone, the fixed effect's
    over all its rows and each wave's, on the average lane (``hvp_sum /
    entities_fit``), over the cells its lanes' own rows fill. Padding rows
    and the lanes a wave waits for are in the seconds, not in the bytes."""
    passes = _fixed_passes(ctx)
    if passes is None:
        return None
    evals, hvps = passes
    n = int(ctx["cell"]["configuration"]["num_rows"])
    width = _widths(ctx)
    fixed = [c for c, v in ctx["cell"]["mix"]["coordinates"].items()
             if v["type"] == "fixed"][0]
    if kernel == "fe_pass":
        return (evals + hvps) * 2 * n * width[fixed] * 4
    if kernel != "tron_cg":
        return None
    waves = traced_waves(ctx)
    if waves is None:
        return None
    cells = hvps * n * width[fixed] + sum(
        r["hvp_sum"] * _lane_cells(r) * width[r["coordinate"]] for r in waves)
    return int(cells * 2 * 4)


def sweep_flops(ctx):
    """FLOPs the traced sweep needs: a multiply and an add per cell in each
    pass of every evaluation and every product the solves took (two passes
    each: the fixed effect's over all rows, a wave's over its lanes' own
    rows) and in one pass a coordinate to rescore every row."""
    passes = _fixed_passes(ctx)
    waves = traced_waves(ctx)
    if passes is None or waves is None:
        return None
    n = int(ctx["cell"]["configuration"]["num_rows"])
    width = _widths(ctx)
    fixed = [c for c, v in ctx["cell"]["mix"]["coordinates"].items()
             if v["type"] == "fixed"][0]
    cells = sum(passes) * n * width[fixed] + sum(
        (r["evals_sum"] + r["hvp_sum"]) * _lane_cells(r)
        * width[r["coordinate"]] for r in waves)
    return int(2 * 2 * cells + 2 * n * sum(width.values()))


# -- the faults a cell of this schema can have --------------------------------

def _artist_misjoined():
    """The artist table keyed by the item id, folded into the table's range:
    the join from item to artist left out."""
    sound = dataset

    def broken(data):
        ds = sound(data)
        names = list(data.num_entities)  # user, item, artist in order
        ds.entity_ids = dict(ds.entity_ids, **{names[2]: (
            data.entity_ids[names[1]] % data.num_entities[names[2]]
        ).astype(np.int32)})
        return ds
    return game_dense._patched(sys.modules[__name__], "dataset", broken)


def _ratio_ignored():
    """TRON with its ratio test left out: every step accepted and the
    radius grown as if the model had predicted the decrease."""
    from contextlib import ExitStack

    from photon_ml_tpu.optim import tron
    stack = ExitStack()
    for name in ("_ETA0", "_ETA1", "_ETA2"):
        stack.enter_context(game_dense._patched(tron, name, -np.inf))
    return stack


faults = {"artist-misjoined": _artist_misjoined,
          "ratio-ignored": _ratio_ignored}


# -- run.py --selfcheck -------------------------------------------------------

def check_generator():
    """The item -> artist map is a function with the taxonomy's rules, the
    activity curves keep their floor and mean, and every seed has the same
    rows per user and per item."""
    conf = {"num_rows": 6000, "global_features": 6,
            "items": {"tracks": 300, "albums": 60, "artists": 20,
                      "genres": 3},
            "entities": [
                {"name": "u", "count": 400, "features": 4,
                 "activity": {"rows": 100000, "floor": 10, "log_sd": 1.3}},
                {"name": "i", "count": 383, "features": 4,
                 "activity": {"rows": 100000, "floor": 1, "log_sd": 2.0}},
                {"name": "a", "count": 21, "features": 4, "of": "i"}],
            "assumed_generator": {
                "artist_zipf_exponent": 1.0, "planted_fixed_sd": 2.0,
                "planted_bias": 50.0, "planted_slope_sd": 3.0,
                "planted_intercept_sd": 10.0, "noise_sd": 20.0,
                "rating_range": [0, 100]}}
    one, two = make(3, conf), make(4, conf)
    for data in (one, two):
        items, artists = data.entity_ids["i"], data.entity_ids["a"]
        # a function: every row of an item names the same artist
        first = np.full(383, -1)
        first[items] = artists
        assert np.array_equal(first[items], artists)
        assert np.array_equal(first[360:380], np.arange(20))  # own artist
        assert (first[380:] == 20).all() and (first[:360] < 20).all()
        assert data.response.min() >= 0 and data.response.max() <= 100
        assert data.shards["re_a"].shape == (6000, 4)
        assert (data.shards["global"][:, -1] == 1).all()
    for name in ("u", "i"):
        assert np.array_equal(
            np.sort(np.bincount(one.entity_ids[name])),
            np.sort(np.bincount(two.entity_ids[name])))
    full = activity_counts(100000, 400, conf["entities"][0]["activity"])
    assert full.sum() == 100000 and full.min() >= 10  # the floor holds
    assert np.all(np.diff(full) >= 0)
    cut = activity_counts(6000, 400, conf["entities"][0]["activity"])
    assert cut.sum() == 6000 and cut.min() == 1


def check_work():
    """The work counts over a hand-made ledger: a fixed solve of 3
    evaluations and 7 products over 1,000 rows x 32, two waves of 8-wide
    tables."""
    ctx = {"cell": {"configuration": {
        "num_rows": 1000, "global_features": 32,
        "entities": [{"name": "u", "features": 8},
                     {"name": "a", "features": 8}]},
        "mix": {"coordinates": {
            "fixed": {"type": "fixed", "shard": "global"},
            "per-u": {"type": "random", "entity": "u"},
            "per-a": {"type": "random", "entity": "a"}}}},
        "traced_sweep": 3,
        "ledger_rows": [
            {"kind": "opt_iter", "coordinate": "fixed", "outer_iteration": 3,
             "iteration": 0, "hvps": 0},
            {"kind": "opt_iter", "coordinate": "fixed", "outer_iteration": 3,
             "iteration": 1, "hvps": 4},
            {"kind": "opt_iter", "coordinate": "fixed", "outer_iteration": 3,
             "iteration": 2, "hvps": 3, "evaluations": 3},
            {"kind": "re_fit_wave", "coordinate": "per-u",
             "outer_iteration": 3, "entities_fit": 10, "rows_useful": 200,
             "evals_sum": 30, "hvp_sum": 50, "hvp_wave": 80},
            {"kind": "re_fit_wave", "coordinate": "per-a",
             "outer_iteration": 3, "entities_fit": 2, "rows_useful": 800,
             "evals_sum": 8, "hvp_sum": 12, "hvp_wave": 12}]}
    assert bytes_needed("fe_pass", ctx) == 10 * 2 * 1000 * 32 * 4
    # products: 7 over 32,000 cells, 50 x 20 rows x 8, 12 x 400 rows x 8
    assert bytes_needed("tron_cg", ctx) == (
        7 * 32000 + 50 * 20 * 8 + 12 * 400 * 8) * 2 * 4
    assert sweep_flops(ctx) == 4 * (10 * 32000 + 80 * 20 * 8
                                    + 20 * 400 * 8) + 2 * 1000 * 48
    assert bytes_needed("other", ctx) is None
    for r in ctx["ledger_rows"][3:]:  # a program that counts no products
        del r["hvp_sum"]
    assert bytes_needed("tron_cg", ctx) is None and sweep_flops(ctx) is None
    del ctx["ledger_rows"][2]["hvps"]
    assert bytes_needed("fe_pass", ctx) is None


selfchecks = (check_generator, check_work)
