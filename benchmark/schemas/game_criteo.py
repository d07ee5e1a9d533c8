"""The schema ``game_criteo``: GAME logistic on Criteo's display-advertising
rows. Every row has one non-zero per field, 13 integer fields (binned to a
category) and 26 categorical ones, each hashed into ``hashed_features``
columns and held in ELL form (the program's ``SparseShard``) with the value
1/sqrt(39); the fixed effect reads that shard. One random effect keyed on one
categorical field (the configuration's ``entity``) reads a dense shard: the
13 integer fields log-transformed, and the intercept in the last column.

The generator and the work counts are below; the plain reference and the
comparison are ``benchmark/criteo_reference.py``. None of them imports the
program; only ``dataset``, ``estimator`` (with its guard), ``model_arrays``
and the faults touch it. The estimator and the leaves are ``game_dense``'s:
the program tells a sparse shard by its type.
"""

from __future__ import annotations

import dataclasses
import inspect
import sys

import numpy as np

import criteo_reference
import game_dense

model_arrays = game_dense.model_arrays
check = criteo_reference.check

HASH_FIELD = np.uint64(0xBF58476D1CE4E5B9)
HASH_VALUE = np.uint64(0x9E3779B97F4A7C15)
_MADE = {}  # the column counts and rows of the data ``make`` made last: the
#             guard in ``estimator`` gets no data, and needs them


@dataclasses.dataclass
class Data:
    indices: np.ndarray  # (n, fields) int32 columns of the hashed fields
    values: np.ndarray  # (n, fields) float32
    num_features: int
    entity: str
    entity_ids: np.ndarray  # (n,) int32
    num_entities: int
    table: np.ndarray  # (n, integer fields + 1) float32, last column 1.0
    response: np.ndarray  # (n,) float32


# -- the generator ------------------------------------------------------------

def zipf_ranks(rng, n: int, cardinality: int, exponent: float) -> np.ndarray:
    """``n`` draws of a rank in [0, cardinality) with probability ~
    (rank + 1) ** -exponent: the inverse of the continuous law's
    distribution function, floored."""
    u = rng.random(n)
    top = float(cardinality) + 1.0
    if abs(exponent - 1.0) < 1e-9:
        x = np.exp(u * np.log(top))
    else:
        p = 1.0 - exponent
        x = (1.0 + u * (top ** p - 1.0)) ** (1.0 / p)
    return np.minimum(x.astype(np.int64) - 1, cardinality - 1)


def integer_bins(x: np.ndarray) -> np.ndarray:
    """The published preprocessing of an integer field (the Kaggle winners',
    which LIBSVM's ``criteo`` follows): a value over 2 becomes
    floor(ln(value) ** 2), so a field has a few hundred categories."""
    big = np.floor(np.log(np.maximum(x, 1.0)) ** 2)
    return np.where(x > 2, big, x).astype(np.int64)


def hashed(field: int, category: np.ndarray, d: int) -> np.ndarray:
    """The column of a field's category: the top bits of a multiplicative
    hash of (field, category) in 64-bit arithmetic. ``d`` is a power of two."""
    bits = int(d).bit_length() - 1
    assert 1 << bits == d, "hashed_features has to be a power of two"
    with np.errstate(over="ignore"):
        h = ((category.astype(np.uint64) + np.uint64(1)) * HASH_VALUE
             + np.uint64(field + 1) * HASH_FIELD)
        h = (h ^ (h >> np.uint64(29))) * HASH_FIELD
    return (h >> np.uint64(64 - bits)).astype(np.int32)


def make(seed: int, conf: dict) -> Data:
    rng = np.random.default_rng(int(seed))
    n, d = int(conf["num_rows"]), int(conf["hashed_features"])
    ints = int(conf["integer_fields"])
    cards = [int(c) for c in conf["categorical_cardinalities"]]
    ent = conf["entity"]
    skew = conf["assumed_generator"]
    fields = ints + len(cards)
    indices = np.empty((n, fields), np.int32)
    table = np.empty((n, ints + 1), np.float32)
    table[:, -1] = 1.0
    # integer fields: a log-normal count, zero on a share of the rows
    mu = rng.uniform(*skew["integer_log_mean"], size=ints)
    for f in range(ints):
        x = np.floor(np.exp(mu[f] + skew["integer_log_sd"]
                            * rng.standard_normal(n)))
        x = np.where(rng.random(n) < skew["integer_zero_share"], 0.0, x)
        table[:, f] = np.log1p(x)
        indices[:, f] = hashed(f, integer_bins(x), d)
    ids = None
    for c, card in enumerate(cards):
        rank = zipf_ranks(rng, n, card, float(skew["zipf_exponent"]))
        indices[:, ints + c] = hashed(ints + c, rank, d)
        if c == int(ent["categorical_index"]):
            ids = rank.astype(np.int32)
    values = np.full((n, fields), 1.0 / np.sqrt(fields), np.float32)
    # planted effects
    w = float(skew["planted_fixed_sd"]) * rng.standard_normal(d)
    W = float(skew["planted_slope_sd"]) * rng.standard_normal(
        (int(ent["count"]), ints + 1))
    W[:, -1] = float(skew["planted_intercept_sd"]) * rng.standard_normal(
        int(ent["count"]))
    logits = float(skew["planted_bias"]) + w[indices].sum(axis=1) / np.sqrt(
        fields) + np.einsum("nd,nd->n", table, W[ids])
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-logits))).astype(np.float32)
    _MADE.update(counts=np.bincount(indices.reshape(-1), minlength=d), rows=n)
    return Data(indices, values, d, ent["name"], ids, int(ent["count"]),
                table, y)


def shrink(conf: dict, rows: int) -> dict:
    """The rehearsal's configuration: fewer rows, and as many entities and
    categories per field as those rows can fill (a twentieth of them at
    most); the fields, their hashing and the columns stay."""
    few = max(8, rows // 20)
    return dict(
        conf, num_rows=rows,
        categorical_cardinalities=[min(int(c), few) for c in
                                   conf["categorical_cardinalities"]],
        entity=dict(conf["entity"],
                    count=min(int(conf["entity"]["count"]), few)))


def dataset(data: Data):
    from photon_ml_tpu.data.game_data import GameDataset, SparseShard

    n = data.response.shape[0]
    return GameDataset(
        response=data.response, offsets=np.zeros(n, np.float32),
        weights=np.ones(n, np.float32),
        feature_shards={
            "global": SparseShard(data.indices, data.values,
                                  data.num_features),
            "re_" + data.entity: data.table},
        entity_ids={data.entity: data.entity_ids},
        num_entities={data.entity: data.num_entities},
        intercept_index={"re_" + data.entity: data.table.shape[1] - 1})


# -- the estimator, behind its guard ------------------------------------------

def resident_plan(mesh, feature_dtype: str) -> dict:
    """What the program's resident layout would allocate for the fixed
    effect of the data made last, reckoned from the program's own planner
    before anything is allocated: the hot block's columns and bytes on one
    device of ``mesh``. A program whose coordinate derives no byte budget
    is asked through ``build_hybrid``'s own count threshold and column cap."""
    import jax.numpy as jnp

    from photon_ml_tpu.game.coordinates import sparse_fixed
    from photon_ml_tpu.ops import hybrid_sparse as hs

    counts, n = _MADE["counts"], _MADE["rows"]
    dt = jnp.bfloat16 if feature_dtype == "bfloat16" else jnp.float32
    if hasattr(sparse_fixed, "hot_block_budget"):
        k = hs.plan_resident_hot(
            counts, n, dt, hot_block_bytes=sparse_fixed.hot_block_budget(mesh))
    else:
        cap = inspect.signature(hs.build_hybrid).parameters["max_hot"].default
        k = min(int(cap), int(
            (counts >= hs._default_hot_threshold(n, dt)).sum()))
    stats = mesh.devices.flat[0].memory_stats() or {}
    return {"num_hot": int(k),
            "hot_bytes": int(k) * n * (2 if dt == jnp.bfloat16 else 4),
            "device_bytes": int(stats.get("bytes_limit", 0))}


def estimator(cell: dict, mesh, sweeps: int, ledger_dir: str,
              feature_dtype: str):
    """``game_dense``'s estimator, after the guard: where the hot block the
    program would allocate is larger than the device, exit with a plain
    message before the host or the device holds any of it."""
    plan = resident_plan(mesh, feature_dtype)
    if plan["device_bytes"] and plan["hot_bytes"] > plan["device_bytes"]:
        raise SystemExit(
            f"game_criteo: this program's resident sparse layout would "
            f"allocate a hot block of {plan['num_hot']} columns x "
            f"{cell['configuration']['num_rows']} rows = "
            f"{plan['hot_bytes']} bytes on the host and then on a device of "
            f"{plan['device_bytes']} bytes: it cannot hold this "
            f"configuration (the block has to be sized from bytes)")
    return game_dense.estimator(cell, mesh, sweeps, ledger_dir,
                                feature_dtype)


# -- the work the traced sweep needs ------------------------------------------

def _traced_fixed(ctx):
    """(L-BFGS iterations, evaluations) of the fixed effect's update in the
    traced sweep, from its ``opt_iter`` rows; None without them."""
    rows = [r for r in ctx["ledger_rows"]
            if r.get("kind") == "opt_iter" and r.get("coordinate") == "fixed"
            and r.get("outer_iteration") == ctx["traced_sweep"]]
    if not rows:
        return None
    its = max(int(r["iteration"]) for r in rows)
    evals = [int(r["evaluations"]) for r in rows
             if r.get("evaluations") is not None]
    return its, (evals[-1] if evals else its + 1)


def layout(ctx):
    """The last ``fe_layout`` row the program wrote, or None."""
    rows = [r for r in ctx["ledger_rows"] if r.get("kind") == "fe_layout"]
    return rows[-1] if rows else None


def bytes_needed(kernel: str, ctx):
    """Bytes the fixed effect's evaluations of the traced sweep have to
    move, whatever implements them: an evaluation makes two passes over the
    non-zeros (margins, gradient), each reading a 4-byte index and a 4-byte
    value per non-zero. ``fe_pass``: all of a row's non-zeros; ``fe_hot`` /
    ``fe_cold``: those the program's layout gives to that part."""
    solved = _traced_fixed(ctx)
    if solved is None:
        return None
    conf = ctx["cell"]["configuration"]
    fields = int(conf["integer_fields"]) + len(
        conf["categorical_cardinalities"])
    if kernel == "fe_pass":
        entries = int(conf["num_rows"]) * fields
    elif kernel in ("fe_hot", "fe_cold"):
        lay = layout(ctx)
        if lay is None:
            return None
        entries = int(lay[kernel[3:] + "_entries"])
    else:
        return None
    return solved[1] * 2 * entries * (4 + 4)


def sweep_flops(ctx):
    """FLOPs the traced sweep needs: per evaluation of the fixed effect a
    multiply and an add per non-zero in each of two passes, and one pass to
    rescore; the table's solves at the iteration cap over the rows, as
    ``work.sweep_flops`` counts a dense table."""
    solved = _traced_fixed(ctx)
    if solved is None:
        return None
    conf = ctx["cell"]["configuration"]
    n = int(conf["num_rows"])
    fields = int(conf["integer_fields"]) + len(
        conf["categorical_cardinalities"])
    cap = int(ctx["cell"]["settings"]["optimizer"]["max_iterations"])
    width = int(conf["entity"]["features"])
    return (solved[1] * 4 * n * fields + 2 * n * fields
            + (cap + 1) * 4 * n * width + 2 * n * width)


# -- the faults a cell of this schema can have --------------------------------

def _half_batch():
    """Half of the rows left out of training (weight 0)."""
    sound = dataset

    def broken(data):
        ds = sound(data)
        ds.weights = np.where(np.arange(ds.num_rows) % 2, 0.0, 1.0
                              ).astype(np.float32)
        return ds
    return game_dense._patched(sys.modules[__name__], "dataset", broken)


def _cold_dropped():
    """The cold part left out of the fixed effect's gradient: the hot
    block's columns alone are trained, the margins stay whole."""
    from photon_ml_tpu.ops import hybrid_sparse as hs
    import jax.numpy as jnp

    sound = hs._cold_grad

    def broken(hb, r, cold_vals):
        return [jnp.zeros_like(g) for g in sound(hb, r, cold_vals)]
    return game_dense._patched(hs, "_cold_grad", broken)


faults = {"half-batch": _half_batch, "cold-dropped": _cold_dropped}


# -- run.py --selfcheck -------------------------------------------------------

def check_generator():
    """The hash spreads a field's categories over the columns, the binning
    is the published one, and the Zipf draw has the head it should."""
    assert list(integer_bins(np.array([0., 1, 2, 3, 8, 100, 60000]))) == [
        0, 1, 2, 1, 4, 21, 121]
    cols = hashed(3, np.arange(100000), 1 << 20)
    assert cols.min() >= 0 and cols.max() < 1 << 20
    assert np.unique(cols).size > 95000  # ~4.6% collide at this load
    assert not np.array_equal(cols[:1000], hashed(4, np.arange(1000), 1 << 20))
    r = zipf_ranks(np.random.default_rng(0), 200000, 93145, 1.1)
    assert r.min() == 0 and r.max() < 93145
    head = np.mean(r == 0)  # (2^-0.1 - 1) / (93146^-0.1 - 1) = 0.0981
    assert 0.09 < head < 0.106, head


def check_work():
    ctx = {"cell": {"configuration": {"num_rows": 1000, "integer_fields": 13,
                                      "categorical_cardinalities": [5] * 26,
                                      "entity": {"features": 14}},
                    "settings": {"optimizer": {"max_iterations": 25}}},
           "traced_sweep": 3,
           "ledger_rows": [
               {"kind": "fe_layout", "hot_entries": 30000,
                "cold_entries": 9000},
               {"kind": "opt_iter", "coordinate": "fixed",
                "outer_iteration": 3, "iteration": 0},
               {"kind": "opt_iter", "coordinate": "fixed",
                "outer_iteration": 3, "iteration": 4, "evaluations": 9}]}
    assert bytes_needed("fe_pass", ctx) == 9 * 2 * 39000 * 8
    assert bytes_needed("fe_hot", ctx) == 9 * 2 * 30000 * 8
    assert bytes_needed("fe_cold", ctx) == 9 * 2 * 9000 * 8
    assert bytes_needed("other", ctx) is None
    assert sweep_flops(ctx) == (9 * 4 * 39000 + 2 * 39000
                                + 26 * 4 * 14000 + 2 * 14000)
    ctx["ledger_rows"] = ctx["ledger_rows"][1:]  # a program without the row
    assert bytes_needed("fe_hot", ctx) is None
    assert bytes_needed("fe_pass", ctx) == 9 * 2 * 39000 * 8


selfchecks = (check_generator, check_work)
