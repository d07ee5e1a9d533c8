"""The fixture schema ``game_sparse_tiny`` (``benchmark/test_schema.py``): a
GLMix whose fixed effect reads a **sparse** shard, a few categorical fields
hashed into ``hashed_features`` columns and held in ELL form (the program's
``SparseShard``), beside one dense random-effect table with the intercept in
its last column. It shows what a second data schema brings, as new files
only: its generator and plain reference (numpy, nothing of the program
imported), its dataset, its work counts and its fault; the estimator and the
leaves it takes from ``game_dense``, a schema that is there. No cell of
``BENCHMARK.json`` uses it.
"""

from __future__ import annotations

import contextlib
import dataclasses
import sys

import numpy as np

# built as for dense shards: the program tells a sparse shard by its type
from game_dense import estimator, model_arrays  # noqa: F401

NEWTON_STEPS = 50


@dataclasses.dataclass
class Data:
    indices: np.ndarray  # (n, fields) int32 columns of the hashed fields
    values: np.ndarray  # (n, fields) float32
    num_features: int
    entity_ids: np.ndarray  # (n,) int32
    num_entities: int
    table: np.ndarray  # (n, d) float32, last column 1.0
    response: np.ndarray  # (n,) float32


def make(seed: int, conf: dict) -> Data:
    rng = np.random.default_rng(int(seed))
    n, d = int(conf["num_rows"]), int(conf["hashed_features"])
    fields, card = int(conf["fields"]), int(conf["field_cardinality"])
    ent = conf["entity"]
    # a skewed category per field, hashed to a column
    cats = np.floor(card * rng.random((n, fields)) ** 2).astype(np.int64)
    indices = ((np.arange(fields) * 2654435761 + cats * 40503 + 12345)
               % d).astype(np.int32)
    values = np.ones((n, fields), np.float32)
    ids = rng.permutation(np.arange(n) % int(ent["count"])).astype(np.int32)
    table = rng.standard_normal((n, int(ent["features"]))).astype(np.float32)
    table[:, -1] = 1.0
    w = 0.5 * rng.standard_normal(d)
    W = 0.7 * rng.standard_normal((int(ent["count"]), int(ent["features"])))
    logits = w[indices].sum(axis=1) + np.einsum("nd,nd->n", table, W[ids])
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-logits))).astype(np.float32)
    return Data(indices, values, d, ids, int(ent["count"]), table, y)


def shrink(conf: dict, rows: int) -> dict:
    return dict(conf, num_rows=rows)


def dataset(data: Data):
    from photon_ml_tpu.data.game_data import GameDataset, SparseShard

    n = data.response.shape[0]
    return GameDataset(
        response=data.response, offsets=np.zeros(n, np.float32),
        weights=np.ones(n, np.float32),
        feature_shards={
            "global": SparseShard(data.indices, data.values,
                                  data.num_features),
            "re_userId": data.table},
        entity_ids={"userId": data.entity_ids},
        num_entities={"userId": data.num_entities},
        intercept_index={"re_userId": data.table.shape[1] - 1})


# -- the plain reference ------------------------------------------------------

def _loss(m, y):
    return np.logaddexp(0.0, m) - y * m


def _newton(X, y, off, w, lam):
    """The minimiser of sum loss(X w + off) + lam/2 |w|^2 over the regularised
    columns (``lam`` is a vector), by Newton steps with step halving."""
    def value(w):
        return _loss(X @ w + off, y).sum() + 0.5 * (lam * w * w).sum()

    f = value(w)
    for _ in range(NEWTON_STEPS):
        p = 1.0 / (1.0 + np.exp(-(X @ w + off)))
        g = X.T @ (p - y) + lam * w
        H = (X * (p * (1 - p))[:, None]).T @ X + np.diag(lam + 1e-9)
        step, t = np.linalg.solve(H, g), 1.0
        while value(w - t * step) > f and t > 1e-4:
            t *= 0.5
        w = w - t * step
        f, moved = value(w), np.abs(t * step).max()
        if moved < 1e-10:
            break
    return w


def reference(data: Data, lam: float, sweeps: int) -> dict:
    """Block coordinate descent in float64: the fixed effect over the columns
    some row touches (the others stay 0 under L2), then every user's block.
    Returns the leaves and the objective at the start of each fixed update."""
    n, d = data.response.shape[0], data.num_features
    y = data.response.astype(np.float64)
    active = np.unique(data.indices)
    col = np.full(d + 1, -1)
    col[active] = np.arange(active.size)
    X = np.zeros((n, active.size))
    np.add.at(X, (np.repeat(np.arange(n), data.indices.shape[1]),
                  col[data.indices].ravel()), data.values.ravel())
    T = data.table.astype(np.float64)
    lam_t = np.full(T.shape[1], lam)
    lam_t[-1] = 0.0  # the intercept
    w = np.zeros(active.size)
    W = np.zeros((data.num_entities, T.shape[1]))
    rows = [np.flatnonzero(data.entity_ids == e)
            for e in range(data.num_entities)]
    values = []
    for _ in range(sweeps):
        off = np.einsum("nd,nd->n", T, W[data.entity_ids])
        values.append(_loss(X @ w + off, y).sum() + 0.5 * lam * (w * w).sum())
        w = _newton(X, y, off, w, np.full(active.size, lam))
        off = X @ w
        for e, r in enumerate(rows):
            W[e] = _newton(T[r], y[r], off[r], W[e], lam_t)
    full = np.zeros(d)
    full[active] = w
    return {"fixed": full, "per-user": W, "values": values}


def check(data: Data, cell: dict, served: dict, ledger_rows, sweeps: int):
    ref = reference(data, float(cell["settings"]["optimizer"]["reg_weight"]),
                    sweeps)
    start = {r["outer_iteration"]: r["value"] for r in ledger_rows
             if r.get("kind") == "opt_iter" and r.get("iteration") == 0
             and r.get("coordinate") == "fixed"}
    got = {}
    for k in range(1, sweeps):  # the objective after sweep k opens sweep k+1
        if k in start:
            got[f"loss_{k}"] = abs(start[k] - ref["values"][k]) / abs(
                ref["values"][k])
    for cid in ("fixed", "per-user"):
        got[f"coef.{cid}"] = float(np.linalg.norm(served[cid] - ref[cid])
                                   / np.linalg.norm(ref[cid]))
    out = {}
    for name, limit in cell["configuration"]["check"]["limits"].items():
        v = got.get(name, float("inf"))
        out[name] = {"value": v if np.isfinite(v) else 1e30, "limit": limit}
    return out


# -- work counts --------------------------------------------------------------

def _fixed_iterations(ctx):
    its = [int(r["iteration"]) for r in ctx["ledger_rows"]
           if r.get("kind") == "opt_iter" and r.get("coordinate") == "fixed"
           and r.get("outer_iteration") == ctx["traced_sweep"]]
    return max(its) if its else None


def bytes_needed(kernel: str, ctx):
    """An ELL pass reads an index and a value per slot, twice an evaluation
    (margins, gradient)."""
    its = _fixed_iterations(ctx)
    if kernel != "fe_pass" or its is None:
        return None
    conf = ctx["cell"]["configuration"]
    return (its + 1) * 2 * conf["num_rows"] * conf["fields"] * (4 + 4)


def sweep_flops(ctx):
    its = _fixed_iterations(ctx)
    if its is None:
        return None
    conf = ctx["cell"]["configuration"]
    cap = int(ctx["cell"]["settings"]["optimizer"]["max_iterations"])
    n, d = conf["num_rows"], conf["entity"]["features"]
    return ((its + 1) * 4 * n * conf["fields"] + 2 * n * conf["fields"]
            + (cap + 1) * 4 * n * d + 2 * n * d)


# -- faults -------------------------------------------------------------------

@contextlib.contextmanager
def _half_batch():
    """Half of the rows left out of training (weight 0)."""
    me, sound = sys.modules[__name__], dataset

    def broken(data):
        ds = sound(data)
        ds.weights = np.where(np.arange(ds.num_rows) % 2, 0.0, 1.0
                              ).astype(np.float32)
        return ds
    me.dataset = broken
    try:
        yield
    finally:
        me.dataset = sound


faults = {"half-batch": _half_batch}
