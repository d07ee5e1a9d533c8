"""Seeded GLMix traffic: the benchmark's own copy of the data generator.

A copy of ``photon_ml_tpu/data/synthetic.game_data`` and
``data/game_data.from_synthetic`` (same model: planted fixed effect plus
planted per-entity effects, last column of every shard the intercept), kept
here so that a later PR to ``data/`` cannot change what the cells are fed. It
imports nothing of the program and returns plain numpy arrays; ``run.py`` wraps
them in the program's ``GameDataset``. Everything drawn is drawn from
``--seed``: planted model, features, labels, and which row belongs to whom.

Three departures from the original:

- the rows of each entity are not drawn from a Zipf law but apportioned to
  the source's own activity: the configuration gives, for each entity column,
  the published least, quartiles and most of rows per entity and the published
  total (``activity``); ``activity_counts`` lays a curve through those anchors
  whose sum is that total, scales it to the configuration's rows (a uniform
  sample of the source's rows) and rounds by largest remainder, every entity
  keeping a row. The counts are real to the anchors; which entity has which
  count, and who meets whom, is drawn from the seed;
- so every seed has the same multiset of rows per entity, hence the same
  bucket shapes and the same compiled programs; a drawn assignment would
  change the padded entity count of nearly every bucket class, and each new
  seed would compile every bucket program anew;
- the draws are made in float32 and in 16 row ranges, each from a child
  stream of the seed, filled on a few threads: 10M rows take seconds. The
  data depends on the seed alone, not on the number of cores.
"""

from __future__ import annotations

import dataclasses
import os
import statistics
from concurrent.futures import ThreadPoolExecutor

import numpy as np


@dataclasses.dataclass
class Data:
    """One seeded dataset, columnar: shard name -> (n, d) float32, last
    column 1.0; entity column name -> (n,) int32 rows of that entity table."""

    task: str
    shards: dict  # "global", "re_<entity>"
    entity_ids: dict  # "<entity>" -> (n,) int32
    num_entities: dict
    response: np.ndarray  # (n,) float32

    @property
    def num_rows(self) -> int:
        return int(self.response.shape[0])


def activity_counts(n: int, entities: int, activity: dict) -> np.ndarray:
    """Rows of each entity, ascending: ``n`` apportioned along the source's
    activity curve. The curve is the quantile function Q(u) at u = (k + 1/2) /
    entities, with ln Q linear in the normal score z(u) between the published
    least, quartiles and median, and from the upper quartile to the published
    most a parabola in z whose one free bend makes the curve sum to the
    published total. Scaled by ``n`` over that total, rounded by largest
    remainder (ties to the lower rank), every entity keeping a row. No seed."""
    inv = statistics.NormalDist().inv_cdf
    z = np.array([inv((k + 0.5) / entities) for k in range(entities)])
    z75 = inv(0.75)
    knots = [z[0], -z75, 0.0, z75]
    logs = np.log([activity[k] for k in ("min", "q1", "median", "q3")])
    ln = np.interp(z, knots, logs)
    top = z > z75
    t, span = z[top] - z75, z[-1] - z75
    rise = (np.log(activity["max"]) - logs[3]) / span

    def curve(bend):
        out = ln.copy()
        out[top] = logs[3] + rise * t + bend * t * (t - span)
        return np.exp(out)

    lo, hi = -8.0, 8.0  # the sum falls as the bend grows
    for _ in range(80):
        bend = 0.5 * (lo + hi)
        lo, hi = (bend, hi) if curve(bend).sum() > activity["rows"] else (
            lo, bend)
    share = curve(bend)
    if np.any(np.diff(share) < 0) or abs(
            share.sum() / activity["rows"] - 1) > 1e-6:
        raise ValueError(f"no rising curve through {activity}")
    # one row to every entity, the others to what its scaled share has over 1
    over = np.maximum(share * (n / share.sum()) - 1.0, 0.0)
    over *= (n - entities) / over.sum()
    counts = np.floor(over).astype(np.int64)
    order = np.argsort(-(over - counts), kind="stable")
    counts[order[:n - entities - int(counts.sum())]] += 1
    counts += 1
    return counts


CHUNKS = 16  # fixed, so that the data does not depend on the core count


def make(seed: int, config: dict) -> Data:
    """The dataset of one configuration file, drawn from ``seed``. The rows
    (features, labels) are filled in ``CHUNKS`` row ranges, each from a child
    stream of the seed, on a few threads."""
    rng = np.random.default_rng(int(seed))
    n = int(config["num_rows"])
    task = config["task"]
    if task not in ("logistic", "linear"):
        raise ValueError(f"unknown task {task!r}")
    dims = {"global": int(config["global_features"])}
    planted = {"global": (rng.standard_normal(dims["global"]) * 0.5
                          ).astype(np.float32)}
    ids, sizes = {}, {}
    for ent in config["entities"]:
        name, ne, d = ent["name"], int(ent["count"]), int(ent["features"])
        counts = rng.permutation(activity_counts(n, ne, ent["activity"]))
        ids[name] = rng.permutation(np.repeat(
            np.arange(ne, dtype=np.int32), counts))
        sizes[name] = ne
        dims[f"re_{name}"] = d
        planted[f"re_{name}"] = (rng.standard_normal((ne, d)) * 0.7
                                 ).astype(np.float32)
    shards = {k: np.empty((n, d), np.float32) for k, d in dims.items()}
    y = np.empty(n, np.float32)
    edges = np.linspace(0, n, CHUNKS + 1).astype(np.int64)

    def fill(job):
        g, a, b = job
        logits = np.zeros(b - a, np.float32)
        for k, d in dims.items():
            x = g.standard_normal(size=(b - a, d), dtype=np.float32)
            x[:, -1] = 1.0
            if k == "global":
                logits += x @ planted[k]
            else:
                logits += np.einsum("nd,nd->n", x,
                                    planted[k][ids[k[3:]][a:b]])
            shards[k][a:b] = x
        if task == "logistic":
            y[a:b] = g.random(b - a, dtype=np.float32) < 1.0 / (
                1.0 + np.exp(-logits))
        else:
            y[a:b] = logits + 0.1 * g.standard_normal(
                b - a, dtype=np.float32)

    jobs = list(zip(rng.spawn(CHUNKS), edges[:-1], edges[1:]))
    with ThreadPoolExecutor(min(CHUNKS, os.cpu_count() or 1)) as pool:
        list(pool.map(fill, jobs))
    return Data(task=task, shards=shards, entity_ids=ids,
                num_entities=sizes, response=y)
