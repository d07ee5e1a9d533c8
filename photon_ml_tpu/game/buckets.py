"""Entity bucketing: the TPU answer to RandomEffectDataset partitioning.

Reference parity: photon-api ``data/RandomEffectDataset.scala`` (build:
keyBy(REId) → ``RandomEffectDatasetPartitioner`` greedy bin-packing →
active/passive split with ``numActiveDataPointsLowerBound`` /
``numActiveDataPointsUpperBound``) and ``data/LocalDataset.scala``.

TPU-first design (SURVEY.md §2.5 P2): instead of an RDD of ragged per-entity
``LocalDataset``s solved sequentially per executor, entities are grouped
into a small number of BUCKETS by sample count (power-of-two capacities).
Each bucket is a dense padded block:

    features (E_b, cap_b, d)   labels/weights/offsets (E_b, cap_b)

so one ``vmap``-ped optimizer solves every entity in the bucket
simultaneously, and the entity axis shards over the mesh. Padding rows have
weight 0 (inert by the aggregator contract). The permutation indices into
the flat example order are kept so per-iteration offsets can be gathered
(and scores scattered) without re-bucketing.

Active/passive semantics (reference):
- entities with fewer than ``lower_bound`` examples get NO model (their
  examples are passive: scored with zero random-effect contribution);
- entities keep at most ``upper_bound`` examples for training (the rest of
  their examples are passive but still scored with the trained model).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np


@dataclasses.dataclass
class EntityBucket:
    """One padded bucket of entities with similar sample counts."""

    entity_rows: np.ndarray  # (E_b,) int32: rows into the entity table; -1 pad
    example_idx: np.ndarray  # (E_b, cap) int64: flat example indices; -1 pad
    counts: np.ndarray  # (E_b,) int32 true (capped) sample counts

    @property
    def num_entities(self) -> int:
        return int(self.entity_rows.shape[0])

    @property
    def capacity(self) -> int:
        return int(self.example_idx.shape[1])


@dataclasses.dataclass
class EntityBucketing:
    """Bucketed grouping of a dataset's examples by entity."""

    buckets: list[EntityBucket]
    num_entities: int
    trained_entities: np.ndarray  # bool (num_entities,): has a model
    # Entities dropped by the lower bound (passive-only).
    num_passive_only_entities: int
    num_passive_examples: int
    # Every bucket's entity count is a multiple of this (consumers chunking
    # the entity axis must keep slice lengths multiples of it to preserve
    # mesh-divisibility of sharded staging).
    entity_pad_multiple: int = 8
    # Entities that keep ``upper_bound`` of their rows for training (the
    # rest of their rows are passive).
    num_capped_entities: int = 0


def _next_pow2(x: int) -> int:
    return 1 << max(0, int(np.ceil(np.log2(max(1, x)))))


def build_bucketing(
    entity_ids: np.ndarray,
    num_entities: int,
    lower_bound: int = 1,
    upper_bound: Optional[int] = None,
    entity_pad_multiple: int = 8,
    min_capacity: int = 8,
    rng: Optional[np.random.Generator] = None,
    counts_all: Optional[np.ndarray] = None,
) -> EntityBucketing:
    """Group example rows by entity into padded power-of-two buckets.

    ``upper_bound`` caps examples per entity (reference
    numActiveDataPointsUpperBound: keeps a random subset); ``lower_bound``
    drops entities with too few examples from training entirely.
    ``counts_all`` optionally supplies the per-entity bincount of
    ``entity_ids`` precomputed elsewhere (the ingestion layer folds it
    while decoding — GameDataset.entity_counts), skipping one pass over
    the id column here; it MUST equal ``np.bincount(entity_ids)`` up to
    trailing zeros, and the result is identical either way.
    """
    entity_ids = np.asarray(entity_ids)
    n = entity_ids.shape[0]
    # Entity ids are rows into the entity table (non-negative, bounded) —
    # the int32 sort key below would silently mis-sort ids >= 2**31 and
    # bincount would raise on negatives, so turn violations into a loud
    # error here.
    if n and (int(entity_ids.min()) < 0
              or int(entity_ids.max()) >= num_entities):
        raise ValueError(
            f"entity ids must lie in [0, {num_entities}); got range "
            f"[{int(entity_ids.min())}, {int(entity_ids.max())}]")
    # Segments come from one bincount pass instead of np.unique's second
    # sort; int32 keys sort measurably faster than int64 at 10⁷ rows (the
    # narrowing is guarded: past int32 range keep the original dtype).
    sort_keys = (entity_ids.astype(np.int32, copy=False)
                 if num_entities <= 2**31 else entity_ids)
    order = np.argsort(sort_keys, kind="stable")
    if counts_all is None:
        counts_all = np.bincount(entity_ids)
    else:
        counts_all = np.asarray(counts_all)
        if int(counts_all.sum()) != n:
            raise ValueError(
                f"precomputed counts_all sums to {int(counts_all.sum())} "
                f"but the id column has {n} rows")
    uniq = np.flatnonzero(counts_all)
    counts = counts_all[uniq]
    starts = (np.cumsum(counts) - counts).astype(np.int64)

    trained = np.zeros(num_entities, bool)
    capped = counts if upper_bound is None else np.minimum(counts, upper_bound)
    keep = counts >= max(1, lower_bound)
    num_passive_only = int((~keep).sum())
    passive_examples = int(counts[~keep].sum())
    if upper_bound is not None:
        passive_examples += int((counts - capped)[keep].sum())

    # Bucket key: power-of-two capacity of the capped count. log2 of an
    # exact power of two is exact in float64, so ceil never overshoots.
    caps = np.maximum(
        min_capacity,
        1 << np.ceil(np.log2(np.maximum(capped, 1))).astype(np.int64))
    buckets: list[EntityBucket] = []
    for cap in np.unique(caps[keep]):
        sel = np.where(keep & (caps == cap))[0]
        e_b = len(sel)
        pad_e = ((e_b + entity_pad_multiple - 1) // entity_pad_multiple
                 ) * entity_pad_multiple
        ex = np.full((pad_e, int(cap)), -1, np.int64)
        rows = np.full((pad_e,), -1, np.int32)
        cnts = np.zeros((pad_e,), np.int32)
        # One padded gather for the whole class (no per-entity loop; at
        # 10⁶ entities the loop dominated staging): lane j of entity i
        # reads order[starts[i] + j] when j < its capped count.
        c_sel = capped[sel].astype(np.int64)
        lane = np.arange(int(cap), dtype=np.int64)[None, :]
        valid = lane < c_sel[:, None]
        src = np.minimum(starts[sel][:, None] + lane, n - 1)
        ex[:e_b] = np.where(valid, order[src], -1)
        if rng is not None:
            # Random capping draws per-entity subsets; only entities whose
            # count exceeds the cap need it (same rng call sequence as the
            # historical per-entity loop: ascending entity order).
            for i in np.flatnonzero(c_sel < counts[sel]):
                u = sel[i]
                pick = rng.choice(counts[u], size=int(c_sel[i]),
                                  replace=False)
                ex[i, :c_sel[i]] = order[starts[u] + pick]
        rows[:e_b] = uniq[sel]
        cnts[:e_b] = c_sel
        trained[uniq[sel]] = True
        buckets.append(EntityBucket(entity_rows=rows, example_idx=ex,
                                    counts=cnts))

    return EntityBucketing(
        buckets=buckets,
        num_entities=num_entities,
        trained_entities=trained,
        num_passive_only_entities=num_passive_only,
        num_passive_examples=passive_examples,
        entity_pad_multiple=entity_pad_multiple,
        num_capped_entities=int(((capped < counts) & keep).sum()),
    )


def gather_bucket_arrays(
    bucket: EntityBucket,
    *arrays: np.ndarray,
) -> tuple[np.ndarray, ...]:
    """Gather per-example arrays into the bucket's (E_b, cap, ...) layout.

    Padded slots gather row 0 but are masked by the zero weight produced by
    ``bucket_weights`` — callers must use that weight array.
    """
    idx = np.maximum(bucket.example_idx, 0)
    return tuple(a[idx] for a in arrays)


def bucket_weights(bucket: EntityBucket, weights: np.ndarray) -> np.ndarray:
    """Example weights in bucket layout with padding slots zeroed."""
    idx = np.maximum(bucket.example_idx, 0)
    w = weights[idx]
    w[bucket.example_idx < 0] = 0.0
    return w
