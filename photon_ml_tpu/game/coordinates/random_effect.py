"""Random-effect coordinate: per-entity vmapped solves over padded entity
buckets (P2), dense or subspace-projected, with optional sparse-shard input.

See the package docstring (photon_ml_tpu/game/coordinates/__init__.py) for
the residency discipline shared by all coordinate types.
"""

from __future__ import annotations

import concurrent.futures as cf
import dataclasses
import functools
import logging
import os
import threading
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu import obs
from photon_ml_tpu.data.batch import LabeledBatch
from photon_ml_tpu.data.game_data import GameDataset
from photon_ml_tpu.game import buckets as bkt
from photon_ml_tpu.game import projector as prj
from photon_ml_tpu.game import staging as stg
from photon_ml_tpu.game.models import (RandomEffectModel,
                                       SubspaceRandomEffectModel,
                                       _subspace_positions,
                                       sort_subspace_rows)
from photon_ml_tpu.normalization import NormalizationContext
from photon_ml_tpu.ops.losses import PointwiseLoss
from photon_ml_tpu.optim import optimize
from photon_ml_tpu.optim.common import scoped
from photon_ml_tpu.optim.problem import (GLMOptimizationConfiguration,
                                         VarianceComputationType,
                                         compute_variances, make_line_oracle,
                                         make_objective,
                                         resolve_optimizer_config,
                                         takes_line_oracle)
from photon_ml_tpu.parallel.mesh import DATA_AXIS, data_sharded

Array = jax.Array

logger = logging.getLogger("photon_ml_tpu.game")

# Sentinel distinguishing "use the coordinate's intercept" from an explicit
# None (projected buckets with no intercept column).
_UNSET = object()

# Max entity lanes per vmapped random-effect solve dispatch: the solver's
# carry/line-search temps scale with lanes, and one dispatch over ~600k
# lanes OOMs a 16 GB chip. 64k lanes keeps temps ~100 MB at typical widths
# while staying large enough to saturate the chip. Shared with the
# staging pipeline so staged shards == device dispatch chunks (one
# host→device put per produced shard, no re-slicing).
_LANE_CHUNK = stg.LANE_CHUNK

# What a fit wave counts inside its program, reduced over live lanes: the
# solver fields of its ``re_fit_wave`` ledger row (docs/OBSERVABILITY.md).
_WAVE_STATS = ("iters_sum", "iters_max", "evals_sum", "lanes_at_cap",
               "trials_sum", "hvp_sum", "hvp_wave", "floor_sum")


def _wave_stats(rows, iterations, evaluations, trials, hvp_history,
                max_iterations: int, floor_stop=None):
    """(8,) int32 in ``_WAVE_STATS`` order, over the lanes that hold an
    entity (``rows >= 0``; padding lanes solve a benign problem of their
    own). ``hvp_history`` is TRON's (lanes, iterations + 1) CG steps, None
    for a solver without them: ``hvp_sum`` the live lanes' own products,
    ``hvp_wave`` those the wave computed, every lane stepping each CG loop
    until its slowest lane stops. ``floor_stop`` is TRON's (lanes,) bool,
    None for the others: ``floor_sum`` the live lanes that ended at their
    objective's float32 floor. Stays on the device until the update's
    ledger drain."""
    live = rows >= 0
    its = jnp.where(live, iterations, 0)
    if hvp_history is None:
        hvps = jnp.zeros((2,), jnp.int32)
    else:
        hvps = jnp.stack([
            jnp.where(live, hvp_history.sum(axis=-1), 0).sum(),
            rows.shape[0] * hvp_history.max(axis=0).sum()])
    floor = (jnp.zeros((1,), jnp.int32) if floor_stop is None
             else (live & floor_stop).sum(keepdims=True))
    return jnp.concatenate([
        jnp.stack([its.sum(), its.max(),
                   jnp.where(live, evaluations, 0).sum(),
                   (its >= max_iterations).sum(),
                   jnp.where(live, trials, 0).sum()]),
        hvps, floor]).astype(jnp.int32)


def _wave_rows(pending):
    """The deferred spill of one update's fit waves (``RunLedger.defer``):
    one device read for all of them, made in the ledger's drain."""
    stats = iter(jax.device_get([st for _, st in pending if st is not None]))
    for fields, st in pending:
        if st is not None:
            fields.update(zip(_WAVE_STATS, map(int, next(stats))))
        yield "re_fit_wave", fields


# The dense table's score, x_i · W[e_i], as the two programs it always was
# (a row gather, a rowwise dot), now jitted so that they carry their scope.
# NOT one program: at 10M x 8 rows the fused gather-and-dot reads 36.3 ms
# on the v5e against 22.4 ms for the pair, bit for bit the same scores
# (PERF.md §6, PR 26).
@jax.jit
@scoped("re.score")
def _entity_rows(W, ids):
    return W[ids]


@jax.jit
@scoped("re.score")
def _rowwise_dot(X, R):
    return jnp.einsum("nd,nd->n", X, R)


@jax.jit
@scoped("re.score")
def _subspace_sparse_scores(W_flat, flatpos, values):
    """Σ_k values[i,k] · W_flat[flatpos[i,k]] with misses (flatpos ≥ |W|)
    contributing zero — one 1-D gather per ELL slot.

    The slot loop is a TPU layout constraint, not style: a single fused
    gather with (n, k, 1)-shaped indices forces the index operand into a
    (8, 128)-tiled copy whose minor dims pad 4→128 — at n=100M that copy
    is 51 GB and the COMPILE itself aborts with an HBM overflow (measured
    on v5e). Per-slot (n,) indices lay out densely; k is ELL-small, so
    the extra gathers cost nothing against the random-access wall.
    """
    lim = W_flat.shape[0]
    acc = jnp.zeros((flatpos.shape[0],), jnp.float32)
    for j in range(flatpos.shape[1]):
        pos = flatpos[:, j]
        g = W_flat[jnp.minimum(pos, lim - 1)] * (pos < lim)
        acc = acc + values[:, j].astype(jnp.float32) * g
    return acc


def _class_of(arrays) -> str:
    """A wave's program class, ``lanes x cap x width``: its feature
    block's shape."""
    return "x".join(str(int(v)) for v in arrays[0].shape)


def _struct(a) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding)


class _WavePrograms:
    """A coordinate's jitted per-bucket fit, and its shapes compiled ahead
    of the first sweep, side by side.

    One program per (lanes, capacity, width) class: a projected table over
    a few thousand columns has 14 of them, each 2 to 18 s of the TPU
    compiler, and a first sweep that meets them one after the other spends
    95 s compiling (PERF.md section 6, PR 35). The shapes are known once
    the blocks are planned, so ``compile_ahead`` lowers and compiles every
    one of them on a thread each while the host still stages, and a call
    runs the compiled program of its arguments' shapes; a shape that was
    not planned, or a program that refuses its arguments, goes through the
    jitted function as before. The same computation either way: one traced
    function, one set of compiler options."""

    def __init__(self, jitted):
        self.jitted = jitted
        self._compiled: dict[tuple, cf.Future] = {}
        self._planned = threading.Event()
        self._planned.set()  # nothing is being planned yet

    @staticmethod
    def _key(arrays) -> tuple:
        return tuple((tuple(a.shape), str(a.dtype)) for a in arrays)

    def compile_ahead(self, table, offsets, plan) -> None:
        """Start compiling the program for every tuple of shape structs
        ``plan()`` returns (it may block: it runs on a thread of its own);
        ``table`` and ``offsets`` are the structs of the leading two
        arguments."""
        self._planned.clear()

        def one(specs):
            return self.jitted.lower(table, offsets, *specs).compile()

        def run():
            try:
                todo = {self._key(specs): specs for specs in plan()}
                if todo:
                    pool = cf.ThreadPoolExecutor(
                        max_workers=min(len(todo), os.cpu_count() or 1),
                        thread_name_prefix="pml-re-compile")
                    for key, specs in todo.items():
                        self._compiled[key] = pool.submit(one, specs)
                    pool.shutdown(wait=False)
            except Exception as e:  # the stager failed: the fit will say
                logger.warning("wave programs not compiled ahead: %s: %s",
                               type(e).__name__, e)
            finally:
                self._planned.set()

        threading.Thread(target=run, daemon=True,
                         name="pml-re-compile-plan").start()

    def __call__(self, W, offsets, *arrays):
        # The fit thread's waits on the compilers, each a ``re.compile_wait``
        # row where it blocked; a steady sweep finds everything done
        if not self._planned.is_set():
            with obs.phase("re.compile_wait", program=_class_of(arrays)):
                self._planned.wait()
        key = self._key(arrays)
        pending = self._compiled.get(key)
        if pending is not None:
            if not pending.done():
                with obs.phase("re.compile_wait",
                               program=_class_of(arrays)):
                    cf.wait([pending])
            try:
                return pending.result()(W, offsets, *arrays)
            except (TypeError, ValueError) as e:
                # refused before anything ran (an argument's placement or
                # type is not what was planned): the jitted function takes
                # whatever it is given
                self._compiled.pop(key, None)
                logger.warning("compiled wave program refused its "
                               "arguments (%s); tracing it anew", e)
        return self.jitted(W, offsets, *arrays)

    def __getattr__(self, name):  # .lower and the like: the jitted one's
        return getattr(self.jitted, name)


@jax.jit
@scoped("re.score")
def _table_sparse_scores(W, ids, indices, values):
    """Σ_k values[i,k] · W[ids[i], indices[i,k]] against the dense (E, d)
    table, one 1-D gather per ELL slot of the flattened table. The fused
    form, ``W[ids[:, None], indices]``, lays its (n, k) index and gathered
    operands out in tiles whose minor dimension pads k to 128 lanes: at 2M
    rows x 14 slots the v5e's compiler counts 2.05 GB of scratch for it
    against 0.37 GB for the slot loop (``_subspace_sparse_scores`` has the
    same reason). ELL padding slots carry value 0 by contract, so clamping
    their sentinel index (== d) into range is exact."""
    d = W.shape[1]
    W_flat = W.reshape(-1)
    base = ids * d
    acc = jnp.zeros((ids.shape[0],), jnp.float32)
    for j in range(indices.shape[1]):
        g = W_flat[base + jnp.minimum(indices[:, j], d - 1)]
        acc = acc + values[:, j].astype(jnp.float32) * g
    return acc


class RandomEffectCoordinate:
    """Per-entity GLMs trained as vmapped bucket solves.

    Reference parity: RandomEffectCoordinate + SingleNodeOptimizationProblem
    (per-entity local L-BFGS inside mapValues) — here all entities of a
    bucket solve simultaneously under vmap with convergence masks.

    Model-space contract: same as FixedEffectCoordinate — solves run in the
    shard's normalization-transformed space; the RandomEffectModel rows are
    ORIGINAL-space, so scoring is the plain gather + rowwise dot everywhere.

    ``projection=True`` enables the per-entity feature-subspace projector
    (reference: LinearSubspaceProjector + IndexMapProjectorRDD, SURVEY §2.1/
    §2.2): each bucket stages features at d_active ≪ d (the union of columns
    its entities actually use), solves in the projected space, and scatters
    coefficients back to full-space rows — the difference between feasible
    and OOM when the RE feature space is large and per-entity sparse.
    """

    def __init__(
        self,
        dataset: GameDataset,
        re_type: str,
        shard_id: str,
        loss: PointwiseLoss,
        config: GLMOptimizationConfiguration,
        mesh,
        lower_bound: int = 1,
        upper_bound: Optional[int] = None,
        norm: NormalizationContext = NormalizationContext(),
        seed: int = 0,
        projection: bool = False,
        features_to_samples_ratio: Optional[float] = None,
        subspace_model: Optional[bool] = None,
        staging_cache_dir: Optional[str] = None,
        feature_dtype: str = "float32",
        staging: Optional[stg.StagingConfig] = None,
    ):
        from photon_ml_tpu.data.game_data import SparseShard
        if feature_dtype not in ("float32", "bfloat16"):
            # Before staging: at flagship scale the projection pass below
            # costs minutes, and a typo'd dtype must not pay it first.
            raise ValueError(f"unsupported feature_dtype {feature_dtype!r}")
        self.is_sparse = isinstance(dataset.feature_shards[shard_id],
                                    SparseShard)
        if self.is_sparse:
            # Large-d per-entity sparse features are exactly the
            # subspace-projection regime (reference: RandomEffectDataset
            # keeps per-entity sparse Breeze rows and projects them via
            # IndexMapProjectorRDD) — projection is implied; the dense
            # (n, d) shard never exists, buckets stage at d_active ≪ d
            # straight from the ELL triplets.
            projection = True
            if norm.factors is not None or norm.shifts is not None:
                raise ValueError(
                    f"normalization is not supported on sparse random-"
                    f"effect shard {shard_id!r} (scaling sparse values "
                    f"would densify shift terms)")
        self.dataset = dataset
        self.re_type = re_type
        self.shard_id = shard_id
        self.loss = loss
        self.config = config
        self.mesh = mesh
        self.norm = norm
        self.num_entities = dataset.num_entities[re_type]
        self.intercept_index = dataset.intercept_index.get(shard_id)
        with obs.phase("re.bucketing", re_type=re_type):
            self.bucketing = bkt.build_bucketing(
                dataset.entity_ids[re_type], self.num_entities,
                lower_bound=lower_bound, upper_bound=upper_bound,
                entity_pad_multiple=max(
                    8, int(np.prod(list(mesh.shape.values())))),
                rng=np.random.default_rng(seed),
                counts_all=dataset.entity_counts.get(re_type))
        with obs.phase("re.transfer", re_type=re_type) as ph:
            # The score-side copies: every row's features and entity id.
            if self.is_sparse:
                shard = dataset.feature_shards[shard_id]
                self._sp_indices = jnp.asarray(shard.indices)
                self._sp_values = jnp.asarray(shard.values)
                self._X = None
                ph["bytes"] = int(self._sp_indices.nbytes
                                  + self._sp_values.nbytes)
            else:
                self._X = jnp.asarray(dataset.feature_shards[shard_id])
                ph["bytes"] = int(self._X.nbytes)
            self._ids = jnp.asarray(dataset.entity_ids[re_type])
            ph["bytes"] += int(self._ids.nbytes)
        # Pearson feature filtering selects per-entity columns, which is
        # exactly what the projection machinery stages — a ratio implies
        # projection (reference: filterFeaturesByPearsonCorrelationScore
        # runs during RandomEffectDataset build when
        # numFeaturesToSamplesRatio is configured).
        self.features_to_samples_ratio = features_to_samples_ratio
        self.projection = bool(projection) or (
            features_to_samples_ratio is not None)
        # Subspace model representation (reference:
        # RandomEffectModelInProjectedSpace): the trained table stays
        # (E, A) in each entity's active-column space instead of the dense
        # (E, d) — mandatory at the scale where E·d is unmaterializable.
        # Auto-on when the dense table would exceed ~1 GiB.
        if subspace_model is None:
            subspace_model = (self.projection and
                              self.num_entities * self.dim > (1 << 28))
        if subspace_model and not self.projection:
            raise ValueError(
                "subspace_model=True requires projection=True (the "
                "subspace IS the per-entity projection)")
        self.subspace = bool(subspace_model)
        # Stage static per-bucket device arrays ONCE: features/labels/weights
        # in (E_b, cap, …) layout plus the gather/scatter index maps. The
        # entity axis is sharded over the mesh's data axis (P2) when the
        # padded entity count divides it. With projection on, features are
        # staged directly at (E_b, cap, d_active) and each tuple carries the
        # (E_b, d_active) column map plus projected normalization arrays —
        # produced by the parallel pipelined stager (game/staging.py) and
        # consumed lazily by the fit stream (_iter_bucket_data), so the
        # first per-entity fits dispatch while later shards still project.
        self._bucket_data = []
        # Host copies of each staged tuple's (E_b,) entity-row map, in
        # fit-stream order: which lanes hold an entity (the refit counter
        # and the wave rows' ``entities_fit``).
        self._host_rows: list[np.ndarray] = []
        # Beside them, each lane's true row count (the rest of its
        # ``cap`` slots is padding): the wave rows' ``rows_useful``.
        self._host_counts: list[np.ndarray] = []
        # And, where the lanes are projected, the columns each lane's own
        # subspace has (the rest of its ``d_active`` slots is padding): the
        # wave rows' ``cols_useful``. None for a wave at the shard's width.
        self._host_active: list[Optional[np.ndarray]] = []
        self._layout_recorded = False
        self._pending = None
        self._stager = None
        self.staging = staging or stg.StagingConfig()
        self.feature_dtype = feature_dtype
        ds = dataset
        X = ds.feature_shards[shard_id]
        self._n_data = mesh.shape[DATA_AXIS]

        # Shifts without factors cannot occur via build_normalization; guard
        # the manual case so the projected solve has one layout.
        f_full = None if norm.factors is None else np.asarray(norm.factors)
        s_full = None if norm.shifts is None else np.asarray(norm.shifts)
        if s_full is not None and f_full is None:
            f_full = np.ones_like(s_full)

        # Projected staging products persist on disk keyed by dataset
        # content + staging params (photon_ml_tpu/game/staging_cache.py),
        # shard-granular: a warm re-fit of the same data memory-maps the
        # staged blocks instead of re-paying the projection pass, and a
        # partial entry (killed run) restages only its missing shards.
        from photon_ml_tpu.game import staging_cache

        self._staging_cache_key = None
        if staging_cache_dir and self.projection:
            self._staging_cache_key = staging_cache.staging_key(
                dataset, norm, re_type=re_type, shard_id=shard_id,
                lower_bound=lower_bound, upper_bound=upper_bound,
                seed=seed, pad=self.bucketing.entity_pad_multiple,
                ratio=self.features_to_samples_ratio,
                intercept=self.intercept_index, subspace=self.subspace,
                # Declared dimensions the array digest cannot see: the
                # staged entity tables and the subspace join sentinels
                # depend on both. The shard size shapes the per-shard
                # file layout, so it keys too.
                num_entities=self.num_entities, dim=self.dim,
                shard_entities=stg.resolved_shard_entities(
                    self.staging, self.bucketing.entity_pad_multiple))

        if self.projection:
            self._stager = stg.ProjectionStager(
                bucketing=self.bucketing, X=X,
                response=np.asarray(ds.response),
                weights=np.asarray(ds.weights),
                intercept_index=self.intercept_index,
                features_to_samples_ratio=self.features_to_samples_ratio,
                factors=f_full, shifts=s_full,
                config=self.staging,
                cache_dir=staging_cache_dir,
                cache_key=self._staging_cache_key,
                expect_subspace=self.subspace,
                label=f"{re_type}:{shard_id}")
            self._pending = self._stager.shards()
            sub = {}
            if self.subspace:
                sub = self._stager.cached_subspace()
                if sub is not None and self.is_sparse and "flat" not in sub:
                    sub = None  # incomplete record: recompute
                if sub is None:
                    # (E, A) active-column table: each entity lives in
                    # exactly one bucket, so its model row is its bucket
                    # row padded to the widest bucket's d_active. The
                    # PUBLIC model layout sorts each row by column id
                    # (padding last) so SubspaceRandomEffectModel.score
                    # can join new datasets with a device-side
                    # searchsorted; the bucket-internal layout (intercept
                    # slot 0) is reached through the stored permutation
                    # at the train/warm-start boundary. Blocks only on
                    # the pipeline's pair-extraction phase — the feature
                    # gathers keep overlapping with whatever runs next.
                    shard_cols = self._stager.cols_list()
                    A = max((c.shape[1] for c in shard_cols), default=1)
                    cols_tab = np.full((self.num_entities, A), -1,
                                       np.int32)
                    for (bi, lo, hi), c in zip(self._stager.plan,
                                               shard_cols):
                        rows_s = self.bucketing.buckets[bi].entity_rows[
                            lo:hi]
                        live = rows_s >= 0
                        cols_tab[rows_s[live], : c.shape[1]] = c[live]
                    cols_sorted, perm = sort_subspace_rows(cols_tab)
                    sub = {"cols": cols_sorted, "perm": perm}
                    if self.is_sparse:
                        # Stage the score-side join ONCE: data nonzeros →
                        # flat slots of the (E, A) table (E*A = miss/
                        # passive → 0).
                        flat = _subspace_positions(
                            cols_sorted, self.dim,
                            np.asarray(ds.entity_ids[re_type]),
                            np.asarray(
                                dataset.feature_shards[shard_id].indices))
                        fp_dtype = (np.int32
                                    if cols_sorted.size < 2**31 - 1
                                    else np.int64)
                        sub["flat"] = flat.astype(fp_dtype)
                self._stager.set_subspace(sub)
        else:
            # Unprojected path: dense gathers, cheap relative to the
            # projection wall — staged eagerly as before.
            host_buckets: list[tuple] = []
            with obs.phase("re.host_stage", re_type=re_type):
                for b in self.bucketing.buckets:
                    wb = bkt.bucket_weights(b, ds.weights)
                    ex = b.example_idx.astype(np.int32)  # (E_b, cap); -1 pad
                    rows = b.entity_rows  # (E_b,) int32; -1 padding
                    Xb, yb = bkt.gather_bucket_arrays(b, X, ds.response)
                    host_buckets.append((Xb, yb, wb, ex, rows))
            sub = {}
            for arrays in host_buckets:
                self._stage_host_tuple(arrays)
            self._pending = None
            self._record_layout()
        if self.subspace:
            cols_sorted = np.asarray(sub["cols"])
            perm = np.asarray(sub["perm"])
            self.subspace_cols = cols_sorted
            # Model-adjacent arrays stay process-local (NOT mesh-sharded),
            # mirroring the dense path's W table: the trained model must be
            # host-fetchable on rank 0 for checkpoints/saves, and a
            # mesh-sharded cols table would span non-addressable devices
            # in multi-host runs. Bucket DATA arrays remain sharded.
            self._cols_dev = jnp.asarray(cols_sorted)
            self._perm_dev = jnp.asarray(perm)
            self._inv_perm_dev = jnp.asarray(
                np.argsort(perm, axis=1, kind="stable").astype(np.int32))
            if self.is_sparse:
                # Like _sp_values: score-side arrays stay process-local.
                flat = np.asarray(sub["flat"])
                if flat.dtype == np.int64:
                    # Device arrays are int32 (x64 off): a silent
                    # jnp.asarray downcast would wrap flat positions
                    # ≥ 2^31 into valid-looking wrong indices and score
                    # garbage. Refuse with the actionable alternatives.
                    if flat.max(initial=0) >= np.iinfo(np.int32).max:
                        raise ValueError(
                            f"subspace flat positions exceed int32 "
                            f"(E×A = {int(self.subspace_cols.size)}): "
                            "split this random effect into smaller "
                            "coordinates or reduce active columns "
                            "(features_to_samples_ratio / upper_bound)")
                    flat = flat.astype(np.int32)
                self._sp_flatpos = jnp.asarray(flat)
                # The raw column ids are only needed by the dense-table
                # score path — free the device copy at scale.
                self._sp_indices = None
        self._build_fits()

    def _put(self, a):
        if a.shape[0] % self._n_data == 0:
            return jax.device_put(a, data_sharded(self.mesh, a.ndim))
        return jnp.asarray(a)

    def _stage_host_tuple(self, arrays) -> None:
        """Split one staged host tuple into ≤ _LANE_CHUNK-lane device
        tuples appended to the fit stream.

        The lane bound caps the vmapped-solve footprint: a single
        dispatch over hundreds of thousands of entity lanes exhausts HBM
        on solver temps (the L-BFGS carry and line-search buffers scale
        with lanes). The chunk is rounded UP to a multiple of this
        coordinate's entity pad so every slice keeps the divisibility
        _put() needs to shard. Pipeline shards default to exactly this
        chunk, making the split a no-op slice; bigger explicit
        shard_entities still re-split here.

        bf16 feature STORAGE happens here (same contract as the dense
        fixed path: aggregators accumulate in f32) — after the staging
        cache, which stays f32 and dtype-independent, so only the staged
        bucket blocks shrink."""
        feat_cast = (jnp.bfloat16 if self.feature_dtype == "bfloat16"
                     else None)
        pad = self.bucketing.entity_pad_multiple
        chunk = ((_LANE_CHUNK + pad - 1) // pad) * pad
        E_b = arrays[4].shape[0]
        with obs.phase("re.transfer", re_type=self.re_type, bytes=0) as ph:
            for lo in range(0, E_b, chunk):
                hi = min(lo + chunk, E_b)
                tup = []
                for ai, a in enumerate(arrays):
                    a = np.asarray(a)[lo:hi]
                    if ai == 0 and feat_cast is not None:  # Xb block
                        a = a.astype(feat_cast)
                    if ai == 3:  # example map: rows each lane really has
                        self._host_counts.append(
                            (a >= 0).sum(axis=1).astype(np.int64))
                    if ai == 4:  # entity rows: which lanes are live
                        self._host_rows.append(np.array(a, copy=True))
                    if ai == 5:  # column map: each lane's own subspace
                        self._host_active.append(
                            (a >= 0).sum(axis=1).astype(np.int64))
                    tup.append(self._put(a))
                    ph["bytes"] += int(a.nbytes)
                if len(arrays) < 6:
                    self._host_active.append(None)
                self._bucket_data.append(tuple(tup))

    def _iter_bucket_data(self):
        """The fit stream: already-staged device tuples first, then — on
        the first full pass — the remaining pipeline shards in plan
        order, device-put as each arrives. This is the consumer side of
        the bounded producer/consumer handoff: while the device fits
        shard i, the worker pool is still projecting shards > i, and at
        most pipeline_depth staged-but-unconsumed host blocks exist.
        Single-consumer by contract (coordinate descent trains
        coordinates sequentially)."""
        i = 0
        while True:
            if i < len(self._bucket_data):
                yield self._bucket_data[i]
                i += 1
                continue
            if self._pending is None:
                return
            try:
                host = next(self._pending)
            except StopIteration:
                self._pending = None
                self._record_layout()
                return
            self._stage_host_tuple(host)

    def _pending_blocks(self):
        """(lanes, cap, width) of every device tuple the stager's shards
        will become, as ``_stage_host_tuple`` splits them. Known once every
        class has its width: blocks on the stager's phase A, not on the
        feature layout."""
        pad = self.bucketing.entity_pad_multiple
        chunk = ((_LANE_CHUNK + pad - 1) // pad) * pad
        for (bi, lo, hi), cols in zip(self._stager.plan,
                                      self._stager.cols_list()):
            cap = self.bucketing.buckets[bi].capacity
            for a in range(0, hi - lo, chunk):
                yield min(chunk, hi - lo - a), cap, int(cols.shape[1])

    def deferred_device_bytes(self) -> int:
        """Bytes this coordinate has yet to put on the device: the projected
        blocks the pipelined stager hands over as the first update consumes
        them, which exist nowhere on the device while the coordinates built
        after this one stage theirs. 0 for an unprojected coordinate, which
        stages in its constructor."""
        if self._pending is None:
            return 0
        cell = 2 if self.feature_dtype == "bfloat16" else 4
        extra = 4 * ((self.norm.factors is not None)
                     + (self.norm.shifts is not None))
        # features; labels, weights, row ids; the lane's entity; the column
        # map and the projected normalization arrays
        total = sum(lanes * (cap * (width * cell + 12) + 4
                             + width * (4 + extra))
                    for lanes, cap, width in self._pending_blocks())
        staged = sum(int(a.nbytes) for t in self._bucket_data for a in t)
        return max(0, total - staged)

    def _record_layout(self) -> None:
        """One ``re_layout`` row, as the fixed effect's ``fe_layout`` is:
        what this coordinate staged, a class a row capacity. Written once,
        when the last block is on the device."""
        led = obs.ledger()
        if led is None or self._layout_recorded:
            return
        self._layout_recorded = True
        classes: dict[int, list] = {}
        staged = useful = 0
        cell = 2 if self.feature_dtype == "bfloat16" else 4
        for t, rows, counts, active in zip(
                self._bucket_data, self._host_rows, self._host_counts,
                self._host_active):
            lanes, cap, width = (int(v) for v in t[0].shape)
            c = classes.setdefault(cap, [0, 0, width])
            c[0] += lanes
            c[1] += int((rows >= 0).sum())
            staged += sum(int(a.nbytes) for a in t)
            live = rows >= 0
            useful += cell * int((counts[live] * (
                width if active is None else active[live])).sum())
        b = self.bucketing
        led.record(
            "re_layout", re_type=self.re_type, shard=self.shard_id,
            model_form="subspace" if self.subspace else "dense",
            projected=bool(self.projection), dim=int(self.dim),
            classes=[[cap, *classes[cap]] for cap in sorted(classes)],
            lanes=sum(c[0] for c in classes.values()),
            entities=int(b.trained_entities.sum()),
            entities_capped=int(b.num_capped_entities),
            staged_bytes=staged, useful_bytes=useful)

    def wait_staged(self) -> "RandomEffectCoordinate":
        """Barrier: drain the staging pipeline onto the device without
        fitting anything (the pre-pipelining behavior; also what tests
        use to compare pipelined vs barrier staging)."""
        for _ in self._iter_bucket_data():
            pass
        if self._stager is not None:
            self._stager.join()  # staging-cache writes included
        return self

    def _build_fits(self):
        """(Re)build the cached jitted per-bucket fit/variance programs.

        ``fit_bucket`` keeps the whole inner step on device: gather each
        entity's offsets and warm start (scope ``re.gather``), run the
        vmapped masked-lane solve (``re.solve``), scatter trained rows back
        into the (E, d) table (``re.scatter``). Padding lanes (rows == -1)
        are redirected to an out-of-bounds index and dropped by the
        scatter. Beside the table it returns the wave's solver counts
        (``_wave_stats``), which nobody reads unless a run ledger is open.
        One executable per bucket SHAPE, cached by jit across buckets and
        coordinate-descent iterations.

        Projected variant: warm starts are gathered through each entity's
        column map (original space, since transforms are per-entity), solved
        at d_active with a per-entity NormalizationContext, mapped back to
        original space in-lane, and scattered through the column map; the
        W table stays in ORIGINAL space throughout.
        """
        num_entities = self.num_entities
        if self.projection:
            fit, self._var_bucket = self._build_projected_fits()
            self._fit_bucket = _WavePrograms(fit)
            self._compile_ahead()
            return
        solve = jax.vmap(self._solve_one)
        var_one = jax.vmap(self._variance_one)
        _gather_rows, _scatter_rows = self._row_movers()
        max_it = self.config.optimizer.max_iterations

        def fit_bucket(W, offsets, Xb, yb, wb, ex, rows):
            with jax.named_scope("re.gather"):
                ob = offsets[jnp.maximum(ex, 0)]
                w0 = _gather_rows(W, rows)
            with jax.named_scope("re.solve"):
                w_fit, *counts, floor = solve(Xb, yb, wb, ob, w0)
                stats = _wave_stats(rows, *counts, max_it, floor)
            with jax.named_scope("re.scatter"):
                return _scatter_rows(W, rows, w_fit), stats

        def var_bucket(W, V, offsets, Xb, yb, wb, ex, rows):
            ob = offsets[jnp.maximum(ex, 0)]
            w_opt = _gather_rows(W, rows)
            var = var_one(Xb, yb, wb, ob, w_opt)
            return _scatter_rows(V, rows, var)

        # Donate the table being rebuilt (W for fits, V for variances) so the
        # scatter updates in place instead of copying (E, d) per bucket.
        self._fit_bucket = _WavePrograms(
            jax.jit(fit_bucket, donate_argnums=(0,)))
        self._var_bucket = jax.jit(var_bucket, donate_argnums=(1,))
        self._compile_ahead()

    def _compile_ahead(self) -> None:
        """Hand the wave programs every shape the fit stream will dispatch:
        the tuples already on the device as they are, the stager's pending
        shards as ``_stage_host_tuple`` will split and place them (known
        once each class has its width: the stager's phase A). On one device
        only: across a mesh the table's and the offsets' placements are the
        caller's, and the jitted function follows them."""
        if self.mesh.devices.size != 1:
            return
        staged = [tuple(map(_struct, t)) for t in self._bucket_data]
        pending = self._pending is not None

        def put_struct(shape, dtype):
            sharded = shape[0] % self._n_data == 0
            return jax.ShapeDtypeStruct(
                shape, jax.dtypes.canonicalize_dtype(dtype),
                sharding=(data_sharded(self.mesh, len(shape)) if sharded
                          else None))

        def plan():
            specs = list(staged)
            if not pending:
                return specs
            ds = self.dataset
            feat = (jnp.bfloat16 if self.feature_dtype == "bfloat16"
                    else jnp.float32)
            extra = (self.norm.factors is not None) + (
                self.norm.shifts is not None)
            for lanes, cap, width in self._pending_blocks():
                specs.append((
                    put_struct((lanes, cap, width), feat),
                    put_struct((lanes, cap), np.asarray(ds.response).dtype),
                    put_struct((lanes, cap), np.asarray(ds.weights).dtype),
                    put_struct((lanes, cap), np.int32),
                    put_struct((lanes,), np.int32),
                    put_struct((lanes, width), np.int32),
                ) + (put_struct((lanes, width), np.float32),) * extra)
            return specs

        shape = (self.subspace_cols.shape if self.subspace
                 else (self.num_entities, self.dim))
        self._fit_bucket.compile_ahead(
            jax.ShapeDtypeStruct(shape, jnp.float32),
            jax.ShapeDtypeStruct((self.dataset.num_rows,), jnp.float32),
            plan)

    def _row_movers(self):
        """The bucket layout's row moves — warm-start gather, fitted-row
        scatter — resolved against the kernel registry at program-build
        time (docs/KERNELS.md): both can run as scalar-prefetch Pallas
        programs (``re_gather_rows``/``re_scatter_rows``). Both are pure
        data movement, so a backend flip is bit-exact by construction and
        the refit bit-identity invariant holds either way. Projected fits
        keep the XLA moves: their gathers route through per-entity column
        maps, a different access pattern (docs/KERNELS.md "What stays
        XLA")."""
        num_entities = self.num_entities
        from photon_ml_tpu.ops import kernels as _kernels
        _reg = _kernels.registry()
        gather_k = scatter_k = None
        if _reg.enabled("re_gather_rows"):
            rk = _reg.resolve("re_gather_rows")
            if rk.backend == "pallas":
                gather_k = rk
        if _reg.enabled("re_scatter_rows"):
            rk = _reg.resolve("re_scatter_rows")
            if rk.backend == "pallas":
                scatter_k = rk

        def _gather_rows(W, rows):
            if gather_k is not None:
                return gather_k(W, rows)
            return W[jnp.maximum(rows, 0)]

        def _scatter_rows(W, rows, vals):
            if scatter_k is not None:
                return scatter_k(W, rows, vals)
            safe = jnp.where(rows >= 0, rows, num_entities)
            return W.at[safe].set(vals, mode="drop")

        return _gather_rows, _scatter_rows

    def _build_projected_fits(self):
        """Jitted per-bucket programs for the projected (d_active) path."""
        num_entities = self.num_entities
        dim = self.dim
        has_f = not (self.norm.factors is None and self.norm.shifts is None)
        has_s = self.norm.shifts is not None
        ii_proj = 0 if self.intercept_index is not None else None

        def ctx_for(f, s):
            if not has_f:
                return NormalizationContext()
            return NormalizationContext(factors=f, shifts=s,
                                        intercept_index=ii_proj)

        def solve_one(X, y, w, o, w0_orig, f, s):
            """One entity's projected solve; original space in and out."""
            ctx = ctx_for(f, s)
            w0 = ctx.model_to_transformed_space(w0_orig)
            w_t, *counts = self._solve_one(X, y, w, o, w0, norm=ctx,
                                           intercept_index=ii_proj)
            return ctx.model_to_original_space(w_t), *counts

        def var_one(X, y, w, o, w_orig, f, s):
            ctx = ctx_for(f, s)
            w_t = ctx.model_to_transformed_space(w_orig)
            var_t = self._variance_one(X, y, w, o, w_t, norm=ctx,
                                       intercept_index=ii_proj)
            return ctx.variances_to_original_space(var_t)

        # vmap lanes: norm arrays are per-entity when present, else closed
        # over as None (static).
        norm_axes = (0 if has_f else None, 0 if has_s else None)
        vsolve = jax.vmap(solve_one, in_axes=(0, 0, 0, 0, 0) + norm_axes)
        vvar = jax.vmap(var_one, in_axes=(0, 0, 0, 0, 0) + norm_axes)

        def unpack(extra):
            cols = extra[0]
            f = extra[1] if has_f else None
            s = extra[2 if has_f else 1] if has_s else None
            return cols, f, s

        def gathers(W, offsets, ex, rows, cols):
            ob = offsets[jnp.maximum(ex, 0)]
            valid = (cols >= 0).astype(W.dtype)
            w0 = W[jnp.maximum(rows, 0)[:, None],
                   jnp.maximum(cols, 0)] * valid
            safe_rows = jnp.where(rows >= 0, rows, num_entities)
            safe_cols = jnp.where(cols >= 0, cols, dim)
            return ob, w0, safe_rows, safe_cols

        subspace = self.subspace
        max_it = self.config.optimizer.max_iterations

        def sub_gathers(W, offsets, ex, rows, da):
            """Subspace-table layout: the entity's model row IS its bucket
            row (same active-column order), so warm starts are a plain row
            gather + static slice to this bucket's width."""
            ob = offsets[jnp.maximum(ex, 0)]
            w0 = W[jnp.maximum(rows, 0)][:, :da]
            safe_rows = jnp.where(rows >= 0, rows, num_entities)
            return ob, w0, safe_rows

        def solve(rows, *args):
            with jax.named_scope("re.solve"):
                w_fit, *counts, floor = vsolve(*args)
                return w_fit, _wave_stats(rows, *counts, max_it, floor)

        def fit_bucket(W, offsets, Xb, yb, wb, ex, rows, *extra):
            cols, f, s = unpack(extra)
            if subspace:
                da = cols.shape[1]
                with jax.named_scope("re.gather"):
                    ob, w0, safe_rows = sub_gathers(W, offsets, ex, rows, da)
                w_fit, stats = solve(rows, Xb, yb, wb, ob, w0, f, s)
                with jax.named_scope("re.scatter"):
                    # Whole-row set: the padding tail past d_active stays
                    # zero.
                    w_pad = jnp.pad(w_fit, ((0, 0), (0, W.shape[1] - da)))
                    return W.at[safe_rows].set(w_pad, mode="drop"), stats
            with jax.named_scope("re.gather"):
                ob, w0, safe_rows, safe_cols = gathers(W, offsets, ex, rows,
                                                       cols)
            w_fit, stats = solve(rows, Xb, yb, wb, ob, w0, f, s)
            with jax.named_scope("re.scatter"):
                # projectBackward semantics: a trained entity's FULL row is
                # rewritten — zero it first so inactive-column mass from an
                # external (e.g. unprojected) warm start cannot survive.
                W = W.at[safe_rows].set(0.0, mode="drop")
                return W.at[safe_rows[:, None], safe_cols].set(
                    w_fit, mode="drop"), stats

        def var_bucket(W, V, offsets, Xb, yb, wb, ex, rows, *extra):
            cols, f, s = unpack(extra)
            if subspace:
                da = cols.shape[1]
                ob, w_opt, safe_rows = sub_gathers(W, offsets, ex, rows, da)
                var = vvar(Xb, yb, wb, ob, w_opt, f, s)
                v_pad = jnp.pad(var, ((0, 0), (0, V.shape[1] - da)))
                return V.at[safe_rows].set(v_pad, mode="drop")
            ob, w_opt, safe_rows, safe_cols = gathers(W, offsets, ex, rows,
                                                      cols)
            var = vvar(Xb, yb, wb, ob, w_opt, f, s)
            return V.at[safe_rows[:, None], safe_cols].set(var, mode="drop")

        return (jax.jit(fit_bucket, donate_argnums=(0,)),
                jax.jit(var_bucket, donate_argnums=(1,)))

    def _solve_one(self, X, y, w, o, w0, norm=None, intercept_index=_UNSET):
        """One entity's GLM solve in transformed space (vmapped per bucket):
        the fitted row, and the iterations, objective evaluations and
        line-search trials the solver took for it, and under TRON the CG
        steps of each iteration and whether it ended at its objective's
        float32 floor (None under the others).

        The projected path passes a per-entity NormalizationContext and the
        projected intercept slot; the unprojected path uses the coordinate's
        own (closed-over) full-space values.

        L-BFGS takes the objective apart as a ``LineOracle``
        (``_line_oracle``): a wave's line searches then read rows, and an
        iteration of a lane is one pair of passes over its block whatever
        the slowest lane's search needed.
        """
        norm = self.norm if norm is None else norm
        ii = self.intercept_index if intercept_index is _UNSET \
            else intercept_index
        batch = LabeledBatch(X, y, w, o)
        problem = (self.loss, batch, norm, self.config.regularization, ii,
                   X.shape[-1])
        vg, hvp, l1w = make_objective(*problem)
        opt_cfg = resolve_optimizer_config(
            self.config.optimizer, l1w is not None)
        line = make_line_oracle(*problem) if self._line_oracle else None
        result = optimize(vg, w0, opt_cfg, hvp=hvp, l1_weights=l1w,
                          line=line)
        trials = result.trials  # TRON has no line search
        return (result.w, result.iterations, result.evaluations,
                jnp.zeros_like(result.iterations) if trials is None
                else trials, result.hvp_history,  # TRON's alone
                getattr(result, "floor_stop", None))

    @property
    def _line_oracle(self) -> bool:
        """Whether the lane solves' line searches go through a
        ``LineOracle`` (``optim/problem.takes_line_oracle``)."""
        return takes_line_oracle(self.config)

    def _variance_one(self, X, y, w, o, w_opt, norm=None,
                      intercept_index=_UNSET):
        """Variances at the trained optimum (no re-solve; reference
        computeVariances evaluates the Hessian at the model coefficients)."""
        norm = self.norm if norm is None else norm
        ii = self.intercept_index if intercept_index is _UNSET \
            else intercept_index
        batch = LabeledBatch(X, y, w, o)
        return compute_variances(
            self.loss, w_opt, batch, norm,
            self.config.variance_computation, self.config.regularization,
            ii)

    @property
    def dim(self) -> int:
        return self.dataset.shard_dim(self.shard_id)

    def with_optimization_config(
        self, config: GLMOptimizationConfiguration
    ) -> "RandomEffectCoordinate":
        """Cheap copy with a new optimization config, reusing the bucketing
        and the staged per-bucket device arrays (the expensive part of
        __init__). Only the jitted programs are rebuilt."""
        import copy

        c = copy.copy(self)
        c.config = config
        c._build_fits()
        return c

    def adapt_initial(self, initial):
        """Accept a factored warm start by materializing its implied
        full-rank (E, d) table (reference: the factored coordinate hands
        RandomEffectModels to neighboring coordinate updates). In subspace
        mode, dense warm starts are additionally gathered into this
        coordinate's (E, A) active-column layout — inactive-column mass
        cannot survive a projected retrain anyway (projectBackward)."""
        from photon_ml_tpu.game.factored import FactoredRandomEffectModel

        if isinstance(initial, FactoredRandomEffectModel):
            initial = initial.to_random_effect_model()
        if not self.subspace:
            if isinstance(initial, SubspaceRandomEffectModel):
                return initial.to_random_effect_model()
            return initial
        if isinstance(initial, SubspaceRandomEffectModel):
            if initial.cols.shape[0] != self.subspace_cols.shape[0]:
                raise ValueError(
                    f"subspace warm start has {initial.cols.shape[0]} "
                    f"entities, coordinate expects "
                    f"{self.subspace_cols.shape[0]}")
            if initial.num_features != self.dim:
                raise ValueError(
                    f"subspace warm start has {initial.num_features} "
                    f"features, coordinate expects {self.dim} (the "
                    f"searchsorted sentinels would collide with real "
                    f"column ids)")
            if np.array_equal(np.asarray(initial.cols),
                              self.subspace_cols):
                return initial
            # Active sets differ (e.g. bucket bounds changed between
            # runs): re-map per entity via sorted-row searchsorted —
            # coefficients for columns no longer active are dropped
            # (projectBackward semantics), never misattributed.
            src_c = jnp.asarray(initial.cols)
            src_s = jnp.where(src_c < 0, self.dim + 1, src_c)
            tgt = jnp.asarray(self.subspace_cols)
            tgt_q = jnp.where(tgt < 0, self.dim + 2, tgt)  # never matches
            pos = jax.vmap(jnp.searchsorted)(src_s, tgt_q)
            posc = jnp.minimum(pos, src_c.shape[1] - 1)
            hit = jnp.take_along_axis(src_s, posc, axis=1) == tgt_q
            means = jnp.take_along_axis(
                jnp.asarray(initial.means), posc, axis=1) * hit
            return SubspaceRandomEffectModel(
                re_type=self.re_type, shard_id=self.shard_id,
                num_features=self.dim, cols=tgt, means=means)
        # Dense (E, d) → gather the active columns per entity.
        if initial.means.shape[0] != self.subspace_cols.shape[0]:
            raise ValueError(
                f"warm start has {initial.means.shape[0]} entities, "
                f"coordinate expects {self.subspace_cols.shape[0]} "
                f"(a clamped gather would misattribute rows)")
        if initial.means.shape[1] != self.dim:
            raise ValueError(
                f"warm start has {initial.means.shape[1]} features, "
                f"coordinate expects {self.dim} "
                f"(a clamped gather would misattribute columns)")
        cols = jnp.asarray(self.subspace_cols)
        means = jnp.asarray(initial.means)
        ga = means[jnp.arange(cols.shape[0])[:, None],
                   jnp.maximum(cols, 0)] * (cols >= 0)
        return SubspaceRandomEffectModel(
            re_type=self.re_type, shard_id=self.shard_id,
            num_features=self.dim, cols=cols, means=ga)

    def _prepare_table(self, initial):
        """Warm-start table in the space the bucket programs run in.

        Warm starts arrive in original space. Unprojected path: the W
        table is transformed once at entry and mapped back once at exit.
        Projected path: transforms are per-entity inside the bucket fit,
        so W stays in original space throughout. Subspace path: same,
        with the table in (E, A) active-column layout — (E, d) never
        exists."""
        if initial is None:
            shape = (self.subspace_cols.shape if self.subspace
                     else (self.num_entities, self.dim))
            return jnp.zeros(shape, jnp.float32)
        if self.subspace:
            # Model layout is column-sorted; the bucket programs run in
            # bucket layout (intercept slot 0). take_along_axis yields a
            # fresh array, safe under fit_bucket's donation.
            return jnp.take_along_axis(jnp.asarray(initial.means),
                                       self._inv_perm_dev, axis=1)
        if self.projection:
            # Explicit copies: fit_bucket donates W.
            return jnp.array(initial.means, copy=True)
        return jnp.array(
            self.norm.model_to_transformed_space(initial.means), copy=True)

    def _finish_model(self, W):
        """Trained table (bucket space) → the public model."""
        if self.subspace:
            return SubspaceRandomEffectModel(
                re_type=self.re_type, shard_id=self.shard_id,
                num_features=self.dim, cols=self._cols_dev,
                means=jnp.take_along_axis(W, self._perm_dev, axis=1))
        W_raw = W if self.projection else self.norm.model_to_original_space(W)
        return RandomEffectModel(
            re_type=self.re_type, shard_id=self.shard_id, means=W_raw)

    def train_model(
        self,
        offsets: Array,
        initial: Optional[RandomEffectModel] = None,
    ) -> RandomEffectModel:
        if initial is not None:
            initial = self.adapt_initial(initial)
        W = self._prepare_table(initial)
        offsets = jnp.asarray(offsets)
        led = obs.ledger()
        mx = obs.metrics()
        pending = []  # this update's wave rows, for the ledger's drain
        for wave, arrays in enumerate(self._iter_bucket_data()):
            t_wave = time.perf_counter()
            # One span per vmapped entity-fit wave (the dispatch unit the
            # lane bound exists for). Dispatch is async: the span times
            # the submission + any blocking the runtime imposes, not the
            # device execution. The device side is the wave's ledger row
            # (iterations, evaluations, padded against useful rows) and
            # the program's scopes (re.gather / re.solve / re.scatter) in
            # a profiler trace.
            with obs.annotated("re.fit_wave", cat="train", wave=wave,
                               re_type=self.re_type):
                W, stats = self._fit_bucket(W, offsets, *arrays)
            live = self._host_rows[wave] >= 0
            lanes = int(live.sum())
            if mx is not None:
                mx.counter("photon_re_entities_refit_total",
                           re_type=self.re_type).inc(lanes)
            if led is not None:
                pending.append((self._wave_fields(
                    wave, time.perf_counter() - t_wave, arrays, live), stats))
        if led is not None:
            led.defer(functools.partial(_wave_rows, pending))
        return self._finish_model(W)

    def _wave_fields(self, wave: int, seconds: float, arrays, fit) -> dict:
        """The host's half of one wave's ``re_fit_wave`` row (per-entity
        rows would be 1M-deep noise): the dispatch's shape and lane counts.
        ``arrays`` is the dispatched tuple, ``fit`` masks its lanes that
        hold an entity. ``seconds`` times the ENQUEUE, not the device. The
        solver's half is counted inside the program and joins at the
        ledger's drain (``_wave_rows``)."""
        cap = int(arrays[3].shape[1])
        lanes = int(arrays[4].shape[0])
        counts = self._host_counts[wave][fit]
        fields = dict(
            re_type=self.re_type, wave=wave, seconds=round(seconds, 6),
            entities_fit=int(fit.sum()), cap=cap, lanes=lanes,
            rows_useful=int(counts.sum()), rows_padded=lanes * cap,
            line="oracle" if self._line_oracle else "evaluation")
        active = self._host_active[wave]
        if active is not None:
            # A projected wave's width is its class's: the cells a lane's
            # own rows x own columns fill, against the block's.
            width = int(arrays[0].shape[2])
            fields.update(d_active=width,
                          cols_useful=int((counts * active[fit]).sum()),
                          cols_padded=lanes * cap * width)
        return fields

    def compute_model_variances(
        self, model: RandomEffectModel, offsets: Array
    ) -> RandomEffectModel:
        """Per-entity coefficient variances at the trained optimum."""
        if VarianceComputationType(self.config.variance_computation) == \
                VarianceComputationType.NONE:
            return model
        if self.subspace:
            # Sorted model layout → bucket layout for the programs.
            W = jnp.take_along_axis(jnp.asarray(model.means),
                                    self._inv_perm_dev, axis=1)
        elif self.projection:
            # Per-entity transforms (and the original-space mapping) happen
            # inside var_bucket; W stays original space.
            W = jnp.asarray(model.means)
        else:
            W = jnp.asarray(self.norm.model_to_transformed_space(model.means))
        V = jnp.zeros(model.means.shape, jnp.float32)
        offsets = jnp.asarray(offsets)
        for arrays in self._iter_bucket_data():
            V = self._var_bucket(W, V, offsets, *arrays)
        if not self.projection and (self.norm.factors is not None
                                    or self.norm.shifts is not None):
            # Same diagonal-approximation transform the projected path and
            # FixedEffectCoordinate use (factor² scaling + intercept
            # shift-mass term).
            V = self.norm.variances_to_original_space(V)
        if self.subspace:
            V = jnp.take_along_axis(V, self._perm_dev, axis=1)
        return dataclasses.replace(model, variances=V)

    def score(self, model) -> Array:
        with obs.annotated("re.score", cat="train"):
            return self._score(model)

    def _score(self, model) -> Array:
        if self.subspace:
            W_flat = jnp.asarray(model.means).reshape(-1)
            if self.is_sparse:
                # Staged join: each data nonzero's flat slot in the (E, A)
                # table was computed once at __init__ (misses → one past
                # the end → zero contribution).
                return _subspace_sparse_scores(W_flat, self._sp_flatpos,
                                               self._sp_values)
            cols = jnp.asarray(self._cols_dev)[self._ids]  # (n, A)
            xa = jnp.take_along_axis(
                self._X, jnp.maximum(cols, 0), axis=1) * (cols >= 0)
            return jnp.einsum("na,na->n", xa,
                              jnp.asarray(model.means)[self._ids])
        if self.is_sparse:
            W = jnp.asarray(model.means)
            if W.size < 2**31:  # flat positions fit the device's int32
                return _table_sparse_scores(W, self._ids, self._sp_indices,
                                            self._sp_values)
            # Σ_k v_ik · W[e_i, idx_ik]. ELL padding slots carry value 0
            # by contract, so clamping their sentinel index (== d) into
            # range is exact — no (E, d+1) padded copy of the table.
            idx = jnp.minimum(self._sp_indices, W.shape[1] - 1)
            return jnp.sum(
                self._sp_values * W[self._ids[:, None], idx], axis=-1)
        return _rowwise_dot(
            self._X, _entity_rows(jnp.asarray(model.means), self._ids))

    def initial_model(self):
        if self.subspace:
            return SubspaceRandomEffectModel(
                re_type=self.re_type, shard_id=self.shard_id,
                num_features=self.dim, cols=self._cols_dev,
                means=jnp.zeros(self.subspace_cols.shape, jnp.float32))
        return RandomEffectModel(
            re_type=self.re_type, shard_id=self.shard_id,
            means=jnp.zeros((self.num_entities, self.dim), jnp.float32))
