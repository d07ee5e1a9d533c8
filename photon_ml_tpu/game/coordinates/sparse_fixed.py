"""Sparse fixed-effect coordinate: ELL / hybrid layouts (the Criteo path).

See the package docstring (photon_ml_tpu/game/coordinates/__init__.py) for
the residency discipline shared by all coordinate types.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu import obs
from photon_ml_tpu.data.game_data import GameDataset
from photon_ml_tpu.game.coordinates._down_sampling import (
    _advance_down_sampling, draw_down_sample)
from photon_ml_tpu.game.models import FixedEffectModel
from photon_ml_tpu.models.coefficients import Coefficients
from photon_ml_tpu.obs.ledger import spill_history
from photon_ml_tpu.ops.losses import PointwiseLoss
from photon_ml_tpu.optim.common import OptimizerType, scoped
from photon_ml_tpu.optim.problem import (GLMOptimizationConfiguration,
                                         VarianceComputationType,
                                         resolve_optimizer_config,
                                         variances_from_diagonal)
from photon_ml_tpu.optim.regularization import intercept_mask
from photon_ml_tpu.parallel.mesh import DATA_AXIS, pad_to_multiple

Array = jax.Array


def _leaf_bytes(tree) -> int:
    return sum(int(a.nbytes) for a in jax.tree.leaves(tree))


# Eighths of the device memory FREE as the coordinate stages that the
# resident hot block may take: one half. Backed by a sweep of the share
# against a sweep's seconds at 2M click-log rows on one v5e (PERF.md section
# 6, PR 32: 9.48 / 8.36 / 7.76 / 7.42 / 7.19 s at 2 to 6 eighths). Every
# eighth still shortens a sweep, so memory binds, not time: one half is the
# largest share whose step is worth its set-up (five eighths adds 7% to it
# for 4.5% of a sweep) and whose peak leaves a third of the device to what
# this coordinate cannot see: the coordinates staged after it, the
# evaluation's scratch.
_HOT_EIGHTHS_OF_FREE = 4


# Vectors of d the compiled solve holds beside its 2m of history, as the
# TPU's compiler counts its scratch at 54.7M columns (``memory_analysis``
# of ``optim.lbfgs.minimize`` for a described v5e: 37 vectors under L-BFGS
# and 44 under OWL-QN with m = 10, fragmentation included; PERF.md section
# 6, PR 33), and the layout's two permutations.
# TRON keeps no history: the one-shard hybrid ``fit`` under TRON, compiled
# for a described v5e at 20,216,830 columns and 1M rows, holds 665,690,624 B
# of scratch, 8.2 vectors of d (5.5 by its slope in d, the rest the rows'
# and the cold entries' arrays), where L-BFGS's same fit holds 36.9 (PERF.md
# section 6, the KDD Cup 2010 deployment).
_SOLVER_VECTORS = {OptimizerType.LBFGS: 17, OptimizerType.OWLQN: 24,
                   OptimizerType.TRON: 9}
_LAYOUT_VECTORS = 2


def solver_state_bytes(dim: int,
                       config: GLMOptimizationConfiguration) -> int:
    """Bytes the fixed effect's solve will hold on a device at ``dim``
    columns under ``config``'s optimiser and history length, beside the
    staged rows: what ``hot_block_budget`` takes off before it halves. 0.16
    GB at d = 2**20; 10 GB, most of a v5e, at 54.7M under OWL-QN; 0.89 GB
    at 20.2M under TRON."""
    opt = resolve_optimizer_config(
        config.optimizer, config.regularization.l1_weight() > 0.0)
    kind = OptimizerType(opt.optimizer_type)
    history = 0 if kind == OptimizerType.TRON else 2 * opt.history_length
    return 4 * int(dim) * (history + _SOLVER_VECTORS.get(kind, 0)
                           + _LAYOUT_VECTORS)


def hot_block_budget(mesh, solver_bytes: int = 0,
                     deferred_bytes: int = 0) -> Optional[int]:
    """Bytes the resident hot block may take on one device of ``mesh``:
    half of what the device has free now (``bytes_limit`` less
    ``bytes_in_use``) once the fixed effect's own solve has its
    ``solver_bytes`` and the job's other coordinates the ``deferred_bytes``
    they have declared but not yet staged (a projected table's blocks cross
    as the first sweep consumes them: 7 GB that ``bytes_in_use`` cannot
    show), so that a device other tables fill, or a coefficient space that
    fills it, gets a narrower block, not an allocation failure. None where
    the backend reports no limit (the CPU), and then the column counts
    alone size the block."""
    stats = mesh.devices.flat[0].memory_stats() or {}
    limit = int(stats.get("bytes_limit", 0))
    if not limit:
        return None
    free = max(0, limit - int(stats.get("bytes_in_use", 0))
               - int(solver_bytes) - int(deferred_bytes))
    return free * _HOT_EIGHTHS_OF_FREE // 8


# Rows of the hot block cross to the device this many bytes at a time.
_PUT_PART_BYTES = 1 << 28


@functools.partial(jax.jit, donate_argnums=0)
def _write_rows(block, part, start):
    return jax.lax.dynamic_update_slice(block, part, (start, 0))


def _put_in_parts(x: np.ndarray, sharding) -> Array:
    """``x`` (rows, columns) on the device, sent in row parts. One
    ``device_put`` of a host array over 4 GiB crosses to a v5e at 0.4-0.6
    GB/s, one under it at 9 GB/s (PERF.md section 6, PR 32: 14 s against
    0.9 s for the cell's 8.2 GB block). Each part is written in place into
    the resident array and waited for, so the device holds one part beside
    the array, never a second copy; the last part starts early enough to be
    as long as the others, so one program serves them all."""
    rows = _PUT_PART_BYTES // max(1, x.shape[1] * x.dtype.itemsize)
    if rows >= x.shape[0]:
        return jax.device_put(x, sharding)
    block = jnp.zeros(x.shape, x.dtype, device=sharding)
    for a in range(0, x.shape[0], rows):
        a = min(a, x.shape[0] - rows)
        block = _write_rows(
            block, jax.device_put(x[a:a + rows], sharding), np.int32(a))
        block.block_until_ready()
    return block


def _cold_column_counts(host) -> np.ndarray:
    """Non-zeros of every cold column of a host layout, read off the layout
    (a count over the shard's slots again costs 1.4 s at 78M of them). A
    class row's live slots belong to the column ``chunk_cols`` names, where
    it is a remainder chunk, and to ``class_starts + i`` past those; a
    data shard's pad slots name the row past its last; a class of fewer
    than 128 slots is held lane-major."""
    pad_row = getattr(host, "rows_per_shard", -1)
    rems = getattr(host, "class_rems", (0,) * len(host.class_lens))
    counts = np.zeros(host.num_features - host.num_hot, np.int64)
    off = 0
    for start, L, rem, rows in zip(host.class_starts, host.class_lens, rems,
                                   host.cold_rowids):
        live = np.asarray(rows) != pad_row
        columns = live.ndim - (2 if L >= 128 else 1)
        per_row = live.sum(axis=tuple(
            a for a in range(live.ndim) if a != columns))
        if rem:
            counts[np.asarray(host.chunk_cols[off:off + rem])] += per_row[:rem]
        counts[start:start + per_row.size - rem] += per_row[rem:]
        off += rem
    return counts


def _spilled(res) -> tuple:
    """What a fit hands the run ledger's post-fit spill besides the
    coefficients: the value and gradient-norm histories, the evaluation
    count, OWL-QN's trials and non-zeros an iteration, and TRON's products
    and curvatures an iteration and whether it ended at float32's floor
    (None where the solver has no such count: no output of the program)."""
    return (res.value_history, res.grad_norm_history, res.evaluations,
            res.trials_history, res.nnz_history, res.hvp_history,
            getattr(res, "curvature_history", None),
            getattr(res, "floor_stop", None))


class SparseFixedEffectCoordinate:
    """Fixed-effect GLM over an ELL sparse shard (the Criteo path).

    Reference parity: same FixedEffectCoordinate contract, but the
    objective is the sparse gather/scatter pipeline
    (parallel/sparse_objective.py) instead of dense matmuls — the analogue
    of the reference training on sparse Breeze vectors + PalDB index maps.
    With ``feature_sharded=True`` the coefficient dimension additionally
    shards over the mesh's ``model`` axis (P3) for feature spaces too large
    to replicate.

    Residency discipline matches the dense coordinate: the staged batch
    lives on device once; per CD step only (n,) offsets and the warm
    start move.

    Two execution layouts:
    - ``hybrid`` (default whenever coefficients replicate): the hot-dense /
      cold-class layout of ops/hybrid_sparse.py — the Zipf head of the
      feature space rides the MXU as a dense block and the cold tail's
      random crossings shrink to ~15% of the volume (measured ~4-10× the
      ELL step at d=1M and n=131072 on one v5e chip; at 2M rows, where
      the block is sized from bytes, half of the chip's free memory
      holds 1024 columns and 24% of the non-zeros stay cold, a pair of
      passes takes 0.29 s against 0.36 s with 512 columns and ~1.1 s
      for a plain ELL pass: PERF.md section 6, PRs 29-32). Exact, not
      approximate: the
      solve happens in a statically permuted feature space and maps back.
      On one data shard, where every hot column planned holds one float32
      value (one-hot fields scaled a row: every click log), the block is
      int8 counts and a float32 scale a column, four times the columns in
      the same bytes and float32(count) * scale the float32 cell bit for
      bit (``hybrid_sparse._count_hot``; the ``fe_layout`` row says
      ``hot_storage: count8``). The layout decides that from the shard: no
      option, and any other shard stages the float32 (or bf16) block and
      traces the programs it always did. This is not the streamed path's
      ``feature_dtype="int8"``, which quantises real values and is lossy;
      ``feature_dtype`` here is float32 or bfloat16 and never reaches the
      count block.
      On one data shard L-BFGS's line search crosses the data twice an
      iteration whatever its trials (parallel/sparse_problem.py
      ``_hybrid_line``), so a fit's seconds follow its iterations alone.
      On a multi-data-shard mesh the rows split contiguously into
      per-shard hybrid layouts under one GLOBAL permutation
      (HybridShards): hot/cold aggregates run shard-local and psum over
      ``data``, so the fast path composes with data parallelism.
    - ELL shard_map pipeline (parallel/sparse_objective.py): required for
      ``feature_sharded=True`` (P3), where the coefficient dimension
      itself shards over ``model`` and the hybrid layout's replicated
      permuted space does not exist.

    Normalization is not supported here (the reference normalizes dense
    shards only; scaling sparse values would densify shift terms).
    Sparse RANDOM effects are deliberately not a separate class: large-d
    sparse per-entity features are exactly the regime the per-entity
    subspace projection handles (RandomEffectCoordinate stages dense
    d_active buckets straight from the ELL triplets).
    """

    def __init__(
        self,
        dataset: GameDataset,
        shard_id: str,
        loss: PointwiseLoss,
        config: GLMOptimizationConfiguration,
        mesh,
        feature_sharded: bool = False,
        down_sampling_seed: int = 0,
        hybrid: Optional[bool] = None,
        feature_dtype: str = "float32",
        deferred_bytes: int = 0,
    ):
        """``deferred_bytes``: what the job's other coordinates have yet to
        put on this device (``RandomEffectCoordinate.
        deferred_device_bytes``, summed by the estimator that builds them):
        the hot block's budget leaves them their room."""
        from photon_ml_tpu.data.game_data import SparseShard
        from photon_ml_tpu.data.sparse import SparseBatch
        from photon_ml_tpu.parallel import sparse_problem as sp

        shard = dataset.feature_shards[shard_id]
        if not isinstance(shard, SparseShard):
            raise TypeError(f"shard {shard_id!r} is not sparse")
        self.dataset = dataset
        self.shard_id = shard_id
        self.loss = loss
        self.config = config
        self.mesh = mesh
        self.feature_sharded = bool(feature_sharded)
        self.intercept_index = dataset.intercept_index.get(shard_id)
        self._down_sampling_seed = down_sampling_seed
        self._rng = np.random.default_rng(down_sampling_seed)
        self._dim = int(shard.num_features)
        self.feature_dtype = feature_dtype

        single_shard = mesh.shape[DATA_AXIS] == 1
        if hybrid is None:
            self.hybrid = not self.feature_sharded
        else:
            self.hybrid = bool(hybrid)
            if self.hybrid and self.feature_sharded:
                raise ValueError(
                    "hybrid=True is incompatible with feature_sharded "
                    "(the hybrid layout needs the permuted coefficient "
                    "space replicated on every shard)")
        self._hybrid_sharded = self.hybrid and not single_shard

        batch = SparseBatch(
            indices=np.asarray(shard.indices),
            values=np.asarray(shard.values),
            labels=np.asarray(dataset.response),
            weights=np.asarray(dataset.weights),
            offsets=np.zeros(dataset.num_rows, np.float32),
            num_features=self._dim)
        from jax.sharding import NamedSharding, PartitionSpec

        # The warm start and, on one data shard, the offsets are placed on
        # the mesh like the staged batch before every fit: an input whose
        # sharding differs from the last call's is another program to the
        # compiler, and the first sweeps hand over both kinds (zeros that
        # sit on no mesh, then the other coordinates' scores and the last
        # fit's own output, which sit on it).
        self._replicated = NamedSharding(mesh, PartitionSpec())
        if self.hybrid:
            from photon_ml_tpu.ops import hybrid_sparse as hybrid_mod

            dt = (jnp.bfloat16 if feature_dtype == "bfloat16"
                  else jnp.float32)
            solver_bytes = solver_state_bytes(self._dim, config)
            budget = hot_block_budget(mesh, solver_bytes, deferred_bytes)
            with obs.phase("fe.host_stage"):
                if self._hybrid_sharded:
                    host = hybrid_mod.build_hybrid_shards(
                        batch, mesh.shape[DATA_AXIS], feature_dtype=dt,
                        hot_block_bytes=budget)
                else:
                    host = hybrid_mod.build_hybrid(
                        batch, feature_dtype=dt, device=False,
                        hot_block_bytes=budget)
            with obs.phase("fe.transfer") as ph:
                if self._hybrid_sharded:
                    self._staged = sp.shard_hybrid(host, mesh)
                else:
                    self._staged = jax.device_put(
                        dataclasses.replace(host, X_hot=_put_in_parts(
                            host.X_hot, self._replicated)),
                        self._replicated)
                ph["bytes"] = _leaf_bytes(self._staged)
            self._ii_perm = (
                None if self.intercept_index is None else int(
                    np.asarray(host.inv_perm)[self.intercept_index]))
            led = obs.ledger()
            if led is not None:
                # What bound the block: the bytes offered (after the
                # solver's), the columns the count threshold asks for
                # (before bytes or max_hot cut them: the planner with
                # neither; every hot column clears the threshold), and
                # what was built: a cell's storage, the columns float32
                # cells would have held in the same bytes, and how far the
                # columns planned at a byte a cell are count-exact; and
                # the columns any row touches.
                cold_counts = _cold_column_counts(host)
                plan = getattr(host, "hot_plan", (host.num_hot, 0))
                led.record(
                    "fe_layout", shard=shard_id, num_hot=host.num_hot,
                    hot_budget_bytes=budget,
                    solver_state_bytes=solver_bytes,
                    deferred_bytes=int(deferred_bytes),
                    touched_columns=host.num_hot + int(
                        np.count_nonzero(cold_counts)),
                    hot_candidates=host.num_hot + int(np.count_nonzero(
                        cold_counts >= hybrid_mod._default_hot_threshold(
                            dataset.num_rows, dt))),
                    hot_bytes=_leaf_bytes(
                        (host.X_hot, getattr(host, "hot_scale", None))),
                    hot_storage=hybrid_mod.hot_storage(host),
                    hot_columns_f32=plan[0], hot_exact_candidates=plan[1],
                    hot_entries=host.entries[0],
                    cold_entries=host.entries[1],
                    cold_slots=sum(int(r.size) for r in host.cold_rowids),
                    cold_chunks=sum(
                        int(r.size) // L for r, L in zip(
                            host.cold_rowids, host.class_lens)),
                    cold_classes=len(host.class_lens))
        else:
            if self.feature_sharded:
                from photon_ml_tpu.parallel.mesh import MODEL_AXIS
                batch = sp._pad_features(
                    batch,
                    pad_to_multiple(self._dim, mesh.shape[MODEL_AXIS]))
            with obs.phase("fe.transfer") as ph:
                self._staged = sp.shard_sparse_batch(batch, mesh)
                ph["bytes"] = _leaf_bytes(self._staged)
        self._build_fits()

    # -- jitted programs ---------------------------------------------------

    def _padded_offsets(self, offsets: jax.Array) -> jax.Array:
        offsets = jnp.asarray(offsets)
        n = self.dataset.num_rows
        return jnp.zeros((self._staged.num_rows,), offsets.dtype
                         ).at[:n].set(offsets)

    def _build_fits(self):
        if self.hybrid:
            self._build_hybrid_fits()
            return
        from photon_ml_tpu.ops import sparse_aggregators as sagg
        from photon_ml_tpu.parallel import sparse_problem as sp

        cfg = dataclasses.replace(
            self.config, variance_computation=VarianceComputationType.NONE)
        loss, mesh, fs = self.loss, self.mesh, self.feature_sharded
        ii = self.intercept_index
        d_true = self._dim
        d_staged = self._staged.num_features

        def lift(w0):
            """True-dim warm start → staged (possibly feature-padded) dim."""
            if d_staged == d_true:
                return w0
            return jnp.zeros((d_staged,), w0.dtype).at[:d_true].set(w0)

        @scoped("fe.fit")
        def fit(staged, offsets, w0):
            batch = dataclasses.replace(
                staged, offsets=self._padded_offsets(offsets))
            coef, res = sp.run(loss, batch, mesh, cfg,
                               initial=Coefficients(lift(w0)),
                               intercept_index=ii,
                               feature_sharded=fs, already_sharded=True)
            # Histories and the evaluation count ride along for the run
            # ledger's post-fit spill (tiny, device-resident, free when no
            # ledger is active).
            return (coef.means[:d_true], *_spilled(res))

        @scoped("fe.fit")
        def fit_sampled(staged, idx, mult, offsets, w0):
            sub = dataclasses.replace(
                staged,
                indices=staged.indices[idx],
                values=staged.values[idx],
                labels=staged.labels[idx],
                weights=staged.weights[idx] * mult,
                offsets=offsets[idx],
            ).pad_to(pad_to_multiple(idx.shape[0], mesh.shape[DATA_AXIS]))
            coef, res = sp.run(loss, sub, mesh, cfg,
                               initial=Coefficients(lift(w0)),
                               intercept_index=ii,
                               feature_sharded=fs, already_sharded=True)
            return (coef.means[:d_true], *_spilled(res))

        @scoped("fe.score")
        def score_fn(staged, means):
            # The staged batch's offsets are zeros whatever the data's are
            # (those reach a fit through descent's residual and are no
            # part of a score), so margins == X @ w exactly.
            return sagg.margins(staged, means)

        self._fit = jax.jit(fit)
        self._fit_sampled = jax.jit(fit_sampled)
        self._score = jax.jit(score_fn)

    def _build_hybrid_fits(self):
        """Jitted hybrid-layout programs. Per CD step only (n,) offsets and
        the warm start move; the staged HybridSparseBatch / HybridShards is
        a jit argument (never a baked constant) so the big hot block stays
        device-resident across compilations. Down-sampling masks weights in
        place of the ELL path's row gather — the objective is identical
        (dropped rows get weight 0, kept rows scale by the rate
        multiplier)."""
        from photon_ml_tpu.ops import hybrid_sparse as hybrid_mod
        from photon_ml_tpu.parallel import sparse_problem as sp

        cfg = dataclasses.replace(
            self.config, variance_computation=VarianceComputationType.NONE)
        loss = self.loss
        ii_perm = self._ii_perm

        if self._hybrid_sharded:
            self._build_hybrid_sharded_fits(cfg, ii_perm)
            return

        @scoped("fe.fit")
        def fit(hb, offsets, w0):
            hbo = dataclasses.replace(hb, offsets=jnp.asarray(offsets))
            coef, res = sp.run_hybrid(loss, hbo, cfg,
                                      initial=Coefficients(w0),
                                      intercept_index_permuted=ii_perm)
            return (coef.means, *_spilled(res))

        @scoped("fe.fit")
        def fit_sampled(hb, idx, mult, offsets, w0):
            w_masked = jnp.zeros_like(hb.weights).at[idx].set(
                hb.weights[idx] * mult)
            hbo = dataclasses.replace(hb, weights=w_masked,
                                      offsets=jnp.asarray(offsets))
            coef, res = sp.run_hybrid(loss, hbo, cfg,
                                      initial=Coefficients(w0),
                                      intercept_index_permuted=ii_perm)
            return (coef.means, *_spilled(res))

        @scoped("fe.score")
        def score_fn(hb, means):
            # The staged batch's offsets are zeros whatever the data's are
            # (``__init__``; the data's reach a fit through descent's
            # residual, ``base + total − scores[cid]``, and are no part of
            # a score), so margins == X @ w exactly.
            return hybrid_mod.margins(
                hb, hybrid_mod.to_permuted_space(hb, means))

        def hess_diag(hb, offsets, means):
            hbo = dataclasses.replace(hb, offsets=jnp.asarray(offsets))
            return hybrid_mod.to_original_space(
                hbo, hybrid_mod.hessian_diagonal(
                    loss, hybrid_mod.to_permuted_space(hbo, means), hbo))

        self._fit = jax.jit(fit)
        self._fit_sampled = jax.jit(fit_sampled)
        self._score = jax.jit(score_fn)
        self._hess_diag = jax.jit(hess_diag)

    def _build_hybrid_sharded_fits(self, cfg, ii_perm):
        """Jitted programs over the data-sharded hybrid layout.

        Offsets/weights keep the contract of the rest of the class — flat
        padded global row order — and reshape to the (S, n_l) grid at the
        jit boundary (padding sits at the global tail, so flat index ==
        original row id)."""
        from photon_ml_tpu.parallel import sparse_objective as sobj
        from photon_ml_tpu.parallel import sparse_problem as sp

        loss = self.loss
        mesh = self.mesh
        S = self._staged.num_shards
        n_l = self._staged.rows_per_shard
        n = self.dataset.num_rows

        def grid(offsets):
            # fit() passes raw (n,) offsets; fit_sampled already padded
            # them to the staged length via _padded_offsets.
            offsets = jnp.asarray(offsets)
            flat = (offsets if offsets.shape[0] == S * n_l
                    else self._padded_offsets(offsets))
            return flat.reshape(S, n_l)

        @scoped("fe.fit")
        def fit(shb, offsets, w0):
            shbo = dataclasses.replace(shb, offsets=grid(offsets))
            coef, res = sp.run_hybrid_sharded(
                loss, shbo, mesh, cfg, initial=Coefficients(w0),
                intercept_index_permuted=ii_perm)
            return (coef.means, *_spilled(res))

        @scoped("fe.fit")
        def fit_sampled(shb, idx, mult, offsets, w0):
            wf = shb.weights.reshape(-1)
            w_masked = jnp.zeros_like(wf).at[idx].set(
                wf[idx] * mult).reshape(shb.weights.shape)
            shbo = dataclasses.replace(shb, weights=w_masked,
                                       offsets=grid(offsets))
            coef, res = sp.run_hybrid_sharded(
                loss, shbo, mesh, cfg, initial=Coefficients(w0),
                intercept_index_permuted=ii_perm)
            return (coef.means, *_spilled(res))

        @scoped("fe.score")
        def score_fn(shb, means):
            # The staged offsets are zeros whatever the data's are (the
            # single-shard ``score_fn`` says why), so margins == X @ w; rows
            # come back in flat padded global order.
            return sobj.make_hybrid_margins(mesh, shb)(means[shb.perm])

        def hess_diag(shb, offsets, means):
            shbo = dataclasses.replace(shb, offsets=grid(offsets))
            diag = sobj.make_hybrid_hessian_diagonal(
                loss, mesh, shbo)(means[shbo.perm])
            return diag[shbo.inv_perm]

        self._fit = jax.jit(fit)
        self._fit_sampled = jax.jit(fit_sampled)
        self._score = jax.jit(score_fn)
        self._hess_diag = jax.jit(hess_diag)

    # -- coordinate contract ----------------------------------------------

    @property
    def dim(self) -> int:
        return self._dim

    def with_optimization_config(
        self, config: GLMOptimizationConfiguration
    ) -> "SparseFixedEffectCoordinate":
        import copy

        c = copy.copy(self)
        c.config = config
        c._rng = np.random.default_rng(self._down_sampling_seed)
        c._build_fits()
        return c

    def train_model(
        self,
        offsets: jax.Array,
        initial: Optional[FixedEffectModel] = None,
    ) -> FixedEffectModel:
        if initial is not None:
            w0 = jnp.asarray(initial.coefficients.means)
        else:
            w0 = jnp.zeros((self.dim,), jnp.float32)
        w0 = jax.device_put(w0, self._replicated)
        offsets = jnp.asarray(offsets)
        if self.mesh.shape[DATA_AXIS] == 1:
            offsets = jax.device_put(offsets, self._replicated)
        rate = self.config.down_sampling_rate
        with obs.annotated("fe.fit", cat="train"):
            if rate < 1.0:
                idx, mult = draw_down_sample(self, rate)
                w, *spill = self._fit_sampled(
                    self._staged, jnp.asarray(idx), jnp.asarray(mult),
                    self._padded_offsets(offsets), w0)
            else:
                w, *spill = self._fit(self._staged, offsets, w0)
        led = obs.ledger()
        if led is not None:
            # Post-fit spill of the compiled histories (one host read,
            # once per coordinate update) — docs/OBSERVABILITY.md. The
            # update's evaluation count rides on the last row; an OWL-QN
            # solve's rows carry its trials, the iterate's non-zeros and
            # the passes over the shard's non-zeros they cost: two for the
            # first evaluation, then a trial each and one for the accepted
            # point's gradient where the solve has its ValueOracle (the
            # one-shard hybrid layout), two a trial elsewhere. A TRON
            # solve's rows carry each iteration's Hessian-vector products,
            # the curvatures its CG solve computed (the one-shard hybrid
            # layout computes the margins at w once a solve; elsewhere each
            # product crosses for them itself) and their crossings: two for
            # the evaluation at the step, one a curvature and two a product
            # (X·v, the gradient pass); and its last row ``floor_stop``.
            (vals, gns, evals, trials, nnz, hvps, curv,
             floor) = jax.device_get(spill)
            counts = None
            if trials is not None:
                oracle = self.hybrid and not self._hybrid_sharded
                crossings = trials + 1 if oracle else 2 * trials
                crossings[0] = 2
                counts = {"trials": trials, "nnz": nnz,
                          "crossings": crossings}
            elif hvps is not None:
                curv = hvps if curv is None else curv
                counts = {"hvps": hvps, "curvatures": curv,
                          "crossings": 2 + curv + 2 * hvps}
            spill_history(
                led, vals, gns,
                opt=("owlqn" if trials is not None else
                     self.config.optimizer.optimizer_type.value.lower()),
                evaluations=int(evals), counts=counts,
                floor_stop=None if floor is None else bool(floor))
        return FixedEffectModel(shard_id=self.shard_id,
                                coefficients=Coefficients(w))

    def compute_model_variances(
        self, model: FixedEffectModel, offsets: jax.Array
    ) -> FixedEffectModel:
        from photon_ml_tpu.parallel import sparse_objective as sobj

        kind = VarianceComputationType(self.config.variance_computation)
        if kind == VarianceComputationType.NONE:
            return model
        if kind == VarianceComputationType.FULL:
            raise NotImplementedError(
                "FULL variance needs the dense d×d Hessian — use SIMPLE at "
                "sparse scale (as the reference does)")
        if self.hybrid:
            diag = self._hess_diag(
                self._staged, self._padded_offsets(offsets),
                jax.device_put(jnp.asarray(model.coefficients.means),
                               self._replicated))
            var = variances_from_diagonal(
                diag, self.config.regularization.l2_weight(),
                jnp.asarray(intercept_mask(self.dim, self.intercept_index)))
            return dataclasses.replace(
                model,
                coefficients=Coefficients(model.coefficients.means, var))
        batch = dataclasses.replace(
            self._staged, offsets=self._padded_offsets(offsets))
        d_staged = batch.num_features
        w = jnp.zeros((d_staged,), jnp.float32
                      ).at[:self.dim].set(model.coefficients.means)
        diag = sobj.make_hessian_diagonal(
            self.loss, self.mesh, batch, self.feature_sharded)(w)
        mask = np.zeros(d_staged, np.float32)
        mask[:self.dim] = intercept_mask(self.dim, self.intercept_index)
        var = variances_from_diagonal(
            diag, self.config.regularization.l2_weight(),
            jnp.asarray(mask))[:self.dim]
        return dataclasses.replace(
            model,
            coefficients=Coefficients(model.coefficients.means, var))

    def score(self, model: FixedEffectModel) -> jax.Array:
        n = self.dataset.num_rows
        means = jnp.asarray(model.coefficients.means)
        d_staged = self._staged.num_features
        if d_staged != self.dim:
            means = jnp.zeros((d_staged,), means.dtype
                              ).at[:self.dim].set(means)
        means = jax.device_put(means, self._replicated)
        with obs.annotated("fe.score", cat="train"):
            return self._score(self._staged, means)[:n]

    def initial_model(self) -> FixedEffectModel:
        return FixedEffectModel(
            shard_id=self.shard_id,
            coefficients=Coefficients.zeros(self.dim))

    def advance_down_sampling(self, steps: int) -> None:
        """See FixedEffectCoordinate.advance_down_sampling."""
        _advance_down_sampling(self, steps)


