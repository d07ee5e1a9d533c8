"""Dense fixed-effect coordinate: one shared GLM, data-parallel (P1).

See the package docstring (photon_ml_tpu/game/coordinates/__init__.py) for
the residency discipline shared by all coordinate types.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu import obs
from photon_ml_tpu.data.batch import LabeledBatch
from photon_ml_tpu.data.game_data import GameDataset
from photon_ml_tpu.game.coordinates._down_sampling import (
    _advance_down_sampling, draw_down_sample)
from photon_ml_tpu.game.models import FixedEffectModel
from photon_ml_tpu.models.coefficients import Coefficients
from photon_ml_tpu.normalization import NormalizationContext
from photon_ml_tpu.obs.ledger import spill_history
from photon_ml_tpu.ops.aggregators import scores as agg_scores
from photon_ml_tpu.ops.losses import PointwiseLoss
from photon_ml_tpu.optim.common import scoped
from photon_ml_tpu.optim.problem import (GLMOptimizationConfiguration,
                                         VarianceComputationType,
                                         variances_from_diagonal,
                                         variances_from_matrix)
from photon_ml_tpu.optim.regularization import intercept_mask
from photon_ml_tpu.parallel import objective as dobj
from photon_ml_tpu.parallel import problem as dist_problem
from photon_ml_tpu.parallel.mesh import (DATA_AXIS, pad_to_multiple,
                                         shard_batch)

Array = jax.Array


class FixedEffectCoordinate:
    """One shared GLM trained data-parallel over the mesh.

    Reference parity: FixedEffectCoordinate + DistributedOptimizationProblem.

    Model-space contract: the optimizer runs in the normalization-transformed
    space, but the FixedEffectModel handed out ALWAYS holds ORIGINAL-space
    coefficients (converted at the train boundary, reconverted for warm
    starts) so every scorer — GameModel.score, the transformer, the CLIs,
    save/load — is a plain X @ w. The two are algebraically identical:
    X @ (w∘f) − (w∘f)·s == X @ model_to_original_space(w).
    """

    def __init__(
        self,
        dataset: GameDataset,
        shard_id: str,
        loss: PointwiseLoss,
        config: GLMOptimizationConfiguration,
        mesh,
        norm: NormalizationContext = NormalizationContext(),
        down_sampling_seed: int = 0,
        feature_dtype: str = "float32",
    ):
        self.dataset = dataset
        self.shard_id = shard_id
        self.loss = loss
        self.config = config
        self.mesh = mesh
        self.norm = norm
        self.intercept_index = dataset.intercept_index.get(shard_id)
        self._down_sampling_seed = down_sampling_seed
        self._rng = np.random.default_rng(down_sampling_seed)
        self.feature_dtype = feature_dtype
        # Stage the full training batch on device ONCE (offsets are a
        # placeholder — they are the per-CD-step input). shard_batch pads to
        # a multiple of the data-axis size with zero-weight rows. Scoring
        # reuses the staged features — no second device copy of X.
        # feature_dtype="bfloat16" stores X at half width (see
        # ops/aggregators._matvec for the f32-accumulation contract).
        with obs.phase("fe.transfer") as ph:
            self._staged = shard_batch(
                LabeledBatch.build(dataset.feature_shards[shard_id],
                                   dataset.response, dataset.weights,
                                   feature_dtype=feature_dtype),
                mesh)
            ph["bytes"] = sum(int(a.nbytes) for a in
                              jax.tree.leaves(self._staged))
        self._build_fits()

    def _padded_offsets(self, offsets: Array) -> Array:
        """Extend (n,) offsets with zeros to the staged padded length
        (padding rows have weight 0, so their offsets are inert)."""
        offsets = jnp.asarray(offsets)
        n = self.dataset.num_rows
        return jnp.zeros((self._staged.num_rows,), offsets.dtype
                         ).at[:n].set(offsets)

    def _build_fits(self):
        """(Re)build the cached jitted fit programs for the current config."""
        cfg = dataclasses.replace(
            self.config, variance_computation=VarianceComputationType.NONE)
        loss, mesh, norm = self.loss, self.mesh, self.norm
        ii = self.intercept_index
        oracle = dist_problem.takes_line_oracle(cfg)

        def solve(batch: LabeledBatch, w0: Array):
            coef, res = dist_problem.run(
                loss, batch, mesh, cfg, initial=Coefficients(w0), norm=norm,
                intercept_index=ii, already_sharded=True)
            # Histories, the evaluation count, under the oracle the trials
            # and under TRON each iteration's Hessian-vector products and
            # whether the solve ended at float32's floor ride along for the
            # run ledger's post-fit spill (tiny (max_it+1,) vectors and a
            # few scalars; they stay on device — and cost nothing — unless
            # a ledger is active).
            return (coef.means, res.value_history, res.grad_norm_history,
                    res.evaluations, res.trials if oracle else None,
                    res.hvp_history, getattr(res, "floor_stop", None))

        @scoped("fe.fit")
        def fit(staged: LabeledBatch, offsets: Array, w0: Array):
            return solve(dataclasses.replace(
                staged, offsets=self._padded_offsets(offsets)), w0)

        @scoped("fe.fit")
        def fit_sampled(staged: LabeledBatch, idx: Array, mult: Array,
                        offsets: Array, w0: Array):
            # Down-sampled pass: gather the subsample on device, rescale
            # weights, pad back to a data-axis multiple (static shapes: the
            # samplers return deterministic sizes).
            sub = LabeledBatch(
                features=staged.features[idx],
                labels=staged.labels[idx],
                weights=staged.weights[idx] * mult,
                offsets=offsets[idx],
            ).pad_to(pad_to_multiple(idx.shape[0], mesh.shape[DATA_AXIS]))
            return solve(sub, w0)

        n = self.dataset.num_rows

        @scoped("fe.score")
        def score(features: Array, means: Array):
            return agg_scores(features, means)[:n]

        self._fit = jax.jit(fit)
        self._fit_sampled = jax.jit(fit_sampled)
        self._score = jax.jit(score)

    @property
    def dim(self) -> int:
        return self.dataset.shard_dim(self.shard_id)

    def with_optimization_config(
        self, config: GLMOptimizationConfiguration
    ) -> "FixedEffectCoordinate":
        """Cheap copy with a new optimization config (same data/device
        arrays) — the estimator's reg-weight grid loop swaps configs without
        re-staging data (reference: datasets built once per coordinate,
        reused across the GameOptimizationConfiguration grid)."""
        import copy

        c = copy.copy(self)
        c.config = config
        # Fresh, identically-seeded RNG so every grid point trains on the
        # SAME down-sampled subsets (grid comparison must not depend on how
        # far a shared RNG advanced in earlier grid points).
        c._rng = np.random.default_rng(self._down_sampling_seed)
        c._build_fits()
        return c

    def train_model(
        self,
        offsets: Array,
        initial: Optional[FixedEffectModel] = None,
    ) -> FixedEffectModel:
        if initial is not None:
            w0 = self.norm.model_to_transformed_space(
                initial.coefficients.means)
        else:
            w0 = jnp.zeros((self.dim,), jnp.float32)
        offsets = jnp.asarray(offsets)
        rate = self.config.down_sampling_rate
        if rate < 1.0:
            # Reference: DownSampler subsamples the fixed-effect coordinate's
            # data each training pass, rescaling weights by 1/rate. Index
            # draw is host-side (cheap, label metadata only); the data
            # gather happens on device.
            idx, mult = draw_down_sample(self, rate)
            with obs.annotated("fe.fit", cat="train"):
                w_t, *spill = self._fit_sampled(
                    self._staged, jnp.asarray(idx), jnp.asarray(mult),
                    offsets, w0)
        else:
            with obs.annotated("fe.fit", cat="train"):
                w_t, *spill = self._fit(self._staged, offsets, w0)
        led = obs.ledger()
        if led is not None:
            # Post-fit spill of the compiled optimizer's NaN-padded
            # histories — the run ledger's view of a solve that lives
            # inside one XLA program (one host read, once per update).
            # The update's evaluation count rides on the last row (under
            # the oracle: pairs of passes over X, one an iteration and the
            # first), and beside it, where the oracle ran, its trials;
            # under TRON every row carries its iteration's ``hvps`` and the
            # last one ``floor_stop``.
            vals, gns, evals, trials, hvps, floor = jax.device_get(spill)
            spill_history(
                led, vals, gns,
                opt=self.config.optimizer.optimizer_type.value.lower(),
                evaluations=int(evals),
                counts=None if hvps is None else {"hvps": hvps},
                trials=None if trials is None else int(trials),
                floor_stop=None if floor is None else bool(floor))
        raw = Coefficients(self.norm.model_to_original_space(w_t))
        return FixedEffectModel(shard_id=self.shard_id, coefficients=raw)

    def compute_model_variances(
        self, model: FixedEffectModel, offsets: Array
    ) -> FixedEffectModel:
        """Coefficient variances at the optimum (post-descent pass).

        Variances are computed in the transformed space and mapped back by
        the factor² scaling implied by w_orig = w∘f (the intercept's extra
        shift term is a location change and does not rescale its variance).
        """
        kind = VarianceComputationType(self.config.variance_computation)
        if kind == VarianceComputationType.NONE:
            return model
        batch = dataclasses.replace(self._staged,
                                    offsets=self._padded_offsets(offsets))
        w_t = self.norm.model_to_transformed_space(model.coefficients.means)
        mask = jnp.asarray(intercept_mask(self.dim, self.intercept_index))
        l2 = self.config.regularization.l2_weight()
        if kind == VarianceComputationType.SIMPLE:
            diag = dobj.make_hessian_diagonal(
                self.loss, self.mesh, batch, self.norm)(w_t)
            var_t = variances_from_diagonal(diag, l2, mask)
        else:
            H = dobj.make_hessian_matrix(
                self.loss, self.mesh, batch, self.norm)(w_t)
            var_t = variances_from_matrix(H, l2, mask)
        var_t = self.norm.variances_to_original_space(var_t)
        return dataclasses.replace(
            model, coefficients=Coefficients(model.coefficients.means, var_t))

    def score(self, model: FixedEffectModel) -> Array:
        """Raw-space score (identical to the training margins by algebra)."""
        with obs.annotated("fe.score", cat="train"):
            return self._score(self._staged.features,
                               model.coefficients.means)

    def initial_model(self) -> FixedEffectModel:
        return FixedEffectModel(
            shard_id=self.shard_id,
            coefficients=Coefficients.zeros(self.dim))

    def advance_down_sampling(self, steps: int) -> None:
        """Fast-forward the down-sampling RNG past ``steps`` completed
        train_model calls (checkpoint resume must subsample the remaining
        steps exactly as the uninterrupted run would have)."""
        _advance_down_sampling(self, steps)


