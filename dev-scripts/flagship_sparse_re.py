"""Flagship-scale sparse random effect on one chip: 10M rows, 1M entities,
d=1M sparse features.

The run docs/PARITY.md describes: all 10^6 per-entity L-BFGS solves and
their AUC against planted effects. It records no time: the projected path's
chip readings are the benchmark cell ``avazu-sparse-re.steady``'s (PERF.md
sections 5 and 6, PR 35), which runs the dense ``(E, d)`` model form at
widths 128 to 1024; this script is what still exercises the *subspace*
model form (d_active <= 16, a SubspaceRandomEffectModel), which no cell
runs (ROADMAP.md, Reach 15). Needs ~12 GB host RAM for data generation and
one TPU chip. Neither the 40 TB dense (n, d) matrix nor the 4 TB (E, d)
model table ever exists.

    python dev-scripts/flagship_sparse_re.py
"""
import os, sys, time
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np
import jax.numpy as jnp

from photon_ml_tpu.data.game_data import GameDataset, SparseShard
from photon_ml_tpu.game.coordinates import RandomEffectCoordinate
from photon_ml_tpu.ops import losses
from photon_ml_tpu.optim import OptimizerConfig
from photon_ml_tpu.optim.problem import GLMOptimizationConfiguration
from photon_ml_tpu.optim.regularization import (RegularizationContext,
                                                RegularizationType)
from photon_ml_tpu.parallel.mesh import make_mesh
from photon_ml_tpu.evaluation.evaluators import auc

n, E, d, nnz = 10_000_000, 1_000_000, 1_000_000, 8
rng = np.random.default_rng(7)
print("generating...", flush=True)
ids = rng.integers(0, E, size=n).astype(np.int32)
# Per-entity feature pools (16 columns each) so subspaces stay small and
# per-entity signal exists.
pools = rng.integers(0, d, size=(E, 16)).astype(np.int32)
slot = rng.integers(0, 16, size=(n, nnz))
idx = np.sort(pools[ids[:, None], slot], axis=1)
dup = np.zeros_like(idx, bool)
dup[:, 1:] = idx[:, 1:] == idx[:, :-1]
vals = rng.normal(size=(n, nnz)).astype(np.float32)
idx[dup] = d
vals[dup] = 0.0
# Planted per-entity coefficient on the pool columns.
beta = rng.normal(0, 1.0, size=(E, 16)).astype(np.float32)
margin = (np.where(dup, 0.0, vals) * beta[ids[:, None], slot]).sum(axis=1)
y = (rng.random(n) < 1.0 / (1.0 + np.exp(-margin))).astype(np.float32)

ds = GameDataset(
    response=y, offsets=np.zeros(n, np.float32),
    weights=np.ones(n, np.float32),
    feature_shards={"re": SparseShard(idx, vals, d)},
    entity_ids={"userId": ids}, num_entities={"userId": E},
    intercept_index={})
cfg = GLMOptimizationConfiguration(
    optimizer=OptimizerConfig(max_iterations=12, tolerance=1e-6),
    regularization=RegularizationContext(RegularizationType.L2, 1.0))

print("staging...", flush=True)
t0 = time.perf_counter()
coord = RandomEffectCoordinate(ds, "userId", "re", losses.LOGISTIC, cfg,
                               make_mesh(), lower_bound=2)
t1 = time.perf_counter()
print(f"staging {t1 - t0:.1f}s; buckets: "
      f"{[(b.capacity, b.num_entities) for b in coord.bucketing.buckets]}",
      flush=True)

off = np.zeros(n, np.float32)
t0 = time.perf_counter()
model = coord.train_model(jnp.asarray(off))
t1 = time.perf_counter()
print(f"first fit (incl. compile) {t1 - t0:.1f}s", flush=True)
t0 = time.perf_counter()
model = coord.train_model(jnp.asarray(off))
scores = np.asarray(coord.score(model))
t1 = time.perf_counter()
print(f"steady-state fit+score {t1 - t0:.1f}s", flush=True)
print(f"AUC vs planted effects: {float(auc(jnp.asarray(scores), jnp.asarray(y))):.4f}", flush=True)
