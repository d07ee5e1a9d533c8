"""Coordinate data/optimization configuration bundles.

Reference parity: photon-api ``data/FixedEffectDataConfiguration.scala``,
``data/RandomEffectDataConfiguration.scala``,
``data/CoordinateDataConfiguration.scala`` and the per-coordinate
optimization bundles of ``optimization/game/*Configuration.scala``; the
reference encodes these as mini-DSL CLI strings parsed by
``parseAndBuild`` — here they are dataclasses with a compact string parser
for CLI use (see photon_ml_tpu/cli/).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Union

from photon_ml_tpu.game.staging import StagingConfig
from photon_ml_tpu.ingest import IngestConfig
from photon_ml_tpu.optim import (OptimizerConfig, OptimizerType,
                                 RegularizationContext, RegularizationType)
from photon_ml_tpu.optim.problem import (GLMOptimizationConfiguration,
                                         VarianceComputationType)

__all__ = [
    "CoordinateConfiguration",
    "CoordinateDataConfiguration",
    "FactoredRandomEffectDataConfiguration",
    "FixedEffectDataConfiguration",
    "IngestConfig",
    "RandomEffectDataConfiguration",
    "StagingConfig",
    "StreamingConfig",
    "parse_ingest_config",
    "parse_kv",
    "parse_optimizer_config",
    "parse_staging_config",
    "parse_streaming_config",
]


@dataclasses.dataclass(frozen=True)
class StreamingConfig:
    """Row-streamed fixed-effect fit configuration (docs/STREAMING.md).

    When passed to ``GameEstimator(streaming=...)`` (CLI: ``game_train
    --streaming``), sparse fixed-effect coordinates route onto the
    streamed path: the SparseShard stages into host-resident hot-dense/
    cold-ELL chunks, chunk ranges partition over the mesh's ``data``
    axis, and every L-BFGS value/gradient streams each device's range
    with partials merged via ``psum`` — n bounded by host RAM, not HBM.

    ``chunk_rows``: rows per chunk, the streamed transfer unit (every
    chunk shares one compiled program; the flagship uses 5M). ``num_hot``:
    hot-dense columns per chunk (the Zipf head). ``feature_dtype``:
    chunk storage dtype — None inherits the coordinate's
    ``FixedEffectDataConfiguration.feature_dtype``; "bfloat16" halves
    the host→device stream, the steady-state cost of every objective
    evaluation, and "int8" (symmetric per-column quantization, f32
    accumulation — docs/STREAMING.md "Quantized streaming") quarters
    it. ``prefetch_depth``: transfers in flight ahead of compute
    per device. ``pin_chunks``: leading chunks pinned resident PER
    DEVICE (spare HBM traded for stream traffic). ``workers``: staging
    canonicalization threads (None = host cores). ``solver``: the
    streamed driver — "lbfgs" (the batch default), "sdca"
    (duality-gap-certified dual coordinate ascent), or "sgd" (primal
    mini-batch fallback) — docs/STREAMING.md "Stochastic solvers"; a
    per-coordinate ``--opt-config optimizer=SDCA|SGD`` overrides it.
    Under sdca, ``pin_chunks`` becomes the GAP-DRIVEN residency budget
    (the pin set re-ranks by per-chunk gap contribution each epoch).
    """

    chunk_rows: int = 262144
    num_hot: int = 512
    feature_dtype: Optional[str] = None
    prefetch_depth: int = 2
    pin_chunks: int = 0
    workers: Optional[int] = None
    solver: str = "lbfgs"

    def __post_init__(self):
        if self.chunk_rows < 1:
            raise ValueError(
                f"chunk_rows must be >= 1, got {self.chunk_rows}")
        if self.num_hot < 1:
            raise ValueError(f"num_hot must be >= 1, got {self.num_hot}")
        if self.feature_dtype not in (None, "float32", "bfloat16",
                                      "int8"):
            raise ValueError(
                f"unsupported feature_dtype {self.feature_dtype!r}; "
                "expected float32, bfloat16, or int8")
        if self.prefetch_depth < 1:
            raise ValueError(
                f"prefetch_depth must be >= 1, got {self.prefetch_depth}")
        if self.pin_chunks < 0:
            raise ValueError(
                f"pin_chunks must be >= 0, got {self.pin_chunks}")
        if self.workers is not None and self.workers < 1:
            raise ValueError(f"workers must be >= 1, got {self.workers}")
        if self.solver not in ("lbfgs", "sdca", "sgd"):
            raise ValueError(
                f"unsupported streaming solver {self.solver!r}; "
                "expected lbfgs, sdca, or sgd")


@dataclasses.dataclass(frozen=True)
class FixedEffectDataConfiguration:
    """Reference: FixedEffectDataConfiguration (featureShardId, minPartitions
    — partitions have no TPU referent).

    ``feature_sharded`` applies to sparse (ELL) shards only: shard the
    coefficient dimension over the mesh's ``model`` axis (P3, the Criteo
    regime where the feature space is too large to replicate).

    ``feature_dtype``: on-device storage dtype for DENSE shards and for
    the hybrid layout's hot block on sparse shards. ``"bfloat16"`` halves
    HBM traffic on the bandwidth-bound GLM hot loop (margins/gradients
    accumulate in f32 on the MXU); optimizer state and coefficients stay
    f32. Expect coefficient deltas ~1e-2 relative — opt in when
    throughput matters more than the last two digits.

    ``hybrid`` (sparse shards only): the hot-dense / cold-class layout of
    ops/hybrid_sparse.py. ``None`` = automatic (on when the mesh has a
    single data shard and the shard is not feature_sharded); True/False
    force it."""

    feature_shard_id: str
    feature_sharded: bool = False
    feature_dtype: str = "float32"
    hybrid: Optional[bool] = None


@dataclasses.dataclass(frozen=True)
class RandomEffectDataConfiguration:
    """Reference: RandomEffectDataConfiguration (randomEffectType,
    featureShardId, active-data bounds)."""

    random_effect_type: str
    feature_shard_id: str
    active_data_lower_bound: int = 1
    active_data_upper_bound: Optional[int] = None
    # Per-entity feature-subspace projection (reference projectorType:
    # INDEX_MAP builds a LinearSubspaceProjector per entity; RANDOM solves
    # every entity in one shared ``projected_dimension``-dim Gaussian
    # random-projection space (ProjectionMatrixBroadcast); NONE solves at
    # the full shard dimension).
    projector: str = "NONE"
    projected_dimension: Optional[int] = None  # RANDOM only
    # Cap each entity's subspace at ceil(ratio · num_samples) columns by
    # |Pearson corr(feature, label)| (reference
    # RandomEffectDataConfiguration.numFeaturesToSamplesRatio →
    # LocalDataset.filterFeaturesByPearsonCorrelationScore). Implies
    # projection.
    features_to_samples_ratio: Optional[float] = None
    # Keep the trained model in each entity's active-column subspace
    # (reference: RandomEffectModelInProjectedSpace) instead of the dense
    # (num_entities, d) table. None = automatic: on when the dense table
    # would exceed ~1 GiB. Requires a projected coordinate.
    subspace_model: Optional[bool] = None
    # On-device storage dtype for the staged (E_b, cap, d_active) bucket
    # blocks — same contract as the fixed-effect knob: "bfloat16" halves
    # the blocks' HBM and the per-entity solves accumulate in f32 on the
    # MXU; coefficients/optimizer state stay f32.
    feature_dtype: str = "float32"

    def __post_init__(self):
        if self.projector.upper() not in ("NONE", "INDEX_MAP", "RANDOM"):
            raise ValueError(
                f"unknown projector {self.projector!r}; "
                "expected NONE, INDEX_MAP, or RANDOM")
        if self.feature_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"unsupported feature_dtype {self.feature_dtype!r}; "
                "expected float32 or bfloat16")
        if self.projector.upper() == "RANDOM":
            if self.projected_dimension is None \
                    or self.projected_dimension < 1:
                raise ValueError(
                    "projector=RANDOM needs projected_dimension >= 1")
            if self.features_to_samples_ratio is not None:
                raise ValueError(
                    "features_to_samples_ratio composes with INDEX_MAP "
                    "projection, not RANDOM (the random projection space "
                    "has no per-feature identity to filter)")
        elif self.projected_dimension is not None:
            raise ValueError(
                "projected_dimension only applies to projector=RANDOM")
        if (self.features_to_samples_ratio is not None
                and not self.features_to_samples_ratio > 0):
            raise ValueError(
                f"features_to_samples_ratio must be > 0, got "
                f"{self.features_to_samples_ratio}")


@dataclasses.dataclass(frozen=True)
class FactoredRandomEffectDataConfiguration:
    """Reference: the pre-fork FactoredRandomEffectDataConfiguration +
    MFOptimizationConfiguration (numLatentFactors → ``rank``,
    numInnerIterations → ``alternations``): per-entity models constrained
    to a shared rank-``rank`` subspace (see game/factored.py)."""

    random_effect_type: str
    feature_shard_id: str
    rank: int = 4
    alternations: int = 2
    active_data_lower_bound: int = 1
    active_data_upper_bound: Optional[int] = None

    def __post_init__(self):
        if self.rank < 1:
            raise ValueError(f"rank must be >= 1, got {self.rank}")
        if self.alternations < 1:
            raise ValueError(
                f"alternations must be >= 1, got {self.alternations}")


CoordinateDataConfiguration = Union[FixedEffectDataConfiguration,
                                    RandomEffectDataConfiguration,
                                    FactoredRandomEffectDataConfiguration]


@dataclasses.dataclass(frozen=True)
class CoordinateConfiguration:
    """One coordinate: its data slice + optimization settings + an optional
    regularization-weight grid (the reference's GameEstimator loops over a
    Seq[GameOptimizationConfiguration] built from per-coordinate grids)."""

    data: CoordinateDataConfiguration
    optimization: GLMOptimizationConfiguration
    reg_weight_grid: tuple[float, ...] = ()

    def expand_grid(self) -> list[GLMOptimizationConfiguration]:
        if not self.reg_weight_grid:
            return [self.optimization]
        out = []
        for w in self.reg_weight_grid:
            reg = dataclasses.replace(self.optimization.regularization,
                                      reg_weight=w)
            out.append(dataclasses.replace(self.optimization,
                                           regularization=reg))
        return out


def parse_kv(spec: str) -> dict[str, str]:
    """Parse the ``key=value,...`` mini-DSL used by reference-style config
    strings (shared by optimizer configs and CLI coordinate specs)."""
    kv: dict[str, str] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        k, sep, v = part.partition("=")
        if not sep:
            raise ValueError(f"bad config token {part!r} in {spec!r}")
        kv[k.strip()] = v.strip()
    return kv


def parse_staging_config(spec: str) -> StagingConfig:
    """Parse ``key=value,...`` mini-DSL for the random-effect staging
    pipeline (game/staging.py).

    Keys: workers (pool size; default = host cores), mode
    (thread|process), depth (max staged-but-unconsumed shard blocks),
    shard_entities (entity lanes per staged shard), retries (bounded
    per-shard retry budget), backoff (base seconds of the jittered
    retry backoff), straggler (straggler deadline in seconds — exceeded
    shards re-stage serially; see docs/ROBUSTNESS.md).
    """
    kv = parse_kv(spec)
    known = {"workers", "mode", "depth", "shard_entities", "retries",
             "backoff", "straggler"}
    unknown = set(kv) - known
    if unknown:
        raise ValueError(f"unknown staging keys {sorted(unknown)}; "
                         f"expected {sorted(known)}")
    defaults = StagingConfig()
    return StagingConfig(
        workers=int(kv["workers"]) if "workers" in kv else None,
        mode=kv.get("mode", "thread").lower(),
        pipeline_depth=int(kv["depth"]) if "depth" in kv else None,
        shard_entities=(int(kv["shard_entities"])
                        if "shard_entities" in kv else None),
        max_retries=(int(kv["retries"]) if "retries" in kv
                     else defaults.max_retries),
        retry_backoff_s=(float(kv["backoff"]) if "backoff" in kv
                         else defaults.retry_backoff_s),
        straggler_timeout_s=(float(kv["straggler"])
                             if "straggler" in kv else None),
    )


def parse_ingest_config(spec: str) -> IngestConfig:
    """Parse ``key=value,...`` mini-DSL for the parallel Avro ingestion
    pipeline (photon_ml_tpu/ingest, docs/INGEST.md).

    Keys: workers (decode pool size; default = host cores), mode
    (thread|process), depth (max decoded-but-unfolded chunks),
    chunk_records (target records per decode task). The columnar ingest
    cache directory is a separate flag (``game_train
    --ingest-cache-dir``), mirroring ``--staging-cache-dir``.
    """
    kv = parse_kv(spec)
    known = {"workers", "mode", "depth", "chunk_records"}
    unknown = set(kv) - known
    if unknown:
        raise ValueError(f"unknown ingest keys {sorted(unknown)}; "
                         f"expected {sorted(known)}")
    defaults = IngestConfig()
    return IngestConfig(
        workers=int(kv["workers"]) if "workers" in kv else None,
        mode=kv.get("mode", "thread").lower(),
        pipeline_depth=int(kv["depth"]) if "depth" in kv else None,
        chunk_records=(int(kv["chunk_records"]) if "chunk_records" in kv
                       else defaults.chunk_records),
    )


def parse_streaming_config(spec: str) -> StreamingConfig:
    """Parse ``key=value,...`` mini-DSL for the row-streamed fixed-effect
    path (docs/STREAMING.md). An empty spec (bare ``--streaming``) takes
    every default.

    Keys: chunk_rows (rows per streamed chunk), num_hot (hot-dense
    columns per chunk), dtype (float32|bfloat16|int8 chunk storage;
    default inherits the coordinate's dtype), depth (prefetch transfers
    in flight per device), pin (leading chunks pinned per device),
    workers (staging canonicalization threads), solver
    (lbfgs|sdca|sgd streamed driver — docs/STREAMING.md "Stochastic
    solvers").
    """
    kv = parse_kv(spec)
    known = {"chunk_rows", "num_hot", "dtype", "depth", "pin", "workers",
             "solver"}
    unknown = set(kv) - known
    if unknown:
        raise ValueError(f"unknown streaming keys {sorted(unknown)}; "
                         f"expected {sorted(known)}")
    defaults = StreamingConfig()
    return StreamingConfig(
        chunk_rows=(int(kv["chunk_rows"]) if "chunk_rows" in kv
                    else defaults.chunk_rows),
        num_hot=int(kv["num_hot"]) if "num_hot" in kv else defaults.num_hot,
        feature_dtype=kv["dtype"].lower() if "dtype" in kv else None,
        prefetch_depth=(int(kv["depth"]) if "depth" in kv
                        else defaults.prefetch_depth),
        pin_chunks=int(kv["pin"]) if "pin" in kv else defaults.pin_chunks,
        workers=int(kv["workers"]) if "workers" in kv else None,
        solver=(kv["solver"].lower() if "solver" in kv
                else defaults.solver),
    )


def parse_optimizer_config(spec: str) -> GLMOptimizationConfiguration:
    """Parse ``key=value,...`` mini-DSL (reference-style config strings).

    Keys: optimizer (LBFGS|OWLQN|TRON), max_iter, tolerance,
    reg (NONE|L1|L2|ELASTIC_NET), reg_weight, alpha, down_sampling_rate,
    variance (NONE|SIMPLE|FULL).
    """
    kv = parse_kv(spec)

    opt = OptimizerConfig(
        optimizer_type=OptimizerType(kv.get("optimizer", "LBFGS").upper()),
        max_iterations=int(kv.get("max_iter", 100)),
        tolerance=float(kv.get("tolerance", 1e-7)),
    )
    reg = RegularizationContext(
        reg_type=RegularizationType(kv.get("reg", "NONE").upper()),
        reg_weight=float(kv.get("reg_weight", 0.0)),
        elastic_net_alpha=float(kv.get("alpha", 0.5)),
    )
    return GLMOptimizationConfiguration(
        optimizer=opt,
        regularization=reg,
        variance_computation=VarianceComputationType(
            kv.get("variance", "NONE").upper()),
        down_sampling_rate=float(kv.get("down_sampling_rate", 1.0)),
    )
