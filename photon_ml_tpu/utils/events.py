"""Training lifecycle events: emitter + listener registry.

Reference parity: photon-lib ``event/`` (PhotonMLEvent hierarchy +
EventEmitter trait consumed by the drivers for audit logging and external
progress reporting). TPU-native shape: plain dataclass events dispatched
synchronously from the coordinate-descent loop and the estimator — there
is no executor fan-in to marshal, so a listener is just a callable.

Listeners must be cheap and non-failing; a raising listener is logged and
detached rather than killing training (the reference swallows listener
errors the same way).
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Callable, Optional

logger = logging.getLogger("photon_ml_tpu.events")


@dataclasses.dataclass(frozen=True)
class Event:
    """Base class for all training events (PhotonMLEvent parity)."""


@dataclasses.dataclass(frozen=True)
class TrainingStart(Event):
    task: str
    update_sequence: tuple
    iterations: int


@dataclasses.dataclass(frozen=True)
class CoordinateUpdate(Event):
    """One (iteration, coordinate) block update finished
    (PhotonOptimizationLogEvent parity)."""

    iteration: int
    coordinate: str
    train_seconds: float
    validation: Optional[dict] = None


@dataclasses.dataclass(frozen=True)
class TrainingFinish(Event):
    task: str
    total_updates: int


@dataclasses.dataclass(frozen=True)
class StagingStart(Event):
    """One random-effect staging pipeline starting: ``num_shards`` staged
    bucket groups over ``workers`` pool workers (``mode`` "thread" or
    "process"); ``cached_shards`` of them will come from the staging cache
    without restaging."""

    label: str  # "<re_type>:<shard_id>"
    num_shards: int
    workers: int
    mode: str
    cached_shards: int


@dataclasses.dataclass(frozen=True)
class StagingShard(Event):
    """One staged bucket group became available to the fit stream.
    ``source`` is "staged" (projected now) or "cache" (memory-mapped from
    the staging cache); ``seconds`` is the projection+gather time for
    staged shards (0.0 for cache hits)."""

    label: str
    index: int
    bucket: int
    entities: int
    seconds: float
    source: str


@dataclasses.dataclass(frozen=True)
class StagingRetry(Event):
    """One staged shard's task failed and is being retried (bounded,
    jittered backoff — docs/ROBUSTNESS.md). ``attempt`` is 1-based."""

    label: str
    index: int
    attempt: int
    error: str


@dataclasses.dataclass(frozen=True)
class StagingStraggler(Event):
    """One staged shard exceeded the straggler deadline and was
    re-staged serially; the late pool result is discarded (content is
    scheduling-independent, so either producer's bytes are THE bytes)."""

    label: str
    index: int
    waited_seconds: float


@dataclasses.dataclass(frozen=True)
class StreamStageStart(Event):
    """One streamed fixed-effect chunk-staging pass starting: the
    coordinate's SparseShard canonicalizes into ``num_chunks``
    hot-dense/cold-ELL chunks over ``workers`` staging threads
    (docs/STREAMING.md)."""

    shard_id: str
    num_rows: int
    chunk_rows: int
    num_chunks: int
    workers: int


@dataclasses.dataclass(frozen=True)
class StreamStageFinish(Event):
    """The chunk-staging pass ended (finally-guarded pair with
    StreamStageStart). ``num_chunks`` is 0 when staging raised before
    the layout was built."""

    shard_id: str
    num_chunks: int
    seconds: float


@dataclasses.dataclass(frozen=True)
class IngestStart(Event):
    """One Avro ingestion pipeline starting: ``num_chunks`` block-aligned
    decode tasks over ``num_files`` container files, fanned over
    ``workers`` pool workers (``mode`` "thread" or "process");
    ``cached_chunks`` of them load from the columnar ingest cache
    without touching Avro bytes (photon_ml_tpu/ingest)."""

    num_files: int
    num_chunks: int
    workers: int
    mode: str
    cached_chunks: int


@dataclasses.dataclass(frozen=True)
class IngestBlock(Event):
    """One decoded chunk (a sync-aligned run of Avro blocks) became
    available to the columnar fold. ``source`` is "decoded" (native
    block decode ran now) or "cache" (memory-mapped from the ingest
    cache); ``seconds`` is the decode time (0.0 for cache hits)."""

    index: int
    records: int
    seconds: float
    source: str


@dataclasses.dataclass(frozen=True)
class IngestFinish(Event):
    """Every chunk of one ingestion pipeline was consumed by the fold
    (or the pipeline was abandoned after ``num_chunks`` consumed chunks
    on error — the Start/Finish pair is finally-guarded)."""

    num_files: int
    num_chunks: int
    records: int
    cached_chunks: int
    wall_seconds: float


@dataclasses.dataclass(frozen=True)
class IngestFallback(Event):
    """Avro ingestion degraded to the pure-Python codec (far slower
    than the native block decoder) instead of the parallel native path; ``reason`` says why (no toolchain,
    unsupported schema, ...)."""

    reason: str


@dataclasses.dataclass(frozen=True)
class KernelFallback(Event):
    """A registered fused kernel (ops/kernels) degraded to its XLA
    fallback closure instead of the Pallas program the flag asked for —
    the kernel-registry analog of IngestFallback's loud-degradation
    discipline. ``kernel`` is the registry name, ``backend`` the backend
    the resolve actually landed on ("xla"), ``reason`` why (an
    injected kernel.launch fault). The obs bridge turns this into
    ``photon_kernel_fallbacks_total{kernel=...}`` + a timeline instant;
    a silent fallback would let a flagged perf win quietly evaporate."""

    kernel: str
    backend: str
    reason: str


@dataclasses.dataclass(frozen=True)
class CheckpointRecovered(Event):
    """A corrupted checkpoint artifact failed its CRC and the manager
    fell back to the previous committed generation (game/checkpoint.py).
    ``done_steps`` is the step count of the RECOVERED state."""

    directory: str
    done_steps: int
    reason: str


@dataclasses.dataclass(frozen=True)
class BootRecovered(Event):
    """A generation store's CURRENT generation could not be trusted
    (blob CRC mismatch, torn/unparseable marker) and the boot path fell
    back one committed generation (boot/generations.py). Loud by
    contract: a replica that silently booted an older model would serve
    stale rows with no operator signal — the obs bridge turns this into
    a timeline instant + ``photon_boot_recoveries_total``."""

    directory: str
    from_version: int  # the generation that failed verification
    to_version: int  # the generation actually booted
    reason: str


@dataclasses.dataclass(frozen=True)
class WatchdogAlert(Event):
    """A convergence watchdog fired (obs/watchdog.py): ``kind`` names
    the detector (nan/stall/divergence/slow_iter), ``action`` what
    happened (warn/stop/raise). The obs bridge turns these into timeline
    instants + ``photon_watchdog_alerts_total{kind=...}``."""

    kind: str
    action: str
    detail: str
    coordinate: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class StagingFinish(Event):
    """Every shard of one staging pipeline is produced (NOT necessarily
    consumed — consumption is the fit stream's side of the handoff)."""

    label: str
    num_shards: int
    cached_shards: int
    wall_seconds: float


@dataclasses.dataclass(frozen=True)
class ScoringStart(Event):
    """A scoring lifecycle begins — one offline driver run (``source=
    "game_score"``) or one online service coming up (``source="serving"``,
    ``num_rows`` None: the stream is unbounded)."""

    source: str
    num_rows: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class ScoringBatch(Event):
    """One device scoring batch finished: ``rows`` real rows scored inside
    a ``padded_rows``-shaped program (shape-bucketing pads; padded_rows ==
    rows on the unbatched offline path)."""

    source: str
    rows: int
    padded_rows: int
    seconds: float


@dataclasses.dataclass(frozen=True)
class ScoringFinish(Event):
    source: str
    num_rows: int
    wall_seconds: float


@dataclasses.dataclass(frozen=True)
class ReplicaDied(Event):
    """A fleet scoring replica was declared dead (process exit or
    heartbeat-deadline expiry) — the `CheckpointRecovered` of the
    serving fleet's failure ladder (docs/SERVING.md "Scaling out")."""

    replica_id: int
    reason: str


@dataclasses.dataclass(frozen=True)
class ShardRehomed(Event):
    """A dead replica's routing shards were re-assigned to survivors
    (serving/router.py ShardMap). ``seconds`` is detection → the new
    owners confirmed healthy — the window `fleet_rehome_seconds`
    gates against the configured deadline."""

    replica_id: int
    shards: tuple[int, ...]
    new_owners: tuple[int, ...]
    seconds: float


@dataclasses.dataclass(frozen=True)
class ReplicaRecovered(Event):
    """A restarted replica answered /healthz and its home shards moved
    back; the fleet leaves the degraded state when every replica is
    healthy again."""

    replica_id: int
    shards_restored: tuple[int, ...]


@dataclasses.dataclass(frozen=True)
class ShardSplit(Event):
    """A hot shard split into two consistent-hash children
    (serving/router.py ShardMap.split + serving/elastic.py;
    docs/SERVING.md "Elastic fleet"). ``heat_fraction`` is the share of
    the window's total heat the parent carried when the controller
    ruled — the triggering evidence, also written to the ``elastic``
    ledger row."""

    shard: int
    children: tuple[int, int]
    heat_fraction: float
    map_version: int


@dataclasses.dataclass(frozen=True)
class ReplicaScaled(Event):
    """The elastic controller changed the fleet's replica count:
    ``direction`` "up" (spawned + warmed + admitted to the map) or
    "down" (drained → migrated empty → retired). ``reason`` names the
    triggering signal (error-budget burn, queue depth, heat
    imbalance, idle)."""

    direction: str
    replica_id: int
    num_replicas: int
    reason: str


@dataclasses.dataclass(frozen=True)
class FleetDegraded(Event):
    """The overload ladder changed state (docs/SERVING.md "Elastic
    fleet" brownout semantics): ``mode`` "brownout" = per-shard
    admission tightened on ``hot_shards`` (their 503s name the shard),
    "recovered" = the ladder released."""

    mode: str
    hot_shards: tuple[int, ...]
    reason: str


@dataclasses.dataclass(frozen=True)
class DeltaPublished(Event):
    """A versioned model delta finished the canary ladder and is live on
    EVERY replica (serving/publish.py + fleet.py; docs/SERVING.md
    "Continuous publication"). ``entities`` is the total dirty-row count
    across coordinates."""

    version: int
    coordinates: tuple[str, ...]
    entities: int
    canary_replica: int
    swap_seconds: float


@dataclasses.dataclass(frozen=True)
class CanaryVerdict(Event):
    """The canary judge ruled on one delta after its bake window:
    ``accepted`` False carries the rejection ``reason`` (the delta never
    reaches a non-canary replica; a RollbackExecuted follows when the
    canary had already applied it)."""

    version: int
    replica_id: int
    accepted: bool
    reason: str
    burn_rate: float


@dataclasses.dataclass(frozen=True)
class RollbackExecuted(Event):
    """A delta was backed out (canary rejection or a failed fleet-wide
    swap): every replica that applied ``version`` restored the previous
    rows. ``replicas`` lists who rolled back."""

    version: int
    reason: str
    replicas: tuple[int, ...]


class EventEmitter:
    """Synchronous listener registry (EventEmitter trait parity)."""

    def __init__(self):
        self._listeners: list[Callable[[Event], None]] = []

    def register(self, listener: Callable[[Event], None]) -> None:
        self._listeners.append(listener)

    def unregister(self, listener: Callable[[Event], None]) -> None:
        self._listeners.remove(listener)

    def emit(self, event: Event) -> None:
        for listener in list(self._listeners):
            try:
                listener(event)
            except Exception:
                logger.exception(
                    "event listener %r failed on %r — detaching it",
                    listener, event)
                if listener in self._listeners:  # may have self-unregistered
                    self._listeners.remove(listener)


# Process-wide default emitter: drivers and libraries emit here unless
# handed an explicit one (the reference's driver-scoped emitter analog).
default_emitter = EventEmitter()
