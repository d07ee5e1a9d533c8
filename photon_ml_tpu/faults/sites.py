"""The fault-site registry: every injection point, as a named constant.

A fault site only exists at the moment a string at a ``fire()`` /
``poison_scalar()`` / ``corrupt_file()`` call matches a string in a
``FaultSpec`` — there is no registration step, so a typo on either side
does not fail, it silently never fires, and the chaos drill that
"passed" exercised nothing. This module is the fix: production call
sites import these constants instead of repeating literals, and lint
rule **PML014** (docs/ANALYSIS.md) checks every dotted site literal in
the tree — test fault plans included — against this registry.
``photon-lint --catalog`` emits the same registry as JSON for docs/CI.

Grouped by the subsystem that owns the instrumentation point; the
failure-ladder semantics of each site live in docs/ROBUSTNESS.md.
Sites addressed by ``corrupt_file`` keep their own name even when they
share a code path with a ``fire`` site: the two hooks count occurrences
independently, and sharing a name would interleave their occurrence
spaces (the ``stream.checkpoint_write`` / ``stream.checkpoint_artifact``
lesson, game/checkpoint.py).
"""

from __future__ import annotations

# -- random-effect staging (game/staging.py, game/staging_cache.py) ----------
STAGING_PHASE_A = "staging.phase_a"
STAGING_PHASE_B = "staging.phase_b"
STAGING_CACHE_SAVE_SHARD = "staging_cache.save_shard"
STAGING_CACHE_LOAD_SHARD = "staging_cache.load_shard"
STAGING_CACHE_SHARD_FILE = "staging_cache.shard_file"  # corrupt_file

# -- descent checkpoints (game/checkpoint.py) --------------------------------
CHECKPOINT_SAVE = "checkpoint.save"
CHECKPOINT_LOAD = "checkpoint.load"
CHECKPOINT_ARTIFACT = "checkpoint.artifact"  # corrupt_file

# -- streamed fixed-effect path (ops/streaming_sparse.py, optim/streaming.py,
#    game/checkpoint.py StreamingStateStore) ---------------------------------
STREAM_CHUNK_TRANSFER = "stream.chunk_transfer"
STREAM_QUANTIZE = "stream.quantize"  # corrupt_file (staged-chunk store)
STREAM_OBJECTIVE = "stream.objective"  # poison_scalar (nan kind)
STREAM_CHECKPOINT_WRITE = "stream.checkpoint_write"
STREAM_CHECKPOINT_LOAD = "stream.checkpoint_load"
STREAM_CHECKPOINT_ARTIFACT = "stream.checkpoint_artifact"  # corrupt_file

# -- stochastic streamed solvers (optim/stochastic.py) -----------------------
# OPT_DUAL_UPDATE fires BEFORE each chunk's stochastic update (kill seam:
# a SIGKILL mid-epoch must resume from the last epoch-boundary (w, α)
# snapshot to bit-identical coefficients); OPT_GAP_CHECK poisons the
# epoch's assembled duality gap (nan seam: the watchdog gap gate must
# turn a sick certificate into a loud, defined error).
OPT_DUAL_UPDATE = "opt.dual_update"
OPT_GAP_CHECK = "opt.gap_check"  # poison_scalar (nan kind)

# -- Avro ingestion (ingest/pipeline.py, ingest/cache.py) --------------------
INGEST_DECODE_BLOCK = "ingest.decode_block"
INGEST_CACHE_WRITE = "ingest.cache_write"
INGEST_CACHE_FILE = "ingest.cache_file"  # corrupt_file

# -- single-process serving (serving/service.py, serving/model_store.py) -----
SERVING_FLUSH = "serving.flush"
SERVING_FETCH = "serving.fetch"

# -- replicated fleet (serving/router.py, serving/supervisor.py,
#    serving/service.py) -----------------------------------------------------
FLEET_ROUTE = "fleet.route"
FLEET_PROBE = "fleet.probe"
FLEET_REPLICA_FLUSH = "fleet.replica_flush"

# -- elastic fleet (serving/elastic.py; docs/SERVING.md "Elastic fleet") -----
# Each fires BEFORE its map/fleet mutation, so a fault leaves the shard
# map at exactly the old version — and the mutation itself is one
# version bump under the map lock, so a fault after it leaves exactly
# the new version: never torn (the mid-split kill contract).
FLEET_SPLIT = "fleet.split"
FLEET_MIGRATE = "fleet.migrate"
FLEET_SCALE = "fleet.scale"

# -- boot: mmap model publication (boot/mapfmt.py, boot/generations.py) ------
BOOT_MAP_WRITE = "boot.map_write"
BOOT_MAP_OPEN = "boot.map_open"  # corrupt_file (post-CRC bit rot in a blob)
BOOT_COMPACT = "boot.compact"

# -- fused-kernel registry (ops/kernels/registry.py) -------------------------
# Fires at the moment the registry commits to the Pallas backend for a
# kernel — BEFORE any program is built — so a fault here exercises the
# degradation contract: the resolve falls back to the XLA closure, emits
# a KernelFallback event + photon_kernel_fallbacks_total, and the caller
# never sees the failure (docs/KERNELS.md "Failure ladder").
KERNEL_LAUNCH = "kernel.launch"

# -- continuous publication (serving/publish.py, serving/fleet.py,
#    serving/model_store.py) -------------------------------------------------
PUBLISH_DELTA_WRITE = "publish.delta_write"
PUBLISH_DELTA_ARTIFACT = "publish.delta_artifact"  # corrupt_file
PUBLISH_CANARY_APPLY = "publish.canary_apply"
PUBLISH_SWAP = "publish.swap"
PUBLISH_ROLLBACK = "publish.rollback"

# -- multi-host fabric (fabric/collective.py, fabric/transport.py,
#    serving/publish.py fetch_delta; docs/ROBUSTNESS.md "Fabric") ------------
# FABRIC_DCN_ALLREDUCE fires once per cross-host allreduce ATTEMPT
# (index = the round's sequence number), inside the retry ladder — a
# `partition` spec here models the DCN edge dropping a round;
# FABRIC_HEARTBEAT fires before each machine-agent liveness query (the
# remote analogue of FLEET_PROBE: a `delay` spec models a slow agent,
# which must NOT be declared a death); FABRIC_ADOPT fires at the moment
# a RemoteTransport adopts an already-running remote replica instead of
# respawning; FABRIC_DELTA_FETCH fires once per artifact file pulled
# over HTTP by a remote replica (a `partition`/`corrupt` spec models a
# torn fetch, which must leave the previous model version servable).
FABRIC_DCN_ALLREDUCE = "fabric.dcn_allreduce"
FABRIC_ADOPT = "fabric.adopt"
FABRIC_HEARTBEAT = "fabric.heartbeat"
FABRIC_DELTA_FETCH = "fabric.delta_fetch"

# Every registered site. Computed from the module's own constants so the
# registry cannot drift from itself; PML014 reads the CONSTANTS above
# via AST (this comprehension never runs under the linter).
ALL_SITES = frozenset(
    v for k, v in dict(globals()).items()
    if not k.startswith("_") and isinstance(v, str) and k.isupper())
