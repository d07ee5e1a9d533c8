"""Device-mesh conventions: the rebuild's "cluster" abstraction.

Reference parity: none file-for-file — this replaces the Spark runtime
(executors, torrent broadcast, netty shuffle, driver-coordinated
``treeAggregate``) with XLA's compiled collectives over a
``jax.sharding.Mesh`` (SURVEY.md §5 "Distributed communication backend").

Axis conventions:

- ``data``   — examples (fixed-effect data parallelism, P1) and entities
               (random-effect entity parallelism, P2). Gradient reductions
               ride ICI as ``psum`` over this axis.
- ``model``  — feature dimension for the sharded sparse path (P3, Criteo
               regime). Usually size 1.

Multi-host (the DCN story, SURVEY.md §2.5 P6 / §5): call
``initialize_distributed()`` (or ``make_mesh(distributed=True)``) once per
process before building the mesh. It wraps ``jax.distributed.initialize``,
which wires every host's local devices into one global device set; XLA then
routes intra-slice collectives over ICI and cross-slice traffic over DCN —
the same ``shard_map``/``psum`` programs compile unchanged from 1 chip to a
multi-host pod (collectives become no-ops at world size 1).

Coordinator discovery follows the standard JAX environment contract
(honored automatically on Cloud TPU metadata; settable explicitly anywhere):

- ``JAX_COORDINATOR_ADDRESS`` (or the ``coordinator_address`` argument)
- ``JAX_NUM_PROCESSES`` / ``JAX_PROCESS_ID`` (or arguments)

Failure model: there is NO lineage re-execution in XLA — a lost host kills
the step. Recovery is restart-from-checkpoint: relaunch the job and pass
``--resume`` to ``cli/game_train.py`` (game/checkpoint.py restores
per-(iteration, coordinate) state; see that module's crash-consistency
notes). This mirrors how the reference's Spark lineage recovery is replaced
throughout the rebuild.
"""

from __future__ import annotations

import logging
import os
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

logger = logging.getLogger("photon_ml_tpu.parallel")

shard_map = jax.shard_map

DATA_AXIS = "data"
MODEL_AXIS = "model"

_distributed_initialized = False


def initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> bool:
    """Join this process to the multi-host world (idempotent).

    Arguments default to the ``JAX_COORDINATOR_ADDRESS`` /
    ``JAX_NUM_PROCESSES`` / ``JAX_PROCESS_ID`` environment variables; on
    Cloud TPU all three are discoverable from metadata and may be omitted
    entirely. Returns True when running multi-process afterwards.

    Reference parity: the Spark cluster bootstrap (SparkSession + executor
    registration) — here one collective-runtime handshake, after which
    ``jax.devices()`` spans every host and ``make_mesh`` lays axes over the
    global device set.
    """
    global _distributed_initialized
    if _distributed_initialized:
        return jax.process_count() > 1
    kwargs = {}
    if coordinator_address or os.environ.get("JAX_COORDINATOR_ADDRESS"):
        kwargs["coordinator_address"] = (
            coordinator_address or os.environ["JAX_COORDINATOR_ADDRESS"])
    if num_processes is not None or os.environ.get("JAX_NUM_PROCESSES"):
        kwargs["num_processes"] = int(
            num_processes if num_processes is not None
            else os.environ["JAX_NUM_PROCESSES"])
    if process_id is not None or os.environ.get("JAX_PROCESS_ID"):
        kwargs["process_id"] = int(
            process_id if process_id is not None
            else os.environ["JAX_PROCESS_ID"])
    jax.distributed.initialize(**kwargs)
    _distributed_initialized = True
    logger.info("distributed runtime up: process %d/%d, %d local / %d "
                "global devices", jax.process_index(), jax.process_count(),
                jax.local_device_count(), jax.device_count())
    return jax.process_count() > 1


def make_mesh(
    num_data: Optional[int] = None,
    num_model: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
    distributed: bool = False,
    local: bool = False,
) -> Mesh:
    """Build a (data, model) mesh over the available devices.

    With ``distributed=True``, first joins the multi-host world (see
    ``initialize_distributed``) so the mesh spans every host's devices;
    shardings over ``data`` then reduce over ICI within a slice and DCN
    across slices, exactly as laid out.

    With ``local=True``, the mesh spans THIS HOST's devices only — the
    fabric topology (fabric/collective.py): intra-host reductions stay
    compiled ICI ``psum`` programs, and the cross-host level is the
    host-driven ``FabricComm`` allreduce instead of an XLA collective
    (mandatory on CPU process groups, where XLA's multiprocess
    collectives are not implemented; on TPU it trades the compiled DCN
    path for a faultable one).
    """
    if distributed:
        initialize_distributed()
    if local and devices is None:
        devices = jax.local_devices()
    devices = list(devices if devices is not None else jax.devices())
    if num_data is None:
        num_data = len(devices) // num_model
    if num_data * num_model != len(devices):
        devices = devices[: num_data * num_model]
    arr = np.asarray(devices).reshape(num_data, num_model)
    return Mesh(arr, (DATA_AXIS, MODEL_AXIS))


def device_summary() -> dict:
    """What this process computes on, as JAX reports it. Every driver logs
    it once at start, so a run that landed on the CPU cannot pass for a
    run on the chip."""
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def data_sharded(mesh: Mesh, ndim: int = 1) -> NamedSharding:
    """Shard the leading (example/entity) dim over ``data``."""
    return NamedSharding(mesh, P(DATA_AXIS, *(None,) * (ndim - 1)))


def pad_to_multiple(n: int, k: int) -> int:
    return ((n + k - 1) // k) * k


def shard_batch(batch, mesh: Mesh):
    """Pad a LabeledBatch to a multiple of the data-axis size and place it
    sharded over ``data`` (zero-weight padding rows are inert by design)."""
    k = mesh.shape[DATA_AXIS]
    n = batch.num_rows
    padded = batch.pad_to(pad_to_multiple(n, k))
    return jax.device_put(
        padded,
        jax.tree.map(
            lambda leaf: data_sharded(mesh, np.ndim(leaf)),
            padded,
        ),
    )
