"""Replica supervision: spawn, probe, and restart scoring replicas.

The fleet (serving/fleet.py) scales the single-process ``ScoringService``
horizontally: N OS-process replicas, each a full ``photon-game-serve``
server over the same model. This module owns their LIFECYCLE — the
process-level analogue of the micro-batcher's supervised worker thread
(PR 4's ``BatcherDied`` discipline, lifted one level):

- **Spawn.** How an incarnation starts is the TRANSPORT's business
  (fabric/transport.py): ``LocalTransport`` is the original subprocess
  mechanism verbatim — ``spawn``-style children (fresh interpreters:
  the parent holds live XLA runtime threads and forking them is
  undefined, the utils/workers.py rule), output to FILES never pipes
  (XLA's CPU warnings alone can overflow a 64 KB pipe buffer, and an
  undrained pipe blocks the child mid-request — the
  tests/test_multiprocess.py lesson), the bound port traveling back
  through a generation-named ready-file (``--ready-file`` in
  cli/serve.py — no port-allocation race). ``RemoteTransport`` starts
  the same replica on another machine via its agent and hands back an
  address. The LADDER below neither knows nor cares which.
- **Probe.** A monitor thread polls each replica: transport-level
  liveness (``proc.poll()`` locally; the agent's view remotely — with
  ``None`` = "cannot see the process layer", which is NOT a death),
  then GET ``/healthz`` (explicit timeout — PML011) for liveness. A
  replica whose last good probe is older than ``heartbeat_deadline_s``
  is DECLARED dead even if the process lingers (a wedged replica is
  dead for routing purposes; the lingering process is SIGKILLed so it
  cannot answer a stale hedge later).
- **Recover.** Death fires ``on_death(replica_id)`` synchronously on the
  monitor thread — the fleet re-homes the replica's shards there, inside
  the detection-to-recovery window the rehome deadline measures — then
  the supervisor restarts the replica (bounded ``max_restarts``,
  deterministic backoff) and fires ``on_recovered(replica_id)`` once the
  newcomer answers ``/healthz``. Under a ``RemoteTransport``, a restart
  whose home MACHINE is dead fails over to the next machine — the
  whole-group-death drill's bounded cross-machine re-home.

Every blocking network call in this module carries an explicit timeout
(lint rule PML011 mechanizes that for router/supervisor code).
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import subprocess
import threading
import time
import urllib.request
from typing import Callable, Optional, Sequence

from photon_ml_tpu import faults as flt
from photon_ml_tpu.fabric.transport import (  # noqa: F401  (re-export)
    LocalTransport, ReplicaStartupError, Transport)

logger = logging.getLogger("photon_ml_tpu.serving.fleet")

# Replica states (the /healthz fleet view renders these verbatim).
STARTING = "starting"
UP = "up"
DOWN = "down"
RESTARTING = "restarting"
FAILED = "failed"  # restart budget exhausted — stays down, fleet degraded
RETIRED = "retired"  # scaled down deliberately — not a failure state


@dataclasses.dataclass
class ReplicaHandle:
    """One supervised replica process (mutable; guarded by the
    supervisor's lock for state transitions)."""

    replica_id: int
    proc: Optional[subprocess.Popen] = None  # LocalTransport only
    host: str = "127.0.0.1"
    port: int = 0
    state: str = STARTING
    last_ok: float = 0.0  # monotonic instant of the last good probe
    restarts: int = 0
    generation: int = 0  # bumped per spawn — ready files never reused
    last_restart_at: float = 0.0  # monotonic instant of the last restart
    log_path: str = ""
    boot_seconds: float = 0.0  # spawn → first healthy probe, last (re)start
    spawned_at: float = 0.0  # monotonic instant of the last _spawn
    machine: str = ""  # placement (agent base URL; '' when local)

    def base_url(self) -> str:
        return f"http://{self.host}:{self.port}"


def _probe_healthz(url: str, timeout_s: float) -> dict:
    """GET ``url``/healthz with an explicit timeout; raises on any
    failure (connection refused/reset, HTTP error, bad JSON)."""
    with urllib.request.urlopen(f"{url}/healthz",
                                timeout=timeout_s) as resp:
        return json.loads(resp.read())


class ReplicaSupervisor:
    """Spawns and babysits ``num_replicas`` scoring-replica processes.

    ``make_argv(replica_id, ready_file)`` returns the child's argv (the
    fleet builds it around ``python -m photon_ml_tpu.cli.serve``); the
    supervisor owns ready-file handshakes, health probing, death
    declaration, and bounded restart. ``on_death`` / ``on_recovered``
    run on the monitor thread — re-homing happens inside ``on_death`` so
    the rehome clock starts at detection.

    ``transport`` picks the replica-start MECHANISM (default: a
    ``LocalTransport`` over ``make_argv``/``workdir``, which is the
    original in-process-supervised subprocess behavior verbatim).
    """

    def __init__(
        self,
        make_argv: Callable[[int, str], Sequence[str]],
        num_replicas: int,
        workdir: str,
        probe_interval_s: float = 0.25,
        probe_timeout_s: float = 1.0,
        heartbeat_deadline_s: float = 2.0,
        start_timeout_s: float = 120.0,
        max_restarts: int = 3,
        restart_backoff_s: float = 0.1,
        backoff_reset_s: float = 60.0,
        on_death: Optional[Callable[[int], None]] = None,
        on_recovered: Optional[Callable[[int], None]] = None,
        transport: Optional[Transport] = None,
    ):
        if num_replicas < 1:
            raise ValueError(f"num_replicas must be >= 1, "
                             f"got {num_replicas}")
        self._make_argv = make_argv
        self.workdir = workdir
        self.transport = (transport if transport is not None
                          else LocalTransport(make_argv, workdir))
        self.probe_interval_s = float(probe_interval_s)
        self.probe_timeout_s = float(probe_timeout_s)
        self.heartbeat_deadline_s = float(heartbeat_deadline_s)
        self.start_timeout_s = float(start_timeout_s)
        self.max_restarts = int(max_restarts)
        self.restart_backoff_s = float(restart_backoff_s)
        # The backoff-ladder amnesty (ISSUE 15 satellite): a replica
        # healthy this long after a restart earns its ladder back — a
        # crash-once-then-healthy-for-hours replica must not pay the
        # escalated backoff (and restart budget) on its NEXT death.
        self.backoff_reset_s = float(backoff_reset_s)
        self._on_death = on_death
        self._on_recovered = on_recovered
        self.replicas = [ReplicaHandle(replica_id=i)
                         for i in range(num_replicas)]
        self._lock = threading.Lock()
        self._running = False
        self._monitor: Optional[threading.Thread] = None

    # -- spawn / handshake ---------------------------------------------------

    def _spawn(self, handle: ReplicaHandle) -> None:
        # Generation, not restart count, names the ready file: the
        # backoff-reset amnesty rewinds `restarts`, and a rewound name
        # could collide with a DEAD incarnation's file.
        handle.generation += 1
        handle.state = STARTING
        handle.spawned_at = time.monotonic()
        self.transport.spawn(handle)

    def _await_ready(self, handle: ReplicaHandle) -> None:
        """Wait for the transport's address handshake, then a first
        good probe. The spawn→healthy wall lands in
        ``handle.boot_seconds`` — the replica-restart tail photon-boot
        attacks, measured where the fleet actually waits for it
        (``bench_serving.py --restart`` reads it back as
        ``photon_fleet_replica_boot_seconds``)."""
        rid = handle.replica_id
        t_spawn = handle.spawned_at or time.monotonic()
        deadline = time.monotonic() + self.start_timeout_s
        host, port = self.transport.await_ready(handle, deadline)
        handle.host = host
        handle.port = int(port)
        while time.monotonic() < deadline:
            try:
                _probe_healthz(handle.base_url(), self.probe_timeout_s)
                with self._lock:
                    handle.state = UP
                    handle.last_ok = time.monotonic()
                    handle.boot_seconds = handle.last_ok - t_spawn
                logger.info("replica %d healthy at %s (boot %.3fs)", rid,
                            handle.base_url(), handle.boot_seconds)
                return
            except (OSError, ValueError):
                time.sleep(0.05)
        raise ReplicaStartupError(
            f"replica {rid} bound {handle.base_url()} but never answered "
            f"/healthz within {self.start_timeout_s}s")

    def start(self) -> None:
        """Spawn every replica and wait until all answer /healthz."""
        os.makedirs(self.workdir, exist_ok=True)
        self.transport.check_capacity(len(self.replicas))
        try:
            for handle in self.replicas:
                self._spawn(handle)
            for handle in self.replicas:
                self._await_ready(handle)
        except ReplicaStartupError:
            self.stop()
            raise
        self._running = True
        self._monitor = threading.Thread(
            target=self._monitor_loop, name="photon-fleet-monitor",
            daemon=True)
        self._monitor.start()

    # -- elastic scale (docs/SERVING.md "Elastic fleet") ---------------------

    def add_replica(self) -> int:
        """Spawn ONE more supervised replica (the scale-up leg): next
        integer id, full spawn → ready-file → healthy handshake before
        it is visible to routing. Returns the new replica id; raises
        ``ReplicaStartupError`` (and reaps the half-started process) on
        failure — the fleet's map never learns about a replica that
        did not reach healthy."""
        handle = ReplicaHandle(replica_id=len(self.replicas))
        self._spawn(handle)
        try:
            self._await_ready(handle)
        except ReplicaStartupError:
            self.transport.kill(handle)
            raise
        with self._lock:
            self.replicas.append(handle)
        logger.info("replica %d scaled up (fleet now %d)",
                    handle.replica_id, len(self.replicas))
        return handle.replica_id

    def retire(self, replica_id: int) -> None:
        """Retire a DRAINED replica (the scale-down leg): mark it
        RETIRED first — the monitor never restarts a retired replica —
        then terminate the process. Deliberate, not a failure: no
        on_death fires, no restart follows."""
        handle = self.replicas[replica_id]
        with self._lock:
            handle.state = RETIRED
        self.transport.terminate(handle, timeout_s=10.0)
        logger.info("replica %d retired", replica_id)

    def kill_replica(self, replica_id: int) -> None:
        """Hard-kill a replica's PROCESS without touching its state —
        the chaos-drill seam (fleet ``/admin/kill``): the monitor must
        DISCOVER the death through its own probes, so detection latency
        stays in the measured rehome window."""
        self.transport.kill(self.replicas[replica_id])

    # -- monitoring ----------------------------------------------------------

    def _probe_once(self, handle: ReplicaHandle) -> bool:
        """One liveness check; True = the replica looked alive."""
        if self.transport.alive(handle) is False:
            return False  # positively gone; None (can't see) still probes
        try:
            # Injection seam: a `partition` spec here models the
            # monitor losing sight of a replica (probes dropped while
            # the replica itself is fine) — the false-positive death
            # the heartbeat deadline turns into a defined re-home.
            flt.fire(flt.sites.FLEET_PROBE, index=handle.replica_id)
            _probe_healthz(handle.base_url(), self.probe_timeout_s)
            return True
        except (OSError, ValueError):
            return False

    def maybe_reset_backoff(self, handle: ReplicaHandle,
                            now: Optional[float] = None) -> bool:
        """Reset a replica's restart ladder after ``backoff_reset_s``
        of healthy uptime since its last restart; True = reset
        happened. Pure bookkeeping — callable from tests directly."""
        now = time.monotonic() if now is None else now
        with self._lock:
            if (handle.state == UP and handle.restarts > 0
                    and handle.last_restart_at > 0.0
                    and now - handle.last_restart_at
                    >= self.backoff_reset_s):
                logger.info(
                    "replica %d healthy %.0fs since its last restart — "
                    "resetting its backoff ladder (%d restart(s) "
                    "forgiven)", handle.replica_id,
                    now - handle.last_restart_at, handle.restarts)
                handle.restarts = 0
                handle.last_restart_at = 0.0
                return True
        return False

    def _monitor_loop(self) -> None:
        while self._running:
            for handle in list(self.replicas):
                if not self._running:
                    return
                if handle.state not in (UP,):
                    continue
                now = time.monotonic()
                if self._probe_once(handle):
                    with self._lock:
                        handle.last_ok = now
                    self.maybe_reset_backoff(handle, now)
                elif (self.transport.alive(handle) is False
                      or now - handle.last_ok
                      >= self.heartbeat_deadline_s):
                    # Positive process death, or /healthz silence past
                    # the deadline. An UNKNOWN process layer (remote
                    # agent unreachable — fabric.heartbeat partition)
                    # deliberately does NOT short-circuit to death.
                    self._handle_death(handle)
            time.sleep(self.probe_interval_s)

    def _handle_death(self, handle: ReplicaHandle) -> None:
        rid = handle.replica_id
        with self._lock:
            if handle.state != UP:
                return
            handle.state = DOWN
        gone = self.transport.alive(handle) is False
        where = self.transport.describe(handle)
        logger.error("replica %d%s declared dead (%s; last good probe "
                     "%.2fs ago)", rid, f" on {where}" if where else "",
                     "process exited" if gone else "heartbeat deadline",
                     time.monotonic() - handle.last_ok)
        # A wedged-but-alive process must not answer a stale request
        # after its shards re-home — kill it before announcing death.
        if not gone:
            self.transport.kill(handle)
        if self._on_death is not None:
            try:
                self._on_death(rid)
            except Exception:
                logger.exception("on_death(%d) callback failed", rid)
        self._restart(handle)

    def _restart(self, handle: ReplicaHandle) -> None:
        rid = handle.replica_id
        if handle.restarts >= self.max_restarts:
            with self._lock:
                handle.state = FAILED
            logger.error("replica %d exhausted its %d restarts — fleet "
                         "stays degraded", rid, self.max_restarts)
            return
        with self._lock:
            handle.state = RESTARTING
            handle.restarts += 1
            handle.last_restart_at = time.monotonic()
        # Deterministic backoff (no jitter: drills must replay exactly).
        time.sleep(self.restart_backoff_s * handle.restarts)
        try:
            self._spawn(handle)
            self._await_ready(handle)
        except ReplicaStartupError as e:
            logger.error("replica %d restart failed: %s", rid, e)
            with self._lock:
                handle.state = DOWN
            # Next monitor pass will not see UP, so retry from here.
            self._restart(handle)
            return
        if self._on_recovered is not None:
            try:
                self._on_recovered(rid)
            except Exception:
                logger.exception("on_recovered(%d) callback failed", rid)

    # -- views / lifecycle ---------------------------------------------------

    def states(self) -> dict[int, str]:
        with self._lock:
            return {h.replica_id: h.state for h in self.replicas}

    def up_replicas(self) -> list[int]:
        with self._lock:
            return [h.replica_id for h in self.replicas if h.state == UP]

    def endpoint(self, replica_id: int) -> tuple[str, int]:
        h = self.replicas[replica_id]
        return h.host, h.port

    def stop(self) -> None:
        self._running = False
        if self._monitor is not None and self._monitor.is_alive():
            self._monitor.join(timeout=5.0)
        for handle in self.replicas:
            self.transport.terminate(handle, timeout_s=10.0)
            handle.state = DOWN

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()
