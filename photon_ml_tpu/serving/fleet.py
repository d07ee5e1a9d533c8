"""photon-fleet: replicated serving with entity-affinity routing.

The single-process ``ScoringService`` (serving/service.py) is the
degenerate case ROADMAP item 3 promised to outgrow: one process, one
device cannot serve "millions of users". ``ServingFleet`` instates the
multi-host layout the host store was designed around:

    clients ──▶ fleet front door (this module)
                  │  admission control (503: replica id + fleet depth)
                  ▼
              FleetRouter (router.py): entity → shard → owning replica,
                  bounded retry, hedged second-sends
                  │
        ┌─────────┼─────────┐
        ▼         ▼         ▼
    replica 0  replica 1  replica N-1     ← ReplicaSupervisor
    (full ScoringService subprocesses:      (supervisor.py): probes,
     fixed effects replicated, host          heartbeat deadlines,
     store complete, device LRU hot          death → re-home →
     on OWN shards only)                     bounded restart

Failure half (the robustness core — docs/SERVING.md failure ladder):
replica death fails in-flight forwards fast (connection errors, the
``BatcherDied`` discipline one level up), the dead replica's shards
re-home to survivors within ``rehome_deadline_s`` (table swap + health
confirmation; survivors serve them from their own host stores with the
SAME scores), the supervisor restarts the replica, and its shards come
home. Every step is observable: ``ReplicaDied`` / ``ShardRehomed`` /
``ReplicaRecovered`` events, ``photon_fleet_*`` metrics, a ``degraded``
flag on ``/healthz`` while any shard is away from home, and a
fleet-level ``SLOTracker`` burning error budget on shed/unserved
requests.

Parity contract (the PR 1 discipline): every routed request's score is
bit-identical to the single-process ``ScoringService`` on the same
model — replicas RUN that service, and re-homing only changes which one
answers. ``tests/test_fleet.py`` proves it through SIGKILL chaos.
"""

from __future__ import annotations

import json
import logging
import os
import sys
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional, Sequence

from photon_ml_tpu import faults as flt
from photon_ml_tpu.serving.metrics import SLOTracker
from photon_ml_tpu.serving.publish import (CanaryRejected, ModelDelta,
                                           PublishError, read_delta)
from photon_ml_tpu.serving.router import (FleetRouter, ReplicaHTTPError,
                                          ReplicaShed, ReplicaUnavailable,
                                          ShardMap, route_key)
from photon_ml_tpu.serving.supervisor import (RETIRED, UP,
                                              ReplicaSupervisor)
from photon_ml_tpu.utils.events import (CanaryVerdict, DeltaPublished,
                                        FleetDegraded, ReplicaDied,
                                        ReplicaRecovered,
                                        RollbackExecuted, ShardRehomed,
                                        default_emitter)

logger = logging.getLogger("photon_ml_tpu.serving.fleet")


class FleetMetrics:
    """The fleet scoreboard: ``photon_fleet_*`` exposition +
    fleet-level SLO window. Thread-safe (router pool threads, the
    supervisor monitor, and HTTP handler threads all record)."""

    def __init__(self, num_replicas: int, slo_window_s: float = 60.0,
                 slo_availability: float = 0.999,
                 slo_latency_ms: Optional[float] = None):
        self._lock = threading.Lock()
        self.num_replicas = num_replicas
        self.requests_total = 0
        self.requests_by_replica = {i: 0 for i in range(num_replicas)}
        self.shed_total = 0  # fleet admission + replica-shed translations
        self.error_total = 0  # non-retryable replica HTTP errors
        self.unserved_total = 0  # retry budget exhausted (ReplicaUnavailable)
        self.forward_retries_total = 0
        self.forward_errors_total = 0
        self.hedges_total = 0
        self.hedge_wins_total = 0
        self.rehomes_total = 0
        self.rehome_seconds_last = 0.0
        self.rehome_seconds_max = 0.0
        self.rehome_deadline_misses_total = 0
        self.replica_deaths_total = 0
        self.replica_restarts_total = 0
        # Elastic control loop (serving/elastic.py).
        self.splits_total = 0
        self.migrations_total = 0
        self.scale_ups_total = 0
        self.scale_downs_total = 0
        self.brownout_sheds_total = 0
        # Continuous publication (serving/publish.py canary ladder).
        self.published_version = 0
        self.publishes_total = 0
        self.canary_rejects_total = 0
        self.publish_rollbacks_total = 0
        self.publish_swap_seconds_last = 0.0
        self.publish_swap_seconds_max = 0.0
        self.slo = SLOTracker(window_s=slo_window_s,
                              availability_objective=slo_availability,
                              latency_objective_ms=slo_latency_ms)

    # Router callbacks (FleetRouter.metrics protocol).
    def record_retry(self, n: int = 1) -> None:
        with self._lock:
            self.forward_retries_total += n

    def record_forward_error(self, n: int = 1) -> None:
        with self._lock:
            self.forward_errors_total += n

    def record_hedge(self) -> None:
        with self._lock:
            self.hedges_total += 1

    def record_hedge_win(self) -> None:
        with self._lock:
            self.hedge_wins_total += 1

    # Fleet-side records.
    def record_routed(self, replica_counts: dict[int, int]) -> None:
        with self._lock:
            for rid, n in replica_counts.items():
                self.requests_by_replica[rid] = \
                    self.requests_by_replica.get(rid, 0) + n
                self.requests_total += n

    def record_ok(self, latency_s: float, n: int = 1) -> None:
        for _ in range(n):
            self.slo.record_ok(latency_s)

    def record_shed(self, n: int = 1) -> None:
        with self._lock:
            self.shed_total += n
        self.slo.record_bad("shed", n)

    def record_error(self, n: int = 1) -> None:
        with self._lock:
            self.error_total += n
        self.slo.record_bad("error", n)

    def record_unserved(self, n: int = 1) -> None:
        with self._lock:
            self.unserved_total += n
        self.slo.record_bad("error", n)

    def record_death(self) -> None:
        with self._lock:
            self.replica_deaths_total += 1

    def record_restart(self) -> None:
        with self._lock:
            self.replica_restarts_total += 1

    def record_publish(self, version: int, swap_seconds: float) -> None:
        with self._lock:
            self.published_version = int(version)
            self.publishes_total += 1
            self.publish_swap_seconds_last = swap_seconds
            self.publish_swap_seconds_max = max(
                self.publish_swap_seconds_max, swap_seconds)

    def record_canary_reject(self) -> None:
        with self._lock:
            self.canary_rejects_total += 1

    def record_publish_rollback(self, n: int = 1) -> None:
        with self._lock:
            self.publish_rollbacks_total += n

    def record_split(self) -> None:
        with self._lock:
            self.splits_total += 1

    def record_migration(self) -> None:
        with self._lock:
            self.migrations_total += 1

    def record_scale(self, direction: str) -> None:
        with self._lock:
            if direction == "up":
                self.scale_ups_total += 1
            else:
                self.scale_downs_total += 1

    def record_brownout_shed(self, n: int = 1) -> None:
        with self._lock:
            self.brownout_sheds_total += n
            self.shed_total += n
        self.slo.record_bad("shed", n)

    def record_rehome(self, seconds: float, deadline_s: float) -> None:
        with self._lock:
            self.rehomes_total += 1
            self.rehome_seconds_last = seconds
            self.rehome_seconds_max = max(self.rehome_seconds_max,
                                          seconds)
            if seconds > deadline_s:
                self.rehome_deadline_misses_total += 1

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "requests_total": self.requests_total,
                "requests_by_replica": dict(self.requests_by_replica),
                "shed_total": self.shed_total,
                "error_total": self.error_total,
                "unserved_total": self.unserved_total,
                "forward_retries_total": self.forward_retries_total,
                "forward_errors_total": self.forward_errors_total,
                "hedges_total": self.hedges_total,
                "hedge_wins_total": self.hedge_wins_total,
                "rehomes_total": self.rehomes_total,
                "rehome_seconds_last": self.rehome_seconds_last,
                "rehome_seconds_max": self.rehome_seconds_max,
                "rehome_deadline_misses_total":
                    self.rehome_deadline_misses_total,
                "replica_deaths_total": self.replica_deaths_total,
                "replica_restarts_total": self.replica_restarts_total,
                "splits_total": self.splits_total,
                "migrations_total": self.migrations_total,
                "scale_ups_total": self.scale_ups_total,
                "scale_downs_total": self.scale_downs_total,
                "brownout_sheds_total": self.brownout_sheds_total,
                "published_version": self.published_version,
                "publishes_total": self.publishes_total,
                "canary_rejects_total": self.canary_rejects_total,
                "publish_rollbacks_total": self.publish_rollbacks_total,
                "publish_swap_seconds_last":
                    self.publish_swap_seconds_last,
                "publish_swap_seconds_max":
                    self.publish_swap_seconds_max,
            }

    def render_text(self, states: dict[int, str], degraded: bool,
                    boot_seconds: Optional[dict[int, float]] = None,
                    shard_heat: Optional[dict[int, dict]] = None,
                    map_version: Optional[int] = None,
                    hedge_after_s: Optional[float] = None,
                    num_live: Optional[int] = None,
                    ) -> str:
        """Prometheus-style ``photon_fleet_*`` lines (the metric
        catalog rows in docs/OBSERVABILITY.md)."""
        s = self.snapshot()
        lines = [
            f"photon_fleet_replicas "
            f"{num_live if num_live is not None else self.num_replicas}",
            f"photon_fleet_degraded {1 if degraded else 0}",
            f"photon_fleet_requests_total {s['requests_total']}",
            f"photon_fleet_shed_total {s['shed_total']}",
            f"photon_fleet_errors_total {s['error_total']}",
            f"photon_fleet_unserved_total {s['unserved_total']}",
            f"photon_fleet_forward_retries_total "
            f"{s['forward_retries_total']}",
            f"photon_fleet_forward_errors_total "
            f"{s['forward_errors_total']}",
            f"photon_fleet_hedges_total {s['hedges_total']}",
            f"photon_fleet_hedge_wins_total {s['hedge_wins_total']}",
            f"photon_fleet_rehomes_total {s['rehomes_total']}",
            f"photon_fleet_rehome_seconds{{window=\"last\"}} "
            f"{s['rehome_seconds_last']:.6f}",
            f"photon_fleet_rehome_seconds{{window=\"max\"}} "
            f"{s['rehome_seconds_max']:.6f}",
            f"photon_fleet_rehome_deadline_misses_total "
            f"{s['rehome_deadline_misses_total']}",
            f"photon_fleet_replica_deaths_total "
            f"{s['replica_deaths_total']}",
            f"photon_fleet_replica_restarts_total "
            f"{s['replica_restarts_total']}",
            f"photon_fleet_splits_total {s['splits_total']}",
            f"photon_fleet_migrations_total {s['migrations_total']}",
            f"photon_fleet_scale_ups_total {s['scale_ups_total']}",
            f"photon_fleet_scale_downs_total {s['scale_downs_total']}",
            f"photon_fleet_brownout_sheds_total "
            f"{s['brownout_sheds_total']}",
            f"photon_publish_model_version {s['published_version']}",
            f"photon_publish_deltas_total {s['publishes_total']}",
            f"photon_publish_canary_rejects_total "
            f"{s['canary_rejects_total']}",
            f"photon_publish_rollbacks_total "
            f"{s['publish_rollbacks_total']}",
            f"photon_publish_swap_seconds{{window=\"last\"}} "
            f"{s['publish_swap_seconds_last']:.6f}",
            f"photon_publish_swap_seconds{{window=\"max\"}} "
            f"{s['publish_swap_seconds_max']:.6f}",
        ]
        for rid in sorted(states):
            lines.append(
                f"photon_fleet_replica_up{{replica=\"{rid}\"}} "
                f"{1 if states[rid] == UP else 0}")
            lines.append(
                f"photon_fleet_requests_routed_total"
                f"{{replica=\"{rid}\"}} "
                f"{s['requests_by_replica'].get(rid, 0)}")
            if boot_seconds is not None and rid in boot_seconds:
                # spawn → first healthy probe of the LAST (re)start —
                # the fleet-side view of photon_boot_seconds.
                lines.append(
                    f"photon_fleet_replica_boot_seconds"
                    f"{{replica=\"{rid}\"}} {boot_seconds[rid]:.6f}")
        if map_version is not None:
            lines.append(f"photon_fleet_map_version {map_version}")
        if hedge_after_s is not None:
            lines.append(f"photon_fleet_hedge_after_seconds "
                         f"{hedge_after_s:.6f}")
        if shard_heat:
            for shard in sorted(shard_heat):
                lines.append(
                    f"photon_fleet_shard_heat{{shard=\"{shard}\"}} "
                    f"{shard_heat[shard]['heat']:.4f}")
        slo = self.slo.snapshot()
        lines.append(f"photon_fleet_slo_requests_in_window "
                     f"{slo['requests_in_window']}")
        lines.append(f"photon_fleet_slo_bad_in_window "
                     f"{slo['bad_in_window']}")
        lines.append(f"photon_fleet_slo_availability "
                     f"{slo['availability']:.6f}")
        lines.append(f"photon_fleet_slo_budget_burn_rate "
                     f"{slo['budget_burn_rate']:.6f}")
        for q in ("p50", "p95", "p99"):
            lines.append(f"photon_fleet_slo_latency_ms"
                         f"{{quantile=\"{q}\"}} {slo[q + '_ms']:.4f}")
        return "\n".join(lines) + "\n"


class ServingFleet:
    """N supervised scoring replicas behind one entity-affinity router.

    ``replica_args`` is the ``photon_ml_tpu.cli.serve`` argv tail every
    replica shares (model flags, batching knobs); the fleet appends the
    per-replica plumbing (``--port 0 --ready-file … --replica-id …`` and
    the fault plan, when drilling). Replicas inherit this process's
    environment, so ``JAX_PLATFORMS=cpu`` tests stay on CPU.
    """

    def __init__(
        self,
        replica_args: Sequence[str],
        num_replicas: int,
        workdir: str,
        num_shards: Optional[int] = None,
        route_re_type: Optional[str] = None,
        request_timeout_s: float = 30.0,
        retries: int = 3,
        retry_backoff_s: float = 0.1,
        hedge_after_s: Optional[float] = None,
        probe_interval_s: float = 0.25,
        probe_timeout_s: float = 1.0,
        heartbeat_deadline_s: float = 2.0,
        rehome_deadline_s: float = 5.0,
        start_timeout_s: float = 120.0,
        max_restarts: int = 3,
        backoff_reset_s: float = 60.0,
        max_inflight: Optional[int] = None,
        fault_plan_file: Optional[str] = None,
        slo_window_s: float = 60.0,
        slo_availability: float = 0.999,
        slo_latency_ms: Optional[float] = None,
        publish_dir: Optional[str] = None,
        publish_bake_s: float = 0.5,
        publish_burn_threshold: float = 1.0,
        elastic=None,
        emitter=default_emitter,
        transport=None,
        delta_base_url: Optional[str] = None,
    ):
        self.replica_args = list(replica_args)
        self.num_replicas = int(num_replicas)
        self.num_shards = int(num_shards if num_shards is not None
                              else max(8, 2 * self.num_replicas))
        self.workdir = workdir
        self.rehome_deadline_s = float(rehome_deadline_s)
        self.probe_timeout_s = float(probe_timeout_s)
        self.fault_plan_file = fault_plan_file
        self.emitter = emitter
        # Fleet admission control: beyond this many in-flight /score
        # bodies the front door sheds (the replicas' own queues are the
        # deeper backstop; this bound keeps the router pool sane).
        self.max_inflight = (int(max_inflight) if max_inflight is not None
                             else 16 * self.num_replicas)
        self._inflight = 0
        self._inflight_lock = threading.Lock()
        self.metrics = FleetMetrics(self.num_replicas,
                                    slo_window_s=slo_window_s,
                                    slo_availability=slo_availability,
                                    slo_latency_ms=slo_latency_ms)
        self.shard_map = ShardMap(self.num_shards, self.num_replicas)
        self.supervisor = ReplicaSupervisor(
            self._replica_argv, self.num_replicas, workdir,
            probe_interval_s=probe_interval_s,
            probe_timeout_s=probe_timeout_s,
            heartbeat_deadline_s=heartbeat_deadline_s,
            start_timeout_s=start_timeout_s,
            max_restarts=max_restarts,
            backoff_reset_s=backoff_reset_s,
            on_death=self._on_death,
            on_recovered=self._on_recovered,
            transport=transport)
        self.router = FleetRouter(
            self.shard_map, self.supervisor.endpoint,
            route_re_type=route_re_type,
            request_timeout_s=request_timeout_s,
            retries=retries, retry_backoff_s=retry_backoff_s,
            hedge_after_s=hedge_after_s, metrics=self.metrics,
            health_fn=self._replica_healthy)
        self._degraded = False
        self._rehoming = False
        self._closed = False
        # Elastic control loop (serving/elastic.py; docs/SERVING.md
        # "Elastic fleet"): heat model always on (cheap sliding window
        # — /metrics readers want the gauge even with the loop off),
        # the controller only when an ElasticConfig is handed in.
        from photon_ml_tpu.serving.elastic import (ElasticConfig,
                                                   ElasticController)
        from photon_ml_tpu.serving.metrics import ShardHeat

        self.elastic_config = elastic
        self.heat = ShardHeat(
            window_s=(elastic.heat_window_s
                      if isinstance(elastic, ElasticConfig)
                      else 30.0))
        self.elastic = (ElasticController(self, elastic)
                        if elastic is not None else None)
        # Brownout state: written only by the controller thread via
        # set_brownout, read by HTTP handler threads; dict swap is
        # atomic under the GIL and staleness of one tick is by design.
        self._brownout: dict[int, str] = {}
        self._elastic_ledger = None
        # Continuous publication state (serving/publish.py ladder):
        # committed deltas newest-last (restarted replicas replay them),
        # one publish at a time, and the publish ledger (lazy — the row
        # sink `photon-obs tail --publish` reads).
        self.publish_dir = publish_dir
        self.publish_bake_s = float(publish_bake_s)
        self.publish_burn_threshold = float(publish_burn_threshold)
        # Publish-over-the-wire (docs/SERVING.md "Multi-host fleet"):
        # when set, replicas are told to PULL delta artifacts from this
        # base URL (a DeltaArtifactServer over the publish dir) instead
        # of resolving a shared-filesystem path — remote replicas have
        # no such filesystem. CRC verification stays with the artifact.
        self.delta_base_url = (delta_base_url.rstrip("/")
                               if delta_base_url else None)
        self._published: list[tuple[int, str]] = []
        # Two locks, strictly ordered _ladder_lock -> _publish_lock
        # (photon-lint --locks proves the graph stays acyclic):
        # _ladder_lock serializes whole publish ladders and IS held
        # across the canary HTTP + bake sleep by design (see the
        # allow[PML019] notes in publish_delta) — only publish_delta
        # takes it, so the monitor thread never convoys on a bake.
        # _publish_lock guards the committed chain and the lazy ledger
        # handles with short holds only; the monitor thread's recovery
        # replay and /healthz readers take just this one.
        self._ladder_lock = threading.Lock()
        self._publish_lock = threading.Lock()
        self._publish_ledger = None

    # -- replica plumbing ----------------------------------------------------

    def _replica_argv(self, replica_id: int, ready_file: str) -> list[str]:
        argv = [sys.executable, "-m", "photon_ml_tpu.cli.serve",
                *self.replica_args,
                "--host", "127.0.0.1", "--port", "0",
                "--ready-file", ready_file,
                "--replica-id", str(replica_id)]
        if self.fault_plan_file:
            argv += ["--fault-plan", self.fault_plan_file]
        return argv

    # -- failure half --------------------------------------------------------

    def _on_death(self, replica_id: int) -> None:
        """Supervisor monitor-thread callback: the rehome window starts
        HERE (detection) and closes when every moved shard's new owner
        confirmed healthy."""
        t0 = time.monotonic()
        self.metrics.record_death()
        # pml: allow[PML015] single-writer publish: only the monitor thread flips these bools; /healthz readers tolerate staleness by design
        self._degraded = True
        self._rehoming = True  # pml: allow[PML015] same single-writer monitor-thread publish as above
        self.emitter.emit(ReplicaDied(replica_id=replica_id,
                                      reason="declared dead by probe"))
        try:
            moved = self.shard_map.mark_down(replica_id)
        except ReplicaUnavailable:
            logger.error("replica %d died and no survivor remains — "
                         "the fleet is down until a restart succeeds",
                         replica_id)
            self._rehoming = False  # pml: allow[PML015] single-writer monitor-thread publish; readers poll
            return
        # Confirm each new owner actually serves before declaring the
        # re-home done — a table swap to another corpse is not recovery.
        from photon_ml_tpu.serving.supervisor import _probe_healthz
        for rid in sorted(set(moved.values())):
            host, port = self.supervisor.endpoint(rid)
            try:
                _probe_healthz(f"http://{host}:{port}",
                               self.probe_timeout_s)
            except (OSError, ValueError) as e:
                logger.warning("re-home target %d not yet healthy "
                               "(%s) — the monitor will handle it", rid, e)
        seconds = time.monotonic() - t0
        self._rehoming = False  # pml: allow[PML015] single-writer monitor-thread publish; readers poll
        self.metrics.record_rehome(seconds, self.rehome_deadline_s)
        self.emitter.emit(ShardRehomed(
            replica_id=replica_id, shards=tuple(sorted(moved)),
            new_owners=tuple(moved[s] for s in sorted(moved)),
            seconds=seconds))
        level = (logger.error if seconds > self.rehome_deadline_s
                 else logger.info)
        level("re-homed %d shard(s) of dead replica %d in %.3fs "
              "(deadline %.3fs)", len(moved), replica_id, seconds,
              self.rehome_deadline_s)

    def _on_recovered(self, replica_id: int) -> None:
        back = self.shard_map.restore(replica_id)
        self.metrics.record_restart()
        self.emitter.emit(ReplicaRecovered(
            replica_id=replica_id, shards_restored=tuple(back)))
        # A restarted replica loaded the BASE model — replay the
        # committed delta chain before declaring it healthy, or it would
        # serve stale rows for every published entity.
        self._reapply_published(replica_id)
        states = self.supervisor.states()
        if all(st in (UP, RETIRED) for st in states.values()):
            self._degraded = False  # pml: allow[PML015] single-writer monitor-thread publish; healthz re-derives from supervisor states anyway
        logger.info("replica %d recovered; %d shard(s) back home; "
                    "fleet %s", replica_id, len(back),
                    "healthy" if not self._degraded else "still degraded")

    # -- elastic fleet (serving/elastic.py; docs/SERVING.md "Elastic
    #    fleet") ---------------------------------------------------------------

    def _replica_healthy(self, replica_id: int) -> bool:
        """The router's liveness oracle beyond the shard map: the
        supervisor's state machine knows a replica is down/restarting
        BEFORE the map re-homes it — hedges must not aim into that
        gap (ISSUE 15 satellite fix)."""
        try:
            return self.supervisor.replicas[replica_id].state == UP
        except IndexError:
            return False

    def set_brownout(self, hot_shards, reason: str) -> None:
        """Engage (or with an empty list, release) per-shard admission
        tightening — the first rung of the overload ladder: requests
        routed to a browned-out shard shed with a 503 NAMING the shard,
        while every other shard keeps serving; the fleet-wide
        ``max_inflight`` bound stays the second rung."""
        new = {int(s): reason for s in hot_shards}
        was = self._brownout
        # Single-writer publish: only the controller thread swaps this
        # dict; handler reads tolerate one-tick staleness by design.
        self._brownout = new
        if new and not was:
            self.emitter.emit(FleetDegraded(
                mode="brownout", hot_shards=tuple(sorted(new)),
                reason=reason))
            self._elastic_record(action="brownout",
                                 hot_shards=sorted(new), reason=reason)
            logger.warning("BROWNOUT: per-shard admission tightened "
                           "for shard(s) %s (%s)", sorted(new), reason)
        elif was and not new:
            self.emitter.emit(FleetDegraded(
                mode="recovered", hot_shards=(), reason=reason))
            self._elastic_record(action="brownout_clear",
                                 reason=reason)
            logger.info("brownout released (%s)", reason)

    def brownout_shard_of(self, request_objs: Sequence[dict]
                          ) -> Optional[tuple[int, str]]:
        """The first browned-out shard a body routes to, or None."""
        hot = self._brownout
        if not hot:
            return None
        for obj in request_objs:
            shard = self.router.shard_for(obj)
            if shard in hot:
                return shard, hot[shard]
        return None

    def add_replica(self) -> int:
        """The scale-up leg: spawn + warm a new supervised replica,
        admit it to the shard map only after it answered /healthz, and
        replay the committed delta chain so it serves the same model
        version as the rest of the fleet."""
        rid = self.supervisor.add_replica()
        admitted = self.shard_map.add_replica()
        if admitted != rid:  # pragma: no cover — ids advance together
            logger.error("replica id drift: supervisor %d vs map %d",
                         rid, admitted)
        self.num_replicas = len(self.shard_map.live())
        self._reapply_published(rid)
        return rid

    def _elastic_record(self, **fields) -> None:
        """One ``elastic`` ledger row (append-as-produced, per-row CRC
        — the obs/ledger.py discipline; ``photon-obs tail --elastic``
        renders the decision tape). Lazy like the publish ledger; rows
        land in ``<workdir>/elastic/ledger``."""
        with self._publish_lock:
            if self._elastic_ledger is None:
                from photon_ml_tpu.obs.ledger import RunLedger

                self._elastic_ledger = RunLedger.resume(
                    os.path.join(self.workdir, "elastic", "ledger"),
                    config={"kind": "elastic",
                            "num_replicas": self.num_replicas,
                            "num_shards": self.num_shards})
            self._elastic_ledger.record(
                "elastic", map_snapshot_version=self.shard_map.version,
                **fields)

    # -- continuous publication (serving/publish.py; docs/SERVING.md
    #    "Continuous publication") --------------------------------------------

    @property
    def published_version(self) -> int:
        with self._publish_lock:
            return self._published[-1][0] if self._published else 0

    def _replica_url(self, replica_id: int) -> str:
        host, port = self.supervisor.endpoint(replica_id)
        return f"http://{host}:{port}"

    def _replica_post(self, replica_id: int, path: str,
                      payload: dict, timeout_s: float = 30.0) -> dict:
        body = json.dumps(payload).encode()
        req = urllib.request.Request(
            self._replica_url(replica_id) + path, data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=timeout_s) as resp:
            return json.loads(resp.read())

    def _replica_get_json(self, replica_id: int, path: str,
                          timeout_s: float = 10.0) -> dict:
        with urllib.request.urlopen(self._replica_url(replica_id) + path,
                                    timeout=timeout_s) as resp:
            return json.loads(resp.read())

    def _publish_record(self, **fields) -> None:
        """One ``publish`` ledger row (append-as-produced, per-row CRC —
        the obs/ledger.py discipline; ``photon-obs tail --publish``
        renders these)."""
        if self.publish_dir is None:
            return
        with self._publish_lock:
            if self._publish_ledger is None:
                from photon_ml_tpu.obs.ledger import RunLedger

                self._publish_ledger = RunLedger.resume(
                    os.path.join(self.publish_dir, "ledger"),
                    config={"kind": "publish",
                            "num_replicas": self.num_replicas})
            self._publish_ledger.record("publish", **fields)

    def _kill_replica(self, replica_id: int) -> None:
        """Last rung of the rollback ladder: a replica that cannot be
        rolled back is in an UNKNOWN model state — SIGKILL it so the
        supervisor restarts it from the base model and
        ``_reapply_published`` replays only the COMMITTED chain
        (consistency restored by construction)."""
        logger.error(
            "replica %d could not roll back — killing it; the "
            "supervised restart replays the committed delta chain",
            replica_id)
        self.supervisor.kill_replica(replica_id)

    def _delta_payload(self, delta_dir: str) -> dict:
        """The ``/admin/delta`` body: a shared-filesystem path, or —
        with ``delta_base_url`` set — the URL the replica PULLS the
        artifacts from (serving/publish.fetch_delta re-verifies the
        CRC fence on its side of the wire)."""
        if self.delta_base_url is not None:
            return {"url":
                    f"{self.delta_base_url}/"
                    f"{os.path.basename(delta_dir.rstrip(os.sep))}"}
        return {"path": delta_dir}

    def _reapply_published(self, replica_id: int) -> None:
        with self._publish_lock:
            chain = list(self._published)
        if not chain:
            return
        # A replica that mmap-booted a COMPACTED generation
        # (boot/generations.py) already holds some prefix of the chain
        # folded into its tables — /healthz says how much; replaying a
        # folded delta would fail the parent check and strand the rest.
        base = 0
        try:
            base = int(self._replica_get_json(
                replica_id, "/healthz").get("model_version", 0) or 0)
        except (OSError, ValueError):
            pass  # unknown base: replay everything (the classic boot)
        for version, path in chain:
            if version <= base:
                continue
            try:
                self._replica_post(replica_id, "/admin/delta",
                                   self._delta_payload(path))
                self._publish_record(phase="reapply", version=version,
                                     replica=replica_id)
            except (OSError, ValueError) as e:
                logger.error(
                    "recovered replica %d failed to re-apply committed "
                    "delta v%d (%s: %s) — it serves STALE rows until "
                    "the next restart", replica_id, version,
                    type(e).__name__, e)
                return

    def _rollback(self, replica_ids: Sequence[int], delta: ModelDelta,
                  reason: str) -> None:
        """Back ``delta`` out of every replica that applied it. A
        replica whose rollback fails is killed (see ``_kill_replica``) —
        the ladder never leaves a replica in an unknown state."""
        rolled = []
        for rid in replica_ids:
            try:
                flt.fire(flt.sites.PUBLISH_ROLLBACK, index=rid)
                self._replica_post(rid, "/admin/rollback",
                                   {"to_version": delta.parent})
                rolled.append(rid)
            except Exception as e:
                logger.error("rollback of delta v%d on replica %d "
                             "failed (%s: %s)", delta.version, rid,
                             type(e).__name__, e)
                self._kill_replica(rid)
        self.metrics.record_publish_rollback(len(replica_ids))
        self.emitter.emit(RollbackExecuted(
            version=delta.version, reason=reason,
            replicas=tuple(rolled)))
        self._publish_record(phase="rollback", version=delta.version,
                             reason=reason, replicas=list(rolled))

    def _judge_canary(self, canary: int, delta: ModelDelta,
                      bake_s: float, burn_threshold: float,
                      probe_objs: Optional[list] = None,
                      probe_max_abs: Optional[float] = None
                      ) -> tuple[bool, str, float]:
        """The canary judge: bake, then rule on (1) probe scores —
        finite, and inside ``probe_max_abs`` when given (the quality
        delta), (2) the canary's error-budget burn and flush errors
        over the window (the SLO half). Returns (accepted, reason,
        burn_rate)."""
        before = self._replica_get_json(canary, "/slo")
        if probe_objs:
            try:
                resp = self._replica_post(
                    canary, "/score", {"requests": probe_objs})
                scores = [float(s) for s in resp.get("scores", [])]
            except (OSError, ValueError) as e:
                return False, f"canary probe failed ({e})", 0.0
            if any(s != s or s in (float("inf"), float("-inf"))
                   for s in scores):
                return False, "canary probe produced non-finite scores", \
                    0.0
            if probe_max_abs is not None and any(
                    abs(s) > probe_max_abs for s in scores):
                worst = max(abs(s) for s in scores)
                return (False,
                        f"canary probe scores out of band "
                        f"(|score| {worst:.4g} > {probe_max_abs:.4g})",
                        0.0)
        time.sleep(bake_s)
        after = self._replica_get_json(canary, "/slo")
        burn = float(after.get("budget_burn_rate", 0.0))
        flush_delta = (after["lifetime"]["flush_errors_total"]
                       - before["lifetime"]["flush_errors_total"])
        if flush_delta > 0:
            return (False, f"{flush_delta} flush error(s) on the canary "
                           f"during the bake window", burn)
        if burn > burn_threshold:
            return (False, f"canary error-budget burn {burn:.3f} over "
                           f"threshold {burn_threshold:.3f}", burn)
        return True, "ok", burn

    def publish_delta(self, delta_dir: str,
                      bake_s: Optional[float] = None,
                      burn_threshold: Optional[float] = None,
                      probe_objs: Optional[list] = None,
                      probe_max_abs: Optional[float] = None) -> dict:
        """The publication ladder: canary-apply → bake/judge → roll
        fleet-wide or auto-roll-back. Raises the defined error classes —
        ``DeltaCorrupt``/``BadDelta`` (nothing applied anywhere),
        ``CanaryRejected`` (canary rolled back, no other replica ever
        saw the delta), ``PublishError`` (a fleet-wide swap leg failed;
        every applied replica rolled back). On success the delta joins
        the committed chain restarted replicas replay."""
        bake_s = self.publish_bake_s if bake_s is None else float(bake_s)
        burn_threshold = (self.publish_burn_threshold
                          if burn_threshold is None
                          else float(burn_threshold))
        # Replicas resolve the path from THEIR cwd (the workdir) — hand
        # them an absolute one.
        delta_dir = os.path.abspath(delta_dir)
        with self._ladder_lock:
            delta = read_delta(delta_dir)  # DeltaCorrupt stops it here
            with self._publish_lock:
                current = (self._published[-1][0]
                           if self._published else 0)
            if delta.parent != current:
                raise PublishError(
                    f"delta v{delta.version} was cut against version "
                    f"{delta.parent} but the fleet serves {current} — "
                    f"publish the chain in order")
            up = self.supervisor.up_replicas()
            if not up:
                raise PublishError("no healthy replica to canary on")
            canary = up[0]
            self._publish_record(phase="canary_apply",
                                 version=delta.version, replica=canary)
            t0 = time.monotonic()
            try:
                # pml: allow[PML019] ladder lock held across fault hook + canary HTTP by design: one publish at a time IS the contract, and nothing on the request path ever takes _ladder_lock
                flt.fire(flt.sites.PUBLISH_CANARY_APPLY, index=canary)
                # pml: allow[PML019] ladder lock held across canary/fleet HTTP + bake by design; every leg carries a finite timeout and only publish_delta takes this lock
                self._replica_post(canary, "/admin/delta",
                                   self._delta_payload(delta_dir))
            except urllib.error.HTTPError as e:
                # The replica REFUSED (validation, chain break): nothing
                # applied, nothing to roll back.
                detail = e.read().decode(errors="replace")
                self.metrics.record_canary_reject()
                self.emitter.emit(CanaryVerdict(
                    version=delta.version, replica_id=canary,
                    accepted=False, reason=detail, burn_rate=0.0))
                self._publish_record(phase="canary_verdict",
                                     version=delta.version,
                                     replica=canary, accepted=False,
                                     reason=detail)
                raise CanaryRejected(delta.version,
                                     f"replica refused the delta: "
                                     f"{detail}")
            except Exception as e:
                # Ambiguous failure (timeout, injected fault): the
                # canary MAY have applied — roll it back (idempotent
                # when it had not).
                self.metrics.record_canary_reject()
                self._rollback([canary], delta,
                               f"canary apply failed: {e}")
                raise CanaryRejected(delta.version,
                                     f"canary apply failed: {e}")
            apply_s = time.monotonic() - t0
            accepted, reason, burn = self._judge_canary(
                canary, delta, bake_s, burn_threshold,
                probe_objs=probe_objs, probe_max_abs=probe_max_abs)
            self.emitter.emit(CanaryVerdict(
                version=delta.version, replica_id=canary,
                accepted=accepted, reason=reason, burn_rate=burn))
            self._publish_record(phase="canary_verdict",
                                 version=delta.version, replica=canary,
                                 accepted=accepted, reason=reason,
                                 burn_rate=burn)
            if not accepted:
                self.metrics.record_canary_reject()
                self._rollback([canary], delta, reason)
                raise CanaryRejected(delta.version, reason)
            # Verdict: roll fleet-wide. A failed leg rolls EVERYTHING
            # back (the failed replica included — its state is unknown).
            t1 = time.monotonic()
            applied = [canary]
            for rid in up[1:]:
                try:
                    flt.fire(flt.sites.PUBLISH_SWAP, index=rid)
                    self._replica_post(rid, "/admin/delta",
                                       self._delta_payload(delta_dir))
                    applied.append(rid)
                    self._publish_record(phase="swap",
                                         version=delta.version,
                                         replica=rid)
                except Exception as e:
                    reason = (f"fleet-wide swap failed on replica "
                              f"{rid}: {type(e).__name__}: {e}")
                    logger.error("%s — rolling every applied replica "
                                 "back", reason)
                    self._rollback(applied + [rid], delta, reason)
                    raise PublishError(reason)
            swap_seconds = apply_s + (time.monotonic() - t1)
            with self._publish_lock:
                self._published.append((delta.version, delta_dir))
            self.metrics.record_publish(delta.version, swap_seconds)
            self.emitter.emit(DeltaPublished(
                version=delta.version, coordinates=delta.coordinates,
                entities=delta.num_rows, canary_replica=canary,
                swap_seconds=swap_seconds))
            self._publish_record(phase="published",
                                 version=delta.version,
                                 entities=delta.num_rows,
                                 replicas=applied,
                                 swap_seconds=round(swap_seconds, 6),
                                 burn_rate=burn)
            logger.info("delta v%d live on %d replica(s) "
                        "(canary %d, swap %.3fs)", delta.version,
                        len(applied), canary, swap_seconds)
            return {"version": delta.version, "canary_replica": canary,
                    "replicas": applied, "entities": delta.num_rows,
                    "swap_seconds": swap_seconds, "burn_rate": burn}

    # -- serving -------------------------------------------------------------

    def start(self) -> None:
        os.makedirs(self.workdir, exist_ok=True)
        self.supervisor.start()
        if self.elastic is not None:
            self.elastic.start()

    def score(self, request_objs: Sequence[dict],
              want_trace: bool = False) -> dict:
        """Route one /score body through the fleet; returns the merged
        response payload. Raises the router's defined errors — the HTTP
        front end maps them to status codes; programmatic callers get
        the same exception classes."""
        counts: dict[int, int] = {}
        shards: list[Optional[int]] = []
        for obj in request_objs:
            shard = self.router.shard_for(obj)
            shards.append(shard)
            if shard is not None:
                # Heat model feed: the request count + distinct-entity
                # cardinality half of the shard's window.
                ents = obj.get("entity_ids") or {}
                key = ents[min(ents)] if ents else None
                self.heat.record(shard, entity=key)
                rid = self.shard_map.owner(shard)
            else:
                rid = self.router.replica_for(obj)
            counts[rid] = counts.get(rid, 0) + 1
        self.metrics.record_routed(counts)
        t0 = time.monotonic()
        out = self.router.score(request_objs, want_trace=want_trace)
        dt = time.monotonic() - t0
        self.metrics.record_ok(dt, n=len(request_objs))
        # The service-seconds half: a shard whose requests run long is
        # hotter at equal QPS (queue contribution, approximated by the
        # body wall split evenly over its requests).
        per = dt / max(len(request_objs), 1)
        for shard in shards:
            if shard is not None:
                self.heat.record_seconds(shard, per)
        return out

    def admission_acquire(self) -> bool:
        with self._inflight_lock:
            if self._inflight >= self.max_inflight:
                return False
            self._inflight += 1
            return True

    def admission_release(self) -> None:
        with self._inflight_lock:
            self._inflight = max(0, self._inflight - 1)

    @property
    def inflight(self) -> int:
        with self._inflight_lock:
            return self._inflight

    def healthz(self) -> dict:
        states = self.supervisor.states()
        # RETIRED is a deliberate scale-down outcome, not degradation.
        degraded = self._degraded or any(st not in (UP, RETIRED)
                                         for st in states.values())
        leaves = self.shard_map.shards()
        return {
            "status": "degraded" if degraded else "ok",
            "degraded": degraded,
            "rehoming": self._rehoming,
            "fleet_depth": len(self.shard_map.live()),
            "replicas": {str(k): v for k, v in states.items()},
            "num_shards": len(leaves),
            "map_version": self.shard_map.version,
            "hot_shards": sorted(self._brownout),
            "shards_away_from_home": sum(
                1 for s in leaves
                if self.shard_map.owner(s) != self.shard_map.home(s)),
            "published_version": self.published_version,
        }

    def metrics_text(self) -> str:
        return self.metrics.render_text(
            self.supervisor.states(), self.healthz()["degraded"],
            boot_seconds={h.replica_id: h.boot_seconds
                          for h in self.supervisor.replicas
                          if h.boot_seconds > 0.0},
            shard_heat=self.heat.snapshot(
                resolver=lambda key: self.shard_map.shard_of_key(
                    route_key(key))),
            map_version=self.shard_map.version,
            hedge_after_s=self.router.hedge_after_s or 0.0,
            num_live=len(self.shard_map.live()))

    def slo_snapshot(self) -> dict:
        out = self.metrics.slo.snapshot()
        out["lifetime"] = self.metrics.snapshot()
        return out

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        if self.elastic is not None:
            self.elastic.stop()
        self.router.close()
        self.supervisor.stop()
        if self._publish_ledger is not None:
            self._publish_ledger.close()
        if self._elastic_ledger is not None:
            self._elastic_ledger.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


# -- fleet HTTP front door ---------------------------------------------------

class _FleetHandler(BaseHTTPRequestHandler):
    """POST /score, GET /metrics, GET /slo, GET /healthz — the same
    surface as one replica, so clients cannot tell the fleet from a
    single ``photon-game-serve`` (except via the richer /healthz)."""

    fleet: ServingFleet = None  # bound by make_fleet_http_server

    def _json(self, code: int, payload: dict) -> None:
        body = json.dumps(payload).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self):
        if self.path == "/metrics":
            body = self.fleet.metrics_text().encode()
            self.send_response(200)
            self.send_header("Content-Type",
                             "text/plain; version=0.0.4")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
        elif self.path == "/slo":
            self._json(200, self.fleet.slo_snapshot())
        elif self.path == "/healthz":
            hz = self.fleet.healthz()
            # Degraded is still SERVING (shards re-homed) — 200 with the
            # flag, not a 5xx that would page as an outage.
            self._json(200, hz)
        else:
            self._json(404, {"error": f"unknown path {self.path}"})

    def _do_publish(self) -> None:
        """``POST /publish``: drive the canary ladder from the front
        door (``photon-game-publish --fleet-url`` lands here). The
        response carries the verdict; rejections are DEFINED statuses —
        409 canary-rejected (rolled back), 422 untrustworthy/unservable
        delta (never applied), 503 swap failure (rolled back)."""
        fleet = self.fleet
        try:
            length = int(self.headers.get("Content-Length", 0))
            payload = json.loads(self.rfile.read(length) or b"{}")
            delta_dir = str(payload["path"])
            probe = payload.get("probe") or {}
        except (ValueError, TypeError, KeyError) as exc:
            self._json(400, {"error": f"malformed publish request: "
                                      f"{exc}"})
            return
        from photon_ml_tpu.serving.publish import (BadDelta,
                                                   DeltaCorrupt)

        try:
            out = fleet.publish_delta(
                delta_dir,
                bake_s=payload.get("bake_s"),
                burn_threshold=payload.get("burn_threshold"),
                probe_objs=probe.get("requests"),
                probe_max_abs=probe.get("max_abs_score"))
        except CanaryRejected as exc:
            self._json(409, {"error": str(exc), "version": exc.version,
                             "reason": exc.reason, "rolled_back": True})
            return
        except (DeltaCorrupt, BadDelta) as exc:
            self._json(422, {"error": str(exc), "applied": False})
            return
        except PublishError as exc:
            self._json(503, {"error": str(exc), "rolled_back": True})
            return
        self._json(200, out)

    def do_POST(self):
        fleet = self.fleet
        if self.path == "/publish":
            self._do_publish()
            return
        if self.path != "/score":
            self._json(404, {"error": f"unknown path {self.path}"})
            return
        try:
            length = int(self.headers.get("Content-Length", 0))
            payload = json.loads(self.rfile.read(length) or b"{}")
            if not isinstance(payload, dict):
                raise ValueError("request body must be a JSON object")
            reqs = payload.get("requests", [])
            if not isinstance(reqs, list) or not reqs:
                raise ValueError("no requests")
            want_trace = bool(payload.get("trace", False))
        except (ValueError, TypeError, AttributeError, KeyError) as exc:
            self._json(400, {"error": f"malformed request: {exc}"})
            return
        # Overload ladder rung 1 — per-shard brownout: admission
        # tightens for the HOT shard before anything fleet-wide, and
        # the 503 NAMES it (docs/SERVING.md "Elastic fleet").
        hot = fleet.brownout_shard_of(reqs)
        if hot is not None:
            shard, reason = hot
            fleet.metrics.record_brownout_shed(len(reqs))
            self._json(503, {
                "error": f"brownout: shard {shard} is overloaded "
                         f"({reason})",
                "hot_shard": shard,
                "replica_id": None,
                "fleet_depth": fleet.num_replicas,
                "degraded": True,
            })
            return
        if not fleet.admission_acquire():
            # Fleet-level admission: the 503 names the FLEET (no single
            # replica shed) and carries the depth context the ISSUE's
            # degradation contract requires.
            fleet.metrics.record_shed(len(reqs))
            self._json(503, {
                "error": "fleet admission control: too many in-flight "
                         "score bodies",
                "replica_id": None,
                "fleet_depth": fleet.num_replicas,
                "inflight": fleet.inflight,
                "max_inflight": fleet.max_inflight,
            })
            return
        try:
            out = fleet.score(reqs, want_trace=want_trace)
        except ReplicaShed as exc:
            fleet.metrics.record_shed(len(reqs))
            self._json(503, {
                "error": str(exc),
                "replica_id": exc.replica_id,
                "fleet_depth": fleet.num_replicas,
                "queue_depth": exc.queue_depth,
                "degraded": fleet.healthz()["degraded"],
            })
            return
        except ReplicaUnavailable as exc:
            fleet.metrics.record_unserved(len(reqs))
            self._json(503, {
                "error": str(exc),
                "replica_id": exc.replica_id,
                "fleet_depth": fleet.num_replicas,
                "degraded": True,
            })
            return
        except ReplicaHTTPError as exc:
            fleet.metrics.record_error(len(reqs))
            self._json(exc.status if exc.status >= 400 else 500, {
                "error": str(exc),
                "replica_id": exc.replica_id,
                "fleet_depth": fleet.num_replicas,
            })
            return
        finally:
            fleet.admission_release()
        body = {"scores": out["scores"],
                "uids": [r.get("uid") for r in reqs]}
        if want_trace and out.get("attribution") is not None:
            body["attribution"] = out["attribution"]
        self._json(200, body)

    def log_message(self, fmt, *args):  # access logs off stderr
        logger.debug("fleet http: " + fmt, *args)


def make_fleet_http_server(fleet: ServingFleet, host: str = "127.0.0.1",
                           port: int = 8080) -> ThreadingHTTPServer:
    """Bind the fleet front door (call ``serve_forever`` to serve);
    ``port=0`` picks a free port — it is ``server.server_address[1]``."""
    handler = type("BoundFleetHandler", (_FleetHandler,),
                   {"fleet": fleet})
    return ThreadingHTTPServer((host, port), handler)
