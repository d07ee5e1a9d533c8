"""ScoringService: the online-inference front door.

Reference parity: none — this is the layer the reference never had (its
GameScoringDriver is a batch job). One service owns the whole serving
pipeline:

    requests → micro-batcher → shape-bucketed padded batch
             → RE cache resolve (host store → LRU device cache)
             → ONE jitted scoring program → scores

The jitted program is a function of (feature matrices, offsets, cache
slots, cache tables) with fixed-effect coefficients closed over as
device-resident constants. Batch sizes are padded to power-of-two buckets
(``batcher.bucket_batch``), so the program compiles once per bucket —
O(log max_batch) programs, persisted across processes by
utils/compile_cache — and steady state NEVER recompiles (asserted by
tests and reported by dev-scripts/bench_serving.py).

Scoring semantics match offline ``cli/game_score.py`` exactly: scores are
offsets + Σ coordinate contributions, unseen entities contribute zero
(fixed-effect-only fallback), ``as_mean`` applies the task's inverse link.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import threading
import time
from concurrent.futures import TimeoutError as _FutureTimeout
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu import faults as flt
from photon_ml_tpu import obs
from photon_ml_tpu.data.game_data import GameDataset, SparseShard
from photon_ml_tpu.game.models import GameModel
from photon_ml_tpu.ops import losses as losses_mod
from photon_ml_tpu.serving.batcher import (BatcherQueueFull,
                                           DeadlineExceeded, MicroBatcher,
                                           bucket_batch)
from photon_ml_tpu.serving.metrics import ServingMetrics
from photon_ml_tpu.serving.model_store import ResidentModelStore
from photon_ml_tpu.utils.events import (ScoringBatch, ScoringFinish,
                                        ScoringStart, default_emitter)

logger = logging.getLogger("photon_ml_tpu.serving")


@dataclasses.dataclass
class ScoringRequest:
    """One example to score.

    ``features``: shard id → dense (d,) vector, or a sparse mapping
    ``{"indices": ..., "values": ...}`` (ELL row contract: out-of-range
    indices are padding and are dropped). Shards the model never reads may
    be omitted; omitted shards contribute zero.
    ``entity_ids``: RE type → entity id — an int vocabulary row, or a raw
    key resolved through the serving vocabularies. Unknown/missing ids
    fall back to fixed-effect-only scoring.
    """

    features: dict[str, object]
    entity_ids: dict[str, object] = dataclasses.field(default_factory=dict)
    offset: float = 0.0
    uid: object = None


def requests_from_dataset(data: GameDataset) -> list[ScoringRequest]:
    """A GameDataset's rows as ScoringRequests (tests, benches, replays)."""
    out = []
    for i in range(data.num_rows):
        feats: dict[str, object] = {}
        for sid, shard in data.feature_shards.items():
            if isinstance(shard, SparseShard):
                feats[sid] = {"indices": shard.indices[i],
                              "values": shard.values[i]}
            else:
                feats[sid] = np.asarray(shard[i])
        out.append(ScoringRequest(
            features=feats,
            entity_ids={rt: int(ids[i])
                        for rt, ids in data.entity_ids.items()},
            offset=float(data.offsets[i]),
            uid=i,
        ))
    return out


class ScoringService:
    """Low-latency scoring over a resident GameModel."""

    def __init__(
        self,
        model: GameModel,
        as_mean: bool = False,
        max_batch: int = 64,
        max_wait_ms: float = 2.0,
        cache_entities: int = 4096,
        store_shards: int = 8,
        entity_vocabs: Optional[dict[str, dict]] = None,
        cache_dtype: str = "float32",
        max_queue: Optional[int] = None,
        request_deadline_s: Optional[float] = 30.0,
        slo_window_s: float = 60.0,
        slo_availability: float = 0.999,
        slo_latency_ms: Optional[float] = None,
        replica_id: Optional[int] = None,
        initial_version: int = 0,
        boot_generation: Optional[int] = None,
        emitter=default_emitter,
    ):
        # Fleet membership (serving/fleet.py): the id is this replica's
        # stable index for fault addressing (`fleet.replica_flush`
        # fires with it) and for log/error attribution.
        self.replica_id = replica_id
        # Boot provenance (boot/generations.py): which generation this
        # service mapped (None = a classic npz boot); surfaced on
        # /healthz + photon_model_generation so the fleet and dashboards
        # can tell a stale replica from a current one.
        self.boot_generation = boot_generation
        # A flush's unique entities must fit the cache simultaneously
        # (model_store pins them during resolve), so the effective budget
        # is at least max_batch.
        self.store = ResidentModelStore(
            model, cache_entities=max(int(cache_entities), int(max_batch)),
            store_shards=store_shards, entity_vocabs=entity_vocabs,
            metrics_retry=self._record_store_retry,
            cache_dtype=cache_dtype, initial_version=initial_version)
        self.as_mean = bool(as_mean)
        self.max_batch = int(max_batch)
        self.metrics = ServingMetrics(slo_window_s=slo_window_s,
                                      slo_availability=slo_availability,
                                      slo_latency_ms=slo_latency_ms)
        self.emitter = emitter
        self._lock = threading.Lock()  # serializes resolve+score per flush
        self._compile_keys: set[int] = set()
        self._score_fn = self._build_score_fn()
        # Admission control default: a queue much deeper than 16 full
        # batches only buys latency nobody asked for — shed instead
        # (docs/ROBUSTNESS.md degradation ladder).
        self.max_queue = (16 * self.max_batch if max_queue is None
                          else int(max_queue))
        self.request_deadline_s = request_deadline_s
        self.batcher = MicroBatcher(
            self._flush, max_batch=max_batch, max_wait_ms=max_wait_ms,
            max_queue=self.max_queue,
            default_deadline_s=request_deadline_s,
            on_worker_death=self._on_worker_death,
            on_deadline=self.metrics.record_deadline_exceeded,
            depth_gauge=self.metrics.queue_depth)
        self._closed = False
        emitter.emit(ScoringStart(source="serving", num_rows=None))

    def _on_worker_death(self, exc: BaseException) -> None:
        self.metrics.record_recovery()
        logger.error("scoring worker died (%s: %s) — pending requests "
                     "failed fast, worker restarted", type(exc).__name__,
                     exc)

    def _record_store_retry(self, n: int = 1) -> None:
        self.metrics.record_retry(n)

    # -- jitted scorer -----------------------------------------------------

    def _build_score_fn(self):
        fixed = tuple(self.store.fixed)
        random = tuple((st.cid, st.shard_id, st.cache_scale is not None)
                       for st in self.store.random)
        mean_fn = (losses_mod.loss_for_task(self.store.task).mean
                   if self.as_mean else None)
        # Kernel-registry resolution happens HERE, at program-build time
        # (docs/KERNELS.md): the backend choice is baked into the jitted
        # program, so steady state never re-decides — a flag flip needs
        # a service rebuild, same contract as every other config knob.
        # Flag off = no registry traffic at all; flag on under an
        # injected kernel.launch fault already emitted its loud
        # KernelFallback inside resolve, and the inline XLA chain below
        # runs exactly as before (flag on with no backend raises).
        from photon_ml_tpu.ops import kernels
        reg = kernels.registry()
        fused = None
        self._kernel_backend = "xla"
        if random and reg.enabled("serving_score"):
            resolved = reg.resolve("serving_score",
                                   dtype=self.store.cache_dtype)
            self._kernel_backend = resolved.backend
            if resolved.backend == "pallas":
                fused = resolved

        def score(mats, offsets, slots, caches, scales):
            total = jnp.asarray(offsets)
            for _cid, sid, w in fixed:
                total = total + mats[sid] @ w
            for cid, sid, quantized in random:
                if fused is not None:
                    # One program per coordinate: gather + int8 dequant
                    # + row-dot + per-row scale, codes upcast in
                    # registers (f32 rows never hit HBM).
                    total = total + fused(
                        mats[sid], slots[cid], caches[cid],
                        scales[cid] if quantized else None)
                    continue
                rows = caches[cid][slots[cid]]
                if quantized:
                    # int8 device cache: gather the codes, accumulate
                    # the einsum in f32, dequantize with ONE per-row
                    # scale multiply (x·(s·q) = s·(x·q) — exact).
                    total = total + jnp.einsum(
                        "nd,nd->n", mats[sid],
                        rows.astype(jnp.float32)) * \
                        scales[cid][slots[cid]]
                else:
                    total = total + jnp.einsum("nd,nd->n", mats[sid],
                                               rows)
            return mean_fn(total) if mean_fn is not None else total

        return jax.jit(score)

    # -- batch assembly ----------------------------------------------------

    def _assemble(self, requests: Sequence[ScoringRequest], padded: int):
        store = self.store
        mats = {sid: np.zeros((padded, dim), np.float32)
                for sid, dim in store.shard_dims.items()}
        offsets = np.zeros(padded, np.float32)
        ids = {st.cid: np.full(len(requests), -1, np.int64)
               for st in store.random}
        for i, req in enumerate(requests):
            offsets[i] = req.offset
            for sid, feats in (req.features or {}).items():
                mat = mats.get(sid)
                if mat is None:
                    raise ValueError(
                        f"request {req.uid!r} carries unknown feature "
                        f"shard {sid!r} (model reads "
                        f"{sorted(store.shard_dims)})")
                d = mat.shape[1]
                if isinstance(feats, dict):
                    fi = np.asarray(feats["indices"], np.int64).reshape(-1)
                    fv = np.asarray(feats["values"], np.float32).reshape(-1)
                elif isinstance(feats, tuple):
                    fi = np.asarray(feats[0], np.int64).reshape(-1)
                    fv = np.asarray(feats[1], np.float32).reshape(-1)
                else:
                    v = np.asarray(feats, np.float32).reshape(-1)
                    if v.shape[0] != d:
                        raise ValueError(
                            f"request {req.uid!r} shard {sid!r}: expected "
                            f"{d} features, got {v.shape[0]}")
                    mat[i] = v
                    continue
                valid = (fi >= 0) & (fi < d)
                np.add.at(mat[i], fi[valid], fv[valid])
            ent = req.entity_ids or {}
            for st in store.random:
                ids[st.cid][i] = store.entity_row_id(
                    st.re_type, ent.get(st.re_type))
        return mats, offsets, ids

    # -- scoring paths -----------------------------------------------------

    def _score_chunk(self, requests: Sequence[ScoringRequest]):
        """Score one ≤max_batch chunk; returns ``(scores, stage_marks)``
        where the marks are the monotonic stage boundaries
        ``(assemble_start, device_start, device_end)`` — the raw material
        of per-request latency attribution (docs/SERVING.md lifecycle).
        All boundaries share ``_Entry.enqueued_at``'s clock so stage
        durations and queue waits subtract cleanly."""
        n = len(requests)
        with self._lock:
            t_a0 = time.monotonic()  # assemble: batch build + RE resolve
            padded = bucket_batch(n, self.max_batch)
            mats, offsets, ids = self._assemble(requests, padded)
            slots = self.store.resolve_slots(ids, metrics=self.metrics)
            slots_full = {
                st.cid: np.concatenate([
                    slots[st.cid],
                    np.full(padded - n, st.fallback_slot, np.int32)])
                for st in self.store.random}
            mx = obs.metrics()
            if padded not in self._compile_keys:
                self._compile_keys.add(padded)
                self.metrics.record_compile()
                if mx is not None:
                    # backend= records which kernel the program scores
                    # through (docs/KERNELS.md) — "xla" both when the
                    # flag is off and when a resolve degraded loudly.
                    mx.counter("photon_compile_cache_misses_total",
                               cache="serving_score",
                               dtype=self.store.cache_dtype,
                               backend=self._kernel_backend).inc()
            elif mx is not None:
                # The hit side of the program-cache ledger: a warm boot
                # whose warmup re-runs already-owned bucket shapes shows
                # HITS here, not silence (docs/SERVING.md "Sub-second
                # restart").
                mx.counter("photon_compile_cache_hits_total",
                           cache="serving_score",
                           dtype=self.store.cache_dtype,
                           backend=self._kernel_backend).inc()
            t_d0 = time.monotonic()  # device: dispatch + block on result
            out = self._score_fn(mats, offsets, slots_full,
                                 self.store.caches(),
                                 self.store.cache_scales())
            # pml: allow[PML019] flush-lock device sync IS the flush: one in-flight batch per device by design (docs/SERVING.md), and waiters queue in the batcher, not on this lock
            out = np.asarray(jax.block_until_ready(out))
            t_d1 = time.monotonic()
        dt = t_d1 - t_d0
        self.metrics.record_batch(n, padded, dt)
        self.emitter.emit(ScoringBatch(source="serving", rows=n,
                                       padded_rows=padded, seconds=dt))
        return out[:n], (t_a0, t_d0, t_d1)

    def warmup(self) -> int:
        """Touch every power-of-two bucket shape once so steady state
        (and the first real request) owns its compiled programs — the
        ``boot.warmup`` phase of a replica restart. Warmup rows carry no
        features and no entity ids (fallback slot only), so caches and
        scores are untouched; with the persistent compilation cache
        warm, every build here is a disk hit, not a compile. Returns the
        number of bucket shapes touched."""
        shapes = 0
        n = 1
        while n <= self.max_batch:
            self._score_chunk([ScoringRequest(features={})
                               for _ in range(n)])
            shapes += 1
            n *= 2
        # One re-run of the smallest bucket verifies the programs now
        # dispatch WARM — and moves the hit counter at boot, so a
        # restart whose cache key rotated (every shape recompiling)
        # is visible as hits staying at zero.
        self._score_chunk([ScoringRequest(features={})])
        return shapes

    def score(self, requests: Sequence[ScoringRequest]) -> np.ndarray:
        """Programmatic batch API: score now, bypassing the queue (the
        device path — bucketing, cache, metrics — is identical)."""
        scores = np.empty(len(requests), np.float32)
        for lo in range(0, len(requests), self.max_batch):
            chunk = requests[lo: lo + self.max_batch]
            scores[lo: lo + len(chunk)] = self._score_chunk(chunk)[0]
        return scores

    def submit(self, request: ScoringRequest,
               deadline_s: Optional[float] = None):
        """Queue one request through the micro-batcher; returns a Future
        resolving to its score (cross-caller batching happens here).
        Raises ``BatcherQueueFull`` when admission control sheds the
        request (counted in ``shed_total``); the returned future always
        resolves — score, error, or ``DeadlineExceeded``."""
        try:
            return self.batcher.submit(request, deadline_s=deadline_s)
        except BatcherQueueFull:
            self.metrics.record_shed()
            raise

    def _flush(self, entries):
        t_flush0 = time.monotonic()  # same clock as _Entry.enqueued_at
        try:
            # Injection sites first: a fault here is indistinguishable
            # from the scorer failing (InjectedThreadDeath, being a
            # BaseException, still sails through to the supervisor).
            # The fleet site carries the replica id as its index, so a
            # `replica_kill` spec can SIGKILL exactly one replica of a
            # fleet mid-flush (indices=[id], occurrences=[k]).
            if self.replica_id is not None:
                flt.fire(flt.sites.FLEET_REPLICA_FLUSH, index=self.replica_id)
            flt.fire(flt.sites.SERVING_FLUSH)
            scores, marks = self._score_chunk(
                [e.request for e in entries])
        except Exception:
            self.metrics.record_flush_error()
            raise
        self._attribute(entries, t_flush0, marks)
        return scores

    def _attribute(self, entries, t_flush0: float, marks) -> None:
        """Per-request latency attribution for one flush (runs on the
        batcher worker, inside the ``serving.flush`` span, BEFORE the
        futures resolve).

        Every request in the flush experienced the flush's whole
        assemble/device/respond walls plus its own queue wait, so those
        are its stages verbatim: stages sum to the request total (the
        10%-agreement contract tests and bench cross-checks rely on).
        With tracing on, each request also becomes a ``serving.request``
        span parented into this flush's span — the queue-crossing edge —
        with one child span per stage.
        """
        t_a0, t_d0, t_d1 = marks
        t_done = time.monotonic()
        assemble_s = t_d0 - t_a0
        device_s = t_d1 - t_d0
        respond_s = t_done - t_d1
        tr = obs.tracer()
        parent = tr.current() if tr is not None else None
        for e in entries:
            queue_wait_s = max(t_flush0 - e.enqueued_at, 0.0)
            total_s = t_done - e.enqueued_at
            attr = {
                "request_id": e.request_id,
                "queue_wait_ms": round(queue_wait_s * 1e3, 4),
                "assemble_ms": round(assemble_s * 1e3, 4),
                "device_score_ms": round(device_s * 1e3, 4),
                "respond_ms": round(respond_s * 1e3, 4),
                "total_ms": round(total_s * 1e3, 4),
            }
            e.attribution = attr
            # Visible to whoever holds the future, race-free: set_result
            # happens after _flush returns (the happens-before edge).
            e.future.attribution = attr
            self.metrics.record_request_latency(total_s)
            self.metrics.record_stages(queue_wait_s, assemble_s,
                                       device_s, respond_s)
            if tr is None or e.t0_epoch_ns is None:
                continue

            def _at(mono: float) -> int:
                # The entry's own (epoch, monotonic) pair anchors its
                # stage boundaries on the cross-thread trace axis.
                return e.t0_epoch_ns + int((mono - e.enqueued_at) * 1e9)

            sid = tr.record_complete(
                "serving.request", cat="serving",
                t0_epoch_ns=e.t0_epoch_ns, dur_s=total_s, parent=parent,
                crosses_queue=True, request_id=e.request_id)
            for name, mono, dur in (
                    ("serving.queue_wait", e.enqueued_at, queue_wait_s),
                    ("serving.assemble", t_a0, assemble_s),
                    ("serving.device_score", t_d0, device_s),
                    ("serving.respond", t_d1, respond_s)):
                tr.record_complete(name, cat="serving",
                                   t0_epoch_ns=_at(mono), dur_s=dur,
                                   parent=sid)

    # -- continuous publication (serving/publish.py) -----------------------

    @property
    def model_version(self) -> int:
        return self.store.version

    def apply_delta(self, delta) -> dict:
        """Zero-drop hot-swap: install one committed delta while traffic
        flows. The service lock serializes against ``_score_chunk``, so
        the in-flight flush finishes against the OLD version, the swap
        lands, and every later flush sees the NEW one — queued requests
        are never dropped and no batch mixes versions. Post-swap scores
        are bit-identical to a cold restart on the new model (the store
        re-fills invalidated cache slots from the swapped host rows
        through the unchanged resolve path)."""
        with self._lock:
            out = self.store.apply_delta(delta)
        self.metrics.record_publish_applied(out["version"])
        return out

    def apply_delta_dir(self, path: str) -> dict:
        """Load + validate + apply a committed delta directory (the
        ``POST /admin/delta`` body). Defined errors only: DeltaCorrupt
        for untrustworthy bytes, BadDelta for unservable content — the
        store never mutates on either."""
        from photon_ml_tpu.serving.publish import read_delta

        return self.apply_delta(read_delta(path))

    def apply_delta_url(self, url: str) -> dict:
        """Fetch a delta's artifacts over HTTP into a local spool, then
        apply — the remote-replica leg of ``POST /admin/delta``
        (``{"url": ...}`` body; docs/SERVING.md "Multi-host fleet").
        ``fetch_delta`` keeps the marker-last commit discipline across
        the wire and ``read_delta`` re-verifies the CRC fence on OUR
        bytes, so a torn or bit-flipped transfer raises DeltaCorrupt
        and the previously applied version stays servable."""
        from photon_ml_tpu.serving.publish import fetch_delta, read_delta

        spool = os.path.join(os.getcwd(),
                             f"delta-spool-{os.getpid()}")
        local = fetch_delta(url, spool)
        return self.apply_delta(read_delta(local))

    def rollback_to(self, version: int) -> dict:
        """Back out deltas newer than ``version`` (the canary ladder's
        auto-rollback leg), under the same flush-serialized lock as
        ``apply_delta``."""
        with self._lock:
            out = self.store.rollback_to(version)
        self.metrics.record_publish_rollback(out["version"])
        return out

    # -- lifecycle ---------------------------------------------------------

    def metrics_text(self) -> str:
        """The ``/metrics`` body: serving's own scoreboard plus — when
        process-wide observability is on — the cross-stack registry
        (transfer accounting, checkpoint/retry counters), so ONE endpoint
        exposes the whole process (docs/OBSERVABILITY.md)."""
        text = self.metrics.render_text()
        registry = obs.metrics()
        if registry is not None:
            text += registry.render_text()
        return text

    def slo_snapshot(self) -> dict:
        """The ``/slo`` body: sliding-window percentiles + error-budget
        burn, with the lifetime shed/deadline/error totals alongside so
        one payload answers both "how is the window" and "how has the
        lifetime been" (docs/SERVING.md)."""
        out = self.metrics.slo.snapshot()
        out["lifetime"] = {
            "rows_total": self.metrics.rows_total,
            "shed_total": self.metrics.shed_total,
            "deadline_exceeded_total":
                self.metrics.deadline_exceeded_total,
            "flush_errors_total": self.metrics.flush_errors_total,
            "queue_depth_peak": self.metrics.queue_depth.peak,
        }
        return out

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self.batcher.close()
        self.emitter.emit(ScoringFinish(
            source="serving", num_rows=self.metrics.rows_total,
            wall_seconds=self.metrics.uptime_seconds()))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


# -- JSON-over-HTTP front end ----------------------------------------------

def _parse_request(obj: dict) -> ScoringRequest:
    return ScoringRequest(
        features=obj.get("features") or {},
        entity_ids=obj.get("entity_ids") or {},
        offset=float(obj.get("offset", 0.0)),
        uid=obj.get("uid"),
    )


class _ServingHandler(BaseHTTPRequestHandler):
    """Minimal stdlib handler: POST /score, GET /metrics, GET /slo,
    GET /healthz.

    Each POSTed request is submitted through the micro-batcher, so
    concurrent HTTP callers coalesce into shared device batches — the
    ThreadingHTTPServer thread-per-connection model is exactly what makes
    the batcher useful here. A ``"trace": true`` key in the /score body
    opts that call into per-request latency attribution
    (queue wait / assemble / device score / respond) in the response.
    """

    service: ScoringService = None  # set by make_http_server
    result_timeout = 60.0

    def _respond(self, code: int, body: bytes, ctype: str) -> None:
        self.send_response(code)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _json(self, code: int, payload: dict) -> None:
        self._respond(code, json.dumps(payload).encode(),
                      "application/json")

    def do_GET(self):
        if self.path == "/metrics":
            self._respond(200, self.service.metrics_text().encode(),
                          "text/plain; version=0.0.4")
        elif self.path == "/slo":
            self._json(200, self.service.slo_snapshot())
        elif self.path == "/healthz":
            self._json(200, {"status": "ok",
                             "model_version":
                                 self.service.model_version,
                             "generation":
                                 self.service.boot_generation})
        else:
            self._json(404, {"error": f"unknown path {self.path}"})

    def _error(self, code: int, message: str, **extra) -> None:
        """One JSON error body + one metrics increment — every failure
        leaves through here, never as an unhandled exception on the
        handler thread (which would reset the connection with no body
        and no count). ``extra`` keys (non-None) ride along in the body
        (the 503 shed body carries the observed queue depth)."""
        self.service.metrics.record_http_error(code)
        body = {"error": message}
        body.update({k: v for k, v in extra.items() if v is not None})
        self._json(code, body)

    def _admin(self, payload: dict) -> None:
        """Publication control plane (``/admin/delta``, ``/admin/
        rollback``): the fleet's canary ladder drives a replica through
        these. Errors are DEFINED and counted: 400 for a delta the
        replica refuses (corrupt bytes, unservable content, chain
        break), never a silent wrong swap."""
        from photon_ml_tpu.serving.publish import PublishError

        try:
            if self.path == "/admin/delta":
                if "url" in payload:
                    out = self.service.apply_delta_url(
                        str(payload["url"]))
                else:
                    out = self.service.apply_delta_dir(
                        str(payload["path"]))
            else:
                out = self.service.rollback_to(
                    int(payload["to_version"]))
        except PublishError as exc:
            self._error(400, str(exc),
                        model_version=self.service.model_version)
            return
        except (KeyError, TypeError, ValueError) as exc:
            self._error(400, f"malformed admin request: {exc}")
            return
        self._json(200, out)

    def do_POST(self):
        if self.path in ("/admin/delta", "/admin/rollback"):
            try:
                length = int(self.headers.get("Content-Length", 0))
                payload = json.loads(self.rfile.read(length) or b"{}")
                if not isinstance(payload, dict):
                    raise ValueError("admin body must be a JSON object")
            except (ValueError, TypeError) as exc:
                self._error(400, f"malformed admin request: {exc}")
                return
            self._admin(payload)
            return
        if self.path != "/score":
            self._error(404, f"unknown path {self.path}")
            return
        try:
            length = int(self.headers.get("Content-Length", 0))
            payload = json.loads(self.rfile.read(length) or b"{}")
            if not isinstance(payload, dict):
                raise ValueError("request body must be a JSON object")
            reqs = [_parse_request(o) for o in payload.get("requests", [])]
            want_trace = bool(payload.get("trace", False))
        except (ValueError, TypeError, AttributeError, KeyError) as exc:
            # Malformed JSON / wrong shapes: the CALLER's fault — 400.
            logger.warning("malformed scoring request: %s", exc)
            self._error(400, f"malformed request: {exc}")
            return
        if not reqs:
            self._error(400, "no requests")
            return
        try:
            futures = [self.service.submit(r) for r in reqs]
        except BatcherQueueFull as exc:
            # Admission control: shed with a Retry-After signal instead
            # of buffering unboundedly (shed_total counts it); the body
            # reports the observed depth so callers and dashboards see
            # HOW saturated, not just that it was.
            self._error(503, str(exc), queue_depth=exc.depth,
                        max_queue=exc.max_queue)
            return
        try:
            scores = [float(f.result(timeout=self.result_timeout))
                      for f in futures]
        except (DeadlineExceeded, TimeoutError, _FutureTimeout) as exc:
            self._error(504, f"scoring deadline exceeded: {exc}")
            return
        except Exception as exc:  # scoring/batcher error → 500 + count
            logger.exception("scoring request failed")
            self._error(500, f"scoring failed: {exc}")
            return
        body = {"scores": scores, "uids": [r.uid for r in reqs]}
        if want_trace:
            # Filled by the flush before each future resolved; reading
            # after result() is the race-free side of that edge.
            body["attribution"] = [getattr(f, "attribution", None)
                                   for f in futures]
        self._json(200, body)

    def log_message(self, fmt, *args):  # route access logs off stderr
        logger.debug("http: " + fmt, *args)


def make_http_server(service: ScoringService, host: str = "127.0.0.1",
                     port: int = 8080) -> ThreadingHTTPServer:
    """Bind (not yet serving — call ``serve_forever``). ``port=0`` picks a
    free port (tests); the bound port is ``server.server_address[1]``."""
    handler = type("BoundServingHandler", (_ServingHandler,),
                   {"service": service})
    return ThreadingHTTPServer((host, port), handler)
