"""Block-parallel, pipelined Avro decode with a deterministic merge.

Native Avro decode is SERIAL host work in front of a cold fit and of
the parallelized staging pass it feeds (docs/STAGING.md); its rate is
not measured on the current chip's host. This module is
the staging pipeline's structure applied one layer upstream: the input
splits at Avro sync-marker block boundaries (ingest/blocks.py), native
decode workers fan over the resulting chunks — a thread pool by
default, because the ctypes calls into native/avro_decode.cc release
the GIL for the whole block decode, with the spawn-process fallback
shared with staging (utils/workers.py) — and a depth-bounded
producer/consumer seam hands decoded column batches to the fold in
plan order as they finish. Scheduling never changes content: the
in-order concatenation of chunk outputs is bit-identical to the serial
whole-file read (tests/test_ingest.py parametrizes worker counts and
both pool modes against the serial reader).

The columnar ingest cache (ingest/cache.py) rides the same seam: each
chunk's decoded columns persist (atomically, CRC-committed) the moment
the chunk is decoded, so warm restarts memory-map columns instead of
re-decoding Avro and a killed run resumes with per-chunk partial
credit.

Failure contract: a chunk whose decode raises (corrupt block, bad
record) fails the read at that chunk's PLAN position — the consumer
drains in order, so the surfaced error is the first bad chunk in
record order, matching the serial reader's fail-fast point. A broken
process pool (crashed worker) quarantines the pool and re-decodes the
remaining chunks inline on the scheduler thread, bit-identically.
Faults are injectable at ``ingest.decode_block`` / ``ingest.cache_write``
/ ``ingest.cache_file`` (photon_ml_tpu/faults, docs/ROBUSTNESS.md).
"""

from __future__ import annotations

import concurrent.futures as cf
import dataclasses
import logging
import os
import threading
import time
from typing import Optional

import numpy as np

from photon_ml_tpu import faults as flt
from photon_ml_tpu.avro import native_decode as nd
from photon_ml_tpu.ingest import cache as ing_cache
from photon_ml_tpu.ingest.blocks import ChunkSpec
from photon_ml_tpu.utils import events as ev_mod
from photon_ml_tpu.utils import workers as pools

logger = logging.getLogger("photon_ml_tpu.ingest")


@dataclasses.dataclass(frozen=True)
class IngestConfig:
    """Knobs of the parallel ingestion pipeline.

    ``workers``: decode pool size (None -> os.cpu_count()). ``mode``:
    "thread" (default; the native block decode releases the GIL) or
    "process" (spawn, shared with StagingConfig — for exotic workloads
    where Python-side work dominates). ``pipeline_depth``: max
    decoded-but-unfolded chunks (None -> workers + 2) — bounds host
    memory the way StagingConfig.pipeline_depth bounds staged shards.
    ``chunk_records``: target records per decode task (chunks round up
    to whole Avro blocks). ``cache_dir``: columnar ingest cache root
    (None disables caching).
    """

    workers: Optional[int] = None
    mode: str = "thread"
    pipeline_depth: Optional[int] = None
    chunk_records: int = 65536
    cache_dir: Optional[str] = None

    def __post_init__(self):
        if self.mode not in ("thread", "process"):
            raise ValueError(f"ingest mode must be 'thread' or "
                             f"'process', got {self.mode!r}")
        for name in ("workers", "pipeline_depth"):
            v = getattr(self, name)
            if v is not None and v < 1:
                raise ValueError(f"ingest {name} must be >= 1, got {v}")
        if self.chunk_records < 1:
            raise ValueError(f"ingest chunk_records must be >= 1, "
                             f"got {self.chunk_records}")

    def resolved_workers(self) -> int:
        return max(1, self.workers or os.cpu_count() or 1)

    def resolved_depth(self) -> int:
        return self.pipeline_depth or self.resolved_workers() + 2


def _decode_chunk_task(spec: ChunkSpec, plan: np.ndarray, n_bags: int,
                       cache_dir: Optional[str], key: Optional[str]):
    """One pool task: decode a sync-aligned byte range and (optionally)
    commit its columns to the ingest cache. Module-level so the spawn
    process pool can pickle it; in thread mode it runs in the driver
    process, so the ``ingest.cache_write`` fault site fires there (the
    chaos suite's driver-kill drill)."""
    flt.fire(flt.sites.INGEST_DECODE_BLOCK, index=spec.index)
    d = nd.decode_span(spec.path, spec.header_len, spec.start, spec.end,
                       plan, n_bags)
    if cache_dir and key:
        try:
            ing_cache.save_chunk(cache_dir, key, spec.index, d)
        except OSError as e:
            # The cache is best-effort; ingestion is not.
            logger.warning(
                "ingest cache write for chunk %d failed (%s: %s); "
                "ingestion continues", spec.index, type(e).__name__, e)
    return d


class IngestPipeline:
    """Background decode pipeline over one ingest plan.

    Construction probes the cache and starts a daemon scheduler thread;
    ``chunks()`` yields each chunk's ``DecodedFile`` in plan order as it
    becomes available (blocking), releasing the depth bound as the
    consumer folds — the ingestion analogue of
    ``ProjectionStager.shards()``.
    """

    def __init__(self, chunks: list[ChunkSpec], plans: list[np.ndarray],
                 n_bags: int, config: Optional[IngestConfig] = None,
                 cache_key: Optional[str] = None,
                 emitter: Optional[ev_mod.EventEmitter] = None):
        self.config = config or IngestConfig()
        self.plan = chunks
        self._plans = plans  # per input file, indexed by spec.file_index
        self._n_bags = n_bags
        self._cache_dir = self.config.cache_dir if cache_key else None
        self._cache_key = cache_key
        self._emitter = emitter or ev_mod.default_emitter
        self._futures = [cf.Future() for _ in chunks]
        self._closed = threading.Event()  # consumer abandoned the stream
        self._quarantined = False
        self._q_lock = threading.Lock()
        self._t0 = time.monotonic()

        self._cached: set[int] = set()
        if self._cache_dir:
            for spec in chunks:
                d = ing_cache.load_chunk(self._cache_dir, self._cache_key,
                                         spec.index, n_bags)
                if d is not None and d.num_records == spec.records:
                    self._cached.add(spec.index)
                    self._futures[spec.index].set_result(("cache", d))
        self.num_cached = len(self._cached)

        missing = [s for s in chunks if s.index not in self._cached]
        if missing:
            self._sem = threading.Semaphore(self.config.resolved_depth())
            self._thread = threading.Thread(
                target=self._run, args=(missing,), daemon=True,
                name="pml-ingest-sched")
            self._thread.start()
        else:
            self._thread = None
            if self._cache_dir and chunks:
                self._finalize_meta()

    # -- scheduler ---------------------------------------------------------

    def _run(self, missing: list[ChunkSpec]) -> None:
        cfg = self.config
        ctx: dict = {}
        fplan = flt.current_plan()
        if fplan is not None:
            ctx["fault_plan"] = fplan
        pool = pools.make_pool(cfg.mode, cfg.resolved_workers(), ctx,
                               thread_name_prefix="pml-ingest")
        try:
            for spec in missing:
                while not self._sem.acquire(timeout=0.1):
                    if self._closed.is_set():
                        return
                if self._closed.is_set():
                    return
                self._dispatch(pool, spec)
            # Retire only once every chunk settled (or the consumer
            # abandoned the stream) — cancel_futures below must never
            # cancel work the consumer is still waiting on.
            while (not self._closed.is_set()
                   and not all(f.done() for f in self._futures)):
                time.sleep(0.05)
        finally:
            pool.shutdown(wait=False, cancel_futures=True)
            if self._cache_dir and all(
                    f.done() and not f.cancelled()
                    and f.exception() is None for f in self._futures):
                self._finalize_meta()

    def _dispatch(self, pool, spec: ChunkSpec) -> None:
        args = (spec, self._plans[spec.file_index], self._n_bags,
                self._cache_dir, self._cache_key)
        t_submit = time.monotonic()
        fut = None
        with self._q_lock:
            quarantined = self._quarantined
        if not quarantined:
            try:
                fut = pool.submit(_decode_chunk_task, *args)
            except RuntimeError as e:  # BrokenExecutor / shut-down pool
                self._note_quarantine(spec.index, e)
        if fut is None:  # quarantined: decode inline, bit-identically
            self._settle(spec.index, t_submit,
                         lambda: _decode_chunk_task(*args))
            return
        fut.add_done_callback(
            lambda f, i=spec.index, t=t_submit, a=args:
            self._on_done(i, t, a, f))

    def _on_done(self, index, t_submit, args, fut) -> None:
        # Pool-callback thread: broken pools fall back to an inline
        # re-decode (the staging quarantine rung); real decode errors
        # settle the chunk's future with the exception.
        try:
            res = fut.result()
        except cf.BrokenExecutor as e:
            self._note_quarantine(index, e)
            self._settle(index, t_submit,
                         lambda: _decode_chunk_task(*args))
        except BaseException as e:
            if not self._futures[index].done():
                self._futures[index].set_exception(e)
        else:
            self._publish(index, t_submit, res)

    def _settle(self, index, t_submit, thunk) -> None:
        try:
            res = thunk()
        except BaseException as e:
            if not self._futures[index].done():
                self._futures[index].set_exception(e)
        else:
            self._publish(index, t_submit, res)

    def _publish(self, index, t_submit, res) -> None:
        self._futures[index].set_result(("decoded", res))
        self._emitter.emit(ev_mod.IngestBlock(
            index=index, records=res.num_records,
            seconds=time.monotonic() - t_submit, source="decoded"))

    def _note_quarantine(self, index, exc) -> None:
        with self._q_lock:
            first = not self._quarantined
            self._quarantined = True
        if first:
            logger.warning(
                "ingest: decode pool broken at chunk %d (%s: %s) — "
                "quarantining the pool; remaining chunks decode inline "
                "(bit-identical, slower)", index, type(exc).__name__, exc)

    def _finalize_meta(self) -> None:
        try:
            ing_cache.save_meta(self._cache_dir, self._cache_key,
                                len(self.plan),
                                sum(s.records for s in self.plan))
        except OSError:
            pass

    # -- consumer ----------------------------------------------------------

    def chunks(self):
        """Yield each chunk's DecodedFile in plan order (blocking); the
        depth bound is released as the consumer takes each decoded
        chunk. Emits the IngestStart/IngestFinish pair around the
        stream (finally-guarded: an error mid-fold still closes the
        lifecycle)."""
        cfg = self.config
        self._emitter.emit(ev_mod.IngestStart(
            num_files=len(self._plans), num_chunks=len(self.plan),
            workers=cfg.resolved_workers(), mode=cfg.mode,
            cached_chunks=self.num_cached))
        consumed = 0
        records = 0
        try:
            for i in range(len(self.plan)):
                src, d = self._futures[i].result()
                if src == "cache":
                    self._emitter.emit(ev_mod.IngestBlock(
                        index=i, records=d.num_records, seconds=0.0,
                        source="cache"))
                try:
                    yield d
                finally:
                    consumed += 1
                    records += d.num_records
                    if src == "decoded":
                        self._sem.release()
        finally:
            self._closed.set()
            self._emitter.emit(ev_mod.IngestFinish(
                num_files=len(self._plans), num_chunks=consumed,
                records=records, cached_chunks=self.num_cached,
                wall_seconds=time.monotonic() - self._t0))
