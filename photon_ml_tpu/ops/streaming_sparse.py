"""Row-streamed sparse GLM aggregates: the Criteo row axis on one chip.

Reference parity: photon-api ``DistributedGLMLossFunction`` computes each
value/gradient as one Spark pass over RDD partitions (``treeAggregate``) —
the n axis never has to fit on any single executor. This module is the
TPU-native equivalent: the example rows live on HOST in fixed-size chunks,
and every objective evaluation streams them through the chip with
double-buffered host→device prefetch, accumulating ``(value, gradient)``
in f32 on device. HBM holds at most ``prefetch_depth`` chunks plus the
accumulators, so n is bounded by host RAM (or disk, via the chunk
iterator), not by the 16 GB of one chip.

**Chunk layout: hot-dense block + cold ELL.** Each chunk densifies its
top-``num_hot`` columns into an (n, H) MXU block (the Zipf head is the
bulk of the nonzeros) and keeps the remaining entries in ELL with their
ORIGINAL column ids (hot entries become inert pad slots). Two hard
lessons at n=100M shape this (both measured on v5e, both aborting
COMPILATION with HBM overflows before any data moved):

  * gathers/scatters must be per-ELL-slot 1-D ops — an index operand
    shaped (n, k) or (n, k, 1) is materialized in a (8, 128)-tiled
    layout whose minor dims pad to 128 (a 51 GB copy at n=100M);
  * no flat concatenated streams — XLA lays a 128M-element 1-D
    intermediate out as (64M, 2) tiled, padding 2→128 (a 33 GB copy).
    This is why the device-resident hybrid's contiguous-class layout
    (ops/hybrid_sparse.py), which wins 6-8× at bench scale, is NOT used
    here: its per-class flat gather/scatter streams cannot compile at
    streamed-chunk scale, and the stream is host→device transfer-bound
    anyway, so the cold formulation's compute rate is immaterial.

Every chunk has identical array shapes by construction ((n, H), (H,),
(n, k)), so the WHOLE stream shares ONE compiled program — per-structure
compiles are multi-minute remote operations in this environment.

**int8 quantized storage (docs/STREAMING.md "Quantized streaming").**
The streamed pass is transfer-bound (~95% host→device at n=100M), so
the storage dtype of the chunk payload IS the pass cost. Beyond the
bf16 half-stream, ``feature_dtype="int8"`` stores ``X_hot`` and
``cold_vals`` as symmetric per-column affine int8 — q = round(x / s),
s = max|column| / 127, zero-point pinned at 0 so sparse zeros stay
EXACT — with f32 scale vectors riding each chunk (``hot_scale`` per hot
column, ``cold_scale`` per original column). Dequantization happens
ON DEVICE inside the jitted chunk kernels, and never materializes a
dense f32 block: the margins pass folds the scales into the coefficient
gathers (w·(s·q) = (w·s)·q), and the gradient pass scatters raw r·q
sums and scales the (d+1,) accumulator once at the end — O(d + H)
dequant flops against an O(n·k) transfer saved. Accumulation stays f32
throughout, so the compiled program count is unchanged (the kernel
caches grow a dtype key) and the measured ``photon_transfer_bytes_total``
per pass drops ~4× vs f32 (~2× vs bf16).
"""

from __future__ import annotations

import dataclasses
import gc
import logging
import time
from typing import Callable, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu import faults as flt
from photon_ml_tpu import obs
# The hot block's dtype table and byte planner live with the resident
# layout, which sizes its block through the same plan_num_hot.
from photon_ml_tpu.ops.hybrid_sparse import (  # noqa: F401
    _dense_hot, _hot_matvec, _hot_rmatvec, feature_dtype_name, plan_num_hot)
from photon_ml_tpu.ops.losses import PointwiseLoss

Array = jax.Array

logger = logging.getLogger("photon_ml_tpu.ops")

# Chunk host→device transfer degradation ladder (docs/ROBUSTNESS.md):
# bounded retry with deterministic backoff, then a loud failure — a
# transfer is idempotent (the chunk is host-resident), so retry is always
# safe, and there is no serial fallback below it to degrade to.
TRANSFER_MAX_RETRIES = 2
TRANSFER_RETRY_BACKOFF_S = 0.05


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CanonicalChunk:
    """One chunk: hot-dense block + cold ELL (leaves may be host numpy —
    device placement happens at stream time).

    Under int8 storage ``X_hot``/``cold_vals`` hold the quantized codes
    and the two scale vectors are present (``quantized`` is True); the
    scheme is symmetric (zero-point ≡ 0), so a zero entry is exactly the
    code 0 and the pad/hot-inert slots stay inert without masks."""

    X_hot: Array  # (n, H) — the chunk's top-H columns, densified
    hot_cols: Array  # (H,) int32 original column ids (pad == d)
    cold_cols: Array  # (n, k) int32 original ids; hot/pad entries == d
    cold_vals: Array  # (n, k); hot/pad entries == 0
    labels: Array  # (n,)
    weights: Array  # (n,); 0 marks pad rows of a short final chunk
    offsets: Array  # (n,)
    num_features: int = dataclasses.field(metadata=dict(static=True))
    # int8 mode only (None otherwise): per-hot-column and per-original-
    # column f32 dequantization scales (x ≈ scale · q, zero-point 0).
    hot_scale: Optional[Array] = None  # (H,)
    cold_scale: Optional[Array] = None  # (d + 1,); sentinel col == 0

    @property
    def num_rows(self) -> int:
        return self.labels.shape[0]

    @property
    def num_hot(self) -> int:
        return self.X_hot.shape[1]

    @property
    def quantized(self) -> bool:
        return self.cold_scale is not None

    def structure(self):
        """Shape signature — equal signatures share one compiled program.
        Identical across chunks by construction (the storage dtype is
        part of the signature: a mixed-dtype stream would silently
        compile two programs); kept for the invariant test."""
        return (self.X_hot.shape, self.cold_cols.shape,
                self.num_features, chunk_dtype(self))


@dataclasses.dataclass(frozen=True)
class ChunkedHybrid:
    """Host-resident chunked layout of one logical (n, d) batch.

    Equal row counts per chunk (short final chunk padded with weight-0
    rows — inert in every aggregate; their margins are dropped by
    ``margins_chunked``). ``num_rows`` is the REAL row count.
    """

    chunks: tuple[CanonicalChunk, ...]
    num_rows: int
    chunk_rows: int

    @property
    def dim(self) -> int:
        return self.chunks[0].num_features

    @property
    def num_chunks(self) -> int:
        return len(self.chunks)


INT8_QMAX = 127.0  # symmetric: codes span [-127, 127], zero-point 0


def chunk_dtype(ch: "CanonicalChunk") -> str:
    """The storage dtype a staged chunk actually carries."""
    if ch.cold_scale is not None:
        return "int8"
    if np.dtype(ch.X_hot.dtype) == np.dtype(jnp.bfloat16):
        return "bfloat16"
    return "float32"


def quantize_rows_int8(x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Symmetric per-ROW int8 quantization: q = round(x / s) with
    s = max|row| / 127 (all-zero rows keep scale 0 and code 0, so
    dequantization is exact for them). Shared by the chunk hot block
    (transposed) and the serving device-LRU fill path."""
    x = np.asarray(x, np.float32)
    scale = np.abs(x).max(axis=-1) / INT8_QMAX if x.size else \
        np.zeros(x.shape[:-1], np.float32)
    scale = np.asarray(scale, np.float32)
    denom = np.where(scale > 0.0, scale, 1.0)
    q = np.clip(np.rint(x / denom[..., None]), -INT8_QMAX,
                INT8_QMAX).astype(np.int8)
    return q, scale


def _quantize_cold_int8(cold_vals: np.ndarray, cold_cols: np.ndarray,
                        d: int) -> tuple[np.ndarray, np.ndarray]:
    """Per-ORIGINAL-column symmetric int8 over a chunk's cold ELL: the
    scale table is (d + 1,) so the kernels can gather it exactly like
    the padded coefficient vector (per-slot, 1-D — the layout rules).
    Inert entries all point at the sentinel column d, whose scale stays
    0 by construction (their stored value is exactly 0)."""
    amax = np.zeros(d + 1, np.float32)
    np.maximum.at(amax, cold_cols.reshape(-1),
                  np.abs(cold_vals).reshape(-1))
    scale = amax / INT8_QMAX
    denom = np.where(scale > 0.0, scale, 1.0)
    q = np.clip(np.rint(cold_vals / denom[cold_cols]), -INT8_QMAX,
                INT8_QMAX).astype(np.int8)
    return q, scale


def _build_canonical(raw, d: int, num_hot: int,
                     feature_dtype) -> CanonicalChunk:
    """Stage one ELL chunk into hot-dense + cold-ELL (host numpy)."""
    indices = np.asarray(raw.indices)
    values = np.asarray(raw.values)
    n = indices.shape[0]
    H = num_hot

    flat_col = indices.reshape(-1)
    flat_val = values.reshape(-1)
    live = (flat_col < d) & (flat_val != 0.0)
    counts = np.bincount(flat_col[live], minlength=d)
    # Top-H by count (count ties at the hot boundary break arbitrarily —
    # the hot/cold split is an execution choice, any split is the same
    # objective). Columns with count 0 may land in the tail of hot_cols
    # on tiny chunks — their X_hot columns stay zero and their id is
    # replaced by the sentinel. build_chunked guarantees H <= d.
    order = np.argpartition(-counts, H - 1)[:H].astype(np.int32)
    order = order[np.argsort(-counts[order], kind="stable")]
    hot_live = counts[order] > 0
    hot_cols = np.where(hot_live, order, d).astype(np.int32)

    hot_slot = np.full(d + 1, -1, np.int64)
    hot_slot[hot_cols[hot_cols < d]] = np.flatnonzero(hot_cols < d)

    slot = hot_slot[np.minimum(flat_col, d)]
    hot_sel = live & (slot >= 0)
    # Slot by slot, so two slots of a row that meet in one hot column add
    # up, as they do on the cold side and in the resident layouts.
    X_hot = _dense_hot(np.where(hot_sel, slot, H).reshape(indices.shape),
                       values, H, n, jnp.float32)

    # Cold ELL: the original (n, k) arrays with hot entries inert.
    is_hot2d = (slot >= 0).reshape(indices.shape)
    dead = is_hot2d | ~live.reshape(indices.shape)
    cold_cols = np.where(dead, d, indices).astype(np.int32)
    cold_vals = np.where(dead, 0.0, values).astype(np.float32)

    dtype_name = feature_dtype_name(feature_dtype)
    hot_scale = cold_scale = None
    if dtype_name == "bfloat16":
        # Host-side cast halves the host→device stream — which IS the
        # steady-state cost of every streamed objective evaluation.
        # Values are storage (products upcast to f32 in-kernel).
        import ml_dtypes

        X_hot = X_hot.astype(ml_dtypes.bfloat16)
        cold_vals = cold_vals.astype(ml_dtypes.bfloat16)
    elif dtype_name == "int8":
        # Symmetric per-column int8: quarters the stream vs f32. The hot
        # block quantizes per hot column (transpose into the per-row
        # helper); the cold ELL per original column so the scale table
        # gathers like w_pad.
        q_hot, hot_scale = quantize_rows_int8(X_hot.T)
        X_hot = np.ascontiguousarray(q_hot.T)
        cold_vals, cold_scale = _quantize_cold_int8(cold_vals, cold_cols,
                                                    d)
    return CanonicalChunk(
        X_hot=X_hot, hot_cols=hot_cols, cold_cols=cold_cols,
        cold_vals=cold_vals, labels=np.asarray(raw.labels),
        weights=np.asarray(raw.weights), offsets=np.asarray(raw.offsets),
        num_features=d, hot_scale=hot_scale, cold_scale=cold_scale)


def build_chunked(
    chunk_iter: Iterable,
    num_features: int,
    chunk_rows: int,
    num_hot: int = 512,
    feature_dtype=jnp.float32,
    log: Optional[Callable[[str], None]] = None,
    workers: int = 1,
) -> ChunkedHybrid:
    """Stage a stream of ELL chunks into host-resident canonical layouts.

    ``chunk_iter`` yields objects with ``indices / values / labels /
    weights / offsets`` host arrays (``data/sparse.SparseBatch`` or any
    duck-typed source — the chunked Avro reader, a synthetic generator).
    Peak host memory beyond the staged output is ONE chunk serially;
    ``workers > 1`` fans the per-chunk canonicalization (bincount +
    argpartition + scatter — GIL-releasing numpy) over a thread pool
    with a bounded in-flight window of ``workers + 2`` chunks, merged in
    plan order BIT-identically to the serial pass (the per-chunk math is
    independent; only the submission order is pipelined)."""
    import concurrent.futures as cf

    num_hot = min(num_hot, num_features)
    total = 0
    short_at = None
    rows_of: list[int] = []

    def _prepped():
        """Serial validation + tail padding (cheap) ahead of the
        canonicalization fan-out; mutates total/short_at bookkeeping."""
        nonlocal total, short_at
        for i, raw in enumerate(chunk_iter):
            if short_at is not None:
                # Row bookkeeping (margins_chunked's z[:num_rows] tail
                # drop, _offsets_for's i*chunk_rows slices) assumes pad
                # rows exist only at the STREAM tail; a mid-stream short
                # chunk would silently misalign residuals.
                raise ValueError(
                    f"chunk {short_at} was short but chunk {i} follows — "
                    f"only the final chunk may have fewer than chunk_rows="
                    f"{chunk_rows} rows")
            n_i = int(np.asarray(raw.labels).shape[0])
            if n_i > chunk_rows:
                raise ValueError(f"chunk {i} has {n_i} rows > chunk_rows="
                                 f"{chunk_rows}")
            total += n_i
            rows_of.append(n_i)
            if n_i < chunk_rows:
                short_at = i
                raw = _pad_chunk(raw, chunk_rows, num_features)
            yield i, raw

    chunks: list[CanonicalChunk] = []

    def _emit(i: int, ch: CanonicalChunk) -> None:
        chunks.append(ch)
        if log is not None:
            cold_live = int((np.asarray(ch.cold_cols) <
                             num_features).sum())
            log(f"staged chunk {i} ({rows_of[i]:,} rows, {num_hot} hot "
                f"cols, {cold_live:,} cold nnz)")

    if workers <= 1:
        for i, raw in _prepped():
            _emit(i, _build_canonical(raw, num_features, num_hot,
                                      feature_dtype))
    else:
        import collections

        window: collections.deque = collections.deque()
        with cf.ThreadPoolExecutor(
                max_workers=workers,
                thread_name_prefix="pml-stream-stage") as pool:
            for i, raw in _prepped():
                window.append((i, pool.submit(
                    _build_canonical, raw, num_features, num_hot,
                    feature_dtype)))
                if len(window) > workers + 2:
                    j, fut = window.popleft()
                    _emit(j, fut.result())
            while window:
                j, fut = window.popleft()
                _emit(j, fut.result())
    if not chunks:
        raise ValueError("empty chunk stream")
    sigs = {ch.structure() for ch in chunks}
    if len(sigs) > 1:
        # Shapes inherit the source's ELL width — a source that pads
        # per-chunk (varying max_nnz) breaks the one-program invariant.
        raise ValueError(
            f"chunks have {len(sigs)} distinct structures {sigs}; pad "
            "every chunk's ELL to one shared max_nnz so the stream "
            "shares a single compiled program")
    return ChunkedHybrid(chunks=tuple(chunks), num_rows=total,
                         chunk_rows=chunk_rows)


def iter_shard_chunks(shard, labels, weights, chunk_rows: int):
    """SparseBatch chunks over an ELL SparseShard's row ranges, staged
    with ZERO offsets (the streaming contract: in coordinate descent the
    residual arrives via ``train_model``'s offsets argument, never via
    the staged chunks). Feeds :func:`build_chunked` from a materialized
    GameDataset shard — the estimator's route onto the streamed path.
    Slices are views (no copy); _build_canonical owns the real work."""
    from photon_ml_tpu.data.sparse import SparseBatch

    labels = np.asarray(labels)
    weights = np.asarray(weights)
    n = int(shard.indices.shape[0])
    for lo in range(0, n, chunk_rows):
        hi = min(lo + chunk_rows, n)
        yield SparseBatch(
            indices=shard.indices[lo:hi], values=shard.values[lo:hi],
            labels=labels[lo:hi], weights=weights[lo:hi],
            offsets=np.zeros(hi - lo, np.float32),
            num_features=int(shard.num_features))


def _pad_chunk(raw, chunk_rows: int, d: int):
    """Pad a short (final) chunk with weight-0 rows: every aggregate
    multiplies by weight before reducing, so pad rows add exactly 0 to
    value/gradient, and their margins are dropped by
    ``margins_chunked``."""
    from photon_ml_tpu.data.sparse import SparseBatch

    idx = np.asarray(raw.indices)
    n_i, nnz = idx.shape
    pad = chunk_rows - n_i

    def pad0(a):
        a = np.asarray(a)
        out = np.zeros((chunk_rows,) + a.shape[1:], a.dtype)
        out[:n_i] = a
        return out

    idx_p = np.full((chunk_rows, nnz), d, np.int32)
    idx_p[:n_i] = idx
    return SparseBatch(
        indices=idx_p, values=pad0(raw.values), labels=pad0(raw.labels),
        weights=pad0(raw.weights), offsets=pad0(raw.offsets),
        num_features=d)


# ---------------------------------------------------------------- kernels


def _masked(weights: Array, term: Array) -> Array:
    return jnp.where(weights > 0.0, weights * term, 0.0)


def _resolve_stream_fused(dtype: str):
    """(margins, rmatvec) fused-kernel resolutions for this stream's
    programs, or None where the flag is off or the resolve degraded.

    Called from the program BUILDERS only (one resolve per compiled
    program — the one-program-per-stream invariant extends to backend
    choice). Flag off means NO registry traffic: the ledger a flag-off
    run writes is byte-identical to the pre-registry tree, which is what
    keeps the trace_smoke ≤3-builds needle honest."""
    from photon_ml_tpu.ops import kernels
    reg = kernels.registry()
    fused_m = fused_r = None
    if reg.enabled("stream_margins"):
        rk = reg.resolve("stream_margins", dtype=dtype)
        if rk.backend == "pallas":
            fused_m = rk
    if reg.enabled("stream_rmatvec"):
        rk = reg.resolve("stream_rmatvec", dtype=dtype)
        if rk.backend == "pallas":
            fused_r = rk
    return fused_m, fused_r


def _chunk_margins_of(ch: CanonicalChunk, w_pad: Array, offsets: Array,
                      fused_margins=None) -> Array:
    """(n,) wᵀx + offset. Hot: one MXU matvec. Cold: one 1-D gather per
    ELL slot (per-slot, 1-D — see the module docstring's layout rules).

    int8 dequant prologue: the per-column scales FOLD into the
    coefficient side — w·(s·q) = (w·s)·q — so the quantized codes feed
    the same matvec/gathers with f32 accumulation and no dense f32
    block is ever materialized.

    ``fused_margins`` (registry ``stream_margins``, docs/KERNELS.md):
    the cold per-slot terms become the PROLOGUE — summed into ``base``
    first, byte-small by the hot/cold split — and the hot tier runs as
    one Pallas program with the dequant upcast inside the matvec tiles,
    so even the explicit ``astype`` copy below never materializes."""
    if ch.cold_scale is not None:
        w_cold = w_pad * ch.cold_scale
        w_hot = w_pad[ch.hot_cols] * ch.hot_scale
        if fused_margins is not None:
            base = offsets
            for j in range(ch.cold_cols.shape[1]):
                base = base + w_cold[ch.cold_cols[:, j]] * \
                    ch.cold_vals[:, j].astype(jnp.float32)
            return fused_margins(ch.X_hot, w_hot, base)
        z = offsets + _hot_matvec(ch.X_hot.astype(jnp.float32), w_hot)
        for j in range(ch.cold_cols.shape[1]):
            z = z + w_cold[ch.cold_cols[:, j]] * \
                ch.cold_vals[:, j].astype(jnp.float32)
        return z
    if fused_margins is not None:
        base = offsets
        for j in range(ch.cold_cols.shape[1]):
            base = base + w_pad[ch.cold_cols[:, j]] * \
                ch.cold_vals[:, j].astype(jnp.float32)
        return fused_margins(ch.X_hot, w_pad[ch.hot_cols], base)
    z = offsets + _hot_matvec(ch.X_hot, w_pad[ch.hot_cols])
    for j in range(ch.cold_cols.shape[1]):
        z = z + w_pad[ch.cold_cols[:, j]] * \
            ch.cold_vals[:, j].astype(jnp.float32)
    return z


def _chunk_rowterm_grad(ch: CanonicalChunk, r: Array,
                        fused_rmatvec=None) -> Array:
    """Σᵢ rᵢ·xᵢ in original space: hot rmatvec + one (d+1,)-table
    scatter-add per cold ELL slot (pad entries land on the sentinel
    column d and are dropped).

    int8 dequant prologue: scatter the RAW r·q sums, then scale the
    (d+1,) accumulator once per column (g_col = s_col · Σ r·q) — the
    dequant costs O(d + H) per chunk instead of O(n·k).

    ``fused_rmatvec`` (registry ``stream_rmatvec``): the hot tier's
    Xᵀr runs with the int8 upcast inside the tiles (no (n,H) f32 copy);
    the O(H) scale epilogue stays out here either way."""
    if ch.cold_scale is not None:
        acc = jnp.zeros((ch.num_features + 1,), jnp.float32)
        for j in range(ch.cold_cols.shape[1]):
            acc = acc.at[ch.cold_cols[:, j]].add(
                r * ch.cold_vals[:, j].astype(jnp.float32))
        acc = acc * ch.cold_scale
        if fused_rmatvec is not None:
            g_hot = fused_rmatvec(ch.X_hot, r) * ch.hot_scale
        else:
            g_hot = _hot_rmatvec(ch.X_hot.astype(jnp.float32), r) * \
                ch.hot_scale
        acc = acc.at[ch.hot_cols].add(g_hot.astype(jnp.float32))
        return acc[:ch.num_features]
    acc = jnp.zeros((ch.num_features + 1,), jnp.float32)
    for j in range(ch.cold_cols.shape[1]):
        acc = acc.at[ch.cold_cols[:, j]].add(
            r * ch.cold_vals[:, j].astype(jnp.float32))
    if fused_rmatvec is not None:
        g_hot = fused_rmatvec(ch.X_hot, r).astype(jnp.float32)
    else:
        g_hot = _hot_rmatvec(ch.X_hot, r).astype(jnp.float32)
    acc = acc.at[ch.hot_cols].add(g_hot)
    return acc[:ch.num_features]


# Kernels are cached per (loss, storage dtype) — the dtype key is how
# quantized streams keep the one-program-per-stream accounting honest
# (an int8 chunk IS a different compiled program; without the key the
# jit dispatch would compile it silently past the miss counter). The
# margins kernel stays a singleton (jit dispatches on chunk structure).
_VG_KERNELS: dict = {}
_V_KERNELS: dict = {}


def _count_kernel_build(cache: str, dtype: str) -> None:
    """One streamed-kernel program cache missed — a fresh trace/compile.
    Steady state should show exactly one build per (loss, cache, dtype);
    a climbing counter means the one-program-per-stream invariant
    broke."""
    mx = obs.metrics()
    if mx is not None:
        mx.counter("photon_compile_cache_misses_total", cache=cache,
                   dtype=dtype).inc()


def _count_kernel_hit(cache: str, dtype: str) -> None:
    """The hit side of the same ledger: a warm pass re-using its
    compiled program. Boot/warm-restart paths should show HITS climbing
    beside a flat miss counter — silence there means the cache key
    rotated and every restart recompiles (docs/SERVING.md "Sub-second
    restart")."""
    mx = obs.metrics()
    if mx is not None:
        mx.counter("photon_compile_cache_hits_total", cache=cache,
                   dtype=dtype).inc()


def _chunk_value_grad(loss: PointwiseLoss, dtype: str = "float32"):
    """One jitted per-chunk pass: original-space w in, original-space
    (value, grad) out — shared by every chunk (identical structures).

    The cache key carries the resolved fused-kernel state: a flag flip
    mid-process gets a FRESH program (and a counted build) instead of
    silently reusing the other backend's compile."""
    fused_m, fused_r = _resolve_stream_fused(dtype)
    key = (loss.name, dtype, fused_m is not None, fused_r is not None)
    f = _VG_KERNELS.get(key)
    if f is not None:
        _count_kernel_hit("stream_value_grad", dtype)
        return f
    _count_kernel_build("stream_value_grad", dtype)

    @jax.jit
    def f(w: Array, offsets: Array, ch: CanonicalChunk):
        w_pad = jnp.concatenate([w, jnp.zeros((1,), w.dtype)])
        z = _chunk_margins_of(ch, w_pad, offsets, fused_margins=fused_m)
        l, dl = loss.loss_and_dz(z, ch.labels)
        value = jnp.sum(_masked(ch.weights, l))
        r = _masked(ch.weights, dl)
        return value, _chunk_rowterm_grad(ch, r, fused_rmatvec=fused_r)

    _VG_KERNELS[key] = f
    return f


def _chunk_value(loss: PointwiseLoss, dtype: str = "float32"):
    """Value-ONLY per-chunk pass: the margins + loss sum of
    ``_chunk_value_grad`` without the gradient half (the hot rmatvec and
    the per-slot cold scatter-adds — the dominant compute of a chunk
    pass). Armijo line-search probes only need the value to gate
    acceptance (ADVICE r5), so probing with this kernel skips the
    gradient work on every rejected step."""
    fused_m, _ = _resolve_stream_fused(dtype)
    key = (loss.name, dtype, fused_m is not None)
    f = _V_KERNELS.get(key)
    if f is not None:
        _count_kernel_hit("stream_value_only", dtype)
        return f
    _count_kernel_build("stream_value_only", dtype)

    @jax.jit
    def f(w: Array, offsets: Array, ch: CanonicalChunk):
        w_pad = jnp.concatenate([w, jnp.zeros((1,), w.dtype)])
        z = _chunk_margins_of(ch, w_pad, offsets, fused_margins=fused_m)
        l, _ = loss.loss_and_dz(z, ch.labels)
        return jnp.sum(_masked(ch.weights, l))

    _V_KERNELS[key] = f
    return f


# Margins-only programs, keyed by fused-kernel state alone (jit
# dispatches on chunk structure/dtype within each entry — the
# pre-registry singleton behavior, per backend).
_MARGINS_KERNELS: dict = {}


def _margins_kernel(w: Array, offsets: Array, ch: CanonicalChunk):
    fused_m, _ = _resolve_stream_fused(str(jnp.asarray(ch.X_hot).dtype))
    key = fused_m is not None
    f = _MARGINS_KERNELS.get(key)
    if f is None:
        @jax.jit
        def f(w: Array, offsets: Array, ch: CanonicalChunk,
              _fused=fused_m):
            w_pad = jnp.concatenate([w, jnp.zeros((1,), w.dtype)])
            return _chunk_margins_of(ch, w_pad, offsets,
                                     fused_margins=_fused)

        _MARGINS_KERNELS[key] = f
    return f(w, offsets, ch)


def _chunk_nbytes(ch) -> int:
    """Host-side payload bytes of one chunk's leaves — the analytic unit
    the transfer accounting sums (ISSUE 7 satellite 1 asserts the total
    IS this sum, per streamed chunk, per pass)."""
    return int(sum(int(getattr(leaf, "nbytes", 0))
                   for leaf in jax.tree.leaves(ch)))


# Per-pass gc floor: the full collection after a streamed pass exists to
# bound lazily-freed transfer buffers (the n=100M lesson: ~60 GB of host
# RSS over 11 L-BFGS iterations before the OOM killer fired). That only
# matters when a pass actually moves serious bytes; eager per-chunk
# ``leaf.delete()`` already frees the device side, and a FULL gc.collect
# in a long-lived process (the test suite: measured 300s of a single
# test's wall, ~8s standalone) costs seconds per call once the heap is
# big. Collect only when the pass streamed enough for buffer pileup to
# matter — flagship passes (GBs) always collect; test passes (KBs) never.
GC_STREAM_BYTES_FLOOR = 1 << 28  # 256 MiB per pass


def _stream_nbytes(chunked: "ChunkedHybrid") -> int:
    """Total streamed payload per pass, memoized on the ChunkedHybrid."""
    cached = getattr(chunked, "_payload_nbytes", None)
    if cached is None:
        cached = sum(_chunk_nbytes(ch) for ch in chunked.chunks)
        object.__setattr__(chunked, "_payload_nbytes", cached)
    return cached


def _collect_after_pass(chunked: "ChunkedHybrid") -> None:
    if _stream_nbytes(chunked) >= GC_STREAM_BYTES_FLOOR:
        gc.collect()


def _transfer(ch: CanonicalChunk, index: int,
              device: Optional[jax.Device] = None):
    """Host→device chunk copy behind the ``stream.chunk_transfer`` fault
    site, with the bounded-retry ladder: a transfer is idempotent, so a
    transient failure retries with deterministic backoff; exhausted
    retries raise loudly (there is no degraded mode below a lost chunk —
    dropping it would silently change the objective).

    This is ALSO the ``device_put`` accounting seam (docs/OBSERVABILITY
    .md): when obs is on, every successful transfer adds its payload to
    ``photon_transfer_bytes_total``/``photon_transfer_seconds_total``
    and bumps the in-flight chunk gauge; off, the cost is one None check.
    The seconds counter measures the HOST-side ``device_put`` time (the
    enqueue/copy commit) — on a transfer-bound stream that is the wall.
    """
    for attempt in range(TRANSFER_MAX_RETRIES + 1):
        try:
            flt.fire(flt.sites.STREAM_CHUNK_TRANSFER, index=index)
            mx, tr = obs.metrics(), obs.tracer()
            if mx is None and tr is None:
                return (jax.device_put(ch, device) if device is not None
                        else jax.device_put(ch))
            return _accounted_transfer(ch, index, device, mx, tr)
        except Exception as e:
            if attempt >= TRANSFER_MAX_RETRIES:
                raise
            logger.warning(
                "chunk %d transfer failed (%s: %s); retry %d/%d",
                index, type(e).__name__, e, attempt + 1,
                TRANSFER_MAX_RETRIES)
            mx = obs.metrics()
            if mx is not None:
                mx.counter("photon_stream_transfer_retries_total").inc()
            time.sleep(TRANSFER_RETRY_BACKOFF_S * (attempt + 1))


def _accounted_transfer(ch, index: int, device, mx, tr):
    """The traced/metered half of :func:`_transfer` (split out so the
    off path stays one None check). The transfer family is tagged with
    the chunk's storage dtype — `photon-obs summarize` attributes the
    stream per dtype, and the quantization bench's byte claims share
    provenance with these counters (readers that don't care sum the
    label family via ``obs.metric_value``)."""
    nbytes = _chunk_nbytes(ch)
    dtype = chunk_dtype(ch)
    t0 = time.perf_counter()
    if tr is not None:
        with tr.span("stream.chunk_transfer", cat="transfer",
                     index=index, bytes=nbytes, dtype=dtype):
            out = (jax.device_put(ch, device) if device is not None
                   else jax.device_put(ch))
    else:
        out = (jax.device_put(ch, device) if device is not None
               else jax.device_put(ch))
    if mx is not None:
        dt = time.perf_counter() - t0
        mx.counter("photon_transfer_bytes_total", kind="stream",
                   dtype=dtype).inc(nbytes)
        mx.counter("photon_transfer_seconds_total", kind="stream",
                   dtype=dtype).inc(dt)
        mx.counter("photon_transfer_chunks_total", kind="stream",
                   dtype=dtype).inc()
        mx.gauge("photon_stream_inflight_chunks").inc()
    return out


def _delete_chunk(ch) -> None:
    """Eagerly drop one STREAMED chunk's device buffers and step the
    in-flight gauge back down — the gauge's peak is the measured form of
    the n=100M enqueue-scratch bound."""
    for leaf in jax.tree.leaves(ch):
        if isinstance(leaf, jax.Array):
            leaf.delete()
    mx = obs.metrics()
    if mx is not None:
        mx.gauge("photon_stream_inflight_chunks").dec()


def _stream(chunked: ChunkedHybrid, depth: int, pinned=()):
    """Yield device-resident chunks with ``depth`` transfers in flight
    ahead of the consumer (same discipline as data/prefetch.py — the
    host→device copy of chunk i+1 overlaps the compute on chunk i).
    ``pinned`` are already-resident leading chunks (yielded as-is, no
    transfer)."""
    import collections

    if depth < 1:
        # depth=0 would silently yield no streamed chunks at all (the
        # priming loop never fills the queue) — a zero value/gradient,
        # not a slower one.
        raise ValueError(f"prefetch_depth must be >= 1, got {depth}")
    for ch in pinned:
        yield ch
    q = collections.deque()
    it = enumerate(chunked.chunks)
    for _ in range(len(pinned)):
        next(it)
    try:
        for _ in range(depth):
            i, ch = next(it)
            q.append(_transfer(ch, i))
    except StopIteration:
        pass
    while q:
        ready = q.popleft()
        try:
            i, ch = next(it)
            q.append(_transfer(ch, i))
        except StopIteration:
            pass
        yield ready


def _offsets_for(chunked: ChunkedHybrid, offsets: Optional[Array], i: int,
                 ch: CanonicalChunk):
    if offsets is None:
        return ch.offsets if isinstance(ch.offsets, jax.Array) \
            else jnp.asarray(ch.offsets)
    lo = i * chunked.chunk_rows
    return jax.lax.dynamic_slice_in_dim(
        offsets, lo, chunked.chunk_rows, 0)


def pin_chunks(chunked: ChunkedHybrid, count: int):
    """Place the first ``count`` chunks on device permanently and return
    them — spare HBM traded for stream traffic (the steady-state cost of
    every objective evaluation drops by the pinned fraction). The caller
    owns the sizing decision: pinned bytes compete with whatever else
    the fit keeps resident (e.g. random-effect bucket blocks)."""
    return tuple(jax.device_put(ch)
                 for ch in chunked.chunks[:max(0, count)])


# ----------------------------------------------------------- chunk store
#
# Staged-chunk persistence (the staging_cache/ingest-cache v3 discipline,
# docs/ROBUSTNESS.md): one npz per chunk written atomically, a CRC32-
# carrying ``.ok`` commit marker per chunk written after it, and a
# ``meta.json`` completion record written LAST. The payload round-trips
# BIT-stable for every storage dtype (the int8 codes and their scale
# vectors are exact bytes — quantization happens once, at staging). A
# chunk whose bytes fail the committed CRC (bit rot, a torn write, an
# injected ``stream.quantize`` fault) degrades to a re-stage of exactly
# that chunk via the caller's ``rebuild`` hook — never a silently wrong
# objective, never a whole-stream restage.

CHUNK_STORE_VERSION = 1
_CHUNK_FIELDS = ("X_hot", "hot_cols", "cold_cols", "cold_vals", "labels",
                 "weights", "offsets", "hot_scale", "cold_scale")


class ChunkStoreError(RuntimeError):
    """A persisted chunk stream that cannot be served and cannot be
    rebuilt (no ``rebuild`` hook was provided)."""


def save_chunked(directory: str, chunked: ChunkedHybrid) -> None:
    """Persist a staged ``ChunkedHybrid`` under ``directory``."""
    import json
    import os

    from photon_ml_tpu.utils.diskio import atomic_write, file_crc32

    os.makedirs(directory, exist_ok=True)
    for i, ch in enumerate(chunked.chunks):
        path = os.path.join(directory, f"chunk_{i}.npz")
        arrays = {name: np.asarray(getattr(ch, name))
                  for name in _CHUNK_FIELDS
                  if getattr(ch, name) is not None}
        atomic_write(path, lambda f, _a=arrays: np.savez(f, **_a))
        crc = file_crc32(path)
        # Injected bit rot lands AFTER the checksum was taken over the
        # good bytes — the torn-page/bit-rot shape the CRC must catch.
        flt.corrupt_file(flt.sites.STREAM_QUANTIZE, path, index=i)
        marker = json.dumps({
            "version": CHUNK_STORE_VERSION, "crc": crc,
            "fields": sorted(arrays),
            "num_features": int(ch.num_features)}).encode()
        atomic_write(os.path.join(directory, f"chunk_{i}.ok"),
                     lambda f, _m=marker: f.write(_m))
    meta = json.dumps({
        "version": CHUNK_STORE_VERSION, "num_rows": int(chunked.num_rows),
        "chunk_rows": int(chunked.chunk_rows),
        "num_chunks": int(chunked.num_chunks),
        "dtype": chunk_dtype(chunked.chunks[0])}).encode()
    atomic_write(os.path.join(directory, "meta.json"),
                 lambda f: f.write(meta))


def _load_stored_chunk(directory: str, i: int) -> Optional[CanonicalChunk]:
    """One committed chunk, or None on any miss (no marker, version
    skew, CRC mismatch, unreadable npz) — the caller degrades to a
    single-chunk re-stage."""
    import json
    import os

    from photon_ml_tpu.utils.diskio import file_crc32

    path = os.path.join(directory, f"chunk_{i}.npz")
    try:
        with open(os.path.join(directory, f"chunk_{i}.ok")) as f:
            marker = json.load(f)
        if marker.get("version") != CHUNK_STORE_VERSION:
            return None
        got = file_crc32(path)
        if got != int(marker["crc"]):
            logger.warning(
                "chunk store entry %s is corrupt (crc %08x != committed "
                "%08x) — re-staging exactly this chunk", path, got,
                int(marker["crc"]))
            return None
        with np.load(path, allow_pickle=False) as z:
            arrays = {name: z[name] for name in marker["fields"]}
        return CanonicalChunk(
            num_features=int(marker["num_features"]),
            **{name: arrays.get(name) for name in _CHUNK_FIELDS})
    except Exception:
        logger.debug("chunk store miss for chunk %d under %s",
                     i, directory, exc_info=True)
        return None


def load_chunked(directory: str, rebuild=None) -> ChunkedHybrid:
    """Load a persisted chunk stream; a chunk that fails its CRC (or is
    missing) re-stages through ``rebuild(i) -> CanonicalChunk`` —
    exactly that chunk, bit-identical to a fresh staging pass — or
    raises :class:`ChunkStoreError` when no hook was given."""
    import json
    import os

    with open(os.path.join(directory, "meta.json")) as f:
        meta = json.load(f)
    if meta.get("version") != CHUNK_STORE_VERSION:
        raise ChunkStoreError(
            f"chunk store {directory} is version {meta.get('version')}, "
            f"expected {CHUNK_STORE_VERSION}")
    chunks = []
    for i in range(int(meta["num_chunks"])):
        ch = _load_stored_chunk(directory, i)
        if ch is None:
            if rebuild is None:
                raise ChunkStoreError(
                    f"chunk {i} of {directory} is missing or corrupt and "
                    f"no rebuild hook was provided")
            ch = rebuild(i)
        chunks.append(ch)
    return ChunkedHybrid(chunks=tuple(chunks),
                         num_rows=int(meta["num_rows"]),
                         chunk_rows=int(meta["chunk_rows"]))


def make_value_and_gradient(
    loss: PointwiseLoss,
    chunked: ChunkedHybrid,
    prefetch_depth: int = 2,
    pinned=(),
) -> Callable[[Array, Optional[Array]], tuple[Array, Array]]:
    """Streamed Σ-over-chunks (value, gradient) in original column space.

    The returned callable is HOST-DRIVEN (a Python loop dispatching one
    jitted pass per chunk) — it cannot be traced into an outer jit; pair
    it with the host-driven optimizer in ``optim/streaming.py``.
    ``offsets``, when given, is the full (padded_n,) device array of
    per-row offsets (coordinate-descent residuals); None uses the offsets
    staged in each chunk. ``pinned`` (from :func:`pin_chunks`) skips the
    host→device transfer for the leading chunks.
    """
    kernel = _chunk_value_grad(loss, chunk_dtype(chunked.chunks[0]))

    def value_and_grad(w: Array, offsets: Optional[Array] = None):
        with obs.span("stream.pass", cat="stream", kind="value_grad",
                      chunks=chunked.num_chunks):
            return _vg_pass(w, offsets)

    def _vg_pass(w: Array, offsets: Optional[Array]):
        value = jnp.zeros((), jnp.float32)
        grad = jnp.zeros((chunked.dim,), jnp.float32)
        for i, ch in enumerate(_stream(chunked, prefetch_depth, pinned)):
            v, g = kernel(w, _offsets_for(chunked, offsets, i, ch), ch)
            value = value + v
            grad = grad + g
            # Barrier per chunk: the runtime holds every enqueued
            # program's scratch from ENQUEUE time, and a full unsynced
            # pass over the stream exhausts HBM at scale (measured: the
            # 100M-row run died on its first evaluation). The next
            # chunk's host→device copy is already in flight (_stream
            # prefetch), so the barrier costs one host-device round trip
            # per chunk against a transfer-bound pass.
            jax.block_until_ready(grad)
            _release(ch, i, pinned)
        # Lazily-freed transfer buffers accumulate across evaluations
        # (measured: the 100M-row run's host RSS climbed ~60 GB over 11
        # L-BFGS iterations until the OOM killer fired); one collection
        # per heavyweight pass keeps the pool bounded (gated on bytes —
        # see GC_STREAM_BYTES_FLOOR).
        _collect_after_pass(chunked)
        return value, grad

    return value_and_grad


def make_value_only(
    loss: PointwiseLoss,
    chunked: ChunkedHybrid,
    prefetch_depth: int = 2,
    pinned=(),
) -> Callable[[Array, Optional[Array]], Array]:
    """Streamed Σ-over-chunks VALUE in original column space — the
    line-search probe companion of :func:`make_value_and_gradient` (same
    streaming discipline: prefetch, per-chunk barrier, eager release)."""
    kernel = _chunk_value(loss, chunk_dtype(chunked.chunks[0]))

    def value_only(w: Array, offsets: Optional[Array] = None):
        with obs.span("stream.pass", cat="stream", kind="value_only",
                      chunks=chunked.num_chunks):
            return _v_pass(w, offsets)

    def _v_pass(w: Array, offsets: Optional[Array]):
        value = jnp.zeros((), jnp.float32)
        for i, ch in enumerate(_stream(chunked, prefetch_depth, pinned)):
            v = kernel(w, _offsets_for(chunked, offsets, i, ch), ch)
            value = value + v
            jax.block_until_ready(value)  # same enqueue-scratch barrier
            _release(ch, i, pinned)
        _collect_after_pass(chunked)
        return value

    return value_only


def _release(ch, i: int, pinned) -> None:
    """Drop a STREAMED chunk's device buffers eagerly — reference-count
    laziness is what let per-eval transfer buffers pile up on host."""
    if i < len(pinned):
        return
    _delete_chunk(ch)


def margins_chunked(
    chunked: ChunkedHybrid,
    w: Array,
    offsets: Optional[Array] = None,
    prefetch_depth: int = 2,
    pinned=(),
) -> Array:
    """(num_rows,) margins (wᵀx + offset), streamed; pad rows dropped."""
    with obs.span("stream.pass", cat="stream", kind="margins",
                  chunks=chunked.num_chunks):
        return _margins_pass(chunked, w, offsets, prefetch_depth, pinned)


def _margins_pass(chunked, w, offsets, prefetch_depth, pinned) -> Array:
    parts = []
    for i, ch in enumerate(_stream(chunked, prefetch_depth, pinned)):
        parts.append(_margins_kernel(
            w, _offsets_for(chunked, offsets, i, ch), ch))
        jax.block_until_ready(parts[-1])  # same enqueue-scratch barrier
        _release(ch, i, pinned)
    _collect_after_pass(chunked)
    z = jnp.concatenate(parts)
    return z[:chunked.num_rows]


# ------------------------------------------------------- sharded streaming
#
# The multi-chip composition (ROADMAP item 1, the reference's
# ``treeAggregate`` shape): chunk ranges partition over the mesh's
# ``data`` axis, each device streams ITS range with the same
# double-buffered prefetch + per-round barrier discipline as the
# single-device path, and per-device partial (value, gradient) merge via
# ``psum`` over ICI/DCN — the host-driven L-BFGS in optim/streaming.py
# sees one global objective exactly as photon-api's Breeze driver loop
# sees one treeAggregate result. Snap ML's local-compute/global-merge
# hierarchy and Trofimov–Genkin's distributed GLM descent (PAPERS.md)
# are the same decomposition.


def shard_chunk_ranges(num_chunks: int, num_devices: int
                       ) -> list[tuple[int, int]]:
    """Contiguous, balanced [lo, hi) chunk ranges, one per device.

    Contiguous (not round-robin) so each device's offsets slice is one
    block of the global (padded_n,) residual array and the short padded
    tail chunk stays on the LAST device — the pad-rows-at-stream-tail
    invariant holds per device.

    A pure function of ``(num_chunks, num_devices)`` — nothing about
    the assignment is persisted anywhere. That is the elastic-resume
    contract (docs/STREAMING.md): a StreamingStateStore snapshot
    carries only device-count-free driver state, and the ranges are
    re-derived HERE on every construction, so a fit checkpointed at D
    devices resumes at D′ ≠ D with re-sharded ranges."""
    if num_devices < 1:
        raise ValueError(f"num_devices must be >= 1, got {num_devices}")
    base, rem = divmod(num_chunks, num_devices)
    ranges = []
    lo = 0
    for k in range(num_devices):
        hi = lo + base + (1 if k < rem else 0)
        ranges.append((lo, hi))
        lo = hi
    return ranges


def data_axis_devices(mesh) -> list:
    """The mesh's devices along ``data`` (streaming does not feature-
    shard, so a model axis > 1 is a config error, not a silent drop)."""
    from photon_ml_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

    if mesh.shape[MODEL_AXIS] != 1:
        raise ValueError(
            f"streaming shards rows over the '{DATA_AXIS}' axis only; "
            f"mesh has {MODEL_AXIS}={mesh.shape[MODEL_AXIS]} (feature-"
            f"sharded streaming is not supported — use the device-"
            f"resident feature-sharded path)")
    return list(np.asarray(mesh.devices).reshape(-1))


_MERGE_FNS: dict = {}


def _merge_fn(mesh):
    """shard_map psum merge of per-device partials: (D,) values and
    (D, d) gradients sharded over ``data`` → replicated global sums.
    This IS the treeAggregate reduction, riding ICI within a slice and
    DCN across slices; cached per mesh (one compile per topology)."""
    import functools

    from jax.sharding import PartitionSpec as P

    from photon_ml_tpu.parallel.mesh import DATA_AXIS, shard_map

    cached = _MERGE_FNS.get(mesh)
    if cached is not None:
        _count_kernel_hit("stream_psum_merge", "float32")
        return cached
    # The merge reduces f32 partials regardless of chunk storage dtype.
    _count_kernel_build("stream_psum_merge", "float32")

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(DATA_AXIS), P(DATA_AXIS, None)),
        out_specs=(P(), P()))
    def _merge(v, g):
        return (jax.lax.psum(jnp.sum(v), DATA_AXIS),
                jax.lax.psum(jnp.sum(g, axis=0), DATA_AXIS))

    # jit so the merge compiles once per (mesh, shape) instead of
    # re-tracing on every objective evaluation.
    merged = jax.jit(_merge)
    _MERGE_FNS[mesh] = merged
    return merged


class ShardedChunkStream:
    """Multi-device streamed aggregates over one ``ChunkedHybrid``.

    Each data-axis device owns a contiguous chunk range and streams it
    through its own prefetch queue; every objective evaluation runs the
    per-chunk kernel round-robin across devices (so D transfers/computes
    are in flight at once) with ONE dispatch barrier per round — the
    multi-device analogue of the single-device per-chunk barrier, holding
    at most D chunks of enqueue scratch. Per-device partials merge via
    the psum program of :func:`_merge_fn`.

    ``pin_device_chunks`` pins that many LEADING chunks of each device's
    range on that device (the per-device share of the spare-HBM budget).

    A 1-device mesh reproduces the single-device path bit-for-bit: same
    kernel, same chunk order, same accumulation order; the psum over a
    singleton axis is the identity.
    """

    def __init__(self, chunked: ChunkedHybrid, mesh,
                 prefetch_depth: int = 2, pin_device_chunks: int = 0):
        if prefetch_depth < 1:
            raise ValueError(
                f"prefetch_depth must be >= 1, got {prefetch_depth}")
        self.chunked = chunked
        self.mesh = mesh
        self.devices = data_axis_devices(mesh)
        self.ranges = shard_chunk_ranges(chunked.num_chunks,
                                         len(self.devices))
        self.prefetch_depth = prefetch_depth
        # Per-device pinned leading chunks (resident once, streamed never).
        self._pinned = []
        for dev, (lo, hi) in zip(self.devices, self.ranges):
            n_pin = min(max(0, pin_device_chunks), hi - lo)
            self._pinned.append(tuple(
                jax.device_put(chunked.chunks[lo + j], dev)
                for j in range(n_pin)))
        # Offsets split cache: id(offsets) → per-device offset blocks.
        # train_model calls the objective many times with the SAME
        # residual array; splitting once per residual keeps the per-pass
        # transfer at exactly the chunk payloads.
        self._off_cache: tuple = (None, None)

    @property
    def num_devices(self) -> int:
        return len(self.devices)

    # -- per-device plumbing ----------------------------------------------

    def _stream_range(self, k: int):
        """Yield (global chunk index, device-resident chunk, streamed?)
        for device k's range, prefetch_depth transfers ahead."""
        import collections

        lo, hi = self.ranges[k]
        dev = self.devices[k]
        pinned = self._pinned[k]
        for j, ch in enumerate(pinned):
            yield lo + j, ch, False
        q: collections.deque = collections.deque()
        it = iter(range(lo + len(pinned), hi))
        try:
            for _ in range(self.prefetch_depth):
                i = next(it)
                q.append((i, _transfer(self.chunked.chunks[i], i, dev)))
        except StopIteration:
            pass
        while q:
            i, ready = q.popleft()
            try:
                j = next(it)
                q.append((j, _transfer(self.chunked.chunks[j], j, dev)))
            except StopIteration:
                pass
            yield i, ready, True

    def _offsets_by_device(self, offsets: Optional[Array]):
        """Split the full (padded_n,) residual array into per-device
        blocks, placed once (cached on the array's identity)."""
        if offsets is None:
            return None
        key, cached = self._off_cache
        if key is not None and key is offsets:
            return cached
        rows = self.chunked.chunk_rows
        host = np.asarray(offsets, np.float32)
        per_dev = []
        for dev, (lo, hi) in zip(self.devices, self.ranges):
            block = host[lo * rows: hi * rows]
            per_dev.append(jax.device_put(jnp.asarray(block), dev)
                           if block.size else None)
        self._off_cache = (offsets, per_dev)
        return per_dev

    def _chunk_offsets(self, per_dev, k: int, i: int, ch: CanonicalChunk):
        if per_dev is None:
            return ch.offsets if isinstance(ch.offsets, jax.Array) \
                else jnp.asarray(ch.offsets)
        lo = self.ranges[k][0]
        return jax.lax.dynamic_slice_in_dim(
            per_dev[k], (i - lo) * self.chunked.chunk_rows,
            self.chunked.chunk_rows, 0)

    def _round_robin(self, w: Array, offsets: Optional[Array],
                     dispatch, accs):
        """Drive every device's stream one chunk per round; barrier per
        round on each touched accumulator, then release streamed chunks
        (the enqueue-scratch bound, held at ≤ D in-flight chunks)."""
        per_dev = self._offsets_by_device(offsets)
        w = jnp.asarray(w, jnp.float32)
        w_dev = [jax.device_put(w, dev) for dev in self.devices]
        streams = [self._stream_range(k) for k in range(self.num_devices)]
        live = [True] * self.num_devices
        while any(live):
            touched = []
            for k in range(self.num_devices):
                if not live[k]:
                    continue
                item = next(streams[k], None)
                if item is None:
                    live[k] = False
                    continue
                i, ch, streamed = item
                off = self._chunk_offsets(per_dev, k, i, ch)
                dispatch(k, w_dev[k], off, ch)
                touched.append((ch, streamed))
            if touched:
                # One barrier per round: the runtime holds every enqueued
                # program's scratch from ENQUEUE time (the 100M lesson) —
                # blocking on each touched device's accumulator caps the
                # un-executed queue at one chunk per device.
                for k in range(self.num_devices):
                    if accs[k] is not None:
                        jax.block_until_ready(accs[k])
                for ch, streamed in touched:
                    if streamed:
                        _delete_chunk(ch)
        # The single-device transfer-buffer lesson, per pass (gated on
        # bytes: heavyweight streams collect, test-scale ones skip).
        _collect_after_pass(self.chunked)

    # -- streamed aggregates ----------------------------------------------

    def value_and_gradient(self, loss: PointwiseLoss):
        """(w, offsets) → replicated global (value, gradient): each
        device streams its range, partials psum-merge (treeAggregate)."""
        kernel = _chunk_value_grad(loss,
                                   chunk_dtype(self.chunked.chunks[0]))
        d = self.chunked.dim
        merge = _merge_fn(self.mesh)

        def vg(w: Array, offsets: Optional[Array] = None):
            with obs.span("stream.pass", cat="stream", kind="value_grad",
                          chunks=self.chunked.num_chunks,
                          devices=self.num_devices):
                return _vg(w, offsets)

        def _vg(w: Array, offsets: Optional[Array]):
            vals = [jax.device_put(jnp.zeros((1,), jnp.float32), dev)
                    for dev in self.devices]
            grads = [jax.device_put(jnp.zeros((1, d), jnp.float32), dev)
                     for dev in self.devices]

            def dispatch(k, w_k, off, ch):
                v, g = kernel(w_k, off, ch)
                vals[k] = vals[k] + v
                grads[k] = grads[k] + g

            self._round_robin(w, offsets, dispatch, grads)
            with obs.span("stream.psum_merge", cat="compute",
                          devices=self.num_devices):
                value, grad = merge(self._global(vals, (1,)),
                                    self._global(grads, (1, d)))
            # The replicated results re-commit to the lead device so the
            # driver loop's jitted helpers (single-device history math)
            # can mix them with their own state freely.
            return (jax.device_put(value, self.devices[0]),
                    jax.device_put(grad, self.devices[0]))

        return vg

    def value_only(self, loss: PointwiseLoss):
        """(w, offsets) → global value — the Armijo-probe pass."""
        kernel = _chunk_value(loss, chunk_dtype(self.chunked.chunks[0]))
        merge = _merge_fn(self.mesh)
        d = self.chunked.dim

        def v_fn(w: Array, offsets: Optional[Array] = None):
            with obs.span("stream.pass", cat="stream", kind="value_only",
                          chunks=self.chunked.num_chunks,
                          devices=self.num_devices):
                return _v(w, offsets)

        def _v(w: Array, offsets: Optional[Array]):
            vals = [jax.device_put(jnp.zeros((1,), jnp.float32), dev)
                    for dev in self.devices]
            zeros = [jax.device_put(jnp.zeros((1, 1), jnp.float32), dev)
                     for dev in self.devices]

            def dispatch(k, w_k, off, ch):
                vals[k] = vals[k] + kernel(w_k, off, ch)

            self._round_robin(w, offsets, dispatch, vals)
            with obs.span("stream.psum_merge", cat="compute",
                          devices=self.num_devices):
                value, _ = merge(self._global(vals, (1,)),
                                 self._global(zeros, (1, 1)))
            return jax.device_put(value, self.devices[0])

        return v_fn

    def margins(self, w: Array, offsets: Optional[Array] = None) -> Array:
        """(num_rows,) margins in global row order (pad tail dropped).
        Parts come home per chunk (scoring runs once per coordinate
        update; the pass is transfer-bound either way)."""
        with obs.span("stream.pass", cat="stream", kind="margins",
                      chunks=self.chunked.num_chunks,
                      devices=self.num_devices):
            return self._margins_pass(w, offsets)

    def _margins_pass(self, w: Array, offsets: Optional[Array]) -> Array:
        parts: dict[int, np.ndarray] = {}
        per_dev = self._offsets_by_device(offsets)
        w32 = jnp.asarray(w, jnp.float32)
        w_dev = [jax.device_put(w32, dev) for dev in self.devices]
        streams = [self._stream_range(k) for k in range(self.num_devices)]
        live = [True] * self.num_devices
        while any(live):
            released = []
            for k in range(self.num_devices):
                if not live[k]:
                    continue
                item = next(streams[k], None)
                if item is None:
                    live[k] = False
                    continue
                i, ch, streamed = item
                off = self._chunk_offsets(per_dev, k, i, ch)
                z = _margins_kernel(w_dev[k], off, ch)
                jax.block_until_ready(z)  # per-chunk barrier + host copy
                # pml: allow[PML001] score-pass reassembly is BY-DESIGN a per-chunk host copy (global row order spans devices); scoring runs once per coordinate update on a transfer-bound pass
                parts[i] = np.asarray(z)
                if streamed:
                    released.append(ch)
            for ch in released:
                _delete_chunk(ch)
        _collect_after_pass(self.chunked)
        z = np.concatenate([parts[i] for i in range(len(parts))])
        return jnp.asarray(z[:self.chunked.num_rows])

    def _global(self, per_dev: list, local_shape: tuple):
        """Assemble per-device partials into one data-sharded global
        array (the psum merge's input layout)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from photon_ml_tpu.parallel.mesh import DATA_AXIS

        D = self.num_devices
        shape = (D * local_shape[0],) + local_shape[1:]
        sharding = NamedSharding(
            self.mesh, P(DATA_AXIS, *(None,) * (len(local_shape) - 1)))
        return jax.make_array_from_single_device_arrays(
            shape, sharding, per_dev)
