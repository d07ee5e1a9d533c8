"""Hybrid hot-dense / cold-class sparse GLM aggregates (the Criteo path).

Reference parity: the same ``ValueAndGradientAggregator`` /
``HessianVectorAggregator`` contracts as ops/sparse_aggregators.py — but
restructured around how a TPU actually moves data.

Why: measured on one v5e chip, XLA's random 4M-element gather runs at
~0.14 Gelem/s and its scatter-add at ~0.16 G-updates/s (at a deployment's
size the same: 23.6M cold entries cross twice an evaluation in 0.32 s, 0.15
G entries/s, and a plain ELL pass over 97.5M entries takes 0.75 s for its
gather and 0.67 s for its segment-sum; PERF.md section 6, PRs 29–30), and a
Mosaic (8, 128)-window vector shuffle tops out at ~0.84 Gelem/s — so ANY exact
ELL step at d=1e6 pays two ~26 ms random crossings (expand w→entries,
reduce entries→gradient) and lands near 60 ms regardless of formulation
(plain scatter, pre-sorted segment-sum, one-hot matmul tiles, and
butterfly-routed permutations all measured within 1.1× of each other;
see docs/PARITY.md "sparse wall" notes). The only real lever is moving
fewer elements through the random path.

CTR feature spaces are Zipf-distributed: on the benchmark's zipf(1.3)
synthetic, the hottest ~1–2k of 1M columns carry ~85% of all nonzeros
(at 2M rows of 39 hashed click-log fields, where half of a v5e's memory
holds 1024 columns, they carry 76%; 512 columns carry 70%).
The hybrid split exploits that:

- **Hot columns** (count ≥ ``hot_threshold``, at most ``max_hot``) are
  densified into an (n, k) matrix: margins and gradient contributions are
  plain MXU matmuls (X_hot @ w, X_hotᵀ r) — the 85% of entries ride the
  365 M-samples/s dense path, with the multiply-by-zero waste costing
  bandwidth, not random access. Memory bounds that block, not time (PERF.md
  section 7 row 17), and a click log's features are one-hot fields scaled a
  row: a float32 cell holds one of {0, s, 2s}. So where every column
  planned is **count-exact** (``_count_hot``: all its live values one
  float32 value s_c, no cell named over 127 times, float32(m) · s_c the
  float32 sum the cell would have held) the block is the **count block**:
  (n, k₁) int8 counts beside ``hot_scale`` (k₁,) float32, k₁ planned from
  the same bytes at a byte a cell and 4 a column, four times the columns
  (4096 where 1024 fit at 2M click-log rows, 1024 where 256 fit at 3M rows
  beside a 10 GB solve). float32(count) · scale IS the float32 cell, and
  both products stay float32: Σ_c count · (scale_c · w_c) and scale_c ·
  Σ_i count · r_i, the convert fused into the reduce, no float32 copy
  anywhere. The rule is the shard's: no option reaches it, a shard that
  fails it (one real-valued column among those planned; bf16 storage; the
  data-sharded layout) keeps its values and the programs it had, and one
  shard holds one or the other, never both. It is NOT the streamed path's
  ``feature_dtype="int8"`` (ops/streaming_sparse.py), which quantises real
  values to 8 bits symmetric a column and is lossy: nothing here rounds.
- **Cold columns** are relabeled into count-descending order (a static
  permutation of the feature space — the GLM objective is permutation-
  equivariant, so the solve happens in permuted space and maps back once
  per fit) and their entries stored in power-of-two classes of **chunks**:
  a column of count c is split along the binary digits of c, one chunk of
  exactly 2^k entries for every set bit k, and class 2^k holds, each as one
  full row of a (C, L = 2^k) block, the chunks of every column whose count
  has bit k set. No slot is padding: what crosses the random path is the
  non-zeros and nothing else (rounding a column up to one power of two
  padded 29% of the slots of a 2M-row click log; PERF.md section 6, PR
  30). Counts descend, so within a class the chunks of the larger columns
  (**remainder chunks**, which name their column through ``chunk_cols``)
  come first and the columns whose TOP bit is k follow as one contiguous
  run of the permuted space (``class_starts``), served by slice:
  * margins: w per chunk (one small gather for the remainder chunks, a
    contiguous slice for the rest), broadcast over the chunk's entries,
    one scatter-add of products by row — the first crossing, now ~15–30%
    of the volume;
  * gradient: one gather r[rowids] (second crossing, same reduced
    volume), then row-sums per class: the top chunks' sums are the
    permuted gradient's contiguous slices, the remainder chunks' are added
    into their columns by one small scatter-add.

The two halves carry the scopes ``fe.hot`` (the dense block's two passes)
and ``fe.cold`` (the scatter-add of margins, the gather of the gradient)
in a profiler trace (docs/OBSERVABILITY.md).

Every evaluation pays both crossings, so L-BFGS's line search does not
evaluate: ``products`` crosses once for the direction's margins,
``row_terms`` reads a trial's value and slope off rows alone, and
``row_gradient`` crosses once at the point accepted
(parallel/sparse_problem.py ``_hybrid_line``).

The data-sharded layout (``HybridShards``) keeps one padded row per column
and class, because a column's count differs by shard while the class shapes
are shared: it has no remainder chunks (chunk i of a class is column
``start + i``), and its pad slots carry rowid == n (a zero sentinel lane)
and value 0, so they are inert in every pass without masks. All layout
arrays are static (computed once at staging from the CSR/ELL structure);
per optimizer iteration only w changes.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.data.sparse import SparseBatch
from photon_ml_tpu.ops.losses import PointwiseLoss

Array = jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class HybridSparseBatch:
    """Hot-dense + cold-class layout of one sparse example batch.

    The feature space is PERMUTED: new column order is
    [hot columns (count desc) | cold present columns (count desc) |
    absent columns]. ``perm`` maps new → original column ids;
    ``inv_perm`` maps original → new. Coefficient vectors seen by the
    ops here live in the permuted space.
    """

    X_hot: Array  # (n, k) dense hot block (k may be 0)
    # Per class: (C, L) int32 row ids and f32 values, one chunk of L entries
    # a row, every slot live; both (L, C) where L < 128. (A shard of the
    # data-sharded layout: one row a column, pad slots rowid == n, value 0.)
    cold_rowids: tuple[Array, ...]
    cold_vals: tuple[Array, ...]
    # The cold column (hot block excluded) of every remainder chunk, class
    # by class in the classes' row order: (sum(class_rems),) int32.
    chunk_cols: Array
    labels: Array  # (n,)
    weights: Array  # (n,)
    offsets: Array  # (n,)
    perm: Array  # (d,) int32: new col -> original col
    inv_perm: Array  # (d,) int32: original col -> new col
    num_features: int = dataclasses.field(metadata=dict(static=True))
    num_hot: int = dataclasses.field(metadata=dict(static=True))
    # Per class: the first permuted column id (hot block excluded) of the
    # run of columns whose top chunk it holds: the rows past its
    # ``class_rems`` remainder chunks, in column order.
    class_starts: tuple[int, ...] = dataclasses.field(
        metadata=dict(static=True))
    # Per class: its slot count L. A class of L < 128 is held lane-major,
    # (L, C), so that its minor dimension is the long one (see
    # ``_class_block``).
    class_lens: tuple[int, ...] = dataclasses.field(
        metadata=dict(static=True))
    # Per class: the rows at its head that are remainder chunks (of columns
    # whose count has a higher bit set too); ``chunk_cols`` names theirs.
    class_rems: tuple[int, ...] = dataclasses.field(
        metadata=dict(static=True))
    # Live entries the hot block and the cold classes serve (the run
    # ledger's ``fe_layout`` row).
    entries: tuple[int, int] = dataclasses.field(
        default=(0, 0), metadata=dict(static=True))
    # Columns some row touches: the head of the permuted space (the absent
    # ones follow, in column order). 0 where it was not counted.
    num_touched: int = dataclasses.field(
        default=0, metadata=dict(static=True))
    # The block's plan (the run ledger's ``fe_layout`` row): the columns
    # value cells would have held in the same bytes, and how many of the
    # columns planned at a byte a cell, from the first, are count-exact
    # (``_count_hot``; 0 where no count block was planned: bf16 storage).
    hot_plan: tuple[int, int] = dataclasses.field(
        default=(0, 0), metadata=dict(static=True))
    # (k,) float32 where ``X_hot`` is the COUNT block: int8 counts of how
    # often a row names a column, every live value of column c being the
    # one float32 ``hot_scale[c]``; the cell's value is float32(count) *
    # scale, bit for bit. None (no leaf) where ``X_hot`` holds values.
    hot_scale: Optional[Array] = None

    @property
    def num_rows(self) -> int:
        return self.X_hot.shape[0] if self.num_hot else self.labels.shape[0]

    @property
    def dim(self) -> int:
        return self.num_features


def hot_storage(layout) -> str:
    """What a cell of a layout's ``X_hot`` is: ``count8`` (the exact count
    block, which only the one-shard layout builds), ``float32`` or
    ``bfloat16`` (values)."""
    if getattr(layout, "hot_scale", None) is not None:
        return "count8"
    return np.dtype(layout.X_hot.dtype).name


# Bytes per element of each hot-block storage dtype. int8 (the streamed
# path's) additionally carries one f32 scale per column (the symmetric-
# quantization dequant vector), so the HBM plan charges it per column — at
# streaming chunk_rows the 4 bytes per column are noise, but a plan that
# ignores them would overshoot a tight budget on many-column/few-row configs.
FEATURE_ITEMSIZE = {"float32": 4, "bfloat16": 2, "int8": 1}
_SCALE_BYTES_PER_COLUMN = {"float32": 0, "bfloat16": 0, "int8": 4}
_LANES = 128  # a wide block keeps whole lane tiles: the device pads the rest
_HOT_BLOCK_ROWS = 1 << 17  # rows densified at a time on the host


def feature_dtype_name(feature_dtype) -> str:
    """Canonical name of a hot-block storage dtype spec (string, numpy/jax
    dtype, or None = float32). Unknown dtypes raise — a silent f32
    fallback would quietly quadruple a stream someone sized for int8."""
    if feature_dtype is None:
        return "float32"
    if isinstance(feature_dtype, str):
        name = feature_dtype.lower()
    else:
        try:
            name = np.dtype(feature_dtype).name
        except TypeError:
            name = str(feature_dtype)
    if name not in FEATURE_ITEMSIZE:
        raise ValueError(
            f"unsupported streaming feature_dtype {feature_dtype!r}; "
            f"expected one of {sorted(FEATURE_ITEMSIZE)}")
    return name


def plan_num_hot(chunk_rows: int, hot_block_bytes: int,
                 feature_dtype) -> int:
    """Hot-block width that fits the byte budget: at scale the binding
    constraint is HBM (block bytes = rows × H × itemsize, plus the
    per-column scale under int8), not the throughput-optimal split of
    ``_default_hot_threshold``. One planner for the streamed chunks
    (ops/streaming_sparse.py) and the resident block (``plan_resident_hot``).
    """
    name = feature_dtype_name(feature_dtype)
    per_column = (chunk_rows * FEATURE_ITEMSIZE[name]
                  + _SCALE_BYTES_PER_COLUMN[name])
    return max(8, int(hot_block_bytes) // per_column)


def plan_resident_hot(counts: np.ndarray, rows_per_device: int,
                      feature_dtype=jnp.float32,
                      hot_threshold: Optional[int] = None,
                      max_hot: int = 4096,
                      hot_block_bytes: Optional[int] = None) -> int:
    """Columns the resident layouts densify: those of count ≥
    ``hot_threshold`` (the throughput-optimal split, at most ``max_hot``,
    which is what that split was swept under), as far as
    ``hot_block_bytes`` of one device hold them at ``rows_per_device``
    rows. The caller that owns the device gives the bytes
    (game/coordinates/sparse_fixed.py); without them only the two counts
    decide. A block wider than a lane tile keeps whole tiles, whichever of
    the three bound it: the device pads the rest of a tile anyway, and a
    pass over a ragged width can cost twice its bytes (2M rows of int8
    counts on a v5e: X w over 2,277 columns 11.3 ms, over 2,282 and over
    2,176 5.9 ms, the other pass 5.8 ms at every width; PERF.md section 6,
    PR 35), which the up to 127 columns it would have held do not repay."""
    if hot_threshold is None:
        hot_threshold = _default_hot_threshold(rows_per_device,
                                               feature_dtype)
    k = int(min(max_hot, (np.asarray(counts) >= hot_threshold).sum()))
    if hot_block_bytes is not None:
        k = min(k, plan_num_hot(max(rows_per_device, 1), hot_block_bytes,
                                feature_dtype))
    return k - k % _LANES if k > _LANES else k


def _class_block(block: np.ndarray, L: int) -> np.ndarray:
    """A cold class's (..., C, L) block as it is held. The device tiles the
    two minor dimensions to (8, 128): a class of many columns with 1 to 64
    slots each would be padded up to 128-fold in memory, and flattening it
    costs the compiler minutes (88 s for the sixteen classes of a 2M-row
    click log, 5 s without the eight narrow ones). Such a class is held
    lane-major, (..., L, C): the long dimension is the minor one."""
    if L >= _LANES:
        return block
    return np.ascontiguousarray(np.swapaxes(block, -1, -2))


def _default_hot_threshold(n: int, feature_dtype) -> int:
    """Dtype-aware hot/cold split point (see build_hybrid docstring): the
    f32 dense block pays 2× the bytes, so fewer columns should densify."""
    return max(8, n // (4096 if feature_dtype == jnp.bfloat16 else 2048))


def build_hybrid(
    batch: SparseBatch,
    hot_threshold: Optional[int] = None,
    max_hot: int = 4096,
    feature_dtype=jnp.float32,
    device: bool = True,
    hot_block_bytes: Optional[int] = None,
) -> HybridSparseBatch:
    """Stage an ELL SparseBatch into the hybrid layout (host-side, once).

    ``hot_threshold``: columns with at least this many nonzeros densify.
    The default is DTYPE-DEPENDENT (swept on one v5e chip, zipf(1.3)
    bench config, 2026-07-31): under f32 the dense block's bandwidth cost
    dominates, so the optimum sits at max(8, n/2048) (~1.8k hot columns,
    16.0 M samples/s vs 12.0 at n/4096); under bf16 the block streams at
    half the bytes and the optimum flattens across n/4096–n/8192 (~18.8 M
    samples/s) — n/4096 is kept. That split was swept at n=131072, where
    ``max_hot`` columns are ~2 GB; at a deployment's rows the block is
    sized from BYTES (``plan_resident_hot``: ``hot_block_bytes``, which
    the coordinate derives from what its mesh's device has free) and the
    columns past it stay cold, so neither the host nor the device ever
    holds more than that. There memory binds, not time: at 2M click-log
    rows the 1024th column is 0.3% non-zero and still worth four times its
    read (PERF.md section 7 row 17).
    """
    indices = np.asarray(batch.indices)
    values = np.asarray(batch.values)
    n = indices.shape[0]
    d = int(batch.num_features)

    flat_col = indices.reshape(-1)
    flat_row = np.repeat(np.arange(n, dtype=np.int32),
                         indices.shape[1])
    flat_val = values.reshape(-1)
    live = (flat_col < d) & (flat_val != 0.0)
    counts = np.bincount(flat_col[live], minlength=d)

    # Permuted order: count-descending (stable → ties break on column id).
    order_desc = np.argsort(-counts, kind="stable").astype(np.int32)
    if hot_threshold is None:
        hot_threshold = _default_hot_threshold(n, feature_dtype)
    k = plan_resident_hot(counts, n, feature_dtype, hot_threshold, max_hot,
                          hot_block_bytes)

    inv_perm = np.empty(d, np.int32)
    inv_perm[order_desc] = np.arange(d, dtype=np.int32)

    # Permuted column of every entry; a dead one gets d, past every block.
    new_col = np.where(live, inv_perm[np.minimum(flat_col, d - 1)], d)
    slot_col = new_col.reshape(indices.shape)

    # The same counts and bytes planned at a byte a cell: where every one
    # of those columns is count-exact the block holds counts and a scale a
    # column, else values as planned above. One or the other, never both.
    X_hot = hot_scale = None
    hot_plan = (k, 0)
    if feature_dtype_name(feature_dtype) == "float32":
        k1 = plan_resident_hot(counts, n, "int8", hot_threshold, max_hot,
                               hot_block_bytes)
        X_hot, hot_scale, exact = _count_hot(slot_col, values, k1, n)
        hot_plan = (k, exact)
        if X_hot is not None:
            k = k1
    if X_hot is None:
        X_hot = _dense_hot(slot_col, values, k, n, feature_dtype)

    # Cold entries, column-contiguous in permuted order.
    cold_sel = live & (new_col >= k)
    c_new = new_col[cold_sel] - k
    c_row = flat_row[cold_sel]
    c_val = flat_val[cold_sel]
    order = np.argsort(c_new, kind="stable")
    c_row, c_val = c_row[order], c_val[order]
    cnts = counts[order_desc][k:]  # descending
    cnts = cnts[:int((cnts > 0).sum())].astype(np.int64)  # the present ones
    col_start = np.cumsum(cnts) - cnts

    # A column of count c gives one chunk of 2^b entries for every set bit
    # b of c: its entries p = 0..c-1 in runs from the top bit down, the
    # chunk of bit b starting where b and every bit under it are cleared
    # from c. Class 2^b holds a row for each column with bit b set, in
    # column order: counts descend, so the columns of count >= 2^(b+1)
    # (remainder chunks) come before the run whose top bit is b. Descending
    # class order == the permuted column layout, so the top chunks' sums
    # concatenate back in place.
    rowids_cls: list[np.ndarray] = []
    vals_cls: list[np.ndarray] = []
    rem_cols: list[np.ndarray] = []
    class_starts: list[int] = []
    class_lens: list[int] = []
    for b in reversed(range(int(cnts.max(initial=0)).bit_length())):
        cols = np.flatnonzero((cnts >> b) & 1)
        if not cols.size:
            continue
        L = 1 << b
        first = col_start[cols] + ((cnts[cols] >> (b + 1)) << (b + 1))
        slots = first[:, None] + np.arange(L)
        rowids_cls.append(_class_block(c_row[slots], L))
        vals_cls.append(_class_block(c_val[slots], L))
        start = int((cnts >= 2 * L).sum())  # the run of top bit b begins
        rem_cols.append(cols[cols < start].astype(np.int32))
        class_starts.append(start)
        class_lens.append(L)

    # device=False keeps the leaves as host numpy (a valid pytree): the
    # row-streaming path (ops/streaming_sparse.py) holds many chunks on
    # host and device_puts them per objective pass instead of pinning
    # them all in HBM.
    put = jnp.asarray if device else (lambda a: a)
    return HybridSparseBatch(
        X_hot=put(X_hot),
        cold_rowids=tuple(put(a) for a in rowids_cls),
        cold_vals=tuple(put(a) for a in vals_cls),
        chunk_cols=put(np.concatenate(rem_cols or [np.zeros(0, np.int32)])),
        hot_scale=None if hot_scale is None else put(hot_scale),
        labels=put(np.asarray(batch.labels)),
        weights=put(np.asarray(batch.weights)),
        offsets=put(np.asarray(batch.offsets)),
        perm=put(order_desc),
        inv_perm=put(inv_perm),
        num_features=d,
        num_hot=k,
        class_starts=tuple(class_starts),
        class_lens=tuple(class_lens),
        class_rems=tuple(c.size for c in rem_cols),
        entries=(int(counts[order_desc[:k]].sum()), int(c_row.size)),
        num_touched=max(k, int((counts > 0).sum())),
        hot_plan=hot_plan,
    )


def _row_blocks(n: int):
    """[a, b) row ranges of ``_HOT_BLOCK_ROWS`` over n rows."""
    for a in range(0, n, _HOT_BLOCK_ROWS):
        yield a, min(a + _HOT_BLOCK_ROWS, n)


def _hot_hits(new_col: np.ndarray, k: int, a: int, b: int):
    """The entries of rows [a, b) under column ``k``, a slot at a time:
    (slot, the rows of the range that hit, their columns). Within one slot
    a row appears once, so a fancy ``+=`` over a hit adds every entry."""
    for j in range(new_col.shape[1]):
        col = new_col[a:b, j]
        sel = np.flatnonzero(col < k)
        yield j, sel, col[sel]


def _dense_hot(new_col: np.ndarray, values: np.ndarray, k: int,
               rows_out: int, feature_dtype) -> np.ndarray:
    """The (rows_out, k) dense block of the entries whose permuted column
    is under ``k``, in its storage dtype (cast on the host: bf16 halves the
    host→device transfer). Filled slot by slot, so two slots of one row
    that meet in one column add up as they do on the cold side, and in row
    blocks, so the host holds the block itself and one f32 slice of it."""
    import ml_dtypes

    dtype = (ml_dtypes.bfloat16 if feature_dtype == jnp.bfloat16
             else np.float32)
    X = np.zeros((rows_out, k), dtype)
    if not k:
        return X
    in_place = dtype == np.float32
    for a, b in _row_blocks(new_col.shape[0]):
        blk = X[a:b] if in_place else np.zeros((b - a, k), np.float32)
        for j, sel, col in _hot_hits(new_col, k, a, b):
            blk[sel, col] += values[a:b, j][sel]
        if not in_place:
            X[a:b] = blk
    return X


_MAX_COUNT = 127  # what an int8 cell holds


def _count_hot(new_col: np.ndarray, values: np.ndarray, k: int,
               rows_out: int):
    """The COUNT block of the entries whose permuted column is under ``k``:
    ((rows_out, k) int8 counts, (k,) float32 scale, k), or (None, None, the
    first column that is not count-exact).

    A column is count-exact where all its live values are one float32 value
    s, no cell is named more than 127 times, and float32(m) * s equals the
    float32 cell ``_dense_hot`` would have summed for every multiplicity m
    met: true of m <= 2 always (s, then a doubling), tested against the
    sequential sum from 3 up. Then float32(count) * scale IS the float32
    block, and the same bytes hold four times the columns. Nothing is
    rounded: a shard with one real-valued column among these keeps its
    float32 block. (Not the streamed path's ``feature_dtype="int8"``,
    which quantises real values to 8 bits and is lossy; this block is never
    reached through ``feature_dtype``.)

    The values are tested first, in one pass over the entries under ``k``:
    any one value of each column written into a guess, every entry held to
    its column's guess; the counts are built only once that has passed."""
    if not k:
        return None, None, 0
    scale, exact = _one_valued(new_col, values, k)
    if exact < k:
        return None, None, exact

    X = np.zeros((rows_out, k), np.int8)
    guard = new_col.shape[1] > _MAX_COUNT  # else no cell can overflow
    for a, b in _row_blocks(new_col.shape[0]):
        blk = X[a:b]
        for _, sel, col in _hot_hits(new_col, k, a, b):
            if guard:
                full = col[blk[sel, col] == _MAX_COUNT]
                if full.size:
                    return None, None, int(full.min())
            blk[sel, col] += 1
        if blk.max(initial=0) > 2:
            inexact = _inexact_columns(blk, scale)
            if inexact.size:
                return None, None, int(inexact.min())
    return X, scale, k


def _one_valued(new_col: np.ndarray, values: np.ndarray,
                k: int) -> tuple[np.ndarray, int]:
    """((k,) float32: one live value of each column under ``k``, the first
    column some entry of which has another value, or k)."""
    cols = np.minimum(new_col, k).reshape(-1)  # k: every other entry
    vals = values.astype(np.float32, copy=False).reshape(-1)
    scale = np.zeros(k + 1, np.float32)
    scale[cols] = vals
    off = cols[(vals != scale[cols]) & (cols < k)]  # a NaN is off its own
    return scale[:k], int(off.min(initial=k))


def _inexact_columns(blk: np.ndarray, scale: np.ndarray) -> np.ndarray:
    """Columns of a count block's rows where float32(m) * scale is not the
    sequential float32 sum of m values, over the cells of m >= 3."""
    rows, col = np.nonzero(blk > 2)
    pairs = np.unique(col * (_MAX_COUNT + 1) + blk[rows, col])
    col, m = np.divmod(pairs, _MAX_COUNT + 1)
    s = scale[col]
    summed = np.zeros_like(s)
    for t in range(int(m.max(initial=0))):
        summed = np.where(t < m, summed + s, summed)
    return col[m.astype(np.float32) * s != summed]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class HybridShards:
    """Data-parallel stack of per-shard hybrid layouts (P3 composition).

    The single-shard hybrid layout above owns the whole batch; this is its
    multi-device composition: rows are padded to ``S * rows_per_shard``
    (padding rows carry weight 0) and split CONTIGUOUSLY into S shards,
    and every data array carries a leading shard axis that shards over the
    mesh's ``data`` axis. The feature-space permutation and the cold count
    classes are GLOBAL — computed from global column counts — so the
    permuted coefficient space (what the optimizer sees, replicated) is
    identical across shards, hot gradients psum like the dense
    data-parallel path, and each shard's cold entries reference LOCAL row
    ids (pad == rows_per_shard, the zero sentinel lane).

    A column that happens to have no nonzeros in some shard still owns its
    class row there (all pad lanes) — inert by the pad contract, so the
    data-axis psum over per-shard gradients is exact.
    """

    X_hot: Array  # (S, n_l, k) dense hot blocks
    cold_rowids: tuple[Array, ...]  # per class: (S, C, L) int32, pad == n_l
    cold_vals: tuple[Array, ...]  # per class: (S, C, L) f32, pad == 0
    labels: Array  # (S, n_l)
    weights: Array  # (S, n_l); padding rows weight 0
    offsets: Array  # (S, n_l)
    perm: Array  # (d,) int32: new col -> original col
    inv_perm: Array  # (d,) int32: original col -> new col
    num_features: int = dataclasses.field(metadata=dict(static=True))
    num_hot: int = dataclasses.field(metadata=dict(static=True))
    class_starts: tuple[int, ...] = dataclasses.field(
        metadata=dict(static=True))
    class_lens: tuple[int, ...] = dataclasses.field(
        metadata=dict(static=True))
    entries: tuple[int, int] = dataclasses.field(
        default=(0, 0), metadata=dict(static=True))

    @property
    def num_shards(self) -> int:
        return self.labels.shape[0]

    @property
    def rows_per_shard(self) -> int:
        return self.labels.shape[1]

    @property
    def num_rows(self) -> int:
        """Padded global row count (S * n_l) — flat score/offset length."""
        return self.labels.shape[0] * self.labels.shape[1]

    @property
    def dim(self) -> int:
        return self.num_features


def local_shard(shb: HybridShards, X_hot: Array,
                cold_rowids: tuple[Array, ...],
                cold_vals: tuple[Array, ...], labels: Array,
                weights: Array, offsets: Array) -> HybridSparseBatch:
    """One shard's block (leading axis 1, as shard_map yields it) as a
    HybridSparseBatch, so every aggregate above runs unchanged per shard.

    The perm fields are deliberately empty: the per-shard aggregates never
    touch them (permutation handling happens once, outside the shard_map).
    """
    empty = jnp.zeros((0,), jnp.int32)
    return HybridSparseBatch(
        X_hot=X_hot[0], cold_rowids=tuple(r[0] for r in cold_rowids),
        cold_vals=tuple(v[0] for v in cold_vals), chunk_cols=empty,
        labels=labels[0], weights=weights[0], offsets=offsets[0], perm=empty,
        inv_perm=empty, num_features=shb.num_features, num_hot=shb.num_hot,
        class_starts=shb.class_starts, class_lens=shb.class_lens,
        class_rems=(0,) * len(shb.class_lens))


def build_hybrid_shards(
    batch: SparseBatch,
    n_shards: int,
    hot_threshold: Optional[int] = None,
    max_hot: int = 4096,
    feature_dtype=jnp.float32,
    hot_block_bytes: Optional[int] = None,
) -> HybridShards:
    """Stage an ELL SparseBatch into S per-shard hybrid layouts (host-side,
    once). Same hot/cold policy as ``build_hybrid`` — global counts decide
    the hot set and the cold classes; only the ROWS split across shards.
    """
    indices = np.asarray(batch.indices)
    values = np.asarray(batch.values)
    n = indices.shape[0]
    d = int(batch.num_features)
    S = int(n_shards)
    n_l = -(-n // S)  # ceil: rows per shard
    n_pad = n_l * S
    if hot_threshold is None:
        hot_threshold = _default_hot_threshold(n, feature_dtype)

    flat_col = indices.reshape(-1)
    flat_row = np.repeat(np.arange(n, dtype=np.int64), indices.shape[1])
    flat_val = values.reshape(-1)
    live = (flat_col < d) & (flat_val != 0.0)
    counts = np.bincount(flat_col[live], minlength=d)

    order_desc = np.argsort(-counts, kind="stable").astype(np.int32)
    # A device holds n_l rows of the block, so its bytes are planned there.
    k = plan_resident_hot(counts, n_l, feature_dtype, hot_threshold,
                          max_hot, hot_block_bytes)
    inv_perm = np.empty(d, np.int32)
    inv_perm[order_desc] = np.arange(d, dtype=np.int32)

    # Hot blocks: one global dense block, then the contiguous row split.
    new_col = np.where(live, inv_perm[np.minimum(flat_col, d - 1)], d)
    X_hot = _dense_hot(new_col.reshape(indices.shape), values, k, n_pad,
                       feature_dtype).reshape(S, n_l, k)

    # Cold entries keyed by (shard, permuted column).
    cold_sel = live & (new_col >= k)
    c_new = (new_col[cold_sel] - k).astype(np.int64)
    c_row = flat_row[cold_sel]
    c_val = flat_val[cold_sel]
    c_shard = c_row // n_l
    c_local = (c_row - c_shard * n_l).astype(np.int32)

    cold_counts = counts[order_desc][k:]  # global, descending
    present = int((cold_counts > 0).sum())

    rowids_cls: list[np.ndarray] = []
    vals_cls: list[np.ndarray] = []
    class_starts: list[int] = []
    class_lens: list[int] = []
    if present:
        key = c_shard * present + c_new
        order = np.argsort(key, kind="stable")
        key_s = key[order]
        c_new_s = c_new[order]
        loc_s = c_local[order]
        val_s = c_val[order]
        grp_counts = np.bincount(key_s, minlength=S * present)
        grp_starts = (np.cumsum(grp_counts) - grp_counts).astype(np.int64)
        pos = np.arange(key_s.size, dtype=np.int64) - grp_starts[key_s]
        M = grp_counts.reshape(S, present)  # per-shard per-column counts

        # Classes by GLOBAL count (same as build_hybrid), so each class is
        # one contiguous run of the permuted space; the per-shard lane
        # width L fits the largest per-shard column count in the class.
        cls = np.ceil(np.log2(np.maximum(
            cold_counts[:present], 1))).astype(np.int64)
        cls_of_entry = cls[c_new_s]
        for kk in np.unique(cls)[::-1]:
            selc = np.flatnonzero(cls == kk)
            c0 = int(selc[0])
            C = selc.size
            Lmax = int(M[:, selc].max())
            L = 1 << max(0, int(np.ceil(np.log2(max(Lmax, 1)))))
            rp = np.full((S, C, L), n_l, np.int32)
            vp = np.zeros((S, C, L), np.float32)
            e = np.flatnonzero(cls_of_entry == kk)
            sh = key_s[e] // present
            co = c_new_s[e] - c0
            rp[sh, co, pos[e]] = loc_s[e]
            vp[sh, co, pos[e]] = val_s[e]
            rowids_cls.append(_class_block(rp, L))
            vals_cls.append(_class_block(vp, L))
            class_starts.append(c0)
            class_lens.append(L)

    def pad1(a):
        return np.concatenate(
            [np.asarray(a, np.float32), np.zeros(n_pad - n, np.float32)])

    # Leaves stay HOST numpy: materializing the global hot block on the
    # default device first would allocate the UNSHARDED array there (the
    # exact OOM this composition avoids) and transfer everything twice.
    # shard_hybrid (parallel/sparse_problem.py) device_puts each leaf
    # straight to its mesh sharding.
    return HybridShards(
        X_hot=X_hot,
        cold_rowids=tuple(rowids_cls),
        cold_vals=tuple(vals_cls),
        labels=pad1(batch.labels).reshape(S, n_l),
        weights=pad1(batch.weights).reshape(S, n_l),
        offsets=pad1(batch.offsets).reshape(S, n_l),
        perm=order_desc,
        inv_perm=inv_perm,
        num_features=d,
        num_hot=k,
        class_starts=tuple(class_starts),
        class_lens=tuple(class_lens),
        entries=(int(counts[order_desc[:k]].sum()), int(c_new.size)),
    )


def _touched_head(hb) -> int:
    """The touched columns' count where permuting them alone pays: where it
    was counted and most columns have no row. A gather of d coefficients
    costs a v5e 19-24 ns each, 1.0-1.3 s at 54.7M columns, three times a
    descent sweep, for a vector whose 52.2M absent columns are zeros
    (PERF.md section 6, PR 33); where nearly every column has a row (2**20
    hashed ones) there is nothing to save, and 0 keeps the one gather."""
    t = getattr(hb, "num_touched", 0)
    return t if 0 < 2 * t <= hb.num_features else 0


def to_permuted_space(hb, w: Array) -> Array:
    """Original-space (d,) vector → permuted space (once per fit).
    Accepts either layout (HybridSparseBatch or HybridShards). Where the
    absent columns are most of the space and all 0 in ``w`` (a model
    trained here under any regulariser, or from zeros), only the touched
    head is gathered; a vector with anything there takes the whole gather,
    as before: the same result either way."""
    t = _touched_head(hb)
    if not t:
        return w[hb.perm]
    head = w[hb.perm[:t]]
    return jax.lax.cond(
        jnp.count_nonzero(w) == jnp.count_nonzero(head),
        lambda: jnp.concatenate(
            [head, jnp.zeros((hb.num_features - t,), w.dtype)]),
        lambda: jnp.concatenate([head, w[hb.perm[t:]]]))


def to_original_space(hb, w_perm: Array) -> Array:
    """Permuted-space (d,) vector → original space (once per fit).
    Accepts either layout (HybridSparseBatch or HybridShards); the touched
    head alone is scattered where ``to_permuted_space`` would gather it
    alone."""
    t = _touched_head(hb)
    if not t:
        return w_perm[hb.inv_perm]
    return jax.lax.cond(
        jnp.count_nonzero(w_perm[t:]) == 0,
        lambda: jnp.zeros_like(w_perm).at[hb.perm[:t]].set(
            w_perm[:t], unique_indices=True),
        lambda: w_perm[hb.inv_perm])


def _hot_matvec(X: Array, w: Array, scale: Optional[Array] = None) -> Array:
    """X_hot @ w with f32 MXU accumulation under bf16 storage (same
    contract as ops/aggregators._matvec). With a ``scale`` X holds the
    count block's counts: Σ_c count[i, c] · (scale[c] · w[c]), the scale
    folded into the k coefficients first, so that every product is the
    float32 block's (for the counts 1 and 2 to the bit), float32
    throughout. The convert fuses into the reduce: the TPU's compiler holds
    no float32 copy of the block (``memory_analysis``: no scratch)."""
    if scale is not None:
        return X.astype(jnp.float32) @ (scale * w)
    if X.dtype == jnp.bfloat16:
        return jnp.einsum("nd,d->n", X, w.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return X @ w


def _hot_rmatvec(X: Array, r: Array, scale: Optional[Array] = None) -> Array:
    """X_hotᵀ r; with a ``scale``, scale[c] · Σ_i count[i, c] · r[i]."""
    if scale is not None:
        return (r @ X.astype(jnp.float32)) * scale
    if X.dtype == jnp.bfloat16:
        return jnp.einsum("n,nd->d", r.astype(jnp.bfloat16), X,
                          preferred_element_type=jnp.float32)
    return r @ X


def _cold_products(hb: HybridSparseBatch, w_perm: Array,
                   cold_vals: tuple[Array, ...]) -> Array:
    """Flat per-entry w[col]·value products over all classes.

    A chunk's coefficient arrives by ONE gather over the remainder chunks
    of all classes (``chunk_cols``) and, for the rest of a class, by
    contiguous SLICE: those columns are one run of the permuted space.
    """
    w_cold = w_perm[hb.num_hot:]
    w_rem = w_cold[hb.chunk_cols]
    parts = []
    off = 0
    for start, L, rems, vals in zip(hb.class_starts, hb.class_lens,
                                    hb.class_rems, cold_vals):
        tops = vals.size // L - rems
        w_c = jnp.concatenate([w_rem[off: off + rems],
                               w_cold[start: start + tops]])
        w_c = w_c[None, :] if L < _LANES else w_c[:, None]
        parts.append((w_c * vals).reshape(-1))
        off += rems
    return jnp.concatenate(parts)


def _cold_flat_rowids(hb: HybridSparseBatch) -> Array:
    return jnp.concatenate([r.reshape(-1) for r in hb.cold_rowids])


def margins(hb: HybridSparseBatch, w_perm: Array) -> Array:
    """(n,) wᵀx + offset. Hot: one MXU matvec. Cold: per-chunk broadcast
    products + ONE fused scatter-add by row (the only crossing of the
    entries in this direction)."""
    n = hb.labels.shape[0]
    z = hb.offsets
    if hb.num_hot:
        with jax.named_scope("fe.hot"):
            z = z + _hot_matvec(hb.X_hot, w_perm[:hb.num_hot], hb.hot_scale)
    if hb.cold_rowids:
        with jax.named_scope("fe.cold"):
            prods = _cold_products(hb, w_perm, hb.cold_vals)
            acc = jnp.zeros((n + 1,), jnp.float32).at[
                _cold_flat_rowids(hb)].add(prods)
            z = z + acc[:n]
    return z


def products(hb: HybridSparseBatch, v_perm: Array) -> Array:
    """(n,) X·v, the margins without the offsets: a direction's."""
    return margins(
        dataclasses.replace(hb, offsets=jnp.zeros_like(hb.offsets)), v_perm)


def _masked(weights: Array, term: Array) -> Array:
    return jnp.where(weights > 0.0, weights * term, 0.0)


def _cold_grad(hb: HybridSparseBatch, r: Array,
               cold_vals: tuple[Array, ...]) -> list[Array]:
    """The cold columns' gradient, (present,), as a list of one: ONE fused
    gather r[rowids] (the second random crossing), then row-sums per class.
    The top chunks' sums are contiguous slices of the result; the remainder
    chunks' are added into their columns by one scatter-add."""
    if not hb.cold_rowids:
        return []
    with jax.named_scope("fe.cold"):
        r_pad = jnp.concatenate([r, jnp.zeros((1,), r.dtype)])
        gathered = r_pad[_cold_flat_rowids(hb)]
        tops, rems = [], []
        off = 0
        for L, n_rems, vals in zip(hb.class_lens, hb.class_rems, cold_vals):
            ru = gathered[off: off + vals.size].reshape(vals.shape)
            sums = jnp.sum(ru * vals, axis=0 if L < _LANES else 1)
            rems.append(sums[:n_rems])
            tops.append(sums[n_rems:])
            off += vals.size
        return [jnp.concatenate(tops).at[hb.chunk_cols].add(
            jnp.concatenate(rems))]


def _assemble_grad(hb: HybridSparseBatch, g_hot: Optional[Array],
                   g_cold: list[Array]) -> Array:
    parts = []
    if hb.num_hot:
        parts.append(g_hot.astype(jnp.float32))
    parts.extend(g_cold)
    if not parts:
        return jnp.zeros((hb.num_features,), jnp.float32)
    dense = jnp.concatenate(parts)
    d = hb.num_features
    if dense.shape[0] == d:
        return dense
    # Absent (zero-count) columns sit at the permuted tail: gradient 0.
    return jnp.zeros((d,), jnp.float32).at[:dense.shape[0]].set(dense)


def row_terms(loss: PointwiseLoss, hb: HybridSparseBatch,
              z: Array) -> tuple[Array, Array]:
    """(Σ w·l, w·dl) at the margins ``z``: no pass over the features."""
    l, dl = loss.loss_and_dz(z, hb.labels)
    return (jnp.sum(_masked(hb.weights, l), axis=-1),
            _masked(hb.weights, dl))


def row_gradient(hb: HybridSparseBatch, r: Array) -> Array:
    """Σ_i r_i·x_i in PERMUTED space: hot matvec + cold chunk sums."""
    g_hot = None
    if hb.num_hot:
        with jax.named_scope("fe.hot"):
            g_hot = _hot_rmatvec(hb.X_hot, r, hb.hot_scale)
    return _assemble_grad(hb, g_hot, _cold_grad(hb, r, hb.cold_vals))


def value_and_gradient(
    loss: PointwiseLoss,
    w_perm: Array,
    hb: HybridSparseBatch,
) -> tuple[Array, Array]:
    """(Σ w·l, Σ w·dl·x) in permuted space — the fused hot/cold pass."""
    value, r = row_terms(loss, hb, margins(hb, w_perm))
    return value, row_gradient(hb, r)


def curvature(
    loss: PointwiseLoss,
    w_perm: Array,
    hb: HybridSparseBatch,
) -> Array:
    """(n,) w·d2l at the margins of ``w``: the rows' curvature, which every
    Hessian-vector product of one CG solve shares. One pass over the
    non-zeros, under the scope ``fe.hvp``."""
    with jax.named_scope("fe.hvp"):
        return _masked(hb.weights, loss.d2z(margins(hb, w_perm), hb.labels))


def hessian_vector_at(
    d2: Array,
    v_perm: Array,
    hb: HybridSparseBatch,
) -> Array:
    """Σ d2·(x·v)·x in permuted space at the rows' ``curvature`` ``d2``:
    TRON's H·v in two passes over the non-zeros (X·v, then the gradient
    pass), under the scope ``fe.hvp``."""
    with jax.named_scope("fe.hvp"):
        xv = margins(hb, v_perm) - hb.offsets
        return row_gradient(hb, d2 * xv)


def hessian_vector(
    loss: PointwiseLoss,
    w_perm: Array,
    v_perm: Array,
    hb: HybridSparseBatch,
) -> Array:
    """Σ w·d2l·(x·v)·x in permuted space (TRON's H·v): the curvature at
    ``w``, then the product at it, three passes over the non-zeros. A CG
    solve computes the curvature once and takes ``hessian_vector_at``."""
    return hessian_vector_at(curvature(loss, w_perm, hb), v_perm, hb)


def hessian_diagonal(
    loss: PointwiseLoss,
    w_perm: Array,
    hb: HybridSparseBatch,
) -> Array:
    """diag(H) = Σ w·d2l·x² in permuted space (SIMPLE variances)."""
    z = margins(hb, w_perm)
    d2 = loss.d2z(z, hb.labels)
    r = _masked(hb.weights, d2)
    g_hot = None
    if hb.num_hot:
        # Squares upcast to f32: x² underflows/quantizes harshly in bf16.
        # The count block's are count² · scale².
        Xsq = hb.X_hot.astype(jnp.float32) ** 2
        g_hot = _hot_rmatvec(Xsq, r, None if hb.hot_scale is None
                             else hb.hot_scale ** 2)
    return _assemble_grad(
        hb, g_hot, _cold_grad(hb, r, tuple(v * v for v in hb.cold_vals)))
