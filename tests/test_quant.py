"""photon-quant: int8 quantized streaming + quantized device cache
(ISSUE 13; docs/STREAMING.md "Quantized streaming", docs/SERVING.md
"Quantized device cache").

Parity discipline: quantization is a STORAGE choice — accumulation
stays f32, the compiled-program count is unchanged (kernel caches grow
a dtype key), sharding stays an execution detail (D=1 bit-identical at
int8), and the transfer counters measure exactly the smaller payload.
The quality cost is bounded by the established streamed tolerances and
anchored multi-seed in docs/PARITY.md.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu import faults, obs
from photon_ml_tpu.data import sparse as sp
from photon_ml_tpu.data.game_data import from_sparse_batch
from photon_ml_tpu.ops import losses
from photon_ml_tpu.ops import streaming_sparse as ss
from photon_ml_tpu.optim import OptimizerConfig
from photon_ml_tpu.optim.problem import GLMOptimizationConfiguration
from photon_ml_tpu.optim.regularization import (RegularizationContext,
                                                RegularizationType)
from photon_ml_tpu.parallel.mesh import make_mesh


@pytest.fixture(scope="module")
def batch():
    b, _ = sp.synthetic_sparse(700, 96, 5, seed=3)
    return b


def _chunks_of(batch, chunk_rows, zero_offsets=False):
    n = batch.num_rows
    for lo in range(0, n, chunk_rows):
        hi = min(lo + chunk_rows, n)
        off = (np.zeros(hi - lo, np.float32) if zero_offsets
               else np.asarray(batch.offsets)[lo:hi])
        yield sp.SparseBatch(
            indices=np.asarray(batch.indices)[lo:hi],
            values=np.asarray(batch.values)[lo:hi],
            labels=np.asarray(batch.labels)[lo:hi],
            weights=np.asarray(batch.weights)[lo:hi],
            offsets=off,
            num_features=batch.num_features)


def _build(batch, dtype="int8", chunk_rows=256, zero_offsets=False):
    return ss.build_chunked(
        _chunks_of(batch, chunk_rows, zero_offsets=zero_offsets),
        batch.num_features, chunk_rows, num_hot=16, feature_dtype=dtype)


# ------------------------------------------------------------- quantizers


def test_quantize_rows_adversarial_columns():
    """Per-slice scale correctness on the columns that break naive
    schemes: all-zero (scale 0, codes 0, EXACT round trip), a single
    outlier (the outlier owns the scale and survives exactly at ±127),
    negative-only (symmetric scheme covers it — no zero-point shift)."""
    x = np.zeros((4, 8), np.float32)
    x[1, :3] = [100.0, 0.001, -0.002]      # single outlier
    x[2] = -np.linspace(0.1, 0.8, 8)       # negative-only
    q, scale = ss.quantize_rows_int8(x)
    assert q.dtype == np.int8 and scale.dtype == np.float32
    # all-zero row: scale 0, codes 0, dequant exactly 0.
    assert scale[0] == 0.0 and not q[0].any()
    # outlier row: scale = 100/127; the outlier is exactly ±127 codes.
    np.testing.assert_allclose(scale[1], 100.0 / 127.0, rtol=1e-6)
    assert q[1, 0] == 127
    # negative-only row: max|.| sets the scale, codes stay in range.
    np.testing.assert_allclose(scale[2], 0.8 / 127.0, rtol=1e-6)
    assert q[2].min() >= -127 and q[2].max() <= 0
    # round-trip error is bounded by half a quantization step per value.
    dq = q.astype(np.float32) * scale[:, None]
    assert np.abs(dq - x).max() <= (scale.max() / 2) + 1e-9
    # exact zeros stay exact zeros everywhere (sparse-data contract).
    assert not dq[x == 0.0].any()


def test_cold_quantization_per_original_column(batch):
    """Cold ELL scales live in ORIGINAL column space: scale[c] =
    max|values of column c in this chunk| / 127, the sentinel column d
    stays scale-0, and every inert (hot/pad) entry stores exactly 0."""
    d = batch.num_features
    chunked = _build(batch)
    for ch in chunked.chunks:
        cols = np.asarray(ch.cold_cols)
        q = np.asarray(ch.cold_vals)
        scale = np.asarray(ch.cold_scale)
        assert scale.shape == (d + 1,) and scale[d] == 0.0
        assert not q[cols == d].any()  # inert entries are code 0
        # Per-column max of the dequantized values reproduces the scale.
        dq = q.astype(np.float32) * scale[cols]
        for c in np.unique(cols[cols < d]):
            m = cols == c
            if scale[c] > 0:
                np.testing.assert_allclose(
                    np.abs(dq[m]).max(), scale[c] * 127.0, rtol=1e-5)


def test_plan_num_hot_dtype_table():
    """The HBM plan uses a dtype→itemsize table (f32/bf16/int8+scale),
    so the hot-block width is right for every storage dtype."""
    rows, budget = 1 << 20, 1 << 30
    assert ss.plan_num_hot(rows, budget, jnp.float32) == budget // (4 * rows)
    assert ss.plan_num_hot(rows, budget, "float32") == budget // (4 * rows)
    assert ss.plan_num_hot(rows, budget, jnp.bfloat16) == \
        budget // (2 * rows)
    # int8 charges the per-column f32 scale alongside the column bytes.
    assert ss.plan_num_hot(rows, budget, "int8") == budget // (rows + 4)
    assert ss.plan_num_hot(rows, budget, jnp.int8) == budget // (rows + 4)
    assert ss.plan_num_hot(4, 1, "float32") == 8  # floor
    with pytest.raises(ValueError, match="feature_dtype"):
        ss.plan_num_hot(rows, budget, "float16")


# ------------------------------------------------------------- kernels


def test_int8_chunk_storage_close_to_f32(batch):
    """int8 chunk storage approximates the f32 objective within
    storage-quantization tolerance (the bf16 test's shape, wider band:
    int8 carries ~0.4% relative error per value)."""
    chunked32 = _build(batch, dtype="float32")
    chunked8 = _build(batch, dtype="int8")
    rng = np.random.default_rng(3)
    w = jnp.asarray(rng.normal(size=batch.num_features).astype(np.float32))
    v32, g32 = ss.make_value_and_gradient(losses.LOGISTIC, chunked32)(w)
    v8, g8 = ss.make_value_and_gradient(losses.LOGISTIC, chunked8)(w)
    assert abs(float(v32) - float(v8)) < 0.02 * max(1.0, abs(float(v32)))
    np.testing.assert_allclose(np.asarray(g8), np.asarray(g32),
                               rtol=0.05, atol=0.5)
    # Margins and the value-only probe agree with their own pass.
    z32 = np.asarray(ss.margins_chunked(chunked32, w))
    z8 = np.asarray(ss.margins_chunked(chunked8, w))
    np.testing.assert_allclose(z8, z32, rtol=0.05, atol=0.1)
    v8_only = ss.make_value_only(losses.LOGISTIC, chunked8)(w)
    np.testing.assert_allclose(float(v8_only), float(v8), rtol=1e-6)


def test_int8_structure_signature_carries_dtype(batch):
    """A mixed-dtype stream would silently compile two programs — the
    structure signature carries the storage dtype so the one-structure
    invariant check catches it."""
    c32 = _build(batch, dtype="float32")
    c8 = _build(batch, dtype="int8")
    assert len({ch.structure() for ch in c8.chunks}) == 1
    assert c8.chunks[0].structure() != c32.chunks[0].structure()
    assert ss.chunk_dtype(c8.chunks[0]) == "int8"
    assert ss.chunk_dtype(c32.chunks[0]) == "float32"


def test_int8_pinned_chunks_change_nothing(batch):
    """Pinning is an execution detail in every dtype: the pinned int8
    pass reproduces the streamed int8 pass bit-for-bit (same kernel,
    same chunks, same order)."""
    chunked = _build(batch)
    rng = np.random.default_rng(2)
    w = jnp.asarray(rng.normal(size=batch.num_features).astype(np.float32))
    v0, g0 = ss.make_value_and_gradient(losses.LOGISTIC, chunked)(w)
    pinned = ss.pin_chunks(chunked, 2)
    v1, g1 = ss.make_value_and_gradient(losses.LOGISTIC, chunked,
                                        pinned=pinned)(w)
    assert float(v0) == float(v1)
    np.testing.assert_array_equal(np.asarray(g0), np.asarray(g1))


def test_sharded_d1_bit_identical_at_int8(batch):
    """Sharding stays an execution detail under quantization: the
    1-device sharded int8 pass is BIT-identical to the mesh-less int8
    pass (same kernel, same chunk order, identity psum)."""
    chunked = _build(batch)
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=batch.num_features).astype(np.float32))
    v0, g0 = ss.make_value_and_gradient(losses.LOGISTIC, chunked)(w)
    mesh = make_mesh(num_data=1, devices=jax.devices()[:1])
    strm = ss.ShardedChunkStream(chunked, mesh)
    v1, g1 = strm.value_and_gradient(losses.LOGISTIC)(w)
    assert float(v0) == float(v1)
    np.testing.assert_array_equal(np.asarray(g0), np.asarray(g1))
    z0 = np.asarray(ss.margins_chunked(chunked, w))
    z1 = np.asarray(strm.margins(w))
    np.testing.assert_array_equal(z0, z1)


def test_int8_full_descent_within_established_tolerance():
    """Full streamed descent at int8 lands within the ESTABLISHED
    streamed-parity tolerance (5e-3) of the f32 fit — quantization
    noise averages out over rows, so the optimum barely moves (the
    multi-seed AUC anchor in docs/PARITY.md is the flagship-scale form
    of this claim)."""
    from photon_ml_tpu.game import descent
    from photon_ml_tpu.game.coordinates import \
        StreamingSparseFixedEffectCoordinate
    from photon_ml_tpu.types import TaskType

    b, _ = sp.synthetic_sparse(2000, 96, 5, seed=3)
    ds = from_sparse_batch(b)
    cfg = GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(max_iterations=12, tolerance=1e-9),
        regularization=RegularizationContext(RegularizationType.L2, 1.0))
    res = {}
    for dtype in ("float32", "int8"):
        chunked = ss.build_chunked(
            _chunks_of(b, 512, zero_offsets=True), b.num_features, 512,
            num_hot=16, feature_dtype=dtype)
        coord = StreamingSparseFixedEffectCoordinate(
            ds, chunked, "global", losses.LOGISTIC, cfg)
        model, _ = descent.run(
            TaskType.LOGISTIC_REGRESSION, {"fixed": coord},
            descent.CoordinateDescentConfig(["fixed"], iterations=1))
        res[dtype] = np.asarray(model.models["fixed"].coefficients.means)
    np.testing.assert_allclose(res["int8"], res["float32"], rtol=5e-3,
                               atol=5e-3)


# ------------------------------------------- transfer accounting + compiles


def test_int8_transfer_bytes_tagged_and_quartered():
    """The PR 7 test pattern at int8: one streamed pass moves EXACTLY
    the analytic chunk-size sum, the counter carries dtype="int8", the
    payload lands ≤ 0.30× the f32 payload at matching chunk config
    (hot-block-dominated, the flagship regime), and a warmed stream
    adds ZERO kernel builds."""
    b, _ = sp.synthetic_sparse(2048, 256, 4, seed=9)
    built = {}
    for dtype in ("float32", "int8"):
        built[dtype] = ss.build_chunked(
            _chunks_of(b, 512), b.num_features, 512, num_hot=128,
            feature_dtype=dtype)
    analytic = {dt: sum(ss._chunk_nbytes(ch) for ch in c.chunks)
                for dt, c in built.items()}
    assert analytic["int8"] <= 0.30 * analytic["float32"], analytic
    w = jnp.zeros((b.num_features,), jnp.float32)
    vg8 = ss.make_value_and_gradient(losses.LOGISTIC, built["int8"])
    float(vg8(w)[0])  # warm-up: compile + first pass, before metrics
    # A registry of this test's own: ``obs.enable`` hands back whatever
    # registry an earlier test on the same worker left installed, counts
    # and all (test_kernels.py's int8 streams did, until PR 37)
    m = obs.MetricsRegistry()
    with obs.activated(metrics_obj=m):
        float(vg8(w)[0])
        parsed = obs.parse_prometheus_text(m.render_text())
        key = 'photon_transfer_bytes_total{dtype="int8",kind="stream"}'
        assert parsed[key] == analytic["int8"]
        assert obs.metric_value(parsed, "photon_transfer_bytes_total") \
            == analytic["int8"]  # nothing moved untagged
        # Zero builds after warmup: the dtype key owns its program.
        assert obs.metric_value(
            parsed, "photon_compile_cache_misses_total",
            default=0.0) == 0


# --------------------------------------------------------- chunk store


def test_chunk_store_roundtrip_bit_stable(batch, tmp_path):
    """The persisted int8 payload (codes + scale vectors) round-trips
    BIT-identically through the per-chunk npz store, and the loaded
    stream computes the same bits."""
    for dtype in ("float32", "int8"):
        chunked = _build(batch, dtype=dtype)
        d = str(tmp_path / f"store-{dtype}")
        ss.save_chunked(d, chunked)
        loaded = ss.load_chunked(d)
        assert loaded.num_rows == chunked.num_rows
        assert loaded.chunk_rows == chunked.chunk_rows
        for a, c in zip(loaded.chunks, chunked.chunks):
            assert ss.chunk_dtype(a) == dtype
            for la, lc in zip(jax.tree.leaves(a), jax.tree.leaves(c)):
                assert np.asarray(la).dtype == np.asarray(lc).dtype
                np.testing.assert_array_equal(np.asarray(la),
                                              np.asarray(lc))
    w = jnp.asarray(np.random.default_rng(1).normal(
        size=batch.num_features).astype(np.float32))
    v0, g0 = ss.make_value_and_gradient(losses.LOGISTIC, chunked)(w)
    v1, g1 = ss.make_value_and_gradient(losses.LOGISTIC, loaded)(w)
    assert float(v0) == float(v1)
    np.testing.assert_array_equal(np.asarray(g0), np.asarray(g1))


def test_chunk_store_corrupt_chunk_restages_exactly_one(batch, tmp_path):
    """Chaos rung (docs/ROBUSTNESS.md): injected bit rot on one
    persisted quantized chunk (the ``stream.quantize`` corrupt-file
    site, landing AFTER the CRC was recorded) fails that chunk's CRC on
    load and re-stages EXACTLY that chunk via the rebuild hook — final
    stream bit-identical to a clean build; without a hook the store
    fails loudly instead of serving wrong bytes."""
    chunked = _build(batch)
    d = str(tmp_path / "store")
    plan = faults.FaultPlan(specs=(faults.FaultSpec(
        site="stream.quantize", kind="corrupt", indices=(1,)),), seed=5)
    with faults.installed(plan):
        ss.save_chunked(d, chunked)
    rebuilt = []

    def rebuild(i):
        rebuilt.append(i)
        return chunked.chunks[i]

    loaded = ss.load_chunked(d, rebuild=rebuild)
    assert rebuilt == [1]  # exactly the corrupted chunk restaged
    for a, c in zip(loaded.chunks, chunked.chunks):
        for la, lc in zip(jax.tree.leaves(a), jax.tree.leaves(c)):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lc))
    with pytest.raises(ss.ChunkStoreError, match="chunk 1"):
        ss.load_chunked(d)


def test_ingest_cache_one_byte_columns_bit_stable(tmp_path):
    """The columnar ingest cache's aligned-blob machinery preserves
    1-byte columns bit-exactly through the mmap round trip — the
    property the quantized payload relies on wherever it is persisted
    (the chunk store above is the staged-side twin of this check)."""
    from photon_ml_tpu.avro.native_decode import BagColumns, DecodedFile
    from photon_ml_tpu.ingest.cache import load_chunk, save_chunk

    n = 64
    rng = np.random.default_rng(2)
    kind = rng.integers(0, 3, size=n).astype(np.uint8)  # 1-byte column
    d = DecodedFile(
        num_records=n,
        response=rng.random(n), offsets=np.zeros(n), weights=np.ones(n),
        uids=np.array([int(i) if k == 2 else (f"u{i}" if k == 1 else i)
                       for i, k in enumerate(kind)], object),
        uid_kind=kind,
        bags=[BagColumns(rows=np.arange(n, dtype=np.int64),
                         keys=np.arange(n, dtype=np.int32),
                         values=rng.random(n),
                         key_strings=["k"])],
        meta_rows=np.zeros(0, np.int64), meta_keys=np.zeros(0, np.int32),
        meta_vals=np.zeros(0, np.int32), meta_key_strings=[],
        meta_val_strings=[])
    save_chunk(str(tmp_path), "k0", 0, d)
    back = load_chunk(str(tmp_path), "k0", 0, n_bags=1)
    assert back is not None
    np.testing.assert_array_equal(np.asarray(back.uid_kind), kind)
    assert np.asarray(back.uid_kind).dtype == np.uint8
    np.testing.assert_array_equal(np.asarray(back.response),
                                  np.asarray(d.response))


# ----------------------------------------------------- config + estimator


def test_streaming_config_accepts_int8():
    from photon_ml_tpu.api.configs import (StreamingConfig,
                                           parse_streaming_config)

    cfg = parse_streaming_config("chunk_rows=1024,dtype=int8")
    assert cfg.feature_dtype == "int8"
    assert StreamingConfig(feature_dtype="int8").feature_dtype == "int8"
    with pytest.raises(ValueError, match="feature_dtype"):
        StreamingConfig(feature_dtype="int4")


def test_estimator_routes_int8_streaming(batch):
    from photon_ml_tpu.api.configs import (CoordinateConfiguration,
                                           FixedEffectDataConfiguration,
                                           StreamingConfig)
    from photon_ml_tpu.api.estimator import GameEstimator
    from photon_ml_tpu.types import TaskType

    ds = from_sparse_batch(batch)
    cfg = GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(max_iterations=4, tolerance=1e-7),
        regularization=RegularizationContext(RegularizationType.L2, 1.0))
    cc = {"fixed": CoordinateConfiguration(
        data=FixedEffectDataConfiguration("global"), optimization=cfg)}
    est = GameEstimator(
        task=TaskType.LOGISTIC_REGRESSION, coordinates=cc,
        update_sequence=["fixed"], mesh=make_mesh(),
        streaming=StreamingConfig(chunk_rows=256, num_hot=16,
                                  feature_dtype="int8"))
    coords = est._build_coordinates(ds, {"fixed": cfg})
    assert ss.chunk_dtype(coords["fixed"].chunked.chunks[0]) == "int8"


# ------------------------------------------------------- serving int8 LRU


def _tiny_model(E=64, dg=8, dr=6, seed=0):
    from photon_ml_tpu.game.models import (FixedEffectModel, GameModel,
                                           RandomEffectModel)
    from photon_ml_tpu.models.coefficients import Coefficients
    from photon_ml_tpu.types import TaskType

    rng = np.random.default_rng(seed)
    return GameModel(task=TaskType.LOGISTIC_REGRESSION, models={
        "fixed": FixedEffectModel("global", Coefficients(
            jnp.asarray(rng.normal(size=dg).astype(np.float32)))),
        "per-user": RandomEffectModel(
            "userId", "re_userId",
            jnp.asarray((rng.normal(size=(E, dr)) * 0.5
                         ).astype(np.float32))),
    })


def _requests(n, E=64, dg=8, dr=6, seed=1):
    from photon_ml_tpu.serving import ScoringRequest

    r = np.random.default_rng(seed)
    return [ScoringRequest(
        features={"global": r.normal(size=dg).astype(np.float32),
                  "re_userId": r.normal(size=dr).astype(np.float32)},
        entity_ids={"userId": int(r.integers(0, E))}) for _ in range(n)]


def test_serving_int8_cache_scores_close_and_lru_identical():
    """The int8 device LRU perturbs scores only by one row's
    quantization noise, and the LRU BEHAVIOR (hits/misses/evictions —
    the part capacity buys) is identical to f32 at equal capacity."""
    from photon_ml_tpu.serving import ScoringService

    model = _tiny_model()
    s32 = ScoringService(model, max_batch=8, cache_entities=16)
    s8 = ScoringService(model, max_batch=8, cache_entities=16,
                        cache_dtype="int8")
    try:
        reqs = _requests(48)
        a = s32.score(reqs)
        b = s8.score(reqs)
        np.testing.assert_allclose(b, a, rtol=0.02, atol=0.05)
        assert s32.metrics.snapshot()["re_cache"] == \
            s8.metrics.snapshot()["re_cache"]
        # int8 halves-and-more the device spend at equal capacity.
        assert s8.store.device_cache_bytes() < \
            0.5 * s32.store.device_cache_bytes()
    finally:
        s32.close()
        s8.close()


def test_serving_int8_rejects_unknown_dtype():
    from photon_ml_tpu.serving.model_store import ResidentModelStore

    with pytest.raises(ValueError, match="cache_dtype"):
        ResidentModelStore(_tiny_model(), cache_dtype="int4")


def test_int8_hot_swap_equals_quantized_cold_restart():
    """Publication parity in int8 mode: hot-swapping rows into a
    quantized store (host write + affected-slot invalidation, then
    fill-time re-quantization on the next resolve) serves the SAME BITS
    as a quantized store cold-started on the already-mutated model."""
    from photon_ml_tpu.serving import ScoringService

    E, dg, dr = 64, 8, 6
    model = _tiny_model(E, dg, dr)
    swapped_ids = np.asarray([3, 7, 11], np.int64)
    new_rows = np.asarray(
        np.random.default_rng(9).normal(size=(3, dr)), np.float32)
    # A small fixed entity pool (≤ capacity) that INCLUDES the swapped
    # ids: no evictions, so the swap definitely hits resident slots.
    reqs = _requests(32, E, dg, dr, seed=4)
    pool = [1, 3, 5, 7, 9, 11]
    for i, r in enumerate(reqs):
        r.entity_ids = {"userId": pool[i % len(pool)]}

    hot = ScoringService(model, max_batch=8, cache_entities=16,
                         cache_dtype="int8")
    try:
        hot.score(reqs)  # warm the cache (swapped ids device-resident)
        st = hot.store.random[0]
        with hot.store._lock:
            invalidated = st.apply_rows(swapped_ids, new_rows)
        assert invalidated >= 1  # at least one swapped row was cached
        hot_scores = hot.score(reqs)
    finally:
        hot.close()

    # Cold restart on the mutated model: same rows, fresh quantized fill.
    mutated = _tiny_model(E, dg, dr)
    cold = ScoringService(mutated, max_batch=8, cache_entities=16,
                          cache_dtype="int8")
    try:
        cold.store.random[0].store.swap_rows(swapped_ids, new_rows)
        cold_scores = cold.score(reqs)
    finally:
        cold.close()
    np.testing.assert_array_equal(hot_scores, cold_scores)


def test_int8_lru_fill_and_invalidate_bookkeeping():
    """Fill/evict/invalidate slot accounting is dtype-blind, and the
    quantized fallback row stays exactly zero (scale 0) so unseen
    entities keep fixed-effect-only semantics bit-for-bit."""
    from photon_ml_tpu.game.models import RandomEffectModel

    rng = np.random.default_rng(5)
    m = RandomEffectModel(
        "userId", "re_userId",
        jnp.asarray(rng.normal(size=(32, 4)).astype(np.float32)))
    from photon_ml_tpu.serving.model_store import REServingState

    st = REServingState("per-user", m, cache_entities=4, store_shards=2,
                        cache_dtype="int8")
    slots, stats = st.resolve(np.asarray([1, 2, 3, 999], np.int64))
    assert stats == {"hits": 0, "misses": 3, "unseen": 1, "evictions": 0}
    assert slots[3] == st.fallback_slot
    # fallback row: code 0, scale 0 → exactly zero contribution.
    assert not np.asarray(st.cache)[st.fallback_slot].any()
    assert float(np.asarray(st.cache_scale)[st.fallback_slot]) == 0.0
    # a swap invalidates exactly the affected resident slots.
    n_inv = st.apply_rows(np.asarray([2, 30], np.int64),
                          np.zeros((2, 4), np.float32))
    assert n_inv == 1  # 2 was resident, 30 was not
    _, stats2 = st.resolve(np.asarray([1, 2], np.int64))
    assert stats2["hits"] == 1 and stats2["misses"] == 1
