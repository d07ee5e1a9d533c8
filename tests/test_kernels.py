"""Fused-kernel registry tests (photon_ml_tpu/ops/kernels/, docs/KERNELS.md).

The contract under test: every fused Pallas program lives behind the
registry seam — a per-kernel flag, an XLA reference closure with the same
signature, an interpret-mode CPU path, backend-tagged compile counters,
and a loud degradation ladder (an injected ``kernel.launch`` fault lands
on the XLA closure with a
:class:`~photon_ml_tpu.utils.events.KernelFallback`; a flag switched on
where no backend can run the program raises). Flag flips change
WHERE the math runs, never what it computes: the parity fixtures here pin
fused == reference down to bit-exactness where the algebra is exact
(int8 folding, power-of-two scales, row gather/scatter).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from photon_ml_tpu import faults, obs
from photon_ml_tpu.data import sparse as sp
from photon_ml_tpu.faults import sites
from photon_ml_tpu.ops import kernels
from photon_ml_tpu.ops import losses
from photon_ml_tpu.ops import streaming_sparse as ss
from photon_ml_tpu.ops.kernels import (ell_scatter, re_rows, serving_score,
                                       stream_fused)
from photon_ml_tpu.parallel.mesh import make_mesh
from photon_ml_tpu.utils import events as ev

ALL_KERNELS = ["ell_scatter", "re_gather_rows", "re_scatter_rows",
               "serving_score", "stream_margins", "stream_rmatvec"]


@pytest.fixture(autouse=True)
def clean_registry():
    reg = kernels.registry()
    reg.reset()
    yield reg
    reg.reset()
    # Two tests below turn the metrics registry on: leave none installed
    # for the next file on this worker (test_quant.py read its counts)
    obs.disable()
    # Streamed kernel caches key on the resolved fused state; drop them
    # so a flag flipped in one test never leaks a closure into the next.
    ss._VG_KERNELS.clear()
    ss._V_KERNELS.clear()
    ss._MARGINS_KERNELS.clear()


@pytest.fixture
def fallback_events():
    seen = []
    listener = seen.append
    ev.default_emitter.register(listener)
    yield seen
    ev.default_emitter.unregister(listener)


def _fallbacks(seen):
    return [e for e in seen if type(e).__name__ == "KernelFallback"]


# ------------------------------------------------------------ registry


def test_registry_catalog(clean_registry):
    assert clean_registry.names() == ALL_KERNELS
    # The only committed default flip is the moderate-d ELL scatter;
    # every other kernel waits for its sweep. The default holds on the
    # TPU backend only — on this CPU box it resolves off, silently.
    for name in ALL_KERNELS:
        assert clean_registry.get(name).default_on == (
            name == "ell_scatter")
        assert not clean_registry.enabled(name)


def test_flag_resolution_order(clean_registry, monkeypatch):
    reg = clean_registry
    assert not reg.enabled("serving_score")  # registered default
    monkeypatch.setenv("PHOTON_KERNEL_SERVING_SCORE", "1")
    assert reg.enabled("serving_score")  # env beats default
    monkeypatch.setenv("PHOTON_KERNEL_SERVING_SCORE", "0")
    assert not reg.enabled("serving_score")
    reg.set_enabled("serving_score", True)
    assert reg.enabled("serving_score")  # override beats env
    reg.set_enabled("serving_score", None)
    assert not reg.enabled("serving_score")  # None restores the ladder


def test_set_enabled_unknown_kernel_raises(clean_registry):
    with pytest.raises(KeyError, match="unknown kernel"):
        clean_registry.set_enabled("no_such_kernel", True)


def test_flag_off_resolves_xla_silently(clean_registry, fallback_events):
    resolved = clean_registry.resolve("serving_score")
    assert resolved.backend == "xla" and not resolved.interpret
    assert _fallbacks(fallback_events) == []  # policy, not degradation


def test_enabled_without_backend_raises(clean_registry, fallback_events):
    clean_registry.set_enabled("serving_score", True)
    with pytest.raises(RuntimeError, match="no TPU backend"):
        clean_registry.resolve("serving_score")
    assert _fallbacks(fallback_events) == []  # refused, not degraded


def test_force_interpret_resolves_pallas(clean_registry, fallback_events):
    reg = clean_registry
    reg.set_enabled("stream_rmatvec", True)
    reg.force_interpret()
    resolved = reg.resolve("stream_rmatvec")
    assert resolved.backend == "pallas" and resolved.interpret
    assert _fallbacks(fallback_events) == []
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.integers(-5, 6, (40, 16)).astype(np.int8))
    r = jnp.asarray(rng.integers(-3, 4, 40).astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(resolved(X, r)),
        np.asarray(stream_fused.hot_rmatvec_xla(X, r)))


def test_injected_launch_fault_degrades_loud(clean_registry,
                                             fallback_events):
    reg = clean_registry
    reg.set_enabled("ell_scatter", True)
    reg.force_interpret()  # would resolve pallas but for the fault
    plan = faults.FaultPlan(specs=(
        faults.FaultSpec(site=sites.KERNEL_LAUNCH, kind="raise"),))
    with faults.installed(plan):
        resolved = reg.resolve("ell_scatter")
    assert resolved.backend == "xla"
    (fb,) = _fallbacks(fallback_events)
    assert fb.kernel == "ell_scatter" and "kernel.launch" in fb.reason
    # The plan gone, the same flag state resolves pallas again.
    assert reg.resolve("ell_scatter").backend == "pallas"


def test_resolve_counters_tagged_by_backend(clean_registry):
    reg = clean_registry
    _, m = obs.enable(trace=False)
    before = obs.parse_prometheus_text(m.render_text())
    reg.set_enabled("stream_margins", True)
    reg.force_interpret()
    reg.resolve("stream_margins", dtype="int8")  # fresh: miss
    reg.resolve("stream_margins", dtype="int8")  # seen: hit
    reg.resolve("stream_margins", dtype="float32")  # new dtype: miss
    parsed = obs.parse_prometheus_text(m.render_text())

    def delta(name, **labels):
        key = name + "{" + ",".join(
            f'{k}="{v}"' for k, v in sorted(labels.items())) + "}"
        return parsed.get(key, 0.0) - before.get(key, 0.0)

    assert delta("photon_compile_cache_misses_total", backend="pallas",
                 cache="kernel_stream_margins", dtype="int8") == 1.0
    assert delta("photon_compile_cache_hits_total", backend="pallas",
                 cache="kernel_stream_margins", dtype="int8") == 1.0
    assert delta("photon_compile_cache_misses_total", backend="pallas",
                 cache="kernel_stream_margins", dtype="float32") == 1.0


def test_flag_off_call_sites_create_zero_registry_traffic():
    """The wiring invariant the compile-needle tests depend on: with a
    kernel's flag OFF, its call site never touches the registry — no
    ``cache="kernel_*"`` label set appears for it (``metric_value`` sums
    every label set of the miss counter, so silent flag-off resolves
    would shift every compile-count needle in the suite)."""
    _, m = obs.enable(trace=False)
    before = obs.parse_prometheus_text(m.render_text())
    batch, _ = sp.synthetic_sparse(300, 64, 5, seed=1)
    chunked = ss.build_chunked(
        [batch], batch.num_features, 300, num_hot=8, feature_dtype="int8")
    w = jnp.zeros(batch.num_features, jnp.float32)
    ss.make_value_and_gradient(losses.LOGISTIC, chunked)(w)
    after = obs.parse_prometheus_text(m.render_text())
    moved = [k for k in after if 'cache="kernel_stream_' in k
             and after[k] != before.get(k, 0.0)]
    assert moved == []


# -------------------------------------------------------------- parity


def test_ell_scatter_parity():
    rng = np.random.default_rng(2)
    idx = jnp.asarray(rng.integers(0, 96, (200, 6)).astype(np.int32))
    rv = jnp.asarray(rng.normal(size=(200, 6)).astype(np.float32))
    got = np.asarray(ell_scatter.scatter_rowterm_pallas(
        idx, rv, 96, interpret=True))
    want = np.asarray(ell_scatter.scatter_rowterm_xla(idx, rv, 96))
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)


def test_serving_score_parity_int8_and_f32():
    rng = np.random.default_rng(3)
    mat = jnp.asarray(rng.normal(size=(20, 30)).astype(np.float32))
    slots = jnp.asarray(rng.integers(0, 8, 20).astype(np.int32))
    cache8 = jnp.asarray(rng.integers(-127, 128, (8, 30)).astype(np.int8))
    scale = jnp.asarray(rng.uniform(0.01, 2.0, 8).astype(np.float32))
    got = np.asarray(serving_score.score_rows_pallas(
        mat, slots, cache8, scale, interpret=True))
    want = np.asarray(serving_score.score_rows_xla(
        mat, slots, cache8, scale))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    cache32 = jnp.asarray(rng.normal(size=(8, 30)).astype(np.float32))
    got = np.asarray(serving_score.score_rows_pallas(
        mat, slots, cache32, None, interpret=True))
    want = np.asarray(serving_score.score_rows_xla(
        mat, slots, cache32, None))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_serving_score_int8_zero_rows_exact():
    """Quantized zero rows dequantize to EXACTLY zero through the fused
    program — no epsilon from the folded scale multiply."""
    mat = jnp.asarray(np.random.default_rng(4).normal(
        size=(6, 12)).astype(np.float32))
    slots = jnp.asarray(np.zeros(6, np.int32))
    cache = jnp.zeros((3, 12), jnp.int8)
    scale = jnp.asarray(np.full(3, 0.37, np.float32))
    got = np.asarray(serving_score.score_rows_pallas(
        mat, slots, cache, scale, interpret=True))
    np.testing.assert_array_equal(got, np.zeros(6, np.float32))


def test_serving_score_adversarial_scales():
    """Per-entity scales spanning ~50 orders of magnitude: the fused
    multiply-after-sum ordering matches the reference's."""
    rng = np.random.default_rng(5)
    mat = jnp.asarray(rng.integers(-4, 5, (8, 16)).astype(np.float32))
    slots = jnp.asarray(np.arange(8, dtype=np.int32) % 4)
    cache = jnp.asarray(rng.integers(-127, 128, (4, 16)).astype(np.int8))
    scale = jnp.asarray(np.array([2.0 ** -40, 2.0 ** 20, 1.0, 2.0 ** -3],
                                 np.float32))
    got = np.asarray(serving_score.score_rows_pallas(
        mat, slots, cache, scale, interpret=True))
    want = np.asarray(serving_score.score_rows_xla(
        mat, slots, cache, scale))
    np.testing.assert_array_equal(got, want)  # int sums + pow2: exact


def test_stream_fused_parity():
    rng = np.random.default_rng(6)
    X = jnp.asarray(rng.integers(-127, 128, (300, 48)).astype(np.int8))
    w = jnp.asarray(rng.normal(size=48).astype(np.float32))
    base = jnp.asarray(rng.normal(size=300).astype(np.float32))
    r = jnp.asarray(rng.normal(size=300).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(stream_fused.hot_margins_pallas(X, w, base,
                                                   interpret=True)),
        np.asarray(stream_fused.hot_margins_xla(X, w, base)),
        rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(stream_fused.hot_rmatvec_pallas(X, r, interpret=True)),
        np.asarray(stream_fused.hot_rmatvec_xla(X, r)),
        rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("d", [70, 128])
def test_re_rows_bit_parity(d):
    """Bucket row traffic is pure data movement — bit parity at an
    unaligned and a lane-aligned width, invalid (-1) lanes included."""
    rng = np.random.default_rng(7)
    W = jnp.asarray(rng.normal(size=(40, d)).astype(np.float32))
    rows_np = rng.permutation(40)[:16].astype(np.int32)
    rows_np[3] = rows_np[11] = -1  # ragged final wave
    rows = jnp.asarray(rows_np)
    vals = jnp.asarray(rng.normal(size=(16, d)).astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(re_rows.gather_rows_pallas(W, rows, interpret=True)),
        np.asarray(re_rows.gather_rows_xla(W, rows)))
    np.testing.assert_array_equal(
        np.asarray(re_rows.scatter_rows_pallas(W, rows, vals,
                                               interpret=True)),
        np.asarray(re_rows.scatter_rows_xla(W, rows, vals)))


def test_re_scatter_all_invalid_wave_is_noop():
    rng = np.random.default_rng(8)
    W = jnp.asarray(rng.normal(size=(10, 24)).astype(np.float32))
    rows = jnp.asarray(np.full(4, -1, np.int32))
    vals = jnp.asarray(rng.normal(size=(4, 24)).astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(re_rows.scatter_rows_pallas(W, rows, vals,
                                               interpret=True)),
        np.asarray(W))


# ------------------------------------------------- end-to-end parity


def _int8_chunked(n=512, d=96, chunk_rows=128):
    batch, _ = sp.synthetic_sparse(n, d, 5, seed=9)
    def chunks():
        for lo in range(0, n, chunk_rows):
            hi = min(lo + chunk_rows, n)
            yield sp.SparseBatch(
                indices=np.asarray(batch.indices)[lo:hi],
                values=np.asarray(batch.values)[lo:hi],
                labels=np.asarray(batch.labels)[lo:hi],
                weights=np.asarray(batch.weights)[lo:hi],
                offsets=np.asarray(batch.offsets)[lo:hi],
                num_features=d)
    chunked = ss.build_chunked(chunks(), d, chunk_rows, num_hot=16,
                               feature_dtype="int8")
    return batch, chunked


def test_streamed_fused_matches_unfused(clean_registry):
    batch, chunked = _int8_chunked()
    rng = np.random.default_rng(10)
    w = jnp.asarray(rng.normal(size=batch.num_features)
                    .astype(np.float32))
    v0, g0 = ss.make_value_and_gradient(losses.LOGISTIC, chunked)(w)
    ss._VG_KERNELS.clear()
    clean_registry.set_enabled("stream_margins", True)
    clean_registry.set_enabled("stream_rmatvec", True)
    clean_registry.force_interpret()
    v1, g1 = ss.make_value_and_gradient(losses.LOGISTIC, chunked)(w)
    scale = float(np.max(np.abs(np.asarray(g0)))) or 1.0
    assert abs(float(v0) - float(v1)) <= 1e-6 * max(abs(float(v0)), 1.0)
    assert float(np.max(np.abs(np.asarray(g0) - np.asarray(g1)))) \
        <= 1e-5 * scale


def test_sharded_d1_bit_identical_through_fused_pass(clean_registry):
    """Sharding stays an execution detail with the fused kernels ON:
    the D=1 sharded int8 pass is BIT-identical to the mesh-less fused
    pass (same resolved kernels, same chunk order, identity psum)."""
    batch, chunked = _int8_chunked()
    clean_registry.set_enabled("stream_margins", True)
    clean_registry.set_enabled("stream_rmatvec", True)
    clean_registry.force_interpret()
    rng = np.random.default_rng(11)
    w = jnp.asarray(rng.normal(size=batch.num_features)
                    .astype(np.float32))
    v0, g0 = ss.make_value_and_gradient(losses.LOGISTIC, chunked)(w)
    mesh = make_mesh(num_data=1, devices=jax.devices()[:1])
    strm = ss.ShardedChunkStream(chunked, mesh)
    v1, g1 = strm.value_and_gradient(losses.LOGISTIC)(w)
    assert float(v0) == float(v1)
    np.testing.assert_array_equal(np.asarray(g0), np.asarray(g1))


def _exact_serving_fixture(rng, E=6, d_re=8, d_global=4, n=24):
    """A quantization-exact serving model: RE rows are small ints times
    a power-of-two, with per-row max exactly 127 * 2^-3 so the int8
    scale lands on 2^-3 exactly; features and offsets are small ints.
    Every product and partial sum is then exactly representable in f32
    (magnitudes far below 2^24), so fused and unfused scoring must
    agree to the BIT, not within a band."""
    from photon_ml_tpu.data.game_data import GameDataset
    from photon_ml_tpu.game.models import (FixedEffectModel, GameModel,
                                           RandomEffectModel)
    from photon_ml_tpu.models.coefficients import Coefficients
    from photon_ml_tpu.types import TaskType

    table = rng.integers(-126, 127, (E, d_re)).astype(np.float32)
    table[:, 0] = 127.0  # pin each row's max: scale = 127*2^-3/127
    table *= 2.0 ** -3
    model = GameModel(task=TaskType.LOGISTIC_REGRESSION, models={
        "fixed": FixedEffectModel("global", Coefficients(
            jnp.asarray(rng.integers(-8, 9, d_global)
                        .astype(np.float32)))),
        "per-user": RandomEffectModel(
            "userId", "re_userId", jnp.asarray(table)),
    })
    ds = GameDataset(
        response=np.zeros(n, np.float32),
        offsets=rng.integers(-4, 5, n).astype(np.float32),
        weights=np.ones(n, np.float32),
        feature_shards={
            "global": rng.integers(-6, 7, (n, d_global))
            .astype(np.float32),
            "re_userId": rng.integers(-6, 7, (n, d_re))
            .astype(np.float32)},
        entity_ids={"userId": rng.integers(0, E, n).astype(np.int32)},
        num_entities={"userId": E}, intercept_index={})
    return model, ds


def test_serving_fused_bits_equal_unfused(clean_registry):
    from photon_ml_tpu.serving import ScoringService, requests_from_dataset

    rng = np.random.default_rng(12)
    model, ds = _exact_serving_fixture(rng)
    reqs = requests_from_dataset(ds)
    off = ScoringService(model, max_batch=8, cache_dtype="int8")
    base = np.asarray(off.score(reqs))
    clean_registry.set_enabled("serving_score", True)
    clean_registry.force_interpret()
    on = ScoringService(model, max_batch=8, cache_dtype="int8")
    assert on._kernel_backend == "pallas"
    np.testing.assert_array_equal(np.asarray(on.score(reqs)), base)


def test_serving_chaos_launch_fault_scores_on_xla(clean_registry,
                                                  fallback_events):
    """The degradation ladder end-to-end: a ``kernel.launch`` fault at
    service build time lands scoring on the XLA closure — loudly
    (KernelFallback + counter), with the scores themselves unchanged."""
    from photon_ml_tpu.serving import ScoringService, requests_from_dataset

    rng = np.random.default_rng(13)
    model, ds = _exact_serving_fixture(rng)
    reqs = requests_from_dataset(ds)
    off = ScoringService(model, max_batch=8, cache_dtype="int8")
    base = np.asarray(off.score(reqs))
    clean_registry.set_enabled("serving_score", True)
    clean_registry.force_interpret()
    plan = faults.FaultPlan(specs=(
        faults.FaultSpec(site=sites.KERNEL_LAUNCH, kind="raise"),))
    with faults.installed(plan):
        degraded = ScoringService(model, max_batch=8, cache_dtype="int8")
    assert degraded._kernel_backend == "xla"
    (fb,) = _fallbacks(fallback_events)
    assert fb.kernel == "serving_score"
    np.testing.assert_array_equal(np.asarray(degraded.score(reqs)), base)
