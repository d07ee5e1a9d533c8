"""The deployment of ISSUE 29, program side, at a size the CPU holds: the
resident sparse fixed effect's hot block is sized from bytes by the planner
the streamed path uses, any hot block gives the same model, the new scopes,
counters and ledger rows are there, nothing is traced again after the set-up
sweeps, and the program agrees with the schema's plain reference
(``benchmark/criteo_reference.py``) on seeded data."""

from __future__ import annotations

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu import obs
from photon_ml_tpu.data import sparse as sp_data
from photon_ml_tpu.data.sparse import SparseBatch
from photon_ml_tpu.game.coordinates import (SparseFixedEffectCoordinate,
                                             sparse_fixed)
from photon_ml_tpu.obs.ledger import read_rows
from photon_ml_tpu.ops import hybrid_sparse as hs
from photon_ml_tpu.ops import losses
from photon_ml_tpu.ops import streaming_sparse as ss
from photon_ml_tpu.optim import (OptimizerConfig, OptimizerType,
                                 minimize_lbfgs, optimize, with_l2)
from photon_ml_tpu.optim.problem import GLMOptimizationConfiguration
from photon_ml_tpu.optim.regularization import (RegularizationContext,
                                                RegularizationType)
from photon_ml_tpu.parallel import sparse_problem as sp
from photon_ml_tpu.parallel.mesh import make_mesh
from photon_ml_tpu.utils import events

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
BENCH = os.path.join(REPO, "benchmark")
for _p in (BENCH, os.path.join(BENCH, "layer_metrics"),
           os.path.join(BENCH, "schemas")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import criteo_reference  # noqa: E402  (benchmark/criteo_reference.py)
import game_criteo  # noqa: E402  (benchmark/schemas/game_criteo.py)

CELL = "criteo-1m-logistic.steady"
SEQ = ["fixed", "per-c10"]


@pytest.fixture(autouse=True)
def _clean_obs_state():
    yield
    obs.set_ledger(None)
    obs.disable()


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def small_cell(rows: int, d: int, max_iterations: int = 100) -> dict:
    """The new cell's files at ``rows`` rows and ``d`` hashed columns."""
    conf = game_criteo.shrink(
        load("configs", "glmix-criteo-1m-logistic.json"), rows)
    settings = load("workloads", CELL + ".json")
    settings["optimizer"]["max_iterations"] = max_iterations
    return {"configuration": dict(conf, hashed_features=d),
            "mix": load("traffic", "steady-fixed-c10.json"),
            "settings": settings}


def fit(cell, data, sweeps, ledger_dir, on_update=None):
    est = game_criteo.estimator(
        cell, make_mesh(devices=jax.devices()[:1]), sweeps, str(ledger_dir),
        "float32")
    if on_update is not None:
        events.default_emitter.register(on_update)
    try:
        model = est.fit(game_criteo.dataset(data))[0].model
    finally:
        if on_update is not None:
            events.default_emitter.unregister(on_update)
    return model, read_rows(str(ledger_dir))[0]


def _opt(max_iterations=200):
    return GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(max_iterations=max_iterations,
                                  tolerance=1e-9),
        regularization=RegularizationContext(RegularizationType.L2, 1.0))


def _batch(data) -> SparseBatch:
    n = data.response.shape[0]
    return SparseBatch(indices=data.indices, values=data.values,
                       labels=data.response, weights=np.ones(n, np.float32),
                       offsets=np.zeros(n, np.float32),
                       num_features=data.num_features)


# -- the byte-sized hot block -------------------------------------------------

V5E_BYTES = 16_909_336_064  # ``bytes_limit`` of one v5e chip


class _Device:
    def __init__(self, stats):
        self.stats = stats

    def memory_stats(self):
        return self.stats


class _Mesh:
    """A stand-in for a mesh over one device that reports ``stats``."""

    def __init__(self, stats):
        self.devices = np.array([_Device(stats)], object)


@pytest.mark.parametrize("k", [8, 64, 128, 384])
def test_a_budget_that_admits_k_columns_yields_k(k):
    rows = 10_000
    counts = np.zeros(4096, np.int64)
    counts[:1000] = rows  # a thousand columns clear any threshold
    budget = 4 * rows * k + 4 * rows - 1  # k whole columns, not k + 1
    assert hs.plan_resident_hot(counts, rows, jnp.float32,
                                hot_block_bytes=budget) == k
    assert hs.plan_resident_hot(counts, rows, jnp.bfloat16,
                                hot_block_bytes=budget // 2) == k
    # the streamed path's planner is the same function
    assert ss.plan_num_hot is hs.plan_num_hot
    assert hs.plan_num_hot(rows, budget, "float32") == k


def test_where_the_bytes_bind_a_wide_block_keeps_whole_lane_tiles():
    rows = 2_500_000
    counts = np.full(1 << 20, rows, np.int64)
    k = hs.plan_resident_hot(counts, rows, jnp.float32,
                             hot_block_bytes=4 << 30)
    assert k == 384 and k * rows * 4 <= 4 << 30  # 429 fit, 3 tiles kept
    # the old cap alone would have asked for 4096 columns: 41 GB
    assert min(4096, int((counts >= rows // 2048).sum())) * rows * 4 > 40e9


def test_the_split_at_the_old_size_is_what_it_was():
    """n = 131,072, the size the hot threshold was swept at: the columns of
    count >= n/2048 (n/4096 under bf16), at most 4096, whatever the bytes,
    in whole lane tiles (since ISSUE 35 wherever the block is wider than
    one, whichever bound it)."""
    n = 131072
    rng = np.random.default_rng(0)
    counts = np.bincount(rng.zipf(1.3, size=n * 39) % (1 << 20),
                         minlength=1 << 20)
    # what the coordinate gives on that chip
    v5e = sparse_fixed.hot_block_budget(_Mesh({"bytes_limit": V5E_BYTES}))
    assert v5e > 8e9
    for dt, div in ((jnp.float32, 2048), (jnp.bfloat16, 4096)):
        want = min(4096, int((counts >= n // div).sum()))
        assert want > 128
        want -= want % 128
        for budget in (None, v5e):
            assert hs.plan_resident_hot(counts, n, dt,
                                        hot_block_bytes=budget) == want, dt
    many = np.full(1 << 20, n, np.int64)  # every column hot: the cap binds
    assert hs.plan_resident_hot(many, n, jnp.float32) == 4096
    assert hs.plan_resident_hot(many, n, jnp.float32,
                                hot_block_bytes=v5e) == 4096


@pytest.mark.parametrize("stats, budget, columns", [
    pytest.param({"bytes_limit": V5E_BYTES, "bytes_in_use": 0},
                 8_454_668_032, 1024, id="an-empty-chip"),
    pytest.param({"bytes_limit": V5E_BYTES},
                 8_454_668_032, 1024, id="no-bytes-in-use-reported"),
    pytest.param({"bytes_limit": V5E_BYTES, "bytes_in_use": 330_000_000},
                 8_289_668_032, 1024, id="the-table-staged-first"),
    pytest.param({"bytes_limit": V5E_BYTES, "bytes_in_use": 12_000_000_000},
                 2_454_668_032, 256, id="a-chip-other-tables-fill"),
    pytest.param({"bytes_limit": V5E_BYTES, "bytes_in_use": V5E_BYTES + 1},
                 0, 8, id="nothing-free"),
    pytest.param(None, None, 4096, id="no-stats"),
    pytest.param({}, None, 4096, id="empty-stats"),
    pytest.param("cpu", None, 4096, id="the-cpu-mesh"),
])
def test_the_coordinate_takes_the_budget_from_its_mesh(stats, budget,
                                                       columns):
    """Half of what the mesh's device has free as it is asked; the CPU
    reports no limit, and then the counts alone decide, as before the block
    had a budget. ``columns`` is what the planner makes of the budget at the
    cell's 2M rows when every column clears the count threshold: whole lane
    tiles where the bytes bind, ``max_hot`` where they do not."""
    mesh = (make_mesh(devices=jax.devices()[:1]) if stats == "cpu"
            else _Mesh(stats))
    got = sparse_fixed.hot_block_budget(mesh)  # the guard's call: mesh alone
    assert got == budget
    rows = 2_000_000
    counts = np.full(8192, rows, np.int64)
    k = hs.plan_resident_hot(counts, rows, jnp.float32, hot_block_bytes=got)
    assert k == columns
    if budget:
        assert k * rows * 4 <= budget and k % 128 == 0


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("rows, part_rows", [(1000, 1000), (1000, 999),
                                             (1000, 256), (1000, 1),
                                             (7, 1000)])
def test_the_hot_block_crosses_in_row_parts_and_arrives_whole(
        monkeypatch, rows, part_rows, dtype):
    """Over 4 GiB one ``device_put`` takes the slow path, so the block goes
    over in row parts written in place: whatever the part's length, the
    array on the device is the host's, on the coordinate's own sharding."""
    import ml_dtypes
    from jax.sharding import NamedSharding, PartitionSpec

    dt = np.dtype(ml_dtypes.bfloat16 if dtype == "bfloat16" else np.float32)
    x = np.random.default_rng(rows + part_rows).standard_normal(
        (rows, 24)).astype(dt)
    monkeypatch.setattr(sparse_fixed, "_PUT_PART_BYTES",
                        part_rows * 24 * dt.itemsize)
    sharding = NamedSharding(make_mesh(devices=jax.devices()[:1]),
                             PartitionSpec())
    got = sparse_fixed._put_in_parts(x, sharding)
    assert got.sharding == sharding and got.dtype == dt
    assert np.array_equal(np.asarray(got), x)
    empty = sparse_fixed._put_in_parts(x[:, :0], sharding)  # no hot column
    assert empty.shape == (rows, 0)


def _chunk_columns(hb) -> list[np.ndarray]:
    """Per class, the permuted column of every row: the remainder chunks'
    from the map, then the run of columns whose top chunk the class holds."""
    out, off = [], 0
    chunk_cols = np.asarray(hb.chunk_cols)
    for start, L, rems, rows in zip(hb.class_starts, hb.class_lens,
                                    hb.class_rems, hb.cold_rowids):
        tops = rows.size // L - rems
        out.append(hb.num_hot + np.concatenate(
            [chunk_cols[off: off + rems], start + np.arange(tops)]))
        off += rems
    assert off == chunk_cols.size
    return out


def _dense(hb) -> np.ndarray:
    """The (n, d) matrix a hybrid layout holds, in the original columns."""
    n, d = int(hb.labels.shape[0]), hb.num_features
    out = np.zeros((n + 1, d), np.float64)
    hot = np.asarray(hb.X_hot)
    if hb.hot_scale is not None:  # the count block: float32(count) * scale
        hot = hot.astype(np.float32) * np.asarray(hb.hot_scale)
    out[:n, :hb.num_hot] = hot.astype(np.float64)
    for cols, L, rows, vals in zip(_chunk_columns(hb), hb.class_lens,
                                   hb.cold_rowids, hb.cold_vals):
        rows, vals = np.asarray(rows), np.asarray(vals, np.float64)
        if L < 128:  # a narrow class is held lane-major, (L, C)
            assert rows.shape[0] == L
            rows, vals = rows.T, vals.T
        np.add.at(out, (rows, cols[:, None].repeat(rows.shape[1], 1)), vals)
    return out[:n][:, np.asarray(hb.inv_perm)]


def _tiles(k: int) -> int:
    """A block wider than a lane tile keeps whole tiles (ISSUE 35)."""
    return k - k % 128 if k > 128 else k


def test_every_number_of_the_layout_is_what_it_was():
    """At a small size the layout holds what the old column-capped build
    held: the columns of count >= the threshold are hot, in count order, and
    hot block and cold classes together are the batch's matrix."""
    batch, _ = sp_data.synthetic_sparse(4096, 512, 8, seed=3)
    hb = hs.build_hybrid(batch)
    idx, val = np.asarray(batch.indices), np.asarray(batch.values)
    live = (idx < 512) & (val != 0)
    counts = np.bincount(idx[live], minlength=512)
    # max(8, 4096 // 2048), in whole lane tiles
    assert hb.num_hot == _tiles(int((counts >= 8).sum())) == 128
    order = np.argsort(-counts, kind="stable")
    assert np.array_equal(np.asarray(hb.perm), order)
    want = np.zeros((4096, 513))
    np.add.at(want, (np.arange(4096)[:, None].repeat(idx.shape[1], 1), idx),
              np.where(live, val, 0.0))
    np.testing.assert_array_equal(_dense(hb), want[:, :512])
    assert hb.entries == (int(counts[order[:hb.num_hot]].sum()),
                          int(live.sum() - counts[order[:hb.num_hot]].sum()))


def test_two_slots_of_a_row_that_meet_in_a_hot_column_add_up():
    idx = np.array([[0, 0, 1], [0, 2, 2], [1, 0, 3]], np.int32)
    val = np.array([[1, 2, 4], [8, 16, 32], [64, 128, 256]], np.float32)
    batch = SparseBatch(indices=idx, values=val,
                        labels=np.zeros(3, np.float32),
                        weights=np.ones(3, np.float32),
                        offsets=np.zeros(3, np.float32), num_features=4)
    want = np.array([[3, 4, 0, 0], [8, 0, 48, 0], [128, 64, 0, 256.]])
    for max_hot in (0, 2, 4):
        hb = hs.build_hybrid(batch, hot_threshold=1, max_hot=max_hot)
        assert hb.num_hot == max_hot
        np.testing.assert_array_equal(_dense(hb), want)
    shb = hs.build_hybrid_shards(batch, 1, hot_threshold=1, max_hot=4)
    np.testing.assert_array_equal(
        np.asarray(shb.X_hot[0])[:, np.asarray(shb.inv_perm)], want)
    # the streamed chunks' hot block, which assigned until PR 29
    for num_hot in (2, 4):
        ch = ss._build_canonical(batch, 4, num_hot, jnp.float32)
        got = np.zeros((3, 5))
        got[:, np.asarray(ch.hot_cols)] += np.asarray(ch.X_hot)
        np.add.at(got, (np.arange(3)[:, None].repeat(3, 1),
                        np.asarray(ch.cold_cols)), np.asarray(ch.cold_vals))
        np.testing.assert_array_equal(got[:, :4], want)


# -- the cold columns as binary chunks (ISSUE 30) ------------------------------

def _batch_of_counts(counts, n=600, seed=5):
    """An ELL batch whose column j holds exactly ``counts[j]`` non-zeros, and
    its dense (n, d) matrix."""
    rng = np.random.default_rng(seed)
    d = len(counts)
    X = np.zeros((n, d), np.float32)
    for j, c in enumerate(counts):
        X[rng.choice(n, size=c, replace=False), j] = rng.uniform(
            0.5, 1.5, size=c).astype(np.float32)
    width = max(1, int((X != 0).sum(1).max()))
    idx = np.full((n, width), d, np.int32)  # d: an empty slot
    val = np.zeros((n, width), np.float32)
    for i in range(n):
        cols = np.flatnonzero(X[i])
        idx[i, :cols.size], val[i, :cols.size] = cols, X[i, cols]
    return SparseBatch(indices=idx, values=val,
                       labels=rng.integers(0, 2, n).astype(np.float32),
                       weights=np.ones(n, np.float32),
                       offsets=np.zeros(n, np.float32), num_features=d), X


_POWERS = [1 << b for b in range(9)]
_COUNTS = {
    "powers": _POWERS,
    "beside-powers": sorted({c + s for c in _POWERS[1:] for s in (-1, 1)}),
    "ties-and-absent": [300, 300, 129, 128, 128, 127, 64, 13, 13, 2, 1, 1,
                        0, 0],
    "one-entry-columns": [1] * 40,
}


@pytest.mark.parametrize("split", ["no-hot", "two-hot", "all-hot"])
@pytest.mark.parametrize("counts", list(_COUNTS))
def test_a_cold_column_is_one_full_chunk_for_every_set_bit_of_its_count(
        counts, split):
    batch, X = _batch_of_counts(_COUNTS[counts])
    n, d = X.shape
    hb = hs.build_hybrid(batch, **{
        "no-hot": dict(max_hot=0), "two-hot": dict(hot_threshold=1, max_hot=2),
        "all-hot": dict(hot_threshold=1, max_hot=d)}[split])
    np.testing.assert_array_equal(_dense(hb), X)
    by_count = np.sort(np.asarray(_COUNTS[counts]))[::-1]
    cold = by_count[hb.num_hot:]
    assert hb.num_hot == {"no-hot": 0, "two-hot": 2,
                          "all-hot": int((by_count > 0).sum())}[split]
    # every non-zero exactly once: the matrix is the batch's, in as many cells
    slots = sum(int(r.size) for r in hb.cold_rowids)
    assert hb.entries == (int(by_count[:hb.num_hot].sum()), int(cold.sum()))
    assert slots == hb.entries[1]  # cold_slots == cold_entries: no padding
    assert int((np.asarray(hb.X_hot) != 0).sum()) == hb.entries[0]
    assert all(int(np.asarray(r).max()) < n for r in hb.cold_rowids)
    assert len(hb.class_lens) <= int(cold.max(initial=0)).bit_length()
    assert list(hb.class_lens) == sorted(set(hb.class_lens), reverse=True)
    # a column's chunks are the set bits of its count, its top chunk by slice
    chunks: dict[int, list[int]] = {}
    for cols, L, rems in zip(_chunk_columns(hb), hb.class_lens,
                             hb.class_rems):
        for i, col in enumerate(cols - hb.num_hot):
            assert (i < rems) == (cold[col] >= 2 * L)
            chunks.setdefault(int(col), []).append(L)
    assert {c: sum(Ls) for c, Ls in chunks.items()} == {
        c: int(v) for c, v in enumerate(cold) if v}
    assert all(len(set(Ls)) == len(Ls) for Ls in chunks.values())


def _float64_passes(batch, w, v, r):
    """Margins, row gradient, Hessian-vector product and Hessian diagonal of
    the logistic objective over an ELL batch, in float64 numpy."""
    idx = np.asarray(batch.indices)
    val = np.where(idx < batch.num_features, np.asarray(batch.values,
                                                        np.float64), 0.0)
    idx = np.minimum(idx, batch.num_features - 1)
    wts = np.asarray(batch.weights, np.float64)

    def rows_to_columns(rows, vals):
        out = np.zeros(batch.num_features)
        np.add.at(out, idx, rows[:, None] * vals)
        return out

    z = np.asarray(batch.offsets, np.float64) + (val * w[idx]).sum(1)
    s = 1.0 / (1.0 + np.exp(-z))
    d2 = wts * s * (1.0 - s)
    return (z, rows_to_columns(r, val),
            rows_to_columns(d2 * (val * v[idx]).sum(1), val),
            rows_to_columns(d2, val * val))


@pytest.mark.parametrize("d", [4096, 1 << 20])
def test_the_four_passes_agree_with_a_float64_product(d):
    cell = small_cell(3000, d)
    data = game_criteo.make(20260930, cell["configuration"])
    rng = np.random.default_rng(d)
    # Two fields of a row that hash to one column stay apart here: squared
    # they are a² + b² as cold slots and (a + b)² in the hot block's cell.
    idx = np.sort(data.indices, axis=1)
    again = np.zeros(idx.shape, bool)
    again[:, 1:] = idx[:, 1:] == idx[:, :-1]
    batch = dataclasses.replace(
        _batch(data), indices=idx,
        values=np.where(again, 0.0, data.values).astype(np.float32),
        weights=rng.uniform(0.5, 1.5, 3000).astype(np.float32),
        offsets=rng.normal(size=3000).astype(np.float32))
    hb = hs.build_hybrid(batch, max_hot=8)
    assert hb.num_hot == 8 and sum(hb.class_rems) > 0 < len(hb.class_lens)
    w, v = 0.3 * rng.normal(size=(2, d))
    r = rng.normal(size=3000)
    want = _float64_passes(batch, w, v, r)

    def permuted(x):
        return hs.to_permuted_space(hb, jnp.asarray(x, jnp.float32))

    got = (hs.margins(hb, permuted(w)),
           hs.to_original_space(hb, hs.row_gradient(
               hb, jnp.asarray(r, jnp.float32))),
           hs.to_original_space(hb, hs.hessian_vector(
               losses.LOGISTIC, permuted(w), permuted(v), hb)),
           hs.to_original_space(hb, hs.hessian_diagonal(
               losses.LOGISTIC, permuted(w), hb)))
    for name, g, f in zip(("margins", "row_gradient", "hessian_vector",
                           "hessian_diagonal"), got, want):
        assert np.abs(np.asarray(g) - f).max() < 2e-5 * np.abs(f).max(), name


def test_the_cold_dropped_fault_still_zeroes_the_cold_part_alone():
    """The seam ``benchmark/schemas/game_criteo.py`` patches: ``_cold_grad``
    by that name, of three arguments, returning a list of arrays."""
    cell = small_cell(3000, 4096)
    data = game_criteo.make(20260930, cell["configuration"])
    hb = hs.build_hybrid(_batch(data), max_hot=8)
    r = jnp.asarray(np.random.default_rng(0).normal(size=3000), jnp.float32)
    sound = np.asarray(hs.row_gradient(hb, r))
    with game_criteo.faults["cold-dropped"]():
        broken = np.asarray(hs.row_gradient(hb, r))
        diagonal = np.asarray(hs.hessian_diagonal(
            losses.LOGISTIC, jnp.zeros((4096,), jnp.float32), hb))
    np.testing.assert_array_equal(broken[:8], sound[:8])
    assert np.all(broken[:8] != 0) and not broken[8:].any()
    assert np.count_nonzero(sound[8:]) > 1000
    assert np.all(diagonal[:8] > 0) and not diagonal[8:].any()
    np.testing.assert_array_equal(np.asarray(hs.row_gradient(hb, r)), sound)


@pytest.mark.parametrize("shards", [1, 4])
def test_the_data_sharded_layout_keeps_a_padded_row_a_column(shards):
    """Its shards share the class shapes while a column's count differs by
    shard, so it has no chunk map: the same passes take its columns by slice
    and give what the one-shard chunks give."""
    batch, X = _batch_of_counts(_COUNTS["ties-and-absent"], n=600)
    hb = hs.build_hybrid(batch, hot_threshold=1, max_hot=2)
    shb = hs.build_hybrid_shards(batch, shards, hot_threshold=1, max_hot=2)
    rng = np.random.default_rng(2)
    w = jnp.asarray(rng.normal(size=X.shape[1]), jnp.float32)
    z = np.asarray(hs.margins(hb, hs.to_permuted_space(hb, w)))
    np.testing.assert_allclose(z, X.astype(np.float64) @ np.asarray(w),
                               rtol=1e-5, atol=1e-5)
    got_z, got_g = [], 0.0
    for s in range(shards):
        local = hs.local_shard(shb, *jax.tree.map(
            lambda a: jnp.asarray(a[s: s + 1]),
            (shb.X_hot, shb.cold_rowids, shb.cold_vals, shb.labels,
             shb.weights, shb.offsets)))
        assert local.class_rems == (0,) * len(shb.class_lens)
        assert local.chunk_cols.size == 0
        got_z.append(np.asarray(hs.margins(local, w[shb.perm])))
        got_g = got_g + np.asarray(hs.row_gradient(
            local, jnp.asarray(z[s * 600 // shards: (s + 1) * 600 // shards]))
        )[np.asarray(shb.inv_perm)]
    np.testing.assert_allclose(np.concatenate(got_z), z, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(
        got_g, np.asarray(hs.to_original_space(hb, hs.row_gradient(
            hb, jnp.asarray(z)))), rtol=1e-5, atol=1e-4)
    # padded still: more slots than entries where counts are no powers of two
    assert sum(int(r.size) for r in shb.cold_rowids) > shb.entries[1]


@pytest.mark.parametrize("d", [4096, 1 << 20])
def test_any_hot_block_gives_the_same_model(d):
    """Hot block of 0, 8 and every present column (at d = 2**20, where that
    would be a hundred thousand columns, the default split): the split is an
    execution choice that leaves the objective as it is."""
    cell = small_cell(3000, d)
    data = game_criteo.make(20260929, cell["configuration"])
    batch = _batch(data)
    everything = dict(hot_threshold=1, max_hot=d) if d == 4096 else {}
    newton = dataclasses.replace(_opt(), optimizer=dataclasses.replace(
        _opt().optimizer, optimizer_type=OptimizerType.TRON))
    models, stalled = [], []
    for kw in (dict(max_hot=0), dict(max_hot=8), everything):
        hb = hs.build_hybrid(batch, hot_block_bytes=1 << 40, **kw)
        for opt, into in ((newton, models), (_opt(), stalled)):
            coef, res = jax.jit(
                lambda hb: sp.run_hybrid(losses.LOGISTIC, hb, opt))(hb)
            into.append((np.asarray(coef.means), float(res.value),
                         float(res.grad_norm)))
        assert sum(hb.entries) == 3000 * 39
    assert hs.build_hybrid(batch, max_hot=0).num_hot == 0
    present = np.unique(data.indices).size
    if d == 4096:
        # every present column but the last ragged tile's
        assert hb.num_hot == _tiles(present) > present - 128
        assert hb.entries[1] < 0.01 * hb.entries[0]
    # The layouts are held to 1e-3 by the Newton solver, whose steps end
    # within 8e-5 (3.6e-4 at d = 2**20) of one another. L-BFGS, the cell's
    # solver, stops where its float32 value (1.6e3, on a grid of 1.2e-4) stops
    # moving, 22 to 25 iterations in and with a gradient of 0.01 to 0.11
    # left: its three models lie 9.9e-4 to 1.04e-3 apart with either order
    # of a cold column's sum. So it is held to the same value, and to what
    # the gradient it stopped at allows: the objective is 1-strongly convex
    # (L2 weight 1), so a model lies within |g| of the optimum.
    best = models[0][0]
    for m, _, _ in models[1:]:
        assert np.linalg.norm(m - best) < 1e-3 * np.linalg.norm(best)
        np.testing.assert_allclose(m, best, rtol=0, atol=4e-3)
    for m, value, g in stalled:
        assert value == pytest.approx(models[0][1], rel=1e-6)
        assert np.linalg.norm(m - best) < g + models[0][2]


# -- the line search that crosses the data twice an iteration ------------------

def _line_problem(max_hot):
    cell = small_cell(3000, 4096)
    data = game_criteo.make(20260929, cell["configuration"])
    hb = hs.build_hybrid(_batch(data), hot_block_bytes=1 << 40,
                         max_hot=max_hot)
    mask = jnp.ones((4096,), jnp.float32)
    vg = with_l2(lambda w: hs.value_and_gradient(losses.LOGISTIC, w, hb),
                 1.0, mask)
    return hb, vg, sp._hybrid_line(losses.LOGISTIC, hb, 1.0, mask)


@pytest.mark.parametrize("max_hot", [0, 8])
def test_the_oracle_reads_the_objective_along_the_line(max_hot):
    """``start`` is an evaluation, a trial the value and the slope at w + αd,
    ``accept`` the value and the gradient there, and its carry the margins."""
    hb, vg, line = _line_problem(max_hot)
    rng = np.random.default_rng(3)
    w = jnp.asarray(0.1 * rng.standard_normal(4096), jnp.float32)
    d = jnp.asarray(rng.standard_normal(4096), jnp.float32)
    f, g, z = line.start(w)
    np.testing.assert_allclose(f, vg(w)[0], rtol=1e-6)
    np.testing.assert_allclose(g, vg(w)[1], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(z, hs.margins(hb, w), rtol=1e-6)
    ray = line.along(z, w, d)
    for alpha in (0.0, 0.25, 2.0):
        want_f, want_g = vg(w + alpha * d)
        got_f, slope = line.trial(ray, jnp.float32(alpha))
        np.testing.assert_allclose(got_f, want_f, rtol=2e-6)
        np.testing.assert_allclose(slope, jnp.dot(want_g, d), rtol=2e-4)
        got_f, got_g, carry = line.accept(ray, jnp.float32(alpha))
        np.testing.assert_allclose(got_f, want_f, rtol=2e-6)
        np.testing.assert_allclose(got_g, want_g, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(carry, hs.margins(hb, w + alpha * d),
                                   rtol=1e-5, atol=1e-5)


def test_a_trial_makes_no_pass_over_the_features():
    """What a trial reads: rows and columns, never the hot block or a cold
    class. The direction's margins cross the data once, the gradient once."""
    hb, _, line = _line_problem(8)
    w = jnp.zeros((4096,), jnp.float32)
    _, _, z = line.start(w)
    ray = line.along(z, w, w + 1.0)
    features = {a.shape for a in (hb.X_hot, *hb.cold_rowids)}

    def reads(fn, *args):
        jaxpr = jax.make_jaxpr(fn)(*args)
        return {v.aval.shape for e in jaxpr.jaxpr.eqns for v in e.invars
                if hasattr(v, "aval")} & features

    assert not reads(line.trial, ray, jnp.float32(0.5))
    assert reads(lambda z, w, d: line.along(z, w, d), z, w, w + 1.0)
    assert reads(line.accept, ray, jnp.float32(0.5))


@pytest.mark.parametrize("max_hot", [0, 8])
def test_an_iteration_costs_one_evaluation_whatever_the_search_needed(
        max_hot):
    """The same solve with and without the oracle: the same model to the
    solver's stopping slack; without it the trials are evaluations (more
    than one an iteration on this data), with it an iteration is one pair
    of passes however many trials it took."""
    hb, vg, line = _line_problem(max_hot)
    cfg = _opt().optimizer
    w0 = jnp.zeros((4096,), jnp.float32)
    plain = jax.jit(lambda w: optimize(vg, w, cfg))(w0)
    along = jax.jit(lambda w: optimize(vg, w, cfg, line=line))(w0)
    assert int(plain.evaluations) > int(plain.iterations) + 1
    assert int(along.evaluations) == int(along.iterations) + 1
    assert float(along.value) <= float(plain.value) * (1 + 1e-6)
    assert np.linalg.norm(along.w - plain.w) < 1e-3 * np.linalg.norm(plain.w)
    # nothing but L-BFGS asks the oracle
    with pytest.raises(ValueError, match="OWL-QN"):
        minimize_lbfgs(vg, w0, cfg, l1_weights=jnp.ones_like(w0), line=line)


# -- the inside view: scopes, counters, rows -----------------------------------

@pytest.fixture(scope="module")
def run(tmp_path_factory):
    """One fit of the new cell's files at 4,000 rows and d = 2**20, five
    sweeps, with a ledger; the compile requests and traces of each update."""
    cell = small_cell(4000, 1 << 20, max_iterations=25)
    data = game_criteo.make(1234567891, cell["configuration"])
    counts = {"requests": 0, "traces": 0}

    def on_event(event, **kw):
        if event.endswith("/compile_requests_use_cache"):
            counts["requests"] += 1

    def on_duration(event, duration_secs, **kw):
        if event == "/jax/core/compile/jaxpr_trace_duration":
            counts["traces"] += 1

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    per_update, last = [], dict(counts)

    def on_update(ev):
        if isinstance(ev, events.CoordinateUpdate):
            per_update.append((ev.iteration, ev.coordinate,
                               {k: counts[k] - last[k] for k in counts}))
            last.update(counts)

    model, rows = fit(cell, data, 5, tmp_path_factory.mktemp("ledger"),
                      on_update)
    obs.set_ledger(None)
    return {"cell": cell, "data": data, "model": model, "rows": rows,
            "per_update": per_update}


def test_nothing_is_traced_after_the_set_up_sweeps(run):
    """``setup_sweeps: 2``: from the third sweep on no update asks the
    compiler for anything (the sparse fit used to be traced once more in
    sweep 3, because its warm start arrived with another sharding)."""
    assert [(i, c) for i, c, _ in run["per_update"]] == [
        (i, c) for i in range(5) for c in SEQ]
    late = [(i, c, n) for i, c, n in run["per_update"]
            if i >= 2 and (n["requests"] or n["traces"])]
    assert not late, late
    # and the fit program itself is traced once, in the first sweep
    assert [n["requests"] for i, c, n in run["per_update"]
            if c == "fixed"][1:] == [0, 0, 0, 0]


def test_the_ledger_has_the_layout_the_phases_and_the_evaluations(run):
    rows = run["rows"]
    layout = [r for r in rows if r.get("kind") == "fe_layout"]
    assert len(layout) == 1
    lay = layout[0]
    assert lay["hot_entries"] + lay["cold_entries"] == 4000 * 39
    # every value is 1/sqrt(39): int8 counts and a float32 scale a column
    assert lay["hot_storage"] == "count8"
    assert lay["hot_bytes"] == lay["num_hot"] * (4000 + 4)
    assert lay["hot_columns_f32"] == lay["hot_exact_candidates"] == lay[
        "num_hot"]
    # the CPU offers no bytes and max_hot is far: the count threshold bound
    assert lay["hot_budget_bytes"] is None
    assert _tiles(lay["hot_candidates"]) == lay["num_hot"]
    assert lay["cold_slots"] == lay["cold_entries"] > 0 < lay["num_hot"]
    assert lay["cold_entries"] > lay["cold_chunks"] > 0  # chunks of 2^b >= 1
    # at most one class for every bit of the largest cold count
    assert 0 < lay["cold_classes"] <= (4000).bit_length()
    phases = {r["name"]: r for r in rows if r.get("kind") == "phase"}
    assert phases["fe.transfer"]["bytes"] > lay["hot_bytes"]
    assert phases["fe.host_stage"]["parent"] == "fit.coordinates"
    for sweep in range(5):
        its = [r for r in rows if r.get("kind") == "opt_iter"
               and r.get("coordinate") == "fixed"
               and r.get("outer_iteration") == sweep]
        assert [r["iteration"] for r in its] == list(range(len(its)))
        assert [r for r in its if "evaluations" in r] == its[-1:]
        assert its[-1]["evaluations"] >= its[-1]["iteration"] + 1


@pytest.mark.parametrize("shards", [1, 2])
@pytest.mark.parametrize("budget, bound", [
    (None, "threshold"), (4 * 4000 * 24, "bytes")])
def test_the_layout_row_says_what_bound_the_block(run, tmp_path, monkeypatch,
                                                  shards, budget, bound):
    """``hot_budget_bytes`` is what the coordinate offered and
    ``hot_candidates`` the columns over the count threshold before bytes or
    ``max_hot`` cut them, read off the layout on either form of it: equal
    to ``num_hot`` where the threshold bound the block, more where the
    bytes did."""
    data = run["data"]
    counts = np.bincount(data.indices.reshape(-1), minlength=1 << 20)
    monkeypatch.setattr(sparse_fixed, "hot_block_budget",
                        lambda mesh, solver_bytes=0, deferred_bytes=0: budget)
    led = obs.RunLedger.create(str(tmp_path))
    obs.set_ledger(led)
    SparseFixedEffectCoordinate(
        game_criteo.dataset(data), "global", losses.LOGISTIC, _opt(5),
        make_mesh(devices=jax.devices()[:shards]))
    obs.set_ledger(None)
    led.close()
    lay, = [r for r in read_rows(str(tmp_path))[0]
            if r.get("kind") == "fe_layout"]
    assert lay["hot_budget_bytes"] == budget
    assert lay["hot_candidates"] == int((counts >= 8).sum())  # max(8, n/2048)
    if bound == "threshold":
        assert lay["num_hot"] == _tiles(lay["hot_candidates"])
    elif shards == 1:  # 24 float32 columns fit, or 95 of counts and a scale
        assert lay["hot_storage"] == "count8"
        assert lay["hot_columns_f32"] == 24
        assert lay["num_hot"] == 384000 // 4004 == 95 < lay["hot_candidates"]
    else:  # a device holds half the rows; the sharded layout keeps float32
        assert lay["hot_storage"] == "float32"
        assert lay["num_hot"] == lay["hot_columns_f32"] == 48 < lay[
            "hot_candidates"]
    assert lay["hot_entries"] + lay["cold_entries"] == 4000 * 39


def test_the_sparse_fit_lowers_with_every_scope(run):
    data = run["data"]
    coord = SparseFixedEffectCoordinate(
        game_criteo.dataset(data), "global", losses.LOGISTIC, _opt(5),
        make_mesh(devices=jax.devices()[:1]))
    n = data.response.shape[0]
    text = coord._fit.lower(coord._staged, jnp.zeros((n,), jnp.float32),
                            jnp.zeros((coord.dim,), jnp.float32)
                            ).as_text(debug_info=True)
    for scope in ("fe.fit", "glm.value_grad", "fe.hot", "fe.cold",
                  "lbfgs.line_search", "lbfgs.direction"):
        assert scope in text, scope
    text = coord._score.lower(coord._staged,
                              jnp.zeros((coord.dim,), jnp.float32)
                              ).as_text(debug_info=True)
    for scope in ("fe.score", "fe.hot", "fe.cold"):
        assert scope in text, scope
    assert "glm.value_grad" not in text


# -- the program against the plain reference -----------------------------------

def compared(cell, data, model, rows, sweeps):
    served = game_criteo.model_arrays(model, cell["mix"])
    return {k: v["value"] for k, v in criteo_reference.check(
        data, cell, served, rows, sweeps).items()}


def test_the_program_agrees_with_the_reference_at_the_full_width(run):
    got = compared(run["cell"], run["data"], run["model"], run["rows"], 5)
    assert set(got) == {"loss_1", "loss_2", "loss_3", "grad0", "coef.fixed",
                        "coef.per-c10", "small.fixed", "small.per-c10"}
    assert max(got["loss_1"], got["loss_2"], got["loss_3"]) < 1e-3, got
    assert got["grad0"] < 1e-4, got
    assert got["coef.fixed"] < 2e-2 and got["coef.per-c10"] < 2e-2, got
    assert got["small.fixed"] < 3e-4 and got["small.per-c10"] < 3e-4, got


def test_the_program_agrees_with_the_reference_at_4096_columns(tmp_path):
    cell = small_cell(4000, 4096)
    data = game_criteo.make(987654321, cell["configuration"])
    model, rows = fit(cell, data, 4, tmp_path / "ledger")
    got = compared(cell, data, model, rows, 4)
    assert max(got["loss_1"], got["loss_2"], got["loss_3"]) < 1e-3, got
    assert got["coef.fixed"] < 2e-2 and got["coef.per-c10"] < 2e-2, got
    assert got["small.fixed"] < 3e-4 and got["small.per-c10"] < 3e-4, got


def test_the_reference_imports_nothing_of_the_program():
    import ast
    for name in ("criteo_reference.py", "reference.py"):
        with open(os.path.join(BENCH, name)) as f:
            tree = ast.parse(f.read())
        names = {a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
                 for a in n.names} | {
            n.module for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)}
        assert not any(m and m.startswith("photon_ml_tpu") for m in names)
