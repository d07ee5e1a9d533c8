"""The deployment of ISSUE 35, program side, at a size the CPU holds: a GLMix
click model whose random effect reads a *sparse* shard, each publisher solved
in the subspace of the columns it has seen (``game/projector.py``, the
pipelined stager, the projected bucket solves). The system's fit agrees with
the schema's plain reference (``benchmark/avazu_reference.py``), which knows no
projection; the projected fit equals an unprojected fit of the densified
shard and writes nothing off a publisher's subspace; ``max_samples`` keeps the
subset ``reference.py`` documents; the hot block's budget leaves the table's
deferred blocks their room; and the new phase, layout row and wave counters
are there and count what a numpy count of the same buckets counts."""

from __future__ import annotations

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu import obs
from photon_ml_tpu.data.game_data import GameDataset
from photon_ml_tpu.game import buckets as bkt
from photon_ml_tpu.game.coordinates import (RandomEffectCoordinate,
                                            SparseFixedEffectCoordinate,
                                            sparse_fixed)
from photon_ml_tpu.obs.ledger import read_rows
from photon_ml_tpu.ops import losses
from photon_ml_tpu.parallel.mesh import make_mesh

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
BENCH = os.path.join(REPO, "benchmark")
for _p in (BENCH, os.path.join(BENCH, "layer_metrics"),
           os.path.join(BENCH, "schemas")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import game_avazu  # noqa: E402  (benchmark/schemas/game_avazu.py)
import game_kdd12  # noqa: E402  (its optimisation blocks)
import reference  # noqa: E402  (benchmark/reference.py)

CELL = "avazu-sparse-re.steady"
ROWS = 12000
V5E_BYTES = 16_909_336_064  # ``bytes_limit`` of one v5e chip


@pytest.fixture(autouse=True)
def _clean_obs_state():
    yield
    obs.set_ledger(None)
    obs.disable()


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def small_cell(rows: int = ROWS) -> dict:
    return {"configuration": game_avazu.shrink(
                load("configs", "glmix-avazu-logistic-sparse-re.json"), rows),
            "mix": load("traffic", "steady-fixed-publisher.json"),
            "settings": load("workloads", CELL + ".json")}


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    """Three sweeps of the cell's own estimator on seeded rows of the shrunk
    schema, its ledger, and the comparison with the plain reference."""
    cell = small_cell()
    data = game_avazu.make(31, cell["configuration"])
    ledger_dir = str(tmp_path_factory.mktemp("ledger"))
    est = game_avazu.estimator(cell, make_mesh(devices=jax.devices()[:1]), 3,
                               ledger_dir, "float32")
    model = est.fit(game_avazu.dataset(data))[0].model
    rows = read_rows(ledger_dir)[0]
    obs.set_ledger(None)
    served = game_avazu.model_arrays(model, cell["mix"])
    numbers = game_avazu.check(data, cell, served, rows, 3)
    return {"cell": cell, "data": data, "rows": rows, "served": served,
            "numbers": numbers}


COMPARED = ("loss_1", "grad0", "coef.fixed", "small.fixed",
            "coef.per-publisher", "small.per-publisher",
            "capped.per-publisher", "rows.per-publisher",
            "offspace.per-publisher")


@pytest.mark.parametrize("name", COMPARED)
def test_the_fit_agrees_with_the_plain_reference(run, name):
    """Every key the cell compares, under the configuration's own limit
    (``loss_2..3`` need a fourth sweep: the benchmark's rehearsal has
    them)."""
    got = run["numbers"][name]
    assert got["value"] <= got["limit"], (name, got)
    assert set(run["numbers"]) == set(COMPARED) | {"loss_2", "loss_3"}


def test_max_samples_binds_in_the_rehearsal_too(run):
    """``shrink`` scales the cap with the rows, so the heaviest publishers
    are capped at the small size as they are at the cell's."""
    cap = game_avazu._settings(run["cell"])["max_samples"]
    assert cap == 65536 * ROWS // 2_000_000
    counts = np.bincount(run["data"].entity_ids)
    assert 2 <= (counts > cap).sum() <= 20
    lay = [r for r in run["rows"] if r["kind"] == "re_layout"]
    assert len(lay) == 1 and lay[0]["entities_capped"] == (counts > cap).sum()


def test_nothing_is_written_off_a_publisher_s_subspace(run):
    """Exactly 0.0 on every (publisher, column) no training row names."""
    data, W = run["data"], run["served"]["per-publisher"]
    seen = np.zeros(W.shape, bool)
    seen[data.entity_ids[:, None], data.table_indices] = True
    assert (W[~seen] == 0.0).all()
    assert np.count_nonzero(W[seen]) > 0.9 * seen.sum()
    assert run["numbers"]["offspace.per-publisher"] == {"value": 0.0,
                                                        "limit": 0}


# -- the tie between projection and the plain model ---------------------------

def _coordinate(ds, cell, shard, mesh, **kw):
    return RandomEffectCoordinate(
        ds, "publisher", shard, losses.LOGISTIC,
        game_kdd12._optimization(
            dict(cell["settings"]["optimizers"]["per-publisher"],
                 max_iterations=200, tolerance=1e-9)),
        mesh, **kw)


def test_the_projected_fit_equals_the_unprojected_fit_of_the_dense_shard():
    cell = small_cell(4000)
    data = game_avazu.make(5, cell["configuration"])
    n, D = data.response.shape[0], data.table_features
    dense = np.zeros((n, D), np.float32)
    dense[np.arange(n)[:, None], data.table_indices] = data.table_values
    ds = game_avazu.dataset(data)
    ds.feature_shards["dense"] = dense
    ds.intercept_index["dense"] = D - 1
    mesh = make_mesh(devices=jax.devices()[:1])
    offsets = jnp.asarray(np.random.default_rng(0).normal(
        0, 0.3, n).astype(np.float32))
    sparse = _coordinate(ds, cell, "re_publisher", mesh)
    plain = _coordinate(ds, cell, "dense", mesh)
    assert sparse.projection and not plain.projection
    assert not sparse.subspace  # the table stays (E, d)
    W_p = np.asarray(sparse.train_model(offsets).means)
    W_u = np.asarray(plain.train_model(offsets).means)
    seen = np.zeros(W_p.shape, bool)
    seen[data.entity_ids[:, None], data.table_indices] = True
    # every off-subspace coefficient is exactly 0 in the projected fit, and
    # the unprojected one leaves them at the L2 term's minimum, 0 too
    assert (W_p[~seen] == 0.0).all()
    np.testing.assert_allclose(W_u[~seen], 0.0, atol=1e-6)
    # a publisher whose rows carry one label has no finite optimum for its
    # unregularised intercept: the two solves stop where they stop. The
    # others reach one minimum.
    clicks = np.bincount(data.entity_ids, data.response, W_p.shape[0])
    rows = np.bincount(data.entity_ids, minlength=W_p.shape[0])
    both = (clicks >= 2) & (rows - clicks >= 2)
    assert both.sum() > 50
    keep = seen & both[:, None]
    np.testing.assert_allclose(W_p[keep], W_u[keep], rtol=5e-3, atol=2e-3)
    mine = both[data.entity_ids]
    np.testing.assert_allclose(
        np.asarray(sparse.score(sparse.train_model(offsets)))[mine],
        np.asarray(plain.score(plain.train_model(offsets)))[mine],
        rtol=2e-3, atol=2e-3)


# -- max_samples --------------------------------------------------------------

def test_max_samples_keeps_the_subset_reference_py_documents():
    rng = np.random.default_rng(3)
    ids = rng.permutation(np.concatenate([
        np.full(300, 2), np.full(170, 5), rng.integers(6, 40, 400),
        np.full(64, 0)])).astype(np.int32)
    cap = 64
    b = bkt.build_bucketing(ids, 40, upper_bound=cap,
                            rng=np.random.default_rng(0))
    assert b.num_capped_entities == 2  # entity 0 has exactly the cap
    want = reference.capped_training_rows(ids, 40, cap)
    got = np.zeros(ids.shape[0], np.float32)
    for bucket in b.buckets:
        got[bucket.example_idx[bucket.example_idx >= 0]] = 1.0
    assert np.array_equal(got, want)
    for e in (2, 5):
        assert want[ids == e].sum() == cap
    assert b.num_passive_examples == (300 - cap) + (170 - cap)


# -- the hot block's budget and the deferred blocks ---------------------------

class _Device:
    def __init__(self, stats):
        self.stats = stats

    def memory_stats(self):
        return self.stats


class _Mesh:
    def __init__(self, stats):
        self.devices = np.array([_Device(stats)], object)


def test_the_budget_leaves_the_deferred_blocks_their_room():
    mesh = _Mesh({"bytes_limit": V5E_BYTES, "bytes_in_use": 400_000_000})
    solver = 163_577_856  # d = 2**20 under L-BFGS, history 10
    table = 7_330_244_160
    was = sparse_fixed.hot_block_budget(mesh, solver)
    now = sparse_fixed.hot_block_budget(mesh, solver, deferred_bytes=table)
    assert was == (V5E_BYTES - 400_000_000 - solver) // 2
    assert now == was - table // 2
    # what was: the block and the table pass 85% of the device; what is:
    # they do not
    assert 400_000_000 + solver + was + table > 0.85 * V5E_BYTES
    assert 400_000_000 + solver + now + table < 0.85 * V5E_BYTES
    # a job with nothing deferred plans as it did
    assert sparse_fixed.hot_block_budget(mesh, solver, 0) == was
    assert sparse_fixed.hot_block_budget(mesh, solver, 2 * V5E_BYTES) == 0
    assert sparse_fixed.hot_block_budget(_Mesh({}), solver, table) is None


def test_a_wide_hot_block_keeps_whole_lane_tiles_whatever_bound_it():
    """The count threshold passes 2,277 columns and the bytes would hold
    them: the block is 17 whole tiles, as it is where the bytes bind
    (PERF.md section 6, PR 35: a pass over the ragged width cost twice)."""
    from photon_ml_tpu.ops import hybrid_sparse as hs
    counts = np.concatenate([np.full(2277, 5000), np.full(10000, 3)])
    n = 2_000_000
    assert hs.plan_resident_hot(counts, n, "int8") == 2176
    assert hs.plan_resident_hot(counts, n, "int8",
                                hot_block_bytes=4_576_000_000) == 2176
    assert hs.plan_resident_hot(counts, n, "int8",
                                hot_block_bytes=4_000_000_000) == 1920
    assert hs.plan_resident_hot(counts, n, jnp.float32,
                                hot_block_bytes=4_576_000_000) == 512
    # a block under one tile is as wide as its bound leaves it
    assert hs.plan_resident_hot(counts[2200:], n, "int8") == 77
    assert hs.plan_resident_hot(counts, n, "int8", max_hot=100) == 100
    assert hs.plan_resident_hot(counts, n, "int8",
                                hot_block_bytes=90 * (n + 4)) == 90


def test_the_estimator_builds_the_projected_table_first(monkeypatch):
    """and hands the fixed effect the bytes the table has yet to stage,
    which are what the table then stages."""
    cell = small_cell(4000)
    data = game_avazu.make(9, cell["configuration"])
    seen = {}
    init = SparseFixedEffectCoordinate.__init__

    def spy(self, *a, **kw):
        seen["deferred_bytes"] = kw.get("deferred_bytes")
        init(self, *a, **kw)
    monkeypatch.setattr(SparseFixedEffectCoordinate, "__init__", spy)
    est = game_avazu.estimator(cell, make_mesh(devices=jax.devices()[:1]), 1,
                               None, "float32")
    coords = est._build_coordinates(
        game_avazu.dataset(data),
        {cid: c.optimization for cid, c in est.coordinate_configs.items()})
    assert list(coords) == ["fixed", "per-publisher"]  # the order given
    table = coords["per-publisher"]
    assert seen["deferred_bytes"] > 0
    assert seen["deferred_bytes"] == table.deferred_device_bytes()
    table.wait_staged()
    staged = sum(int(a.nbytes) for t in table._bucket_data for a in t)
    assert staged == seen["deferred_bytes"]
    assert table.deferred_device_bytes() == 0
    # the guard's count, made from the rows alone, is the same plan; its
    # widths are taken over all of a publisher's rows, the program's over
    # the rows max_samples keeps, so it is an upper bound
    plan = game_avazu.table_plan(
        game_avazu._settings(cell)["max_samples"], "float32")
    assert staged <= plan["staged_bytes"] <= 1.5 * staged


def test_the_guard_refuses_a_program_whose_budget_does_not_reckon_the_table(
        monkeypatch):
    cell = small_cell(4000)
    game_avazu.make(9, cell["configuration"])
    mesh = make_mesh(devices=jax.devices()[:1])
    big = {"classes": [(8, 8, 128), (65536, 8, 1024)],
           "staged_bytes": 7_330_244_160, "useful_bytes": 3_171_698_224,
           "capped": 3}
    monkeypatch.setattr(game_avazu, "table_plan", lambda *a: big)
    stats = {"bytes_limit": V5E_BYTES, "bytes_in_use": 400_000_000}
    monkeypatch.setattr(game_avazu, "_MADE", dict(
        game_avazu._MADE, rows=2_000_000,
        counts=np.full(1 << 20, 2_000_000, np.int64)))

    class _OneDevice:
        devices = np.array([_Device(stats)], object)
    real = sparse_fixed.hot_block_budget
    _plan = game_avazu.resident_plan
    # this program: the block narrows and the job fits
    monkeypatch.setattr(game_avazu, "resident_plan",
                        lambda mesh, dt, cfg, tb: _plan(_OneDevice, dt, cfg,
                                                        tb))
    est = game_avazu.estimator(cell, mesh, 1, None, "float32")
    assert est is not None
    # the parent's: a budget that knows no deferred bytes
    monkeypatch.setattr(sparse_fixed, "hot_block_budget",
                        lambda mesh, solver_bytes=0: real(mesh, solver_bytes))
    with pytest.raises(SystemExit) as e:
        game_avazu.estimator(cell, mesh, 1, None, "float32")
    msg = str(e.value)
    assert msg.startswith("game_avazu: this program would stage 7330244160")
    assert "does not reckon" in msg and "cannot hold" in msg
    assert "\n" not in msg


# -- the phase, the layout row, the wave counters -----------------------------

def test_the_projection_pass_is_a_phase_row(run):
    rows = [r for r in run["rows"] if r["kind"] == "phase"
            and r["name"] == "re.project"]
    assert len(rows) == 1
    r = rows[0]
    assert r["label"] == "publisher:re_publisher" and r["seconds"] > 0
    assert (r["split_seconds"] + r["phase_a_seconds"] + r["phase_b_seconds"]
            <= r["seconds"] + 1e-3)
    assert isinstance(r["overlapped"], bool) and r["workers"] >= 1
    lay = [r for r in run["rows"] if r["kind"] == "re_layout"][0]
    assert r["bytes"] == lay["staged_bytes"]
    # written before the window: the reader finds it
    sys.path.insert(0, os.path.join(BENCH, "layer_metrics"))
    import phase_s
    ctx = {"ledger_rows": run["rows"], "setup_sweeps": 2}
    assert phase_s.read("phase_s.project", ctx) == r["seconds"]


def test_the_layout_row_counts_what_numpy_counts(run):
    """``re_layout`` against a numpy count of the same buckets: a class a
    row capacity, its lanes, its width; the cells the publishers' own rows x
    own columns fill."""
    cell, data = run["cell"], run["data"]
    lay = [r for r in run["rows"] if r["kind"] == "re_layout"][0]
    assert (lay["model_form"], lay["projected"], lay["dim"]) == (
        "dense", True, data.table_features)
    cap = game_avazu._settings(cell)["max_samples"]
    b = bkt.build_bucketing(data.entity_ids, data.num_entities,
                            upper_bound=cap, rng=np.random.default_rng(0))
    useful = 0
    classes = []
    for bucket in b.buckets:
        live = bucket.entity_rows >= 0
        widths = []
        for lane in np.flatnonzero(live):
            ex = bucket.example_idx[lane]
            active = np.unique(data.table_indices[ex[ex >= 0]]).size
            widths.append(active)
            useful += int((ex >= 0).sum()) * active * 4
        width = min(data.table_features,
                    max(8, 1 << int(np.ceil(np.log2(max(widths))))))
        classes.append([bucket.capacity, bucket.num_entities,
                        int(live.sum()), width])
    assert lay["classes"] == classes
    assert lay["useful_bytes"] == useful
    assert lay["entities"] == int(b.trained_entities.sum())
    assert lay["lanes"] == sum(c[1] for c in classes)
    assert lay["staged_bytes"] > sum(c[0] * c[1] * c[3] * 4 for c in classes)
    assert lay["useful_bytes"] < 0.7 * lay["staged_bytes"]


def test_the_wave_rows_count_padded_columns(run):
    waves = [r for r in run["rows"] if r["kind"] == "re_fit_wave"]
    lay = [r for r in run["rows"] if r["kind"] == "re_layout"][0]
    assert waves and all(r["coordinate"] == "per-publisher" for r in waves)
    for r in waves:
        assert 0 < r["cols_useful"] <= r["cols_padded"]
        assert r["cols_padded"] == r["lanes"] * r["cap"] * r["d_active"]
        assert r["cols_useful"] >= r["rows_useful"] * 14 // 14
        assert r["cols_useful"] <= r["rows_useful"] * r["d_active"]
    for sweep in range(3):
        mine = [r for r in waves if r["outer_iteration"] == sweep]
        assert sum(r["cols_useful"] for r in mine) * 4 == lay["useful_bytes"]
        assert [[r["cap"], r["d_active"]] for r in mine] == [
            [c[0], c[3]] for c in lay["classes"]]
    # the new readers read them
    import width_pad_share
    ctx = {"ledger_rows": run["rows"], "setup_sweeps": 2}
    share = width_pad_share.read("width_pad_share.per-publisher", ctx)
    padded = sum(c[0] * c[1] * c[3] * 4 for c in lay["classes"])
    assert share == pytest.approx(100 * (1 - lay["useful_bytes"] / padded))
    import pad_share
    assert share > pad_share.read("pad_share.per-publisher", ctx)
    assert width_pad_share.read("width_pad_share.per-publisher", dict(
        ctx, ledger_rows=[dict(r, cols_padded=None) for r in waves])) is None


def test_a_dense_table_s_waves_carry_no_column_counts(tmp_path):
    """The unprojected path writes its ``re_layout`` row and wave rows
    without ``d_active``: every accepted reader reads what it read."""
    rng = np.random.default_rng(1)
    n, E = 600, 20
    ds = GameDataset(
        response=(rng.random(n) < 0.3).astype(np.float32),
        offsets=np.zeros(n, np.float32), weights=np.ones(n, np.float32),
        feature_shards={"re": np.concatenate(
            [rng.normal(size=(n, 3)), np.ones((n, 1))], 1).astype(np.float32)},
        entity_ids={"e": rng.integers(0, E, n).astype(np.int32)},
        num_entities={"e": E}, intercept_index={"re": 3})
    from photon_ml_tpu.obs.ledger import RunLedger
    led = RunLedger.create(str(tmp_path / "led"))
    obs.set_ledger(led)
    cell = small_cell(4000)
    coord = RandomEffectCoordinate(
        ds, "e", "re", losses.LOGISTIC, game_kdd12._optimization(
            cell["settings"]["optimizers"]["per-publisher"]),
        make_mesh(devices=jax.devices()[:1]))
    assert coord.deferred_device_bytes() == 0
    coord.train_model(jnp.zeros((n,), jnp.float32))
    led.drain()
    led.close()
    obs.set_ledger(None)
    rows = read_rows(str(tmp_path / "led"))[0]
    lay = [r for r in rows if r["kind"] == "re_layout"]
    assert len(lay) == 1 and lay[0]["projected"] is False
    assert lay[0]["entities_capped"] == 0
    assert all(c[3] == 4 for c in lay[0]["classes"])
    waves = [r for r in rows if r["kind"] == "re_fit_wave"]
    assert waves and all("d_active" not in r and "cols_useful" not in r
                         for r in waves)
    assert not [r for r in rows if r["kind"] == "phase"
                and r["name"] == "re.project"]
