"""The deployment of ISSUE 33, program side, at a size the CPU holds: GAME
Poisson click counts with log-impression offsets, an L1 fixed effect through
OWL-QN's ``ValueOracle`` over the resident sparse layout beside one L2 table.
The system's fit agrees with the schema's plain reference
(``benchmark/kdd12_reference.py``) on seeded data, the data's offsets reach
the fit, columns no row touches come back exactly 0.0, the hot block's budget
reckons the solver, and the new scope, counters and ledger fields are
there."""

from __future__ import annotations

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu import obs
from photon_ml_tpu.data.sparse import SparseBatch
from photon_ml_tpu.game.coordinates import sparse_fixed
from photon_ml_tpu.obs.ledger import read_rows
from photon_ml_tpu.ops import hybrid_sparse as hs
from photon_ml_tpu.ops import losses
from photon_ml_tpu.optim import (OptimizerConfig, OptimizerType,
                                 RegularizationContext, RegularizationType)
from photon_ml_tpu.optim.problem import GLMOptimizationConfiguration
from photon_ml_tpu.parallel import sparse_problem as sp
from photon_ml_tpu.parallel.mesh import make_mesh

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
BENCH = os.path.join(REPO, "benchmark")
for _p in (BENCH, os.path.join(BENCH, "layer_metrics"),
           os.path.join(BENCH, "schemas")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import game_kdd12  # noqa: E402  (benchmark/schemas/game_kdd12.py)
import kdd12_reference  # noqa: E402  (benchmark/kdd12_reference.py)

CELL = "kdd12-poisson-l1.steady"
V5E_BYTES = 16_909_336_064  # ``bytes_limit`` of one v5e chip
KDD12_COLUMNS = 54_686_452


@pytest.fixture(autouse=True)
def _clean_obs_state():
    yield
    obs.set_ledger(None)
    obs.disable()


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def small_cell(rows: int, max_iterations: int) -> dict:
    """The new cell's files at ``rows`` rows, both solvers' caps raised."""
    settings = load("workloads", CELL + ".json")
    for o in settings["optimizers"].values():
        o["max_iterations"] = max_iterations
    return {"configuration": game_kdd12.shrink(
                load("configs", "glmix-kdd12-poisson-l1.json"), rows),
            "mix": load("traffic", "steady-fixed-advertiser.json"),
            "settings": settings}


def fit(cell, data, sweeps, ledger_dir):
    est = game_kdd12.estimator(
        cell, make_mesh(devices=jax.devices()[:1]), sweeps, str(ledger_dir),
        "float32")
    model = est.fit(game_kdd12.dataset(data))[0].model
    return model, read_rows(str(ledger_dir))[0]


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    """One fit of the small cell, solved to the end: 4 sweeps, both solvers
    with 200 iterations."""
    cell = small_cell(12000, 200)
    data = game_kdd12.make(11, cell["configuration"])
    model, rows = fit(cell, data, 4, tmp_path_factory.mktemp("ledger"))
    return {"cell": cell, "data": data, "rows": rows,
            "served": game_kdd12.model_arrays(model, cell["mix"])}


# -- the fit against the plain reference --------------------------------------

def test_the_fit_agrees_with_the_plain_reference(run):
    """Both solved to their own rule, four sweeps: OWL-QN's block minima are
    the accelerated proximal gradient's. The limits are this size's: the
    cell's own, read on the chip, are in its configuration file."""
    cell, data = run["cell"], run["data"]
    ref = kdd12_reference.train(data, cell["mix"], cell["settings"], 4,
                                run["served"])
    got = kdd12_reference.compare(
        ref, run["served"], run["rows"], cell["mix"],
        cell["configuration"]["check"]["nnz_band"])
    assert set(got) == set(cell["configuration"]["check"]["limits"])
    assert got["grad0"] < 1e-4, got
    for k in (1, 2, 3):  # the whole objective, L1 term included
        assert got[f"loss_{k}"] < 2e-3, got
    assert got["coef.fixed"] < 0.1 and got["small.fixed"] < 2e-3, got
    assert got["zeros.fixed"] == 0, got
    assert got["coef.per-advertiser"] < 0.02, got
    assert got["small.per-advertiser"] < 1e-3, got
    # L1 pruned: the reference keeps a few per cent of the touched columns
    touched = int((~ref["untouched"]).sum())
    assert 0.01 * touched < ref["nnz"][-1] < 0.5 * touched


def test_columns_no_row_touches_come_back_exactly_zero(run):
    data, w = run["data"], run["served"]["fixed"]
    counts = np.bincount(data.indices.reshape(-1),
                         minlength=data.num_features)
    assert (counts == 0).sum() > 100
    assert not w[counts == 0].any()  # 0.0 or -0.0, never a small number
    assert 0 < np.count_nonzero(w) < (counts > 0).sum()


def test_the_ledger_rows_carry_the_solve_s_counts(run):
    rows = run["rows"]
    lay, = [r for r in rows if r.get("kind") == "fe_layout"]
    data = run["data"]
    touched = np.unique(data.indices).size
    assert lay["touched_columns"] == touched
    assert lay["solver_state_bytes"] == sparse_fixed.solver_state_bytes(
        data.num_features, game_kdd12._optimization(
            run["cell"]["settings"]["optimizers"]["fixed"]))
    assert lay["solver_state_bytes"] == 4 * data.num_features * (20 + 24 + 2)
    its = [r for r in rows if r.get("kind") == "opt_iter"
           and r.get("coordinate") == "fixed"]
    assert its and all(r["opt"] == "owlqn" for r in its)
    for sweep in range(4):
        solve = sorted((r for r in its if r["outer_iteration"] == sweep),
                       key=lambda r: r["iteration"])
        assert solve[0]["trials"] == 0 and solve[0]["crossings"] == 2
        for r in solve[1:]:  # a trial a pass, one more for the gradient
            assert r["trials"] >= 1 and r["crossings"] == r["trials"] + 1
            assert 0 < r["nnz"] <= touched
        assert solve[-1]["evaluations"] == 1 + sum(
            r["trials"] for r in solve)
    assert its[-1]["nnz"] == np.count_nonzero(run["served"]["fixed"])
    # the table's solves are L-BFGS under L2: no such counts on their rows
    waves = [r for r in rows if r.get("kind") == "re_fit_wave"]
    assert waves and all("trials" not in r for r in waves)


def test_the_new_readers_read_the_run(run):
    ctx = {"cell": run["cell"], "ledger_rows": run["rows"],
           "traced_sweep": 3, "setup_sweeps": 2, "trace": None,
           "trace_dir": None, "schema": game_kdd12,
           "peak": {"hbm_bytes_per_s": 819e9}}
    sys.path.insert(0, os.path.join(BENCH))
    import faults
    bench = faults.load_run()
    share = bench.layer_reader("coef_nnz_share.fixed")(
        "coef_nnz_share.fixed", ctx)
    lay, = [r for r in run["rows"] if r.get("kind") == "fe_layout"]
    assert share == pytest.approx(100.0 * np.count_nonzero(
        run["served"]["fixed"]) / lay["touched_columns"])
    solve = [r for r in run["rows"] if r.get("kind") == "opt_iter"
             and r.get("coordinate") == "fixed"
             and r["outer_iteration"] == 3]
    crossed = sum(r["crossings"] for r in solve)
    n, fields = run["data"].indices.shape
    assert game_kdd12.bytes_needed("fe_pass", ctx) == crossed * n * fields * 8
    assert game_kdd12.bytes_needed("fe_hot", ctx) == (
        crossed * lay["hot_entries"] * 8)
    assert game_kdd12.bytes_needed("fe_vec", ctx) > 0
    assert game_kdd12.sweep_flops(ctx) > (crossed + 1) * 2 * n * fields
    # no trace: the device readers read nothing and do not raise
    for name in ("owlqn_s.orthant", "fe_vec_roofline"):
        assert bench.layer_reader(name)(name, ctx) is None
    # a program that writes none of it (the parent): nothing, no raise
    bare = dict(ctx, ledger_rows=[
        {k: v for k, v in r.items()
         if k not in ("trials", "nnz", "crossings", "touched_columns")}
        for r in run["rows"]])
    assert bench.layer_reader("coef_nnz_share.fixed")(
        "coef_nnz_share.fixed", bare) is None
    assert game_kdd12.bytes_needed("fe_pass", bare) is None
    assert game_kdd12.sweep_flops(bare) is None


# -- data offsets -------------------------------------------------------------

def _fixed_only(data):
    cell = small_cell(data.response.shape[0], 200)
    mix = dict(cell["mix"], update_sequence=["fixed"],
               coordinates={"fixed": cell["mix"]["coordinates"]["fixed"]})
    return dict(cell, mix=mix)


def test_the_data_s_offsets_reach_the_fixed_effect_s_fit(tmp_path):
    """Through ``GameEstimator.fit`` the data's offsets are descent's
    ``base``; the staged batch carries zeros. The fit equals the solver
    given the same rows with the offsets in the batch itself, and differs
    from the fit of the rows without them."""
    cell = _fixed_only(game_kdd12.make(3, small_cell(6000, 200)[
        "configuration"]))
    data = game_kdd12.make(3, cell["configuration"])
    assert data.offsets.max() > 2.0 and (data.offsets == 0).mean() > 0.5
    model, _ = fit(cell, data, 1, tmp_path / "with")
    w = game_kdd12.model_arrays(model, cell["mix"])["fixed"]

    n = data.response.shape[0]
    cfg = game_kdd12._optimization(cell["settings"]["optimizers"]["fixed"])

    def direct(offsets):
        hb = hs.build_hybrid(SparseBatch(
            data.indices, data.values, data.response,
            np.ones(n, np.float32), offsets, data.num_features))
        coef, res = jax.jit(lambda hb: sp.run_hybrid(
            losses.POISSON, hb, cfg))(hb)
        return np.asarray(coef.means), float(res.value)

    w_direct, value = direct(data.offsets)
    np.testing.assert_allclose(w, w_direct, rtol=1e-4, atol=1e-5)
    w_dropped, value_dropped = direct(np.zeros(n, np.float32))
    assert np.linalg.norm(w - w_dropped) > 0.05 * np.linalg.norm(w)
    assert abs(value - value_dropped) > 0.01 * abs(value)

    with game_kdd12.faults["offsets-dropped"]():
        model, _ = fit(cell, data, 1, tmp_path / "dropped")
    np.testing.assert_allclose(
        game_kdd12.model_arrays(model, cell["mix"])["fixed"], w_dropped,
        rtol=1e-4, atol=1e-5)


# -- the hot block's budget ---------------------------------------------------

class _Device:
    def __init__(self, stats):
        self.stats = stats

    def memory_stats(self):
        return self.stats


class _Mesh:
    """A stand-in for a mesh over one device that reports ``stats``."""

    def __init__(self, stats):
        self.devices = np.array([_Device(stats)], object)


def _config(kind, reg, m=10):
    return GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(optimizer_type=kind, history_length=m),
        regularization=RegularizationContext(reg, 1.0))


def test_the_budget_takes_the_solver_s_bytes_off_before_it_halves():
    mesh = _Mesh({"bytes_limit": V5E_BYTES, "bytes_in_use": 27_136})
    # the Criteo cell: 0.16 GB of solver, 1024 columns as before
    lbfgs = _config(OptimizerType.LBFGS, RegularizationType.L2)
    solver = sparse_fixed.solver_state_bytes(1 << 20, lbfgs)
    assert solver == 4 * (1 << 20) * (20 + 17 + 2)
    was = sparse_fixed.hot_block_budget(mesh)
    assert was == 8_454_654_464  # PERF.md section 6, PR 32
    now = sparse_fixed.hot_block_budget(mesh, solver)
    assert now == was - solver // 2 and now >= 1024 * 2_000_000 * 4
    counts = np.full(8192, 2_000_000, np.int64)
    assert hs.plan_resident_hot(counts, 2_000_000, jnp.float32,
                                hot_block_bytes=now) == 1024
    # this deployment: the solver is most of the chip, the block narrows
    owlqn = _config(OptimizerType.OWLQN, RegularizationType.L1)
    solver = sparse_fixed.solver_state_bytes(KDD12_COLUMNS, owlqn)
    assert solver == 4 * KDD12_COLUMNS * 46 == 10_062_307_168
    budget = sparse_fixed.hot_block_budget(mesh, solver)
    assert budget == (V5E_BYTES - 27_136 - solver) // 2
    assert hs.plan_resident_hot(counts, 3_000_000, jnp.float32,
                                hot_block_bytes=budget) == 256
    assert hs.plan_resident_hot(counts, 2_000_000, jnp.float32,
                                hot_block_bytes=budget) == 384
    # without the subtraction the block and the solver pass the device
    assert was + solver > V5E_BYTES
    # L1 under an L-BFGS configuration is OWL-QN's solve; a longer history
    # is more vectors; TRON keeps none; a solver that fills the device
    # leaves the block nothing, not a negative budget; the CPU reports none
    assert sparse_fixed.solver_state_bytes(
        KDD12_COLUMNS, _config(OptimizerType.LBFGS, RegularizationType.L1)
    ) == solver
    assert sparse_fixed.solver_state_bytes(
        1000, _config(OptimizerType.LBFGS, RegularizationType.L2, m=20)
    ) == 4000 * (40 + 17 + 2)
    assert sparse_fixed.solver_state_bytes(
        1000, _config(OptimizerType.TRON, RegularizationType.L2)
    ) == 4000 * (9 + 2)
    assert sparse_fixed.hot_block_budget(mesh, 2 * V5E_BYTES) == 0
    assert sparse_fixed.hot_block_budget(_Mesh({}), solver) is None


def test_the_guard_reckons_the_solver(monkeypatch):
    """This program plans its block after its solver and fits; a program
    whose budget does not reckon the solver (the parent) would put a block
    of half the device beside 10 GB of vectors, and the guard exits before
    anything is allocated."""
    rows = 3_000_000
    counts = np.full(4096, rows, np.int64)
    monkeypatch.setitem(game_kdd12._MADE, "counts", counts)
    monkeypatch.setitem(game_kdd12._MADE, "rows", rows)
    monkeypatch.setitem(game_kdd12._MADE, "columns", KDD12_COLUMNS)
    mesh = _Mesh({"bytes_limit": V5E_BYTES, "bytes_in_use": 27_136})
    cell = {"settings": load("workloads", CELL + ".json"),
            "mix": load("traffic", "steady-fixed-advertiser.json"),
            "configuration": load("configs", "glmix-kdd12-poisson-l1.json")}
    cfg = game_kdd12._optimization(cell["settings"]["optimizers"]["fixed"])
    plan = game_kdd12.resident_plan(mesh, "float32", cfg)
    assert plan["reckons"] and plan["num_hot"] == 256
    assert plan["solver_bytes"] + plan["hot_bytes"] < 0.8 * V5E_BYTES
    monkeypatch.delattr(sparse_fixed, "solver_state_bytes")
    plan = game_kdd12.resident_plan(mesh, "float32", cfg)
    assert not plan["reckons"] and plan["num_hot"] == 640
    assert plan["solver_bytes"] + plan["hot_bytes"] > V5E_BYTES
    with pytest.raises(SystemExit) as e:
        game_kdd12.estimator(cell, mesh, 5, "unused", "float32")
    assert "640 columns" in str(e.value) and "cannot hold" in str(e.value)
    assert "does not reckon" in str(e.value)


# -- the scope ----------------------------------------------------------------

def test_the_orthant_scope_is_on_the_pseudo_gradient_the_cut_and_the_projection():
    data = game_kdd12.make(5, small_cell(3000, 5)["configuration"])
    n = data.response.shape[0]
    hb = hs.build_hybrid(SparseBatch(
        data.indices, data.values, data.response, np.ones(n, np.float32),
        data.offsets, data.num_features))
    cfg = _config(OptimizerType.OWLQN, RegularizationType.L1)
    text = jax.jit(lambda hb: sp.run_hybrid(losses.POISSON, hb, cfg)).lower(
        hb).as_text(debug_info=True)
    for path in ("owlqn.orthant",  # the pseudo-gradient, before the loop
                 "lbfgs.direction/owlqn.orthant",  # the direction's cut
                 # a trial's projection, and its one pass
                 "lbfgs.line_search/while/body/owlqn.orthant",
                 "lbfgs.line_search/while/body/glm.value_grad/fe.cold"):
        assert path in text, path
    lbfgs = _config(OptimizerType.LBFGS, RegularizationType.L2)
    text = jax.jit(lambda hb: sp.run_hybrid(losses.POISSON, hb, lbfgs)).lower(
        hb).as_text(debug_info=True)
    assert "owlqn.orthant" not in text


# -- the permutation of a space most of whose columns have no row -------------

def test_only_the_touched_head_is_permuted_where_the_rest_is_zero():
    """54.7M columns, 2.5M of them touched: a whole gather of the
    coefficients is a second of a v5e, three times a sweep. Either branch
    gives what the whole gather gives; a layout whose columns nearly all
    have a row keeps the whole gather."""
    data = game_kdd12.make(5, dict(  # 3,000 rows over 60,000 rows' columns
        small_cell(60000, 5)["configuration"], num_rows=3000))
    n, d = data.response.shape[0], data.num_features
    hb = hs.build_hybrid(SparseBatch(
        data.indices, data.values, data.response, np.ones(n, np.float32),
        data.offsets, d), hot_threshold=50)
    touched = np.unique(data.indices)
    assert hb.num_touched == touched.size and 2 * touched.size <= d
    assert hs._touched_head(hb) == touched.size
    perm, inv = np.asarray(hb.perm), np.asarray(hb.inv_perm)
    assert set(perm[:touched.size]) == set(touched)
    rng = np.random.default_rng(0)
    sparse = np.zeros(d, np.float32)
    sparse[touched] = rng.standard_normal(touched.size)
    dense = rng.standard_normal(d).astype(np.float32)
    there = jax.jit(lambda w: hs.to_permuted_space(hb, w))
    back = jax.jit(lambda w: hs.to_original_space(hb, w))
    for w in (sparse, dense, np.zeros(d, np.float32)):
        np.testing.assert_array_equal(there(jnp.asarray(w)), w[perm])
        np.testing.assert_array_equal(back(jnp.asarray(w[perm])), w)
        np.testing.assert_array_equal(back(there(jnp.asarray(w))), w)
    text = there.lower(jnp.asarray(sparse)).as_text()
    assert "stablehlo.case" in text or "stablehlo.if" in text
    # nearly every column touched: one gather, no branch
    full = hs.build_hybrid(SparseBatch(
        (np.arange(n * 4).reshape(n, 4) % 1000).astype(np.int32),
        np.ones((n, 4), np.float32), data.response, np.ones(n, np.float32),
        data.offsets, 1000))
    assert full.num_touched == 1000 and hs._touched_head(full) == 0
    text = jax.jit(lambda w: hs.to_permuted_space(full, w)).lower(
        jnp.zeros(1000)).as_text()
    assert "stablehlo.case" not in text and "stablehlo.if" not in text
