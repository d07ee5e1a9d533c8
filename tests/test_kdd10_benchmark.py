"""The KDD Cup 2010 cell, benchmark side, on the CPU: the cell rehearsed
through ``benchmark/run.py`` reads what it read when recorded (limits, keys,
readings and ``argv`` from
``benchmark/selfcheck/kdd10.rehearsal.expected.json``); the whole
two-coordinate fit against ``benchmark/kdd10_reference.py`` is ``correct``;
the ``bfloat16`` control and the ``values-flattened`` fault are not; the
``ratio-ignored`` fault is (TRON's steps are accepted at ratios far over
its thresholds on this loss too: PERF.md section 6); the selfcheck holds the
new schema to the contract; the new readers read a hand-made ledger and
trace; and ``BENCHMARK.json`` gained the entries and lost nothing."""

from __future__ import annotations

import json
import os
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
BENCH = os.path.join(REPO, "benchmark")
for _p in (BENCH, os.path.join(BENCH, "layer_metrics"),
           os.path.join(BENCH, "schemas")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import faults  # noqa: E402  (benchmark/faults.py)
import game_kdd10  # noqa: E402  (benchmark/schemas/game_kdd10.py)
from record_scoped import field, plane  # noqa: E402  (its xplane encoder)

CELL = "kdd10-algebra-tron.steady"
CONFIG = "glmix-kdd10-algebra-logistic-tron"
EXPECTED = os.path.join(BENCH, "selfcheck", "kdd10.rehearsal.expected.json")
LIMITS = {"loss_1", "loss_2", "loss_3", "grad0", "coef.fixed",
          "coef.per-student", "small.fixed", "small.per-student"}
SHARED = {"stage_s", "update_s.fixed", "fe_iters", "sweep_mfu",
          "device_idle_share", "phase_s.digest", "phase_s.bucketing",
          "phase_s.host_stage", "phase_s.transfer", "phase_s.program_load",
          "scope_s.value_grad", "scope_s.gather_scatter", "scope_s.score",
          "sparse_s.hot", "sparse_s.cold", "hot_entry_share",
          "fe_hot_roofline", "fe_cold_roofline", "fe_pass_roofline",
          "cg_steps.fixed", "tron_s",
          "tron_cg_roofline", "setup_wall_s.staging",
          "setup_wall_s.program_load", "setup_wall_s.compile_wait",
          "setup_wall_s.stage_wait", "setup_wall_s.sweeps",
          "setup_wall_s.other", "program_load_wall_s"}
NEW_METRICS = ({f"{s}.per-student" for s in ("update_s", "re_iters",
                                              "cg_steps", "pad_share",
                                              "lane_util", "cg_util")}
               | {"fe_hvp_s", "fe_hvp_roofline"})


@pytest.fixture(scope="module")
def run():
    return faults.load_run()


@pytest.fixture(scope="module")
def want():
    with open(EXPECTED) as f:
        return json.load(f)


def result(run, capsys, want, *extra):
    assert run.main([*want["argv"], *extra]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def over(out):
    return {k for k, v in out["compared"].items() if v["value"] > v["limit"]}


def test_the_rehearsal_reads_what_it_read(run, capsys, want):
    """The whole two-coordinate fit, TRON on both, against the plain
    reference's block descent over the same sweeps: every number inside
    its limit and where it was recorded."""
    out = result(run, capsys, want)
    assert out["correct"] is want["correct"] is True, out["compared"]
    assert (out["attempted"], out["failed"]) == (want["attempted"], 0)
    assert out["window"]["sweeps"] == want["window_sweeps"]
    assert out["window"]["asked_in_window"] == 0
    assert sorted(out["metrics"]) == want["metrics"] == ["setup_s", "sweep_s"]
    assert out["compared"].keys() == want["compared"].keys() == LIMITS
    conf = run.load_cell(CELL)["configuration"]["check"]["limits"]
    for name, v in want["compared"].items():
        got = out["compared"][name]
        assert got["limit"] == v["limit"] == conf[name], name
        assert got["value"] == pytest.approx(v["value"], rel=1e-4,
                                             abs=1e-12), name
        assert got["value"] <= got["limit"], name


def test_control_bfloat16_is_not_correct(run, capsys, want):
    """bf16 storage rounds the hot block's values 1/sqrt(L), which the
    first gradient reads at once."""
    out = result(run, capsys, want, "--control", "bfloat16")
    assert out["correct"] is False, out["compared"]
    assert "grad0" in over(out)
    assert out["compared"]["grad0"]["value"] > 20 * want["compared"][
        "grad0"]["value"]


def test_values_flattened_is_not_correct(run, capsys, want):
    """Every hot column's values replaced by its first, as if the shard
    were one-valued: another data set, which every loss and the fixed
    effect's coefficients read."""
    with faults.planted("values-flattened", run, CELL):
        out = result(run, capsys, want)
    assert out["correct"] is False, out["compared"]
    assert {"grad0", "loss_1", "loss_2", "loss_3", "coef.fixed",
            "small.fixed"} <= over(out)


def test_ratio_ignored_is_not_seen(run, capsys, want):
    """Every step accepted: TRON's steps on this logistic objective are
    accepted by the ratio test anyway but near float32's floor, where a
    rejection only refuses a decrease the objective cannot resolve, so no
    comparison of results tells the fault from the sound program."""
    with faults.planted("ratio-ignored", run, CELL):
        out = result(run, capsys, want)
    assert out["correct"] is True, out["compared"]


def test_the_selfcheck_holds_the_new_schema_to_the_contract(run, capsys):
    assert run.main(["--selfcheck"]) == 0
    err = capsys.readouterr().err
    assert f"{CONFIG}: game_kdd10 ok" in err
    assert err.count("selfcheck check_generator: ok") >= 5
    assert err.count("selfcheck check_work: ok") >= 6


def test_the_entries_are_added_and_nothing_that_was_there_is_changed(run):
    """One configuration and one cell at the end of their lists, the cell's
    name appended to the lists of the 29 readers it shares, 8 new metrics
    of its own; every reader is found by name; the configuration keeps the
    published width and mean row length."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [c["name"] for c in bench["configs"]][-1] == CONFIG
    assert [w["name"] for w in bench["workloads"]][-1] == CELL
    assert len(bench["configs"]) == len(bench["workloads"]) == 6
    assert all(w["chips"] == 1 for w in bench["workloads"])
    assert bench["configs"][-1]["reduced"] == ["num_rows",
                                               "lbfgs_max_iterations"]
    assert all(1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
               for c in bench["configs"])
    assert all(1 <= len(w["why"]) <= 200 for w in bench["workloads"])
    cell = run.load_cell(CELL)
    assert {m["name"] for m in cell["end_to_end"]} == {"sweep_s", "setup_s"}
    mine = {m["name"] for m in cell["per_layer"]}
    assert mine == SHARED | NEW_METRICS
    for m in cell["per_layer"]:
        assert callable(run.layer_reader(m["name"])), m["name"]
        assert m["workloads"][-1] == CELL
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL] and m["moves"] == "sweep_s"
    for old in ("ml20m-logistic.steady", "criteo-1m-logistic.steady",
                "kdd12-poisson-l1.steady", "avazu-sparse-re.steady",
                "yahoo-music-tron.steady"):
        theirs = {m["name"] for m in run.load_cell(old)["per_layer"]}
        assert not theirs & NEW_METRICS, old
    conf = cell["configuration"]
    assert (conf["num_features"], conf["nonzeros_per_row"]) == (20216830,
                                                                36.35)
    game_kdd10.field_offsets(conf)  # the fields fill the width exactly
    assert (conf["entity"]["name"], conf["entity"]["count"],
            conf["entity"]["features"]) == ("student", 3310, 4)
    assert (conf["task"], conf["storage_dtype"]) == ("logistic", "float32")
    assert set(conf["reduced"]) == {"num_rows", "lbfgs_max_iterations"}
    opts = cell["settings"]["optimizers"]
    assert set(opts) == {"fixed", "per-student"}
    assert all((o["optimizer"], o["regularization"], o["reg_weight"],
                o["max_iterations"]) == ("TRON", "L2", 1.0, 25)
               for o in opts.values())
    assert cell["settings"]["max_samples"] == 65536
    assert cell["mix"]["update_sequence"] == ["fixed", "per-student"]
    assert (cell["mix"]["setup_sweeps"], cell["mix"]["min_window_sweeps"],
            cell["mix"]["locked_coordinates"]) == (2, 3, [])
    assert set(conf["check"]["limits"]) == LIMITS
    assert set(game_kdd10.faults) == {"values-flattened", "ratio-ignored"}


# -- the new readers ----------------------------------------------------------

def _trace(tmp_path):
    """A traced sweep whose device plane holds a product's operations under
    ``fe.hvp`` (inside TRON's CG loop), an evaluation's outside it, and a
    product after the sweep's last marker."""
    fit = "jit(fit)/fe.fit/while/body/"
    ops = {1: ("%fusion.a = f32[8]",
               fit + "tron.cg/while/body/fe.hvp/fe.cold/scatter-add:"),
           2: ("%fusion.b = f32[8]", fit + "glm.value_grad/fe.cold/gather:"),
           3: ("%fusion.c = f32[8]",
               fit + "tron.cg/while/body/fe.hvp/fe.hot/dot_general:")}
    k = 1000  # ns -> ps
    device = plane("/device:TPU:0", [
        ("XLA Ops", 0, [(1, 1100 * k, 300 * k), (2, 1400 * k, 200 * k),
                        (3, 2000 * k, 500 * k), (3, 2300 * k, 400 * k),
                        (1, 5200 * k, 100 * k)])], ops)
    host = plane("/host:CPU", [("python3", 0, [
        (1, 0, 1), (2, 3000 * k, 1)])],
        {1: ("bench.mark.start", None),
         2: ("bench.mark.3.per-student", None)})
    d = tmp_path / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    (d / "x.xplane.pb").write_bytes(field(1, host) + field(1, device))
    return {"traced_sweep": 3, "trace": {"busy_s": 1.0},
            "trace_dir": str(tmp_path),
            "cell": {"mix": {"update_sequence": ["fixed", "per-student"]}}}


def test_fe_hvp_s_reads_the_products_of_the_traced_sweep(tmp_path):
    """a: 300 ns; c: [2000, 2700) = 700 ns; b is no product and the last a
    lies after the sweep."""
    import fe_hvp_roofline
    import fe_hvp_s
    ctx = _trace(tmp_path)
    assert fe_hvp_s.read("fe_hvp_s", ctx) == pytest.approx(1000e-9)

    class Schema:
        @staticmethod
        def bytes_needed(kernel, ctx):
            return 819 if kernel == "fe_hvp" else None

    ctx.update(schema=Schema, peak={"hbm_bytes_per_s": 819e9})
    assert fe_hvp_roofline.read("fe_hvp_roofline", ctx) == pytest.approx(
        100 * 1e-9 / 1000e-9)


def test_the_products_readers_read_nothing_without_the_scope(tmp_path):
    """The parent's program has no ``fe.hvp`` scope and counts no products
    on the sparse coordinate's rows: both new readers and the schema's
    product and pass bytes read nothing there."""
    import fe_hvp_roofline
    import fe_hvp_s
    ctx = _trace(tmp_path)
    ctx["trace_dir"] = str(tmp_path / "none")
    assert fe_hvp_s.read("fe_hvp_s", ctx) is None
    ctx.update(schema=game_kdd10, peak={"hbm_bytes_per_s": 819e9},
               ledger_rows=[{"kind": "opt_iter", "coordinate": "fixed",
                             "outer_iteration": 3, "iteration": 0}])
    assert fe_hvp_roofline.read("fe_hvp_roofline", ctx) is None
    assert game_kdd10.bytes_needed("fe_hvp", ctx) is None
    assert game_kdd10.bytes_needed("fe_pass", ctx) is None
