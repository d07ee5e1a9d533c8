"""The Yahoo! Music cell, benchmark side, on the CPU: the cell rehearsed
through ``benchmark/run.py`` reads what it read when recorded (limits, keys
and ``argv`` from ``benchmark/selfcheck/music.rehearsal.expected.json``,
readings from ``tests/data/music.rehearsal.floor.json``); the ``bfloat16``
control and the ``artist-misjoined`` fault are not correct; the
``ratio-ignored`` fault is, and reads the reference closer than the sound
program (on a quadratic the ratio test only rejects steps whose decrease
float32 cannot see: PERF.md section 6); the selfcheck holds the new schema
to the contract; the new readers read a hand-made ledger and trace; and
``BENCHMARK.json`` gained the entries and lost nothing."""

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
import game_music  # noqa: E402  (benchmark/schemas/game_music.py)
from record_scoped import field, plane  # noqa: E402  (its xplane encoder)

CELL = "yahoo-music-tron.steady"
EXPECTED = os.path.join(BENCH, "selfcheck", "music.rehearsal.expected.json")
RECORDED = os.path.join(REPO, "tests", "data", "music.rehearsal.floor.json")
TABLES = ("per-user", "per-item", "per-artist")
# one-row artists are not compared (the configuration's check says why)
LIMITS = ({"loss_1", "loss_2", "loss_3", "grad0", "coef.fixed",
           "small.per-user", "small.per-item"}
          | {f"coef.{c}" for c in TABLES})
SHARED = {"stage_s", "update_s.fixed", "update_s.per-user",
          "update_s.per-item", "fe_iters", "fe_pass_roofline", "sweep_mfu",
          "device_idle_share", "re_iters.per-user", "re_iters.per-item",
          "lane_util.per-user", "lane_util.per-item", "pad_share.per-user",
          "pad_share.per-item", "phase_s.digest", "phase_s.bucketing",
          "phase_s.host_stage", "phase_s.transfer", "phase_s.program_load",
          "scope_s.value_grad", "scope_s.gather_scatter", "scope_s.score",
          "setup_wall_s.staging", "setup_wall_s.program_load",
          "setup_wall_s.compile_wait", "setup_wall_s.stage_wait",
          "setup_wall_s.sweeps", "setup_wall_s.other", "program_load_wall_s"}
NEW_METRICS = ({f"{s}.per-artist" for s in ("update_s", "re_iters",
                                             "lane_util", "pad_share")}
               | {f"cg_steps.{c}" for c in ("fixed",) + TABLES}
               | {f"cg_util.{c}" for c in TABLES}
               | {"tron_s", "tron_cg_roofline"})


@pytest.fixture(scope="module")
def run():
    return faults.load_run()


@pytest.fixture(scope="module")
def want():
    with open(EXPECTED) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def recorded(want):
    """This tree's readings. Limits, keys and ``argv`` are the
    benchmark's; its readings were recorded before TRON ended a solve at
    its objective's float32 floor, which moves where every solve lands by
    what float32 cannot resolve. ``grad0`` is taken before any step."""
    with open(RECORDED) as f:
        got = json.load(f)["compared"]
    assert got.keys() == want["compared"].keys()
    assert got["grad0"] == want["compared"]["grad0"]["value"]
    return got


def result(run, capsys, want, *extra):
    assert run.main([*want["argv"], *extra]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def over(out):
    return {k for k, v in out["compared"].items() if v["value"] > v["limit"]}


def test_the_rehearsal_reads_what_it_read(run, capsys, want, recorded):
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
        assert got["value"] == pytest.approx(recorded[name], rel=1e-4,
                                             abs=1e-12), name
        assert got["value"] <= got["limit"], name


def test_control_bfloat16_is_not_correct(run, capsys, want):
    out = result(run, capsys, want, "--control", "bfloat16")
    assert out["correct"] is False, out["compared"]
    # bf16 features move every table's block minimum by a part in a hundred
    assert {"coef.per-user", "coef.per-item"} <= over(out)
    assert out["compared"]["coef.per-user"]["value"] > 20 * want[
        "compared"]["coef.per-user"]["value"]


def test_the_artist_table_keyed_by_item_is_not_correct(run, capsys, want):
    with faults.planted("artist-misjoined", run, CELL):
        out = result(run, capsys, want)
    assert out["correct"] is False, out["compared"]
    assert {"loss_1", "loss_2", "loss_3", "coef.per-artist",
            "coef.per-user", "coef.per-item"} <= over(out)
    assert out["compared"]["grad0"]["value"] == 0.0  # the first gradient is
    # the fixed effect's, taken before any table is trained


def test_ratio_ignored_reads_the_reference_closer(run, capsys, want,
                                                  recorded):
    """Every step accepted: on the squared loss TRON's model is exact, so
    the ratio test only ever rejects steps whose decrease float32 cannot
    resolve, and a solve that takes them lands nearer the block minimum.
    No comparison of results can tell it from the sound program."""
    with faults.planted("ratio-ignored", run, CELL):
        out = result(run, capsys, want)
    assert out["correct"] is True, out["compared"]
    for c in ("fixed",) + TABLES[:2]:
        name = f"coef.{c}"
        assert out["compared"][name]["value"] < recorded[name], name


def test_the_selfcheck_holds_the_new_schema_to_the_contract(run, capsys):
    assert run.main(["--selfcheck"]) == 0
    err = capsys.readouterr().err
    assert "glmix-yahoo-music-linear-tron: game_music ok" in err
    assert err.count("selfcheck check_generator: ok") >= 4
    assert err.count("selfcheck check_work: ok") >= 5


def test_the_entries_are_added_and_nothing_that_was_there_is_changed(run):
    """One configuration and one cell at the end of their lists, the cell's
    name appended to the lists of the 29 readers it shares, 13 new metrics
    of its own; every reader is found by name; the older cells read what
    they read."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [c["name"] for c in bench["configs"]][4] == (
        "glmix-yahoo-music-linear-tron")
    assert [w["name"] for w in bench["workloads"]][4] == CELL
    assert len(bench["configs"]) == len(bench["workloads"]) >= 5
    assert all(w["chips"] == 1 for w in bench["workloads"])
    assert bench["configs"][4]["reduced"] == ["num_rows",
                                              "lbfgs_max_iterations"]
    assert all(1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
               for c in bench["configs"])
    assert all(1 <= len(w["why"]) <= 200 for w in bench["workloads"])
    later = {w["name"] for w in bench["workloads"][5:]}
    cell = run.load_cell(CELL)
    assert {m["name"] for m in cell["end_to_end"]} == {"sweep_s", "setup_s"}
    mine = {m["name"] for m in cell["per_layer"]}
    assert mine == SHARED | NEW_METRICS
    assert not any(m["name"].startswith("ls_evals") for m in
                   cell["per_layer"])  # TRON has no line search
    for m in cell["per_layer"]:
        assert callable(run.layer_reader(m["name"])), m["name"]
        # appended to the end of every list then; only later cells follow
        tail = m["workloads"][m["workloads"].index(CELL) + 1:]
        assert set(tail) <= later, m["name"]
        if m["name"] in NEW_METRICS:  # this cell's first
            assert m["workloads"][0] == CELL and m["moves"] == "sweep_s"
    for old in ("ml20m-logistic.steady", "criteo-1m-logistic.steady",
                "kdd12-poisson-l1.steady", "avazu-sparse-re.steady"):
        theirs = {m["name"] for m in run.load_cell(old)["per_layer"]}
        assert not theirs & NEW_METRICS, old
    conf = cell["configuration"]
    assert [(e["name"], e["count"], e["features"])
            for e in conf["entities"]] == [("userId", 1000990, 8),
                                           ("itemId", 624961, 8),
                                           ("artistId", 27889, 8)]
    assert conf["items"] == {"tracks": 507172, "albums": 88909,
                             "artists": 27888, "genres": 992}
    assert sum(conf["items"].values()) == 624961
    assert (conf["task"], conf["storage_dtype"], conf["global_features"]) == (
        "linear", "float32", 32)
    assert conf["num_rows"] in (16_000_000, 12_000_000)
    opts = cell["settings"]["optimizers"]
    assert set(opts) == {"fixed"} | set(TABLES)
    assert all((o["optimizer"], o["regularization"], o["reg_weight"],
                o["max_iterations"]) == ("TRON", "L2", 1.0, 25)
               for o in opts.values())
    assert cell["settings"]["max_samples"] == 65536
    assert cell["mix"]["update_sequence"] == ["fixed"] + list(TABLES)
    assert (cell["mix"]["setup_sweeps"], cell["mix"]["min_window_sweeps"],
            cell["mix"]["locked_coordinates"]) == (2, 3, [])
    assert set(conf["check"]["limits"]) == LIMITS
    assert set(game_music.faults) == {"artist-misjoined", "ratio-ignored"}


# -- the new readers ----------------------------------------------------------

def _ledger_ctx(hvps=True):
    """Window sweeps 2 and 3; the fixed effect 2 + 3 iterations with 1 and
    2 products each, a table's two waves."""
    rows = []
    for sweep, its in ((2, 2), (3, 3)):
        for i in range(its + 1):
            r = {"kind": "opt_iter", "coordinate": "fixed",
                 "outer_iteration": sweep, "iteration": i}
            if hvps:
                r["hvps"] = 0 if i == 0 else sweep - 1
            rows.append(r)
    for sweep, (its, own, wave) in ((1, (9, 90, 900)), (2, (10, 30, 60)),
                                    (3, (20, 50, 100))):
        r = {"kind": "re_fit_wave", "coordinate": "per-user",
             "outer_iteration": sweep, "entities_fit": 5, "iters_sum": its}
        if hvps:
            r.update(hvp_sum=own, hvp_wave=wave)
        rows.append(r)
    return {"ledger_rows": rows, "setup_sweeps": 2, "cell": {"mix": {
        "coordinates": {"fixed": {"type": "fixed"},
                        "per-user": {"type": "random"}}}}}


def test_the_products_readers_read_the_window():
    import cg_steps
    import cg_util
    ctx = _ledger_ctx()
    # fixed: 2 x 1 + 3 x 2 products over 5 iterations
    assert cg_steps.read("cg_steps.fixed", ctx) == pytest.approx(8 / 5)
    assert cg_steps.read("cg_steps.per-user", ctx) == pytest.approx(80 / 30)
    assert cg_util.read("cg_util.per-user", ctx) == pytest.approx(
        100 * 80 / 160)
    parent = _ledger_ctx(hvps=False)  # a program that counts no products
    for name, mod in (("cg_steps.fixed", cg_steps),
                      ("cg_steps.per-user", cg_steps),
                      ("cg_util.per-user", cg_util)):
        assert mod.read(name, parent) is None, name


def test_tron_s_reads_the_cg_scope_of_the_traced_sweep(tmp_path):
    """Device operations under ``tron.cg``, the fixed effect's and a
    wave's, inside the traced sweep's markers; one after it is left out."""
    import tron_cg_roofline
    import tron_s
    fit = "jit(fit)/fe.fit/while/body/"
    wave = "jit(fit_bucket)/re.solve/vmap(while)/body/"
    ops = {1: ("%fusion.a = f32[8]", fit + "tron.cg/while/body/dot_general:"),
           2: ("%fusion.b = f32[8]", fit + "glm.value_grad/dot_general:"),
           3: ("%fusion.c = f32[8]", wave + "tron.cg/while/body/mul:")}
    k = 1000  # ns -> ps
    device = plane("/device:TPU:0", [
        ("XLA Ops", 0, [(1, 1100 * k, 300 * k), (2, 1400 * k, 200 * k),
                        (3, 2000 * k, 500 * k), (3, 2300 * k, 400 * k),
                        (1, 5200 * k, 100 * k)])], ops)
    host = plane("/host:CPU", [("python3", 0, [
        (1, 0, 1), (2, 3000 * k, 1)])],
        {1: ("bench.mark.start", None),
         2: ("bench.mark.3.per-artist", None)})
    d = tmp_path / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    (d / "x.xplane.pb").write_bytes(field(1, host) + field(1, device))
    ctx = {"traced_sweep": 3, "trace": {"busy_s": 1.0},
           "trace_dir": str(tmp_path),
           "cell": {"mix": {"update_sequence": ["fixed", "per-artist"]}}}
    # a: 300 ns; c: [2000, 2700) = 700 ns; b is not under the scope
    assert tron_s.read("tron_s", ctx) == pytest.approx(1000e-9)

    class Schema:
        @staticmethod
        def bytes_needed(kernel, ctx):
            return 819 if kernel == "tron_cg" else None

    ctx.update(schema=Schema, peak={"hbm_bytes_per_s": 819e9})
    assert tron_cg_roofline.read("tron_cg_roofline", ctx) == pytest.approx(
        100 * 1e-9 / 1000e-9)
