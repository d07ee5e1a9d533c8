"""The cell of ISSUE 35, benchmark side, on the CPU: the cell rehearsed
through ``benchmark/run.py`` reads what it read when recorded (limits, keys
and ``argv`` from ``benchmark/selfcheck/avazu.rehearsal.expected.json``, the
readings from ``tests/data/avazu.rehearsal.pr36.json``), its control and its
faults read by the number that exists for each (``correct`` false for the
control and three of them; the fourth is out of the limits' reach at the
rehearsal's size and is held to its readings), the
selfcheck holds the new schema to the contract, the new device readers read a
hand-made trace, and ``BENCHMARK.json`` gained the entries and lost
nothing."""

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
import game_avazu  # noqa: E402  (benchmark/schemas/game_avazu.py)
from record_scoped import field, plane  # noqa: E402  (its xplane encoder)

CELL = "avazu-sparse-re.steady"
EXPECTED = os.path.join(BENCH, "selfcheck", "avazu.rehearsal.expected.json")
RECORDED = os.path.join(REPO, "tests", "data", "avazu.rehearsal.pr36.json")
OLD_READERS = {"stage_s", "update_s.fixed", "fe_iters", "fe_pass_roofline",
               "sweep_mfu", "device_idle_share", "ls_evals.fixed",
               "phase_s.digest", "phase_s.bucketing", "phase_s.host_stage",
               "phase_s.transfer", "phase_s.program_load",
               "scope_s.line_search", "scope_s.value_grad",
               "scope_s.direction", "scope_s.gather_scatter", "scope_s.score",
               "sparse_s.hot", "sparse_s.cold", "hot_entry_share",
               "fe_hot_roofline", "fe_cold_roofline"}
# PR 37's set-up wall: every cell reports them, and a cell's counts below
# are of the metrics before them
SETUP_WALL = {f"setup_wall_s.{p}" for p in (
    "staging", "program_load", "compile_wait", "stage_wait", "sweeps",
    "other")} | {"program_load_wall_s"}
NEW_METRICS = {"update_s.per-publisher", "re_iters.per-publisher",
               "lane_util.per-publisher", "pad_share.per-publisher",
               "ls_evals.per-publisher", "phase_s.project",
               "width_pad_share.per-publisher", "re_solve_s",
               "re_fit_roofline"}
LIMITS = {"loss_1", "loss_2", "loss_3", "grad0", "coef.fixed", "small.fixed",
          "coef.per-publisher", "small.per-publisher",
          "capped.per-publisher", "rows.per-publisher",
          "offspace.per-publisher"}
# (field, cardinality) of the issue, in its order
FIELDS = [("hour", 24), ("C1", 7), ("banner_pos", 7), ("site_id", 4737),
          ("site_domain", 7745), ("site_category", 26), ("app_id", 8552),
          ("app_domain", 559), ("app_category", 36), ("device_id", 2686408),
          ("device_ip", 6729486), ("device_model", 8251), ("device_type", 5),
          ("device_conn_type", 4), ("C14", 2626), ("C15", 8), ("C16", 9),
          ("C17", 435), ("C18", 4), ("C19", 68), ("C20", 172), ("C21", 60)]


@pytest.fixture(scope="module")
def run():
    return faults.load_run()


def result(run, capsys, *extra):
    with open(EXPECTED) as f:
        argv = json.load(f)["argv"]
    assert run.main([*argv, *extra]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def over(out):
    return {k for k, v in out["compared"].items() if v["value"] > v["limit"]}


def test_the_rehearsal_reads_what_it_read(run, capsys):
    with open(EXPECTED) as f:
        want = json.load(f)
    out = result(run, capsys)
    assert out["correct"] is want["correct"] is True, out["compared"]
    assert (out["attempted"], out["failed"]) == (want["attempted"], 0)
    assert out["window"]["sweeps"] == want["window_sweeps"]
    assert out["window"]["asked_in_window"] == 0  # ``setup_sweeps: 2`` holds
    assert sorted(out["metrics"]) == want["metrics"] == ["setup_s", "sweep_s"]
    assert out["compared"].keys() == want["compared"].keys() == LIMITS
    # Limits, keys and argv are the benchmark's. The readings are held to
    # this tree's own recording: the benchmark's is PR 35's, which a PR that
    # claims a gain may not record anew, and all but ``grad0`` and the two
    # exact counts are the slack of solves that stop by their own rule,
    # which follows float32 rounding (since ISSUE 36 the table's accepted
    # gradients come from margins carried along the line).
    with open(RECORDED) as f:
        recorded = json.load(f)["compared"]
    assert recorded.keys() == want["compared"].keys()
    assert recorded["grad0"] == want["compared"]["grad0"]["value"]
    for name, v in want["compared"].items():
        got = out["compared"][name]
        assert got["limit"] == v["limit"], name
        assert recorded[name] <= v["limit"], name
        assert got["value"] == pytest.approx(recorded[name], rel=1e-4,
                                             abs=1e-12), name
    # the cap binds in the rehearsal (``shrink`` scales it with the rows)
    assert out["compared"]["capped.per-publisher"]["value"] > 0
    assert out["compared"]["offspace.per-publisher"] == {"value": 0.0,
                                                         "limit": 0}
    assert out["compared"]["rows.per-publisher"] == {"value": 0.0,
                                                     "limit": 0}


def test_control_bfloat16_is_not_correct(run, capsys):
    out = result(run, capsys, "--control", "bfloat16")
    assert out["correct"] is False, out["compared"]
    # bf16 rounds 1/sqrt(22) down by 1.45e-3 in the hot block, which holds
    # most of the first gradient
    assert 1e-3 < out["compared"]["grad0"]["value"] < 2e-3
    assert "grad0" in over(out)


def test_half_the_batch_is_not_correct(run, capsys):
    with faults.planted("half-batch", run, CELL):
        out = result(run, capsys)
    assert out["correct"] is False, out["compared"]
    assert out["compared"]["loss_1"]["value"] > 0.3
    assert out["compared"]["grad0"]["value"] > 0.3
    assert {"coef.per-publisher", "small.per-publisher"} <= over(out)


def test_the_cold_part_left_out_of_the_gradient_moves_what_reads_it(
        run, capsys):
    """At 20,000 rows the cold part is 2% of the non-zeros (11.6% at the
    cell's size, where ``coef.fixed``, ``small.fixed``, ``loss_2`` and
    ``loss_3`` pass their limits: PERF.md section 6, PR 35), so the cell's
    limits do not reach it here: the readings that exist for it rise
    severalfold to a hundredfold over the sound rehearsal's."""
    with faults.planted("cold-dropped", run, CELL):
        out = result(run, capsys)
    with open(EXPECTED) as f:
        sound = json.load(f)["compared"]
    for name, factor in (("loss_2", 5), ("loss_3", 5),
                         ("small.fixed", 50), ("grad0", 100)):
        assert out["compared"][name]["value"] > factor * sound[name][
            "value"], name
    # the table is trained as it was
    assert out["compared"]["offspace.per-publisher"]["value"] == 0.0
    assert "coef.per-publisher" not in over(out)


def test_a_truncated_projection_is_not_correct(run, capsys):
    """Half of every lane's columns never trained: the table's coefficients
    stand far from the reference's, and still nothing is written off a
    publisher's subspace."""
    with faults.planted("projection-truncated", run, CELL):
        out = result(run, capsys)
    assert out["correct"] is False, out["compared"]
    assert "coef.per-publisher" in over(out), out["compared"]
    assert out["compared"]["coef.per-publisher"]["value"] > 0.1
    assert out["compared"]["offspace.per-publisher"]["value"] == 0.0


def test_the_cap_ignored_by_the_reference_is_not_correct(run, capsys):
    """The reference training every publisher on all of its rows where the
    program keeps ``max_samples`` of them: the capped publishers' rows
    stand apart."""
    with faults.planted("cap-ignored", run, CELL):
        out = result(run, capsys)
    assert out["correct"] is False, out["compared"]
    assert {"rows.per-publisher", "capped.per-publisher"} <= over(out)
    # the program trained the table on fewer rows than the reference did, by
    # an exact count: what the cap took off its heaviest publishers
    assert out["compared"]["rows.per-publisher"]["value"] > 1000
    with open(EXPECTED) as f:
        sound = json.load(f)["compared"]["capped.per-publisher"]["value"]
    assert out["compared"]["capped.per-publisher"]["value"] > 20 * sound


def test_the_selfcheck_holds_the_new_schema_to_the_contract(run, capsys):
    assert run.main(["--selfcheck"]) == 0
    err = capsys.readouterr().err
    for line in ("glmix-avazu-logistic-sparse-re: game_avazu ok",
                 "glmix-kdd12-poisson-l1: game_kdd12 ok",
                 "glmix-criteo-1m-logistic: game_criteo ok",
                 "glmix-ml20m-logistic: game_dense ok"):
        assert line in err, line
    assert err.count("selfcheck check_generator: ok") >= 3
    assert err.count("selfcheck check_work: ok") >= 4


def test_the_entries_are_added_and_nothing_that_was_there_is_changed(run):
    """One configuration and one cell after the three before it (later
    cells after it), the cell's name appended to the lists of the 22
    readers it shares, nine new metrics of its own; every reader is found
    by name."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [c["name"] for c in bench["configs"]][:4] == [
        "glmix-ml20m-logistic", "glmix-criteo-1m-logistic",
        "glmix-kdd12-poisson-l1", "glmix-avazu-logistic-sparse-re"]
    assert [w["name"] for w in bench["workloads"]][:4] == [
        "ml20m-logistic.steady", "criteo-1m-logistic.steady",
        "kdd12-poisson-l1.steady", CELL]
    assert all(w["chips"] == 1 for w in bench["workloads"])
    assert bench["configs"][3]["reduced"] == ["num_rows",
                                               "lbfgs_max_iterations"]
    assert all(1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
               for c in bench["configs"])
    assert all(1 <= len(w["why"]) <= 200 for w in bench["workloads"])
    assert bench["run_seconds"] == 10
    assert [(m["name"], m["bound"]) for m in bench["end_to_end"]] == [
        ("sweep_s", 0.08), ("setup_s", 0.1)]
    later = {w["name"] for w in bench["workloads"][4:]}
    cell = run.load_cell(CELL)
    assert {m["name"] for m in cell["end_to_end"]} == {"sweep_s", "setup_s"}
    mine = {m["name"] for m in cell["per_layer"]}
    assert mine == OLD_READERS | NEW_METRICS | SETUP_WALL
    for m in cell["per_layer"]:
        assert callable(run.layer_reader(m["name"])), m["name"]
        # where it was appended: only a later cell's name comes after it
        tail = m["workloads"][m["workloads"].index(CELL) + 1:]
        assert set(tail) <= later, m["name"]
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL]
            assert m["moves"] == ("setup_s" if m["name"] == "phase_s.project"
                                  else "sweep_s")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert (by_name["re_fit_roofline"]["unit"],
            by_name["re_fit_roofline"]["source"],
            by_name["re_fit_roofline"]["layer"]) == ("%", "device_trace",
                                                     "kernels")
    assert by_name["re_solve_s"]["source"] == "device_trace"
    assert (by_name["width_pad_share.per-publisher"]["source"],
            by_name["width_pad_share.per-publisher"]["layer"]) == (
        "program_counter", "coordinate descent")
    # the cells that were there report what they reported
    for old, count in (("ml20m-logistic.steady", 27),
                       ("criteo-1m-logistic.steady", 27),
                       ("kdd12-poisson-l1.steady", 30)):
        theirs = {m["name"] for m in run.load_cell(old)["per_layer"]}
        assert SETUP_WALL <= theirs and len(theirs - SETUP_WALL) == count
        assert not theirs & NEW_METRICS
    conf = cell["configuration"]
    assert [(f["name"], f["cardinality"]) for f in conf["fields"]] == FIELDS
    assert (conf["hashed_features"], conf["nonzeros_per_row"]) == (1 << 20,
                                                                   22)
    assert conf["entity"] == {"name": "publisher", "count": 13287,
                              "features": 3430, "nonzeros_per_row": 14}
    assert 4737 + 8552 - 2 == 13287
    assert sum(c for (n, c), f in zip(FIELDS, conf["fields"])
               if f.get("table")) + 1 == 3430
    assert [f["name"] for f in conf["fields"] if f.get("table")] == [
        "hour", "C1", "banner_pos", "device_type", "device_conn_type", "C14",
        "C15", "C16", "C17", "C18", "C19", "C20", "C21"]
    assert {f["name"]: f["side"] for f in conf["fields"]
            if f.get("side") in ("site", "app")} == {
        "site_id": "site", "site_domain": "site", "site_category": "site",
        "app_id": "app", "app_domain": "app", "app_category": "app"}
    assert conf["site_row_share"] == 0.64
    assert conf["num_rows"] in (2_000_000, 1_500_000)
    assert (conf["task"], conf["storage_dtype"]) == ("logistic", "float32")
    assert set(conf["reduced"]) == {"num_rows", "lbfgs_max_iterations"}
    for key in ("source", "deployment", "kept", "assumed", "published"):
        assert conf[key], key
    opts = cell["settings"]["optimizers"]
    assert set(opts) == {"fixed", "per-publisher"}
    assert all((o["optimizer"], o["regularization"], o["reg_weight"],
                o["max_iterations"], o["history_length"]) == (
        "LBFGS", "L2", 1.0, 25, 10) for o in opts.values())
    assert conf["lbfgs_max_iterations"] == 25
    assert cell["settings"]["max_samples"] == 65536
    assert cell["mix"]["update_sequence"] == ["fixed", "per-publisher"]
    assert (cell["mix"]["setup_sweeps"], cell["mix"]["min_window_sweeps"],
            cell["mix"]["locked_coordinates"]) == (2, 3, [])
    assert set(conf["check"]["limits"]) == LIMITS
    assert conf["check"]["limits"]["offspace.per-publisher"] == 0
    assert conf["check"]["limits"]["rows.per-publisher"] == 0
    # the table's lanes stop on a rule float32 holds steadily; the fixed
    # effect keeps the program's own
    assert opts["per-publisher"]["tolerance"] == 1e-4
    assert "tolerance" not in opts["fixed"]
    assert conf["check"]["limits"]["grad0"] <= 1e-4
    assert set(game_avazu.faults) == {"half-batch", "cold-dropped",
                                      "projection-truncated", "cap-ignored"}


def hand_made_xspace():
    """Times in ns. Markers: start 0, sweep 3's fixed 1000, per-publisher
    5000. Device operations: g [1100, 1300) under re.gather; s1 [1300, 2300)
    under re.solve/lbfgs.line_search/glm.value_grad; s2 [2000, 2600) under
    re.solve/lbfgs.direction (union with s1 1300); c [2600, 2700) under
    re.scatter; s1 again [5200, 5500), after the window."""
    body = "jit(fit_bucket)/"
    ops = {1: ("%gather.g = f32[8]", body + "re.gather/gather:"),
           2: ("%fusion.s1 = f32[8]", body + "re.solve/vmap(while)/body/"
               "lbfgs.line_search/while/body/glm.value_grad/dot_general:"),
           3: ("%fusion.s2 = f32[8]", body + "re.solve/vmap(while)/body/"
               "lbfgs.direction/mul:"),
           4: ("%scatter.c = f32[9]", body + "re.scatter/scatter:")}
    k = 1000  # ns -> ps
    device = plane("/device:TPU:0", [
        ("XLA Ops", 0, [(1, 1100 * k, 200 * k), (2, 1300 * k, 1000 * k),
                        (3, 2000 * k, 600 * k), (4, 2600 * k, 100 * k),
                        (2, 5200 * k, 300 * k)])],
        ops, event_stat=field(1, 9) + field(3, 5))
    host = plane("/host:CPU", [("python3", 0, [
        (1, 0, 1), (2, 1000 * k, 1), (3, 5000 * k, 1)])],
        {1: ("bench.mark.start", None), 2: ("bench.mark.3.fixed", None),
         3: ("bench.mark.3.per-publisher", None)})
    return field(1, host) + field(1, device)


def test_the_new_device_readers_on_a_hand_made_trace(run, tmp_path):
    os.makedirs(tmp_path / "plugins" / "profile" / "x")
    with open(tmp_path / "plugins" / "profile" / "x" / "t.xplane.pb",
              "wb") as f:
        f.write(hand_made_xspace())
    cell = run.load_cell(CELL)
    wave = {"kind": "re_fit_wave", "coordinate": "per-publisher",
            "outer_iteration": 3, "entities_fit": 4, "evals_sum": 40,
            "iters_sum": 20, "cols_useful": 5000, "cols_padded": 20000}
    rows = [wave, dict(wave, entities_fit=1, evals_sum=6, cols_useful=1000,
                       cols_padded=4000),
            dict(wave, outer_iteration=2, cols_useful=7777)]
    ctx = {"cell": cell, "traced_sweep": 3, "trace": {"window_s": 5e-6},
           "trace_dir": str(tmp_path), "ledger_rows": rows,
           "setup_sweeps": 2, "schema": game_avazu,
           "peak": {"hbm_bytes_per_s": 819e9}}
    read = {m: run.layer_reader(m)(m, ctx) for m in (
        "re_solve_s", "re_fit_roofline", "width_pad_share.per-publisher")}
    assert read["re_solve_s"] == pytest.approx(1300e-9)
    # 10 evaluations a lane over 5000 cells, 6 over 1000: x 2 passes x 4 B
    need = (10 * 5000 + 6 * 1000) * 2 * 4
    assert game_avazu.bytes_needed("re_fit", ctx) == need
    assert read["re_fit_roofline"] == pytest.approx(
        100 * need / 819e9 / 1300e-9)
    # the window's waves: sweeps 2 and 3 (``setup_sweeps`` 2)
    assert read["width_pad_share.per-publisher"] == pytest.approx(
        100 * (1 - (5000 + 1000 + 7777) / 44000))
    # a program that writes neither the scope nor the counts (the parent):
    # nothing, and no raise
    bare = dict(ctx, trace=None, ledger_rows=[
        {k: v for k, v in r.items() if not k.startswith("cols_")}
        for r in rows])
    for key in ("_owlqn_trace", "_re_solve_s"):
        bare.pop(key, None)
    for m in read:
        assert run.layer_reader(m)(m, bare) is None, m
    # with the trace but without the counters: the seconds, not the share
    half = {k: v for k, v in dict(bare, trace=ctx["trace"]).items()
            if k not in ("_owlqn_trace", "_re_solve_s")}
    assert run.layer_reader("re_solve_s")("re_solve_s", half) == \
        pytest.approx(1300e-9)
    assert run.layer_reader("re_fit_roofline")("re_fit_roofline",
                                               half) is None
