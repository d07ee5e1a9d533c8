"""photon-ledger suite (ISSUE 9): run-ledger integrity, convergence
watchdogs, live/spilled telemetry, crash/resume discipline, diffing.

The contracts under test:

* a ledger is a CRC-committed manifest + append-as-produced rows whose
  clean prefix SURVIVES any crash shape (torn tail, SIGKILL mid-fit) and
  whose ``--resume`` append continues the SAME run (identity validated
  against the checkpoint fingerprint, seq monotone across the kill);
* watchdogs turn sick-run shapes (NaN objective, stall, divergence)
  into a loud event + a DEFINED error or early stop — never a silent
  stall, and the partial ledger stays parseable;
* ``photon-obs diff`` of two runs renders a convergence comparison with
  time-to-target (the acceptance criterion).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from photon_ml_tpu import faults, obs
from photon_ml_tpu.obs.ledger import (LedgerError, RunLedger,
                                      build_manifest, convergence_curves,
                                      diff_ledgers, fit_wave_summary,
                                      identity_of, read_manifest, read_rows,
                                      spill_history, time_to_fraction,
                                      time_to_target, verify_ledger)
from photon_ml_tpu.obs.watchdog import (ConvergenceWatchdog,
                                        WatchdogConfig, WatchdogError,
                                        parse_watchdog_config)
from photon_ml_tpu.utils import events as ev

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))

FP = {"task": "LOGISTIC_REGRESSION", "sequence": ["fixed"],
      "iterations": 1, "locked": [], "num_rows": 100,
      "data_digest": "abc123", "coordinates": {"fixed": {"config": {}}}}


@pytest.fixture(autouse=True)
def _clean_obs_state():
    """Ledger/watchdog globals must never leak across tests."""
    yield
    obs.set_ledger(None)
    obs.set_watchdog(None)
    faults.install(None)


# ---------------------------------------------------------------- core IO


def test_ledger_round_trip_and_verify(tmp_path):
    d = str(tmp_path / "run")
    led = RunLedger.resume(d, manifest=build_manifest(config={"k": 1}))
    led.bind_fingerprint(FP)
    with led.bound(coordinate="fixed", step=1):
        for i in range(1, 5):
            led.record("opt_iter", iteration=i, value=10.0 / i,
                       grad_norm=1.0 / i, seconds=0.01,
                       value_passes=1, grad_passes=1)
    led.close()
    rows, problems = read_rows(d)
    assert problems == []
    assert [r["seq"] for r in rows] == list(range(5))  # + run_end
    assert rows[-1]["kind"] == "run_end"
    assert all(rows[i]["t"] <= rows[i + 1]["t"]
               for i in range(len(rows) - 1))
    assert rows[0]["coordinate"] == "fixed"  # bound context rode along
    assert verify_ledger(d) == []
    manifest = read_manifest(d)
    assert manifest["identity"] == identity_of(FP)


def test_torn_tail_keeps_clean_prefix_and_resume_repairs(tmp_path):
    d = str(tmp_path / "run")
    led = RunLedger.resume(d)
    led.bind_fingerprint(FP)
    for i in range(3):
        led.record("opt_iter", iteration=i + 1, value=float(3 - i),
                   grad_norm=0.1)
    led.flush()
    run_id = led.manifest["run_id"]
    # SIGKILL shape: the process dies mid-append — no close(), half a
    # final line on disk.
    with open(led.telemetry_path, "a") as f:
        f.write('{"seq": 3, "kind": "opt_it')
    rows, problems = read_rows(d)
    assert len(rows) == 3 and problems  # clean prefix + reported tear
    # resume truncates the tear and APPENDS with the same identity.
    led2 = RunLedger.resume(d)
    led2.bind_fingerprint(FP)
    led2.record("opt_iter", iteration=4, value=0.5, grad_norm=0.05)
    led2.close()
    rows2, problems2 = read_rows(d)
    assert problems2 == []
    assert [r["seq"] for r in rows2] == list(range(5))
    assert read_manifest(d)["run_id"] == run_id
    assert rows2[3]["t"] >= rows2[2]["t"]  # monotone across the crash


def test_corrupt_row_crc_stops_the_prefix(tmp_path):
    d = str(tmp_path / "run")
    led = RunLedger.resume(d)
    for i in range(4):
        led.record("opt_iter", iteration=i, value=float(i), grad_norm=1.0)
    led.close()
    # Bit rot in row 2's value: the CRC must fence everything from there.
    lines = open(led.telemetry_path).read().splitlines()
    lines[2] = lines[2].replace('"value":2', '"value":7')
    with open(led.telemetry_path, "w") as f:
        f.write("\n".join(lines) + "\n")
    rows, problems = read_rows(d)
    assert len(rows) == 2
    assert any("CRC" in p for p in problems)
    assert verify_ledger(d) != []


def test_manifest_crc_mismatch_is_loud(tmp_path):
    d = str(tmp_path / "run")
    RunLedger.resume(d).close()
    path = os.path.join(d, "manifest.json")
    body = json.load(open(path))
    body["run_id"] = "f" * 32
    with open(path, "w") as f:
        json.dump(body, f)  # marker CRC now stale
    with pytest.raises(LedgerError):
        read_manifest(d)


def test_identity_mismatch_resets_to_fresh_run(tmp_path):
    d = str(tmp_path / "run")
    led = RunLedger.resume(d)
    led.bind_fingerprint(FP)
    led.record("opt_iter", iteration=1, value=1.0, grad_norm=1.0)
    led.close()
    old_id = led.manifest["run_id"]
    led2 = RunLedger.resume(d)
    led2.bind_fingerprint(dict(FP, data_digest="DIFFERENT"))
    led2.record("opt_iter", iteration=1, value=2.0, grad_norm=1.0)
    led2.close()
    rows, _ = read_rows(d)
    # The old curve was discarded (a different run must not append).
    assert [r["kind"] for r in rows] == ["opt_iter", "run_end"]
    assert rows[0]["value"] == 2.0
    assert read_manifest(d)["run_id"] != old_id


def test_grid_and_trial_fingerprints_share_one_identity():
    # Tuning/grid swaps change per-coordinate optimizer configs but are
    # ONE run: the identity digest must ignore the coordinates block.
    fp_b = dict(FP, coordinates={"fixed": {"config": {"reg_weight": 9}}})
    assert identity_of(FP) == identity_of(fp_b)
    assert identity_of(dict(FP, data_digest="x")) != identity_of(FP)


# ---------------------------------------------------------------- curves


def test_curves_spill_and_time_to_target(tmp_path):
    d = str(tmp_path / "run")
    led = RunLedger.resume(d)
    vals = np.array([10.0, 5.0, 2.0, 1.0, np.nan, np.nan])
    gns = np.array([3.0, 2.0, 1.0, 0.5, np.nan, np.nan])
    with led.bound(coordinate="fixed"):
        n = spill_history(led, vals, gns, opt="lbfgs")
    led.close()
    assert n == 4  # NaN padding skipped
    rows, _ = read_rows(d)
    curve = convergence_curves(rows)["fixed"]
    assert [p["value"] for p in curve] == [10.0, 5.0, 2.0, 1.0]
    tt = time_to_target(curve, 2.0)
    assert tt["iteration"] == 2 and tt["value"] == 2.0
    ttf = time_to_fraction(curve, fraction=0.99)
    assert ttf is not None and ttf["target_value"] == pytest.approx(
        1.0 + 0.01 * 9.0)
    assert time_to_target(curve, 0.5) is None  # never got there


def test_fit_wave_summary_aggregates_per_iteration():
    rows = [
        {"kind": "re_fit_wave", "coordinate": "per-user",
         "outer_iteration": 0, "wave": 0, "seconds": 0.5,
         "entities_fit": 8},
        {"kind": "re_fit_wave", "coordinate": "per-user",
         "outer_iteration": 0, "wave": 1, "seconds": 0.25,
         "entities_fit": 4},
        {"kind": "re_fit_wave", "coordinate": "per-user",
         "outer_iteration": 1, "wave": 0, "seconds": 0.1,
         "entities_fit": 2},
        {"kind": "opt_iter", "coordinate": "per-user"},
    ]
    got = fit_wave_summary(rows)
    assert list(got) == ["per-user"]
    assert got["per-user"] == [
        {"outer_iteration": 0, "entities_fit": 12, "seconds": 0.75,
         "waves": 2},
        {"outer_iteration": 1, "entities_fit": 2, "seconds": 0.1,
         "waves": 1}]


# ---------------------------------------------------------------- watchdog


def _alerts():
    seen = []
    ev.default_emitter.register(seen.append)
    return seen


def test_watchdog_nan_raises_defined_error_and_emits_event():
    wd = ConvergenceWatchdog(WatchdogConfig())  # defaults: nan=raise
    seen = _alerts()
    try:
        wd.observe(1, 2.0, 1.0, 0.1)  # healthy
        with pytest.raises(WatchdogError) as exc:
            wd.observe(2, float("nan"), 1.0, 0.1)
    finally:
        ev.default_emitter.unregister(seen.append)
    assert exc.value.kind == "nan"
    alerts = [e for e in seen if isinstance(e, ev.WatchdogAlert)]
    assert len(alerts) == 1 and alerts[0].kind == "nan" \
        and alerts[0].action == "raise"


def test_watchdog_nan_writes_ledger_row_before_raising(tmp_path):
    led = RunLedger.resume(str(tmp_path / "run"))
    obs.set_ledger(led)
    wd = ConvergenceWatchdog(WatchdogConfig(), coordinate="fixed")
    with pytest.raises(WatchdogError):
        wd.observe(1, float("inf"), 1.0, 0.1)
    rows, problems = read_rows(led.directory)
    assert problems == []  # partial ledger stays parseable
    assert rows[-1]["kind"] == "watchdog"
    assert rows[-1]["watchdog_kind"] == "nan"


def test_watchdog_stall_stops_after_k_flat_iterations():
    wd = ConvergenceWatchdog(WatchdogConfig(
        nan="off", stall_iterations=3, stall_action="stop"))
    assert wd.observe(1, 5.0, 1.0, 0.1) is None
    assert wd.observe(2, 4.0, 1.0, 0.1) is None  # progress resets
    assert wd.observe(3, 4.0, 1.0, 0.1) is None
    assert wd.observe(4, 4.0, 1.0, 0.1) is None
    assert wd.observe(5, 4.0, 1.0, 0.1) == "stop"


def test_watchdog_divergence_raises_beyond_tolerance():
    wd = ConvergenceWatchdog(WatchdogConfig(
        nan="off", divergence_factor=2.0))
    wd.observe(1, 1.0, 1.0, 0.1)
    wd.observe(2, 0.5, 1.0, 0.1)
    with pytest.raises(WatchdogError) as exc:
        wd.observe(3, 4.0, 1.0, 0.1)  # 4.0 > 0.5 + 2*max(|1|,1)
    assert exc.value.kind == "divergence"


def test_watchdog_slow_iteration_warns_not_raises(caplog):
    import logging

    wd = ConvergenceWatchdog(WatchdogConfig(
        nan="off", iter_seconds_factor=5.0))
    with caplog.at_level(logging.WARNING, "photon_ml_tpu.obs"):
        for i in range(1, 5):
            assert wd.observe(i, 1.0 / i, 1.0, 0.1) is None
        assert wd.observe(5, 0.1, 1.0, 10.0) is None  # 100x the EMA
    assert any("slow_iter" in r.message for r in caplog.records)


def test_parse_watchdog_config():
    cfg = parse_watchdog_config("")
    assert cfg == WatchdogConfig()
    cfg = parse_watchdog_config(
        "nan=warn,stall=8:raise,stall_rtol=1e-6,divergence=3,"
        "slow_iter=10:stop")
    assert cfg.nan == "warn"
    assert cfg.stall_iterations == 8 and cfg.stall_action == "raise"
    assert cfg.stall_rtol == 1e-6
    assert cfg.divergence_factor == 3.0
    assert cfg.iter_seconds_factor == 10.0 and cfg.iter_action == "stop"
    with pytest.raises(ValueError):
        parse_watchdog_config("bogus=1")
    with pytest.raises(ValueError):
        parse_watchdog_config("nan=explode")


# -------------------------------------------- streaming driver integration


def _quadratic():
    import jax.numpy as jnp

    def vg(w):
        return 0.5 * jnp.sum(w * w), w

    def v(w):
        return 0.5 * jnp.sum(w * w)

    return vg, v


def test_minimize_streaming_records_live_opt_iter_rows(tmp_path):
    from photon_ml_tpu.optim.common import OptimizerConfig
    from photon_ml_tpu.optim.streaming import minimize_streaming

    led = RunLedger.resume(str(tmp_path / "run"))
    obs.set_ledger(led)
    vg, v = _quadratic()
    with led.bound(coordinate="fixed"):
        res = minimize_streaming(
            vg, np.ones(4, np.float32),
            OptimizerConfig(max_iterations=6, tolerance=1e-9),
            value_only=v)
    led.close()
    rows, problems = read_rows(led.directory)
    assert problems == []
    iters = [r for r in rows if r["kind"] == "opt_iter"]
    assert len(iters) == int(res.iterations)
    assert [r["iteration"] for r in iters] == \
        list(range(1, len(iters) + 1))
    for r in iters:
        # Live rows carry the full telemetry column set.
        assert r["coordinate"] == "fixed"
        assert r["seconds"] > 0 and r["probes"] >= 1
        assert r["grad_passes"] >= 1  # acceptance gradient pass
    # Values decrease on a convex quadratic.
    vals = [r["value"] for r in iters]
    assert vals == sorted(vals, reverse=True)


def test_injected_nan_dies_with_watchdog_error_ledger_survives(tmp_path):
    """The ISSUE 9 acceptance chaos shape, unit scale: a photon-fault
    "nan" spec poisons the streamed objective; the armed watchdog turns
    the resulting line-search death into the DEFINED WatchdogError; the
    partial ledger stays parseable and resume-appendable."""
    from photon_ml_tpu.optim.common import OptimizerConfig
    from photon_ml_tpu.optim.streaming import minimize_streaming

    led = RunLedger.resume(str(tmp_path / "run"))
    obs.set_ledger(led)
    obs.set_watchdog(WatchdogConfig())  # nan=raise
    vg, v = _quadratic()
    plan = faults.FaultPlan(specs=(faults.FaultSpec(
        site="stream.objective", kind="nan",
        occurrences=tuple(range(1, 80))),))
    with faults.installed(plan) as inj:
        with pytest.raises(WatchdogError) as exc:
            minimize_streaming(
                vg, np.ones(4, np.float32),
                OptimizerConfig(max_iterations=6, tolerance=1e-9),
                value_only=v)
    assert exc.value.kind == "nan"
    assert inj.fires("stream.objective") >= 1
    rows, _ = read_rows(led.directory)  # open ledger: flushed rows
    assert [r["seq"] for r in rows] == list(range(len(rows)))
    assert rows[-1]["kind"] == "watchdog"
    kept = [r for r in rows if r["kind"] == "opt_iter"]
    assert len(kept) >= 1  # the pre-poison prefix kept its curve
    led.close()
    # ...and the ledger is resume-appendable after the crash.
    led2 = RunLedger.resume(led.directory)
    led2.record("opt_iter", iteration=99, value=0.0, grad_norm=0.0)
    led2.close()
    rows2, problems2 = read_rows(led.directory)
    assert problems2 == []
    assert [r["seq"] for r in rows2] == list(range(len(rows2)))


def test_watchdog_early_stop_keeps_partial_result(tmp_path):
    from photon_ml_tpu.optim.common import OptimizerConfig
    from photon_ml_tpu.optim.streaming import minimize_streaming

    import jax.numpy as jnp

    obs.set_watchdog(WatchdogConfig(
        nan="off", stall_iterations=2, stall_action="stop",
        stall_rtol=1.0))  # everything counts as a stall
    # A quartic converges slowly enough that the stall detector fires
    # long before the optimizer's own convergence test does.
    res = minimize_streaming(
        lambda w: (0.25 * jnp.sum(w ** 4), w ** 3),
        np.ones(4, np.float32),
        OptimizerConfig(max_iterations=50, tolerance=0.0),
        value_only=lambda w: 0.25 * jnp.sum(w ** 4))
    # Stopped early, with a defined (non-converged) partial result.
    assert int(res.iterations) <= 4
    assert not bool(res.converged)


# ---------------------------------------------------------- tuning rows


def test_tuner_logs_per_trial_rows(tmp_path):
    from photon_ml_tpu.hyperparameter.search import (RandomSearch,
                                                     SearchDimension)
    from photon_ml_tpu.utils.ranges import DoubleRange

    led = RunLedger.resume(str(tmp_path / "run"))
    obs.set_ledger(led)
    dims = [SearchDimension("reg", DoubleRange(1e-3, 1e3))]
    searcher = RandomSearch(dims, lambda p: float(np.log10(p[0]) ** 2))
    searcher.find(4)
    led.close()
    rows, _ = read_rows(led.directory)
    trials = [r for r in rows if r["kind"] == "tuning_trial"]
    assert [t["trial"] for t in trials] == [1, 2, 3, 4]
    for t in trials:
        assert "reg" in t["point"] and t["seconds"] >= 0
        assert "objective" in t
        assert t["expected_improvement"] is None  # random search: no EI


# --------------------------------------------- game_train two-seed diff


def _train_args(train_dir, out, extra=()):
    return [
        "--train", train_dir,
        "--coordinate", "name=fixed,type=fixed,shard=global",
        "--update-sequence", "fixed",
        "--opt-config", "fixed:optimizer=LBFGS,reg=L2,reg_weight=1.0",
        "--output-dir", out,
    ] + list(extra)


def _make_dataset(tmp_path, seed, n=200, name="train"):
    from photon_ml_tpu.data import synthetic
    from photon_ml_tpu.data.game_data import from_synthetic
    from photon_ml_tpu.data.io import save_game_dataset

    rng = np.random.default_rng(seed)
    train_dir = str(tmp_path / f"{name}{seed}")
    save_game_dataset(from_synthetic(synthetic.game_data(
        rng, n=n, d_global=6, re_specs={"userId": (8, 3)})), train_dir)
    return train_dir


def test_game_train_two_seed_diff_renders_time_to_target(tmp_path):
    """Acceptance: a tiny game_train run produces a ledger from which
    `photon-obs diff` of two seeds renders a convergence comparison
    with time-to-target."""
    from photon_ml_tpu.cli import game_train
    from photon_ml_tpu.cli.obs import main as obs_main, render_diff

    ledgers = []
    for seed in (0, 1):
        train_dir = _make_dataset(tmp_path, seed)
        out = str(tmp_path / f"out{seed}")
        summary = game_train.run(game_train.build_parser().parse_args(
            _train_args(train_dir, out, ["--no-checkpoint"])))
        assert summary["ledger"]["dir"] == os.path.join(out, "ledger")
        ledgers.append(summary["ledger"]["dir"])
    diff = diff_ledgers(*ledgers)
    entry = diff["coordinates"]["fixed"]
    assert entry["time_to_target_a"] is not None
    assert entry["time_to_target_b"] is not None
    assert entry["time_to_target_ratio"] is not None
    text = render_diff(diff)
    assert "time to target" in text and "value vs wall clock" in text
    # The CLI form exits 0 on the same pair.
    assert obs_main(["diff", ledgers[0], ledgers[1]]) == 0
    assert obs_main(["verify", ledgers[0]]) == 0
    assert obs_main(["tail", ledgers[0]]) == 0


def test_game_train_fresh_run_replaces_stale_ledger(tmp_path):
    from photon_ml_tpu.cli import game_train

    train_dir = _make_dataset(tmp_path, 0)
    out = str(tmp_path / "out")
    s1 = game_train.run(game_train.build_parser().parse_args(
        _train_args(train_dir, out, ["--no-checkpoint"])))
    s2 = game_train.run(game_train.build_parser().parse_args(
        _train_args(train_dir, out, ["--no-checkpoint"])))
    # A fresh (non---resume) rerun is a NEW run: new run id, rows reset.
    assert s1["ledger"]["run_id"] != s2["ledger"]["run_id"]
    rows, problems = read_rows(s2["ledger"]["dir"])
    assert problems == []
    assert sum(r["kind"] == "run_end" for r in rows) == 1


# --------------------------------------- crash/resume integrity (chaos)


def _stream_args(train_dir, out):
    return [
        "--train", train_dir,
        "--coordinate", "name=fixed,type=fixed,shard=global",
        "--update-sequence", "fixed",
        "--opt-config", "fixed:optimizer=LBFGS,reg=L2,reg_weight=1.0",
        "--streaming", "chunk_rows=128,num_hot=8,workers=2",
        "--output-dir", out,
    ]


def test_sigkill_mid_fit_ledger_prefix_and_resume_append(tmp_path):
    """ISSUE 9 satellite: subprocess SIGKILL mid-fit (via --fault-plan)
    leaves a parseable ledger whose rows are the completed prefix, and
    --resume appends monotonically under the SAME run identity."""
    from photon_ml_tpu.cli import game_train
    from photon_ml_tpu.data import sparse as sp
    from photon_ml_tpu.data.game_data import from_sparse_batch
    from photon_ml_tpu.data.io import save_game_dataset

    batch, _ = sp.synthetic_sparse(700, 64, 5, seed=11)
    ds = from_sparse_batch(batch)
    train_dir = str(tmp_path / "train")
    save_game_dataset(ds, train_dir)
    out = str(tmp_path / "out")

    plan = faults.FaultPlan(specs=(faults.FaultSpec(
        site="stream.checkpoint_write", kind="kill", occurrences=(4,)),))
    plan_path = str(tmp_path / "plan.json")
    with open(plan_path, "w") as f:
        f.write(plan.to_json())
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "PYTHONPATH": REPO + (os.pathsep + env["PYTHONPATH"]
                                      if env.get("PYTHONPATH") else "")})
    log_path = str(tmp_path / "phase1.log")
    with open(log_path, "w") as log:
        proc = subprocess.run(
            [sys.executable, "-m", "photon_ml_tpu.cli.game_train"]
            + _stream_args(train_dir, out)
            + ["--fault-plan", plan_path],
            env=env, cwd=REPO, stdout=log, stderr=subprocess.STDOUT,
            timeout=600)
    assert proc.returncode == -9, (
        f"driver survived the SIGKILL plan (rc={proc.returncode}):\n"
        + open(log_path).read()[-3000:])

    ledger_dir = os.path.join(out, "ledger")
    rows, _ = read_rows(ledger_dir)  # torn tail tolerated, prefix clean
    killed_manifest = read_manifest(ledger_dir)
    iters = [r for r in rows if r["kind"] == "opt_iter"]
    # The killed run kept its curve: live rows up to the 4th-checkpoint
    # kill (iterations are recorded BEFORE the checkpoint write).
    assert len(iters) >= 4
    assert [r["iteration"] for r in iters] == \
        list(range(1, len(iters) + 1))
    assert not any(r["kind"] == "run_end" for r in rows)  # died hot
    assert killed_manifest.get("identity")

    # Phase 2 (in-process): --resume appends to the SAME ledger.
    game_train.run(game_train.build_parser().parse_args(
        _stream_args(train_dir, out) + ["--resume"]))
    rows2, problems2 = read_rows(ledger_dir)
    assert problems2 == []
    assert read_manifest(ledger_dir)["run_id"] == \
        killed_manifest["run_id"]
    assert [r["seq"] for r in rows2] == list(range(len(rows2)))
    assert len(rows2) > len(rows)
    assert all(rows2[i]["t"] <= rows2[i + 1]["t"]
               for i in range(len(rows2) - 1))
    assert rows2[-1]["kind"] == "run_end" \
        and rows2[-1]["status"] == "ok"
    # The resumed curve continues PAST the killed prefix, monotone in
    # optimizer iteration within the resumed stretch.
    iters2 = [r for r in rows2 if r["kind"] == "opt_iter"]
    assert len(iters2) > len(iters)


def test_game_train_watchdog_nan_chaos_end_to_end(tmp_path):
    """Acceptance: an injected-NaN chaos run dies with the defined
    watchdog error while the partial ledger remains parseable and
    resume-appendable."""
    from photon_ml_tpu.cli import game_train
    from photon_ml_tpu.data import sparse as sp
    from photon_ml_tpu.data.game_data import from_sparse_batch
    from photon_ml_tpu.data.io import save_game_dataset

    batch, _ = sp.synthetic_sparse(400, 32, 5, seed=7)
    ds = from_sparse_batch(batch)
    train_dir = str(tmp_path / "train")
    save_game_dataset(ds, train_dir)
    out = str(tmp_path / "out")
    plan = faults.FaultPlan(specs=(faults.FaultSpec(
        site="stream.objective", kind="nan",
        occurrences=tuple(range(3, 120))),))
    plan_path = str(tmp_path / "plan.json")
    with open(plan_path, "w") as f:
        f.write(plan.to_json())
    with pytest.raises(WatchdogError) as exc:
        game_train.run(game_train.build_parser().parse_args(
            _stream_args(train_dir, out)
            + ["--fault-plan", plan_path, "--watchdog"]))
    assert exc.value.kind == "nan"
    faults.install(None)
    ledger_dir = os.path.join(out, "ledger")
    rows, problems = read_rows(ledger_dir)
    assert problems == []  # closed via the arming stack's finally
    assert rows[-1]["kind"] == "run_end" and rows[-1]["status"] == "error"
    alerts = [r for r in rows if r["kind"] == "watchdog"]
    assert alerts and alerts[-1]["watchdog_kind"] == "nan"
    kept = [r for r in rows if r["kind"] == "opt_iter"]
    assert len(kept) >= 1  # the curve prefix survived
    # Resume-appendable: a rerun (no faults) with --resume continues
    # the same run to completion.
    summary = game_train.run(game_train.build_parser().parse_args(
        _stream_args(train_dir, out) + ["--resume"]))
    assert summary["ledger"]["run_id"] == \
        read_manifest(ledger_dir)["run_id"]
    rows2, problems2 = read_rows(ledger_dir)
    assert problems2 == []
    assert [r["seq"] for r in rows2] == list(range(len(rows2)))
    assert rows2[-1]["kind"] == "run_end" and rows2[-1]["status"] == "ok"


# ---------------------------------------------------------- estimator API


def test_estimator_ledger_dir_library_path(tmp_path):
    from photon_ml_tpu.api.configs import (CoordinateConfiguration,
                                           FixedEffectDataConfiguration)
    from photon_ml_tpu.api.estimator import GameEstimator
    from photon_ml_tpu.data import synthetic
    from photon_ml_tpu.data.game_data import from_synthetic
    from photon_ml_tpu.optim.problem import GLMOptimizationConfiguration
    from photon_ml_tpu.parallel.mesh import make_mesh
    from photon_ml_tpu.types import TaskType

    rng = np.random.default_rng(3)
    ds = from_synthetic(synthetic.game_data(rng, n=128, d_global=5))
    d = str(tmp_path / "ledger")
    est = GameEstimator(
        task=TaskType.LOGISTIC_REGRESSION,
        coordinates={"fixed": CoordinateConfiguration(
            data=FixedEffectDataConfiguration("global"),
            optimization=GLMOptimizationConfiguration())},
        update_sequence=["fixed"], mesh=make_mesh(), ledger_dir=d)
    est.fit(ds)
    assert verify_ledger(d) == []
    rows, _ = read_rows(d)
    assert any(r["kind"] == "opt_iter" for r in rows)
    assert rows[-1]["kind"] == "run_end"
    assert obs.ledger() is None  # deactivated after fit
