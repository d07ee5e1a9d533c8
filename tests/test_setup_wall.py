"""Set-up's wall from inside the program (ISSUE 37): ``phase``,
``program.load`` and ``coordinate_update`` rows as intervals on a thread,
the fit thread's waits on compile-ahead and on the stager, and the
benchmark's two readers of them (``setup_wall_s.<part>``,
``program_load_wall_s``).

Everything here runs on the CPU: counts, names, orderings and hand-worked
clocks — never a measured time.
"""

from __future__ import annotations

import collections
import concurrent.futures as cf
import importlib.util
import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu import obs
from photon_ml_tpu.data import synthetic
from photon_ml_tpu.data.game_data import from_synthetic
from photon_ml_tpu.game import staging as stg
from photon_ml_tpu.game.coordinates import random_effect as re_mod
from photon_ml_tpu.obs.ledger import RunLedger, read_rows
from photon_ml_tpu.optim import OptimizerConfig
from photon_ml_tpu.optim.problem import GLMOptimizationConfiguration
from photon_ml_tpu.optim.regularization import (RegularizationContext,
                                                RegularizationType)
from photon_ml_tpu.parallel.mesh import make_mesh
from photon_ml_tpu.types import TaskType

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
METRICS = os.path.join(REPO, "benchmark", "layer_metrics")
PARTS = ("staging", "program_load", "compile_wait", "stage_wait", "sweeps",
         "other")
NEW = tuple(f"setup_wall_s.{p}" for p in PARTS) + ("program_load_wall_s",)
SEQ = ["fixed", "per-user"]


@pytest.fixture(autouse=True)
def _clean_obs_state():
    yield
    obs.set_ledger(None)


def _read(metric, rows, setup_sweeps=2):
    """The benchmark's reader of ``metric``, loaded as run.py loads it."""
    import sys
    if METRICS not in sys.path:
        sys.path.insert(0, METRICS)
    stem = metric.split(".", 1)[0]
    spec = importlib.util.spec_from_file_location(
        f"layer_metrics.{stem}", os.path.join(METRICS, stem + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(metric, {"ledger_rows": rows,
                             "setup_sweeps": setup_sweeps})


def _opened(tmp_path):
    d = str(tmp_path / "ledger")
    led = RunLedger.create(d)
    obs.set_ledger(led)
    return d, led


def _closed(d, led):
    obs.set_ledger(None)
    led.close()
    rows, problems = read_rows(d)
    assert problems == []
    return rows


# -- (a) the readers on hand-worked rows --------------------------------------

def _hand_worked():
    """Set-up from 0 to 10 s on thread ``main``; a compile-ahead thread
    ``pool``; a load after set-up's end. What each part holds is worked out
    beside each row."""
    rows = []

    def add(kind, t0, t, **r):
        rows.append(dict(r, kind=kind, t0=t0, t=t, seq=len(rows)))

    def phase(name, t0, t, thread="main", **r):
        add("phase", t0, t, name=name, thread=thread,
            seconds=round(t - t0, 6), **r)

    def load(event, program, t0, t, thread="main", **r):
        phase("program.load", t0, t, thread=thread, event=event,
              program=program, **r)

    phase("fit.digest", 0.5, 1.5)                        # staging 1.0
    phase("re.bucketing", 2.5, 3.0, parent="fit.coordinates")
    load("trace", "f", 3.0, 3.5, parent="fit.coordinates")   # load 0.5
    load("lower", "jit(f)", 3.5, 3.7)                    # load 0.2
    # 3.7 to 3.75: JAX between f's lower and its compile, no event: load
    load("cache_fetch", "jit(f)", 4.0, 4.25)             # inside compile
    load("compile", "jit(f)", 3.75, 4.5)                 # load 0.75
    phase("fit.coordinates", 2.0, 6.0)                   # staging 4 - 1.5
    # the wave program compiles ahead on another thread ...
    load("trace", "fit_bucket", 5.0, 6.0, thread="pool")
    load("lower", "jit(fit_bucket)", 6.0, 6.25, thread="pool")
    # ... while the fit thread's first update waits 0.5 s of it
    phase("re.compile_wait", 7.0, 7.5, program="8x4x3",
          coordinate="fixed", outer_iteration=0)
    load("compile", "jit(fit_bucket)", 6.25, 7.4, thread="pool")
    add("coordinate_update", 6.5, 8.0, coordinate="fixed",
        outer_iteration=0, thread="main")                # sweeps 1.5 - 0.5
    phase("re.stage_wait", 9.0, 9.25, shard=1)           # stage_wait 0.25
    phase("re.transfer", 9.25, 9.5, bytes=8)             # staging 0.25
    add("coordinate_update", 8.5, 10.0, coordinate="per-user",
        outer_iteration=1, thread="main")                # sweeps 1.5 - 0.5
    # set-up ended at 10.0: a recompile in the window is left out
    load("compile", "jit(g)", 11.0, 12.0, outer_iteration=2)
    add("coordinate_update", 10.5, 12.5, coordinate="fixed",
        outer_iteration=2, thread="main")
    return rows


HAND_WORKED = {
    "setup_wall_s.staging": 1.0 + 2.5 + 0.25,
    "setup_wall_s.program_load": 1.5,
    "setup_wall_s.compile_wait": 0.5,
    "setup_wall_s.stage_wait": 0.25,
    "setup_wall_s.sweeps": 1.0 + 1.0,
    "setup_wall_s.other": 0.5 + 0.5 + 0.5 + 0.5,  # 0-0.5, 1.5-2, 6-6.5, 8-8.5
    # the fit thread's [3, 4.5] and the pool's [5, 7.4], counted once where
    # the wait on the fit thread overlaps the pool's compile
    "program_load_wall_s": 1.5 + 2.4,
}


@pytest.mark.parametrize("metric", NEW)
def test_readers_on_hand_worked_rows(metric, capsys):
    rows = _hand_worked()
    assert _read(metric, rows) == pytest.approx(HAND_WORKED[metric])
    err = capsys.readouterr().err
    if metric == "setup_wall_s.staging":
        assert "set-up 10.000000 s" in err and "sum to 10.000000 s" in err
    if metric == "program_load_wall_s":
        assert "costliest: fit_bucket 2.400000 s, f 1.500000 s" in err
        assert "1 on the fit thread, 1 on others" in err


def test_the_parts_tile_set_up():
    rows = _hand_worked()
    parts = [_read(m, rows) for m in NEW[:-1]]
    assert sum(parts) == pytest.approx(10.0, abs=1e-9)
    assert (HAND_WORKED["setup_wall_s.program_load"]
            <= _read("program_load_wall_s", rows) <= 10.0)


# -- (b) older ledgers, absent parts ------------------------------------------

@pytest.mark.parametrize("metric", NEW)
def test_none_on_an_older_ledger_zero_for_an_absent_part(metric):
    older = [dict(r, seq=i) for i, r in enumerate(_hand_worked())]
    for r in older:  # the parent's rows: no start, no thread
        r.pop("t0")
        r.pop("thread", None)
    assert _read(metric, older) is None
    assert _read(metric, []) is None
    # a new ledger with no wait and no load in it
    quiet = [r for r in _hand_worked() if r.get("name") not in (
        "re.compile_wait", "re.stage_wait", "program.load")]
    got = _read(metric, quiet)
    if metric in ("setup_wall_s.compile_wait", "setup_wall_s.stage_wait",
                  "setup_wall_s.program_load", "program_load_wall_s"):
        assert got == 0.0
    else:
        assert got > 0


def test_benchmark_lists_the_seven_additively():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(NEW[0])  # appended together; later metrics after
    mine = bench["per_layer"][first:first + len(NEW)]
    assert [m["name"] for m in mine] == list(NEW)
    for m in mine:
        assert m == {"name": m["name"], "unit": "s", "better": "lower",
                     "source": "program_span", "layer": "host staging",
                     "moves": "setup_s", "workloads": cells}
        assert os.path.exists(os.path.join(
            METRICS, m["name"].split(".", 1)[0] + ".py"))


# -- (c) a CPU fit's rows -----------------------------------------------------

def _opt():
    return GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(max_iterations=4, tolerance=1e-7),
        regularization=RegularizationContext(RegularizationType.L2, 1.0))


def _estimator(ledger_dir, sweeps, mesh, **re_kw):
    from photon_ml_tpu.api.configs import (CoordinateConfiguration,
                                           FixedEffectDataConfiguration,
                                           RandomEffectDataConfiguration)
    from photon_ml_tpu.api.estimator import GameEstimator

    return GameEstimator(
        task=TaskType.LOGISTIC_REGRESSION,
        coordinates={
            "fixed": CoordinateConfiguration(
                data=FixedEffectDataConfiguration("global"),
                optimization=_opt()),
            "per-user": CoordinateConfiguration(
                data=RandomEffectDataConfiguration(
                    random_effect_type="userId",
                    feature_shard_id="re_userId", **re_kw),
                optimization=_opt())},
        update_sequence=SEQ, mesh=mesh, descent_iterations=sweeps,
        ledger_dir=ledger_dir)


@pytest.fixture(scope="module")
def fitted(tmp_path_factory):
    """One projected GLMix fit on one device, three sweeps: its wave
    programs compile ahead on threads of their own, its blocks come from
    the pipelined stager."""
    ds = from_synthetic(synthetic.game_data(
        np.random.default_rng(5), n=400, d_global=4,
        re_specs={"userId": (16, 3)}))
    d = str(tmp_path_factory.mktemp("fit") / "ledger")
    _estimator(d, 3, make_mesh(devices=jax.devices()[:1]),
               projector="INDEX_MAP").fit(ds)
    rows, problems = read_rows(d)
    assert problems == []
    return rows


def test_every_set_up_row_is_an_interval_on_a_thread(fitted):
    timed = [r for r in fitted
             if r["kind"] in ("phase", "coordinate_update")]
    assert timed
    for r in timed:
        assert 0 <= r["t0"] <= r["t"] and isinstance(r["thread"], str), r
    threads = {r["thread"] for r in timed
               if r.get("name") == "program.load"}
    assert "MainThread" in threads
    assert any(t.startswith("pml-re-compile") for t in threads), threads


def test_a_trace_row_is_the_programs_own(fitted):
    loads = [r for r in fitted if r.get("name") == "program.load"]
    traces = [r for r in loads if r["event"] == "trace"
              and r["program"] == "fit_bucket"]
    assert traces
    for tr in traces:
        lowers = [r for r in loads if r["thread"] == tr["thread"]
                  and r["event"] == "lower"
                  and r["program"] == "jit(fit_bucket)"
                  and r["t0"] >= tr["t"]]
        assert lowers, tr  # its lower starts after its trace has ended
    # no trace row lies inside another on its thread
    for a in loads:
        for b in loads:
            if (a is not b and a["event"] == b["event"] == "trace"
                    and a["thread"] == b["thread"]):
                assert not (a["t0"] <= b["t0"] and b["t"] <= a["t"]
                            and (a["t0"], a["t"]) != (b["t0"], b["t"])), \
                    (a, b)


def test_the_readers_tile_a_cpu_fit(fitted):
    end = max(r["t"] for r in fitted if r["kind"] == "coordinate_update"
              and r["outer_iteration"] == 1)
    parts = [_read(m, fitted) for m in NEW[:-1]]
    assert all(p >= 0 for p in parts)
    assert sum(parts) == pytest.approx(end, abs=1e-3)
    wall = _read("program_load_wall_s", fitted)
    assert parts[1] <= wall + 1e-6 and wall <= end


def test_a_steady_sweep_writes_what_the_parent_wrote(fitted):
    """Sweep 3 (the window's kind): the parent's rows and no more — one
    ``coordinate_update`` an update, one ``re_fit_wave`` a wave, the fixed
    effect's ``opt_iter`` rows; no phase, no load, no wait."""
    def kinds(it):
        return collections.Counter(
            r["kind"] for r in fitted if r.get("outer_iteration") == it
            and r["kind"] != "opt_iter")
    waves = sum(r["kind"] == "re_fit_wave" and r["outer_iteration"] == 2
                for r in fitted)
    assert waves > 0
    assert kinds(2) == {"coordinate_update": 2, "re_fit_wave": waves}
    # the second sweep still loads a program or two (a warm start's), as
    # the parent's did; it waits on nothing
    assert not [r for r in fitted if r.get("outer_iteration") == 1
                and r["kind"] == "phase" and r["name"] != "program.load"]
    fixed_iters = [r for r in fitted if r["kind"] == "opt_iter"
                   and r["outer_iteration"] == 2]
    assert {r["coordinate"] for r in fixed_iters} <= {"fixed"}


def test_a_trace_inside_another_gets_no_row(tmp_path):
    obs.record_program_loads()

    @jax.jit
    def inner_fn(x):
        return x * 2.0

    @jax.jit
    def outer_fn(x):
        return inner_fn(x) + 1.0

    d, led = _opened(tmp_path)
    try:
        outer_fn(jnp.ones((3,), jnp.float32)).block_until_ready()
    finally:
        rows = _closed(d, led)
    loads = [(r["event"], r["program"]) for r in rows
             if r.get("name") == "program.load" and r["event"] != "cache_fetch"]
    assert ("trace", "outer_fn") in loads
    assert not any(p == "inner_fn" for _, p in loads), loads
    assert ("lower", "jit(outer_fn)") in loads
    assert ("compile", "jit(outer_fn)") in loads


# -- (d) the waits, only where the fit thread blocked --------------------------

def _held(value):
    """A future that a gate holds pending, and the gate."""
    gate = threading.Event()
    pool = cf.ThreadPoolExecutor(1)
    fut = pool.submit(lambda: (gate.wait(10), value)[1])
    pool.shutdown(wait=False)
    return fut, gate


@pytest.mark.parametrize("held", ["program", "plan"])
def test_compile_wait_is_written_once_where_it_blocked(tmp_path, held):
    traced = []
    progs = re_mod._WavePrograms(
        lambda W, offsets, *arrays: traced.append(1) or "traced")
    arrays = (np.zeros((8, 4, 3), np.float32), np.zeros((8, 4), np.float32))
    fut, gate = _held(lambda W, offsets, *a: "compiled")
    progs._compiled[progs._key(arrays)] = fut
    if held == "plan":  # the plan is still being made: the event holds it
        gate.set()
        fut.result()
        progs._planned.clear()
        gate = progs._planned
    d, led = _opened(tmp_path)
    try:
        threading.Timer(0.05, gate.set).start()
        assert progs(None, None, *arrays) == "compiled"  # blocked
        assert progs(None, None, *arrays) == "compiled"  # done: no row
    finally:
        rows = _closed(d, led)
    waits = [r for r in rows if r["kind"] == "phase"]
    assert len(waits) == 1 and not traced
    (w,) = waits
    assert w["name"] == "re.compile_wait" and w["program"] == "8x4x3"
    assert w["seconds"] > 0 and w["thread"] == threading.current_thread().name


def test_stage_wait_is_written_once_where_it_blocked(tmp_path):
    stager = object.__new__(stg.ProjectionStager)
    ready = cf.Future()
    ready.set_result(("cache", ("shard 0",)))
    fut, gate = _held(("cache", ("shard 1",)))
    stager.num_shards = 2
    stager._futures = [ready, fut]
    stager._t_first_taken = None
    d, led = _opened(tmp_path)
    try:
        threading.Timer(0.05, gate.set).start()
        got = list(stager.shards())
    finally:
        rows = _closed(d, led)
    assert got == [("shard 0",), ("shard 1",)]
    waits = [r for r in rows if r["kind"] == "phase"]
    assert [(r["name"], r["shard"]) for r in waits] == [("re.stage_wait", 1)]
    assert waits[0]["seconds"] > 0


def test_waits_are_free_without_a_ledger():
    progs = re_mod._WavePrograms(lambda W, offsets, *arrays: "traced")
    arrays = (np.zeros((8, 4, 3), np.float32),)
    fut, gate = _held(lambda W, offsets, *a: "compiled")
    progs._compiled[progs._key(arrays)] = fut
    threading.Timer(0.05, gate.set).start()
    assert progs(None, None, *arrays) == "compiled"
    assert obs.current_phase() is None
