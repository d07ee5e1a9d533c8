"""The fit waves' inside view (ISSUE 26): named scopes in the jitted
programs, per-wave solver counters and set-up phases on the run ledger, the
clock anchors, and the benchmark's readers of them.

Everything here runs on the CPU: counts, names and orderings — never a time.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu import obs
from photon_ml_tpu.data import synthetic
from photon_ml_tpu.data.game_data import from_synthetic
from photon_ml_tpu.game import descent
from photon_ml_tpu.game.coordinates import (FixedEffectCoordinate,
                                            RandomEffectCoordinate)
from photon_ml_tpu.game.coordinates import random_effect as re_mod
from photon_ml_tpu.obs.ledger import (RunLedger, monotonic_of, read_manifest,
                                      read_rows)
from photon_ml_tpu.ops import losses
from photon_ml_tpu.optim import OptimizerConfig
from photon_ml_tpu.optim.problem import GLMOptimizationConfiguration
from photon_ml_tpu.optim.regularization import (RegularizationContext,
                                                RegularizationType)
from photon_ml_tpu.parallel.mesh import make_mesh
from photon_ml_tpu.types import TaskType

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
BENCH = os.path.join(REPO, "benchmark")
for _p in (BENCH, os.path.join(BENCH, "layer_metrics")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import scope_reduce  # noqa: E402  (benchmark/scope_reduce.py)
import record_scoped  # noqa: E402  (benchmark/record_scoped.py)
from record_scoped import field, plane  # noqa: E402  (its xplane encoder)

MAX_IT = 6
SEQ = ["fixed", "per-user"]
WAVE_FIELDS = ("cap", "lanes", "rows_useful", "rows_padded", "iters_sum",
               "iters_max", "evals_sum", "lanes_at_cap", "entities_fit",
               "seconds", "trials_sum", "line")
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"


@pytest.fixture(autouse=True)
def _clean_obs_state():
    yield
    obs.set_ledger(None)
    obs.disable()


def _opt():
    return GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(max_iterations=MAX_IT, tolerance=1e-7),
        regularization=RegularizationContext(RegularizationType.L2, 1.0))


@pytest.fixture(scope="module")
def game():
    """One tiny GLMix problem and its coordinates, built once: every test
    below runs the same jitted programs."""
    mesh = make_mesh()
    ds = from_synthetic(synthetic.game_data(
        np.random.default_rng(7), n=640, d_global=4,
        re_specs={"userId": (40, 3)}))
    coords = {
        "fixed": FixedEffectCoordinate(ds, "global", losses.LOGISTIC, _opt(),
                                       mesh),
        "per-user": RandomEffectCoordinate(ds, "userId", "re_userId",
                                           losses.LOGISTIC, _opt(), mesh)}
    return ds, coords


def _variant(game, variant, **bounds):
    """The game's coordinates with ``per-user`` rebuilt as another model
    type (both projected builders' programs) or under active-data bounds."""
    ds, coords = game
    if variant == "dense" and not bounds:
        return coords
    kind = ({} if variant == "dense" else
            dict(projection=True, subspace_model=variant == "subspace"))
    return dict(coords, **{"per-user": RandomEffectCoordinate(
        ds, "userId", "re_userId", losses.LOGISTIC, _opt(),
        coords["per-user"].mesh, **kind, **bounds)})


def _descend(coords, sweeps=2):
    model, _ = descent.run(
        TaskType.LOGISTIC_REGRESSION, coords,
        descent.CoordinateDescentConfig(SEQ, sweeps, sync_updates=True))
    return {"fixed": np.asarray(model.models["fixed"].coefficients.means),
            "per-user": np.asarray(model.models["per-user"].means)}


def _with_ledger(tmp_path, coords, sweeps=2):
    d = str(tmp_path / "ledger")
    led = RunLedger.create(d)
    obs.set_ledger(led)
    try:
        arrays = _descend(coords, sweeps)
    finally:
        obs.set_ledger(None)
        led.close()
    rows, problems = read_rows(d)
    assert problems == []
    return arrays, rows, d


# -- (a) the wave rows ---------------------------------------------------------

@pytest.mark.parametrize("variant", ["dense", "projected", "subspace"])
def test_wave_rows_carry_the_solver_counts(game, tmp_path, variant):
    ds = game[0]
    coords = _variant(game, variant)
    _, rows, _ = _with_ledger(tmp_path, coords)
    waves = [r for r in rows if r["kind"] == "re_fit_wave"]
    bucketing = coords["per-user"].bucketing
    # one wave per staged tuple: a bucket, or a shard of one
    assert len(waves) == 2 * len(coords["per-user"]._bucket_data) \
        >= 2 * len(bucketing.buckets)
    for w in waves:
        for f in WAVE_FIELDS:
            assert f in w, (f, w)
        assert w["coordinate"] == "per-user"
        assert 0 < w["iters_max"] <= MAX_IT
        assert w["iters_sum"] <= w["entities_fit"] * w["iters_max"]
        # L-BFGS lanes without L1 search through the oracle: an
        # evaluation is a pair of passes, the trials are counted apart
        assert w["line"] == "oracle"
        assert w["evals_sum"] == w["iters_sum"] + w["entities_fit"]
        assert w["trials_sum"] >= w["iters_sum"]
        assert 0 < w["rows_useful"] <= w["rows_padded"] == \
            w["lanes"] * w["cap"]
        assert 0 <= w["lanes_at_cap"] <= w["entities_fit"] <= w["lanes"]
    own = sum(int(b.counts[b.entity_rows >= 0].sum())
              for b in bucketing.buckets)
    for sweep in (0, 1):
        assert sum(w["rows_useful"] for w in waves
                   if w["outer_iteration"] == sweep) == own
    assert own == ds.num_rows  # every row trains: no bound cuts this data


@pytest.mark.parametrize("bound", ["lower_bound", "upper_bound"])
def test_wave_rows_count_only_trained_entities(game, tmp_path, bound):
    """Under an active-data bound some entities have no model and some rows
    are passive (scored, never fit): the waves count neither."""
    ds = game[0]
    counts = np.bincount(ds.entity_ids["userId"])
    coords = _variant(game, "dense", **{bound: int(np.median(counts))})
    bucketing = coords["per-user"].bucketing
    trained = int(bucketing.trained_entities.sum())
    active = ds.num_rows - bucketing.num_passive_examples
    assert bucketing.num_passive_examples > 0
    assert (trained < len(counts)) == (bound == "lower_bound")
    _, rows, _ = _with_ledger(tmp_path, coords)
    waves = [r for r in rows if r["kind"] == "re_fit_wave"]
    for sweep in (0, 1):
        mine = [w for w in waves if w["outer_iteration"] == sweep]
        assert sum(w["entities_fit"] for w in mine) == trained
        assert sum(w["rows_useful"] for w in mine) == active
        assert all(w["rows_useful"] <= w["entities_fit"] * w["cap"]
                   for w in mine)


def test_fixed_update_reports_its_evaluations(game, tmp_path):
    _, coords = game
    _, rows, _ = _with_ledger(tmp_path, coords)
    for sweep in (0, 1):
        its = [r for r in rows if r["kind"] == "opt_iter"
               and r["coordinate"] == "fixed"
               and r["outer_iteration"] == sweep]
        assert [r["iteration"] for r in its] == list(range(len(its)))
        assert all("evaluations" not in r for r in its[:-1])
        # one evaluation at the start and at least one trial an iteration
        assert its[-1]["evaluations"] >= its[-1]["iteration"] + 1


def test_evaluations_count_the_line_search_trials():
    """A quadratic the unit step does not fit: the first line search has to
    bisect, and every trial is counted; frozen vmap lanes stop counting."""
    from photon_ml_tpu.optim import optimize

    A = jnp.diag(jnp.array([1.0, 40.0, 900.0]))

    def vg(w):
        return 0.5 * w @ A @ w - jnp.sum(w), A @ w - 1.0

    cfg = OptimizerConfig(max_iterations=30)
    one = optimize(vg, jnp.ones(3), cfg)
    assert int(one.evaluations) > int(one.iterations) + 1
    lanes = jax.vmap(lambda w: optimize(vg, w, cfg))(
        jnp.stack([jnp.ones(3), jnp.diag(1.0 / A)]))  # lane 1 starts solved
    assert int(lanes.iterations[1]) == 0 and int(lanes.evaluations[1]) == 1
    assert int(lanes.evaluations[0]) == int(one.evaluations)
    assert int(lanes.iterations[0]) == int(one.iterations)


# -- (b) the ledger changes nothing -------------------------------------------

def test_model_is_bitwise_the_same_with_a_ledger(game, tmp_path):
    _, coords = game
    plain = _descend(coords)  # also warms every program
    lowered = []

    def on_duration(event, duration_secs, **kw):
        if event == LOWER:
            lowered.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        with_led, _, _ = _with_ledger(tmp_path, coords)
        n_led = len(lowered)
        again = _descend(coords)
        n_plain = len(lowered) - n_led
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    for k in plain:
        assert np.array_equal(plain[k], with_led[k]), k
        assert np.array_equal(plain[k], again[k]), k
    # the ledger asks for no program the plain fit does not ask for
    assert n_led == n_plain, lowered


# -- (c) no read before the barrier -------------------------------------------

def test_wave_stats_wait_for_the_drain(game, tmp_path):
    _, coords = game
    coord = coords["per-user"]
    d = str(tmp_path / "ledger")
    led = RunLedger.create(d)
    obs.set_ledger(led)
    offsets = np.zeros(coord.dataset.num_rows, np.float32)
    coord.train_model(offsets)
    led.flush()
    assert len(led._deferred) == 1
    assert not [r for r in read_rows(d)[0] if r["kind"] == "re_fit_wave"]
    assert led.drain() == len(coord.bucketing.buckets)
    assert led._deferred == []
    waves = [r for r in read_rows(d)[0] if r["kind"] == "re_fit_wave"]
    assert len(waves) == len(coord.bucketing.buckets)
    assert all(w["iters_max"] > 0 for w in waves)
    # a train call made outside descent.run loses nothing at close
    coord.train_model(offsets)
    obs.set_ledger(None)
    led.close()
    rows, _ = read_rows(d)
    assert len([r for r in rows if r["kind"] == "re_fit_wave"]) == \
        2 * len(coord.bucketing.buckets)
    assert rows[-1]["kind"] == "run_end"


def test_descent_reads_the_stats_after_its_barrier(game, tmp_path,
                                                   monkeypatch):
    _, coords = game
    log = []
    real_sync = jax.block_until_ready
    real_rows = re_mod._wave_rows

    def sync(x):
        log.append("sync")
        return real_sync(x)

    def rows(pending):
        log.append("read")
        return real_rows(pending)

    monkeypatch.setattr(descent.jax, "block_until_ready", sync)
    monkeypatch.setattr(re_mod, "_wave_rows", rows)
    defers = []
    real_defer = RunLedger.defer
    monkeypatch.setattr(RunLedger, "defer",
                        lambda self, fn: (defers.append(len(log)),
                                          real_defer(self, fn))[1])
    _descend(coords)
    assert "read" not in log and defers == [], \
        "with no ledger nothing is queued and nothing is read"
    log.clear()
    _with_ledger(tmp_path, coords)
    assert len(defers) == 2 and log.count("read") == 2
    for i, what in enumerate(log):
        if what == "read":  # the update's own barrier came just before
            assert log[i - 1] == "sync", log


# -- (d) phases and the clock -------------------------------------------------

def _estimator(ledger_dir):
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
                    feature_shard_id="re_userId"),
                optimization=_opt())},
        update_sequence=SEQ, mesh=make_mesh(), descent_iterations=1,
        ledger_dir=ledger_dir)


def test_phase_rows_and_program_loads(tmp_path):
    ds = from_synthetic(synthetic.game_data(
        np.random.default_rng(11), n=320, d_global=4,
        re_specs={"userId": (12, 3)}))
    d = str(tmp_path / "ledger")
    # a listener of the test's own on the same interval events, beside the
    # one obs.record_program_loads registers
    mine = obs.ProgramLoads()
    seen = []

    def keep(rows):
        if obs.ledger() is not None:
            seen.extend(rows)

    def on_start(event, value, **kw):
        mine.started(event, **kw)

    def on_span(event, start, end, **kw):
        keep(mine.ended(event, start, end, **kw))

    def on_duration(event, duration_secs, **kw):
        keep(mine.fetched(event, duration_secs))

    t_before = time.monotonic()
    jax.monitoring.register_scalar_listener(on_start)
    jax.monitoring.register_event_time_span_listener(on_span)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        _estimator(d).fit(ds)
    finally:
        jax.monitoring.unregister_scalar_listener(on_start)
        jax.monitoring.unregister_event_time_span_listener(on_span)
        jax.monitoring.unregister_event_duration_listener(on_duration)
    t_after = time.monotonic()
    rows, problems = read_rows(d)
    assert problems == []
    phases = [r for r in rows if r["kind"] == "phase"]
    names = {r["name"] for r in phases}
    assert {"fit.digest", "fit.coordinates", "re.bucketing", "re.host_stage",
            "re.transfer", "fe.transfer", "program.load"} <= names
    assert all(r["seconds"] >= 0 for r in phases)
    for r in phases:
        if r["name"].endswith(".transfer"):
            assert r["bytes"] > 0 and r["parent"] == "fit.coordinates"
        if r["name"] in ("re.bucketing", "re.host_stage"):
            assert r["parent"] == "fit.coordinates"
        # an interval on a thread: it starts before it is written
        assert r["t0"] <= r["t"] and r["thread"] == "MainThread"
    loads = [{k: r[k] for k in ("event", "program", "seconds")}
             for r in phases if r["name"] == "program.load"]
    assert loads and loads == [{k: r[k] for k in ("event", "program",
                                                  "seconds")}
                               for r in seen]  # one for one, in order
    assert {r["event"] for r in loads} >= {"trace", "lower", "compile"}
    fits = [r for r in phases if r["name"] == "program.load"
            and r["program"] in ("fit_bucket", "jit(fit_bucket)")]
    assert {r["event"] for r in fits} >= {"trace", "lower", "compile"}
    assert all(r["coordinate"] == "per-user" and r["outer_iteration"] == 0
               for r in fits)
    # the manifest's anchors put every row on the host's monotonic clock
    manifest = read_manifest(d)
    assert len(manifest["clock"]) == 1 and manifest["clock"][0]["t"] == 0.0
    for r in rows:
        assert t_before <= monotonic_of(manifest, r["t"]) <= t_after
        if "t0" in r:
            assert t_before <= monotonic_of(manifest, r["t0"]) <= t_after
    assert monotonic_of({}, 1.0) is None


def test_phase_is_free_without_a_ledger():
    with obs.phase("re.transfer", bytes=3) as ph:
        ph["bytes"] += 1
    assert ph == {"bytes": 4} and obs.current_phase() is None


def test_obs_still_imports_without_jax():
    import subprocess
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys, photon_ml_tpu.obs, photon_ml_tpu.obs.programs; "
         "print('jax' in sys.modules)"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "False"


# -- (e) the scope vocabulary in the lowered programs -------------------------

def _lowered(jitted, *args):
    return jitted.lower(*args).as_text(debug_info=True)


@pytest.mark.parametrize("variant", ["dense", "projected", "subspace"])
def test_fit_bucket_lowers_with_every_scope(game, variant):
    coord = _variant(game, variant)["per-user"].wait_staged()
    W = coord._prepare_table(None)
    offsets = jnp.zeros((coord.dataset.num_rows,), jnp.float32)
    text = _lowered(coord._fit_bucket, W, offsets, *coord._bucket_data[0])
    for scope in ("re.gather", "re.solve", "re.scatter", "lbfgs.direction",
                  "lbfgs.line_search", "glm.value_grad"):
        assert scope in text, scope
    assert "jit(fit_bucket)/re.solve/" in text
    # under vmap the scope is wrapped by the transform, inside a component
    assert any(scope_reduce.scopes_of(p) >= {"re.solve", "lbfgs.line_search",
                                             "glm.value_grad"}
               for p in _paths(text))


def _paths(text):
    import re
    return set(re.findall(r'loc\("(jit\([^"]*)"', text))


def test_direction_scope_names_the_unrolled_recursion(game):
    """The two-loop recursion is unrolled over the history's age (ISSUE 27):
    its dot products, the slices of the history and the shift that ages it
    sit under ``lbfgs.direction`` themselves, with no loop and no per-lane
    index between them and the scope, and the compiled program's operations
    still carry the scope for the trace to read."""
    import re
    _, coords = game
    coord = coords["per-user"]
    W = jnp.zeros((coord.num_entities, coord.dim), jnp.float32)
    offsets = jnp.zeros((coord.dataset.num_rows,), jnp.float32)
    lowered = coord._fit_bucket.lower(W, offsets, *coord._bucket_data[0])
    under = {p.split("lbfgs.direction/", 1)[1]
             for p in _paths(lowered.as_text(debug_info=True))
             if "lbfgs.direction/" in p}
    assert {"dot_general", "slice", "concatenate"} <= under, under
    assert not [p for p in under if re.search(
        r"while|gather|scatter|dynamic_(update_)?slice", p)], under
    compiled = re.findall(r'op_name="([^"]*lbfgs\.direction[^"]*)"',
                          lowered.compile().as_text())
    assert len(compiled) >= 10  # a rolled loop's body would leave a few
    assert all("re.solve" in scope_reduce.scopes_of(p) for p in compiled)


def test_fixed_fit_and_scores_lower_with_their_scopes(game):
    _, coords = game
    fixed, per_user = coords["fixed"], coords["per-user"]
    n = fixed.dataset.num_rows
    text = _lowered(fixed._fit, fixed._staged, jnp.zeros((n,), jnp.float32),
                    jnp.zeros((fixed.dim,), jnp.float32))
    for scope in ("fe.fit", "lbfgs.direction", "lbfgs.line_search",
                  "glm.value_grad"):
        assert scope in text, scope
    assert "jit(fit)/fe.fit/" in text  # the program keeps its name
    assert "fe.score" in _lowered(fixed._score, fixed._staged.features,
                                  jnp.zeros((fixed.dim,), jnp.float32))
    table = jnp.zeros((per_user.num_entities, per_user.dim), jnp.float32)
    assert "re.score" in _lowered(re_mod._entity_rows, table, per_user._ids)
    assert "re.score" in _lowered(re_mod._rowwise_dot, per_user._X,
                                  per_user._X)


# -- (f) the scope reduction --------------------------------------------------

def hand_made_xspace():
    """Times in ns (offsets in ps). Markers: start 0, sweep 3's fixed 1000,
    per-user 5000. Device operations:

    - a [100, 300) under fe.fit, inside ``fixed``; b [400, 500) under no
      scope (residual arithmetic); f [600, 900) under fe.score;
    - the outer ``while`` [1000, 4000) holds the line search's ``while``
      [1200, 3000), which holds v [1300, 1800) under re.solve,
      lbfgs.line_search and glm.value_grad and u [2000, 2600) under the
      first two only, and then d [3000, 3400) under re.solve and
      lbfgs.direction. Neither loop has a path of its own: the inner one
      takes what v and u share, the outer one what v, u and d share;
    - g [4000, 4200) under re.gather, s [4300, 4600) under re.scatter;
    - z [5200, 5600) under re.solve, after the window.
    Two executions of the bucket program inside ``per-user``: [1000, 4100)
    and [4200, 4700). Host annotations: descent.update [900, 5000) around
    re.fit_wave [950, 1100)."""
    solve = "jit(fit_bucket)/re.solve/vmap(jit(minimize))/while"
    ops = {
        1: ("%fusion.a = f32[8]", "jit(fit)/fe.fit/mul:"),
        2: ("%add.b = f32[8]", "jit(add)/add:"),
        3: ("%fusion.f = f32[8]", "jit(score)/fe.score/dot_general:"),
        # as on the chip, the loops themselves carry no path
        4: ("%while.10 = (f32[64,8]{1,0:T(8,128)}, s32[]{:T(128)})", None),
        5: ("%while.20 = (s32[]{:T(128)}, f32[64,8]{1,0:T(8,128)})", None),
        6: ("%fusion.v = f32[64]", solve + "/body/vmap(lbfgs.line_search)/"
            "while/body/vmap(glm.value_grad)/reduce_sum:"),
        7: ("%fusion.d = f32[64,8]", solve + "/body/lbfgs.direction/dot:"),
        13: ("%fusion.u = f32[64]", solve + "/body/vmap(lbfgs.line_search)/"
             "while/body/select_n:"),
        8: ("%gather.g = f32[64,8]", "jit(fit_bucket)/re.gather/gather:"),
        9: ("%scatter.s = f32[99,8]", "jit(fit_bucket)/re.scatter/scatter:"),
        10: ("%fusion.z = f32[8]", "jit(fit_bucket)/re.solve/mul:"),
        11: ("jit_fit_bucket(123)", None), 12: ("jit_fit(9)", None),
    }
    k = 1000  # ns -> ps
    device = plane("/device:TPU:0", [
        ("XLA Ops", 0, [(1, 100 * k, 200 * k), (2, 400 * k, 100 * k),
                        (3, 600 * k, 300 * k), (4, 1000 * k, 3000 * k),
                        (5, 1200 * k, 1800 * k), (6, 1300 * k, 500 * k),
                        (13, 2000 * k, 600 * k), (7, 3000 * k, 400 * k),
                        (8, 4000 * k, 200 * k), (9, 4300 * k, 300 * k),
                        (10, 5200 * k, 400 * k)]),
        ("XLA Modules", 0, [(12, 100 * k, 800 * k), (11, 1000 * k, 3100 * k),
                            (11, 4200 * k, 500 * k)])],
        ops, event_stat=field(1, 9) + field(3, 5))
    host = plane("/host:CPU", [("python3", 50, [
        (1, 0, 1), (2, 950 * k, 1), (3, 4950 * k, 1), (4, 850 * k, 4100 * k),
        (5, 900 * k, 150 * k), (6, 7, 7)])],
        {1: ("bench.mark.start", None), 2: ("bench.mark.3.fixed", None),
         3: ("bench.mark.3.per-user", None), 4: ("descent.update", None),
         5: ("re.fit_wave", None), 6: ("PjitFunction(fit)", None)})
    return field(1, host) + field(1, device)


def test_scope_reduce_on_a_hand_made_profile():
    planes = scope_reduce.parse_xspace(hand_made_xspace())
    assert [p["name"] for p in planes] == ["/host:CPU", "/device:TPU:0"]
    assert planes[1]["tf_op"][6].endswith("reduce_sum:")
    r = scope_reduce.reduce_planes(planes, "bench.mark", 3, SEQ)
    ns = 1e-9
    want = {"line_search": 1800, "value_grad": 500, "direction": 400,
            "gather_scatter": 500, "score": 300}
    assert r["scope_s"].keys() == want.keys()
    for g, v in want.items():
        assert r["scope_s"][g] == pytest.approx(v * ns, rel=1e-9), g
    # the window is [50, 5000): fixed [50, 1000), per-user [1000, 5000)
    assert r["busy_s"]["fixed"] == pytest.approx(600 * ns)
    assert r["busy_s"]["per-user"] == pytest.approx(3500 * ns)
    assert r["busy_s"]["sweep"] == pytest.approx(4100 * ns)
    assert r["unscoped_share"]["fixed"] == pytest.approx(100 / 600)
    assert r["unscoped_share"]["per-user"] == pytest.approx(0.0, abs=1e-12)
    assert r["unscoped_share"]["sweep"] == pytest.approx(100 / 4100)
    assert r["wave_device_s"]["per-user"] == pytest.approx(
        [3100 * ns, 500 * ns])
    assert r["wave_device_s"]["fixed"] == []
    whiles = r["whiles"]
    assert whiles["%while.20 (s32[]"]["scopes"] == ["lbfgs.line_search",
                                                    "re.solve"]
    assert whiles["%while.10 (f32[64,8]"]["scopes"] == ["re.solve"]
    assert whiles["%while.20 (s32[]"]["seconds"] == pytest.approx(1800 * ns)
    gaps = r["idle_gaps"]
    # longest: [4600, 5000) in descent.update; then [300, 400) etc.
    assert gaps[0]["seconds"] == pytest.approx(400 * ns)
    assert gaps[0]["host"] == "descent.update"
    at_950 = [g for g in gaps if g["at_s"] == pytest.approx(850 * ns)]
    assert at_950 and at_950[0]["host"] == "descent.update"  # [900, 1000)
    assert sum(g["seconds"] for g in gaps) + r["busy_s"]["sweep"] == \
        pytest.approx(4950 * ns)
    with pytest.raises(ValueError, match="markers"):
        scope_reduce.reduce_planes(planes, "bench.mark", 4, SEQ)
    # the recorder's slimmed copy reads exactly as the file it was cut from
    small = scope_reduce.parse_xspace(record_scoped.slim(planes,
                                                         "/device:TPU:"))
    assert [len(ln["events"]) for p in small for ln in p["lines"]] == \
        [5, 11, 3]  # the host's stray event is gone
    assert record_scoped.expected(scope_reduce.reduce_planes(
        small, "bench.mark", 3, SEQ)) == dict(
            record_scoped.expected(r), traced_sweep=2)


def test_scope_reduce_on_the_recorded_chip_trace():
    """The small scoped trace recorded on the chip reads as it did when it
    was recorded (benchmark/selfcheck/scoped.expected.json)."""
    base = os.path.join(BENCH, "selfcheck")
    with open(os.path.join(base, "scoped.expected.json")) as f:
        want = json.load(f)
    assert os.path.getsize(os.path.join(base, "scoped.xplane.pb")) < 100_000
    with open(os.path.join(base, "scoped.xplane.pb"), "rb") as f:
        planes = scope_reduce.parse_xspace(f.read())
    r = scope_reduce.reduce_planes(planes, "bench.mark", want["traced_sweep"],
                                   want["sequence"])
    assert r["scope_s"].keys() == want["scope_s"].keys() == \
        scope_reduce.GROUPS.keys()
    for g, v in want["scope_s"].items():
        assert r["scope_s"][g] == pytest.approx(v, rel=1e-6), g
        assert 0 < r["scope_s"][g] <= r["busy_s"]["sweep"]
    for c, v in want["unscoped_share"].items():
        assert r["unscoped_share"][c] == pytest.approx(v, rel=1e-6, abs=1e-9)
    for c, v in want["wave_device_s"].items():
        assert r["wave_device_s"][c] == pytest.approx(v, rel=1e-6)
    assert {n: w["scopes"] for n, w in r["whiles"].items()} == want["whiles"]
    # every loop the solver runs is owned by a scope
    assert all(w for w in want["whiles"].values())


# -- (g) the readers ----------------------------------------------------------

def _reader(metric):
    """The reader as the harness loads it (``run.layer_reader``)."""
    import faults  # benchmark/faults.py
    return faults.load_run().layer_reader(metric)


def _new_metrics():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    stems = ("re_iters", "lane_util", "pad_share", "ls_evals", "phase_s",
             "scope_s")
    return [m for m in bench["per_layer"]
            if m["name"].split(".", 1)[0] in stems]


def _ctx(rows, **over):
    mix = {"update_sequence": ["fixed", "per-user", "per-item"],
           "coordinates": {"fixed": {"type": "fixed"},
                           "per-user": {"type": "random"},
                           "per-item": {"type": "random"}}}
    return dict({"cell": {"mix": mix}, "ledger_rows": rows,
                 "setup_sweeps": 2, "traced_sweep": None, "trace": None},
                **over)


def test_benchmark_lists_the_new_metrics_additively():
    new = _new_metrics()
    first, second = "ml20m-logistic.steady", "criteo-1m-logistic.steady"
    third, fourth = "kdd12-poisson-l1.steady", "avazu-sparse-re.steady"
    fifth, sixth = "yahoo-music-tron.steady", "kdd10-algebra-tron.steady"
    # PR 26's nineteen read the first cell alone; PR 29 appended its cell to
    # the eleven of them a cell with a sparse fixed effect and one table can
    # report, and added four of these stems for that table; PR 33 appended
    # its cell to the same eleven and added the four for its own table, as
    # PR 35 did, with ``phase_s.project`` for its projection pass
    # The TRON cell appended itself to those of the eleven a solver without
    # a line search reads and to the first cell's tables' three that are not
    # ``ls_evals``, and added those three for its third table. The KDD Cup
    # 2010 cell appended itself to the eight that every TRON cell reads and
    # added three of these stems for its per-student table.
    all_four = {"ls_evals.fixed", "scope_s.line_search", "scope_s.direction"}
    all_five = {"phase_s." + p for p in (
        "digest", "bucketing", "host_stage", "transfer", "program_load")} | {
        "scope_s." + p for p in ("value_grad", "gather_scatter", "score")}
    stems = ("re_iters", "lane_util", "pad_share", "ls_evals")
    assert len(new) == 38
    assert {m["name"] for m in new
            if m["workloads"] == [first, second, third, fourth]} == all_four
    assert {m["name"] for m in new
            if m["workloads"] == [first, second, third, fourth, fifth, sixth]
            } == all_five
    assert {m["name"] for m in new if m["workloads"] == [sixth]} == {
        "re_iters.per-student", "lane_util.per-student",
        "pad_share.per-student"}
    assert {m["name"] for m in new if m["workloads"] == [second]} == {
        stem + ".per-c10" for stem in stems}
    assert {m["name"] for m in new if m["workloads"] == [third]} == {
        stem + ".per-advertiser" for stem in stems}
    assert {m["name"] for m in new if m["workloads"] == [fourth]} == {
        stem + ".per-publisher" for stem in stems} | {"phase_s.project"}
    assert {m["name"] for m in new if m["workloads"] == [first]} == {
        "ls_evals.per-user", "ls_evals.per-item"}
    assert {m["name"] for m in new if m["workloads"] == [first, fifth]} == {
        stem + c for stem in ("re_iters", "lane_util", "pad_share")
        for c in (".per-user", ".per-item")}
    assert {m["name"] for m in new if m["workloads"] == [fifth]} == {
        stem + ".per-artist" for stem in ("re_iters", "lane_util",
                                          "pad_share")}
    for m in new:
        assert os.path.exists(os.path.join(
            BENCH, "layer_metrics", m["name"].split(".", 1)[0] + ".py"))
    assert {m["moves"] for m in new
            if m["name"].startswith("phase_s")} == {"setup_s"}


@pytest.mark.parametrize("metric", [m["name"] for m in _new_metrics()])
def test_reader_finds_nothing_in_an_older_ledger(metric):
    """Rows as the parent commit writes them: no solver counters on the
    waves, no evaluations, no phases — None, and no exception."""
    old = [{"kind": "re_fit_wave", "coordinate": c, "outer_iteration": it,
            "wave": 0, "seconds": 0.01, "entities_fit": 8, "seq": 1}
           for c in ("per-user", "per-item") for it in (1, 2, 3)]
    old += [{"kind": "opt_iter", "coordinate": "fixed", "outer_iteration": 2,
             "iteration": 3, "value": 1.0, "seq": 2},
            {"kind": "coordinate_update", "coordinate": "per-item",
             "outer_iteration": 1, "seq": 3}]
    assert _reader(metric)(metric, _ctx(old)) is None
    assert _reader(metric)(metric, _ctx([])) is None
    assert _reader(metric)(metric, _ctx(old, traced_sweep=3,
                                        trace=None)) is None


def test_readers_on_hand_worked_rows():
    rows, seq = [], 0

    def add(**r):
        nonlocal seq
        rows.append(dict(r, seq=seq))
        seq += 1

    add(kind="phase", name="fit.digest", seconds=1.5)
    add(kind="phase", name="re.bucketing", seconds=0.25)
    add(kind="phase", name="re.bucketing", seconds=0.5)
    add(kind="phase", name="re.transfer", seconds=2.0, bytes=10)
    add(kind="phase", name="fe.transfer", seconds=1.0, bytes=10)
    add(kind="phase", name="fit.coordinates", seconds=9.0)
    add(kind="phase", name="program.load", event="trace", seconds=0.5)
    add(kind="phase", name="program.load", event="cache_fetch", seconds=0.75)
    add(kind="phase", name="program.load", event="compile", seconds=1.0)
    for it in (0, 1, 2, 3):
        if it == 2:  # set-up has ended; a recompile inside the window
            add(kind="phase", name="program.load", event="compile",
                seconds=64.0, coordinate="per-user", outer_iteration=2)
        add(kind="opt_iter", coordinate="fixed", outer_iteration=it,
            iteration=4, evaluations=4 + 2 * it)
        add(kind="re_fit_wave", coordinate="per-user", outer_iteration=it,
            entities_fit=10, iters_sum=30 + it, iters_max=5, evals_sum=50,
            lanes_at_cap=0, rows_useful=60, rows_padded=80, cap=8, lanes=10)
        add(kind="re_fit_wave", coordinate="per-user", outer_iteration=it,
            entities_fit=5, iters_sum=20, iters_max=10, evals_sum=30,
            lanes_at_cap=1, rows_useful=100, rows_padded=160, cap=32,
            lanes=5)
        add(kind="coordinate_update", coordinate="per-item",
            outer_iteration=it)
    ctx = _ctx(rows)

    def read(metric):
        return _reader(metric)(metric, ctx)

    # window = sweeps 2 and 3: iters 32+20+33+20 over 30 lanes
    assert read("re_iters.per-user") == pytest.approx(105 / 30)
    assert read("lane_util.per-user") == pytest.approx(
        100 * 105 / (2 * (10 * 5 + 5 * 10)))
    assert read("pad_share.per-user") == pytest.approx(
        100 * (1 - 320 / 480))
    assert read("ls_evals.per-user") == pytest.approx(160 / 105)
    assert read("ls_evals.fixed") == pytest.approx((8 + 10) / 8)
    assert read("re_iters.per-item") is None
    assert read("phase_s.digest") == 1.5
    assert read("phase_s.bucketing") == 0.75
    assert read("phase_s.transfer") == 3.0
    assert read("phase_s.host_stage") is None
    assert read("phase_s.program_load") == 1.5  # the late compile is not set-up


def test_photon_obs_tail_shows_the_waves(game, tmp_path, capsys):
    from photon_ml_tpu.cli import obs as obs_cli

    _, coords = game
    _, rows, d = _with_ledger(tmp_path, coords)
    tail = obs_cli.tail_ledger(d)
    waves = tail["fit_waves"]
    assert waves["coordinate"] == "per-user" and waves["outer_iteration"] == 1
    assert len(waves["waves"]) == len(coords["per-user"].bucketing.buckets)
    last = [r for r in rows if r["kind"] == "re_fit_wave"][-1]
    assert waves["waves"][-1]["iters_max"] == last["iters_max"]
    assert waves["waves"][-1]["iters_mean"] == pytest.approx(
        last["iters_sum"] / last["entities_fit"], abs=0.005)
    text = obs_cli.render_tail(tail)
    assert "iters_max iters_mean" in text
