"""Fleet chaos suite (photon_ml_tpu/serving/fleet.py + router.py +
supervisor.py, docs/SERVING.md "Scaling out").

The contract under test, the single-process robustness contract lifted
one level (docs/ROBUSTNESS.md):

    every routed request scores BIT-identically to the single-process
    ScoringService, through replica SIGKILL, network partition, and
    hedged sends — or degrades fast with a DEFINED 503 carrying the
    replica id and fleet depth; shards of a dead replica re-home to a
    survivor within the configured deadline (event + metric), and the
    supervised restart brings them home.

Process tests share one module-scoped 2-replica fleet (spawning a
replica costs a JAX interpreter); the SIGKILL drill gets its own fleet
built through the photon-game-fleet CLI path with a --fault-plan, which
doubles as the full HTTP-path smoke.
"""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from photon_ml_tpu import faults
from photon_ml_tpu.serving.router import ShardMap, route_key
from photon_ml_tpu.utils import events as ev

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))


@pytest.fixture(autouse=True)
def _no_leftover_plan():
    yield
    faults.install(None)


# ----------------------------------------------------------- shard map unit


def test_shard_map_home_rehome_restore_deterministic():
    sm = ShardMap(num_shards=8, num_replicas=3)
    assert [sm.owner(s) for s in range(8)] == [0, 1, 2, 0, 1, 2, 0, 1]
    moved = sm.mark_down(1)
    # Replica 1's shards {1, 4, 7} re-home round-robin over survivors
    # {0, 2} — deterministically, so a drill replays identically.
    assert moved == {1: 0, 4: 2, 7: 0}
    assert sm.up() == [0, 2]
    assert sm.shards_of(1) == []
    # A second fleet makes the identical decision.
    sm2 = ShardMap(num_shards=8, num_replicas=3)
    assert sm2.mark_down(1) == moved
    # Restore sends exactly the HOME shards back.
    back = sm.restore(1)
    assert sorted(back) == [1, 4, 7]
    assert [sm.owner(s) for s in range(8)] == [0, 1, 2, 0, 1, 2, 0, 1]
    assert sm.up() == [0, 1, 2]


def test_shard_map_cascading_death_and_exhaustion():
    sm = ShardMap(num_shards=4, num_replicas=2)
    sm.mark_down(0)
    assert all(sm.owner(s) == 1 for s in range(4))
    from photon_ml_tpu.serving.router import ReplicaUnavailable

    with pytest.raises(ReplicaUnavailable):
        sm.mark_down(1)  # no survivor: down, loudly
    with pytest.raises(ValueError):
        ShardMap(num_shards=2, num_replicas=4)  # ownerless replicas


def test_shard_map_next_up_ring_skips_dead():
    sm = ShardMap(num_shards=8, num_replicas=4)
    assert sm.next_up(1) == 2
    sm.mark_down(2)
    assert sm.next_up(1) == 3
    assert sm.next_up(3) == 0


def test_route_key_stability_and_types():
    # Integer ids route by VALUE (the host store's own modulo); strings
    # hash via crc32 — process-stable, unlike salted hash().
    assert route_key(17) == 17
    assert route_key(np.int64(17)) == 17 or route_key(int(np.int64(17))) == 17
    assert route_key(-3) == 3
    assert route_key(None) == 0 and route_key(True) == 0
    import zlib

    assert route_key("user-42") == zlib.crc32(b"user-42")
    assert route_key("user-42") == route_key("user-42")


# ------------------------------------------------- new fault kinds (PR 10)


def test_new_fault_kinds_fire_as_documented():
    plan = faults.FaultPlan(specs=(
        faults.FaultSpec(site="edge", kind="partition",
                         occurrences=(1,)),
        faults.FaultSpec(site="link", kind="delay", seconds=0.15,
                         occurrences=(0,)),
    ))
    inj = faults.FaultInjector(plan)
    inj.fire("edge")  # occurrence 0: clean
    with pytest.raises(faults.InjectedPartition) as ei:
        inj.fire("edge")
    # A partition IS a ConnectionError — what routers fail over on.
    assert isinstance(ei.value, ConnectionError)
    t0 = time.monotonic()
    inj.fire("link")
    assert time.monotonic() - t0 >= 0.14
    assert inj.fires("edge") == 1 and inj.fires("link") == 1
    # replica_kill validates as a kind (mechanics = kill: SIGKILL —
    # drilled for real in the CLI fleet test below).
    faults.FaultSpec(site="x", kind="replica_kill")
    with pytest.raises(ValueError):
        faults.FaultSpec(site="x", kind="network_blip")


def test_new_kinds_deterministic_across_spawn():
    """The plan crosses the spawn boundary (pool initializer) and the
    new kinds fire at the SAME addressed occurrences in a fresh
    interpreter — twice, identically (training sites get the same
    guarantee: the kinds are site-agnostic)."""
    from photon_ml_tpu.utils.workers import make_pool

    plan = faults.FaultPlan(specs=(
        faults.FaultSpec(site="edge", kind="partition",
                         occurrences=(1,), scope="worker"),))

    def fire_pattern():
        with make_pool("process", workers=1,
                       ctx={"fault_plan": plan}) as pool:
            outcomes = []
            for i in range(3):
                exc = pool.submit(faults.fire, "edge").exception()
                outcomes.append(type(exc).__name__ if exc else None)
            return outcomes

    first = fire_pattern()
    assert first == [None, "InjectedPartition", None]
    assert fire_pattern() == first


# ------------------------------------------------ DCN dryrun seam (PR 10)


def test_sync_global_devices_skips_loudly_on_cpu(caplog):
    """The PR 6 sync seam must not crash the CPU-backend DCN dryrun
    ("Multiprocess computations aren't implemented"): unsupported
    backends skip with a loud log instead."""
    import logging

    from photon_ml_tpu.cli import game_train

    with caplog.at_level(logging.WARNING,
                         logger="photon_ml_tpu.cli"):
        game_train._sync_global_devices_or_skip("checkpoint-cleanup")
    assert any("SKIPPING sync_global_devices" in r.message
               for r in caplog.records)


def test_sync_global_devices_raises_off_the_cpu_backend(monkeypatch):
    """Only the CPU backend may skip the barrier: anywhere else a failed
    barrier raises, whatever its message says."""
    import jax
    from jax.experimental import multihost_utils

    from photon_ml_tpu.cli import game_train

    monkeypatch.setattr(jax, "default_backend", lambda: "notcpu")

    def unimplemented(tag):
        raise RuntimeError("Multiprocess computations aren't implemented")

    monkeypatch.setattr(multihost_utils, "sync_global_devices",
                        unimplemented)
    with pytest.raises(RuntimeError, match="implemented"):
        game_train._sync_global_devices_or_skip("t")


# ------------------------------------------------------- live fleet tests


E, DG, DR = 32, 6, 4


def _tiny_model():
    import jax.numpy as jnp

    from photon_ml_tpu.game.models import (FixedEffectModel, GameModel,
                                           RandomEffectModel)
    from photon_ml_tpu.models.coefficients import Coefficients
    from photon_ml_tpu.types import TaskType

    rng = np.random.default_rng(11)
    return GameModel(task=TaskType.LOGISTIC_REGRESSION, models={
        "fixed": FixedEffectModel("global", Coefficients(
            jnp.asarray(rng.normal(size=DG).astype(np.float32)))),
        "per-user": RandomEffectModel(
            "userId", "re_userId",
            jnp.asarray(rng.normal(size=(E, DR)).astype(np.float32))),
    })


def _request_objs(n, seed=5, entity_fn=None):
    rng = np.random.default_rng(seed)
    objs = []
    for i in range(n):
        eid = int(entity_fn(i)) if entity_fn else int(i % E)
        objs.append({
            "features": {
                "global": rng.normal(size=DG).astype(
                    np.float32).tolist(),
                "re_userId": rng.normal(size=DR).astype(
                    np.float32).tolist()},
            "entity_ids": {"userId": eid}, "uid": i})
    return objs


def _oracle_scores(model, objs):
    """Single-process oracle through the SAME flush shape as serial
    fleet posts (one request per flush → bucket-1 program → the bit
    pattern the fleet must reproduce)."""
    from photon_ml_tpu.serving import ScoringRequest, ScoringService

    svc = ScoringService(model, max_wait_ms=0.5)
    try:
        return np.asarray([
            float(svc.submit(ScoringRequest(
                features={k: np.asarray(v, np.float32)
                          for k, v in o["features"].items()},
                entity_ids=o["entity_ids"])).result(timeout=60))
            for o in objs], np.float32)
    finally:
        svc.close()


def _post(url, objs, timeout=60.0, trace=False):
    body = json.dumps({"requests": objs, "trace": trace}).encode()
    req = urllib.request.Request(
        url + "/score", data=body,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def _get(url, path, timeout=10.0):
    with urllib.request.urlopen(url + path, timeout=timeout) as resp:
        return resp.read().decode()


@pytest.fixture(scope="module")
def fleet_env(tmp_path_factory):
    """One running 2-replica fleet + oracle scores, shared by the
    non-destructive process tests (each replica is a JAX interpreter —
    spawn once)."""
    from photon_ml_tpu.models import io as model_io
    from photon_ml_tpu.serving.fleet import (ServingFleet,
                                             make_fleet_http_server)

    td = tmp_path_factory.mktemp("fleet")
    model = _tiny_model()
    model_dir = str(td / "model")
    model_io.save_game_model(model, model_dir)
    fleet = ServingFleet(
        replica_args=["--model-dir", model_dir, "--max-wait-ms", "0.5"],
        num_replicas=2, workdir=str(td / "work"),
        probe_interval_s=0.1, heartbeat_deadline_s=1.0,
        rehome_deadline_s=5.0, hedge_after_s=0.2,
        retry_backoff_s=0.1, retries=3)
    server = None
    # finally-guarded teardown (PML016): a bind failure after
    # fleet.start(), or a test body raising, must still reap the
    # replica subprocesses.
    try:
        fleet.start()
        server = make_fleet_http_server(fleet, port=0)
        threading.Thread(target=server.serve_forever,
                         daemon=True).start()
        url = f"http://127.0.0.1:{server.server_address[1]}"
        objs = _request_objs(12)
        env = {"fleet": fleet, "url": url, "model": model, "objs": objs,
               "model_dir": model_dir,
               "expected": _oracle_scores(model, objs)}
        yield env
    finally:
        if server is not None:
            server.shutdown()
            server.server_close()
        fleet.close()


def test_fleet_parity_bit_identical_and_affinity(fleet_env):
    """Serial singleton posts through the fleet reproduce the
    single-process service's bits exactly, and entity affinity holds:
    the same entity always lands on the same replica."""
    url, objs = fleet_env["url"], fleet_env["objs"]
    fleet = fleet_env["fleet"]
    got = np.asarray([_post(url, [o])["scores"][0] for o in objs],
                     np.float32)
    np.testing.assert_array_equal(got, fleet_env["expected"])
    # Affinity: entity e routes to shard e % num_shards, owned by its
    # home replica — assert through the router's own resolution.
    for o in objs:
        shard = fleet.router.shard_for(o)
        assert shard == o["entity_ids"]["userId"] % fleet.num_shards
        assert fleet.router.replica_for(o) == fleet.shard_map.home(shard)
    hz = json.loads(_get(url, "/healthz"))
    assert hz["status"] == "ok" and not hz["degraded"]
    assert hz["fleet_depth"] == 2


def test_fleet_trace_attribution_rides_through(fleet_env):
    """`"trace": true` forwards to the replica and its per-request
    stage attribution rides back through the router merge."""
    url, objs = fleet_env["url"], fleet_env["objs"]
    out = _post(url, objs[:3], trace=True)
    attr = out.get("attribution")
    assert attr is not None and len(attr) == 3
    assert all(a is not None and "device_score_ms" in a for a in attr)


def test_fleet_partition_gives_defined_503_no_double_score(fleet_env):
    """A partition dropping EVERY route to the fleet's replicas during
    the flush window degrades to one defined 503 carrying replica id +
    fleet depth — no hang, no double-score, and the error budget
    burns exactly once per request."""
    url = fleet_env["url"]
    fleet = fleet_env["fleet"]
    before = fleet.metrics.snapshot()
    obj = _request_objs(1, seed=77)[0]
    # Driver-side plan: fleet.route is the router's send seam; dropping
    # every attempt (any index) exhausts the bounded retries.
    plan = faults.FaultPlan(specs=(
        faults.FaultSpec(site="fleet.route", kind="partition"),))
    faults.install(plan)
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(url, [obj], timeout=30.0)
        assert ei.value.code == 503
        body = json.loads(ei.value.read())
        assert body["fleet_depth"] == 2
        assert "replica_id" in body
        assert body["degraded"] is True  # the defined during-failure view
    finally:
        faults.install(None)
    after = fleet.metrics.snapshot()
    assert after["unserved_total"] == before["unserved_total"] + 1
    assert after["forward_errors_total"] > before["forward_errors_total"]
    # The edge heals → the SAME request scores exactly once, correctly.
    out = _post(url, [obj])
    assert len(out["scores"]) == 1
    exp = _oracle_scores(fleet_env["model"], [obj])
    np.testing.assert_array_equal(
        np.asarray(out["scores"], np.float32), exp)


def test_fleet_hedged_send_dedup_exactly_one_response(fleet_env):
    """A slow primary triggers a hedged second-send; the response
    arrives EXACTLY once (winner claimed, loser discarded) with the
    same bits either replica would produce, and the hedge counters
    move."""
    url = fleet_env["url"]
    fleet = fleet_env["fleet"]
    # Entity 0 → shard 0 → replica 0; delay only replica 0's edge so
    # the hedge target (replica 1) wins the race.
    obj = _request_objs(1, seed=88, entity_fn=lambda i: 0)[0]
    exp = _oracle_scores(fleet_env["model"], [obj])
    before = fleet.metrics.snapshot()
    plan = faults.FaultPlan(specs=(
        faults.FaultSpec(site="fleet.route", kind="delay",
                         seconds=3.0, indices=(0,), max_fires=1),))
    faults.install(plan)
    try:
        t0 = time.monotonic()
        out = _post(url, [obj], timeout=30.0)
        dt = time.monotonic() - t0
    finally:
        faults.install(None)
    assert len(out["scores"]) == 1  # exactly-one response
    np.testing.assert_array_equal(
        np.asarray(out["scores"], np.float32), exp)
    assert dt < 2.9  # the hedge answered before the delayed primary
    after = fleet.metrics.snapshot()
    assert after["hedges_total"] == before["hedges_total"] + 1
    assert after["hedge_wins_total"] == before["hedge_wins_total"] + 1


def test_fleet_metrics_and_slo_render(fleet_env):
    url = fleet_env["url"]
    text = _get(url, "/metrics")
    for line in ("photon_fleet_replicas 2", "photon_fleet_requests_total",
                 "photon_fleet_hedge_wins_total",
                 "photon_fleet_slo_availability",
                 'photon_fleet_replica_up{replica="0"} 1'):
        assert line in text, text
    slo = json.loads(_get(url, "/slo"))
    assert slo["requests_in_window"] >= 1
    assert "lifetime" in slo and "rehomes_total" in slo["lifetime"]


def test_fleet_admission_control_503_carries_depth(fleet_env):
    """Fleet-level admission: with the in-flight bound forced to zero,
    the front door sheds with the fleet-depth body instead of
    queueing."""
    url = fleet_env["url"]
    fleet = fleet_env["fleet"]
    old = fleet.max_inflight
    fleet.max_inflight = 0
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(url, _request_objs(1, seed=99))
        assert ei.value.code == 503
        body = json.loads(ei.value.read())
        assert body["fleet_depth"] == 2
        assert body["max_inflight"] == 0
    finally:
        fleet.max_inflight = old


def test_fleet_replica_sigkill_mid_request_retry_parity_rehome(
        tmp_path):
    """THE chaos acceptance drill, through the photon-game-fleet CLI
    path: a --fault-plan replica_kill SIGKILLs replica 1 inside its
    3rd flush — mid-request. The router retries onto the re-homed
    owner, the caller sees the SAME bits the single-process service
    produces, the re-home lands within the deadline with its event,
    and the supervised restart returns the shards home."""
    from photon_ml_tpu.cli import fleet as fleet_cli
    from photon_ml_tpu.models import io as model_io
    from photon_ml_tpu.serving.fleet import make_fleet_http_server

    model = _tiny_model()
    model_dir = str(tmp_path / "model")
    model_io.save_game_model(model, model_dir)
    plan = faults.FaultPlan(specs=(faults.FaultSpec(
        site="fleet.replica_flush", kind="replica_kill",
        indices=(1,), occurrences=(2,)),))
    plan_path = str(tmp_path / "plan.json")
    with open(plan_path, "w") as f:
        f.write(plan.to_json())

    # Odd entities route to odd shards → home replica 1 (num_shards=8,
    # 2 replicas): the doomed replica owns every request we send.
    objs = _request_objs(8, seed=6, entity_fn=lambda i: 2 * i + 1)
    expected = _oracle_scores(model, objs)

    events = []
    ev.default_emitter.register(events.append)
    args = fleet_cli.build_parser().parse_args([
        "--model-dir", model_dir, "--replicas", "2", "--port", "0",
        "--workdir", str(tmp_path / "work"),
        "--fault-plan", plan_path,
        "--probe-interval-s", "0.1", "--heartbeat-deadline-s", "1.0",
        "--rehome-deadline-s", "5.0", "--max-wait-ms", "0.5",
        "--retries", "3", "--retry-backoff-s", "0.1"])
    fleet = fleet_cli.create_fleet(args)
    try:
        fleet.start()
        server = make_fleet_http_server(fleet, port=0)
        threading.Thread(target=server.serve_forever,
                         daemon=True).start()
        url = f"http://127.0.0.1:{server.server_address[1]}"
        try:
            got = []
            for o in objs:  # the 3rd flush dies mid-request
                got.append(_post(url, [o], timeout=60.0)["scores"][0])
            np.testing.assert_array_equal(
                np.asarray(got, np.float32), expected)

            snap = fleet.metrics.snapshot()
            assert snap["replica_deaths_total"] == 1
            assert snap["rehomes_total"] == 1
            assert snap["unserved_total"] == 0
            assert snap["forward_retries_total"] >= 1
            assert snap["rehome_seconds_last"] <= 5.0
            died = [e for e in events if isinstance(e, ev.ReplicaDied)]
            rehomed = [e for e in events
                       if isinstance(e, ev.ShardRehomed)]
            assert died and died[0].replica_id == 1
            assert rehomed and rehomed[0].replica_id == 1
            assert rehomed[0].seconds <= 5.0
            assert set(rehomed[0].new_owners) == {0}

            # Recovery: restart brings the shards home and the degraded
            # flag clears (the CheckpointRecovered-style closing leg).
            deadline = time.monotonic() + 60
            hz = json.loads(_get(url, "/healthz"))
            while time.monotonic() < deadline and hz["degraded"]:
                time.sleep(0.2)
                hz = json.loads(_get(url, "/healthz"))
            assert not hz["degraded"], hz
            assert hz["shards_away_from_home"] == 0
            assert any(isinstance(e, ev.ReplicaRecovered)
                       for e in events)
            # Full HTTP-path smoke epilogue: metrics + slo still answer.
            assert "photon_fleet_rehomes_total 1" in _get(url,
                                                          "/metrics")
            json.loads(_get(url, "/slo"))
        finally:
            server.shutdown()
            server.server_close()
    finally:
        ev.default_emitter.unregister(events.append)
        fleet.close()
        faults.install(None)
