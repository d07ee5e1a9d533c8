"""Multi-host fabric suite (photon_ml_tpu/fabric/*, docs/SERVING.md
"Multi-host fleet", docs/STREAMING.md "Multi-host streaming").

The contract under test, the single-host robustness contract lifted to
the DCN edge (docs/ROBUSTNESS.md):

  TRAINING — the streamed FE pass sharded over W hosts computes the
  same objective as one host (world 1 bit-identical, world 2 within
  the sharded-parity band); a partition mid-allreduce retries the
  bounded deterministic ladder then fails DEFINED (FabricPartitioned);
  per-iteration rank digests either match or raise RankDivergence on
  every rank; a host dying mid-fit leaves rank 0's checkpoint behind
  and the W→W' resume lands within the sharded-parity band.

  SERVING — a fleet spanning machine agents scores bit-identically to
  the single-process oracle; an unreachable agent control plane is
  UNKNOWN, never a death; the publish chain crosses the wire with its
  CRC fence intact (a torn fetch leaves the previous version
  servable); whole-machine SIGKILL turns into a bounded cross-machine
  re-home with zero unserved requests.

Process tests share one module-scoped two-agent remote fleet (each
replica is a JAX interpreter — spawn once); the whole-machine drill
runs LAST because it permanently kills agent 0.
"""

import hashlib
import json
import logging
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from photon_ml_tpu import faults
from photon_ml_tpu.fabric import runtime as fabric_runtime
from photon_ml_tpu.fabric.collective import (FabricComm, FabricPartitioned,
                                             RankDivergence)

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))


@pytest.fixture(autouse=True)
def _no_leftover_plan():
    yield
    faults.install(None)
    fabric_runtime.install(None)


# ------------------------------------------------------ comm harness


def _make_world(world, **kw):
    """W comms in one process: rank 0 first (binds the coordinator),
    the rest dial it — the in-process stand-in for W hosts."""
    comms = [FabricComm(0, world, **kw)]
    for r in range(1, world):
        comms.append(FabricComm(r, world,
                                coordinator=comms[0].coordinator, **kw))
    return comms


def _run_ranks(comms, fn, join_s=60.0):
    """Run ``fn(comm)`` on one thread per rank; returns (results,
    errors) indexed by rank — a raise on one rank never hides the
    others' outcomes (the drill must see EVERY rank's verdict)."""
    results = [None] * len(comms)
    errors = [None] * len(comms)

    def go(r):
        try:
            results[r] = fn(comms[r])
        except BaseException as e:  # noqa: BLE001 - verdict collection
            errors[r] = e

    threads = [threading.Thread(target=go, args=(r,), daemon=True)
               for r in range(len(comms))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(join_s)
    return results, errors


def _close_world(comms):
    for c in comms:
        c.close()


# ------------------------------------------------- collective units


def test_allreduce_allgather_rank_identical_and_deterministic():
    comms = _make_world(3, timeout_s=10.0)
    base = np.arange(4, dtype=np.float64)
    try:
        results, errors = _run_ranks(
            comms, lambda c: (c.allreduce(base * (c.rank + 1), tag="vg"),
                              c.allgather(np.full(c.rank + 1, float(c.rank)),
                                          tag="margins")))
        assert errors == [None, None, None]
        for red, gath in results:
            # 1x + 2x + 3x = 6x, identical BITS on every rank (one
            # rank-order f64 reduction at the coordinator).
            np.testing.assert_array_equal(red, 6.0 * base)
            np.testing.assert_array_equal(
                gath, np.array([0., 1., 1., 2., 2., 2.]))
        # Second round on the same tags: seq advances, same answer.
        results2, errors2 = _run_ranks(
            comms, lambda c: c.allreduce(base * (c.rank + 1), tag="vg"))
        assert errors2 == [None, None, None]
        for red in results2:
            np.testing.assert_array_equal(red, 6.0 * base)
    finally:
        _close_world(comms)


def test_world_one_is_bit_identical_and_socket_free():
    """The single-host path: no server, no socket, and the array comes
    back bit-identical — the bench gate's D=1 parity line."""
    comm = FabricComm(0, 1)
    x = np.random.default_rng(7).normal(size=33)
    out = comm.allreduce(x, tag="vg")
    np.testing.assert_array_equal(out, x)
    assert comm._server is None  # never bound a port
    assert comm.digest_check("digest/1", "abc") == {
        "digests": {"0": "abc"}, "match": True}
    np.testing.assert_array_equal(comm.allgather(x, tag="m"), x)
    comm.close()


def test_partition_one_attempt_retries_then_succeeds():
    """One injected drop of the first round's first attempt: the ladder
    retries with deterministic backoff, the round completes, and the
    retry counter moves — degradation, not failure."""
    from photon_ml_tpu import obs
    from photon_ml_tpu.obs.metrics import MetricsRegistry

    comms = _make_world(2, timeout_s=10.0, retry_backoff_s=0.01)
    plan = faults.FaultPlan(specs=(faults.FaultSpec(
        site="fabric.dcn_allreduce", kind="partition", indices=(1,),
        max_fires=1),))
    mx = MetricsRegistry()
    try:
        with obs.activated(metrics_obj=mx), faults.installed(plan):
            results, errors = _run_ranks(
                comms,
                lambda c: c.allreduce(np.ones(3) * (c.rank + 1), tag="vg"))
        assert errors == [None, None]
        for red in results:
            np.testing.assert_array_equal(red, np.full(3, 3.0))
        snap = mx.snapshot()
        assert snap.get("photon_fabric_retries_total", 0) >= 1
        assert snap.get('photon_fabric_allreduce_total{op="allreduce"}',
                        0) >= 2
    finally:
        _close_world(comms)


def test_partition_every_attempt_fails_defined():
    """The DCN edge stays down: after the bounded ladder every rank
    raises FabricPartitioned — loud and defined, never a hang."""
    comms = _make_world(2, timeout_s=5.0, retry_backoff_s=0.01)
    plan = faults.FaultPlan(specs=(faults.FaultSpec(
        site="fabric.dcn_allreduce", kind="partition"),))
    try:
        with faults.installed(plan):
            _, errors = _run_ranks(
                comms, lambda c: c.allreduce(np.ones(2), tag="vg"))
        assert all(isinstance(e, FabricPartitioned) for e in errors)
        assert "attempts" in str(errors[0])
    finally:
        _close_world(comms)


def test_rank_silent_mid_round_times_out_to_partition():
    """A rank that never shows up (SIGKILL'd host): the coordinator's
    finite round deadline turns the survivor's wait into retries and
    then FabricPartitioned — the blocking call has a bound."""
    comms = _make_world(2, timeout_s=0.5, retry_backoff_s=0.01,
                        max_retries=1)
    try:
        t0 = time.monotonic()
        with pytest.raises(FabricPartitioned):
            comms[0].allreduce(np.ones(2), tag="vg")  # rank 1 silent
        assert time.monotonic() - t0 < 10.0
    finally:
        _close_world(comms)


def test_digest_divergence_raises_on_every_rank():
    comms = _make_world(2, timeout_s=10.0)
    try:
        results, errors = _run_ranks(
            comms, lambda c: c.digest_check("digest/1", "same"))
        assert errors == [None, None]
        assert all(r["match"] and set(r["digests"]) == {"0", "1"}
                   for r in results)
        _, errors = _run_ranks(
            comms,
            lambda c: c.digest_check("digest/2", f"rank-{c.rank}"))
        assert all(isinstance(e, RankDivergence) for e in errors)
    finally:
        _close_world(comms)


# ------------------------------------------ sharded streamed FE pass


def _chunks_of(batch, chunk_rows):
    from photon_ml_tpu.data import sparse as sp

    n = batch.num_rows
    for lo in range(0, n, chunk_rows):
        hi = min(lo + chunk_rows, n)
        yield sp.SparseBatch(
            indices=np.asarray(batch.indices)[lo:hi],
            values=np.asarray(batch.values)[lo:hi],
            labels=np.asarray(batch.labels)[lo:hi],
            weights=np.asarray(batch.weights)[lo:hi],
            offsets=np.asarray(batch.offsets)[lo:hi],
            num_features=batch.num_features,
        )


@pytest.fixture(scope="module")
def chunked():
    from photon_ml_tpu.data import sparse as sp
    from photon_ml_tpu.ops import streaming_sparse as ss

    batch, _ = sp.synthetic_sparse(700, 96, 5, seed=3)
    # 3 chunks of 256 rows (last one short): world 2 splits them 2/1,
    # so both the multi-chunk and the single-chunk host leg run.
    return ss.build_chunked(_chunks_of(batch, 256), batch.num_features,
                            256, num_hot=16)


def _pad_offsets(chunked):
    import jax.numpy as jnp

    return jnp.zeros((chunked.num_chunks * chunked.chunk_rows,))


def test_fabric_stream_world_one_bit_identical(chunked):
    """W=1 FabricChunkStream is the wrapped local stream, bit for bit
    (f32 -> f64 wire -> f32 is exact)."""
    import jax.numpy as jnp

    from photon_ml_tpu.fabric.stream import FabricChunkStream
    from photon_ml_tpu.ops import losses
    from photon_ml_tpu.ops import streaming_sparse as ss

    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=chunked.dim).astype(np.float32))
    off = _pad_offsets(chunked)
    comm = FabricComm(0, 1)
    fs = FabricChunkStream(chunked, comm)
    v_f, g_f = fs.value_and_gradient(losses.LOGISTIC)(w, off)
    v_l, g_l = ss.make_value_and_gradient(losses.LOGISTIC, chunked)(w, off)
    assert float(v_f) == float(v_l)
    np.testing.assert_array_equal(np.asarray(g_f), np.asarray(g_l))
    np.testing.assert_array_equal(
        np.asarray(fs.margins(w)),
        np.asarray(ss.margins_chunked(chunked, w)))
    comm.close()


def test_fabric_stream_world_two_parity(chunked):
    """W=2: both ranks see the SAME reduced (value, grad) bits, within
    the sharded-parity band of the one-host stream; margins reassemble
    in global row order bit-identically."""
    import jax.numpy as jnp

    from photon_ml_tpu.fabric.stream import FabricChunkStream
    from photon_ml_tpu.ops import losses
    from photon_ml_tpu.ops import streaming_sparse as ss

    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.normal(size=chunked.dim).astype(np.float32))
    off = _pad_offsets(chunked)
    comms = _make_world(2, timeout_s=30.0)

    def pass_once(comm):
        fs = FabricChunkStream(chunked, comm)
        v, g = fs.value_and_gradient(losses.LOGISTIC)(w, off)
        return (float(v), np.asarray(g), np.asarray(fs.margins(w)))

    try:
        results, errors = _run_ranks(comms, pass_once, join_s=120.0)
        assert errors == [None, None]
        (v0, g0, m0), (v1, g1, m1) = results
        assert v0 == v1  # the reduction happened ONCE, at rank 0
        np.testing.assert_array_equal(g0, g1)
        np.testing.assert_array_equal(m0, m1)
        v_l, g_l = ss.make_value_and_gradient(losses.LOGISTIC,
                                              chunked)(w, off)
        assert abs(v0 - float(v_l)) < 1e-3 * max(abs(float(v_l)), 1.0)
        np.testing.assert_allclose(g0, np.asarray(g_l), rtol=1e-4,
                                   atol=1e-3)
        np.testing.assert_array_equal(
            m0, np.asarray(ss.margins_chunked(chunked, w)))
        assert m0.shape == (700,)
    finally:
        _close_world(comms)


def _l2_wrap(vg, off, l2=1.0):
    import jax.numpy as jnp

    def vg_l2(w):
        f, g = vg(w, off)
        return f + 0.5 * l2 * jnp.sum(w * w), g + l2 * w

    return vg_l2


def test_fabric_fit_two_ranks_digest_clean_and_parity(chunked):
    """The tentpole's training leg end-to-end, in-process: a 2-rank
    sharded streamed L-BFGS fit with the per-iteration cross-rank
    digest exchange — digests MATCH every accepted iteration, both
    ranks land on identical bits, and the optimum sits within the
    sharded-parity band of the one-host fit."""
    import jax.numpy as jnp

    from photon_ml_tpu.fabric.stream import FabricChunkStream
    from photon_ml_tpu.ops import losses
    from photon_ml_tpu.ops import streaming_sparse as ss
    from photon_ml_tpu.optim import OptimizerConfig
    from photon_ml_tpu.optim.streaming import minimize_streaming

    off = _pad_offsets(chunked)
    cfg = OptimizerConfig(max_iterations=25, tolerance=1e-9)
    w0 = jnp.zeros((chunked.dim,), jnp.float32)
    r_ref = minimize_streaming(
        _l2_wrap(ss.make_value_and_gradient(losses.LOGISTIC, chunked),
                 off), w0, cfg)

    comms = _make_world(2, timeout_s=60.0)

    def fit(comm):
        fs = FabricChunkStream(chunked, comm)
        vg = _l2_wrap(fs.value_and_gradient(losses.LOGISTIC), off)

        def digest_hook(it, w, fv, gn):
            d = hashlib.sha1(np.asarray(w, np.float32).tobytes()
                             + np.float64(fv).tobytes()).hexdigest()
            comm.digest_check(f"digest/{it}", d)

        r = minimize_streaming(vg, w0, cfg, on_accept=digest_hook)
        return np.asarray(r.w), float(r.value), int(r.iterations)

    try:
        results, errors = _run_ranks(comms, fit, join_s=300.0)
        assert errors == [None, None]
        (wa, va, ita), (wb, vb, itb) = results
        np.testing.assert_array_equal(wa, wb)  # rank-identical bits
        assert va == vb and ita == itb
        np.testing.assert_allclose(wa, np.asarray(r_ref.w), rtol=5e-3,
                                   atol=5e-3)
    finally:
        _close_world(comms)


def test_host_death_mid_fit_checkpoints_survive_elastic_resume(
        chunked, tmp_path, caplog):
    """A host dies mid-fit (W=2): the survivor's next allreduce fails
    DEFINED (FabricPartitioned) after the bounded ladder, rank 0's
    StreamingStateStore holds the last accepted iteration, and the
    W=2 -> W=1 resume is announced as ELASTIC and converges within the
    sharded-parity band of the uninterrupted one-host fit."""
    import jax.numpy as jnp

    from photon_ml_tpu.fabric.stream import FabricChunkStream
    from photon_ml_tpu.game.checkpoint import StreamingStateStore
    from photon_ml_tpu.ops import losses
    from photon_ml_tpu.ops import streaming_sparse as ss
    from photon_ml_tpu.optim import OptimizerConfig
    from photon_ml_tpu.optim.streaming import minimize_streaming

    off = _pad_offsets(chunked)
    cfg = OptimizerConfig(max_iterations=30, tolerance=1e-9)
    w0 = jnp.zeros((chunked.dim,), jnp.float32)
    local_vg = _l2_wrap(
        ss.make_value_and_gradient(losses.LOGISTIC, chunked), off)
    r_ref = minimize_streaming(local_vg, w0, cfg)

    fp = {"d": int(chunked.dim), "loss": "logistic", "l2": 1.0}
    store = StreamingStateStore(str(tmp_path / "stream"))
    comms = _make_world(2, timeout_s=0.75, retry_backoff_s=0.01,
                        max_retries=1)

    def fit(comm):
        fs = FabricChunkStream(chunked, comm)
        vg = _l2_wrap(fs.value_and_gradient(losses.LOGISTIC), off)
        calls = [0]

        def vg_mortal(w):
            calls[0] += 1
            if comm.rank == 1 and calls[0] > 8:
                raise RuntimeError("host lost")  # the SIGKILL stand-in
            return vg(w)

        save = None
        if comm.rank == 0:
            save = lambda st: store.save(  # noqa: E731
                st, fingerprint=fp, environment={"fabric_world": 2})
        return minimize_streaming(vg_mortal, w0, cfg,
                                  checkpoint_save=save)

    try:
        _, errors = _run_ranks(comms, fit, join_s=300.0)
    finally:
        _close_world(comms)
    assert isinstance(errors[1], RuntimeError)  # the dead host
    assert isinstance(errors[0], FabricPartitioned)  # the survivor

    with caplog.at_level(logging.WARNING,
                         logger="photon_ml_tpu.game.checkpoint"):
        state = store.load(expected_fingerprint=fp,
                           environment={"fabric_world": 1})
    assert state is not None  # rank 0 committed at least one iteration
    assert any("ELASTIC resume" in r.message for r in caplog.records)
    r_resumed = minimize_streaming(local_vg, w0, cfg, resume_state=state)
    np.testing.assert_allclose(np.asarray(r_resumed.w),
                               np.asarray(r_ref.w), rtol=5e-3, atol=5e-3)


# --------------------------------------------- serving: machine agents


def _start_agent(workdir, name):
    """One per-machine agent subprocess in its OWN process group, so a
    whole-machine SIGKILL (killpg) takes the agent AND every replica it
    spawned — the drill's death shape."""
    os.makedirs(workdir, exist_ok=True)
    ready = os.path.join(workdir, "agent.ready")
    env = dict(os.environ)
    env["PYTHONPATH"] = (REPO + os.pathsep + env["PYTHONPATH"]
                         if env.get("PYTHONPATH") else REPO)
    log_f = open(os.path.join(workdir, "agent.log"), "ab")
    try:
        proc = subprocess.Popen(
            [sys.executable, "-m", "photon_ml_tpu.fabric.agent",
             "--workdir", workdir, "--machine", name,
             "--host", "127.0.0.1", "--port", "0", "--ready-file", ready],
            stdout=log_f, stderr=subprocess.STDOUT, env=env,
            start_new_session=True)
    finally:
        log_f.close()
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise RuntimeError(f"agent {name} exited rc={proc.returncode}")
        if os.path.exists(ready):
            try:
                with open(ready) as f:
                    info = json.load(f)
                return proc, f"http://127.0.0.1:{int(info['port'])}"
            except (OSError, ValueError):
                pass  # torn read mid-write; poll again
        time.sleep(0.05)
    raise RuntimeError(f"agent {name} not ready before its deadline")


def _kill_machine(proc):
    """SIGKILL the agent's whole process group (agent + its replicas)."""
    try:
        os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
    except (OSError, ProcessLookupError):
        pass
    try:
        proc.wait(timeout=5.0)
    except subprocess.TimeoutExpired:
        pass


def test_local_transport_gives_replica_i_chip_i_or_refuses(
        tmp_path, monkeypatch):
    """On a TPU host a chip belongs to one process: replica i is pinned
    to chip i through the child environment, and a replica with no chip
    left is refused at spawn with the counts in the message (no child
    is started to fail on its own minutes later)."""
    from photon_ml_tpu.fabric import transport as tr
    from photon_ml_tpu.serving.supervisor import ReplicaHandle

    # This suite runs with JAX_PLATFORMS=cpu: no placement, no limit.
    assert tr.local_tpu_chips() == []
    monkeypatch.setattr(tr, "local_tpu_chips",
                        lambda: ["/dev/vfio/0", "/dev/vfio/1"])
    t = tr.LocalTransport(lambda rid, rf: [
        sys.executable, "-c",
        "import os; print(os.environ['TPU_VISIBLE_CHIPS'], "
        "os.environ['TPU_PROCESS_BOUNDS'])"], str(tmp_path))
    handle = ReplicaHandle(replica_id=1, generation=1)
    t.spawn(handle)
    assert handle.proc.wait(timeout=30) == 0
    with open(handle.log_path) as f:
        assert f.read().split() == ["1", "1,1,1"]
    with pytest.raises(tr.ReplicaStartupError,
                       match=r"3 replica\(s\) cannot be placed.*2 TPU chip"):
        t.spawn(ReplicaHandle(replica_id=2, generation=1))
    # The supervisor asks before it spawns anything.
    t.check_capacity(2)
    with pytest.raises(tr.ReplicaStartupError, match="at most 2"):
        t.check_capacity(5)


def test_remote_transport_adopts_running_replica(tmp_path):
    """First contact with a replica already up under an agent ADOPTS it
    (same pid, no respawn) — restarting a serving replica just to learn
    its address would be a self-inflicted outage."""
    from photon_ml_tpu.fabric.transport import RemoteTransport
    from photon_ml_tpu.serving.supervisor import ReplicaHandle

    fake = str(tmp_path / "fake_replica.py")
    with open(fake, "w") as f:
        f.write(
            "import json, os, sys, time\n"
            "rf = sys.argv[sys.argv.index('--ready-file') + 1]\n"
            "tmp = rf + '.tmp'\n"
            "with open(tmp, 'w') as fh:\n"
            "    json.dump({'pid': os.getpid(), 'host': '127.0.0.1',\n"
            "               'port': 1}, fh)\n"
            "os.replace(tmp, rf)\n"
            "time.sleep(120)\n")
    proc, url = _start_agent(str(tmp_path / "m0"), "m0")
    try:
        argv = [sys.executable, fake, "--ready-file", "x"]
        with urllib.request.urlopen(urllib.request.Request(
                f"{url}/spawn",
                data=json.dumps({"replica_id": 7, "argv": argv}).encode(),
                headers={"Content-Type": "application/json"}),
                timeout=10.0) as resp:
            json.loads(resp.read())

        def replica_info():
            with urllib.request.urlopen(f"{url}/replica/7",
                                        timeout=5.0) as resp:
                return json.loads(resp.read())

        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline:
            info = replica_info()
            if info.get("state") == "up":
                break
            time.sleep(0.05)
        assert info["state"] == "up"
        pid_before = info["pid"]

        t = RemoteTransport([url], lambda rid, rf: [
            sys.executable, fake, "--ready-file", rf])
        handle = ReplicaHandle(replica_id=7, generation=1)
        t.spawn(handle)  # first contact -> adopt, not respawn
        assert handle.machine == url
        assert replica_info()["pid"] == pid_before
        assert t.alive(handle) is True
        host, port = t.await_ready(handle, time.monotonic() + 10.0)
        assert (host, port) == ("127.0.0.1", 1)
        t.kill(handle)
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline and t.alive(handle) is not False:
            time.sleep(0.05)
        assert t.alive(handle) is False  # POSITIVELY gone
    finally:
        _kill_machine(proc)


def test_dead_machine_alive_is_unknown_not_death(tmp_path):
    """An unreachable agent reads as UNKNOWN (None) at the process
    layer — the heartbeat-miss leg, never a death verdict."""
    from photon_ml_tpu.fabric.transport import (RemoteTransport,
                                                ReplicaStartupError)
    from photon_ml_tpu.serving.supervisor import ReplicaHandle

    proc, url = _start_agent(str(tmp_path / "m0"), "m0")
    _kill_machine(proc)
    t = RemoteTransport([url], lambda rid, rf: ["true"], timeout_s=0.5)
    handle = ReplicaHandle(replica_id=0, generation=1)
    assert t.alive(handle) is None
    with pytest.raises(ReplicaStartupError, match="no machine"):
        t.spawn(handle)


# ----------------------------------------- serving: the remote fleet


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


def _request_objs(n, seed=5):
    rng = np.random.default_rng(seed)
    objs = []
    for i in range(n):
        objs.append({
            "features": {
                "global": rng.normal(size=DG).astype(np.float32).tolist(),
                "re_userId": rng.normal(size=DR).astype(
                    np.float32).tolist()},
            "entity_ids": {"userId": int(i % E)}, "uid": i})
    return objs


def _oracle_scores(model, objs):
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


def _post(url, objs, timeout=60.0):
    body = json.dumps({"requests": objs}).encode()
    req = urllib.request.Request(
        url + "/score", data=body,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


@pytest.fixture(scope="module")
def remote_fleet(tmp_path_factory):
    """Two machine agents + a 2-replica fleet homed one per machine,
    publishing over the wire (DeltaArtifactServer). Shared by every
    remote test; the whole-machine drill runs last and kills agent 0
    for good, so order in this file IS the teardown plan."""
    from photon_ml_tpu.fabric.transport import (DeltaArtifactServer,
                                                RemoteTransport)
    from photon_ml_tpu.models import io as model_io
    from photon_ml_tpu.serving.fleet import (ServingFleet,
                                             make_fleet_http_server)

    td = tmp_path_factory.mktemp("remote-fleet")
    model = _tiny_model()
    model_dir = str(td / "model")
    model_io.save_game_model(model, model_dir)
    publish_dir = str(td / "publish")
    os.makedirs(publish_dir)
    agents = []
    server = None
    delta_server = None
    fleet = None
    try:
        agents = [_start_agent(str(td / f"m{m}"), f"m{m}")
                  for m in range(2)]
        delta_server = DeltaArtifactServer(publish_dir)
        fleet = ServingFleet(
            replica_args=["--model-dir", model_dir,
                          "--max-wait-ms", "0.5"],
            num_replicas=2, workdir=str(td / "work"),
            probe_interval_s=0.1, heartbeat_deadline_s=1.0,
            rehome_deadline_s=5.0, retry_backoff_s=0.4, retries=4,
            publish_dir=publish_dir, publish_bake_s=0.2,
            delta_base_url=delta_server.base_url)
        # The transport needs the fleet's own argv builder — swap it in
        # before start() spawns anything (the cli/fleet.py pattern).
        fleet.supervisor.transport = RemoteTransport(
            [u for _, u in agents], fleet._replica_argv, timeout_s=2.0)
        fleet.start()
        server = make_fleet_http_server(fleet, port=0)
        threading.Thread(target=server.serve_forever, daemon=True).start()
        url = f"http://127.0.0.1:{server.server_address[1]}"
        objs = _request_objs(12)
        yield {"fleet": fleet, "url": url, "model": model, "objs": objs,
               "agents": agents, "publish_dir": publish_dir,
               "expected": _oracle_scores(model, objs)}
    finally:
        if server is not None:
            server.shutdown()
            server.server_close()
        if fleet is not None:
            fleet.close()
        if delta_server is not None:
            delta_server.close()
        for proc, _ in agents:
            _kill_machine(proc)


def test_remote_fleet_parity_bit_identical(remote_fleet):
    """Replicas spawned THROUGH machine agents score bit-identically to
    the single-process oracle — placement is a mechanism, never a model
    change."""
    env = remote_fleet
    fleet = env["fleet"]
    got = np.asarray([_post(env["url"], [o])["scores"][0]
                      for o in env["objs"]], np.float32)
    np.testing.assert_array_equal(got, env["expected"])
    # And they really are remote: one replica homed per machine.
    homes = [fleet.supervisor.transport.describe(h)
             for h in fleet.supervisor.replicas]
    assert sorted(homes) == sorted(u for _, u in env["agents"])
    hz = json.loads(urllib.request.urlopen(
        env["url"] + "/healthz", timeout=10).read())
    assert hz["status"] == "ok" and hz["fleet_depth"] == 2


def test_delayed_heartbeat_is_unknown_not_death(remote_fleet):
    """The agent control plane drops out for several probe intervals
    while replicas keep serving: liveness reads UNKNOWN, direct healthz
    probes keep last_ok fresh, and NO death is declared."""
    from photon_ml_tpu.utils import events as ev

    env = remote_fleet
    fleet = env["fleet"]
    events = []
    ev.default_emitter.register(events.append)
    plan = faults.FaultPlan(specs=(faults.FaultSpec(
        site="fabric.heartbeat", kind="partition"),))
    try:
        with faults.installed(plan):
            time.sleep(0.6)  # ~6 probe rounds of heartbeat misses
            assert fleet.supervisor.states() == {0: "up", 1: "up"}
            out = _post(env["url"], [env["objs"][0]])
    finally:
        ev.default_emitter.unregister(events.append)
    assert not [e for e in events if isinstance(e, ev.ReplicaDied)]
    np.testing.assert_array_equal(
        np.asarray(out["scores"], np.float32), env["expected"][:1])


def test_publish_delta_over_the_wire(remote_fleet):
    """The canary ladder with replicas PULLING the delta by URL: same
    error classes, same committed chain, and served bits flip to the delta'd
    model on both replicas."""
    from photon_ml_tpu.serving.publish import DeltaStore

    env = remote_fleet
    fleet = env["fleet"]
    store = DeltaStore(env["publish_dir"])
    ids = np.arange(0, E, 2, dtype=np.int64)
    rows = np.random.default_rng(17).normal(
        size=(len(ids), DR)).astype(np.float32)
    delta = store.write({"per-user": (ids, rows)})
    out = fleet.publish_delta(store.delta_dir(delta.version))
    assert out["version"] == delta.version
    for rid in (0, 1):
        hz = fleet._replica_get_json(rid, "/healthz")
        assert hz["model_version"] == delta.version
    import dataclasses as dc

    import jax.numpy as jnp

    means = np.array(np.asarray(
        env["model"].models["per-user"].means), copy=True)
    means[ids] = rows
    bumped = dc.replace(env["model"], models={
        **env["model"].models,
        "per-user": dc.replace(env["model"].models["per-user"],
                               means=jnp.asarray(means))})
    got = np.asarray([_post(env["url"], [o])["scores"][0]
                      for o in env["objs"]], np.float32)
    np.testing.assert_array_equal(got, _oracle_scores(bumped, env["objs"]))


def test_torn_remote_delta_fetch_previous_version_servable(
        tmp_path, monkeypatch):
    """A fetch torn at the marker (rows landed, commit marker did not):
    DeltaCorrupt, NOTHING applied, the previous version keeps serving —
    the publish commit-point discipline crossing the wire intact. The
    healed retry then applies cleanly."""
    from photon_ml_tpu.fabric.transport import DeltaArtifactServer
    from photon_ml_tpu.serving import ScoringService
    from photon_ml_tpu.serving.publish import DeltaCorrupt, DeltaStore

    monkeypatch.chdir(tmp_path)  # the fetch spool lands in cwd
    publish_dir = str(tmp_path / "publish")
    os.makedirs(publish_dir)
    store = DeltaStore(publish_dir)
    ids = np.array([1, 3], np.int64)
    d1 = store.write({"per-user": (
        ids, np.ones((2, DR), np.float32))})
    d2 = store.write({"per-user": (
        ids, np.full((2, DR), 2.0, np.float32))})
    svc = ScoringService(_tiny_model(), max_wait_ms=0.5)
    try:
        with DeltaArtifactServer(publish_dir) as ds:
            out = svc.apply_delta_url(
                f"{ds.base_url}/{os.path.basename(store.delta_dir(d1.version))}")
            assert out["version"] == d1.version
            plan = faults.FaultPlan(specs=(faults.FaultSpec(
                site="fabric.delta_fetch", kind="partition",
                indices=(1,), max_fires=1),))
            v2_url = (f"{ds.base_url}/"
                      f"{os.path.basename(store.delta_dir(d2.version))}")
            with faults.installed(plan):
                with pytest.raises(DeltaCorrupt, match="previous version"):
                    svc.apply_delta_url(v2_url)
            assert svc.model_version == d1.version  # still v1, servable
            # The torn spool holds rows but no commit marker.
            spool = os.path.join(
                str(tmp_path), f"delta-spool-{os.getpid()}",
                os.path.basename(store.delta_dir(d2.version)))
            assert not os.path.exists(os.path.join(spool, "delta.json"))
            # The edge heals: the SAME url applies cleanly.
            out = svc.apply_delta_url(v2_url)
            assert out["version"] == d2.version
            assert svc.model_version == d2.version
    finally:
        svc.close()


def test_whole_machine_sigkill_bounded_rehome_zero_unserved(remote_fleet):
    """THE drill: SIGKILL machine 0's whole process group (agent + its
    replica) under live traffic. The supervisor discovers the death
    through its own probes, shards re-home to the survivor, the restart
    FAILS OVER to machine 1, and every request in flight lands — zero
    unserved, every score bit-identical to the oracle. Runs last: agent
    0 stays dead."""
    from photon_ml_tpu.utils import events as ev

    env = remote_fleet
    fleet = env["fleet"]
    # The published chain may have moved the model past the fixture's
    # base oracle (the publish test runs first) — the drill's parity
    # baseline is the fleet's OWN pre-drill bits, already proven
    # oracle-identical by the parity and publish tests above.
    expected = np.asarray([_post(env["url"], [o])["scores"][0]
                           for o in env["objs"]], np.float32)
    before = fleet.metrics.snapshot()
    agent0_proc, agent0_url = env["agents"][0]
    agent1_url = env["agents"][1][1]
    stop = threading.Event()
    failures = []
    served = []

    def scorer():
        i = 0
        while not stop.is_set():
            obj = env["objs"][i % len(env["objs"])]
            try:
                out = _post(env["url"], [obj], timeout=30.0)
                served.append((i % len(env["objs"]),
                               np.float32(out["scores"][0])))
            except Exception as e:  # noqa: BLE001 - drill verdict
                failures.append((i, repr(e)))
            i += 1
            time.sleep(0.05)

    events = []
    ev.default_emitter.register(events.append)
    t = threading.Thread(target=scorer, daemon=True)
    t.start()
    try:
        time.sleep(0.5)  # traffic flowing on both replicas
        t0 = time.monotonic()
        _kill_machine(agent0_proc)  # machine 0 is GONE
        # Bounded re-home: the dead replica comes back UP on machine 1.
        deadline = time.monotonic() + 90.0
        while time.monotonic() < deadline:
            if (fleet.supervisor.states() == {0: "up", 1: "up"}
                    and not fleet._degraded):
                break
            time.sleep(0.2)
        recovery_s = time.monotonic() - t0
        assert fleet.supervisor.states() == {0: "up", 1: "up"}, \
            f"fleet did not recover within 90s (took {recovery_s:.1f}s)"
        time.sleep(0.5)  # a tail of post-recovery traffic
    finally:
        stop.set()
        t.join(timeout=60.0)
        ev.default_emitter.unregister(events.append)
    died = [e for e in events if isinstance(e, ev.ReplicaDied)]
    assert died and died[0].replica_id == 0  # discovered via probes
    # The restart re-homed ACROSS machines.
    handle = fleet.supervisor.replicas[0]
    assert fleet.supervisor.transport.describe(handle) == agent1_url
    assert handle.machine == agent1_url != agent0_url
    # Zero unserved, through death, re-home, and recovery...
    assert failures == []
    after = fleet.metrics.snapshot()
    assert after["unserved_total"] == before["unserved_total"]
    # ...and every served score is the oracle's bits.
    assert len(served) > 10
    for idx, score in served:
        assert score == expected[idx], \
            f"request {idx}: {score} != {expected[idx]}"
