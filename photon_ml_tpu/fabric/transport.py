"""Address-based replica transports (docs/SERVING.md "Multi-host fleet").

``ReplicaSupervisor`` owns the POLICY of replica lifecycle — heartbeat
deadlines, death declaration, bounded restart backoff, the amnesty
ladder. This module owns the MECHANISM: how a replica incarnation is
started, how its address is learned, how process-level liveness is
read, and how it is killed. Splitting the two lets the same supervisor
ladder babysit replicas it cannot ``Popen``:

- ``LocalTransport`` — today's subprocess spawn, verbatim: ``spawn``
  -style children, output to FILES never pipes, generation-named
  ready-file handshake, ``proc.poll()`` liveness, SIGKILL + reap. On a
  TPU host replica *i* is given chip *i* and nothing else: a chip
  belongs to one process, and an unpinned child takes every chip.
- ``RemoteTransport`` — replicas owned by per-machine agents
  (fabric/agent.py), addressed by host:port. Spawn/kill/liveness go
  through the agent's HTTP control plane (every call a finite timeout —
  PML011); an already-running healthy replica is ADOPTED instead of
  respawned (``fabric.adopt``); a dead MACHINE fails the spawn over to
  the next machine, which is how a whole-group SIGKILL turns into a
  bounded cross-machine re-home instead of a dead fleet.

``alive()`` is deliberately tri-state: ``False`` is a positive "the
process is gone" (local ``poll()``, agent-reported exit); ``None`` is
"cannot see the process layer right now" (agent unreachable —
``fabric.heartbeat`` partition), which must NOT count as death: the
supervisor keeps trusting direct ``/healthz`` probes until the
heartbeat deadline says otherwise. A slow agent is a slow agent; only
silence PAST the deadline is a death.

``DeltaArtifactServer`` is the publish chain's wire leg: it serves a
publish directory's CRC-fenced delta artifacts over HTTP so remote
replicas can pull them (serving/publish.fetch_delta) instead of
assuming a shared filesystem.
"""

from __future__ import annotations

import glob
import http.server
import json
import logging
import os
import signal
import socketserver
import subprocess
import threading
import time
import urllib.request
from typing import Callable, Optional, Sequence

from photon_ml_tpu import faults as flt
from photon_ml_tpu import obs

logger = logging.getLogger("photon_ml_tpu.serving.fleet")


class ReplicaStartupError(RuntimeError):
    """A replica did not reach ready/healthy within its deadline."""


def local_tpu_chips() -> list[str]:
    """This host's TPU chip device nodes, found without JAX — the fleet
    parent must never initialise a backend, or it would hold the chips
    its replicas need. Empty when the replicas are not headed for a TPU
    (no nodes, or ``JAX_PLATFORMS`` names other platforms only)."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return []
    return sorted(glob.glob("/dev/vfio/[0-9]*")
                  + glob.glob("/dev/accel[0-9]*"))


def _get_json(url: str, timeout_s: float) -> dict:
    with urllib.request.urlopen(url, timeout=timeout_s) as resp:
        return json.loads(resp.read())


def _post_json(url: str, payload: dict, timeout_s: float) -> dict:
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode("utf-8"),
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=timeout_s) as resp:
        return json.loads(resp.read())


class Transport:
    """The mechanism seam under ReplicaSupervisor (module docstring).

    ``handle`` is the supervisor's ReplicaHandle; transports read
    ``replica_id``/``generation`` and fill ``proc``/``machine`` — state
    transitions stay the supervisor's job.
    """

    def spawn(self, handle) -> None:
        """Start incarnation ``handle.generation`` of this replica (or
        adopt a running one). Raises ReplicaStartupError when no
        machine can take it."""
        raise NotImplementedError

    def await_ready(self, handle, deadline: float) -> tuple[str, int]:
        """Block until the incarnation is addressable; returns
        ``(host, port)``. Raises ReplicaStartupError on child exit or
        deadline (``time.monotonic()`` instant)."""
        raise NotImplementedError

    def check_capacity(self, num_replicas: int) -> None:
        """Raise ``ReplicaStartupError`` when this transport cannot
        place that many replicas at once (default: no limit). Asked
        BEFORE anything is spawned, so a refusal starts no process."""

    def alive(self, handle) -> Optional[bool]:
        """Process-layer liveness: True = running, False = POSITIVELY
        gone, None = cannot see the process layer (not a death)."""
        raise NotImplementedError

    def kill(self, handle) -> None:
        """SIGKILL-equivalent + reap (wedged replicas must not answer a
        stale hedge after their shards re-home)."""
        raise NotImplementedError

    def terminate(self, handle, timeout_s: float = 10.0) -> None:
        """Graceful stop (retire/shutdown), escalating to kill."""
        raise NotImplementedError

    def describe(self, handle) -> str:
        """Human-readable placement for logs ('' when local)."""
        return ""


class LocalTransport(Transport):
    """Today's subprocess spawn, verbatim (moved from ReplicaSupervisor
    — see that module's docstring for the spawn/pipe/ready-file
    rationale)."""

    def __init__(self, make_argv: Callable[[int, str], Sequence[str]],
                 workdir: str):
        self._make_argv = make_argv
        self.workdir = workdir

    def _ready_file(self, rid: int, generation: int) -> str:
        # Generation in the name: a restart must never trust the ready
        # file the DEAD incarnation wrote (its port is gone).
        return os.path.join(self.workdir,
                            f"replica-{rid}.g{generation}.ready")

    def check_capacity(self, num_replicas: int) -> None:
        chips = local_tpu_chips()
        if chips and num_replicas > len(chips):
            raise ReplicaStartupError(
                f"{num_replicas} replica(s) cannot be placed: this host "
                f"has {len(chips)} TPU chip(s) ({', '.join(chips)}), "
                f"replica i runs on chip i, and a chip belongs to one "
                f"process — ask for at most {len(chips)}")

    def spawn(self, handle) -> None:
        rid = handle.replica_id
        ready = self._ready_file(rid, handle.generation)
        if os.path.exists(ready):
            os.unlink(ready)
        handle.log_path = os.path.join(self.workdir, f"replica-{rid}.log")
        argv = list(self._make_argv(rid, ready))
        # The child's cwd is the workdir (its logs and ready files stay
        # together), so put the package's root on its path explicitly —
        # a dev checkout that was never pip-installed must still fleet.
        import photon_ml_tpu

        pkg_root = os.path.dirname(os.path.dirname(
            os.path.abspath(photon_ml_tpu.__file__)))
        env = dict(os.environ)
        env["PYTHONPATH"] = (pkg_root + os.pathsep + env["PYTHONPATH"]
                             if env.get("PYTHONPATH") else pkg_root)
        self.check_capacity(rid + 1)
        if local_tpu_chips():
            # libtpu's own placement variables: one visible chip, and a
            # 1x1x1 process/chip grid so it does not wait for the
            # host's other chips.
            env.update({"TPU_VISIBLE_CHIPS": str(rid),
                        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
                        "TPU_PROCESS_BOUNDS": "1,1,1"})
        log_f = open(handle.log_path, "ab")
        try:
            handle.proc = subprocess.Popen(
                argv, stdout=log_f, stderr=subprocess.STDOUT,
                cwd=self.workdir, env=env)
        finally:
            log_f.close()  # the child holds its own descriptor now
        logger.info("replica %d spawned (pid %d, log %s)", rid,
                    handle.proc.pid, handle.log_path)

    def await_ready(self, handle, deadline: float) -> tuple[str, int]:
        rid = handle.replica_id
        ready = self._ready_file(rid, handle.generation)
        while time.monotonic() < deadline:
            if handle.proc.poll() is not None:
                raise ReplicaStartupError(
                    f"replica {rid} exited rc={handle.proc.returncode} "
                    f"before ready (see {handle.log_path})")
            if os.path.exists(ready):
                try:
                    with open(ready) as f:
                        info = json.load(f)
                    return info.get("host", "127.0.0.1"), int(info["port"])
                except (OSError, ValueError):
                    pass  # torn read of a mid-write file; poll again
            time.sleep(0.02)
        raise ReplicaStartupError(
            f"replica {rid} not ready before its deadline "
            f"(see {handle.log_path})")

    def alive(self, handle) -> Optional[bool]:
        if handle.proc is None:
            return None
        return handle.proc.poll() is None

    def kill(self, handle) -> None:
        if handle.proc is None or handle.proc.poll() is not None:
            return
        try:
            handle.proc.send_signal(signal.SIGKILL)
            handle.proc.wait(timeout=5.0)
        except (OSError, subprocess.TimeoutExpired):
            logger.warning("could not reap replica %d",
                           handle.replica_id)

    def terminate(self, handle, timeout_s: float = 10.0) -> None:
        if handle.proc is None or handle.proc.poll() is not None:
            return
        handle.proc.terminate()
        try:
            handle.proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            handle.proc.kill()
            try:
                handle.proc.wait(timeout=5.0)
            except subprocess.TimeoutExpired:
                logger.warning("could not reap replica %d",
                               handle.replica_id)


class RemoteTransport(Transport):
    """Replicas owned by per-machine agents (fabric/agent.py).

    ``machines`` are agent base URLs (``http://host:port``); replica
    ``rid``'s HOME machine is ``rid % len(machines)``, sticky until a
    spawn has to fail over. Every agent call carries ``timeout_s``
    (PML011) and the control-plane edges are injection seams:
    ``fabric.heartbeat`` before each liveness query, ``fabric.adopt``
    at the moment a running replica is adopted instead of respawned.
    """

    def __init__(self, machines: Sequence[str],
                 make_argv: Callable[[int, str], Sequence[str]],
                 timeout_s: float = 5.0):
        if not machines:
            raise ValueError("RemoteTransport needs >= 1 machine agent")
        self.machines = [m.rstrip("/") for m in machines]
        self._make_argv = make_argv
        self.timeout_s = float(timeout_s)
        self._home: dict[int, int] = {}
        self._lock = threading.Lock()

    def _home_of(self, rid: int) -> int:
        with self._lock:
            return self._home.get(rid, rid % len(self.machines))

    def _set_home(self, rid: int, idx: int) -> None:
        with self._lock:
            self._home[rid] = idx

    def _candidates(self, rid: int) -> list[int]:
        start = self._home_of(rid)
        n = len(self.machines)
        return [(start + i) % n for i in range(n)]

    def spawn(self, handle) -> None:
        rid = handle.replica_id
        # Agent replaces argv[0] (its own interpreter) and the
        # --ready-file value (its own workdir); everything else —
        # model args, ports, fault plans — travels verbatim.
        argv = list(self._make_argv(rid, "<agent>"))
        errors = []
        for idx in self._candidates(rid):
            agent = self.machines[idx]
            try:
                if handle.generation <= 1 and handle.restarts == 0:
                    # First contact: a healthy replica already running
                    # under this agent (a previous controller's, or one
                    # that survived its controller) is ADOPTED, not
                    # respawned — restarting a serving replica to learn
                    # its address would be a self-inflicted outage.
                    info = _get_json(f"{agent}/replica/{rid}",
                                     self.timeout_s)
                    if info.get("state") == "up":
                        flt.fire(flt.sites.FABRIC_ADOPT, index=rid)
                        mx = obs.metrics()
                        if mx is not None:
                            mx.counter("photon_fabric_adopt_total").inc()
                        self._set_home(rid, idx)
                        handle.machine = agent
                        logger.info(
                            "replica %d adopted on %s (pid %s, %s:%s)",
                            rid, agent, info.get("pid"),
                            info.get("host"), info.get("port"))
                        return
                _post_json(f"{agent}/spawn",
                           {"replica_id": rid, "argv": argv},
                           self.timeout_s)
                self._set_home(rid, idx)
                handle.machine = agent
                logger.info("replica %d spawned on %s", rid, agent)
                return
            except (OSError, ValueError) as e:
                # Machine unreachable or refused: fail over — this is
                # the cross-machine re-home leg of whole-machine death.
                errors.append(f"{agent}: {e}")
                continue
        raise ReplicaStartupError(
            f"replica {rid}: no machine could take it "
            f"({'; '.join(errors)})")

    def await_ready(self, handle, deadline: float) -> tuple[str, int]:
        rid = handle.replica_id
        agent = self.machines[self._home_of(rid)]
        while time.monotonic() < deadline:
            try:
                info = _get_json(f"{agent}/replica/{rid}",
                                 self.timeout_s)
            except (OSError, ValueError):
                time.sleep(0.05)
                continue
            state = info.get("state")
            if state == "exited":
                raise ReplicaStartupError(
                    f"replica {rid} exited rc={info.get('rc')} on "
                    f"{agent} before ready (see {info.get('log_path')})")
            if state == "up" and info.get("port"):
                return str(info.get("host", "127.0.0.1")), int(info["port"])
            time.sleep(0.05)
        raise ReplicaStartupError(
            f"replica {rid} not ready on {agent} before its deadline")

    def alive(self, handle) -> Optional[bool]:
        rid = handle.replica_id
        agent = self.machines[self._home_of(rid)]
        try:
            # Injection seam: a `partition`/`delay` spec here models the
            # agent control plane dropping out while replicas keep
            # serving — which must read as UNKNOWN, not as death.
            flt.fire(flt.sites.FABRIC_HEARTBEAT, index=rid)
            info = _get_json(f"{agent}/replica/{rid}", self.timeout_s)
        except (OSError, ValueError):
            mx = obs.metrics()
            if mx is not None:
                mx.counter("photon_fabric_heartbeat_miss_total").inc()
            return None
        state = info.get("state")
        if state in ("up", "starting"):
            return True
        if state == "exited":
            return False
        return None  # agent answered but has no record — unknown

    def kill(self, handle) -> None:
        rid = handle.replica_id
        agent = self.machines[self._home_of(rid)]
        try:
            _post_json(f"{agent}/kill", {"replica_id": rid},
                       self.timeout_s)
        except (OSError, ValueError) as e:
            # The machine is gone — its replicas died with it; there is
            # nothing left to reap on this side of the wire.
            logger.warning("could not kill replica %d via %s (%s)",
                           rid, agent, e)

    def terminate(self, handle, timeout_s: float = 10.0) -> None:
        rid = handle.replica_id
        agent = self.machines[self._home_of(rid)]
        try:
            _post_json(f"{agent}/stop",
                       {"replica_id": rid, "timeout_s": timeout_s},
                       max(self.timeout_s, timeout_s + 1.0))
        except (OSError, ValueError) as e:
            logger.warning("could not stop replica %d via %s (%s)",
                           rid, agent, e)

    def describe(self, handle) -> str:
        return self.machines[self._home_of(handle.replica_id)]


# -- publish-over-the-wire (docs/SERVING.md "Multi-host fleet") --------------


class _DeltaHandler(http.server.BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def log_message(self, fmt, *args):  # quiet: the fleet logs routing
        logger.debug("delta server: " + fmt, *args)

    def do_GET(self):
        root = self.server.root  # type: ignore[attr-defined]
        rel = self.path.lstrip("/")
        full = os.path.realpath(os.path.join(root, rel))
        # Traversal fence: only files UNDER the publish root are
        # servable, no matter what the path spells.
        if not full.startswith(os.path.realpath(root) + os.sep):
            self.send_error(404)
            return
        try:
            with open(full, "rb") as f:
                blob = f.read()
        except OSError:
            self.send_error(404)
            return
        self.send_response(200)
        self.send_header("Content-Type", "application/octet-stream")
        self.send_header("Content-Length", str(len(blob)))
        self.end_headers()
        self.wfile.write(blob)


class _ThreadingHTTPServer(socketserver.ThreadingMixIn,
                           http.server.HTTPServer):
    daemon_threads = True
    allow_reuse_address = True


class DeltaArtifactServer:
    """Serves a publish directory's delta artifacts over HTTP (read-
    only, traversal-fenced). The CRC fence stays with the ARTIFACT:
    the fetching replica re-verifies via ``read_delta``, so a torn or
    bit-flipped transfer lands in the same ``DeltaCorrupt`` class
    as a torn shared-filesystem write."""

    def __init__(self, publish_dir: str, host: str = "127.0.0.1",
                 port: int = 0):
        self._server = _ThreadingHTTPServer((host, port), _DeltaHandler)
        self._server.root = publish_dir  # type: ignore[attr-defined]
        self._thread = threading.Thread(
            target=self._server.serve_forever,
            name="photon-delta-server", daemon=True)
        self._thread.start()
        self.host, self.port = self._server.server_address[:2]

    @property
    def base_url(self) -> str:
        return f"http://{self.host}:{self.port}"

    def close(self) -> None:
        self._server.shutdown()
        self._server.server_close()
        self._thread.join(timeout=5.0)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
