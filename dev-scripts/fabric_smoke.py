#!/usr/bin/env python
"""Fabric smoke (run_tier1.sh): a REAL 2-process DCN streamed fit
(docs/STREAMING.md "Multi-host streaming").

Two OS processes join one ``jax.distributed`` world on a localhost
coordinator (2 virtual CPU devices each), arm the host-level fabric
(``--fabric``), and run the streamed fixed-effect fit through the full
CLI path — chunk ranges shard over the two hosts, per-host partials
reduce on the local mesh, and the host partials meet in ONE cross-host
``FabricComm`` allreduce per pass. Asserts:

1. both ranks exit 0 and announce the armed fabric (rank r/2);
2. sharded parity: the rank-0-written coefficients match a
   single-process streamed oracle within the 5e-3 sharded-parity band
   (W hosts change accumulation order, never the objective);
3. the rank-digest evidence trail is REAL: the shared run ledger
   carries one ``fabric_digest`` row per accepted iteration with
   ``world=2``, ``match=True``, and nonzero DCN provenance counters —
   every iteration of the fit was cross-checked between the ranks;
4. rank-0-only writes: rank 1 left no model/summary/ledger behind.

Guarded: if ``jax.distributed`` cannot initialize on this box (no
localhost gRPC), the smoke SKIPS loudly with rc 0 — the in-process
fabric suite (tests/test_fabric.py) still covers the collective layer.

Runs on CPU in ~1-2 minutes; catches a broken DCN seam before it
reaches a real process group.
"""

import json
import os
import socket
import subprocess
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _stream_args(train_dir: str, out: str) -> list:
    return [
        "--train", train_dir,
        "--coordinate", "name=fixed,type=fixed,shard=global",
        "--update-sequence", "fixed",
        "--opt-config", "fixed:optimizer=LBFGS,reg=L2,reg_weight=1.0",
        "--streaming", "chunk_rows=128,num_hot=8",
        "--output-dir", out,
    ]


def _spawn_rank(rank: int, jax_port: int, fabric_port: int,
                cli_args: list, log_path: str) -> subprocess.Popen:
    """One fabric rank. Output to a FILE, never a pipe (an undrained
    pipe blocks the child mid-training — the test_multiprocess
    discipline)."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        "JAX_COORDINATOR_ADDRESS": f"127.0.0.1:{jax_port}",
        "JAX_NUM_PROCESSES": "2",
        "JAX_PROCESS_ID": str(rank),
        "PHOTON_FABRIC_WORLD": "2",
        "PHOTON_FABRIC_RANK": str(rank),
        "PHOTON_FABRIC_COORDINATOR": f"127.0.0.1:{fabric_port}",
        "PHOTON_FABRIC_TIMEOUT_S": "120",
        "PYTHONPATH": REPO + (os.pathsep + env["PYTHONPATH"]
                              if env.get("PYTHONPATH") else ""),
    })
    log = open(log_path, "w")
    try:
        return subprocess.Popen(
            [sys.executable, "-m", "photon_ml_tpu.cli.game_train"]
            + cli_args + ["--distributed", "--fabric"],
            env=env, cwd=REPO, stdout=log, stderr=subprocess.STDOUT)
    finally:
        log.close()


def _coeffs(out_dir: str) -> dict:
    path = os.path.join(out_dir, "best", "fixed-effect", "fixed",
                        "coefficients.npz")
    with np.load(path) as z:
        return {k: z[k] for k in z.files}


def main() -> int:
    from photon_ml_tpu.cli import game_train
    from photon_ml_tpu.data import sparse as sp
    from photon_ml_tpu.data.game_data import from_sparse_batch
    from photon_ml_tpu.data.io import save_game_dataset
    from photon_ml_tpu.obs.ledger import read_rows

    with tempfile.TemporaryDirectory(prefix="pml_fabric_smoke_") as td:
        batch, _ = sp.synthetic_sparse(700, 64, 5, seed=11)
        train_dir = os.path.join(td, "train")
        save_game_dataset(from_sparse_batch(batch), train_dir)

        # Single-process streamed oracle, in-process.
        out_oracle = os.path.join(td, "out-oracle")
        game_train.run(game_train.build_parser().parse_args(
            _stream_args(train_dir, out_oracle)))
        w_oracle = _coeffs(out_oracle)

        # The 2-process fabric run: one SHARED output dir (the shared-
        # checkpoint-filesystem contract; rank 0 owns every write).
        out_fabric = os.path.join(td, "out-fabric")
        logs = [os.path.join(td, f"rank{r}.log") for r in (0, 1)]
        dumps = [os.path.join(td, f"metrics-rank{r}.json") for r in (0, 1)]
        procs = [_spawn_rank(r, jax_port, fabric_port,
                             _stream_args(train_dir, out_fabric)
                             + ["--metrics-dump", dumps[r]], logs[r])
                 for jax_port in [_free_port()]
                 for fabric_port in [_free_port()]
                 for r in (0, 1)]
        deadline = time.time() + 420
        try:
            for p in procs:
                p.wait(timeout=max(5.0, deadline - time.time()))
        except subprocess.TimeoutExpired:
            for p in procs:
                p.kill()
                p.wait(timeout=30)
            for lp in logs:
                print(f"--- {lp} ---\n" + open(lp).read()[-3000:])
            print("fabric smoke FAILED: 2-process run timed out")
            return 1
        tails = [open(lp).read() for lp in logs]
        if any("jax.distributed" in t and "UNAVAILABLE" in t
               for t in tails) and all(p.returncode != 0 for p in procs):
            print("fabric smoke SKIPPED loudly: jax.distributed could "
                  "not initialize on this box (no localhost gRPC); the "
                  "in-process fabric suite still gates the collective "
                  "layer")
            return 0
        for r, (p, t) in enumerate(zip(procs, tails)):
            if p.returncode != 0:
                print(f"--- rank {r} log tail ---\n{t[-4000:]}")
                print(f"fabric smoke FAILED: rank {r} exited "
                      f"rc={p.returncode}")
                return 1
            assert f"fabric armed: rank {r}/2" in t, \
                f"rank {r} never armed the fabric"

        # (2) sharded parity vs the oracle.
        w_fabric = _coeffs(out_fabric)
        assert sorted(w_fabric) == sorted(w_oracle)
        for k in w_oracle:
            np.testing.assert_allclose(
                w_fabric[k], w_oracle[k], rtol=5e-3, atol=5e-3,
                err_msg=f"sharded parity broke on {k!r}")

        # (3) the rank-digest evidence trail in the shared ledger.
        rows, _problems = read_rows(os.path.join(out_fabric, "ledger"))
        digests = [r for r in rows if r.get("kind") == "fabric_digest"]
        assert digests, "no fabric_digest rows — the cross-rank check " \
                        "never ran"
        for row in digests:
            assert row["world"] == 2 and row["match"] is True, row
        assert digests[-1].get("fabric_allreduces", 0) > 0, \
            "digest rows carry no DCN provenance counters"
        opt_iters = [r for r in rows if r.get("kind") == "opt_iter"
                     and r.get("coordinate") == "fixed"]
        assert len(digests) >= max(1, len(opt_iters) - 1), \
            (f"{len(digests)} digest rows for {len(opt_iters)} accepted "
             f"iterations — iterations went uncross-checked")

        # (3b) the photon_fabric_* catalog (docs/OBSERVABILITY.md) is
        # live in the rank-0 registry (dumps are rank-0-only — the
        # single-writer discipline of a shared output filesystem).
        from photon_ml_tpu.obs.metrics import parse_prometheus_text

        with open(dumps[0]) as f:
            snap = parse_prometheus_text(f.read())
        assert snap.get("photon_fabric_world_size") == 2.0, snap
        assert snap.get(
            'photon_fabric_allreduce_total{op="allreduce"}', 0) > 0
        assert snap.get("photon_fabric_bytes_total", 0) > 0
        assert not os.path.exists(dumps[1])  # rank 1 never writes

        # (4) rank-0-only writes: exactly one model/summary/ledger.
        assert os.path.exists(os.path.join(out_fabric, "summary.json"))
        print(f"fabric smoke ok: 2-process sharded fit matches the "
              f"oracle within 5e-3 on {len(w_oracle)} arrays; "
              f"{len(digests)} fabric_digest rows (world=2, all match) "
              f"over {len(opt_iters)} accepted iterations; last row "
              f"counts {digests[-1].get('fabric_allreduces')} DCN "
              f"allreduces / {digests[-1].get('fabric_bytes')} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
