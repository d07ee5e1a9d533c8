"""Continuous model publication driver (docs/SERVING.md "Continuous
publication").

Closes the ingest→fit→publish→serve loop: refit the dirty entities of a
served GameModel from logged ``(features, label, offset)`` tuples
(game/refit.py — warm-started per-entity solves against the offline
fit), commit the changed rows as a monotone-versioned delta artifact
(serving/publish.py — CRC/two-generation discipline, SIGKILL-safe), and
optionally push it through a running fleet's canary ladder
(``POST /publish`` on the photon-game-fleet front door: canary → bake →
judge → roll fleet-wide or auto-roll-back).

Quickstart::

    # cut a delta from logged traffic (no fleet needed)
    photon-game-publish --model-dir out/best --publish-dir out/publish \
        --refit per-user=logged-tuples.npz

    # same, then gate it through a live fleet
    photon-game-publish --model-dir out/best --publish-dir out/publish \
        --refit per-user=logged-tuples.npz \
        --fleet-url http://127.0.0.1:8080 --bake-window-s 2

Exit codes: 0 published (or written, without ``--fleet-url``); 3 the
canary rejected the delta (it was rolled back and RETRACTED from the
version chain); 2 anything else went wrong.

Ledgers: this publisher records its refit/delta_write/verdict rows in
``<publish-dir>/publisher-ledger``; a fleet started with
``--publish-dir`` records the canary ladder's rows in
``<publish-dir>/ledger`` — two DIFFERENT files on purpose (one
append-as-produced stream has one writer; two processes interleaving
``seq`` numbers would tear it). Render either with ``photon-obs tail
--publish``.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import sys
import urllib.error
import urllib.request

from photon_ml_tpu.utils.logging import setup_logging

logger = logging.getLogger("photon_ml_tpu.cli")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--model-dir", required=True,
                   help="the BASE GameModel directory (the offline fit "
                        "refits warm-start from)")
    p.add_argument("--publish-dir", required=True,
                   help="delta-store home: versioned delta artifacts + "
                        "the publish ledger live here")
    p.add_argument("--refit", action="append", default=[],
                   metavar="CID=TUPLES.npz",
                   help="refit one coordinate from a logged-tuple batch "
                        "(game/refit.py npz format; repeatable). A batch "
                        "must carry each dirty entity's COMPLETE logged "
                        "history — that contract is what keeps served "
                        "scores bit-identical to an offline full refit")
    p.add_argument("--delta-dir",
                   help="publish an ALREADY-CUT delta directory instead "
                        "of refitting (mutually exclusive with --refit)")
    p.add_argument("--fleet-url",
                   help="photon-game-fleet front door; when set, the "
                        "committed delta goes through the canary ladder "
                        "(POST /publish). Without it the delta is only "
                        "written (--write-only mode)")
    p.add_argument("--bake-window-s", type=float, default=None,
                   help="canary bake window before the verdict "
                        "(fleet default when omitted)")
    p.add_argument("--burn-threshold", type=float, default=None,
                   help="max canary error-budget burn rate over the "
                        "bake window (fleet default when omitted)")
    p.add_argument("--probe",
                   help="JSON file with scoring request objects POSTed "
                        "to the canary; non-finite probe scores reject "
                        "the delta")
    p.add_argument("--probe-max-abs", type=float, default=None,
                   help="reject when any canary probe |score| exceeds "
                        "this (the quality band)")
    p.add_argument("--max-iterations", type=int, default=100,
                   help="refit optimizer iterations (match training)")
    p.add_argument("--tolerance", type=float, default=1e-7)
    p.add_argument("--reg-weight", type=float, default=1.0,
                   help="L2 weight of the refit solves (match training)")
    p.add_argument("--publish-timeout-s", type=float, default=120.0,
                   help="HTTP timeout of the POST /publish call (covers "
                        "the bake window)")
    p.add_argument("--fault-plan",
                   help="JSON FaultPlan armed in this publisher "
                        "(chaos drills: kill at publish.delta_write, "
                        "corrupt at publish.delta_artifact)")
    p.add_argument("--compact-generations", metavar="GEN_ROOT",
                   help="after a successful publish, fold the committed "
                        "delta chain into the next mmap generation "
                        "under GEN_ROOT (boot/generations.py) — "
                        "replicas then restart from one mmap swap "
                        "instead of replaying the chain (docs/SERVING.md "
                        "\"Sub-second restart\"). Bootstraps gen-000001 "
                        "from --model-dir when the root is empty")
    return p


def _parse_refits(specs: list[str]) -> list[tuple[str, str]]:
    out = []
    for spec in specs:
        cid, sep, path = spec.partition("=")
        if not sep or not cid or not path:
            raise ValueError(f"--refit expects CID=TUPLES.npz, "
                             f"got {spec!r}")
        out.append((cid, path))
    return out


def cut_delta(args, ledger) -> "object":
    """Refit (or adopt) + commit one delta; returns the ModelDelta."""
    from photon_ml_tpu.game.refit import load_refit_batch, refit_rows
    from photon_ml_tpu.models import io as model_io
    from photon_ml_tpu.optim import OptimizerConfig
    from photon_ml_tpu.optim.problem import GLMOptimizationConfiguration
    from photon_ml_tpu.optim.regularization import (RegularizationContext,
                                                    RegularizationType)
    from photon_ml_tpu.serving.publish import DeltaStore, read_delta

    store = DeltaStore(args.publish_dir)
    if args.delta_dir:
        return read_delta(args.delta_dir)
    refits = _parse_refits(args.refit)
    if not refits:
        raise ValueError("nothing to publish: give --refit or "
                         "--delta-dir")
    model = model_io.load_game_model(args.model_dir, host=True)
    config = GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(max_iterations=args.max_iterations,
                                  tolerance=args.tolerance),
        regularization=RegularizationContext(
            RegularizationType.L2, args.reg_weight))
    rows_by_cid = {}
    for cid, path in refits:
        batch = load_refit_batch(path)
        ids, rows, stats = refit_rows(model, cid, batch, config=config)  # pml: allow[PML012] one loop iteration IS one whole coordinate refit; its result must land on host to become the delta artifact — the sync is the product, not per-step chatter
        rows_by_cid[cid] = (ids, rows)
        ledger.record("publish", phase="refit", **stats)
    delta = store.write(rows_by_cid,
                        extra={"source": "photon-game-publish",
                               "model_dir": args.model_dir})
    ledger.record("publish", phase="delta_write", version=delta.version,
                  parent=delta.parent, entities=delta.num_rows,
                  coordinates=list(delta.coordinates))
    return delta


def compact_generations(args, ledger) -> dict:
    """Fold the committed delta chain into the next mmap generation
    (boot/generations.py): the restart path's amortization leg —
    publication cost moves INTO the artifact, so a rebooted replica has
    nothing to replay. Bootstraps the base generation from
    ``--model-dir`` when the root holds none."""
    from photon_ml_tpu.boot import GenerationStore
    from photon_ml_tpu.boot.generations import publish_generation
    from photon_ml_tpu.serving.publish import DeltaStore

    store = GenerationStore(args.compact_generations)
    if not store.versions():
        gen, _ = publish_generation(args.model_dir,
                                    args.compact_generations)
        ledger.record("publish", phase="generation_bootstrap",
                      generation=gen)
    out = store.compact(DeltaStore(args.publish_dir))
    if out is None:  # chain already folded — idempotent no-op
        return {"generation": store.current_version(),
                "compaction_skipped": True}
    gen, path = out
    ledger.record("publish", phase="compacted", generation=gen,
                  path=path)
    logger.info("delta chain compacted into generation gen-%06d (%s)",
                gen, path)
    return {"generation": gen, "generation_path": path}


def push_to_fleet(args, delta, ledger) -> dict:
    """Drive the fleet's canary ladder over HTTP; raises the publish
    error classes mapped back from the front door's defined statuses."""
    from photon_ml_tpu.serving.publish import (CanaryRejected,
                                               PublishError)

    payload: dict = {"path": os.path.abspath(delta.path)}
    if args.bake_window_s is not None:
        payload["bake_s"] = args.bake_window_s
    if args.burn_threshold is not None:
        payload["burn_threshold"] = args.burn_threshold
    probe: dict = {}
    if args.probe:
        with open(args.probe) as f:
            probe["requests"] = json.load(f)
    if args.probe_max_abs is not None:
        probe["max_abs_score"] = args.probe_max_abs
    if probe:
        payload["probe"] = probe
    req = urllib.request.Request(
        args.fleet_url.rstrip("/") + "/publish",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(
                req, timeout=args.publish_timeout_s) as resp:
            return json.loads(resp.read())
    except urllib.error.HTTPError as e:
        body = e.read().decode(errors="replace")
        try:
            detail = json.loads(body)
        except ValueError:
            detail = {"error": body}
        ledger.record("publish", phase="verdict", version=delta.version,
                      accepted=False, status=e.code,
                      reason=detail.get("error", ""))
        if e.code == 409:
            raise CanaryRejected(delta.version,
                                 detail.get("reason",
                                            detail.get("error", "")))
        raise PublishError(
            f"fleet refused delta v{delta.version} "
            f"(HTTP {e.code}): {detail.get('error', body)}")


def run(args) -> int:
    setup_logging()
    from photon_ml_tpu.boot import GenerationError
    from photon_ml_tpu.obs.ledger import RunLedger
    from photon_ml_tpu.serving.publish import (CanaryRejected,
                                               DeltaStore, PublishError)

    if args.fault_plan:
        from photon_ml_tpu import faults as flt

        with open(args.fault_plan) as f:
            flt.install(flt.FaultPlan.from_json(f.read()))
        logger.warning("fault plan %s ARMED in this publisher",
                       args.fault_plan)
    os.makedirs(args.publish_dir, exist_ok=True)
    # publisher-ledger, NOT ledger: the fleet process owns that one
    # (module docstring) — an append-as-produced stream has ONE writer.
    ledger = RunLedger.resume(
        os.path.join(args.publish_dir, "publisher-ledger"),
        config={"kind": "publish", "model_dir": args.model_dir})
    status = "ok"
    try:
        delta = cut_delta(args, ledger)
        summary = {"version": delta.version, "parent": delta.parent,
                   "entities": delta.num_rows,
                   "coordinates": list(delta.coordinates),
                   "path": delta.path}
        if not args.fleet_url:
            summary["published"] = False
            if args.compact_generations:
                summary.update(compact_generations(args, ledger))
            print(json.dumps(summary))
            return 0
        try:
            verdict = push_to_fleet(args, delta, ledger)
        except CanaryRejected as e:
            # Rejected deltas leave the version chain (retracted, kept
            # as rejected-v* for forensics) so the next publish reuses
            # the number and the applied chain stays gapless.
            DeltaStore(args.publish_dir).retract(delta.version)
            logger.error("%s", e)
            summary.update({"published": False, "rejected": True,
                            "reason": e.reason})
            print(json.dumps(summary))
            status = "canary_rejected"
            return 3
        except PublishError as e:
            # Swap failure (rolled back fleet-side) or an untrustworthy
            # artifact: either way it never went live — retract it.
            DeltaStore(args.publish_dir).retract(delta.version)
            logger.error("publish failed: %s", e)
            status = "error"
            return 2
        summary.update({"published": True, **verdict})
        if args.compact_generations:
            summary.update(compact_generations(args, ledger))
        print(json.dumps(summary))
        return 0
    except (PublishError, GenerationError, ValueError, OSError) as e:
        logger.error("publish failed: %s", e)
        status = "error"
        return 2
    finally:
        ledger.close(status=status)


def main(argv=None) -> None:
    sys.exit(run(build_parser().parse_args(argv)))


if __name__ == "__main__":
    main()
