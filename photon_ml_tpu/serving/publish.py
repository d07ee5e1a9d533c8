"""photon-publish: versioned model-delta artifacts + their trust rules.

The fleet (serving/fleet.py) used to serve a frozen snapshot; a
production GLMix system refits per-entity random effects continuously
and publishes them WITHOUT downtime (ROADMAP item 1). This module owns
the at-rest half of that loop — the delta artifact format and the rules
for deciding one can be trusted; the in-memory half (row hot-swap) lives
in serving/model_store.py and the fleet-grade gating (canary → judge →
roll or roll back) in serving/fleet.py.

Artifact layout under one publish directory::

    delta-v000001/
        rows.npz      # per coordinate: "<cid>::ids" (k,) int64 vocabulary
                      # rows + "<cid>::rows" (k, d) float32 replacement
                      # coefficient rows (ABSOLUTE rows, not diffs — a
                      # re-applied delta is idempotent)
    delta-v000002/
        ...
        delta.json    # the COMMIT POINT, written LAST and atomically:
                      # version, parent version, per-file CRC32, row
                      # counts. A delta directory without a valid
                      # delta.json does not exist.

Crash/corruption discipline (the game/checkpoint.py contract, verbatim):
every file write is atomic (``utils/diskio.atomic_write``), the marker
carries the payload's CRC32 taken over the good bytes, and readers
verify before trusting. A SIGKILL mid-publish leaves a marker-less
directory — invisible; the previous version stays fully servable. Bit
rot (or the ``publish.delta_artifact`` corrupt fault) fails the CRC and
raises the defined :class:`DeltaCorrupt` instead of swapping garbage
rows into a live store.

Versions are MONOTONE: ``write`` always commits ``latest + 1`` and
stamps the parent, so a reader can tell a gap (missing/torn version)
from a clean chain and the fleet can refuse to apply out of order.

Failure classes (docs/ROBUSTNESS.md publication ladder):

* :class:`DeltaCorrupt` — the artifact's bytes cannot be trusted
  (CRC mismatch, unparseable marker, missing payload);
* :class:`BadDelta`     — the artifact is intact but the CONTENT is
  unservable (non-finite rows, wrong dimension, ids outside the entity
  table) — what validation rejects before any store mutates;
* :class:`CanaryRejected` — the delta applied cleanly but the canary
  judge refused it (SLO burn, insane probe scores); raised by the
  fleet ladder after the rollback ran.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import re
from typing import Optional

import numpy as np

from photon_ml_tpu import faults as flt
from photon_ml_tpu.utils.diskio import atomic_write, file_crc32

logger = logging.getLogger("photon_ml_tpu.serving.publish")

_ROWS = "rows.npz"
_MARKER = "delta.json"
_DIR_RE = re.compile(r"^delta-v(\d{6,})$")
DELTA_FORMAT_VERSION = 1


class PublishError(RuntimeError):
    """Base class of the publication ladder's defined errors."""


class DeltaCorrupt(PublishError):
    """A delta artifact whose bytes fail their committed CRC (or whose
    marker is torn/unparseable) — never applied, by construction."""


class BadDelta(PublishError):
    """An intact delta whose CONTENT is unservable (NaN/Inf rows, wrong
    dimension, out-of-table ids) — rejected by validation before any
    store row mutates."""


class CanaryRejected(PublishError):
    """The canary judge refused a delta after its bake window; the
    canary (when it had applied) has already been rolled back and no
    non-canary replica ever saw the delta."""

    def __init__(self, version: int, reason: str):
        super().__init__(f"delta v{version} rejected at the canary: "
                         f"{reason}")
        self.version = version
        self.reason = reason


@dataclasses.dataclass(frozen=True)
class ModelDelta:
    """One committed row-delta: coordinate id → (ids, replacement rows)."""

    version: int
    parent: int  # version this delta was cut against (0 = the base model)
    rows: dict[str, tuple[np.ndarray, np.ndarray]]
    path: str = ""

    @property
    def num_rows(self) -> int:
        return sum(int(ids.shape[0]) for ids, _ in self.rows.values())

    @property
    def coordinates(self) -> tuple[str, ...]:
        return tuple(sorted(self.rows))


def validate_delta(delta: ModelDelta,
                   dims: Optional[dict[str, tuple[int, int]]] = None
                   ) -> None:
    """Content validation — THE gate between an intact artifact and a
    live store. ``dims`` (coordinate → (num_entities, dim)) comes from
    the store about to apply; None checks only self-consistency.
    Raises :class:`BadDelta`; never mutates anything."""
    if not delta.rows:
        raise BadDelta(f"delta v{delta.version} carries no rows")
    for cid, (ids, rows) in delta.rows.items():
        if ids.ndim != 1 or rows.ndim != 2 \
                or ids.shape[0] != rows.shape[0]:
            raise BadDelta(
                f"delta v{delta.version} coordinate {cid!r}: ids "
                f"{ids.shape} and rows {rows.shape} do not pair up")
        if ids.shape[0] == 0:
            raise BadDelta(f"delta v{delta.version} coordinate {cid!r} "
                           f"is empty")
        if len(np.unique(ids)) != ids.shape[0]:
            raise BadDelta(f"delta v{delta.version} coordinate {cid!r} "
                           f"repeats entity ids (ambiguous row intent)")
        if not np.all(np.isfinite(rows)):
            raise BadDelta(
                f"delta v{delta.version} coordinate {cid!r} carries "
                f"non-finite coefficient rows — refusing to swap NaN/Inf "
                f"into a live store")
        if dims is not None:
            if cid not in dims:
                raise BadDelta(
                    f"delta v{delta.version} names coordinate {cid!r} "
                    f"the serving store does not hold "
                    f"(has {sorted(dims)})")
            num_entities, dim = dims[cid]
            if rows.shape[1] != dim:
                raise BadDelta(
                    f"delta v{delta.version} coordinate {cid!r}: rows "
                    f"are {rows.shape[1]}-dimensional, store expects "
                    f"{dim}")
            if ids.shape[0] and (int(ids.min()) < 0
                                 or int(ids.max()) >= num_entities):
                raise BadDelta(
                    f"delta v{delta.version} coordinate {cid!r}: entity "
                    f"ids outside [0, {num_entities})")


class DeltaStore:
    """Monotone-versioned delta artifacts under one publish directory.

    Thread-compatibility: one writer (the publisher process); readers
    (replicas applying a committed delta) only ever see committed
    generations — the marker is the commit point.
    """

    def __init__(self, directory: str):
        self.directory = directory

    # -- layout --------------------------------------------------------------

    def delta_dir(self, version: int) -> str:
        return os.path.join(self.directory, f"delta-v{version:06d}")

    def versions(self) -> list[int]:
        """Committed versions, ascending (marker present and parseable;
        payload CRC is verified at read time)."""
        if not os.path.isdir(self.directory):
            return []
        out = []
        for name in os.listdir(self.directory):
            m = _DIR_RE.match(name)
            if not m:
                continue
            marker = os.path.join(self.directory, name, _MARKER)
            if os.path.exists(marker):
                out.append(int(m.group(1)))
        return sorted(out)

    def latest_version(self) -> int:
        versions = self.versions()
        return versions[-1] if versions else 0

    # -- write ---------------------------------------------------------------

    def write(self, rows: dict[str, tuple[np.ndarray, np.ndarray]],
              extra: Optional[dict] = None) -> ModelDelta:
        """Commit the next version. The payload is written first, its
        CRC32 taken over the good bytes, then the marker — so a kill
        anywhere before the marker leaves no committed version and a
        kill after leaves a fully committed one. ``publish.delta_write``
        is the crash seam; ``publish.delta_artifact`` the corruption
        seam (bit rot lands AFTER the checksum, the shape ``read`` must
        catch)."""
        parent = self.latest_version()
        version = parent + 1
        delta = ModelDelta(version=version, parent=parent, rows={
            cid: (np.asarray(ids, np.int64),
                  np.asarray(mat, np.float32))
            for cid, (ids, mat) in rows.items()})
        validate_delta(delta)
        d = self.delta_dir(version)
        os.makedirs(d, exist_ok=True)
        flt.fire(flt.sites.PUBLISH_DELTA_WRITE)
        payload = {}
        counts = {}
        for cid, (ids, mat) in delta.rows.items():
            payload[f"{cid}::ids"] = ids
            payload[f"{cid}::rows"] = mat
            counts[cid] = int(ids.shape[0])
        rows_path = os.path.join(d, _ROWS)
        atomic_write(rows_path, lambda f: np.savez(f, **payload))
        crc = file_crc32(rows_path)
        flt.corrupt_file(flt.sites.PUBLISH_DELTA_ARTIFACT, rows_path)
        # Occurrence 1 of the crash seam: payload on disk, marker not —
        # THE torn window a mid-publish SIGKILL must leave invisible.
        flt.fire(flt.sites.PUBLISH_DELTA_WRITE)
        marker = {
            "format": DELTA_FORMAT_VERSION,
            "version": version,
            "parent": parent,
            "crc": crc,
            "counts": counts,
        }
        if extra:
            marker["extra"] = extra
        body = json.dumps(marker, indent=2, sort_keys=True)
        atomic_write(os.path.join(d, _MARKER),
                     lambda f: f.write(body.encode()))
        logger.info("delta v%d committed: %d row(s) across %s -> %s",
                    version, delta.num_rows, delta.coordinates, d)
        return dataclasses.replace(delta, path=d)

    def retract(self, version: int) -> Optional[str]:
        """Take a rejected delta OUT of the version chain (the canary
        said no): the directory is renamed to ``rejected-v…`` — kept
        for forensics, invisible to ``versions()`` — so the next write
        reuses the number and the applied chain stays gapless. Returns
        the new path (None when the version does not exist)."""
        d = self.delta_dir(version)
        if not os.path.isdir(d):
            return None
        n = 0
        while True:
            target = os.path.join(self.directory,
                                  f"rejected-v{version:06d}.{n}")
            if not os.path.exists(target):
                break
            n += 1
        os.rename(d, target)
        logger.warning("delta v%d retracted -> %s", version, target)
        return target

    # -- read ----------------------------------------------------------------

    def read(self, version: int) -> ModelDelta:
        return read_delta(self.delta_dir(version))


def read_delta(path: str) -> ModelDelta:
    """Load one committed delta directory, verifying the marker and the
    payload CRC. Raises :class:`DeltaCorrupt` when the bytes cannot be
    trusted — the caller falls back to the previous committed version
    (which a torn write never touched)."""
    marker_path = os.path.join(path, _MARKER)
    if not os.path.exists(marker_path):
        raise DeltaCorrupt(f"{path} has no committed marker "
                           f"({_MARKER} missing — torn or absent publish)")
    try:
        with open(marker_path) as f:
            marker = json.load(f)
    except (OSError, ValueError) as e:
        raise DeltaCorrupt(f"{path} marker unreadable "
                           f"({type(e).__name__}: {e})")
    rows_path = os.path.join(path, _ROWS)
    try:
        got = file_crc32(rows_path)
    except OSError as e:
        raise DeltaCorrupt(f"{path} payload unreadable "
                           f"({type(e).__name__}: {e})")
    want = int(marker.get("crc", -1))
    if got != want:
        raise DeltaCorrupt(
            f"{path} payload fails its committed CRC (got {got}, marker "
            f"{want}) — refusing to apply corrupt rows")
    try:
        with np.load(rows_path, allow_pickle=False) as z:
            arrays = {k: z[k] for k in z.files}
    except Exception as e:
        raise DeltaCorrupt(f"{path} payload does not parse "
                           f"({type(e).__name__}: {e})")
    rows: dict[str, tuple[np.ndarray, np.ndarray]] = {}
    for cid in marker.get("counts", {}):
        try:
            rows[cid] = (np.asarray(arrays[f"{cid}::ids"], np.int64),
                         np.asarray(arrays[f"{cid}::rows"], np.float32))
        except KeyError:
            raise DeltaCorrupt(f"{path} marker names coordinate {cid!r} "
                               f"the payload does not carry")
    return ModelDelta(version=int(marker["version"]),
                      parent=int(marker.get("parent", 0)),
                      rows=rows, path=path)


def fetch_delta(url: str, dest_root: str, timeout_s: float = 30.0) -> str:
    """Pull one delta directory's artifacts over HTTP (the wire leg of
    docs/SERVING.md "Multi-host fleet": a DeltaArtifactServer exports
    the publisher's directory; remote replicas call this instead of
    assuming a shared filesystem). Returns the LOCAL delta directory,
    ready for :func:`read_delta`.

    The at-rest commit discipline crosses the wire intact: the payload
    is fetched and atomically written FIRST, the marker LAST — a torn
    fetch (connection cut, ``fabric.delta_fetch`` injection) leaves a
    marker-less local directory that :func:`read_delta` refuses, and
    the previously applied version stays servable. Every transfer
    failure lands in the same :class:`DeltaCorrupt` class as a torn
    shared-filesystem write; CRC verification happens in
    :func:`read_delta` exactly as for a local artifact.
    """
    import urllib.request

    from photon_ml_tpu import obs

    url = url.rstrip("/")
    name = url.rsplit("/", 1)[-1]
    if not _DIR_RE.match(name):
        raise DeltaCorrupt(f"{url} does not name a delta directory "
                           f"(want .../delta-vNNNNNN)")
    dest = os.path.join(dest_root, name)
    os.makedirs(dest, exist_ok=True)
    total = 0
    # Payload first, marker LAST — the marker IS the commit point.
    for i, fname in enumerate((_ROWS, _MARKER)):
        try:
            flt.fire(flt.sites.FABRIC_DELTA_FETCH, index=i)
            with urllib.request.urlopen(f"{url}/{fname}",
                                        timeout=timeout_s) as resp:
                blob = resp.read()
        except (OSError, ValueError) as e:
            raise DeltaCorrupt(
                f"fetch of {url}/{fname} failed ({type(e).__name__}: "
                f"{e}) — previous version stays servable")
        atomic_write(os.path.join(dest, fname),
                     lambda f, b=blob: f.write(b))
        total += len(blob)
    mx = obs.metrics()
    if mx is not None:
        mx.counter("photon_fabric_delta_fetch_total").inc()
        mx.counter("photon_fabric_delta_fetch_bytes_total").inc(total)
    logger.info("fetched delta %s from %s (%d bytes)", name, url, total)
    return dest
