"""Avro training-data reader: container files -> columnar GameDataset.

Reference parity: photon-client ``data/avro/AvroDataReader.scala`` (+
``AvroFieldNames.scala`` field-name presets,
``data/FeatureShardConfiguration.scala``). The reference assembles one
sparse-vector DataFrame column per feature shard; the TPU-first equivalent
assembles one dense (n, d_shard) host matrix per shard (sparse CSR shards
for huge feature spaces live in the Criteo path, ``data/sparse.py``), plus
int32 entity-id columns mapped through per-RE-type vocabularies.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Optional, Sequence, Union

import numpy as np

from photon_ml_tpu.avro.container import read_records
from photon_ml_tpu.data.game_data import (GameDataset, SparseShard,
                                          vocab_token)
from photon_ml_tpu.index.indexmap import (DefaultIndexMap, INTERCEPT_KEY,
                                          IndexMap, feature_key)
from photon_ml_tpu.utils import events as ev_mod

logger = logging.getLogger("photon_ml_tpu.avro")

# What the fallback warning says about the pure-Python codec; the ratio
# to the native block decoder is not measured on the current chip's host.
_FALLBACK_RATE_GAP = "far slower (one Python call per field)"


@dataclasses.dataclass(frozen=True)
class FieldNames:
    """Record field-name preset (AvroFieldNames parity)."""

    response: str = "label"
    offset: str = "offset"
    weight: str = "weight"
    uid: str = "uid"
    metadata: str = "metadataMap"


TRAINING_EXAMPLE_FIELDS = FieldNames()  # TrainingExampleFieldNames parity
RESPONSE_PREDICTION_FIELDS = FieldNames(response="response")


@dataclasses.dataclass(frozen=True)
class FeatureShardConfig:
    """A feature shard = named union of feature bags + intercept flag
    (FeatureShardConfiguration parity).

    ``sparse=True`` materializes the shard as ELL (data/game_data.py
    SparseShard) instead of a dense (n, d) matrix — the Criteo regime,
    where d reaches millions and densifying is impossible. Repeated
    features within a record accumulate (same as the dense path), keeping
    the ELL rows canonical."""

    feature_bags: tuple[str, ...] = ("features",)
    has_intercept: bool = True
    sparse: bool = False


def _record_features(record: dict, bags: Sequence[str]):
    for bag in bags:
        for f in record.get(bag) or ():
            yield feature_key(f["name"], f.get("term", ""))


def _entity_value(record: dict, re_type: str,
                  meta_field: str) -> Optional[str]:
    v = record.get(re_type)
    if v is None:
        meta = record.get(meta_field) or {}
        v = meta.get(re_type)
    return None if v is None else str(v)


class AvroDataReader:
    """Read Avro container files into a GameDataset.

    ``read`` makes one pass if index maps (and entity vocabularies) are
    supplied, otherwise a scan pass builds DefaultIndexMaps per shard —
    mirroring the reference's choice between PalDB-backed maps and
    from-data map generation.
    """

    def __init__(self, field_names: FieldNames = TRAINING_EXAMPLE_FIELDS):
        self.fields = field_names

    def read(
        self,
        paths: Union[str, Sequence[str]],
        feature_shard_configs: dict[str, FeatureShardConfig],
        random_effect_types: Sequence[str] = (),
        index_maps: Optional[dict[str, IndexMap]] = None,
        entity_vocabs: Optional[dict[str, dict[str, int]]] = None,
        use_native: bool = True,
        allow_unseen_entities: bool = False,
        chunk_rows: int = 65536,
        ingest=None,
    ):
        """Returns (GameDataset, ReadMeta).

        ``use_native=True`` (default) decodes supported schemas through the
        C++ block decoder (native/avro_decode.cc), block-parallel and
        pipelined (photon_ml_tpu/ingest, knobs via ``ingest=
        IngestConfig(...)`` including the columnar warm-restart cache) with
        vectorized columnar assembly — identical results to the pure-Python
        path, which remains the fallback for exotic schemas or when no
        toolchain is available. The fallback is LOUD: it logs the measured
        rate gap and emits an ``IngestFallback`` event, because silently
        degrading ~20x on the cold-fit input layer cost a round of
        benchmarking to notice (docs/INGEST.md).

        ``allow_unseen_entities=True`` makes a frozen ``entity_vocabs``
        EXTENSIBLE: ids absent from it get fresh rows appended after the
        frozen range instead of raising. Scoring-time semantics match the
        reference — a random-effect model has no row for those ids, and
        model scoring contributes exactly zero for them (fixed effect
        only).

        Bounded-memory streaming (reference: executors stream HDFS
        partitions through ``AvroDataReader.scala``; SURVEY §0 "host-side
        readers feeding a device-prefetch pipeline"): the Python path
        decodes at most ``chunk_rows`` record dicts at a time (decoded
        records cost ~50× their columnar size, so this bounds the
        dominant transient); the native path frees each file's decoded
        columns as soon as they are folded in whenever ``index_maps`` is
        given — the production flow (frozen feature space over daily
        partitions) never holds more than one partition's columns beyond
        the output arrays. Without ``index_maps`` the feature space is
        discovered in a separate streaming pass first, trading one extra
        read of the input for flat memory.
        """
        if isinstance(paths, str):
            paths = [paths]
        if use_native:
            out, fallback = self._read_native(
                paths, feature_shard_configs, random_effect_types,
                index_maps, entity_vocabs, allow_unseen_entities,
                ingest=ingest)
            if out is not None:
                return out
            if fallback:
                logger.warning(
                    "avro ingest is falling back to the pure-Python "
                    "codec — %s — reason: %s (docs/INGEST.md)",
                    _FALLBACK_RATE_GAP, fallback)
                ev_mod.default_emitter.emit(
                    ev_mod.IngestFallback(reason=fallback))

        def stream():
            for p in paths:
                yield from read_records(p)

        if index_maps is None:
            # Discovery pass: ONE extra stream over the input collects
            # every shard's key set simultaneously (bounded by vocabulary
            # size, not input size), then assembly streams again.
            keys_by_shard: dict[str, dict] = {
                s: {} for s in feature_shard_configs}
            for r in stream():
                for shard, cfg in feature_shard_configs.items():
                    sk = keys_by_shard[shard]
                    for k in _record_features(r, cfg.feature_bags):
                        sk[k] = None
            index_maps = {
                shard: DefaultIndexMap.from_keys(
                    keys_by_shard[shard],
                    add_intercept=cfg.has_intercept)
                for shard, cfg in feature_shard_configs.items()
            }

        frozen_vocab = entity_vocabs is not None
        vocabs: dict[str, dict[str, int]] = (
            {t: dict(v) for t, v in entity_vocabs.items()} if frozen_vocab
            else {t: {} for t in random_effect_types})

        acc = _ChunkAccumulator(self.fields, feature_shard_configs,
                                index_maps, random_effect_types, vocabs,
                                frozen_vocab, allow_unseen_entities)
        chunk: list[dict] = []
        for rec in stream():
            chunk.append(rec)
            if len(chunk) >= max(1, chunk_rows):
                acc.add_chunk(chunk)
                chunk = []
        if chunk:
            acc.add_chunk(chunk)
        if acc.num_rows == 0:
            raise ValueError(f"no records under {paths}")
        ds, uids = acc.finalize()
        ds.vocab_tokens = _make_vocab_tokens(entity_vocabs, vocabs)
        return ds, ReadMeta(index_maps=index_maps, entity_vocabs=vocabs,
                            uids=uids)


    # -- native fast path --------------------------------------------------

    def _read_native(self, paths, feature_shard_configs,
                     random_effect_types, index_maps, entity_vocabs,
                     allow_unseen_entities=False, ingest=None):
        """Vectorized read over native/avro_decode.cc columns, block-
        parallel and pipelined (photon_ml_tpu/ingest): the inputs split
        at sync-marker boundaries, decode workers fan over the chunks,
        and this thread folds each chunk's columns in plan order as it
        arrives — so decode and fold overlap, and warm restarts
        memory-map the columnar ingest cache instead of decoding.

        Returns ``(result, fallback_reason)``; ``result is None`` means
        the caller falls back to the per-record Python loop (loudly when
        ``fallback_reason`` is set; a None reason means the Python path
        is about to raise its own error). Semantics are kept IDENTICAL
        to that loop: encounter-order index maps, first-occurrence
        entity vocabularies, accumulate-then-set-intercept feature
        assembly, and the same error conditions."""
        import os

        from photon_ml_tpu import ingest as ing
        from photon_ml_tpu.avro import native_decode as nd

        if not nd.native_available():
            return None, ("the native Avro decoder is unavailable (no "
                          "C++ toolchain, or PHOTON_TPU_NO_NATIVE_AVRO=1)")
        files: list[str] = []
        for p in paths:
            if os.path.isdir(p):
                files.extend(os.path.join(p, name)
                             for name in sorted(os.listdir(p))
                             if name.endswith(".avro"))
            elif os.path.exists(p):
                files.append(p)
            else:
                # Let the Python path raise its own error (not a silent
                # degradation — the read fails either way).
                return None, None
        if not files:
            raise ValueError(f"no records under {list(paths)}")

        fields = self.fields
        bag_names = list(dict.fromkeys(
            b for cfg in feature_shard_configs.values()
            for b in cfg.feature_bags))
        captures = {
            fields.response: (nd.CAP_RESPONSE, 0),
            fields.offset: (nd.CAP_OFFSET, 0),
            fields.weight: (nd.CAP_WEIGHT, 0),
            fields.uid: (nd.CAP_UID, 0),
            fields.metadata: (nd.CAP_META, 0),
        }
        if len(captures) != 5:
            return None, "colliding field-name preset"
        for k, b in enumerate(bag_names):
            if b in captures:
                return None, (f"feature bag {b!r} collides with a "
                              f"scalar field name")
            captures[b] = (nd.CAP_BAG, k)
        bag_pos = {b: k for k, b in enumerate(bag_names)}

        # Block scan + per-file decode plans. Any file whose writer
        # schema the native plan compiler cannot express sends the WHOLE
        # read down the Python path (one feature space, one code path).
        forbidden = frozenset(random_effect_types)
        fbs: list[ing.FileBlocks] = []
        plans: list[np.ndarray] = []
        for f in files:
            fb = ing.scan_file(f)
            schema = fb.schema
            if isinstance(schema, dict) and any(
                    fld.get("name") in forbidden
                    for fld in schema.get("fields", ())):
                return None, (f"{f}: an entity id is a top-level record "
                              f"field (metadataMap layout required)")
            plan = nd.compile_plan(schema, captures)
            if plan is None:
                return None, f"{f}: schema outside the native family"
            fbs.append(fb)
            plans.append(plan)
        if not sum(fb.num_records for fb in fbs):
            raise ValueError(f"no records under {list(paths)}")

        config = ingest or ing.IngestConfig()
        chunks = ing.plan_chunks(fbs, config.chunk_records)
        cache_key = None
        if config.cache_dir:
            cache_key = ing.ingest_key(fbs, captures, len(bag_names),
                                       config.chunk_records)
        pipe = ing.IngestPipeline(chunks, plans, n_bags=len(bag_names),
                                  config=config, cache_key=cache_key)

        # Fold. With ``index_maps`` given (the production frozen-feature-
        # space flow), each chunk's decoded columns are folded into compact
        # accumulators and FREED before the next chunk is folded — peak
        # memory is the output arrays plus the pipeline's depth bound.
        # Without maps the feature space must be known before columns can
        # be mapped, so all chunks stay decoded until the union key tables
        # are built (the one-pass trade; pass index_maps to bound memory).
        incremental = index_maps is not None
        decoded: list = []
        scal_chunks: list[tuple] = []  # (response, offsets, weights, uids)
        coo_chunks: dict[str, list[tuple]] = {
            s: [] for s in feature_shard_configs}
        n = 0

        def fold_scalars(d, base):
            uid_seg = np.arange(base, base + d.num_records).astype(object)
            present = d.uid_kind != 0
            if present.any():
                uid_seg[present] = d.uids[present]
            scal_chunks.append((d.response.astype(np.float32),
                                d.offsets.astype(np.float32),
                                d.weights.astype(np.float32), uid_seg))

        def fold_features(d, base):
            for shard, cfg in feature_shard_configs.items():
                imap = index_maps[shard]
                for b in cfg.feature_bags:
                    bag = d.bags[bag_pos[b]]
                    if not len(bag.rows):
                        continue
                    lut = np.asarray([imap.get_index(s)
                                      for s in bag.key_strings], np.int64)
                    cols = lut[np.asarray(bag.keys)]
                    keep = cols >= 0
                    coo_chunks[shard].append(
                        (np.asarray(bag.rows)[keep] + base, cols[keep],
                         np.asarray(bag.values)[keep]))

        for d in pipe.chunks():
            if incremental:
                fold_scalars(d, n)
                fold_features(d, n)
                # Entity ids still need the string tables; keep only those
                # and DROP the bag/scalar columns before the next fold
                # (otherwise chunks peak-coexist beyond the depth bound).
                decoded.append(_MetaOnly(d))
                n += d.num_records
                del d
            else:
                decoded.append(d)
                n += d.num_records
        if n == 0:
            raise ValueError(f"no records under {list(paths)}")

        # Index maps: DefaultIndexMap.from_keys SORTS its keys, so the
        # union of each shard's bag key tables is all that matters (the
        # tables already deduplicate per bag per file).
        if index_maps is None:
            index_maps = {}
            for shard, cfg in feature_shard_configs.items():
                keys: set[str] = set()
                for d in decoded:
                    for b in cfg.feature_bags:
                        keys.update(d.bags[bag_pos[b]].key_strings)
                index_maps[shard] = DefaultIndexMap.from_keys(
                    keys, add_intercept=cfg.has_intercept)
            base = 0
            for d in decoded:
                fold_scalars(d, base)
                fold_features(d, base)
                base += d.num_records

        response = np.concatenate([c[0] for c in scal_chunks])
        offsets = np.concatenate([c[1] for c in scal_chunks])
        weights = np.concatenate([c[2] for c in scal_chunks])
        uids = np.concatenate([c[3] for c in scal_chunks])

        # Feature shards.
        feature_shards: dict = {}
        for shard, cfg in feature_shard_configs.items():
            imap = index_maps[shard]
            dcols = len(imap)
            ji = imap.get_index(INTERCEPT_KEY) if cfg.has_intercept else -1
            pieces = coo_chunks[shard]
            rows = (np.concatenate([p[0] for p in pieces]) if pieces
                    else np.zeros(0, np.int64))
            cols = (np.concatenate([p[1] for p in pieces]) if pieces
                    else np.zeros(0, np.int64))
            vals = (np.concatenate([p[2] for p in pieces]) if pieces
                    else np.zeros(0, np.float64))
            if not cfg.sparse:
                mat = np.zeros((n, dcols), np.float32)
                np.add.at(mat, (rows, cols), vals.astype(np.float32))
                if ji >= 0:
                    mat[:, ji] = 1.0
                feature_shards[shard] = mat
                continue
            # Sparse (ELL via CSR): accumulate duplicates, then SET the
            # intercept (the per-record dict semantics of the slow path).
            if ji >= 0:
                keep = cols != ji
                rows, cols, vals = rows[keep], cols[keep], vals[keep]
            pair = rows * dcols + cols
            uniq, inv = np.unique(pair, return_inverse=True)
            sums = np.bincount(inv, weights=vals,
                               minlength=len(uniq)).astype(np.float32)
            urows, ucols = uniq // dcols, uniq % dcols
            if ji >= 0:
                urows = np.concatenate([urows, np.arange(n)])
                ucols = np.concatenate(
                    [ucols, np.full(n, ji, np.int64)])
                sums = np.concatenate([sums, np.ones(n, np.float32)])
                order = np.lexsort((ucols, urows))
                urows, ucols, sums = (urows[order], ucols[order],
                                      sums[order])
            from photon_ml_tpu.data.sparse import from_csr

            indptr = np.zeros(n + 1, np.int64)
            np.cumsum(np.bincount(urows, minlength=n), out=indptr[1:])
            ell = from_csr(indptr, ucols.astype(np.int32), sums,
                           labels=response, num_features=dcols)
            feature_shards[shard] = SparseShard(
                indices=ell.indices, values=ell.values,
                num_features=dcols)

        # Entity ids (from metadataMap; direct-field layouts fell back).
        frozen = entity_vocabs is not None
        vocabs: dict[str, dict[str, int]] = (
            {t: dict(v) for t, v in entity_vocabs.items()} if frozen
            else {t: {} for t in random_effect_types})
        id_cols = {}
        for t in random_effect_types:
            col = np.zeros(n, np.int64)
            base = 0
            for d in decoded:
                try:
                    key_id = d.meta_key_strings.index(t)
                    sel = d.meta_keys == key_id
                except ValueError:
                    sel = np.zeros(len(d.meta_keys), bool)
                rows_t = d.meta_rows[sel]
                val_ids = d.meta_vals[sel]
                if (len(rows_t) != d.num_records
                        or not np.array_equal(
                            rows_t, np.arange(d.num_records))):
                    present = np.zeros(d.num_records, bool)
                    present[rows_t] = True
                    missing = np.flatnonzero(~present)
                    if len(missing):
                        raise ValueError(
                            f"record {base + int(missing[0])} missing "
                            f"random-effect id {t!r}")
                    # Wire-level duplicate map keys: keep the LAST value
                    # per record, the Python dict-decode semantics.
                    last = np.full(d.num_records, -1, np.int64)
                    last[rows_t] = np.arange(len(rows_t))
                    val_ids = val_ids[last]
                lut = np.full(len(d.meta_val_strings), -1, np.int64)
                uniq_vids, first = np.unique(val_ids, return_index=True)
                for vid in uniq_vids[np.argsort(first)]:
                    raw = d.meta_val_strings[vid]
                    if raw not in vocabs[t]:
                        if frozen and not allow_unseen_entities:
                            raise KeyError(
                                f"unseen entity {raw!r} for {t!r} under a "
                                f"frozen vocabulary (scoring with unseen "
                                f"entities must map them explicitly, or "
                                f"pass allow_unseen_entities=True)")
                        vocabs[t][raw] = len(vocabs[t])
                    lut[vid] = vocabs[t][raw]
                col[base: base + d.num_records] = lut[val_ids]
                base += d.num_records
            id_cols[t] = col.astype(np.int32)

        ds = GameDataset(
            response=response,
            offsets=offsets,
            weights=weights,
            feature_shards=feature_shards,
            entity_ids=id_cols,
            num_entities={t: len(v) for t, v in vocabs.items()},
            intercept_index={
                shard: (index_maps[shard].get_index(INTERCEPT_KEY)
                        if cfg.has_intercept else None)
                for shard, cfg in feature_shard_configs.items()
            },
            vocab_tokens=_make_vocab_tokens(entity_vocabs, vocabs),
            entity_counts={
                t: np.bincount(col, minlength=len(vocabs[t]))
                for t, col in id_cols.items()},
        )
        return (ds, ReadMeta(index_maps=index_maps, entity_vocabs=vocabs,
                             uids=uids)), None


class _MetaOnly:
    """Retains only a DecodedFile's metadataMap columns (what entity-id
    assembly still needs) so the much larger bag/scalar columns can be
    freed file-by-file in the incremental native path."""

    __slots__ = ("num_records", "meta_key_strings", "meta_keys",
                 "meta_rows", "meta_vals", "meta_val_strings")

    def __init__(self, d):
        self.num_records = d.num_records
        self.meta_key_strings = d.meta_key_strings
        self.meta_keys = d.meta_keys
        self.meta_rows = d.meta_rows
        self.meta_vals = d.meta_vals
        self.meta_val_strings = d.meta_val_strings


class _ChunkAccumulator:
    """Bounded-memory columnar assembly for the Python decode path.

    Per chunk it runs exactly the historical per-record loop (missing-
    response errors with GLOBAL record indices, accumulate-then-set-
    intercept feature assembly, encounter-order entity vocabularies) but
    emits compact columnar pieces and lets the record dicts go; peak
    transient memory is one chunk of dicts, independent of input size.
    """

    def __init__(self, fields, feature_shard_configs, index_maps,
                 random_effect_types, vocabs, frozen_vocab,
                 allow_unseen_entities):
        self.fields = fields
        self.cfgs = feature_shard_configs
        self.index_maps = index_maps
        self.re_types = list(random_effect_types)
        self.vocabs = vocabs
        self.frozen_vocab = frozen_vocab
        self.allow_unseen = allow_unseen_entities
        self.num_rows = 0
        self._response: list[np.ndarray] = []
        self._offsets: list[np.ndarray] = []
        self._weights: list[np.ndarray] = []
        self._uids: list[np.ndarray] = []
        self._dense: dict[str, list[np.ndarray]] = {
            s: [] for s, c in feature_shard_configs.items() if not c.sparse}
        # Sparse shards accumulate CSR pieces: (row_nnz, cols, vals).
        self._sparse: dict[str, list[tuple]] = {
            s: [] for s, c in feature_shard_configs.items() if c.sparse}
        self._ids: dict[str, list[np.ndarray]] = {
            t: [] for t in random_effect_types}

    def add_chunk(self, records: list[dict]) -> None:
        fields = self.fields
        base = self.num_rows
        n = len(records)
        response = np.zeros(n, np.float32)
        offsets = np.zeros(n, np.float32)
        weights = np.ones(n, np.float32)
        uids = np.empty(n, object)
        mats = {s: np.zeros((n, len(self.index_maps[s])), np.float32)
                for s in self._dense}
        sp_rows = {s: [dict() for _ in range(n)] for s in self._sparse}
        ids = {t: np.zeros(n, np.int32) for t in self.re_types}

        for i, rec in enumerate(records):
            # Reference AvroDataReader fails fast on a missing response
            # column; defaulting would silently train on all-zero labels.
            if rec.get(fields.response) is None:
                raise ValueError(
                    f"record {base + i} is missing required response field "
                    f"{fields.response!r}")
            response[i] = rec[fields.response]
            off = rec.get(fields.offset)
            offsets[i] = 0.0 if off is None else off
            w = rec.get(fields.weight)
            weights[i] = 1.0 if w is None else w
            uid = rec.get(fields.uid)
            uids[i] = base + i if uid is None else uid
            for shard, cfg in self.cfgs.items():
                imap = self.index_maps[shard]
                if cfg.sparse:
                    row = sp_rows[shard][i]
                    for bag in cfg.feature_bags:
                        for f in rec.get(bag) or ():
                            j = imap.get_index(feature_key(
                                f["name"], f.get("term", "")))
                            if j >= 0:
                                row[j] = row.get(j, 0.0) + f["value"]
                    if cfg.has_intercept:
                        j = imap.get_index(INTERCEPT_KEY)
                        if j >= 0:
                            row[j] = 1.0
                    continue
                mat = mats[shard]
                for bag in cfg.feature_bags:
                    for f in rec.get(bag) or ():
                        j = imap.get_index(feature_key(
                            f["name"], f.get("term", "")))
                        if j >= 0:
                            mat[i, j] += f["value"]
                if cfg.has_intercept:
                    j = imap.get_index(INTERCEPT_KEY)
                    if j >= 0:
                        mat[i, j] = 1.0
            for t in self.re_types:
                raw = _entity_value(rec, t, fields.metadata)
                if raw is None:
                    raise ValueError(
                        f"record {base + i} missing random-effect id {t!r}")
                vocab = self.vocabs[t]
                if raw not in vocab:
                    if self.frozen_vocab and not self.allow_unseen:
                        raise KeyError(
                            f"unseen entity {raw!r} for {t!r} under a "
                            f"frozen vocabulary (scoring with unseen "
                            f"entities must map them explicitly, or pass "
                            f"allow_unseen_entities=True)")
                    vocab[raw] = len(vocab)
                ids[t][i] = vocab[raw]

        self._response.append(response)
        self._offsets.append(offsets)
        self._weights.append(weights)
        self._uids.append(uids)
        for s, m in mats.items():
            self._dense[s].append(m)
        for s, rows in sp_rows.items():
            row_nnz = np.asarray([len(r) for r in rows], np.int64)
            by_row = [sorted(r.items()) for r in rows]
            cols = np.asarray([j for r in by_row for j, _ in r], np.int32)
            vals = np.asarray([v for r in by_row for _, v in r],
                              np.float32)
            self._sparse[s].append((row_nnz, cols, vals))
        for t, col in ids.items():
            self._ids[t].append(col)
        self.num_rows += n

    def finalize(self):
        from photon_ml_tpu.data.sparse import from_csr

        n = self.num_rows
        response = np.concatenate(self._response)
        feature_shards: dict = {
            s: np.concatenate(chunks) for s, chunks in self._dense.items()}
        for s, pieces in self._sparse.items():
            d = len(self.index_maps[s])
            indptr = np.zeros(n + 1, np.int64)
            np.cumsum(np.concatenate([p[0] for p in pieces]),
                      out=indptr[1:])
            ell = from_csr(indptr,
                           np.concatenate([p[1] for p in pieces]),
                           np.concatenate([p[2] for p in pieces]),
                           labels=response, num_features=d)
            feature_shards[s] = SparseShard(
                indices=ell.indices, values=ell.values, num_features=d)
        id_cols = {t: np.concatenate(chunks)
                   for t, chunks in self._ids.items()}
        ds = GameDataset(
            response=response,
            offsets=np.concatenate(self._offsets),
            weights=np.concatenate(self._weights),
            feature_shards=feature_shards,
            entity_ids=id_cols,
            num_entities={t: len(v) for t, v in self.vocabs.items()},
            intercept_index={
                s: (self.index_maps[s].get_index(INTERCEPT_KEY)
                    if c.has_intercept else None)
                for s, c in self.cfgs.items()
            },
            entity_counts={
                t: np.bincount(col, minlength=len(self.vocabs[t]))
                for t, col in id_cols.items()},
        )
        return ds, np.concatenate(self._uids)


def _make_vocab_tokens(frozen_vocabs, final_vocabs):
    """(base, final) provenance digests per RE type: ``base`` identifies
    the frozen vocabulary the ids extend (the final vocabulary itself when
    built fresh), ``final`` the resulting one. Lets a consumer distinguish
    a true vocabulary extension from an unrelated same-size vocabulary —
    counts cannot (GameEstimator.fit checks validation.base ==
    training.final)."""
    tokens = {}
    for t, v in final_vocabs.items():
        final = vocab_token(v)
        if frozen_vocabs is not None and t in frozen_vocabs:
            base = (final if len(frozen_vocabs[t]) == len(v)
                    else vocab_token(frozen_vocabs[t]))
        else:
            base = final
        tokens[t] = (base, final)
    return tokens


@dataclasses.dataclass
class ReadMeta:
    """Side products of a read: feature maps, entity vocabularies, uids."""

    index_maps: dict[str, IndexMap]
    entity_vocabs: dict[str, dict[str, int]]
    uids: np.ndarray
