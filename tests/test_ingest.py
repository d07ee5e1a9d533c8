"""photon-ingest: block-parallel Avro ingestion (photon_ml_tpu/ingest).

The contract under test:

- parallel decode is BIT-IDENTICAL to the serial pure-Python reader for
  every worker count and both pool modes (scheduling never changes
  content, only timing);
- the columnar mmap cache round-trips exactly, warm reads run ZERO
  decode work, a corrupt chunk re-decodes exactly itself, and a driver
  SIGKILL mid-ingest resumes from the ``.ok`` markers with final
  coefficients bit-identical to a never-killed run;
- the pipeline's lifecycle events fire (finally-guarded on errors) and
  the pure-Python fallback is LOUD.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from photon_ml_tpu import faults
from photon_ml_tpu import ingest as ing
from photon_ml_tpu.avro import native_decode as nd
from photon_ml_tpu.avro import schemas
from photon_ml_tpu.avro.container import DataFileWriter
from photon_ml_tpu.avro.data_reader import (AvroDataReader,
                                            FeatureShardConfig)
from photon_ml_tpu.data.game_data import SparseShard
from photon_ml_tpu.utils import events as ev

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))

needs_native = pytest.mark.skipif(not nd.native_available(),
                                  reason="no native toolchain")


@pytest.fixture(autouse=True)
def _no_leftover_plan():
    yield
    faults.install(None)


def _records(rng, n, n_users=12):
    recs = []
    for i in range(n):
        recs.append({
            "uid": (i if i % 3 == 0 else f"u{i}" if i % 3 == 1 else None),
            "label": float(rng.integers(0, 2)),
            "weight": float(rng.uniform(0.5, 2.0)),
            "offset": float(rng.normal()),
            "features": [{"name": f"x{rng.integers(0, 40)}",
                          "term": rng.choice(["", "a"]),
                          "value": float(rng.normal())}
                         for _ in range(rng.integers(1, 6))],
            "metadataMap": {"userId": f"u{rng.integers(0, n_users)}"},
        })
    return recs


def _write(path, recs, codec="deflate", block_records=128):
    with DataFileWriter(str(path), schemas.TRAINING_EXAMPLE_AVRO,
                        codec=codec, block_records=block_records) as w:
        for r in recs:
            w.append(r)


def _compare(a, b):
    ds_a, meta_a = a
    ds_b, meta_b = b
    np.testing.assert_array_equal(ds_a.response, ds_b.response)
    np.testing.assert_array_equal(ds_a.offsets, ds_b.offsets)
    np.testing.assert_array_equal(ds_a.weights, ds_b.weights)
    assert set(ds_a.feature_shards) == set(ds_b.feature_shards)
    for s, y in ds_b.feature_shards.items():
        x = ds_a.feature_shards[s]
        if isinstance(y, SparseShard):
            np.testing.assert_array_equal(x.indices, y.indices)
            np.testing.assert_array_equal(x.values, y.values)
            assert x.num_features == y.num_features
        else:
            np.testing.assert_array_equal(x, y)
    for t, col in ds_b.entity_ids.items():
        np.testing.assert_array_equal(ds_a.entity_ids[t], col)
    assert meta_a.entity_vocabs == meta_b.entity_vocabs
    for s in meta_b.index_maps:
        assert len(meta_a.index_maps[s]) == len(meta_b.index_maps[s])
    np.testing.assert_array_equal(meta_a.uids, meta_b.uids)


# ------------------------------------------------------------ block scan


def test_scan_file_partitions_blocks(rng, tmp_path):
    p = tmp_path / "a.avro"
    _write(p, _records(rng, 700), block_records=100)
    fb = ing.scan_file(str(p))
    assert fb.num_records == 700
    assert len(fb.block_counts) == 7
    assert fb.block_offsets[0] == fb.header_len
    assert fb.block_offsets[-1] == fb.size
    assert all(a < b for a, b in zip(fb.block_offsets, fb.block_offsets[1:]))


def test_plan_chunks_groups_whole_blocks(rng, tmp_path):
    p = tmp_path / "a.avro"
    _write(p, _records(rng, 1000), block_records=100)
    fb = ing.scan_file(str(p))
    chunks = ing.plan_chunks([fb], chunk_records=250)
    # Greedy grouping: 100-record blocks accumulate to >= 250 -> 3+3+3+1.
    assert [c.records for c in chunks] == [300, 300, 300, 100]
    assert chunks[0].start == fb.header_len
    assert chunks[-1].end == fb.size
    for a, b in zip(chunks, chunks[1:]):
        assert a.end == b.start
    assert [c.index for c in chunks] == [0, 1, 2, 3]


def test_scan_file_rejects_corruption(rng, tmp_path):
    p = tmp_path / "a.avro"
    _write(p, _records(rng, 300), block_records=100)
    raw = bytearray(p.read_bytes())
    raw[len(raw) - 8] ^= 0xFF  # inside the final sync marker
    p.write_bytes(bytes(raw))
    with pytest.raises(ValueError, match="sync marker|truncated"):
        ing.scan_file(str(p))


# ------------------------------------------------- parallel decode parity


@needs_native
@pytest.mark.parametrize("workers", [1, 2, 8])
@pytest.mark.parametrize("codec", ["null", "deflate"])
def test_parallel_decode_bit_identical(rng, tmp_path, workers, codec):
    paths = []
    for i in range(2):  # multi-file: the merge crosses file boundaries
        p = tmp_path / f"p{i}.avro"
        _write(p, _records(rng, 400 + 37 * i), codec=codec,
               block_records=64)
        paths.append(str(p))
    cfgs = {"dense": FeatureShardConfig(("features",), True),
            "sp": FeatureShardConfig(("features",), True, sparse=True)}
    serial = AvroDataReader().read(paths, cfgs,
                                   random_effect_types=["userId"],
                                   use_native=False)
    par = AvroDataReader().read(
        paths, cfgs, random_effect_types=["userId"],
        ingest=ing.IngestConfig(workers=workers, chunk_records=100))
    _compare(par, serial)


@needs_native
def test_parallel_decode_process_mode(rng, tmp_path):
    p = tmp_path / "a.avro"
    _write(p, _records(rng, 600), block_records=64)
    cfgs = {"global": FeatureShardConfig(("features",), True)}
    serial = AvroDataReader().read(str(p), cfgs,
                                   random_effect_types=["userId"],
                                   use_native=False)
    par = AvroDataReader().read(
        str(p), cfgs, random_effect_types=["userId"],
        ingest=ing.IngestConfig(workers=2, mode="process",
                                chunk_records=150))
    _compare(par, serial)


@needs_native
def test_frozen_maps_and_vocab_parallel(rng, tmp_path):
    """The incremental (index_maps given) fold path, chunked."""
    p = tmp_path / "a.avro"
    _write(p, _records(rng, 500), block_records=64)
    cfgs = {"global": FeatureShardConfig(("features",), True)}
    reader = AvroDataReader()
    _, meta = reader.read(str(p), cfgs, random_effect_types=["userId"],
                          use_native=False)
    serial = reader.read(str(p), cfgs, random_effect_types=["userId"],
                         index_maps=meta.index_maps,
                         entity_vocabs=meta.entity_vocabs,
                         use_native=False)
    par = reader.read(str(p), cfgs, random_effect_types=["userId"],
                      index_maps=meta.index_maps,
                      entity_vocabs=meta.entity_vocabs,
                      ingest=ing.IngestConfig(workers=4,
                                              chunk_records=120))
    _compare(par, serial)


@needs_native
def test_decode_error_surfaces_at_plan_order(rng, tmp_path):
    """A corrupt payload fails the read with the serial reader's error
    class, and the Start/Finish event pair still closes (PML007's
    finally-guard, observed from outside)."""
    p = tmp_path / "a.avro"
    _write(p, _records(rng, 400), codec="deflate", block_records=100)
    fb = ing.scan_file(str(p))
    raw = bytearray(p.read_bytes())
    # Rewrite block 2's record-count varint: 100 (zigzag 200 = C8 01)
    # becomes 127 (FE 01, same byte length) — the block then declares
    # more records than its payload holds, a deterministic truncated-
    # decode error (raw DEFLATE carries no checksum, so payload bit
    # flips are NOT guaranteed to fail).
    off = fb.block_offsets[2]
    assert raw[off:off + 2] == b"\xc8\x01"
    raw[off:off + 2] = b"\xfe\x01"
    p.write_bytes(bytes(raw))
    cfgs = {"global": FeatureShardConfig(("features",), True)}
    seen = []
    ev.default_emitter.register(seen.append)
    try:
        with pytest.raises(ValueError):
            AvroDataReader().read(
                str(p), cfgs, random_effect_types=["userId"],
                ingest=ing.IngestConfig(workers=4, chunk_records=100))
    finally:
        ev.default_emitter.unregister(seen.append)
    starts = [e for e in seen if isinstance(e, ev.IngestStart)]
    finishes = [e for e in seen if isinstance(e, ev.IngestFinish)]
    assert len(starts) == 1 and len(finishes) == 1


# ------------------------------------------------------------ ingest cache


@needs_native
def test_cache_roundtrip_and_zero_decode_warm(rng, tmp_path):
    p = tmp_path / "a.avro"
    _write(p, _records(rng, 500), block_records=64)
    cfgs = {"dense": FeatureShardConfig(("features",), True),
            "sp": FeatureShardConfig(("features",), True, sparse=True)}
    cfg = ing.IngestConfig(workers=2, chunk_records=120,
                           cache_dir=str(tmp_path / "icache"))
    cold = AvroDataReader().read(str(p), cfgs,
                                 random_effect_types=["userId"],
                                 ingest=cfg)
    # Warm read under an injector: the decode site must never fire.
    inj = faults.install(faults.FaultPlan())
    seen = []
    ev.default_emitter.register(seen.append)
    try:
        warm = AvroDataReader().read(str(p), cfgs,
                                     random_effect_types=["userId"],
                                     ingest=cfg)
    finally:
        ev.default_emitter.unregister(seen.append)
    assert inj.occurrences("ingest.decode_block") == 0
    blocks = [e for e in seen if isinstance(e, ev.IngestBlock)]
    assert blocks and all(b.source == "cache" for b in blocks)
    _compare(warm, cold)
    # The entry carries a completion record.
    entry = os.path.join(str(tmp_path / "icache"),
                         os.listdir(str(tmp_path / "icache"))[0])
    assert os.path.exists(os.path.join(entry, "meta.json"))


@needs_native
def test_cache_corrupt_chunk_redecodes_exactly_one(rng, tmp_path):
    p = tmp_path / "a.avro"
    _write(p, _records(rng, 500), block_records=64)
    cfgs = {"global": FeatureShardConfig(("features",), True)}
    cache_root = str(tmp_path / "icache")
    cfg = ing.IngestConfig(workers=2, chunk_records=120,
                           cache_dir=cache_root)
    cold = AvroDataReader().read(str(p), cfgs,
                                 random_effect_types=["userId"],
                                 ingest=cfg)
    entry = os.path.join(cache_root, os.listdir(cache_root)[0])
    # Bit-rot chunk 1's committed blob (marker untouched).
    victim = os.path.join(entry, "c1.bin")
    raw = bytearray(open(victim, "rb").read())
    raw[len(raw) // 2] ^= 0xFF
    with open(victim, "wb") as f:
        f.write(bytes(raw))
    seen = []
    ev.default_emitter.register(seen.append)
    try:
        warm = AvroDataReader().read(str(p), cfgs,
                                     random_effect_types=["userId"],
                                     ingest=cfg)
    finally:
        ev.default_emitter.unregister(seen.append)
    blocks = [e for e in seen if isinstance(e, ev.IngestBlock)]
    sources = {b.index: b.source for b in blocks}
    assert sources[1] == "decoded"  # exactly the corrupt chunk
    assert all(s == "cache" for i, s in sources.items() if i != 1)
    _compare(warm, cold)
    # The re-decode re-committed the chunk: a third read is all-cache.
    d = ing.load_chunk(cache_root, os.path.basename(entry), 1, n_bags=1)
    assert d is not None


@needs_native
def test_injected_cache_corruption_fails_crc(rng, tmp_path):
    """The ``ingest.cache_file`` corrupt site garbles bytes AFTER the
    checksum was recorded — loads must catch it and re-decode."""
    p = tmp_path / "a.avro"
    _write(p, _records(rng, 300), block_records=64)
    cfgs = {"global": FeatureShardConfig(("features",), True)}
    cache_root = str(tmp_path / "icache")
    cfg = ing.IngestConfig(workers=1, chunk_records=100,
                           cache_dir=cache_root)
    plan = faults.FaultPlan(specs=(
        faults.FaultSpec(site="ingest.cache_file", kind="corrupt",
                         indices=(0,), max_fires=1),))
    with faults.installed(plan) as inj:
        cold = AvroDataReader().read(str(p), cfgs,
                                     random_effect_types=["userId"],
                                     ingest=cfg)
        assert inj.fires("ingest.cache_file") == 1
    seen = []
    ev.default_emitter.register(seen.append)
    try:
        warm = AvroDataReader().read(str(p), cfgs,
                                     random_effect_types=["userId"],
                                     ingest=cfg)
    finally:
        ev.default_emitter.unregister(seen.append)
    sources = {e.index: e.source for e in seen
               if isinstance(e, ev.IngestBlock)}
    assert sources[0] == "decoded"
    assert all(s == "cache" for i, s in sources.items() if i != 0)
    _compare(warm, cold)


# ------------------------------------------------------------- loud fallback


def test_python_fallback_is_loud(rng, tmp_path, caplog, monkeypatch):
    p = tmp_path / "a.avro"
    _write(p, _records(rng, 60))
    cfgs = {"global": FeatureShardConfig(("features",), True)}
    monkeypatch.setattr(nd, "_lib", None)
    monkeypatch.setattr(nd, "_lib_failed", True)
    seen = []
    ev.default_emitter.register(seen.append)
    try:
        with caplog.at_level("WARNING", logger="photon_ml_tpu.avro"):
            ds, _ = AvroDataReader().read(str(p), cfgs,
                                          random_effect_types=["userId"])
    finally:
        ev.default_emitter.unregister(seen.append)
    assert ds.num_rows == 60  # degraded but correct
    warnings = [r for r in caplog.records
                if "pure-Python" in r.getMessage()]
    assert warnings and "far slower" in warnings[0].getMessage()
    fallbacks = [e for e in seen if isinstance(e, ev.IngestFallback)]
    assert fallbacks and "unavailable" in fallbacks[0].reason


@needs_native
def test_unsupported_schema_fallback_is_loud(tmp_path, caplog):
    from photon_ml_tpu.avro.container import write_records

    schema = {"type": "record", "name": "Odd", "fields": [
        {"name": "label", "type": "double"},
        {"name": "features", "type": {"type": "array", "items": "string"}},
    ]}
    p = tmp_path / "odd.avro"
    write_records(str(p), schema, [{"label": 1.0, "features": ["a"]}
                                   for _ in range(5)])
    seen = []
    ev.default_emitter.register(seen.append)
    try:
        with caplog.at_level("WARNING", logger="photon_ml_tpu.avro"):
            ds, _ = AvroDataReader().read(
                str(p), {"g": FeatureShardConfig((), False)})
    finally:
        ev.default_emitter.unregister(seen.append)
    assert ds.num_rows == 5
    assert [e for e in seen if isinstance(e, ev.IngestFallback)]
    assert any("schema" in r.getMessage() for r in caplog.records)


# ------------------------------------------------------------ config + CLI


def test_parse_ingest_config():
    from photon_ml_tpu.api.configs import parse_ingest_config

    cfg = parse_ingest_config("workers=8,mode=thread,depth=2,"
                              "chunk_records=4096")
    assert cfg.workers == 8 and cfg.mode == "thread"
    assert cfg.pipeline_depth == 2 and cfg.chunk_records == 4096
    with pytest.raises(ValueError, match="unknown ingest keys"):
        parse_ingest_config("workerz=8")
    with pytest.raises(ValueError, match="mode"):
        ing.IngestConfig(mode="fork")
    with pytest.raises(ValueError, match="workers"):
        ing.IngestConfig(workers=0)


def test_cli_ingest_requires_avro(rng, tmp_path):
    from photon_ml_tpu.cli import game_train
    from photon_ml_tpu.data import synthetic
    from photon_ml_tpu.data.game_data import from_synthetic
    from photon_ml_tpu.data.io import save_game_dataset

    syn = synthetic.game_data(rng, n=120, d_global=4,
                              re_specs={"userId": (10, 3)})
    train_dir = str(tmp_path / "train")
    save_game_dataset(from_synthetic(syn), train_dir)
    args = game_train.build_parser().parse_args([
        "--train", train_dir,
        "--coordinate", "name=fixed,type=fixed,shard=global",
        "--update-sequence", "fixed",
        "--ingest", "workers=2",
        "--output-dir", str(tmp_path / "out")])
    with pytest.raises(ValueError, match="--ingest"):
        game_train.run(args)


def test_build_bucketing_precomputed_counts_identical(rng):
    from photon_ml_tpu.game import buckets as bkt

    ids = rng.integers(0, 50, 4000).astype(np.int32)
    a = bkt.build_bucketing(ids, 50, lower_bound=2)
    b = bkt.build_bucketing(ids, 50, lower_bound=2,
                            counts_all=np.bincount(ids, minlength=50))
    assert len(a.buckets) == len(b.buckets)
    for x, y in zip(a.buckets, b.buckets):
        np.testing.assert_array_equal(x.entity_rows, y.entity_rows)
        np.testing.assert_array_equal(x.example_idx, y.example_idx)
        np.testing.assert_array_equal(x.counts, y.counts)
    with pytest.raises(ValueError, match="counts_all"):
        bkt.build_bucketing(ids, 50, counts_all=np.zeros(50, np.int64))


# ------------------------------------------------------------- chaos drill


@needs_native
def test_driver_sigkill_mid_ingest_resumes_bit_identical(rng, tmp_path):
    """The satellite drill: game_train is SIGKILLed at the 3rd ingest
    cache commit (--fault-plan through the ``ingest.cache_write`` site);
    the rerun resumes from the committed ``.ok`` chunks with partial
    credit and the final coefficients are bit-identical to a clean
    run."""
    from photon_ml_tpu.cli import game_train

    p = str(tmp_path / "train.avro")
    recs = []
    for i in range(600):
        feats = [{"name": f"x{j}", "term": "",
                  "value": float(rng.normal())} for j in range(4)]
        margin = feats[0]["value"] - feats[1]["value"]
        recs.append({
            "uid": i,
            "label": float(rng.uniform() < 1 / (1 + np.exp(-margin))),
            "weight": 1.0, "offset": 0.0, "features": feats,
            "metadataMap": {"userId": f"u{rng.integers(0, 12)}"},
        })
    _write(p, recs, block_records=50)
    cache = str(tmp_path / "ingest-cache")

    def _args(out, cache_dir=None):
        return [
            "--train", p,
            "--avro-feature-shard",
            "name=global,bags=features,intercept=true",
            "--avro-re-types", "userId",
            "--coordinate", "name=fixed,type=fixed,shard=global",
            "--coordinate", "name=per-user,type=random,shard=global,"
                            "re=userId",
            "--update-sequence", "fixed,per-user",
            "--iterations", "1",
            "--opt-config", "fixed:optimizer=LBFGS,reg=L2,reg_weight=1.0",
            "--opt-config",
            "per-user:optimizer=LBFGS,reg=L2,reg_weight=5.0",
            "--ingest", "workers=2,chunk_records=100",
            "--ingest-cache-dir", cache_dir or cache,
            "--no-checkpoint",
            "--output-dir", out,
        ]

    plan = faults.FaultPlan(specs=(
        faults.FaultSpec(site="ingest.cache_write", kind="kill",
                         occurrences=(2,)),))
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
            + _args(str(tmp_path / "out-killed"))
            + ["--fault-plan", plan_path],
            env=env, cwd=REPO, stdout=log, stderr=subprocess.STDOUT,
            timeout=600)
    assert proc.returncode == -9, (
        f"driver survived the SIGKILL plan (rc={proc.returncode}):\n"
        + open(log_path).read()[-3000:])
    # Partial credit on disk: only chunks COMMITTED before the kill have
    # markers (the 3rd commit was entered, never finished), no
    # completion record.
    entries = os.listdir(cache)
    assert len(entries) == 1
    markers = [f for f in os.listdir(os.path.join(cache, entries[0]))
               if f.endswith(".ok")]
    assert 1 <= len(markers) <= 2, markers
    assert not os.path.exists(
        os.path.join(cache, entries[0], "meta.json"))

    # Phase 2 (in-process): the rerun resumes from the markers...
    seen = []
    ev.default_emitter.register(seen.append)
    try:
        game_train.run(game_train.build_parser().parse_args(
            _args(str(tmp_path / "out-resumed"))))
    finally:
        ev.default_emitter.unregister(seen.append)
    starts = [e for e in seen if isinstance(e, ev.IngestStart)]
    assert starts and starts[0].cached_chunks == len(markers)
    assert starts[0].num_chunks > len(markers)  # the rest re-decoded

    # ...and a never-faulted run from scratch (fresh cache) matches bit
    # for bit.
    game_train.run(game_train.build_parser().parse_args(
        _args(str(tmp_path / "out-clean"),
              cache_dir=str(tmp_path / "fresh-cache"))))
    for rel in (os.path.join("best", "fixed-effect", "fixed",
                             "coefficients.npz"),
                os.path.join("best", "random-effect", "per-user",
                             "coefficients.npz")):
        a = np.load(os.path.join(str(tmp_path), "out-resumed", rel))
        b = np.load(os.path.join(str(tmp_path), "out-clean", rel))
        assert sorted(a.files) == sorted(b.files)
        for k in a.files:
            np.testing.assert_array_equal(a[k], b[k])
