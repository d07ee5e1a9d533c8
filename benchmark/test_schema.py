"""The harness is open to a second data schema, and the first reads what it
read, on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/test_schema.py -q

- a deployment whose data are not dense matrices lands as new files only: the
  fixture under ``benchmark/fixtures/new_schema/`` (a sparse hashed-field
  fixed effect beside one dense random-effect table: configuration, workload,
  traffic mix, schema with its generator, reference, work counts and fault,
  and the entries for ``BENCHMARK.json``) is laid into a copy of the
  benchmark, edits no file of it, and runs through ``run.py``;
- ``run.py`` itself knows no schema;
- the dense schema's rehearsal reads what the parent of PR 28 read
  (``benchmark/selfcheck/rehearsal.expected.json``).
"""

from __future__ import annotations

import ast
import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
FIXTURE = os.path.join(HERE, "fixtures", "new_schema")
CELL = "tiny-sparse.steady"

sys.path.insert(0, HERE)
import faults  # noqa: E402


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A copy of what the benchmark is made of, with the fixture's files
    added and its entries appended; no file that was there is written to."""
    root = str(tmp_path_factory.mktemp("checkout"))
    shutil.copytree(HERE, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "fixtures"))
    added = []
    for sub in ("configs", "workloads", "traffic", "schemas"):
        for name in os.listdir(os.path.join(FIXTURE, sub)):
            if name == "__pycache__":
                continue
            target = os.path.join(root, "benchmark", sub, name)
            assert not os.path.exists(target), f"{target} is not a new file"
            shutil.copy(os.path.join(FIXTURE, sub, name), target)
            added.append(target)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(FIXTURE, "entries.json")) as f:
        for key, entries in json.load(f).items():
            bench[key] += entries
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    assert len(added) == 4
    return root


def process(checkout, script, *args):
    """One process in the checkout, the program found through PYTHONPATH."""
    return subprocess.run(
        [sys.executable, os.path.join("benchmark", script), *args],
        cwd=checkout, env=dict(os.environ, JAX_PLATFORMS="cpu",
                               PYTHONPATH=ROOT),
        capture_output=True, text=True, timeout=600)


def run_there(checkout, script, *args):
    """A rehearsal of the fixture's cell: its exit code and result line."""
    p = process(checkout, script, *args, "--rehearsal", "--workload", CELL,
                "--seed", "2147483659", "--seconds", "1", "--trace", "0")
    lines = p.stdout.strip().splitlines()
    assert lines, p.stderr[-4000:]
    return p.returncode, json.loads(lines[-1])


def test_a_new_schema_lands_as_new_files(checkout):
    rc, out = run_there(checkout, "run.py")
    assert rc == 0
    assert out["correct"] is True, out["compared"]
    assert out["attempted"] == 3 and out["failed"] == 0
    assert set(out["metrics"]) == {"sweep_s", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert set(out["compared"]) == {"loss_1", "loss_2", "loss_3", "loss_4",
                                    "loss_5", "coef.fixed", "coef.per-user"}
    assert out["window"]["asked_in_window"] == 0


def test_the_new_schema_s_fault_is_not_correct(checkout):
    rc, out = run_there(checkout, "faults.py", "half-batch")
    assert rc == 0
    assert out["correct"] is False, out["compared"]
    assert out["compared"]["loss_1"]["value"] > 0.3


def test_the_selfcheck_holds_every_schema_to_the_contract(checkout):
    p = process(checkout, "run.py", "--selfcheck")
    assert p.returncode == 0, p.stderr[-4000:]
    assert "tiny-sparse: game_sparse_tiny ok" in p.stderr
    assert "glmix-ml20m-logistic: game_dense ok" in p.stderr
    # a schema that lacks a part of the contract is refused by name
    path = os.path.join(checkout, "benchmark", "schemas",
                        "game_sparse_tiny.py")
    with open(path) as f:
        whole = f.read()
    try:
        with open(path, "w") as f:
            f.write(whole.replace("def bytes_needed(", "def _bytes_needed("))
        p = process(checkout, "run.py", "--selfcheck")
    finally:
        with open(path, "w") as f:
            f.write(whole)
    assert p.returncode != 0 and "lacks bytes_needed" in p.stderr
    assert not p.stdout.strip()


def test_a_missing_schema_is_a_plain_message():
    run = faults.load_run()
    with pytest.raises(SystemExit) as e:
        run.load_schema("no_such_schema")
    assert "no_such_schema" in str(e.value) and "there is no" in str(e.value)
    with pytest.raises(SystemExit):
        run.load_schema(None)  # a configuration file without "schema"


def test_run_py_knows_no_schema():
    """``run.py`` imports no module of a schema and names no task, dataset
    or coordinate class of the program."""
    with open(os.path.join(HERE, "run.py")) as f:
        source = f.read()
    imported = set()
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, ast.Import):
            imported |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add(node.module)
    assert not imported & {"gen", "reference", "work", "faults"}, imported
    assert not any(m.startswith(("photon_ml_tpu.api", "photon_ml_tpu.data",
                                 "photon_ml_tpu.optim", "photon_ml_tpu.game"))
                   for m in imported), imported
    for word in ("LOGISTIC_REGRESSION", "LINEAR_REGRESSION", "logistic",
                 "linear", "DataConfiguration", "CoordinateConfiguration",
                 "EffectCoordinate", "GameDataset", "GameEstimator",
                 "SparseShard", "entities", "activity"):
        assert word not in source, word
    run = faults.load_run()
    for gone in ("build_estimator", "to_dataset", "model_arrays"):
        assert not hasattr(run, gone), gone


def test_the_dense_schema_reads_what_it_read(capsys):
    with open(os.path.join(HERE, "selfcheck", "rehearsal.expected.json")) as f:
        want = json.load(f)
    rc = faults.load_run().main(want["argv"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is want["correct"]
    assert (out["attempted"], out["failed"]) == (want["attempted"],
                                                 want["failed"])
    assert out["window"]["sweeps"] == want["window_sweeps"]
    assert out["window"]["asked_in_window"] == want["asked_in_window"]
    assert sorted(out["metrics"]) == want["metrics"]
    assert out["compared"].keys() == want["compared"].keys()
    for name, v in want["compared"].items():
        assert out["compared"][name]["limit"] == v["limit"], name
        assert out["compared"][name]["value"] == pytest.approx(
            v["value"], rel=1e-6), name
