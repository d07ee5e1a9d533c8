"""The compile cache is placed from outside, by one rule
(photon_ml_tpu/utils/compile_cache.py): ``JAX_COMPILATION_CACHE_DIR`` when
set, ``<checkout>/.jax_cache`` otherwise, the same thresholds either way.

The placement cases run in child interpreters: JAX reads the variable at
import and builds its cache object once, so a process that already
compiled something cannot show where a fresh one would put its files.
"""

import json
import logging
import os
import subprocess
import sys

from photon_ml_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = """
import json, os, sys
from photon_ml_tpu.utils.compile_cache import enable_compilation_cache
returned = enable_compilation_cache()
import jax, jax.numpy as jnp
def cache_placement_probe(x):
    return x * 3 + 1
jax.jit(cache_placement_probe)(jnp.arange(4.0)).block_until_ready()
print(json.dumps({
    "returned": returned,
    "configured": jax.config.jax_compilation_cache_dir,
    "min_secs": jax.config.jax_persistent_cache_min_compile_time_secs,
    "min_bytes": jax.config.jax_persistent_cache_min_entry_size_bytes,
}))
"""


def _run_child(cache_dir):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    if cache_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    env["PYTHONPATH"] = REPO
    proc = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                          capture_output=True, text=True, timeout=120,
                          cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_variable_set_places_the_cache_there_and_nowhere_else(tmp_path):
    placed = str(tmp_path / "placed")
    default = os.path.join(REPO, ".jax_cache")

    def probes(directory):
        # The child's one program, by its name: other processes may be
        # filling the default directory while this test runs.
        return [f for f in os.listdir(directory)
                if "cache_placement_probe" in f]

    stale = probes(default) if os.path.isdir(default) else []
    for f in stale:
        os.unlink(os.path.join(default, f))
    out = _run_child(placed)
    assert out["returned"] == placed and out["configured"] == placed
    assert probes(placed), "the child compiled but cached nothing there"
    assert not (os.path.isdir(default) and probes(default)), (
        "artifacts leaked into the default directory")
    # Same thresholds as the default placement below (cache everything).
    assert out["min_secs"] == 0.0 and out["min_bytes"] == 0


def test_variable_unset_uses_the_checkout_directory():
    out = _run_child(None)
    assert out["returned"] == out["configured"] == os.path.join(
        REPO, ".jax_cache")
    assert out["min_secs"] == 0.0 and out["min_bytes"] == 0


def test_uncreatable_directory_warns(tmp_path, monkeypatch, caplog):
    blocker = tmp_path / "a-file"
    blocker.write_text("")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(blocker / "cache"))
    with caplog.at_level(logging.WARNING, logger="photon_ml_tpu.utils"):
        compile_cache.enable_compilation_cache()
    assert any("cannot be created" in r.getMessage()
               for r in caplog.records)


def test_private_cache_knobs_are_gone():
    gone = ("PHOTON_TPU_" + "COMPILE_CACHE_DIR",
            "PHOTON_TPU_" + "NO_COMPILE_CACHE")
    hits = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".")]
        for name in files:
            if name.endswith((".py", ".sh", ".toml", ".yml")):
                path = os.path.join(root, name)
                with open(path, encoding="utf-8") as f:
                    text = f.read()
                hits += [(path, g) for g in gone if g in text]
    assert hits == []
