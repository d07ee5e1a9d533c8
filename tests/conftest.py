"""Test harness: force an 8-device virtual CPU mesh.

This is the rebuild's analogue of the reference's local-mode Spark fixture
(photon-test-utils ``SparkTestUtils.sparkTest``): "distributed" behavior is
exercised without hardware by running real sharding/collective code paths on
8 virtual CPU devices (SURVEY.md §4).

Nothing imports jax before this file does, so the platform, the virtual
device count and the compile-cache location are plain environment
settings made here — before the first ``import jax`` below — and every
subprocess a test spawns inherits them.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8").strip()
# CPU test artifacts get their own fixed directory (prunable apart from
# the chip's .jax_cache) unless the caller already placed the cache.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), ".jax_cache_cpu"))

# XLA's CPU loader logs two ERROR lines for every persistent-cache hit —
# LLVM tuning pseudo-features (+prefer-no-scatter/-gather) that are not
# host ISA features, reported on the very host that compiled the entry.
# Tens of megabytes of them interleave with pytest's progress dots; real
# XLA failures still arrive as Python exceptions.
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")

import numpy as np
import pytest

from photon_ml_tpu.utils import lockdep
from photon_ml_tpu.utils.compile_cache import enable_compilation_cache

# Arm the runtime lockdep validator iff PHOTON_LOCKDEP=1 (run_tier1.sh's
# lockdep leg). Must happen before any package module constructs a lock,
# i.e. before test modules import serving/fleet code — conftest import
# time is the one place that is guaranteed.
lockdep.maybe_instrument()

# Persist compiled executables across test processes (the directory was
# placed above; this applies the package's cache-everything thresholds).
enable_compilation_cache()


@pytest.fixture
def rng():
    return np.random.default_rng(12345)
