"""Logging utilities and the profiler scope.

Reference parity: photon-lib ``util/PhotonLogger.scala`` (log4j logger whose
output is also persisted next to the job output). The reference's
``util/Timer.scala`` wall-clock scopes are ``obs.span`` / ``obs.phase``
here (docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import contextlib
import logging
import os
from typing import Optional


def setup_logging(
    level: int = logging.INFO,
    log_file: Optional[str] = None,
) -> logging.Logger:
    """Configure the framework logger; optionally tee to a file beside the
    job output (PhotonLogger behavior)."""
    logger = logging.getLogger("photon_ml_tpu")
    logger.setLevel(level)
    if not logger.handlers:
        h = logging.StreamHandler()
        h.setFormatter(logging.Formatter(
            "%(asctime)s %(levelname)s %(name)s: %(message)s"))
        logger.addHandler(h)
    if log_file:
        os.makedirs(os.path.dirname(log_file) or ".", exist_ok=True)
        fh = logging.FileHandler(log_file)
        fh.setFormatter(logging.Formatter(
            "%(asctime)s %(levelname)s %(name)s: %(message)s"))
        logger.addHandler(fh)
    return logger


@contextlib.contextmanager
def profile_trace(trace_dir: Optional[str]):
    """XLA/TPU profiler scope (SURVEY.md §5 tracing row): when ``trace_dir``
    is set, everything inside the scope is captured with ``jax.profiler``
    (HLO timelines, per-op device time, memory) viewable in
    TensorBoard/Perfetto; a no-op when None. This replaces the reference's
    Spark-UI stage timeline as the "where did the time go" tool."""
    if not trace_dir:
        yield
        return
    import jax

    os.makedirs(trace_dir, exist_ok=True)
    with jax.profiler.trace(trace_dir):
        yield

