"""``program.load`` rows: what each program cost on its way to the device,
each step an interval on the thread that took it.

JAX reports every step of a program's way to the device through
``jax.monitoring``: a scalar event as the step starts (its wall-clock start,
``fun_name``) and a time-span event as it ends (start, end, ``fun_name``).
:class:`ProgramLoads` turns that stream into the ``phase`` rows
(``name: "program.load"``) of the run ledger, one per step per program,
each written at the step's own end:

* ``trace`` — the program's own trace, keyed by the traced function
  (``fit_bucket``). JAX also reports the trace of every jitted function
  called inside it (each ``jnp`` operation is one): such a trace opens and
  closes inside the outer one on the same thread, is part of its seconds
  and gets no row. A thread's open traces are counted from the start
  events.
* ``lower`` — jaxpr to MLIR module (``jit(fit_bucket)``).
* ``compile`` — the backend step; with the persistent cache on it holds the
  ``cache_fetch`` of the same program, or the real compile after a miss.
* ``cache_fetch`` — the read from the persistent cache, on a hit. JAX
  reports only its duration, at its end, inside its ``compile``: the row
  takes the program of the compile open on its thread.

Each row carries ``ago``, the seconds from the step's start to the
listener's call; ``obs.record_program_loads``, which registers the
listeners, turns it into the row's ``t0`` on the ledger's clock.
Import cost: stdlib.
"""

from __future__ import annotations

import threading
import time

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_COMPILE = "/jax/core/compile/backend_compile_duration"
_FETCH = "/jax/compilation_cache/cache_retrieval_time_sec"
PROGRAM_LOAD_EVENTS = {
    _TRACE: "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    _COMPILE: "compile",
}


class ProgramLoads:
    """Filter from ``jax.monitoring``'s start, time-span and duration
    events to ``program.load`` row fields. One instance per process; the
    nesting it keeps is per thread (a program is traced, lowered and
    compiled on one thread)."""

    def __init__(self):
        self._local = threading.local()

    def _thread(self) -> dict:
        st = self._local.__dict__
        if "traces" not in st:
            st["traces"] = 0  # traces open on this thread
            st["compiles"] = []  # programs compiling on this thread
        return st

    def started(self, event: str, **kw) -> None:
        """A step began (``register_scalar_listener``)."""
        if event == _TRACE:
            self._thread()["traces"] += 1
        elif event == _COMPILE:
            self._thread()["compiles"].append(kw.get("fun_name"))

    def ended(self, event: str, start: float, end: float, **kw
              ) -> list[dict]:
        """The row (a list of 0 or 1 dicts of ``event``, ``program``,
        ``seconds``, ``ago``) a step completes
        (``register_event_time_span_listener``)."""
        step = PROGRAM_LOAD_EVENTS.get(event)
        if step is None:
            return []
        st = self._thread()
        if step == "trace":
            depth = st["traces"]
            st["traces"] = max(depth - 1, 0)
            if depth > 1:  # inside another trace on this thread
                return []
        elif step == "compile" and st["compiles"]:
            st["compiles"].pop()
        # JAX stamps the step with time.time(): how long ago it started
        # can only be read on that clock
        ago = time.time() - float(start)  # pml: allow[PML004] JAX's own stamps are wall-clock; seconds, not a deadline
        return [{"event": step, "program": kw.get("fun_name"),
                 "seconds": round(float(end - start), 6), "ago": ago}]

    def fetched(self, event: str, duration_secs: float) -> list[dict]:
        """The ``cache_fetch`` row of a persistent-cache hit
        (``register_event_duration_secs_listener``: JAX times that step
        alone)."""
        if event != _FETCH:
            return []
        compiles = self._thread()["compiles"]
        return [{"event": "cache_fetch",
                 "program": compiles[-1] if compiles else None,
                 "seconds": round(float(duration_secs), 6),
                 "ago": float(duration_secs)}]
