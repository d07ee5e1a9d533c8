"""``program.load`` rows: what each program cost on its way to the device.

JAX reports, through ``jax.monitoring``'s duration events, every function it
traces, every module it lowers, every backend compile and every fetch from
the persistent compilation cache. :class:`ProgramLoads` turns that stream
into the ``phase`` rows (``name: "program.load"``) of the run ledger, one
per step per program:

* ``trace`` — the program's own trace. JAX also reports the trace of every
  jitted function called inside it (each ``jnp`` operation is one): those
  are part of the outer trace's seconds and get no row. The program's trace
  is the last one its thread reported before the program was lowered.
* ``lower`` — jaxpr to MLIR module.
* ``compile`` — the backend step; with the persistent cache on it holds the
  ``cache_fetch`` of the same program, or the real compile after a miss.
* ``cache_fetch`` — the read from the persistent cache, on a hit.

``obs.record_program_loads`` registers the one listener that writes them.
Import cost: stdlib.
"""

from __future__ import annotations

import threading

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
PROGRAM_LOAD_EVENTS = {
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_fetch",
}


class ProgramLoads:
    """Filter from ``jax.monitoring`` duration events to ``program.load``
    row fields. One instance per listener; safe across threads (a program
    is traced and lowered on one thread)."""

    def __init__(self):
        self._last_trace = threading.local()

    def rows(self, event: str, duration_secs: float, **kw) -> list[dict]:
        """The rows (0, 1 or 2 dicts of ``event``, ``program``,
        ``seconds``) this event completes."""
        if event == _TRACE:
            self._last_trace.value = (kw.get("fun_name"), duration_secs)
            return []
        step = PROGRAM_LOAD_EVENTS.get(event)
        if step is None:
            return []
        out = []
        if step == "lower":
            traced = getattr(self._last_trace, "value", None)
            self._last_trace.value = None
            # ``fit_bucket`` is lowered as ``jit(fit_bucket)``
            if traced is not None and str(traced[0]) in str(
                    kw.get("fun_name")):
                out.append({"event": "trace", "program": traced[0],
                            "seconds": round(float(traced[1]), 6)})
        out.append({"event": step, "program": kw.get("fun_name"),
                    "seconds": round(float(duration_secs), 6)})
        return out
