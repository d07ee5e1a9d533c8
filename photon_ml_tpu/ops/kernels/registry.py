"""The fused-kernel registry: every Pallas program, behind one seam.

ROADMAP item 4's pattern, made structural: a hand-written kernel only
pays where the sweep says it does, so every fused program in this
package registers here with

* a **per-kernel flag** — default OFF unless a committed ``bench_kernels``
  sweep (docs/KERNELS.md "The sweep workflow") showed the fused program
  winning on the deployment box; overridable per-process
  (:meth:`KernelRegistry.set_enabled`) and per-environment
  (``PHOTON_KERNEL_<NAME>=0|1``);
* an **XLA fallback closure** — the exact math the call site would run
  unfused, so parity tests, the CPU smoke, and the degradation ladder
  all have a reference implementation with the registry's signature;
* an **interpret-mode path** — ``force_interpret()`` runs the Pallas
  program through the interpreter on CPU, which is how tier-1 keeps the
  whole registry exercised without a TPU (never timed: bench stamps
  interpret results invalid);
* **compile-cache counters tagged by backend** — resolving a kernel
  counts into ``photon_compile_cache_misses_total{cache="kernel_<name>",
  dtype=..., backend="pallas"|"xla"}`` on the first resolve per key and
  the hit counter after, so `photon-obs summarize --kernels` can split
  program builds by backend;
* a **loud failure ladder** — the fault site ``kernel.launch`` fires at
  the moment the registry commits to the Pallas backend; a fault there
  degrades to the XLA closure and emits
  :class:`~photon_ml_tpu.utils.events.KernelFallback` +
  ``photon_kernel_fallbacks_total`` — the ingest native-fallback
  discipline, applied to kernels. A kernel switched on where nothing
  can run it (no TPU, interpret mode not forced) is an error, not a
  degradation: the run would otherwise finish on another program than
  the one it was told to use.

Resolution happens at program-BUILD time (service init, streamed-kernel
cache fill, bucket-program build), never per launch: the resolved
callable is jit-traceable and the backend choice is baked into the
compiled program, which is what keeps the one-program-per-stream
invariant intact (flag flips require a rebuild, and the per-site kernel
caches key on the resolved backend).

PML017 (docs/ANALYSIS.md) enforces the seam: a direct ``pl.pallas_call``
anywhere outside ``ops/kernels/`` is a lint finding.
"""

from __future__ import annotations

import dataclasses
import os
import threading
from typing import Callable, Optional

import jax

from photon_ml_tpu import obs
from photon_ml_tpu.faults import injector as faults
from photon_ml_tpu.faults import sites
from photon_ml_tpu.utils.events import KernelFallback, default_emitter


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """One registered kernel: the Pallas program, its XLA reference, and
    the flag default the committed sweep justified."""

    name: str
    pallas_fn: Callable  # (*args, interpret=bool) -> Array
    xla_fn: Callable  # (*args) -> Array, same signature minus interpret
    doc: str
    default_on: bool = False


@dataclasses.dataclass(frozen=True)
class ResolvedKernel:
    """The outcome of one registry resolution: a jit-traceable callable
    plus the backend it landed on. ``interpret`` marks the CPU
    interpreter path (parity-grade, never timing-grade)."""

    name: str
    fn: Callable
    backend: str  # "pallas" | "xla"
    interpret: bool = False

    def __call__(self, *args, **kw):
        return self.fn(*args, **kw)


class KernelRegistry:
    """Name → :class:`KernelSpec`, with per-kernel flag state.

    Thread-safety: registration happens at import time; flag overrides
    and resolves can race with serving threads, so mutation holds the
    lock (the counters have their own locks)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._specs: dict[str, KernelSpec] = {}
        self._overrides: dict[str, Optional[bool]] = {}
        self._force_interpret = False
        self._resolved_keys: set[tuple] = set()

    # -- registration ------------------------------------------------------

    def register(self, spec: KernelSpec) -> KernelSpec:
        with self._lock:
            if spec.name in self._specs:
                raise ValueError(f"kernel {spec.name!r} already registered")
            self._specs[spec.name] = spec
        return spec

    def names(self) -> list[str]:
        with self._lock:
            return sorted(self._specs)

    def get(self, name: str) -> KernelSpec:
        with self._lock:
            spec = self._specs.get(name)
        if spec is None:
            raise KeyError(f"unknown kernel {name!r} (registered: "
                           f"{self.names()})")
        return spec

    # -- flags -------------------------------------------------------------

    def set_enabled(self, name: str, value: Optional[bool]) -> None:
        """Override one kernel's flag (None restores the default). Takes
        effect at the next program BUILD — already-compiled programs keep
        the backend they resolved."""
        self.get(name)  # raise on unknown names, not silently no-op
        with self._lock:
            self._overrides[name] = value

    def enabled(self, name: str) -> bool:
        """Override > environment (``PHOTON_KERNEL_<NAME>``) > the
        registered sweep default. The default records a sweep on the
        TPU and holds there only: elsewhere a default-on kernel is off
        by policy (the silent XLA rung), so only an explicit override
        or variable can ask for a program the backend cannot run."""
        spec = self.get(name)
        with self._lock:
            ov = self._overrides.get(name)
        if ov is not None:
            return ov
        env = os.environ.get(f"PHOTON_KERNEL_{name.upper()}")
        if env is not None:
            return env not in ("0", "false", "off", "")
        return spec.default_on and jax.default_backend() == "tpu"

    def force_interpret(self, value: bool = True) -> None:
        """Run Pallas programs through the interpreter on non-TPU
        backends instead of refusing — the tier-1 CPU smoke/test
        mode. Parity-grade only, never timed."""
        with self._lock:
            self._force_interpret = value

    @property
    def interpret_forced(self) -> bool:
        return self._force_interpret

    def reset(self) -> None:
        """Clear overrides + interpret mode + counter first-seen state
        (tests)."""
        with self._lock:
            self._overrides.clear()
            self._force_interpret = False
            self._resolved_keys.clear()

    # -- resolution --------------------------------------------------------

    def resolve(self, name: str, dtype: str = "float32") -> ResolvedKernel:
        """Commit to a backend for ``name`` and hand back the program.

        The decision ladder, in order: flag off → XLA (policy, silent);
        injected ``kernel.launch`` fault → XLA (loud KernelFallback);
        TPU backend → Pallas; interpret forced → Pallas interpreter;
        anything else raises — a flag asked for a fused program this
        box cannot run."""
        spec = self.get(name)
        if not self.enabled(name):
            return self._done(spec, dtype, spec.xla_fn, "xla")
        try:
            faults.fire(sites.KERNEL_LAUNCH)
        except Exception as e:  # injected: degrade, never crash the site
            return self._fallback(spec, dtype,
                                  f"injected fault at kernel.launch "
                                  f"({type(e).__name__}: {e})")
        if jax.default_backend() == "tpu":
            return self._done(spec, dtype, spec.pallas_fn, "pallas")
        if self._force_interpret:
            def interp(*args, _fn=spec.pallas_fn, **kw):
                return _fn(*args, interpret=True, **kw)
            return self._done(spec, dtype, interp, "pallas",
                              interpret=True)
        raise RuntimeError(
            f"kernel {name!r} is switched on but cannot run here: no TPU "
            f"backend (backend={jax.default_backend()}) and interpret "
            f"mode is not forced")

    # -- internals ---------------------------------------------------------

    def _fallback(self, spec: KernelSpec, dtype: str,
                  reason: str) -> ResolvedKernel:
        default_emitter.emit(KernelFallback(
            kernel=spec.name, backend="xla", reason=reason))
        return self._done(spec, dtype, spec.xla_fn, "xla")

    def _done(self, spec: KernelSpec, dtype: str, fn: Callable,
              backend: str, interpret: bool = False) -> ResolvedKernel:
        self._count(spec.name, dtype, backend)
        return ResolvedKernel(name=spec.name, fn=fn, backend=backend,
                              interpret=interpret)

    def _count(self, name: str, dtype: str, backend: str) -> None:
        """First resolve per (kernel, dtype, backend) is a program BUILD
        (the caller compiles a fresh jit program around it); later
        resolves are hits — the same miss/hit ledger the streamed kernel
        caches keep, tagged with the backend the program landed on.
        Fresh resolves also drop a ``kernel.resolve`` timeline instant
        (the raw material of ``photon-obs summarize --kernels``); hit
        resolves stay instant-free — a per-chunk resolve in a streamed
        hot loop must not flood the trace."""
        key = (name, dtype, backend)
        with self._lock:
            fresh = key not in self._resolved_keys
            if fresh:
                self._resolved_keys.add(key)
        if fresh:
            obs.instant("kernel.resolve", cat="kernel", kernel=name,
                        backend=backend, dtype=dtype,
                        interpret=self._force_interpret)
        mx = obs.metrics()
        if mx is None:
            return
        counter = ("photon_compile_cache_misses_total" if fresh
                   else "photon_compile_cache_hits_total")
        mx.counter(counter, cache=f"kernel_{name}", dtype=dtype,
                   backend=backend).inc()


_REGISTRY = KernelRegistry()


def registry() -> KernelRegistry:
    """The process-wide registry (kernels register at import of
    ``photon_ml_tpu.ops.kernels``)."""
    return _REGISTRY
