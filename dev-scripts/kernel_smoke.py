#!/usr/bin/env python
"""Kernel-registry smoke (run_tier1.sh): every registered Pallas program
runs through the interpreter on CPU and matches its XLA reference; a
kernel switched on where nothing can run it is refused; an injected
launch fault degrades loudly; warm resolves never rebuild. Seconds on CPU
(docs/KERNELS.md).

Asserts, through the REAL registry surfaces:

1. with ``force_interpret()`` every kernel resolves backend=pallas and
   its output matches the registered XLA closure (bit-equal for the row
   movers, accumulation-order band for the f32 reductions);
2. with interpret mode OFF (and no TPU), resolving an enabled kernel
   RAISES; with an injected ``kernel.launch`` fault it degrades to the
   XLA closure LOUDLY — one KernelFallback event per kernel and
   ``photon_kernel_fallbacks_total`` moving;
3. warm resolves are hits, not misses: after the parity loop, resolving
   every kernel again moves only ``photon_compile_cache_hits_total`` —
   a hot streamed loop can resolve per chunk without rebuilding;
4. the trace carries one ``kernel.resolve`` instant per fresh
   (kernel, dtype, backend) and ``photon-obs summarize --kernels``
   renders it.
"""

import json
import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    import jax.numpy as jnp

    from photon_ml_tpu import obs
    from photon_ml_tpu.cli.obs import main as obs_main
    from photon_ml_tpu.ops import kernels
    from photon_ml_tpu.ops.kernels import (ell_scatter, re_rows,
                                           serving_score, stream_fused)
    from photon_ml_tpu.utils import events as ev

    obs.enable(trace=True)
    _, m = obs.enable(trace=False)
    reg = kernels.registry()
    reg.reset()
    rng = np.random.default_rng(17)

    # One fixture per kernel: (args for the pallas/xla pair, exact?).
    idx = jnp.asarray(rng.integers(0, 96, (128, 6)).astype(np.int32))
    rv = jnp.asarray(rng.normal(size=(128, 6)).astype(np.float32))
    mat = jnp.asarray(rng.normal(size=(16, 24)).astype(np.float32))
    slots = jnp.asarray(rng.integers(0, 8, 16).astype(np.int32))
    cache = jnp.asarray(rng.integers(-127, 128, (8, 24)).astype(np.int8))
    scl = jnp.asarray(rng.uniform(0.01, 2.0, 8).astype(np.float32))
    X = jnp.asarray(rng.integers(-127, 128, (96, 32)).astype(np.int8))
    w = jnp.asarray(rng.normal(size=32).astype(np.float32))
    base = jnp.asarray(rng.normal(size=96).astype(np.float32))
    resid = jnp.asarray(rng.normal(size=96).astype(np.float32))
    W = jnp.asarray(rng.normal(size=(20, 24)).astype(np.float32))
    rows_np = rng.permutation(20)[:8].astype(np.int32)
    rows_np[2] = -1
    rows = jnp.asarray(rows_np)
    vals = jnp.asarray(rng.normal(size=(8, 24)).astype(np.float32))

    fixtures = {
        "ell_scatter": ((idx, rv, 96), False),
        "serving_score": ((mat, slots, cache, scl), False),
        "stream_margins": ((X, w, base), False),
        "stream_rmatvec": ((X, resid), False),
        "re_gather_rows": ((W, rows), True),
        "re_scatter_rows": ((W, rows, vals), True),
    }
    assert sorted(fixtures) == reg.names(), \
        f"smoke fixtures out of sync with the registry: " \
        f"{sorted(fixtures)} vs {reg.names()}"

    # 1. interpret-mode parity for every kernel.
    fallbacks = []
    listener = fallbacks.append
    ev.default_emitter.register(listener)
    for name in reg.names():
        reg.set_enabled(name, True)
    reg.force_interpret()
    for name, (args, exact) in fixtures.items():
        spec = reg.get(name)
        resolved = reg.resolve(name)
        assert resolved.backend == "pallas" and resolved.interpret, \
            f"{name}: expected interpret-mode pallas, got {resolved}"
        got = np.asarray(resolved(*args), np.float64)
        want = np.asarray(spec.xla_fn(*args), np.float64)
        if exact:
            assert np.array_equal(got, want), \
                f"{name}: fused != reference (bit contract)"
        else:
            scale = max(float(np.max(np.abs(want))), 1.0)
            delta = float(np.max(np.abs(got - want)))
            assert delta <= 1e-5 * scale, \
                f"{name}: parity delta {delta} at scale {scale}"
    kf = [e for e in fallbacks if type(e).__name__ == "KernelFallback"]
    assert not kf, f"interpret-mode parity loop degraded: {kf}"

    # 2. interpret off on a TPU-less box: refused. An injected launch
    # fault (interpret back on): loud fallback per kernel.
    from photon_ml_tpu import faults
    from photon_ml_tpu.faults import sites

    reg.force_interpret(False)
    for name in fixtures:
        try:
            reg.resolve(name)
        except RuntimeError as e:
            assert "no TPU backend" in str(e), e
        else:
            raise AssertionError(f"{name}: resolved without a backend")
    reg.force_interpret()
    plan = faults.FaultPlan(specs=(
        faults.FaultSpec(site=sites.KERNEL_LAUNCH, kind="raise"),))
    with faults.installed(plan):
        for name in fixtures:
            resolved = reg.resolve(name)
            assert resolved.backend == "xla", \
                f"{name}: expected XLA fallback, got {resolved}"
    kf = [e for e in fallbacks if type(e).__name__ == "KernelFallback"]
    assert len(kf) == len(fixtures), \
        f"expected {len(fixtures)} loud fallbacks, saw {len(kf)}"
    ev.default_emitter.unregister(listener)
    parsed = obs.parse_prometheus_text(m.render_text())
    fb_total = obs.metric_value(parsed, "photon_kernel_fallbacks_total",
                                default=0.0)
    assert fb_total >= len(fixtures), \
        f"photon_kernel_fallbacks_total {fb_total} < {len(fixtures)}"

    # 3. warm resolves: hits only, zero rebuilds.
    reg.force_interpret()
    before = obs.parse_prometheus_text(m.render_text())
    for name in fixtures:
        reg.resolve(name)
    after = obs.parse_prometheus_text(m.render_text())
    miss_moved = [k for k in after if 'cache="kernel_' in k
                  and k.startswith("photon_compile_cache_misses_total")
                  and after[k] != before.get(k, 0.0)]
    assert miss_moved == [], \
        f"warm resolves rebuilt programs: {miss_moved}"

    # 4. the trace renders through photon-obs summarize --kernels.
    trace_path = os.path.join(tempfile.mkdtemp(prefix="kernel-smoke-"),
                              "trace.json")
    obs.dump_trace(trace_path)
    rc = obs_main(["summarize", trace_path, "--kernels"])
    assert rc == 0, f"photon-obs summarize --kernels exited {rc}"
    with open(trace_path) as f:
        trace = json.load(f)
    resolves = [e for e in trace["traceEvents"]
                if e.get("ph") == "i" and e["name"] == "kernel.resolve"]
    seen = {(e["args"]["kernel"], e["args"]["dtype"],
             e["args"]["backend"]) for e in resolves}
    assert len(seen) == len(resolves), \
        "duplicate kernel.resolve instants — hot resolves are flooding " \
        "the timeline"
    assert {k for k, _, _ in seen} == set(fixtures), \
        f"kernel.resolve coverage gap: {seen}"

    print(f"kernel smoke ok: {len(fixtures)} kernels parity-checked in "
          f"interpret mode, {len(fixtures)} loud XLA fallbacks with the "
          f"interpreter off, warm resolves hit-only, "
          f"{len(resolves)} resolve instant(s) rendered")
    return 0


if __name__ == "__main__":
    sys.exit(main())
