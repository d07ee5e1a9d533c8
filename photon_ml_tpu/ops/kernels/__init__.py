"""Fused Pallas kernels on the measured hot paths (docs/KERNELS.md).

The registry (:mod:`.registry`) is the only way production code reaches
a Pallas program — PML017 flags a raw ``pl.pallas_call`` anywhere else
in the package — and importing THIS package is what populates it: each
kernel module pairs a Pallas program with its XLA reference closure, and
the specs below bind them under a flag.

All six compile on the TPU v5e and meet their parity bands there
(chip_smoke.py's kernel leg). Which of them is FASTER than its XLA
closure is not measured on the current chip: ``ell_scatter`` keeps the
default-on it has always had on the TPU backend, the other five stay
off, and a sweep on the chip decides each (ROADMAP Design 3).
"""

from __future__ import annotations

from photon_ml_tpu.ops.kernels import (ell_scatter, re_rows, serving_score,
                                       stream_fused)
from photon_ml_tpu.ops.kernels.registry import (KernelSpec, ResolvedKernel,
                                                registry)

registry().register(KernelSpec(
    name="ell_scatter",
    pallas_fn=ell_scatter.scatter_rowterm_pallas,
    xla_fn=ell_scatter.scatter_rowterm_xla,
    doc="ELL scatter-add as one-hot compare+accumulate tiles "
        "(gradient of the sparse GLM pass)",
    default_on=True,  # on the TPU backend; not measured on the current chip
))

registry().register(KernelSpec(
    name="serving_score",
    pallas_fn=serving_score.score_rows_pallas,
    xla_fn=serving_score.score_rows_xla,
    doc="serving gather->int8-dequant->row-dot->scale as one program "
        "(int8 cache rows never materialize as f32 in HBM)",
))

registry().register(KernelSpec(
    name="stream_margins",
    pallas_fn=stream_fused.hot_margins_pallas,
    xla_fn=stream_fused.hot_margins_xla,
    doc="streamed hot-dense margins with int8 dequant fused into the "
        "matvec tiles (no (n,H) f32 HBM copy)",
))

registry().register(KernelSpec(
    name="stream_rmatvec",
    pallas_fn=stream_fused.hot_rmatvec_pallas,
    xla_fn=stream_fused.hot_rmatvec_xla,
    doc="streamed hot-dense gradient rmatvec with fused dequant "
        "(the gradient half of the chunk pass)",
))

registry().register(KernelSpec(
    name="re_gather_rows",
    pallas_fn=re_rows.gather_rows_pallas,
    xla_fn=re_rows.gather_rows_xla,
    doc="RE bucket warm-start row gather via scalar-prefetch block "
        "addressing (bit-exact data movement)",
))

registry().register(KernelSpec(
    name="re_scatter_rows",
    pallas_fn=re_rows.scatter_rows_pallas,
    xla_fn=re_rows.scatter_rows_xla,
    doc="RE bucket fitted-row scatter, table aliased in place "
        "(bit-exact data movement)",
))

__all__ = ["KernelSpec", "ResolvedKernel", "registry"]
