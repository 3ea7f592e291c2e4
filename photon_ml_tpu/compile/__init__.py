"""Compile-once execution layer.

Three pillars for keeping the steady-state solve loop free of compilation
overhead (ISSUE 3; the Snap ML / DrJAX compile-amortization idea):

  * **Shape canonicalization** (:mod:`.canonical`): a geometric ladder of
    canonical shapes so N near-identical blocks/buckets/chunks hit ~log(N)
    compiled executables, with masked padding the kernels treat as exact
    no-ops.
  * **Compile telemetry** (:mod:`.stats`): per-site trace/call counters
    (:func:`instrumented_jit`) plus XLA persistent-cache hit/miss counts
    and backend-compile seconds via ``jax.monitoring``.
  * **Persistent compilation cache**: always on, through
    :func:`photon_ml_tpu.compat.enable_persistent_cache` (one directory
    rule for every entry point) — warm driver runs skip XLA compilation
    entirely and report it through the same telemetry.

Buffer donation rides the same layer: :func:`donation_enabled` gates the
``donate_argnums`` annotations on the coordinate-descent update/cycle
functions and the streaming accumulators (``PHOTON_DONATE=0`` opts out for
debugging use-after-donate reports).
"""

from __future__ import annotations

from photon_ml_tpu.compile.canonical import (
    ShapeBucketer,
    canonicalize_re_arrays,
    canonicalize_re_dataset,
    pad_axis,
    pad_glm_chunk,
    resolve_bucketer,
)
from photon_ml_tpu.compile.cost import CostModel, WorkloadProfile
from photon_ml_tpu.compile.overrides import (
    DONATE_ENV as _DONATE_ENV,  # legacy alias, kept for importers
    Overrides,
    donation_enabled,
    resolve_overrides,
)
from photon_ml_tpu.compile.plan import ExecutionPlan, PlanDecision, PlanError
from photon_ml_tpu.compile.stats import (
    CompileStats,
    CompileWatermark,
    compile_stats,
    instrumented_jit,
)

__all__ = [
    "CompileStats",
    "CompileWatermark",
    "CostModel",
    "ExecutionPlan",
    "Overrides",
    "PlanDecision",
    "PlanError",
    "ShapeBucketer",
    "WorkloadProfile",
    "canonicalize_re_arrays",
    "canonicalize_re_dataset",
    "compile_stats",
    "donation_enabled",
    "instrumented_jit",
    "pad_axis",
    "pad_glm_chunk",
    "resolve_bucketer",
    "resolve_overrides",
]
