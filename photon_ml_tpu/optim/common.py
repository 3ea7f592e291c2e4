"""Shared optimizer plumbing: configs, results, convergence bookkeeping.

Design notes (TPU-first):
  * Optimizers are pure jitted kernels built on ``lax.while_loop`` with
    fixed-shape carried state — no Python-side iteration, so the whole solve
    (all iterations, all line-search steps) is ONE XLA computation.
  * Every kernel is ``vmap``-safe: the GAME random-effect coordinate vmaps
    the same kernel over thousands of per-entity problems; converged lanes
    keep iterating harmlessly (masked no-op updates) until all lanes finish.
  * Convergence reasons and per-iteration (value, |grad|) history live in
    fixed-size arrays, mirroring the reference's OptimizationStatesTracker
    (ring buffer of states, OptimizationStatesTracker.scala:31-100).

Reference behavior spec: optimization/Optimizer.scala:29-263,
AbstractOptimizer.scala:26-132 (convergence criteria :47-61).
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax

from photon_ml_tpu.types import ConvergenceReason

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    """Static solve configuration (shapes the compiled kernel).

    Defaults mirror the reference: LBFGS max 80 iters / tol 1e-7 / 10
    corrections (LBFGS.scala:136-139); TRON max 15 / tol 1e-5 / 20 CG iters
    (TRON.scala:226-233).
    """

    max_iterations: int = 80
    tolerance: float = 1e-7
    # LBFGS
    num_corrections: int = 10
    max_line_search_steps: int = 25
    # TRON
    max_cg_iterations: int = 20
    max_improvement_failures: int = 5

    @staticmethod
    def lbfgs_default() -> "OptimizerConfig":
        return OptimizerConfig(max_iterations=80, tolerance=1e-7)

    @staticmethod
    def tron_default() -> "OptimizerConfig":
        return OptimizerConfig(max_iterations=15, tolerance=1e-5)


class OptResult(NamedTuple):
    """Result of one solve. All fields are arrays (vmap-stackable)."""

    coefficients: Array  # (D,)
    value: Array  # () final objective value (incl. L1 term for OWL-QN)
    grad_norm: Array  # () final (pseudo-)gradient norm
    iterations: Array  # () int32 — iterations actually performed
    reason: Array  # () int32 ConvergenceReason code
    value_history: Array  # (max_iter + 1,) — NaN beyond `iterations`
    grad_norm_history: Array  # (max_iter + 1,) — NaN beyond `iterations`
    # per-iteration coefficient snapshots (max_iter + 1, D) when the solve
    # was run with track_coefficients (the ModelTracker analogue,
    # supervised/model/ModelTracker.scala); None otherwise
    coefficient_history: Optional[Array] = None
    # () int32 objective evaluations the problem made, and (max_iter + 1,)
    # int32 of them an iteration (entry 0 the initial one, 0 past
    # `iterations`): a lane's own work under vmap (optim/lbfgs.py _State).
    # None where the solver keeps no count (TRON, the streaming solvers)
    evaluations: Optional[Array] = None
    evaluation_history: Optional[Array] = None


def summarize_result(res: OptResult) -> str:
    """Human-readable solve summary (Summarizable.toSummaryString analogue)."""
    reason = ConvergenceReason(int(res.reason)).name
    return (
        f"value={float(res.value):.6g} |grad|={float(res.grad_norm):.3e} "
        f"iters={int(res.iterations)} reason={reason}"
    )


def iteration_histogram(iterations) -> str:
    """Power-of-2 histogram of per-lane iteration counts, e.g.
    ``<=4:120 <=8:30 <=32:1`` — makes the convergence skew (and therefore
    the one-shot vmapped solve's straggler waste, which every lane pays up
    to the max bucket) visible in one log line."""
    import numpy as np

    iters = np.asarray(iterations).ravel()
    if iters.size == 0:
        return "(empty)"
    top = int(iters.max())
    parts = []
    lo = -1
    hi = 1
    while lo < top:
        n = int(np.sum((iters > lo) & (iters <= hi)))
        if n:
            parts.append(f"<={hi}:{n}")
        lo = hi
        hi *= 2
    return " ".join(parts) if parts else "(empty)"


def summarize_stacked_results(res: OptResult) -> str:
    """Aggregate summary of a vmapped solve (leading entity axis on every
    field) — convergence-reason counts + iteration histogram/stats, the
    analogue of RandomEffectOptimizationTracker.toSummaryString
    (optimization/game/RandomEffectOptimizationTracker.scala:62-95). The
    histogram is the before/after ledger for solve compaction: a long tail
    of high-iteration lanes is exactly the waste compaction removes."""
    import numpy as np

    reasons = np.asarray(res.reason).ravel()
    iters = np.asarray(res.iterations).ravel()
    values = np.asarray(res.value).ravel()
    counts = {
        ConvergenceReason(code).name: int(n)
        for code, n in zip(*np.unique(reasons, return_counts=True))
        if code != 0
    }
    return (
        f"entities={reasons.size} convergenceReasons={counts} "
        f"iterations(mean={iters.mean():.1f} max={int(iters.max())} "
        f"histogram: {iteration_histogram(iters)}) "
        f"value(mean={values.mean():.6g} max={values.max():.6g})"
    )
