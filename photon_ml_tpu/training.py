"""High-level GLM training: warm-started regularization-weight grid.

Reference spec: ModelTraining.scala:51-197 — regularization weights sorted
high-to-low ("which would potentially speed up the overall convergence
time"), each solve warm-started from the previous lambda's model; optional
per-lambda state trackers.

TPU-native: the per-lambda solve is one compiled kernel reused across the
whole grid (reg weight is a traced scalar), so the sweep costs one
compilation + k solves.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from photon_ml_tpu.types import real_dtype
from photon_ml_tpu.models.glm import GeneralizedLinearModel
from photon_ml_tpu.ops.normalization import NormalizationContext
from photon_ml_tpu.ops.objective import GLMBatch
from photon_ml_tpu.optim.common import OptResult
from photon_ml_tpu.optim.problem import GLMOptimizationProblem
from photon_ml_tpu.utils import profiling


@dataclasses.dataclass
class TrainedModelList:
    """(lambda, model, solve-result) triples, sorted high-to-low lambda
    (the training order — NOT the caller's input order)."""

    weights: List[float]
    models: List[GeneralizedLinearModel]
    results: List[OptResult]

    def best_by(self, key) -> Tuple[float, GeneralizedLinearModel]:
        idx = max(range(len(self.weights)), key=lambda i: key(self.weights[i], self.models[i]))
        return self.weights[idx], self.models[idx]

    def as_map(self) -> Dict[float, GeneralizedLinearModel]:
        return dict(zip(self.weights, self.models))


@functools.partial(jax.jit, static_argnames=("problem",))
def _solve(problem, batch, norm, w0, lam):
    return problem.run(batch, norm, init_coefficients=w0, reg_weight=lam)


def train_glm_grid(
    problem: GLMOptimizationProblem,
    batch: GLMBatch,
    norm: NormalizationContext,
    reg_weights: Sequence[float],
    warm_start_models: Optional[Dict[float, GeneralizedLinearModel]] = None,
) -> TrainedModelList:
    """Train one model per regularization weight with warm starts.

    The grid is iterated high-to-low; the first solve starts from the
    highest-lambda warm-start model when provided (ModelTraining.scala:
    158-191 behavior), otherwise zeros.
    """
    sorted_weights = sorted(reg_weights, reverse=True)

    problem = problem.with_fused_block_for(batch)

    try:
        # module-level jit: repeat calls with the same problem + shapes (e.g.
        # the fitting diagnostic's 9 prefix solves, which differ only by a
        # weight mask) hit one compiled kernel instead of recompiling
        hash(problem)
        solve = lambda w0, lam: _solve(problem, batch, norm, w0, lam)
    except TypeError:  # unhashable problem (e.g. array-valued box constraints)
        solve = jax.jit(
            lambda w0, lam: problem.run(batch, norm, init_coefficients=w0, reg_weight=lam)
        )

    weights, models, results = [], [], []
    with profiling.span("pml.glm.grid", lambdas=len(sorted_weights)):
        if warm_start_models:
            max_lambda = max(warm_start_models.keys())
            w = warm_start_models[max_lambda].coefficients.means
        else:
            w = jnp.zeros((batch.dim,), real_dtype())
        for lam in sorted_weights:
            with profiling.span("pml.glm.solve", reg_weight=lam):
                model, res = solve(w, jnp.asarray(lam, real_dtype()))
            w = model.coefficients.means
            weights.append(lam)
            models.append(model)
            results.append(res)

    return TrainedModelList(weights, models, results)


def train_glm_grid_streaming(
    problem: GLMOptimizationProblem,
    source,
    norm: NormalizationContext,
    reg_weights: Sequence[float],
    bucketer=None,
) -> TrainedModelList:
    """Warm-started lambda grid over CHUNK-STREAMED data (out-of-core):
    same high-to-low warm-start chain as :func:`train_glm_grid`, but each
    solve is host-driven over the chunks — data >> device+host memory
    trains (the StorageLevel.scala:22-24 DISK_ONLY answer, VERDICT r3 #5).

    LBFGS/OWL-QN stream one pass per evaluation; TRON additionally streams
    one pass per CG Hessian-vector product — the reference's cost profile
    exactly (one treeAggregate per CG step, TRON.scala:268-281).

    ``bucketer`` (photon_ml_tpu.compile; None = PHOTON_SHAPE_LADDER) rounds
    chunk row counts up the canonical ladder so the tail chunk reuses the
    other chunks' compiled partial instead of compiling its own.
    """
    from photon_ml_tpu.optim.problem import _split_reg_weight, variances_from_hessian_diag
    from photon_ml_tpu.optim.streaming import (
        lbfgs_minimize_streaming,
        make_streaming_hvp,
        make_streaming_value_and_grad,
        streaming_hessian_diagonal,
        tron_minimize_streaming,
    )
    from photon_ml_tpu.types import OptimizerType
    from photon_ml_tpu.models.glm import Coefficients

    obj = problem.objective
    bounds = (
        (problem.constraints.lower, problem.constraints.upper)
        if problem.constraints is not None
        else None
    )
    w = jnp.zeros((source.dim,), real_dtype())
    # ONE factory for the whole grid: l2 rides through as an argument, so
    # the per-chunk kernel compiles once (the streaming counterpart of the
    # in-memory path's module-level jitted _solve)
    vg_base = make_streaming_value_and_grad(source, obj, norm, bucketer=bucketer)
    hvp_base = (
        make_streaming_hvp(source, obj, norm, bucketer=bucketer)
        if problem.optimizer == OptimizerType.TRON else None
    )
    weights, models, results = [], [], []
    for lam in sorted(reg_weights, reverse=True):
        l1, l2 = _split_reg_weight(problem.regularization, lam)
        vg = lambda wt, l2=l2: vg_base(wt, l2_weight=float(l2))
        if problem.optimizer == OptimizerType.TRON:
            hvp = lambda wt, v, l2=l2: hvp_base(wt, v, l2_weight=float(l2))
            res = tron_minimize_streaming(
                vg, hvp, w, problem.optimizer_config, bounds=bounds
            )
        else:
            res = lbfgs_minimize_streaming(
                vg, w, problem.optimizer_config, l1_weight=float(l1), bounds=bounds
            )
        w = res.coefficients
        variances = None
        if problem.compute_variance:
            diag = streaming_hessian_diagonal(
                source, obj, norm, w, float(l2), bucketer=bucketer
            )
            variances = variances_from_hessian_diag(diag)
        models.append(
            GeneralizedLinearModel(Coefficients(w, variances), problem.task)
        )
        weights.append(lam)
        results.append(res)
    return TrainedModelList(weights, models, results)


def train_glm_grid_vmapped(
    problem: GLMOptimizationProblem,
    batch: GLMBatch,
    norm: NormalizationContext,
    reg_weights: Sequence[float],
) -> TrainedModelList:
    """Solve EVERY regularization weight simultaneously: one vmapped
    optimizer kernel whose lanes are the lambdas.

    A TPU-native alternative the reference cannot express: each iteration's
    margin/gradient pass becomes one batched MXU matmul serving all K
    lambdas, so the sweep's wall-clock approaches ONE solve instead of K
    (converged lanes run masked no-ops until the slowest lane finishes —
    the same branch-free while_loop property the per-entity random-effect
    solves rely on). The trade vs. :func:`train_glm_grid` is cold starts
    (no warm-start chain) and K× coefficient memory; both converge to the
    same per-lambda optima, so model selection is unchanged.
    """
    sorted_weights = sorted(reg_weights, reverse=True)
    k = len(sorted_weights)
    # the one-pass kernel does not serve a vmapped solve: vmapping a
    # pallas_call adds a grid axis its accumulation does not know
    if problem.fused_block_rows is not None:
        problem = dataclasses.replace(problem, fused_block_rows=None)
    lams = jnp.asarray(sorted_weights, real_dtype())
    w0 = jnp.zeros((k, batch.dim), real_dtype())

    solve = jax.jit(
        jax.vmap(
            lambda w, lam: problem.run(batch, norm, init_coefficients=w, reg_weight=lam),
            in_axes=(0, 0),
        )
    )
    stacked_models, stacked_results = solve(w0, lams)
    models = [
        jax.tree_util.tree_map(lambda leaf, i=i: leaf[i], stacked_models)
        for i in range(k)
    ]
    results = [
        jax.tree_util.tree_map(lambda leaf, i=i: leaf[i], stacked_results)
        for i in range(k)
    ]
    return TrainedModelList(list(sorted_weights), models, results)
