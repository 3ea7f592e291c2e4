"""Fixed-effect coordinate: one distributed GLM solve over the whole dataset.

Reference spec: algorithm/FixedEffectCoordinate.scala:33-176 — updateModel =
(down-sample ->) solve on full data with residual offsets; scoring = dense
dot-product with the (broadcast) model. TPU-native: the batch lives sharded
over the mesh's data axis; the solve is the while_loop kernel with psum
reductions (under shard_map) or XLA-auto-collectives (plain jit); "broadcast
model" = replicated coefficient vector.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from photon_ml_tpu.models.glm import GeneralizedLinearModel
from photon_ml_tpu.ops.normalization import NormalizationContext
from photon_ml_tpu.ops.objective import GLMBatch
from photon_ml_tpu.optim.common import OptResult
from photon_ml_tpu.optim.problem import GLMOptimizationProblem
from photon_ml_tpu.types import real_dtype

Array = jax.Array


@dataclasses.dataclass
class FixedEffectCoordinate:
    """Couples a fixed-effect batch with its optimization problem."""

    batch: GLMBatch
    problem: GLMOptimizationProblem
    norm: NormalizationContext = dataclasses.field(default_factory=NormalizationContext.identity)
    down_sampling_rate: Optional[float] = None
    seed: int = 7

    @property
    def dim(self) -> int:
        return self.batch.dim

    def initial_coefficients(self) -> Array:
        return jnp.zeros((self.dim,), real_dtype())

    @jax.named_scope("pml.fe.solve")
    def update(self, residual_offsets: Array, init_coefficients: Array,
               reg_weight: Optional[Array] = None) -> Tuple[Array, OptResult]:
        """Solve on residuals: offsets = base + other coordinates' scores.

        (Coordinate.updateModel = addScoresToOffsets -> solve,
        Coordinate.scala:43-49.) ``reg_weight`` overrides the problem's
        total regularization weight as a TRACED scalar — the lambda-grid
        vmap axis (updateObjective analogue).
        """
        from photon_ml_tpu.data.sampler import maybe_down_sample

        batch = GLMBatch(
            self.batch.features,
            self.batch.labels,
            self.batch.offsets + residual_offsets,
            self.batch.weights,
        )
        batch = maybe_down_sample(
            batch, self.problem.task, self.down_sampling_rate, self.seed
        )
        model, result = self.problem.run(
            batch, self.norm, init_coefficients, reg_weight=reg_weight
        )
        return model.coefficients.means, result

    @jax.named_scope("pml.fe.score")
    def score(self, coefficients: Array) -> Array:
        """Raw margins x.w (NO offset, NO mean function): GAME scores are
        additive margin contributions (FixedEffectModel.scala:91-100)."""
        w_eff = self.norm.effective_coefficients(coefficients)
        return self.batch.features.matvec(w_eff) + self.norm.margin_shift(w_eff)

    def coefficient_variances(self, coefficients: Array,
                              residual_offsets: Array) -> Array:
        """variances = 1/diag(H) at the final coefficients on the
        residual-offset batch (the computeVariances the reference's
        problem runs when isComputingVariance,
        LogisticRegressionOptimizationProblem.scala:109-124) — computed at
        save time from the final state, one Hessian-diagonal pass."""
        from photon_ml_tpu.optim.problem import variances_from_hessian_diag

        batch = GLMBatch(
            self.batch.features,
            self.batch.labels,
            self.batch.offsets + residual_offsets,
            self.batch.weights,
        )
        l2 = self.problem.regularization.l2_weight
        diag = self.problem.objective.hessian_diagonal(
            coefficients, batch, self.norm, l2
        )
        return variances_from_hessian_diag(diag)

    def regularization_term(self, coefficients: Array,
                            reg_weight: Optional[Array] = None) -> Array:
        return self.problem.regularization_term_value(coefficients, reg_weight)

    def model(self, coefficients: Array) -> GeneralizedLinearModel:
        from photon_ml_tpu.models.glm import Coefficients

        return GeneralizedLinearModel(Coefficients(coefficients), self.problem.task)
