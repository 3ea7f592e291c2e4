"""GLM optimization problems: couple an objective + optimizer + regularization.

Reference spec: optimization/GeneralizedLinearOptimizationProblem.scala:42-279
(run/updateObjective/variance) and the per-task problem factories
(LogisticRegressionOptimizationProblem.scala etc.): LBFGS accepts any
once-differentiable loss (L1/elastic-net switches to OWL-QN); TRON requires a
twice-differentiable loss and rejects L1 (OptimizerFactory.scala:49-70,
Params.scala:177-180); smoothed-hinge SVM is first-order only.

TPU-native: the problem is a thin static config whose ``run`` builds pure
closures over a batch and dispatches to the while_loop kernels. The
regularization weight is a *traced* scalar so a lambda-grid sweep reuses one
compiled kernel. Variances = 1 / diag(Hessian) as in the reference
(:109-124 of the per-task problems).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from photon_ml_tpu.types import real_dtype
from photon_ml_tpu.models.glm import Coefficients, GeneralizedLinearModel
from photon_ml_tpu.ops import losses as losses_mod
from photon_ml_tpu.ops.normalization import NormalizationContext
from photon_ml_tpu.ops.objective import GLMBatch, GLMObjective
from photon_ml_tpu.optim.common import OptimizerConfig, OptResult
from photon_ml_tpu.optim.constraints import BoxConstraints
from photon_ml_tpu.optim.lbfgs import lbfgs_minimize_
from photon_ml_tpu.optim.tron import tron_minimize_
from photon_ml_tpu.ops.regularization import RegularizationContext
from photon_ml_tpu.types import OptimizerType, RegularizationType, TaskType

Array = jax.Array


def variances_from_hessian_diag(diag: Array) -> Array:
    """variance = 1/H_jj with the shared numerical floor — THE formula for
    every coefficient-variance producer (fixed/random/distributed), so the
    floor cannot drift between call sites."""
    return 1.0 / jnp.maximum(diag, 1e-12)


def _split_reg_weight(reg: RegularizationContext, reg_weight):
    """Split a total regularization weight into (l1, l2) per the context's
    type; ``reg_weight=None`` uses the context's own weight."""
    if reg_weight is None:
        return reg.l1_weight, reg.l2_weight
    if reg.reg_type == RegularizationType.L1:
        return reg_weight, 0.0
    if reg.reg_type == RegularizationType.L2:
        return 0.0, reg_weight
    if reg.reg_type == RegularizationType.ELASTIC_NET:
        a = reg.elastic_net_alpha
        return a * reg_weight, (1.0 - a) * reg_weight
    return 0.0, 0.0


@dataclasses.dataclass(frozen=True)
class GLMOptimizationProblem:
    """Static problem description; ``run`` is pure and jit/vmap-composable."""

    task: TaskType
    optimizer: OptimizerType = OptimizerType.LBFGS
    # None -> per-optimizer reference defaults (LBFGS 80/1e-7, TRON 15/1e-5)
    optimizer_config: Optional[OptimizerConfig] = None
    regularization: RegularizationContext = dataclasses.field(
        default_factory=RegularizationContext.none
    )
    compute_variance: bool = False
    axis_name: Optional[str] = None  # set under shard_map for psum reductions
    # box constraints on coefficients (OptimizationUtils.projectCoefficientsToHypercube);
    # densified (lower, upper) arrays — see optim/constraints.py
    constraints: Optional["BoxConstraints"] = None
    # rows a block of the one-pass dense value+grad kernel, set from the
    # batch's shape (with_fused_block_for); None = XLA two-pass
    fused_block_rows: Optional[int] = None
    # carry per-iteration coefficient snapshots through the solve (the
    # ModelTracker analogue backing --validate-per-iteration; costs
    # (max_iter+1, D) extra carry memory)
    track_coefficients: bool = False

    def __post_init__(self):
        if self.optimizer_config is None:
            cfg = (
                OptimizerConfig.tron_default()
                if self.optimizer == OptimizerType.TRON
                else OptimizerConfig.lbfgs_default()
            )
            object.__setattr__(self, "optimizer_config", cfg)
        loss = losses_mod.for_task(self.task)
        if self.optimizer == OptimizerType.TRON:
            if not loss.twice_differentiable:
                raise ValueError(
                    f"TRON requires a twice-differentiable loss; {self.task} is first-order "
                    "only (OptimizerFactory.scala:49-70 parity)"
                )
            if self.regularization.reg_type in (
                RegularizationType.L1,
                RegularizationType.ELASTIC_NET,
            ):
                raise ValueError(
                    "TRON does not support L1/ELASTIC_NET regularization "
                    "(Params.scala:177-180 parity)"
                )

    @property
    def objective(self) -> GLMObjective:
        return GLMObjective(
            losses_mod.for_task(self.task), self.axis_name, self.fused_block_rows
        )

    # ------------------------------------------------------------------
    def run(
        self,
        batch: GLMBatch,
        norm: NormalizationContext,
        init_coefficients: Optional[Array] = None,
        reg_weight: Optional[Array] = None,
    ) -> Tuple[GeneralizedLinearModel, OptResult]:
        """Solve; returns (model, solve result). Pure — jit/vmap freely.

        ``reg_weight`` overrides the context's total weight (traced scalar,
        the updateObjective analogue for lambda sweeps).
        """
        obj = self.objective
        l1, l2 = _split_reg_weight(self.regularization, reg_weight)

        w0 = (
            init_coefficients
            if init_coefficients is not None
            else jnp.zeros((batch.dim,), real_dtype())
        )
        vg = lambda w: obj.value_and_grad(w, batch, norm, l2)
        bounds = (
            (self.constraints.lower, self.constraints.upper)
            if self.constraints is not None
            else None
        )

        if self.optimizer == OptimizerType.TRON:
            hvp = lambda w, v: obj.hessian_vector(w, v, batch, norm, l2)
            result = tron_minimize_(
                vg, hvp, w0, self.optimizer_config, bounds=bounds,
                track_coefficients=self.track_coefficients,
            )
        else:
            result = lbfgs_minimize_(
                vg, w0, self.optimizer_config, l1_weight=l1, bounds=bounds,
                track_coefficients=self.track_coefficients,
            )

        w = result.coefficients
        variances = None
        if self.compute_variance:
            diag = obj.hessian_diagonal(w, batch, norm, l2)
            variances = variances_from_hessian_diag(diag)
        model = GeneralizedLinearModel(Coefficients(w, variances), self.task)
        return model, result

    # ------------------------------------------------------------------
    def regularization_term_value(self, w: Array, reg_weight: Optional[Array] = None) -> Array:
        """lambda_1 * ||w||_1 + lambda_2/2 * ||w||^2 (GLOP.scala:235-278)."""
        l1, l2 = _split_reg_weight(self.regularization, reg_weight)
        return l1 * jnp.sum(jnp.abs(w)) + 0.5 * l2 * jnp.sum(jnp.square(w))  # lint: bitwise-reduction — l1/l2 reg over the fixed (D,) w, not a slab batch axis

    # ------------------------------------------------------------------
    def with_fused_block_for(
        self, batch: GLMBatch, num_shards: int = 1
    ) -> "GLMOptimizationProblem":
        """This problem with ``fused_block_rows`` set where the batch calls
        for the one-pass kernel: dense features, no block set by the caller,
        and ``ops.fused_glm.select_fused_block_rows`` gives one for the shape
        a device sees (the batch's rows over ``num_shards`` under
        ``shard_map``). From platform, dtype and shape, microseconds a call;
        the vmapped solves never ask."""
        from photon_ml_tpu.ops.features import DenseFeatures
        from photon_ml_tpu.ops.fused_glm import select_fused_block_rows

        if self.fused_block_rows is not None or not isinstance(batch.features, DenseFeatures):
            return self
        block = select_fused_block_rows(
            batch.num_rows // num_shards, batch.dim, batch.features.matrix.dtype
        )
        return self if block is None else dataclasses.replace(self, fused_block_rows=block)
