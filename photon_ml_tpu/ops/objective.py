"""The GLM objective: value / gradient / Hessian-vector / Hessian-diagonal.

This is the hot loop of the whole framework (the reference's
ValueAndGradientAggregator + HessianVectorAggregator, re-designed batched):

  value(w)  = sum_i weight_i * l(z_i, y_i)  +  l2/2 * ||w||^2
  z_i       = (x_i - shift) . (w * factor) + offset_i
            = x_i . w_eff + margin_shift + offset_i           (folded form)

where ``w_eff = w * factor`` and ``margin_shift = -w_eff . shift``; raw data
is never normalized in memory. On Spark this was a per-datum loop inside
treeAggregate (ValueAndGradientAggregator.scala:120-139 / :205-220); here each
quantity is one batched matmul/gather pass that XLA fuses end-to-end, and the
cross-device reduction is a single ``psum`` when running under ``shard_map``
(the treeAggregate-depth knob is obsolete).

Padding rows are expressed with ``weight == 0`` — they contribute exactly
zero to every sum, so bucketed/padded batches need no separate mask.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from photon_ml_tpu.ops.features import Features
from photon_ml_tpu.ops.losses import PointwiseLoss
from photon_ml_tpu.ops.normalization import NormalizationContext

Array = jax.Array


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class GLMBatch:
    """Struct-of-arrays batch: the TPU analogue of RDD[LabeledPoint].

    (data/LabeledPoint.scala:28-62 spec: label, features, offset, weight.)
    """

    features: Features
    labels: Array  # (N,)
    offsets: Array  # (N,)
    weights: Array  # (N,)  — 0 marks padding rows

    @property
    def num_rows(self) -> int:
        return self.labels.shape[0]

    @property
    def dim(self) -> int:
        return self.features.dim

    @staticmethod
    def create(features: Features, labels: Array, offsets=None, weights=None) -> "GLMBatch":
        n = labels.shape[0]
        if offsets is None:
            offsets = jnp.zeros((n,), labels.dtype)
        if weights is None:
            weights = jnp.ones((n,), labels.dtype)
        return GLMBatch(features, labels, offsets, weights)

    def tree_flatten(self):
        return (self.features, self.labels, self.offsets, self.weights), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def _maybe_psum(x, axis_name: Optional[str]):
    return lax.psum(x, axis_name) if axis_name is not None else x


def _wmul(weights: Array, x: Array) -> Array:
    """weights * x with a hard mask: padding rows (weight 0) contribute an
    exact 0 even when x is inf/nan (e.g. exp overflow on garbage padding)."""
    return jnp.where(weights > 0.0, weights * x, 0.0)


def _row_sum(features, x: Array) -> Array:
    """Scalar row reduction, slab-aware.

    Sparse-slab batches reduce through the fixed-association pairwise tree
    (``fused_sparse.tree_row_sum``) so every sparse family — the generic
    scatter/segment path here AND the fused Pallas wrappers — produces the
    bitwise-identical scalar in every fusion context (a plain ``reduce``'s
    association order changes with producer fusion; a one-ulp loss value
    flips line searches). Dense batches keep the plain ``jnp.sum``.
    """
    from photon_ml_tpu.ops.fused_sparse import SparseSlab, tree_row_sum

    if isinstance(features, SparseSlab):
        return tree_row_sum(x)
    return jnp.sum(x)


@dataclasses.dataclass(frozen=True)
class GLMObjective:
    """Pure-function objective bundle for one pointwise loss.

    ``axis_name``: when the batch is sharded over a mesh axis and the caller
    runs this under ``shard_map``, set it to that axis name — every global
    sum becomes a ``psum`` and each device sees only its shard. Under plain
    jit with sharded-array inputs, leave it None and XLA inserts the
    collectives itself.

    ``fused_block_rows``: when set (by the runtime autotune,
    ``ops.fused_glm.select_fused_block_rows``) and the batch is dense,
    ``value_and_grad`` runs the single-pass Pallas kernel — one HBM stream
    of X instead of the two-pass XLA pipeline — with the normalization and
    regularization algebra folded around it here, identically to the XLA
    path.

    All methods take ``l2_weight`` as a (traceable) scalar so a lambda-grid
    sweep does not retrigger compilation.

    Each of value, value_and_grad, hessian_vector and hessian_diagonal runs
    under one device scope (``pml.objective.*``) whichever branch computes
    it, so the fused kernels keep the generic path's name in a trace.
    """

    loss: PointwiseLoss
    axis_name: Optional[str] = None
    fused_block_rows: Optional[int] = None

    # -- margins ------------------------------------------------------------
    def margins(self, w: Array, batch: GLMBatch, norm: NormalizationContext) -> Array:
        w_eff = norm.effective_coefficients(w)
        return batch.features.matvec(w_eff) + norm.margin_shift(w_eff) + batch.offsets

    # -- value --------------------------------------------------------------
    @jax.named_scope("pml.objective.value")
    def value(self, w, batch, norm, l2_weight=0.0) -> Array:
        z = self.margins(w, batch, norm)
        total = _row_sum(
            batch.features, _wmul(batch.weights, self.loss.loss(z, batch.labels))
        )
        total = _maybe_psum(total, self.axis_name)
        return total + 0.5 * l2_weight * jnp.sum(jnp.square(w))  # lint: bitwise-reduction — l2 reg over the fixed (D,) w; pinned arithmetic of the bitwise gates

    # -- value + gradient (one fused pass) ----------------------------------
    @jax.named_scope("pml.objective.value_and_grad")
    def value_and_grad(self, w, batch, norm, l2_weight=0.0) -> Tuple[Array, Array]:
        w_eff = norm.effective_coefficients(w)
        if self._use_fused(batch):
            from photon_ml_tpu.ops import fused_glm

            offsets = batch.offsets + norm.margin_shift(w_eff)
            lv, grad_eff, sum_d = fused_glm.fused_value_grad_parts(
                self.loss, batch.features.matrix, batch.labels, batch.weights,
                offsets, w_eff, block_rows=self.fused_block_rows,
            )
            if norm.shifts is not None:
                grad_eff = grad_eff - norm.shifts * sum_d
        elif self._use_sparse_fused(batch):
            # fused single-pass sparse GEVM over the bucketed slab (one
            # load of idx/val feeds margin + loss + gradient scatter);
            # bitwise-equal to the generic slab path by construction —
            # verified at selection time (ops/fused_sparse.py)
            from photon_ml_tpu.ops import fused_sparse

            offsets = batch.offsets + norm.margin_shift(w_eff)
            lv, grad_eff, sum_d = fused_sparse.fused_value_grad_parts(
                self.loss, batch.features, batch.labels, batch.weights,
                offsets, w_eff,
            )
            if norm.shifts is not None:
                grad_eff = grad_eff - norm.shifts * sum_d
        else:
            z = batch.features.matvec(w_eff) + norm.margin_shift(w_eff) + batch.offsets
            lv = _row_sum(
                batch.features,
                _wmul(batch.weights, self.loss.loss(z, batch.labels)),
            )
            d = _wmul(batch.weights, self.loss.d1(z, batch.labels))  # (N,)
            grad_eff = batch.features.rmatvec(d)
            if norm.shifts is not None:
                grad_eff = grad_eff - norm.shifts * _row_sum(batch.features, d)
        lv = _maybe_psum(lv, self.axis_name)
        grad_eff = _maybe_psum(grad_eff, self.axis_name)
        grad = grad_eff * norm.factors if norm.factors is not None else grad_eff
        value = lv + 0.5 * l2_weight * jnp.sum(jnp.square(w))  # lint: bitwise-reduction — l2 reg over the fixed (D,) w; pinned arithmetic of the bitwise gates
        grad = grad + l2_weight * w
        return value, grad

    def _use_fused(self, batch: GLMBatch) -> bool:
        """Static (trace-time) dispatch to the single-pass Pallas kernel."""
        from photon_ml_tpu.ops.features import DenseFeatures

        return (
            self.fused_block_rows is not None
            and isinstance(batch.features, DenseFeatures)
            and batch.features.matrix.dtype != jnp.float64
        )

    def _use_sparse_fused(self, batch: GLMBatch) -> bool:
        """Static (trace-time) dispatch to the fused sparse-slab kernels:
        the slab's ``kernel`` family is a static pytree aux, so per-bucket
        selection changes the executable, never retraces mid-solve."""
        from photon_ml_tpu.ops.fused_sparse import SparseSlab

        return (
            isinstance(batch.features, SparseSlab)
            and batch.features.kernel.startswith("pallas")
            and batch.features.val.dtype != jnp.float64
        )

    def grad(self, w, batch, norm, l2_weight=0.0) -> Array:
        return self.value_and_grad(w, batch, norm, l2_weight)[1]

    # -- Hessian-vector product (TRON's CG inner loop) ----------------------
    @jax.named_scope("pml.objective.hvp")
    def hessian_vector(self, w, v, batch, norm, l2_weight=0.0) -> Array:
        """H(w) @ v.  (HessianVectorAggregator.scala:90-116 algebra, batched.)"""
        w_eff = norm.effective_coefficients(w)
        v_eff = norm.effective_coefficients(v)
        if self._use_sparse_fused(batch):
            # fused sparse HVP: one load of the slab feeds BOTH
            # contractions (z from w, z_v from v) and the transpose scatter
            from photon_ml_tpu.ops import fused_sparse

            offsets = batch.offsets + norm.margin_shift(w_eff)
            hv_eff, sum_c = fused_sparse.fused_hvp_parts(
                self.loss, batch.features, batch.labels, batch.weights,
                offsets, w_eff, v_eff, norm.margin_shift(v_eff),
            )
            if norm.shifts is not None:
                hv_eff = hv_eff - norm.shifts * sum_c
            hv_eff = _maybe_psum(hv_eff, self.axis_name)
            hv = hv_eff * norm.factors if norm.factors is not None else hv_eff
            return hv + l2_weight * v
        z = batch.features.matvec(w_eff) + norm.margin_shift(w_eff) + batch.offsets
        d2 = _wmul(batch.weights, self.loss.d2(z, batch.labels))  # (N,)
        zv = batch.features.matvec(v_eff) + norm.margin_shift(v_eff)  # (x_i - shift).v_eff
        c = d2 * zv
        hv_eff = batch.features.rmatvec(c)
        if norm.shifts is not None:
            hv_eff = hv_eff - norm.shifts * _row_sum(batch.features, c)
        hv_eff = _maybe_psum(hv_eff, self.axis_name)
        hv = hv_eff * norm.factors if norm.factors is not None else hv_eff
        return hv + l2_weight * v

    # -- Hessian diagonal (coefficient variance: 1/H_jj) ---------------------
    @jax.named_scope("pml.objective.hessian_diagonal")
    def hessian_diagonal(self, w, batch, norm, l2_weight=0.0) -> Array:
        """diag(H) = sum_i d2_i * ((x_i - shift) * factor)_j^2  + l2.

        Expanded so sparse layouts never densify:
          factor^2 * [ (X^2)^T d2 - 2*shift*(X^T d2) + shift^2 * sum(d2) ]
        (TwiceDiffFunction.scala:151-162 behavior.)
        """
        w_eff = norm.effective_coefficients(w)
        z = batch.features.matvec(w_eff) + norm.margin_shift(w_eff) + batch.offsets
        d2 = _wmul(batch.weights, self.loss.d2(z, batch.labels))
        diag = batch.features.sq_rmatvec(d2)
        if norm.shifts is not None:
            diag = (
                diag
                - 2.0 * norm.shifts * batch.features.rmatvec(d2)
                + jnp.square(norm.shifts) * _row_sum(batch.features, d2)
            )
        diag = _maybe_psum(diag, self.axis_name)
        if norm.factors is not None:
            diag = diag * jnp.square(norm.factors)
        return diag + l2_weight

    # -- scoring ------------------------------------------------------------
    def mean_prediction(self, w, batch, norm) -> Array:
        return self.loss.mean(self.margins(w, batch, norm))
