"""The GLM objective: value / gradient / Hessian-vector / Hessian-diagonal.

This is the hot loop of the whole framework (the reference's
ValueAndGradientAggregator + HessianVectorAggregator, re-designed batched):

  value(w)  = sum_i weight_i * l(z_i, y_i)  +  l2/2 * ||w||^2
  z_i       = (x_i - shift) . (w * factor) + offset_i
            = x_i . w_eff + margin_shift + offset_i           (folded form)

where ``w_eff = w * factor`` and ``margin_shift = -w_eff . shift``; raw data is
never normalized in memory. On Spark this was a per-datum loop inside
treeAggregate (ValueAndGradientAggregator.scala:120-139 / :205-220); here each
quantity is one batched matmul/gather pass that XLA fuses end-to-end, and the
cross-device reduction is a single ``psum`` when running under ``shard_map``.
Padding rows are expressed with ``weight == 0`` — they contribute exactly
zero to every sum, so bucketed/padded batches need no separate mask.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from photon_ml_tpu.ops.features import Features, SparseFeatures, _acc_dtype
from photon_ml_tpu.ops.losses import PointwiseLoss
from photon_ml_tpu.ops.normalization import NormalizationContext
from photon_ml_tpu.ops.tiled_sparse import TiledFeatures, pairwise_sum

Array = jax.Array


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class GLMBatch:
    """Struct-of-arrays batch: the TPU analogue of RDD[LabeledPoint]
    (data/LabeledPoint.scala:28-62 spec: label, features, offset, weight)."""

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


#: Stored non-zeros (rows x padded K) a row block of the blocked value-and-
#: gradient pass aims at; a padded-sparse batch that holds more is walked in
#: blocks of ``ROW_BLOCK_NNZ // K`` rows (a v5e sweep: PERF.md section 6, PR 26).
ROW_BLOCK_NNZ = 1 << 22


def _block_rows(features) -> Optional[int]:
    """Rows per block of the blocked value-and-gradient pass, from the batch's
    static shape alone; None where the whole batch is one block. Only
    ``SparseFeatures`` in row order blocks: the sorted transpose (``t_idx``)
    and the tile layout (``tiles``: built by ``auto_transpose`` at placement,
    read by this pass alone; its kernels walk their own blocks) are in feature
    order and cannot be cut by rows. Under ``shard_map`` the shape is the local
    shard's; no caller hands over rows that plain ``jit`` has sharded."""
    if not isinstance(features, SparseFeatures) or features.t_idx is not None:
        return None
    n, k = features.indices.shape
    if n * k <= ROW_BLOCK_NNZ or features.tiles is not None:
        return None
    rows = max(ROW_BLOCK_NNZ // k, 1)
    return rows - rows % 8 if rows > 8 else rows


def _pass_view(batch):
    """(the batch as the value-and-gradient pass reads it, its rows a block):
    features that carry the tile layout are read through it, in one pass."""
    tiled = batch.features.tiled() if isinstance(batch.features, SparseFeatures) else None
    return (batch, _block_rows(batch.features)) if tiled is None else (
        dataclasses.replace(batch, features=tiled), None)


def _maybe_psum(x, axis_name: Optional[str]):
    return lax.psum(x, axis_name) if axis_name is not None else x


def _wmul(weights: Array, x: Array) -> Array:
    """weights * x with a hard mask: padding rows (weight 0) contribute an
    exact 0 even when x is inf/nan (e.g. exp overflow on garbage padding)."""
    return jnp.where(weights > 0.0, weights * x, 0.0)


def _row_sum(features, x: Array) -> Array:
    """Scalar row reduction, slab-aware. Sparse-slab batches reduce through
    the fixed-association pairwise tree (``fused_sparse.tree_row_sum``) so
    every sparse family produces the bitwise-identical scalar whatever fusion
    the ``reduce`` would land in. The tile layout's one pass sums all of its
    rows' losses at once, by halves: one float32 ``reduce`` over 2^22 of them
    is 2e-6 off. Dense batches and a row block's rows keep ``jnp.sum``."""
    from photon_ml_tpu.ops.fused_sparse import SparseSlab, tree_row_sum

    if isinstance(features, TiledFeatures):
        return pairwise_sum(x)
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

    ``fused_block_rows``: rows a block; when set (from the batch's shape, by
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
        batch, rows = _pass_view(batch)  # rows None: the whole batch at once
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
        elif rows is not None:
            lv, grad_eff, sum_d = self._blocked_value_grad_parts(
                w_eff, norm.margin_shift(w_eff), batch, rows
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
        if rows is not None:
            # The solver reduces this gradient (its norm) right after. Left
            # free, the compiler fuses that reduction with the scan's epilogue
            # above, and a float32 reduction of 2^21 squares on the v5e moves
            # by 5e-7 of itself with the fusion it is in (it is 2e-6 from the
            # float64 sum either way). Behind the barrier it is fused as it
            # is after the whole-batch pass and gives the same bits.
            grad = lax.optimization_barrier(grad)
        return value, grad

    def _blocked_value_grad_parts(self, w_eff, margin_shift, batch, rows: int):
        """(loss sum, X^T d, sum d) of a padded-sparse batch, walked in
        contiguous blocks of ``rows`` rows: one ``lax.scan`` whose step does a
        block's gather, margin, loss, slope and scatter-add before the next
        block's gather starts, so the (rows, K) temporaries of one block are
        all the pass holds. The same arithmetic as the whole-batch pass, in
        the same precision: the scatter-add lands on the carried gradient in
        row order, as the whole-batch one does on zeros, and the blocks' loss
        and slope sums are added up after the scan (a sum carried through
        thousands of steps would round thousands of times). Rows the block
        does not divide are a last, shorter step after the scan."""
        feats = batch.features
        n = feats.num_rows
        # Every block gathers from w_eff. A buffer this evaluation makes the
        # compiler keeps in the chip's fast memory for the whole scan; the
        # solver's starting point is the program's own parameter, stays in
        # HBM, and the gather from there takes 1.8 times as long (PERF.md
        # section 6, PR 26). Times a one the compiler cannot see through: the
        # same bits in a buffer of the evaluation's own.
        w_eff = w_eff * lax.optimization_barrier(jnp.ones((), w_eff.dtype))

        @jax.named_scope("pml.objective.row_block")
        def add_block(grad_eff, start, size: int):
            cut = lambda a: lax.dynamic_slice_in_dim(
                a, start, size, allow_negative_indices=False)
            block = SparseFeatures(cut(feats.indices), cut(feats.values), feats.dim)
            labels, weights = cut(batch.labels), cut(batch.weights)
            z = block.matvec(w_eff) + margin_shift + cut(batch.offsets)
            d = _wmul(weights, self.loss.d1(z, labels))
            lv = _row_sum(block, _wmul(weights, self.loss.loss(z, labels)))
            return block.rmatvec(d, into=grad_eff), (lv, _row_sum(block, d))

        grad_eff = jnp.zeros((feats.dim,), _acc_dtype(feats.values.dtype))
        if self.axis_name is not None:
            # under shard_map every block's sum varies over the mesh axis
            grad_eff = lax.pcast(grad_eff, self.axis_name, to="varying")
        starts = jnp.arange(n // rows, dtype=jnp.int32) * rows
        grad_eff, (lv, sum_d) = lax.scan(
            lambda g, start: add_block(g, start, rows), grad_eff, starts
        )
        lv, sum_d = _row_sum(feats, lv), _row_sum(feats, sum_d)  # over blocks
        if n % rows:
            grad_eff, (lv_tail, sum_d_tail) = add_block(
                grad_eff, n - n % rows, n % rows
            )
            lv, sum_d = lv + lv_tail, sum_d + sum_d_tail
        return lv, grad_eff, sum_d

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
