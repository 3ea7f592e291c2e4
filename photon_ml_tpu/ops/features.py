"""Feature-matrix abstractions for TPU-friendly GLM math.

The reference (photon-ml) stores each example as a Breeze sparse/dense vector
and loops per-datum inside Spark partitions (ValueAndGradientAggregator.add).
On TPU the same math must be *batched*: the whole (sub-)batch participates in
one fused matmul / gather so the MXU sees large contractions.

Two layouts:

  * ``DenseFeatures``  — an ``(N, D)`` dense matrix. The fast path whenever
    the (possibly projected) feature dimension is modest. All four GLM
    kernels (margin, X^T d, Hessian-vector, Hessian diagonal) are matmuls.

  * ``SparseFeatures`` — padded per-row COO: ``indices (N, K)`` into the
    feature axis plus ``values (N, K)``, with out-of-row slots pointing at a
    dedicated padding column. Margin is a gather + row-sum; the transpose
    action is a scatter-add. This handles photon-ml's wide-sparse regime
    (millions of features, few non-zeros per row) without materializing
    ``(N, D)``.

Both expose the same protocol so the objective is layout-agnostic:

  matvec(w)        -> X @ w                      shape (N,)
  rmatvec(d)       -> X^T @ d                    shape (D,)
  sq_rmatvec(d)    -> (X*X)^T @ d                shape (D,)  (Hessian diag)
  col_stats()      -> per-column summary helpers used by normalization

Each of the three runs under the device scope ``pml.features.<name>``, the
same name whatever the layout, so a trace attributes gather, scatter-add and
matmul time to the layer and a kernel swap keeps the name.

Reference behavior spec: function/ValueAndGradientAggregator.scala:87-139,
HessianVectorAggregator.scala:90-116 (re-derived algebra, batched here).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Union

import jax
import jax.numpy as jnp

Array = jax.Array


def _acc_dtype(storage_dtype) -> jnp.dtype:
    """Accumulation dtype for contractions over a given storage dtype.

    f32 accumulation on the MXU for f32/bf16 storage (the TPU path);
    f64 when the framework runs in reference-precision float64 mode
    (PHOTON_ML_TPU_DTYPE=float64 on CPU) so the matvec does not silently
    round the trajectory back to f32.
    """
    return jnp.float64 if storage_dtype == jnp.float64 else jnp.float32


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DenseFeatures:
    """Dense (N, D) feature matrix.

    The matrix may be stored in bfloat16 — the HBM-bandwidth lever for the
    GLM hot loop (the matvec is memory-bound; bf16 storage halves traffic).
    Contractions accumulate in and return ``_acc_dtype``: float32 on the
    MXU regardless of (bf16/f32) storage, or float64 when the storage dtype
    is float64 (the PHOTON_ML_TPU_DTYPE=float64 reference-precision mode).
    """

    matrix: Array  # (N, D)

    @property
    def num_rows(self) -> int:
        return self.matrix.shape[0]

    @property
    def dim(self) -> int:
        return self.matrix.shape[1]

    @jax.named_scope("pml.features.matvec")
    def matvec(self, w: Array) -> Array:
        acc = _acc_dtype(self.matrix.dtype)
        return jnp.dot(
            self.matrix, w.astype(self.matrix.dtype),
            preferred_element_type=acc,
        )

    @jax.named_scope("pml.features.rmatvec")
    def rmatvec(self, d: Array) -> Array:
        acc = _acc_dtype(self.matrix.dtype)
        return jnp.dot(
            d.astype(self.matrix.dtype), self.matrix,
            preferred_element_type=acc,
        )

    @jax.named_scope("pml.features.sq_rmatvec")
    def sq_rmatvec(self, d: Array) -> Array:
        acc = _acc_dtype(self.matrix.dtype)
        sq = jnp.square(self.matrix.astype(acc))
        return jnp.dot(d, sq, preferred_element_type=acc)

    def row_sq_norms(self) -> Array:
        acc = _acc_dtype(self.matrix.dtype)
        return jnp.sum(jnp.square(self.matrix.astype(acc)), axis=-1)

    def to_dense(self) -> Array:
        return self.matrix.astype(_acc_dtype(self.matrix.dtype))

    def astype(self, dtype) -> "DenseFeatures":
        """Re-store the matrix in another dtype (bf16 for bandwidth)."""
        return DenseFeatures(self.matrix.astype(dtype))

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        return (self.matrix,), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SparseFeatures:
    """Padded per-row sparse features.

    ``indices``/``values`` have shape (N, K) where K is the max non-zeros per
    row in the batch. Padding slots carry ``values == 0`` and any valid index
    (conventionally 0) so gathers stay in-bounds and scatter-adds of zero are
    no-ops.

    ``tiles``: the second layout of a large wide batch
    (``ops/tiled_sparse.py``): the same stored values in (row block x
    feature tile) buckets, on which the gather and the scatter-add are
    float32-exact one-hot products on the matrix unit. :func:`auto_transpose`
    builds it once, on the device, when the batch is placed; the fixed
    effect's value-and-gradient pass reads it through :meth:`tiled`, and
    nothing else does: ``matvec`` / ``rmatvec`` / ``sq_rmatvec`` as called
    here (scoring, TRON's Hessian-vector product, the variances) read
    ``indices`` / ``values``, which stay. It is in feature order and cannot
    be cut by rows: whatever builds a ``SparseFeatures`` from some of these
    rows, pads or shards them, or re-stores the values, leaves it behind.
    """

    indices: Array  # (N, K) int32
    values: Array  # (N, K) — may be stored bfloat16; accumulation is f32

    dim: int = dataclasses.field(metadata={"static": True})

    # optional index-sorted transpose layout (``with_transpose()``): the
    # gradient pass becomes a segment-sum over SORTED feature indices
    # instead of a random scatter-add into a (dim,)-wide vector — the
    # scatter is the TPU-hostile op in the sparse-wide regime (D ~ 2^20),
    # a sorted segment sum lowers to sequential accumulation runs.
    t_idx: Optional[Array] = None  # (nnz,) int32, sorted feature index
    t_row: Optional[Array] = None  # (nnz,) int32, source row of each entry
    t_val: Optional[Array] = None  # (nnz,) entry values in t_idx order
    tiles: Optional["TileLayout"] = None  # ops/tiled_sparse.py

    @property
    def num_rows(self) -> int:
        return self.indices.shape[0]

    def tiled(self) -> Optional["TiledFeatures"]:
        """The two products over the tile layout, for the value-and-gradient
        pass; None where the batch carries none. Refuses a layout that does
        not fit these rows (one of the two was cut without the other)."""
        from photon_ml_tpu.ops.tiled_sparse import TiledFeatures

        if self.tiles is None or self.t_idx is not None:
            return None
        return TiledFeatures(self.tiles.check(self.num_rows))

    def without_tiles(self) -> "SparseFeatures":
        """These rows with the row-order layouts alone: what goes on to be
        padded or sharded by rows."""
        return dataclasses.replace(self, tiles=None)

    def with_transpose(self) -> "SparseFeatures":
        """Precompute the sorted transpose layout (host-side, once at
        ingest — the analogue of building a CSC view)."""
        import numpy as np

        idx = np.asarray(self.indices).reshape(-1)
        val = np.asarray(self.values).reshape(-1)
        n, k = self.indices.shape
        rows = np.repeat(np.arange(n, dtype=np.int32), k)
        order = np.argsort(idx, kind="stable")
        return SparseFeatures(
            self.indices,
            self.values,
            self.dim,
            t_idx=jnp.asarray(idx[order]),
            t_row=jnp.asarray(rows[order]),
            t_val=jnp.asarray(val[order]),
        )

    @jax.named_scope("pml.features.matvec")
    def matvec(self, w: Array) -> Array:
        acc = _acc_dtype(self.values.dtype)
        prods = w[self.indices].astype(acc) * self.values.astype(acc)
        return jnp.sum(prods, axis=-1)

    @jax.named_scope("pml.features.rmatvec")
    def rmatvec(self, d: Array, into: Optional[Array] = None) -> Array:
        """X^T d. ``into``: a ``(dim,)`` sum the products are scatter-added
        onto instead of zeros (the row-blocked gradient pass carries one
        across its blocks); the row-order layout only."""
        acc = _acc_dtype(self.values.dtype)
        if self.t_idx is not None:
            assert into is None, "the sorted transpose is never cut by rows"
            contrib = self.t_val.astype(acc) * d.astype(acc)[self.t_row]
            return jax.ops.segment_sum(
                contrib, self.t_idx, num_segments=self.dim,
                indices_are_sorted=True,
            )
        contrib = self.values.astype(acc) * d.astype(acc)[:, None]
        base = jnp.zeros((self.dim,), acc) if into is None else into
        return base.at[self.indices.reshape(-1)].add(contrib.reshape(-1))

    @jax.named_scope("pml.features.sq_rmatvec")
    def sq_rmatvec(self, d: Array) -> Array:
        acc = _acc_dtype(self.values.dtype)
        if self.t_idx is not None:
            # Hessian-diagonal path (TRON/variance) rides the same sorted
            # segment sum as rmatvec
            contrib = jnp.square(self.t_val.astype(acc)) * d.astype(acc)[self.t_row]
            return jax.ops.segment_sum(
                contrib, self.t_idx, num_segments=self.dim,
                indices_are_sorted=True,
            )
        contrib = jnp.square(self.values.astype(acc)) * d.astype(acc)[:, None]
        return jnp.zeros((self.dim,), acc).at[self.indices.reshape(-1)].add(
            contrib.reshape(-1)
        )

    def row_sq_norms(self) -> Array:
        acc = _acc_dtype(self.values.dtype)
        return jnp.sum(jnp.square(self.values.astype(acc)), axis=-1)

    def to_dense(self) -> Array:
        acc = _acc_dtype(self.values.dtype)
        n, k = self.indices.shape
        out = jnp.zeros((n, self.dim), acc)
        rows = jnp.broadcast_to(jnp.arange(n)[:, None], (n, k))
        return out.at[rows.reshape(-1), self.indices.reshape(-1)].add(
            self.values.reshape(-1).astype(acc)
        )

    def astype(self, dtype) -> "SparseFeatures":
        """Re-store the values in another dtype (bf16 for bandwidth)."""
        return SparseFeatures(
            self.indices,
            self.values.astype(dtype),
            self.dim,
            t_idx=self.t_idx,
            t_row=self.t_row,
            t_val=None if self.t_val is None else self.t_val.astype(dtype),
        )

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        return (self.indices, self.values, self.t_idx, self.t_row, self.t_val,
                self.tiles), self.dim

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], aux, *children[2:])


Features = Union[DenseFeatures, SparseFeatures]


def from_scipy_like(rows, dim: int, dtype=jnp.float32) -> SparseFeatures:
    """Build SparseFeatures from a list of (indices, values) per row (host)."""
    import numpy as np

    n = len(rows)
    k = max((len(ix) for ix, _ in rows), default=1)
    k = max(k, 1)
    indices = np.zeros((n, k), np.int32)
    values = np.zeros((n, k), np.float32)
    for i, (ix, vs) in enumerate(rows):
        indices[i, : len(ix)] = ix
        values[i, : len(vs)] = vs
    return SparseFeatures(jnp.asarray(indices), jnp.asarray(values, dtype), dim)


# Production rule for the transpose layout. The two layouts (the random
# scatter-add into a (dim,)-wide vector, and the sorted segment sum over the
# CSC view) have not been raced on a benchmark cell (ROADMAP S2), so no
# record says which the v5e prefers in the wide regime. Until one does, the
# default is the scatter layout everywhere, and
# ``PHOTON_ML_TPU_SPARSE_TRANSPOSE=1`` forces the CSC view on for a
# comparison without a code change. The row-blocked value-and-gradient pass
# (``ops/objective.py``) needs the row-order layout: a batch with the CSC
# view keeps the whole-batch pass.
#
# The tile layout (``ops/tiled_sparse.py``) is the production rule for the
# second layout since PR 36: 15.011 -> 2 to 3 s a job of the benchmark's
# sparse cell (PERF.md section 6). It is chosen from what can be seen when the
# batch is placed, with no flag and no race.
SPARSE_TRANSPOSE_MIN_DIM = 1 << 16


def wants_tiles(platform: str, index_dtype, value_dtype, n: int, k: int,
                dim: int) -> bool:
    """The rule for the tile layout, a pure function of what placement sees:
    a TPU (the kernels are the matrix unit's), int32 indices and float32
    values (the pieces are float32's), more stored slots than one row block
    of the blocked pass (a smaller batch's element loops are milliseconds,
    and the per-entity batches never come near), a feature space at or over
    ``SPARSE_TRANSPOSE_MIN_DIM`` (under it the row-order gather stays in fast
    memory) and at or under ``tiled_sparse.MAX_DIM`` (over it the kernels'
    resident tables do not fit)."""
    from photon_ml_tpu.ops.objective import ROW_BLOCK_NNZ
    from photon_ml_tpu.ops.tiled_sparse import MAX_DIM

    return (
        platform == "tpu"
        and jnp.dtype(index_dtype) == jnp.int32
        and jnp.dtype(value_dtype) == jnp.float32
        and n * k > ROW_BLOCK_NNZ
        and SPARSE_TRANSPOSE_MIN_DIM <= dim <= MAX_DIM
    )


def auto_transpose(feats: SparseFeatures) -> SparseFeatures:
    """Give freshly placed features their second layout, by the production
    rule. ``io/libsvm.py`` ``to_batch`` (the GLM driver) and the benchmark's
    sparse family call this once on a new ``SparseFeatures``, outside every
    job. Where :func:`wants_tiles` says so (and the arrays lie whole on one
    device: the layout cannot be sharded by rows) the features come back
    with the tile layout built on that device beside ``indices`` /
    ``values``, for ``GLMObjective.value_and_grad``; else, under
    ``PHOTON_ML_TPU_SPARSE_TRANSPOSE=1``, with the sorted transpose; else as
    they came."""
    from photon_ml_tpu.compile.overrides import sparse_transpose_forced

    if feats.t_idx is not None or feats.tiles is not None \
            or feats.dim < SPARSE_TRANSPOSE_MIN_DIM:
        return feats
    if sparse_transpose_forced():
        return feats.with_transpose()
    placed = isinstance(feats.indices, jax.Array) and not isinstance(
        feats.indices, jax.core.Tracer)
    if placed and len(feats.indices.devices()) == 1 and wants_tiles(
            next(iter(feats.indices.devices())).platform, feats.indices.dtype,
            feats.values.dtype, *feats.indices.shape, feats.dim):
        from photon_ml_tpu.ops import tiled_sparse

        return dataclasses.replace(feats, tiles=tiled_sparse.build(
            feats.indices, feats.values, feats.dim))
    return feats
