"""Mesh construction and sharding helpers.

The reference's cluster topology is implicit (YARN executors + Spark
partitioners, e.g. LongHashPartitioner, RandomEffectIdPartitioner). Here the
topology is an explicit ``jax.sharding.Mesh``; placement is declared with
``NamedSharding`` and XLA lowers cross-device movement to ICI collectives.

Two axes cover the reference's parallelism vocabulary (SURVEY.md §2.4):

  * ``data``  — examples (fixed effect) or entities (random effect) are
    sharded along it. This is Spark's partition axis.
  * replication (no axis) — small global state: coefficient vectors,
    normalization contexts, projection matrices. This is Spark broadcast.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from photon_ml_tpu.ops.features import DenseFeatures, SparseFeatures
from photon_ml_tpu.ops.objective import GLMBatch

Array = jax.Array

DATA_AXIS = "data"


def data_mesh(n_devices: Optional[int] = None, axis: str = DATA_AXIS) -> Mesh:
    """1-D mesh over the first ``n_devices`` devices (default: all).

    A 1-D data mesh is the right topology for GLM training: the model is a
    single replicated vector (there is no intra-op tensor axis to shard), so
    all ICI bandwidth goes to the gradient all-reduce.
    """
    devices = jax.devices()
    if n_devices is not None:
        if len(devices) < n_devices:
            raise ValueError(f"need {n_devices} devices, have {len(devices)}")
        devices = devices[:n_devices]
    return Mesh(np.array(devices), (axis,))


@dataclasses.dataclass(frozen=True)
class MeshContext:
    """A mesh plus the shardings used throughout training."""

    mesh: Mesh
    axis: str = DATA_AXIS

    @property
    def num_devices(self) -> int:
        return int(np.prod(self.mesh.devices.shape))

    def sharded(self, ndim_sharded_leading: int = 1) -> NamedSharding:
        """Sharding that splits the leading axis across the mesh."""
        return NamedSharding(self.mesh, P(self.axis))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def put_sharded(self, tree):
        """Place every array leaf with its leading axis sharded."""
        sh = self.sharded()
        return jax.tree_util.tree_map(lambda a: jax.device_put(a, sh), tree)

    def put_replicated(self, tree):
        sh = self.replicated()
        return jax.tree_util.tree_map(lambda a: jax.device_put(a, sh), tree)


def _pad_array_leading(a: Array, target: int, fill=0.0) -> Array:
    n = a.shape[0]
    if n == target:
        return a
    pad_shape = (target - n,) + tuple(a.shape[1:])
    return jnp.concatenate([a, jnp.full(pad_shape, fill, a.dtype)], axis=0)


def pad_leading(a: Array, multiple: int, fill=0.0) -> Array:
    """Pad the leading axis up to the next multiple (for even sharding)."""
    n = a.shape[0]
    target = ((n + multiple - 1) // multiple) * multiple
    return _pad_array_leading(a, target, fill)


def pad_rows(batch: GLMBatch, multiple: int) -> GLMBatch:
    """Pad a GLMBatch with weight-0 rows so rows % multiple == 0.

    Padding rows carry weight 0 and contribute exactly zero to every
    objective sum (ops/objective.py `_wmul`), so no mask plumbing is needed —
    the reference's uneven Spark partitions become even shards for free.

    Sparse features come back without their tile layout whether rows were
    added or not: this is the gateway to ``put_sharded``, and the layout is
    in feature order (the sharded solve blocks its own rows instead).
    """
    n = batch.num_rows
    target = ((n + multiple - 1) // multiple) * multiple
    feats = batch.features
    if isinstance(feats, SparseFeatures) and feats.tiles is not None:
        # what follows pads these rows or shards them over devices, and the
        # tile layout (ops/tiled_sparse.py) can be neither: it stays behind
        feats = feats.without_tiles()
        batch = GLMBatch(feats, batch.labels, batch.offsets, batch.weights)
    if target == n:
        return batch
    if isinstance(feats, DenseFeatures):
        feats = DenseFeatures(_pad_array_leading(feats.matrix, target))
    elif isinstance(feats, SparseFeatures):
        # the transpose layout stays valid unchanged: padding rows carry
        # only zero values, which contribute nothing to the segment sums
        feats = SparseFeatures(
            _pad_array_leading(feats.indices, target, 0),
            _pad_array_leading(feats.values, target, 0.0),
            feats.dim,
            t_idx=feats.t_idx,
            t_row=feats.t_row,
            t_val=feats.t_val,
        )
    else:
        raise TypeError(f"unsupported features type {type(feats)}")
    return GLMBatch(
        feats,
        _pad_array_leading(batch.labels, target),
        _pad_array_leading(batch.offsets, target),
        _pad_array_leading(batch.weights, target),  # weight 0 = padding
    )
