"""Per-host factored random-effect coordinate (multihost-trainable MF).

The multihost analogue of the reference's cluster-side factored coordinate
(FactoredRandomEffectCoordinate.scala:36-285, built by the training driver
at cli/game/training/Driver.scala:379-396): per-entity latent coefficients
v_e live entity-sharded on the device that OWNS the entity (the same
per-host slab ownership as PerHostRandomEffectSolver), the shared latent
matrix M is replicated, and one shard_map runs the alternating update —

  (a) per-entity latent solves over the owner's slab projected by M
      (zero collectives: entities are independent);
  (b) the latent-matrix refit computes per-device partial (value, grad,
      Hv) over the device's OWN rows and ``psum``s them across the mesh
      axis (which spans hosts under ``jax.distributed``), so every device
      on every host walks one identical optimizer trajectory on M — the
      reference's treeAggregate over executors becomes the psum.

The dataset must be built by ``per_host_re_dataset(projector="IDENTITY")``:
the factored model projects the GLOBAL shard space through M, so slabs
carry raw global-dim features (exactly the constraint the single-process
FactoredRandomEffectCoordinate enforces).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from photon_ml_tpu.algorithm.factored_random_effect import (
    FactoredRandomEffectCoordinate,
    FactoredState,
    MFOptimizationConfig,
)
from photon_ml_tpu.ops.regularization import RegularizationContext
from photon_ml_tpu.optim.common import OptimizerConfig
from photon_ml_tpu.parallel.mesh import MeshContext
from photon_ml_tpu.parallel.perhost_ingest import ShardedREData, local_shards
from photon_ml_tpu.projectors import gaussian_random_projection_matrix
from photon_ml_tpu.types import OptimizerType, TaskType, real_dtype

Array = jax.Array


@dataclasses.dataclass
class PerHostFactoredRandomEffectCoordinate:
    """Drop-in CoordinateDescent coordinate over per-host IDENTITY slabs.

    State is a :class:`FactoredState` pytree whose ``v`` is entity-sharded
    ``P(axis)`` and whose ``matrix`` is replicated ``P()`` — the placement
    every update preserves.
    """

    data: ShardedREData
    task: TaskType
    mf_config: MFOptimizationConfig = dataclasses.field(
        default_factory=MFOptimizationConfig
    )
    re_optimizer: OptimizerType = OptimizerType.LBFGS
    re_optimizer_config: Optional[OptimizerConfig] = None
    re_regularization: RegularizationContext = dataclasses.field(
        default_factory=RegularizationContext.none
    )
    latent_optimizer: OptimizerType = OptimizerType.LBFGS
    latent_optimizer_config: Optional[OptimizerConfig] = None
    latent_regularization: RegularizationContext = dataclasses.field(
        default_factory=RegularizationContext.none
    )
    seed: int = 1234567890
    ctx: MeshContext = None  # type: ignore[assignment]

    def __post_init__(self):
        if self.data.projector != "IDENTITY":
            raise ValueError(
                "PerHostFactoredRandomEffectCoordinate requires slabs built "
                "with per_host_re_dataset(projector='IDENTITY') — got "
                f"{self.data.projector!r} (the latent matrix projects the "
                "global shard space; see FactoredRandomEffectCoordinate)"
            )
        self._update_fn = None
        self._score_fn = None
        self._coef_fn = None
        self._vterm_fn = None
        # same contract as PerHostRandomEffectSolver: under multihost SPMD
        # the sharded slabs are non-addressable, so CoordinateDescent must
        # not close over them in an outer jit
        self.cd_jit = jax.process_count() == 1

    # ------------------------------------------------------------------
    @property
    def latent_dim(self) -> int:
        return self.mf_config.latent_space_dimension

    def initial_coefficients(self) -> FactoredState:
        d = self.data
        m0 = gaussian_random_projection_matrix(
            self.latent_dim, d.local_dim, keep_intercept=False, seed=self.seed
        )
        v0 = jnp.zeros((d.entity_mask.shape[0], self.latent_dim), real_dtype())
        return FactoredState(
            v=jax.device_put(v0, NamedSharding(self.ctx.mesh, P(self.ctx.axis))),
            matrix=jax.device_put(
                jnp.asarray(m0), NamedSharding(self.ctx.mesh, P())
            ),
        )

    def _inner_for(self, ds) -> FactoredRandomEffectCoordinate:
        return FactoredRandomEffectCoordinate(
            ds,
            self.task,
            mf_config=self.mf_config,
            re_optimizer=self.re_optimizer,
            re_optimizer_config=self.re_optimizer_config,
            re_regularization=self.re_regularization,
            latent_optimizer=self.latent_optimizer,
            latent_optimizer_config=self.latent_optimizer_config,
            latent_regularization=self.latent_regularization,
            seed=self.seed,
            axis_name=self.ctx.axis,
        )

    # ------------------------------------------------------------------
    def update(self, residual_offsets: Array, state: FactoredState):
        from photon_ml_tpu.data.game import RandomEffectDataset

        if self._update_fn is None:
            axis = self.ctx.axis
            gdim = self.data.global_dim

            def solve_shard(x, labels, offs, wgts, row_index, v0, mat0,
                            residuals):
                dummy = jnp.zeros((1,), jnp.int32)
                ds = RandomEffectDataset(
                    row_index=row_index, x=x, labels=labels,
                    base_offsets=offs, weights=wgts, entity_pos=dummy,
                    feat_idx=dummy[None],
                    feat_val=dummy[None].astype(x.dtype),
                    local_to_global=dummy[None],
                    num_entities=x.shape[0], global_dim=gdim,
                )
                st, results = self._inner_for(ds).update(
                    residuals, FactoredState(v0, mat0)
                )
                return st.v, st.matrix, results

            self._update_fn = jax.jit(
                shard_map(
                    solve_shard,
                    mesh=self.ctx.mesh,
                    in_specs=(P(axis), P(axis), P(axis), P(axis), P(axis),
                              P(axis), P(), P()),
                    out_specs=(P(axis), P(), P(axis)),
                    # same rationale as DistributedFactoredRandomEffect-
                    # Coordinate: the replicated-M optimizer loop carries
                    # inside the vmapped while_loop kernels trip the
                    # varying-axes check although the latent psums make M
                    # genuinely replicated; compensating control is the
                    # multihost-vs-single-process parity test
                    # (tests/test_multihost.py factored parity).
                    check_vma=False,
                )
            )
        d = self.data
        residuals = jax.device_put(
            residual_offsets, NamedSharding(self.ctx.mesh, P())
        )
        v, mat, results = self._update_fn(
            d.x, d.labels, d.base_offsets, d.weights, d.row_index,
            state.v, state.matrix, residuals,
        )
        return FactoredState(v=v, matrix=mat), results

    # ------------------------------------------------------------------
    def score(self, state: FactoredState) -> Array:
        """Owner-computes factored scoring over the per-host scoring
        tensors: each device projects its OWN rows' (IDENTITY-space = global
        index) features through the replicated M, dots with its v-slab, and
        one psum merges the scattered (N,) partials."""
        if not self.data.row_ids_dense:
            raise ValueError(
                "dataset was built slab_build_only from non-dense row ids; "
                "scoring would silently drop out-of-bounds scatters"
            )
        if self._score_fn is None:
            axis = self.ctx.axis
            n = self.data.num_rows

            def score_shard(v_loc, mat, srow, sslot, sfi, sfv):
                wsel = v_loc[jnp.maximum(sslot, 0)]  # (R, k)
                cols = jnp.maximum(sfi, 0)
                vals = jnp.where(sfi >= 0, sfv, 0.0)
                m_cols = mat.T[cols]  # (R, K, k)
                xp = jnp.sum(m_cols * vals[:, :, None], axis=1)  # (R, k)
                s = jnp.where(srow >= 0, jnp.sum(xp * wsel, axis=-1), 0.0)
                out = jnp.zeros((n,), s.dtype).at[jnp.maximum(srow, 0)].add(s)
                return jax.lax.psum(out, axis)

            self._score_fn = jax.jit(
                shard_map(
                    score_shard,
                    mesh=self.ctx.mesh,
                    in_specs=(P(axis), P(), P(axis), P(axis), P(axis), P(axis)),
                    out_specs=P(),
                )
            )
        d = self.data
        return self._score_fn(
            state.v, state.matrix, d.score_row_index, d.score_slot,
            d.score_feat_idx, d.score_feat_val,
        )

    # ------------------------------------------------------------------
    def regularization_term(self, state: FactoredState) -> Array:
        re, lat = self.re_regularization, self.latent_regularization
        # v is sharded: sum its term under a shard_map psum so every host
        # sees the global value; M is replicated — term computed directly.
        # The jitted shard_map closure is cached on the instance (like
        # _update_fn/_score_fn): rebuilding it per call re-traced and
        # re-jitted the collective every evaluation (ADVICE.md).
        if self._vterm_fn is None:
            axis = self.ctx.axis

            def v_term(v):
                t = re.l1_weight * jnp.sum(jnp.abs(v)) + (
                    0.5 * re.l2_weight * jnp.sum(jnp.square(v))
                )
                return jax.lax.psum(t, axis)

            self._vterm_fn = jax.jit(
                shard_map(v_term, mesh=self.ctx.mesh, in_specs=(P(axis),),
                          out_specs=P())
            )
        vterm = self._vterm_fn(state.v)
        mterm = lat.l1_weight * jnp.sum(jnp.abs(state.matrix)) + (
            0.5 * lat.l2_weight * jnp.sum(jnp.square(state.matrix))
        )
        return vterm + mterm

    # ------------------------------------------------------------------
    def random_effect_coefficients(self, state: FactoredState) -> Array:
        """Entity-sharded equivalent plain coefficients W = V M — stays
        sharded so model save can write per-host part files."""
        if self._coef_fn is None:
            axis = self.ctx.axis
            self._coef_fn = jax.jit(
                shard_map(
                    lambda v, m: v @ m, mesh=self.ctx.mesh,
                    in_specs=(P(axis), P()), out_specs=P(axis),
                )
            )
        return self._coef_fn(state.v, state.matrix)

    def latent_factors_by_raw_id(self, state: FactoredState):
        """HOST-LOCAL raw-id -> latent vector map for this host's entities
        (what per-host LatentFactorAvro part files need)."""
        from photon_ml_tpu.parallel.perhost_ingest import _unpack_u64

        d = self.data
        out = {}
        for v_d, k_d, m_d in zip(
            local_shards(state.v), local_shards(d.entity_keys),
            local_shards(d.entity_mask),
        ):
            keys = _unpack_u64(k_d[:, 0], k_d[:, 1])
            for lane in np.nonzero(m_d.astype(bool))[0]:
                out[d.raw_ids_by_key[int(keys[lane])]] = np.asarray(
                    v_d[lane], np.float32
                )
        return out
