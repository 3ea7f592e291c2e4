"""Random-effect coordinate: vmapped per-entity GLM solves.

Reference spec: algorithm/RandomEffectCoordinate.scala:36-201 — per-entity
solve = activeData join problems join models -> mapValues{ local Breeze
optimizer }, scoring = join models with data by entity. TPU-native:

  * entities are the leading axis of padded ``(E, M, D_loc)`` tensors
    (built at ingest, data/game.py), so "one optimizer per entity"
    (RandomEffectOptimizationProblem.scala:39-125) is the SAME while_loop
    kernel ``vmap``-ed over the entity axis — converged entities keep
    looping as masked no-ops until the slowest lane finishes, which is why
    the kernels are branch-free;
  * sharding the entity axis over the mesh gives the reference's
    co-partitioned-RDD model parallelism with zero joins;
  * scoring is one gather: score_n = sum_k val_nk * W[entity(n), col_nk] —
    the cogroup in RandomEffectModel.scala:129-158 with static indices;
    rows whose entity has no model score 0 (same semantics).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from photon_ml_tpu.data.game import RandomEffectDataset
from photon_ml_tpu.ops import losses as losses_mod
from photon_ml_tpu.ops.features import DenseFeatures
from photon_ml_tpu.ops.normalization import NormalizationContext
from photon_ml_tpu.ops.objective import GLMBatch, GLMObjective
from photon_ml_tpu.optim.common import OptimizerConfig, OptResult
from photon_ml_tpu.optim.lbfgs import lbfgs_minimize_
from photon_ml_tpu.optim.tron import tron_minimize_
from photon_ml_tpu.ops.regularization import RegularizationContext
from photon_ml_tpu.types import OptimizerType, TaskType, real_dtype

Array = jax.Array


def entity_lane_fns(task, optimizer, optimizer_config, regularization,
                    reg_weight=None):
    """Per-lane solver closures over ONE entity's ``(x, y, off, w, ...)``
    problem, shared by the one-shot vmapped solve and the convergence-
    compaction scheduler (optim/scheduler.py) — both paths build the SAME
    objective closures, so their per-iteration arithmetic is bit-identical.

    Returns ``(solve_one, init_one, advance_one, result_of)``:
      * ``solve_one(x, y, off_e, w_e, w0) -> OptResult`` — the one-shot body
        ``RandomEffectCoordinate.update`` vmaps;
      * ``init_one(x, y, off_e, w_e, w0) -> state`` — fresh resumable state;
      * ``advance_one(x, y, off_e, w_e, state, limit) -> state`` — run until
        convergence or the absolute iteration ``limit`` (traced ok);
      * ``result_of(state) -> OptResult`` — view of a final state (works on
        lane-stacked states too).

    The three solving closures run under the device scope
    ``pml.re.lane_solve``, so the one-shot program, the scheduler's
    init/chunk programs and the fused rung program all show the per-lane
    solve under one name in a trace.
    """
    from photon_ml_tpu.optim.lbfgs import (
        lbfgs_advance_,
        lbfgs_init_,
        lbfgs_result,
    )
    from photon_ml_tpu.optim.problem import _split_reg_weight
    from photon_ml_tpu.optim.tron import tron_advance_, tron_init_, tron_result

    loss = losses_mod.for_task(task)
    obj = GLMObjective(loss)
    norm = NormalizationContext.identity()
    l1, l2 = _split_reg_weight(regularization, reg_weight)
    cfg = optimizer_config

    def feats_of(x):
        # the lane's features: a dense (M, D) array, or a per-lane
        # SparseSlab view (ops/fused_sparse.py) — the slab already speaks
        # the Features protocol, and its static ``kernel`` field routes
        # the objective to the selected sparse family (fused Pallas GEVM /
        # XLA scatter / segment-sum) without touching the solver kernels
        return x if hasattr(x, "matvec") else DenseFeatures(x)

    def vg_of(x, y, off_e, w_e):
        batch = GLMBatch(feats_of(x), y, off_e, w_e)
        return lambda wt: obj.value_and_grad(wt, batch, norm, l2)

    if optimizer == OptimizerType.TRON:

        def hvp_of(x, y, off_e, w_e):
            batch = GLMBatch(feats_of(x), y, off_e, w_e)
            return lambda wt, v: obj.hessian_vector(wt, v, batch, norm, l2)

        @jax.named_scope("pml.re.lane_solve")
        def solve_one(x, y, off_e, w_e, w0):
            return tron_minimize_(
                vg_of(x, y, off_e, w_e), hvp_of(x, y, off_e, w_e), w0, cfg
            )

        @jax.named_scope("pml.re.lane_solve")
        def init_one(x, y, off_e, w_e, w0):
            return tron_init_(vg_of(x, y, off_e, w_e), w0, cfg)

        @jax.named_scope("pml.re.lane_solve")
        def advance_one(x, y, off_e, w_e, state, limit):
            return tron_advance_(
                vg_of(x, y, off_e, w_e), hvp_of(x, y, off_e, w_e), state, cfg,
                iteration_limit=limit,
            )

        return solve_one, init_one, advance_one, tron_result

    @jax.named_scope("pml.re.lane_solve")
    def solve_one(x, y, off_e, w_e, w0):
        return lbfgs_minimize_(vg_of(x, y, off_e, w_e), w0, cfg, l1_weight=l1)

    @jax.named_scope("pml.re.lane_solve")
    def init_one(x, y, off_e, w_e, w0):
        return lbfgs_init_(vg_of(x, y, off_e, w_e), w0, cfg, l1_weight=l1)

    @jax.named_scope("pml.re.lane_solve")
    def advance_one(x, y, off_e, w_e, state, limit):
        return lbfgs_advance_(
            vg_of(x, y, off_e, w_e), state, cfg, l1_weight=l1,
            iteration_limit=limit,
        )

    return solve_one, init_one, advance_one, lbfgs_result


@dataclasses.dataclass
class RandomEffectCoordinate:
    """Per-entity models over a RandomEffectDataset.

    ``solve_schedule`` (optim/scheduler.SolveSchedule, None = one-shot)
    routes ``update`` through the convergence-compaction scheduler: the
    vmapped solve runs in chunks of K iterations, unconverged lanes are
    compacted into ladder-sized batches between chunks, and finished lanes'
    results scatter back to entity order — bit-identical coefficients, far
    fewer wasted lane-iterations on skewed convergence distributions. A
    scheduled coordinate re-enters the host between chunks, so it opts out
    of the CoordinateDescent outer jit (``cd_jit=False``, like streaming).
    """

    dataset: RandomEffectDataset
    task: TaskType
    optimizer: OptimizerType = OptimizerType.LBFGS
    optimizer_config: Optional[OptimizerConfig] = None
    regularization: RegularizationContext = dataclasses.field(
        default_factory=RegularizationContext.none
    )
    solve_schedule: Optional[object] = None  # optim.scheduler.SolveSchedule
    # telemetry label the compacted solves record under (solve_stats):
    # wrappers set e.g. "bucket3" / "streaming-re[block 7]"
    solve_label: str = "re_solve"
    # sparse per-entity kernels (ops/fused_sparse.py). ``sparse_kernel``:
    # None = PHOTON_SPARSE_KERNEL (default off) | "auto" (race the families
    # and the dense incumbent on this dataset's own tensors) | a family
    # name. ``sparse_slab``: a prebuilt slab from a wrapper (bucketed /
    # streaming coordinates build per-bucket/per-block slabs host-side and
    # pass them through jit; its ``kernel`` field carries the selection).
    sparse_kernel: Optional[str] = None
    sparse_slab: Optional[object] = None  # ops.fused_sparse.SparseSlab
    # GSPMD entity sharding for SCHEDULED solves (parallel.mesh.MeshContext):
    # the dataset's entity axis is padded to a device multiple and sharded
    # over the mesh, and the scheduler's shared chunk kernels run over the
    # sharded arrays — XLA partitions the vmapped lanes across devices
    # while the compaction loop stays host-side OUTSIDE the mesh program.
    # Numerical contract: same as the shard_map engine (allclose at f32 —
    # XLA may fuse a lane's sample/feature reductions differently per
    # per-device batch size); the BITWISE host-count guarantee lives on
    # the owner-computes streaming path, which never re-partitions lanes.
    # One-shot mesh solves keep using the shard_map engine
    # (parallel.distributed.DistributedRandomEffectSolver).
    mesh_ctx: Optional[object] = None

    def __post_init__(self):
        if self.optimizer_config is None:
            self.optimizer_config = (
                OptimizerConfig.tron_default()
                if self.optimizer == OptimizerType.TRON
                else OptimizerConfig.lbfgs_default()
            )
        self._true_entities = self.dataset.num_entities
        if self.mesh_ctx is not None:
            if self.solve_schedule is None:
                raise ValueError(
                    "mesh_ctx on RandomEffectCoordinate is the GSPMD-"
                    "sharded scheduled path and needs a solve_schedule; "
                    "one-shot mesh solves use parallel.distributed."
                    "DistributedRandomEffectSolver"
                )
            from photon_ml_tpu.parallel.distributed import (
                pad_and_shard_re_dataset,
            )

            self.dataset = pad_and_shard_re_dataset(self.dataset, self.mesh_ctx)
            # sparse slabs stay dense under the mesh: the bucketed-COO
            # slab build is a host-side single-device construct (the
            # execution plan records this as a pinned decision)
            self.sparse_kernel = "off"
            self.sparse_slab = None
        if self.solve_schedule is not None:
            # chunk pauses re-enter the host: the outer CoordinateDescent
            # jit must call this coordinate's update raw (instance attr —
            # the class default stays True for one-shot coordinates)
            self.cd_jit = False
        self._slab = self.sparse_slab
        if self._slab is None:
            from photon_ml_tpu.ops.fused_sparse import resolve_sparse_kernel

            spec = resolve_sparse_kernel(self.sparse_kernel)
            if spec is not None:
                self._slab = self._build_slab(spec)

    def _build_slab(self, spec: str):
        """Host-side slab build + (for "auto") the per-dataset family race.
        Needs concrete tensors: coordinates constructed under a trace must
        receive a prebuilt ``sparse_slab`` instead (wrappers that construct
        sub-coordinates inside jit/shard_map pin ``sparse_kernel="off"``)."""
        from photon_ml_tpu.ops import fused_sparse

        ds = self.dataset
        if isinstance(ds.x, jax.core.Tracer):
            raise ValueError(
                "sparse-kernel selection builds the slab host-side and "
                "cannot run under a trace; pass a prebuilt sparse_slab "
                "when constructing this coordinate inside jit"
            )
        # None = the race handed the bucket back to the dense incumbent
        return fused_sparse.build_and_select(
            self.task, ds.x, ds.labels, ds.base_offsets, ds.weights,
            spec, self.solve_label,
        )

    @property
    def num_entities(self) -> int:
        return self.dataset.num_entities

    @property
    def true_entities(self) -> int:
        """Real (pre-mesh-padding) entity count — what exports and exact
        reductions slice to."""
        return self._true_entities

    @property
    def local_dim(self) -> int:
        return self.dataset.local_dim

    def initial_coefficients(self) -> Array:
        return jnp.zeros((self.num_entities, self.local_dim), real_dtype())

    # ------------------------------------------------------------------
    def gathered_offsets(self, residual_offsets: Array) -> Array:
        """Global (N,) residual scores gathered into the entity-major
        (E, M) layout and added to the base offsets (the addScoresToOffsets
        of RandomEffectDataSet.scala:57-74, as a gather instead of a
        join). Masked slots (row_index == -1) contribute base offset only."""
        ds = self.dataset
        safe_rows = jnp.maximum(ds.row_index, 0)
        gathered = residual_offsets[safe_rows]
        return ds.base_offsets + jnp.where(ds.row_index >= 0, gathered, 0.0)

    def update(self, residual_offsets: Array, init_coefficients: Array,
               reg_weight: Optional[Array] = None,
               resume: Optional[dict] = None) -> Tuple[Array, OptResult]:
        """Solve every entity's local problem (vmapped).

        ``residual_offsets`` is the global (N,) residual-score vector from
        the other coordinates. ``reg_weight`` overrides the context's
        total regularization weight as a TRACED scalar (the lambda-grid
        vmap axis). ``resume`` is a scheduler preemption snapshot (the
        ``partial`` payload of a
        :class:`~photon_ml_tpu.resilience.preemption.Preempted` raised at a
        chunk boundary) — the interrupted solve continues bitwise-identically
        from its paused carries; only valid with a ``solve_schedule``.

        Returns stacked coefficients (E, D_loc) and the vmapped OptResult
        (every field gains a leading entity axis — this is the
        RandomEffectOptimizationTracker's raw material).
        """
        ds = self.dataset
        off = self.gathered_offsets(residual_offsets)
        # the per-lane feature leaf: the dense (E, M, D) stack, or the
        # bucketed sparse slab when a sparse family was selected — the
        # solver kernels and the scheduler treat it as an opaque pytree
        feats = self._slab if self._slab is not None else ds.x

        if self.solve_schedule is not None:
            if reg_weight is not None:
                raise ValueError(
                    "solve compaction re-enters the host between chunks and "
                    "cannot run inside the traced-lambda grid; drop "
                    "solve_schedule or the reg_weight override"
                )
            from photon_ml_tpu.optim.scheduler import compacted_solve

            results = compacted_solve(
                (feats, ds.labels, off, ds.weights),
                init_coefficients,
                task=self.task,
                optimizer=self.optimizer,
                optimizer_config=self.optimizer_config,
                regularization=self.regularization,
                schedule=self.solve_schedule,
                label=self.solve_label,
                resume=resume,
            )
            if self.mesh_ctx is not None:
                # the coefficient slab keeps the sharded padded shape (the
                # carry contract); trackers trim to real entities at the
                # source, like the shard_map engine
                from photon_ml_tpu.parallel.distributed import (
                    trim_entity_tracker,
                )

                return results.coefficients, trim_entity_tracker(
                    results, self._true_entities, self.num_entities
                )
            return results.coefficients, results

        if resume is not None:
            raise ValueError(
                "a mid-solve resume snapshot needs the convergence "
                "scheduler's chunk boundaries; this coordinate solves "
                "one-shot (no solve_schedule)"
            )
        solve_one, _, _, _ = entity_lane_fns(
            self.task, self.optimizer, self.optimizer_config,
            self.regularization, reg_weight,
        )
        results = jax.vmap(solve_one)(feats, ds.labels, off, ds.weights, init_coefficients)
        return results.coefficients, results

    # ------------------------------------------------------------------
    def coefficient_variances(self, coefficients: Array,
                              residual_offsets: Array) -> Array:
        """Per-entity coefficient variances = 1 / Hessian-diagonal at the
        final coefficients, vmapped over entities -> (E, D_loc).

        Parity: RandomEffectOptimizationProblem builds its per-entity
        problems with the driver's isComputingVariance flag
        (optimization/game/RandomEffectOptimizationProblem.scala:110-124),
        each computing variance = 1/H_jj like the fixed effect
        (LogisticRegressionOptimizationProblem.scala:109-124). Computed
        lazily at save time (one vmapped pass), not per update.
        """
        ds = self.dataset
        loss = losses_mod.for_task(self.task)
        obj = GLMObjective(loss)
        norm = NormalizationContext.identity()
        l2 = self.regularization.l2_weight

        off = self.gathered_offsets(residual_offsets)

        def diag_one(x, y, off_e, w_e, w):
            batch = GLMBatch(DenseFeatures(x), y, off_e, w_e)
            return obj.hessian_diagonal(w, batch, norm, l2)

        from photon_ml_tpu.optim.problem import variances_from_hessian_diag

        diag = jax.vmap(diag_one)(ds.x, ds.labels, off, ds.weights, coefficients)
        return variances_from_hessian_diag(diag)

    # ------------------------------------------------------------------
    @jax.named_scope("pml.re.score")
    def score(self, coefficients: Array) -> Array:
        """Global (N,) scores for ALL rows (active + passive).

        score_n = sum_k val_nk * W[entity_pos_n, feat_idx_nk]; rows whose
        entity has no model (entity_pos == -1) score 0.
        """
        ds = self.dataset
        ep = jnp.maximum(ds.entity_pos, 0)
        li = jnp.maximum(ds.feat_idx, 0)
        coefs = coefficients[ep[:, None], li]  # (N, K)
        valid = (ds.entity_pos[:, None] >= 0) & (ds.feat_idx >= 0)
        return jnp.sum(jnp.where(valid, coefs * ds.feat_val, 0.0), axis=-1)

    # ------------------------------------------------------------------
    def regularization_term(self, coefficients: Array,
                            reg_weight: Optional[Array] = None) -> Array:
        """Sum of per-entity regularization terms
        (RandomEffectOptimizationProblem.getRegularizationTermValue)."""
        from photon_ml_tpu.optim.problem import _split_reg_weight

        l1, l2 = _split_reg_weight(self.regularization, reg_weight)
        if self.mesh_ctx is not None:
            # slice the mesh padding off so the reduction runs over exactly
            # the unsharded coordinate's array shape — the term stays
            # bitwise-equal by construction, not by pad-lanes-are-zero
            coefficients = coefficients[: self._true_entities]
        return l1 * jnp.sum(jnp.abs(coefficients)) + 0.5 * l2 * jnp.sum(
            jnp.square(coefficients)
        )

    # ------------------------------------------------------------------
    def global_coefficients(self, coefficients: Array) -> Array:
        return global_coefficients(self.dataset, coefficients)


def global_coefficients(dataset: RandomEffectDataset, coefficients: Array) -> Array:
    """Per-entity local coefficients back in the global feature space
    -> (E, D_global) (RandomEffectModelInProjectedSpace.toRandomEffectModel
    parity). INDEX_MAP/IDENTITY datasets scatter via local_to_global;
    RANDOM datasets back-project through the stored projection matrix
    (W_global = W_proj @ M). Host-sized output; for export/inspection."""
    ds = dataset
    if ds.projection_matrix is not None:
        return coefficients @ ds.projection_matrix
    e, d_loc = coefficients.shape
    out = jnp.zeros((e, ds.global_dim), coefficients.dtype)
    cols = jnp.maximum(ds.local_to_global, 0)
    valid = ds.local_to_global >= 0
    rows = jnp.broadcast_to(jnp.arange(e)[:, None], cols.shape)
    return out.at[rows.reshape(-1), cols.reshape(-1)].add(
        jnp.where(valid, coefficients, 0.0).reshape(-1)
    )
