"""Multi-host SPMD GAME training driver.

Every host runs this SAME program under ``jax.distributed``: it decodes
ONLY its slice of the input part files (per-partition decode with the
shared mmap'd feature index, DataProcessingUtils.scala:57-80 semantics),
ingests per host — the collective shuffle regroups random-effect rows by
entity owner (parallel/shuffle.py), fixed-effect rows stay host-local as
uniform row blocks — trains the coordinate descent over multihost-sharded
coordinates, and each host writes its OWN part file of the random-effect
model (the coefficient slab is never gathered); the coordinator writes the
fixed-effect model and metadata.

This is the driver-contract completion of the reference's cluster driver
(cli/game/training/Driver.scala:537 on Spark executors): same flag
grammar, SPMD instead of driver/executor. Scope (v2): the full coordinate
grid (combo sweep with best-combo selection by the primary validation
evaluator, Driver.scala:330-402 semantics; ``--grid-warm-start true``
additionally seeds each combo from the previous combo's coefficients, the
ModelTraining.scala:158-191 warm-start idea lifted to the combo axis —
off by default so the sweep matches the single-process driver and the
reference exactly), plain + bucketed + factored random-effect
coordinates, all three projector types (INDEX_MAP / RANDOM / IDENTITY,
projector/ProjectorType.scala:22-30), and prebuilt feature index maps
(``--offheap-indexmap-dir`` or a name-and-term path) — index vocabularies
must not require a full-data scan on every host. Datasets are ingested
ONCE (they are combo-invariant); each combo binds fresh optimization
problems to the shared slabs.

Run (one process per host):

    python -m photon_ml_tpu.cli.game_multihost_driver \\
        --multihost-coordinator HOST:PORT --multihost-num-processes N \\
        --multihost-process-id I  <game training flags...>
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from photon_ml_tpu.cli.game_params import (
    CoordinateOptConfig,
    parse_training_params,
)
from photon_ml_tpu.io import model_io
from photon_ml_tpu.io.avro_data import read_game_data
from photon_ml_tpu.ops import losses as losses_mod
from photon_ml_tpu.ops.features import DenseFeatures
from photon_ml_tpu.ops.objective import GLMBatch
from photon_ml_tpu.ops.normalization import NormalizationContext
from photon_ml_tpu.optim.common import OptResult
from photon_ml_tpu.optim.problem import GLMOptimizationProblem
from photon_ml_tpu.parallel import multihost
from photon_ml_tpu.parallel.distributed import DistributedFixedEffectSolver
from photon_ml_tpu.parallel.mesh import MeshContext
from photon_ml_tpu.parallel.perhost_ingest import (
    HostRows,
    PerHostRandomEffectSolver,
    _unpack_u64,
    concat_host_rows,
    csr_to_padded,
    global_row_layout,
    host_file_share,
    local_shards,
    merge_group_ids,
    merge_row_vectors,
    per_host_re_dataset,
)
from photon_ml_tpu.parallel.shuffle import collective_sum
from photon_ml_tpu.types import real_dtype
from photon_ml_tpu.utils.logging import PhotonLogger

Array = jax.Array


class MultihostFixedEffectCoordinate:
    """Fixed-effect coordinate over per-host row blocks (drop-in for
    CoordinateDescent): rows stay where they were decoded; the solve is the
    psum-in-kernel data-parallel GLM; scoring scatters this host's margins
    into the global (N,) vector and one psum merges (owner-computes, like
    the random-effect side — the broadcast model IS the replicated w)."""

    cd_jit = False  # arrays span hosts: CoordinateDescent must not re-jit

    def __init__(self, x, labels, offsets, weights, row_ids, num_rows: int,
                 problem: GLMOptimizationProblem, ctx: MeshContext,
                 mh: "multihost.MultihostContext"):
        self.ctx = ctx
        self.num_rows = num_rows
        self.problem = problem
        self.norm = NormalizationContext.identity()
        self.solver = DistributedFixedEffectSolver(problem, ctx)
        self._score_fn = None
        self._fold_fn = jax.jit(
            lambda base, ids, resid: base
            + jnp.where(ids >= 0, resid[jnp.maximum(ids, 0)], 0.0)
        )
        local = max(ctx.num_devices // mh.num_processes, 1)
        n_loc = x.shape[0]
        from photon_ml_tpu.parallel.shuffle import collective_max

        r_max = int(collective_max(np.asarray([n_loc], np.int64), ctx,
                                   mh.num_processes)[0])
        r_max = -(-r_max // local) * local  # device multiple

        def pad(a, fill=0.0):
            if a.shape[0] == r_max:
                return a
            p = np.full((r_max - a.shape[0],) + a.shape[1:], fill, a.dtype)
            return np.concatenate([a, p])

        sharding = NamedSharding(ctx.mesh, P(ctx.axis))
        self.x = jax.make_array_from_process_local_data(
            sharding, pad(x.astype(np.float32))
        )
        self.labels = jax.make_array_from_process_local_data(
            sharding, pad(labels.astype(np.float32))
        )
        self.base_offsets = jax.make_array_from_process_local_data(
            sharding, pad(offsets.astype(np.float32))
        )
        self.weights = jax.make_array_from_process_local_data(
            sharding, pad(weights.astype(np.float32), 0.0)  # pad weight 0
        )
        self.row_ids = jax.make_array_from_process_local_data(
            sharding, pad(row_ids.astype(np.int32), -1)
        )

    @property
    def dim(self) -> int:
        return self.x.shape[1]

    def initial_coefficients(self) -> Array:
        return jnp.zeros((self.dim,), real_dtype())

    def update(self, residual_offsets: Array,
               init_coefficients: Array) -> Tuple[Array, OptResult]:
        # residuals arrive in GLOBAL row order; gather this shard's rows
        offs = self._fold_fn(self.base_offsets, self.row_ids, residual_offsets)
        batch = GLMBatch(DenseFeatures(self.x), self.labels, offs, self.weights)
        model, result = self.solver.run(batch, self.norm, init_coefficients)
        return model.coefficients.means, result

    def score(self, coefficients: Array) -> Array:
        if self._score_fn is None:
            axis = self.ctx.axis
            n = self.num_rows

            def score_shard(w, x, ids):
                s = x @ w  # (R_loc,)
                out = jnp.zeros((n,), s.dtype).at[jnp.maximum(ids, 0)].add(
                    jnp.where(ids >= 0, s, 0.0)
                )
                return jax.lax.psum(out, axis)

            self._score_fn = jax.jit(
                shard_map(
                    score_shard, mesh=self.ctx.mesh,
                    in_specs=(P(), P(self.ctx.axis), P(self.ctx.axis)),
                    out_specs=P(),
                )
            )
        return self._score_fn(coefficients, self.x, self.row_ids)

    def regularization_term(self, coefficients: Array) -> Array:
        return self.problem.regularization_term_value(coefficients)

    def rebind(self, problem: GLMOptimizationProblem
               ) -> "MultihostFixedEffectCoordinate":
        """Shallow copy sharing the device-resident data arrays (and the
        jitted score fn) but solving a DIFFERENT optimization problem —
        what the combo grid needs: the design matrix uploads once, only
        the per-combo problem binding changes."""
        import copy

        c = copy.copy(self)
        c.problem = problem
        c.solver = DistributedFixedEffectSolver(problem, self.ctx)
        return c


def _add_multihost_flags(argv: List[str]) -> Tuple[dict, List[str]]:
    """Strip the --multihost-* / --grid-warm-start flags; the rest is the
    normal game grammar."""
    mh_args = {"coordinator": None, "num_processes": None, "process_id": None,
               "grid_warm_start": False}
    rest: List[str] = []
    i = 0
    while i < len(argv):
        a = argv[i]
        if a in ("--multihost-coordinator", "--multihost-num-processes",
                 "--multihost-process-id", "--grid-warm-start"):
            if i + 1 >= len(argv):
                raise ValueError(f"{a} requires a value")
            value = argv[i + 1]
            if a == "--multihost-coordinator":
                mh_args["coordinator"] = value
            elif a == "--multihost-num-processes":
                mh_args["num_processes"] = int(value)
            elif a == "--grid-warm-start":
                mh_args["grid_warm_start"] = value.strip().lower() in (
                    "true", "1", "yes"
                )
            else:
                mh_args["process_id"] = int(value)
            i += 2
        else:
            rest.append(a); i += 1
    return mh_args, rest


def main(argv: Optional[List[str]] = None) -> dict:
    import sys

    from photon_ml_tpu.resilience import preemption

    mh_args, rest = _add_multihost_flags(
        list(argv) if argv is not None else sys.argv[1:]
    )
    p = parse_training_params(rest)

    # SPMD preemption: every host observes the same request (the pod
    # scheduler SIGTERMs all workers; PHOTON_PREEMPT_AT counts polls
    # identically on every host) and drains at the same boundary, so the
    # emergency-checkpoint collectives stay aligned. A relaunch re-ingests
    # (the slabs are process state) and resumes descent from the
    # collective-min checkpoint step.
    with preemption.signal_scope():
        try:
            return preemption.run_with_restarts(
                lambda attempt: _main_once(mh_args, p, restart=attempt > 0),
                p.max_restarts,
            )
        except preemption.Preempted as e:
            print(
                f"photon-ml-tpu multihost: preempted ({e}); exiting "
                f"{preemption.PREEMPT_EXIT_CODE}",
                file=sys.stderr,
            )
            raise SystemExit(preemption.PREEMPT_EXIT_CODE) from e


def _check_multihost_support(p) -> None:
    """Loud scope checks for this driver (unit-testable without launching
    processes): flags it does not implement are rejected, never silently
    ignored."""
    unsupported = [
        flag for flag, on in (
            ("--compute-variance", p.compute_variance),
            ("--fused-cycle", p.fused_cycle),
            ("--vmapped-grid", p.vmapped_grid != "false"),
        ) if on
    ]
    if unsupported:
        raise ValueError(
            f"multihost driver does not implement {unsupported} — "
            "rejecting rather than silently ignoring (the sharded slabs "
            "are non-addressable, so an outer jit over the whole cycle "
            "cannot close over them)"
        )
    from photon_ml_tpu.optim.scheduler import resolve_schedule

    if (resolve_schedule(p.solve_compaction) is not None
            and not p.streaming_random_effects):
        raise ValueError(
            "multihost driver composes --solve-compaction with "
            "--streaming-random-effects (each host compacts its owned "
            "blocks through the shared chunk kernels; updates are "
            "owner-computes, no collective) — the in-memory shard_map "
            "random-effect solver cannot pause at chunk boundaries; add "
            "--streaming-random-effects or drop --solve-compaction"
        )


def _attempt_relaunch_adoption(p, mh, ctx, logger) -> Dict[str, object]:
    """Relaunch-time re-plan (parallel/elastic.py:relaunch_replan) for
    every streaming random-effect coordinate: restore the prior cohort's
    plan-versioned sidecars, re-plan against THIS cohort's membership, and
    delta-transfer only the moved block/state files — a supervised relaunch
    onto a smaller or larger fleet resumes instead of re-ingesting.

    Returns ``{coordinate: RelaunchReplanResult}`` only when EVERY host
    succeeded for EVERY coordinate (one collective vote); any failure — or
    a same-cohort restart, which needs no re-plan — returns ``{}`` and the
    caller takes the ordinary full-ingest path on all hosts together."""
    import re as _re

    from photon_ml_tpu.parallel.elastic import ElasticError, relaunch_replan
    from photon_ml_tpu.parallel.perhost_streaming import load_plan_sidecars
    from photon_ml_tpu.parallel.shuffle import collective_max

    names = [
        n for n in p.updating_sequence
        if n in p.random_effect_data_configs and n not in p.factored_configs
    ]
    state_base = os.path.join(p.output_dir, "streaming-re-state")
    adopted: Dict[str, object] = {}
    code, why = 1, ""  # 0 = failed, 1 = adopted, 2 = same cohort
    try:
        prior_cohort = None
        first_root = (
            os.path.join(p.output_dir, "streaming-re", names[0])
            if names else None
        )
        if first_root and os.path.isdir(first_root):
            for d in sorted(os.listdir(first_root)):
                mdir = os.path.join(first_root, d)
                if d.startswith("process-") and os.path.isfile(
                        os.path.join(mdir, "manifest.json")):
                    meta, _, _ = load_plan_sidecars(mdir)
                    if meta is not None:
                        prior_cohort = sorted(
                            {int(q) for q in meta["binding"].values()}
                        )
                    break
        if prior_cohort is None:
            code, why = 0, "no committed plan-versioned prior layout"
        elif prior_cohort == list(range(mh.num_processes)):
            code = 2
        else:
            for name in names:
                coord_root = os.path.join(p.output_dir, "streaming-re", name)
                # prior spill roots by OLD physical pid, grouped per
                # coordinate-state instance (the -<seq> suffix), each paired
                # with MY destination root of the same instance
                pairs = []
                if os.path.isdir(state_base):
                    pat = _re.compile(_re.escape(name) + r"-host(\d+)-(\d+)$")
                    by_seq: Dict[int, Dict[int, str]] = {}
                    for d in os.listdir(state_base):
                        m = pat.match(d)
                        if m:
                            by_seq.setdefault(int(m.group(2)), {})[
                                int(m.group(1))
                            ] = os.path.join(state_base, d)
                    pairs = [
                        (srcs, os.path.join(
                            state_base, f"{name}-host{mh.process_id}-{seq}"
                        ))
                        for seq, srcs in sorted(by_seq.items())
                    ]
                adopted[name] = relaunch_replan(
                    coord_root, mh.process_id, mh.num_processes,
                    state_root_pairs=pairs,
                )
    except (ElasticError, OSError, ValueError, KeyError) as e:
        code, why = 0, f"{type(e).__name__}: {e}"
        adopted = {}
    # EVERY host votes, failed or not — the verdict must be unanimous or
    # everyone falls back to the full re-ingest TOGETHER (a mixed resume
    # would strand the routing collectives)
    v = np.asarray([code], np.int64)
    vmax = int(collective_max(v, ctx, mh.num_processes)[0])
    vmin = -int(collective_max(-v, ctx, mh.num_processes)[0])
    if vmax != vmin or vmin != 1:
        if vmax == vmin == 2:
            logger.info(
                "relaunch: same cohort as the prior run — plain resume "
                "from the plan-versioned checkpoints, no re-plan needed"
            )
        else:
            logger.warn(
                "relaunch re-plan unavailable on at least one host"
                + (f" (here: {why})" if code != 1 else "")
                + " — full re-ingest on the new cohort (recorded decision)"
            )
        return {}
    return adopted


def _fe_chunk_share(all_files, adopted, mh, logger):
    """This host's input-file share. An adopted re-plan carries the prior
    run's fixed-effect chunk ownership re-based onto the new cohort (chunk
    c IS input file c, versioned with the entity-shard plan); otherwise the
    split is the deterministic positional share."""
    if adopted:
        result = next(iter(adopted.values()))
        shard_plan = result.plan
        own = getattr(shard_plan, "fe_chunk_owners", None)
        if own is not None and len(own) == len(all_files):
            chunks = shard_plan.owned_fe_chunks(
                mh.process_id, membership=result.membership
            )
            logger.info(
                f"host {mh.process_id}: FE chunk ownership from re-based "
                f"plan v{shard_plan.version} "
                f"({len(chunks)}/{len(all_files)} chunks)"
            )
            return [(all_files[int(c)], int(c)) for c in chunks]
        logger.info(
            "adopted plan has no usable FE chunk ownership — positional "
            "file share (chunk merge is exact either way; ownership only "
            "balances the streaming fixed-effect load)"
        )
    return host_file_share(all_files, mh.num_processes, mh.process_id)


def _attach_fe_ownership(mh, all_files, g_file_counts, streaming_manifests,
                         logger) -> None:
    """Fresh ingest: fold the ACTUAL per-host file split into every
    streaming coordinate's committed plan sidecars, so a later relaunch
    re-plan re-bases fixed-effect chunks exactly like entity blocks."""
    from photon_ml_tpu.parallel.perhost_streaming import (
        attach_fe_chunks_to_sidecars,
    )

    owners = np.zeros(len(all_files), np.int32)
    for pid in range(mh.num_processes):
        for _, ordinal in host_file_share(all_files, mh.num_processes, pid):
            owners[ordinal] = pid
    for name, sm in streaming_manifests.items():
        try:
            attach_fe_chunks_to_sidecars(sm.dir, owners, g_file_counts)
        except (OSError, ValueError) as e:
            logger.warn(
                f"streaming RE {name}: could not record FE chunk ownership "
                f"in the plan sidecars ({e}) — a relaunch re-plan falls "
                "back to the positional file share"
            )


def _mh_ingest_inputs(p, plan) -> Dict[str, object]:
    """The pre-feature-map ingest identity (the single-process driver's
    ``_ingest_inputs`` shape) — what the delta planner compares."""
    bk = plan.bucketer
    return {
        "sections": {k: list(v) for k, v in sorted(
            (p.feature_shard_sections or {}).items())},
        "intercepts": {k: bool(v) for k, v in sorted(
            (p.feature_shard_intercepts or {}).items())},
        "id_types": sorted({c.random_effect_id
                            for c in p.random_effect_data_configs.values()}),
        "ladder": (
            f"{bk.base}:{bk.growth:g}" if bk is not None else None
        ),
        "offheap_indexmap_dir": p.offheap_indexmap_dir,
        "name_and_term": p.feature_name_and_term_set_path,
    }


def _mh_eval_identity(p) -> Dict[str, object]:
    """Validation-side identity (file stats + evaluator specs): a changed
    validation set must re-score even when training has nothing to do."""
    from photon_ml_tpu.cli.game_training_driver import (
        _input_files,
        resolve_date_range_dirs,
    )
    from photon_ml_tpu.io.tensor_cache import file_stat_token

    val_files = []
    if p.validate_input_dirs:
        val_files = _input_files(resolve_date_range_dirs(
            p.validate_input_dirs, p.validate_date_range,
            p.validate_date_range_days_ago,
        ))
    return {
        "validate_files": file_stat_token(val_files),
        "evaluators": [
            [etype.value, k, id_name]
            for etype, k, id_name in (p.evaluators or [])
        ],
    }


def _mh_ingest_digest(p, plan, shard_maps) -> str:
    """SHA-256 of the full ingest identity incl. per-shard feature-map
    digests (the feature-space identity warm reuse requires)."""
    import hashlib
    import json as _json

    from photon_ml_tpu.io.tensor_cache import index_map_digest

    cfg = dict(
        _mh_ingest_inputs(p, plan),
        index_maps={
            shard: index_map_digest(imap)
            for shard, imap in sorted(shard_maps.items())
        },
    )
    return hashlib.sha256(
        _json.dumps(cfg, sort_keys=True, default=str).encode()
    ).hexdigest()


def _blocking_unchanged(prior, name, manifest) -> bool:
    """Freezing a streaming coordinate additionally requires the prior
    run's entity blocking to BE this run's blocking — ``block_of`` is a
    pure function of the agreed entity counts, so the guard is
    membership-invariant (it holds across topology changes) and fails
    closed for a prior without plan sidecars (e.g. a single-process
    run's manifest)."""
    rec = prior.coordinates.get(name)
    if rec is None or not rec.streaming_manifest_dir:
        return False
    from photon_ml_tpu.parallel.perhost_streaming import _PLAN_BLOCK_OF

    try:
        prior_bo = np.load(
            os.path.join(rec.streaming_manifest_dir, _PLAN_BLOCK_OF)
        )
        cur_bo, _ = manifest.plan_arrays()
    except OSError:
        return False
    return bool(np.array_equal(np.asarray(prior_bo), np.asarray(cur_bo)))


def _prepare_multihost_warm(p, mh, ctx, logger, plan, shard_maps, all_files,
                            streaming_manifests, combos):
    """--warm-start-from for the multihost driver: every host plans its
    own delta against the prior ``retrain.json``, builds its warm seeds,
    and ONE collective agreement compares a digest of the outcome
    (classification + warm + frozen sets) across the cohort. Any
    disagreement — or any host's unusable prior, including an injected
    ``retrain.multihost_delta_agree`` fault — degrades EVERY host to a
    RECORDED cold run; a split-brain warm resume is impossible by
    construction.

    Returns ``(initial_params or None, frozen_blocks_by_name,
    frozen_coordinate_names)``."""
    if not p.warm_start_from:
        return None, {}, set()
    import hashlib
    import json as _json

    from photon_ml_tpu import retrain
    from photon_ml_tpu.parallel.shuffle import collective_max
    from photon_ml_tpu.resilience import faults
    from photon_ml_tpu.retrain.delta import NEW

    prior = delta = None
    warm: Dict[str, object] = {}
    frozen_blocks: Dict[str, frozenset] = {}
    frozen: set = set()
    digest, why = -1, ""
    try:
        # the chaos seam fires FIRST and the collectives run AFTER, no
        # matter what: a one-sided failure poisons THIS host's digest
        # (-1) but the host still votes below — it must never strand its
        # peers in a collective
        faults.inject(
            "retrain.multihost_delta_agree", process=int(mh.process_id)
        )
        prior = retrain.load_prior_manifest(p.warm_start_from)
        combo_configs = None
        if len(combos) == 1:
            combo_configs = {
                name: str(combos[0].get(name, CoordinateOptConfig()))
                for name in p.updating_sequence
            }
        delta = retrain.plan_delta(
            prior, all_files,
            task=p.task_type.value,
            updating_sequence=p.updating_sequence,
            ingest_inputs=_mh_ingest_inputs(p, plan),
            combo_configs=combo_configs,
            eval_identity=_mh_eval_identity(p),
        )
        freezable = (
            delta.frozen_coordinates() if len(combos) == 1 else set()
        )
        for name in p.updating_sequence:
            cdelta = delta.coordinates.get(name)
            if cdelta is None or cdelta.status == NEW:
                continue
            if name in p.fixed_effect_data_configs:
                spec = p.fixed_effect_data_configs[name]
                w0 = retrain.fixed_effect_init(
                    prior.model_dir, name,
                    shard_maps[spec.feature_shard_id],
                )
                if w0 is None:
                    logger.info(f"warm start {name}: prior fixed-effect "
                                "model missing — cold")
                    continue
                warm[name] = jnp.asarray(w0)
                if name in freezable:
                    frozen.add(name)
            elif name in streaming_manifests:
                dc = p.random_effect_data_configs[name]
                means = retrain.random_effect_entity_means(
                    prior.model_dir, name, shard_maps[dc.feature_shard_id]
                )
                if means is None:
                    logger.info(f"warm start {name}: prior random-effect "
                                "model missing or factored — cold")
                    continue
                warm[name] = retrain.seed_perhost_spilled_state(
                    streaming_manifests[name], means,
                    os.path.join(p.output_dir, "retrain-warm",
                                 f"{name}-host{mh.process_id}"),
                )
                if name in freezable and _blocking_unchanged(
                        prior, name, streaming_manifests[name]):
                    frozen.add(name)
                    # every LOCAL owned block skips its solve bitwise —
                    # per-host, the fleet-wide freeze the agreement
                    # guarantees is consistent
                    frozen_blocks[name] = frozenset(
                        range(len(streaming_manifests[name].blocks))
                    )
            else:
                # in-memory multihost RE solvers hold device-sharded slabs
                # with no host-side seeding path — a recorded cold solve,
                # the same rule as factored coordinates
                logger.info(f"warm start {name}: no multihost warm path "
                            "for this coordinate kind — cold")
        canon = _json.dumps(
            {
                "status": {n: c.status for n, c in
                           delta.coordinates.items()},
                "warm": sorted(warm),
                "frozen": sorted(frozen),
            },
            sort_keys=True,
        )
        # non-negative int63 (-1 stays a distinguishable poison value)
        digest = int.from_bytes(
            hashlib.sha256(canon.encode()).digest()[:8], "big"
        ) >> 1
    except Exception as e:  # noqa: BLE001 — ANY unusable prior (bad JSON, vanished model, unwritable seed dir, injected fault) must degrade to a cold run, never a wrong warm result or a stranded collective
        warm, frozen_blocks, frozen = {}, {}, set()
        why = f"{type(e).__name__}: {e}"
    d = np.asarray([digest], np.int64)
    dmax = int(collective_max(d, ctx, mh.num_processes)[0])
    dmin = -int(collective_max(-d, ctx, mh.num_processes)[0])
    if dmax != dmin or dmin < 0:
        logger.warn(
            "--warm-start-from: delta plan "
            + ("disagrees across hosts" if dmax != dmin
               else "failed on at least one host")
            + (f" (here: {why})" if why else "")
            + " — retraining cold everywhere (recorded decision)"
        )
        return None, {}, set()
    logger.info(
        f"delta retrain plan (agreed across {mh.num_processes} hosts): "
        f"files {delta.files.describe()}; "
        + " ".join(f"{n}={c.status}" for n, c in delta.coordinates.items())
    )
    for line in delta.describe_decisions():
        logger.info(f"delta retrain: {line}")
    if warm:
        logger.info(
            f"warm start: {sorted(warm)} seeded from {prior.model_dir}"
            + (f"; frozen {sorted(frozen)}" if frozen else "")
        )
    return (warm or None), frozen_blocks, frozen


def _write_mh_retrain_manifest(p, plan, best_dir, shard_maps, combos,
                               best_index, streaming_manifests,
                               coord_cache_keys, train_file_stats,
                               logger, coord_objs=None) -> None:
    """The coordinator's ``retrain.json`` (the single-process driver's
    record, multihost leg): next run's planner diffs against it, and the
    fleet rollout's provenance check traces its ``model_dir``."""
    from photon_ml_tpu.retrain import RetrainManifest
    from photon_ml_tpu.retrain.manifest import CoordinateRecord

    sel = combos[best_index]
    coords: Dict[str, CoordinateRecord] = {}
    for name in p.updating_sequence:
        if name in p.fixed_effect_data_configs:
            kind = "fixed"
        elif name in p.factored_configs:
            kind = "factored"
        elif name in streaming_manifests:
            kind = "streaming_random"
        elif p.bucketed_random_effects:
            kind = "bucketed"
        else:
            kind = "random"
        sm = streaming_manifests.get(name)
        # the coordinator's convergence ledger (its OWN blocks, keyed by
        # global block id) rides along; the other hosts' entries live in
        # their per-host manifest-dir sidecars, re-based by elastic commits
        ledger = None
        export = getattr((coord_objs or {}).get(name), "ledger_export", None)
        if callable(export):
            ledger = export() or None
        coords[name] = CoordinateRecord(
            kind=kind,
            opt_config=str(sel.get(name, CoordinateOptConfig())),
            cache_key=coord_cache_keys.get(name),
            streaming_manifest_dir=(
                os.path.abspath(sm.dir) if sm is not None else None
            ),
            shard_plan_version=int(
                getattr(sm, "plan_version", 1) if sm is not None else 1
            ),
            convergence_ledger=ledger,
        )
    manifest = RetrainManifest(
        output_dir=os.path.abspath(p.output_dir),
        model_dir=os.path.abspath(best_dir),
        task=p.task_type.value,
        file_stats=train_file_stats,
        ingest_inputs=_mh_ingest_inputs(p, plan),
        ingest_digest=_mh_ingest_digest(p, plan, shard_maps),
        updating_sequence=list(p.updating_sequence),
        coordinates=coords,
        data_cache_key=None,
        eval_identity=_mh_eval_identity(p),
    )
    path = manifest.save(p.output_dir)
    logger.info(f"retrain manifest written: {path}")


def _main_once(mh_args: dict, p, restart: bool = False) -> dict:
    mh = multihost.initialize(
        coordinator_address=mh_args["coordinator"],
        num_processes=mh_args["num_processes"],
        process_id=mh_args["process_id"],
    )
    ctx = mh.mesh_context()
    # the coordinator owns the output dir lifecycle (incl. purge — stale
    # per-host RE part files from a previous topology must never be merged
    # into a reloaded model); everyone else waits. A supervised relaunch
    # keeps the dir — the checkpoints under it are what it resumes from.
    if mh.coordinator_only_io():
        from photon_ml_tpu.utils.io_utils import prepare_output_dir

        if restart:
            os.makedirs(p.output_dir, exist_ok=True)
        else:
            prepare_output_dir(p.output_dir, p.delete_output_dir_if_exists)
    mh.barrier("output-dir")
    logger = PhotonLogger(
        os.path.join(p.output_dir, f"photon-ml-tpu-mh-{mh.process_id}.log")
    )
    from photon_ml_tpu.compile import compile_stats

    compile_stats.install_xla_listeners()
    from photon_ml_tpu import compat

    compat.start_up(logger.info, p.persistent_cache_dir)

    _check_multihost_support(p)
    # the execution plan (photon_ml_tpu.compile.plan) threads the shape
    # ladder + solve schedule + sparse selection through the per-host
    # streaming coordinates — the PR 4 compaction scheduler and the PR 7
    # sparse races now run ON the billion-coefficient path, per host, with
    # no collective in the update (owner-computes)
    from photon_ml_tpu.compile.plan import ExecutionPlan

    plan = ExecutionPlan.resolve(
        shape_canonicalization=p.shape_canonicalization,
        solve_compaction=p.solve_compaction,
        adaptive_schedule=p.adaptive_schedule,
        distributed=True,
        streaming=p.streaming_random_effects,
        bucketed=p.bucketed_random_effects,
        fused_cycle=p.fused_cycle,
        num_processes=mh.num_processes,
    )
    logger.info(plan.describe())
    for line in plan.describe_decisions():
        logger.info(f"execution plan: {line}")
    for cname, dc in p.random_effect_data_configs.items():
        proj = dc.projector.upper()
        if proj not in ("INDEX_MAP", "IDENTITY", "RANDOM"):
            raise ValueError(
                f"coordinate {cname!r} requests unknown projector "
                f"{dc.projector!r}"
            )
        if proj == "RANDOM" and dc.random_projection_dim is None:
            raise ValueError(
                f"coordinate {cname!r}: RANDOM projector needs "
                "random_projection_dim in its data configuration"
            )
    combos = p.config_grid()

    # ---- feature maps: prebuilt, shared, mmap'd ---------------------------
    shard_maps = {}
    needed_shards = {c.feature_shard_id for c in p.fixed_effect_data_configs.values()}
    needed_shards |= {c.feature_shard_id for c in p.random_effect_data_configs.values()}
    for shard in needed_shards:
        if p.offheap_indexmap_dir:
            from photon_ml_tpu.io.offheap import load_shard_index_map

            shard_maps[shard] = load_shard_index_map(p.offheap_indexmap_dir, shard)
        elif p.feature_name_and_term_set_path:
            from photon_ml_tpu.io.name_and_term import NameAndTermFeatureSetContainer

            all_sections = sorted(
                {s for secs in p.feature_shard_sections.values() for s in secs}
            )
            nt = NameAndTermFeatureSetContainer.read_from_text(
                p.feature_name_and_term_set_path, all_sections
            )
            shard_maps[shard] = nt.index_map(
                p.feature_shard_sections.get(shard) or ["features"],
                p.feature_shard_intercepts.get(shard, True),
            )
        else:
            raise ValueError(
                "multihost ingest needs prebuilt feature maps: pass "
                "--offheap-indexmap-dir (FeatureIndexingJob output) or "
                "--feature-name-and-term-set-path"
            )

    # ---- per-host decode --------------------------------------------------
    from photon_ml_tpu.cli.game_training_driver import (
        _input_files,
        resolve_date_range_dirs,
    )

    # _input_files is deterministic (per-dir sorted, dirs in argument
    # order) and identical on every host — no global re-sort, matching the
    # single-process driver's row order
    all_files = _input_files(resolve_date_range_dirs(
        p.train_input_dirs, p.train_date_range, p.train_date_range_days_ago
    ))
    # pre-ingest stat tokens for the retrain manifest (a file overwritten
    # mid-run must be recorded with its pre-overwrite identity, same rule
    # as the single-process driver)
    from photon_ml_tpu.io.tensor_cache import file_stat_token

    train_file_stats = file_stat_token(all_files)
    # relaunch-time re-plan (the elasticity x supervised-relaunch seam): a
    # restart onto a DIFFERENT cohort adopts the prior cohort's durable
    # streaming layout — plan-versioned sidecars restored, replan() against
    # the new membership, only MOVED block/state files copied — instead of
    # re-ingesting everything. ANY host failing degrades EVERY host to a
    # recorded full re-ingest (collectively agreed: never a mixed resume).
    adopted: Dict[str, object] = {}
    if restart and p.streaming_random_effects:
        adopted = _attempt_relaunch_adoption(p, mh, ctx, logger)
    host_files = _fe_chunk_share(all_files, adopted, mh, logger)
    id_types = sorted({c.random_effect_id
                       for c in p.random_effect_data_configs.values()})
    gds = []
    for f, ordinal in host_files:
        gd = read_game_data(
            [f], shard_maps,
            {s: p.feature_shard_sections.get(s) or ["features"]
             for s in needed_shards},
            id_types,
            shard_intercepts={
                s: p.feature_shard_intercepts.get(s, True) for s in needed_shards
            },
        )
        gds.append((ordinal, gd))
    file_base, n_global = global_row_layout(
        len(all_files), gds, ctx, mh.num_processes
    )
    logger.info(
        f"host {mh.process_id}: {len(host_files)}/{len(all_files)} files, "
        f"{sum(gd.num_rows for _, gd in gds)}/{n_global} rows"
    )

    # replicated (N,) label/weight vectors for the training objective:
    # scatter own rows, one psum merges (these are O(N) scalars — the same
    # footprint as the score vectors the descent already carries)
    def assemble_global(vec_per_gd):
        merged = merge_row_vectors(
            gds, file_base, n_global, ctx, mh.num_processes, vec_per_gd
        )
        return jax.device_put(merged, NamedSharding(ctx.mesh, P()))

    labels_g = assemble_global(lambda gd: gd.response.astype(np.float32))
    weights_g = assemble_global(lambda gd: gd.weight.astype(np.float32))

    # ---- build DATASETS once (combo-invariant) ----------------------------
    fe_tensors: Dict[str, tuple] = {}
    fe_chunks: Dict[str, tuple] = {}  # streaming: (chunk_sizes, owned, dim)
    re_datasets: Dict[str, object] = {}
    streaming_manifests: Dict[str, object] = {}
    coord_cache_keys: Dict[str, Optional[str]] = {}
    # per-file row counts (identical on every host): the global chunk grid
    # of the streaming fixed effect — chunk c IS input file c, so chunk
    # ownership falls out of the per-host file share with no routing
    g_file_counts = np.diff(np.append(file_base, n_global)).astype(np.int64)
    for name in p.updating_sequence:
        if name in p.fixed_effect_data_configs:
            spec = p.fixed_effect_data_configs[name]
            feats_parts, y_parts, o_parts, w_parts, id_parts = [], [], [], [], []
            dim = len(shard_maps[spec.feature_shard_id])
            owned_loaders: Dict[int, object] = {}
            for ordinal, gd in gds:
                f = gd.shards[spec.feature_shard_id]
                if p.streaming_random_effects:
                    # one chunk per input file, densified INSIDE the loader:
                    # the streaming contract is one dense chunk resident at
                    # a time — only the (much smaller) CSR shards persist
                    def load(f=f, gd=gd, dim=dim):
                        dense = np.zeros((gd.num_rows, dim), np.float32)
                        rr = np.repeat(np.arange(gd.num_rows), np.diff(f.indptr))
                        dense[rr, f.indices] = f.values
                        return {
                            "x": dense,
                            "y": gd.response.astype(np.float32),
                            "offsets": gd.offset.astype(np.float32),
                            "weights": gd.weight.astype(np.float32),
                        }

                    owned_loaders[ordinal] = load
                    continue
                dense = np.zeros((gd.num_rows, dim), np.float32)
                nnz = np.diff(f.indptr)
                rows_rep = np.repeat(np.arange(gd.num_rows), nnz)
                dense[rows_rep, f.indices] = f.values
                feats_parts.append(dense)
                y_parts.append(gd.response)
                o_parts.append(gd.offset)
                w_parts.append(gd.weight)
                id_parts.append(file_base[ordinal] + np.arange(gd.num_rows))
            if p.streaming_random_effects:
                fe_chunks[name] = (
                    [int(c) for c in g_file_counts], owned_loaders, dim
                )
                continue
            # upload ONCE: the device-resident coordinate is combo-invariant;
            # each combo rebinds only its optimization problem (rebind())
            fe_tensors[name] = MultihostFixedEffectCoordinate(
                np.concatenate(feats_parts) if feats_parts else np.zeros((0, dim), np.float32),
                np.concatenate(y_parts) if y_parts else np.zeros(0),
                np.concatenate(o_parts) if o_parts else np.zeros(0),
                np.concatenate(w_parts) if w_parts else np.zeros(0),
                np.concatenate(id_parts) if id_parts else np.zeros(0, np.int64),
                n_global,
                GLMOptimizationProblem(
                    p.task_type, CoordinateOptConfig().optimizer,
                    CoordinateOptConfig().optimizer_config(),
                    CoordinateOptConfig().regularization_context(),
                ),
                ctx, mh,
            )
        else:
            dc = p.random_effect_data_configs[name]
            if name in p.factored_configs and dc.projector.upper() != "IDENTITY":
                raise ValueError(
                    f"factored coordinate {name!r} requires an IDENTITY "
                    f"projector in its data config (got {dc.projector!r}) — "
                    "the latent matrix projects the global shard space"
                )
            if name in adopted:
                # relaunch adoption (agreed above, so every host skips the
                # routing collectives together): the re-based manifest IS
                # this run's ingest output — resume without re-reading a row
                streaming_manifests[name] = adopted[name].manifest
                logger.info(
                    f"streaming RE {name}: adopted relaunch re-plan "
                    f"v{adopted[name].plan.version} — host {mh.process_id} "
                    f"owns {len(streaming_manifests[name].blocks)}/"
                    f"{streaming_manifests[name].num_blocks_total} blocks, "
                    "no re-ingest"
                )
                continue
            parts = []
            for ordinal, gd in gds:
                f = gd.shards[dc.feature_shard_id]
                fi, fv = csr_to_padded(f, gd.num_rows)
                vocab = gd.id_vocabs[dc.random_effect_id]
                parts.append(HostRows(
                    entity_raw_ids=[vocab[i] for i in gd.ids[dc.random_effect_id]],
                    row_index=file_base[ordinal] + np.arange(gd.num_rows, dtype=np.int64),
                    labels=gd.response.astype(np.float32),
                    weights=gd.weight.astype(np.float32),
                    offsets=gd.offset.astype(np.float32),
                    feat_idx=fi, feat_val=fv,
                    global_dim=f.dim,
                ))
            rows = concat_host_rows(
                parts, len(shard_maps[dc.feature_shard_id])
            )
            if p.streaming_random_effects and name not in p.factored_configs:
                # entity-sharded streaming: agree counts -> agreed global
                # blocking -> route rows to block owners (one all_to_all) ->
                # build ONLY the owned blocks under the per-host manifest
                # layout (each host a private subdir — or a shard-scoped
                # tensor-cache entry that can never cross-read a peer's)
                from photon_ml_tpu.parallel.perhost_streaming import (
                    build_perhost_streaming_manifest,
                )

                budget = (
                    int(p.re_memory_budget_mb * 1e6)
                    if p.re_memory_budget_mb is not None else None
                )
                cache = cache_key = None
                block_cache = block_key_base = None
                if p.tensor_cache_dir:
                    from photon_ml_tpu.io.tensor_cache import (
                        TensorCache,
                        content_key,
                        process_shard_scope,
                    )

                    cache = TensorCache(
                        p.tensor_cache_dir,
                        shard_scope=process_shard_scope(
                            mh.process_id, mh.num_processes
                        ),
                    )
                    bk = plan.bucketer
                    # key on the GLOBAL file list (shared input dir): this
                    # host's cached blocks hold rows routed from EVERY
                    # host's files, so a peer's input change must miss
                    # here. The resolved ladder spec is part of the key —
                    # a --shape-canonicalization change alters the PADDED
                    # block tensors a hit would serve
                    key_config = {
                        "kind": "perhost_streaming_re_blocks",
                        "coord": name, "config": str(dc),
                        "budget": budget, "n_files": len(all_files),
                        "ladder": (
                            f"{bk.base}:{bk.growth:g}"
                            if bk is not None else None
                        ),
                    }
                    cache_key = cache.key_for(all_files, key_config)
                    # per-BLOCK entries keyed on owned-block IDENTITY with
                    # NO process scope: a block's tensors are a pure
                    # function of the global data + plan, so a membership/
                    # topology change keeps every unmoved block's entry
                    # warm — the old scoped dir key rebuilt the whole host
                    # layout on ANY fleet change
                    block_cache = TensorCache(p.tensor_cache_dir)
                    block_key_base = content_key(
                        all_files, dict(key_config, entry="block")
                    )
                streaming_manifests[name] = build_perhost_streaming_manifest(
                    rows, dc,
                    os.path.join(
                        p.output_dir, "streaming-re", name,
                        f"process-{mh.process_id}",
                    ),
                    ctx, mh.num_processes, mh.process_id,
                    block_entities=None if budget is not None else 1024,
                    memory_budget_bytes=budget,
                    # "off", never None: the plan already consumed
                    # PHOTON_SHAPE_LADDER — None would let the builder
                    # re-resolve the env underneath an explicit off
                    bucketer=plan.bucketer or "off",
                    tensor_cache=cache, cache_key=cache_key,
                    block_cache=block_cache, block_key_base=block_key_base,
                )
                coord_cache_keys[name] = cache_key
                logger.info(
                    f"streaming RE {name}: host {mh.process_id} owns "
                    f"{len(streaming_manifests[name].blocks)}/"
                    f"{streaming_manifests[name].num_blocks_total} blocks"
                )
                continue
            bucketed = (
                p.bucketed_random_effects and name not in p.factored_configs
            )
            re_datasets[name] = per_host_re_dataset(
                rows, ctx, mh.num_processes, mh.process_id,
                active_upper_bound=dc.active_upper_bound,
                size_buckets=8 if bucketed else 1,
                projector=dc.projector.upper(),
                projection_dim=dc.random_projection_dim,
                projection_seed=dc.seed,
                projection_keep_intercept=dc.random_projection_intercept,
            )

    # fresh ingest: record the ACTUAL fixed-effect chunk ownership (the
    # host_file_share split above) into the versioned plan sidecars, so a
    # later relaunch re-plan re-bases FE chunks exactly like RE blocks
    if streaming_manifests and not adopted:
        _attach_fe_ownership(
            mh, all_files, g_file_counts, streaming_manifests, logger
        )

    # ---- --warm-start-from: fleet-wide delta retrain ----------------------
    # per-host delta plans agreed collectively; disagreement (or any host's
    # unusable prior) degrades EVERY host to a recorded cold run
    warm_init_mh, mh_frozen_blocks, frozen_names = _prepare_multihost_warm(
        p, mh, ctx, logger, plan, shard_maps, all_files,
        streaming_manifests, combos,
    )

    stream_state_seq = [0]

    def build_coords(combo: Dict[str, CoordinateOptConfig]) -> Dict[str, object]:
        from photon_ml_tpu.parallel.perhost_factored import (
            PerHostFactoredRandomEffectCoordinate,
        )
        from photon_ml_tpu.parallel.perhost_ingest import (
            BucketedShardedREData,
            PerHostBucketedRandomEffectSolver,
        )
        from photon_ml_tpu.algorithm.streaming_fixed_effect import (
            PerHostStreamingFixedEffectCoordinate,
        )
        from photon_ml_tpu.parallel.perhost_streaming import (
            PerHostStreamingRandomEffectCoordinate,
        )

        coords: Dict[str, object] = {}
        for name in p.updating_sequence:
            cfg = combo.get(name, CoordinateOptConfig())
            if name in fe_chunks:
                chunk_sizes, owned_loaders, dim = fe_chunks[name]
                coords[name] = PerHostStreamingFixedEffectCoordinate(
                    chunk_sizes, owned_loaders, dim,
                    GLMOptimizationProblem(
                        p.task_type, cfg.optimizer, cfg.optimizer_config(),
                        cfg.regularization_context(),
                    ),
                    ctx=ctx, num_processes=mh.num_processes,
                    plan=plan,
                )
            elif name in streaming_manifests:
                stream_state_seq[0] += 1
                coords[name] = PerHostStreamingRandomEffectCoordinate(
                    manifest=streaming_manifests[name],
                    task=p.task_type,
                    optimizer=cfg.optimizer,
                    optimizer_config=cfg.optimizer_config(),
                    regularization=cfg.regularization_context(),
                    # spilled state per host + combo instance, under OUR
                    # output dir (never inside a shared cache entry)
                    state_root=os.path.join(
                        p.output_dir, "streaming-re-state",
                        f"{name}-host{mh.process_id}-{stream_state_seq[0]}",
                    ),
                    # the plan threads the solve schedule, the per-block
                    # sparse-kernel race, and the prefetch depth — the
                    # PR 4 / PR 7 wins on the billion-coefficient path
                    plan=plan,
                    ctx=ctx, num_processes=mh.num_processes,
                    # delta retrain: LOCAL block indices whose solves are
                    # skipped bitwise (coefficients carried from the warm
                    # seed) — set only when the delta plan froze this
                    # coordinate on every host
                    frozen_blocks=mh_frozen_blocks.get(name),
                )
            elif name in p.fixed_effect_data_configs:
                coords[name] = fe_tensors[name].rebind(
                    GLMOptimizationProblem(
                        p.task_type, cfg.optimizer, cfg.optimizer_config(),
                        cfg.regularization_context(),
                    )
                )
            elif name in p.factored_configs:
                from photon_ml_tpu.algorithm.factored_random_effect import (
                    MFOptimizationConfig,
                )

                spec = p.factored_configs[name]
                coords[name] = PerHostFactoredRandomEffectCoordinate(
                    re_datasets[name], p.task_type,
                    mf_config=MFOptimizationConfig(
                        spec.mf_num_iterations, spec.latent_dim
                    ),
                    re_optimizer=spec.random_effect.optimizer,
                    re_optimizer_config=spec.random_effect.optimizer_config(),
                    re_regularization=spec.random_effect.regularization_context(),
                    latent_optimizer=spec.latent_factor.optimizer,
                    latent_optimizer_config=spec.latent_factor.optimizer_config(),
                    latent_regularization=spec.latent_factor.regularization_context(),
                    ctx=ctx,
                )
            else:
                sd = re_datasets[name]
                solver_cls = (
                    PerHostBucketedRandomEffectSolver
                    if isinstance(sd, BucketedShardedREData)
                    else PerHostRandomEffectSolver
                )
                coords[name] = solver_cls(
                    sd, p.task_type, cfg.optimizer, cfg.optimizer_config(),
                    cfg.regularization_context(), ctx,
                )
        return coords

    # ---- validation data decoded once (combo-invariant) -------------------
    val_data = None
    if p.validate_input_dirs:
        val_data = _decode_validation(p, mh, ctx, shard_maps, needed_shards,
                                      id_types)

    # ---- warm-started grid sweep ------------------------------------------
    from photon_ml_tpu.algorithm.coordinate_descent import CoordinateDescent
    from photon_ml_tpu.evaluation.evaluators import evaluator_for
    from photon_ml_tpu.cli.game_training_driver import _default_evaluators

    loss = losses_mod.for_task(p.task_type)
    loss_fn = lambda scores: jnp.sum(weights_g * loss.loss(scores, labels_g))
    specs = p.evaluators or _default_evaluators(p.task_type)
    primary = specs[0]
    primary_key = (
        primary[0].value if primary[1] is None
        else f"{primary[0].value}@{primary[1]}"
    )
    primary_ev = evaluator_for(primary[0], primary[1] or 10)

    best_index = 0
    best_value: Optional[float] = None
    best_result = None
    best_coords = None
    all_metrics: List[Dict[str, float]] = []
    prev_coefficients = None
    # per-host heartbeats (multihost health fencing): every host stamps the
    # shared dir at its safe boundaries; the coordinator logs the ages so a
    # wedged host — the one whose barrier everyone else is stuck in — is
    # diagnosable by name instead of by silence
    hb_dir = os.path.join(p.output_dir, "heartbeats")
    mh.write_heartbeat(hb_dir, step=None)
    if mh.coordinator_only_io():
        logger.info(mh.describe_heartbeats(hb_dir))
    for i, combo in enumerate(combos):
        coords = build_coords(combo)
        checkpointer = None
        if p.checkpoint_dir:
            from photon_ml_tpu.checkpoint import (
                CoordinateDescentCheckpointer,
                fingerprint,
            )
            from photon_ml_tpu.checkpoint_async import maybe_async

            # multihost-safe: sharded leaves are allgathered for the write,
            # the coordinator writes, barriers fence (checkpoint.py
            # multihost mode; restore agrees on the step via collective min)
            checkpointer = maybe_async(
                CoordinateDescentCheckpointer(
                    os.path.join(p.checkpoint_dir, f"combo-{i}"),
                    run_fingerprint=fingerprint({
                        # cohort-INVARIANT marker, deliberately not
                        # num_processes: a supervised relaunch onto a
                        # smaller/larger cohort must restore this
                        # plan-versioned checkpoint and resume — per-host
                        # streaming state re-bases through the plan
                        # sidecars (see MIGRATION.md)
                        "multihost": True,
                        "coordinates": p.updating_sequence,
                        "num_rows": n_global,
                        "combo": i,
                        "warm_start": mh_args["grid_warm_start"],
                        # a config change must NOT silently resume the old run
                        # (same rule as the single-process driver's fingerprint)
                        "configs": {k: str(v) for k, v in combo.items()},
                    }),
                    multihost=mh,
                ),
                p.checkpoint_async,
            )
        cd = CoordinateDescent(coords, loss_fn)
        try:
            result = cd.run(
                num_iterations=p.num_iterations, num_rows=n_global,
                checkpointer=checkpointer,
                # combo 0 (or the whole run, without --grid-warm-start)
                # seeds from the delta-retrain warm start; later combos
                # under --grid-warm-start keep the previous combo's
                # coefficients (the stronger start)
                initial_params=(
                    prev_coefficients
                    if mh_args["grid_warm_start"] and prev_coefficients
                    is not None else warm_init_mh
                ),
                # non-empty only for a single-combo run (a sweep compares
                # configurations, so nothing may be skipped)
                frozen=frozen_names,
            )
        finally:
            # async fence before this combo retires (preemption already
            # fenced inside the emergency save)
            if checkpointer is not None and hasattr(checkpointer, "close"):
                checkpointer.close()
        prev_coefficients = result.coefficients
        mh.write_heartbeat(hb_dir, step=(i + 1) * p.num_iterations)
        if mh.coordinator_only_io():
            logger.info(mh.describe_heartbeats(hb_dir))
        logger.info(
            f"combo {i}: objective history "
            + " ".join(f"{v:.6g}" for v in result.objective_history)
        )
        metrics: Dict[str, float] = {}
        if val_data is not None:
            metrics = _validate(
                p, mh, ctx, coords=coords, result=result, logger=logger,
                val_data=val_data,
            )
            logger.info(
                f"combo {i} validation: "
                + " ".join(f"{k}={v:.6g}" for k, v in metrics.items())
            )
        all_metrics.append(metrics)
        if metrics and primary_key in metrics:
            value = metrics[primary_key]
            if best_value is None or primary_ev.better_than(value, best_value):
                best_value, best_index = value, i
                best_result, best_coords = result, coords
        elif best_result is None:
            best_result, best_coords = result, coords
    if len(combos) > 1:
        logger.info(
            f"best combo: {best_index}"
            + (f" ({primary_key}={best_value:.6g})" if best_value is not None else "")
        )
    result, coords = best_result, best_coords
    metrics = all_metrics[best_index]

    # ---- save (reference layout; RE parts written per host) ---------------
    out = os.path.join(p.output_dir, "best")
    mh.barrier("pre-save")
    if mh.coordinator_only_io():
        os.makedirs(out, exist_ok=True)
    mh.barrier("outdir")
    for name in p.updating_sequence:
        coord = coords[name]
        w = result.coefficients[name]
        if name in p.fixed_effect_data_configs:
            # replicated (D,) model either way — in-memory psum coordinate
            # or the per-host streaming chunk coordinate
            if mh.coordinator_only_io():
                spec = p.fixed_effect_data_configs[name]
                model_io.save_fixed_effect(
                    out, name, p.task_type,
                    np.asarray(jax.device_get(w)),
                    shard_maps[spec.feature_shard_id],
                    feature_shard_id=spec.feature_shard_id,
                )
        elif name in p.factored_configs:
            dc = p.random_effect_data_configs[name]
            _save_factored_parts(
                out, name, p, dc, coord, w,
                shard_maps[dc.feature_shard_id], mh,
            )
        elif name in streaming_manifests:
            dc = p.random_effect_data_configs[name]
            _save_streaming_re_parts(
                out, name, p, dc, coord, w, shard_maps[dc.feature_shard_id], mh
            )
        else:
            dc = p.random_effect_data_configs[name]
            _save_random_effect_parts(
                out, name, p, dc, coord, w, shard_maps[dc.feature_shard_id], mh
            )
        mh.barrier(f"saved-{name}")
    logger.info(f"model saved to {out}")
    # the coordinator leaves this run's retrain.json so the NEXT run (and
    # the fleet rollout's provenance check) can diff against it — the
    # multihost leg of the retrain -> re-shard -> export -> swap loop
    if mh.coordinator_only_io():
        try:
            _write_mh_retrain_manifest(
                p, plan, out, shard_maps, combos, best_index,
                streaming_manifests, coord_cache_keys, train_file_stats,
                logger, coord_objs=coords,
            )
        except (OSError, TypeError, ValueError) as e:
            # a failed manifest write degrades tomorrow's run to cold — it
            # must not fail TODAY's completed training run
            logger.warn(f"retrain manifest write failed ({e}); the next "
                        "run retrains cold")
    mh.barrier("retrain-manifest")
    from photon_ml_tpu.compile import compile_stats

    logger.info(compile_stats.summary())
    if plan.schedule is not None or plan.adaptive is not None:
        from photon_ml_tpu.optim.scheduler import solve_stats

        logger.info(solve_stats.summary())
    if plan.adaptive is not None:
        # every adaptive skip/degrade is a recorded decision — per host,
        # like the plan's own composition decisions above
        for name, coord in coords.items():
            for dec in getattr(coord, "skip_decisions", ()) or ():
                logger.info(f"[{name}] {dec.describe()}")
    logger.close()
    return {
        "objective_history": result.objective_history,
        "validation_metrics": metrics,
        "all_metrics": all_metrics,
        "best_index": best_index,
        "num_rows": n_global,
        "process_id": mh.process_id,
        "output": out,
    }


def _save_random_effect_parts(out, name, p, dc, coord, w, imap, mh):
    """Each host writes ONE part file with ITS devices' entities — the
    coefficient slab never crosses hosts (ModelProcessingUtils.scala:205-219
    writes per-partition part files the same way). Raw entity ids come from
    the host's own decode (key -> raw id map built during ingest)."""
    from photon_ml_tpu.io import avro as avro_io
    from photon_ml_tpu.io import schemas
    from photon_ml_tpu.io.model_io import (
        COEFFICIENTS,
        ID_INFO,
        RANDOM_EFFECT,
        _model_record,
    )

    from photon_ml_tpu.parallel.perhost_ingest import BucketedShardedREData

    sd = coord.data
    base = os.path.join(out, RANDOM_EFFECT, name)
    if mh.coordinator_only_io():
        os.makedirs(os.path.join(base, COEFFICIENTS), exist_ok=True)
        with open(os.path.join(base, ID_INFO), "w") as f:
            f.write(f"{dc.random_effect_id}\n{dc.feature_shard_id}\n")
    mh.barrier(f"re-dir-{name}")
    # this host's slab rows (addressable shards of the sharded arrays);
    # raw ids rode the exchange (ShardedREData.raw_ids_by_key), so the
    # OWNER can name every entity it holds without any model gather.
    # Bucketed datasets contribute one group per size bucket (the
    # coefficients arrive as the solver's per-bucket tuple).
    if isinstance(sd, BucketedShardedREData):
        groups = [
            (wb, b.entity_keys, b.entity_mask, b.local_to_global)
            for b, wb in zip(sd.buckets, w)
        ]
    else:
        groups = [(w, sd.entity_keys, sd.entity_mask, sd.local_to_global)]
    pm = getattr(sd, "projection_matrix", None)
    records = []
    for warr, karr, marr, larr in groups:
        local = {}
        for arr, field in ((warr, "w"), (karr, "keys"),
                           (marr, "mask"), (larr, "l2g")):
            # local_shards orders by slab position so the four arrays' lanes
            # align (addressable_shards iteration order is unspecified)
            local[field] = np.concatenate(local_shards(arr))
        mask = local["mask"].astype(bool)
        for lane in np.nonzero(mask)[0]:
            key = int(_unpack_u64(local["keys"][lane, :1], local["keys"][lane, 1:2])[0])
            raw = sd.raw_ids_by_key[key]
            if pm is not None:
                # RANDOM projector: coefficients live in the shared
                # projected space — back-project through the matrix
                # (RandomEffectModelInProjectedSpace.toRandomEffectModel)
                dense = np.asarray(pm).T @ np.asarray(
                    local["w"][lane], np.float32
                )
            else:
                dense = np.zeros(sd.global_dim, np.float32)
                valid = local["l2g"][lane] >= 0
                dense[local["l2g"][lane][valid]] = local["w"][lane][valid]
            records.append(_model_record(raw, p.task_type, dense, None, imap))
    avro_io.write_container(
        os.path.join(base, COEFFICIENTS, f"part-{mh.process_id:05d}.avro"),
        records,
        schemas.BAYESIAN_LINEAR_MODEL,
    )


def _save_streaming_re_parts(out, name, p, dc, coord, state, imap, mh):
    """Per-host streaming model save: each host writes ONE part file with
    the entities whose blocks it owns (the spilled coefficient state never
    crosses hosts; back-projection streams block metadata, not data slabs).
    Owner-computes end to end — the write-side mirror of the solve."""
    from photon_ml_tpu.io import avro as avro_io
    from photon_ml_tpu.io import schemas
    from photon_ml_tpu.io.model_io import (
        COEFFICIENTS,
        ID_INFO,
        RANDOM_EFFECT,
        _model_record,
    )

    base = os.path.join(out, RANDOM_EFFECT, name)
    if mh.coordinator_only_io():
        os.makedirs(os.path.join(base, COEFFICIENTS), exist_ok=True)
        with open(os.path.join(base, ID_INFO), "w") as f:
            f.write(f"{dc.random_effect_id}\n{dc.feature_shard_id}\n")
    mh.barrier(f"re-dir-{name}")
    means = coord.entity_means_by_raw_id(state)
    records = [
        _model_record(raw, p.task_type, np.asarray(vec, np.float32), None, imap)
        for raw, vec in sorted(means.items())
    ]
    avro_io.write_container(
        os.path.join(base, COEFFICIENTS, f"part-{mh.process_id:05d}.avro"),
        records,
        schemas.BAYESIAN_LINEAR_MODEL,
    )


def _save_factored_parts(out, name, p, dc, coord, state, imap, mh):
    """Factored random effect under multihost: each host writes ITS
    entities' flattened-W coefficients part AND latent-factor part; the
    coordinator writes the shared latent matrix + id-info (the factored
    STRUCTURE persists, model_io.save_factored_random_effect layout —
    AvroUtils.scala:244-266 semantics, per-host part files)."""
    from photon_ml_tpu.io import avro as avro_io
    from photon_ml_tpu.io import schemas
    from photon_ml_tpu.io.model_io import (
        ID_INFO,
        LATENT_FACTORS,
        LATENT_MATRIX,
        RANDOM_EFFECT,
        save_latent_factors,
    )

    base = os.path.join(out, RANDOM_EFFECT, name)
    if mh.coordinator_only_io():
        os.makedirs(os.path.join(base, LATENT_FACTORS), exist_ok=True)
        matrix = np.asarray(jax.device_get(state.matrix), np.float32)
        save_latent_factors(
            os.path.join(base, LATENT_MATRIX),
            {str(k): matrix[k] for k in range(matrix.shape[0])},
        )
    mh.barrier(f"fre-dir-{name}")
    # flattened W = V M part (scoring compat) via the shared RE writer
    w_flat = coord.random_effect_coefficients(state)
    _save_random_effect_parts(out, name, p, dc, coord, w_flat, imap, mh)
    # the factored marker goes LAST: the shared RE writer writes the plain
    # 2-line id-info, and is_factored_random_effect keys off the 3rd line
    if mh.coordinator_only_io():
        import json as _json

        from photon_ml_tpu.io.model_io import (
            LATENT_MATRIX_FEATURES,
            _split_key,
        )

        with open(os.path.join(base, ID_INFO), "w") as f:
            f.write(f"{dc.random_effect_id}\n{dc.feature_shard_id}\nfactored\n")
        # column -> feature-key binding (same artifact as the single-process
        # save): lets a consumer with a different index map realign columns
        pairs = [
            list(_split_key(imap.get_feature_name(j) or str(j)))
            for j in range(matrix.shape[1])
        ]
        with open(os.path.join(base, LATENT_MATRIX_FEATURES), "w") as f:
            _json.dump({"columns": pairs}, f)
    # this host's latent factors part
    factors = coord.latent_factors_by_raw_id(state)
    recs = [
        {"effectId": str(eid), "latentFactor": [float(v) for v in vec]}
        for eid, vec in sorted(factors.items())
    ]
    avro_io.write_container(
        os.path.join(base, LATENT_FACTORS, f"part-{mh.process_id:05d}.avro"),
        recs,
        schemas.LATENT_FACTOR,
    )




def _decode_validation(p, mh, ctx, shard_maps, needed_shards, id_types):
    """Per-host decode of the validation slice + the merged replicated
    label/weight/offset vectors — combo-invariant, decoded ONCE per run."""
    from photon_ml_tpu.cli.game_training_driver import (
        _default_evaluators,
        _input_files,
        resolve_date_range_dirs,
    )

    specs = p.evaluators or _default_evaluators(p.task_type)
    grouped_ids = sorted({idn for _, _, idn in specs if idn is not None})
    id_types = sorted(set(id_types) | set(grouped_ids))
    val_files = _input_files(resolve_date_range_dirs(
        p.validate_input_dirs, p.validate_date_range,
        p.validate_date_range_days_ago,
    ))
    host_files = host_file_share(val_files, mh.num_processes, mh.process_id)
    vgds = []
    for f, ordinal in host_files:
        gd = read_game_data(
            [f], shard_maps,
            {s: p.feature_shard_sections.get(s) or ["features"]
             for s in needed_shards},
            id_types,
            shard_intercepts={
                s: p.feature_shard_intercepts.get(s, True) for s in needed_shards
            },
        )
        vgds.append((ordinal, gd))
    file_base, nv = global_row_layout(
        len(val_files), vgds, ctx, mh.num_processes
    )

    def merge(vec_per_gd):
        return merge_row_vectors(
            vgds, file_base, nv, ctx, mh.num_processes, vec_per_gd
        )

    return {
        "specs": specs,
        "grouped_ids": grouped_ids,
        "vgds": vgds,
        "file_base": file_base,
        "nv": nv,
        "labels": merge(lambda gd: gd.response.astype(np.float32)),
        "weights": merge(lambda gd: gd.weight.astype(np.float32)),
        "offsets": merge(lambda gd: gd.offset.astype(np.float32)),
    }


def _validate(p, mh, ctx, coords, result, logger, val_data):
    """Validation metrics under multihost: each host decodes only its slice
    of the validation files; fixed-effect margins are computed locally (the
    model is replicated) and random-effect rows are ROUTED to their
    entity's owner with the training shuffle's agreed owner map
    (score_routed_rows) — cold entities/features contribute 0. Factored
    coordinates route against the flattened W = V M slab; bucketed
    coordinates against the per-bucket tuple. Scores merge with one
    collective sum; every host computes the same metric values and the
    coordinator logs them."""
    from photon_ml_tpu.evaluation.evaluators import evaluator_for
    from photon_ml_tpu.parallel.perhost_factored import (
        PerHostFactoredRandomEffectCoordinate,
    )
    from photon_ml_tpu.parallel.perhost_ingest import score_routed_rows

    specs = val_data["specs"]
    grouped_ids = val_data["grouped_ids"]
    vgds = val_data["vgds"]
    file_base = val_data["file_base"]
    nv = val_data["nv"]
    labels_v = val_data["labels"]
    weights_v = val_data["weights"]
    offsets_v = val_data["offsets"]

    scores = offsets_v.astype(np.float64).copy()
    for name in p.updating_sequence:
        coord = coords[name]
        w = result.coefficients[name]
        if name in p.fixed_effect_data_configs:
            # replicated (D,) model: in-memory psum coordinate and the
            # per-host streaming chunk coordinate score identically here
            spec = p.fixed_effect_data_configs[name]
            w_host = np.asarray(jax.device_get(w))
            local = np.zeros(nv, np.float32)
            for ordinal, gd in vgds:
                f = gd.shards[spec.feature_shard_id]
                fi, fv = csr_to_padded(f, gd.num_rows)
                sel = np.where(fi >= 0, w_host[np.maximum(fi, 0)], 0.0)
                local[file_base[ordinal] + np.arange(gd.num_rows)] = np.sum(
                    sel * fv, axis=1
                )
            scores += collective_sum(local, ctx, mh.num_processes)
        else:
            dc = p.random_effect_data_configs[name]
            parts = []
            for ordinal, gd in vgds:
                f = gd.shards[dc.feature_shard_id]
                fi, fv = csr_to_padded(f, gd.num_rows)
                vocab = gd.id_vocabs[dc.random_effect_id]
                parts.append(HostRows(
                    entity_raw_ids=[vocab[i] for i in gd.ids[dc.random_effect_id]],
                    row_index=file_base[ordinal] + np.arange(gd.num_rows, dtype=np.int64),
                    labels=gd.response.astype(np.float32),
                    weights=gd.weight.astype(np.float32),
                    offsets=gd.offset.astype(np.float32),
                    feat_idx=fi, feat_val=fv,
                    global_dim=f.dim,
                ))
            from photon_ml_tpu.parallel.perhost_streaming import (
                PerHostStreamingRandomEffectCoordinate,
                score_routed_rows_streaming,
            )

            if isinstance(coord, PerHostStreamingRandomEffectCoordinate):
                # streaming models: route rows to the block-owner host, who
                # dots them against its back-projected entity means
                vrows = concat_host_rows(parts, coord.manifest.global_dim)
                scores += score_routed_rows_streaming(
                    coord.manifest, coord.entity_means_by_raw_id(w), vrows,
                    nv, ctx, mh.num_processes, mh.process_id,
                )
                continue
            vrows = concat_host_rows(parts, coord.data.global_dim)
            if isinstance(coord, PerHostFactoredRandomEffectCoordinate):
                # route against the flattened per-entity coefficients
                # W = V M (IDENTITY local space, so the l2g lookup is exact)
                w = coord.random_effect_coefficients(w)
            scores += score_routed_rows(
                coord.data, w, vrows, nv, ctx, mh.num_processes, mh.process_id
            )

    metrics: Dict[str, float] = {}
    s = jnp.asarray(scores.astype(np.float32))
    # one hash-merge per distinct id column, shared across evaluators
    group_cols = {
        idn: jnp.asarray(merge_group_ids(vgds, file_base, nv, idn, ctx, mh.num_processes))
        for idn in grouped_ids
    }
    for etype, k, id_name in specs:
        ev = evaluator_for(etype, k or 10)
        kwargs = {"labels": jnp.asarray(labels_v), "weights": jnp.asarray(weights_v)}
        if id_name is not None:
            kwargs["group_ids"] = group_cols[id_name]
        key = etype.value if k is None else f"{etype.value}@{k}"
        metrics[key] = float(ev.evaluate(s, **kwargs))
    return metrics


if __name__ == "__main__":
    main()
