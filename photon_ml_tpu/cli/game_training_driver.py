"""GAME (GLMix) training driver.

Reference spec: cli/game/training/Driver.scala:64-537 — prepare feature maps
(:475), load GAME data (:480), build per-coordinate datasets (:485), build
evaluators (:490-508), run the config grid x coordinate descent (:511,
:313-415), save best/all models in the reference's on-disk layout
(:424-463, ModelProcessingUtils layout).

TPU-native: coordinates hold device-resident tensors (entity-major stacks
for random effects); the grid reuses compiled update kernels across combos
with identical shapes; model save goes through io/model_io (Avro wire-format
parity).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, List, Optional, Tuple

import numpy as np

import jax.numpy as jnp

from photon_ml_tpu.algorithm.coordinate_descent import CoordinateDescent, CoordinateDescentResult
from photon_ml_tpu.algorithm.factored_random_effect import (
    FactoredRandomEffectCoordinate,
    FactoredState,
    MFOptimizationConfig,
)
from photon_ml_tpu.algorithm.fixed_effect import FixedEffectCoordinate
from photon_ml_tpu.algorithm.random_effect import RandomEffectCoordinate
from photon_ml_tpu.cli.game_params import (
    CoordinateOptConfig,
    GameTrainingParams,
    parse_training_params,
)
from photon_ml_tpu.data.game import (
    GameData,
    RandomEffectDataConfig,
    build_fixed_effect_batch,
    build_random_effect_dataset,
    padded_row_coo,
)
from photon_ml_tpu.evaluation.evaluators import Evaluator, evaluator_for
from photon_ml_tpu.io import avro_data
from photon_ml_tpu.io import model_io
from photon_ml_tpu.io.index_map import IndexMap
from photon_ml_tpu.ops import losses as losses_mod
from photon_ml_tpu.optim.problem import GLMOptimizationProblem
from photon_ml_tpu.types import ModelOutputMode, TaskType, real_dtype
from photon_ml_tpu.utils.io_utils import prepare_output_dir
from photon_ml_tpu.utils.logging import PhotonLogger
from photon_ml_tpu.utils.timer import Timer

DENSE_DIM_THRESHOLD = 4096
BEST_MODEL_DIR = "best"
ALL_MODELS_DIR = "all"


def _summarize_tracker(tracker) -> str:
    """Per-coordinate convergence summary from the last update's OptResult
    (the reference's per-coordinate OptimizationTracker logging,
    CoordinateDescent.scala:150-156 / RandomEffectOptimizationTracker).

    Distributed solvers trim entity padding at the source
    (``parallel.distributed.trim_entity_tracker``), so every tracker that
    arrives here covers real entities only.
    """
    import numpy as np

    from photon_ml_tpu.optim.common import (
        OptResult,
        summarize_result,
        summarize_stacked_results,
    )

    if tracker is None:
        return ""
    # OptResult IS a NamedTuple — test for it BEFORE the generic tuple
    # (bucketed) case or every tracker would fall into the tuple branch
    if isinstance(tracker, OptResult):
        if np.asarray(tracker.reason).ndim >= 1:
            return summarize_stacked_results(tracker)
        return summarize_result(tracker)
    if isinstance(tracker, tuple):  # bucketed: one OptResult per bucket
        parts = [_summarize_tracker(t) for t in tracker]
        return " | ".join(f"bucket{j}: {s}" for j, s in enumerate(parts) if s)
    return ""


def _input_files(dirs: List[str]) -> List[str]:
    files = []
    for d in dirs:
        if os.path.isfile(d):
            files.append(d)
        else:
            files.extend(
                os.path.join(d, f)
                for f in sorted(os.listdir(d))
                if not f.startswith((".", "_"))
            )
    return files


def resolve_date_range_dirs(
    dirs: List[str],
    date_range: Optional[str],
    days_ago: Optional[str],
) -> List[str]:
    """Expand input dirs into their daily/yyyy/MM/dd subdirs within the
    requested range (IOUtils.scala:85-130 discovery); no range -> unchanged."""
    if not date_range and not days_ago:
        return dirs
    from photon_ml_tpu.utils.date_range import DateRange, expand_date_range_paths

    dr = (
        DateRange.from_string(date_range)
        if date_range
        else DateRange.from_days_ago(days_ago)
    )
    out: List[str] = []
    for d in dirs:
        try:
            out.extend(expand_date_range_paths(d, dr))
        except FileNotFoundError:
            pass  # error only if the union over ALL dirs is empty (IOUtils parity)
    if not out:
        raise FileNotFoundError(
            f"no daily inputs under any of {dirs} within {dr.start}..{dr.end}"
        )
    return out


class GameTrainingDriver:
    """Builds coordinates from params + data, runs the grid, saves models."""

    def __init__(self, params: GameTrainingParams, logger: Optional[PhotonLogger] = None):
        params.validate()
        self.params = params
        from photon_ml_tpu.compile import ExecutionPlan, compile_stats

        # ONE execution plan resolves every orthogonal policy — shape
        # ladder, solve schedule (ladder-bound), sharding mode, sparse
        # selection — and records every composition decision; the
        # coordinates below all read from it instead of re-resolving flags
        self.plan = ExecutionPlan.resolve(
            shape_canonicalization=params.shape_canonicalization,
            solve_compaction=params.solve_compaction,
            adaptive_schedule=params.adaptive_schedule,
            distributed=params.distributed,
            streaming=params.streaming_random_effects,
            bucketed=params.bucketed_random_effects,
            fused_cycle=params.fused_cycle,
            vmapped_grid=params.vmapped_grid,
            plan=params.plan,
            # warm starts inherit the prior run's realized costs; cold runs
            # read back their own sidecar on the next invocation
            cost_model_dir=(params.warm_start_from or params.output_dir),
        )
        self.bucketer = self.plan.bucketer
        self.solve_schedule = self.plan.schedule
        compile_stats.install_xla_listeners()
        self._own_logger = logger is None
        self.logger = logger or PhotonLogger(
            os.path.join(params.output_dir, "photon-ml-tpu-game.log")
        )
        self.timer = Timer(self.logger.info)
        self.shard_index_maps: Dict[str, IndexMap] = {}
        self.train_data: Optional[GameData] = None
        self.validation_data: Optional[GameData] = None
        self.re_datasets: Dict[str, object] = {}
        self.bucketed_bundles: Dict[str, object] = {}  # --bucketed-random-effects
        self.streaming_manifests: Dict[str, object] = {}  # --streaming-random-effects
        self.fe_batches: Dict[str, object] = {}
        # combo results: (config map, CoordinateDescentResult, metrics)
        self.results: List[Tuple[Dict[str, CoordinateOptConfig], CoordinateDescentResult, Dict[str, float]]] = []
        self.combo_coords: List[Dict[str, object]] = []  # per-combo coordinates
        self.best_index: int = 0
        # --- incremental delta retraining (photon_ml_tpu.retrain) ---------
        self.retrain_prior = None  # prior run's RetrainManifest (or None)
        self.delta_plan = None  # resolved DeltaPlan (or None: cold run)
        self.block_deltas: Dict[str, list] = {}  # streaming coord -> [BlockDelta]
        self._train_files: List[str] = []
        self._frozen_blocks: Dict[str, frozenset] = {}  # coord -> skip set
        self._warm_fixed: Dict[str, np.ndarray] = {}
        self._warm_dense_re: Dict[str, np.ndarray] = {}
        self._warm_spilled: Dict[str, object] = {}  # coord -> SpilledREState
        self._warm_bucketed: Dict[str, list] = {}  # coord -> per-bucket stacks
        self._warm_means_cache: Dict[str, Optional[dict]] = {}
        self._coord_cache_keys: Dict[str, Optional[str]] = {}
        self._data_cache_key: Optional[str] = None
        self._eval_identity_cache: Optional[Dict[str, object]] = None

    # ------------------------------------------------------------------
    def _shard_ids(self) -> List[str]:
        p = self.params
        shards = {spec.feature_shard_id for spec in p.fixed_effect_data_configs.values()}
        shards |= {cfg.feature_shard_id for cfg in p.random_effect_data_configs.values()}
        return sorted(shards)

    def _train_dirs(self) -> List[str]:
        p = self.params
        return resolve_date_range_dirs(
            p.train_input_dirs, p.train_date_range, p.train_date_range_days_ago
        )

    def _validate_dirs(self) -> List[str]:
        p = self.params
        return resolve_date_range_dirs(
            p.validate_input_dirs or [],
            p.validate_date_range,
            p.validate_date_range_days_ago,
        )

    def prepare_feature_maps(self) -> None:
        """GAMEDriver.prepareFeatureMaps parity: offheap load (:76-82), the
        deprecated NameAndTerm vocabulary path, or whole-dataset scan
        (:49-69) — in that priority order."""
        p = self.params
        paths = _input_files(self._train_dirs())
        nt_container = None
        if p.feature_name_and_term_set_path and not p.offheap_indexmap_dir:
            from photon_ml_tpu.io.name_and_term import NameAndTermFeatureSetContainer

            # resolve sections PER SHARD (incl. the "features" default for
            # unconfigured shards) so no shard silently gets an empty vocab
            all_sections = sorted(
                {
                    s
                    for shard in self._shard_ids()
                    for s in (p.feature_shard_sections.get(shard) or ["features"])
                }
            )
            nt_container = NameAndTermFeatureSetContainer.read_from_text(
                p.feature_name_and_term_set_path, all_sections
            )
        for shard in self._shard_ids():
            if p.offheap_indexmap_dir:
                from photon_ml_tpu.io.offheap import load_shard_index_map

                self.shard_index_maps[shard] = load_shard_index_map(
                    p.offheap_indexmap_dir, shard
                )
            elif nt_container is not None:
                sections = p.feature_shard_sections.get(shard) or ["features"]
                add_intercept = p.feature_shard_intercepts.get(shard, True)
                self.shard_index_maps[shard] = nt_container.index_map(
                    sections, add_intercept
                )
            else:
                sections = p.feature_shard_sections.get(shard) or ["features"]
                keys = avro_data.collect_feature_keys(paths, sections)
                add_intercept = p.feature_shard_intercepts.get(shard, True)
                self.shard_index_maps[shard] = IndexMap.build(keys, add_intercept)
            self.logger.info(
                f"feature shard {shard!r}: {len(self.shard_index_maps[shard])} features"
            )

    # ------------------------------------------------------------------
    def _id_types(self) -> List[str]:
        """Random-effect grouping ids + any id column an evaluator needs
        (e.g. PRECISION@K:documentId)."""
        ids = {cfg.random_effect_id for cfg in self.params.random_effect_data_configs.values()}
        ids |= {id_name for _, _, id_name in self.params.evaluators if id_name}
        return sorted(ids)

    def _next_stream_state_seq(self) -> int:
        self._stream_state_seq = getattr(self, "_stream_state_seq", 0) + 1
        return self._stream_state_seq

    def _tensor_cache(self):
        """The --tensor-cache store (lazy), or None."""
        if not self.params.tensor_cache_dir:
            return None
        if not hasattr(self, "_tensor_cache_obj"):
            from photon_ml_tpu.io.tensor_cache import TensorCache

            self._tensor_cache_obj = TensorCache(self.params.tensor_cache_dir)
        return self._tensor_cache_obj

    def _ingest_cache_config(self) -> Dict[str, object]:
        """The ingest-config part of every tensor-cache key: anything that
        changes the decoded columns or the feature index assignment must
        change the key (a config change is a MISS, never a stale hit) —
        including the canonical shape ladder, which changes the PADDED
        tensors a hit would serve."""
        from photon_ml_tpu.io.tensor_cache import index_map_digest

        p = self.params
        return {
            "sections": p.feature_shard_sections,
            "intercepts": p.feature_shard_intercepts,
            "id_types": self._id_types(),
            "ladder": (
                f"{self.bucketer.base}:{self.bucketer.growth:g}"
                if self.bucketer is not None else None
            ),
            "index_maps": {
                shard: index_map_digest(imap)
                for shard, imap in sorted(self.shard_index_maps.items())
            },
        }

    # --- incremental delta retraining (photon_ml_tpu.retrain) -------------
    def _ingest_inputs(self) -> Dict[str, object]:
        """The PRE-feature-map ingest identity (JSON-safe by construction):
        everything that determines the decoded columns and feature space
        given the input files. Equality with the prior manifest's record
        (plus unchanged files) proves the whole ingest output is identical
        — the delta planner's cheap short-circuit check; the full
        index-map-digest equality (:meth:`_ingest_digest`) gates
        block-level reuse after feature maps build."""
        p = self.params
        return {
            "sections": {k: list(v) for k, v in sorted(
                (p.feature_shard_sections or {}).items())},
            "intercepts": {k: bool(v) for k, v in sorted(
                (p.feature_shard_intercepts or {}).items())},
            "id_types": self._id_types(),
            "ladder": (
                f"{self.bucketer.base}:{self.bucketer.growth:g}"
                if self.bucketer is not None else None
            ),
            "offheap_indexmap_dir": p.offheap_indexmap_dir,
            "name_and_term": p.feature_name_and_term_set_path,
        }

    def _eval_identity(self) -> Dict[str, object]:
        """Validation-side identity (validation file stats + evaluator
        specs): gates the delta short-circuit only — a changed validation
        set must re-score, even when training has nothing left to do.
        Computed ONCE, before the validation files are read (_run_guarded
        snapshots it next to the train stat tokens): like the train side,
        a file overwritten mid-run is recorded with its pre-overwrite
        identity so tomorrow's diff classifies it changed — and a
        validation file deleted mid-run cannot fail the manifest write of
        an otherwise-completed training run."""
        if self._eval_identity_cache is None:
            from photon_ml_tpu.io.tensor_cache import file_stat_token

            p = self.params
            val_files = (
                _input_files(self._validate_dirs())
                if p.validate_input_dirs else []
            )
            self._eval_identity_cache = {
                "validate_files": file_stat_token(val_files),
                "evaluators": [
                    [etype.value, k, id_name]
                    for etype, k, id_name in (p.evaluators or [])
                ],
            }
        return self._eval_identity_cache

    def _ingest_digest(self) -> str:
        """SHA-256 of the FULL ingest cache config (incl. per-shard index
        map digests) — the feature-space identity block reuse requires."""
        import hashlib as _hashlib
        import json as _json

        return _hashlib.sha256(
            _json.dumps(
                self._ingest_cache_config(), sort_keys=True, default=str
            ).encode()
        ).hexdigest()

    def _maybe_plan_delta(self, train_files: List[str]) -> None:
        """Load the prior manifest and resolve the delta plan
        (--warm-start-from). ANY failure reading the prior degrades to a
        recorded cold run — a broken prior must never produce a wrong warm
        result (chaos-covered via the retrain.delta_plan fault site)."""
        p = self.params
        if not p.warm_start_from:
            return
        from photon_ml_tpu import retrain

        try:
            self.retrain_prior = retrain.load_prior_manifest(p.warm_start_from)
            combos = p.config_grid()
            combo_configs = None
            if len(combos) == 1:
                combo_configs = {
                    name: str(combos[0].get(name, CoordinateOptConfig()))
                    for name in p.updating_sequence
                }
            # classification stays INSIDE the guard: a parseable-but-
            # malformed manifest (bad file_stats entries, wrong field
            # shapes) surfaces here, not as a crashed training run
            self.delta_plan = retrain.plan_delta(
                self.retrain_prior,
                train_files,
                task=p.task_type.value,
                updating_sequence=p.updating_sequence,
                ingest_inputs=self._ingest_inputs(),
                combo_configs=combo_configs,
                eval_identity=self._eval_identity(),
            )
        except Exception as e:  # noqa: BLE001 — any unreadable/corrupt/malformed prior (bad JSON, vanished model, bad stat tokens, injected fault) must degrade to a cold run, never propagate into a wrong warm result
            self.retrain_prior = None
            self.delta_plan = None
            self.logger.warn(
                f"--warm-start-from {p.warm_start_from}: prior manifest "
                f"unusable ({type(e).__name__}: {e}) — retraining cold"
            )
            return
        self.logger.info(
            f"delta retrain plan: files {self.delta_plan.files.describe()}; "
            + " ".join(
                f"{n}={c.status}"
                for n, c in self.delta_plan.coordinates.items()
            )
        )
        for line in self.delta_plan.describe_decisions():
            self.logger.info(f"delta retrain: {line}")

    def _dirty_entities(self) -> Dict[str, set]:
        """Raw entity ids whose data moved (probed once from the changed/
        new files' id columns — cost scales with the delta)."""
        if self.delta_plan is None:
            return {}
        if not self.delta_plan.dirty_entities:
            from photon_ml_tpu import retrain

            self.delta_plan.dirty_entities = retrain.probe_dirty_entities(
                self.delta_plan.files, self._id_types()
            )
            for t, s in sorted(self.delta_plan.dirty_entities.items()):
                self.logger.info(
                    f"delta retrain: {len(s)} dirty {t!r} entities"
                )
        return self.delta_plan.dirty_entities

    def prepare_datasets(self) -> None:
        from photon_ml_tpu.data.game import (
            game_data_from_arrays,
            game_data_to_arrays,
        )

        p = self.params
        cache = self._tensor_cache()
        # reuse the file list the delta plan + manifest stat tokens were
        # computed from (one file set for plan, ingest, AND retrain.json
        # — a part file landing between the listings would otherwise be
        # ingested while the plan still says 'unchanged'); the fallback
        # covers direct prepare_datasets() calls outside run()
        train_files = self._train_files or _input_files(self._train_dirs())
        self._train_files = train_files
        train_key = (
            cache.key_for(
                train_files, {"kind": "game_data", **self._ingest_cache_config()}
            )
            if cache is not None
            else None
        )
        self._data_cache_key = train_key
        if (
            cache is not None
            and self.retrain_prior is not None
            and self.retrain_prior.data_cache_key
            and self.retrain_prior.data_cache_key != train_key
        ):
            # cache hygiene: the prior run's whole-set ingest entry can
            # never be addressed again (its file stats are history) —
            # invalidate it so the store stays bounded across daily deltas.
            # Streaming-block entries are deliberately KEPT: the prior
            # manifest dir (which the block reuse below reads) may BE one.
            if cache.invalidate(self.retrain_prior.data_cache_key):
                self.logger.info(
                    "tensor cache: invalidated superseded prior ingest "
                    f"entry {self.retrain_prior.data_cache_key[:12]}"
                )
        hit = cache.get(train_key) if cache is not None else None
        if hit is not None:
            self.train_data = game_data_from_arrays(hit.arrays, hit.meta)
            self.logger.info(
                f"tensor cache HIT {train_key[:12]}: Avro decode skipped"
            )
        else:
            self.train_data = avro_data.read_game_data(
                train_files,
                self.shard_index_maps,
                p.feature_shard_sections,
                self._id_types(),
                shard_intercepts=p.feature_shard_intercepts or None,
            )
            if cache is not None:
                from photon_ml_tpu.resilience import RetryError

                try:
                    arrays, meta = game_data_to_arrays(self.train_data)
                    cache.put(train_key, arrays, meta)
                    self.logger.info(f"tensor cache stored {train_key[:12]}")
                except RetryError as e:
                    self.logger.info(f"tensor cache write failed (uncached): {e}")
        self.logger.info(f"training rows: {self.train_data.num_rows}")
        if p.validate_input_dirs:
            self.validation_data = avro_data.read_game_data(
                _input_files(self._validate_dirs()),
                self.shard_index_maps,
                p.feature_shard_sections,
                self._id_types(),
                shard_intercepts=p.feature_shard_intercepts or None,
                id_vocabs=self.train_data.id_vocabs,
            )
            self.logger.info(f"validation rows: {self.validation_data.num_rows}")

        for name, spec in p.fixed_effect_data_configs.items():
            dense = len(self.shard_index_maps[spec.feature_shard_id]) <= DENSE_DIM_THRESHOLD
            self.fe_batches[name] = build_fixed_effect_batch(
                self.train_data, spec.feature_shard_id, dense=dense
            )
        for name, cfg in p.random_effect_data_configs.items():
            if name in p.factored_configs and cfg.projector != "IDENTITY":
                # the factored coordinate factors the UNprojected dataset
                cfg = RandomEffectDataConfig(
                    **{**cfg.__dict__, "projector": "IDENTITY"}
                )
            if p.streaming_random_effects and name not in p.factored_configs:
                # out-of-core: write the entity blocks to disk ONCE (each
                # block built and released in turn — the full stack never
                # exists); combos stream the same blocks
                from photon_ml_tpu.algorithm.streaming_random_effect import (
                    write_re_entity_blocks,
                )

                budget = (
                    int(p.re_memory_budget_mb * 1e6)
                    if p.re_memory_budget_mb is not None else None
                )
                block_key = (
                    cache.key_for(
                        train_files,
                        {"kind": "streaming_re_blocks", "coord": name,
                         "config": dataclasses.asdict(cfg),
                         "budget": budget,
                         **self._ingest_cache_config()},
                    )
                    if cache is not None else None
                )
                self._coord_cache_keys[name] = block_key
                if self._delta_streaming_build(
                    name, cfg, budget, cache, train_files
                ):
                    continue
                self.streaming_manifests[name] = write_re_entity_blocks(
                    self.train_data, cfg,
                    os.path.join(p.output_dir, "streaming-re", name),
                    # `is None`, not falsy: a (rejected-downstream) zero
                    # budget must not silently pass BOTH sizing modes
                    block_entities=None if budget is not None else 1024,
                    memory_budget_bytes=budget,
                    # "off", never None: the plan consumed the env already
                    bucketer=self.bucketer or "off",
                    tensor_cache=cache,
                    cache_key=block_key,
                )
                self.logger.info(
                    f"streaming RE {name}: "
                    f"{len(self.streaming_manifests[name].blocks)} blocks, "
                    f"max resident slab "
                    f"{self.streaming_manifests[name].max_block_bytes}B"
                )
                continue
            if p.bucketed_random_effects and name not in p.factored_configs:
                # bucketed coordinates own per-bucket stacks — building the
                # single globally-padded stack here would allocate exactly
                # the memory bucketing exists to avoid. Build the shared
                # bundle ONCE; combos reuse it.
                from photon_ml_tpu.algorithm.bucketed_random_effect import (
                    BucketedDatasetBundle,
                )

                self.bucketed_bundles[name] = BucketedDatasetBundle.build(
                    self.train_data, cfg, bucketer=self.bucketer or "off"
                )
                continue
            re_key = (
                cache.key_for(
                    train_files,
                    {"kind": "re_dataset", "coord": name,
                     "config": dataclasses.asdict(cfg),
                     **self._ingest_cache_config()},
                )
                if cache is not None else None
            )
            self._coord_cache_keys[name] = re_key
            if (
                cache is not None
                and self.retrain_prior is not None
                and (prior_rec := self.retrain_prior.coordinates.get(name))
                is not None
                and prior_rec.kind == "random"
                and prior_rec.cache_key
                and prior_rec.cache_key != re_key
            ):
                # superseded in-memory RE dataset entry (warm starts read
                # the saved MODEL, never the cached dataset) — same
                # hygiene as the whole-set ingest entry above
                cache.invalidate(prior_rec.cache_key)
            self.re_datasets[name] = build_random_effect_dataset(
                self.train_data, cfg,
                tensor_cache=cache,
                cache_key=re_key,
            )

    def _load_prior_layout(self, name: str, rec):
        """The prior run's streaming block layout, or None with the
        degrade logged — ONE load-and-degrade contract shared by the
        unchanged-verbatim-reuse and dirty-delta-build paths (a vanished/
        corrupt prior layout costs a recorded cold rebuild, never a
        failed run or stale blocks)."""
        from photon_ml_tpu.algorithm.streaming_random_effect import (
            StreamingREManifest,
        )

        try:
            return StreamingREManifest.load(rec.streaming_manifest_dir)
        except Exception as e:  # noqa: BLE001 — a vanished/corrupt prior block layout (lost cache entry) must degrade to a recorded cold build, never fail or warm wrongly
            self.logger.warn(
                f"delta retrain [{name}]: prior block layout at "
                f"{rec.streaming_manifest_dir} unusable "
                f"({type(e).__name__}: {e}) — cold block build"
            )
            return None

    def _delta_streaming_build(
        self, name: str, cfg, budget: Optional[int], cache, train_files,
    ) -> bool:
        """Build ``name``'s entity blocks through the DELTA builder (prior
        blocking pinned, unchanged payloads reused, per-block
        classification recorded) when the plan says the coordinate is
        dirty and the prior run's blocks are reusable. Returns True when
        it handled the build; False falls back to the cold builder with
        the degrade reason logged."""
        p = self.params
        plan = self.delta_plan
        prior = self.retrain_prior
        if plan is None or prior is None:
            return False
        cdelta = plan.coordinates.get(name)
        rec = prior.coordinates.get(name)
        if cdelta is None or rec is None:
            return False
        if (
            cdelta.status == "unchanged"
            and rec.kind == "streaming_random"
            and rec.streaming_manifest_dir
            and prior.ingest_digest == self._ingest_digest()
        ):
            # the whole coordinate is unchanged (clean files + identical
            # ingest): the prior block layout is verbatim THIS run's — no
            # rebuild, no re-decode, just open it (row space and vocab are
            # identical by construction). Falls through to the cold build
            # if the durable layout has since vanished.
            prior_sm = self._load_prior_layout(name, rec)
            if prior_sm is None:
                return False
            self.streaming_manifests[name] = prior_sm
            self._coord_cache_keys[name] = rec.cache_key
            self.logger.info(
                f"delta retrain [{name}]: coordinate unchanged — prior "
                f"block layout reused verbatim ({len(prior_sm.blocks)} "
                "blocks, no rebuild)"
            )
            return True
        if cdelta.status != "dirty":
            return False
        if rec.kind != "streaming_random" or not rec.streaming_manifest_dir:
            self.logger.info(
                f"delta retrain [{name}]: prior coordinate was "
                f"{rec.kind!r}, not streaming — cold block build"
            )
            return False
        if prior.ingest_digest != self._ingest_digest():
            self.logger.info(
                f"delta retrain [{name}]: feature space changed since the "
                "prior run (index-map digests differ) — block reuse off, "
                "cold block build (warm start stays on, by feature name)"
            )
            return False
        from photon_ml_tpu import retrain

        prior_sm = self._load_prior_layout(name, rec)
        if prior_sm is None:
            return False
        dirty_raw = self._dirty_entities().get(cfg.random_effect_id, set())
        delta_key = (
            cache.key_for(
                train_files,
                {"kind": "streaming_re_blocks_delta", "coord": name,
                 "config": dataclasses.asdict(cfg), "budget": budget,
                 "prior": prior.model_dir,
                 "dirty": retrain.dirty_set_digest(dirty_raw),
                 **self._ingest_cache_config()},
            )
            if cache is not None else None
        )
        manifest, deltas = retrain.build_delta_streaming_manifest(
            self.train_data, cfg,
            os.path.join(p.output_dir, "streaming-re", name),
            prior_sm, dirty_raw,
            bucketer=self.bucketer or "off",
            block_entities=None if budget is not None else 1024,
            memory_budget_bytes=budget,
            tensor_cache=cache,
            cache_key=delta_key,
        )
        self.streaming_manifests[name] = manifest
        self.block_deltas[name] = deltas
        if delta_key is not None:
            self._coord_cache_keys[name] = delta_key
        by_status = {"unchanged": 0, "dirty": 0, "new": 0}
        for d in deltas:
            by_status[d.status] = by_status.get(d.status, 0) + 1
        self.logger.info(
            f"delta retrain [{name}]: {len(deltas)} blocks — "
            f"{by_status['unchanged']} unchanged (solve skipped, payload "
            f"reused), {by_status['dirty']} dirty, {by_status['new']} new"
        )
        return True

    # ------------------------------------------------------------------
    def _mesh_context(self):
        """One MeshContext over all visible devices (lazy; --distributed)."""
        if not hasattr(self, "_mesh_ctx"):
            from photon_ml_tpu.parallel import MeshContext, data_mesh

            self._mesh_ctx = MeshContext(data_mesh())
            self.logger.info(
                f"distributed: {self._mesh_ctx.num_devices}-device mesh"
            )
        return self._mesh_ctx

    def _build_coordinates(self, opt_configs: Dict[str, CoordinateOptConfig]) -> Dict[str, object]:
        """Coordinate objects per updating sequence
        (cli/game/training/Driver.scala:344-402). With --distributed, fixed
        effects solve row-sharded, random effects entity-sharded, and
        factored coordinates entity-sharded with a psum'd latent refit over
        the device mesh."""
        p = self.params
        coords: Dict[str, object] = {}
        for name in p.updating_sequence:
            cfg = opt_configs.get(name, CoordinateOptConfig())
            if name in p.fixed_effect_data_configs:
                fe = FixedEffectCoordinate(
                    self.fe_batches[name],
                    GLMOptimizationProblem(
                        task=p.task_type,
                        optimizer=cfg.optimizer,
                        optimizer_config=cfg.optimizer_config(),
                        regularization=cfg.regularization_context(),
                        # variance is computed ONCE at save time from the
                        # final state (coefficient_variances), not per
                        # update inside the CD loop
                    ),
                    down_sampling_rate=(
                        cfg.down_sampling_rate if cfg.down_sampling_rate < 1.0 else None
                    ),
                )
                if p.distributed:
                    from photon_ml_tpu.parallel.distributed import (
                        DistributedFixedEffectCoordinate,
                    )

                    fe = DistributedFixedEffectCoordinate(fe, self._mesh_context())
                coords[name] = fe
            elif name in p.factored_configs:
                spec = p.factored_configs[name]
                fac = FactoredRandomEffectCoordinate(
                    self.re_datasets[name],
                    p.task_type,
                    mf_config=MFOptimizationConfig(
                        spec.mf_num_iterations, spec.latent_dim
                    ),
                    re_optimizer=spec.random_effect.optimizer,
                    re_optimizer_config=spec.random_effect.optimizer_config(),
                    re_regularization=spec.random_effect.regularization_context(),
                    latent_optimizer=spec.latent_factor.optimizer,
                    latent_optimizer_config=spec.latent_factor.optimizer_config(),
                    latent_regularization=spec.latent_factor.regularization_context(),
                )
                if p.distributed:
                    from photon_ml_tpu.parallel.distributed import (
                        DistributedFactoredRandomEffectCoordinate,
                    )

                    fac = DistributedFactoredRandomEffectCoordinate(
                        fac, self._mesh_context()
                    )
                coords[name] = fac
            elif p.streaming_random_effects:
                from photon_ml_tpu.algorithm.streaming_random_effect import (
                    StreamingRandomEffectCoordinate,
                )

                common = dict(
                    task=p.task_type,
                    optimizer=cfg.optimizer,
                    optimizer_config=cfg.optimizer_config(),
                    regularization=cfg.regularization_context(),
                    # the plan threads schedule + sparse selection +
                    # prefetch in one object (compaction and the sparse
                    # race now reach the streaming path)
                    plan=self.plan,
                    # delta retrain: blocks classified unchanged skip
                    # their solves (coefficients carry forward bitwise
                    # from the warm-seeded state; empty/None when cold)
                    frozen_blocks=self._frozen_blocks.get(name),
                    # warm delta retrain seeds the adaptive convergence
                    # ledger from the prior run's record so importance
                    # ordering survives across runs (manifest-sidecar
                    # ledgers, when present, still win inside the coord)
                    ledger_seed=(
                        rec.convergence_ledger
                        if self.retrain_prior is not None
                        and (rec := self.retrain_prior.coordinates.get(name))
                        is not None
                        else None
                    ),
                    # spilled state goes under OUR output dir, never inside
                    # the manifest dir (a --tensor-cache hit points that at
                    # the shared cache entry, which must stay run-agnostic);
                    # unique per coordinate INSTANCE like the coordinate's
                    # own default (grid combos must not share spill dirs)
                    state_root=os.path.join(
                        p.output_dir, "streaming-re-state",
                        f"{name}-{os.getpid()}-{self._next_stream_state_seq()}",
                    ),
                )
                if p.distributed:
                    # entity-sharded streaming (the streaming x distributed
                    # fence is gone): under this single-process driver the
                    # mesh holds one process, so the merges are identities
                    # and results are bitwise the plain streaming run's.
                    # Genuinely multi-process runs MUST use the multihost
                    # driver — its manifests are per-host partitions of an
                    # agreed plan. This driver's manifest holds ALL blocks,
                    # so wiring num_processes>1 here would psum P identical
                    # full score vectors (P-times-counted, silently wrong):
                    # refuse loudly instead.
                    import jax as _jax

                    from photon_ml_tpu.parallel.perhost_streaming import (
                        PerHostStreamingRandomEffectCoordinate,
                    )

                    if _jax.process_count() > 1:
                        raise ValueError(
                            "--streaming-random-effects with --distributed "
                            "under a multi-process runtime requires the "
                            "multihost driver (game_multihost_driver): this "
                            "driver's single-host manifest owns every block "
                            "on every process, so merging would "
                            f"{_jax.process_count()}x-count the scores"
                        )
                    coords[name] = PerHostStreamingRandomEffectCoordinate(
                        manifest=self.streaming_manifests[name],
                        ctx=self._mesh_context(),
                        num_processes=1,
                        **common,
                    )
                else:
                    coords[name] = StreamingRandomEffectCoordinate(
                        manifest=self.streaming_manifests[name], **common
                    )
            elif p.bucketed_random_effects:
                from photon_ml_tpu.algorithm.bucketed_random_effect import (
                    BucketedRandomEffectCoordinate,
                )

                coords[name] = BucketedRandomEffectCoordinate(
                    self.train_data,
                    p.random_effect_data_configs[name],
                    p.task_type,
                    optimizer=cfg.optimizer,
                    optimizer_config=cfg.optimizer_config(),
                    regularization=cfg.regularization_context(),
                    bundle=self.bucketed_bundles[name],
                    mesh_ctx=self._mesh_context() if p.distributed else None,
                    solve_schedule=self.solve_schedule,
                    adaptive=self.plan.adaptive,
                )
            else:
                scheduled_mesh = p.distributed and self.solve_schedule is not None
                re = RandomEffectCoordinate(
                    self.re_datasets[name],
                    p.task_type,
                    optimizer=cfg.optimizer,
                    optimizer_config=cfg.optimizer_config(),
                    regularization=cfg.regularization_context(),
                    solve_schedule=self.solve_schedule,
                    solve_label=name,
                    # distributed solves pin sparse off at the shard level
                    # — don't race/build a slab the solver will discard
                    sparse_kernel="off" if p.distributed else None,
                    # compaction x mesh (the old fence is gone): the
                    # coordinate pads + GSPMD-shards its entity axis and
                    # runs the scheduler's shared chunk kernels over the
                    # sharded arrays — the compaction loop stays host-side
                    # outside the mesh program (the mesh path's allclose
                    # numerical contract, like the shard_map engine)
                    mesh_ctx=self._mesh_context() if scheduled_mesh else None,
                )
                if p.distributed and not scheduled_mesh:
                    # one-shot mesh solves keep the measured shard_map engine
                    from photon_ml_tpu.parallel.distributed import (
                        DistributedRandomEffectSolver,
                    )

                    re = DistributedRandomEffectSolver(re, self._mesh_context())
                coords[name] = re
        return coords

    # ------------------------------------------------------------------
    def _training_loss_fn(self):
        """Training-objective loss evaluator over total scores
        (the loss-evaluator analogue of Driver.scala:185-202)."""
        loss = losses_mod.for_task(self.params.task_type)
        labels = jnp.asarray(self.train_data.response)
        offsets = jnp.asarray(self.train_data.offset)
        weights = jnp.asarray(self.train_data.weight)

        def fn(total_scores):
            return jnp.sum(weights * loss.loss(total_scores + offsets, labels))

        return fn

    # ------------------------------------------------------------------
    def _entity_position_of_vocab(self, name: str) -> np.ndarray:
        """raw-vocab index -> tensor position in coordinate ``name``'s
        stacked coefficients (built from training rows)."""
        cfg = self.params.random_effect_data_configs[name]
        ids = self.train_data.ids[cfg.random_effect_id]
        ds = self.re_datasets[name]
        entity_pos = np.asarray(ds.entity_pos)
        vocab_size = len(self.train_data.id_vocabs[cfg.random_effect_id])
        pos = np.full(vocab_size, -1, np.int32)
        # only rows that carry a real tensor position: dropped-passive rows
        # have entity_pos -1 and must not clobber their entity's mapping
        known = entity_pos >= 0
        pos[ids[known]] = entity_pos[known]
        return pos

    def _validation_scorer(self, coords: Dict[str, object]):
        """coefficients map -> (Nv,) margin scores on validation data.

        Fixed effects score via matvec; random effects back-project to the
        global feature space and gather per validation row (the
        RandomEffectModel.scala:129-158 cogroup as static gathers). Rows of
        unseen entities contribute 0.
        """
        p = self.params
        vdata = self.validation_data
        nv = vdata.num_rows
        fe_feats = {}
        re_info = {}
        for name in p.updating_sequence:
            if name in p.fixed_effect_data_configs:
                spec = p.fixed_effect_data_configs[name]
                dense = len(self.shard_index_maps[spec.feature_shard_id]) <= DENSE_DIM_THRESHOLD
                fe_feats[name] = build_fixed_effect_batch(
                    vdata, spec.feature_shard_id, dense=dense
                ).features
            else:
                cfg = p.random_effect_data_configs[name]
                # padded per-row COO of validation rows in the GLOBAL space
                cols, vals = padded_row_coo(vdata.shards[cfg.feature_shard_id])
                vocab_ids = vdata.ids[cfg.random_effect_id]
                coord = coords.get(name)
                from photon_ml_tpu.algorithm.bucketed_random_effect import (
                    BucketedRandomEffectCoordinate,
                )
                from photon_ml_tpu.algorithm.streaming_random_effect import (
                    StreamingRandomEffectCoordinate,
                )

                if isinstance(
                    coord,
                    (BucketedRandomEffectCoordinate, StreamingRandomEffectCoordinate),
                ):
                    # map each validation row into the CONCATENATED stack:
                    # bucket/block row offset + within-unit tensor position
                    bucket_of, pos_in_bucket = coord.vocab_position_maps()
                    sizes = coord.stack_sizes()
                    offsets = np.concatenate([[0], np.cumsum(sizes)[:-1]]).astype(np.int32)
                    safe_vid = np.maximum(vocab_ids, 0)
                    b_of = bucket_of[safe_vid]
                    p_in = pos_in_bucket[safe_vid]
                    ent_pos = np.where(
                        (vocab_ids >= 0) & (b_of >= 0) & (p_in >= 0),
                        offsets[np.maximum(b_of, 0)] + p_in,
                        -1,
                    ).astype(np.int32)
                    re_info[name] = (
                        jnp.asarray(cols), jnp.asarray(vals),
                        ("bucketed", coord, jnp.asarray(ent_pos)),
                    )
                else:
                    pos_of_vocab = self._entity_position_of_vocab(name)
                    ent_pos = np.where(
                        vocab_ids >= 0, pos_of_vocab[np.maximum(vocab_ids, 0)], -1
                    ).astype(np.int32)
                    re_info[name] = (
                        jnp.asarray(cols), jnp.asarray(vals), jnp.asarray(ent_pos)
                    )

        def scorer(params_map):
            from photon_ml_tpu.algorithm.random_effect import global_coefficients

            total = jnp.zeros((nv,), jnp.float32)
            for name in p.updating_sequence:
                w = params_map[name]
                if name in fe_feats:
                    total = total + fe_feats[name].matvec(w)
                else:
                    cols, vals, info = re_info[name]
                    if isinstance(info, tuple) and info and info[0] == "bucketed":
                        # concatenate the per-bucket stacks once: entity
                        # position = bucket row offset + within-bucket pos,
                        # then the SAME single gather as the plain path
                        _, coord, ent_pos = info
                        wg = jnp.concatenate(
                            coord.global_coefficient_stacks(w), axis=0
                        )
                    else:
                        ent_pos = info
                        ds = self.re_datasets[name]
                        if isinstance(w, FactoredState):
                            wg = w.v @ w.matrix  # (E, D_global): IDENTITY local space
                        else:
                            # distributed solves pad the entity axis; slice back
                            wg = global_coefficients(ds, w[: ds.num_entities])
                    safe_pos = jnp.maximum(ent_pos, 0)
                    safe_cols = jnp.maximum(cols, 0)
                    gathered = wg[safe_pos[:, None], safe_cols]
                    valid = (ent_pos[:, None] >= 0) & (cols >= 0)
                    total = total + jnp.sum(
                        jnp.where(valid, gathered * vals, 0.0), axis=-1
                    )
            return total + jnp.asarray(vdata.offset)

        return scorer

    def _validation_evaluators(self) -> Dict[str, Tuple[Evaluator, dict]]:
        p = self.params
        vdata = self.validation_data
        labels = jnp.asarray(vdata.response)
        weights = jnp.asarray(vdata.weight)
        out: Dict[str, Tuple[Evaluator, dict]] = {}
        specs = p.evaluators or _default_evaluators(p.task_type)
        for etype, k, id_name in specs:
            ev = evaluator_for(etype, k or 10)
            kwargs = {"labels": labels, "weights": weights}
            if id_name is not None:
                kwargs["group_ids"] = jnp.asarray(vdata.ids[id_name])
            key = etype.value if k is None else f"{etype.value}@{k}"
            out[key] = (ev, kwargs)
        return out

    # --- warm starts (photon_ml_tpu.retrain.warm) ----------------------
    def _prior_entity_means(self, name: str):
        """Prior per-entity global rows for coordinate ``name`` (cached;
        None when the prior model lacks it or it is factored)."""
        if name not in self._warm_means_cache:
            from photon_ml_tpu import retrain

            cfg = self.params.random_effect_data_configs[name]
            self._warm_means_cache[name] = retrain.random_effect_entity_means(
                self.retrain_prior.model_dir, name,
                self.shard_index_maps[cfg.feature_shard_id],
            )
        return self._warm_means_cache[name]

    def _prepare_warm_starts(self) -> None:
        """Build every coordinate's warm-start state from the prior model
        (once; combos share them) and resolve the frozen-block sets.
        Paths without a warm representation (factored latent state,
        bucketed stacks, distributed padded shards) stay cold with a
        logged reason — a recorded decision, never a silent wrong warm."""
        if self.retrain_prior is None or self.delta_plan is None:
            return
        p = self.params
        if p.distributed:
            self.logger.info(
                "delta retrain: --distributed solvers manage their own "
                "sharded/padded state — warm starts off (cold solves)"
            )
            return
        from photon_ml_tpu import retrain

        prior = self.retrain_prior
        combos = p.config_grid()
        single = combos[0] if len(combos) == 1 else None
        for name in p.updating_sequence:
            cdelta = self.delta_plan.coordinates.get(name)
            if cdelta is None or cdelta.status == "new":
                continue
            if name in p.factored_configs:
                self.logger.info(
                    f"delta retrain [{name}]: factored latent state does "
                    "not round-trip through dense rows — cold solve"
                )
                continue
            if name in p.fixed_effect_data_configs:
                spec = p.fixed_effect_data_configs[name]
                w = retrain.fixed_effect_init(
                    prior.model_dir, name,
                    self.shard_index_maps[spec.feature_shard_id],
                )
                if w is not None:
                    self._warm_fixed[name] = w
                continue
            if p.bucketed_random_effects and name in self.bucketed_bundles:
                means = self._prior_entity_means(name)
                if means is None:
                    self.logger.info(
                        f"delta retrain [{name}]: prior model has no "
                        "reusable coefficients for this bucketed "
                        "coordinate — cold solve"
                    )
                    continue
                self._warm_bucketed[name] = retrain.bucketed_random_effect_init(
                    means, self.bucketed_bundles[name]
                )
                self.logger.info(
                    f"delta retrain [{name}]: warm-starting "
                    f"{len(self._warm_bucketed[name])} bucket stacks from "
                    "the prior model (gathered through the bucket layout)"
                )
                continue
            means = self._prior_entity_means(name)
            if means is None:
                self.logger.info(
                    f"delta retrain [{name}]: prior model has no reusable "
                    "coefficients for this coordinate — cold solve"
                )
                continue
            cfg = p.random_effect_data_configs[name]
            if name in self.streaming_manifests:
                seed_dir = os.path.join(p.output_dir, "retrain-warm", name)
                self._warm_spilled[name] = retrain.seed_spilled_state(
                    self.streaming_manifests[name], means, seed_dir
                )
                deltas = self.block_deltas.get(name)
                rec = prior.coordinates.get(name)
                cfg_now = (
                    str(single.get(name, CoordinateOptConfig()))
                    if single is not None else None
                )
                if deltas and rec is not None and cfg_now == rec.opt_config:
                    self._frozen_blocks[name] = frozenset(
                        d.index for d in deltas if d.status == "unchanged"
                    )
                    self.logger.info(
                        f"delta retrain [{name}]: freezing "
                        f"{len(self._frozen_blocks[name])}/{len(deltas)} "
                        "unchanged blocks (solves skipped, coefficients "
                        "bitwise from the prior model)"
                    )
                elif deltas:
                    self.logger.info(
                        f"delta retrain [{name}]: optimization grid "
                        "differs from the prior selected combo — no block "
                        "freezing (warm start only)"
                    )
            else:
                ds = self.re_datasets[name]
                self._warm_dense_re[name] = retrain.dense_random_effect_init(
                    means,
                    vocab=self.train_data.id_vocabs[cfg.random_effect_id],
                    pos_of_vocab=self._entity_position_of_vocab(name),
                    local_to_global=np.asarray(ds.local_to_global),
                )

    def _warm_init(self) -> Optional[Dict[str, object]]:
        """The per-coordinate warm-start params dict (shared across
        combos; CD copies donated leaves per combo), or None when cold."""
        out: Dict[str, object] = {}
        for n, w in self._warm_fixed.items():
            out[n] = jnp.asarray(w)
        for n, w in self._warm_dense_re.items():
            out[n] = jnp.asarray(w)
        for n, stacks in self._warm_bucketed.items():
            # per-bucket stacks mirror initial_coefficients()'s tuple
            out[n] = tuple(jnp.asarray(w) for w in stacks)
        out.update(self._warm_spilled)
        return out or None

    def _frozen_coordinate_names(self, warm_init) -> set:
        """Coordinates the plan froze AND we could warm-seed — freezing
        without the prior coefficients would freeze zeros."""
        if self.delta_plan is None:
            return set()
        frozen = self.delta_plan.frozen_coordinates()
        out = {n for n in frozen if warm_init is not None and n in warm_init}
        for n in sorted(frozen - out):
            self.logger.warn(
                f"delta retrain [{n}]: classified unchanged but no warm "
                "state could be built — re-solving instead of freezing"
            )
        if out and self.plan.cycle_fusion == "full":
            self.logger.info(
                "delta retrain: --fused-cycle compiles every coordinate "
                "into one program — frozen coordinates re-solve warm "
                "instead of skipping"
            )
            return set()
        return out

    # ------------------------------------------------------------------
    def _vmapped_grid_blocker(self, combos) -> Optional[str]:
        """Why --vmapped-grid cannot apply, or None when it can: the grid
        must vary ONLY per-coordinate lambda on plain fixed/random
        coordinates, with no orthogonal machinery that cannot nest under
        vmap (sharding) or that needs per-combo static coordinates."""
        p = self.params
        if len(combos) < 2:
            return "grid has a single combo"
        if p.distributed:
            return "--distributed (shard_map cannot nest under the combo vmap)"
        if p.bucketed_random_effects:
            return "--bucketed-random-effects (static per-bucket lambdas)"
        if p.streaming_random_effects:
            return "--streaming-random-effects (host streaming cannot vmap)"
        if p.factored_configs:
            return "factored coordinates (lambda lives in nested configs)"
        if p.compute_variance:
            return "--compute-variance (save-time Hessians need per-combo statics)"
        # --checkpoint-dir no longer blocks the grid: run_grid lands
        # PER-CYCLE checkpoints (params/scores/total lane pytree at every
        # iteration boundary) — only per-UPDATE granularity is inherently
        # unavailable (updates live inside the compiled cycle)
        if p.divergence_guard != "off":
            return "--divergence-guard (per-update host gate cannot enter the compiled cycle)"
        if self.solve_schedule is not None:
            return "--solve-compaction (chunk pauses re-enter the host per update)"
        import dataclasses as _dc

        for name in p.updating_sequence:
            # compare configs with lambda zeroed: any OTHER field differing
            # blocks the vmap (and a future CoordinateOptConfig field
            # automatically participates in this check)
            non_lambda = {
                _dc.replace(c.get(name, CoordinateOptConfig()), reg_weight=0.0)
                for c in combos
            }
            if len(non_lambda) > 1:
                return f"combos vary beyond lambda for coordinate {name!r}"
        return None

    def _grid_cd(self, combos, loss_fn):
        """(coords, CoordinateDescent, evaluators, primary) for the
        traced-lambda grid — built once so every combo reuses the single
        compiled cycle."""
        coords = self._build_coordinates(combos[0])
        scorer = None
        evaluators = None
        primary = None
        if self.validation_data is not None:
            scorer = self._validation_scorer(coords)
            evaluators = self._validation_evaluators()
            if evaluators:
                primary = next(iter(evaluators))
        cd = CoordinateDescent(coords, loss_fn, scorer, evaluators)
        return coords, cd, evaluators, primary

    def _grid_lambdas(self, combos):
        return {
            name: jnp.asarray(
                [c.get(name, CoordinateOptConfig()).reg_weight for c in combos],
                real_dtype(),
            )
            for name in self.params.updating_sequence
        }

    def _make_checkpointer(self, combo_index: int, opt_configs, grid: bool = False):
        """Per-combo checkpointer (async-wrapped under --checkpoint-async);
        None without --checkpoint-dir. Grid and per-combo runs fingerprint
        differently — their step granularities must never cross-resume."""
        p = self.params
        if not p.checkpoint_dir:
            return None
        from photon_ml_tpu.checkpoint import (
            CoordinateDescentCheckpointer,
            fingerprint,
        )
        from photon_ml_tpu.checkpoint_async import maybe_async

        return maybe_async(
            CoordinateDescentCheckpointer(
                os.path.join(p.checkpoint_dir, f"combo-{combo_index}"),
                # num_iterations intentionally excluded: extending a
                # finished run with more iterations IS the resume case
                run_fingerprint=fingerprint(
                    {
                        "coordinates": p.updating_sequence,
                        "num_rows": self.train_data.num_rows,
                        "combo": combo_index,
                        "configs": {k: str(v) for k, v in opt_configs.items()},
                        **({"grid": True} if grid else {}),
                    }
                ),
            ),
            p.checkpoint_async,
        )

    @staticmethod
    def _close_checkpointer(checkpointer) -> None:
        """Fence + stop an async checkpointer (no-op for the sync one):
        every commit durable — and any background failure surfaced —
        before models are saved or the run retires."""
        if checkpointer is not None and hasattr(checkpointer, "close"):
            checkpointer.close()

    def _train_shared_compile_grid(self, combos, loss_fn,
                                   init_params=None) -> None:
        """All grid combos through the traced-lambda grid API
        (CoordinateDescent.run_grid): ONE compiled cycle serves every
        combo; results and best_index land in self.results exactly like
        the per-combo rebuild path. With --checkpoint-dir each combo
        checkpoints per cycle and resumes from its last complete
        iteration. ``init_params`` (delta retrain) seeds EVERY lambda lane
        from the prior run's selected model — the PR-2 warm-start hook
        generalized to per-coordinate GAME warm starts."""
        p = self.params
        coords, cd, evaluators, primary = self._grid_cd(combos, loss_fn)
        lam = self._grid_lambdas(combos)
        checkpointers = (
            [
                self._make_checkpointer(i, combos[i], grid=True)
                for i in range(len(combos))
            ]
            if p.checkpoint_dir
            else None
        )
        from photon_ml_tpu.utils.profiling import maybe_trace

        try:
            with maybe_trace("game-grid"), self.timer.measure("shared-compile-grid"):
                grid_results = cd.run_grid(
                    lam, p.num_iterations, self.train_data.num_rows,
                    init_params=init_params,
                    checkpointers=checkpointers,
                )
        finally:
            for ck in checkpointers or ():
                self._close_checkpointer(ck)
        best_value: Optional[float] = None
        for i, (opt_configs, result) in enumerate(zip(combos, grid_results)):
            metrics = result.validation_history[-1] if result.validation_history else {}
            self.combo_coords.append(coords)
            self.results.append((opt_configs, result, metrics))
            self.logger.info(
                f"combo {i} (grid): objective={result.objective_history[-1]:.6g} "
                + " ".join(f"{k}={v:.6g}" for k, v in metrics.items())
            )
            if primary is not None and metrics:
                ev = evaluators[primary][0]
                value = metrics[primary]
                if best_value is None or ev.better_than(value, best_value):
                    best_value = value
                    self.best_index = i

    # ------------------------------------------------------------------
    def train(self) -> None:
        p = self.params
        loss_fn = self._training_loss_fn()
        combos = p.config_grid()
        primary: Optional[str] = None
        best_value: Optional[float] = None
        self._prepare_warm_starts()
        warm_init = self._warm_init()
        frozen = self._frozen_coordinate_names(warm_init)

        if p.vmapped_grid in ("true", "auto"):
            # the batched G-lane variant this flag once selected lost the
            # measured race on every platform three rounds running and was
            # REMOVED (VERDICT r4 #9); the flag now always routes through
            # the sequential shared-compile grid API — exactly what the old
            # auto-selector picked every time it measured
            blocker = (
                "delta-frozen coordinates (the per-coordinate skip lives "
                "outside the compiled grid cycle)"
                if frozen else self._vmapped_grid_blocker(combos)
            )
            if blocker is None:
                self.logger.info(
                    "--vmapped-grid: training through the shared-compile "
                    "grid (the batched G-lane variant was removed; "
                    "sequential won every measured race)"
                    + (" — every lane warm-started from the prior model"
                       if warm_init else "")
                )
                self._train_shared_compile_grid(
                    combos, loss_fn, init_params=warm_init
                )
                return
            else:
                self.logger.warn(
                    f"--vmapped-grid requested but falling back to the "
                    f"per-combo rebuild grid: {blocker}"
                )

        for i, opt_configs in enumerate(combos):
            coords = self._build_coordinates(opt_configs)
            scorer = None
            evaluators = None
            if self.validation_data is not None:
                scorer = self._validation_scorer(coords)
                evaluators = self._validation_evaluators()
                if primary is None and evaluators:
                    primary = next(iter(evaluators))
            checkpointer = self._make_checkpointer(i, opt_configs)
            guard = None
            if p.divergence_guard != "off":
                from photon_ml_tpu.resilience import DivergenceGuard

                guard = DivergenceGuard(mode=p.divergence_guard)
            self.combo_coords.append(coords)
            cd = CoordinateDescent(
                coords, loss_fn, scorer, evaluators,
                # full-cycle fusion only when the plan resolved it so:
                # under compaction/streaming the flag promotes to per-solve
                # fusion (cycle_fusion="solve", the device scheduler loop)
                # and the descent loop itself stays host-side
                fused_cycle=self.plan.cycle_fusion == "full",
                divergence_guard=guard,
            )
            from photon_ml_tpu.utils.profiling import maybe_trace

            try:
                with maybe_trace(f"game-combo-{i}"), self.timer.measure(f"combo-{i}"):
                    result = cd.run(
                        p.num_iterations, self.train_data.num_rows,
                        checkpointer,
                        initial_params=warm_init,
                        frozen=frozen,
                    )
            finally:
                # async fence: every commit durable (and any background
                # commit failure surfaced) before this combo retires —
                # on the preemption path the emergency save already fenced
                self._close_checkpointer(checkpointer)
            metrics = result.validation_history[-1] if result.validation_history else {}
            self.results.append((opt_configs, result, metrics))
            self.logger.info(
                f"combo {i}: objective={result.objective_history[-1]:.6g} "
                + " ".join(f"{k}={v:.6g}" for k, v in metrics.items())
            )
            for ev in result.guard_events:
                self.logger.warn(
                    f"combo {i}: divergence guard {ev.action} at coordinate "
                    f"{ev.coordinate!r} step {ev.step} ({ev.detail})"
                )
            for cname, tracker in result.trackers.items():
                summary = _summarize_tracker(tracker)
                if summary:
                    self.logger.info(f"combo {i} [{cname}] {summary}")
            if primary is not None and metrics:
                ev = evaluators[primary][0]
                value = metrics[primary]
                if best_value is None or ev.better_than(value, best_value):
                    best_value = value
                    self.best_index = i

    # ------------------------------------------------------------------
    def _entity_means_global(self, name: str, coefficients) -> Dict[str, np.ndarray]:
        """Stacked coefficients -> {raw entity id: dense global-space row}."""
        from photon_ml_tpu.algorithm.random_effect import global_coefficients

        cfg = self.params.random_effect_data_configs[name]
        ds = self.re_datasets[name]
        if isinstance(coefficients, FactoredState):
            wg = np.asarray(coefficients.v @ coefficients.matrix)
            return self._rows_by_raw_id(name, wg)
        # distributed solves pad the entity axis; slice back to E
        coeffs = jnp.asarray(coefficients)[: ds.num_entities]
        return self._rows_by_raw_id(
            name, np.asarray(global_coefficients(ds, coeffs))
        )

    def _rows_by_raw_id(self, name: str, rows: np.ndarray) -> Dict[str, np.ndarray]:
        """(E, D_global) stack -> {raw entity id: row} via the vocab map."""
        cfg = self.params.random_effect_data_configs[name]
        pos_of_vocab = self._entity_position_of_vocab(name)
        vocab = self.train_data.id_vocabs[cfg.random_effect_id]
        out: Dict[str, np.ndarray] = {}
        for vi, raw in enumerate(vocab):
            tp = pos_of_vocab[vi]
            if tp >= 0:
                out[raw] = rows[tp]
        return out

    def _entity_latent_factors(self, name: str, state: FactoredState) -> Dict[str, np.ndarray]:
        """FactoredState.v rows keyed by raw entity id (for LatentFactorAvro)."""
        cfg = self.params.random_effect_data_configs[name]
        v = np.asarray(state.v)
        pos_of_vocab = self._entity_position_of_vocab(name)
        vocab = self.train_data.id_vocabs[cfg.random_effect_id]
        out: Dict[str, np.ndarray] = {}
        for vi, raw in enumerate(vocab):
            tp = pos_of_vocab[vi]
            if tp >= 0:
                out[raw] = v[tp]
        return out

    def save_models(self, output_dir: str, result: CoordinateDescentResult,
                    combo_index: Optional[int] = None) -> None:
        p = self.params

        def _wants_variances(name) -> bool:
            """THE --compute-variance gate, shared by every save branch
            (RandomEffectOptimizationProblem isComputingVariance parity)."""
            if not p.compute_variance or combo_index is None:
                return False
            cfg = p.random_effect_data_configs.get(name)
            if cfg is not None and cfg.projector == "RANDOM":
                # a diagonal variance does not survive a dense random
                # back-projection; the reference has the same limitation
                self.logger.warn(
                    f"[{name}] variances skipped: RANDOM-projected space"
                )
                return False
            return True

        def _variances_for(name, coeffs):
            """Per-coordinate 1/H_jj at the final state; residual = total
            minus this coordinate's own score."""
            if not _wants_variances(name):
                return None
            coord = self.combo_coords[combo_index].get(name)
            if coord is None or not hasattr(coord, "coefficient_variances"):
                return None
            resid = result.total_scores - coord.score(coeffs)
            return coord.coefficient_variances(coeffs, resid)

        for name in p.updating_sequence:
            coeffs = result.coefficients[name]
            if name in p.fixed_effect_data_configs:
                spec = p.fixed_effect_data_configs[name]
                fe_var = _variances_for(name, coeffs)
                model_io.save_fixed_effect(
                    output_dir,
                    name,
                    p.task_type,
                    np.asarray(coeffs),
                    self.shard_index_maps[spec.feature_shard_id],
                    variances=None if fe_var is None else np.asarray(fe_var),
                    feature_shard_id=spec.feature_shard_id,
                )
            else:
                from photon_ml_tpu.algorithm.bucketed_random_effect import (
                    BucketedRandomEffectCoordinate,
                )
                from photon_ml_tpu.algorithm.streaming_random_effect import (
                    StreamingRandomEffectCoordinate,
                )

                if p.bucketed_random_effects or p.streaming_random_effects:
                    if combo_index is None or not (
                        0 <= combo_index < len(self.combo_coords)
                    ):
                        raise ValueError(
                            "save_models on a bucketed/streaming random-"
                            "effects run needs the combo_index of the result "
                            "being saved (the per-bucket/per-block "
                            "coefficients are extracted through that combo's "
                            "coordinate objects)"
                        )
                    coord = self.combo_coords[combo_index].get(name)
                else:
                    coord = None
                cfg = p.random_effect_data_configs[name]
                entity_variances = None
                if isinstance(
                    coord,
                    (BucketedRandomEffectCoordinate, StreamingRandomEffectCoordinate),
                ):
                    resid = (
                        result.total_scores - coord.score(coeffs)
                        if _wants_variances(name)
                        else None
                    )
                    entity_means, entity_variances = coord.entity_export_by_raw_id(
                        coeffs, resid
                    )
                else:
                    entity_means = self._entity_means_global(name, coeffs)
                    if not isinstance(coeffs, FactoredState):
                        re_var = _variances_for(name, coeffs)
                        if re_var is not None:
                            from photon_ml_tpu.algorithm.random_effect import (
                                global_coefficients,
                            )

                            ds = self.re_datasets[name]
                            # mesh-scheduled coordinates compute variances
                            # over their PADDED entity axis; slice back to
                            # this (unpadded) dataset's extent, same as
                            # the means path above
                            entity_variances = self._rows_by_raw_id(
                                name,
                                np.asarray(global_coefficients(
                                    ds, re_var[: ds.num_entities]
                                )),
                            )
                model_io.save_random_effect(
                    output_dir,
                    name,
                    p.task_type,
                    entity_means,
                    self.shard_index_maps[cfg.feature_shard_id],
                    random_effect_id=cfg.random_effect_id,
                    feature_shard_id=cfg.feature_shard_id,
                    num_files=p.num_output_files_re_model,
                    entity_variances=entity_variances,
                )
                if isinstance(coeffs, FactoredState):
                    # persist the factored STRUCTURE too (latent coefficients
                    # + shared matrix, LatentFactorAvro — AvroUtils.scala:
                    # 244-266): the projected-back coefficients above are for
                    # scoring compat, but alone they cannot reconstruct the
                    # model (VERDICT r2 missing #3)
                    model_io.save_factored_random_effect(
                        output_dir,
                        name,
                        self._entity_latent_factors(name, coeffs),
                        np.asarray(coeffs.matrix),
                        random_effect_id=cfg.random_effect_id,
                        feature_shard_id=cfg.feature_shard_id,
                        num_files=p.num_output_files_re_model,
                        index_map=self.shard_index_maps[cfg.feature_shard_id],
                    )

    # ------------------------------------------------------------------
    def _resilience_config(self):
        """Process-wide ingest resilience settings from the driver flags
        (corrupt-shard policy + I/O retry/backoff), installed for the whole
        run so every read path — feature scan, dataset load, checkpoint —
        behaves consistently."""
        import dataclasses

        from photon_ml_tpu import resilience

        p = self.params
        # flags override attempts/base-delay; the rest of the policy keeps
        # the env-tunable defaults (PHOTON_IO_RETRY_MAX_DELAY / _DEADLINE)
        return resilience.ResilienceConfig(
            on_corrupt=p.on_corrupt,
            corrupt_skip_budget=p.corrupt_skip_budget,
            io_policy=dataclasses.replace(
                resilience.RetryPolicy.io_default(),
                max_attempts=p.io_retries,
                base_delay=p.io_retry_base_delay,
            ),
        )

    def run(self, restart: bool = False) -> None:
        """``restart=True`` (a supervised relaunch after a preemption)
        keeps the existing output dir: the streaming entity blocks, spilled
        coordinate state, and logs written by the interrupted attempt are
        exactly what the checkpoint's by-reference entries resume from."""
        from photon_ml_tpu import resilience

        with resilience.resilience_scope(self._resilience_config()):
            self._run_guarded(restart)

    def _run_guarded(self, restart: bool = False) -> None:
        p = self.params
        if restart:
            os.makedirs(p.output_dir, exist_ok=True)
        else:
            prepare_output_dir(p.output_dir, p.delete_output_dir_if_exists)
        from photon_ml_tpu import compat

        compat.start_up(self.logger.info, p.persistent_cache_dir)
        self.logger.info(self.plan.describe())
        for line in self.plan.describe_decisions():
            self.logger.info(f"execution plan: {line}")
        try:
            train_files = _input_files(self._train_dirs())
            self._train_files = train_files
            # stat tokens captured NOW — before ingest — so the manifest
            # describes the files this run is ABOUT to read (the tensor
            # cache's own discipline): a file overwritten mid-training is
            # recorded with its pre-overwrite identity and tomorrow's
            # delta run classifies it CHANGED, never wrongly frozen
            from photon_ml_tpu.io.tensor_cache import file_stat_token

            self._train_file_stats = file_stat_token(train_files)
            self._eval_identity()  # snapshot the validation side pre-read too
            self._maybe_plan_delta(train_files)
            if self.delta_plan is not None and self.delta_plan.short_circuit:
                # nothing changed: the prior model IS this run's result —
                # re-export it bitwise, skip ingest and training entirely
                with self.timer.measure("delta-short-circuit"):
                    self._short_circuit_run()
                self._log_run_summaries()
                return
            with self.timer.measure("prepare-feature-maps"):
                self.prepare_feature_maps()
            with self.timer.measure("prepare-datasets"):
                self.prepare_datasets()
            with self.timer.measure("train"):
                self.train()
            if p.model_output_mode != ModelOutputMode.NONE:
                best_dir = os.path.join(p.output_dir, BEST_MODEL_DIR)
                self.save_models(
                    best_dir, self.results[self.best_index][1], self.best_index
                )
                self.logger.info(
                    f"saved best model (combo {self.best_index}) to {best_dir}"
                )
                if p.model_output_mode == ModelOutputMode.ALL:
                    for i, (_, result, _) in enumerate(self.results):
                        self.save_models(
                            os.path.join(p.output_dir, ALL_MODELS_DIR, str(i)),
                            result,
                            i,
                        )
                self._record_realized_costs()
                self._write_retrain_manifest(best_dir)
                self._export_store(best_dir)
            elif p.warm_start_from or p.export_serve_store:
                self.logger.warn(
                    "--model-output-mode NONE: no saved model, so no "
                    "retrain manifest / serving store can be written"
                )
            self._log_run_summaries()
        finally:
            if self._own_logger:
                self.logger.close()

    def _log_run_summaries(self) -> None:
        p = self.params
        self.logger.info(self.timer.summary())
        from photon_ml_tpu.compile import compile_stats

        self.logger.info(compile_stats.summary())
        if self.solve_schedule is not None or self.plan.adaptive is not None:
            from photon_ml_tpu.optim.scheduler import solve_stats

            self.logger.info(solve_stats.summary())
        if self.plan.adaptive is not None:
            # every adaptive skip/degrade is a recorded decision; surface
            # them in the log like the plan's own composition decisions
            for combo in self.combo_coords:
                for name, coord in combo.items():
                    for dec in getattr(coord, "skip_decisions", ()) or ():
                        self.logger.info(f"[{name}] {dec.describe()}")
        if p.tensor_cache_dir:
            from photon_ml_tpu.io.tensor_cache import cache_stats

            self.logger.info(cache_stats.summary())
        if compile_stats.xla_cache_misses == 0:
            self.logger.info(
                "persistent cache fully warm: zero new XLA compiles"
            )

    # --- delta-retrain output side (photon_ml_tpu.retrain) --------------
    def _short_circuit_run(self) -> None:
        """All-unchanged rerun: copy the prior model forward bitwise and
        re-export — 0 solves, 0 new XLA compiles, no ingest."""
        import shutil

        p = self.params
        prior = self.retrain_prior
        best_dir = os.path.join(p.output_dir, BEST_MODEL_DIR)
        if os.path.abspath(prior.model_dir) != os.path.abspath(best_dir):
            shutil.copytree(prior.model_dir, best_dir, dirs_exist_ok=True)
        self.logger.info(
            "delta retrain: inputs, configuration, and grid identical to "
            f"the prior run — prior model reused wholesale at {best_dir} "
            "(0 solves, 0 new XLA compiles)"
        )
        self._write_retrain_manifest(best_dir, short_circuit=True)
        self._export_store(best_dir)

    def _record_realized_costs(self) -> None:
        """Close the planner loop (--plan auto): attach this run's realized
        costs — from the same stats registries the planner predicts over —
        to the plan's decisions, fold them into the cost model, and persist
        the ``cost-model.json`` sidecar beside ``retrain.json`` so the next
        run (or ``fleetctl status --plan``) starts from observed reality.
        No-op under --plan off: the sidecar only exists when planning is on."""
        if getattr(self.plan, "plan_mode", "off") != "auto":
            return
        from photon_ml_tpu.compile import compile_stats
        from photon_ml_tpu.compile.cost import TRACE_COST

        p = self.params
        from photon_ml_tpu.optim.scheduler import solve_stats

        sched_cost = solve_stats.realized_plan_cost()
        if sched_cost is not None:
            self.plan.record_realized("schedule", sched_cost)
            # sharding's realized burden is the same executed-iteration
            # ledger the lanes produced, minus the pause tariff
            self.plan.record_realized(
                "sharding",
                float(solve_stats.totals()["executed_lane_iterations"]),
            )
        traces = compile_stats.total_traces()
        if traces:
            self.plan.record_realized("ladder", TRACE_COST * float(traces))
        # blocking realized = per-block imbalance from the best combo's
        # convergence ledgers (the quantity reblock_recommendation gates on)
        block_costs = self._ledger_block_costs()
        if block_costs:
            self.plan.record_realized(
                "blocking", max(block_costs) / max(1e-9, min(block_costs))
            )
        path = self.plan.save_cost_model(p.output_dir)
        if path:
            self.logger.info(f"plan cost model written: {path}")
            for dec in self.plan.decisions:
                if dec.realized_cost is not None:
                    self.logger.info(dec.describe())

    def _plan_cost_model_json(self) -> Optional[dict]:
        """The plan's cost model for retrain.json — None under --plan off
        (the manifest field stays absent, bitwise-identical to before)."""
        if getattr(self.plan, "plan_mode", "off") != "auto":
            return None
        model = self.plan.cost_model
        return model.to_json() if model is not None else None

    def _ledger_block_costs(self) -> list:
        """Best-combo per-block observed costs (empty when no coordinate
        kept a convergence ledger) — the planner's blocking-drift signal."""
        costs: list = []
        if not self.combo_coords:
            return costs
        if not (0 <= self.best_index < len(self.combo_coords)):
            return costs
        for coord in self.combo_coords[self.best_index].values():
            ledger = getattr(coord, "_ledger", None)
            observed = getattr(ledger, "observed_costs", None)
            if callable(observed):
                try:
                    costs.extend(float(c) for c in observed().values())
                except Exception:  # lint: broad-except — blocking drift is advisory telemetry; a malformed ledger on one coordinate must never fail the training run
                    continue
        return costs

    def _write_retrain_manifest(self, best_dir: str,
                                short_circuit: bool = False) -> None:
        """Leave this run's ``retrain.json`` for the next run's planner."""
        from photon_ml_tpu.io.tensor_cache import file_stat_token
        from photon_ml_tpu.retrain import RetrainManifest
        from photon_ml_tpu.retrain.manifest import CoordinateRecord

        p = self.params
        # pre-ingest stat tokens (captured in _run_guarded); re-stat'ing
        # here would record a mid-run overwrite as this run's identity
        file_stats = getattr(self, "_train_file_stats", None)
        if file_stats is None:
            file_stats = file_stat_token(
                self._train_files or _input_files(self._train_dirs())
            )
        if short_circuit:
            prior = self.retrain_prior
            manifest = RetrainManifest(
                output_dir=os.path.abspath(p.output_dir),
                model_dir=os.path.abspath(best_dir),
                task=p.task_type.value,
                file_stats=file_stats,
                ingest_inputs=self._ingest_inputs(),
                # inputs identical by construction: the prior's digests and
                # durable block layouts remain this run's identity too
                ingest_digest=prior.ingest_digest,
                updating_sequence=list(p.updating_sequence),
                coordinates=dict(prior.coordinates),
                data_cache_key=prior.data_cache_key,
                eval_identity=self._eval_identity(),
                cost_model=self._plan_cost_model_json(),
            )
        else:
            combos = p.config_grid()
            sel = combos[self.best_index] if self.results else combos[0]
            coords: Dict[str, CoordinateRecord] = {}
            for name in p.updating_sequence:
                if name in p.fixed_effect_data_configs:
                    kind = "fixed"
                elif name in p.factored_configs:
                    kind = "factored"
                elif name in self.streaming_manifests:
                    kind = "streaming_random"
                elif p.bucketed_random_effects:
                    kind = "bucketed"
                else:
                    kind = "random"
                sm = self.streaming_manifests.get(name)
                # the best combo's convergence ledger rides along so the
                # next run's adaptive schedule starts warm (None when the
                # coordinate kind has no ledger or the run kept none)
                ledger = None
                if self.combo_coords and 0 <= self.best_index < len(
                    self.combo_coords
                ):
                    coord = self.combo_coords[self.best_index].get(name)
                    export = getattr(coord, "ledger_export", None)
                    if callable(export):
                        ledger = export() or None
                coords[name] = CoordinateRecord(
                    kind=kind,
                    opt_config=str(sel.get(name, CoordinateOptConfig())),
                    cache_key=self._coord_cache_keys.get(name),
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
                file_stats=file_stats,
                ingest_inputs=self._ingest_inputs(),
                ingest_digest=self._ingest_digest(),
                updating_sequence=list(p.updating_sequence),
                coordinates=coords,
                data_cache_key=self._data_cache_key,
                eval_identity=self._eval_identity(),
                cost_model=self._plan_cost_model_json(),
            )
        path = manifest.save(p.output_dir)
        self.logger.info(f"retrain manifest written: {path}")

    def _export_store(self, best_dir: str) -> None:
        """--export-serve-store: the trained model as an mmap'd serving
        store — what a live ScoringServer/fleet hot-swaps in (the
        retrain->swap loop's handoff artifact)."""
        p = self.params
        if not p.export_serve_store:
            return
        from photon_ml_tpu.compile import ShapeBucketer
        from photon_ml_tpu.serve.model_store import build_model_store

        with self.timer.measure("export-serve-store"):
            build_model_store(
                best_dir, p.export_serve_store,
                bucketer=self.bucketer or ShapeBucketer(),
                store_dtype=p.store_dtype,
            )
        self.logger.info(
            f"serving store exported: {p.export_serve_store} "
            f"(dtype {p.store_dtype}; swap it into a live server via "
            "serve.swap.ModelSwapper / the fleet generation barrier)"
        )


def _default_evaluators(task: TaskType):
    from photon_ml_tpu.evaluation.evaluators import EvaluatorType

    default = {
        TaskType.LOGISTIC_REGRESSION: EvaluatorType.AUC,
        TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM: EvaluatorType.AUC,
        TaskType.LINEAR_REGRESSION: EvaluatorType.RMSE,
        TaskType.POISSON_REGRESSION: EvaluatorType.POISSON_LOSS,
    }[task]
    return [(default, None, None)]


def main(argv: Optional[List[str]] = None) -> GameTrainingDriver:
    import logging
    import sys

    from photon_ml_tpu.resilience import preemption

    params = parse_training_params(argv)

    def run_once(attempt: int) -> GameTrainingDriver:
        driver = GameTrainingDriver(params)
        driver.run(restart=attempt > 0)
        return driver

    def on_restart(attempt: int, e: preemption.Preempted) -> None:
        logging.getLogger(__name__).warning(
            "preempted (%s); relaunching from the latest checkpoint "
            "(restart %d/%d)", e, attempt, params.max_restarts
        )

    # SIGTERM/SIGINT become cooperative preemption requests for the whole
    # run; the loops drain to the nearest safe boundary, write an emergency
    # checkpoint, and either relaunch in-process (--max-restarts) or exit
    # with the distinct preemption code for tools/run_supervised.py
    with preemption.signal_scope():
        try:
            return preemption.run_with_restarts(
                run_once, params.max_restarts, on_restart=on_restart
            )
        except preemption.Preempted as e:
            print(
                f"photon-ml-tpu: preempted ({e}); emergency checkpoint "
                f"{e.checkpoint_path or '(no --checkpoint-dir)'}; "
                f"exiting {preemption.PREEMPT_EXIT_CODE}",
                file=sys.stderr,
            )
            raise SystemExit(preemption.PREEMPT_EXIT_CODE) from e


if __name__ == "__main__":
    main()
