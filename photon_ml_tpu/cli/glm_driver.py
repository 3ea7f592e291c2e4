"""GLM training driver: the staged end-to-end pipeline.

Reference spec: Driver.scala:69-598 — stage progression INIT -> PREPROCESSED
-> TRAINED -> VALIDATED -> DIAGNOSED (DriverStage.scala; stage assertions
Driver.scala:513-527): preprocess (:228-254) loads + validates + summarizes
data, train (:256-290) runs the warm-started lambda grid, validate
(:363-372) computes metric maps and selects the best lambda, diagnose
(:484-511) builds the HTML model-diagnostic report (writer :577-597), and
models are written in text form (:160-163).

TPU-native: one host process owns ingest and orchestration; each solve is a
compiled XLA program on the batch (the Spark context / executors / kryo /
partition knobs have no analogue and are accepted-but-ignored for CLI
compatibility).
"""

from __future__ import annotations

import enum
import os
from typing import Dict, List, Optional, Tuple

import numpy as np

import jax.numpy as jnp

from photon_ml_tpu.cli.glm_params import (
    FieldNamesType,
    GLMParams,
    InputFormatType,
    parse_from_command_line,
)
from photon_ml_tpu.data.validators import sanity_check_data
from photon_ml_tpu.diagnostics import render_html
from photon_ml_tpu.diagnostics import (
    bootstrap_diagnostic,
    feature_importance,
    fitting,
    hosmer_lemeshow,
    independence,
)
from photon_ml_tpu.diagnostics.reports import (
    ModelDiagnosticReport,
    SystemReport,
    assemble_document,
)
from photon_ml_tpu.evaluation import metrics as metrics_mod
from photon_ml_tpu.io import avro_data
from photon_ml_tpu.io.index_map import INTERCEPT_KEY, DELIMITER, IndexMap
from photon_ml_tpu.io.libsvm import HostDataset, read_libsvm, to_batch
from photon_ml_tpu.model_selection import select_best_model
from photon_ml_tpu.models.glm import Coefficients, GeneralizedLinearModel
from photon_ml_tpu.ops.normalization import NormalizationContext
from photon_ml_tpu.ops.objective import GLMBatch
from photon_ml_tpu.ops.regularization import RegularizationContext
from photon_ml_tpu.ops.stats import BasicStatisticalSummary, summarize
from photon_ml_tpu.optim.common import OptimizerConfig, summarize_result
from photon_ml_tpu.optim.constraints import BoxConstraints, parse_constraint_string
from photon_ml_tpu.optim.problem import GLMOptimizationProblem
from photon_ml_tpu.training import TrainedModelList, train_glm_grid
from photon_ml_tpu.types import (
    NormalizationType,
    RegularizationType,
    TaskType,
)
from photon_ml_tpu.utils.io_utils import (
    prepare_output_dir,
    write_basic_statistics,
    write_models_in_text,
)
from photon_ml_tpu.utils.logging import PhotonLogger
from photon_ml_tpu.utils.timer import Timer

# Above this dense width, batches stay in padded-sparse layout
DENSE_DIM_THRESHOLD = 4096
LEARNED_MODELS_TEXT = "output"  # Driver.LEARNED_MODELS_TEXT parity
REPORT_FILE = "model-diagnostic.html"


class DriverStage(enum.IntEnum):
    """Ordered driver stages (DriverStage.scala parity)."""

    INIT = 0
    PREPROCESSED = 1
    TRAINED = 2
    VALIDATED = 3
    DIAGNOSED = 4


class Driver:
    """Staged GLM training pipeline. Construct with params, call run()."""

    def __init__(self, params: GLMParams, logger: Optional[PhotonLogger] = None):
        params.validate()
        self.params = params
        self.stage = DriverStage.INIT
        self.stage_history: List[DriverStage] = []
        self._own_logger = logger is None
        self.logger = logger or PhotonLogger(
            os.path.join(params.output_dir, "photon-ml-tpu.log")
        )
        self.timer = Timer(self.logger.info)

        self.index_map: Optional[IndexMap] = None
        self.train_ds: Optional[HostDataset] = None
        self.train_batch: Optional[GLMBatch] = None
        # out-of-core mode: chunk source replaces train_batch
        self.streaming_source = None
        self.validation_batch: Optional[GLMBatch] = None
        self.summary: Optional[BasicStatisticalSummary] = None
        self.norm: NormalizationContext = NormalizationContext.identity()
        self.trained: Optional[TrainedModelList] = None
        # raw-space (back-transformed) models keyed in training order
        self.models: List[Tuple[float, GeneralizedLinearModel]] = []
        self.best_reg_weight: Optional[float] = None
        self.best_model: Optional[GeneralizedLinearModel] = None
        self.validation_metrics: Dict[float, Dict[str, float]] = {}
        # lambda -> [metric map per completed iteration] (validate-per-iteration)
        self.per_iteration_metrics: Dict[float, List[Dict[str, float]]] = {}
        self.problem: Optional[GLMOptimizationProblem] = None

    # ------------------------------------------------------------------
    def _advance(self, stage: DriverStage) -> None:
        """Stage assertion (Driver.scala:513-527 parity)."""
        if stage <= self.stage:
            raise RuntimeError(f"cannot move back from {self.stage.name} to {stage.name}")
        self.stage_history.append(self.stage)
        self.stage = stage

    def _assert_stage(self, expected: DriverStage) -> None:
        if self.stage != expected:
            raise RuntimeError(
                f"stage {expected.name} required, currently {self.stage.name}"
            )

    # ------------------------------------------------------------------
    def run(self) -> None:
        p = self.params
        prepare_output_dir(p.output_dir, p.delete_output_dirs_if_exist)
        self.logger.info(f"job {p.job_name}: {p.task_type.value} via "
                         f"{p.optimizer_type.value}, lambdas={p.regularization_weights}")
        from photon_ml_tpu.compile import compile_stats

        compile_stats.install_xla_listeners()
        from photon_ml_tpu import compat

        compat.start_up(self.logger.info, p.persistent_cache_dir)
        try:
            with self.timer.measure("preprocess"):
                self.preprocess()
            with self.timer.measure("train"):
                self.train()
            if p.validating_data_dir:
                with self.timer.measure("validate"):
                    self.validate()
            if p.diagnostic_mode.runs_train or p.diagnostic_mode.runs_validate:
                with self.timer.measure("diagnose"):
                    self.diagnose()
            self.logger.info(self.timer.summary())
            self.logger.info(compile_stats.summary())
            if p.tensor_cache_dir:
                from photon_ml_tpu.io.tensor_cache import cache_stats

                self.logger.info(cache_stats.summary())
            if compile_stats.xla_cache_misses == 0:
                self.logger.info(
                    "persistent cache fully warm: zero new XLA compiles"
                )
        finally:
            if self._own_logger:
                self.logger.close()

    # ------------------------------------------------------------------
    # stage: preprocess
    # ------------------------------------------------------------------
    def _input_paths(self, directory: str) -> List[str]:
        if os.path.isfile(directory):
            return [directory]
        return [
            os.path.join(directory, f)
            for f in sorted(os.listdir(directory))
            if not f.startswith((".", "_"))
        ]

    def _selected_features(self) -> Optional[set]:
        """Whitelist of feature keys (GLMSuite.scala:141-180 parity: a file
        of name/term entries; text lines 'name<TAB>term' or 'name')."""
        path = self.params.selected_features_file
        if not path:
            return None
        keys = set()
        if path.endswith(".avro"):
            from photon_ml_tpu.io import avro as avro_io

            for rec in avro_io.read_container(path):
                keys.add(f"{rec['name']}{DELIMITER}{rec.get('term') or ''}")
        else:
            with open(path) as f:
                for line in f:
                    line = line.rstrip("\n")
                    if not line:
                        continue
                    if DELIMITER in line:
                        keys.add(line)
                    elif "\t" in line:
                        name, term = line.split("\t", 1)
                        keys.add(f"{name}{DELIMITER}{term}")
                    else:
                        keys.add(f"{line}{DELIMITER}")
        return keys

    def _read_avro(self, directory: str) -> HostDataset:
        label_field = (
            "response"
            if self.params.field_names_type == FieldNamesType.RESPONSE_PREDICTION
            else "label"
        )
        return avro_data.read_training_examples(
            self._input_paths(directory),
            self.index_map,
            add_intercept=self.params.add_intercept,
            label_field=label_field,
        )

    def _build_index_map(self) -> IndexMap:
        p = self.params
        if p.offheap_indexmap_dir:
            from photon_ml_tpu.io.offheap import load_index_map

            return load_index_map(p.offheap_indexmap_dir)
        keys = avro_data.collect_feature_keys(self._input_paths(p.training_data_dir))
        selected = self._selected_features()
        if selected is not None:
            keys = [k for k in keys if k in selected]
        return IndexMap.build(
            keys,
            add_intercept=p.add_intercept,
            num_partitions=max(p.offheap_indexmap_num_partitions, 1),
        )

    def _preprocess_streaming(self) -> None:
        """Out-of-core preprocess: decode input FILE BY FILE, spill dense
        row chunks to <output>/stream-chunks/, never materializing the full
        batch (the DISK_ONLY persistence analogue, StorageLevel.scala:22-24).
        Per-file sanity checks replace the whole-batch pass; the colStats
        summary accumulates over chunks (optim/streaming.py).

        Peak host memory is O(largest single input file + one chunk) — the
        decode granularity is the file, exactly like the reference's
        per-partition decode (DataProcessingUtils.scala:57-80); split huge
        inputs into more part files to bound it. Rows are re-chunked ACROSS
        file boundaries so all chunks but the final tail share one shape
        (one XLA executable for the whole stream)."""
        p = self.params
        from photon_ml_tpu.optim.streaming import (
            ChunkedGLMSource,
            streaming_summarize,
        )

        paths = self._input_paths(p.training_data_dir)
        if p.input_file_format == InputFormatType.LIBSVM:
            dim = p.feature_dimension if p.feature_dimension > 0 else None
            first = read_libsvm(paths[0], dim=dim, add_intercept=p.add_intercept)
            names = [str(i) for i in range(first.dim - int(p.add_intercept))]
            if p.add_intercept:
                names.append(INTERCEPT_KEY)
            self.index_map = IndexMap({k: i for i, k in enumerate(names)}, names)
            read_file = lambda path: read_libsvm(
                path, dim=first.dim - int(p.add_intercept),
                add_intercept=p.add_intercept,
            )
            file_ds = {paths[0]: first}
        else:
            self.index_map = self._build_index_map()
            label_field = (
                "response"
                if p.field_names_type == FieldNamesType.RESPONSE_PREDICTION
                else "label"
            )
            read_file = lambda path: avro_data.read_training_examples(
                [path], self.index_map,
                add_intercept=p.add_intercept, label_field=label_field,
            )
            file_ds = {}

        dim = len(self.index_map)
        if dim > DENSE_DIM_THRESHOLD:
            raise ValueError(
                f"--streaming-chunk-rows spills DENSE chunks; {dim} features "
                f"exceeds the dense threshold ({DENSE_DIM_THRESHOLD}). The "
                "wide-sparse regime streams through the in-memory sparse "
                "layout instead (sparse chunk spilling is not implemented)."
            )
        def _spill_chunks(chunk_dir: str) -> None:
            """Decode file by file and spill re-chunked rows into
            ``chunk_dir`` (rows carried across file boundaries so every
            chunk but the final tail shares one shape -> one executable)."""
            chunk_i = 0
            total_rows = 0
            buf: List[dict] = []
            buf_rows = 0

            def _flush(final=False):
                nonlocal chunk_i, buf, buf_rows
                while buf_rows >= p.streaming_chunk_rows or (final and buf_rows > 0):
                    take = min(buf_rows, p.streaming_chunk_rows)
                    parts: List[dict] = []
                    got = 0
                    while got < take:
                        head = buf[0]
                        n_h = len(head["y"])
                        if got + n_h <= take:
                            parts.append(buf.pop(0))
                            got += n_h
                        else:
                            split = take - got
                            parts.append({k: v[:split] for k, v in head.items()})
                            buf[0] = {k: v[split:] for k, v in head.items()}
                            got = take
                    payload = {
                        k: np.concatenate([q[k] for q in parts])
                        for k in parts[0]
                    }
                    from photon_ml_tpu.optim.streaming import write_chunk

                    write_chunk(chunk_dir, chunk_i, payload)
                    chunk_i += 1
                    buf_rows -= take

            for path in paths:
                ds = file_ds.pop(path, None) or read_file(path)
                batch = to_batch(ds, dense=True)
                sanity_check_data(batch, p.task_type, p.data_validation_type)
                # uniform keys across files (a file without offsets/weights
                # must still concatenate with one that has them)
                piece = {
                    "x": np.asarray(batch.features.matrix)[: ds.num_rows],
                    "y": np.asarray(ds.labels),
                    "offsets": (
                        np.asarray(ds.offsets)
                        if ds.offsets is not None
                        else np.zeros(ds.num_rows, np.float32)
                    ),
                    "weights": (
                        np.asarray(ds.weights)
                        if ds.weights is not None
                        else np.ones(ds.num_rows, np.float32)
                    ),
                }
                buf.append(piece)
                buf_rows += ds.num_rows
                total_rows += ds.num_rows
                _flush()
            _flush(final=True)
            self.logger.info(
                f"streaming mode: {total_rows} rows x {dim} features spilled "
                f"to {chunk_i} chunks of {p.streaming_chunk_rows} rows (+ tail)"
            )

        source_dir = None
        if p.tensor_cache_dir:
            # content-addressed chunk reuse: a warm run over unchanged
            # inputs + config mmaps the committed chunks, skipping decode +
            # sanity pass + spill entirely
            from photon_ml_tpu.io.tensor_cache import (
                TensorCache,
                index_map_digest,
            )
            from photon_ml_tpu.resilience import RetryError

            cache = TensorCache(p.tensor_cache_dir)
            cache_key = cache.key_for(
                paths,
                {"kind": "glm_stream_chunks",
                 "chunk_rows": p.streaming_chunk_rows,
                 "format": p.input_file_format,
                 "fields": p.field_names_type,
                 "intercept": p.add_intercept,
                 "index_map": index_map_digest(self.index_map)},
            )
            source_dir = cache.get_dir(cache_key)
            if source_dir is not None:
                self.logger.info(
                    f"tensor cache HIT {cache_key[:12]}: decode + spill skipped"
                )
            else:
                try:
                    source_dir = cache.build_dir(cache_key, _spill_chunks)
                    self.logger.info(f"tensor cache stored {cache_key[:12]}")
                except RetryError as e:
                    self.logger.info(f"tensor cache unusable (uncached): {e}")
                    source_dir = None
        if source_dir is None:
            source_dir = os.path.join(p.output_dir, "stream-chunks")
            # stale chunks from an aborted prior run must never be trained
            # on — and a FAILED purge must be loud, not a silent mixed model
            import shutil

            if os.path.exists(source_dir):
                shutil.rmtree(source_dir)  # raises loudly if the purge fails
            os.makedirs(source_dir)
            _spill_chunks(source_dir)
        self.streaming_source = ChunkedGLMSource.from_chunk_dir(source_dir)

        needs_summary = (
            p.normalization_type != NormalizationType.NONE
            or p.summarization_output_dir is not None
        )
        if needs_summary:
            self.summary = streaming_summarize(self.streaming_source)
            if p.summarization_output_dir:
                write_basic_statistics(
                    self.summary, p.summarization_output_dir, self.index_map
                )
        if p.normalization_type != NormalizationType.NONE:
            intercept = self.index_map.intercept_index
            self.norm = NormalizationContext.build(
                p.normalization_type,
                mean=self.summary.mean,
                std=self.summary.std,
                max_magnitude=self.summary.max_magnitude,
                intercept_id=intercept if intercept >= 0 else None,
            )

        if p.validating_data_dir:
            if p.input_file_format == InputFormatType.LIBSVM:
                vds = read_libsvm(
                    self._input_paths(p.validating_data_dir)[0],
                    dim=len(self.index_map) - int(p.add_intercept),
                    add_intercept=p.add_intercept,
                )
            else:
                vds = self._read_avro(p.validating_data_dir)
            self.validation_batch = to_batch(vds, dense=True)
            sanity_check_data(self.validation_batch, p.task_type, p.data_validation_type)
        self._advance(DriverStage.PREPROCESSED)

    def preprocess(self) -> None:
        self._assert_stage(DriverStage.INIT)
        p = self.params
        if p.streaming_chunk_rows > 0:
            self._preprocess_streaming()
            return

        if p.input_file_format == InputFormatType.LIBSVM:
            paths = self._input_paths(p.training_data_dir)
            dim = p.feature_dimension if p.feature_dimension > 0 else None
            ds = read_libsvm(paths[0], dim=dim, add_intercept=p.add_intercept)
            for extra in paths[1:]:
                more = read_libsvm(extra, dim=ds.dim - int(p.add_intercept),
                                   add_intercept=p.add_intercept)
                ds = _concat_datasets(ds, more)
            self.train_ds = ds
            names = [str(i) for i in range(ds.dim - int(p.add_intercept))]
            if p.add_intercept:
                names.append(INTERCEPT_KEY)
            self.index_map = IndexMap({k: i for i, k in enumerate(names)}, names)
        else:
            self.index_map = self._build_index_map()
            self.train_ds = self._read_avro(p.training_data_dir)

        dense = self.train_ds.dim <= DENSE_DIM_THRESHOLD
        self.train_batch = to_batch(self.train_ds, dense=dense)
        self.logger.info(
            f"training data: {self.train_ds.num_rows} rows x {self.train_ds.dim} "
            f"features ({'dense' if dense else 'sparse'} layout)"
        )

        sanity_check_data(self.train_batch, p.task_type, p.data_validation_type)

        needs_summary = (
            p.normalization_type != NormalizationType.NONE
            or p.summarization_output_dir is not None
            or p.diagnostic_mode.runs_train
            or p.diagnostic_mode.runs_validate
        )
        if needs_summary:
            self.summary = summarize(self.train_batch)
            if p.summarization_output_dir:
                write_basic_statistics(
                    self.summary, p.summarization_output_dir, self.index_map
                )

        if p.normalization_type != NormalizationType.NONE:
            intercept = self.index_map.intercept_index
            self.norm = NormalizationContext.build(
                p.normalization_type,
                mean=self.summary.mean,
                std=self.summary.std,
                max_magnitude=self.summary.max_magnitude,
                intercept_id=intercept if intercept >= 0 else None,
            )

        if p.validating_data_dir:
            if p.input_file_format == InputFormatType.LIBSVM:
                vds = read_libsvm(
                    self._input_paths(p.validating_data_dir)[0],
                    dim=self.train_ds.dim - int(p.add_intercept),
                    add_intercept=p.add_intercept,
                )
            else:
                vds = self._read_avro(p.validating_data_dir)
            self.validation_batch = to_batch(vds, dense=dense)
            sanity_check_data(self.validation_batch, p.task_type, p.data_validation_type)

        self._advance(DriverStage.PREPROCESSED)

    # ------------------------------------------------------------------
    # stage: train
    # ------------------------------------------------------------------
    def _regularization_context(self) -> RegularizationContext:
        p = self.params
        if p.regularization_type == RegularizationType.NONE:
            return RegularizationContext.none()
        if p.regularization_type == RegularizationType.L1:
            return RegularizationContext.l1(1.0)
        if p.regularization_type == RegularizationType.ELASTIC_NET:
            return RegularizationContext.elastic_net(
                1.0, p.elastic_net_alpha if p.elastic_net_alpha is not None else 0.5
            )
        return RegularizationContext.l2(1.0)

    def _constraints(self) -> Optional[BoxConstraints]:
        p = self.params
        if not p.coefficient_box_constraints:
            return None
        cmap = parse_constraint_string(
            p.coefficient_box_constraints, self.index_map.name_to_index
        )
        if not cmap:
            return None
        return BoxConstraints.from_map(len(self.index_map), cmap)

    def _to_raw_space(self, model: GeneralizedLinearModel) -> GeneralizedLinearModel:
        if self.norm.is_identity:
            return model
        w = self.norm.model_to_original_space(model.coefficients.means)
        variances = model.coefficients.variances
        if variances is not None and self.norm.factors is not None:
            variances = variances * jnp.square(self.norm.factors)
        return GeneralizedLinearModel(Coefficients(w, variances), model.task)

    def train(self) -> None:
        self._assert_stage(DriverStage.PREPROCESSED)
        p = self.params
        self.problem = GLMOptimizationProblem(
            task=p.task_type,
            optimizer=p.optimizer_type,
            optimizer_config=OptimizerConfig(
                max_iterations=p.max_num_iterations, tolerance=p.tolerance
            ),
            regularization=self._regularization_context(),
            compute_variance=p.compute_variance,
            constraints=self._constraints(),
            # per-iteration coefficient snapshots back the ModelTracker-style
            # validate-per-iteration pass (Driver.scala:292-361)
            track_coefficients=p.validate_per_iteration,
        )
        from photon_ml_tpu.utils.profiling import maybe_trace

        with maybe_trace("glm-train"):
            if self.streaming_source is not None:
                from photon_ml_tpu.compile import resolve_bucketer
                from photon_ml_tpu.training import train_glm_grid_streaming

                self.trained = train_glm_grid_streaming(
                    self.problem, self.streaming_source, self.norm,
                    p.regularization_weights,
                    bucketer=resolve_bucketer(p.shape_canonicalization),
                )
                # the spilled chunks are dead weight once training completes
                import shutil

                shutil.rmtree(
                    os.path.join(p.output_dir, "stream-chunks"),
                    ignore_errors=True,
                )
            else:
                self.trained = train_glm_grid(
                    self.problem, self.train_batch, self.norm,
                    p.regularization_weights,
                )
        self.models = [
            (lam, self._to_raw_space(m))
            for lam, m in zip(self.trained.weights, self.trained.models)
        ]
        for lam, res in zip(self.trained.weights, self.trained.results):
            self.logger.info(f"lambda={lam:g}: {summarize_result(res)}")
            if p.enable_optimization_state_tracker:
                hist = np.asarray(res.value_history)
                hist = hist[~np.isnan(hist)]
                self.logger.debug(
                    f"lambda={lam:g} value history: "
                    + " ".join(f"{v:.6g}" for v in hist)
                )

        write_models_in_text(
            self.models,
            os.path.join(p.output_dir, LEARNED_MODELS_TEXT),
            self.index_map,
        )
        self._advance(DriverStage.TRAINED)

    # ------------------------------------------------------------------
    # stage: validate
    # ------------------------------------------------------------------
    def validate(self) -> None:
        self._assert_stage(DriverStage.TRAINED)
        best_lam, best_model, all_metrics = select_best_model(
            self.models, self.validation_batch
        )
        self.best_reg_weight = best_lam
        self.best_model = best_model
        self.validation_metrics = all_metrics
        for lam in sorted(all_metrics):
            for name, value in sorted(all_metrics[lam].items()):
                self.logger.info(f"lambda={lam:g} {name}: {value:.6g}")
        if self.params.validate_per_iteration:
            self._validate_per_iteration()
        self.logger.info(f"best model: lambda={best_lam:g}")
        write_models_in_text(
            [(best_lam, best_model)],
            os.path.join(self.params.output_dir, "best"),
            self.index_map,
        )
        self._advance(DriverStage.VALIDATED)

    def _validate_per_iteration(self) -> None:
        """Validation metrics for EVERY iteration's model snapshot
        (Driver.scala:292-361: computeAndLogModelMetrics over the
        ModelTrackers). Snapshots live in the solve results'
        coefficient_history (row 0 = w0, row k = after iteration k);
        results land in ``self.per_iteration_metrics[lambda]`` as one
        metric map per completed iteration, and the per-task selection
        metric is logged per iteration."""
        from photon_ml_tpu.model_selection import selection_metric_for

        p = self.params
        sel_metric = selection_metric_for(p.task_type)
        self.per_iteration_metrics = {}
        for lam, res in zip(self.trained.weights, self.trained.results):
            hist = res.coefficient_history
            if hist is None:
                continue
            iters = int(res.iterations)
            per_iter = []
            for it in range(1, iters + 1):
                if it == iters and lam in self.validation_metrics:
                    # hist[iters] IS the final model — its metrics were
                    # already computed during model selection
                    m = self.validation_metrics[lam]
                else:
                    snap = GeneralizedLinearModel(
                        Coefficients(hist[it]), p.task_type
                    )
                    m = metrics_mod.evaluate(
                        self._to_raw_space(snap), self.validation_batch
                    )
                per_iter.append(m)
                self.logger.info(
                    f"lambda={lam:g} iteration {it}/{iters} "
                    f"{sel_metric}: {m[sel_metric]:.6g}"
                )
            self.per_iteration_metrics[lam] = per_iter

    # ------------------------------------------------------------------
    # stage: diagnose
    # ------------------------------------------------------------------
    def diagnose(self) -> None:
        p = self.params
        feature_names = [
            (self.index_map.get_feature_name(j) or str(j)).replace(DELIMITER, ":")
            for j in range(len(self.index_map))
        ]
        model_reports: List[ModelDiagnosticReport] = []

        import dataclasses as _dc

        # diagnostics never read coefficient histories — don't let a
        # --validate-per-iteration run carry (max_iter+1, D) tracking
        # buffers through every prefix/bootstrap solve
        diag_problem = _dc.replace(self.problem, track_coefficients=False)

        fitting_reports = {}
        if p.diagnostic_mode.runs_train:
            fitting_reports = fitting.diagnose(
                diag_problem,
                self.train_batch,
                self.norm,
                p.regularization_weights,
            )

        from photon_ml_tpu.diagnostics import avro_reports
        from photon_ml_tpu.types import ConvergenceReason

        results_by_lam = dict(zip(self.trained.weights, self.trained.results))
        eval_records = []

        for lam, model in self.models:
            sections = []
            if p.diagnostic_mode.runs_validate and self.validation_batch is not None:
                metrics = self.validation_metrics.get(lam)
                if metrics is None:
                    metrics = metrics_mod.evaluate(model, self.validation_batch)
                sections.append(
                    feature_importance.to_section(
                        feature_importance.diagnose(
                            model, self.summary, feature_names=feature_names
                        )
                    )
                )
                sections.append(
                    independence.to_section(
                        independence.diagnose(model, self.validation_batch)
                    )
                )
                if p.task_type == TaskType.LOGISTIC_REGRESSION:
                    sections.append(
                        hosmer_lemeshow.to_section(
                            hosmer_lemeshow.diagnose(model, self.validation_batch)
                        )
                    )
            else:
                metrics = metrics_mod.evaluate(model, self.train_batch)
            if p.diagnostic_mode.runs_train and lam in fitting_reports:
                sections.append(fitting.to_section({lam: fitting_reports[lam]}))
            model_reports.append(
                ModelDiagnosticReport(model, lam, metrics, sections)
            )

            # machine-readable EvaluationResultAvro per model (the schemas the
            # reference ships for offline consumers; VERDICT r2 missing #5).
            # The batch/path pair MUST match where `metrics` was computed
            # above (validation only when runs_validate chose it).
            res = results_by_lam.get(lam)
            reg = self._regularization_context().with_weight(lam)
            on_validation = (
                p.diagnostic_mode.runs_validate and self.validation_batch is not None
            )
            eval_batch = self.validation_batch if on_validation else self.train_batch
            data_path = (
                p.validating_data_dir if on_validation else p.training_data_dir
            )
            with_curves = p.task_type in (
                TaskType.LOGISTIC_REGRESSION,
                TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM,
            )
            # score only when the curves will consume it
            scores = (
                np.asarray(model.compute_mean_functions(eval_batch))
                if with_curves
                else None
            )
            eval_records.append(
                avro_reports.evaluation_result(
                    model_id=f"{p.job_name}-lambda-{lam:g}",
                    model_path=os.path.join(p.output_dir, LEARNED_MODELS_TEXT),
                    data_path=data_path,
                    train_ctx=avro_reports.training_context(
                        p.task_type,
                        reg.l1_weight,
                        reg.l2_weight,
                        p.normalization_type != NormalizationType.NONE,
                        p.optimizer_type.value,
                        p.tolerance,
                        p.max_num_iterations,
                        ConvergenceReason(int(res.reason)) if res is not None else None,
                        p.training_data_dir,
                    ),
                    scalar_metrics=metrics,
                    scores=scores,
                    labels=np.asarray(eval_batch.labels),
                    weights=np.asarray(eval_batch.weights),
                    with_curves=with_curves,
                )
            )

        if p.diagnostic_mode.runs_train and self.validation_batch is not None:
            # dataset-level bootstrap at the best (or first) lambda
            lam0 = self.best_reg_weight if self.best_reg_weight is not None else self.models[0][0]
            boot_problem = _dc.replace(
                diag_problem,
                regularization=self.problem.regularization.with_weight(lam0),
            )
            boot = bootstrap_diagnostic.diagnose(
                boot_problem,
                self.train_batch,
                self.norm,
                self.validation_batch,
                feature_names=feature_names,
            )
            model_reports[0].sections.append(bootstrap_diagnostic.to_section(boot))

        doc = assemble_document(
            f"{p.job_name} model diagnostics",
            SystemReport(
                {
                    "task": p.task_type.value,
                    "optimizer": p.optimizer_type.value,
                    "regularization": p.regularization_type.value,
                    "lambdas": p.regularization_weights,
                    "normalization": p.normalization_type.value,
                    "training data": p.training_data_dir,
                    "validating data": p.validating_data_dir or "(none)",
                },
                self.summary,
                feature_names,
            ),
            model_reports,
        )
        with open(os.path.join(p.output_dir, REPORT_FILE), "w") as f:
            f.write(render_html(doc))
        self.logger.info(f"wrote {REPORT_FILE}")

        diag_dir = os.path.join(p.output_dir, "diagnostics")
        avro_reports.write_evaluation_results(diag_dir, eval_records)
        avro_reports.write_feature_summaries(
            diag_dir, avro_reports.feature_summaries(feature_names, self.summary)
        )
        self.logger.info(
            f"wrote {len(eval_records)} EvaluationResultAvro + feature summaries "
            f"to {diag_dir}"
        )
        if self.stage == DriverStage.TRAINED:
            self._advance(DriverStage.VALIDATED)  # keep ordering monotone
        self._advance(DriverStage.DIAGNOSED)


def _concat_datasets(a: HostDataset, b: HostDataset) -> HostDataset:
    if a.dim != b.dim:
        raise ValueError(f"feature dims differ: {a.dim} vs {b.dim}")

    def cat(x, y, fill):
        # fill must match to_batch's default for a missing column: offsets
        # default to 0, weights default to 1
        if x is None and y is None:
            return None
        x = x if x is not None else np.full(a.num_rows, fill, np.float32)
        y = y if y is not None else np.full(b.num_rows, fill, np.float32)
        return np.concatenate([x, y])

    return HostDataset(
        labels=np.concatenate([a.labels, b.labels]),
        indptr=np.concatenate([a.indptr, b.indptr[1:] + a.indptr[-1]]),
        indices=np.concatenate([a.indices, b.indices]),
        values=np.concatenate([a.values, b.values]),
        dim=a.dim,
        offsets=cat(a.offsets, b.offsets, 0.0),
        weights=cat(a.weights, b.weights, 1.0),
    )


def main(argv: Optional[List[str]] = None) -> Driver:
    import sys

    from photon_ml_tpu.resilience import preemption

    params = parse_from_command_line(argv)
    driver = Driver(params)
    # cooperative interruption: SIGTERM/SIGINT set the preemption flag; a
    # loop that polls (e.g. a compacted solve's chunk boundary) drains and
    # unwinds here, and the process exits with the distinct preemption code
    # so a supervisor (tools/run_supervised.py) can tell "rescheduled" from
    # "broken" and relaunch
    with preemption.signal_scope():
        try:
            driver.run()
        except preemption.Preempted as e:
            print(
                f"photon-ml-tpu glm: preempted ({e}); exiting "
                f"{preemption.PREEMPT_EXIT_CODE}",
                file=sys.stderr,
            )
            raise SystemExit(preemption.PREEMPT_EXIT_CODE) from e
    return driver


if __name__ == "__main__":
    main()
