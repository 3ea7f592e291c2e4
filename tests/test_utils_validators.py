"""Utility layer (timer/logger/date-range/text IO) + data validators."""

import datetime
import os

import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.data import validators
from photon_ml_tpu.io.index_map import IndexMap, feature_key
from photon_ml_tpu.models.glm import Coefficients, GeneralizedLinearModel
from photon_ml_tpu.ops.features import DenseFeatures
from photon_ml_tpu.ops.objective import GLMBatch

from trace_utils import trace_spans
from photon_ml_tpu.types import DataValidationType, TaskType
from photon_ml_tpu.utils import (
    DateRange,
    PhotonLogger,
    Timer,
    expand_date_range_paths,
    prepare_output_dir,
    read_models_from_text,
    write_models_in_text,
)


# -- validators --------------------------------------------------------------


def _batch(x, y, offsets=None):
    return GLMBatch.create(
        DenseFeatures(jnp.asarray(x)), jnp.asarray(y),
        jnp.asarray(offsets) if offsets is not None else None,
    )


def test_validators_pass_clean_data(rng):
    x = rng.normal(size=(20, 3)).astype(np.float32)
    y = (rng.random(20) > 0.5).astype(np.float32)
    validators.sanity_check_data(_batch(x, y), TaskType.LOGISTIC_REGRESSION)


def test_validators_reject_nonbinary_labels_for_logistic(rng):
    x = rng.normal(size=(10, 2)).astype(np.float32)
    y = np.linspace(0, 2, 10).astype(np.float32)
    with pytest.raises(ValueError, match="Binary labels"):
        validators.sanity_check_data(_batch(x, y), TaskType.LOGISTIC_REGRESSION)


def test_validators_reject_nan_features_and_offsets(rng):
    x = rng.normal(size=(10, 2)).astype(np.float32)
    x[3, 1] = np.nan
    y = (rng.random(10) > 0.5).astype(np.float32)
    with pytest.raises(ValueError, match="Finite features"):
        validators.sanity_check_data(_batch(x, y), TaskType.LOGISTIC_REGRESSION)
    x2 = rng.normal(size=(10, 2)).astype(np.float32)
    off = np.zeros(10, np.float32)
    off[0] = np.inf
    with pytest.raises(ValueError, match="Finite offsets"):
        validators.sanity_check_data(_batch(x2, y, off), TaskType.LOGISTIC_REGRESSION)


def test_validators_poisson_negative_labels(rng):
    x = rng.normal(size=(10, 2)).astype(np.float32)
    y = rng.normal(size=10).astype(np.float32)  # has negatives
    with pytest.raises(ValueError, match="Non-negative labels"):
        validators.sanity_check_data(_batch(x, y), TaskType.POISSON_REGRESSION)
    # disabled skips the check entirely
    validators.sanity_check_data(
        _batch(x, y), TaskType.POISSON_REGRESSION, DataValidationType.VALIDATE_DISABLED
    )


# -- timer / logger ----------------------------------------------------------


def test_timer_spans():
    t = Timer()
    with t.measure("phase1"):
        pass
    with t.measure("phase1"):
        pass
    assert t.totals["phase1"] >= 0.0
    with pytest.raises(RuntimeError):
        t.stop("never-started")
    assert "phase1" in t.summary()


def test_photon_logger_copies_on_close(tmp_path):
    out = tmp_path / "logs" / "photon.log"
    with PhotonLogger(str(out), echo=False) as log:
        log.info("hello world")
        log.debug("dropped below level")
    text = out.read_text()
    assert "hello world" in text
    assert "dropped" not in text


# -- date range --------------------------------------------------------------


def test_date_range_parsing_and_paths(tmp_path):
    r = DateRange.from_string("20160101-20160103")
    assert r.days() == [
        datetime.date(2016, 1, 1),
        datetime.date(2016, 1, 2),
        datetime.date(2016, 1, 3),
    ]
    for d in ("01", "03"):  # day 02 missing
        os.makedirs(tmp_path / "daily" / "2016" / "01" / d)
    paths = expand_date_range_paths(str(tmp_path), r)
    assert len(paths) == 2 and paths[0].endswith("01") and paths[1].endswith("03")
    with pytest.raises(FileNotFoundError):
        expand_date_range_paths(str(tmp_path), DateRange.from_string("20200101-20200102"))

    today = datetime.date(2016, 1, 10)
    r2 = DateRange.from_days_ago("9-7", today=today)
    assert r2.start == datetime.date(2016, 1, 1) and r2.end == datetime.date(2016, 1, 3)

    with pytest.raises(ValueError):
        DateRange.from_string("20160103-20160101")


# -- text model IO -----------------------------------------------------------


def test_write_read_models_in_text(tmp_path):
    imap = IndexMap.build([feature_key("f1", "a"), feature_key("f2", "")],
                          add_intercept=False)
    d = len(imap)
    means = np.zeros(d, np.float32)
    means[imap.get_index(feature_key("f1", "a"))] = 2.5
    means[imap.get_index(feature_key("f2", ""))] = -1.0
    model = GeneralizedLinearModel(Coefficients(jnp.asarray(means)),
                                   TaskType.LOGISTIC_REGRESSION)
    write_models_in_text([(0.5, model)], str(tmp_path / "models"), imap)
    back = read_models_from_text(str(tmp_path / "models"))
    assert back[0.5][("f1", "a")] == pytest.approx(2.5)
    assert back[0.5][("f2", "")] == pytest.approx(-1.0)
    # descending order by value in the file
    lines = (tmp_path / "models" / "part-00000.txt").read_text().splitlines()
    assert lines[0].startswith("f1\ta\t2.5")


def test_prepare_output_dir(tmp_path):
    target = tmp_path / "out"
    prepare_output_dir(str(target))
    (target / "junk.txt").write_text("x")
    with pytest.raises(FileExistsError):
        prepare_output_dir(str(target))
    prepare_output_dir(str(target), delete_if_exists=True)
    assert not list(target.iterdir())


def test_write_basic_statistics_avro(tmp_path, rng):
    from photon_ml_tpu.io.avro import read_container
    from photon_ml_tpu.ops.stats import summarize
    from photon_ml_tpu.utils import write_basic_statistics

    imap = IndexMap.build([feature_key("f1", ""), feature_key("f2", "t")],
                          add_intercept=False)
    x = rng.normal(size=(30, len(imap))).astype(np.float32)
    y = np.zeros(30, np.float32)
    summary = summarize(_batch(x, y))
    write_basic_statistics(summary, str(tmp_path / "stats"), imap)
    recs = list(read_container(str(tmp_path / "stats" / "part-00000.avro")))
    assert len(recs) == 2
    by_name = {(r["featureName"], r["featureTerm"]): r["metrics"] for r in recs}
    col = imap.get_index(feature_key("f2", "t"))
    assert by_name[("f2", "t")]["mean"] == pytest.approx(float(x[:, col].mean()), abs=1e-5)
    assert set(recs[0]["metrics"]) == {"max", "min", "mean", "normL1", "normL2",
                                       "numNonzeros", "variance"}


class TestProfilerHooks:
    """PHOTON_ML_TPU_PROFILE device-trace hooks (SURVEY §5.1 upgrade)."""

    def test_no_env_is_noop(self, monkeypatch):
        from photon_ml_tpu.utils.profiling import maybe_trace

        monkeypatch.delenv("PHOTON_ML_TPU_PROFILE", raising=False)
        with maybe_trace("stage"):
            pass  # must not require a profiler session

    def test_trace_writes_artifacts(self, monkeypatch, tmp_path):
        import jax.numpy as jnp

        from photon_ml_tpu.utils.profiling import maybe_trace, span

        monkeypatch.setenv("PHOTON_ML_TPU_PROFILE", str(tmp_path))
        with maybe_trace("unit"):
            with span("pml.test.solve", lanes=3):
                jnp.sum(jnp.ones((64, 64))).block_until_ready()
        stage_dir = tmp_path / "unit"
        assert stage_dir.is_dir()
        # the trace holds the span, with its metadata beside it
        found = [e for e in trace_spans(stage_dir) if e.name == "pml.test.solve"]
        assert len(found) == 1 and found[0].meta == {"lanes": "3"}

    def test_span_builds_no_string_without_a_trace(self):
        from photon_ml_tpu.utils.profiling import span

        class Unprintable:
            def __str__(self):
                raise AssertionError("formatted with no trace running")

            __repr__ = __str__

        with span("pml.test.quiet", value=Unprintable()):
            pass

    def test_timer_stage_is_a_span(self, monkeypatch, tmp_path):
        from photon_ml_tpu.utils.profiling import maybe_trace
        from photon_ml_tpu.utils.timer import Timer

        monkeypatch.setenv("PHOTON_ML_TPU_PROFILE", str(tmp_path))
        timer = Timer()
        with maybe_trace("unit"), timer.measure("train"):
            pass
        assert "train" in timer.totals
        stages = [e for e in trace_spans(tmp_path / "unit") if e.name == "pml.stage"]
        assert [e.meta for e in stages] == [{"stage": "train"}]


class TestNativeLibsvmParser:
    """native/libsvm_parser.cpp fast path vs the pure-Python parser —
    byte-identical CSR output (the data-loader half of the native runtime)."""

    def _write(self, path):
        path.write_text(
            "1 1:0.5 3:-1.25 7:2e-3  # trailing comment\n"
            "\n"
            "-1 2:1.0\n"
            "# full-line comment\n"
            "1 1:3.5\n"
            "-1 5:0.125 6:-0.5\n"
        )

    def test_differential_vs_python(self, tmp_path, monkeypatch):
        import numpy as np

        from photon_ml_tpu.io import libsvm, native_build

        f = tmp_path / "data.txt"
        self._write(f)
        native_lib = libsvm._load_lsv_native()
        if native_lib is None:
            pytest.skip("no native toolchain")
        ds_n = libsvm.read_libsvm(str(f))

        monkeypatch.setenv(native_build.NATIVE_ENV, "0")
        native_build._cache.clear()
        ds_p = libsvm.read_libsvm(str(f))
        native_build._cache.clear()  # don't leak the disabled state

        np.testing.assert_array_equal(ds_n.labels, ds_p.labels)
        np.testing.assert_array_equal(ds_n.indptr, ds_p.indptr)
        np.testing.assert_array_equal(ds_n.indices, ds_p.indices)
        np.testing.assert_array_equal(ds_n.values, ds_p.values)
        assert ds_n.dim == ds_p.dim
        # {-1,1} labels remapped to {0,1} on both paths
        assert set(np.unique(ds_n.labels).tolist()) == {0.0, 1.0}

    def test_zero_based_and_explicit_dim(self, tmp_path):
        from photon_ml_tpu.io import libsvm

        f = tmp_path / "zb.txt"
        f.write_text("0 0:1.0 2:2.0\n1 1:3.0\n")
        ds = libsvm.read_libsvm(str(f), zero_based=True, add_intercept=False, dim=5)
        assert ds.dim == 5
        assert ds.indices.tolist() == [0, 2, 1]
