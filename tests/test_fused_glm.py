"""Fused logistic value+grad Pallas kernel tests (interpreter mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.ops.fused_glm import (
    fused_logistic_value_and_grad,
    reference_logistic_value_and_grad,
)


def _data(rng, n, d, dtype=jnp.float32):
    x = rng.normal(size=(n, d)).astype(np.float32)
    w = (rng.normal(size=d) * 0.2).astype(np.float32)
    y = (rng.random(n) < 0.5).astype(np.float32)
    wt = rng.uniform(0.5, 2.0, size=n).astype(np.float32)
    return (
        jnp.asarray(x, dtype),
        jnp.asarray(y),
        jnp.asarray(wt),
        jnp.asarray(w),
        x,
    )


class TestFusedLogistic:
    def test_matches_reference_f32(self, rng):
        x, y, wt, w, _ = _data(rng, 512, 64)
        v, g = fused_logistic_value_and_grad(x, y, wt, w, block_rows=128)
        v_ref, g_ref = reference_logistic_value_and_grad(x, y, wt, w)
        assert float(v) == pytest.approx(float(v_ref), rel=1e-5)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), rtol=1e-4, atol=1e-4)

    def test_bf16_storage_close_to_f32(self, rng):
        x, y, wt, w, x_np = _data(rng, 1024, 32, dtype=jnp.bfloat16)
        v, g = fused_logistic_value_and_grad(x, y, wt, w, block_rows=256)
        v_ref, g_ref = reference_logistic_value_and_grad(
            jnp.asarray(x_np), y, wt, w
        )
        assert float(v) == pytest.approx(float(v_ref), rel=2e-2)
        ref_norm = float(jnp.linalg.norm(g_ref))
        assert float(jnp.linalg.norm(g - g_ref)) < 0.03 * ref_norm

    def test_l2_term(self, rng):
        x, y, wt, w, _ = _data(rng, 256, 16)
        v, g = fused_logistic_value_and_grad(x, y, wt, w, l2=0.5, block_rows=128)
        v_ref, g_ref = reference_logistic_value_and_grad(x, y, wt, w, l2=0.5)
        assert float(v) == pytest.approx(float(v_ref), rel=1e-5)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), rtol=1e-4, atol=1e-4)

    def test_ragged_n_padded(self, rng):
        # N not a multiple of block_rows -> internal zero-weight padding
        x, y, wt, w, _ = _data(rng, 300, 8)
        v, g = fused_logistic_value_and_grad(x, y, wt, w, block_rows=128)
        v_ref, g_ref = reference_logistic_value_and_grad(x, y, wt, w)
        assert float(v) == pytest.approx(float(v_ref), rel=1e-5)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), rtol=1e-4, atol=1e-4)

    def test_zero_weight_rows_excluded(self, rng):
        x, y, wt, w, _ = _data(rng, 256, 8)
        wt0 = wt.at[:64].set(0.0)
        v, _ = fused_logistic_value_and_grad(x, y, wt0, w, block_rows=64)
        v_ref, _ = reference_logistic_value_and_grad(x, y, wt0, w)
        assert float(v) == pytest.approx(float(v_ref), rel=1e-5)

    @pytest.mark.parametrize("loss_name", ["logistic", "squared", "poisson", "smoothed_hinge"])
    def test_all_losses_with_offsets(self, rng, loss_name):
        """Generalized kernel: every pointwise loss, nonzero offsets, and the
        sum(d) accumulator all match the XLA objective path."""
        from photon_ml_tpu.ops import losses
        from photon_ml_tpu.ops.features import DenseFeatures
        from photon_ml_tpu.ops.fused_glm import fused_value_grad_parts
        from photon_ml_tpu.ops.normalization import NormalizationContext
        from photon_ml_tpu.ops.objective import GLMBatch, GLMObjective

        loss = getattr(losses, loss_name)
        x, y, wt, w, _ = _data(rng, 384, 16)
        if loss_name == "poisson":
            y = jnp.asarray(rng.poisson(1.5, size=384).astype(np.float32))
        off = jnp.asarray(rng.normal(scale=0.3, size=384).astype(np.float32))
        lv, g, sumd = fused_value_grad_parts(loss, x, y, wt, off, w, block_rows=128)
        batch = GLMBatch(DenseFeatures(x), y, off, wt)
        obj = GLMObjective(loss)
        v_ref, g_ref = obj.value_and_grad(w, batch, NormalizationContext.identity())
        assert float(lv) == pytest.approx(float(v_ref), rel=1e-5)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), rtol=1e-4, atol=1e-4)
        d_ref = wt * loss.d1(x @ w + off, y)
        assert float(sumd) == pytest.approx(float(jnp.sum(d_ref)), rel=1e-4, abs=1e-4)

    def test_objective_fused_dispatch_with_normalization(self, rng):
        """GLMObjective(fused_block_rows=...) folds shift/factor/L2 algebra
        around the kernel identically to the XLA path."""
        from photon_ml_tpu.ops import losses
        from photon_ml_tpu.ops.features import DenseFeatures
        from photon_ml_tpu.ops.normalization import NormalizationContext
        from photon_ml_tpu.ops.objective import GLMBatch, GLMObjective
        from photon_ml_tpu.types import NormalizationType

        x, y, wt, w, x_np = _data(rng, 512, 8)
        off = jnp.asarray(rng.normal(scale=0.2, size=512).astype(np.float32))
        batch = GLMBatch(DenseFeatures(x), y, off, wt)
        norm = NormalizationContext.build(
            NormalizationType.STANDARDIZATION,
            mean=jnp.asarray(x_np.mean(0)),
            std=jnp.asarray(x_np.std(0)),
            intercept_id=7,
        )
        plain = GLMObjective(losses.logistic)
        fused = GLMObjective(losses.logistic, fused_block_rows=128)
        v0, g0 = plain.value_and_grad(w, batch, norm, 0.25)
        v1, g1 = fused.value_and_grad(w, batch, norm, 0.25)
        assert float(v1) == pytest.approx(float(v0), rel=1e-5)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g0), rtol=1e-4, atol=1e-4)

    def test_race_off_tpu(self, monkeypatch):
        from photon_ml_tpu.ops import fused_glm, losses

        monkeypatch.delenv("PHOTON_ML_TPU_FUSED", raising=False)
        assert fused_glm.race_fused_block_rows(losses.logistic, 4096, 128) is None
        monkeypatch.setenv("PHOTON_ML_TPU_FUSED", "0")
        assert fused_glm.race_fused_block_rows(losses.logistic, 4096, 128) is None

    def test_race_forced_runs_interpreted(self, monkeypatch):
        """PHOTON_ML_TPU_FUSED=1 exercises the full race machinery off-TPU
        (interpreter mode) and returns a usable block size."""
        from photon_ml_tpu.ops import fused_glm, losses

        monkeypatch.setenv("PHOTON_ML_TPU_FUSED", "1")
        block = fused_glm.race_fused_block_rows(
            losses.logistic, 2048, 128, candidates=(1024,)
        )
        assert block == 1024

    def test_matches_objective_module(self, rng):
        """Consistency with the framework's GLMObjective path."""
        from photon_ml_tpu.ops import losses
        from photon_ml_tpu.ops.features import DenseFeatures
        from photon_ml_tpu.ops.normalization import NormalizationContext
        from photon_ml_tpu.ops.objective import GLMBatch, GLMObjective

        x, y, wt, w, _ = _data(rng, 512, 24)
        batch = GLMBatch(DenseFeatures(x), y, jnp.zeros_like(y), wt)
        obj = GLMObjective(losses.logistic)
        v_obj, g_obj = obj.value_and_grad(w, batch, NormalizationContext.identity(), 0.3)
        v, g = fused_logistic_value_and_grad(x, y, wt, w, l2=0.3, block_rows=128)
        assert float(v) == pytest.approx(float(v_obj), rel=1e-5)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_obj), rtol=1e-4, atol=1e-4)


class TestManualDoubleBufferedVariant:
    """NEGATIVE block sizes select the explicit-DMA double-buffered kernel
    (x chunks streamed from HBM, y/wt/off resident in VMEM) — the autotune's
    second pipeline family. Must agree with the oracle and the grid-pipeline
    kernel bit-for-bit in f32 interpreter mode."""

    @pytest.mark.parametrize("loss_name", ["logistic", "squared", "poisson"])
    def test_matches_grid_pipeline_and_oracle(self, rng, loss_name):
        from photon_ml_tpu.ops import fused_glm, losses

        loss = getattr(losses, loss_name)
        x, y, wt, w, _ = _data(rng, 700, 128)  # non-multiple of block
        off = jnp.asarray(np.random.default_rng(5).normal(size=700).astype(np.float32) * 0.1)
        if loss_name == "poisson":
            y = jnp.abs(y) * 2.0  # counts
        v_a, g_a, s_a = fused_glm.fused_value_grad_parts(
            loss, x, y, wt, off, w, block_rows=256, interpret=True
        )
        v_m, g_m, s_m = fused_glm.fused_value_grad_parts(
            loss, x, y, wt, off, w, block_rows=-256, interpret=True
        )
        assert float(v_m) == pytest.approx(float(v_a), rel=1e-6)
        assert float(s_m) == pytest.approx(float(s_a), rel=1e-5, abs=1e-6)
        np.testing.assert_allclose(np.asarray(g_m), np.asarray(g_a), rtol=1e-5, atol=1e-6)

        # oracle: plain f32 dense computation
        z = x @ w + off
        lv = float(jnp.sum(wt * loss.loss(z, y)))
        d = wt * loss.d1(z, y)
        assert float(v_m) == pytest.approx(lv, rel=1e-5)
        # gradient columns can cancel catastrophically (poisson: row
        # contributions ~1e3 summing to ~1e0), and interpreter-mode chunk
        # accumulation order differs across jax versions — bound the error
        # by the per-column |contribution| mass, not the tiny net value
        col_mass = np.abs(np.asarray(d)) @ np.abs(np.asarray(x))
        err = np.abs(np.asarray(g_m) - np.asarray(d @ x))
        assert (err <= 1e-5 * col_mass + 1e-4).all(), (
            f"max err {err.max()} vs col-mass-scaled bound"
        )

    def test_race_accepts_negative_candidates(self, monkeypatch):
        from photon_ml_tpu.ops import fused_glm, losses

        monkeypatch.setenv("PHOTON_ML_TPU_FUSED", "1")
        block = fused_glm.race_fused_block_rows(
            losses.logistic, 1024, 128, candidates=(-512,)
        )
        assert block == -512


class TestVpuFamily:
    """The VPU elementwise formulation (encoded VPU_MARK + rows) must match
    the MXU grid kernel and the XLA oracle exactly — interpreter-mode
    equivalence; the perf race happens on real hardware."""

    def test_vpu_kernel_matches_oracle(self, rng):
        import jax.numpy as jnp

        from photon_ml_tpu.ops.fused_glm import (
            VPU_MARK,
            fused_value_grad_parts,
            reference_logistic_value_and_grad,
        )
        from photon_ml_tpu.ops import losses

        n, d = 512, 256
        x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        y = jnp.asarray((rng.random(n) < 0.5).astype(np.float32))
        wt = jnp.asarray(rng.uniform(0.5, 2.0, n).astype(np.float32))
        off = jnp.asarray(rng.normal(scale=0.2, size=n).astype(np.float32))
        w = jnp.asarray(rng.normal(size=d).astype(np.float32) * 0.1)
        lv, g, sumd = fused_value_grad_parts(
            losses.logistic, x, y, wt, off, w, block_rows=VPU_MARK + 128
        )
        lv2, g2, sumd2 = fused_value_grad_parts(
            losses.logistic, x, y, wt, off, w, block_rows=128
        )
        np.testing.assert_allclose(float(lv), float(lv2), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g2), rtol=2e-3, atol=1e-4)
        np.testing.assert_allclose(float(sumd), float(sumd2), rtol=1e-4, atol=1e-5)

    def test_decode_block(self):
        from photon_ml_tpu.ops.fused_glm import VPU_MARK, _decode_block

        assert _decode_block(4096) == ("grid", 4096)
        assert _decode_block(-2048) == ("manual", 2048)
        assert _decode_block(VPU_MARK + 8192) == ("vpu", 8192)


class TestScanFamily:
    """Pure-XLA single-pass scan family (SCAN_MARK encodings): no Pallas
    anywhere, so it must be exact against the two-pass oracle on every
    backend and through the ragged pad path."""

    def test_matches_oracle_all_blocks(self, rng):
        from photon_ml_tpu.ops import losses
        from photon_ml_tpu.ops.fused_glm import SCAN_MARK, fused_value_grad_parts

        n, d = 3072, 192
        x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        y = jnp.asarray((rng.random(n) < 0.5).astype(np.float32))
        wt = jnp.asarray(rng.uniform(0.2, 2.0, n).astype(np.float32))
        off = jnp.asarray(rng.normal(scale=0.2, size=n).astype(np.float32))
        w = jnp.asarray(rng.normal(size=d).astype(np.float32) * 0.1)
        z = x @ w + off
        val_ref = float(jnp.sum(wt * losses.logistic.loss(z, y)))
        g_ref = np.asarray((wt * losses.logistic.d1(z, y)) @ x)
        d_ref = float(jnp.sum(wt * losses.logistic.d1(z, y)))
        for block in (256, 1024, 3072, 4096):  # incl. block > n (pad) and n itself
            v, g, ds = fused_value_grad_parts(
                losses.logistic, x, y, wt, off, w, block_rows=SCAN_MARK + block
            )
            np.testing.assert_allclose(float(v), val_ref, rtol=1e-5, err_msg=str(block))
            np.testing.assert_allclose(np.asarray(g), g_ref, rtol=1e-4, atol=1e-4)
            np.testing.assert_allclose(float(ds), d_ref, rtol=1e-4, atol=1e-4)

    def test_decode_and_autotune_candidates(self):
        from photon_ml_tpu.ops.fused_glm import (
            AUTOTUNE_CANDIDATES,
            SCAN_MARK,
            VPU_MARK,
            _decode_block,
        )

        assert _decode_block(SCAN_MARK + 8192) == ("scan", 8192)
        # SCAN_MARK encodings must not collide with the VPU band
        assert all(
            _decode_block(c)[0] != "vpu"
            for c in AUTOTUNE_CANDIDATES if c >= SCAN_MARK
        )
        assert any(_decode_block(c)[0] == "scan" for c in AUTOTUNE_CANDIDATES)
        assert VPU_MARK + 16384 < SCAN_MARK


# -- the one-pass kernel the selection wires in (PR 32) -----------------------

#: (rows, width): widths 200 and 2000 are held column-major on the device at
#: these row counts (the rows-in-lanes orientation), 256 row-major; none of
#: the row counts is a multiple of the 256-row block
ONE_PASS_SHAPES = {200: 1100, 2000: 5200, 256: 1100}
ONE_PASS_BLOCK = 256


def _one_pass_problem(rng, width, dtype, loss_name):
    """Rows with offsets, a tail the block does not divide, and zero-weight
    rows whose labels and offsets make the loss inf and nan."""
    n = ONE_PASS_SHAPES[width]
    x = rng.normal(size=(n, width)).astype(np.float32) / np.sqrt(width)
    w = rng.normal(size=width).astype(np.float32)
    y = (rng.random(n) < 0.5).astype(np.float32)
    if loss_name == "poisson":
        y = rng.poisson(1.5, size=n).astype(np.float32)
    wt = rng.uniform(0.5, 2.0, size=n).astype(np.float32)
    off = rng.normal(scale=0.3, size=n).astype(np.float32)
    dead = np.array([3, 300, 700, n - 2])  # in the first and later blocks and the tail
    wt[dead] = 0.0
    y[dead] = np.array([np.nan, np.inf, 1.0, np.nan], np.float32)
    off[dead] = np.array([0.0, 1.0, np.inf, -np.inf], np.float32)
    return tuple(jnp.asarray(a) for a in (x, y, wt, off, w)), jnp.dtype(dtype)


def _float64_parts(loss, x, y, wt, off, w):
    """(loss sum, X^T d, sum d) in float64 numpy from the stored values, and
    the mass of each column's terms (the scale of its rounding error)."""
    import jax

    with jax.enable_x64(True):
        xs = np.asarray(x.astype(jnp.float32), np.float64)
        z = xs @ np.asarray(w, np.float64) + np.asarray(off, np.float64)
        alive = np.asarray(wt) > 0
        z64, y64 = jnp.asarray(z[alive]), jnp.asarray(np.asarray(y, np.float64)[alive])
        wl = np.asarray(wt, np.float64)[alive] * np.asarray(loss.loss(z64, y64))
        d = np.asarray(wt, np.float64)[alive] * np.asarray(loss.d1(z64, y64))
    return wl.sum(), d @ xs[alive], d.sum(), np.abs(d) @ np.abs(xs[alive])


class TestOnePassKernel:
    """``fused_value_grad_parts`` under the ``vpu`` encoding, as
    ``select_fused_block_rows`` hands it out: either orientation, exact
    float32 products, no padded copy, the tail through the two-pass
    arithmetic (CPU, interpret mode)."""

    @pytest.mark.parametrize("loss_name", ["logistic", "squared", "poisson", "smoothed_hinge"])
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("width", [200, 2000, 256])
    def test_parts_match_float64(self, rng, width, dtype, loss_name):
        from photon_ml_tpu.ops import fused_glm, losses

        loss = getattr(losses, loss_name)
        (x, y, wt, off, w), dtype = _one_pass_problem(rng, width, dtype, loss_name)
        x = x.astype(dtype)
        n = x.shape[0]
        assert fused_glm.held_column_major(n, width) == (width != 256)
        assert n % ONE_PASS_BLOCK != 0
        lv, g, sumd = fused_glm.fused_value_grad_parts(
            loss, x, y, wt, off, w, block_rows=fused_glm.VPU_MARK + ONE_PASS_BLOCK)
        lv_ref, g_ref, sumd_ref, mass = _float64_parts(loss, x, y, wt, off, w)
        # float32 arithmetic on the stored values whatever the storage: the
        # products are exact to a float32 rounding of each term
        assert np.isfinite(float(lv)) and np.all(np.isfinite(np.asarray(g)))
        assert float(lv) == pytest.approx(lv_ref, rel=2e-6)
        assert float(sumd) == pytest.approx(sumd_ref, rel=1e-5, abs=1e-4)
        err = np.abs(np.asarray(g, np.float64) - g_ref)
        assert (err <= 2e-6 * mass + 1e-6).all(), err.max()

    @pytest.mark.parametrize("width", [200, 2000, 256])
    def test_objective_folds_normalization_around_it(self, rng, width):
        """Shifts, factors and L2 through ``GLMObjective.value_and_grad``
        read as on the two-pass path."""
        from photon_ml_tpu.ops import fused_glm, losses
        from photon_ml_tpu.ops.features import DenseFeatures
        from photon_ml_tpu.ops.normalization import NormalizationContext
        from photon_ml_tpu.ops.objective import GLMBatch, GLMObjective
        from photon_ml_tpu.types import NormalizationType

        (x, y, wt, off, w), _ = _one_pass_problem(rng, width, "float32", "logistic")
        y, off = jnp.nan_to_num(y, nan=0.0, posinf=1.0), jnp.nan_to_num(off, posinf=0.0, neginf=0.0)
        x_np = np.asarray(x)
        norm = NormalizationContext.build(
            NormalizationType.STANDARDIZATION,
            mean=jnp.asarray(x_np.mean(0)), std=jnp.asarray(x_np.std(0)),
            intercept_id=width - 1,
        )
        batch = GLMBatch(DenseFeatures(x), y, off, wt)
        v0, g0 = GLMObjective(losses.logistic).value_and_grad(w, batch, norm, 0.25)
        v1, g1 = GLMObjective(
            losses.logistic, fused_block_rows=fused_glm.VPU_MARK + ONE_PASS_BLOCK
        ).value_and_grad(w, batch, norm, 0.25)
        assert float(v1) == pytest.approx(float(v0), rel=2e-6)
        # both round a column's sum at the scale of its largest terms
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g0), rtol=2e-4,
                                   atol=4e-6 * float(jnp.max(jnp.abs(g0))))

    @pytest.mark.parametrize("optimizer", ["LBFGS", "TRON"])
    def test_solves_match_the_two_pass_path(self, rng, optimizer):
        """A whole solve on the kernel against the same solve on the
        two-pass path, at float32 tolerances."""
        import dataclasses

        from photon_ml_tpu.ops import fused_glm
        from photon_ml_tpu.ops.features import DenseFeatures
        from photon_ml_tpu.ops.normalization import NormalizationContext
        from photon_ml_tpu.ops.objective import GLMBatch
        from photon_ml_tpu.ops.regularization import RegularizationContext
        from photon_ml_tpu.optim.common import OptimizerConfig
        from photon_ml_tpu.optim.problem import GLMOptimizationProblem
        from photon_ml_tpu.types import OptimizerType, TaskType

        n, width = 1100, 200
        x = rng.normal(size=(n, width)).astype(np.float32)
        truth = rng.normal(size=width).astype(np.float32) * 0.3
        y = (rng.random(n) < 1.0 / (1.0 + np.exp(-x @ truth))).astype(np.float32)
        batch = GLMBatch.create(DenseFeatures(jnp.asarray(x)), jnp.asarray(y))
        plain = GLMOptimizationProblem(
            task=TaskType.LOGISTIC_REGRESSION, optimizer=OptimizerType[optimizer],
            optimizer_config=OptimizerConfig(max_iterations=15, tolerance=0.0),
            regularization=RegularizationContext.l2(1.0))
        fused = dataclasses.replace(
            plain, fused_block_rows=fused_glm.VPU_MARK + ONE_PASS_BLOCK)
        norm = NormalizationContext.identity()
        (m0, r0), (m1, r1) = plain.run(batch, norm), fused.run(batch, norm)
        assert int(r1.iterations) == int(r0.iterations) > 3
        np.testing.assert_allclose(
            np.asarray(m1.coefficients.means), np.asarray(m0.coefficients.means),
            rtol=2e-3, atol=2e-5)
        assert float(r1.value) == pytest.approx(float(r0.value), rel=1e-5)

    def test_a_batch_smaller_than_a_chunk_is_all_tail(self, rng):
        from photon_ml_tpu.ops import fused_glm, losses

        x = jnp.asarray(rng.normal(size=(100, 20)).astype(np.float32))
        assert fused_glm.held_column_major(100, 20)
        y = jnp.asarray((rng.random(100) < 0.5).astype(np.float32))
        ones, w = jnp.ones((100,)), jnp.asarray(rng.normal(size=20).astype(np.float32))
        got = fused_glm.fused_value_grad_parts(
            losses.logistic, x, y, ones, 0.0 * ones, w,
            block_rows=fused_glm.VPU_MARK + ONE_PASS_BLOCK)
        want = fused_glm._two_pass_parts(losses.logistic, x, y, ones, 0.0 * ones, w)
        for a, b in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestSelection:
    """``select_fused_block_rows``: a pure function of platform, dtype and
    shape; nothing built, nothing timed."""

    @pytest.fixture
    def on_tpu(self, monkeypatch):
        from photon_ml_tpu.ops import fused_glm

        def no_device_work(*a, **k):
            raise AssertionError("the selection touched the device")

        monkeypatch.setattr(fused_glm, "_on_tpu", lambda: True)
        monkeypatch.setattr(fused_glm, "_time_value_and_grad", no_device_work)
        monkeypatch.setattr(fused_glm, "fused_value_grad_parts", no_device_work)
        monkeypatch.setattr(jax.random, "normal", no_device_work)
        monkeypatch.setattr(jnp, "zeros", no_device_work)
        monkeypatch.delenv("PHOTON_ML_TPU_FUSED", raising=False)
        return fused_glm

    @pytest.mark.parametrize("n,d,dtype,want", [
        (400000, 2000, "float32", ("vpu", 640)),   # the dense cell: 625 blocks, no tail
        (400000, 2000, "bfloat16", ("vpu", 2048)),
        (400000, 200, "float32", ("vpu", 2048)),
        (1 << 22, 64, "float32", ("vpu", 2048)),
        (400000, 2048, "float32", ("vpu", 640)),   # held row-major, rows of 8 KiB
        (400000, 2048, "bfloat16", ("vpu", 2048)),
        (1600000, 512, "float32", ("vpu", 3200)),  # rows of 2 KiB: the narrowest that won
        (1600000, 512, "bfloat16", None),          # rows of 1 KiB: bound by its transposes
        (100000, 1920, "float32", ("vpu", 1024)),  # held row-major; 1,000 rows of tail
        (1 << 20, 127, "float32", None),           # held row-major at a ragged width
        (400000, 2000, "float64", None),
        (300, 16, "float32", None),                # a per-entity problem
        (8192, 2000, "float32", ("vpu", 1024)),    # 62 MiB: over the line
        (4096, 2000, "float32", None),             # 31 MiB: under it
        (8192, 2048, "bfloat16", None),            # 32 MiB: on it
        (1 << 15, 1 << 15, "float32", None),       # wider than the kernel unrolls
        (1 << 20, 40000, "float32", None),         # held column-major: 128 rows over VMEM
    ])
    def test_follows_from_the_shape(self, on_tpu, n, d, dtype, want):
        got = on_tpu.select_fused_block_rows(n, d, jnp.dtype(dtype))
        assert (got and on_tpu._decode_block(got)) == want
        assert on_tpu.select_fused_block_rows(n, d, jnp.dtype(dtype)) == got

    @pytest.mark.parametrize("mode,tpu,big,small", [
        ("0", True, None, None),
        ("auto", True, ("vpu", 640), None),
        ("auto", False, None, None),
        ("1", False, ("vpu", 640), ("vpu", 2048)),  # clipped to the batch when it runs
    ])
    def test_environment_switch_keeps_its_three_meanings(
            self, on_tpu, monkeypatch, mode, tpu, big, small):
        monkeypatch.setattr(on_tpu, "_on_tpu", lambda: tpu)
        monkeypatch.setenv("PHOTON_ML_TPU_FUSED", mode)
        decoded = lambda b: b and on_tpu._decode_block(b)
        assert decoded(on_tpu.select_fused_block_rows(400000, 2000, jnp.float32)) == big
        assert decoded(on_tpu.select_fused_block_rows(300, 16, jnp.float32)) == small

    def test_off_a_tpu_the_grid_keeps_the_two_pass_path(self, monkeypatch):
        from photon_ml_tpu.ops import fused_glm

        monkeypatch.delenv("PHOTON_ML_TPU_FUSED", raising=False)
        assert fused_glm.select_fused_block_rows(400000, 2000, jnp.float32) is None

    def test_vmapped_grid_clears_the_kernel(self, rng):
        """``train_glm_grid_vmapped`` (lanes are lambdas) and the per-entity
        solves (lanes are entities) stay on the two-pass path."""
        import dataclasses

        from photon_ml_tpu import training
        from photon_ml_tpu.ops import fused_glm
        from photon_ml_tpu.ops.features import DenseFeatures
        from photon_ml_tpu.ops.normalization import NormalizationContext
        from photon_ml_tpu.ops.objective import GLMBatch
        from photon_ml_tpu.ops.regularization import RegularizationContext
        from photon_ml_tpu.optim.common import OptimizerConfig
        from photon_ml_tpu.optim.problem import GLMOptimizationProblem
        from photon_ml_tpu.types import OptimizerType, TaskType

        x = jnp.asarray(rng.normal(size=(300, 16)).astype(np.float32))
        y = jnp.asarray((rng.random(300) < 0.5).astype(np.float32))
        batch = GLMBatch.create(DenseFeatures(x), y)
        problem = GLMOptimizationProblem(
            task=TaskType.LOGISTIC_REGRESSION, optimizer=OptimizerType.LBFGS,
            optimizer_config=OptimizerConfig(max_iterations=5, tolerance=0.0),
            regularization=RegularizationContext.l2(1.0),
            fused_block_rows=fused_glm.VPU_MARK + ONE_PASS_BLOCK)
        norm = NormalizationContext.identity()
        forced = training.train_glm_grid_vmapped(problem, batch, norm, [1.0, 10.0])
        plain = training.train_glm_grid_vmapped(
            dataclasses.replace(problem, fused_block_rows=None), batch, norm, [1.0, 10.0])
        for a, b in zip(forced.models, plain.models):
            np.testing.assert_array_equal(
                np.asarray(a.coefficients.means), np.asarray(b.coefficients.means))


class TestUnderShardMap:
    """The distributed fixed effect wraps the solve in ``shard_map`` with
    ``check_vma=True``: the kernel sees the local shard and has to say which
    mesh axes its outputs vary over."""

    DEVICES = 8

    @pytest.fixture
    def solve(self, rng, monkeypatch):
        from photon_ml_tpu.ops.features import DenseFeatures
        from photon_ml_tpu.ops.normalization import NormalizationContext
        from photon_ml_tpu.ops.objective import GLMBatch
        from photon_ml_tpu.ops.regularization import RegularizationContext
        from photon_ml_tpu.optim.common import OptimizerConfig
        from photon_ml_tpu.optim.problem import GLMOptimizationProblem
        from photon_ml_tpu.parallel import (
            DistributedFixedEffectSolver, MeshContext, data_mesh, pad_rows)
        from photon_ml_tpu.types import OptimizerType, TaskType

        ctx = MeshContext(data_mesh(self.DEVICES))
        norm = NormalizationContext.identity()

        def build(width, switch):
            """(solver with its block chosen, padded batch) on 300 rows a
            device and a few more, so that a shard has two whole blocks of
            128 rows and a tail."""
            monkeypatch.setenv("PHOTON_ML_TPU_FUSED", switch)
            n = self.DEVICES * 300 - 3
            state = np.random.default_rng(7)
            x = state.normal(size=(n, width)).astype(np.float32) / np.sqrt(width)
            truth = state.normal(size=width).astype(np.float32) * 3.0
            y = (state.random(n) < 1.0 / (1.0 + np.exp(-x @ truth))).astype(np.float32)
            batch = pad_rows(
                GLMBatch.create(DenseFeatures(jnp.asarray(x)), jnp.asarray(y)), self.DEVICES)
            solver = DistributedFixedEffectSolver(
                GLMOptimizationProblem(
                    TaskType.LOGISTIC_REGRESSION, OptimizerType.LBFGS,
                    OptimizerConfig(max_iterations=5, tolerance=0.0),
                    RegularizationContext.l2(0.5)), ctx)
            solver._maybe_autotune_fused(batch)
            return solver, batch

        return build, ctx, norm

    @pytest.mark.parametrize("width", [200, 256])
    def test_the_distributed_solve_traces_with_the_kernel(self, solve, width):
        """``DistributedFixedEffectSolver``'s own program, ``check_vma`` as
        it sets it, traced with the kernel in it (no lowering: interpret
        mode's own loops do not pass that check in jax 0.9.0, the chip's
        compiler does, ``tests/test_dense_grid_reference.py``)."""
        from photon_ml_tpu.ops import fused_glm

        build, ctx, norm = solve
        solver, batch = build(width, "1")
        assert fused_glm._decode_block(solver.problem.fused_block_rows)[0] == "vpu"
        assert fused_glm.held_column_major(batch.num_rows // self.DEVICES, width) == (width == 200)
        jaxpr = jax.make_jaxpr(solver._build(norm))(
            ctx.put_sharded(batch), jnp.zeros((width,), jnp.float32), jnp.float32(0.5))
        assert "pallas_call" in str(jaxpr)

    @pytest.mark.parametrize("width", [200, 256])
    def test_the_distributed_solve_matches_the_two_pass_solve(self, solve, monkeypatch, width):
        """The shards' parts summed over the mesh: the kernel's solve against
        the two-pass solve, both through ``DistributedFixedEffectSolver.run``
        (with ``check_vma`` off, for interpret mode's sake)."""
        import functools

        from photon_ml_tpu.parallel import distributed

        monkeypatch.setattr(
            distributed, "shard_map", functools.partial(jax.shard_map, check_vma=False))
        build, _, norm = solve
        fused, batch = build(width, "1")
        plain, _ = build(width, "0")
        assert fused.problem.fused_block_rows and plain.problem.fused_block_rows is None
        (m1, r1), (m0, r0) = fused.run(batch, norm), plain.run(batch, norm)
        assert int(r1.iterations) == int(r0.iterations) == 5
        np.testing.assert_allclose(
            np.asarray(m1.coefficients.means), np.asarray(m0.coefficients.means),
            rtol=2e-3, atol=2e-5)
        assert float(r1.value) == pytest.approx(float(r0.value), rel=1e-5)
