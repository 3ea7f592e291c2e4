"""The one-pass dense value-and-gradient kernel, its selection and the rule
that sets a problem's block (interpreter mode on the CPU)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

LOSSES = ["logistic", "squared", "poisson", "smoothed_hinge"]
ONE_PASS_BLOCK = 256

#: (rows, width, rows a block, storage, loss). The first three shapes under
#: either storage and every loss: 200 and 2,000 wide are held column-major on
#: the device at these row counts (the rows-in-lanes orientation), 256 wide
#: row-major, and none of the row counts is a multiple of the block. Then
#: shapes with whole blocks only, a tail, a block under one 128-row chunk
#: (all tail), and fewer features than a sublane tile.
ONE_PASS_CASES = [
    (n, width, ONE_PASS_BLOCK, dtype, loss)
    for n, width in ((1100, 200), (5200, 2000), (1100, 256))
    for dtype in ("float32", "bfloat16") for loss in LOSSES
] + [
    (512, 64, 128, "float32", "logistic"),   # four whole blocks, rows along lanes
    (300, 8, 128, "float32", "logistic"),    # two blocks and 44 rows of tail
    (256, 8, 64, "float32", "logistic"),     # a block under a chunk: all tail
    *[(384, 16, 128, "float32", loss) for loss in LOSSES],  # three whole blocks
    (512, 256, 128, "float32", "logistic"),  # held row-major, whole blocks only
]


def _one_pass_problem(rng, n, width, loss_name):
    """Rows with offsets and zero-weight rows whose labels and offsets make
    the loss inf and nan, in the first and later blocks and the tail."""
    x = rng.normal(size=(n, width)).astype(np.float32) / np.sqrt(width)
    w = rng.normal(size=width).astype(np.float32)
    y = (rng.random(n) < 0.5).astype(np.float32)
    if loss_name == "poisson":
        y = rng.poisson(1.5, size=n).astype(np.float32)
    wt = rng.uniform(0.5, 2.0, size=n).astype(np.float32)
    off = rng.normal(scale=0.3, size=n).astype(np.float32)
    dead = np.array([3, n // 3, 2 * n // 3, n - 2])
    wt[dead] = 0.0
    y[dead] = np.array([np.nan, np.inf, 1.0, np.nan], np.float32)
    off[dead] = np.array([0.0, 1.0, np.inf, -np.inf], np.float32)
    return tuple(jnp.asarray(a) for a in (x, y, wt, off, w))


def _float64_parts(loss, x, y, wt, off, w):
    """(loss sum, X^T d, sum d) in float64 numpy from the stored values, and
    the mass of each column's terms (the scale of its rounding error)."""
    with jax.enable_x64(True):
        xs = np.asarray(x.astype(jnp.float32), np.float64)
        z = xs @ np.asarray(w, np.float64) + np.asarray(off, np.float64)
        alive = np.asarray(wt) > 0
        z64, y64 = jnp.asarray(z[alive]), jnp.asarray(np.asarray(y, np.float64)[alive])
        wl = np.asarray(wt, np.float64)[alive] * np.asarray(loss.loss(z64, y64))
        d = np.asarray(wt, np.float64)[alive] * np.asarray(loss.d1(z64, y64))
    return wl.sum(), d @ xs[alive], d.sum(), np.abs(d) @ np.abs(xs[alive])



def _objective_batch(rng, n, width):
    """A logistic batch the two-pass objective can take too: the dead rows'
    labels and offsets made finite."""
    from photon_ml_tpu.ops.features import DenseFeatures
    from photon_ml_tpu.ops.objective import GLMBatch

    x, y, wt, off, w = _one_pass_problem(rng, n, width, "logistic")
    y, off = jnp.nan_to_num(y, nan=0.0, posinf=1.0), jnp.nan_to_num(off, posinf=0.0, neginf=0.0)
    return GLMBatch(DenseFeatures(x), y, off, wt), w


class TestOnePassKernel:
    """``fused_value_grad_parts`` at a number of rows a block, as
    ``select_fused_block_rows`` hands it out: either orientation, exact
    float32 products, no padded copy, the tail through the two-pass
    arithmetic (CPU, interpret mode)."""

    @pytest.mark.parametrize(
        "n,width,block,dtype,loss_name", ONE_PASS_CASES,
        ids=["{}x{}-{}-{}-{}".format(*c) for c in ONE_PASS_CASES])
    def test_parts_match_float64(self, rng, n, width, block, dtype, loss_name):
        from photon_ml_tpu.ops import fused_glm, losses

        loss = getattr(losses, loss_name)
        x, y, wt, off, w = _one_pass_problem(rng, n, width, loss_name)
        x = x.astype(jnp.dtype(dtype))
        assert fused_glm.held_column_major(n, width) == (width != 256)
        lv, g, sumd = fused_glm.fused_value_grad_parts(
            loss, x, y, wt, off, w, block_rows=block)
        lv_ref, g_ref, sumd_ref, mass = _float64_parts(loss, x, y, wt, off, w)
        # float32 arithmetic on the stored values whatever the storage: the
        # products are exact to a float32 rounding of each term
        assert np.isfinite(float(lv)) and np.all(np.isfinite(np.asarray(g)))
        assert float(lv) == pytest.approx(lv_ref, rel=2e-6)
        assert float(sumd) == pytest.approx(sumd_ref, rel=1e-5, abs=1e-4)
        err = np.abs(np.asarray(g, np.float64) - g_ref)
        assert (err <= 2e-6 * mass + 1e-6).all(), err.max()

    def test_bfloat16_storage_is_close_to_float32_storage(self, rng):
        """What rounding the matrix to bfloat16 costs, against the two-pass
        objective on the unrounded matrix."""
        from photon_ml_tpu.ops import losses
        from photon_ml_tpu.ops.normalization import NormalizationContext
        from photon_ml_tpu.ops.objective import GLMObjective

        batch, w = _objective_batch(rng, 1024, 32)
        norm = NormalizationContext.identity()
        v0, g0 = GLMObjective(losses.logistic).value_and_grad(w, batch, norm)
        rounded = dataclasses.replace(batch, features=batch.features.astype(jnp.bfloat16))
        v1, g1 = GLMObjective(losses.logistic, fused_block_rows=ONE_PASS_BLOCK).value_and_grad(
            w, rounded, norm)
        assert float(v1) == pytest.approx(float(v0), rel=2e-2)
        assert float(jnp.linalg.norm(g1 - g0)) < 0.03 * float(jnp.linalg.norm(g0))

    @pytest.mark.parametrize("n,width,block,normalized,l2", [
        (1100, 200, ONE_PASS_BLOCK, True, 0.25),
        (5200, 2000, ONE_PASS_BLOCK, True, 0.25),
        (1100, 256, ONE_PASS_BLOCK, True, 0.25),
        (512, 8, 128, True, 0.25),     # the intercept the last of 8 features
        (256, 16, 128, False, 0.5),    # the L2 term alone
        (512, 24, 128, False, 0.3),
    ])
    def test_objective_folds_normalization_around_it(self, rng, n, width, block, normalized, l2):
        """Shifts, factors and L2 through ``GLMObjective.value_and_grad``
        read as on the two-pass path."""
        from photon_ml_tpu.ops import losses
        from photon_ml_tpu.ops.normalization import NormalizationContext
        from photon_ml_tpu.ops.objective import GLMObjective
        from photon_ml_tpu.types import NormalizationType

        batch, w = _objective_batch(rng, n, width)
        norm = NormalizationContext.identity()
        if normalized:
            x_np = np.asarray(batch.features.matrix)
            norm = NormalizationContext.build(
                NormalizationType.STANDARDIZATION,
                mean=jnp.asarray(x_np.mean(0)), std=jnp.asarray(x_np.std(0)),
                intercept_id=width - 1,
            )
        v0, g0 = GLMObjective(losses.logistic).value_and_grad(w, batch, norm, l2)
        v1, g1 = GLMObjective(losses.logistic, fused_block_rows=block).value_and_grad(
            w, batch, norm, l2)
        assert float(v1) == pytest.approx(float(v0), rel=2e-6)
        # both round a column's sum at the scale of its largest terms
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g0), rtol=2e-4,
                                   atol=4e-6 * float(jnp.max(jnp.abs(g0))))

    @pytest.mark.parametrize("width", [200, 256])
    def test_an_overflowing_zero_weight_row_adds_exactly_nothing(self, rng, width):
        """Poisson's ``exp`` of a margin of 1e30 is inf, and 0 * inf is nan:
        the row is masked, not multiplied, in both orientations, so the
        three parts are the bits they are with that row's margin at 0."""
        from photon_ml_tpu.ops import fused_glm, losses

        n = 1100
        assert fused_glm.held_column_major(n, width) == (width == 200)
        x, y, wt, off, w = _one_pass_problem(rng, n, width, "poisson")
        row = 300  # in the second block; 1090 is in the tail
        wt = wt.at[jnp.array([row, 1090])].set(0.0)
        parts = lambda offsets: fused_glm.fused_value_grad_parts(
            losses.poisson, x, y, wt, offsets, w, block_rows=ONE_PASS_BLOCK)
        calm = parts(off.at[jnp.array([row, 1090])].set(0.0))
        wild = parts(off.at[jnp.array([row, 1090])].set(1e30))
        for a, b in zip(wild, calm):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("optimizer", ["LBFGS", "TRON"])
    def test_solves_match_the_two_pass_path(self, rng, optimizer):
        """A whole solve on the kernel against the same solve on the
        two-pass path, at float32 tolerances."""
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
        fused = dataclasses.replace(plain, fused_block_rows=ONE_PASS_BLOCK)
        norm = NormalizationContext.identity()
        (m0, r0), (m1, r1) = plain.run(batch, norm), fused.run(batch, norm)
        assert int(r1.iterations) == int(r0.iterations) > 3
        np.testing.assert_allclose(
            np.asarray(m1.coefficients.means), np.asarray(m0.coefficients.means),
            rtol=2e-3, atol=2e-5)
        assert float(r1.value) == pytest.approx(float(r0.value), rel=1e-5)

    def test_a_batch_smaller_than_a_chunk_is_all_tail(self, rng):
        from photon_ml_tpu.ops import fused_glm, losses

        x, y, wt, off, w = _one_pass_problem(rng, 100, 20, "logistic")
        assert fused_glm.held_column_major(100, 20)
        parts = lambda *a: fused_glm.fused_value_grad_parts(
            losses.logistic, *a, block_rows=ONE_PASS_BLOCK)
        assert "pallas_call" not in str(jax.make_jaxpr(parts)(x, y, wt, off, w))
        lv, g, sumd = parts(x, y, wt, off, w)
        lv_ref, g_ref, sumd_ref, mass = _float64_parts(losses.logistic, x, y, wt, off, w)
        assert float(lv) == pytest.approx(lv_ref, rel=2e-6)
        assert float(sumd) == pytest.approx(sumd_ref, rel=1e-5, abs=1e-4)
        assert (np.abs(np.asarray(g, np.float64) - g_ref) <= 2e-6 * mass + 1e-6).all()


class TestSelection:
    """``select_fused_block_rows``: a pure function of platform, dtype and
    shape; nothing built, nothing timed."""

    @pytest.fixture
    def on_tpu(self, monkeypatch):
        from photon_ml_tpu.ops import fused_glm

        def no_device_work(*a, **k):
            raise AssertionError("the selection touched the device")

        monkeypatch.setattr(fused_glm, "_on_tpu", lambda: True)
        monkeypatch.setattr(fused_glm, "fused_value_grad_parts", no_device_work)
        monkeypatch.setattr(jax.random, "normal", no_device_work)
        monkeypatch.setattr(jnp, "zeros", no_device_work)
        monkeypatch.delenv("PHOTON_ML_TPU_FUSED", raising=False)
        return fused_glm


    @pytest.mark.parametrize("n,d,dtype,want", [
        (400000, 2000, "float32", 640),    # the dense cell: 625 blocks, no tail
        (400000, 2000, "bfloat16", 2048),
        (400000, 200, "float32", 2048),
        (1 << 22, 64, "float32", 2048),
        (400000, 2048, "float32", 640),    # held row-major, rows of 8 KiB
        (400000, 2048, "bfloat16", 2048),
        (1600000, 512, "float32", 3200),   # rows of 2 KiB: the narrowest that won
        (1600000, 512, "bfloat16", None),  # rows of 1 KiB: bound by its transposes
        (100000, 1920, "float32", 1024),   # held row-major; 1,000 rows of tail
        (1 << 20, 127, "float32", None),   # held row-major at a ragged width
        (400000, 2000, "float64", None),
        (300, 16, "float32", None),        # a per-entity problem
        (8192, 2000, "float32", 1024),     # 62 MiB: over the line
        (4096, 2000, "float32", None),     # 31 MiB: under it
        (8192, 2048, "bfloat16", None),    # 32 MiB: on it
        (1 << 15, 1 << 15, "float32", None),  # wider than the kernel unrolls
        (1 << 20, 40000, "float32", None),    # held column-major: 128 rows over VMEM
    ])
    def test_follows_from_the_shape(self, on_tpu, n, d, dtype, want):
        assert on_tpu.select_fused_block_rows(n, d, jnp.dtype(dtype)) == want
        assert on_tpu.select_fused_block_rows(n, d, jnp.dtype(dtype)) == want

    @pytest.mark.parametrize("mode,tpu,big,small", [
        ("0", True, None, None),
        ("auto", True, 640, None),
        ("auto", False, None, None),
        ("1", False, 640, 2048),  # clipped to the batch when it runs
    ])
    def test_environment_switch_keeps_its_three_meanings(
            self, on_tpu, monkeypatch, mode, tpu, big, small):
        monkeypatch.setattr(on_tpu, "_on_tpu", lambda: tpu)
        monkeypatch.setenv("PHOTON_ML_TPU_FUSED", mode)
        assert on_tpu.select_fused_block_rows(400000, 2000, jnp.float32) == big
        assert on_tpu.select_fused_block_rows(300, 16, jnp.float32) == small


    def test_off_a_tpu_the_grid_keeps_the_two_pass_path(self, monkeypatch):
        from photon_ml_tpu.ops import fused_glm

        monkeypatch.delenv("PHOTON_ML_TPU_FUSED", raising=False)
        assert fused_glm.select_fused_block_rows(400000, 2000, jnp.float32) is None

    def test_vmapped_grid_clears_the_kernel(self, rng):
        """``train_glm_grid_vmapped`` (lanes are lambdas) and the per-entity
        solves (lanes are entities) stay on the two-pass path."""
        from photon_ml_tpu import training
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
            fused_block_rows=ONE_PASS_BLOCK)
        norm = NormalizationContext.identity()
        forced = training.train_glm_grid_vmapped(problem, batch, norm, [1.0, 10.0])
        plain = training.train_glm_grid_vmapped(
            dataclasses.replace(problem, fused_block_rows=None), batch, norm, [1.0, 10.0])
        for a, b in zip(forced.models, plain.models):
            np.testing.assert_array_equal(
                np.asarray(a.coefficients.means), np.asarray(b.coefficients.means))


def _logistic_problem(**kw):
    from photon_ml_tpu.ops.regularization import RegularizationContext
    from photon_ml_tpu.optim.common import OptimizerConfig
    from photon_ml_tpu.optim.problem import GLMOptimizationProblem
    from photon_ml_tpu.types import OptimizerType, TaskType

    return GLMOptimizationProblem(
        task=TaskType.LOGISTIC_REGRESSION, optimizer=OptimizerType.LBFGS,
        optimizer_config=OptimizerConfig(max_iterations=3, tolerance=0.0),
        regularization=RegularizationContext.l2(1.0), **kw)


def _dense_shapes(n, width, dtype=jnp.float32):
    """A dense batch of shapes alone: the rule reads nothing else."""
    from photon_ml_tpu.ops.features import DenseFeatures
    from photon_ml_tpu.ops.objective import GLMBatch

    rows = jax.ShapeDtypeStruct((n,), jnp.float32)
    return GLMBatch(DenseFeatures(jax.ShapeDtypeStruct((n, width), dtype)), rows, rows, rows)


class TestTheRuleThatSetsTheBlock:
    """``GLMOptimizationProblem.with_fused_block_for``: dense features and no
    block set, so ask the selection for the shape a device sees. Called by
    ``train_glm_grid`` and ``DistributedFixedEffectSolver.run``."""

    @pytest.fixture
    def on_tpu(self, monkeypatch):
        from photon_ml_tpu.ops import fused_glm

        monkeypatch.setattr(fused_glm, "_on_tpu", lambda: True)
        monkeypatch.delenv("PHOTON_ML_TPU_FUSED", raising=False)

    def test_sets_a_block_for_dense_features_on_a_tpu(self, on_tpu):
        problem = _logistic_problem()
        chosen = problem.with_fused_block_for(_dense_shapes(400000, 2000))
        assert chosen.fused_block_rows == 640
        assert chosen == dataclasses.replace(problem, fused_block_rows=640)
        # under the selection's line the problem comes back as it went in
        assert problem.with_fused_block_for(_dense_shapes(300, 16)) is problem

    def test_leaves_sparse_features_alone(self, monkeypatch, rng):
        from photon_ml_tpu.ops.features import SparseFeatures
        from photon_ml_tpu.ops.objective import GLMBatch

        monkeypatch.setenv("PHOTON_ML_TPU_FUSED", "1")  # dense would get one anywhere
        n, k, dim = 64, 4, 32
        sparse = SparseFeatures(
            jnp.asarray(rng.integers(0, dim, size=(n, k)), jnp.int32),
            jnp.ones((n, k), jnp.float32), dim)
        batch = GLMBatch.create(sparse, jnp.zeros((n,), jnp.float32))
        problem = _logistic_problem()
        assert problem.with_fused_block_for(batch) is problem
        assert problem.with_fused_block_for(_dense_shapes(n, dim)).fused_block_rows

    def test_keeps_a_block_the_caller_set(self, on_tpu):
        problem = _logistic_problem(fused_block_rows=256)
        assert problem.with_fused_block_for(_dense_shapes(400000, 2000)) is problem

    @pytest.mark.parametrize("n,shards,want", [
        (1600000, 4, 640),   # 400,000 rows a chip: the one-chip cell's block
        (16384, 1, 1024),    # 125 MiB on one chip: over the selection's line
        (16384, 4, None),    # 31 MiB a chip: under it
    ])
    def test_divides_the_rows_by_the_shards(self, on_tpu, n, shards, want):
        chosen = _logistic_problem().with_fused_block_for(_dense_shapes(n, 2000), shards)
        assert chosen.fused_block_rows == want

    @pytest.mark.parametrize("switch,kernel", [("1", True), ("0", False)])
    def test_train_glm_grid_reaches_the_kernel_by_it(self, rng, monkeypatch, switch, kernel):
        """``train_glm_grid`` hands ``_solve`` the problem the rule gives:
        with the switch at 1 its program holds the kernel, at 0 it does not."""
        from photon_ml_tpu import training
        from photon_ml_tpu.ops.features import DenseFeatures
        from photon_ml_tpu.ops.normalization import NormalizationContext
        from photon_ml_tpu.ops.objective import GLMBatch

        monkeypatch.setenv("PHOTON_ML_TPU_FUSED", switch)
        x = jnp.asarray(rng.normal(size=(300, 16)).astype(np.float32))
        y = jnp.asarray((rng.random(300) < 0.5).astype(np.float32))
        batch = GLMBatch.create(DenseFeatures(x), y)
        solved, real = [], training._solve

        def recording(problem, *args):
            solved.append((problem, args))
            return real(problem, *args)

        monkeypatch.setattr(training, "_solve", recording)
        models = training.train_glm_grid(
            _logistic_problem(), batch, NormalizationContext.identity(), [1.0]).models
        assert np.all(np.isfinite(np.asarray(models[0].coefficients.means)))
        (problem, args), = solved
        assert (problem.fused_block_rows is not None) == kernel
        jaxpr = jax.make_jaxpr(lambda b, n, w0, lam: problem.run(
            b, n, init_coefficients=w0, reg_weight=lam))(*args)
        assert ("pallas_call" in str(jaxpr)) == kernel


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

        def build(width, switch, choose=True):
            """(solver, padded batch) on 300 rows a device and a few more, so
            that a shard has two whole blocks of 128 rows and a tail; with
            ``choose`` the solver's block is set as its ``run`` sets it."""
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
            if choose:
                solver.problem = solver.problem.with_fused_block_for(batch, self.DEVICES)
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
        assert solver.problem.fused_block_rows
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
        plain, batch = build(width, "0", choose=False)
        (m0, r0) = plain.run(batch, norm)
        fused, _ = build(width, "1", choose=False)
        (m1, r1) = fused.run(batch, norm)  # run asks the rule itself
        assert fused.problem.fused_block_rows and plain.problem.fused_block_rows is None
        assert int(r1.iterations) == int(r0.iterations) == 5
        np.testing.assert_allclose(
            np.asarray(m1.coefficients.means), np.asarray(m0.coefficients.means),
            rtol=2e-3, atol=2e-5)
        assert float(r1.value) == pytest.approx(float(r0.value), rel=1e-5)
