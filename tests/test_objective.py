"""GLM objective tests: gradient/HVP/Hessian-diag vs autodiff; sparse==dense;
normalization-folding == explicit normalization; psum path under shard_map.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from photon_ml_tpu.ops import losses
from photon_ml_tpu.ops.features import DenseFeatures, SparseFeatures
from photon_ml_tpu.ops.normalization import NormalizationContext
from photon_ml_tpu.ops.objective import GLMBatch, GLMObjective
from photon_ml_tpu.types import NormalizationType


def make_batch(rng, n=64, d=9, dense=True, with_weights=True):
    x = rng.normal(size=(n, d)).astype(np.float32)
    x[:, -1] = 1.0  # intercept column
    y = (rng.random(n) > 0.5).astype(np.float32)
    off = rng.normal(size=n).astype(np.float32) * 0.1
    w = rng.random(n).astype(np.float32) + 0.5 if with_weights else np.ones(n, np.float32)
    if dense:
        feats = DenseFeatures(jnp.asarray(x))
    else:
        # exact sparse representation of the dense matrix
        idx = np.tile(np.arange(d, dtype=np.int32), (n, 1))
        feats = SparseFeatures(jnp.asarray(idx), jnp.asarray(x), d)
    return GLMBatch(feats, jnp.asarray(y), jnp.asarray(off), jnp.asarray(w)), x


@pytest.mark.parametrize("loss", [losses.logistic, losses.squared, losses.poisson],
                         ids=lambda l: l.name)
@pytest.mark.parametrize("normed", [False, True])
def test_grad_hvp_diag_vs_autodiff(rng, loss, normed):
    batch, x = make_batch(rng)
    d = x.shape[1]
    if normed:
        norm = NormalizationContext.build(
            NormalizationType.STANDARDIZATION,
            mean=jnp.asarray(x.mean(0)), std=jnp.asarray(x.std(0)), intercept_id=d - 1)
    else:
        norm = NormalizationContext.identity()
    obj = GLMObjective(loss)
    w = jnp.asarray(rng.normal(size=d).astype(np.float32) * 0.3)
    l2 = 0.7

    f = lambda ww: obj.value(ww, batch, norm, l2)
    v0, g0 = obj.value_and_grad(w, batch, norm, l2)
    np.testing.assert_allclose(v0, f(w), rtol=1e-5)
    np.testing.assert_allclose(g0, jax.grad(f)(w), rtol=2e-4, atol=2e-4)

    v = jnp.asarray(rng.normal(size=d).astype(np.float32))
    hv_want = jax.jvp(jax.grad(f), (w,), (v,))[1]
    hv_got = obj.hessian_vector(w, v, batch, norm, l2)
    np.testing.assert_allclose(hv_got, hv_want, rtol=2e-3, atol=2e-3)

    diag_want = jnp.diag(jax.hessian(f)(w))
    diag_got = obj.hessian_diagonal(w, batch, norm, l2)
    np.testing.assert_allclose(diag_got, diag_want, rtol=6e-3, atol=6e-3)


def test_sparse_matches_dense(rng):
    dense_batch, x = make_batch(rng, dense=True)
    sparse_batch, _ = make_batch(np.random.default_rng(20260729), dense=False)
    obj = GLMObjective(losses.logistic)
    norm = NormalizationContext.identity()
    w = jnp.asarray(np.random.default_rng(7).normal(size=x.shape[1]).astype(np.float32))
    vd, gd = obj.value_and_grad(w, dense_batch, norm, 0.1)
    vs, gs = obj.value_and_grad(w, sparse_batch, norm, 0.1)
    np.testing.assert_allclose(vd, vs, rtol=1e-5)
    np.testing.assert_allclose(gd, gs, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        obj.hessian_diagonal(w, dense_batch, norm, 0.1),
        obj.hessian_diagonal(w, sparse_batch, norm, 0.1), rtol=1e-4, atol=1e-5)


def test_folding_equals_explicit_normalization(rng):
    """Folded (factor, shift) must equal materializing x' = (x-shift)*factor."""
    batch, x = make_batch(rng)
    d = x.shape[1]
    norm = NormalizationContext.build(
        NormalizationType.STANDARDIZATION,
        mean=jnp.asarray(x.mean(0)), std=jnp.asarray(x.std(0)), intercept_id=d - 1)
    xn = (x - np.asarray(norm.shifts)) * np.asarray(norm.factors)
    explicit = GLMBatch(DenseFeatures(jnp.asarray(xn)), batch.labels, batch.offsets,
                        batch.weights)
    obj = GLMObjective(losses.logistic)
    w = jnp.asarray(rng.normal(size=d).astype(np.float32))
    v1, g1 = obj.value_and_grad(w, batch, norm, 0.0)
    v2, g2 = obj.value_and_grad(w, explicit, NormalizationContext.identity(), 0.0)
    np.testing.assert_allclose(v1, v2, rtol=1e-5)
    np.testing.assert_allclose(g1, g2, rtol=1e-4, atol=1e-4)


def test_zero_weight_rows_are_padding(rng):
    batch, x = make_batch(rng, n=32)
    obj = GLMObjective(losses.poisson)
    norm = NormalizationContext.identity()
    w = jnp.asarray(rng.normal(size=x.shape[1]).astype(np.float32) * 0.2)
    # append garbage rows with weight 0
    x2 = np.concatenate([x, np.full((8, x.shape[1]), 1e3, np.float32)])
    pad = lambda a, fill: jnp.concatenate([a, jnp.full((8,), fill, a.dtype)])
    batch2 = GLMBatch(DenseFeatures(jnp.asarray(x2)), pad(batch.labels, 1.0),
                      pad(batch.offsets, 0.0), pad(batch.weights, 0.0))
    v1, g1 = obj.value_and_grad(w, batch, norm, 0.3)
    v2, g2 = obj.value_and_grad(w, batch2, norm, 0.3)
    np.testing.assert_allclose(v1, v2, rtol=1e-5)
    np.testing.assert_allclose(g1, g2, rtol=1e-5, atol=1e-5)


def test_psum_path_matches_single_device(rng):
    """shard_map + axis_name psum == unsharded computation (treeAggregate parity)."""
    n_dev = len(jax.devices())
    batch, x = make_batch(rng, n=8 * 16)
    d = x.shape[1]
    w = jnp.asarray(rng.normal(size=d).astype(np.float32))
    norm = NormalizationContext.identity()
    obj_local = GLMObjective(losses.logistic)
    obj_dist = GLMObjective(losses.logistic, axis_name="data")

    mesh = Mesh(np.array(jax.devices()), ("data",))
    fn = shard_map(
        lambda ww, bb: obj_dist.value_and_grad(ww, bb, norm, 0.5),
        mesh=mesh,
        in_specs=(P(), P("data")),
        out_specs=(P(), P()),
    )
    v_d, g_d = jax.jit(fn)(w, batch)
    v_l, g_l = obj_local.value_and_grad(w, batch, norm, 0.5)
    np.testing.assert_allclose(v_d, v_l, rtol=1e-5)
    np.testing.assert_allclose(g_d, g_l, rtol=1e-4, atol=1e-5)


def test_normalization_back_transform(rng):
    """model_to_original_space: scoring raw data with transformed coefficients
    equals scoring normalized data with trained coefficients."""
    batch, x = make_batch(rng)
    d = x.shape[1]
    norm = NormalizationContext.build(
        NormalizationType.STANDARDIZATION,
        mean=jnp.asarray(x.mean(0)), std=jnp.asarray(x.std(0)), intercept_id=d - 1)
    w = jnp.asarray(rng.normal(size=d).astype(np.float32))
    obj = GLMObjective(losses.logistic)
    margins_normed = obj.margins(w, batch, norm)
    w_raw = norm.model_to_original_space(w)
    margins_raw = obj.margins(w_raw, batch, NormalizationContext.identity())
    np.testing.assert_allclose(margins_normed, margins_raw, rtol=1e-4, atol=1e-4)


class TestSortedTransposeLayout:
    """SparseFeatures.with_transpose(): the sorted-segment-sum gradient
    layout must match the scatter-add layout through the full objective."""

    def test_value_and_grad_equal(self, rng):
        import numpy as np

        from photon_ml_tpu.ops import losses
        from photon_ml_tpu.ops.features import SparseFeatures
        from photon_ml_tpu.ops.normalization import NormalizationContext
        from photon_ml_tpu.ops.objective import GLMBatch, GLMObjective

        n, k, d = 400, 6, 5000
        idx = jnp.asarray(rng.integers(0, d, size=(n, k)).astype(np.int32))
        val = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32))
        y = jnp.asarray((rng.random(n) < 0.5).astype(np.float32))
        w = jnp.asarray(rng.normal(size=d).astype(np.float32) * 0.1)
        obj = GLMObjective(losses.logistic)
        norm = NormalizationContext.identity()

        plain = SparseFeatures(idx, val, d)
        tr = plain.with_transpose()
        v1, g1 = obj.value_and_grad(w, GLMBatch.create(plain, y), norm, 0.1)
        v2, g2 = obj.value_and_grad(w, GLMBatch.create(tr, y), norm, 0.1)
        assert float(v2) == pytest.approx(float(v1), rel=1e-6)
        np.testing.assert_allclose(np.asarray(g2), np.asarray(g1), rtol=1e-5, atol=1e-6)

    def test_solve_through_optimizer(self, rng):
        import numpy as np

        from photon_ml_tpu.ops.features import SparseFeatures
        from photon_ml_tpu.ops.normalization import NormalizationContext
        from photon_ml_tpu.ops.objective import GLMBatch
        from photon_ml_tpu.ops.regularization import RegularizationContext
        from photon_ml_tpu.optim.common import OptimizerConfig
        from photon_ml_tpu.optim.problem import GLMOptimizationProblem
        from photon_ml_tpu.types import OptimizerType, TaskType

        n, k, d = 300, 5, 800
        idx = jnp.asarray(rng.integers(0, d, size=(n, k)).astype(np.int32))
        val = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32))
        y = jnp.asarray((rng.random(n) < 0.5).astype(np.float32))
        problem = GLMOptimizationProblem(
            TaskType.LOGISTIC_REGRESSION,
            OptimizerType.LBFGS,
            OptimizerConfig(max_iterations=25, tolerance=1e-9),
            RegularizationContext.l2(1.0),
        )
        norm = NormalizationContext.identity()
        m1, _ = problem.run(GLMBatch.create(SparseFeatures(idx, val, d), y), norm)
        m2, _ = problem.run(
            GLMBatch.create(SparseFeatures(idx, val, d).with_transpose(), y), norm
        )
        np.testing.assert_allclose(
            np.asarray(m2.coefficients.means),
            np.asarray(m1.coefficients.means),
            rtol=1e-4, atol=1e-5,
        )
