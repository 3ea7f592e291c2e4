"""The row-blocked value-and-gradient pass for padded sparse features
(``GLMObjective._blocked_value_grad_parts``) against the whole-batch pass.

Tolerances. Both passes do the same float32 products; only the sums
associate differently (by block, and the scatter-add lands on a carried
gradient instead of on zeros). A sum of n float32 terms moves by about
sqrt(n) * 2^-24 of the sum of their magnitudes when it is re-associated: for
the 2,000 rows here under 5e-6, so the value is held to 1e-5 (relative) and a
gradient entry to 1e-5 of the largest entry.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from photon_ml_tpu import training
from photon_ml_tpu.ops import losses, objective
from photon_ml_tpu.ops.features import DenseFeatures, SparseFeatures
from photon_ml_tpu.ops.normalization import NormalizationContext
from photon_ml_tpu.ops.objective import GLMBatch, GLMObjective
from photon_ml_tpu.ops.regularization import RegularizationContext
from photon_ml_tpu.optim.common import OptimizerConfig
from photon_ml_tpu.optim.problem import GLMOptimizationProblem
from photon_ml_tpu.types import NormalizationType, OptimizerType, TaskType

K, DIM = 6, 300
LOSSES = [losses.logistic, losses.squared, losses.poisson]
IDENTITY = NormalizationContext.identity()


def sparse_batch(rng, n, garbage_rows=0):
    """n real rows, then ``garbage_rows`` padding rows of weight 0 whose
    values are huge (exp overflows on them)."""
    idx = rng.integers(0, DIM, (n + garbage_rows, K)).astype(np.int32)
    val = rng.normal(size=(n + garbage_rows, K)).astype(np.float32) * 0.5
    val[n:] = 1e4
    y = rng.integers(0, 2, n + garbage_rows).astype(np.float32)
    off = rng.normal(size=n + garbage_rows).astype(np.float32) * 0.1
    wts = rng.random(n + garbage_rows).astype(np.float32) + 0.5
    wts[n:] = 0.0
    return GLMBatch(SparseFeatures(jnp.asarray(idx), jnp.asarray(val), DIM),
                    jnp.asarray(y), jnp.asarray(off), jnp.asarray(wts))


def coefficients(rng):
    return jnp.asarray(rng.normal(size=DIM).astype(np.float32) * 0.2)


def standardization(rng):
    return NormalizationContext.build(
        NormalizationType.STANDARDIZATION,
        mean=jnp.asarray(rng.normal(size=DIM).astype(np.float32) * 0.1),
        std=jnp.asarray(rng.random(DIM).astype(np.float32) + 0.5),
        intercept_id=DIM - 1)


def blocks_of_64_rows(monkeypatch):
    """For the (n, 6) batches of this file."""
    monkeypatch.setattr(objective, "ROW_BLOCK_NNZ", 64 * K)


def one_block(monkeypatch):
    monkeypatch.setattr(objective, "ROW_BLOCK_NNZ", 1 << 40)


def assert_same(got, want):
    (v1, g1), (v0, g0) = got, want
    np.testing.assert_allclose(v1, v0, rtol=1e-5)
    np.testing.assert_allclose(
        g1, g0, rtol=0, atol=1e-5 * float(jnp.max(jnp.abs(g0))))


CASES = {
    # rows, zero-weight garbage rows, normalization with shifts and factors
    "divides": (2048, 0, False),
    "tail": (2000, 0, False),
    "fewer-than-two-blocks": (100, 0, False),
    "garbage-padding": (1990, 58, False),
    "garbage-padding-in-tail": (1984, 30, False),
    "shifts-and-factors": (2000, 0, True),
}


@pytest.mark.parametrize("loss", LOSSES, ids=lambda l: l.name)
@pytest.mark.parametrize("case", sorted(CASES))
def test_blocked_pass_matches_one_block(rng, monkeypatch, loss, case):
    rows, garbage, normed = CASES[case]
    batch = sparse_batch(rng, rows, garbage)
    norm = standardization(rng) if normed else IDENTITY
    w, obj = coefficients(rng), GLMObjective(loss)
    want = obj.value_and_grad(w, batch, norm, 0.7)
    assert objective._block_rows(batch.features) is None
    blocks_of_64_rows(monkeypatch)
    assert objective._block_rows(batch.features) == 64
    got = obj.value_and_grad(w, batch, norm, 0.7)
    assert np.isfinite(got[0]) and np.all(np.isfinite(got[1]))
    assert_same(got, want)


@pytest.mark.parametrize("check_vma", [True, False])
def test_blocked_pass_under_shard_map(rng, monkeypatch, check_vma):
    """Each device walks its own 250 local rows in blocks of 64 and a tail
    of 58; the psum follows the scan."""
    batch, w = sparse_batch(rng, 8 * 250), coefficients(rng)
    norm = standardization(rng)
    blocks_of_64_rows(monkeypatch)
    mapped = shard_map(
        lambda ww, bb: GLMObjective(losses.logistic, axis_name="data")
        .value_and_grad(ww, bb, norm, 0.5),
        mesh=Mesh(np.array(jax.devices()), ("data",)),
        in_specs=(P(), P("data")), out_specs=(P(), P()), check_vma=check_vma)
    text = str(jax.make_jaxpr(mapped)(w, batch))
    assert "scan" in text and "psum" in text
    got = jax.jit(mapped)(w, batch)
    one_block(monkeypatch)
    assert_same(got, GLMObjective(losses.logistic).value_and_grad(
        w, batch, norm, 0.5))


def vg_jaxpr(batch, norm=IDENTITY):
    obj = GLMObjective(losses.logistic)
    return str(jax.make_jaxpr(
        lambda w: obj.value_and_grad(w, batch, norm, 0.5))(
            jnp.zeros((batch.dim,), jnp.float32)))


@pytest.mark.parametrize("rows", [1, 63, 64], ids=lambda r: f"{r}-rows")
def test_batch_at_or_under_the_target_is_todays_pass(rng, monkeypatch, rows):
    """No scan of length 1: the jaxpr is the one the whole-batch branch
    gives when nothing can block."""
    batch = sparse_batch(rng, rows)
    one_block(monkeypatch)
    want = vg_jaxpr(batch)
    blocks_of_64_rows(monkeypatch)
    got = vg_jaxpr(batch)
    assert got == want
    for absent in ("scan", "dynamic_slice", "optimization_barrier"):
        assert absent not in got


def test_shipped_target_leaves_small_batches_alone(rng):
    """At the constant the package ships, the batches the other tests and
    the per-entity coordinates use are one block."""
    assert objective.ROW_BLOCK_NNZ >= 1 << 16
    assert "scan" not in vg_jaxpr(sparse_batch(rng, 2048))


def test_sorted_transpose_keeps_the_unblocked_pass(rng, monkeypatch):
    batch = sparse_batch(rng, 500)
    blocks_of_64_rows(monkeypatch)
    # (the gradient leaves the blocked pass behind a barrier, so that the
    # solver's norm of it is fused as after the whole-batch pass)
    assert "scan" in vg_jaxpr(batch) and "optimization_barrier" in vg_jaxpr(batch)
    sorted_batch = GLMBatch(batch.features.with_transpose(), batch.labels,
                            batch.offsets, batch.weights)
    assert objective._block_rows(sorted_batch.features) is None
    assert "scan" not in vg_jaxpr(sorted_batch)


def test_dense_features_never_block(rng, monkeypatch):
    blocks_of_64_rows(monkeypatch)
    x = jnp.asarray(rng.normal(size=(500, 16)), jnp.float32)
    batch = GLMBatch.create(DenseFeatures(x), jnp.zeros((500,), jnp.float32))
    assert objective._block_rows(batch.features) is None
    assert "scan" not in vg_jaxpr(batch)


@pytest.mark.parametrize("shape,target,want", [
    ((1 << 22, 64), 1 << 22, 1 << 16),  # the benchmark cell at a 4 Mi target
    ((1 << 16, 64), 1 << 22, None),      # exactly the target: one block
    ((1 << 16, 65), 1 << 22, 64520),     # just over: a multiple of 8 rows
    ((1000, 7), 64, 8),                  # 9 rows fit, 8 are taken
    ((1000, 20), 64, 3),                 # under 8 rows a block: as many as fit
    ((10, 100), 64, 1),                  # a row wider than the target
], ids=lambda v: str(v).replace(" ", ""))
def test_rows_per_block_come_from_the_shape(monkeypatch, shape, target, want):
    monkeypatch.setattr(objective, "ROW_BLOCK_NNZ", target)
    feats = jax.eval_shape(lambda: SparseFeatures(
        jnp.zeros(shape, jnp.int32), jnp.zeros(shape, jnp.float32), 1 << 21))
    assert objective._block_rows(feats) == want


@pytest.mark.parametrize("method", ["value", "hessian_vector", "hessian_diagonal",
                                    "margins"])
def test_other_passes_stay_whole_batch(rng, monkeypatch, method):
    blocks_of_64_rows(monkeypatch)
    batch, w = sparse_batch(rng, 500), coefficients(rng)
    obj = GLMObjective(losses.logistic)
    args = {"value": (w, batch, IDENTITY, 0.5),
            "hessian_vector": (w, w, batch, IDENTITY, 0.5),
            "hessian_diagonal": (w, batch, IDENTITY, 0.5),
            "margins": (w, batch, IDENTITY)}[method]
    text = str(jax.make_jaxpr(getattr(obj, method))(*args))
    assert "scan" not in text and "dynamic_slice" not in text


def lbfgs_problem():
    """Four iterations from zero, as the benchmark cell runs two: far from
    the optimum every Armijo test is decided by much more than the passes'
    1e-5. (Near it the objective's changes fall under float32's resolution
    and any re-association may flip a trial.)"""
    return GLMOptimizationProblem(
        TaskType.LOGISTIC_REGRESSION, OptimizerType.LBFGS,
        OptimizerConfig(max_iterations=4, tolerance=0.0),
        RegularizationContext.l2(1.0))


def counted_solve(monkeypatch, batch, grid=training.train_glm_grid):
    """One solve through ``grid`` and how many times the compiled program
    evaluated ``value_and_grad``."""
    calls = []
    inner = GLMObjective.value_and_grad

    def counting(self, w, b, norm, l2_weight=0.0):
        jax.debug.callback(lambda: calls.append(1))
        return inner(self, w, b, norm, l2_weight)

    monkeypatch.setattr(GLMObjective, "value_and_grad", counting)
    training._solve.clear_cache()
    trained = grid(lbfgs_problem(), batch, IDENTITY, [1.0])
    jax.block_until_ready(trained.results[0].coefficients)
    jax.effects_barrier()
    monkeypatch.setattr(GLMObjective, "value_and_grad", inner)
    training._solve.clear_cache()
    return trained.results[0], len(calls)


@pytest.mark.parametrize("rows", [2048, 2000], ids=["divides", "tail"])
def test_lbfgs_solve_takes_the_same_steps_blocked(rng, monkeypatch, rows):
    batch = sparse_batch(rng, rows)
    blocks_of_64_rows(monkeypatch)
    blocked, blocked_evals = counted_solve(monkeypatch, batch)
    one_block(monkeypatch)
    whole, whole_evals = counted_solve(monkeypatch, batch)
    assert int(blocked.iterations) == int(whole.iterations) == 4
    assert blocked_evals == whole_evals >= int(whole.iterations) + 1
    assert int(blocked.reason) == int(whole.reason)
    np.testing.assert_allclose(blocked.value, whole.value, rtol=1e-5)
    # a solve amplifies the passes' 1e-5: the minimiser moves by the
    # gradient's change over the curvature (L2 1.0 bounds it from below)
    np.testing.assert_allclose(
        blocked.coefficients, whole.coefficients, rtol=0,
        atol=1e-4 * float(jnp.max(jnp.abs(whole.coefficients))))


def test_vmapped_grid_blocks_too(rng, monkeypatch):
    """``train_glm_grid_vmapped`` batches the coefficients, not the rows: the
    carried gradient gets the lambdas' axis."""
    batch = sparse_batch(rng, 500)
    blocks_of_64_rows(monkeypatch)
    blocked = training.train_glm_grid_vmapped(
        lbfgs_problem(), batch, IDENTITY, [1.0, 4.0])
    one_block(monkeypatch)
    whole = training.train_glm_grid_vmapped(
        lbfgs_problem(), batch, IDENTITY, [1.0, 4.0])
    for got, want in zip(blocked.results, whole.results):
        assert int(got.iterations) == int(want.iterations)
        np.testing.assert_allclose(
            got.coefficients, want.coefficients, rtol=0,
            atol=1e-4 * float(jnp.max(jnp.abs(want.coefficients))))
