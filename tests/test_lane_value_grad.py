"""The per-entity lanes' one-pass value and gradient: the lane-block kernel
(``ops/fused_glm.lane_value_grad_parts``, interpreter mode on the CPU), its
selection, and the batching rule of ``algorithm/random_effect.entity_lane_fns``
that hands it the lanes. The chip's compiler holds the kernel at the GLMix
cell's shape in ``tests/test_dense_grid_reference.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.ops import fused_glm, losses
from photon_ml_tpu.ops.features import DenseFeatures

#: (lanes, rows, features, lanes a block): the GLMix cell's rows and width
#: with lanes no multiple of the block; fewer rows than a vector's lanes at a
#: width no multiple of 8; more lanes than one block, all of them whole
SHAPES = [(20, 512, 22, 8), (13, 40, 13, 8), (48, 128, 22, 16)]


def _lanes(rng, e, m, d, loss_name="logistic"):
    """E problems of M rows, each lane's rows past its own count padding of
    weight 0, whose labels are nan and offsets make the loss inf or nan."""
    x = rng.normal(size=(e, m, d)).astype(np.float32) / np.sqrt(d)
    y = (rng.random((e, m)) < 0.5).astype(np.float32)
    if loss_name == "poisson":
        y = rng.poisson(1.5, size=(e, m)).astype(np.float32)
    count = rng.integers(1, m + 1, size=(e, 1))
    wt = np.where(np.arange(m) < count, rng.uniform(0.5, 2.0, size=(e, m)), 0.0)
    off = rng.normal(scale=0.3, size=(e, m)).astype(np.float32)
    dead = wt == 0.0
    off[dead] = np.where(rng.random(dead.sum()) < 0.5, np.inf, 1e4)
    y[dead] = np.nan
    w = rng.normal(scale=0.5, size=(e, d)).astype(np.float32)
    return tuple(jnp.asarray(a, jnp.float32) for a in (x, y, wt, off, w))


def _two_pass(loss, x, y, wt, off, w):
    """Each lane's (loss sum, X^T d, sum d) as the XLA path makes them (the
    dense products vmapped over the lanes, the loss and slope between), and
    the mass of each sum's terms: the scale of its float32 rounding."""

    def one(x, y, wt, off, w):
        feats = DenseFeatures(x)
        z = feats.matvec(w) + off
        wl = jnp.where(wt > 0, wt * loss.loss(z, y), 0.0)
        d = jnp.where(wt > 0, wt * loss.d1(z, y), 0.0)
        parts = jnp.sum(wl), feats.rmatvec(d), jnp.sum(d)
        mass = (jnp.sum(jnp.abs(wl)), jnp.abs(d) @ jnp.abs(x), jnp.sum(jnp.abs(d)))
        return parts, mass

    return jax.jit(jax.vmap(one))(x, y, wt, off, w)


def _parts(x, y, wt, off, w, loss=losses.logistic, block=fused_glm._LANE_BLOCK):
    return jax.jit(
        lambda *a: fused_glm.lane_value_grad_parts(loss, *a, block)
    )(x, y, wt, off, w)


@pytest.mark.parametrize("e,m,d,block", SHAPES)
@pytest.mark.parametrize("loss_name", ["logistic", "squared", "poisson"])
def test_parts_match_the_two_pass_products(rng, loss_name, e, m, d, block):
    """Loss sum, gradient and ``sum(d)`` of every lane agree with the
    vmapped two-pass path to float32 rounding of their terms' mass; padding
    rows add exactly nothing though their loss is inf or nan."""
    loss = getattr(losses, loss_name)
    lanes = _lanes(rng, e, m, d, loss_name)
    got = _parts(*lanes, loss=loss, block=block)
    want, mass = _two_pass(loss, *lanes)
    # each side a sum of m terms whose margins are sums of d: both within
    # (m + d) roundings of the mass
    ulps = (m + d) * np.finfo(np.float32).eps
    for g, r, s in zip(got, want, mass):
        assert g.shape == r.shape and np.isfinite(np.asarray(g)).all()
        assert np.all(np.abs(np.asarray(g) - np.asarray(r)) <= ulps * np.asarray(s) + 1e-30)


@pytest.mark.parametrize("company", ["8 lanes", "13 lanes", "64 lanes", "permuted"])
def test_a_lanes_bits_do_not_depend_on_its_company(rng, company):
    """A lane's three outputs are the same bits called among 8, 13 or 64 of
    100 lanes, or with the 100 in another order: the scheduler compacts lanes
    into batches of other sizes and holds their coefficients to the one-shot
    solve's bit for bit."""
    lanes = _lanes(rng, 100, 128, 22)
    alone = _parts(*lanes)
    if company == "permuted":
        order = rng.permutation(100)
        got = _parts(*(a[order] for a in lanes))
        got = tuple(g[np.argsort(order)] for g in got)
        want = alone
    else:
        n = int(company.split()[0])
        got = _parts(*(a[100 - n:] for a in lanes))
        want = tuple(a[100 - n:] for a in alone)
    for g, r in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


# -- the selection: platform, dtype and (M, D), never the number of lanes ------


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(fused_glm, "_on_tpu", lambda: True)
    return fused_glm


@pytest.mark.parametrize("m,d,dtype,want", [
    (512, 22, "float32", fused_glm._LANE_BLOCK),   # the GLMix cell
    (128, 1, "float32", fused_glm._LANE_BLOCK),
    (512, 126, "float32", fused_glm._LANE_BLOCK),  # the gradient and two sums fill a lane-row
    (512, 127, "float32", None),
    (40, 22, "float32", None),                     # rows short of a vector's lanes
    (640, 22, "float32", fused_glm._LANE_BLOCK),
    (512, 22, "bfloat16", None),
    (512, 22, "float64", None),
    (4096, 100, "float32", 16),                    # long rows: fewer lanes a step fit VMEM
    (1 << 15, 100, "float32", None),               # not even 8
])
def test_the_block_follows_from_platform_dtype_and_shape(on_tpu, m, d, dtype, want):
    assert on_tpu.select_lane_block(m, d, jnp.dtype(dtype)) == want


def test_off_a_tpu_no_block_is_selected():
    assert fused_glm.select_lane_block(512, 22, jnp.float32) is None


# -- the batching rule inside the per-entity solves ------------------------------


def _solve(lanes, optimizer, gspmd=False):
    from photon_ml_tpu.algorithm.random_effect import entity_lane_fns
    from photon_ml_tpu.optim.common import OptimizerConfig
    from photon_ml_tpu.ops.regularization import RegularizationContext
    from photon_ml_tpu.types import OptimizerType, TaskType

    optimizer = OptimizerType[optimizer]
    cfg = (OptimizerConfig.tron_default() if optimizer == OptimizerType.TRON
           else OptimizerConfig.lbfgs_default())
    solve_one, *_ = entity_lane_fns(
        TaskType.LOGISTIC_REGRESSION, optimizer, cfg,
        RegularizationContext.l2(1.0), gspmd=gspmd)
    x, y, wt, off, _ = lanes
    args = (x, jnp.nan_to_num(y), jnp.where(wt > 0, off, 0.0), wt,
            jnp.zeros((x.shape[0], x.shape[2]), jnp.float32))
    jaxpr = str(jax.make_jaxpr(jax.vmap(solve_one))(*args))
    return jax.jit(jax.vmap(solve_one))(*args), "pallas_call" in jaxpr


@pytest.mark.parametrize("optimizer", ["LBFGS", "TRON"])
def test_the_lanes_solve_through_the_kernel_where_it_is_selected(rng, monkeypatch, optimizer):
    """With a block selected the vmapped solve's evaluations are the
    kernel's, and its coefficients those of the two-pass solve up to the
    rounding that steers a few lanes' float32 stopping by an iteration;
    with none selected (off a TPU) or where GSPMD partitions the lanes it
    is the two-pass solve bit for bit."""
    lanes = _lanes(rng, 21, 128, 6)
    plain, used = _solve(lanes, optimizer)
    assert not used
    monkeypatch.setattr(fused_glm, "_on_tpu", lambda: True)
    fused, used = _solve(lanes, optimizer)
    assert used
    np.testing.assert_allclose(
        np.asarray(fused.coefficients), np.asarray(plain.coefficients),
        rtol=0, atol=1e-3 * float(jnp.max(jnp.abs(plain.coefficients))))
    sharded, used = _solve(lanes, optimizer, gspmd=True)
    assert not used
    np.testing.assert_array_equal(
        np.asarray(sharded.coefficients), np.asarray(plain.coefficients))


@pytest.mark.parametrize("sharded", [False, True])
def test_a_coordinate_on_a_gspmd_mesh_keeps_the_two_pass_products(rng, monkeypatch, sharded):
    """A scheduled coordinate hands the scheduler ``gspmd`` from its own
    ``mesh_ctx``: GSPMD partitions the lanes' XLA products, never a pallas
    call."""
    from game_test_utils import make_glmix_data

    from photon_ml_tpu.algorithm.random_effect import RandomEffectCoordinate
    from photon_ml_tpu.data.game import RandomEffectDataConfig, build_random_effect_dataset
    from photon_ml_tpu.optim import scheduler
    from photon_ml_tpu.parallel.mesh import MeshContext, data_mesh
    from photon_ml_tpu.types import TaskType

    class Handed(Exception):
        pass

    def compacted_solve(data, w0, **kw):
        raise Handed(kw["gspmd"])

    monkeypatch.setattr(scheduler, "compacted_solve", compacted_solve)
    data, _ = make_glmix_data(rng, num_users=8)
    coord = RandomEffectCoordinate(
        build_random_effect_dataset(data, RandomEffectDataConfig("userId", "per_user")),
        TaskType.LOGISTIC_REGRESSION,
        solve_schedule=scheduler.SolveSchedule(chunk_size=4),
        mesh_ctx=MeshContext(data_mesh()) if sharded else None)
    with pytest.raises(Handed) as handed:
        coord.update(jnp.zeros((data.num_rows,), jnp.float32), coord.initial_coefficients())
    assert handed.value.args == (sharded,)
