"""End-to-end GAME training: coordinate descent on synthetic GLMix data.

(Reference analogue: integTest cli/game/training/DriverTest.scala:44-393 —
train fixed / random / full models, assert output shapes + metric wiring;
BaseGLMIntegTest-style statistical validators instead of exact weights.)
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from photon_ml_tpu.algorithm import (
    CoordinateDescent,
    FixedEffectCoordinate,
    RandomEffectCoordinate,
)
from photon_ml_tpu.data.game import (
    RandomEffectDataConfig,
    build_fixed_effect_batch,
    build_random_effect_dataset,
)
from photon_ml_tpu.evaluation import area_under_roc_curve
from photon_ml_tpu.ops import losses
from photon_ml_tpu.optim.common import OptimizerConfig
from photon_ml_tpu.optim.problem import GLMOptimizationProblem
from photon_ml_tpu.ops.regularization import RegularizationContext
from photon_ml_tpu.types import OptimizerType, TaskType

from game_test_utils import make_glmix_data


@pytest.fixture(scope="module")
def glmix():
    rng = np.random.default_rng(42)
    data, truth = make_glmix_data(
        rng, num_users=15, rows_per_user_range=(20, 60), d_fixed=6, d_random=3
    )
    return data, truth


def build_coordinates(data, re_cfg=None):
    fixed_batch = build_fixed_effect_batch(data, "global", dense=True)
    fixed = FixedEffectCoordinate(
        fixed_batch,
        GLMOptimizationProblem(
            TaskType.LOGISTIC_REGRESSION,
            OptimizerType.LBFGS,
            OptimizerConfig(max_iterations=50, tolerance=1e-7),
            RegularizationContext.l2(1e-2),
        ),
    )
    re_cfg = re_cfg or RandomEffectDataConfig("userId", "per_user")
    re_ds = build_random_effect_dataset(data, re_cfg)
    random = RandomEffectCoordinate(
        re_ds,
        TaskType.LOGISTIC_REGRESSION,
        OptimizerType.LBFGS,
        OptimizerConfig(max_iterations=40, tolerance=1e-6),
        RegularizationContext.l2(1e-1),
    )
    return fixed, random


def test_coordinate_descent_glmix(glmix):
    data, truth = glmix
    fixed, random = build_coordinates(data)
    n = data.num_rows
    labels = jnp.asarray(data.response)
    loss_fn = lambda scores: jnp.sum(losses.logistic.loss(scores, labels))

    cd = CoordinateDescent({"fixed": fixed, "random": random}, loss_fn)
    result = cd.run(num_iterations=2, num_rows=n)

    # objective decreases over updates
    hist = result.objective_history
    assert hist[-1] < hist[0]
    # GAME model separates classes far better than fixed effect alone
    auc_game = float(area_under_roc_curve(result.total_scores, labels))

    cd_fixed = CoordinateDescent({"fixed": build_coordinates(data)[0]}, loss_fn)
    result_fixed = cd_fixed.run(num_iterations=1, num_rows=n)
    auc_fixed = float(area_under_roc_curve(result_fixed.total_scores, labels))

    assert auc_game > auc_fixed + 0.02, (auc_game, auc_fixed)
    assert auc_game > 0.9, auc_game

    # total score == sum of coordinate scores (GAMEModel.scala:92-94)
    total = sum(
        np.asarray(
            (fixed if name == "fixed" else random).score(result.coefficients[name])
        )
        for name in ("fixed", "random")
    )
    np.testing.assert_allclose(np.asarray(result.total_scores), total, rtol=1e-4, atol=1e-4)


def test_random_effect_recovers_per_user_signal(glmix):
    """With no fixed effect, per-user solves should approximate w_users on
    entities with enough data."""
    data, truth = glmix
    _, random = build_coordinates(data)
    n = data.num_rows
    zero_off = jnp.zeros((n,), jnp.float32)
    # include the fixed-effect part of the margin as offsets (oracle), so the
    # random-effect solve sees exactly its own residual problem
    oracle_off = jnp.asarray(truth["x_fixed"] @ truth["w_fixed"])
    coeffs, results = jax.jit(random.update)(oracle_off, random.initial_coefficients())
    # scoring correlation with the true per-user margin component
    score = np.asarray(random.score(coeffs))
    true_component = np.sum(
        truth["x_random"] * truth["w_users"][truth["user_of_row"]], axis=1
    )
    corr = np.corrcoef(score, true_component)[0, 1]
    assert corr > 0.85, corr


def test_tron_random_effect(glmix):
    data, truth = glmix
    re_ds = build_random_effect_dataset(data, RandomEffectDataConfig("userId", "per_user"))
    random = RandomEffectCoordinate(
        re_ds,
        TaskType.LOGISTIC_REGRESSION,
        OptimizerType.TRON,
        OptimizerConfig(max_iterations=10, tolerance=1e-5),
        RegularizationContext.l2(1e-1),
    )
    n = data.num_rows
    coeffs, results = jax.jit(random.update)(
        jnp.zeros((n,), jnp.float32), random.initial_coefficients()
    )
    assert np.all(np.isfinite(np.asarray(coeffs)))
    # per-entity convergence reasons are tracked per lane
    assert results.reason.shape == (re_ds.num_entities,)
    assert np.all(np.asarray(results.reason) > 0)


def test_sharded_random_effect_update(glmix):
    """Entity axis sharded over the mesh: vmapped solves distribute."""
    data, truth = glmix
    n_dev = len(jax.devices())
    re_cfg = RandomEffectDataConfig("userId", "per_user", num_shards=n_dev)
    re_ds = build_random_effect_dataset(data, re_cfg)
    assert re_ds.num_entities % n_dev == 0
    random = RandomEffectCoordinate(
        re_ds, TaskType.LOGISTIC_REGRESSION, OptimizerType.LBFGS,
        OptimizerConfig(max_iterations=30, tolerance=1e-6),
        RegularizationContext.l2(1e-1),
    )
    n = data.num_rows

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()), ("entity",))
    sharding = NamedSharding(mesh, P("entity"))
    w0 = jax.device_put(random.initial_coefficients(), sharding)
    coeffs, _ = jax.jit(random.update)(jnp.zeros((n,), jnp.float32), w0)
    coeffs_local, _ = jax.jit(random.update)(
        jnp.zeros((n,), jnp.float32), random.initial_coefficients()
    )
    np.testing.assert_allclose(np.asarray(coeffs), np.asarray(coeffs_local),
                               rtol=1e-4, atol=1e-4)


def test_fused_cycle_matches_unfused(glmix):
    """fused_cycle=True (one XLA program per full iteration) must reproduce
    the per-update loop exactly: same coefficients, same objective history
    length and values, same total scores."""
    data, _ = glmix
    n = data.num_rows
    labels = jnp.asarray(data.response)
    loss_fn = lambda scores: jnp.sum(losses.logistic.loss(scores, labels))

    results = {}
    for fused in (False, True):
        fixed, random = build_coordinates(data)
        cd = CoordinateDescent(
            {"fixed": fixed, "random": random}, loss_fn, fused_cycle=fused
        )
        results[fused] = cd.run(num_iterations=2, num_rows=n)

    a, b = results[False], results[True]
    assert len(a.objective_history) == len(b.objective_history) == 4
    np.testing.assert_allclose(
        np.asarray(b.objective_history), np.asarray(a.objective_history),
        rtol=1e-5,
    )
    for name in ("fixed", "random"):
        np.testing.assert_allclose(
            np.asarray(b.coefficients[name]), np.asarray(a.coefficients[name]),
            rtol=1e-4, atol=1e-5,
        )
    np.testing.assert_allclose(
        np.asarray(b.total_scores), np.asarray(a.total_scores), rtol=1e-4, atol=1e-4
    )
    assert b.path == "fused-cycle"


def test_fused_cycle_checkpoint_iteration_granularity(glmix, tmp_path):
    """Fused-cycle checkpoints land at iteration boundaries and resume
    bit-exactly into a fresh fused run."""
    from photon_ml_tpu.checkpoint import CoordinateDescentCheckpointer

    data, _ = glmix
    n = data.num_rows
    labels = jnp.asarray(data.response)
    loss_fn = lambda scores: jnp.sum(losses.logistic.loss(scores, labels))

    def make_cd():
        fixed, random = build_coordinates(data)
        return CoordinateDescent(
            {"fixed": fixed, "random": random}, loss_fn, fused_cycle=True
        )

    ck = CoordinateDescentCheckpointer(str(tmp_path / "ck"), run_fingerprint="f")
    full = make_cd().run(num_iterations=2, num_rows=n, checkpointer=ck)
    assert ck.latest_step() == 4  # 2 iterations x 2 coordinates

    # resume from the checkpoint: no further iterations needed, identical state
    resumed = make_cd().run(num_iterations=2, num_rows=n,
                            checkpointer=CoordinateDescentCheckpointer(
                                str(tmp_path / "ck"), run_fingerprint="f"))
    np.testing.assert_array_equal(
        np.asarray(resumed.total_scores), np.asarray(full.total_scores)
    )


def test_fused_resume_rejects_mid_iteration_checkpoint(glmix, tmp_path):
    """A per-update checkpoint taken MID-iteration cannot resume into
    fused-cycle mode (which replays whole iterations): the guard must raise
    with guidance instead of silently recomputing or skipping updates."""
    import shutil

    from photon_ml_tpu.checkpoint import CoordinateDescentCheckpointer

    data, _ = glmix
    n = data.num_rows
    labels = jnp.asarray(data.response)
    loss_fn = lambda scores: jnp.sum(losses.logistic.loss(scores, labels))

    fixed, random = build_coordinates(data)
    ck_dir = str(tmp_path / "ck")
    cd = CoordinateDescent({"fixed": fixed, "random": random}, loss_fn)
    cd.run(num_iterations=1, num_rows=n,
           checkpointer=CoordinateDescentCheckpointer(ck_dir, run_fingerprint="x"))
    # drop the iteration-final checkpoint so only the mid-iteration one
    # (after coordinate 1 of 2) remains
    shutil.rmtree(str(tmp_path / "ck" / "step-2"))
    ck = CoordinateDescentCheckpointer(ck_dir, run_fingerprint="x")
    assert ck.latest_step() == 1

    fixed2, random2 = build_coordinates(data)
    cd_fused = CoordinateDescent(
        {"fixed": fixed2, "random": random2}, loss_fn, fused_cycle=True
    )
    with pytest.raises(ValueError, match="mid-iteration"):
        cd_fused.run(num_iterations=1, num_rows=n, checkpointer=ck)


def test_trackers_surface_per_coordinate_convergence(glmix):
    """CoordinateDescentResult.trackers: the last update's OptResult per
    coordinate (per-entity stacked for random effects) — the reference's
    OptimizationTracker raw material."""
    from photon_ml_tpu.optim.common import OptResult
    from photon_ml_tpu.types import ConvergenceReason

    data, _ = glmix
    n = data.num_rows
    labels = jnp.asarray(data.response)
    loss_fn = lambda scores: jnp.sum(losses.logistic.loss(scores, labels))
    fixed, random = build_coordinates(data)
    cd = CoordinateDescent({"fixed": fixed, "random": random}, loss_fn)
    result = cd.run(num_iterations=1, num_rows=n)

    assert set(result.trackers) == {"fixed", "random"}
    fe = result.trackers["fixed"]
    assert isinstance(fe, OptResult) and np.asarray(fe.reason).ndim == 0
    assert int(fe.iterations) > 0
    re = result.trackers["random"]
    reasons = np.asarray(re.reason)
    assert reasons.shape == (random.num_entities,)
    valid = {r.value for r in ConvergenceReason}
    assert set(np.unique(reasons).tolist()) <= valid

    # fused mode documents empty trackers
    fixed2, random2 = build_coordinates(data)
    cd_f = CoordinateDescent(
        {"fixed": fixed2, "random": random2}, loss_fn, fused_cycle=True
    )
    assert cd_f.run(num_iterations=1, num_rows=n).trackers == {}


def test_summarize_tracker_formats_all_shapes(glmix):
    """_summarize_tracker must actually emit text for every tracker shape
    (OptResult is a NamedTuple, i.e. a tuple — the bucketed branch must not
    shadow it)."""
    from photon_ml_tpu.cli.game_training_driver import _summarize_tracker

    data, _ = glmix
    n = data.num_rows
    labels = jnp.asarray(data.response)
    loss_fn = lambda scores: jnp.sum(losses.logistic.loss(scores, labels))
    fixed, random = build_coordinates(data)
    cd = CoordinateDescent({"fixed": fixed, "random": random}, loss_fn)
    result = cd.run(num_iterations=1, num_rows=n)

    fe_summary = _summarize_tracker(result.trackers["fixed"])
    assert "reason=" in fe_summary and "iters=" in fe_summary
    re_summary = _summarize_tracker(result.trackers["random"])
    assert "convergenceReasons=" in re_summary
    assert f"entities={random.num_entities}" in re_summary
    # bucketed trackers: a tuple OF OptResults renders per bucket
    both = _summarize_tracker((result.trackers["random"], result.trackers["random"]))
    assert both.count("convergenceReasons=") == 2 and "bucket0:" in both
    assert _summarize_tracker(None) == ""


def test_distributed_trackers_are_trimmed_at_source(glmix):
    """Entity-sharded solvers must return trackers covering REAL entities
    only — the padding pseudo-solves the mesh adds are trimmed before any
    consumer sees them (trim_entity_tracker), so convergence logs are not
    skewed by zero-row lanes."""
    from photon_ml_tpu.algorithm.random_effect import RandomEffectCoordinate
    from photon_ml_tpu.data.game import (
        RandomEffectDataConfig,
        build_random_effect_dataset,
    )
    from photon_ml_tpu.parallel import MeshContext, data_mesh
    from photon_ml_tpu.parallel.distributed import DistributedRandomEffectSolver

    data, _ = glmix
    ds = build_random_effect_dataset(
        data, RandomEffectDataConfig("userId", "per_user")
    )
    coord = RandomEffectCoordinate(
        ds,
        TaskType.LOGISTIC_REGRESSION,
        OptimizerType.LBFGS,
        OptimizerConfig(max_iterations=10, tolerance=1e-7),
        RegularizationContext.l2(0.1),
    )
    solver = DistributedRandomEffectSolver(coord, MeshContext(data_mesh(8)))
    assert solver.padded_entities > ds.num_entities  # padding actually happens
    resid = jnp.zeros((data.num_rows,), jnp.float32)
    coefs, tracker = solver.update(resid, solver.initial_coefficients())
    # coefficients keep the padded sharded shape; the tracker does not
    assert coefs.shape[0] == solver.padded_entities
    assert np.asarray(tracker.reason).shape[0] == ds.num_entities
    assert np.asarray(tracker.iterations).shape[0] == ds.num_entities


def test_full_game_four_coordinate_cycle():
    """make_full_game_data (BASELINE config-5 shape) through coordinate
    descent with the SHARED 4-coordinate stack (make_full_game_coords —
    the same wiring bench.py times): objective decreases across cycles,
    scores finite, AUC strong, fused == unfused."""
    from game_test_utils import make_full_game_coords, make_full_game_data

    rng = np.random.default_rng(9)
    data, _ = make_full_game_data(
        rng, num_users=20, num_items=8, num_artists=4,
        rows_per_user_range=(6, 12),
        d_fixed=5, d_user=3, d_item=3, d_artist=4,
    )
    n = data.num_rows
    coords = make_full_game_coords(
        data, fe_iters=20, re_iters=15, mf_re_iters=8, latent_dim=2
    )
    labels = jnp.asarray(data.response)
    loss_fn = lambda scores: jnp.sum(losses.logistic.loss(scores, labels))
    for fused in (False, True):
        cd = CoordinateDescent(coords, loss_fn, fused_cycle=fused)
        result = cd.run(num_iterations=2, num_rows=n)
        objs = result.objective_history
        assert len(objs) == 8  # 2 iterations x 4 coordinates
        # descent across full cycles (per-update values can wiggle when a
        # coordinate re-fits against new residuals)
        assert objs[-1] <= objs[0]
        total = np.asarray(result.total_scores)
        assert np.isfinite(total).all()
        from photon_ml_tpu.evaluation import area_under_roc_curve

        assert float(area_under_roc_curve(result.total_scores, labels)) > 0.8


@pytest.mark.slow  # ~8s: warm-start-from-initial-params stays tier-1 via test_retrain.py's warm-start pins and test_vmapped_grid.py test_grid_warm_start_reaches_same_optima
def test_initial_params_warm_start(glmix):
    """run(initial_params=...) seeds named coordinates from a previous
    result (the grid warm-start hook): a second run warm-started from a
    converged fit must land on the same solution and not regress the
    objective on its first update."""
    data, _ = glmix
    fixed, random = build_coordinates(data)
    labels = jnp.asarray(data.response)
    loss_fn = lambda scores: jnp.sum(losses.logistic.loss(scores, labels))
    cd = CoordinateDescent({"fixed": fixed, "random": random}, loss_fn)
    first = cd.run(num_iterations=3, num_rows=data.num_rows)

    f2, r2 = build_coordinates(data)
    cd2 = CoordinateDescent({"fixed": f2, "random": r2}, loss_fn)
    warm = cd2.run(
        num_iterations=1, num_rows=data.num_rows,
        initial_params=first.coefficients,
    )
    # warm-started single iteration stays at/below the 3-iteration
    # objective (the warm params' scores seed the residuals, so update one
    # CONTINUES the descent rather than restarting it) ...
    assert warm.objective_history[-1] <= first.objective_history[-1] + 1e-3
    # ... and beats a cold single iteration
    f4, r4 = build_coordinates(data)
    cold = CoordinateDescent({"fixed": f4, "random": r4}, loss_fn).run(
        num_iterations=1, num_rows=data.num_rows
    )
    assert warm.objective_history[-1] <= cold.objective_history[-1] + 1e-3
    # partial maps fall back to the coordinate's own init for missing names
    only_fixed = {"fixed": first.coefficients["fixed"]}
    f3, r3 = build_coordinates(data)
    cd3 = CoordinateDescent({"fixed": f3, "random": r3}, loss_fn)
    partial = cd3.run(
        num_iterations=1, num_rows=data.num_rows, initial_params=only_fixed
    )
    assert np.isfinite(partial.objective_history[-1])
