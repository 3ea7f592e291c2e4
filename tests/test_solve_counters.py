"""Objective evaluations counted where the line search makes them
(``optim/lbfgs.py``: ``evaluations`` and ``evaluation_history`` in the
carried state and in ``OptResult``), and the solve ledger that prices the
per-entity solves in them (``optim/scheduler.py``: ``evaluation_counts``,
``solve_stats``), for the one-shot vmapped solve a descent's program runs
and for compacted solves, the one-shot solve's priced at the widths its
ladder ran each iteration at (``optim/fused_schedule.py``); the benchmark's
readers of that ledger (``benchmark/metrics/readers/counters.py``,
``widths.py``)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from game_test_utils import make_glmix_data

from photon_ml_tpu.algorithm import (
    CoordinateDescent,
    FixedEffectCoordinate,
    RandomEffectCoordinate,
)
from photon_ml_tpu.algorithm.random_effect import entity_lane_fns
from photon_ml_tpu.data.game import (
    RandomEffectDataConfig,
    build_fixed_effect_batch,
    build_random_effect_dataset,
)
from photon_ml_tpu.ops import losses
from photon_ml_tpu.ops.regularization import RegularizationContext
from photon_ml_tpu.optim.common import OptimizerConfig
from photon_ml_tpu.optim.lbfgs import lbfgs_minimize_
from photon_ml_tpu.optim.problem import GLMOptimizationProblem
from photon_ml_tpu.optim.scheduler import (
    SolveSchedule,
    compacted_solve,
    evaluation_counts,
    solve_stats,
)
from photon_ml_tpu.types import ConvergenceReason, OptimizerType, TaskType

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOGISTIC = TaskType.LOGISTIC_REGRESSION
CONFIG = OptimizerConfig(max_iterations=30, tolerance=1e-7)

# f(w) = w.Aw / 2 - b.w with the gradient reported as Aw - b + e. STALLED
# (e != 0) reports a gradient that no step descends along, so its first
# line search fails all 25 trials and it stops at iteration 1; HONEST runs
# ten iterations of mostly one trial.
STALLED = (np.eye(3), np.zeros(3), np.ones(3))
HONEST = (np.diag([1.0, 10.0, 100.0]), np.array([1.0, 2.0, 3.0]), np.zeros(3))


def counted_solve(calls):
    """An L-BFGS solve of the quadratic above whose objective counts its
    calls on the host (once per lane where vmapped)."""

    def bump(_):
        calls.append(1)

    def solve(A, b, e, w0):
        def vg(w):
            jax.debug.callback(bump, w[0])
            return 0.5 * w @ (A @ w) - b @ w, A @ w - b + e

        return lbfgs_minimize_(vg, w0, CONFIG)

    return solve


def f32(*arrays):
    return tuple(jnp.asarray(a, jnp.float32) for a in arrays)


def run_counted(solve_fn, *args):
    calls = []
    result = jax.jit(solve_fn(calls))(*args)
    jax.block_until_ready(result)
    jax.effects_barrier()
    return result, len(calls)


@pytest.mark.parametrize("problem", [STALLED, HONEST], ids=["stalled", "honest"])
def test_an_unbatched_solve_counts_its_objective_calls(problem):
    res, calls = run_counted(counted_solve, *f32(*problem, np.zeros(3)))
    history = np.asarray(res.evaluation_history)
    it = int(res.iterations)
    assert int(res.evaluations) == calls == history.sum()
    assert history[0] == 1 and (history[1:it + 1] >= 1).all()
    assert not history[it + 1:].any()
    assert history.shape == res.value_history.shape


def test_the_stalled_problem_stops_at_iteration_one_on_a_failed_line_search():
    res, _ = run_counted(counted_solve, *f32(*STALLED, np.zeros(3)))
    assert int(res.iterations) == 1
    assert int(res.reason) == ConvergenceReason.OBJECTIVE_NOT_IMPROVING
    assert list(np.asarray(res.evaluation_history)[:2]) == [
        1, CONFIG.max_line_search_steps]


def _lanes(lanes=12, rows=6, dim=3):
    """Per-entity logistic problems, two of them slow."""
    rng = np.random.default_rng(3)
    x = rng.normal(size=(lanes, rows, dim)).astype(np.float32)
    x[:2] *= np.geomspace(1.0, 32.0, dim).astype(np.float32)
    y = (rng.random((lanes, rows)) > 0.5).astype(np.float32)
    data = f32(x, y, np.zeros((lanes, rows)), np.ones((lanes, rows)))
    return data, jnp.zeros((lanes, dim), jnp.float32)


LANE_KW = dict(task=LOGISTIC, optimizer=OptimizerType.LBFGS,
               optimizer_config=OptimizerConfig(max_iterations=30, tolerance=1e-9),
               regularization=RegularizationContext.l2(0.5))


def _one_shot(data, w0):
    solve_one, _, _, _ = entity_lane_fns(
        LANE_KW["task"], LANE_KW["optimizer"], LANE_KW["optimizer_config"],
        LANE_KW["regularization"])
    return jax.jit(jax.vmap(solve_one))(*data, w0), jax.jit(solve_one)


def _assert_lane_counters_equal(batched, lane, one):
    assert int(batched.evaluations[lane]) == int(one.evaluations)
    assert np.array_equal(np.asarray(batched.evaluation_history[lane]),
                          np.asarray(one.evaluation_history))


def test_each_vmapped_lane_carries_its_own_unbatched_counters():
    args = [f32(*STALLED, np.zeros(3)), f32(*HONEST, np.zeros(3))]
    batched, _ = run_counted(
        lambda calls: jax.vmap(counted_solve(calls)),
        *(jnp.stack(col) for col in zip(*args)))
    for lane, one in enumerate(args):
        _assert_lane_counters_equal(batched, lane, run_counted(counted_solve, *one)[0])


def test_each_entity_lane_carries_its_own_unbatched_counters():
    """The per-entity closures the descent vmaps. On the CPU, XLA rounds a
    lane's sums in a batch of 12 and alone differently, so some lanes take
    another path; every lane that takes the unbatched one, bit for bit,
    carries the unbatched counters, and every lane's history adds up."""
    data, w0 = _lanes()
    batched, solve_one = _one_shot(data, w0)
    assert len(set(np.asarray(batched.iterations).tolist())) > 1
    same = 0
    for lane in range(w0.shape[0]):
        history = np.asarray(batched.evaluation_history[lane])
        it = int(batched.iterations[lane])
        assert history.sum() == int(batched.evaluations[lane])
        assert (history[:it + 1] >= 1).all() and not history[it + 1:].any()
        one = solve_one(*(a[lane] for a in data), w0[lane])
        if np.array_equal(np.asarray(batched.value_history[lane]),
                          np.asarray(one.value_history), equal_nan=True):
            _assert_lane_counters_equal(batched, lane, one)
            same += 1
    assert same >= w0.shape[0] // 2


def test_needed_evaluations_lie_below_what_the_loop_ran():
    """One lane stops at iteration 1 on a failed line search, the other runs
    on: the batched loop repeats the stopped lane's 25 discarded trials every
    iteration, which no lane's counter counts, and the needed count leaves
    out."""
    args = [f32(*STALLED, np.zeros(3)), f32(*HONEST, np.zeros(3))]
    batched, calls = run_counted(
        lambda calls: jax.vmap(counted_solve(calls)),
        *(jnp.stack(col) for col in zip(*args)))
    executed = calls // 2  # the callback runs once per lane an evaluation
    lane, needed = (int(v) for v in evaluation_counts(
        batched.evaluations, batched.evaluation_history))
    honest = np.asarray(batched.evaluation_history[1])
    assert lane == int(batched.evaluations.sum())
    assert needed == 1 + max(CONFIG.max_line_search_steps, honest[1]) + honest[2:].sum()
    assert lane / 2 <= needed < executed
    assert executed == 1 + CONFIG.max_line_search_steps * int(batched.iterations[1])


@pytest.mark.parametrize("loop", ["host", "device"])
def test_a_compacted_solve_hands_back_the_one_shot_counters(loop):
    data, w0 = _lanes()
    one_shot, _ = _one_shot(data, w0)
    solve_stats.reset()
    try:
        got = compacted_solve(data, w0, schedule=SolveSchedule(chunk_size=2, loop=loop),
                              label="lanes", **LANE_KW)
        assert np.array_equal(np.asarray(got.evaluations), np.asarray(one_shot.evaluations))
        assert np.array_equal(np.asarray(got.evaluation_history),
                              np.asarray(one_shot.evaluation_history))
        (rec,) = solve_stats.evaluation_snapshot()
        want = [int(v) for v in evaluation_counts(one_shot.evaluations,
                                                  one_shot.evaluation_history)]
        assert (rec.label, rec.kind, rec.lanes) == ("lanes", "compacted", 12)
        assert [rec.lane_evaluations, rec.needed_evaluations] == want
    finally:
        solve_stats.reset()


# -- the one-shot solve a descent's program runs ----------------------------


@pytest.fixture(scope="module")
def glmix():
    data, _ = make_glmix_data(np.random.default_rng(11), num_users=14)
    return data


def _descent(data):
    loss = losses.for_task(LOGISTIC)
    labels, weights = jnp.asarray(data.response), jnp.asarray(data.weight)
    fixed = FixedEffectCoordinate(
        build_fixed_effect_batch(data, "global", dense=True),
        GLMOptimizationProblem(LOGISTIC, OptimizerType.LBFGS,
                               OptimizerConfig(max_iterations=5, tolerance=1e-6),
                               RegularizationContext.l2(1e-2)))
    random = RandomEffectCoordinate(
        build_random_effect_dataset(data, RandomEffectDataConfig("userId", "per_user")),
        LOGISTIC, OptimizerType.LBFGS, OptimizerConfig(max_iterations=8, tolerance=1e-6),
        RegularizationContext.l2(1e-1), solve_label="per-user")
    return CoordinateDescent({"global": fixed, "per-user": random},
                             lambda total: jnp.sum(weights * loss.loss(total, labels)))


@pytest.fixture()
def ledger():
    solve_stats.reset()
    yield solve_stats
    solve_stats.reset()


def _host_counts(tracker):
    history = np.asarray(tracker.evaluation_history)
    return int(np.asarray(tracker.evaluations).sum()), int(history.max(axis=0).sum())


def test_a_descent_records_its_one_shot_solves_without_a_transfer(glmix, ledger, monkeypatch):
    """Recording makes no device-to-host transfer: the ledger keeps the
    program's int32 scalars and reads them when it is read. (The descent's
    own drain of the objective's float32 values is a transfer it always
    made.)"""
    from jax._src.array import ArrayImpl

    descent = _descent(glmix)
    descent.run(1, glmix.num_rows)  # compile outside the patch
    ledger.reset()
    device_get = jax.device_get

    def no_counts(tree):
        if any(getattr(leaf, "dtype", None) == jnp.int32
               for leaf in jax.tree_util.tree_leaves(tree)):
            raise AssertionError("an int32 count left the device")
        return device_get(tree)

    array = ArrayImpl.__array__

    def no_array(self, *args, **kwargs):
        if self.dtype == jnp.int32:
            raise AssertionError("an int32 array was converted on the host")
        return array(self, *args, **kwargs)

    with monkeypatch.context() as patch:
        patch.setattr(jax, "device_get", no_counts)
        patch.setattr(ArrayImpl, "__array__", no_array)
        result = descent.run(2, glmix.num_rows)
        with pytest.raises(AssertionError, match="int32 count"):
            ledger.evaluation_totals()  # reading is what transfers
    records = ledger.evaluation_snapshot()
    assert [(r.label, r.kind) for r in records] == [("per-user", "one-shot")] * 2
    tracker = result.trackers["per-user"]
    assert records[-1].lanes == tracker.evaluations.shape[0]
    assert [records[-1].lane_evaluations, records[-1].needed_evaluations] == list(
        _host_counts(tracker))


def test_one_shot_records_leave_the_planners_ledger_alone(glmix, ledger):
    _descent(glmix).run(2, glmix.num_rows)
    assert ledger.totals()["solves"] == 0
    assert ledger.realized_plan_cost() is None
    totals = ledger.evaluation_totals("per-user")
    assert set(totals) == {"one-shot"} and totals["one-shot"]["solves"] == 2
    summary = ledger.summary()
    assert "solve compaction: no compacted solves recorded" in summary
    assert "solve evaluations: one-shot 2 solves" in summary
    assert "compacted none" in summary


def test_a_scheduled_coordinate_records_its_compacted_solves(glmix, ledger):
    descent = _descent(glmix)
    descent.coordinates["per-user"] = RandomEffectCoordinate(
        descent.coordinates["per-user"].dataset, LOGISTIC, OptimizerType.LBFGS,
        OptimizerConfig(max_iterations=8, tolerance=1e-6), RegularizationContext.l2(1e-1),
        solve_schedule=SolveSchedule(chunk_size=2), solve_label="per-user")
    descent = CoordinateDescent(descent.coordinates, descent.training_loss)
    result = descent.run(1, glmix.num_rows)
    (rec,) = ledger.evaluation_snapshot()
    assert (rec.label, rec.kind) == ("per-user", "compacted")
    assert [rec.lane_evaluations, rec.needed_evaluations] == list(
        _host_counts(result.trackers["per-user"]))
    assert ledger.totals()["solves"] == 1


def test_the_ledger_stays_bounded(ledger):
    keep = ledger.RECENT_KEEP
    for i in range(5 * keep):
        ledger.record_evaluations("x", "one-shot", 4,
                                  (jnp.int32(8 + i % 2), jnp.int32(3)))
        assert len(ledger._evaluations_unread) <= 2 * keep
    totals = ledger.evaluation_totals()["one-shot"]
    assert totals["solves"] == 5 * keep
    assert totals["lane_evaluations"] == sum(8 + i % 2 for i in range(5 * keep))
    assert totals["lane_means"] == pytest.approx(totals["lane_evaluations"] / 4)
    assert len(ledger.evaluation_snapshot()) == keep


# -- the benchmark's readers of the ledger ----------------------------------


def _readers():
    from benchmark.metrics.readers import counters

    def spec(name):
        with open(os.path.join(ROOT, "benchmark", "metrics", name + ".json")) as f:
            return json.load(f)

    return {
        "needed": lambda: counters.needed_evaluations_per_solve(
            {"metric": spec("re_needed_evaluations_per_solve")}),
        "lane": lambda: counters.lane_evaluations_per_solve(
            {"metric": spec("re_lane_evaluations_per_solve")}),
    }


def test_the_counter_readers_give_the_ledgers_means(glmix, ledger):
    read = _readers()
    assert read["needed"]() is None and read["lane"]() is None
    result = _descent(glmix).run(2, glmix.num_rows)
    records = ledger.evaluation_snapshot()
    assert len(records) == 2
    assert read["needed"]() == pytest.approx(
        np.mean([r.needed_evaluations for r in records]))
    assert read["lane"]() == pytest.approx(
        np.mean([r.lane_evaluations / r.lanes for r in records]))
    lanes = result.trackers["per-user"].evaluations.shape[0]
    assert read["lane"]() <= read["needed"]()
    assert records[-1].lane_evaluations / lanes <= records[-1].needed_evaluations


def test_the_counter_readers_read_nothing_under_another_label(glmix, ledger):
    ledger.record_evaluations("per-item", "one-shot", 4, (jnp.int32(8), jnp.int32(3)))
    read = _readers()
    assert read["needed"]() is None and read["lane"]() is None


# -- the needed evaluations priced at the widths the loop ran ---------------


def _host_width_evaluations(tracker, widths):
    """The ladder run on the host: each iteration's width from the lanes
    still running before it, times the most evaluations any lane made."""
    iterations = np.asarray(tracker.iterations)
    history = np.asarray(tracker.evaluation_history)
    lanes = iterations.shape[0]
    total = lanes * int(history[:, 0].max())
    for i in range(1, history.shape[1]):
        running = int(np.count_nonzero(iterations > i - 1))
        width = widths[sum(running <= w for w in widths[1:])]
        total += width * int(history[:, i].max())
    return total / lanes


def test_at_one_width_the_priced_count_is_the_needed_one(glmix, ledger):
    _descent(glmix).run(2, glmix.num_rows)
    records = ledger.evaluation_snapshot()
    assert len(records) == 2
    for rec in records:
        assert rec.widths == (rec.lanes,) and rec.rung_entries == (0,)
        assert rec.width_evaluations == rec.needed_evaluations
    totals = ledger.evaluation_totals("per-user")["one-shot"]
    assert totals["width_evaluations"] == totals["needed_evaluations"]
    assert "at the widths run" in ledger.summary()


@pytest.fixture(scope="module")
def skewed():
    """About 2,000 users of 3 to 300 rows: the one-shot solve's ladder is
    2,000, 1,024, 512 and 256 lanes wide, and the lanes stop over enough
    iterations to run at each."""
    data, _ = make_glmix_data(np.random.default_rng(43), num_users=2000,
                              rows_per_user_range=(3, 300), d_fixed=4, d_random=3)
    return data


def test_the_ladder_prices_each_iteration_at_its_width(skewed, ledger):
    coord = RandomEffectCoordinate(
        build_random_effect_dataset(skewed, RandomEffectDataConfig("userId", "per_user")),
        LOGISTIC, regularization=RegularizationContext.l2(1e-1), solve_label="per-user")
    loss = losses.for_task(LOGISTIC)
    labels, weights = jnp.asarray(skewed.response), jnp.asarray(skewed.weight)
    result = CoordinateDescent(
        {"per-user": coord}, lambda total: jnp.sum(weights * loss.loss(total, labels)),
    ).run(1, skewed.num_rows)
    tracker = result.trackers["per-user"]
    (rec,) = ledger.evaluation_snapshot()
    assert rec.widths == coord.rung_widths == (2000, 1024, 512, 256)
    iterations = np.asarray(tracker.iterations)
    running = [int(np.count_nonzero(iterations > i))
               for i in range(coord.optimizer_config.max_iterations)]
    assert rec.rung_entries == tuple(
        [0] + [sum(r > w for r in running) for w in rec.widths[1:]])
    assert rec.width_evaluations == pytest.approx(
        _host_width_evaluations(tracker, rec.widths), rel=1e-12)
    assert rec.width_evaluations < rec.needed_evaluations
    totals = ledger.evaluation_totals("per-user")["one-shot"]
    assert f"{totals['width_evaluations']:.1f} at the widths run" in ledger.summary()
    read = _width_reader()
    assert read() == pytest.approx(rec.width_evaluations)


def test_a_compacted_solve_is_priced_at_its_needed_count(ledger):
    """The compacted loops keep no widths in the ledger: their price is the
    needed count, as a one-shot solve's at one width."""
    data, w0 = _lanes()
    compacted_solve(data, w0, schedule=SolveSchedule(chunk_size=2), label="lanes", **LANE_KW)
    (rec,) = ledger.evaluation_snapshot()
    assert rec.kind == "compacted" and rec.widths is None
    assert rec.width_evaluations == rec.needed_evaluations
    totals = ledger.evaluation_totals("lanes")["compacted"]
    assert totals["width_evaluations"] == totals["needed_evaluations"]


def _width_reader():
    from benchmark.metrics.readers import widths

    with open(os.path.join(ROOT, "benchmark", "metrics",
                           "re_width_evaluations_per_solve.json")) as f:
        spec = json.load(f)
    return lambda: widths.width_evaluations_per_solve({"metric": spec})


def test_the_width_reader_reads_only_its_label(glmix, ledger):
    read = _width_reader()
    assert read() is None
    ledger.record_evaluations("per-item", "one-shot", 4, (jnp.int32(8), jnp.int32(3)))
    assert read() is None
    _descent(glmix).run(2, glmix.num_rows)
    records = [r for r in ledger.evaluation_snapshot() if r.label == "per-user"]
    assert read() == pytest.approx(np.mean([r.width_evaluations for r in records]))
