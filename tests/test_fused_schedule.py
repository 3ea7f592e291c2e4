"""On-device whole-cycle compaction (optim/fused_schedule.py).

The load-bearing claims, pinned BITWISE:

  * the fused device loop — chunk→compact→resume inside one
    ``lax.while_loop`` per ladder rung — equals the host chunk loop AND
    the one-shot kernel bit for bit (LBFGS / OWL-QN / TRON), with the
    same executed-lane-iteration count as the host loop;
  * host dispatches per solve are O(#rungs): one ChunkRecord per rung
    hop, widths strictly decreasing, with the in-program chunk count on
    the new ``SolveRecord.device_chunks`` ledger field;
  * preemption at the ``"rung"`` site snapshots the same
    ``kind="scheduler"`` carried pytree the host loop emits, and the
    snapshot resumes bitwise on EITHER loop;
  * the ``optim.device_drain`` fault site degrades the solve to the host
    chunk loop — results stay bitwise, and the next solve is fused again.
"""

import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from game_test_utils import make_glmix_data

from photon_ml_tpu.algorithm.random_effect import (
    RandomEffectCoordinate,
    entity_lane_fns,
)
from photon_ml_tpu.compile import ShapeBucketer
from photon_ml_tpu.data.game import (
    RandomEffectDataConfig,
    build_random_effect_dataset,
)
from photon_ml_tpu.optim import fused_schedule
from photon_ml_tpu.optim.common import OptimizerConfig
from photon_ml_tpu.optim.scheduler import (
    SolveSchedule,
    compacted_solve,
    resolve_schedule,
    solve_stats,
)
from photon_ml_tpu.ops.regularization import RegularizationContext
from photon_ml_tpu.resilience import faults, preemption
from photon_ml_tpu.types import OptimizerType, TaskType

pytestmark = pytest.mark.compaction


def assert_results_bitwise(a, b):
    for name, x, y in zip(a._fields, a, b):
        if x is None or y is None:
            assert x is y, name
            continue
        assert np.array_equal(np.asarray(x), np.asarray(y), equal_nan=True), name


def skewed_lane_problem(rng, E=40, M=10, D=4, hard=4):
    """A few ill-conditioned lanes among many easy ones."""
    x = rng.normal(size=(E, M, D)).astype(np.float32)
    x[:hard] *= np.geomspace(1.0, 32.0, D).astype(np.float32)
    w_true = (rng.normal(size=(E, D)) * 0.5).astype(np.float32)
    z = np.einsum("emd,ed->em", x.astype(np.float64), w_true)
    y = (1.0 / (1.0 + np.exp(-z)) > rng.random((E, M))).astype(np.float32)
    data = tuple(
        jnp.asarray(a)
        for a in (x, y, np.zeros((E, M), np.float32), np.ones((E, M), np.float32))
    )
    return data, jnp.zeros((E, D), jnp.float32)


# ---------------------------------------------------------------------------
# rung ladder geometry
# ---------------------------------------------------------------------------


class TestRungLadder:
    def test_ladder_is_full_width_then_descending_rungs(self):
        b = ShapeBucketer()  # base 8, growth 2: 8, 16, 32, 64, ...
        assert fused_schedule.rung_ladder(b, 40) == [40, 32, 16, 8]
        assert fused_schedule.rung_ladder(b, 8) == [8]
        assert fused_schedule.rung_ladder(b, 5) == [5]
        assert fused_schedule.rung_ladder(b, 64) == [64, 32, 16, 8]

    def test_next_lower_rung(self):
        b = ShapeBucketer()
        assert fused_schedule.next_lower_rung(b, 64) == 32
        assert fused_schedule.next_lower_rung(b, 40) == 32
        assert fused_schedule.next_lower_rung(b, 16) == 8
        assert fused_schedule.next_lower_rung(b, 8) == 0
        assert fused_schedule.next_lower_rung(b, 3) == 0

    def test_hop_targets_guarantee_progress(self):
        # target < rung for every ladder width => every dispatch retires
        # at least one chunk, so the hop loop terminates
        b = ShapeBucketer()
        for lanes in (3, 8, 9, 40, 64, 513):
            for rung in fused_schedule.rung_ladder(b, lanes):
                assert fused_schedule.next_lower_rung(b, rung) < rung


# ---------------------------------------------------------------------------
# bitwise: device loop == host loop == one-shot
# ---------------------------------------------------------------------------


class TestDeviceSolveBitwise:
    @pytest.mark.parametrize(
        "optimizer,reg",
        [
            (OptimizerType.LBFGS, RegularizationContext.l2(0.5)),
            pytest.param(
                OptimizerType.LBFGS,
                RegularizationContext.elastic_net(0.3, 0.5),
                # ~5s of OWL-QN rung-program compiles; tier-1 keeps the
                # LBFGS + TRON device pins here, and the OWL-QN chunked
                # vs one-shot pin in test_scheduler.py covers the l1
                # kernel's resumability — the device loop advances lanes
                # through that same kernel
                marks=pytest.mark.slow,
            ),
            (OptimizerType.TRON, RegularizationContext.l2(0.5)),
        ],
        ids=["lbfgs-l2", "owlqn-l1", "tron"],
    )
    def test_bitwise_vs_one_shot_and_host_loop(self, rng, optimizer, reg):
        data, w0 = skewed_lane_problem(rng)
        cfg = (
            OptimizerConfig(max_iterations=25, tolerance=1e-6)
            if optimizer == OptimizerType.TRON
            else OptimizerConfig(max_iterations=60, tolerance=1e-7)
        )
        kw = dict(
            task=TaskType.LOGISTIC_REGRESSION,
            optimizer=optimizer,
            optimizer_config=cfg,
            regularization=reg,
        )
        solve_one, *_ = entity_lane_fns(**kw)
        one = jax.jit(jax.vmap(solve_one))(*data, w0)
        solve_stats.reset()
        host = compacted_solve(
            data, w0, schedule=SolveSchedule(chunk_size=5), label="host", **kw
        )
        dev = compacted_solve(
            data, w0,
            schedule=SolveSchedule(chunk_size=5, loop="device"),
            label="dev", **kw,
        )
        assert_results_bitwise(host, one)
        assert_results_bitwise(dev, one)
        assert_results_bitwise(dev, host)
        # re-batching changes WHICH lanes burn iterations, never any
        # lane's arithmetic — so the two ledgers agree exactly
        rec_host, rec_dev = solve_stats.snapshot()[-2:]
        assert rec_host.label == "host" and rec_dev.label == "dev"
        assert rec_dev.executed == rec_host.executed
        assert rec_dev.saved == rec_host.saved

    def test_dispatches_are_o_rungs(self, rng):
        data, w0 = skewed_lane_problem(rng, E=40, hard=4)
        # same config as the bitwise pin above: the chunk executables are
        # already warm, so this test only pays for its assertions
        kw = dict(
            task=TaskType.LOGISTIC_REGRESSION,
            optimizer=OptimizerType.LBFGS,
            optimizer_config=OptimizerConfig(max_iterations=60, tolerance=1e-7),
            regularization=RegularizationContext.l2(0.5),
        )
        solve_stats.reset()
        compacted_solve(
            data, w0, schedule=SolveSchedule(chunk_size=5), label="host", **kw
        )
        compacted_solve(
            data, w0, schedule=SolveSchedule(chunk_size=5, loop="device"),
            label="dev", **kw,
        )
        rec_host, rec_dev = solve_stats.snapshot()[-2:]
        # the host loop pays one dispatch per chunk boundary; the device
        # loop pays one per rung hop, bounded by the ladder depth
        ladder = fused_schedule.rung_ladder(SolveSchedule().bucketer, 40)
        assert rec_dev.dispatches <= len(ladder)
        assert rec_dev.dispatches < rec_host.dispatches
        widths = [c.batch_lanes for c in rec_dev.chunks]
        assert widths == sorted(widths, reverse=True)
        assert len(set(widths)) == len(widths)  # strictly decreasing
        # the in-program chunk count rides the device ledger; the host
        # loop's chunk iterations all count as dispatches instead
        assert rec_dev.device_chunks >= rec_dev.dispatches
        assert rec_host.device_chunks == 0
        totals = solve_stats.totals()
        assert totals["device_chunk_iterations"] == rec_dev.device_chunks
        assert totals["chunk_dispatches"] == (
            rec_host.dispatches + rec_dev.dispatches
        )

    def test_rung_programs_reuse_compiled_executables(self, rng):
        from photon_ml_tpu.compile import compile_stats

        data, w0 = skewed_lane_problem(rng, E=40, hard=4)
        kw = dict(
            task=TaskType.LOGISTIC_REGRESSION,
            optimizer=OptimizerType.LBFGS,
            optimizer_config=OptimizerConfig(max_iterations=60, tolerance=1e-7),
            regularization=RegularizationContext.l2(0.5),
        )
        schedule = SolveSchedule(chunk_size=5, loop="device")
        compacted_solve(data, w0, schedule=schedule, label="warm", **kw)
        before = compile_stats.traces_of("scheduler.rung")
        compacted_solve(data, w0, schedule=schedule, label="reuse", **kw)
        assert compile_stats.traces_of("scheduler.rung") == before, (
            "scheduler.rung recompiled on an identical warm solve"
        )


# ---------------------------------------------------------------------------
# schedule spellings
# ---------------------------------------------------------------------------


class TestDeviceSpellings:
    def test_resolve_schedule_device_spellings(self, monkeypatch):
        d = resolve_schedule("device")
        assert d.loop == "device"
        assert d.chunk_size == SolveSchedule().chunk_size
        d12 = resolve_schedule("device:12")
        assert (d12.loop, d12.chunk_size) == ("device", 12)
        assert "loop=device" in d12.describe()
        assert "loop" not in SolveSchedule().describe()
        with pytest.raises(ValueError, match="off"):
            resolve_schedule("device:off")
        with pytest.raises(ValueError):
            resolve_schedule("device:sideways")
        monkeypatch.setenv("PHOTON_SOLVE_CHUNK", "device:7")
        env = resolve_schedule(None)
        assert (env.loop, env.chunk_size) == ("device", 7)

    def test_schedule_rejects_unknown_loop(self):
        with pytest.raises(ValueError, match="'host' or 'device'"):
            SolveSchedule(loop="gpu")


# ---------------------------------------------------------------------------
# preemption: drain at the rung boundary, resume on either loop
# ---------------------------------------------------------------------------


class TestRungPreemption:
    @pytest.fixture(autouse=True)
    def _clean_preemption(self):
        yield
        preemption.reset()

    def test_rung_preempt_snapshots_and_resumes_on_either_loop(self, rng):
        data, w0 = skewed_lane_problem(rng)
        kw = dict(
            task=TaskType.LOGISTIC_REGRESSION,
            optimizer=OptimizerType.LBFGS,
            optimizer_config=OptimizerConfig(max_iterations=60, tolerance=1e-7),
            regularization=RegularizationContext.l2(0.5),
        )
        dev = SolveSchedule(chunk_size=5, loop="device")
        clean = compacted_solve(data, w0, schedule=dev, label="clean", **kw)

        preemption.install_plan({"rung": 1})
        with pytest.raises(preemption.Preempted) as ei:
            compacted_solve(data, w0, schedule=dev, label="interrupted", **kw)
        assert ei.value.site == "rung"
        partial = ei.value.partial
        assert partial["meta"]["kind"] == "scheduler"
        assert 0 < partial["meta"]["limit"] < kw["optimizer_config"].max_iterations

        preemption.reset()
        resumed_dev = compacted_solve(
            data, w0, schedule=dev, label="resumed-dev", resume=partial, **kw
        )
        assert_results_bitwise(resumed_dev, clean)
        # the snapshot is the host loop's kind="scheduler" contract: a
        # device-loop drain resumes on the HOST loop too, bitwise
        resumed_host = compacted_solve(
            data, w0, schedule=SolveSchedule(chunk_size=5),
            label="resumed-host", resume=partial, **kw,
        )
        assert_results_bitwise(resumed_host, clean)

    def test_host_chunk_preempt_resumes_on_device_loop(self, rng):
        data, w0 = skewed_lane_problem(rng)
        kw = dict(
            task=TaskType.LOGISTIC_REGRESSION,
            optimizer=OptimizerType.LBFGS,
            optimizer_config=OptimizerConfig(max_iterations=60, tolerance=1e-7),
            regularization=RegularizationContext.l2(0.5),
        )
        clean = compacted_solve(
            data, w0, schedule=SolveSchedule(chunk_size=5), label="clean", **kw
        )
        preemption.install_plan({"chunk": 2})
        with pytest.raises(preemption.Preempted) as ei:
            compacted_solve(
                data, w0, schedule=SolveSchedule(chunk_size=5),
                label="interrupted", **kw,
            )
        preemption.reset()
        resumed = compacted_solve(
            data, w0, schedule=SolveSchedule(chunk_size=5, loop="device"),
            label="resumed", resume=ei.value.partial, **kw,
        )
        assert_results_bitwise(resumed, clean)


# ---------------------------------------------------------------------------
# chaos: the optim.device_drain fault site degrades to the host loop
# ---------------------------------------------------------------------------


@pytest.mark.faults
class TestChaosDegrade:
    def test_device_drain_fault_degrades_to_host_loop(self, rng):
        data, w0 = skewed_lane_problem(rng)
        kw = dict(
            task=TaskType.LOGISTIC_REGRESSION,
            optimizer=OptimizerType.LBFGS,
            optimizer_config=OptimizerConfig(max_iterations=60, tolerance=1e-7),
            regularization=RegularizationContext.l2(0.5),
        )
        dev = SolveSchedule(chunk_size=5, loop="device")
        host_res = compacted_solve(
            data, w0, schedule=SolveSchedule(chunk_size=5), label="host", **kw
        )
        solve_stats.reset()
        with faults.fault_scope(faults.FaultPlan(
            [faults.FaultSpec("optim.device_drain", at=1)]
        )):
            degraded = compacted_solve(
                data, w0, schedule=dev, label="degraded", **kw
            )
        assert_results_bitwise(degraded, host_res)
        assert solve_stats.snapshot()[-1].device_chunks == 0  # ran on host
        # the NEXT solve (fault plan gone) is fused again
        fused = compacted_solve(data, w0, schedule=dev, label="refused", **kw)
        assert_results_bitwise(fused, host_res)
        assert solve_stats.snapshot()[-1].device_chunks > 0


# ---------------------------------------------------------------------------
# coordinate wiring: one-shot / bucketed / streaming vs the device loop
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def glmix():
    rng = np.random.default_rng(77)
    data, _ = make_glmix_data(
        rng, num_users=40, rows_per_user_range=(3, 30), d_fixed=4, d_random=3
    )
    return data


class TestCoordinateWiring:
    def test_random_effect_coordinate_device_bitwise(self, glmix):
        ds = build_random_effect_dataset(
            glmix, RandomEffectDataConfig("userId", "per_user")
        )
        kw = dict(
            dataset=ds,
            task=TaskType.LOGISTIC_REGRESSION,
            optimizer=OptimizerType.LBFGS,
            regularization=RegularizationContext.l2(0.1),
        )
        plain = RandomEffectCoordinate(**kw)
        dev = RandomEffectCoordinate(
            **kw, solve_schedule=SolveSchedule(chunk_size=6, loop="device")
        )
        assert dev.cd_jit is False
        resid = jnp.zeros((glmix.num_rows,), jnp.float32)
        w_plain, res_plain = jax.jit(plain.update)(
            resid, plain.initial_coefficients()
        )
        w_dev, res_dev = dev.update(resid, dev.initial_coefficients())
        assert np.array_equal(np.asarray(w_plain), np.asarray(w_dev))
        assert_results_bitwise(res_dev, jax.tree.map(jnp.asarray, res_plain))
        assert np.array_equal(
            np.asarray(plain.score(w_plain)), np.asarray(dev.score(w_dev))
        )

    @pytest.mark.slow  # ~15s of per-bucket chunk kernels; tier-1 pins the
    # same composition via the RE-coordinate device test above plus the
    # host-loop bucketed pin in test_scheduler.py — the device loop enters
    # through the identical compacted_solve seam in all three
    def test_bucketed_coordinate_device_bitwise(self, glmix):
        from photon_ml_tpu.algorithm.bucketed_random_effect import (
            BucketedRandomEffectCoordinate,
        )

        cfg = RandomEffectDataConfig("userId", "per_user")
        kw = dict(
            data=glmix,
            config=cfg,
            task=TaskType.LOGISTIC_REGRESSION,
            regularization=RegularizationContext.l2(0.2),
        )
        host = BucketedRandomEffectCoordinate(
            **kw, solve_schedule=SolveSchedule(chunk_size=6)
        )
        dev = BucketedRandomEffectCoordinate(
            **kw,
            bundle=host.bundle,  # share the built stacks
            solve_schedule=SolveSchedule(chunk_size=6, loop="device"),
        )
        resid = jnp.zeros((glmix.num_rows,), jnp.float32)
        st_host, _ = host.update(resid, host.initial_coefficients())
        st_dev, _ = dev.update(resid, dev.initial_coefficients())
        for a, b in zip(st_host, st_dev):
            assert np.array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.slow  # ~4s of per-block rung compiles; tier-1 pins this
    # seam via the RE-coordinate device test above plus the host-loop
    # streaming pin in test_scheduler.py — streaming blocks call the same
    # compacted_solve the plain coordinate does
    def test_streaming_coordinate_device_bitwise(self, glmix, tmp_path):
        from photon_ml_tpu.algorithm.streaming_random_effect import (
            StreamingRandomEffectCoordinate,
            write_re_entity_blocks,
        )

        manifest = write_re_entity_blocks(
            glmix,
            RandomEffectDataConfig("userId", "per_user"),
            str(tmp_path / "blocks"),
            block_entities=16,
        )
        kw = dict(
            manifest=manifest,
            task=TaskType.LOGISTIC_REGRESSION,
            regularization=RegularizationContext.l2(0.1),
        )
        host = StreamingRandomEffectCoordinate(
            **kw,
            state_root=str(tmp_path / "state-host"),
            solve_schedule=SolveSchedule(chunk_size=6),
        )
        dev = StreamingRandomEffectCoordinate(
            **kw,
            state_root=str(tmp_path / "state-dev"),
            solve_schedule=SolveSchedule(chunk_size=6, loop="device"),
        )
        resid = jnp.zeros((glmix.num_rows,), jnp.float32)
        st_host, _ = host.update(resid, host.initial_coefficients())
        st_dev, _ = dev.update(resid, dev.initial_coefficients())
        for i in range(len(manifest.blocks)):
            assert np.array_equal(st_host.block(i), st_dev.block(i)), i
        assert np.array_equal(
            np.asarray(host.score(st_host)), np.asarray(dev.score(st_dev))
        )


# ---------------------------------------------------------------------------
# the one-shot solve's ladder: compaction inside the descent's own program
# ---------------------------------------------------------------------------


#: The three solvers a one-shot update can run per lane, as the device-loop
#: tests above name them: regularization and optimizer of the coordinate.
LANE_SOLVERS = {
    "lbfgs-l2": (RegularizationContext.l2(0.1), OptimizerType.LBFGS),
    "owlqn-l1": (RegularizationContext.elastic_net(0.3, 0.5), OptimizerType.LBFGS),
    "tron": (RegularizationContext.l2(0.1), OptimizerType.TRON),
}


@functools.lru_cache(maxsize=None)
def _ladder_case(solver: str):
    """About 2,000 users of 3 to 300 rows each: their lanes stop from the
    third iteration to the thirteenth or later, so a one-shot update runs
    iterations at every width of its ladder (2,000, 1,024, 512, 256). The
    update as a descent compiles it, and the plain vmapped loop of the same
    lanes beside it."""
    data, _ = make_glmix_data(
        np.random.default_rng(43), num_users=2000,
        rows_per_user_range=(3, 300), d_fixed=4, d_random=3,
    )
    ds = build_random_effect_dataset(
        data, RandomEffectDataConfig("userId", "per_user")
    )
    reg, optimizer = LANE_SOLVERS[solver]
    coord = RandomEffectCoordinate(
        dataset=ds, task=TaskType.LOGISTIC_REGRESSION, optimizer=optimizer,
        regularization=reg,
    )
    solve_one, init_one, advance_one, result_of = entity_lane_fns(
        coord.task, coord.optimizer, coord.optimizer_config,
        coord.regularization,
    )
    resid = jnp.zeros((data.num_rows,), jnp.float32)
    lanes = (ds.x, ds.labels, coord.gathered_offsets(resid), ds.weights)
    w0 = coord.initial_coefficients()
    ladder = jax.jit(lambda lanes, w0: fused_schedule.ladder_solve(
        lanes, w0, coord.rung_widths, init_one, advance_one,
        coord.optimizer_config.max_iterations,
    ))
    return dict(
        data=data, coord=coord, resid=resid, lanes=lanes, w0=w0,
        ladder=lambda lanes, w0: result_of(ladder(lanes, w0)[0]),
        ladder_entries=lambda: ladder(lanes, w0)[1],
        one_shot=jax.jit(jax.vmap(solve_one))(*lanes, w0),
        one_shot_of=jax.jit(jax.vmap(solve_one)),
    )


def _top_level_whiles(fn, *args):
    return sum(e.primitive.name == "while" for e in jax.make_jaxpr(fn)(*args).eqns)


class TestOneShotLadder:
    """The ladder's lanes against the plain vmapped loop's, bit for bit.

    Both sides run the same lanes in the same order: XLA's CPU backend
    does not give a lane the same bits in every place of a batch (it
    contracts a multiply and an add into one FMA in some places of a
    vectorized loop), so on the CPU the plain loop of permuted lanes is
    itself a few ulp off in some lanes. That a lane's bits follow neither
    its place nor its batch's width is checked on the chip, where the
    benchmark's GLMix cell compares to the parent's digits."""

    def test_widths_halve_from_the_lanes_down_to_the_floor(self):
        widths = fused_schedule.lane_widths
        assert widths(34624) == (34624, 17344, 8704, 4352, 2176, 1088, 576, 320)
        assert widths(2000) == (2000, 1024, 512, 256)
        assert widths(512) == (512, 256)
        assert widths(511) == (511,) and widths(40) == (40,)
        # the unit is the lanes' kernel block where it picks one
        assert widths(2000, 16) == (2000, 1008, 512, 256, 128, 64)
        for lanes in (600, 2000, 34624, 100003):
            for unit in (8, 64):
                w = widths(lanes, unit)
                assert all(a > b for a, b in zip(w, w[1:]))
                assert all(x % unit == 0 and x >= 4 * unit for x in w[1:])

    @pytest.mark.parametrize("solver", list(LANE_SOLVERS))
    def test_matches_the_one_shot_loop_bitwise_across_the_rungs(self, solver):
        case = _ladder_case(solver)
        coord, one_shot = case["coord"], case["one_shot"]
        widths = coord.rung_widths
        assert widths == (2000, 1024, 512, 256)
        _, got = jax.jit(coord.update)(case["resid"], case["w0"])
        assert_results_bitwise(got, one_shot)
        # iterations ran at three widths at least: the lanes crossed rungs
        entries = np.asarray(fused_schedule.rung_entries(
            one_shot.iterations, widths, coord.optimizer_config.max_iterations))
        ends = np.append(entries[1:], int(np.max(one_shot.iterations)))
        assert np.count_nonzero(ends > entries) >= 3

    def test_the_entries_read_back_are_the_loops_own(self):
        """``rung_entries`` re-derives from the lanes' iteration counts the
        iteration at which the ladder took each width; it must read what the
        loop itself recorded."""
        case = _ladder_case("lbfgs-l2")
        coord = case["coord"]
        recorded = np.asarray(case["ladder_entries"]())
        derived = np.asarray(fused_schedule.rung_entries(
            case["one_shot"].iterations, coord.rung_widths,
            coord.optimizer_config.max_iterations))
        assert np.array_equal(recorded, derived)
        assert np.all(np.diff(recorded) > 0)

    def test_each_width_evaluates_in_a_place_of_its_own(self):
        """A trace counts a scope's executions per place in the program (the
        op_name's prefix): the lane kernel of each rung has to sit under a
        prefix of its own, or every width's evaluations count as the
        widest's."""
        case = _ladder_case("lbfgs-l2")
        coord = case["coord"]
        text = jax.jit(coord.update).lower(
            case["resid"], case["w0"]).as_text(debug_info=True)
        rungs = set(re.findall(
            r"(pml\.re\.rung\d+)/while/body/[^\"]*pml\.re\.value_and_grad", text))
        assert rungs == {f"pml.re.rung{k}" for k in range(len(coord.rung_widths))}

    def test_permuted_lanes_keep_their_bits(self):
        case = _ladder_case("lbfgs-l2")
        order = np.random.default_rng(3).permutation(case["w0"].shape[0])
        take = lambda a: a[order]
        lanes, w0 = jax.tree.map(take, case["lanes"]), take(case["w0"])
        assert_results_bitwise(case["ladder"](lanes, w0),
                               case["one_shot_of"](*lanes, w0))

    def test_inside_the_descents_compiled_update(self):
        from photon_ml_tpu.algorithm.coordinate_descent import CoordinateDescent
        from photon_ml_tpu.ops import losses

        case = _ladder_case("lbfgs-l2")
        data, coord = case["data"], case["coord"]
        loss = losses.for_task(TaskType.LOGISTIC_REGRESSION)
        labels, weights = jnp.asarray(data.response), jnp.asarray(data.weight)
        descent = CoordinateDescent(
            {"per_user": coord},
            lambda total: jnp.sum(weights * loss.loss(total, labels)),
        )
        assert getattr(coord, "cd_jit", True)
        result = descent.run(num_iterations=1, num_rows=data.num_rows)
        one_shot = case["one_shot"]
        assert_results_bitwise(result.trackers["per_user"], one_shot)
        assert np.array_equal(
            np.asarray(result.coefficients["per_user"]),
            np.asarray(one_shot.coefficients),
        )

    def test_a_sparse_slab_steps_over_the_ladder_bitwise(self):
        """The streaming coordinate's blocks may hand ``update`` a sparse
        slab; the ladder gathers its leaves as it gathers a dense stack."""
        data, _ = make_glmix_data(
            np.random.default_rng(43), num_users=1200,
            rows_per_user_range=(3, 200), d_fixed=4, d_random=5,
        )
        ds = build_random_effect_dataset(
            data, RandomEffectDataConfig("userId", "per_user"))
        coord = RandomEffectCoordinate(
            dataset=ds, task=TaskType.LOGISTIC_REGRESSION,
            regularization=RegularizationContext.l2(0.1),
            sparse_kernel="scatter",
        )
        assert coord._slab is not None
        assert coord.rung_widths == (1200, 640, 320)
        resid = jnp.zeros((data.num_rows,), jnp.float32)
        w0 = coord.initial_coefficients()
        _, got = jax.jit(coord.update)(resid, w0)
        solve_one, *_ = entity_lane_fns(
            coord.task, coord.optimizer, coord.optimizer_config,
            coord.regularization,
        )
        one_shot = jax.jit(jax.vmap(solve_one))(
            coord._slab, ds.labels, coord.gathered_offsets(resid), ds.weights, w0)
        assert_results_bitwise(got, one_shot)
        entries = np.asarray(fused_schedule.rung_entries(
            one_shot.iterations, coord.rung_widths,
            coord.optimizer_config.max_iterations))
        assert np.all(np.diff(entries) > 0)

    def test_below_the_floor_the_update_is_the_one_shot_loop(self, glmix):
        """Under twice the floor the ladder is one width, and the update's
        program is the vmapped solve's own single loop."""
        ds = build_random_effect_dataset(
            glmix, RandomEffectDataConfig("userId", "per_user"))
        small = RandomEffectCoordinate(
            dataset=ds, task=TaskType.LOGISTIC_REGRESSION,
            regularization=RegularizationContext.l2(0.1),
        )
        assert small.rung_widths == (ds.num_entities,)
        solve_one, *_ = entity_lane_fns(
            small.task, small.optimizer, small.optimizer_config,
            small.regularization,
        )
        resid = jnp.zeros((glmix.num_rows,), jnp.float32)
        w0 = small.initial_coefficients()

        def plain(resid, w0):
            off = small.gathered_offsets(resid)
            results = jax.vmap(solve_one)(ds.x, ds.labels, off, ds.weights, w0)
            return results.coefficients, results

        assert _top_level_whiles(small.update, resid, w0) == 1
        assert str(jax.make_jaxpr(small.update)(resid, w0)) == str(
            jax.make_jaxpr(plain)(resid, w0))
        case = _ladder_case("lbfgs-l2")
        big = case["coord"]
        assert _top_level_whiles(
            big.update, case["resid"], big.initial_coefficients(),
        ) == len(big.rung_widths)
