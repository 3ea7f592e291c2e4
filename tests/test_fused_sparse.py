"""Fused sparse per-entity kernels: slab construction, family bit-identity,
solver wiring, selection race, and executable reuse.

The discipline under test (ops/fused_sparse.py): every sparse family —
XLA scatter, the XLA two-pass segment-sum baseline, the fused single-pass
Pallas GEVM/HVP (whole-slab and row-blocked) — shares ONE arithmetic, so a
per-entity solve through the fused kernel is BITWISE-equal to the same
solve with the kernel off (the XLA baseline). The dense path is a
different arithmetic (XLA reassociates the dense dot), so dense agreement
is at float tolerance and switching a bucket to sparse at all is a raced,
per-bucket decision.
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from photon_ml_tpu.ops import fused_sparse, losses
from photon_ml_tpu.ops.fused_sparse import (
    SPARSE_BASELINE,
    SparseSlab,
    build_sparse_slab,
    fused_hvp_parts,
    fused_value_grad_parts,
    race_sparse_kernels,
    resolve_sparse_kernel,
    slab_nnz_stats,
)

pytestmark = pytest.mark.sparse


def _skewed_dense(rng, e, m, d, max_nnz=None, pad_lanes=0):
    """Dense (E, M, D) stack with skewed per-row nnz; the last ``pad_lanes``
    lanes get zero-weight garbage rows beyond row m//2 (bucket padding)."""
    max_nnz = max_nnz or max(d // 4, 2)
    x = np.zeros((e, m, d), np.float32)
    for ei in range(e):
        for mi in range(m):
            nnz = int(rng.integers(0, max_nnz + 1))
            if nnz:
                cols = rng.choice(d, size=nnz, replace=False)
                x[ei, mi, cols] = rng.normal(size=nnz)
    wt = np.ones((e, m), np.float32)
    for ei in range(e - pad_lanes, e):
        wt[ei, m // 2:] = 0.0
        # garbage in padding rows must be masked to an exact zero
        x[ei, m // 2:] = rng.normal(size=(m - m // 2, d)) * 1e6
    y = (rng.random((e, m)) < 0.5).astype(np.float32)
    off = (rng.normal(size=(e, m)) * 0.1).astype(np.float32)
    return x, y, wt, off


class TestSlabBuild:
    def test_ascending_order_and_padding(self, rng):
        x, *_ = _skewed_dense(rng, 3, 8, 16)
        slab = build_sparse_slab(x)
        idx, val = np.asarray(slab.idx), np.asarray(slab.val)
        counts = (x != 0).sum(-1)
        assert slab.dim == 16
        assert idx.shape == val.shape == (3, 8, counts.max())
        for e in range(3):
            for m in range(8):
                k = counts[e, m]
                cols = np.nonzero(x[e, m])[0]
                assert (idx[e, m, :k] == cols).all()  # ascending column order
                np.testing.assert_array_equal(val[e, m, :k], x[e, m, cols])
                # padding slots: index 0, value 0
                assert (idx[e, m, k:] == 0).all()
                assert (val[e, m, k:] == 0).all()

    def test_all_zero_rows_and_k_floor(self):
        slab = build_sparse_slab(np.zeros((2, 4, 8), np.float32))
        assert slab.max_nnz == 1  # K >= 1 keeps downstream shapes sane
        assert (np.asarray(slab.val) == 0).all()
        stats = slab_nnz_stats(slab)
        assert stats["max_nnz"] == 0 and stats["mean_nnz"] == 0.0

    def test_empty_bucket(self):
        slab = build_sparse_slab(np.zeros((0, 4, 8), np.float32))
        assert slab.idx.shape == (0, 4, 1)

    def test_ladder_rounds_k(self, rng):
        from photon_ml_tpu.compile import ShapeBucketer

        x, *_ = _skewed_dense(rng, 2, 6, 32, max_nnz=9)
        k_raw = int((x != 0).sum(-1).max())
        slab = build_sparse_slab(x, bucketer=ShapeBucketer(base=8, growth=2.0))
        # K lands on the 8 * 2^k ladder rung >= raw max nnz, capped at D
        assert slab.max_nnz >= k_raw
        assert slab.max_nnz in (8, 16, 32)

    def test_dense_roundtrip(self, rng):
        x, *_ = _skewed_dense(rng, 1, 5, 12)
        slab = build_sparse_slab(x[0])
        np.testing.assert_array_equal(np.asarray(slab.to_dense()), x[0])


class TestFamilyBitIdentity:
    """scatter == segment == fused pallas (whole-slab AND row-blocked),
    bitwise; dense reference at float tolerance."""

    @pytest.fixture()
    def lane(self, rng):
        x, y, wt, off = _skewed_dense(rng, 1, 64, 24)
        slab = build_sparse_slab(x[0])
        w = jnp.asarray(rng.normal(size=24).astype(np.float32) * 0.3)
        return (
            slab, x[0], jnp.asarray(y[0]), jnp.asarray(wt[0]),
            jnp.asarray(off[0]), w,
        )

    def _baseline_parts(self, slab, y, wt, off, w, loss):
        # the scalar pieces reduce through the shared fixed-association
        # tree — the arithmetic every sparse family reproduces bitwise
        z = slab.matvec(w) + off
        wl = jnp.where(wt > 0, wt * loss.loss(z, y), 0.0)
        d = jnp.where(wt > 0, wt * loss.d1(z, y), 0.0)
        return (
            fused_sparse.tree_row_sum(wl),
            slab.rmatvec(d),
            fused_sparse.tree_row_sum(d),
        )

    @pytest.mark.parametrize("loss_name", ["logistic", "squared", "poisson"])
    def test_vg_families(self, lane, loss_name):
        slab, x, y, wt, off, w = lane
        loss = getattr(losses, loss_name)
        lv, g, sd = self._baseline_parts(slab, y, wt, off, w, loss)
        g_seg = slab.with_kernel("segment").rmatvec(
            jnp.where(wt > 0, wt * loss.d1(slab.matvec(w) + off, y), 0.0)
        )
        assert np.array_equal(np.asarray(g), np.asarray(g_seg))
        for kernel in ("pallas", "pallas:16"):
            lvF, gF, sdF = fused_value_grad_parts(
                loss, slab.with_kernel(kernel), y, wt, off, w
            )
            assert float(lvF) == float(lv), kernel
            assert np.array_equal(np.asarray(gF), np.asarray(g)), kernel
            assert float(sdF) == float(sd), kernel
        # the flat lane-offset family: unbatched it IS the plain scatter
        g_flat = slab.with_kernel("flat").rmatvec(
            jnp.where(wt > 0, wt * loss.d1(slab.matvec(w) + off, y), 0.0)
        )
        assert np.array_equal(np.asarray(g_flat), np.asarray(g))
        # dense reference: same math, different (reassociated) accumulation
        z_d = jnp.asarray(x) @ w + off
        lv_d = jnp.sum(jnp.where(wt > 0, wt * loss.loss(z_d, y), 0.0))
        np.testing.assert_allclose(float(lv), float(lv_d), rtol=1e-4)

    def test_hvp_families(self, lane, rng):
        slab, x, y, wt, off, w = lane
        loss = losses.logistic
        v = jnp.asarray(rng.normal(size=24).astype(np.float32))
        z = slab.matvec(w) + off
        d2 = jnp.where(wt > 0, wt * loss.d2(z, y), 0.0)
        c = d2 * (slab.matvec(v) + jnp.zeros(()))
        hv = slab.rmatvec(c)
        for kernel in ("pallas", "pallas:16"):
            hvF, scF = fused_hvp_parts(
                loss, slab.with_kernel(kernel), y, wt, off, w, v, jnp.zeros(())
            )
            assert np.array_equal(np.asarray(hvF), np.asarray(hv)), kernel
            assert float(scF) == float(fused_sparse.tree_row_sum(c)), kernel

    def test_flat_batched_rule_bitwise(self, rng):
        """The interesting path for "flat": under vmap the custom_vmap
        rule folds lane offsets into ONE (E*D,) scatter — lanes are
        disjoint, so it must be bitwise-equal to the batched per-lane
        scatter/segment lowerings."""
        x, y, wt, off = _skewed_dense(rng, 8, 32, 16)
        slab = build_sparse_slab(x)
        d = jnp.asarray(rng.normal(size=(8, 32)).astype(np.float32))

        def rm(kernel):
            fn = jax.vmap(
                lambda i, v, dd: SparseSlab(i, v, 16, kernel).rmatvec(dd)
            )
            return np.asarray(jax.jit(fn)(slab.idx, slab.val, d))  # jit-ok: test fixture

        ref = rm("segment")
        assert np.array_equal(rm("flat"), ref)
        assert np.array_equal(rm("scatter"), ref)

    def test_pad_rows_hard_masked(self, rng):
        # weight-0 rows carry garbage that would overflow poisson exp —
        # every family must contribute an exact 0 for them
        x, y, wt, off = _skewed_dense(rng, 2, 16, 8, pad_lanes=1)
        slab = build_sparse_slab(x)
        lane = 1  # the padded lane
        sl = SparseSlab(slab.idx[lane], slab.val[lane], 8, "pallas")
        w = jnp.asarray(rng.normal(size=8).astype(np.float32))
        lv, g, sd = fused_value_grad_parts(
            losses.poisson, sl, jnp.asarray(y[lane]), jnp.asarray(wt[lane]),
            jnp.asarray(off[lane]), w,
        )
        assert np.isfinite(float(lv)) and np.isfinite(np.asarray(g)).all()

    def test_ragged_m_single_block(self, rng):
        # M that no row-block divides: the whole-slab default covers it in
        # one grid step (the "tail chunk" of the sparse family)
        x, y, wt, off = _skewed_dense(rng, 1, 37, 12)
        slab = build_sparse_slab(x[0]).with_kernel("pallas")
        w = jnp.asarray(rng.normal(size=12).astype(np.float32))
        lv, g, sd = fused_value_grad_parts(
            losses.logistic, slab, jnp.asarray(y[0]), jnp.asarray(wt[0]),
            jnp.asarray(off[0]), w,
        )
        base = slab.with_kernel("scatter")
        z = base.matvec(w) + jnp.asarray(off[0])
        d = jnp.where(jnp.asarray(wt[0]) > 0,
                      jnp.asarray(wt[0]) * losses.logistic.d1(z, jnp.asarray(y[0])), 0.0)
        assert np.array_equal(np.asarray(g), np.asarray(base.rmatvec(d)))
        # a forced row block that does not tile M degrades to the
        # whole-slab grid (identical arithmetic) instead of aborting —
        # a global "pallas:<rows>" spec must survive heterogeneous rungs
        lvB, gB, sdB = fused_value_grad_parts(
            losses.logistic, slab.with_kernel("pallas:16"),
            jnp.asarray(y[0]), jnp.asarray(wt[0]), jnp.asarray(off[0]), w,
        )
        assert float(lvB) == float(lv)
        assert np.array_equal(np.asarray(gB), np.asarray(g))


class TestSolveBitIdentity:
    """Full per-entity solves: fused sparse path bitwise-equal to the
    kernel-off (XLA baseline) path; dense at tolerance."""

    @pytest.fixture()
    def problem(self, rng):
        from game_test_utils import make_glmix_data
        from photon_ml_tpu.data.game import (
            RandomEffectDataConfig,
            build_random_effect_dataset,
        )

        data, _ = make_glmix_data(
            rng, num_users=10, rows_per_user_range=(4, 20), d_fixed=4,
            d_random=3,
        )
        ds = build_random_effect_dataset(
            data, RandomEffectDataConfig("userId", "per_user")
        )
        return ds, jnp.zeros((data.num_rows,))

    def _solve(self, ds, resid, kernel, optimizer="LBFGS", schedule=None):
        from photon_ml_tpu.algorithm.random_effect import RandomEffectCoordinate
        from photon_ml_tpu.optim.common import OptimizerConfig
        from photon_ml_tpu.ops.regularization import RegularizationContext
        from photon_ml_tpu.types import OptimizerType, TaskType

        coord = RandomEffectCoordinate(
            ds, TaskType.LOGISTIC_REGRESSION, OptimizerType[optimizer],
            OptimizerConfig(max_iterations=8, tolerance=1e-8),
            RegularizationContext.l2(0.4),
            sparse_kernel=kernel, solve_schedule=schedule,
        )
        coefs, _ = coord.update(resid, coord.initial_coefficients())
        return np.asarray(coefs)

    @pytest.mark.parametrize("optimizer", ["LBFGS", "TRON"])
    def test_fused_bitwise_vs_kernel_off(self, problem, optimizer):
        ds, resid = problem
        w_off = self._solve(ds, resid, SPARSE_BASELINE, optimizer)
        for kernel in ("scatter", "flat", "pallas"):
            w_on = self._solve(ds, resid, kernel, optimizer)
            assert np.array_equal(w_on, w_off), kernel

    def test_dense_reference_at_tolerance(self, problem):
        ds, resid = problem
        w_dense = self._solve(ds, resid, None)
        w_sparse = self._solve(ds, resid, "scatter")
        # dense is a different arithmetic (XLA reassociates the dense dot):
        # agreement is at float tolerance, bitwise equality is NOT expected
        np.testing.assert_allclose(w_sparse, w_dense, rtol=1e-2, atol=1e-3)

    def test_scheduled_solve_bitwise(self, problem):
        from photon_ml_tpu.optim.scheduler import SolveSchedule

        ds, resid = problem
        one_shot = self._solve(ds, resid, "pallas")
        chunked = self._solve(
            ds, resid, "pallas", schedule=SolveSchedule(chunk_size=3)
        )
        assert np.array_equal(one_shot, chunked)

    def test_traced_construction_requires_prebuilt_slab(self, problem):
        from photon_ml_tpu.algorithm.random_effect import RandomEffectCoordinate
        from photon_ml_tpu.types import TaskType

        ds, resid = problem

        def build(ds):
            return RandomEffectCoordinate(
                ds, TaskType.LOGISTIC_REGRESSION, sparse_kernel="scatter"
            ).initial_coefficients()

        with pytest.raises(ValueError, match="under a trace"):
            jax.jit(build)(ds)  # jit-ok: test fixture exercising the guard


class TestExecutableReuse:
    def test_same_ladder_buckets_share_chunk_executable(self, rng):
        """Two buckets on the same (E, M, K) rung solve through ONE
        scheduler chunk executable; a warm re-solve adds zero compiles
        (the CompileStats watermark assertion from the acceptance gate)."""
        from photon_ml_tpu.compile import compile_stats
        from photon_ml_tpu.optim.common import OptimizerConfig
        from photon_ml_tpu.optim.scheduler import SolveSchedule, compacted_solve
        from photon_ml_tpu.ops.regularization import RegularizationContext
        from photon_ml_tpu.types import OptimizerType, TaskType

        kw = dict(
            task=TaskType.LOGISTIC_REGRESSION,
            optimizer=OptimizerType.LBFGS,
            optimizer_config=OptimizerConfig(max_iterations=12, tolerance=1e-7),
            regularization=RegularizationContext.l2(0.5),
        )
        schedule = SolveSchedule(chunk_size=4)

        def solve(seed):
            r = np.random.default_rng(seed)
            x, y, wt, off = _skewed_dense(r, 8, 16, 12, max_nnz=4)
            # pin the rung: row (0,0) carries exactly the nnz cap, so both
            # seeds' slabs land on K=4 and share every executable
            x[0, 0] = 0.0
            x[0, 0, :4] = 1.0
            slab = build_sparse_slab(x).with_kernel("pallas")
            assert slab.idx.shape == (8, 16, 4)
            data = (slab, jnp.asarray(y), jnp.asarray(off), jnp.asarray(wt))
            res = compacted_solve(
                data, jnp.zeros((8, 12), jnp.float32), schedule=schedule,
                label=f"reuse{seed}", **kw,
            )
            jax.block_until_ready(res.coefficients)

        solve(0)  # cold: compiles the rung's chunk kernels
        mark = compile_stats.watermark()
        solve(1)  # same rung, different bucket: NO new executables
        assert mark.new_traces() == 0, (
            "a same-ladder bucket recompiled the scheduler kernels: "
            f"{mark.new_traces()} new traces"
        )


class TestSelectionRace:
    def test_every_candidate_accounted_for(self, rng):
        from photon_ml_tpu.types import TaskType

        x, y, wt, off = _skewed_dense(rng, 4, 16, 12)
        slab = build_sparse_slab(x)
        report = race_sparse_kernels(
            TaskType.LOGISTIC_REGRESSION, slab, x, jnp.asarray(y),
            jnp.asarray(off), jnp.asarray(wt),
        )
        raced = set(fused_sparse.sparse_candidates(32)) | {"dense"}
        # no silent caps: every raced name shows up with a timing or a
        # failure reason
        assert raced <= set(report["candidates"])
        for name, rec in report["candidates"].items():
            assert ("sec_per_pass" in rec) or ("failed" in rec), name
        assert report["baseline"] == SPARSE_BASELINE

    def test_f64_disqualifies_pallas_with_reason(self, rng):
        from jax import enable_x64
        from photon_ml_tpu.types import TaskType

        x, y, wt, off = _skewed_dense(rng, 3, 8, 8)
        with enable_x64():
            slab = build_sparse_slab(x, dtype=np.float64)
            report = race_sparse_kernels(
                TaskType.LOGISTIC_REGRESSION, slab,
                x.astype(np.float64), jnp.asarray(y), jnp.asarray(off),
                jnp.asarray(wt),
            )
        rec = report["candidates"]["pallas"]
        assert "failed" in rec and "float64" in rec["failed"]

    def test_forced_pallas_f64_runs_scatter_family(self, rng):
        """A FORCED pallas family under float64 must normalize to the
        family that actually executes (the objective's f64 gate falls back
        to the generic scatter) instead of lying in telemetry and keying a
        duplicate executable on a "pallas" static field."""
        from jax import enable_x64
        from photon_ml_tpu.types import TaskType

        x, y, wt, off = _skewed_dense(rng, 3, 8, 6)
        with enable_x64():
            with pytest.warns(UserWarning, match="ineligible under float64"):
                slab = fused_sparse.build_and_select(
                    TaskType.LOGISTIC_REGRESSION, x.astype(np.float64),
                    jnp.asarray(y), jnp.asarray(off), jnp.asarray(wt),
                    "pallas", "f64-forced",
                )
        assert slab is not None and slab.kernel == "scatter"

    def test_race_cache_keyed_by_dtype(self, rng, monkeypatch):
        """An f32 bucket's raced winner must not be reused for a
        same-shaped f64 slab — eligibility differs (pallas is out under
        f64), so the cache key carries the dtype."""
        from photon_ml_tpu.types import TaskType

        calls = []

        def fake_race(task, slab, *a, **kw):
            calls.append(jnp.dtype(slab.val.dtype).name)
            return {"winner": "flat"}

        monkeypatch.setattr(fused_sparse, "race_sparse_kernels", fake_race)
        monkeypatch.setattr(fused_sparse, "_race_cache", {})
        monkeypatch.setattr(fused_sparse, "_race_reports", {})
        x, y, wt, off = _skewed_dense(rng, 3, 8, 6)
        args = (jnp.asarray(y), jnp.asarray(off), jnp.asarray(wt))
        slab32 = build_sparse_slab(x)
        for _ in range(2):  # second call: cache hit, no re-race
            fused_sparse.select_sparse_kernel(
                TaskType.LOGISTIC_REGRESSION, slab32, x, *args, spec="auto"
            )
        assert calls == ["float32"]
        # same shape, f64 leaves (host numpy — the race is faked, so no
        # x64 mode needed): must MISS the f32 entry and race again
        slab64 = SparseSlab(
            np.asarray(slab32.idx), np.asarray(slab32.val, np.float64),
            slab32.dim,
        )
        fused_sparse.select_sparse_kernel(
            TaskType.LOGISTIC_REGRESSION, slab64, x, *args, spec="auto"
        )
        assert calls == ["float32", "float64"]

    def test_resolve_spec(self, monkeypatch):
        monkeypatch.delenv("PHOTON_SPARSE_KERNEL", raising=False)
        assert resolve_sparse_kernel(None) is None
        assert resolve_sparse_kernel("off") is None
        assert resolve_sparse_kernel("auto") == "auto"
        assert resolve_sparse_kernel("pallas:256") == "pallas:256"
        monkeypatch.setenv("PHOTON_SPARSE_KERNEL", "segment")
        assert resolve_sparse_kernel(None) == "segment"
        with pytest.raises(ValueError, match="bad sparse-kernel spec"):
            resolve_sparse_kernel("bogus")
        # ":<rows>" is pallas-only grammar — "flat:128" would silently run
        # the scatter schedule under a "flat:128" static key
        with pytest.raises(ValueError, match="bad sparse-kernel spec"):
            resolve_sparse_kernel("flat:128")

    def test_env_off_keeps_dense_path(self, rng, monkeypatch):
        from photon_ml_tpu.algorithm.random_effect import RandomEffectCoordinate
        from photon_ml_tpu.data.game import (
            RandomEffectDataConfig,
            build_random_effect_dataset,
        )
        from photon_ml_tpu.types import TaskType
        from game_test_utils import make_glmix_data

        monkeypatch.delenv("PHOTON_SPARSE_KERNEL", raising=False)
        data, _ = make_glmix_data(
            rng, num_users=4, rows_per_user_range=(3, 8), d_fixed=3,
            d_random=2,
        )
        ds = build_random_effect_dataset(
            data, RandomEffectDataConfig("userId", "per_user")
        )
        coord = RandomEffectCoordinate(ds, TaskType.LOGISTIC_REGRESSION)
        assert coord._slab is None


class TestCoordinateWiring:
    # slow: 2 full bucketed solves compile per-rung executables twice each —
    # tier-1 keeps the cheap cousins (solve bit-identity pins, env-driven
    # streaming bitwise, bucketed mesh/subs construction)
    @pytest.mark.slow
    def test_bucketed_per_bucket_bitwise(self, rng):
        from game_test_utils import make_glmix_data
        from photon_ml_tpu.algorithm.bucketed_random_effect import (
            BucketedRandomEffectCoordinate,
        )
        from photon_ml_tpu.data.game import RandomEffectDataConfig
        from photon_ml_tpu.optim.common import OptimizerConfig
        from photon_ml_tpu.ops.regularization import RegularizationContext
        from photon_ml_tpu.types import OptimizerType, TaskType

        data, _ = make_glmix_data(
            rng, num_users=8, rows_per_user_range=(3, 20), d_fixed=4,
            d_random=4,
        )
        cfg = RandomEffectDataConfig("userId", "per_user")
        resid = jnp.zeros((data.num_rows,))

        def solve(kernel):
            coord = BucketedRandomEffectCoordinate(
                data, cfg, TaskType.LOGISTIC_REGRESSION, OptimizerType.LBFGS,
                OptimizerConfig(max_iterations=12, tolerance=1e-8),
                RegularizationContext.l2(0.3), sparse_kernel=kernel,
            )
            state, _ = coord.update(resid, coord.initial_coefficients())
            return [np.asarray(s) for s in state]

        w_off = solve(SPARSE_BASELINE)
        # flat, not pallas: per-bucket WIRING is what's under test here and
        # every bucket rung pays a fresh interpret-mode compile on CPU;
        # pallas solve bit-identity is pinned one-shot/scheduled/streaming
        w_fused = solve("flat")
        assert all(np.array_equal(a, b) for a, b in zip(w_fused, w_off))

    @pytest.mark.slow  # same budget rationale as the bucketed test above
    def test_streaming_blocks_bitwise(self, rng, tmp_path):
        from game_test_utils import make_glmix_data
        from photon_ml_tpu.algorithm.streaming_random_effect import (
            StreamingRandomEffectCoordinate,
            write_re_entity_blocks,
        )
        from photon_ml_tpu.data.game import RandomEffectDataConfig
        from photon_ml_tpu.optim.common import OptimizerConfig
        from photon_ml_tpu.ops.regularization import RegularizationContext
        from photon_ml_tpu.types import OptimizerType, TaskType

        data, _ = make_glmix_data(
            rng, num_users=10, rows_per_user_range=(3, 16), d_fixed=4,
            d_random=3,
        )
        cfg = RandomEffectDataConfig("userId", "per_user")
        manifest = write_re_entity_blocks(
            data, cfg, str(tmp_path / "blocks"), block_entities=5
        )
        resid = jnp.zeros((data.num_rows,))

        def solve(kernel):
            coord = StreamingRandomEffectCoordinate(
                manifest, TaskType.LOGISTIC_REGRESSION, OptimizerType.LBFGS,
                OptimizerConfig(max_iterations=10, tolerance=1e-8),
                RegularizationContext.l2(0.3), sparse_kernel=kernel,
                state_root=str(tmp_path / f"state-{kernel}"),
            )
            state, _ = coord.update(resid, coord.initial_coefficients())
            return [state.block(i) for i in range(len(manifest.blocks))]

        w_off = solve(SPARSE_BASELINE)
        w_fused = solve("pallas")
        assert all(np.array_equal(a, b) for a, b in zip(w_fused, w_off))

    def test_block_slab_cache_is_host_resident(self, rng, tmp_path):
        """The streaming contract keeps device memory O(one block): cached
        per-block slabs must hold HOST leaves (re-uploaded per touch like
        the block tensors), not device buffers that accumulate across the
        first epoch and OOM a manifest whose dense blocks streamed fine."""
        from game_test_utils import make_glmix_data
        from photon_ml_tpu.algorithm.streaming_random_effect import (
            StreamingRandomEffectCoordinate,
            write_re_entity_blocks,
        )
        from photon_ml_tpu.data.game import RandomEffectDataConfig
        from photon_ml_tpu.optim.common import OptimizerConfig
        from photon_ml_tpu.ops.regularization import RegularizationContext
        from photon_ml_tpu.types import OptimizerType, TaskType

        data, _ = make_glmix_data(
            rng, num_users=6, rows_per_user_range=(3, 8), d_fixed=3,
            d_random=2,
        )
        cfg = RandomEffectDataConfig("userId", "per_user")
        manifest = write_re_entity_blocks(
            data, cfg, str(tmp_path / "blocks"), block_entities=3
        )
        coord = StreamingRandomEffectCoordinate(
            manifest, TaskType.LOGISTIC_REGRESSION, OptimizerType.LBFGS,
            OptimizerConfig(max_iterations=3, tolerance=1e-6),
            RegularizationContext.l2(0.3), sparse_kernel="scatter",
            state_root=str(tmp_path / "state"),
        )
        coord.update(
            jnp.zeros((data.num_rows,)), coord.initial_coefficients()
        )
        slabs = [s for s in coord._sparse_slabs.values() if s is not None]
        assert slabs, "no block selected the sparse path"
        assert all(
            isinstance(s.idx, np.ndarray) and isinstance(s.val, np.ndarray)
            for s in slabs
        )


class TestMeshPathEnvImmunity:
    def test_distributed_solver_ignores_env_spec(self, rng, monkeypatch):
        """Regression: the distributed RE solver re-constructs the
        coordinate (dataclasses.replace) INSIDE shard_map — with
        PHOTON_SPARSE_KERNEL set it used to re-resolve the env under the
        trace and die on the traced-construction guard. The mesh path has
        no per-shard slab selection: it must pin sparse off and run."""
        from game_test_utils import make_glmix_data
        from photon_ml_tpu.algorithm.random_effect import RandomEffectCoordinate
        from photon_ml_tpu.data.game import (
            RandomEffectDataConfig,
            build_random_effect_dataset,
        )
        from photon_ml_tpu.optim.common import OptimizerConfig
        from photon_ml_tpu.ops.regularization import RegularizationContext
        from photon_ml_tpu.parallel.distributed import DistributedRandomEffectSolver
        from photon_ml_tpu.parallel.mesh import MeshContext, data_mesh
        from photon_ml_tpu.types import OptimizerType, TaskType

        data, _ = make_glmix_data(
            rng, num_users=8, rows_per_user_range=(3, 10), d_fixed=3,
            d_random=2,
        )
        ds = build_random_effect_dataset(
            data, RandomEffectDataConfig("userId", "per_user")
        )
        coord = RandomEffectCoordinate(
            ds, TaskType.LOGISTIC_REGRESSION, OptimizerType.LBFGS,
            OptimizerConfig(max_iterations=10, tolerance=1e-7),
            RegularizationContext.l2(0.5),
        )
        solver = DistributedRandomEffectSolver(coord, MeshContext(data_mesh()))
        resid = jnp.zeros((data.num_rows,))
        monkeypatch.setenv("PHOTON_SPARSE_KERNEL", "auto")
        coefs, _ = solver.update(resid, solver.initial_coefficients())
        assert np.isfinite(np.asarray(coefs)).all()

    def test_bucketed_mesh_subs_skip_slab_build(self, rng, monkeypatch):
        """Under mesh_ctx the distributed solvers pin sparse off at the
        shard level — the per-bucket subs must not race/build slabs that
        update() will never use (wasted compiles + device-resident idx/val
        held for the coordinate's lifetime)."""
        from game_test_utils import make_glmix_data
        from photon_ml_tpu.algorithm.bucketed_random_effect import (
            BucketedRandomEffectCoordinate,
        )
        from photon_ml_tpu.data.game import RandomEffectDataConfig
        from photon_ml_tpu.parallel.mesh import MeshContext, data_mesh
        from photon_ml_tpu.types import TaskType

        monkeypatch.setenv("PHOTON_SPARSE_KERNEL", "auto")
        data, _ = make_glmix_data(
            rng, num_users=6, rows_per_user_range=(3, 8), d_fixed=3,
            d_random=2,
        )
        coord = BucketedRandomEffectCoordinate(
            data, RandomEffectDataConfig("userId", "per_user"),
            TaskType.LOGISTIC_REGRESSION, mesh_ctx=MeshContext(data_mesh()),
        )
        assert all(sub._slab is None for sub in coord._subs)


class TestStreamingEnvActivation:
    def test_env_spec_drives_streaming_blocks_and_score(self, rng, tmp_path,
                                                        monkeypatch):
        """Regression: the streaming coordinate owns slab selection; its
        per-block sub-coordinates (built INSIDE the block jit, where ds.x
        is a tracer) must never re-resolve PHOTON_SPARSE_KERNEL themselves
        — with the env set, update AND score used to die on the
        traced-construction guard."""
        from game_test_utils import make_glmix_data
        from photon_ml_tpu.algorithm.streaming_random_effect import (
            StreamingRandomEffectCoordinate,
            write_re_entity_blocks,
        )
        from photon_ml_tpu.data.game import RandomEffectDataConfig
        from photon_ml_tpu.optim.common import OptimizerConfig
        from photon_ml_tpu.ops.regularization import RegularizationContext
        from photon_ml_tpu.types import OptimizerType, TaskType

        data, _ = make_glmix_data(
            rng, num_users=6, rows_per_user_range=(3, 10), d_fixed=3,
            d_random=3,
        )
        cfg = RandomEffectDataConfig("userId", "per_user")
        manifest = write_re_entity_blocks(
            data, cfg, str(tmp_path / "blocks"), block_entities=3
        )
        resid = jnp.zeros((data.num_rows,))

        def solve(env, tag):
            if env is None:
                monkeypatch.delenv("PHOTON_SPARSE_KERNEL", raising=False)
            else:
                monkeypatch.setenv("PHOTON_SPARSE_KERNEL", env)
            coord = StreamingRandomEffectCoordinate(
                manifest, TaskType.LOGISTIC_REGRESSION, OptimizerType.LBFGS,
                OptimizerConfig(max_iterations=8, tolerance=1e-8),
                RegularizationContext.l2(0.3),
                state_root=str(tmp_path / f"state-{tag}"),
            )
            state, _ = coord.update(resid, coord.initial_coefficients())
            scores = np.asarray(coord.score(state))
            return [state.block(i) for i in range(len(manifest.blocks))], scores

        w_env, s_env = solve("flat", "flat")
        # the flat family is bitwise vs the segment baseline end-to-end
        w_seg, s_seg = solve("segment", "seg")
        assert all(np.array_equal(a, b) for a, b in zip(w_env, w_seg))
        # scoring is margin-only (dense path) — identical coefficients in,
        # identical scores out
        assert np.array_equal(s_env, s_seg)
