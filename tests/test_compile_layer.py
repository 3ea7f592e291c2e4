"""Compile-once execution layer (photon_ml_tpu/compile/).

Coverage the ISSUE names: ladder math, masked-padding bit-identity for the
bucketed RE update/score and the streaming chunk passes, the masked
objective, a recompile-count assertion (M same-ladder blocks compile once,
via CompileStats), persistent-cache enablement, and donation semantics.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from game_test_utils import make_glmix_data

from photon_ml_tpu.compile import (
    ShapeBucketer,
    canonicalize_re_dataset,
    compile_stats,
    donation_enabled,
    instrumented_jit,
    pad_axis,
    pad_glm_chunk,
    resolve_bucketer,
)
from photon_ml_tpu.data.game import RandomEffectDataConfig, build_random_effect_dataset
from photon_ml_tpu.optim.common import OptimizerConfig
from photon_ml_tpu.ops.regularization import RegularizationContext
from photon_ml_tpu.types import OptimizerType, TaskType


class TestLadder:
    def test_canon_rounds_up_geometric(self):
        b = ShapeBucketer(base=8, growth=2.0)
        assert [b.canon(n) for n in (1, 7, 8, 9, 16, 17, 100)] == [
            8, 8, 8, 16, 16, 32, 128,
        ]

    def test_canon_passes_nonpositive_through(self):
        b = ShapeBucketer()
        assert b.canon(0) == 0

    def test_fractional_growth_climbs(self):
        b = ShapeBucketer(base=8, growth=1.5)
        rungs = sorted({b.canon(n) for n in range(1, 100)})
        assert rungs[0] == 8
        assert all(y > x for x, y in zip(rungs, rungs[1:]))
        assert all(b.canon(r) == r for r in rungs)  # rungs are fixed points

    def test_bad_params_rejected(self):
        with pytest.raises(ValueError):
            ShapeBucketer(base=0)
        with pytest.raises(ValueError):
            ShapeBucketer(growth=1.0)

    def test_resolve_spellings(self, monkeypatch):
        assert resolve_bucketer("off") is None
        assert resolve_bucketer("on") == ShapeBucketer()
        assert resolve_bucketer("16:1.5") == ShapeBucketer(16, 1.5)
        assert resolve_bucketer(False) is None
        with pytest.raises(ValueError):
            resolve_bucketer("sideways")
        monkeypatch.setenv("PHOTON_SHAPE_LADDER", "4:2")
        assert resolve_bucketer(None) == ShapeBucketer(4, 2.0)
        monkeypatch.delenv("PHOTON_SHAPE_LADDER")
        assert resolve_bucketer(None) is None

    def test_pad_axis(self):
        a = np.arange(6, dtype=np.float32).reshape(2, 3)
        p = pad_axis(a, 0, 4, -1.0)
        assert p.shape == (4, 3) and (p[2:] == -1.0).all()
        assert pad_axis(a, 1, 3, 0).shape == (2, 3)  # already there: no-op

    def test_pad_glm_chunk_weights_zero(self):
        x = np.ones((5, 3), np.float32)
        y = np.ones(5, np.float32)
        off = np.ones(5, np.float32)
        wt = np.ones(5, np.float32)
        xp, yp, op, wp = pad_glm_chunk((x, y, off, wt), ShapeBucketer(8, 2.0))
        assert xp.shape == (8, 3) and wp.shape == (8,)
        assert (wp[5:] == 0.0).all()
        assert pad_glm_chunk((x, y, off, wt), None) == (x, y, off, wt)


@pytest.fixture(scope="module")
def glmix_small():
    rng = np.random.default_rng(77)
    data, _ = make_glmix_data(
        rng, num_users=40, rows_per_user_range=(4, 12), d_fixed=4, d_random=4
    )
    return data


class TestMaskedPaddingExactness:
    """Padded-vs-unpadded bit-identity at the canonical shapes the layer
    actually produces (small solver extents: appended zeros are exact
    no-ops and XLA keeps the real elements' reduction order)."""

    def test_masked_objective_zero_weight_rows_exact(self):
        from photon_ml_tpu.ops import losses
        from photon_ml_tpu.ops.features import DenseFeatures
        from photon_ml_tpu.ops.normalization import NormalizationContext
        from photon_ml_tpu.ops.objective import GLMBatch, GLMObjective

        rng = np.random.default_rng(3)
        n, d = 11, 5
        x = rng.normal(size=(n, d)).astype(np.float32)
        y = (rng.random(n) < 0.5).astype(np.float32)
        wt = rng.random(n).astype(np.float32)
        w = rng.normal(size=d).astype(np.float32)
        obj = GLMObjective(losses.logistic)
        norm = NormalizationContext.identity()

        def vg(x_, y_, wt_):
            batch = GLMBatch(
                DenseFeatures(jnp.asarray(x_)), jnp.asarray(y_),
                jnp.zeros(len(y_), jnp.float32), jnp.asarray(wt_),
            )
            return obj.value_and_grad(jnp.asarray(w), batch, norm, 0.1)

        f0, g0 = jax.jit(vg)(x, y, wt)
        xp, yp, _, wp = pad_glm_chunk(
            (x, y, np.zeros(n, np.float32), wt), ShapeBucketer(8, 2.0)
        )
        f1, g1 = jax.jit(vg)(xp, yp, wp)
        assert np.asarray(f0).tobytes() == np.asarray(f1).tobytes()
        assert np.asarray(g0).tobytes() == np.asarray(g1).tobytes()

    @pytest.mark.slow  # ~19s: tier-1 rides the 870s budget's edge; the masked-padding exactness contract stays tier-1 via test_masked_objective_zero_weight_rows_exact and the bucketed export pin test_streaming_chunk_vg_bit_identical_and_fewer_compiles
    def test_bucketed_update_and_score_bit_identical(self, glmix_small):
        from photon_ml_tpu.algorithm.bucketed_random_effect import (
            BucketedRandomEffectCoordinate,
        )

        def train(bucketer):
            coord = BucketedRandomEffectCoordinate(
                glmix_small,
                RandomEffectDataConfig("userId", "per_user"),
                TaskType.LOGISTIC_REGRESSION,
                optimizer_config=OptimizerConfig(max_iterations=8, tolerance=1e-7),
                regularization=RegularizationContext.l2(0.1),
                bucketer=bucketer,
            )
            resid = jnp.zeros((glmix_small.num_rows,), jnp.float32)
            state, _ = coord.update(resid, coord.initial_coefficients())
            return coord, state, np.asarray(coord.score(state))

        coord_off, state_off, score_off = train(None)
        coord_on, state_on, score_on = train(ShapeBucketer(8, 2.0))
        assert score_off.tobytes() == score_on.tobytes()
        for w_off, w_on, sub_off in zip(
            state_off, state_on, coord_off._subs
        ):
            e, d = sub_off.dataset.num_entities, sub_off.dataset.local_dim
            # padding appends lanes/cols at the END: real lanes lead
            assert np.asarray(w_on).shape >= np.asarray(w_off).shape
            assert (
                np.asarray(w_on)[:e, :d].tobytes()
                == np.asarray(w_off).tobytes()
            )
            # padded lanes/cols solve all-zero problems: exactly 0
            assert not np.asarray(w_on)[e:].any()
            assert not np.asarray(w_on)[:, d:].any()

    def test_canonicalized_dataset_rejects_random_projection(self, glmix_small):
        ds = build_random_effect_dataset(
            glmix_small,
            RandomEffectDataConfig(
                "userId", "per_user", projector="RANDOM", random_projection_dim=3
            ),
        )
        with pytest.raises(ValueError, match="RANDOM"):
            canonicalize_re_dataset(ds, ShapeBucketer())

    def test_streaming_chunk_vg_bit_identical_and_fewer_compiles(self):
        from photon_ml_tpu.ops import losses
        from photon_ml_tpu.ops.normalization import NormalizationContext
        from photon_ml_tpu.ops.objective import GLMObjective
        from photon_ml_tpu.optim.streaming import (
            ChunkedGLMSource,
            make_streaming_value_and_grad,
        )

        rng = np.random.default_rng(5)
        n, d = 40, 6
        x = rng.normal(size=(n, d)).astype(np.float32)
        y = (rng.random(n) < 0.5).astype(np.float32)
        w = jnp.asarray(rng.normal(size=d).astype(np.float32))
        obj = GLMObjective(losses.logistic)
        norm = NormalizationContext.identity()
        # chunk_rows=7 is off-ladder: chunks are 7,7,7,7,7,5 -> TWO compiled
        # partials without canonicalization, ONE (all pad to 8) with it
        src = ChunkedGLMSource.from_arrays(x, y, chunk_rows=7)

        compile_stats.reset()
        vg_off = make_streaming_value_and_grad(src, obj, norm, l2_weight=0.1,
                                               prefetch_depth=0, bucketer=None)
        f0, g0 = jax.device_get(vg_off(w))
        traces_off = compile_stats.traces_of("streaming.vg_chunk")

        compile_stats.reset()
        vg_on = make_streaming_value_and_grad(
            src, obj, norm, l2_weight=0.1, prefetch_depth=0,
            bucketer=ShapeBucketer(8, 2.0),
        )
        f1, g1 = jax.device_get(vg_on(w))
        traces_on = compile_stats.traces_of("streaming.vg_chunk")

        assert np.asarray(f0).tobytes() == np.asarray(f1).tobytes()
        assert np.asarray(g0).tobytes() == np.asarray(g1).tobytes()
        assert traces_off == 2
        assert traces_on == 1


@pytest.fixture(scope="module")
def uniform_glmix():
    """Every entity has the same row count -> every streaming block lands
    on ONE ladder shape (the 'M same-ladder blocks' premise)."""
    rng = np.random.default_rng(99)
    data, _ = make_glmix_data(
        rng, num_users=48, rows_per_user_range=(8, 9), d_fixed=4, d_random=4
    )
    return data


class TestRecompileCounts:
    def test_same_ladder_blocks_compile_once(self, uniform_glmix, tmp_path):
        from photon_ml_tpu.algorithm.streaming_random_effect import (
            StreamingRandomEffectCoordinate,
            write_re_entity_blocks,
        )

        manifest = write_re_entity_blocks(
            uniform_glmix,
            RandomEffectDataConfig("userId", "per_user"),
            str(tmp_path / "blocks"),
            block_entities=8,
            bucketer=ShapeBucketer(8, 2.0),
        )
        assert len(manifest.blocks) == 6
        assert manifest.ladder == "8:2"
        # every block identical ladder shape -> one (E, D) stack signature
        assert len({(b["num_entities"], b["local_dim"]) for b in manifest.blocks}) == 1

        coord = StreamingRandomEffectCoordinate(
            manifest, TaskType.LOGISTIC_REGRESSION,
            optimizer_config=OptimizerConfig(max_iterations=6, tolerance=1e-7),
            regularization=RegularizationContext.l2(0.1),
            state_root=str(tmp_path / "state"),
            prefetch_depth=0,
        )
        resid = jnp.zeros((uniform_glmix.num_rows,), jnp.float32)
        compile_stats.reset()
        state, _ = coord.update(resid, coord.initial_coefficients())
        stats = compile_stats.snapshot()["streaming_re.block_update"]
        # THE assertion of the ISSUE: M same-ladder blocks compile ONCE
        assert stats["calls"] == 6
        assert stats["traces"] == 1
        assert stats["cache_hits"] == 5

        compile_stats.reset()
        coord.score(state)
        stats = compile_stats.snapshot()["streaming_re.block_score"]
        assert stats["calls"] == 6
        assert stats["traces"] == 1

    def test_streaming_ladder_on_off_coefficients_match(self, uniform_glmix, tmp_path):
        from photon_ml_tpu.algorithm.streaming_random_effect import (
            StreamingRandomEffectCoordinate,
            write_re_entity_blocks,
        )

        def train(bucketer, tag):
            manifest = write_re_entity_blocks(
                uniform_glmix,
                RandomEffectDataConfig("userId", "per_user"),
                str(tmp_path / f"blocks-{tag}"),
                block_entities=8,
                bucketer=bucketer,
            )
            coord = StreamingRandomEffectCoordinate(
                manifest, TaskType.LOGISTIC_REGRESSION,
                optimizer_config=OptimizerConfig(max_iterations=6, tolerance=1e-7),
                regularization=RegularizationContext.l2(0.1),
                state_root=str(tmp_path / f"state-{tag}"),
                prefetch_depth=0,
            )
            resid = jnp.zeros((uniform_glmix.num_rows,), jnp.float32)
            state, _ = coord.update(resid, coord.initial_coefficients())
            blocks = [state.block(i) for i in range(len(manifest.blocks))]
            return manifest, blocks, np.asarray(coord.score(state))

        m_off, blocks_off, score_off = train(None, "off")
        m_on, blocks_on, score_on = train(ShapeBucketer(8, 2.0), "on")
        assert score_off.tobytes() == score_on.tobytes()
        for boff, bon, meta in zip(blocks_off, blocks_on, m_off.blocks):
            e, d = meta["num_entities"], meta["local_dim"]
            assert bon[:e, :d].tobytes() == boff.tobytes()

    def test_ladder_manifest_entity_export(self, uniform_glmix, tmp_path):
        """Model-save paths on a CANONICALIZED manifest: pad rows carry
        entity_pos -1 beyond the rows dense_ids covers, and the vocab /
        export maps must slice to the real extent (regression: boolean-
        index length mismatch caught by the driver drive)."""
        from photon_ml_tpu.algorithm.streaming_random_effect import (
            StreamingRandomEffectCoordinate,
            write_re_entity_blocks,
        )

        def export(bucketer, tag):
            manifest = write_re_entity_blocks(
                uniform_glmix,
                RandomEffectDataConfig("userId", "per_user"),
                str(tmp_path / f"xblocks-{tag}"),
                block_entities=8,
                bucketer=bucketer,
            )
            coord = StreamingRandomEffectCoordinate(
                manifest, TaskType.LOGISTIC_REGRESSION,
                optimizer_config=OptimizerConfig(max_iterations=6, tolerance=1e-7),
                regularization=RegularizationContext.l2(0.1),
                state_root=str(tmp_path / f"xstate-{tag}"),
                prefetch_depth=0,
            )
            resid = jnp.zeros((uniform_glmix.num_rows,), jnp.float32)
            state, _ = coord.update(resid, coord.initial_coefficients())
            block_of, pos_in = coord.vocab_position_maps()
            return coord.entity_means_by_raw_id(state), block_of, pos_in

        means_off, _, _ = export(None, "off")
        means_on, block_of, pos_in = export(ShapeBucketer(8, 2.0), "on")
        assert set(means_on) == set(means_off)
        assert (block_of >= 0).all() and (pos_in >= 0).all()
        for k in means_off:
            assert means_on[k].tobytes() == means_off[k].tobytes()

    @pytest.mark.slow  # ~9s: ladder export stays tier-1 via test_ladder_manifest_entity_export and compile-count discipline via test_same_ladder_blocks_compile_once
    def test_bucketed_entity_export_with_ladder(self, glmix_small):
        from photon_ml_tpu.algorithm.bucketed_random_effect import (
            BucketedRandomEffectCoordinate,
        )

        def export(bucketer):
            coord = BucketedRandomEffectCoordinate(
                glmix_small,
                RandomEffectDataConfig("userId", "per_user"),
                TaskType.LOGISTIC_REGRESSION,
                optimizer_config=OptimizerConfig(max_iterations=6, tolerance=1e-7),
                regularization=RegularizationContext.l2(0.1),
                bucketer=bucketer,
            )
            resid = jnp.zeros((glmix_small.num_rows,), jnp.float32)
            state, _ = coord.update(resid, coord.initial_coefficients())
            return coord.entity_means_by_raw_id(state)

        means_off = export(None)
        means_on = export(ShapeBucketer(8, 2.0))
        assert set(means_on) == set(means_off)
        for k in means_off:
            assert means_on[k].tobytes() == means_off[k].tobytes()


class TestCompileStats:
    def test_trace_and_hit_counting(self):
        compile_stats.reset()
        f = instrumented_jit(lambda x: x * 2 + 1, site="test.site")
        for n in (4, 4, 8, 4):
            f(jnp.ones((n,)))
        s = compile_stats.snapshot()["test.site"]
        assert s["calls"] == 4 and s["traces"] == 2 and s["cache_hits"] == 2
        assert s["compile_seconds"] > 0
        assert "test.site" in compile_stats.summary()

    def test_donation_composes_with_instrumentation(self):
        f = instrumented_jit(lambda x: x + 1, site="test.donate",
                             donate_argnums=(0,))
        a = jnp.ones((16,))
        f(a)
        with pytest.raises(RuntimeError, match="deleted"):
            _ = a + 1  # the input buffer was genuinely donated

    def test_donation_env_gate(self, monkeypatch):
        assert donation_enabled()
        monkeypatch.setenv("PHOTON_DONATE", "0")
        assert not donation_enabled()


class TestPersistentCache:
    @pytest.fixture(autouse=True)
    def _restore_cache_config(self):
        saved = {
            k: getattr(jax.config, k) for k in (
                "jax_compilation_cache_dir",
                "jax_persistent_cache_min_entry_size_bytes",
                "jax_persistent_cache_min_compile_time_secs",
                "jax_compilation_cache_include_metadata_in_key",
                "jax_traceback_in_locations_limit",
            )
        }
        yield
        for k, v in saved.items():
            jax.config.update(k, v)
        from jax._src import compilation_cache as _cc

        _cc.reset_cache()

    def test_enable_writes_and_hits(self, tmp_path, monkeypatch):
        from photon_ml_tpu import compat

        monkeypatch.delenv(compat.COMPILE_CACHE_ENV, raising=False)
        cache_dir = str(tmp_path / "xla-cache")
        compile_stats.install_xla_listeners()
        assert compat.enable_persistent_cache(cache_dir) == cache_dir
        compile_stats.reset()

        def scoped(name):
            def f(x):
                with jax.named_scope(name):
                    return x * 3 + 2

            return jax.jit(f)

        scoped("pml.test.a")(jnp.ones((64,)))
        assert os.listdir(cache_dir), "no cache entries written"
        misses = compile_stats.xla_cache_misses
        assert misses >= 1
        # an IDENTICAL computation under a fresh jit wrapper must come
        # from the persistent cache, not a new XLA compile
        scoped("pml.test.a")(jnp.ones((64,)))
        assert compile_stats.xla_cache_hits >= 1
        assert compile_stats.xla_cache_misses == misses
        # the same arithmetic under another scope name is another entry: a
        # trace must show the names of the code that ran, not those of the
        # build that filled the cache
        scoped("pml.test.b")(jnp.ones((64,)))
        assert compile_stats.xla_cache_misses == misses + 1

    @staticmethod
    def _min_knobs():
        return (
            jax.config.jax_persistent_cache_min_entry_size_bytes,
            jax.config.jax_persistent_cache_min_compile_time_secs,
        )

    def test_environment_places_the_cache(self, tmp_path, monkeypatch):
        """With JAX_COMPILATION_CACHE_DIR set, no other directory is set in
        code — not even a driver's --persistent-cache."""
        from photon_ml_tpu import compat

        env_dir = str(tmp_path / "from-env")
        monkeypatch.setenv(compat.COMPILE_CACHE_ENV, env_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
        logged = []
        got = compat.start_up(logged.append, str(tmp_path / "from-flag"))
        assert got == env_dir
        assert jax.config.jax_compilation_cache_dir == env_dir
        assert not os.path.exists(tmp_path / "from-flag")
        assert self._min_knobs() == (-1, 0)
        assert logged[0].startswith("platform: cpu, device_kind: ")
        assert env_dir in logged[1]

    def test_default_is_one_fixed_path_in_the_checkout(self, tmp_path,
                                                       monkeypatch):
        """Unset, the directory is the same in-checkout path whatever the
        cwd or pid of the caller — the path is part of the cache key."""
        import subprocess
        import sys

        from photon_ml_tpu import compat

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        want = os.path.join(repo, ".jax_compilation_cache")
        monkeypatch.delenv(compat.COMPILE_CACHE_ENV, raising=False)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
        assert compat.enable_persistent_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert self._min_knobs() == (-1, 0)
        # another process, another cwd
        env = {k: v for k, v in os.environ.items()
               if k != compat.COMPILE_CACHE_ENV}
        env["PYTHONPATH"] = repo
        out = subprocess.run(
            [sys.executable, "-c",
             "from photon_ml_tpu import compat; "
             "print(compat.enable_persistent_cache())"],
            cwd=str(tmp_path), env=env, capture_output=True, text=True,
            timeout=120,
        )
        assert out.returncode == 0, out.stderr
        assert out.stdout.strip().splitlines()[-1] == want


class TestDescentDonation:
    def test_run_results_identical_donation_on_off(self, glmix_small, monkeypatch):
        from photon_ml_tpu.algorithm import (
            CoordinateDescent,
            FixedEffectCoordinate,
            RandomEffectCoordinate,
        )
        from photon_ml_tpu.data.game import build_fixed_effect_batch
        from photon_ml_tpu.ops import losses
        from photon_ml_tpu.optim.problem import GLMOptimizationProblem

        labels = jnp.asarray(glmix_small.response)
        loss_fn = lambda s: jnp.sum(losses.logistic.loss(s, labels))

        def build_cd():
            fixed = FixedEffectCoordinate(
                build_fixed_effect_batch(glmix_small, "global", dense=True),
                GLMOptimizationProblem(
                    TaskType.LOGISTIC_REGRESSION, OptimizerType.LBFGS,
                    OptimizerConfig(max_iterations=10, tolerance=1e-7),
                    RegularizationContext.l2(0.01),
                ),
            )
            rand = RandomEffectCoordinate(
                build_random_effect_dataset(
                    glmix_small, RandomEffectDataConfig("userId", "per_user")
                ),
                TaskType.LOGISTIC_REGRESSION,
                optimizer_config=OptimizerConfig(max_iterations=8, tolerance=1e-6),
                regularization=RegularizationContext.l2(0.1),
            )
            return CoordinateDescent({"fixed": fixed, "re": rand}, loss_fn)

        monkeypatch.setenv("PHOTON_DONATE", "0")
        r_off = build_cd().run(num_iterations=2, num_rows=glmix_small.num_rows)
        monkeypatch.setenv("PHOTON_DONATE", "1")
        cd = build_cd()
        assert cd._donate
        r_on = cd.run(num_iterations=2, num_rows=glmix_small.num_rows)
        assert (
            np.asarray(r_on.total_scores).tobytes()
            == np.asarray(r_off.total_scores).tobytes()
        )
        for n in ("fixed", "re"):
            assert (
                np.asarray(r_on.coefficients[n]).tobytes()
                == np.asarray(r_off.coefficients[n]).tobytes()
            )

    def test_guard_disables_donation(self, glmix_small):
        from photon_ml_tpu.algorithm import CoordinateDescent, RandomEffectCoordinate
        from photon_ml_tpu.ops import losses
        from photon_ml_tpu.resilience import DivergenceGuard

        labels = jnp.asarray(glmix_small.response)
        rand = RandomEffectCoordinate(
            build_random_effect_dataset(
                glmix_small, RandomEffectDataConfig("userId", "per_user")
            ),
            TaskType.LOGISTIC_REGRESSION,
            optimizer_config=OptimizerConfig(max_iterations=4, tolerance=1e-6),
            regularization=RegularizationContext.l2(0.1),
        )
        cd = CoordinateDescent(
            {"re": rand},
            lambda s: jnp.sum(losses.logistic.loss(s, labels)),
            divergence_guard=DivergenceGuard(mode="rollback"),
        )
        assert not cd._donate  # rollback needs the pre-update state alive
        cd.run(num_iterations=1, num_rows=glmix_small.num_rows)
