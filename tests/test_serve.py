"""Online scoring service tests (photon_ml_tpu/serve).

Covers the serving acceptance claims end-to-end on CPU:

  * ModelStore export/open: mmap'd slabs, entity->row probes, feature maps
    shared with the batch driver via --offheap-indexmap-dir.
  * MicroBatcher: coalescing, ladder padding, response slicing, error fans.
  * BITWISE parity: concurrently-served scores equal the batch
    game_scoring_driver's device output for the same inputs (offset term
    included), which itself equals the --host-scoring oracle.
  * Warm start: a second server process over a filled persistent XLA cache
    reports zero new compiles (CompileStats-asserted).
  * Live model swap: by-reference roll with zero new compiles, zero
    dropped requests, new coefficients served after.
  * JSON-lines loop: scoring, stats, swap, shutdown, malformed input.
"""

import concurrent.futures
import io
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from game_test_utils import (
    game_avro_records,
    make_glmix_data,
    save_synthetic_game_model,
    serve_requests_from_records,
    write_game_avro,
)

from photon_ml_tpu.compile import ShapeBucketer, compile_stats
from photon_ml_tpu.serve import (
    MicroBatcher,
    ModelStore,
    ModelSwapper,
    RowBatch,
    ScoringServer,
    ServeStats,
    build_model_store,
    is_model_store,
)

pytestmark = pytest.mark.serve

SECTIONS = {"global": ["fixedFeatures"], "per_user": ["userFeatures"]}
SECTIONS_FLAG = "global:fixedFeatures|per_user:userFeatures"


@pytest.fixture(scope="module")
def serving_world(tmp_path_factory):
    """One synthetic model + avro scoring inputs (with offsets) + built
    serve store, shared by the module."""
    base = tmp_path_factory.mktemp("serve")
    rng = np.random.default_rng(42)
    data, truth = make_glmix_data(
        rng, num_users=10, rows_per_user_range=(6, 12), d_fixed=5, d_random=3
    )
    offsets = rng.normal(size=data.num_rows).astype(np.float32)
    model_dir = str(base / "model")
    w_fixed, entity_means, fmap, umap = save_synthetic_game_model(
        model_dir, rng, d_fixed=5, d_random=3, num_users=10
    )
    in_dir = base / "in"
    in_dir.mkdir()
    write_game_avro(
        str(in_dir / "part-0.avro"), data, range(data.num_rows), truth, offsets
    )
    store_dir = str(base / "store")
    build_model_store(model_dir, store_dir, bucketer=ShapeBucketer())
    records = list(game_avro_records(data, range(data.num_rows), truth, offsets))
    return {
        "base": base,
        "model_dir": model_dir,
        "in_dir": str(in_dir),
        "store_dir": store_dir,
        "records": records,
        "requests": serve_requests_from_records(records),
        "w_fixed": w_fixed,
        "entity_means": entity_means,
        "data": data,
    }


def _run_scoring_driver(world, out_dir, host=False):
    from photon_ml_tpu.cli import game_scoring_driver

    args = [
        "--input-dirs", world["in_dir"],
        "--game-model-input-dir", world["model_dir"],
        "--output-dir", str(out_dir),
        "--offheap-indexmap-dir", os.path.join(world["store_dir"], "features"),
        "--feature-shard-id-to-feature-section-keys-map", SECTIONS_FLAG,
        "--evaluator-type", "AUC,RMSE",
        "--delete-output-dir-if-exists", "true",
    ]
    if host:
        args += ["--host-scoring", "true"]
    return game_scoring_driver.main(args)


# ---------------------------------------------------------------------------
# ModelStore
# ---------------------------------------------------------------------------


class TestModelStore:
    def test_detect_and_meta(self, serving_world):
        assert is_model_store(serving_world["store_dir"])
        store = ModelStore(serving_world["store_dir"])
        assert [f.name for f in store.fixed] == ["fixed"]
        assert [r.name for r in store.random] == ["per-user"]
        assert store.meta["shards"]["global"]["dim"] == 6  # 5 features + intercept
        store.close()

    def test_fixed_coefficients_roundtrip(self, serving_world):
        store = ModelStore(serving_world["store_dir"])
        w = np.asarray(store.fixed[0].coefficients)
        # densified against the STORE's map: compare value multiset (the
        # store's feature order may differ from the training IndexMap's)
        assert sorted(np.round(w, 6)) == sorted(
            np.round(serving_world["w_fixed"], 6)
        )
        store.close()

    def test_entity_rows_and_slab(self, serving_world):
        store = ModelStore(serving_world["store_dir"])
        re = store.random[0]
        assert re.entities == 10
        # ladder-padded slab rows (10 -> 16 on the default 8:2 ladder)
        assert re.slab.shape[0] == 16
        umap = store.feature_maps["per_user"]
        for raw, vec in serving_world["entity_means"].items():
            row = store.entity_row("per-user", raw)
            assert 0 <= row < 10
            # value multiset parity per entity row (store feature order)
            assert sorted(np.round(np.asarray(re.slab[row]), 6)) == sorted(
                np.round(vec, 6)
            )
        assert store.entity_row("per-user", "never-seen") == -1
        assert store.entity_row("per-user", None) == -1
        # padded rows are all-zero
        assert not np.asarray(re.slab[10:]).any()
        assert len(umap) == 4
        store.close()

    def test_checkpoint_ref_roundtrip(self, serving_world):
        from photon_ml_tpu.checkpoint import CheckpointRefError, rebuild_from_ref

        store = ModelStore(serving_world["store_dir"])
        ref = store.__checkpoint_ref__()
        rebuilt = rebuild_from_ref(store, ref)
        assert rebuilt.store_dir == store.store_dir
        rebuilt.close()
        with pytest.raises(CheckpointRefError):
            rebuild_from_ref(store, {"kind": "game-serve-store",
                                     "store_dir": "/nonexistent"})
        with pytest.raises(CheckpointRefError):
            rebuild_from_ref(store, {"kind": "something-else"})
        store.close()

    def test_unknown_coordinate_raises(self, serving_world):
        store = ModelStore(serving_world["store_dir"])
        with pytest.raises(KeyError):
            store.entity_row("no-such-coordinate", "u0")
        store.close()


# ---------------------------------------------------------------------------
# MicroBatcher
# ---------------------------------------------------------------------------


def _one_row_batch(value: float, k: int = 2) -> RowBatch:
    return RowBatch(
        offset=np.asarray([value], np.float32),
        shard_idx={"s": np.zeros((1, k), np.int32)},
        shard_val={"s": np.zeros((1, k), np.float32)},
        ent_row={"c": np.asarray([-1], np.int32)},
    )


class TestMicroBatcher:
    def test_coalesces_and_slices(self):
        seen = []

        def score(batch):
            seen.append(batch.num_rows)
            return batch.offset * 2.0

        b = MicroBatcher(
            score, max_batch_rows=64, max_wait_ms=50.0,
            bucketer=ShapeBucketer(), stats=ServeStats(),
        ).start()
        futs = [b.submit(_one_row_batch(float(i))) for i in range(20)]
        got = np.concatenate([f.result() for f in futs])
        np.testing.assert_array_equal(got, np.arange(20, dtype=np.float32) * 2)
        b.close()
        # coalesced: far fewer device calls than requests, every batch
        # padded to a ladder rung
        assert len(seen) < 20
        assert all(n in (8, 16, 32, 64) for n in seen)
        snap = b.stats.snapshot()
        assert snap["requests"] == 20
        assert 0 < snap["batch_fill_ratio"] <= 1.0

    def test_wait_bound_flushes_single_request(self):
        b = MicroBatcher(
            lambda batch: batch.offset, max_batch_rows=1024, max_wait_ms=5.0,
            bucketer=None, stats=ServeStats(),
        ).start()
        # one lonely request must not wait for a full batch
        assert b.submit(_one_row_batch(3.0)).result(timeout=10) == [3.0]
        b.close()

    def test_batch_cap_flushes_without_wait(self):
        release = threading.Event()
        calls = []

        def score(batch):
            release.wait(10)
            calls.append(batch.num_rows)
            return batch.offset

        b = MicroBatcher(
            score, max_batch_rows=4, max_wait_ms=10_000.0,
            bucketer=None, stats=ServeStats(),
        ).start()
        futs = [b.submit(_one_row_batch(float(i))) for i in range(8)]
        release.set()
        for f in futs:
            f.result(timeout=10)
        b.close()
        # a saturated queue never waits the window out: row cap flushes
        assert max(calls) <= 4 and len(calls) >= 2

    @pytest.mark.slow  # ~10s randomized sweep; the cap contract stays tier-1 via test_batch_cap_flushes_without_wait / test_coalesces_and_slices
    def test_multi_row_requests_never_overshoot_cap(self):
        """A coalesced batch must stay <= max_batch_rows even when multi-
        row requests arrive (overshoot would pad to an unwarmed ladder
        rung — a request-path compile); the overflow request is carried to
        the next batch instead."""
        release = threading.Event()
        calls = []

        def score(batch):
            release.wait(30)
            calls.append(batch.num_rows)
            return batch.offset

        b = MicroBatcher(
            score, max_batch_rows=8, max_wait_ms=10_000.0,
            bucketer=None, stats=ServeStats(),
        ).start()
        sizes = [6, 5, 4, 8, 3]  # 6+5 would overshoot; so would 4+8
        futs = [
            b.submit(
                RowBatch(
                    offset=np.arange(n, dtype=np.float32),
                    shard_idx={"g": np.zeros((n, 1), np.int32)},
                    shard_val={"g": np.zeros((n, 1), np.float32)},
                    ent_row={},
                )
            )
            for n in sizes
        ]
        release.set()
        for f, n in zip(futs, sizes):
            np.testing.assert_array_equal(
                f.result(timeout=30), np.arange(n, dtype=np.float32)
            )
        b.close()
        assert max(calls) <= 8

    def test_error_fans_to_all_members(self):
        def score(batch):
            raise RuntimeError("device fell over")

        b = MicroBatcher(
            score, max_batch_rows=8, max_wait_ms=20.0,
            bucketer=None, stats=ServeStats(),
        ).start()
        futs = [b.submit(_one_row_batch(1.0)) for _ in range(3)]
        for f in futs:
            with pytest.raises(RuntimeError, match="device fell over"):
                f.result(timeout=10)
        assert b.stats.snapshot()["errors"] >= 1
        b.close()

    def test_drain_fence(self):
        b = MicroBatcher(
            lambda batch: batch.offset, max_batch_rows=8, max_wait_ms=1.0,
            bucketer=None, stats=ServeStats(),
        ).start()
        futs = [b.submit(_one_row_batch(float(i))) for i in range(10)]
        assert b.drain(timeout=10)
        assert all(f.done() for f in futs)
        assert b.outstanding() == 0
        b.close()

    def test_score_fn_pinning_groups_generations(self):
        """Requests pinned to different scoring closures never share a
        device call (the swap-correctness invariant)."""
        calls = []

        def fn_a(batch):
            calls.append(("a", batch.num_rows))
            return batch.offset

        def fn_b(batch):
            calls.append(("b", batch.num_rows))
            return batch.offset + 100.0

        b = MicroBatcher(
            fn_a, max_batch_rows=64, max_wait_ms=100.0,
            bucketer=None, stats=ServeStats(),
        ).start()
        futs = []
        for i in range(6):
            futs.append(b.submit(_one_row_batch(float(i)),
                                 score_fn=fn_a if i % 2 == 0 else fn_b))
        vals = np.concatenate([f.result(timeout=10) for f in futs])
        b.close()
        expect = np.asarray([0, 101, 2, 103, 4, 105], np.float32)
        np.testing.assert_array_equal(vals, expect)


# ---------------------------------------------------------------------------
# Serving parity + oracle (offset term + evaluators covered end-to-end)
# ---------------------------------------------------------------------------


class TestServingParity:
    def test_device_driver_matches_host_oracle_with_offsets(
        self, serving_world, tmp_path
    ):
        """The batch driver's device path vs the reference-style host
        oracle, on data WITH a nonzero offset term, metrics included."""
        dev = _run_scoring_driver(serving_world, tmp_path / "dev")
        host = _run_scoring_driver(serving_world, tmp_path / "host", host=True)
        np.testing.assert_allclose(dev.scores, host.scores, rtol=1e-5, atol=1e-6)
        # offsets actually mattered (scores shift by them)
        offs = np.asarray([r["offset"] for r in serving_world["records"]])
        assert np.abs(offs).max() > 0.1
        assert set(dev.metrics) == {"AUC", "RMSE"}
        for k in dev.metrics:
            assert dev.metrics[k] == pytest.approx(host.metrics[k], rel=1e-4)

    def test_served_scores_bitwise_equal_batch_driver(
        self, serving_world, tmp_path
    ):
        """THE serving acceptance bit: concurrent single-row requests
        through the micro-batched server == the batch driver's device
        scores, bitwise."""
        drv = _run_scoring_driver(serving_world, tmp_path / "drv")
        server = ScoringServer(
            ModelStore(serving_world["store_dir"]), shard_sections=SECTIONS,
            max_batch_rows=16, max_wait_ms=5.0, stats=ServeStats(),
        )
        server.warmup(warm_nnz=8)
        wm = compile_stats.watermark()
        with concurrent.futures.ThreadPoolExecutor(8) as pool:
            futs = list(
                pool.map(lambda q: server.submit_rows([q]),
                         serving_world["requests"])
            )
        served = np.concatenate([f.result(timeout=60) for f in futs])
        assert np.array_equal(served, drv.scores)
        # steady-state requests hit warmed executables only
        assert wm.new_traces() == 0
        assert server.new_request_compiles() == 0
        snap = server.stats.snapshot()
        assert snap["requests"] == len(serving_world["requests"])
        assert snap["batches"] < snap["requests"]  # coalescing happened
        server.close()

    def test_multi_row_requests_and_cold_entities(self, serving_world, tmp_path):
        drv = _run_scoring_driver(serving_world, tmp_path / "drv2")
        server = ScoringServer(
            ModelStore(serving_world["store_dir"]), shard_sections=SECTIONS,
            max_batch_rows=32, max_wait_ms=1.0, stats=ServeStats(),
        )
        server.warmup(warm_nnz=8)
        reqs = serving_world["requests"]
        # one request carrying ALL rows (wider than max_batch_rows: split
        # into cap-sized sub-batches, so no batch pads past the warmed
        # ladder top — zero request-path compiles); plus a cold-entity
        # request
        served = server.score_rows(reqs)
        assert np.array_equal(served, drv.scores)
        assert len(reqs) > server.batcher.max_batch_rows
        assert server.new_request_compiles() == 0
        cold = dict(reqs[0], ids={"userId": "cold-user-999"})
        base = dict(reqs[0], ids={})
        np.testing.assert_array_equal(
            server.score_rows([cold]), server.score_rows([base])
        )
        server.close()

    def test_empty_rows(self, serving_world):
        server = ScoringServer(
            ModelStore(serving_world["store_dir"]), shard_sections=SECTIONS,
            max_batch_rows=8, max_wait_ms=1.0, stats=ServeStats(),
        )
        assert server.score_rows([]).shape == (0,)
        server.close()


# ---------------------------------------------------------------------------
# Warm start (persistent cache) — fresh-process arms
# ---------------------------------------------------------------------------


_WARM_CHILD = r"""
import json, os, sys
import numpy as np
sys.path.insert(0, {repo!r})
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
from photon_ml_tpu import compat
from photon_ml_tpu.compile import compile_stats
from photon_ml_tpu.serve import ModelStore, ScoringServer, ServeStats
assert compat.enable_persistent_cache({cache!r})
compile_stats.install_xla_listeners()
server = ScoringServer(ModelStore({store!r}),
                       shard_sections={{"global": ["fixedFeatures"],
                                        "per_user": ["userFeatures"]}},
                       max_batch_rows=8, max_wait_ms=1.0, stats=ServeStats())
report = server.warmup(warm_nnz=4)
scores = server.score_rows([{{"features": {{"fixedFeatures":
    [{{"name": "f0", "term": "", "value": 1.0}}]}},
    "ids": {{"userId": "u0"}}, "offset": 0.5}}])
server.close()
print(json.dumps({{"misses": compile_stats.xla_cache_misses,
                   "hits": compile_stats.xla_cache_hits,
                   "warm": report, "score": float(scores[0]),
                   "fully_warm": compile_stats.xla_cache_misses == 0}}))
"""


@pytest.mark.slow
class TestWarmStart:
    def test_second_process_is_fully_warm(self, serving_world, tmp_path):
        """Cold process fills the persistent cache; an identical warm
        process reports ZERO new XLA compiles — the zero-per-request-
        compile startup claim, CompileStats-asserted across processes."""
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        cache = str(tmp_path / "xla-cache")
        child = _WARM_CHILD.format(
            repo=repo, cache=cache, store=serving_world["store_dir"]
        )
        results = []
        for _ in range(2):
            out = subprocess.run(
                [sys.executable, "-c", child], capture_output=True,
                text=True, timeout=600, cwd=repo,
            )
            assert out.returncode == 0, out.stderr[-2000:]
            results.append(json.loads(out.stdout.strip().splitlines()[-1]))
        cold, warm = results
        assert cold["misses"] > 0, "cold start should have compiled"
        assert not cold["fully_warm"]
        assert warm["fully_warm"], warm
        assert warm["misses"] == 0
        assert warm["hits"] > 0
        assert warm["score"] == cold["score"]


# ---------------------------------------------------------------------------
# Live model swap
# ---------------------------------------------------------------------------


class TestModelSwap:
    @pytest.fixture()
    def second_store(self, serving_world):
        """A perturbed model with the SAME entity count (same ladder rung)."""
        base = serving_world["base"]
        model2 = str(base / "model2")
        if not os.path.isdir(model2):
            save_synthetic_game_model(
                model2, np.random.default_rng(43), d_fixed=5, d_random=3,
                num_users=10,
            )
            build_model_store(model2, str(base / "store2"),
                              bucketer=ShapeBucketer())
        return str(base / "store2")

    def test_swap_zero_compiles_zero_drops(self, serving_world, second_store):
        server = ScoringServer(
            ModelStore(serving_world["store_dir"]), shard_sections=SECTIONS,
            max_batch_rows=16, max_wait_ms=2.0, stats=ServeStats(),
        )
        server.warmup(warm_nnz=8)
        before = server.score_rows(serving_world["requests"][:4])
        swapper = ModelSwapper(server)
        wm = compile_stats.watermark()
        with concurrent.futures.ThreadPoolExecutor(8) as pool:
            futs = [
                pool.submit(server.score_rows, [q])
                for q in serving_world["requests"]
            ]
            report = swapper.swap(second_store)
            results = [f.result(timeout=60) for f in futs]
        assert report["new_compiles"] == 0
        assert report["shape_compatible"]
        assert report["dropped_requests"] == 0
        assert wm.new_traces() == 0
        assert len(results) == len(serving_world["requests"])
        assert all(len(r) == 1 for r in results)
        # the new model actually serves now
        after = server.score_rows(serving_world["requests"][:4])
        assert not np.allclose(before, after)
        assert server.model.generation == 2
        assert server.stats.snapshot()["swaps"] == 1
        server.close()

    def test_swap_refuses_missing_store(self, serving_world):
        from photon_ml_tpu.checkpoint import CheckpointRefError

        server = ScoringServer(
            ModelStore(serving_world["store_dir"]), shard_sections=SECTIONS,
            max_batch_rows=8, max_wait_ms=1.0, stats=ServeStats(),
        )
        swapper = ModelSwapper(server)
        with pytest.raises(CheckpointRefError):
            swapper.swap("/nonexistent/store")
        # old model keeps serving after the refused swap
        assert server.model.generation == 1
        assert len(server.score_rows(serving_world["requests"][:2])) == 2
        server.close()

    def test_swap_detects_shape_change(self, serving_world, tmp_path):
        """An entity count crossing a ladder rung is reported (and refused
        under require_compatible)."""
        from photon_ml_tpu.checkpoint import CheckpointRefError

        model3 = str(tmp_path / "model3")
        save_synthetic_game_model(
            model3, np.random.default_rng(44), d_fixed=5, d_random=3,
            num_users=20,  # 20 -> rung 32 vs 10 -> rung 16
        )
        store3 = str(tmp_path / "store3")
        build_model_store(model3, store3, bucketer=ShapeBucketer())
        server = ScoringServer(
            ModelStore(serving_world["store_dir"]), shard_sections=SECTIONS,
            max_batch_rows=8, max_wait_ms=1.0, stats=ServeStats(),
        )
        swapper = ModelSwapper(server)
        with pytest.raises(CheckpointRefError, match="slab"):
            swapper.swap(store3, require_compatible=True)
        assert server.model.generation == 1
        server.close()


# ---------------------------------------------------------------------------
# JSON-lines request loop
# ---------------------------------------------------------------------------


class TestJsonLinesLoop:
    def _serve(self, serving_world, lines, swapper_for=None):
        from photon_ml_tpu.serve import serve_json_lines

        server = ScoringServer(
            ModelStore(serving_world["store_dir"]), shard_sections=SECTIONS,
            max_batch_rows=8, max_wait_ms=1.0, stats=ServeStats(),
        )
        server.warmup(warm_nnz=8)
        swapper = ModelSwapper(server) if swapper_for else None
        out = io.StringIO()
        handled = serve_json_lines(
            server, io.StringIO("\n".join(lines) + "\n"), out, swapper=swapper
        )
        server.close()
        return handled, [json.loads(l) for l in out.getvalue().splitlines()]

    def test_score_stats_shutdown(self, serving_world, tmp_path):
        drv = _run_scoring_driver(serving_world, tmp_path / "loop-drv")
        reqs = serving_world["requests"]
        lines = [
            json.dumps({"id": f"r{i}", "rows": [q]})
            for i, q in enumerate(reqs)
        ]
        lines += [json.dumps({"cmd": "stats", "id": "st"}),
                  json.dumps({"cmd": "shutdown"}),
                  json.dumps({"id": "after", "rows": [reqs[0]]})]
        handled, responses = self._serve(serving_world, lines)
        assert handled == len(reqs)  # the post-shutdown line never ran
        by_id = {r.get("id"): r for r in responses}
        served = np.asarray(
            [by_id[f"r{i}"]["scores"][0] for i in range(len(reqs))],
            np.float32,
        )
        # f64 JSON round-trip preserves every f32 exactly
        assert np.array_equal(served, drv.scores)
        assert "stats" in by_id["st"]
        assert "after" not in by_id

    def test_bad_lines_fail_softly(self, serving_world):
        lines = [
            "this is not json",
            json.dumps({"rows": []}),
            json.dumps({"rows": "nope"}),
            json.dumps({"cmd": "swap", "store_dir": "/nonexistent"}),
            json.dumps({"id": "ok", "rows": [serving_world["requests"][0]]}),
            json.dumps({"cmd": "shutdown"}),
        ]
        handled, responses = self._serve(serving_world, lines)
        assert handled == 1
        errs = [r for r in responses if "error" in r]
        assert len(errs) == 4
        ok = [r for r in responses if r.get("id") == "ok"]
        assert len(ok) == 1 and len(ok[0]["scores"]) == 1

    def test_swap_command(self, serving_world):
        base = serving_world["base"]
        model2 = str(base / "model2-loop")
        save_synthetic_game_model(
            model2, np.random.default_rng(45), d_fixed=5, d_random=3,
            num_users=10,
        )
        store2 = str(base / "store2-loop")
        build_model_store(model2, store2, bucketer=ShapeBucketer())
        q = serving_world["requests"][0]
        lines = [
            json.dumps({"id": "pre", "rows": [q]}),
            json.dumps({"cmd": "swap", "store_dir": store2, "id": "sw"}),
            json.dumps({"id": "post", "rows": [q]}),
            json.dumps({"cmd": "shutdown"}),
        ]
        handled, responses = self._serve(serving_world, lines, swapper_for=True)
        by_id = {r.get("id"): r for r in responses}
        assert by_id["sw"]["swap"]["new_compiles"] == 0
        assert by_id["pre"]["scores"] != by_id["post"]["scores"]


# ---------------------------------------------------------------------------
# ServeStats
# ---------------------------------------------------------------------------


class TestServeStats:
    def test_percentiles_and_summary(self):
        s = ServeStats()
        for ms in range(1, 101):
            s.record_request(ms / 1e3)
        s.record_batch(rows_real=75, rows_padded=100, num_requests=100)
        snap = s.snapshot()
        assert snap["requests"] == 100
        assert 45 <= snap["p50_ms"] <= 55
        assert 95 <= snap["p99_ms"] <= 100
        assert snap["batch_fill_ratio"] == 0.75
        text = s.summary()
        assert "p50" in text and "p99" in text and "fill" in text
        s.reset()
        assert s.snapshot()["requests"] == 0


# ---------------------------------------------------------------------------
# Serve driver CLI
# ---------------------------------------------------------------------------


class TestServeDriver:
    def test_build_store_only_then_serve(self, serving_world, tmp_path):
        from photon_ml_tpu.cli import serve_driver

        store_dir = str(tmp_path / "driver-store")
        d = serve_driver.main([
            "--model-store-dir", store_dir,
            "--game-model-input-dir", serving_world["model_dir"],
            "--build-store-only", "true",
        ])
        assert is_model_store(store_dir)
        assert d.server is None

        reqs = serving_world["requests"]
        in_text = "\n".join(
            [json.dumps({"id": str(i), "rows": [q]})
             for i, q in enumerate(reqs[:5])]
            + [json.dumps({"cmd": "shutdown"})]
        ) + "\n"
        out = io.StringIO()
        driver = serve_driver.GameServeDriver(
            serve_driver.parse_serve_params([
                "--model-store-dir", store_dir,
                "--feature-shard-id-to-feature-section-keys-map",
                SECTIONS_FLAG,
                "--max-batch-rows", "8",
                "--warm-nnz", "4",
            ])
        )
        driver.run(in_stream=io.StringIO(in_text), out_stream=out)
        assert driver.handled == 5
        responses = [json.loads(l) for l in out.getvalue().splitlines()]
        assert sum(1 for r in responses if "scores" in r) == 5

    def test_parse_validation(self):
        from photon_ml_tpu.cli.game_params import GameServeParams

        with pytest.raises(ValueError, match="model-store-dir"):
            GameServeParams().validate()
        # the compile cache is always on (compat.enable_persistent_cache),
        # so --assert-warm alone is a checkable request
        GameServeParams(model_store_dir="x", assert_warm=True).validate()
        with pytest.raises(ValueError, match="max-batch-rows"):
            GameServeParams(model_store_dir="x", max_batch_rows=0).validate()
        with pytest.raises(ValueError, match="shape-canonicalization"):
            GameServeParams(
                model_store_dir="x", shape_canonicalization="nope"
            ).validate()
        # --assert-warm with warmup disabled would hold vacuously
        with pytest.raises(ValueError, match="warmup"):
            GameServeParams(
                model_store_dir="x", assert_warm=True,
                persistent_cache_dir="c", warmup=False,
            ).validate()
        # defaults are valid
        GameServeParams(model_store_dir="x").validate()


# ---------------------------------------------------------------------------
# Quantized serving stores (store_dtype bf16/int8; serve/quantize.py)
# ---------------------------------------------------------------------------


class TestQuantizedStore:
    """The accuracy/speed dial: bf16/int8 slabs under a PINNED error
    budget, with the f32 default untouched (bitwise stays bitwise)."""

    @pytest.fixture(scope="class")
    def q_world(self, serving_world, tmp_path_factory):
        base = tmp_path_factory.mktemp("qstores")
        stores = {"f32": serving_world["store_dir"]}
        metas = {"f32": ModelStore(serving_world["store_dir"]).meta}
        for dt in ("bf16", "int8"):
            sd = str(base / f"store-{dt}")
            metas[dt] = build_model_store(
                serving_world["model_dir"], sd,
                bucketer=ShapeBucketer(), store_dtype=dt,
            )
            stores[dt] = sd
        return {"base": base, "stores": stores, "metas": metas}

    def _server(self, store_dir):
        server = ScoringServer(
            ModelStore(store_dir), shard_sections=SECTIONS,
            max_batch_rows=16, max_wait_ms=1.0, stats=ServeStats(),
        )
        server.warmup(warm_nnz=8)
        return server

    def test_export_bytes_and_pinned_budget(self, q_world):
        from photon_ml_tpu.serve import quantize

        slab_path = os.path.join(
            q_world["stores"]["f32"], "random", "per-user", "slab.npy"
        )
        f32_bytes = os.path.getsize(slab_path)
        true_slab = np.asarray(
            ModelStore(q_world["stores"]["f32"]).random[0].slab
        )
        for dt in ("bf16", "int8"):
            store = ModelStore(q_world["stores"][dt])
            assert store.store_dtype == dt
            re = store.random[0]
            q = re.quantization
            # the pinned-budget contract: realized error recorded at
            # export, within the analytic budget
            assert 0 < q["realized_max_abs_coeff_err"] <= q["coeff_err_budget"]
            # realized error against the TRUE slab honors the per-row bound
            row_budget = quantize.row_coeff_budget(
                dt, np.max(np.abs(true_slab), axis=1)
            )
            err = np.abs(re.dequantized().astype(np.float64) - true_slab)
            assert np.all(err <= row_budget[:, None])
            # bytes: the dial actually pays (raw slab payloads; npy
            # headers wash out at real sizes but count against us here)
            got = os.path.getsize(
                os.path.join(
                    q_world["stores"][dt], "random", "per-user", "slab.npy"
                )
            )
            if dt == "bf16":
                assert got <= 0.55 * f32_bytes + 128
            else:
                scales = os.path.getsize(
                    os.path.join(
                        q_world["stores"][dt], "random", "per-user",
                        "scales.npy",
                    )
                )
                assert got + scales <= 0.55 * f32_bytes + 256
            store.close()

    def test_version1_meta_opens_as_f32_and_future_version_refused(
        self, q_world, tmp_path
    ):
        import shutil

        v1 = str(tmp_path / "v1-store")
        shutil.copytree(q_world["stores"]["f32"], v1)
        meta_path = os.path.join(v1, "meta.json")
        with open(meta_path) as f:
            meta = json.load(f)
        # a PR-6-era export: version 1, no store_dtype / quantization keys
        meta["version"] = 1
        meta.pop("store_dtype", None)
        for e in meta["random"]:
            e.pop("quantization", None)
        with open(meta_path, "w") as f:
            json.dump(meta, f)
        store = ModelStore(v1)
        assert store.store_dtype == "f32"
        assert store.random[0].scales is None
        store.close()
        meta["version"] = 99
        with open(meta_path, "w") as f:
            json.dump(meta, f)
        with pytest.raises(IOError, match="version-99"):
            ModelStore(v1)

    def test_quantized_scores_within_budget_f32_bitwise(self, q_world, serving_world):
        from game_test_utils import assert_scores_match_store

        reqs = serving_world["requests"]
        f32_server = self._server(q_world["stores"]["f32"])
        oracle = f32_server.score_rows(reqs)
        f32_server.close()
        for dt in ("f32", "bf16", "int8"):
            server = self._server(q_world["stores"][dt])
            served = server.score_rows(reqs)
            # f32 goes through the helper's BITWISE branch; bf16/int8
            # through the pinned per-score budget from store meta
            assert_scores_match_store(
                served, oracle, server.store.meta, reqs, SECTIONS,
                err_msg=f"store_dtype={dt}",
            )
            if dt != "f32":
                assert not np.array_equal(served, oracle), (
                    "quantized scores bitwise-equal to f32 — the dtype "
                    "dial is not actually engaged"
                )
            server.close()

    def test_same_dtype_swap_compile_free_dtype_change_flagged(
        self, q_world, serving_world, tmp_path
    ):
        # a second int8 export of a perturbed model (same shapes)
        model2 = str(tmp_path / "model2")
        save_synthetic_game_model(
            model2, np.random.default_rng(77), d_fixed=5, d_random=3,
            num_users=10,
        )
        store2 = str(tmp_path / "store2-int8")
        build_model_store(
            model2, store2, bucketer=ShapeBucketer(), store_dtype="int8"
        )
        server = self._server(q_world["stores"]["int8"])
        swapper = ModelSwapper(server)
        report = swapper.swap(store2)
        assert report["new_compiles"] == 0
        assert report["shape_compatible"]
        assert report["dropped_requests"] == 0
        # dtype change is a loud validation problem (and refused under
        # require_compatible) — never a silent recompile
        problems = swapper.validate_compatible(
            ModelStore(q_world["stores"]["bf16"])
        )
        assert any("dtype" in p for p in problems)
        from photon_ml_tpu.checkpoint import CheckpointRefError

        with pytest.raises(CheckpointRefError, match="dtype"):
            swapper.swap(q_world["stores"]["bf16"], require_compatible=True)
        server.close()

    def test_corrupt_scale_sidecar_refuses_open(self, q_world, tmp_path):
        import shutil

        broken = str(tmp_path / "broken-int8")
        shutil.copytree(q_world["stores"]["int8"], broken)
        scales_path = os.path.join(broken, "random", "per-user", "scales.npy")
        n_rows = np.load(scales_path).shape[0]
        # non-finite scales: mmap-able but poisonous — must refuse, not serve
        np.save(scales_path, np.full(n_rows, np.nan, np.float32))
        with pytest.raises(IOError, match="corrupt"):
            ModelStore(broken)
        # unreadable garbage: ditto, with the actionable re-export message
        with open(scales_path, "wb") as f:
            f.write(b"not an npy file")
        with pytest.raises(IOError, match="missing or unreadable"):
            ModelStore(broken)
        os.unlink(scales_path)
        with pytest.raises(IOError, match="missing or unreadable"):
            ModelStore(broken)

    def test_over_budget_meta_refuses_open(self, q_world, tmp_path):
        import shutil

        tampered = str(tmp_path / "tampered-int8")
        shutil.copytree(q_world["stores"]["int8"], tampered)
        meta_path = os.path.join(tampered, "meta.json")
        with open(meta_path) as f:
            meta = json.load(f)
        q = meta["random"][0]["quantization"]
        q["realized_max_abs_coeff_err"] = q["coeff_err_budget"] * 2
        with open(meta_path, "w") as f:
            json.dump(meta, f)
        with pytest.raises(IOError, match="budget"):
            ModelStore(tampered)

    def test_serve_dequant_fault_injection(self, q_world):
        from photon_ml_tpu.resilience import faults

        plan = faults.FaultPlan(
            [faults.FaultSpec(site="serve.dequant", at=1)]
        )
        with faults.fault_scope(plan):
            with pytest.raises(OSError, match="serve.dequant"):
                ModelStore(q_world["stores"]["int8"])
        assert plan.fire_count("serve.dequant") == 1
        # f32 stores never pass the dequant gate (no quantized slabs)
        plan2 = faults.FaultPlan(
            [faults.FaultSpec(site="serve.dequant", at=1)]
        )
        with faults.fault_scope(plan2):
            ModelStore(q_world["stores"]["f32"]).close()
        assert plan2.fire_count("serve.dequant") == 0

    def test_store_footprint_gauges(self, q_world):
        server = self._server(q_world["stores"]["int8"])
        snap = server.stats.snapshot()
        assert snap["store_dtype"] == "int8"
        assert snap["store_slab_bytes"] > 0
        assert snap["store_mapped_bytes"] > 0
        assert "int8" in server.stats.summary()
        server.close()

    def test_export_over_budget_slab_fails(self, tmp_path):
        """A quantization whose realized error exceeds the analytic
        budget must fail the EXPORT (never write a serving store)."""
        from photon_ml_tpu.serve import quantize

        slab = np.random.default_rng(3).normal(size=(8, 6)).astype(np.float32)
        stored, scales = quantize.quantize_slab(slab, "int8")
        with pytest.raises(IOError, match="budget"):
            # a tampered quantization (wrong scales) realizes over budget
            quantize.slab_error_report(slab, stored, scales * 2.0, "int8")

    def test_non_finite_slab_fails_export_and_open(self, q_world, tmp_path):
        """A NaN coefficient (the optim.step corruption fault mode) must
        FAIL the budget gate, not slide through it — every comparison
        against a NaN realized error is False, so the gate must be
        written as `not (realized <= budget)`."""
        import shutil

        from photon_ml_tpu.serve import quantize

        slab = np.random.default_rng(4).normal(size=(8, 6)).astype(np.float32)
        slab[3, 2] = np.nan
        for dt in ("bf16", "int8"):
            stored, scales = quantize.quantize_slab(slab, dt)
            with pytest.raises(IOError, match="budget"):
                quantize.slab_error_report(slab, stored, scales, dt)
        # a NaN smuggled into an already-written store's meta (e.g. by a
        # pre-fix exporter) is refused at open the same way
        tampered = str(tmp_path / "nan-meta-int8")
        shutil.copytree(q_world["stores"]["int8"], tampered)
        meta_path = os.path.join(tampered, "meta.json")
        with open(meta_path) as f:
            meta = json.load(f)
        meta["random"][0]["quantization"]["realized_max_abs_coeff_err"] = (
            float("nan")
        )
        with open(meta_path, "w") as f:
            json.dump(meta, f)
        with pytest.raises(IOError, match="budget"):
            ModelStore(tampered)
