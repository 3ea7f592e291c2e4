"""The chip smoke: the GLMix trainer end to end on the TPU, once.

Drives BASELINE config 4 (GLMix: fixed effect + per-user random effect,
logistic) through the normal path — synthetic ML-1M-shaped Avro from a seed
-> feature index -> ``photon_ml_tpu.cli.game_training_driver.main`` — at the
model's full width and ML-1M's full row count (1,000,209 ratings, 6,040
users, 3,706 movies, 21 features per shard, ``active_cap`` 512, 2
coordinate-descent iterations, AUC on a 10 % held-out set), with the
generator and flags of ``tools/movielens_baseline.py``.

Contract (one process; nothing is written into BASELINE.json or any other
record):

  * prints ``platform`` / ``device_kind`` / device count first and exits
    non-zero unless the platform is ``tpu`` — it never falls back;
  * fails unless the objective is finite and non-increasing over the
    iterations and the held-out AUC is within :data:`AUC_TOLERANCE` of
    :data:`CPU_AUC`, the CPU value for the same seed, flags and rows;
  * prints per-phase wall seconds (data write, ingest, compile, train), the
    driver's compile statistics, which Avro decoder ran, and the device's
    peak bytes in use;
  * with more than one chip visible, runs the same job again with
    ``--distributed true`` in the same process and requires its AUC within
    :data:`DISTRIBUTED_AUC_TOLERANCE` of the one-chip run and the sharded
    data resident on every device;
  * prints the run's figures as one ``summary: {...}`` line, then as the
    LAST line of stdout one JSON object with exactly these keys,
    ``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
    and exits 0. Any exception is a non-zero exit with no such line.

Run:  python3 chip_smoke.py          (on the machine with the chip)
"""

from __future__ import annotations

import gc
import json
import math
import sys
import tempfile
import time
from typing import Optional

#: rows of the synthetic dataset: ML-1M's, uncut
#: (tools.movielens_baseline.N_RATINGS)
ROWS = 1_000_209
ITERATIONS = 2
#: held-out AUC of this job on the CPU backend for
#: tools.movielens_baseline.SEED, these flags and ROWS rows: 0.8788
#: (BASELINE.json config4_movielens1m_scale; 0.878843 again on jax 0.9.0,
#: my CPU run, PR 21)
CPU_AUC = 0.8788
AUC_TOLERANCE = 0.01
DISTRIBUTED_AUC_TOLERANCE = 1e-3


def _say(msg: str) -> None:
    print(f"[chip_smoke {time.strftime('%H:%M:%S')}] {msg}", flush=True)


def memory_report() -> list:
    """Per-device ``{"id", "bytes_in_use", "peak_bytes_in_use"}`` (values
    None where the backend reports no memory statistics, e.g. the CPU)."""
    import jax

    out = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        out.append({
            "id": d.id,
            "bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        })
    return out


def train(rows: int, workdir: str, n_users: Optional[int] = None,
          n_movies: Optional[int] = None, distributed: bool = False,
          reuse_data: bool = False) -> dict:
    """Write the dataset under ``workdir`` (unless ``reuse_data``) and train
    it through ``game_training_driver.main``. Returns the run's report:
    AUC, objective history, per-phase wall seconds, compile counters,
    decoder, device memory (read while the driver still holds its
    training tensors, so ``bytes_in_use`` is what the job keeps resident)."""
    from photon_ml_tpu.cli.game_training_driver import main as game_main
    from photon_ml_tpu.compile import compile_stats
    from photon_ml_tpu.io import avro_native
    from tools import movielens_baseline as ml

    t0 = time.perf_counter()
    if not reuse_data:
        ml.write_dataset(
            workdir, rows, n_users or ml.N_USERS, n_movies or ml.N_MOVIES
        )
    data_write_s = time.perf_counter() - t0

    compile_stats.reset()
    driver = game_main(
        ml.game_args(workdir, iterations=ITERATIONS, distributed=distributed)
    )
    _, result, metrics = driver.results[driver.best_index]
    totals = driver.timer.totals
    return {
        "distributed": distributed,
        "rows": rows,
        "auc": float(metrics["AUC"]),
        "objective_history": [float(v) for v in result.objective_history],
        "phase_seconds": {
            "data_write": round(data_write_s, 2),
            "ingest": round(
                totals.get("prepare-feature-maps", 0.0)
                + totals.get("prepare-datasets", 0.0), 2
            ),
            # XLA backend compile time, spent inside the train phase
            "compile": round(compile_stats.backend_compile_seconds, 2),
            "train": round(totals.get("train", 0.0), 2),
        },
        "xla_cache_hits": compile_stats.xla_cache_hits,
        "xla_cache_misses": compile_stats.xla_cache_misses,
        "compile_summary": compile_stats.summary(),
        "avro_decoder": "native" if avro_native._load() is not None else "python",
        "mesh_devices": (
            driver._mesh_context().num_devices if distributed else 1
        ),
        "memory": memory_report(),
    }


def check(report: dict) -> None:
    """Raise unless the run's objective is finite after every coordinate
    update and non-increasing from one iteration's end to the next.

    Iteration ends, not single updates: the per-user solve fits each user's
    ``active_cap`` rows while the objective is taken over all rows, so one
    update may move it up a little (at 300,000 rows it does, on the CPU
    and on the chip alike: 66003.6 -> 66061.8 inside iteration 2, with
    66279.2 -> 66061.8 across the two iterations)."""
    hist = report["objective_history"]
    if not hist or len(hist) % ITERATIONS:
        raise AssertionError(
            f"objective history does not cover {ITERATIONS} iterations: {hist}"
        )
    if not all(math.isfinite(v) for v in hist):
        raise AssertionError(f"non-finite objective: {hist}")
    updates = len(hist) // ITERATIONS  # coordinate updates per iteration
    per_iteration = hist[updates - 1::updates]
    if any(b > a for a, b in zip(per_iteration, per_iteration[1:])):
        raise AssertionError(
            f"objective increased over the iterations: {per_iteration} "
            f"(all updates: {hist})"
        )
    if not math.isfinite(report["auc"]):
        raise AssertionError(f"non-finite AUC: {report['auc']}")


def _print_report(report: dict) -> None:
    tag = "distributed" if report["distributed"] else "one-chip"
    _say(f"{tag}: rows {report['rows']:,}, AUC {report['auc']:.4f}, "
         f"objective {report['objective_history']}")
    _say(f"{tag}: phase seconds {report['phase_seconds']}")
    _say(f"{tag}: avro decoder: {report['avro_decoder']}")
    _say(f"{tag}: XLA cache {report['xla_cache_hits']} hits / "
         f"{report['xla_cache_misses']} misses")
    for line in report["compile_summary"].splitlines():
        _say(f"{tag}:   {line}")
    for m in report["memory"]:
        _say(f"{tag}: device {m['id']}: bytes_in_use {m['bytes_in_use']}, "
             f"peak_bytes_in_use {m['peak_bytes_in_use']}")


def main() -> int:
    import jax

    devs = jax.devices()
    device = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    print(f"platform: {device['platform']}, device_kind: {device['kind']}, "
          f"count: {device['count']}", flush=True)
    if device["platform"] != "tpu":
        print("chip_smoke: jax found no TPU; this check never falls back",
              file=sys.stderr)
        return 2

    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as workdir:
        one = train(ROWS, workdir)
        _print_report(one)
        check(one)
        if abs(one["auc"] - CPU_AUC) > AUC_TOLERANCE:
            raise AssertionError(
                f"held-out AUC {one['auc']:.4f} is not within "
                f"{AUC_TOLERANCE} of the CPU value {CPU_AUC:.4f} "
                f"({ROWS:,} rows)"
            )
        summary = {
            "rows": ROWS,
            "auc": round(one["auc"], 4),
            "cpu_auc": CPU_AUC,
            "phase_seconds": one["phase_seconds"],
            "xla_cache_misses": one["xla_cache_misses"],
            "avro_decoder": one["avro_decoder"],
            "peak_bytes_in_use": one["memory"][0]["peak_bytes_in_use"],
        }
        if device["count"] > 1:
            gc.collect()  # drop the one-chip run's tensors from device 0
            dist = train(ROWS, workdir, distributed=True, reuse_data=True)
            _print_report(dist)
            check(dist)
            if dist["mesh_devices"] != device["count"]:
                raise AssertionError(
                    f"mesh holds {dist['mesh_devices']} devices, "
                    f"jax sees {device['count']}"
                )
            if abs(dist["auc"] - one["auc"]) > DISTRIBUTED_AUC_TOLERANCE:
                raise AssertionError(
                    f"distributed AUC {dist['auc']:.5f} differs from the "
                    f"one-chip {one['auc']:.5f} by more than "
                    f"{DISTRIBUTED_AUC_TOLERANCE}"
                )
            # the row-sharded fixed effect and the entity-sharded random
            # effect must be resident on every device, not on device 0:
            # non-zero and comparable bytes in use on each
            in_use = [m["bytes_in_use"] for m in dist["memory"]]
            if not all(in_use) or min(in_use) * 4 < max(in_use):
                raise AssertionError(
                    f"sharded data not resident on every device: bytes in "
                    f"use per device {in_use}"
                )
            summary["distributed"] = {
                "mesh_devices": dist["mesh_devices"],
                "auc": round(dist["auc"], 4),
                "phase_seconds": dist["phase_seconds"],
                "bytes_in_use": in_use,
            }
    _say(f"summary: {json.dumps(summary)}")
    # the contract's last line: exactly "ok" and "device", nothing else
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
