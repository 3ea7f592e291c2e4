"""chip_smoke.py on the CPU: its trainer runs the normal GAME driver path
at toy size, and its entry point refuses to run without a TPU."""

import json
import math

import chip_smoke


def test_trainer_runs_the_driver_path_at_toy_size(tmp_path):
    report = chip_smoke.train(
        rows=2000, workdir=str(tmp_path), n_users=40, n_movies=60
    )
    chip_smoke.check(report)
    # 2 coordinate-descent iterations x (fixed, per-user)
    assert len(report["objective_history"]) == 4
    assert 0.5 < report["auc"] <= 1.0
    assert report["avro_decoder"] in ("native", "python")
    assert set(report["phase_seconds"]) == {
        "data_write", "ingest", "compile", "train"
    }
    assert report["mesh_devices"] == 1
    # the CPU backend reports no memory statistics; the report says so
    # instead of inventing a number
    assert report["memory"][0]["peak_bytes_in_use"] is None


def test_check_rejects_a_rising_or_non_finite_objective():
    # judged at iteration ends (entries 1 and 3): one update inside an
    # iteration may rise (the capped per-user solve), an iteration may not
    for good in ([10.0, 9.0, 9.0, 8.5], [10.0, 9.0, 8.0, 8.5]):
        chip_smoke.check({"objective_history": good, "auc": 0.8})
    for bad in ([10.0, 9.0, 9.5, 9.2], [10.0, math.nan, 9.0, 8.0], [10.0]):
        try:
            chip_smoke.check({"objective_history": bad, "auc": 0.8})
        except AssertionError:
            continue
        raise AssertionError(f"check accepted {bad}")


def test_main_refuses_to_run_without_a_tpu(capsys):
    assert chip_smoke.main() != 0
    out = capsys.readouterr()
    assert out.out.startswith("platform: cpu, device_kind: ")
    # no result line: the last stdout line is not the JSON object
    assert '"ok"' not in out.out
    assert math.isfinite(chip_smoke.CPU_AUC)


def test_last_stdout_line_is_exactly_ok_and_device(monkeypatch, capsys):
    # the driver's contract: the last line is a JSON object with exactly
    # the keys "ok" and "device" ({"platform", "kind", "count"}); the run's
    # figures go on an earlier line
    import jax

    class FakeTpu:
        platform, device_kind, id = "tpu", "TPU v5 lite", 0

    report = {
        "distributed": False, "rows": chip_smoke.ROWS,
        "auc": chip_smoke.CPU_AUC,
        "objective_history": [4.0, 3.0, 2.5, 2.0],
        "phase_seconds": {"data_write": 0.0, "ingest": 0.0, "compile": 0.0,
                          "train": 0.0},
        "xla_cache_hits": 0, "xla_cache_misses": 0, "compile_summary": "",
        "avro_decoder": "native", "mesh_devices": 1,
        "memory": [{"id": 0, "bytes_in_use": 1, "peak_bytes_in_use": 2}],
    }
    monkeypatch.setattr(jax, "devices", lambda: [FakeTpu()])
    monkeypatch.setattr(chip_smoke, "train", lambda *a, **k: report)
    assert chip_smoke.main() == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
