"""bench.py CLI surface that must work WITHOUT a device: section
enumeration (the orchestrator / CI smoke path) never imports jax or any
TPU-only module, so a backend-free host can still list what the bench
would run."""

import os
import subprocess
import sys

BENCH = os.path.join(os.path.dirname(__file__), os.pardir, "bench.py")


def test_list_sections_enumerates_all_sections():
    out = subprocess.run(
        [sys.executable, BENCH, "--list-sections"],
        capture_output=True, text=True, timeout=120,
        # a poisoned platform value must not matter: --list-sections exits
        # before any backend (or photon_ml_tpu module) import
        env={**os.environ, "JAX_PLATFORMS": "this-backend-does-not-exist"},
    )
    assert out.returncode == 0, out.stderr
    sections = out.stdout.split()
    assert sections == [
        "dense", "sparse", "sparse_race", "game", "game5", "grid",
        "streaming", "streaming_pipeline", "compile_reuse", "compaction",
        "fused_schedule",
        "adaptive_schedule",
        "plan_auto",
        "preemption_resume",
        "perhost", "perhost_streaming", "elastic_reshard", "scoring",
        "serving",
        "serving_fleet", "quantized_serving", "retrain_delta",
        "delta_rollout", "day_in_life", "ingest",
    ]


def test_list_sections_does_not_touch_jax():
    """The flag must list sections even where importing jax would crash
    outright — audit via an import tripwire."""
    tripwire = (
        "import builtins, sys\n"
        "real = builtins.__import__\n"
        "def guard(name, *a, **k):\n"
        "    if name == 'jax' or name.startswith('jax.'):\n"
        "        raise RuntimeError('jax imported during --list-sections')\n"
        "    return real(name, *a, **k)\n"
        "builtins.__import__ = guard\n"
        f"sys.argv = ['bench.py', '--list-sections']\n"
        f"__file__ = {BENCH!r}\n"
        f"exec(compile(open({BENCH!r}).read(), 'bench.py', 'exec'))\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", tripwire],
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr
    assert "compaction" in out.stdout.split()
