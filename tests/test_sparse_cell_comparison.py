"""A sum in another order is not a fault: the sparse cell's comparison.

Three cases of ``benchmark/tests/test_correct.py`` (ROADMAP D15), here at
the family's ``TINY`` sizes so that tier-1 counts them, importing the
benchmark as ``tests/test_dense_grid_reference.py`` does. They hold what
the wide fixed effect's tile layout (``ops/tiled_sparse.py``, PR 36) leans
on: the reference's gradient is the float32 nearest the exact sum, so a
sound program that adds in another order passes ``correct``, and a fault
still fails.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib
import io
import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from benchmark.families import glm_sparse as family  # noqa: E402

SPARSE = "glm-sparse-2m.lbfgs"


def _cell_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    _, cell_file, _, config = harness.find_cell(manifest, SPARSE)
    return cell_file, config


@pytest.fixture(scope="module")
def gradient_readings():
    cell_file, config = _cell_files()
    cell = family.build(config, cell_file["job"], 22, tiny=True)
    cell.free()
    return {line["at"]: line for line in cell.gradient_readings(22)}


@pytest.mark.parametrize("at", ["zero", "random"])
def test_reference_gradient_is_the_exact_sum(gradient_readings, at):
    """Against the float64 sum of the same float32 products the reference's
    gradient is within 3e-7 of the norm, over all features and over the 128
    most frequent, and nearer than one float32 vector carried through the
    rows (what a row-order program does) on the frequent ones."""
    line = gradient_readings[at]
    assert line["kept"] < 3e-7 and line["kept_most_frequent"] < 3e-7, line
    assert line["kept_most_frequent"] < line["carried_most_frequent"], line


@pytest.fixture
def cache_settings_restored():
    """The harness turns the persistent compilation cache on for its
    process; the tests after this file's get the settings they had."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    names = ("jax_compilation_cache_dir", "jax_persistent_cache_min_entry_size_bytes",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_compilation_cache_include_metadata_in_key",
             "jax_traceback_in_locations_limit")
    before = {name: getattr(jax.config, name) for name in names}
    yield
    for name, value in before.items():
        jax.config.update(name, value)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("seed", [12, 13])
def test_a_run_on_permuted_rows_is_correct(seed, monkeypatch, cache_settings_restored):
    """Same data, same mathematics, another order of addition: a whole run
    of the harness (all but its look for a chip) comes out ``correct``."""
    import jax

    sound = family.build

    def permuted(config, job, seed, tiny=False):
        cell = sound(config, job, seed, tiny)
        cell.permute_rows(seed)
        return cell

    monkeypatch.setattr(family, "build", permuted)
    jax.clear_caches()
    out = io.StringIO()
    args = argparse.Namespace(workload=SPARSE, seed=seed, seconds=0.1, trace=0)
    with contextlib.redirect_stdout(out):
        code = harness.run(args, time.time(), allow_cpu=True, tiny=True)
    jax.clear_caches()
    assert code == 0
    last = json.loads(out.getvalue().strip().splitlines()[-1])
    assert last["correct"], last["compared"]


@pytest.mark.parametrize("seed", [5, 6, 7])
def test_the_half_batch_fails_four_numbers(seed):
    """The planted fault (every second row left out, the rest counted double)
    fails every number but the count of iterations."""
    cell_file, config = _cell_files()
    cell = importlib.import_module(
        f"benchmark.families.{config['family']}").build(
            config, cell_file["job"], seed, tiny=True)
    numbers = cell.compare(cell.reference(half_batch=True), cell.reference())
    failed = [n for n, limit in cell.limits.items() if numbers[n] > limit]
    assert set(failed) == set(cell.limits) - {"iterations_gap"}, numbers
