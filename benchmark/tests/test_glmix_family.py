"""The ``glmix`` family is not a cell yet (PERF.md, Open questions, first
row): the program bakes a coordinate's dataset into its compiled programs,
so every seed compiles anew. Its files stay for the PR that adds the cell;
this keeps them alive at the family's ``TINY`` sizes on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _cell(seed):
    from benchmark.families import glmix

    here = os.path.join(ROOT, "benchmark")
    config = json.load(open(os.path.join(here, "configs", "glmix-ml20m-quarter.json")))
    job = json.load(open(os.path.join(
        here, "workloads", "glmix-ml20m-quarter.cd2.json")))["job"]
    return glmix.build(config, job, seed, tiny=True)


def test_program_agrees_with_the_plain_reference_and_faults_do_not():
    cell = _cell(21)
    got = cell.collect(cell.run_job())
    ref = cell.reference()
    sound = cell.compare(got, ref)
    # the two float32 solvers stop within rounding of one optimum: values
    # agree far closer than coefficients (flat directions under L2 = 1)
    assert sound["objective_gap"] < 1e-3, sound
    assert sound["user_value_sum_gap"] < 1e-4, sound
    assert sound["per_user_gap"] < 5e-2, sound
    halved = cell.compare(cell.reference(half_batch=True), ref)
    assert halved["per_user_gap"] > 10 * sound["per_user_gap"], halved
    assert halved["objective_gap"] > 10 * sound["objective_gap"], halved
    work = cell.work(ref)
    assert work["job"]["bytes"] > work["job"]["flops"] > 0


def test_a_new_seed_recompiles_the_descent_programs():
    """The fault that keeps the cell out, at a size a test can hold: the
    per-coordinate programs close over the dataset, so their HLO differs
    from seed to seed although every shape is the same."""
    texts = []
    for seed in (31, 32):
        cell = _cell(seed)
        update = cell._descent._update_fns["global"]._jitted
        coord = cell._descent.coordinates["global"]
        import jax.numpy as jnp

        n = len(cell.rows["labels"])
        texts.append(update.lower(
            jnp.zeros((n,), jnp.float32), coord.initial_coefficients()).as_text())
    assert "stablehlo.constant dense<\"0x" in texts[0]
    assert texts[0] != texts[1]
