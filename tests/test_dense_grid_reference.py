"""The dense GLM's warm-started grid against the benchmark's plain reference.

``training.train_glm_grid`` over the README's four L2 weights on
``DenseFeatures`` (the call ``cli/glm_driver`` makes, and the job of the
benchmark cell ``glm-dense-epsilon.grid4``) against
``benchmark/references/glm_dense.py`` (float32 ``jax.numpy`` at the highest
matmul precision, its own L-BFGS, nothing of ``photon_ml_tpu`` imported), on
seeded data at a small size whose width is no multiple of 128, as the cell's
2,000 is not. Off a TPU ``ops/fused_glm.select_fused_block_rows`` keeps the
two-pass path, so the grid here is that; the one-pass kernel the cell runs on
the chip is held to float64 in ``tests/test_fused_glm.py``, and at the end of
this file the chip's compiler says what ``training._solve`` is made of at the
cell's shape.
"""

from __future__ import annotations

import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.families import glm_dense as family  # noqa: E402
from benchmark.references import glm_dense as reference  # noqa: E402

GRID = [0.1, 1.0, 10.0, 100.0]
ROWS, WIDTH = family.TINY["train_rows"], family.TINY["features"]
assert WIDTH % 128 != 0

#: Why each tolerance. Program and reference do the same float32 arithmetic
#: in another order (the program sums the losses in one reduction, the
#: reference keeps the rounding errors; the program's products are one
#: contraction, the reference's run over two blocks of rows).
#: values: the first value is ROWS ln 2 = 5678.1 whose float32 neighbours are
#: 8.6e-8 apart; a plain float32 sum of 8,192 losses lands a few of them off,
#: and later values carry the coefficients' gap.
#: coefficients, their change and the warm starts' first gradients: rounding
#: of the gradient (1e-7 of itself) reaches the coefficients through ten
#: quasi-Newton steps a solve on correlated features (the Hessian's condition
#: is some 10^2), four solves chained: 2e-5 is the largest read over these
#: seeds, the bfloat16 control reads 1e-2 to 1e-1.
TOLERANCE = {"values_gap": 2e-5, "coefficients_gap": 3e-4,
             "change_norm_gap": 3e-4, "first_grad_gap": 3e-4}


def _config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "glm-dense-epsilon.json")) as f:
        return json.load(f)


ITERATIONS = _config()["sizes"]["solver"]["max_iterations"]


def _cell(seed):
    return family.build(_config(), {}, seed, tiny=True)


@pytest.fixture(scope="module", params=[21, 22, 2 ** 31 + 23])
def trained(request):
    cell = _cell(request.param)
    got = cell.collect(cell.run_job())
    return cell, got, cell.reference()


def test_grid_matches_the_float32_reference(trained):
    """Values after every iteration, coefficients, the norm of their change,
    each solve's first gradient norm and the iterations (exact), the worst
    over the four solves."""
    cell, got, ref = trained
    assert [len(s["values"]) for s in got["solves"]] == [ITERATIONS + 1] * 4
    numbers = cell.compare(got, ref)
    assert numbers["iterations_gap"] == 0
    for name, limit in TOLERANCE.items():
        assert numbers[name] <= limit, (name, numbers)


def test_every_solve_starts_where_the_one_before_ended(trained):
    """The warm-start chain: solve k's first value is the reference's
    objective at solve k-1's coefficients under solve k's L2 weight (for the
    first solve, at zero: ROWS ln 2 whatever the weight)."""
    cell, got, _ = trained
    ones = jnp.ones((ROWS,), jnp.float32)
    start = jnp.zeros((WIDTH,), jnp.float32)
    assert got["solves"][0]["values"][0] == pytest.approx(ROWS * np.log(2.0), rel=2e-6)
    for l2, solve in zip(sorted(GRID, reverse=True), got["solves"]):
        at_start = reference.fit(cell.matrix, cell.labels, ones, start,
                                 jnp.float32(l2), 0, 0.0, 2)
        assert solve["values"][0] == pytest.approx(
            float(at_start.values[0]), rel=TOLERANCE["values_gap"])
        # and it is not the objective under the weight of the solve before
        if l2 != max(GRID):
            before = reference.fit(cell.matrix, cell.labels, ones, start,
                                   jnp.float32(l2 * 10.0), 0, 0.0, 2)
            assert abs(solve["values"][0] - float(before.values[0])) \
                > 1e-3 * solve["values"][0]
        start = jnp.asarray(solve["coefficients"])


def test_bfloat16_control_fails_the_same_comparison(trained):
    """The reference with the matrix and the coefficients held in bfloat16
    for the two products, put in the program's place."""
    cell, _, ref = trained
    numbers = cell.compare(cell.reference("bfloat16"), ref)
    failed = [n for n, limit in TOLERANCE.items() if numbers[n] > limit]
    assert set(failed) == set(TOLERANCE), numbers


def test_the_cell_is_the_published_recipe():
    """The configuration's grid, corrections and widths are the source's;
    only the iteration cap is cut, and it is listed."""
    config = _config()
    solver, published = config["sizes"]["solver"], config["published"]
    assert sorted(solver["l2_grid"]) == sorted(published["l2_grid"]) == GRID
    assert solver["corrections"] == published["corrections"] == 10
    assert (config["sizes"]["features"], config["sizes"]["train_rows"],
            config["sizes"]["held_out_rows"]) == (2000, 400000, 100000)
    assert config["reduced"] == ["max_iterations"]
    assert config["sizes"]["features"] % 128 != 0


def test_every_solve_runs_to_the_cap(trained):
    """Half of the rule that chose the cap (PERF.md section 4), at the small
    size: all four solves run their ten iterations, in the program and in
    the reference."""
    _, got, ref = trained
    assert [s["iterations"] for s in got["solves"]] == [ITERATIONS] * 4
    assert [s["iterations"] for s in ref["solves"]] == [ITERATIONS] * 4


def test_every_seed_counts_the_same_evaluations():
    """The other half: the seeds hold one data set shuffled, so the
    reference counts the same evaluations on each, line search included."""
    counts = [[s["evaluations"] for s in _cell(seed).reference()["solves"]]
              for seed in (41, 42, 2 ** 31 + 43)]
    assert counts[0] == counts[1] == counts[2], counts
    assert all(c >= ITERATIONS + 1 for c in counts[0])


def test_features_have_the_stated_correlation():
    """``mixing_matrix``: unit-variance features of correlation rho^|i - j|,
    eigenvalues inside ((1 - rho) / (1 + rho), (1 + rho) / (1 - rho))."""
    rho = _config()["assumed"]["feature_correlation"]
    mix = family.mixing_matrix(WIDTH, rho).astype(np.float64)
    lag = np.abs(np.arange(WIDTH)[:, None] - np.arange(WIDTH)[None, :])
    np.testing.assert_allclose(mix.T @ mix, rho ** lag, atol=1e-6)
    spectrum = np.linalg.eigvalsh(mix.T @ mix)
    assert (1 - rho) / (1 + rho) < spectrum[0] < spectrum[-1] < (1 + rho) / (1 - rho)
    assert spectrum[-1] / spectrum[0] > 100


def test_rows_are_unit_length_and_classes_balanced(trained):
    cell, _, _ = trained
    matrix = np.asarray(cell.matrix, np.float64)
    np.testing.assert_allclose(np.linalg.norm(matrix, axis=1), 1.0, atol=1e-6)
    assert matrix.shape == (ROWS, WIDTH) and cell.held_out[0].shape[1] == WIDTH
    assert 0.45 < float(np.mean(np.asarray(cell.labels))) < 0.55
    # the planted vector does not separate the classes: the Bayes error is
    # not zero, and a neighbour's correlation is the configuration's
    sample = np.corrcoef(matrix, rowvar=False)
    assert np.max(np.abs(sample)[~np.eye(WIDTH, dtype=bool)]) > 0.9


def test_a_seed_shuffles_the_configurations_data_set():
    """Two seeds hold the same rows in another order, with the features in
    another order and under other signs: a row's sorted magnitudes are what
    neither shuffle changes."""
    a, b = _cell(31), _cell(2 ** 31 + 32)
    assert not np.array_equal(np.asarray(a.matrix), np.asarray(b.matrix))

    def canonical(cell):
        rows = np.sort(np.abs(np.asarray(cell.matrix, np.float64)), axis=1)
        labelled = np.concatenate([rows, np.asarray(cell.labels)[:, None]], axis=1)
        return labelled[np.lexsort(rows.T[::-1])]

    np.testing.assert_allclose(canonical(a), canonical(b), atol=1e-6)
    again = _cell(31)
    assert np.array_equal(np.asarray(a.matrix), np.asarray(again.matrix))
    assert np.array_equal(np.asarray(a.labels), np.asarray(again.labels))


# -- the chip's compiler, no chip: what a float32 matrix-vector product is ----


@pytest.fixture(scope="module")
def four_chips():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return topo.devices


@pytest.fixture(scope="module")
def one_chip(four_chips):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(four_chips[0])


@pytest.mark.parametrize("product", ["matvec", "rmatvec"])
def test_dense_products_stay_float32_on_the_v5e(one_chip, product):
    """At the cell's shape the v5e's compiler makes each of ``DenseFeatures``'
    products one multiply-and-reduce fusion of float32 values: no matrix unit,
    so no rounding of the operands to bfloat16, at the default precision.
    (PERF.md section 6, PR 30: the comparison on the chip read the same.)"""
    from photon_ml_tpu.ops.features import DenseFeatures

    rows, width = 400000, 2000
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    vector = shape(width) if product == "matvec" else shape(rows)
    text = jax.jit(
        lambda m, v: getattr(DenseFeatures(m), product)(v)
    ).lower(shape(rows, width), vector).compile().as_text()
    assert "multiply_reduce_fusion" in text
    assert "convolution" not in text and "bf16" not in text


#: (rows, width) and whether the v5e holds it column-major: widths that are
#: and are not multiples of 128, many and few rows, a tie
HELD = [((400000, 2000), True), ((400000, 2048), False), ((400000, 200), True),
        ((300, 2000), False), ((1000, 2000), False), ((1024, 2000), True),
        ((100000, 127), False), ((100000, 129), True), ((8192, 40), True)]


@pytest.mark.parametrize("storage", ["float32", "bfloat16"])
def test_the_layout_rule_is_the_v5es(one_chip, storage):
    """``fused_glm.held_column_major`` against the layout the v5e's compiler
    gives a program's matrix parameter: the one-pass kernel reads the matrix
    in that layout, and a wrong guess costs a copy of the matrix a launch."""
    import re

    from photon_ml_tpu.ops.fused_glm import held_column_major

    dtype = jnp.dtype(storage)
    for (rows, width), column_major in HELD:
        assert held_column_major(rows, width) == column_major, (rows, width)
        shape = lambda *s: jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)
        text = jax.jit(lambda m, v: m @ v).lower(
            shape(rows, width), shape(width)).compile().as_text()
        held = re.search(r"entry_computation_layout=\{\(\w+\[%d,%d\]\{([01],[01])"
                         % (rows, width), text).group(1)
        assert held == ("0,1" if column_major else "1,0"), (rows, width, held)


@pytest.mark.parametrize("width", [2000, 2048])
def test_the_cells_solve_reads_the_matrix_once_on_the_v5e(one_chip, monkeypatch, width):
    """``training._solve`` at 400,000 x 2,000 float32 (the cell's shape, held
    column-major) and at 2,048 wide (held row-major) with the block the
    selection gives on a TPU, compiled for the v5e: the optimized program
    calls the kernel once an evaluation (the one before the solver's loop and
    the line search's), and holds no product, copy or bfloat16 convert of
    the whole matrix."""
    from photon_ml_tpu import training
    from photon_ml_tpu.ops import fused_glm
    from photon_ml_tpu.ops.features import DenseFeatures
    from photon_ml_tpu.ops.normalization import NormalizationContext
    from photon_ml_tpu.ops.objective import GLMBatch
    from photon_ml_tpu.ops.regularization import RegularizationContext
    from photon_ml_tpu.optim.common import OptimizerConfig
    from photon_ml_tpu.optim.problem import GLMOptimizationProblem
    from photon_ml_tpu.types import OptimizerType, TaskType

    rows = 400000
    # the process's backend is the CPU: steer the two places that ask
    monkeypatch.setattr(fused_glm, "_on_tpu", lambda: True)
    monkeypatch.setattr(fused_glm, "_interpret_default", lambda: False)
    monkeypatch.delenv("PHOTON_ML_TPU_FUSED", raising=False)
    block = fused_glm.select_fused_block_rows(rows, width, jnp.float32)
    assert block == 640
    solver = _config()["sizes"]["solver"]
    problem = GLMOptimizationProblem(
        task=TaskType.LOGISTIC_REGRESSION, optimizer=OptimizerType.LBFGS,
        optimizer_config=OptimizerConfig(
            max_iterations=ITERATIONS, tolerance=0.0,
            num_corrections=int(solver["corrections"])),
        regularization=RegularizationContext.l2(100.0), fused_block_rows=block)
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    batch = GLMBatch(DenseFeatures(shape(rows, width)),
                     shape(rows), shape(rows), shape(rows))
    training._solve.clear_cache()
    compiled = training._solve.lower(
        problem, batch, NormalizationContext.identity(), shape(width), shape()
    ).compile()
    training._solve.clear_cache()
    _reads_the_matrix_once(compiled, rows, width)


def _reads_the_matrix_once(compiled, rows, width):
    """The optimized program of an L-BFGS solve on a ``rows`` x ``width``
    float32 matrix (a device's own rows, where there are several)."""
    import re

    text = compiled.as_text()
    whole = r"\[(%d,%d|%d,%d)\]" % (rows, width, width, rows)
    calls = [l for l in text.splitlines() if 'custom_call_target="tpu_custom_call"' in l]
    assert len(calls) == 2, len(calls)
    assert all("pml.objective.value_and_grad/pml.features.value_grad" in l
               for l in calls)
    assert sum("pml.lbfgs.line_search" in l for l in calls) == 1
    # every other line that names the whole matrix carries it or bitcasts it
    for line in text.splitlines():
        made = re.match(r"\s*(?:ROOT )?%\S+ = (\w+)" + whole + r"\S* (\S+?)\(", line)
        if made:
            assert made.group(1) == "f32", line[:200]
            assert made.group(3) in ("parameter", "bitcast", "get-tuple-element"), line[:200]
    assert not re.search(r"bf16" + whole, text)
    assert "convolution" not in text
    # the compiler's temporaries: megabytes, not the matrix's 3.2 GB again
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("width", [2000, 2048])
def test_the_distributed_solve_runs_the_kernel_on_four_v5es(four_chips, monkeypatch, width):
    """``DistributedFixedEffectSolver``'s program (``shard_map`` over the
    rows with ``check_vma`` on, as it builds it) with 400,000 rows a chip,
    compiled for the four chips of a v5e host: each chip's shard is held as
    the one-chip matrix is, the kernel reads it once an evaluation, and the
    shards' sums cross the mesh."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from photon_ml_tpu.ops import fused_glm
    from photon_ml_tpu.ops.features import DenseFeatures
    from photon_ml_tpu.ops.normalization import NormalizationContext
    from photon_ml_tpu.ops.objective import GLMBatch
    from photon_ml_tpu.ops.regularization import RegularizationContext
    from photon_ml_tpu.optim.common import OptimizerConfig
    from photon_ml_tpu.optim.problem import GLMOptimizationProblem
    from photon_ml_tpu.parallel import DistributedFixedEffectSolver, MeshContext
    from photon_ml_tpu.types import OptimizerType, TaskType

    rows = 400000
    monkeypatch.setattr(fused_glm, "_on_tpu", lambda: True)
    monkeypatch.setattr(fused_glm, "_interpret_default", lambda: False)
    monkeypatch.delenv("PHOTON_ML_TPU_FUSED", raising=False)
    mesh = Mesh(np.array(four_chips), ("data",))
    solver = DistributedFixedEffectSolver(
        GLMOptimizationProblem(
            task=TaskType.LOGISTIC_REGRESSION, optimizer=OptimizerType.LBFGS,
            optimizer_config=OptimizerConfig(max_iterations=ITERATIONS, tolerance=0.0),
            regularization=RegularizationContext.l2(100.0)),
        MeshContext(mesh))
    shape = lambda spec, *s: jax.ShapeDtypeStruct(
        s, jnp.float32, sharding=NamedSharding(mesh, spec))
    split, whole = PartitionSpec("data"), PartitionSpec()
    n = rows * len(four_chips)
    batch = GLMBatch(DenseFeatures(shape(split, n, width)),
                     shape(split, n), shape(split, n), shape(split, n))
    solver.problem = solver.problem.with_fused_block_for(batch, len(four_chips))
    assert solver.problem.fused_block_rows == 640
    compiled = solver._build(NormalizationContext.identity()).lower(
        batch, shape(whole, width), shape(whole)).compile()
    _reads_the_matrix_once(compiled, rows, width)
    assert "all-reduce" in compiled.as_text()


#: (rows, width, storage): shapes just over the selection's line and odd ones
#: over it: a tail of rows, fewer features than a tile, one 128-row chunk of
#: a wide matrix, either orientation, either storage
NEAR_THE_LINE = [(8197, 2000, "float32"), (300000, 30, "float32"), (1024, 10000, "float32"),
                 (8320, 2048, "float32"), (16640, 2048, "bfloat16"), (66000, 300, "bfloat16")]


@pytest.mark.parametrize("rows,width,storage", NEAR_THE_LINE)
def test_what_the_selection_hands_out_compiles_on_the_v5e(one_chip, monkeypatch, rows, width, storage):
    """A block ``select_fused_block_rows`` gives on a TPU is one the v5e's
    compiler takes, reading the matrix in the layout it arrives in."""
    import re

    from photon_ml_tpu.ops import fused_glm, losses

    monkeypatch.setattr(fused_glm, "_on_tpu", lambda: True)
    monkeypatch.delenv("PHOTON_ML_TPU_FUSED", raising=False)
    dtype = jnp.dtype(storage)
    block = fused_glm.select_fused_block_rows(rows, width, dtype)
    assert block is not None
    shape = lambda *s, of=jnp.float32: jax.ShapeDtypeStruct(s, of, sharding=one_chip)
    text = jax.jit(
        lambda x, y, wt, off, w: fused_glm.fused_value_grad_parts(
            losses.logistic, x, y, wt, off, w, block_rows=block, interpret=False)
    ).lower(shape(rows, width, of=dtype), shape(rows), shape(rows), shape(rows),
            shape(width)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    whole = r"\[(%d,%d|%d,%d)\]" % (rows, width, width, rows)
    assert not re.search(r"= \w+" + whole + r"\S* (copy|transpose|fusion|convert)\(", text)


# -- the wide fixed effect's tile layout on the v5e (the sparse cell's job) -----


def test_the_sparse_cells_solve_runs_both_kernels_on_the_v5e(one_chip, monkeypatch):
    """``training._solve`` at the sparse cell's own shape (2^22 rows of 64
    over 2^21 features, float32) on features that carry the tile layout in
    the shipped geometry, compiled for the v5e: every evaluation (the one
    before the solver's loop and the line search's) is one gather kernel and
    one scatter-add kernel under the scopes the benchmark's metrics read,
    and no element-by-element gather or scatter of the stored values is
    left."""
    import re

    from photon_ml_tpu import training
    from photon_ml_tpu.ops import tiled_sparse as ts
    from photon_ml_tpu.ops.features import SparseFeatures
    from photon_ml_tpu.ops.normalization import NormalizationContext
    from photon_ml_tpu.ops.objective import GLMBatch
    from photon_ml_tpu.ops.regularization import RegularizationContext
    from photon_ml_tpu.optim.common import OptimizerConfig
    from photon_ml_tpu.optim.problem import GLMOptimizationProblem
    from photon_ml_tpu.types import OptimizerType, TaskType

    n, k, dim = 1 << 22, 64, 1 << 21
    from photon_ml_tpu.ops import fused_glm

    # the process's backend is the CPU: steer the one place that asks
    monkeypatch.setattr(fused_glm, "_interpret_default", lambda: False)
    g = ts.GEOMETRY
    chunks = g.blocks(n) * g.slots_per_block(k, dim) // g.chunk
    shape = lambda dtype, *s: jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)
    f32 = functools.partial(shape, jnp.float32)
    layout = ts.TileLayout(
        shape(jnp.int32, chunks // g.group, 1, g.group),
        shape(jnp.int32, chunks, g.chunk), f32(chunks, g.chunk), g, n, k, dim)
    assert layout.check(n) is layout
    feats = SparseFeatures(shape(jnp.int32, n, k), f32(n, k), dim, tiles=layout)
    problem = GLMOptimizationProblem(
        task=TaskType.LOGISTIC_REGRESSION, optimizer=OptimizerType.LBFGS,
        optimizer_config=OptimizerConfig(
            max_iterations=2, tolerance=0.0, num_corrections=10),
        regularization=RegularizationContext.l2(1.0))
    training._solve.clear_cache()
    compiled = training._solve.lower(
        problem, GLMBatch(feats, f32(n), f32(n), f32(n)),
        NormalizationContext.identity(), f32(dim), f32()).compile()
    training._solve.clear_cache()
    text = compiled.as_text()
    calls = [l for l in text.splitlines() if 'custom_call_target="tpu_custom_call"' in l]
    inside = "pml.objective.value_and_grad/pml.features.%s/pml.features.tile_%s"
    for product in ("matvec", "rmatvec"):
        mine = [l for l in calls if inside % (product, product) in l]
        assert len(mine) == 2, (product, len(mine))
        assert sum("pml.lbfgs.line_search" in l for l in mine) == 1
    assert len(calls) == 4
    # the row-order arrays are parameters the solve never reads
    rows = [l for l in text.splitlines() if "[%d,%d]" % (n, k) in l]
    assert all(re.match(r"HloModule |\s*%\S+ = \w+\[%d,%d\]\S* parameter\(" % (n, k), l)
               for l in rows), [l[:160] for l in rows]
    # temporaries: the solver's history and row vectors, no longer the 4.3 GB
    # of two padded copies of the rows (PERF.md section 7)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
