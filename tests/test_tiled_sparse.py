"""The tile layout of a wide padded-sparse batch (``ops/tiled_sparse.py``).

The production rule builds it on a TPU only, so here the layout is built
directly at a small geometry and the two kernels run in Pallas' interpret
mode (the same arithmetic: exact bfloat16 one-hot operands, three-piece
float32 operands, float32 accumulation). Rows are drawn with the benchmark
cell's popularity power (feature ``floor(D u^3)``) and uniformly: the layout
must not lean on the skew. The chip's compiler is asked about the kernels in
``tests/test_dense_grid_reference.py``, the one file that loads it.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.ops import tiled_sparse as ts
from photon_ml_tpu.ops.features import SparseFeatures, auto_transpose, wants_tiles
from photon_ml_tpu.ops.losses import logistic
from photon_ml_tpu.ops.normalization import NormalizationContext
from photon_ml_tpu.ops.objective import (
    ROW_BLOCK_NNZ, GLMBatch, GLMObjective, _block_rows, _row_sum)

#: blocks of 256 rows, tiles of 2,048 features, chunks of 128 slots
SMALL = ts.Geometry(block_rows=256, tile_features=2048, chunk=128, group=8)
#: tables of 32 rows a side: the kernels at another table height than SMALL's
TABLES32 = ts.Geometry(block_rows=4096, tile_features=4096, chunk=128, group=8)
GEOMETRIES = {"small": SMALL, "tables32": TABLES32}
POPULARITY = {"power3": 3.0, "uniform": 1.0}


def _rows(popularity: str, n=700, k=8, dim=5000, seed=0, empty=0.1):
    """(indices, values) with some of the padded layout's own empty slots."""
    rng = np.random.default_rng(seed)
    idx = np.minimum((dim * rng.random((n, k)) ** POPULARITY[popularity])
                     .astype(np.int32), dim - 1)
    val = rng.standard_normal((n, k)).astype(np.float32)
    val[rng.random((n, k)) < empty] = 0.0
    return idx, val, dim


def _tiled(idx, val, dim, geometry=SMALL) -> SparseFeatures:
    feats = SparseFeatures(jnp.asarray(idx), jnp.asarray(val), dim)
    return dataclasses.replace(
        feats, tiles=ts.build(feats.indices, feats.values, dim, geometry))


def _shape(geometry: ts.Geometry):
    """Rows and features that cut the last block and the last tile: 700 and
    5,000 for :data:`SMALL`."""
    return 2 * geometry.block_rows + 188, 2 * geometry.tile_features + 904


def _relative(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


# -- the layout ----------------------------------------------------------------


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
@pytest.mark.parametrize("popularity", sorted(POPULARITY))
@pytest.mark.parametrize("whole_blocks", [False, True])
def test_layout_holds_every_stored_value_once_and_pads_with_zeros(geometry, popularity,
                                                                   whole_blocks):
    g = GEOMETRIES[geometry]
    n, dim = _shape(g)
    n -= 188 if whole_blocks else 0
    idx, val, dim = _rows(popularity, n=n, dim=dim)
    t = _tiled(idx, val, dim, g).tiles
    assert t.geometry == g
    shift = g.tile_features.bit_length() - 1
    vals, ids = np.asarray(t.vals), np.asarray(t.ids)
    tile = np.asarray(t.chunk_tile).reshape(-1)
    per_block = vals.shape[0] // g.blocks(n)
    block = np.arange(vals.shape[0]) // per_block
    # a dead chunk holds nothing, and a block's dead chunks are its last
    assert not vals[tile == ts.DEAD].any() and not ids[tile == ts.DEAD].any()
    for b in range(g.blocks(n)):
        dead = tile[block == b] == ts.DEAD
        assert not dead[:-1][~dead[1:]].any()
    live = vals != 0
    c, _ = np.nonzero(live)
    got = np.stack([block[c] * g.block_rows + (ids[live] >> shift),
                    tile[c] * g.tile_features + (ids[live] & (g.tile_features - 1)),
                    vals[live].view(np.int32)], axis=1)
    r, s = np.nonzero(val)
    want = np.stack([r, idx[r, s], val[r, s].view(np.int32)], axis=1)
    order = lambda a: a[np.lexsort(a.T[::-1])]
    assert np.array_equal(order(got), order(want))
    # padding: zeros at address 0, and never over the bound, for any popularity
    assert not ids[~live].any()
    assert vals.size <= g.worst_padding(val.shape[1], dim) * g.blocks(n) * g.block_rows * val.shape[1]


@pytest.mark.parametrize("k,dim,bound", [(64, 1 << 21, 1.125), (64, 1 << 16, 1.032), (16, 1 << 22, 2.0)])
def test_the_shipped_geometrys_padding_is_bounded_for_any_popularity(k, dim, bound):
    """Slots over stored values, from the shapes alone: what the benchmark's
    sparse cell pays at most, a narrow feature space (its 8 chunks of padding
    a block rounded up to a whole step of 64 chunks), few values a row."""
    assert ts.GEOMETRY.worst_padding(k, dim) <= bound


def test_a_layout_cut_beside_its_rows_is_refused():
    idx, val, dim = _rows("power3")
    whole = _tiled(idx, val, dim)
    cut = SparseFeatures(whole.indices[:300], whole.values[:300], dim, tiles=whole.tiles)
    with pytest.raises(ValueError, match="cannot be cut"):
        cut.tiled()
    halved = jax.tree_util.tree_map(lambda a: a[: a.shape[0] // 2], whole)
    with pytest.raises(ValueError, match="cannot be cut"):
        halved.tiled()


# -- the two products ------------------------------------------------------------


def _wide_floats(rng, n):
    """float32 of every sign over sixty binades, all 24 bits in use."""
    return (rng.standard_normal(n) * 2.0 ** rng.integers(-30, 30, n)).astype(np.float32)


def test_three_pieces_add_up_exactly_in_any_order():
    x = _wide_floats(np.random.default_rng(1), 1 << 16)
    hi, mid, lo = (np.asarray(p.astype(jnp.float32)) for p in ts.split3(jnp.asarray(x)))
    for a, b, c in [(hi, mid, lo), (lo, mid, hi), (hi, lo, mid)]:
        assert np.array_equal((a + b) + c, x)
    assert np.array_equal(np.asarray(ts.split3(jnp.asarray(x))[0]),
                          np.asarray(jnp.asarray(hi).astype(jnp.bfloat16)))


@pytest.mark.parametrize("rows", [16, 32, 128])
def test_pick_returns_the_float32_itself(rows):
    """The gather's arithmetic alone: a one-hot product over the pieces'
    table and a lane mask give ``table[high, low]`` bit for bit."""
    rng = np.random.default_rng(rows)
    table = _wide_floats(rng, rows * ts.LANES)
    at = rng.integers(0, table.size, (1, 256)).astype(np.int32)
    got = ts._pick(ts.pieces_table(jnp.asarray(table), rows),
                   jnp.asarray(at >> 7), jnp.asarray(at & 127), rows)
    assert np.array_equal(np.asarray(got).view(np.int32), table[at].view(np.int32))


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
@pytest.mark.parametrize("popularity", sorted(POPULARITY))
def test_tiled_gather_is_w_of_indices_bit_for_bit(geometry, popularity):
    """One stored 1.0 a row: the margin is the gathered coefficient."""
    g = GEOMETRIES[geometry]
    n, dim = _shape(g)
    idx, _, dim = _rows(popularity, k=1, n=n - 100, dim=dim)
    w = _wide_floats(np.random.default_rng(2), dim)
    z = ts.matvec(_tiled(idx, np.ones_like(idx, np.float32), dim, g).tiles, jnp.asarray(w))
    assert np.array_equal(np.asarray(z).view(np.int32), w[idx[:, 0]].view(np.int32))


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
@pytest.mark.parametrize("popularity", sorted(POPULARITY))
def test_tiled_matvec_against_float64(geometry, popularity):
    g = GEOMETRIES[geometry]
    n, dim = _shape(g)
    idx, val, dim = _rows(popularity, n=n, dim=dim)
    w = np.random.default_rng(3).standard_normal(dim).astype(np.float32)
    z = ts.matvec(_tiled(idx, val, dim, g).tiles, jnp.asarray(w))
    assert _relative(z, (w[idx].astype(np.float64) * val).sum(1)) < 2e-7


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
@pytest.mark.parametrize("popularity,most", [("power3", 2000), ("uniform", 4)])
def test_tiled_scatter_add_against_float64(geometry, popularity, most):
    """Where one feature takes thousands of addends (the cell's popularity
    over 4,096 features: one stored value in 16) the sums stay within 1e-6 of
    a float64 ``bincount`` of the same float32 products, over two blocks of
    rows at least."""
    g = GEOMETRIES[geometry]
    idx, val, dim = _rows(popularity, n=max(4096, 2 * g.block_rows), k=8, dim=4096,
                          empty=0.0)
    assert np.bincount(idx.reshape(-1)).max() >= most
    d = np.random.default_rng(4).standard_normal(idx.shape[0]).astype(np.float32)
    got = ts.rmatvec(_tiled(idx, val, dim, g).tiles, jnp.asarray(d))
    exact = np.bincount(idx.reshape(-1), (val * d[:, None]).astype(np.float64).reshape(-1),
                        minlength=dim)
    assert _relative(got, exact) < 1e-6
    # and nearer than the row-order float32 scatter-add on the busiest feature
    assert abs(float(got[0]) - exact[0]) <= 4 * np.spacing(np.float32(abs(exact[0])))


# -- through the objective -------------------------------------------------------


@pytest.mark.parametrize("popularity", sorted(POPULARITY))
@pytest.mark.parametrize("l2", [0.0, 1.0])
def test_value_and_grad_through_the_layout_against_the_row_order_pass(popularity, l2):
    """Same mathematics, another order of addition: not bitwise (ROADMAP D11).
    A float32 sum of ``m`` terms moves by some ``sqrt(m)`` roundings with its
    order; the busiest feature here has under 2^12 addends."""
    idx, val, dim = _rows(popularity, n=1000)
    rng = np.random.default_rng(5)
    y = jnp.asarray((rng.random(idx.shape[0]) < 0.5).astype(np.float32))
    w = jnp.asarray(0.1 * rng.standard_normal(dim).astype(np.float32))
    tiled = _tiled(idx, val, dim)
    obj, norm = GLMObjective(logistic), NormalizationContext.identity()
    run = jax.jit(lambda feats: obj.value_and_grad(w, GLMBatch.create(feats, y), norm, l2))
    v0, g0 = run(tiled.without_tiles())
    v1, g1 = run(tiled)
    eps = float(jnp.finfo(jnp.float32).eps)
    assert abs(float(v0) - float(v1)) <= 8 * eps * abs(float(v0))
    assert _relative(g1, g0) <= 64 * eps


def test_the_pass_reads_the_layout_and_nothing_else_does(monkeypatch):
    """``value_and_grad`` runs both kernels; the margins, the Hessian-vector
    product and the Hessian's diagonal keep the row-order products."""
    idx, val, dim = _rows("power3", n=300)
    tiled = _tiled(idx, val, dim)
    calls = []
    for name in ("matvec", "rmatvec"):
        inner = getattr(ts, name)
        monkeypatch.setattr(ts, name, lambda *a, _f=inner, _n=name: (calls.append(_n), _f(*a))[1])
    y, w = jnp.ones((300,)), jnp.zeros((dim,))
    obj, norm = GLMObjective(logistic), NormalizationContext.identity()
    batch = GLMBatch.create(tiled, y)
    obj.value_and_grad(w, batch, norm, 1.0)
    assert calls == ["matvec", "rmatvec"]
    obj.value(w, batch, norm)
    obj.hessian_vector(w, w + 1.0, batch, norm)
    obj.hessian_diagonal(w, batch, norm)
    tiled.matvec(w), tiled.rmatvec(y), tiled.sq_rmatvec(y)
    assert calls == ["matvec", "rmatvec"]
    assert _block_rows(tiled) is None and tiled.without_tiles().tiled() is None


@pytest.mark.parametrize("loss", [np.log(2.0), 0.1, 3.0000002])
def test_the_loss_sum_of_equal_losses_is_exact(loss):
    idx, val, dim = _rows("uniform", n=256)
    view = _tiled(idx, val, dim).tiled()
    x = np.float32(loss)
    total = _row_sum(view, jnp.full((1 << 16,), x))
    assert float(total) == float(np.float64(x) * (1 << 16))


# -- the rule --------------------------------------------------------------------

BIG = ROW_BLOCK_NNZ // 64 + 1  # rows of 64 that just pass one row block

RULE = [
    # platform, indices, values, n, k, dim -> layout?
    ("tpu", "int32", "float32", 1 << 22, 64, 1 << 21, True),   # the cell
    ("tpu", "int32", "float32", BIG, 64, 1 << 16, True),       # the least of both
    ("tpu", "int32", "float32", BIG - 1, 64, 1 << 21, False),  # one row block
    ("tpu", "int32", "float32", 6040, 32, 1 << 21, False),     # a per-entity batch
    ("tpu", "int32", "float32", 1 << 22, 64, (1 << 16) - 1, False),  # narrow
    ("tpu", "int32", "float32", 1 << 22, 64, 1 << 22, True),
    ("tpu", "int32", "float32", 1 << 22, 64, (1 << 22) + 1, False),  # tables too wide
    ("tpu", "int32", "bfloat16", 1 << 22, 64, 1 << 21, False),
    ("tpu", "int32", "float64", 1 << 22, 64, 1 << 21, False),
    ("tpu", "int64", "float32", 1 << 22, 64, 1 << 21, False),
    ("cpu", "int32", "float32", 1 << 22, 64, 1 << 21, False),
    ("gpu", "int32", "float32", 1 << 22, 64, 1 << 21, False),
]


@pytest.mark.parametrize("case", RULE, ids=lambda c: "-".join(map(str, c[:-1])))
def test_the_rule_from_platform_dtype_and_shape(case):
    *seen, layout = case
    assert wants_tiles(*seen) is layout


def test_auto_transpose_builds_where_the_rule_says(monkeypatch):
    """Off a TPU placement builds nothing; where the rule says so the
    features come back with the shipped geometry's layout, and a second call
    builds nothing more. No flag, no environment variable."""
    from photon_ml_tpu.ops import features

    idx, val, dim = _rows("power3", n=200, dim=1 << 16)
    fresh = SparseFeatures(jnp.asarray(idx), jnp.asarray(val), dim)
    assert auto_transpose(fresh) is fresh
    monkeypatch.setattr(features, "wants_tiles", lambda *a: True)
    placed = auto_transpose(fresh)
    assert placed.tiles.geometry == ts.GEOMETRY and placed.tiles.num_rows == 200
    assert placed.indices is fresh.indices and placed.values is fresh.values
    assert auto_transpose(placed) is placed
    under_jit = jax.jit(lambda f: auto_transpose(f).tiles is None)
    assert under_jit(fresh)  # traced arrays are not placement
    narrow = SparseFeatures(fresh.indices, fresh.values, 1 << 10)
    assert auto_transpose(narrow) is narrow
    monkeypatch.setenv("PHOTON_ML_TPU_SPARSE_TRANSPOSE", "1")
    forced = auto_transpose(fresh)
    assert forced.t_idx is not None and forced.tiles is None


# -- whatever cuts, pads, shards or re-stores the rows leaves the layout behind --


def _batch(n=300):
    idx, val, dim = _rows("power3", n=n)
    return GLMBatch.create(_tiled(idx, val, dim), jnp.ones((n,)))


@pytest.mark.parametrize("how", ["pad_rows", "pad_rows_even", "astype", "with_transpose",
                                 "validators", "row_block"])
def test_rows_rebuilt_drop_the_layout(how):
    from photon_ml_tpu.parallel.mesh import pad_rows

    batch = _batch()
    feats = batch.features
    assert feats.tiles is not None
    if how == "pad_rows":
        out = pad_rows(batch, 8).features
        assert out.num_rows == 304
    elif how == "pad_rows_even":  # nothing to pad: what follows still shards
        out = pad_rows(batch, 4).features
        assert out.num_rows == 300
    elif how == "astype":
        out = feats.astype(jnp.bfloat16)
    elif how == "with_transpose":
        out = feats.with_transpose()
    elif how == "validators":
        from photon_ml_tpu.data.validators import _subsample

        out = _subsample(batch, 0.3).features
    else:
        out = SparseFeatures(feats.indices[:128], feats.values[:128], feats.dim)
    assert out.tiles is None and out.tiled() is None
    assert feats.tiles is not None  # the original keeps its own


def test_a_solve_through_the_layout_matches_the_row_order_solve():
    """``train_glm_grid`` builds nothing: it solves on what placement gave."""
    from photon_ml_tpu.optim.common import OptimizerConfig
    from photon_ml_tpu.optim.problem import GLMOptimizationProblem
    from photon_ml_tpu.ops.regularization import RegularizationContext
    from photon_ml_tpu.training import train_glm_grid
    from photon_ml_tpu.types import OptimizerType, TaskType

    idx, val, dim = _rows("power3", n=600)
    rng = np.random.default_rng(7)
    y = jnp.asarray((rng.random(600) < 0.5).astype(np.float32))
    tiled = _tiled(idx, val, dim)
    problem = GLMOptimizationProblem(
        task=TaskType.LOGISTIC_REGRESSION, optimizer=OptimizerType.LBFGS,
        optimizer_config=OptimizerConfig(max_iterations=3, tolerance=0.0, num_corrections=10),
        regularization=RegularizationContext.l2(1.0))
    norm = NormalizationContext.identity()
    fit = lambda feats: train_glm_grid(problem, GLMBatch.create(feats, y), norm, [1.0])
    a, b = fit(tiled.without_tiles()), fit(tiled)
    assert int(a.results[0].iterations) == int(b.results[0].iterations) == 3
    assert _relative(b.models[0].coefficients.means, a.models[0].coefficients.means) < 1e-5
