"""The second layout of a wide padded-sparse batch: its stored non-zeros in
(row block x feature tile) buckets, on which the gather ``w[indices]`` and the
scatter-add ``.at[indices].add`` run as one-hot products on the matrix unit.

Element by element the chip takes 7 ns a stored non-zero either way, because
any of ``dim`` addresses may come next (PERF.md section 5). Here the non-zeros
of ``block_rows`` consecutive rows are sorted by feature tile
(``tile_features`` features) and cut into chunks of ``chunk`` slots that touch
one block of rows and one tile of features only. Within a chunk "pick
``w[f]``" is a product of a one-hot matrix with the tile's 128-lane rows and a
lane mask, and "add onto ``g[f]``" is its transpose; the block of rows is
addressed the same way from the other side. A one-hot matrix is exact in
bfloat16; a float32 operand goes through the unit as three bfloat16 pieces
that add up to it exactly (:func:`split3`), with float32 accumulation, so a
gather returns the float32 value itself and a scatter-add a
float32-accumulated sum of float32 products: the products are float32, as
``SparseFeatures``' row-order ones are.

The layout is built once, on the device, when the batch is placed
(``ops.features.auto_transpose``), never inside a job. It is in feature
order within a block and cannot be cut, padded or sharded by rows: whatever
rebuilds a ``SparseFeatures`` from rows drops it, and :func:`matvec` /
:func:`rmatvec` refuse a layout whose shapes do not fit the rows beside it.

Padding is bounded for any popularity of the features: every bucket is padded
to a whole chunk, at most ``chunk - 1`` slots for each of a block's
``ceil(dim / tile_features)`` tiles, and every block holds the same number of
slots, that bound included (:meth:`Geometry.slots_per_block`); the chunks no
bucket needed are marked dead and skipped. No tile is treated specially.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import time

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from photon_ml_tpu.ops import fused_glm

Array = jax.Array
logger = logging.getLogger(__name__)

LANES = 128
#: a dead chunk's tile: no bucket needed the chunk, the kernels skip it
DEAD = -1

#: The rule's lines (``ops.features.auto_transpose``). Widest feature space the
#: kernels hold in fast memory: the gather keeps ``6 * dim`` bytes of ``w``
#: resident, the scatter-add ``8 * dim`` of sums, of the v5e's 128 MiB.
MAX_DIM = 1 << 22
_VMEM_LIMIT = 100 * 1024 * 1024


@dataclasses.dataclass(frozen=True)
class Geometry:
    """Sizes of the layout, all powers of two; the shipped ones won races on
    the v5e at (2^22, 64) over 2^21 features (PERF.md section 6): tables of 32
    rows cost more a chunk than 64, not less, and pad more (0.243 against 0.158
    s a product), 128 rows nearly twice as much, chunks of 512 over a third
    more. A chunk is the unit of padding; a grid step's chunks are unrolled,
    so that the compiler runs one chunk's second product under the next one's
    first: 64 a step beat 32 by 4 to 5 %."""

    block_rows: int = 8192  # rows a block: a table of 64 x 128
    tile_features: int = 8192  # features a tile: a table of 64 x 128
    chunk: int = 256  # slots a chunk
    group: int = 64  # chunks a grid step

    def __post_init__(self):
        for v in dataclasses.astuple(self):
            assert v > 0 and v & (v - 1) == 0, self
        assert self.block_rows % LANES == 0 and self.tile_features % (16 * LANES) == 0
        assert self.chunk % LANES == 0 and self.group % 8 == 0

    def blocks(self, n: int) -> int:
        return -(-n // self.block_rows)

    def tiles(self, dim: int) -> int:
        return -(-dim // self.tile_features)

    def slots_per_block(self, k: int, dim: int) -> int:
        """Every stored value of a block's rows, a chunk of padding for each
        tile, rounded up to whole grid steps."""
        step = self.chunk * self.group
        need = self.block_rows * k + self.tiles(dim) * self.chunk
        return -(-need // step) * step

    def worst_padding(self, k: int, dim: int) -> float:
        """Slots over stored values, whatever the features' popularity."""
        return self.slots_per_block(k, dim) / (self.block_rows * k)


GEOMETRY = Geometry()


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class TileLayout:
    """``SparseFeatures``' second layout. Slot ``j`` of chunk ``c`` holds a
    value and its packed address ``local row << log2(tile_features) | local
    feature``; chunk ``c`` lies in block ``c // chunks a block`` and in tile
    ``chunk_tile`` (:data:`DEAD`: skipped). Padding slots hold 0 at address 0."""

    chunk_tile: Array  # (grid steps, 1, group) int32: a step's are one block in SMEM
    ids: Array  # (chunks, chunk) int32
    vals: Array  # (chunks, chunk) float32
    geometry: Geometry
    num_rows: int
    nnz_per_row: int
    dim: int

    def tree_flatten(self):
        return ((self.chunk_tile, self.ids, self.vals),
                (self.geometry, self.num_rows, self.nnz_per_row, self.dim))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    def check(self, num_rows: int) -> "TileLayout":
        """Refuse, at trace time, a layout that was cut beside its rows or
        whose rows were cut beside it."""
        g = self.geometry
        chunks = g.blocks(self.num_rows) * (
            g.slots_per_block(self.nnz_per_row, self.dim) // g.chunk)
        if (num_rows != self.num_rows
                or self.vals.shape != (chunks, g.chunk)
                or self.ids.shape != self.vals.shape
                or self.chunk_tile.shape != (chunks // g.group, 1, g.group)):
            raise ValueError(
                f"the tile layout of {self.num_rows} rows does not fit "
                f"{num_rows} rows with arrays {self.vals.shape}, "
                f"{self.chunk_tile.shape}: it is in feature order and cannot "
                "be cut, padded or sharded by rows; rebuild the features")
        return self


def _thirds(x: Array):
    """float32 ``x`` as three float32 (hi, mid, lo), each exact in bfloat16,
    with ``hi + mid + lo == x`` exactly in any order of float32 addition: they
    are ``x``'s mantissa cut into thirds, so they share no bit and have one
    sign."""
    top = jnp.int32(-65536)  # the sign, the exponent and seven mantissa bits
    cut = lambda v: lax.bitcast_convert_type(
        lax.bitcast_convert_type(v, jnp.int32) & top, jnp.float32)
    hi = cut(x)
    rest = x - hi
    mid = cut(rest)
    return hi, mid, rest - mid


def split3(x: Array):
    """:func:`_thirds` as the matrix unit takes them: three bfloat16."""
    return tuple(p.astype(jnp.bfloat16) for p in _thirds(x))


def pieces_table(x: Array, rows: int) -> Array:
    """``x`` (a multiple of ``rows * 128`` long) as the kernels read a table:
    for each ``rows * 128`` of it the three pieces' ``(rows, 128)`` one under
    the other, ``(len / 128 * 3, 128)`` bfloat16."""
    parts = split3(x.astype(jnp.float32).reshape(-1, rows, LANES))
    return jnp.stack(parts, axis=1).reshape(-1, LANES)


def _padded(x: Array, size: int) -> Array:
    return x if x.shape[0] == size else jnp.pad(x, (0, size - x.shape[0]))


def pairwise_sum(x: Array) -> Array:
    """The sum of a vector by halves, ``x[:h] + x[h:]`` until one is left: a
    balanced tree of float32 adds (2^k equal terms sum exactly) out of
    contiguous slices. ``fused_sparse.tree_row_sum`` pairs neighbours, and its
    lane-strided slices of 2^22 losses took the v5e 66 ms an evaluation
    (PERF.md section 6, PR 36)."""
    x = _padded(x, 1 << max(x.shape[0] - 1, 0).bit_length())
    while x.shape[0] > 1:
        half = x.shape[0] // 2
        x = x[:half] + x[half:]
    return x[0]


# -- the kernels ---------------------------------------------------------------


def _onehot(rows, index):
    """(rows, chunk) bfloat16: 1 where the sublane's number is the slot's
    ``index`` ((1, chunk) int32)."""
    at = lax.broadcasted_iota(jnp.int32, (rows, index.shape[1]), 0)
    return jnp.where(at == index, 1.0, 0.0).astype(jnp.bfloat16)


def _pick(table, high, low, rows: int):
    """Per slot, ``table2d[high, low]`` in float32, exactly. ``table``:
    ``(3 * rows, 128)`` bfloat16, the three pieces of a float32 ``(rows,
    128)``. A one-hot product selects each slot's lane, the pieces are added
    (exact) and a mask keeps the slot's row."""
    lanes = jnp.dot(table, _onehot(LANES, low),
                    preferred_element_type=jnp.float32)  # (3 rows, chunk)
    lanes = lanes[:rows] + lanes[rows:2 * rows] + lanes[2 * rows:]
    at = lax.broadcasted_iota(jnp.int32, lanes.shape, 0)
    return jnp.sum(jnp.where(at == high, lanes, 0.0), axis=0, keepdims=True)  # lint: bitwise-reduction — one non-zero a column: exact in any order


def _spread(x, high, low, rows: int):
    """``(rows, 128)`` float32: every slot's ``x`` ((1, chunk) float32) added
    at ``[high, low]``: the transpose of :func:`_pick`, ``x`` in three pieces
    on the rows' side, float32 accumulation over the chunk."""
    at = lax.broadcasted_iota(jnp.int32, (rows, x.shape[1]), 0) == high
    lane = _onehot(LANES, low)
    nt = (((1,), (1,)), ((), ()))
    return sum(
        lax.dot_general(jnp.where(at, p, 0.0).astype(jnp.bfloat16), lane, nt,
                        preferred_element_type=jnp.float32)
        for p in _thirds(x))


def _addresses(ids, g: Geometry):
    """(row high, row low, feature high, feature low) of packed ``ids``."""
    shift = g.tile_features.bit_length() - 1
    feat, row = ids & (g.tile_features - 1), ids >> shift
    return row >> 7, row & (LANES - 1), feat >> 7, feat & (LANES - 1)


def _matvec_kernel(g: Geometry, tile_ref, ids_ref, vals_ref, w_ref, z_ref):
    """One grid step: ``group`` chunks of one block of rows. ``w_ref``: the
    pieces of all of ``w``, resident; ``z_ref``: the block's margins as
    ``(block_rows / 128, 128)``, resident across the block's steps."""
    rows, feats = g.block_rows // LANES, g.tile_features // LANES

    @pl.when(pl.program_id(1) == 0)
    def _():
        z_ref[...] = jnp.zeros_like(z_ref)

    def chunk(c):
        # a dead chunk among live ones holds zeros: it reads tile 0 and adds 0
        tile = jnp.maximum(tile_ref[0, c], 0)
        r_hi, r_lo, f_hi, f_lo = _addresses(ids_ref[pl.ds(c, 1), :], g)
        table = w_ref[pl.ds(pl.multiple_of(tile * (3 * feats), 3 * feats),
                            3 * feats), :]
        prod = _pick(table, f_hi, f_lo, feats) * vals_ref[pl.ds(c, 1), :]
        z_ref[...] += _spread(prod, r_hi, r_lo, rows)

    # a block's dead chunks are its last: a step that starts dead is all dead
    @pl.when(tile_ref[0, 0] != DEAD)
    def _():
        for c in range(g.group):
            chunk(c)


def _rmatvec_kernel(g: Geometry, tile_ref, ids_ref, vals_ref, d_ref, out_ref,
                    block_ref):
    """The transpose. ``d_ref``: the pieces of the block's slopes;
    ``block_ref``: the sums of this block of rows over all of ``dim``,
    resident, added onto ``out_ref`` (resident too) when the block ends, so
    that a feature's sum is a sum of block sums of chunk sums and no single
    float32 runs through all of its addends."""
    rows, feats = g.block_rows // LANES, g.tile_features // LANES
    first = pl.program_id(1) == 0
    slab = feats  # a tile's rows at a time

    def slabs(body):
        def step(i, carry):
            body(pl.ds(pl.multiple_of(i * slab, slab), slab))
            return carry

        lax.fori_loop(0, out_ref.shape[0] // slab, step, 0)

    @pl.when(first & (pl.program_id(0) == 0))
    def _():
        def zero(at):
            out_ref[at, :] = jnp.zeros((slab, LANES), jnp.float32)
            block_ref[at, :] = jnp.zeros((slab, LANES), jnp.float32)

        slabs(zero)

    def chunk(c):
        tile = jnp.maximum(tile_ref[0, c], 0)
        r_hi, r_lo, f_hi, f_lo = _addresses(ids_ref[pl.ds(c, 1), :], g)
        prod = _pick(d_ref[...], r_hi, r_lo, rows) * vals_ref[pl.ds(c, 1), :]
        at = pl.ds(pl.multiple_of(tile * feats, feats), feats)
        block_ref[at, :] += _spread(prod, f_hi, f_lo, feats)

    @pl.when(tile_ref[0, 0] != DEAD)
    def _():
        for c in range(g.group):
            chunk(c)

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _():
        def fold(at):
            out_ref[at, :] += block_ref[at, :]
            block_ref[at, :] = jnp.zeros((slab, LANES), jnp.float32)

        slabs(fold)


def _specs(t: TileLayout):
    g = t.geometry
    steps = g.slots_per_block(t.nnz_per_row, t.dim) // (g.chunk * g.group)
    step = lambda b, s: (b * steps + s, 0)
    return (g.blocks(t.num_rows), steps), [
        pl.BlockSpec((None, 1, g.group), lambda b, s: (b * steps + s, 0, 0),
                     memory_space=pltpu.SMEM),
        pl.BlockSpec((g.group, g.chunk), step),
        pl.BlockSpec((g.group, g.chunk), step),
    ]


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT)


@jax.named_scope("pml.features.tile_matvec")
def matvec(t: TileLayout, w: Array) -> Array:
    """``X @ w`` over the layout: ``(num_rows,)`` float32."""
    g = t.geometry
    rows, feats = g.block_rows // LANES, g.tile_features // LANES
    grid, specs = _specs(t)
    table = pieces_table(_padded(w, g.tiles(t.dim) * g.tile_features), feats)
    z = pl.pallas_call(
        functools.partial(_matvec_kernel, g),
        grid=grid,
        in_specs=specs + [pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((rows, LANES), lambda b, s: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((grid[0] * rows, LANES), jnp.float32),
        compiler_params=_PARAMS,
        interpret=fused_glm._interpret_default(),
    )(t.chunk_tile, t.ids, t.vals, table)
    return z.reshape(-1)[:t.num_rows]


@jax.named_scope("pml.features.tile_rmatvec")
def rmatvec(t: TileLayout, d: Array) -> Array:
    """``X^T @ d`` over the layout: ``(dim,)`` float32."""
    g = t.geometry
    rows, feats = g.block_rows // LANES, g.tile_features // LANES
    grid, specs = _specs(t)
    table = pieces_table(_padded(d, grid[0] * g.block_rows), rows)
    shape = (g.tiles(t.dim) * feats, LANES)
    out = pl.pallas_call(
        functools.partial(_rmatvec_kernel, g),
        grid=grid,
        in_specs=specs + [pl.BlockSpec((3 * rows, LANES), lambda b, s: (b, 0))],
        out_specs=pl.BlockSpec(shape, lambda b, s: (0, 0)),
        out_shape=jax.ShapeDtypeStruct(shape, jnp.float32),
        scratch_shapes=[pltpu.VMEM(shape, jnp.float32)],
        compiler_params=_PARAMS,
        interpret=fused_glm._interpret_default(),
    )(t.chunk_tile, t.ids, t.vals, table)
    return out.reshape(-1)[:t.dim]


@dataclasses.dataclass(frozen=True)
class TiledFeatures:
    """What ``GLMObjective.value_and_grad`` reads of a ``SparseFeatures`` that
    carries the layout (``SparseFeatures.tiled()``): the two products under
    the names every layout's run under, each with the kernel's own scope
    inside, whose executions a trace counts beside the pass's."""

    layout: TileLayout

    @property
    def num_rows(self) -> int:
        return self.layout.num_rows

    @property
    def dim(self) -> int:
        return self.layout.dim

    @jax.named_scope("pml.features.matvec")
    def matvec(self, w: Array) -> Array:
        return matvec(self.layout, w)

    @jax.named_scope("pml.features.rmatvec")
    def rmatvec(self, d: Array) -> Array:
        return rmatvec(self.layout, d)


# -- the build -----------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("dim", "g"))
def _build(indices: Array, values: Array, dim: int, g: Geometry):
    n, k = indices.shape
    blocks, tiles = g.blocks(n), g.tiles(dim)
    slots = g.slots_per_block(k, dim)
    stored = g.block_rows * k
    shift = g.tile_features.bit_length() - 1
    if blocks * g.block_rows != n:
        grow = ((0, blocks * g.block_rows - n), (0, 0))
        indices, values = jnp.pad(indices, grow), jnp.pad(values, grow)
    local_row = jnp.repeat(jnp.arange(g.block_rows, dtype=jnp.int32), k)
    tile_ids = jnp.arange(tiles, dtype=jnp.int32)

    def block(rows):
        idx, val = (a.reshape(stored) for a in rows)
        live = val != 0
        # the padded layout's own empty slots are no stored values: their key
        # sorts behind every tile's
        key = jnp.where(live, idx >> shift, tiles)
        count = jnp.sum(key[:, None] == tile_ids[None, :], axis=0, dtype=jnp.int32)  # lint: bitwise-reduction — an integer count
        short = -count % g.chunk  # slots that fill each bucket's last chunk
        filler = jnp.where(
            jnp.arange(g.chunk, dtype=jnp.int32)[None, :] < short[:, None],
            tile_ids[:, None], tiles).reshape(-1)
        spare = jnp.full((slots - stored - tiles * g.chunk,), tiles, jnp.int32)
        empty = slots - stored
        key, ids, val = lax.sort(
            (jnp.concatenate([key, filler, spare]),
             jnp.concatenate([
                 jnp.where(live, local_row << shift | idx & (g.tile_features - 1), 0),
                 jnp.zeros((empty,), jnp.int32)]),
             jnp.concatenate([val, jnp.zeros((empty,), val.dtype)])),
            num_keys=1, is_stable=False)
        first = key[::g.chunk]
        return (jnp.where(first == tiles, DEAD, first).reshape(-1, 1, g.group),
                ids.reshape(-1, g.chunk), val.reshape(-1, g.chunk))

    cut = lambda a: a.reshape(blocks, g.block_rows, k)
    out = lax.map(block, (cut(indices), cut(values)))
    return tuple(a.reshape(-1, *a.shape[2:]) for a in out)


def build(indices: Array, values: Array, dim: int,
          geometry: Geometry = GEOMETRY) -> TileLayout:
    """The layout of ``(indices, values)``, made on the device they are on,
    under the host span ``pml.features.tile_layout``; blocks until it is
    there, so that the span and the log hold the build's seconds."""
    from photon_ml_tpu.utils import profiling

    n, k = indices.shape
    g = geometry
    chunks = g.blocks(n) * g.slots_per_block(k, dim) // g.chunk
    nbytes = chunks * (8 * g.chunk + 4)
    facts = dict(block_rows=g.block_rows, tile_features=g.tile_features,
                 chunk=g.chunk, group=g.group, tiles=g.tiles(dim), chunks=chunks,
                 slots_over_stored=chunks * g.chunk / max(n * k, 1), bytes=nbytes)
    t0 = time.perf_counter()
    with profiling.span("pml.features.tile_layout", **facts):
        arrays = jax.block_until_ready(_build(indices, values, dim, g))
    layout = TileLayout(*arrays, g, n, k, dim)
    logger.info("tile layout of (%d, %d) over %d features: %s, %.3f s",
                n, k, dim, facts, time.perf_counter() - t0)
    return layout.check(n)
