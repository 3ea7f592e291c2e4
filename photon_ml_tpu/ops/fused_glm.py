"""One-pass GLM value and gradient over a dense matrix: the training hot loop.

The GLM hot loop (ValueAndGradientAggregator semantics, SURVEY.md §2.2,
reference spec function/ValueAndGradientAggregator.scala:120-139) is
HBM-bandwidth-bound on TPU: the two XLA passes (margin ``X @ w``, gradient
``d @ X``) each stream the whole (N, D) feature matrix from HBM. The kernel
here makes them ONE pass: each block of rows is loaded into VMEM once and
used for the margins and for the gradient.

It is one kernel in two orientations, generic over any
:class:`PointwiseLoss`. It multiplies and reduces on the vector unit, so
float32 storage is float32 arithmetic, and it reads the matrix in the layout
the device holds it in (:func:`held_column_major`), so nothing is padded or
copied. It also gives ``sum(d)`` so callers can reconstruct the
normalization-shift gradient term (``grad_eff = X^T d - shifts * sum(d)``)
without a second data pass. It slots into ``GLMObjective.value_and_grad``
(see ``fused_block_rows`` there) behind :func:`select_fused_block_rows`, a
pure function of platform, dtype and shape: a number of rows a block on a
TPU for a matrix of more than ``MIN_MATRIX_BYTES``, ``None`` (the two-pass
path) elsewhere.

Many small problems at once, the per-entity lanes of a random effect, are
the same pass over a stack ``(E, M, D)``: :func:`lane_value_grad_parts`
reads a block of lanes once for their margins, losses and gradients, in the
layout the device holds the stack in, behind :func:`select_lane_block`
(platform, dtype and ``(M, D)``; ``algorithm/random_effect.entity_lane_fns``
hands it the vmapped lanes).

Numerically: margins/loss/derivative are computed in f32. Zero-weight rows
contribute exactly nothing (hard-masked, so even an inf/nan loss on such a
row is zeroed). Runs in interpreter mode on the CPU backend only (tests).
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from photon_ml_tpu.ops.losses import PointwiseLoss

_FUSED_ENV = "PHOTON_ML_TPU_FUSED"  # "auto" (default) | "0" (off) | "1" (force)


def _on_tpu() -> bool:
    """True when jax's default backend is the TPU."""
    return jax.default_backend() == "tpu"


def _interpret_default() -> bool:
    """Pallas interpret mode only where the backend is the CPU (tests): on
    any device backend the kernels go through the real compiler, so a
    kernel the compiler refuses is an error the run reports, not a silent
    drop into the interpreter."""
    return jax.default_backend() == "cpu"


def _first_line(exc: BaseException) -> str:
    """``Type: first line`` of an exception — what a race logs when the
    compiler refuses a candidate."""
    lines = str(exc).strip().splitlines()
    return f"{type(exc).__name__}: {lines[0] if lines else ''}"


# Mosaic's default scoped-VMEM limit, and how far a kernel may raise it
# (a v5e core has 128 MiB of VMEM; the compiler refuses a limit it cannot
# place).
_DEFAULT_SCOPED_VMEM = 16 << 20
_MAX_SCOPED_VMEM = 100 << 20


LANES = 128
# Sublane tiles (8 float32 rows of the transposed block, 16 bfloat16) one
# step of the rows-in-lanes kernel's inner loops handles: Mosaic does not
# pipeline a loop's steps, so a step of one tile waits out its own loads
# (PERF.md section 6, PR 32: the race's table).
_LANES_UNROLL = 5
# The most 128-row chunks a rows-in-lanes block may have: a chunk is one
# vector register of margin sums all through the pass, and the chip has 64.
_LANES_MAX_CHUNKS = 16


def _row_terms(loss: PointwiseLoss, z, y, wt):
    """(weighted loss, weighted slope) of each row from its margin, hard
    masked: a zero-weight row contributes an exact 0 even where its loss is
    inf or nan (a padding row, Poisson's exp overflow)."""
    alive = wt > 0.0
    return (jnp.where(alive, wt * loss.loss(z, y), 0.0),
            jnp.where(alive, wt * loss.d1(z, y), 0.0))


def held_column_major(n: int, d: int) -> bool:
    """Whether the TPU holds an ``(n, d)`` array column-major, that is as a
    row-major ``(d, n)``: the device's default layout is the order that pads
    least under its (8, 128) tiles, row-major on a tie
    (``f32[400000,2000]{0,1:T(8,128)}``: 400,000 is a multiple of 128 and
    2,000 is not). ``tests/test_dense_grid_reference.py`` holds this rule to
    the v5e's compiler."""
    pad = lambda size, tile: -(-size // tile) * tile
    return pad(d, 8) * pad(n, LANES) < pad(n, 8) * pad(d, LANES)


def _make_lanes_kernel(loss: PointwiseLoss, d: int, rows: int, tile: int):
    """The kernel on a matrix held column-major: a block is ``(d, rows)`` of
    the transposed matrix, rows along lanes, so the row vectors are
    lane-dense ``(1, rows)`` and neither contraction crosses lanes. Margins:
    ``tile`` features at a time, multiplied by their coefficients (spread
    over the lanes outside the kernel) and added into one ``(tile, 128)``
    sum per 128 rows, then reduced over sublanes. The
    gradient: each feature's products with the rows' slopes added over the
    block's 128-row chunks into a ``(d, 128)`` output block that stays in
    VMEM across the grid; its lanes are summed once, after the kernel. Loss
    terms and slopes go out per row and are summed after the kernel too."""
    chunks = rows // LANES
    group = tile * _LANES_UNROLL
    d_main = d - d % group
    lane = lambda k: slice(k * LANES, (k + 1) * LANES)

    def kernel(x_ref, y_ref, wt_ref, off_ref, w_ref, wl_out, d_out, grad_out):
        @pl.when(pl.program_id(0) == 0)
        def _():
            grad_out[...] = jnp.zeros_like(grad_out)

        def features(start, size):
            return [x_ref[pl.ds(start, size), lane(k)].astype(jnp.float32)
                    for k in range(chunks)]

        def margin_step(g, sums):
            start = pl.multiple_of(g * group, group)
            for t in range(_LANES_UNROLL):
                at = start + t * tile
                wg = w_ref[pl.ds(at, tile), :]
                sums = tuple(s + xk * wg for s, xk in zip(sums, features(at, tile)))
            return sums

        sums = lax.fori_loop(
            0, d_main // group, margin_step,
            tuple(jnp.zeros((tile, LANES), jnp.float32) for _ in range(chunks)))
        zs = [jnp.sum(s, axis=0, keepdims=True) for s in sums]  # lint: bitwise-reduction — pallas block-local reduce over the feature axis
        if d_main < d:  # the features the loop's step does not divide
            wg = w_ref[d_main:d, :]
            zs = [z + jnp.sum(xk * wg, axis=0, keepdims=True)  # lint: bitwise-reduction — pallas block-local reduce over the feature axis
                  for z, xk in zip(zs, features(d_main, d - d_main))]

        slopes = []
        for k, z in enumerate(zs):
            wl, dk = _row_terms(loss, z + off_ref[:, lane(k)],
                                y_ref[:, lane(k)], wt_ref[:, lane(k)])
            wl_out[:, lane(k)], d_out[:, lane(k)] = wl, dk
            slopes.append(dk)

        def add_products(start, size, spread):
            total = None
            for xk, dk in zip(features(start, size), spread):
                total = xk * dk if total is None else total + xk * dk
            grad_out[pl.ds(start, size), :] += total

        spread = [jnp.broadcast_to(dk, (tile, LANES)) for dk in slopes]

        def grad_step(g, carry):
            start = pl.multiple_of(g * group, group)
            for t in range(_LANES_UNROLL):
                add_products(start + t * tile, tile, spread)
            return carry

        lax.fori_loop(0, d_main // group, grad_step, 0)
        if d_main < d:
            add_products(d_main, d - d_main, [
                jnp.broadcast_to(dk, (d - d_main, LANES)) for dk in slopes])

    return kernel


def _make_sublanes_kernel(loss: PointwiseLoss, d: int, rows: int, tile: int):
    """The kernel on a matrix held row-major whose width is a multiple of
    128: a block is ``(rows, d)``, rows along sublanes. The row vectors stay
    lane-dense ``(1, rows)`` as in :func:`_make_lanes_kernel`;
    what crosses between the two forms is a 128 x 128 transpose a 128-row
    group each way. Margins: a group's rows times the coefficients (spread
    over ``tile`` sublanes outside the kernel) added over the width's
    128-lane chunks into 128 lane sums a row, transposed, and reduced over
    sublanes to one lane-dense margin a row. The gradient: the slopes spread
    over the lanes and transposed back to one a row, the products added over
    the group's rows into a ``(tile, d)`` output block that stays in VMEM
    across the grid; its sublanes are summed after the kernel."""
    chunks = d // LANES
    tiles = LANES // tile  # sublane tiles of a 128-row group
    lane = lambda c: slice(c * LANES, (c + 1) * LANES)

    def kernel(x_ref, y_ref, wt_ref, off_ref, w_ref, wl_out, d_out, grad_out):
        @pl.when(pl.program_id(0) == 0)
        def _():
            grad_out[...] = jnp.zeros_like(grad_out)

        def group(q, carry):
            at = pl.multiple_of(q * LANES, LANES)
            here = pl.ds(at, LANES)
            features = lambda t, c: x_ref[
                pl.ds(at + t * tile, tile), lane(c)].astype(jnp.float32)
            sums = [jnp.zeros((tile, LANES), jnp.float32) for _ in range(tiles)]
            for c in range(chunks):
                wc = w_ref[:, lane(c)]
                sums = [s + features(t, c) * wc for t, s in enumerate(sums)]
            z = jnp.sum(jnp.concatenate(sums, axis=0).T, axis=0, keepdims=True)  # lint: bitwise-reduction — pallas block-local reduce over the feature axis
            wl, slopes = _row_terms(
                loss, z + off_ref[:, here], y_ref[:, here], wt_ref[:, here])
            wl_out[:, here], d_out[:, here] = wl, slopes
            spread = jnp.broadcast_to(slopes, (LANES, LANES)).T  # [row, lane] = slopes[row]
            spread = [spread[t * tile:(t + 1) * tile, :] for t in range(tiles)]
            for c in range(chunks):
                total = None
                for t, dt in enumerate(spread):
                    p = features(t, c) * dt
                    total = p if total is None else total + p
                grad_out[:, lane(c)] += total
            return carry

        lax.fori_loop(0, rows // LANES, group, 0)

    return kernel


def _vpu_vmem_limit(rows: int, d: int, itemsize: int) -> Optional[int]:
    """``vmem_limit_bytes`` of either orientation: the pipeline's two
    buffers of the matrix block, of the spread coefficients and of the
    gradient block (at most ``(d, 128)`` float32 each), and the row
    vectors."""
    need = 2 * rows * d * itemsize + 4 * d * LANES * 4 + 10 * 8 * rows * 4
    if need + (2 << 20) <= _DEFAULT_SCOPED_VMEM:
        return None
    return min(need + (4 << 20), _MAX_SCOPED_VMEM)


@functools.lru_cache(maxsize=64)
def _fused_fn_vpu(loss: PointwiseLoss, block_rows: int, interpret: bool, lanes: bool):
    """Jitted single-pass (loss_sum, grad, sum_d) over the first
    ``n // block_rows`` blocks of the matrix, in the orientation the device
    holds it in: ``lanes`` for column-major (the kernel reads the transpose,
    a bitcast), else row-major with a width that is a multiple of 128."""

    @jax.jit
    def call(x, y, weights, offsets, w):
        n, d = x.shape
        grid = n // block_rows
        tile = 8 * (4 // x.dtype.itemsize)
        w = w.astype(jnp.float32)
        if lanes:
            kernel = _make_lanes_kernel(loss, d, block_rows, tile)
            x, block, at = x.T, (d, block_rows), lambda i: (0, i)
            spread = jnp.broadcast_to(w[:, None], (d, LANES))
        else:
            kernel = _make_sublanes_kernel(loss, d, block_rows, tile)
            block, at = (block_rows, d), lambda i: (i, 0)
            spread = jnp.broadcast_to(w, (tile, d))
        row = lambda v: v.reshape(1, n).astype(jnp.float32)
        rows_spec = pl.BlockSpec((1, block_rows), lambda i: (0, i))
        whole = pl.BlockSpec(spread.shape, lambda i: (0, 0))
        # under shard_map the outputs vary over the mesh axes the shard does
        out = functools.partial(
            jax.ShapeDtypeStruct, dtype=jnp.float32, vma=jax.typeof(x).vma)
        covered = out((1, grid * block_rows))
        wl, slopes, grad = pl.pallas_call(
            kernel,
            grid=(grid,),
            in_specs=[pl.BlockSpec(block, at), rows_spec, rows_spec, rows_spec, whole],
            out_specs=[rows_spec, rows_spec, whole],
            out_shape=[covered, covered, out(spread.shape)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=_vpu_vmem_limit(block_rows, d, x.dtype.itemsize),
            ),
            interpret=interpret,
        )(x, row(y), row(weights), row(offsets), spread)
        # one reduction each after the kernel, as on the two-pass path
        return jnp.sum(wl), jnp.sum(grad, axis=1 if lanes else 0), jnp.sum(slopes)  # lint: bitwise-reduction — dense-family canonical arithmetic

    return call


def _two_pass_parts(loss, x, y, weights, offsets, w):
    """(loss sum, X^T d, sum d) of a few rows in plain float32
    multiply-and-reduce: the rows a kernel's block does not divide."""
    xf = x.astype(jnp.float32)
    z = jnp.sum(xf * w, axis=1) + offsets  # lint: bitwise-reduction — the margins' feature axis, not a slab batch axis
    wl, d = _row_terms(loss, z, y, weights)
    return jnp.sum(wl), jnp.sum(xf * d[:, None], axis=0), jnp.sum(d)  # lint: bitwise-reduction — dense-family canonical arithmetic


@jax.named_scope("pml.features.value_grad")
def fused_value_grad_parts(
    loss: PointwiseLoss,
    x: jax.Array,
    y: jax.Array,
    weights: jax.Array,
    offsets: jax.Array,
    w: jax.Array,
    block_rows: int,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Raw single-pass pieces: (sum w_i*l_i, X^T d, sum d) with d = w_i*l'_i.

    No regularization, no normalization — the caller owns that algebra
    (``GLMObjective.value_and_grad`` folds shifts/factors/L2 around these).
    ``x``: (N, D), float32 or bfloat16; ``block_rows``: rows a block, as
    :func:`select_fused_block_rows` hands them out.

    The matrix is read where it lies and never padded or copied. A kernel
    in the orientation the device holds the matrix in
    (:func:`held_column_major`) walks the whole blocks of rows; the rows the
    block does not divide (fewer than a block) go through
    :func:`_two_pass_parts`, as does a matrix held row-major at a width that
    is no multiple of 128, which neither kernel serves.
    """
    if interpret is None:
        interpret = _interpret_default()
    n, d = x.shape
    lanes = held_column_major(n, d)
    block = min(block_rows, n) // LANES * LANES
    if lanes:
        block = min(block, _LANES_MAX_CHUNKS * LANES)
    elif d % LANES:
        block = 0
    covered = n // block * block if block else 0
    parts = []
    if covered:
        parts.append(_fused_fn_vpu(loss, block, interpret, lanes)(x, y, weights, offsets, w))
    if covered < n:
        parts.append(_two_pass_parts(
            loss, x[covered:], y[covered:], weights[covered:],
            offsets[covered:], w.astype(jnp.float32)))
    return tuple(sum(p) for p in zip(*parts))


# ---------------------------------------------------------------------------
# Many small problems at once: the per-entity lanes.
# ---------------------------------------------------------------------------


def _pairwise(terms):
    """The sum of ``terms`` by adjacent pairs, level by level (an odd one
    out goes up a level as it is)."""
    while len(terms) > 1:
        terms = [a + b for a, b in zip(terms[::2], terms[1::2])] + terms[len(terms) // 2 * 2:]
    return terms[0]


def _make_lane_block_kernel(loss: PointwiseLoss, d: int, lanes: int):
    """The kernel over a block of ``lanes`` problems of a lane stack held as
    ``(d, E, m)``: each feature a plane of the lanes' rows, rows along the
    vector lanes. Eight problems (a sublane tile) at a time: the margins are
    the features' planes times the coefficients (each spread over the rows)
    added by pairs (:func:`_pairwise`), then the offsets; loss and slope a
    row; the gradient each feature's plane times the slopes, summed over the
    rows. Of the sound orders raced on the chip, margins by pairs kept the
    GLMix cell's comparison with its reference within its limits; added in
    feature order they did not (PERF.md section 6).
    A problem's sums, loss and ``sum(d)`` included, are over its own rows in
    an order set by ``(m, d)`` alone, so its bits do not depend on which
    problems share the block or the call. The ``(8, 128)`` result a tile
    holds the gradient in its first ``d`` lanes, the loss sum in lane ``d``
    and ``sum(d)`` in lane ``d + 1``."""

    def kernel(x_ref, y_ref, wt_ref, off_ref, w_ref, out_ref):
        lane = lax.broadcasted_iota(jnp.int32, (8, LANES), 1)

        def tile(t, carry):
            here = pl.ds(pl.multiple_of(t * 8, 8), 8)
            w = w_ref[here, :]
            z = _pairwise([x_ref[j, here, :] * w[:, j:j + 1] for j in range(d)])
            wl, slopes = _row_terms(loss, z + off_ref[here, :],
                                    y_ref[here, :], wt_ref[here, :])
            out = jnp.zeros((8, LANES), jnp.float32)
            for j in range(d):
                g = jnp.sum(x_ref[j, here, :] * slopes, axis=1, keepdims=True)  # lint: bitwise-reduction — pallas block-local reduce over a lane's own rows
                out = jnp.where(lane == j, g, out)
            out = jnp.where(lane == d, jnp.sum(wl, axis=1, keepdims=True), out)  # lint: bitwise-reduction — pallas block-local reduce over a lane's own rows
            out = jnp.where(lane == d + 1, jnp.sum(slopes, axis=1, keepdims=True), out)  # lint: bitwise-reduction — pallas block-local reduce over a lane's own rows
            out_ref[here, :] = out
            return carry

        lax.fori_loop(0, lanes // 8, tile, 0)

    return kernel


def _lanes_vmem_limit(lanes: int, m: int, d: int) -> Optional[int]:
    """``vmem_limit_bytes`` of the lane-block kernel: two buffers each of
    the block's features, its three row vectors, its coefficients and its
    result, rows padded to whole vector lanes."""
    m_pad = -(-m // LANES) * LANES
    need = 2 * 4 * lanes * (m_pad * (d + 3) + 2 * LANES)
    if need + (2 << 20) <= _DEFAULT_SCOPED_VMEM:
        return None
    return min(need + (4 << 20), _MAX_SCOPED_VMEM)


@jax.named_scope("pml.re.fused_value_grad")
def lane_value_grad_parts(
    loss: PointwiseLoss,
    x: jax.Array,
    y: jax.Array,
    weights: jax.Array,
    offsets: jax.Array,
    w: jax.Array,
    block_lanes: int,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Each lane's (sum w_i*l_i, X^T d, sum d) with d = w_i*l'_i, for a
    stack of E float32 problems ``x`` (E, M, D), rows ``y``, ``weights``,
    ``offsets`` (E, M) and coefficients ``w`` (E, D), in one read of the
    stack: :func:`fused_value_grad_parts` for many small problems.

    The kernel reads the stack as ``(D, E, M)``: rows minor, features
    major. Where the TPU holds the stack so (the order that pads least: at
    the GLMix cell's 34,624 x 512 x 22 ``{1,0,2}``) the transpose is a
    bitcast and nothing is copied; where it does not (a width that is a
    multiple of 8, a few lanes) the compiler lays the stack out so once, in
    front of the solver's loops. ``block_lanes`` lanes a
    grid step, as :func:`select_lane_block` hands them out; E need not be a
    multiple of it (the last block's missing lanes are read as garbage and
    never written)."""
    if interpret is None:
        interpret = _interpret_default()
    e, m, d = x.shape
    rows = pl.BlockSpec((block_lanes, m), lambda i: (i, 0))
    parts = pl.pallas_call(
        _make_lane_block_kernel(loss, d, block_lanes),
        grid=(pl.cdiv(e, block_lanes),),
        in_specs=[pl.BlockSpec((d, block_lanes, m), lambda i: (0, i, 0)),
                  rows, rows, rows,
                  pl.BlockSpec((block_lanes, d), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_lanes, LANES), lambda i: (i, 0)),
        # under shard_map the result varies over the mesh axes the shard does
        out_shape=jax.ShapeDtypeStruct(
            (e, LANES), jnp.float32, vma=jax.typeof(x).vma),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_lanes_vmem_limit(block_lanes, m, d),
        ),
        interpret=interpret,
    )(jnp.transpose(x, (2, 0, 1)), y, weights, offsets, w)
    return parts[:, d], parts[:, :d], parts[:, d + 1]


# ---------------------------------------------------------------------------
# Selection: from the shape, in microseconds.
# ---------------------------------------------------------------------------

# The smallest matrix the kernel is handed on a TPU. The two-pass path reads
# the matrix from HBM twice at every size raced, down to 8 MiB; from 31 MiB
# up the kernel won by 1.46 to 1.93 times at every width and in either
# orientation (64, 200, 512, 2,000, 2,048 wide; float32 and bfloat16), at
# 16 MiB by 1.47, and at 8 MiB by 1.14 to 1.26, where its fixed 20 us an
# evaluation show (PERF.md section 6, PR 32, second round). Under the line
# the two-pass path stays: the per-entity problems, a few hundred rows of 8
# to 22 features, are four orders of magnitude under it.
MIN_MATRIX_BYTES = 32 << 20
# Bytes of one pipeline buffer of the matrix block the kernels aim at: on
# the chip 640, 1,024, 1,920 and 3,200 rows at 2,000 float32 and 512 to
# 2,048 rows at 2,048 read alike (PERF.md section 6, PR 32).
_BLOCK_BYTES = 8 << 20
# Held row-major, a row pays some 2.7 ns for its group's two transposes and
# its loss whatever its width, so a narrow matrix is bound by that and not
# by HBM: at rows of 1 KiB (512 bfloat16) the kernel read 5.45 ms where the
# two-pass path read 4.45, at 2 KiB (512 float32) 5.46 against 8.77, at
# 4 KiB and 8 KiB (2,048 wide) it ran at HBM's pace, 1.97 times the
# two-pass path's (PERF.md section 6, PR 32). The kernel unrolls over the
# width's 128-lane chunks, which bounds the width it is built for.
_SUBLANES_MIN_ROW_BYTES = 2048
_SUBLANES_MAX_WIDTH = 1 << 14


def _vpu_block_rows(n: int, d: int, itemsize: int, lanes: bool) -> Optional[int]:
    """Rows a block in either orientation: the most 128-row chunks within
    ``_BLOCK_BYTES`` (and ``_LANES_MAX_CHUNKS``); a count from half of that
    up that divides ``n`` is preferred (no rows are left to the two-pass
    tail: 640 at 400,000 x 2,000 float32). None where 128 rows of the
    matrix do not fit the kernel's VMEM."""
    if _vpu_vmem_limit(LANES, d, itemsize) == _MAX_SCOPED_VMEM:
        return None
    chunks = max(1, _BLOCK_BYTES // (d * itemsize * LANES))
    if lanes:
        chunks = min(chunks, _LANES_MAX_CHUNKS)
    for c in range(chunks, chunks // 2, -1):
        if n % (c * LANES) == 0:
            return c * LANES
    return chunks * LANES


def select_fused_block_rows(n: int, d: int, dtype=jnp.bfloat16) -> Optional[int]:
    """The one-pass kernel's rows a block for an (N, D) dense GLM pass, or
    ``None`` where the two-pass XLA path should run. A pure function of what
    the caller can see before tracing — platform, storage dtype, the static
    shape (under ``shard_map``: the local shard's) — that builds no data and
    times nothing. The vmapped solves do not ask: ``train_glm_grid_vmapped``
    clears the block and the per-entity solves never carry one (a vmapped
    ``pallas_call`` grows a grid axis the kernel's accumulation does not
    know).

    ``PHOTON_ML_TPU_FUSED``: "auto" (default) gives the kernel on a TPU
    where the matrix is over ``MIN_MATRIX_BYTES`` and, held row-major, its rows
    are wide enough for HBM to bound the kernel; "0" never; "1" wherever the
    kernel can run, whatever the platform and the size (interpreter mode on
    the CPU, for tests). Never for float64.
    """
    mode = os.environ.get(_FUSED_ENV, "auto")
    dtype = jnp.dtype(dtype)
    if mode == "0" or dtype not in (jnp.float32, jnp.bfloat16):
        return None
    lanes = held_column_major(n, d)
    if not lanes and (d % LANES or d > _SUBLANES_MAX_WIDTH):
        return None
    if mode != "1" and not (
        _on_tpu()
        and n * d * dtype.itemsize > MIN_MATRIX_BYTES
        and (lanes or d * dtype.itemsize >= _SUBLANES_MIN_ROW_BYTES)
    ):
        return None
    return _vpu_block_rows(n, d, dtype.itemsize, lanes)


# Lanes a grid step of the lane-block kernel. At 34,624 lanes of 512 x 22
# float32 on the chip (margins then added in feature order), 32, 64 and 128
# read alike (2.425 to 2.428 ms an evaluation, 731 GB/s), 16 read 2.53 ms
# and 8 3.07 ms (PERF.md section 6); 64 divides that stack.
_LANE_BLOCK = 64


def select_lane_block(m: int, d: int, dtype=jnp.float32) -> Optional[int]:
    """Lanes a grid step of :func:`lane_value_grad_parts` for a stack of
    ``(m, d)`` problems, or ``None`` where the lanes keep XLA's two passes.
    A pure function of platform, dtype and ``(m, d)``, never of the number
    of lanes, so a lane computes the same bits however many lanes share
    the call. On a TPU, for float32 rows that fill whole vector lanes
    (``m`` a multiple of 128) and a gradient that fits one lane-row of the
    result with its two sums (``d + 2 <= 128``): the most lanes, from
    ``_LANE_BLOCK`` down, whose buffers fit the kernel's VMEM."""
    if not (_on_tpu() and jnp.dtype(dtype) == jnp.float32
            and m % LANES == 0 and d + 2 <= LANES):
        return None
    lanes = _LANE_BLOCK
    while lanes > 8 and _lanes_vmem_limit(lanes, m, d) == _MAX_SCOPED_VMEM:
        lanes //= 2
    return None if _lanes_vmem_limit(lanes, m, d) == _MAX_SCOPED_VMEM else lanes
