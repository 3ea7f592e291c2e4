"""Fused GLM value+gradient Pallas kernels — the training hot loop.

The GLM hot loop (ValueAndGradientAggregator semantics, SURVEY.md §2.2,
reference spec function/ValueAndGradientAggregator.scala:120-139) is
HBM-bandwidth-bound on TPU: the two XLA passes (margin ``X @ w``, gradient
``d @ X``) each stream the whole (N, D) feature matrix from HBM. The kernels
here make them ONE pass — each block of rows is loaded into VMEM once and
used for the margins and for the gradient — which pairs with bfloat16
feature storage for another 2x traffic cut.

The kernels are generic over any :class:`PointwiseLoss` and also give
``sum(d)`` so callers can reconstruct the normalization-shift gradient term
(``grad_eff = X^T d - shifts * sum(d)``) without a second data pass. They
slot into ``GLMObjective.value_and_grad`` (see ``fused_block_rows`` there)
behind :func:`select_fused_block_rows`, a pure function of platform, dtype
and shape: the ``vpu`` family's kernel on a TPU for a matrix of more than
``MIN_MATRIX_BYTES``, ``None`` (the two-pass path) elsewhere.
That family multiplies and reduces on the vector unit, so float32 storage is
float32 arithmetic, and reads the matrix in the layout the device holds it
in (:func:`held_column_major`), so nothing is padded or copied. The other
families (``grid`` on the matrix unit, ``manual``, the pure-XLA ``scan``)
stay for :func:`race_fused_block_rows`, the bench's race.

Numerically: margins/loss/derivative are computed in f32. Zero-weight rows
contribute exactly nothing (hard-masked, so even an inf/nan loss on such a
row is zeroed). Runs in interpreter mode on the CPU backend only (tests).
"""

from __future__ import annotations

import functools
import logging
import os
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from photon_ml_tpu.ops.losses import PointwiseLoss, logistic

logger = logging.getLogger(__name__)

DEFAULT_BLOCK_ROWS = 1024

# Candidate encodings for the autotuner (decoded by _decode_block):
#   positive < VPU_MARK  — automatic grid pipeline, MXU matmuls;
#   negative             — manual double-buffered variant (explicit chunked
#                          async DMA for all row streams), |size| rows/chunk;
#   VPU_MARK + rows      — the VPU formulation: both contractions as
#                          elementwise multiply + reduction instead of M=1
#                          matmuls. Rationale: at one output column the MXU
#                          still pays BN*D/128 cycles per contraction, which
#                          makes the GEVM pair COMPUTE-bound (~1.2e8 ex/s at
#                          D=512 by that arithmetic), while
#                          the VPU's elementwise throughput can keep pace
#                          with full HBM bandwidth.
# Bigger blocks amortize grid overhead; the ceiling is VMEM (BN x D x 2B
# for bf16 plus the f32 scalars), so 8192 x 512 bf16 = 8 MiB stays
# comfortably under budget.
VPU_MARK = 1 << 20
# SCAN_MARK + rows — pure-XLA single pass: lax.scan over row blocks with
# both contractions per block and f32 accumulators (no Pallas at all; see
# _scan_value_grad_parts). A test of whether XLA alone can hold a block
# resident between the matvec and the rank-update.
SCAN_MARK = 2 << 20
AUTOTUNE_CANDIDATES = (
    1024, 2048, 4096, 8192, 16384, -2048, -4096, -8192,
    VPU_MARK + 2048, VPU_MARK + 4096, VPU_MARK + 8192, VPU_MARK + 16384,
    SCAN_MARK + 2048, SCAN_MARK + 8192, SCAN_MARK + 32768,
)


def _decode_block(block_rows: int) -> Tuple[str, int]:
    """(family, rows) from the encoded autotune candidate."""
    if block_rows >= SCAN_MARK:
        return "scan", block_rows - SCAN_MARK
    if block_rows >= VPU_MARK:
        return "vpu", block_rows - VPU_MARK
    if block_rows < 0:
        return "manual", -block_rows
    return "grid", block_rows

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


def _make_kernel(loss: PointwiseLoss):
    """Build the row-block kernel for one pointwise loss."""

    def _kernel(
        x_ref, y_ref, wt_ref, off_ref, w_ref,
        loss_out, grad_out, sumd_out,
        acc_grad, acc_loss, acc_sumd,
    ):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            acc_grad[:] = jnp.zeros_like(acc_grad)
            acc_loss[:] = jnp.zeros_like(acc_loss)
            acc_sumd[:] = jnp.zeros_like(acc_sumd)

        x = x_ref[:]  # (BN, D) storage dtype (bf16 fast path)
        w = w_ref[:]  # (D, 1) f32
        y = y_ref[:]  # (BN, 1) f32
        wt = wt_ref[:]  # (BN, 1) f32
        off = off_ref[:]  # (BN, 1) f32

        z = jnp.dot(x, w.astype(x.dtype), preferred_element_type=jnp.float32) + off
        lv = loss.loss(z, y)
        # hard mask: padding rows (weight 0) contribute an exact 0 even when
        # the loss is inf/nan on garbage padding (e.g. Poisson exp overflow)
        wl = jnp.where(wt > 0.0, wt * lv, 0.0)
        d = jnp.where(wt > 0.0, wt * loss.d1(z, y), 0.0)  # (BN, 1) f32

        acc_loss[:] += jnp.sum(wl, keepdims=True).reshape(1, 1)  # lint: bitwise-reduction — pallas block-local accumulate; order pinned by the sequential grid
        acc_sumd[:] += jnp.sum(d, keepdims=True).reshape(1, 1)  # lint: bitwise-reduction — pallas block-local accumulate; order pinned by the sequential grid
        acc_grad[:] += jnp.dot(
            d.astype(x.dtype).T, x, preferred_element_type=jnp.float32
        )  # (1, D)

        @pl.when(i == pl.num_programs(0) - 1)
        def _():
            loss_out[:] = acc_loss[:]
            grad_out[:] = acc_grad[:]
            sumd_out[:] = acc_sumd[:]

    return _kernel


def _marshal_inputs(x, y, weights, offsets, w):
    """Common calling convention of both kernel families: row vectors as
    (N, 1) f32 columns, coefficients as a (D, 1) f32 column."""
    n, d = x.shape
    return (
        x,
        y.reshape(n, 1).astype(jnp.float32),
        weights.reshape(n, 1).astype(jnp.float32),
        offsets.reshape(n, 1).astype(jnp.float32),
        w.reshape(d, 1).astype(jnp.float32),
    )


def _unpack_outputs(loss_sum, grad, sumd):
    return loss_sum[0, 0], grad[0], sumd[0, 0]


# Mosaic's default scoped-VMEM limit, and how far a kernel may raise it
# (a v5e core has 128 MiB of VMEM; the compiler refuses a limit it cannot
# place, and the race records the refusal).
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
    masked as in every family: a zero-weight row contributes an exact 0 even
    where its loss is inf or nan (a padding row, Poisson's exp overflow)."""
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
    """The ``vpu`` family on a matrix held column-major: a block is
    ``(d, rows)`` of the transposed matrix, rows along lanes, so the row
    vectors are lane-dense ``(1, rows)`` and neither contraction crosses
    lanes. Margins: ``tile`` features at a time, multiplied by their
    coefficients (spread over the lanes outside the kernel) and added into
    one ``(tile, 128)`` sum per 128 rows, then reduced over sublanes. The
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
    """The ``vpu`` family on a matrix held row-major whose width is a
    multiple of 128: a block is ``(rows, d)``, rows along sublanes. The row
    vectors stay lane-dense ``(1, rows)`` as in :func:`_make_lanes_kernel`;
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
    """``vmem_limit_bytes`` of the ``vpu`` family's kernels: the pipeline's
    two buffers of the matrix block, of the spread coefficients and of the
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


def _grid_vmem_limit(block_rows: int, d: int, itemsize: int) -> Optional[int]:
    """``vmem_limit_bytes`` for one grid-pipeline block config, or None
    while the pipeline's buffers fit the default limit (1024- and 2048-row
    blocks at 512 x bf16 compile unchanged).

    The pipeline double-buffers every input block, and a ``(block_rows, 1)``
    f32 block occupies ``block_rows x 128`` lanes in VMEM — 512 B a row for
    each of y / weights / offsets, next to the x block's ``d * itemsize``.
    That alone is 20 MiB at 4096 x 512 bf16 (the compiler's own figure on a
    v5e, refused at the 16 MiB default). The raised limit adds room for
    the kernel's column temporaries (z, loss, derivative, ... — lane-padded
    the same way)."""
    column = block_rows * 128 * 4
    buffers = 2 * (block_rows * d * itemsize + 3 * column)
    if buffers + (2 << 20) <= _DEFAULT_SCOPED_VMEM:
        return None
    return min(buffers + 6 * column + (2 << 20), _MAX_SCOPED_VMEM)


@functools.lru_cache(maxsize=64)
def _fused_fn(loss: PointwiseLoss, block_rows: int, interpret: bool):
    """Jitted single-pass (loss_sum, grad, sum_d) of the ``grid`` family for
    one loss/block config."""
    kernel = _make_kernel(loss)

    @jax.jit
    def call(x, y, weights, offsets, w):
        n, d = x.shape
        grid = n // block_rows
        loss_sum, grad, sumd = pl.pallas_call(
            kernel,
            grid=(grid,),
            in_specs=[
                pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
                pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
                pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
                pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
                pl.BlockSpec((d, 1), lambda i: (0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1), lambda i: (0, 0)),
                pl.BlockSpec((1, d), lambda i: (0, 0)),
                pl.BlockSpec((1, 1), lambda i: (0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((1, 1), jnp.float32),
                jax.ShapeDtypeStruct((1, d), jnp.float32),
                jax.ShapeDtypeStruct((1, 1), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((1, d), jnp.float32),
                pltpu.VMEM((1, 1), jnp.float32),
                pltpu.VMEM((1, 1), jnp.float32),
            ],
            # the grid axis is a pure reduction: no ordering constraint
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=_grid_vmem_limit(block_rows, d, x.dtype.itemsize),
            ),
            interpret=interpret,
        )(*_marshal_inputs(x, y, weights, offsets, w))
        return _unpack_outputs(loss_sum, grad, sumd)

    return call


# ---------------------------------------------------------------------------
# manual double-buffered variant: every row stream (x AND y/wt/off) chunked
# from HBM with explicit async copies (2-slot rotation), so VMEM use is
# bounded by the chunk size at ANY dataset size. A structurally different
# pipeline from the automatic grid pipeline above — raced against it by the
# autotuner (encoded as NEGATIVE block sizes).
# ---------------------------------------------------------------------------


def _make_manual_kernel(loss: PointwiseLoss, block_rows: int):
    def kernel(x_hbm, y_hbm, wt_hbm, off_hbm, w_ref,
               loss_out, grad_out, sumd_out):
        n = y_hbm.shape[0]
        num_chunks = n // block_rows

        def body(xbuf, ybuf, wtbuf, offbuf, acc_grad, sem):
            # ALL row streams (x + the aux vectors) are chunked: nothing in
            # VMEM scales with N, so a probe-time winner stays valid at any
            # training-set size (the aux arrays resident would pin (N,1)x3
            # f32 and blow VMEM for N in the millions)
            def dmas(slot, chunk):
                sl = pl.ds(chunk * block_rows, block_rows)
                return (
                    pltpu.make_async_copy(x_hbm.at[sl], xbuf.at[slot], sem.at[slot, 0]),
                    pltpu.make_async_copy(y_hbm.at[sl], ybuf.at[slot], sem.at[slot, 1]),
                    pltpu.make_async_copy(wt_hbm.at[sl], wtbuf.at[slot], sem.at[slot, 2]),
                    pltpu.make_async_copy(off_hbm.at[sl], offbuf.at[slot], sem.at[slot, 3]),
                )

            for dma in dmas(0, 0):
                dma.start()

            def loop_body(chunk, carry):
                acc_loss, acc_sumd = carry
                slot = chunk % 2

                @pl.when(chunk + 1 < num_chunks)
                def _():
                    for dma in dmas((chunk + 1) % 2, chunk + 1):
                        dma.start()

                for dma in dmas(slot, chunk):
                    dma.wait()
                x = xbuf[slot]  # (BN, D) storage dtype
                yv = ybuf[slot]
                wt = wtbuf[slot]
                off = offbuf[slot]
                w = w_ref[:]
                z = jnp.dot(x, w.astype(x.dtype),
                            preferred_element_type=jnp.float32) + off
                lv = loss.loss(z, yv)
                wl = jnp.where(wt > 0.0, wt * lv, 0.0)
                dd = jnp.where(wt > 0.0, wt * loss.d1(z, yv), 0.0)
                acc_grad[:] += jnp.dot(
                    dd.astype(x.dtype).T, x, preferred_element_type=jnp.float32
                )
                return (
                    acc_loss + jnp.sum(wl, keepdims=True).reshape(1, 1),  # lint: bitwise-reduction — pallas block-local accumulate; order pinned by the sequential grid
                    acc_sumd + jnp.sum(dd, keepdims=True).reshape(1, 1),  # lint: bitwise-reduction — pallas block-local accumulate; order pinned by the sequential grid
                )

            acc_grad[:] = jnp.zeros_like(acc_grad)
            acc_loss, acc_sumd = jax.lax.fori_loop(
                0, num_chunks, loop_body,
                (jnp.zeros((1, 1), jnp.float32), jnp.zeros((1, 1), jnp.float32)),
            )
            loss_out[:] = acc_loss
            sumd_out[:] = acc_sumd
            grad_out[:] = acc_grad[:]

        d = x_hbm.shape[1]
        pl.run_scoped(
            body,
            xbuf=pltpu.VMEM((2, block_rows, d), x_hbm.dtype),
            ybuf=pltpu.VMEM((2, block_rows, 1), jnp.float32),
            wtbuf=pltpu.VMEM((2, block_rows, 1), jnp.float32),
            offbuf=pltpu.VMEM((2, block_rows, 1), jnp.float32),
            acc_grad=pltpu.VMEM((1, d), jnp.float32),
            sem=pltpu.SemaphoreType.DMA((2, 4)),
        )

    return kernel


@functools.lru_cache(maxsize=64)
def _fused_fn_manual(loss: PointwiseLoss, block_rows: int, interpret: bool):
    kernel = _make_manual_kernel(loss, block_rows)

    @jax.jit
    def call(x, y, weights, offsets, w):
        n, d = x.shape
        loss_sum, grad, sumd = pl.pallas_call(
            kernel,
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),  # x stays in HBM
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pltpu.VMEM),
            ],
            out_specs=[
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pltpu.VMEM),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((1, 1), jnp.float32),
                jax.ShapeDtypeStruct((1, d), jnp.float32),
                jax.ShapeDtypeStruct((1, 1), jnp.float32),
            ],
            interpret=interpret,
        )(*_marshal_inputs(x, y, weights, offsets, w))
        return _unpack_outputs(loss_sum, grad, sumd)

    return call


def _two_pass_parts(loss, x, y, weights, offsets, w):
    """(loss sum, X^T d, sum d) of a few rows in plain float32
    multiply-and-reduce: the rows a kernel's block does not divide."""
    xf = x.astype(jnp.float32)
    z = jnp.sum(xf * w, axis=1) + offsets  # lint: bitwise-reduction — the margins' feature axis, not a slab batch axis
    wl, d = _row_terms(loss, z, y, weights)
    return jnp.sum(wl), jnp.sum(xf * d[:, None], axis=0), jnp.sum(d)  # lint: bitwise-reduction — dense-family canonical arithmetic


def _vpu_value_grad_parts(loss, rows, interpret, x, y, weights, offsets, w):
    """The ``vpu`` family: the matrix is read where it lies and never padded
    or copied. A kernel in the orientation the device holds the matrix in
    (:func:`held_column_major`) walks the whole blocks of rows; the rows the
    block does not divide (fewer than a block) go through
    :func:`_two_pass_parts`, as does a matrix held row-major at a width that
    is no multiple of 128, which neither kernel serves."""
    n, d = x.shape
    lanes = held_column_major(n, d)
    block = min(rows, n) // LANES * LANES
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


@jax.named_scope("pml.features.value_grad")
def fused_value_grad_parts(
    loss: PointwiseLoss,
    x: jax.Array,
    y: jax.Array,
    weights: jax.Array,
    offsets: jax.Array,
    w: jax.Array,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Raw single-pass pieces: (sum w_i*l_i, X^T d, sum d) with d = w_i*l'_i.

    No regularization, no normalization — the caller owns that algebra
    (``GLMObjective.value_and_grad`` folds shifts/factors/L2 around these).
    ``x``: (N, D), any float dtype — bfloat16 recommended for bandwidth.

    ``block_rows``: an encoded (family, rows) candidate (see _decode_block).
    >= VPU_MARK = multiply-and-reduce on the vector unit, the family
    :func:`select_fused_block_rows` hands out: float32 arithmetic, the
    matrix read in the device's own layout (:func:`_vpu_value_grad_parts`).
    The race's other families pad the rows (weight 0) up to a block
    multiple: positive = automatic grid pipeline (MXU matmuls), negative =
    the manual double-buffered variant with |block_rows| rows per chunk,
    >= SCAN_MARK = the pure-XLA scan.
    """
    if interpret is None:
        interpret = _interpret_default()
    family, rows = _decode_block(block_rows)
    if family == "vpu":
        return _vpu_value_grad_parts(loss, rows, interpret, x, y, weights, offsets, w)
    block = min(rows, max(x.shape[0], 1))
    n, d = x.shape
    pad = (-n) % block
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad, d), x.dtype)])
        y = jnp.concatenate([y, jnp.zeros((pad,), y.dtype)])
        weights = jnp.concatenate([weights, jnp.zeros((pad,), weights.dtype)])
        offsets = jnp.concatenate([offsets, jnp.zeros((pad,), offsets.dtype)])
    if family == "scan":
        return _scan_value_grad_parts(loss, block, x, y, weights, offsets, w)
    if family == "manual":
        fn = _fused_fn_manual(loss, block, interpret)
    else:
        fn = _fused_fn(loss, block, interpret)
    return fn(x, y, weights, offsets, w)


def _scan_value_grad_parts(loss, block, x, y, weights, offsets, w):
    """Pure-XLA single-pass family: lax.scan over row blocks, both
    contractions (margins + gradient) computed per block with f32
    accumulators. No Pallas anywhere, and the block is small enough
    (block x D bf16) that XLA can keep it resident in VMEM between the
    matvec and the rank-update, approaching one-pass HBM traffic without
    hand-written kernels."""
    n, d = x.shape
    nb = n // block
    xb = x.reshape(nb, block, d)
    yb = y.reshape(nb, block)
    wb = weights.reshape(nb, block)
    ob = offsets.reshape(nb, block)
    wx = w.astype(x.dtype)

    def step(carry, inp):
        val, g, ds = carry
        xx, yy, ww, oo = inp
        z = jnp.dot(xx, wx, preferred_element_type=jnp.float32) + oo
        # same masking rule as every other family: zero-weight rows must be
        # EXCLUDED, not multiplied (0 * inf = NaN for e.g. Poisson d1 at a
        # large margin)
        dvec = jnp.where(ww > 0, ww * loss.d1(z, yy), 0.0)
        val = val + jnp.sum(jnp.where(ww > 0, ww * loss.loss(z, yy), 0.0))  # lint: bitwise-reduction — dense-family canonical arithmetic; fused candidates are verified against THIS
        g = g + jnp.dot(dvec.astype(xx.dtype), xx,
                        preferred_element_type=jnp.float32)
        ds = ds + jnp.sum(dvec)  # lint: bitwise-reduction — dense-family canonical arithmetic; fused candidates are verified against THIS
        return (val, g, ds), None

    init = (
        jnp.float32(0.0),
        jnp.zeros((d,), jnp.float32),
        jnp.float32(0.0),
    )
    (val, g, ds), _ = lax.scan(step, init, (xb, yb, wb, ob))
    return val, g, ds


def fused_logistic_value_and_grad(
    x: jax.Array,
    y: jax.Array,
    weights: jax.Array,
    w: jax.Array,
    l2: float = 0.0,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Fused logistic (value, gradient) over a dense feature matrix.

    ``x``: (N, D), any float dtype — bfloat16 recommended for bandwidth.
    ``y``/``weights``: (N,); weight 0 marks padding. Returns f32
    (value, (D,) grad) including the L2 term.
    """
    n, d = x.shape
    if n == 0:
        value = 0.5 * l2 * jnp.sum(jnp.square(w)) if l2 else jnp.float32(0.0)  # lint: bitwise-reduction — l2 reg over the fixed (D,) w, not a slab batch axis
        return value, (l2 * w if l2 else jnp.zeros_like(w))
    value, grad, _ = fused_value_grad_parts(
        logistic, x, y, weights, jnp.zeros((n,), jnp.float32), w,
        block_rows=block_rows, interpret=interpret,
    )
    if l2:
        value = value + 0.5 * l2 * jnp.sum(jnp.square(w))  # lint: bitwise-reduction — l2 reg over the fixed (D,) w, not a slab batch axis
        grad = grad + l2 * w
    return value, grad


def reference_logistic_value_and_grad(x, y, weights, w, l2: float = 0.0):
    """Plain-XLA two-pass computation (the correctness oracle)."""
    z = x.astype(jnp.float32) @ w + 0.0
    loss = jnp.maximum(z, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(z))) - y * z
    s = jax.nn.sigmoid(z)
    d = weights * (s - y)
    value = jnp.sum(weights * loss) + 0.5 * l2 * jnp.sum(jnp.square(w))  # lint: bitwise-reduction — reference oracle; dense-family canonical arithmetic
    grad = d @ x.astype(jnp.float32) + l2 * w
    return value, grad


# ---------------------------------------------------------------------------
# Selection: from the shape, in microseconds. The race below is a bench and
# diagnostic surface (bench.py, tools/); no training path calls it.
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
    """Rows a block of the ``vpu`` family's kernels: the most 128-row chunks
    within ``_BLOCK_BYTES`` (and ``_LANES_MAX_CHUNKS``); a count from half
    of that up that divides ``n`` is preferred (no rows are left to the
    two-pass tail: 640 at 400,000 x 2,000 float32). None where 128 rows of
    the matrix do not fit the kernel's VMEM."""
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
    """The one-pass kernel's encoded block for an (N, D) dense GLM pass, or
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
    rows = _vpu_block_rows(n, d, dtype.itemsize, lanes)
    return None if rows is None else VPU_MARK + rows


_autotune_cache: dict = {}
_autotune_timings: dict = {}  # key -> {candidate: sec/pass} from the race
# key -> {candidate: reason} for every candidate that did NOT produce a
# timing — compile/run failures and eligibility skips. A candidate that
# failed must READ as failed in the race record, not silently vanish
# (bench postmortems need to distinguish "lost the race" from "never ran").
_autotune_failures: dict = {}


def _time_value_and_grad(vg_fn, w0, data, iters: int = 16) -> float:
    """Seconds per value+grad pass, serialized on-chip via lax.scan (a
    host loop would time the asynchronous enqueue, not the passes).

    ``data`` (the probe arrays) flows in as a jit ARGUMENT: a closure
    capture would inline the feature matrix into the HLO as a literal."""

    def run(w, d):
        def step(w, _):
            v, g = vg_fn(w, d)
            return w - 1e-6 * g, v

        return lax.scan(step, w, None, length=iters)

    scan = jax.jit(run)
    w = jax.block_until_ready(scan(w0, data))[0]  # compile + warm
    best = float("inf")
    for _ in range(3):
        # each repeat feeds the PREVIOUS repeat's final w, so every timed
        # call is novel work
        t0 = time.perf_counter()
        out = scan(w, data)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / iters)
        w = out[0]
    return best


def race_fused_block_rows(
    loss: PointwiseLoss,
    n: int,
    d: int,
    dtype=jnp.bfloat16,
    candidates: Tuple[int, ...] = AUTOTUNE_CANDIDATES,
) -> Optional[int]:
    """Time every candidate and the two-pass XLA path on the live default
    device with synthetic data (row count capped at 2^17) and return the
    fastest, ``None`` for XLA. A bench and diagnostic surface: seconds of
    device time and a program per candidate, so no training path calls it
    (they call :func:`select_fused_block_rows`). Results are cached per
    (loss, n, d, dtype, platform). ``PHOTON_ML_TPU_FUSED``: "0" returns
    None, "1" leaves XLA out of the race and runs off a TPU too
    (interpreter mode), "auto" races on a TPU only.
    """
    mode = os.environ.get(_FUSED_ENV, "auto")
    if mode == "0":
        return None
    platform = jax.devices()[0].platform
    if not _on_tpu() and mode != "1":
        return None
    # TPU lane tiling: the kernel needs the feature axis in full 128-lane
    # tiles and f64 never runs on the MXU
    if d % 128 != 0 or jnp.dtype(dtype) == jnp.float64:
        return None

    n_probe = min(n, 1 << 17)
    key = (loss.name, n_probe, d, jnp.dtype(dtype).name, platform, mode)
    if key in _autotune_cache:
        return _autotune_cache[key]

    kx = jax.random.PRNGKey(0)
    x = (jax.random.normal(kx, (n_probe, d), jnp.float32)).astype(dtype)
    y = (jax.random.uniform(jax.random.PRNGKey(1), (n_probe,)) < 0.5).astype(jnp.float32)
    wt = jnp.ones((n_probe,), jnp.float32)
    off = jnp.zeros((n_probe,), jnp.float32)
    w0 = jnp.zeros((d,), jnp.float32)

    def xla_vg(w, data):
        xx, yy, wwt, ooff = data
        z = jnp.dot(xx, w.astype(xx.dtype), preferred_element_type=jnp.float32) + ooff
        val = jnp.sum(jnp.where(wwt > 0, wwt * loss.loss(z, yy), 0.0))  # lint: bitwise-reduction — two-pass XLA baseline = the dense family's defined arithmetic
        dvec = jnp.where(wwt > 0, wwt * loss.d1(z, yy), 0.0)
        g = jnp.dot(dvec.astype(xx.dtype), xx, preferred_element_type=jnp.float32)
        return val, g

    probe_data = (x, y, wt, off)
    timings = {}
    failures = {}
    if mode != "1":
        timings[None] = _time_value_and_grad(xla_vg, w0, probe_data)
    interpret = _interpret_default()
    for block in candidates:
        if _decode_block(block)[1] > n_probe:
            failures[block] = (
                f"skipped: block rows {_decode_block(block)[1]} > probe rows "
                f"{n_probe}"
            )
            continue
        try:
            fn = lambda w, data, b=block: fused_value_grad_parts(
                loss, data[0], data[1], data[2], data[3], w,
                block_rows=b, interpret=interpret,
            )[:2]
            timings[block] = _time_value_and_grad(fn, w0, probe_data)
        except Exception as e:  # noqa: BLE001 — autotune probe: any compile/run failure just disqualifies the candidate (recorded, not dropped)
            failures[block] = f"failed: {type(e).__name__}: {e}"[:300]
            logger.warning(
                "fused dense race: candidate %s:%d refused (%s)",
                *_decode_block(block), _first_line(e),
            )
            continue
    _autotune_timings[key] = dict(timings)
    _autotune_failures[key] = failures
    if not timings:
        _autotune_cache[key] = None
        return None
    best = min(timings, key=timings.get)
    _autotune_cache[key] = best
    return best


def autotune_report(loss: PointwiseLoss, n: int, d: int, dtype=jnp.bfloat16) -> dict:
    """Run the autotune and return the winner plus the full per-candidate
    race — sec/pass, examples/sec, and the implied HBM read bandwidth of a
    single X stream (GB/s; the two-pass XLA entry, key "xla", reads X twice
    so its effective traffic is 2x the listed figure). Diagnostic surface
    for bench.py."""
    race_fused_block_rows(loss, n, d, dtype)  # populate cache
    mode = os.environ.get(_FUSED_ENV, "auto")
    platform = jax.devices()[0].platform
    n_probe = min(n, 1 << 17)
    key = (loss.name, n_probe, d, jnp.dtype(dtype).name, platform, mode)
    x_bytes = n_probe * d * jnp.dtype(dtype).itemsize
    candidates = {}
    for cand, sec in _autotune_timings.get(key, {}).items():
        name = (
            "xla"
            if cand is None
            else "{}:{}".format(*_decode_block(cand))
        )
        candidates[name] = {
            "sec_per_pass": round(sec, 6),
            "examples_per_sec": round(n_probe / sec, 1),
            "one_stream_gb_per_sec": round(x_bytes / sec / 1e9, 1),
        }
    for cand, reason in _autotune_failures.get(key, {}).items():
        candidates["{}:{}".format(*_decode_block(cand))] = {"failed": reason}
    return {"winner": _autotune_cache.get(key), "candidates": candidates}
