"""Fused GLM value+gradient Pallas kernel — the training hot loop.

The GLM hot loop (ValueAndGradientAggregator semantics, SURVEY.md §2.2,
reference spec function/ValueAndGradientAggregator.scala:120-139) is
HBM-bandwidth-bound on TPU: the two XLA GEMV passes (margin ``X @ w``,
gradient ``d @ X``) each stream the whole (N, D) feature matrix from HBM.
This kernel fuses them into ONE pass — each row block is loaded into VMEM
once and used for both the margin matmul and the gradient outer-product —
and pairs with bfloat16 feature storage (f32 accumulation on the MXU) for
another 2x traffic cut: ~4x less HBM traffic than the naive f32 two-pass.

The kernel is generic over any :class:`PointwiseLoss` and also accumulates
``sum(d)`` so callers can reconstruct the normalization-shift gradient term
(``grad_eff = X^T d - shifts * sum(d)``) without a second data pass. It
therefore slots directly into ``GLMObjective.value_and_grad`` (see
``fused_block_rows`` there) behind a runtime autotune:
:func:`select_fused_block_rows` times the kernel against the two-pass XLA
path on the live device and returns the winning block size — or ``None``
when XLA wins or the shape/platform is ineligible — so the fused path is
the default exactly where it is faster.

Numerically: margins/loss/derivative are computed in f32; only the feature
matrix (and the per-block derivative entering the second matmul) are bf16.
Padding rows carry weight 0 and contribute exactly nothing (hard-masked, so
even inf/nan garbage in padding rows is zeroed). Runs in interpreter mode
on the CPU backend only (tests).
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


def _make_vpu_kernel(loss: PointwiseLoss):
    """Grid kernel with BOTH contractions as elementwise multiply +
    reduction on the VPU (no matmuls): z via a lane reduction over D,
    the gradient via a sublane reduction over the row block. Escapes the
    M=1 MXU GEVM ceiling (see AUTOTUNE_CANDIDATES) at the cost of f32
    elementwise work the VPU can sustain at full HBM rate."""

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

        x = x_ref[:].astype(jnp.float32)  # (BN, D)
        w_row = w_ref[:]  # (1, D) f32 — marshalled row-major for the VPU
        y = y_ref[:]
        wt = wt_ref[:]
        off = off_ref[:]

        z = jnp.sum(x * w_row, axis=1, keepdims=True) + off  # (BN, 1)
        lv = loss.loss(z, y)
        wl = jnp.where(wt > 0.0, wt * lv, 0.0)
        d = jnp.where(wt > 0.0, wt * loss.d1(z, y), 0.0)  # (BN, 1)

        acc_loss[:] += jnp.sum(wl, keepdims=True).reshape(1, 1)  # lint: bitwise-reduction — pallas block-local accumulate; order pinned by the sequential grid
        acc_sumd[:] += jnp.sum(d, keepdims=True).reshape(1, 1)  # lint: bitwise-reduction — pallas block-local accumulate; order pinned by the sequential grid
        acc_grad[:] += jnp.sum(x * d, axis=0, keepdims=True)  # (1, D)  # lint: bitwise-reduction — pallas block-local accumulate; order pinned by the sequential grid

        @pl.when(i == pl.num_programs(0) - 1)
        def _():
            loss_out[:] = acc_loss[:]
            grad_out[:] = acc_grad[:]
            sumd_out[:] = acc_sumd[:]

    return _kernel


# Mosaic's default scoped-VMEM limit, and how far a kernel may raise it
# (a v5e core has 128 MiB of VMEM; the compiler refuses a limit it cannot
# place, and the race records the refusal).
_DEFAULT_SCOPED_VMEM = 16 << 20
_MAX_SCOPED_VMEM = 100 << 20


def _grid_vmem_limit(block_rows: int, d: int, itemsize: int, vpu: bool) -> Optional[int]:
    """``vmem_limit_bytes`` for one grid-pipeline block config, or None
    while the pipeline's buffers fit the default limit (1024- and 2048-row
    blocks at 512 x bf16 compile unchanged).

    The pipeline double-buffers every input block, and a ``(block_rows, 1)``
    f32 block occupies ``block_rows x 128`` lanes in VMEM — 512 B a row for
    each of y / weights / offsets, next to the x block's ``d * itemsize``.
    That alone is 20 MiB at 4096 x 512 bf16 (the compiler's own figure on a
    v5e, refused at the 16 MiB default). The raised limit adds room for
    the kernel's column temporaries (z, loss, derivative, ... — lane-padded
    the same way) and, for the VPU family, the f32 copy of the x block
    and its product."""
    column = block_rows * 128 * 4
    buffers = 2 * (block_rows * d * itemsize + 3 * column)
    if buffers + (2 << 20) <= _DEFAULT_SCOPED_VMEM:
        return None
    temporaries = 6 * column + (2 * block_rows * d * 4 if vpu else 0)
    return min(buffers + temporaries + (2 << 20), _MAX_SCOPED_VMEM)


@functools.lru_cache(maxsize=64)
def _fused_fn(loss: PointwiseLoss, block_rows: int, interpret: bool, vpu: bool = False):
    """Jitted single-pass (loss_sum, grad, sum_d) for one loss/block config."""
    kernel = _make_vpu_kernel(loss) if vpu else _make_kernel(loss)

    @jax.jit
    def call(x, y, weights, offsets, w):
        n, d = x.shape
        grid = n // block_rows
        inputs = _marshal_inputs(x, y, weights, offsets, w)
        # the VPU formulation wants w row-major (1, D) so the broadcast
        # multiply needs no in-kernel relayout
        w_spec = (
            pl.BlockSpec((1, d), lambda i: (0, 0))
            if vpu
            else pl.BlockSpec((d, 1), lambda i: (0, 0))
        )
        if vpu:
            inputs = inputs[:4] + (inputs[4].reshape(1, d),)
        loss_sum, grad, sumd = pl.pallas_call(
            kernel,
            grid=(grid,),
            in_specs=[
                pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
                pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
                pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
                pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
                w_spec,
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
                vmem_limit_bytes=_grid_vmem_limit(
                    block_rows, d, x.dtype.itemsize, vpu
                ),
            ),
            interpret=interpret,
        )(*inputs)
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
    Rows are padded (weight 0) up to a block multiple.

    ``block_rows``: an encoded (family, rows) candidate — positive =
    automatic grid pipeline (MXU matmuls), negative = the manual
    double-buffered variant with |block_rows| rows per chunk, >= VPU_MARK
    = the VPU elementwise formulation (see _decode_block; the autotuner
    races all three families and returns the winning encoding).
    """
    if interpret is None:
        interpret = _interpret_default()
    family, rows = _decode_block(block_rows)
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
        fn = _fused_fn(loss, block, interpret, vpu=family == "vpu")
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
# Runtime autotune: fused kernel vs. XLA two-pass, per (loss, shape, dtype)
# ---------------------------------------------------------------------------

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


def select_fused_block_rows(
    loss: PointwiseLoss,
    n: int,
    d: int,
    dtype=jnp.bfloat16,
    candidates: Tuple[int, ...] = AUTOTUNE_CANDIDATES,
) -> Optional[int]:
    """Pick the fused-kernel block size for an (N, D) dense GLM pass, or
    ``None`` when the plain XLA path should be used.

    Measures on the live default device with synthetic data (row count
    capped at 2^17 — throughput is row-count-invariant past that). Results
    are cached per (loss, n, d, dtype, platform). Controlled by
    ``PHOTON_ML_TPU_FUSED``: "auto" (default) races fused vs. XLA on TPU,
    "0" disables the fused path, "1" forces it (best fused candidate, no
    XLA comparison; runs in interpreter mode on the CPU, for testing).
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
    select_fused_block_rows(loss, n, d, dtype)  # populate cache
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
