"""Fused sparse per-entity kernels: bucketed-slab GEVM + HVP families.

The dominant production cost of GLMix is the skewed sparse per-entity
random-effect solves. The bucketed/streaming coordinates already fixed the
PADDING waste (entity-size buckets on the PR-3 shape ladder — the 542x
bucketed-vs-global-max win) and the ITERATION waste (convergence
compaction); what remains is the ARITHMETIC waste: every per-entity solve
runs its value/gradient/Hessian-vector passes through the dense
``(E, M, D_loc)`` slab, burning MXU cycles and HBM bandwidth on the zeros
of rows that carry only a handful of non-zero features.

This module is the sparse answer: the per-entity feature rows live in a
bucketed padded-COO **slab** — ``idx/val (E, M, K)`` with ``K`` the
bucket's max row-nnz rounded up the canonical shape ladder — and a family
of kernels computes the gathered-entity matvec (GEVM), the fused
loss+gradient, and the Hessian-vector product directly on that slab:

  * ``"scatter"`` — plain XLA: margin = gather + row-sum, gradient /
    HVP transpose = one flat scatter-add. The canonical arithmetic every
    other family must reproduce BITWISE.
  * ``"segment"`` — the XLA two-pass segment-sum baseline: the transpose
    action as ``jax.ops.segment_sum`` over the flattened slab entries.
    This is the race's reference point ("kernel off").
  * ``"flat"`` — the lane-offset flat scatter: under ``vmap`` over
    entities the per-lane transposes become ONE 1-D scatter-add into the
    ``(E*D,)`` ravel (lane ``e``'s entries offset by ``e*D``), via a
    ``custom_vmap`` batching rule. Lanes are disjoint index segments, so
    every column accumulates in exactly the per-lane flat ``(m, k)``
    order — bitwise-equal to ``scatter``/``segment`` by construction —
    while XLA sees a single dense scatter loop instead of a batched
    scatter (measured ~1.3x over the two-pass baseline on CPU).
  * ``"pallas"`` / ``"pallas:<block>"`` — the fused single-pass Pallas
    kernel: one load of ``idx/val`` feeds margin, loss, derivative AND the
    gradient scatter (the HVP variant computes both ``z`` and ``z_v`` from
    that one load), gridded over row blocks with hierarchical
    accumulation: per-row partials are emitted at full row extent and
    reduced OUTSIDE the kernel by the fixed-association pairwise tree
    (``tree_row_sum``) every sparse family shares (lane level — a plain
    ``reduce``'s association is fusion-context-dependent, and a one-ulp
    loss value flips line searches), the gradient accumulates
    sequentially across row blocks into a VMEM accumulator (slab level),
    and per-entity outputs are psum-ready for the mesh reduction (device
    level — Snap ML's device-local partials feeding host/cluster
    reduction levels, arXiv:1803.06333; the reduction placement follows
    DrJAX's MapReduce-primitives framing, 2403.07128).

Bitwise discipline (the gate every prior optimization shipped under): all
sparse families share ONE arithmetic — contributions gathered in ascending
column order, transpose contributions applied in flat ``(m, k)`` order,
row reductions at the full padded extent — so a solve through the fused
kernel is bitwise-equal to the same solve with the kernel off (the XLA
baseline family). Candidates are VERIFIED for that equality at selection
time and disqualified (with a recorded reason) when a backend breaks it.
The dense path is a different arithmetic (XLA reassociates the dense dot),
so dense-vs-sparse agreement is at float tolerance, and turning the sparse
path on at all is an explicit, raced choice per bucket.

Selection (``PHOTON_SPARSE_KERNEL`` = ``off`` (default) | ``auto`` |
family name): ``auto`` races every family — and the incumbent dense path —
on the bucket's own tensors through the solver-identical vmapped
value+grad closure, disqualifies unverifiable candidates, and returns the
winner (``None`` = dense keeps the bucket). Every candidate that did not
produce a timing is recorded with a reason — a candidate that failed to
compile must read as FAILED in the race record, not be silently absent.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import os
import time
import warnings
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from photon_ml_tpu.ops.features import _acc_dtype
from photon_ml_tpu.ops.fused_glm import _first_line, _interpret_default
from photon_ml_tpu.ops.losses import PointwiseLoss

Array = jax.Array

logger = logging.getLogger(__name__)

_SPARSE_ENV = "PHOTON_SPARSE_KERNEL"

#: the two-pass XLA family the race measures candidates against and the
#: bit-identity gate verifies candidates against ("the kernel off")
SPARSE_BASELINE = "segment"

#: structurally distinct schedules; pallas row-block variants are derived
#: from the slab's padded row count at race time (see sparse_candidates)
SPARSE_FAMILIES = ("scatter", "segment", "flat", "pallas")

#: row-block sizes for the blocked pallas variants (only raced when they
#: divide the slab's padded row count — ladder-padded M usually does)
PALLAS_ROW_BLOCKS = (256, 2048)


def _family_block(kernel: str) -> Tuple[str, int]:
    """("pallas", block_rows) from "pallas:<block>"; 0 = whole-slab block."""
    if ":" in kernel:
        fam, block = kernel.split(":", 1)
        return fam, int(block)
    return kernel, 0


def sparse_candidates(m: int) -> Tuple[str, ...]:
    """The raced family set for a slab with ``m`` padded rows per lane."""
    blocked = tuple(
        f"pallas:{b}" for b in PALLAS_ROW_BLOCKS if m > b and m % b == 0
    )
    return SPARSE_FAMILIES + blocked


def tree_row_sum(x: Array) -> Array:
    """Fixed-association pairwise reduction over the LAST axis.

    Explicit adds that XLA executes exactly as written — a ``reduce`` op's
    accumulation order is backend-internal and changes with producer
    fusion (observed: the same (M,) loss vector summing to values one ulp
    apart inside vs outside a jit, which flips line-search decisions).
    Every sparse family reduces its row axis through THIS — the generic
    objective branch for slab features and the fused kernel wrappers alike
    — so the scalar pieces are bitwise-equal across families by
    construction, on every backend. Zero-padding to a power of two is
    exact (x + 0 == x in IEEE754 for every finite/inf x).
    """
    n = x.shape[-1]
    p = 1 << (n - 1).bit_length() if n > 1 else 1
    if p != n:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, p - n)])
    while x.shape[-1] > 1:
        x = x[..., 0::2] + x[..., 1::2]
    return x[..., 0]


try:  # public since jax 0.3; routed defensively like every version seam
    from jax.custom_batching import custom_vmap as _custom_vmap
except ImportError:  # ancient jax: "flat" degrades to the plain scatter
    _custom_vmap = None


@functools.lru_cache(maxsize=None)
def _flat_rmatvec(dim: int, dtype_name: str):
    """The ``"flat"`` family's transpose: per-lane it IS the canonical
    flat scatter-add; under ``vmap`` a ``custom_vmap`` rule folds the lane
    offset ``e*dim`` into the indices and runs ONE 1-D scatter into the
    ``(E*dim,)`` ravel. Lanes are disjoint segments, so each column's
    contributions still arrive in the per-lane flat ``(m, k)`` order —
    bitwise-equal to the batched-scatter lowering — but XLA executes a
    single flat scatter loop instead of E nested ones. ``promise_in_bounds``
    is safe by construction: slab indices come from valid columns and
    padding slots carry index 0."""
    dtype = jnp.dtype(dtype_name)

    def plain(flat_idx, flat_contrib):
        return jnp.zeros((dim,), dtype).at[flat_idx].add(
            flat_contrib, mode="promise_in_bounds"
        )

    if _custom_vmap is None:
        return plain
    impl = _custom_vmap(plain)

    @impl.def_vmap
    def _rule(axis_size, in_batched, flat_idx, flat_contrib):  # noqa: ARG001
        if not all(in_batched) or axis_size * dim >= np.iinfo(np.int32).max:
            # unbatched operands or an int32-overflowing ravel: keep the
            # stock batched-scatter lowering (same numbers, no fusion)
            return jax.vmap(plain)(
                jnp.broadcast_to(flat_idx, (axis_size,) + flat_idx.shape[-1:]),
                jnp.broadcast_to(
                    flat_contrib, (axis_size,) + flat_contrib.shape[-1:]
                ),
            ), True
        lane = (jnp.arange(axis_size, dtype=flat_idx.dtype) * dim)[:, None]
        out = jnp.zeros((axis_size * dim,), dtype).at[
            (flat_idx + lane).reshape(-1)
        ].add(flat_contrib.reshape(-1), mode="promise_in_bounds")
        return out.reshape(axis_size, dim), True

    return impl


# ---------------------------------------------------------------------------
# the slab
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SparseSlab:
    """Bucketed padded-COO per-entity features (the Features protocol).

    ``idx``/``val`` have shape ``(E, M, K)`` at the slab level; under
    ``jax.vmap`` over the entity axis each lane sees the ``(M, K)`` view —
    the SAME class, so the solver's per-lane closures are layout-blind.
    Padding slots carry ``val == 0`` and index 0 (in-bounds gathers,
    no-op scatters); entries within a row are in ascending column order
    (the order the dense accumulation visits the same non-zeros).

    ``kernel`` (static) names the family the objective dispatches on:
    ``"scatter"`` / ``"segment"`` ride the generic two-pass objective with
    this class's matvec/rmatvec; ``"pallas*"`` short-circuits into the
    fused single-pass kernels below.
    """

    idx: Array  # (..., M, K) int32
    val: Array  # (..., M, K)
    dim: int = dataclasses.field(metadata={"static": True})
    kernel: str = dataclasses.field(
        default="scatter", metadata={"static": True}
    )

    @property
    def num_rows(self) -> int:
        return self.idx.shape[-2]

    @property
    def max_nnz(self) -> int:
        return self.idx.shape[-1]

    # -- Features protocol (lane-level (M, K); batched shapes also work) ----
    @jax.named_scope("pml.features.matvec")
    def matvec(self, w: Array) -> Array:
        acc = _acc_dtype(self.val.dtype)
        return jnp.sum(w[self.idx].astype(acc) * self.val.astype(acc), axis=-1)

    def _flat_contrib(self, d: Array) -> Tuple[Array, Array]:
        acc = _acc_dtype(self.val.dtype)
        contrib = self.val.astype(acc) * d.astype(acc)[..., None]
        return self.idx.reshape(-1), contrib.reshape(-1)

    @jax.named_scope("pml.features.rmatvec")
    def rmatvec(self, d: Array) -> Array:
        acc = _acc_dtype(self.val.dtype)
        flat_idx, flat_contrib = self._flat_contrib(d)
        return self._transpose_apply(flat_idx, flat_contrib, acc)

    @jax.named_scope("pml.features.sq_rmatvec")
    def sq_rmatvec(self, d: Array) -> Array:
        acc = _acc_dtype(self.val.dtype)
        contrib = jnp.square(self.val.astype(acc)) * d.astype(acc)[..., None]
        return self._transpose_apply(
            self.idx.reshape(-1), contrib.reshape(-1), acc
        )

    def _transpose_apply(self, flat_idx: Array, flat_contrib: Array, acc) -> Array:
        """The family's transpose action — one arithmetic (flat (m, k)
        contribution order), three schedules."""
        if self.kernel == "segment":
            return jax.ops.segment_sum(
                flat_contrib, flat_idx, num_segments=self.dim
            )
        if self.kernel == "flat":
            return _flat_rmatvec(self.dim, jnp.dtype(acc).name)(
                flat_idx, flat_contrib
            )
        return jnp.zeros((self.dim,), acc).at[flat_idx].add(flat_contrib)

    def row_sq_norms(self) -> Array:
        acc = _acc_dtype(self.val.dtype)
        return jnp.sum(jnp.square(self.val.astype(acc)), axis=-1)

    def to_dense(self) -> Array:
        acc = _acc_dtype(self.val.dtype)
        shape = self.idx.shape[:-1] + (self.dim,)
        out = jnp.zeros(shape, acc)
        lead = jnp.broadcast_to(
            jnp.arange(self.idx.shape[-2])[:, None], self.idx.shape[-2:]
        )
        if self.idx.ndim != 2:
            raise NotImplementedError("to_dense is a lane-level debug view")
        return out.at[lead.reshape(-1), self.idx.reshape(-1)].add(
            self.val.reshape(-1).astype(acc)
        )

    def with_kernel(self, kernel: str) -> "SparseSlab":
        return SparseSlab(self.idx, self.val, self.dim, kernel)

    def astype(self, dtype) -> "SparseSlab":
        return SparseSlab(self.idx, self.val.astype(dtype), self.dim, self.kernel)

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        return (self.idx, self.val), (self.dim, self.kernel)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], aux[0], aux[1])


def build_sparse_slab(
    x,
    bucketer=None,
    kernel: str = "scatter",
    dtype=None,
) -> SparseSlab:
    """Extract the padded-COO slab from a dense ``(..., M, D)`` feature
    stack (host-side, once per bucket/block).

    ``K`` = the slab's max row-nnz, rounded up the canonical shape ladder
    (``bucketer``: photon_ml_tpu.compile spec, None = PHOTON_SHAPE_LADDER)
    and capped at ``D`` — slabs from different buckets that land on the
    same ``(M, K)`` rung share compiled solver executables. Entries keep
    ascending column order; rows with zero non-zeros (padding rows,
    nnz=0 entities) become all-(idx 0, val 0) rows, and ``K >= 1`` always
    holds so downstream shapes stay non-degenerate.
    """
    from photon_ml_tpu.compile import resolve_bucketer

    x = np.asarray(x)
    d = x.shape[-1]
    mask = x != 0
    counts = mask.sum(axis=-1)  # (..., M)
    k_raw = max(int(counts.max(initial=0)), 1)
    b = resolve_bucketer(bucketer)
    k = k_raw if b is None else min(b.canon(k_raw), d)
    k = max(min(k, d), 1)
    # stable argsort of the ~mask puts non-zero columns first, preserving
    # ascending column order among them (the dense accumulation order)
    order = np.argsort(~mask, axis=-1, kind="stable")[..., :k]
    val = np.take_along_axis(x, order, axis=-1)
    pad = np.arange(k) >= counts[..., None]
    idx = np.where(pad, 0, order).astype(np.int32)
    val = np.where(pad, 0, val)
    if dtype is None:
        dtype = x.dtype
    return SparseSlab(jnp.asarray(idx), jnp.asarray(val, dtype), d, kernel)


def slab_nnz_stats(slab: SparseSlab) -> dict:
    """Host-side nnz accounting (bench/diagnostics): how much arithmetic
    the slab avoids vs its dense (M, D) counterpart."""
    val = np.asarray(slab.val)
    nnz = (val != 0).sum(axis=-1)
    dense_elems = int(np.prod(val.shape[:-1])) * slab.dim
    slab_elems = int(np.prod(val.shape))
    return {
        "rows": int(np.prod(val.shape[:-1])),
        "max_nnz": int(nnz.max(initial=0)),
        "mean_nnz": round(float(nnz.mean()) if nnz.size else 0.0, 2),
        "padded_k": slab.max_nnz,
        "dim": slab.dim,
        "slab_elements": slab_elems,
        "dense_elements": dense_elems,
        "density": round(slab_elems / dense_elems, 4) if dense_elems else 0.0,
    }


# ---------------------------------------------------------------------------
# fused single-pass Pallas kernels (lane-level; vmap over entities adds the
# slab grid dimension)
# ---------------------------------------------------------------------------


def _make_gevm_kernel(loss: PointwiseLoss, block_rows: int, m: int):
    """One-pass (row_wl, grad, row_d) over a lane's (M, K) slab rows.

    Hierarchical accumulation with a bitwise discipline: the per-row
    weighted-loss/derivative partials are EMITTED at full (M, 1) extent
    (lane level) — the final row reductions run OUTSIDE the kernel through
    the fixed-association ``tree_row_sum`` every sparse family shares,
    because a reduction's association order (in-kernel or fused by XLA)
    is backend-internal and a one-ulp loss value flips line-search
    decisions. The gradient accumulates across row blocks sequentially in
    flat (m, k) order (slab level), reproducing the flat scatter-add
    exactly.
    """
    last = m // block_rows - 1

    def kernel(
        idx_ref, val_ref, y_ref, wt_ref, off_ref, w_ref,
        wl_out, grad_out, d_out,
        acc_grad,
    ):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            acc_grad[:] = jnp.zeros_like(acc_grad)

        idx = idx_ref[:]  # (BM, K) int32
        val = val_ref[:]  # (BM, K) f32
        w = w_ref[:]  # (1, D) f32
        y = y_ref[:]  # (BM, 1) f32
        wt = wt_ref[:]
        off = off_ref[:]

        z = jnp.sum(w[0][idx] * val, axis=-1, keepdims=True) + off
        lv = loss.loss(z, y)
        # hard mask, same rule as every family: weight-0 (padding) rows
        # contribute an exact 0 even on inf/nan garbage
        wl_out[:] = jnp.where(wt > 0.0, wt * lv, 0.0)
        dd = jnp.where(wt > 0.0, wt * loss.d1(z, y), 0.0)
        d_out[:] = dd
        acc_grad[:] = acc_grad[:].at[0, idx.reshape(-1)].add(
            (val * dd).reshape(-1)
        )

        @pl.when(i == last)
        def _():
            grad_out[:] = acc_grad[:]

    return kernel


def _make_hvp_kernel(loss: PointwiseLoss, block_rows: int, m: int):
    """One-pass (hvp, row_c) over a lane's (M, K) slab rows: ONE load of
    idx/val feeds both contractions (z from w, z_v from v) and the
    transpose scatter — the sparse analogue of the dense fused kernel's
    one-HBM-stream-two-contractions trick. ``c`` is emitted at full
    (M, 1) extent; the ``sum_c`` reduction runs outside the kernel via
    ``tree_row_sum`` (same bitwise rationale as the GEVM row outputs)."""
    last = m // block_rows - 1

    def kernel(
        idx_ref, val_ref, y_ref, wt_ref, off_ref, w_ref, v_ref, vshift_ref,
        hvp_out, c_out,
        acc_hvp,
    ):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            acc_hvp[:] = jnp.zeros_like(acc_hvp)

        idx = idx_ref[:]
        val = val_ref[:]
        w = w_ref[:]
        v = v_ref[:]
        y = y_ref[:]
        wt = wt_ref[:]
        off = off_ref[:]

        z = jnp.sum(w[0][idx] * val, axis=-1, keepdims=True) + off
        zv = jnp.sum(v[0][idx] * val, axis=-1, keepdims=True) + vshift_ref[:]
        d2 = jnp.where(wt > 0.0, wt * loss.d2(z, y), 0.0)
        c = d2 * zv

        c_out[:] = c
        acc_hvp[:] = acc_hvp[:].at[0, idx.reshape(-1)].add(
            (val * c).reshape(-1)
        )

        @pl.when(i == last)
        def _():
            hvp_out[:] = acc_hvp[:]

    return kernel


def _marshal_rows(m: int, *vecs):
    return tuple(v.reshape(m, 1).astype(jnp.float32) for v in vecs)


def _resolve_block(block_rows: int, m: int) -> int:
    """Effective row-block size: 0 = the whole padded extent in one grid
    step; a requested block that does not tile M falls back to the
    whole-slab grid — a forced ``pallas:<rows>`` spec applies globally
    across buckets on heterogeneous ladder rungs, and the row-block grid
    is a schedule, not a result (identical arithmetic either way), so one
    non-tiling bucket must not abort the run. The race only ever offers
    divisors (sparse_candidates)."""
    if block_rows <= 0 or block_rows >= m or m % block_rows:
        return max(m, 1)
    return block_rows


@functools.lru_cache(maxsize=128)
def _gevm_fn(loss: PointwiseLoss, block_rows: int, m: int, k: int, d: int,
             interpret: bool):
    kernel = _make_gevm_kernel(loss, block_rows, m)
    grid = m // block_rows

    def call(idx, val, y, wt, off, w):
        row_wl, grad, row_d = pl.pallas_call(
            kernel,
            grid=(grid,),
            in_specs=[
                pl.BlockSpec((block_rows, k), lambda i: (i, 0)),
                pl.BlockSpec((block_rows, k), lambda i: (i, 0)),
                pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
                pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
                pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
                pl.BlockSpec((1, d), lambda i: (0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
                pl.BlockSpec((1, d), lambda i: (0, 0)),
                pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((m, 1), jnp.float32),
                jax.ShapeDtypeStruct((1, d), jnp.float32),
                jax.ShapeDtypeStruct((m, 1), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((1, d), jnp.float32),
            ],
            interpret=interpret,
        )(idx, val, *_marshal_rows(m, y, wt, off), w.reshape(1, d))
        # the FINAL row reductions run out here, over the full (M,) extent,
        # through the fixed-association pairwise tree every sparse family
        # uses — a plain reduce's order is fusion-context-dependent, and a
        # one-ulp loss value flips line searches (bitwise gate)
        return tree_row_sum(row_wl[:, 0]), grad[0], tree_row_sum(row_d[:, 0])

    return call


@functools.lru_cache(maxsize=128)
def _hvp_fn(loss: PointwiseLoss, block_rows: int, m: int, k: int, d: int,
            interpret: bool):
    kernel = _make_hvp_kernel(loss, block_rows, m)
    grid = m // block_rows

    def call(idx, val, y, wt, off, w, v, vshift):
        hvp, row_c = pl.pallas_call(
            kernel,
            grid=(grid,),
            in_specs=[
                pl.BlockSpec((block_rows, k), lambda i: (i, 0)),
                pl.BlockSpec((block_rows, k), lambda i: (i, 0)),
                pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
                pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
                pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
                pl.BlockSpec((1, d), lambda i: (0, 0)),
                pl.BlockSpec((1, d), lambda i: (0, 0)),
                pl.BlockSpec((1, 1), lambda i: (0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, d), lambda i: (0, 0)),
                pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((1, d), jnp.float32),
                jax.ShapeDtypeStruct((m, 1), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((1, d), jnp.float32),
            ],
            interpret=interpret,
        )(
            idx, val, *_marshal_rows(m, y, wt, off),
            w.reshape(1, d), v.reshape(1, d),
            vshift.reshape(1, 1).astype(jnp.float32),
        )
        return hvp[0], tree_row_sum(row_c[:, 0])

    return call


def fused_value_grad_parts(
    loss: PointwiseLoss,
    slab: SparseSlab,
    labels: Array,
    weights: Array,
    offsets: Array,
    w: Array,
    interpret: Optional[bool] = None,
) -> Tuple[Array, Array, Array]:
    """Raw one-pass pieces for one lane: (sum w_i*l_i, X^T d, sum d).

    ``offsets`` must already fold the normalization margin shift (the
    caller owns the shift/factor/L2 algebra, like the dense fused path).
    """
    if interpret is None:
        interpret = _interpret_default()
    m, k = slab.idx.shape[-2:]
    _, block = _family_block(slab.kernel)
    fn = _gevm_fn(loss, _resolve_block(block, m), m, k, slab.dim, interpret)
    return fn(slab.idx, slab.val, labels, weights, offsets, w)


def fused_hvp_parts(
    loss: PointwiseLoss,
    slab: SparseSlab,
    labels: Array,
    weights: Array,
    offsets: Array,
    w: Array,
    v: Array,
    vshift: Array,
    interpret: Optional[bool] = None,
) -> Tuple[Array, Array]:
    """Raw one-pass HVP pieces for one lane: (X^T c, sum c) with
    c = weight * l''(z) * (X v + vshift)."""
    if interpret is None:
        interpret = _interpret_default()
    m, k = slab.idx.shape[-2:]
    _, block = _family_block(slab.kernel)
    fn = _hvp_fn(loss, _resolve_block(block, m), m, k, slab.dim, interpret)
    return fn(
        slab.idx, slab.val, labels, weights, offsets, w, v,
        jnp.asarray(vshift, jnp.float32),
    )


# ---------------------------------------------------------------------------
# selection: the per-bucket race (dense incumbent vs sparse families)
# ---------------------------------------------------------------------------


def resolve_sparse_kernel(spec: Optional[str] = None) -> Optional[str]:
    """Effective sparse-kernel spec: an explicit value wins; ``None``
    falls back to ``PHOTON_SPARSE_KERNEL``. Returns ``None`` (off),
    ``"auto"`` (race per bucket), or a family name."""
    if spec is None:
        spec = os.environ.get(_SPARSE_ENV)
    if spec is None:
        return None
    text = str(spec).strip().lower()
    if text in ("", "off", "false", "0", "none"):
        return None
    if text in ("on", "auto", "race"):
        return "auto"
    fam, _ = _family_block(text)
    if fam not in SPARSE_FAMILIES or (":" in text and fam != "pallas"):
        # ":<rows>" is pallas-only grammar: "flat:128" would carry the
        # suffix into the static kernel field, miss _transpose_apply's
        # exact-match dispatch, and silently run the scatter schedule
        raise ValueError(
            f"bad sparse-kernel spec {spec!r} (want off | auto | "
            f"{' | '.join(SPARSE_FAMILIES)} | pallas:<rows>)"
        )
    return text


_race_cache: dict = {}
_race_reports: dict = {}


def _lane_vg_fns(task, l2: float = 0.0):
    """The solver-identical vmapped value+grad closure builder: candidates
    are timed through the EXACT code path the coordinates run (GLMObjective
    over a per-lane GLMBatch), so the race measures what production pays."""
    from photon_ml_tpu.ops import losses as losses_mod
    from photon_ml_tpu.ops.features import DenseFeatures
    from photon_ml_tpu.ops.normalization import NormalizationContext
    from photon_ml_tpu.ops.objective import GLMBatch, GLMObjective

    loss = losses_mod.for_task(task)
    obj = GLMObjective(loss)
    norm = NormalizationContext.identity()

    def one(feats, y, off, wt, w):
        if isinstance(feats, jax.Array):
            feats = DenseFeatures(feats)
        return obj.value_and_grad(w, GLMBatch(feats, y, off, wt), norm, l2)

    return jax.vmap(one, in_axes=(0, 0, 0, 0, 0))


def _time_lane_vg(vg, w0, data, iters: int = 8) -> float:
    """Seconds per vmapped value+grad pass, serialized on-chip by a scan
    (a host loop would time the enqueue), each repeat on fresh carries."""

    def run(w, d):
        def step(w, _):
            vals, grads = vg(d[0], d[1], d[2], d[3], w)
            return w - 1e-6 * grads, vals

        return lax.scan(step, w, None, length=iters)

    scan = jax.jit(run)  # jit-ok: bench-only race harness
    w = jax.block_until_ready(scan(w0, data))[0]
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        out = scan(w, data)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / iters)
        w = out[0]
    return best


def race_sparse_kernels(
    task,
    slab: SparseSlab,
    x_dense,
    labels: Array,
    offsets: Array,
    weights: Array,
    include_dense: bool = True,
    max_lanes: int = 512,
    candidates: Optional[Tuple[str, ...]] = None,
) -> dict:
    """Race every sparse family (and the dense incumbent) on this bucket's
    own tensors through the solver-identical vmapped vg closure.

    Returns ``{"winner", "baseline", "candidates": {name: {...}}}`` where
    every raced name appears either with timings or with a ``"failed"``
    reason (verification mismatch, compile error, eligibility) — no silent
    drops. ``winner`` is a family name, or ``None`` when the dense path
    keeps the bucket.
    """
    e, m, k = slab.idx.shape
    d = slab.dim
    probe = slice(0, min(e, max_lanes))
    slab_p = SparseSlab(slab.idx[probe], slab.val[probe], d, slab.kernel)
    y_p, off_p, wt_p = labels[probe], offsets[probe], weights[probe]
    w0 = jnp.zeros((slab_p.idx.shape[0], d), slab_p.val.dtype)
    vg = _lane_vg_fns(task)

    report = {}
    timings = {}
    outputs = {}
    cands = list(candidates if candidates is not None else sparse_candidates(m))
    if SPARSE_BASELINE not in cands:
        cands.insert(0, SPARSE_BASELINE)
    f64 = jnp.dtype(slab.val.dtype) == jnp.float64

    for fam in cands:
        if _family_block(fam)[0] == "pallas" and f64:
            report[fam] = {"failed": "skipped: pallas family ineligible under float64"}
            continue
        data = (slab_p.with_kernel(fam), y_p, off_p, wt_p)
        try:
            vals, grads = jax.jit(vg)(*data, w0)  # jit-ok: bench-only race harness
            outputs[fam] = (np.asarray(vals), np.asarray(grads))
            # timing stays inside the try: a candidate that verifies but
            # dies under the scan-timing harness must also read as failed,
            # not abort the race (the no-silent-drops contract)
            timings[fam] = _time_lane_vg(vg, w0, data)
        except Exception as exc:  # noqa: BLE001 — race probe: failure disqualifies the candidate (recorded, not dropped)
            report[fam] = {"failed": f"error: {type(exc).__name__}: {exc}"[:300]}
            logger.warning(
                "sparse race: candidate %s refused (%s)", fam, _first_line(exc)
            )
            outputs.pop(fam, None)
            continue

    base_out = outputs.get(SPARSE_BASELINE)
    verified = {}
    for fam, out in outputs.items():
        if base_out is None:
            report.setdefault(fam, {})["failed"] = (
                "baseline family failed; no verification possible"
            )
            continue
        bitwise = np.array_equal(out[0], base_out[0]) and np.array_equal(
            out[1], base_out[1]
        )
        if not bitwise:
            report[fam] = {
                "failed": "numerics: not bitwise-equal to the "
                f"{SPARSE_BASELINE} baseline on this backend"
            }
            timings.pop(fam, None)
            continue
        verified[fam] = timings[fam]

    if include_dense:
        try:
            data_d = (jnp.asarray(np.asarray(x_dense)[probe]), y_p, off_p, wt_p)
            timings["dense"] = _time_lane_vg(vg, w0, data_d)
        except Exception as exc:  # noqa: BLE001 — incumbent probe failure: sparse race proceeds without it (recorded)
            report["dense"] = {"failed": f"error: {type(exc).__name__}: {exc}"[:300]}
            logger.warning(
                "sparse race: dense incumbent refused (%s)", _first_line(exc)
            )

    rows = int(slab_p.idx.shape[0]) * m
    for fam, sec in timings.items():
        if fam in verified or fam == "dense":
            report[fam] = {
                "sec_per_pass": round(sec, 6),
                "lane_rows_per_sec": round(rows / sec, 1) if sec else 0.0,
            }
    eligible = dict(verified)
    if include_dense and "dense" in timings:
        eligible["dense"] = timings["dense"]
    winner = min(eligible, key=eligible.get) if eligible else None
    if winner == "dense":
        winner = None
    return {
        "winner": winner,
        "baseline": SPARSE_BASELINE,
        "shape": {"lanes": int(e), "rows": m, "k": k, "dim": d},
        "nnz": slab_nnz_stats(slab),
        "candidates": report,
    }


def select_sparse_kernel(
    task,
    slab: SparseSlab,
    x_dense,
    labels: Array,
    offsets: Array,
    weights: Array,
    spec: Optional[str] = None,
    label: str = "re",
    candidates: Optional[Tuple[str, ...]] = None,
) -> Optional[str]:
    """Per-bucket family selection. ``spec`` (or PHOTON_SPARSE_KERNEL):
    ``None``/off -> dense path stays; a family name -> forced; ``auto`` ->
    race on this bucket's tensors, cached per (task, shape, platform).
    Returns the family to use, or ``None`` for the dense path.

    ``candidates`` narrows the race to the named families (plus the dense
    incumbent): the cost-based planner's "predicted pick + cheap
    validation" — one predicted family validated against dense instead of
    every family timed per bucket (``ExecutionPlan.sparse_candidates``)."""
    resolved = resolve_sparse_kernel(spec)
    if resolved is None:
        return None
    if resolved != "auto":
        return resolved
    from photon_ml_tpu.ops import losses as losses_mod

    e, m, k = slab.idx.shape
    platform = jax.devices()[0].platform
    # dtype is part of the key: eligibility differs (pallas is out under
    # f64), so an f32 bucket's winner must not be reused for an f64 slab;
    # a planner-narrowed race must not poison the full-race cache either
    key = (
        losses_mod.for_task(task).name, e, m, k, slab.dim,
        jnp.dtype(slab.val.dtype).name, platform,
        tuple(candidates) if candidates else None,
    )
    if key in _race_cache:
        return _race_cache[key]
    report = race_sparse_kernels(
        task, slab, x_dense, labels, offsets, weights,
        candidates=tuple(candidates) if candidates else None,
    )
    _race_reports[(label,) + key] = report
    _race_cache[key] = report["winner"]
    return report["winner"]


def race_reports() -> dict:
    """All recorded per-bucket race reports (bench/diagnostics surface)."""
    return dict(_race_reports)


def build_and_select(
    task,
    x,
    labels: Array,
    offsets: Array,
    weights: Array,
    spec: str,
    label: str,
    bucketer=None,
    candidates: Optional[Tuple[str, ...]] = None,
) -> Optional[SparseSlab]:
    """Host-side slab build + family selection for ONE bucket/block — the
    shared sequence behind every coordinate's sparse wiring. ``spec`` is an
    already-resolved spec (``"auto"`` races on this bucket's own tensors,
    optionally narrowed to the planner's predicted ``candidates``; a
    family name is forced). Returns the slab carrying the selected
    family, or ``None`` when the dense path keeps the bucket."""
    slab = build_sparse_slab(x, bucketer=bucketer)
    if spec == "auto":
        family = select_sparse_kernel(
            task, slab, x, labels, offsets, weights, spec="auto",
            label=label, candidates=candidates,
        )
    else:
        family = spec
        if (
            _family_block(family)[0] == "pallas"
            and jnp.dtype(slab.val.dtype) == jnp.float64
        ):
            # mirror the race's eligibility rule for FORCED specs: the
            # objective's f64 gate would run the generic scatter anyway —
            # under a "pallas" static key, so telemetry would lie and the
            # identical arithmetic would compile a duplicate executable
            warnings.warn(
                f"{label}: pallas family is ineligible under float64; "
                "running the scatter family instead",
                stacklevel=2,
            )
            family = "scatter"
    return slab.with_kernel(family) if family is not None else None
