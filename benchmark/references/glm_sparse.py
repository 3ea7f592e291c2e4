"""Plain reference for the ``glm_sparse`` family: L2 logistic regression
over padded sparse rows, fitted by L-BFGS from zero.

Float32 ``jax.numpy``, nothing of the program under test imported. A row is
``K`` (index, value) pairs; its margin is the plain gather-and-sum
``sum_k w[index_k] * value_k`` and the gradient the plain scatter-add of
``value_k * slope`` into ``index_k``. Both run over blocks of rows so that
the temporaries stay small.

``storage`` is the type the values and the coefficients are held in for the
two products; ``bfloat16`` is the control. Sums stay float32 **and keep their
rounding errors**, the value's and the gradient's alike, so that a gap between
the program's numbers and these is the program's to explain:

* the rows' losses are summed pairwise with Knuth's two-sum at every level
  (:func:`sum_with_error`): the value is the float32 nearest the exact sum of
  the float32 losses. (Summed plainly, one float32 total carried through the
  blocks, the first value 2^22 ln 2 came out 1.5e-6 low on the chip, nine
  times the program's own error: PERF.md section 6, PR 28.)
* each block of rows is scatter-added onto **zeros**, and the blocks' vectors
  are added with :func:`two_sum`, what each addition lost carried beside the
  sum and folded in once at the end. With blocks of 4,096 rows the most
  frequent feature takes some two thousand addends a block, and the blocks'
  small errors average out over the 1,024 blocks: on the chip the gradient
  is within 4e-8 of its norm of the float64 sum of its float32 products,
  over all features and over the 128 most frequent (``benchmark/control.py
  --gradient``; 8 blocks two-summed read 1e-6). (Added into one float32
  vector carried through all the rows, as this file did until PR 35 and as
  a row-order program does, two million addends land on one feature one
  after the other and the sum ends 0.7e-5 to 2.3e-5 from exact: a program
  that adds in another order then reads as a fault. PERF.md section 6, PRs
  31, 34 and 35.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.references.lbfgs import lbfgs, logistic_loss, logistic_slope


def margins(indices, values, w):
    return jnp.sum(w[indices] * values, axis=-1)


def two_sum(a, b):
    """(float32 sum, what its rounding lost): Knuth's error-free addition."""
    s = a + b
    b_seen = s - a
    return s, (a - (s - b_seen)) + (b - b_seen)


def sum_with_error(x):
    """A vector's sum in halves, pairwise, with every level's rounding errors
    added up beside it: (sum, error), the exact sum being their sum to a
    float32 rounding of the small error term."""
    error = jnp.zeros((), x.dtype)
    while x.shape[0] > 1:
        if x.shape[0] % 2:
            x = jnp.concatenate([x, jnp.zeros((1,), x.dtype)])
        half = x.shape[0] // 2
        x, lost = two_sum(x[:half], x[half:])
        error = error + jnp.sum(lost)
    return x[0], error


def in_blocks(indices, values, labels, weights, blocks, storage=jnp.float32):
    """The rows cut into ``blocks`` equal blocks, values held in ``storage``."""
    n, k = indices.shape
    return (indices.reshape(blocks, n // blocks, k),
            values.astype(storage).reshape(blocks, n // blocks, k),
            labels.reshape(blocks, -1), weights.reshape(blocks, -1))


def row_terms(w_s, i, v, y, rw):
    """One block's weighted losses ``(rows,)`` and the float32 products
    ``value * slope`` ``(rows, K)`` its gradient is the sum of."""
    z = jnp.sum((w_s[i] * v).astype(jnp.float32), axis=-1)
    slope = (rw * logistic_slope(z, y)).astype(v.dtype)
    return rw * logistic_loss(z, y), (v * slope[:, None]).astype(jnp.float32)


def block_sum(i, products, dim):
    """One block's products scatter-added onto zeros: a ``(dim,)`` vector."""
    return jnp.zeros((dim,), jnp.float32).at[i.reshape(-1)].add(
        products.reshape(-1))


def value_and_grad(w, rows, l2, dim):
    """Value and gradient at ``w`` over ``rows`` (:func:`in_blocks`), both the
    float32 nearest their exact sums (the module's docstring)."""
    w_s = w.astype(rows[1].dtype)

    def block(carry, part):
        value, error, grad, grad_error = carry
        i, v, y, rw = part
        losses, products = row_terms(w_s, i, v, y, rw)
        part_sum, part_error = sum_with_error(losses)
        value, lost = two_sum(value, part_sum)
        grad, grad_lost = two_sum(grad, block_sum(i, products, dim))
        return (value, error + (lost + part_error),
                grad, grad_error + grad_lost), None

    zero, zeros = jnp.zeros((), jnp.float32), jnp.zeros((dim,), jnp.float32)
    (value, error, grad, grad_error), _ = lax.scan(
        block, (zero, zero, zeros, zeros), rows)
    return (value + (error + 0.5 * l2 * jnp.dot(w, w)),
            (grad + grad_error) + l2 * w)


@functools.partial(
    jax.jit, static_argnames=("dim", "max_iter", "tol", "blocks", "storage"))
def fit(indices, values, labels, weights, l2, dim, max_iter, tol, blocks,
        storage=jnp.float32):
    """L-BFGS from zero. ``weights`` is one per row (all ones in a sound run;
    the planted fault "half of the batch left out" zeroes every second)."""
    rows = in_blocks(indices, values, labels, weights, blocks, storage)
    return lbfgs(lambda w: value_and_grad(w, rows, l2, dim),
                 jnp.zeros((dim,), jnp.float32), max_iter, tol)
