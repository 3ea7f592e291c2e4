"""Plain reference for the ``glm_dense`` family: L2 logistic regression over
a dense matrix of rows, a grid of L2 weights fitted by L-BFGS from high to
low, each solve started from the one before.

Float32 ``jax.numpy`` at the highest matmul precision (on a TPU a float32
product otherwise may round its operands to bfloat16), nothing of the program
under test imported. The margins ``X w`` and the gradient ``X^T s`` run over
blocks of rows so that the temporaries stay small; the sum of the rows'
losses keeps its rounding errors (``glm_sparse.sum_with_error``), so the
value is the float32 nearest the exact sum of the float32 losses.

``storage`` is the type the matrix and the coefficients are held in for the
two products; ``bfloat16`` is the control. Sums stay float32.
"""

from __future__ import annotations

import functools
from typing import List

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.references.glm_sparse import sum_with_error, two_sum
from benchmark.references.lbfgs import Solve, lbfgs, logistic_loss, logistic_slope


def margins(matrix, w):
    with jax.default_matmul_precision("highest"):
        return jnp.dot(matrix, w)


@functools.partial(
    jax.jit, static_argnames=("max_iter", "tol", "blocks", "corrections"))
def fit(matrix, labels, weights, w0, l2, max_iter, tol, blocks, corrections=10):
    """One L-BFGS solve from ``w0``. ``matrix`` is held in the storage type;
    ``weights`` is one per row (all ones in a sound run; the planted fault
    "half of the batch left out" zeroes every second)."""
    n, d = matrix.shape
    storage = matrix.dtype
    x = matrix.reshape(blocks, n // blocks, d)
    y = labels.reshape(blocks, -1)
    rw = weights.reshape(blocks, -1)

    def vg(w):
        w_s = w.astype(storage)

        def block(carry, part):
            value, error, grad = carry
            x_b, y_b, rw_b = part
            z = jnp.dot(x_b, w_s, preferred_element_type=jnp.float32)
            part_sum, part_error = sum_with_error(rw_b * logistic_loss(z, y_b))
            value, lost = two_sum(value, part_sum)
            error = error + (lost + part_error)
            slope = (rw_b * logistic_slope(z, y_b)).astype(storage)
            grad = grad + jnp.dot(slope, x_b, preferred_element_type=jnp.float32)
            return (value, error, grad), None

        zero = jnp.zeros((), jnp.float32)
        (value, error, grad), _ = lax.scan(
            block, (zero, zero, jnp.zeros((d,), jnp.float32)), (x, y, rw))
        return value + (error + 0.5 * l2 * jnp.dot(w, w)), grad + l2 * w

    # traced under the highest precision: the two products above and the
    # solver's own dot products
    with jax.default_matmul_precision("highest"):
        return lbfgs(vg, w0, max_iter, tol, corrections)


def fit_grid(matrix, labels, weights, l2_grid, max_iter, tol, blocks,
             corrections=10, storage=jnp.float32) -> List[Solve]:
    """The solves of the grid in the order they run: the L2 weights sorted
    high to low, the first from zero, each next one from the coefficients of
    the one before."""
    held = matrix.astype(storage)
    w = jnp.zeros((matrix.shape[1],), jnp.float32)
    solves = []
    for l2 in sorted(l2_grid, reverse=True):
        sol = fit(held, labels, weights, w, jnp.float32(l2), max_iter, tol,
                  blocks, corrections)
        w = sol.w
        solves.append(sol)
    return solves
