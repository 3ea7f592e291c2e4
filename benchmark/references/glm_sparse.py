"""Plain reference for the ``glm_sparse`` family: L2 logistic regression
over padded sparse rows, fitted by L-BFGS from zero.

Float32 ``jax.numpy``, nothing of the program under test imported. A row is
``K`` (index, value) pairs; its margin is the plain gather-and-sum
``sum_k w[index_k] * value_k`` and the gradient the plain scatter-add of
``value_k * slope`` into ``index_k``. Both run over blocks of rows so that
the temporaries stay small.

``storage`` is the type the values and the coefficients are held in for the
two products; ``bfloat16`` is the control. Sums stay float32, and the sum of
the rows' losses keeps its rounding errors (:func:`sum_with_error`): the value
is the float32 nearest the exact sum of the float32 losses, so that a gap
between the program's value and this one is the program's to explain. (Summed
plainly, one float32 total carried through the blocks, the first value
2^22 ln 2 came out 1.5e-6 low on the chip, nine times the program's own
error: PERF.md section 6, PR 28.)
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


@functools.partial(
    jax.jit, static_argnames=("dim", "max_iter", "tol", "blocks", "storage"))
def fit(indices, values, labels, weights, l2, dim, max_iter, tol, blocks,
        storage=jnp.float32):
    """L-BFGS from zero. ``weights`` is one per row (all ones in a sound run;
    the planted fault "half of the batch left out" zeroes every second)."""
    n, k = indices.shape
    idx = indices.reshape(blocks, n // blocks, k)
    val = values.astype(storage).reshape(blocks, n // blocks, k)
    y = labels.reshape(blocks, -1)
    rw = weights.reshape(blocks, -1)

    def vg(w):
        w_s = w.astype(storage)

        def block(carry, part):
            value, error, grad = carry
            i, v, y_b, rw_b = part
            z = jnp.sum((w_s[i] * v).astype(jnp.float32), axis=-1)
            part_sum, part_error = sum_with_error(rw_b * logistic_loss(z, y_b))
            value, lost = two_sum(value, part_sum)
            error = error + (lost + part_error)
            slope = (rw_b * logistic_slope(z, y_b)).astype(storage)
            grad = grad.at[i.reshape(-1)].add(
                (v * slope[:, None]).astype(jnp.float32).reshape(-1))
            return (value, error, grad), None

        zero = jnp.zeros((), jnp.float32)
        (value, error, grad), _ = lax.scan(
            block, (zero, zero, jnp.zeros((dim,), jnp.float32)),
            (idx, val, y, rw))
        return value + (error + 0.5 * l2 * jnp.dot(w, w)), grad + l2 * w

    return lbfgs(vg, jnp.zeros((dim,), jnp.float32), max_iter, tol)
