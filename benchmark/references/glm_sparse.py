"""Plain reference for the ``glm_sparse`` family: L2 logistic regression
over padded sparse rows, fitted by L-BFGS from zero.

Float32 ``jax.numpy``, nothing of the program under test imported. A row is
``K`` (index, value) pairs; its margin is the plain gather-and-sum
``sum_k w[index_k] * value_k`` and the gradient the plain scatter-add of
``value_k * slope`` into ``index_k``. Both run over blocks of rows so that
the temporaries stay small.

``storage`` is the type the values and the coefficients are held in for the
two products; ``bfloat16`` is the control. Sums stay float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.references.lbfgs import lbfgs, logistic_loss, logistic_slope


def margins(indices, values, w):
    return jnp.sum(w[indices] * values, axis=-1)


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
            value, grad = carry
            i, v, y_b, rw_b = part
            z = jnp.sum((w_s[i] * v).astype(jnp.float32), axis=-1)
            value = value + jnp.sum(rw_b * logistic_loss(z, y_b))
            slope = (rw_b * logistic_slope(z, y_b)).astype(storage)
            grad = grad.at[i.reshape(-1)].add(
                (v * slope[:, None]).astype(jnp.float32).reshape(-1))
            return (value, grad), None

        (value, grad), _ = lax.scan(
            block, (jnp.zeros((), jnp.float32), jnp.zeros((dim,), jnp.float32)),
            (idx, val, y, rw))
        return value + 0.5 * l2 * jnp.dot(w, w), grad + l2 * w

    return lbfgs(vg, jnp.zeros((dim,), jnp.float32), max_iter, tol)
