"""Plain L-BFGS for the benchmark's references: float32 ``jax.numpy``, no
import of the program under test.

Written from the published algorithm the configurations name (Nocedal's
two-loop recursion with ``m`` curvature pairs, the initial Hessian scaled by
``s.y / y.y`` of the newest pair, a backtracking Armijo line search with
``c1 = 1e-4`` that halves the step, and the reference implementation's
stopping rules: gradient norm relative to the first gradient, change of the
value relative to the first value, a failed line search, the iteration cap).
Where the publication leaves a choice open the configuration files state it
(``line_search``: first trial step ``1 / max(|d|, 1)`` while no curvature
pair is stored and 1 afterwards, at most 25 trials; a pair is stored only
when ``s.y > 1e-10``).

One lane: ``w`` is ``(D,)``. The per-user solve ``jax.vmap``s it.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

C1 = 1e-4
EPS = 1e-10

# why a solve stopped (the benchmark's own codes)
RUNNING, GRADIENT, LINE_SEARCH, VALUE, CAP = 0, 1, 2, 3, 4


class Solve(NamedTuple):
    w: jax.Array
    value: jax.Array
    grad_norm: jax.Array
    iterations: jax.Array
    evaluations: jax.Array  # value-and-gradient passes, line search included
    stopped: jax.Array
    values: jax.Array  # (max_iter + 1,) value after each iteration, NaN beyond
    grad_norms: jax.Array
    first_grad: jax.Array  # the gradient at ``w0``


def _direction(g, S, Y, rho, n_pairs, m):
    """-H g by the two-loop recursion; pairs sit in ring buffers."""
    have = jnp.minimum(n_pairs, m)

    def first(i, carry):
        q, alpha = carry
        at = jnp.mod(n_pairs - 1 - i, m)
        a = jnp.where(i < have, rho[at] * jnp.dot(S[at], q), 0.0)
        return q - a * Y[at], alpha.at[i].set(a)

    q, alpha = lax.fori_loop(0, m, first, (g, jnp.zeros((m,), g.dtype)))
    newest = jnp.mod(n_pairs - 1, m)
    scale = jnp.where(
        n_pairs > 0,
        jnp.dot(S[newest], Y[newest])
        / jnp.maximum(jnp.dot(Y[newest], Y[newest]), EPS),
        1.0,
    )
    r = scale * q

    def second(j, r):
        i = m - 1 - j
        at = jnp.mod(n_pairs - 1 - i, m)
        b = rho[at] * jnp.dot(Y[at], r)
        return r + jnp.where(i < have, alpha[i] - b, 0.0) * S[at]

    return -lax.fori_loop(0, m, second, r)


def lbfgs(value_and_grad, w0, max_iterations: int, tolerance: float,
          corrections: int = 10, line_search_trials: int = 25) -> Solve:
    """Minimise a smooth function from ``w0``."""
    m = corrections
    dim = w0.shape[0]
    dtype = w0.dtype
    f0, g0 = value_and_grad(w0)
    g0_norm = jnp.linalg.norm(g0)
    nan_hist = jnp.full((max_iterations + 1,), jnp.nan, dtype)

    state = dict(
        w=w0, f=f0, g=g0, g_norm=g0_norm,
        S=jnp.zeros((m, dim), dtype), Y=jnp.zeros((m, dim), dtype),
        rho=jnp.zeros((m,), dtype), n_pairs=jnp.zeros((), jnp.int32),
        it=jnp.zeros((), jnp.int32), evals=jnp.ones((), jnp.int32),
        stopped=jnp.where(g0_norm == 0.0, GRADIENT, RUNNING).astype(jnp.int32),
        values=nan_hist.at[0].set(f0), grad_norms=nan_hist.at[0].set(g0_norm),
    )

    def keep_going(s):
        return (s["stopped"] == RUNNING) & (s["it"] < max_iterations)

    def iterate(s):
        w, f, g = s["w"], s["f"], s["g"]
        d = _direction(g, s["S"], s["Y"], s["rho"], s["n_pairs"], m)
        slope = jnp.dot(g, d)
        uphill = slope >= 0.0
        d = jnp.where(uphill, -g, d)
        t0 = jnp.where(
            s["n_pairs"] == 0, 1.0 / jnp.maximum(jnp.linalg.norm(d), 1.0), 1.0
        ).astype(dtype)

        def searching(c):
            return (~c[5]) & (c[4] < line_search_trials)

        def trial(c):
            t, _, _, _, n, _ = c
            w_t = w + t * d
            f_t, g_t = value_and_grad(w_t)
            ok = f_t <= f + C1 * jnp.dot(g, w_t - w)
            return (jnp.where(ok, t, 0.5 * t), w_t, f_t, g_t, n + 1, ok)

        _, w_t, f_t, g_t, n_trials, ok = lax.while_loop(
            searching, trial,
            (t0, w, f, g, jnp.zeros((), jnp.int32), jnp.zeros((), bool)),
        )
        ok = ok & jnp.isfinite(f_t) & jnp.all(jnp.isfinite(w_t)) \
            & jnp.all(jnp.isfinite(g_t))

        step, bend = w_t - w, g_t - g
        sy = jnp.dot(step, bend)
        store = ok & (sy > EPS)
        at = jnp.mod(s["n_pairs"], m)
        S = jnp.where(store, s["S"].at[at].set(step), s["S"])
        Y = jnp.where(store, s["Y"].at[at].set(bend), s["Y"])
        rho = jnp.where(
            store, s["rho"].at[at].set(1.0 / jnp.maximum(sy, EPS)), s["rho"]
        )

        w_n = jnp.where(ok, w_t, w)
        f_n = jnp.where(ok, f_t, f)
        g_n = jnp.where(ok, g_t, g)
        g_norm = jnp.linalg.norm(g_n)
        it = s["it"] + 1
        stopped = jnp.where(
            g_norm <= tolerance * jnp.maximum(g0_norm, EPS), GRADIENT,
            jnp.where(
                ~ok, LINE_SEARCH,
                jnp.where(
                    jnp.abs(f - f_n) <= tolerance * jnp.maximum(jnp.abs(f0), EPS),
                    VALUE,
                    jnp.where(it >= max_iterations, CAP, RUNNING),
                ),
            ),
        ).astype(jnp.int32)
        return dict(
            w=w_n, f=f_n, g=g_n, g_norm=g_norm, S=S, Y=Y, rho=rho,
            n_pairs=jnp.where(store, s["n_pairs"] + 1, s["n_pairs"]),
            it=it, evals=s["evals"] + n_trials, stopped=stopped,
            values=s["values"].at[it].set(f_n),
            grad_norms=s["grad_norms"].at[it].set(g_norm),
        )

    s = lax.while_loop(keep_going, iterate, state)
    return Solve(s["w"], s["f"], s["g_norm"], s["it"], s["evals"],
                 s["stopped"], s["values"], s["grad_norms"], g0)


def logistic_loss(z, y):
    """log(1 + e^z) - y z for y in {0, 1}, in the overflow-safe form."""
    return jnp.maximum(z, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(z))) - y * z


def logistic_slope(z, y):
    return jax.nn.sigmoid(z) - y
