"""Plain reference for the ``glmix`` family: logistic GLMix (a fixed effect
plus one random effect per user) fitted by alternating L-BFGS solves.

Float32 ``jax.numpy`` at ``"highest"`` matmul precision, nothing of the
program under test imported and nothing it made taken: the inputs are the
generated rows (dense ``x`` with the intercept column, user of each row,
label) and the configuration's numbers.

What it follows, as the configuration states it:
  * every row trains the fixed effect; a user with more than
    ``active_upper_bound`` rows trains their own model on that many, drawn
    by giving every row a priority ``default_rng(reservoir_seed).random(N)``
    and keeping the user's smallest, each kept row weighted by
    rows / kept (the reference implementation's reservoir rescale);
  * one pass = solve the fixed effect on the per-user scores as offsets,
    rescore, solve every user on the fixed-effect scores as offsets,
    rescore; both solves start from the previous pass's coefficients;
  * objective after each solve = sum of logistic losses of the summed
    scores + L2/2 * (|fixed|^2 + |per-user|^2).

The per-user solve runs in blocks of users so that it fits beside nothing
else: the program's state is freed before this runs.

``storage`` is the type the features and the coefficients are held in for
the two products (margins and gradient); ``bfloat16`` is the control, the
precision a later change would be tempted by. Sums stay float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.references.lbfgs import lbfgs, logistic_loss, logistic_slope

HIGHEST = jax.lax.Precision.HIGHEST
USER_BLOCK = 4096


def active_rows(users: np.ndarray, n_users: int, cap: int, seed: int):
    """(row_of (n_users, M) int32 with -1 where a user has fewer rows,
    weight_scale (n_users,)) by the configuration's reservoir rule."""
    n = len(users)
    priority = np.random.default_rng(seed).random(n)
    order = np.lexsort((priority, users))
    u_sorted = users[order]
    first = np.searchsorted(u_sorted, np.arange(n_users), side="left")
    rank = np.arange(n) - first[u_sorted]
    counts = np.bincount(users, minlength=n_users)
    kept = np.minimum(counts, cap)
    m = max(int(kept.max()), 1)
    row_of = np.full((n_users, m), -1, np.int32)
    keep = rank < cap
    row_of[u_sorted[keep], rank[keep]] = order[keep]
    scale = np.where(counts > cap, counts / np.maximum(kept, 1), 1.0)
    return row_of, scale.astype(np.float32)


def _products(storage):
    """(margins, gradient) products with features and coefficients held in
    ``storage``, accumulated in float32."""
    def margins(x, w):
        return jnp.matmul(x.astype(storage), w.astype(storage),
                          precision=HIGHEST,
                          preferred_element_type=jnp.float32)

    def gradient(x, d):
        return jnp.matmul(d.astype(storage), x.astype(storage),
                          precision=HIGHEST,
                          preferred_element_type=jnp.float32)

    return margins, gradient


@functools.partial(jax.jit, static_argnames=("max_iter", "tol", "storage"))
def _solve_fixed(x, y, offsets, weights, w0, l2, max_iter, tol, storage):
    margins, gradient = _products(storage)

    def vg(w):
        z = margins(x, w) + offsets
        value = jnp.sum(weights * logistic_loss(z, y)) + 0.5 * l2 * jnp.dot(w, w)
        grad = gradient(x, weights * logistic_slope(z, y)) + l2 * w
        return value, grad

    return lbfgs(vg, w0, max_iter, tol)


@functools.partial(jax.jit, static_argnames=("max_iter", "tol", "storage"))
def _solve_users(x, y, weights, row_of, scale, offsets, w0, l2, max_iter, tol,
                 storage):
    """One block of users: gather their kept rows, solve each lane."""
    margins, gradient = _products(storage)
    safe = jnp.maximum(row_of, 0)
    real = row_of >= 0
    xb = x[safe]  # (B, M, D)
    yb = y[safe]
    ob = jnp.where(real, offsets[safe], 0.0)
    wb = jnp.where(real, scale[:, None] * weights[safe], 0.0)

    def one(x_u, y_u, o_u, w_u, w0_u):
        def vg(w):
            z = margins(x_u, w) + o_u
            value = jnp.sum(w_u * logistic_loss(z, y_u)) + 0.5 * l2 * jnp.dot(w, w)
            grad = gradient(x_u, w_u * logistic_slope(z, y_u)) + l2 * w
            return value, grad

        return lbfgs(vg, w0_u, max_iter, tol)

    return jax.vmap(one)(xb, yb, ob, wb, w0)


@jax.jit
def _user_scores(x, users, coefficients):
    return jnp.sum(x * coefficients[users], axis=-1)


@jax.jit
def _objective(total, y, weights, w_fixed, w_users, l2_fixed, l2_users):
    return (jnp.sum(weights * logistic_loss(total, y))
            + 0.5 * l2_fixed * jnp.sum(jnp.square(w_fixed))
            + 0.5 * l2_users * jnp.sum(jnp.square(w_users)))


def fit(rows: dict, sizes: dict, storage=jnp.float32,
        half_batch: bool = False) -> dict:
    """Follow the job. ``rows``: ``x (N, D)``, ``users (N,)``, ``labels (N,)``
    as NumPy. Returns what the program's job is compared on, under the same
    names, plus the counts of passes over the data that ``work`` needs.

    ``half_batch`` plants the fault "half of the batch left out, the mean
    taken over the rest": every second row gets weight 0 and the others 2.
    """
    n, dim = rows["x"].shape
    n_users = int(sizes["users"])
    fixed, per_user = sizes["fixed_effect"], sizes["per_user"]
    passes = int(sizes["passes"])

    x = jnp.asarray(rows["x"], jnp.float32)
    y = jnp.asarray(rows["labels"], jnp.float32)
    users = jnp.asarray(rows["users"], jnp.int32)
    row_weight = np.ones(n, np.float32)
    if half_batch:
        row_weight[1::2] = 0.0
        row_weight[0::2] = 2.0
    weights = jnp.asarray(row_weight)

    row_of, scale = active_rows(
        rows["users"], n_users, int(per_user["active_upper_bound"]),
        int(per_user["reservoir_seed"]),
    )
    block = min(USER_BLOCK, -(-n_users // 8) * 8)
    blocks = -(-n_users // block)
    pad = blocks * block - n_users
    row_of = np.concatenate([row_of, np.full((pad, row_of.shape[1]), -1, np.int32)])
    scale = np.concatenate([scale, np.ones(pad, np.float32)])

    w_fixed = jnp.zeros((dim,), jnp.float32)
    w_users = jnp.zeros((n_users + pad, dim), jnp.float32)
    s_fixed = jnp.zeros((n,), jnp.float32)
    s_users = jnp.zeros((n,), jnp.float32)
    objective, counts = [], []
    fixed_first_grad = user_value_sum = None

    for _ in range(passes):
        sol = _solve_fixed(
            x, y, s_users, weights, w_fixed, float(fixed["l2"]),
            int(fixed["max_iterations"]), float(fixed["tolerance"]), storage,
        )
        w_fixed = sol.w
        fixed_first_grad = sol.grad_norms[0]
        counts.append(("fixed", np.asarray(sol.iterations)[None], n))
        s_fixed = jnp.matmul(x, w_fixed, precision=HIGHEST)
        objective.append(_objective(
            s_fixed + s_users, y, weights, w_fixed, w_users[:n_users],
            float(fixed["l2"]), float(per_user["l2"])))

        solved, iters, values = [], [], []
        for b in range(blocks):
            cut = slice(b * block, (b + 1) * block)
            sol = _solve_users(
                x, y, weights, jnp.asarray(row_of[cut]), jnp.asarray(scale[cut]),
                s_fixed, w_users[cut], float(per_user["l2"]),
                int(per_user["max_iterations"]), float(per_user["tolerance"]),
                storage,
            )
            solved.append(sol.w)
            iters.append(np.asarray(sol.iterations))
            values.append(sol.value)
        w_users = jnp.concatenate(solved)
        user_value_sum = jnp.sum(jnp.concatenate(values)[:n_users])
        kept = (row_of[:n_users] >= 0).sum(axis=1)
        counts.append(("per_user", np.concatenate(iters)[:n_users], kept))
        s_users = _user_scores(x, users, w_users)
        objective.append(_objective(
            s_fixed + s_users, y, weights, w_fixed, w_users[:n_users],
            float(fixed["l2"]), float(per_user["l2"])))

    return {
        "objective": np.asarray(jnp.stack(objective), np.float64),
        "fixed": np.asarray(w_fixed),
        "per_user": np.asarray(w_users[:n_users]),
        "scores": np.asarray(s_fixed + s_users),
        "fixed_first_grad": float(fixed_first_grad),
        "user_value_sum": float(user_value_sum),
        "counts": counts,
    }
