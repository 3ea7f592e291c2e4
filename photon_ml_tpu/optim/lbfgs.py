"""LBFGS / OWL-QN as a single jitted ``lax.while_loop`` kernel.

The reference wraps Breeze's iterator-object LBFGS/OWLQN
(optimization/LBFGS.scala:41-140: OWL-QN chosen when the objective carries an
L1 term, defaults m=10 / 80 iters / tol 1e-7). Here the whole solve — limited
-memory two-loop recursion, backtracking line search, orthant-wise L1
machinery — is one XLA computation with fixed-shape carried state:

  * history pairs (S, Y, rho) live in ``(m, D)`` ring buffers;
  * the line search is an inner ``while_loop``;
  * L1 is handled orthant-wise (pseudo-gradient + orthant projection),
    enabled smoothly by ``l1_weight > 0`` so the same compiled kernel serves
    both LBFGS and OWL-QN and a lambda grid never recompiles;
  * everything is branch-free (``where``/masks), so the kernel ``vmap``s
    over thousands of per-entity problems in the GAME random-effect path.

The smooth objective is supplied as ``value_and_grad_fn(w) -> (f, g)``; L2
regularization should already be folded into it.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from photon_ml_tpu.optim.common import OptimizerConfig, OptResult
from photon_ml_tpu.types import ConvergenceReason

Array = jax.Array

_EPS = 1e-10
_C1 = 1e-4  # Armijo sufficient-decrease constant


def _pseudo_gradient(w: Array, g: Array, l1: Array) -> Array:
    """OWL-QN pseudo-gradient of f(w) + l1*||w||_1 (= g when l1 == 0)."""
    at_zero = jnp.where(g > l1, g - l1, jnp.where(g < -l1, g + l1, 0.0))
    return jnp.where(w != 0.0, g + l1 * jnp.sign(w), at_zero)


@jax.named_scope("pml.lbfgs.direction")
def _two_loop_direction(pg, S, Y, rho, k, m):
    """Limited-memory two-loop recursion over ring buffers (newest-first)."""
    n_valid = jnp.minimum(k, m)

    def fwd(j, carry):
        q, alphas = carry
        pos = jnp.mod(k - 1 - j, m)
        valid = j < n_valid
        a = jnp.where(valid, rho[pos] * jnp.dot(S[pos], q), 0.0)
        return q - a * Y[pos], alphas.at[j].set(a)

    q, alphas = lax.fori_loop(0, m, fwd, (pg, jnp.zeros((m,), pg.dtype)))

    newest = jnp.mod(k - 1, m)
    sy = jnp.dot(S[newest], Y[newest])
    yy = jnp.dot(Y[newest], Y[newest])
    gamma = jnp.where(k > 0, sy / jnp.maximum(yy, _EPS), 1.0)
    r = gamma * q

    def bwd(j2, r):
        j = m - 1 - j2
        pos = jnp.mod(k - 1 - j, m)
        valid = j < n_valid
        b = rho[pos] * jnp.dot(Y[pos], r)
        return r + jnp.where(valid, alphas[j] - b, 0.0) * S[pos]

    r = lax.fori_loop(0, m, bwd, r)
    return -r


class _State(NamedTuple):
    """Carried solve state. Self-contained for RESUMABILITY: the two
    reference scalars the convergence tests compare against (``F0``,
    ``pg0_norm``, fixed at init) ride in the state instead of living as
    Python-closure constants, so a paused state can be handed to a
    different compiled chunk kernel (or gathered into a compacted batch by
    optim/scheduler.py) and resumed bit-exactly.

    ``evaluations`` counts the objective evaluations this problem made: 1
    at init, then each line-search trial of each iteration it ran;
    ``evaluation_history[i]`` holds iteration ``i``'s trials (entry 0 the
    initial evaluation, entries past ``iteration`` 0). Both count the
    problem's OWN work, also under ``vmap``: the batched outer loop keeps a
    lane's carry only while that lane's own predicate holds, so the line
    searches a stopped lane's body still runs (while other lanes go on)
    are discarded with the rest of that body and never counted."""

    w: Array
    f: Array  # smooth value
    g: Array  # smooth gradient
    F: Array  # f + l1*||w||_1
    pg_norm: Array
    S: Array
    Y: Array
    rho: Array
    k: Array  # number of curvature pairs ever stored
    iteration: Array
    reason: Array
    value_history: Array
    grad_norm_history: Array
    w_history: Array  # (max_iter + 1, D) if tracking, else (1, 1) dummy
    F0: Array  # objective at w0 (function-convergence reference)
    pg0_norm: Array  # initial pseudo-gradient norm (gradient-tol reference)
    evaluations: Array  # () int32 objective evaluations made
    evaluation_history: Array  # (max_iter + 1,) int32 evaluations an iteration


@functools.partial(jax.jit, static_argnames=("value_and_grad_fn", "config"))
def lbfgs_minimize(
    value_and_grad_fn: Callable[[Array], Tuple[Array, Array]],
    w0: Array,
    config: OptimizerConfig = OptimizerConfig.lbfgs_default(),
    l1_weight: Array | float = 0.0,
    bounds: Optional[Tuple[Array, Array]] = None,
) -> OptResult:
    """Minimize f(w) + l1_weight * ||w||_1.

    ``value_and_grad_fn`` must be a pure jax function of ``w`` alone
    (close over data, or partially apply before calling). For a traced/
    data-dependent objective, use :func:`lbfgs_minimize_` below.
    """
    return lbfgs_minimize_(value_and_grad_fn, w0, config, l1_weight, bounds)


def _problem_fns(l1, bounds):
    """(F_of, reduced_pg) closures shared by init and advance."""

    def F_of(w, f):
        return f + l1 * jnp.sum(jnp.abs(w))  # lint: bitwise-reduction — l1 reg over the fixed (D,) w, not a slab batch axis

    def reduced_pg(w, g):
        """(Pseudo-)gradient with bound-blocked components zeroed: at an
        active bound whose descent direction (-pg) points outward, the
        coordinate cannot move, so it must not steer the direction or the
        convergence test (standard gradient-projection reduction)."""
        pg = _pseudo_gradient(w, g, l1)
        if bounds is not None:
            blocked = ((w >= bounds[1]) & (pg < 0.0)) | ((w <= bounds[0]) & (pg > 0.0))
            pg = jnp.where(blocked, 0.0, pg)
        return pg

    return F_of, reduced_pg


def lbfgs_init_(
    value_and_grad_fn,
    w0: Array,
    config: OptimizerConfig,
    l1_weight: Array | float = 0.0,
    bounds: Optional[Tuple[Array, Array]] = None,
    track_coefficients: bool = False,
) -> _State:
    """Fresh resumable solve state at ``w0`` (one objective evaluation)."""
    m = config.num_corrections
    max_iter = config.max_iterations
    dtype = w0.dtype
    dim = w0.shape[0]
    l1 = jnp.asarray(l1_weight, dtype)
    F_of, reduced_pg = _problem_fns(l1, bounds)

    if bounds is not None:
        w0 = jnp.clip(w0, bounds[0], bounds[1])
    f0, g0 = value_and_grad_fn(w0)
    F0 = F_of(w0, f0)
    pg0 = reduced_pg(w0, g0)
    pg0_norm = jnp.linalg.norm(pg0)

    hist0 = jnp.full((max_iter + 1,), jnp.nan, dtype)
    if track_coefficients:
        w_hist0 = jnp.zeros((max_iter + 1, dim), dtype).at[0].set(w0)
    else:
        w_hist0 = jnp.zeros((1, 1), dtype)
    return _State(
        w=w0,
        f=f0,
        g=g0,
        F=F0,
        pg_norm=pg0_norm,
        S=jnp.zeros((m, dim), dtype),
        Y=jnp.zeros((m, dim), dtype),
        rho=jnp.zeros((m,), dtype),
        k=jnp.zeros((), jnp.int32),
        iteration=jnp.zeros((), jnp.int32),
        reason=jnp.where(pg0_norm == 0.0, ConvergenceReason.GRADIENT_CONVERGED, 0).astype(
            jnp.int32
        ),
        value_history=hist0.at[0].set(F0),
        grad_norm_history=hist0.at[0].set(pg0_norm),
        w_history=w_hist0,
        F0=F0,
        pg0_norm=pg0_norm,
        evaluations=jnp.ones((), jnp.int32),
        evaluation_history=jnp.zeros((max_iter + 1,), jnp.int32).at[0].set(1),
    )


def lbfgs_advance_(
    value_and_grad_fn,
    state: _State,
    config: OptimizerConfig,
    l1_weight: Array | float = 0.0,
    bounds: Optional[Tuple[Array, Array]] = None,
    iteration_limit=None,
    track_coefficients: bool = False,
) -> _State:
    """Run the while_loop from ``state`` until convergence or the ABSOLUTE
    ``iteration_limit`` (traced or static int; None = config.max_iterations).
    Per-lane trajectories are deterministic functions of the carried state,
    so advancing in chunks of K iterations and re-feeding the paused state —
    including through a scheduler's gather/compact/scatter — replays exactly
    the one-shot iteration sequence: bitwise-equal results (pinned by
    tests/test_scheduler.py)."""
    max_iter = config.max_iterations
    tol = config.tolerance
    dtype = state.w.dtype
    l1 = jnp.asarray(l1_weight, dtype)
    limit = max_iter if iteration_limit is None else iteration_limit
    F_of, reduced_pg = _problem_fns(l1, bounds)

    m = config.num_corrections

    def orthant_project(w_trial, xi):
        # project onto the orthant xi; identity when no L1
        projected = jnp.where(w_trial * xi > 0.0, w_trial, 0.0)
        w_trial = jnp.where(l1 > 0.0, projected, w_trial)
        # box-constraint projection after each step (LBFGS.scala:94-97 via
        # OptimizationUtils.projectCoefficientsToHypercube). Caveat: combined
        # with L1 and a box that excludes 0, the clip can move an
        # orthant-zeroed coordinate onto a nonzero bound — the reference has
        # the same post-hoc-projection semantics (OWL-QN cannot honor boxes
        # that exclude the origin); prefer L2 or pure bounds in that regime.
        if bounds is not None:
            w_trial = jnp.clip(w_trial, bounds[0], bounds[1])
        return w_trial

    def cond(s: _State):
        return (s.reason == 0) & (s.iteration < limit)

    def body(s: _State):
        pg = reduced_pg(s.w, s.g)
        d = _two_loop_direction(pg, s.S, s.Y, s.rho, s.k, m)
        # OWL-QN: constrain direction to the descent orthant of -pg
        d = jnp.where(l1 > 0.0, jnp.where(d * pg < 0.0, d, 0.0), d)
        deriv = jnp.dot(pg, d)
        # safeguard: fall back to steepest descent if not a descent direction
        bad = deriv >= 0.0
        d = jnp.where(bad, -pg, d)
        deriv = jnp.where(bad, -s.pg_norm**2, deriv)

        xi = jnp.where(s.w != 0.0, jnp.sign(s.w), jnp.sign(-pg))
        d_norm = jnp.linalg.norm(d)
        t0 = jnp.where(s.k == 0, 1.0 / jnp.maximum(d_norm, 1.0), 1.0).astype(dtype)

        # ---- backtracking Armijo line search (inner while_loop) ----------
        def ls_cond(c):
            t, w_n, f_n, g_n, F_n, steps, ok = c
            return (~ok) & (steps < config.max_line_search_steps)

        def ls_body(c):
            t, w_n, f_n, g_n, F_n, steps, ok = c
            w_t = orthant_project(s.w + t * d, xi)
            f_t, g_t = value_and_grad_fn(w_t)
            F_t = F_of(w_t, f_t)
            # Armijo on the step ACTUALLY taken (pg . (w_t - w)): identical to
            # _C1*t*deriv when nothing is projected, but correct when the
            # orthant/box projection removes part of the direction — the
            # OWL-QN sufficient-decrease form, also right for bounds.
            ok_t = F_t <= s.F + _C1 * jnp.dot(pg, w_t - s.w)
            t_next = jnp.where(ok_t, t, t * 0.5)
            return (t_next, w_t, f_t, g_t, F_t, steps + 1, ok_t)

        init = (t0, s.w, s.f, s.g, s.F, jnp.zeros((), jnp.int32), jnp.zeros((), bool))
        with jax.named_scope("pml.lbfgs.line_search"):
            t, w_new, f_new, g_new, F_new, trials, ls_ok = lax.while_loop(
                ls_cond, ls_body, init
            )

        # divergence guard (resilience): a trial point with a non-finite
        # value, gradient, or coefficient vector is rejected exactly like a
        # failed line search — the carried state stays at the last good
        # iterate instead of poisoning the curvature history (branch-free,
        # so the guard also protects every vmapped per-entity lane)
        finite = (
            jnp.isfinite(F_new)
            & jnp.all(jnp.isfinite(w_new))
            & jnp.all(jnp.isfinite(g_new))
        )
        ls_ok = ls_ok & finite

        # ---- curvature pair update --------------------------------------
        with jax.named_scope("pml.lbfgs.pair_update"):
            sv = w_new - s.w
            yv = g_new - s.g
            sy = jnp.dot(sv, yv)
            store = ls_ok & (sy > _EPS)
            pos = jnp.mod(s.k, m)
            S = jnp.where(store, s.S.at[pos].set(sv), s.S)
            Y = jnp.where(store, s.Y.at[pos].set(yv), s.Y)
            rho = jnp.where(
                store, s.rho.at[pos].set(1.0 / jnp.maximum(sy, _EPS)), s.rho
            )
            k = jnp.where(store, s.k + 1, s.k)

        w_out = jnp.where(ls_ok, w_new, s.w)
        f_out = jnp.where(ls_ok, f_new, s.f)
        g_out = jnp.where(ls_ok, g_new, s.g)
        F_out = jnp.where(ls_ok, F_new, s.F)

        pg_new = reduced_pg(w_out, g_out)
        pg_norm = jnp.linalg.norm(pg_new)
        it = s.iteration + 1

        grad_ok = pg_norm <= tol * jnp.maximum(s.pg0_norm, _EPS)
        func_ok = jnp.abs(s.F - F_out) <= tol * jnp.maximum(jnp.abs(s.F0), _EPS)
        reason = jnp.where(
            grad_ok,
            ConvergenceReason.GRADIENT_CONVERGED,
            jnp.where(
                ~ls_ok,
                ConvergenceReason.OBJECTIVE_NOT_IMPROVING,
                jnp.where(
                    func_ok,
                    ConvergenceReason.FUNCTION_VALUES_CONVERGED,
                    jnp.where(it >= max_iter, ConvergenceReason.MAX_ITERATIONS, 0),
                ),
            ),
        ).astype(jnp.int32)

        return _State(
            w=w_out,
            f=f_out,
            g=g_out,
            F=F_out,
            pg_norm=pg_norm,
            S=S,
            Y=Y,
            rho=rho,
            k=k,
            iteration=it,
            reason=reason,
            value_history=s.value_history.at[it].set(F_out),
            grad_norm_history=s.grad_norm_history.at[it].set(pg_norm),
            w_history=(
                s.w_history.at[it].set(w_out) if track_coefficients else s.w_history
            ),
            F0=s.F0,
            pg0_norm=s.pg0_norm,
            evaluations=s.evaluations + trials,
            # a select, not a scatter: under vmap each lane writes its own
            # entry, which the chip does in one elementwise pass
            evaluation_history=jnp.where(
                jnp.arange(s.evaluation_history.shape[0]) == it,
                trials, s.evaluation_history,
            ),
        )

    return lax.while_loop(cond, body, state)


def lbfgs_result(state: _State, track_coefficients: bool = False) -> OptResult:
    """OptResult view of a (possibly paused) solve state. Works unchanged on
    a vmapped state (every field gains the leading lane axis)."""
    return OptResult(
        coefficients=state.w,
        value=state.F,
        grad_norm=state.pg_norm,
        iterations=state.iteration,
        reason=state.reason,
        value_history=state.value_history,
        grad_norm_history=state.grad_norm_history,
        coefficient_history=state.w_history if track_coefficients else None,
        evaluations=state.evaluations,
        evaluation_history=state.evaluation_history,
    )


def lbfgs_minimize_(
    value_and_grad_fn,
    w0: Array,
    config: OptimizerConfig,
    l1_weight: Array | float = 0.0,
    bounds: Optional[Tuple[Array, Array]] = None,
    track_coefficients: bool = False,
) -> OptResult:
    """Non-jitted one-shot body (callable from inside other jitted code /
    vmap): init + advance-to-convergence + result, the same while_loop the
    pre-resumable kernel ran (the body sets MAX_ITERATIONS at max_iter, so
    the static limit below never changes which states are visited).

    ``track_coefficients`` carries per-iteration coefficient snapshots
    through the while_loop ((max_iter+1, D) extra memory — the ModelTracker
    analogue for validate-per-iteration)."""
    state = lbfgs_init_(
        value_and_grad_fn, w0, config, l1_weight, bounds, track_coefficients
    )
    final = lbfgs_advance_(
        value_and_grad_fn, state, config, l1_weight, bounds,
        iteration_limit=config.max_iterations,
        track_coefficients=track_coefficients,
    )
    return lbfgs_result(final, track_coefficients)
