"""TRON — trust-region Newton method — as a jitted ``lax.while_loop`` kernel.

Implements the standard trust-region Newton algorithm (Lin & Moré 1999, as
popularized by LIBLINEAR) that the reference also implements
(optimization/TRON.scala:78-316: truncated conjugate-gradient inner loop with
<= 20 CG iterations, trust-region update rules, <= 5 improvement-failure
retries, defaults 15 outer iterations / tol 1e-5). Re-derived here from the
published algorithm, branch-free and vmappable:

  * the inner Steihaug-CG solve is an inner ``while_loop`` where every CG
    step costs one Hessian-vector product — under data sharding that is one
    batched pass + one psum, the analogue of the reference's one
    treeAggregate per CG step (TRON.scala:268-281);
  * step acceptance / radius update are ``where``-selected, so converged
    or rejected lanes are no-ops under ``vmap``.

Requires a twice-differentiable objective: ``value_and_grad_fn(w)`` and
``hvp_fn(w, v)`` (L2 already folded in). TRON + L1 is rejected at config
validation, as in the reference (Params.scala:177-180).
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
# trust-region update constants (Lin & Moré / LIBLINEAR standard values)
_ETA0, _ETA1, _ETA2 = 1e-4, 0.25, 0.75
_SIGMA1, _SIGMA2, _SIGMA3 = 0.25, 0.5, 4.0
_CG_TOL = 0.1  # inner CG solves to ||r|| <= 0.1 * ||g||


@jax.named_scope("pml.tron.cg")
def _truncated_cg(hvp, g, delta, max_cg_iter, dtype):
    """Steihaug truncated CG: approximately solve H s = -g, ||s|| <= delta.

    Returns (s, r) with r the final residual (-g - H s), used for the
    predicted-reduction formula prered = -0.5 * (g.s - s.r).
    """
    dim = g.shape[0]
    gnorm = jnp.linalg.norm(g)

    class C(NamedTuple):
        s: Array
        r: Array
        d: Array
        rtr: Array
        i: Array
        done: Array

    c0 = C(
        s=jnp.zeros((dim,), dtype),
        r=-g,
        d=-g,
        rtr=jnp.dot(g, g),
        i=jnp.zeros((), jnp.int32),
        done=gnorm == 0.0,
    )

    def cond(c: C):
        return (~c.done) & (c.i < max_cg_iter)

    def body(c: C):
        hd = hvp(c.d)
        dhd = jnp.dot(c.d, hd)
        alpha = c.rtr / jnp.maximum(dhd, _EPS)
        s_try = c.s + alpha * c.d
        # negative curvature (non-convex lane) or step leaving the region:
        # walk to the boundary along d and stop.
        hit = (dhd <= 0.0) | (jnp.linalg.norm(s_try) >= delta)
        sd = jnp.dot(c.s, c.d)
        dd = jnp.maximum(jnp.dot(c.d, c.d), _EPS)
        ss = jnp.dot(c.s, c.s)
        rad = jnp.sqrt(jnp.maximum(sd * sd + dd * (delta * delta - ss), 0.0))
        tau = (-sd + rad) / dd
        s_new = jnp.where(hit, c.s + tau * c.d, s_try)
        r_new = c.r - jnp.where(hit, tau, alpha) * hd
        rtr_new = jnp.dot(r_new, r_new)
        small = jnp.sqrt(rtr_new) <= _CG_TOL * gnorm
        beta = rtr_new / jnp.maximum(c.rtr, _EPS)
        d_new = r_new + beta * c.d
        return C(s=s_new, r=r_new, d=d_new, rtr=rtr_new, i=c.i + 1, done=hit | small)

    cf = lax.while_loop(cond, body, c0)
    return cf.s, cf.r


class _State(NamedTuple):
    """Carried solve state. Self-contained for RESUMABILITY (the
    optim/scheduler.py chunk/compact/resume contract): the init-time
    reference scalars the convergence tests compare against (``f0``,
    ``g0_norm``) ride in the state, so a paused state survives a hop to a
    different compiled chunk kernel bit-exactly."""

    w: Array
    f: Array
    g: Array
    delta: Array
    iteration: Array
    failures: Array
    reason: Array
    value_history: Array
    grad_norm_history: Array
    w_history: Array  # (max_iter + 1, D) if tracking, else (1, 1) dummy
    f0: Array  # objective at w0 (function-convergence reference)
    g0_norm: Array  # initial reduced-gradient norm (gradient-tol reference)


@functools.partial(jax.jit, static_argnames=("value_and_grad_fn", "hvp_fn", "config"))
def tron_minimize(
    value_and_grad_fn: Callable[[Array], Tuple[Array, Array]],
    hvp_fn: Callable[[Array, Array], Array],
    w0: Array,
    config: OptimizerConfig = OptimizerConfig.tron_default(),
    bounds: Optional[Tuple[Array, Array]] = None,
) -> OptResult:
    return tron_minimize_(value_and_grad_fn, hvp_fn, w0, config, bounds)


def _reduced_grad_fn(bounds):
    def reduced_grad(w, g):
        """Gradient with bound-blocked components zeroed (a coordinate at an
        active bound whose descent direction points outward cannot move):
        steers the CG subproblem into the free subspace and keeps the
        convergence test honest at the constrained optimum."""
        if bounds is None:
            return g
        blocked = ((w >= bounds[1]) & (g < 0.0)) | ((w <= bounds[0]) & (g > 0.0))
        return jnp.where(blocked, 0.0, g)

    return reduced_grad


def tron_init_(
    value_and_grad_fn, w0, config: OptimizerConfig, bounds=None,
    track_coefficients: bool = False,
) -> _State:
    """Fresh resumable solve state at ``w0`` (one objective evaluation)."""
    dtype = w0.dtype
    max_iter = config.max_iterations
    reduced_grad = _reduced_grad_fn(bounds)

    if bounds is not None:
        w0 = jnp.clip(w0, bounds[0], bounds[1])
    f0, g0 = value_and_grad_fn(w0)
    g0_norm = jnp.linalg.norm(reduced_grad(w0, g0))
    hist0 = jnp.full((max_iter + 1,), jnp.nan, dtype)
    if track_coefficients:
        w_hist0 = jnp.zeros((max_iter + 1, w0.shape[0]), dtype).at[0].set(w0)
    else:
        w_hist0 = jnp.zeros((1, 1), dtype)
    return _State(
        w=w0,
        f=f0,
        g=g0,
        delta=g0_norm,
        iteration=jnp.zeros((), jnp.int32),
        failures=jnp.zeros((), jnp.int32),
        reason=jnp.where(g0_norm == 0.0, ConvergenceReason.GRADIENT_CONVERGED, 0).astype(
            jnp.int32
        ),
        value_history=hist0.at[0].set(f0),
        grad_norm_history=hist0.at[0].set(g0_norm),
        w_history=w_hist0,
        f0=f0,
        g0_norm=g0_norm,
    )


def tron_advance_(
    value_and_grad_fn, hvp_fn, state: _State, config: OptimizerConfig,
    bounds=None, iteration_limit=None, track_coefficients: bool = False,
) -> _State:
    """Run the trust-region loop from ``state`` until convergence or the
    ABSOLUTE ``iteration_limit`` (traced or static int; None =
    config.max_iterations). Chunked advances replay the one-shot iteration
    sequence bit-exactly (tests/test_scheduler.py pins it)."""
    dtype = state.w.dtype
    max_iter = config.max_iterations
    tol = config.tolerance
    limit = max_iter if iteration_limit is None else iteration_limit
    reduced_grad = _reduced_grad_fn(bounds)
    s0 = state

    def cond(s: _State):
        return (s.reason == 0) & (s.iteration < limit)

    def body(s: _State):
        step, r = _truncated_cg(
            lambda v: hvp_fn(s.w, v),
            reduced_grad(s.w, s.g),
            s.delta,
            config.max_cg_iterations,
            dtype,
        )

        # clip the trial point BEFORE evaluating so the carried (w, f, g)
        # stay consistent (the reference projects after evaluation,
        # TRON.scala:200-202; evaluating at the projected point is strictly
        # more correct for the trust-region accept/shrink decisions)
        w_trial = s.w + step
        if bounds is not None:
            w_trial = jnp.clip(w_trial, bounds[0], bounds[1])
            # the step actually taken is the clipped one: measure the
            # quadratic model (gs, prered) and the radius-update step length
            # on it, else improving clipped steps are judged against the
            # unclipped step's predicted reduction and rejected forever
            step = w_trial - s.w
            snorm = jnp.linalg.norm(step)
            gs = jnp.dot(s.g, step)
            prered = -(gs + 0.5 * jnp.dot(step, hvp_fn(s.w, step)))
        else:
            snorm = jnp.linalg.norm(step)
            gs = jnp.dot(s.g, step)
            # r = -g - H s  =>  -0.5*(gs - s.r) = -(g.s + 0.5 s.H.s)
            prered = -0.5 * (gs - jnp.dot(step, r))
        f_new, g_new = value_and_grad_fn(w_trial)
        actred = s.f - f_new

        # first iteration: shrink the initial radius to the first step length
        delta = jnp.where(s.iteration == 0, jnp.minimum(s.delta, snorm), s.delta)

        # radius update (interpolated step-length alpha, LIBLINEAR rules)
        denom = f_new - s.f - gs
        alpha = jnp.where(denom <= 0.0, _SIGMA3, jnp.maximum(_SIGMA1, -0.5 * (gs / denom)))
        asn = alpha * snorm
        delta = jnp.where(
            actred < _ETA0 * prered,
            jnp.minimum(jnp.maximum(asn, _SIGMA1 * snorm), _SIGMA2 * delta),
            jnp.where(
                actred < _ETA1 * prered,
                jnp.maximum(_SIGMA1 * delta, jnp.minimum(asn, _SIGMA2 * delta)),
                jnp.where(
                    actred < _ETA2 * prered,
                    jnp.maximum(_SIGMA1 * delta, jnp.minimum(asn, _SIGMA3 * delta)),
                    jnp.maximum(delta, jnp.minimum(asn, _SIGMA3 * delta)),
                ),
            ),
        )

        # divergence guard (resilience): never accept a non-finite trial
        # point — it counts as an improvement failure and the trust region
        # shrinks, so the solver retries from the last good iterate
        finite = (
            jnp.isfinite(f_new)
            & jnp.all(jnp.isfinite(w_trial))
            & jnp.all(jnp.isfinite(g_new))
        )
        accept = (actred > _ETA0 * prered) & finite
        w_out = jnp.where(accept, w_trial, s.w)
        f_out = jnp.where(accept, f_new, s.f)
        g_out = jnp.where(accept, g_new, s.g)
        failures = jnp.where(accept, 0, s.failures + 1).astype(jnp.int32)
        # a NaN objective poisons the interpolated radius formula; restore a
        # finite, shrunken radius so the retry is meaningful. snorm itself
        # is NaN when CG diverged — fall back to shrinking the previous
        # (finite by induction) radius in that case
        delta = jnp.where(
            jnp.isfinite(delta),
            delta,
            jnp.where(
                jnp.isfinite(snorm),
                jnp.maximum(_SIGMA1 * snorm, _EPS),
                jnp.maximum(_SIGMA1 * s.delta, _EPS),
            ),
        )

        g_norm = jnp.linalg.norm(reduced_grad(w_out, g_out))
        it = s.iteration + 1
        grad_ok = g_norm <= tol * jnp.maximum(s.g0_norm, _EPS)
        func_ok = accept & (jnp.abs(actred) <= tol * jnp.maximum(jnp.abs(s.f0), _EPS))
        reason = jnp.where(
            grad_ok,
            ConvergenceReason.GRADIENT_CONVERGED,
            jnp.where(
                failures >= config.max_improvement_failures,
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
            delta=delta,
            iteration=it,
            failures=failures,
            reason=reason,
            value_history=s.value_history.at[it].set(f_out),
            grad_norm_history=s.grad_norm_history.at[it].set(g_norm),
            w_history=(
                s.w_history.at[it].set(w_out) if track_coefficients else s.w_history
            ),
            f0=s.f0,
            g0_norm=s.g0_norm,
        )

    return lax.while_loop(cond, body, s0)


def tron_result(
    state: _State, bounds=None, track_coefficients: bool = False
) -> OptResult:
    """OptResult view of a (possibly paused) solve state. The final
    reduced-gradient norm reduces over the trailing coefficient axis, so a
    vmapped (lane-stacked) state works unchanged."""
    reduced_grad = _reduced_grad_fn(bounds)
    return OptResult(
        coefficients=state.w,
        value=state.f,
        grad_norm=jnp.linalg.norm(reduced_grad(state.w, state.g), axis=-1),
        iterations=state.iteration,
        reason=state.reason,
        value_history=state.value_history,
        grad_norm_history=state.grad_norm_history,
        coefficient_history=state.w_history if track_coefficients else None,
    )


def tron_minimize_(
    value_and_grad_fn, hvp_fn, w0, config: OptimizerConfig, bounds=None,
    track_coefficients: bool = False,
) -> OptResult:
    """Non-jitted one-shot body (callable from inside jit / vmap /
    shard_map): init + advance-to-convergence + result, the same loop the
    pre-resumable kernel ran (the body sets MAX_ITERATIONS at max_iter, so
    the static limit never changes which states are visited).

    ``track_coefficients`` carries per-iteration coefficient snapshots
    ((max_iter+1, D) extra memory — the ModelTracker analogue)."""
    state = tron_init_(value_and_grad_fn, w0, config, bounds, track_coefficients)
    final = tron_advance_(
        value_and_grad_fn, hvp_fn, state, config, bounds,
        iteration_limit=config.max_iterations,
        track_coefficients=track_coefficients,
    )
    return tron_result(final, bounds, track_coefficients)
