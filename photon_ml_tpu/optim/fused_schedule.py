"""On-device whole-cycle compaction: the chunk→compact→resume loop in XLA.

The host scheduler (optim/scheduler.py) wins 36-71% of lane-iterations but
pays one host round-trip per chunk — the dispatch tariff
``compile/cost.py`` prices at 150 lane-iterations — and that host re-entry
is the sole reason the ``--fused-cycle`` x {compaction, streaming} fences
existed in ``compile/plan.py``. The Julia-to-TPU result (PAPERS.md) is
that whole programs INCLUDING control flow compile to XLA; this module
applies it to the compaction cycle itself:

  * one jitted **rung program** per ladder width R carries the FULL
    entity-order solver state through a ``lax.while_loop``; every loop
    body re-compacts in-program — a stable ``argsort`` of the converged
    flags puts active lanes first (ascending entity index, exactly the
    host loop's ``np.nonzero`` order), a static ``[:R]`` slice +
    ``jnp.take`` gathers their problem data and carried state, the
    resumable vmapped kernel advances them one chunk, and a ``.at[idx]
    .set`` scatter lands them back in entity order. The R gathered
    indices are always distinct (a slice of a permutation), and gathered
    CONVERGED filler lanes advance as the identity (the kernel's
    ``reason != 0`` mask), so the scatter is bitwise-safe with no pad
    bookkeeping at all.
  * the while_loop exits when the active count drops to the NEXT ladder
    rung (or the horizon drains); the host then re-dispatches at the
    smaller width. Rung widths strictly decrease across hops, so host
    dispatches per solve are O(#rungs) ~ log(E), not O(max_iter/chunk).
  * the ledger stays device-resident: executed lane-iterations and the
    in-program chunk count ride the while_loop carry as scalars, pulled
    (with the active count) once per hop — the only D2H traffic between
    dispatches. The full state is pulled exactly once, post-solve.

Per-lane trajectories are branch-free and batch-independent (the PR 4
contract tests/test_scheduler.py pins), so re-batching changes WHICH
lanes burn device iterations but never any lane's arithmetic: the device
loop is bitwise-equal to the host chunk loop and to the one-shot kernel
(tests/test_fused_schedule.py pins all three for LBFGS and TRON).

Preemption (resilience/preemption.py) keeps a safe boundary at RUNG
granularity: while a request is pending, the next rung program's horizon
is bounded at the drain horizon (one more chunk), and the ``"rung"``
preempt site raises :class:`~photon_ml_tpu.resilience.preemption.
Preempted` carrying the same ``kind="scheduler"`` snapshot the host loop
emits — a device-loop snapshot resumes on either loop, bitwise.

Selection: ``SolveSchedule(loop="device")`` — spelled ``--solve-compaction
device[:CHUNK]`` or ``PHOTON_SOLVE_CHUNK=device[:CHUNK]`` via
``compile/overrides.py``; default stays the host loop, bitwise. The
``optim.device_drain`` fault site (resilience/sites.py) guards the
dispatch: an injected fault degrades the solve to the host chunk loop
(results stay bitwise), recorded in the log; a real compile or runtime
error of the fused program raises.
"""

from __future__ import annotations

import logging
import math
from typing import List, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from photon_ml_tpu.compile import instrumented_jit
from photon_ml_tpu.optim.common import OptResult
from photon_ml_tpu.resilience import preemption
from photon_ml_tpu.utils import profiling

logger = logging.getLogger(__name__)

__all__ = ["device_solve", "rung_ladder", "next_lower_rung"]

_RUNG_JIT = None


def rung_ladder(bucketer, lanes: int) -> List[int]:
    """The descending dispatch widths a ``lanes``-wide solve can visit:
    the full width first, then every ladder rung strictly below it. The
    hop loop only ever moves DOWN this list, which is the O(#rungs)
    dispatch bound."""
    rungs = []
    size = bucketer.base
    while size < lanes:
        rungs.append(size)
        size = max(int(math.ceil(size * bucketer.growth)), size + 1)
    return [lanes] + rungs[::-1]


def next_lower_rung(bucketer, rung: int) -> int:
    """The largest ladder value strictly below ``rung`` (0 below the
    base) — the active-count target at which a rung program exits and
    hands the solve to the next-smaller width."""
    if rung <= bucketer.base:
        return 0
    prev = 0
    size = bucketer.base
    while size < rung:
        prev = size
        size = max(int(math.ceil(size * bucketer.growth)), size + 1)
    return prev


def _rung_step(data, state, limit, horizon, target, chunk, *, rung, **cfg):
    """One fused rung dispatch: while_loop the chunk→compact→resume cycle
    at width ``rung`` until the active count drops to ``target`` or the
    iteration ``limit`` reaches ``horizon``. Returns the advanced full
    state plus the hop scalars (limit, executed delta, in-program chunk
    count, active count) — the only values the host pulls between hops."""
    global _RUNG_JIT
    if _RUNG_JIT is None:
        from photon_ml_tpu.compile import donation_enabled
        from photon_ml_tpu.optim.scheduler import _STATICS, _lane_fns

        def impl(data, state, limit, horizon, target, chunk, rung, **cfg):
            _, _, advance_one, _ = _lane_fns(**cfg)

            def n_active_of(st):
                return jnp.sum((st.reason == 0).astype(jnp.int32))  # lint: bitwise-reduction — int32 flag count; integer addition is exact in any order

            def cond(carry):
                st, lim, _, _ = carry
                return (n_active_of(st) > target) & (lim < horizon)

            def body(carry):
                st, lim, executed, dchunks = carry
                # in-program compaction: actives first, each group in
                # ascending entity index — the host loop's np.nonzero
                # order, so the gathered batch is the same one the host
                # loop would have built on this rung
                inactive = (st.reason != 0).astype(jnp.int32)
                order = jnp.argsort(inactive, stable=True)
                idx = order[:rung]  # static slice: shapes stay fixed
                take = lambda a: jnp.take(a, idx, axis=0)
                data_r = jax.tree.map(take, data)
                st_r = jax.tree.map(take, st)
                new_lim = jnp.minimum(lim + chunk, horizon)
                st_r = jax.vmap(
                    advance_one, in_axes=(0, 0, 0, 0, 0, None)
                )(*data_r, st_r, new_lim)
                # idx holds rung DISTINCT entity indices; converged
                # fillers advanced as the identity, so scattering every
                # lane back at its own index is exact
                st = jax.tree.map(
                    lambda f, p: f.at[idx].set(p), st, st_r
                )
                advanced = jnp.maximum(
                    jnp.minimum(jnp.max(st_r.iteration), new_lim) - lim, 0
                )
                return (
                    st, new_lim,
                    executed + jnp.int32(rung) * advanced.astype(jnp.int32),
                    dchunks + jnp.int32(1),
                )

            zero = jnp.int32(0)
            st, lim, executed, dchunks = lax.while_loop(
                cond, body, (state, limit, zero, zero)
            )
            return st, lim, executed, dchunks, n_active_of(st)

        _RUNG_JIT = instrumented_jit(
            impl,
            site="scheduler.rung",
            static_argnames=_STATICS + ("rung",),
            # the pre-hop state is dead once advanced — update in place
            donate_argnums=(1,) if donation_enabled() else (),
        )
    return _RUNG_JIT(data, state, limit, horizon, target, chunk,
                     rung=rung, **cfg)


def device_solve(
    data,
    w0,
    *,
    task,
    optimizer,
    optimizer_config,
    regularization,
    schedule,
    label: str = "re_solve",
    resume: Optional[dict] = None,
) -> OptResult:
    """Solve every lane of ``data`` with the fused on-device
    chunk→compact→resume loop; bitwise-equal to
    :func:`photon_ml_tpu.optim.scheduler.compacted_solve` on the host
    loop and to ``vmap(solve_one)``. Telemetry lands in the same
    :data:`~photon_ml_tpu.optim.scheduler.solve_stats` registry: one
    :class:`ChunkRecord` per RUNG HOP (each hop is one host dispatch),
    with the in-program chunk count carried on
    ``SolveRecord.device_chunks``."""
    from photon_ml_tpu.optim.scheduler import (
        ChunkRecord,
        SolveRecord,
        _init_batch,
        _lane_fns,
        _restore_state,
        _snapshot_state,
        solve_stats,
    )

    cfg = dict(
        task=task,
        optimizer=optimizer,
        optimizer_config=optimizer_config,
        regularization=regularization,
    )
    lanes = int(w0.shape[0])
    max_iter = optimizer_config.max_iterations
    chunk = schedule.chunk_size
    bucketer = schedule.bucketer

    _, _, _, result_of = _lane_fns(**cfg)

    state = _init_batch(data, w0, **cfg)
    chunks: List[ChunkRecord] = []
    executed = 0
    device_chunks = 0
    limit = 0
    active = lanes
    if resume is not None:
        # same kind="scheduler" snapshot as the host loop: a preempted
        # device solve resumes on either loop, bitwise
        state = _restore_state(state, resume)
        limit = int(resume["meta"]["limit"])
        executed = int(resume["meta"]["executed"])
        chunks = [ChunkRecord(**c) for c in resume["meta"]["chunks"]]
        active = int(np.count_nonzero(np.asarray(state.reason) == 0))

    while active > 0 and limit < max_iter:
        rung = min(bucketer.canon(active), lanes)
        target = next_lower_rung(bucketer, rung)
        # drain horizon: with a preemption request already pending, bound
        # the program at one more chunk so the snapshot below is reached
        # promptly; otherwise the program runs the rung to the budget
        horizon = (
            min(limit + chunk, max_iter)
            if preemption.requested()
            else max_iter
        )
        with profiling.span("pml.rung.step", rung=rung, limit=limit):
            state, lim_d, exec_d, dch_d, act_d = _rung_step(
                data, state, jnp.int32(limit), jnp.int32(horizon),
                jnp.int32(target), jnp.int32(chunk), rung=rung, **cfg
            )
        # the ONLY per-hop D2H: four scalars (the state stays on device)
        with profiling.span("pml.rung.sync"):
            new_limit, exec_d, dch_d, act_d = (
                int(v) for v in jax.device_get((lim_d, exec_d, dch_d, act_d))
            )
        chunks.append(
            ChunkRecord(
                chunk=len(chunks),
                batch_lanes=rung,
                active_lanes=active,
                limit=new_limit,
                advanced=new_limit - limit,
            )
        )
        executed += exec_d
        device_chunks += dch_d
        limit = new_limit
        active = act_d
        if active == 0 or limit >= max_iter:
            break
        if preemption.check("rung", label=label, limit=limit):
            raise preemption.Preempted(
                f"preempted at rung boundary ({label}, iteration limit "
                f"{limit}/{max_iter}): {preemption.reason()}",
                site="rung",
                partial=_snapshot_state(state, label, limit, executed,
                                        chunks),
            )

    max_iteration = int(np.asarray(state.iteration).max(initial=0))
    solve_stats.record(
        SolveRecord(
            label=label,
            lanes=lanes,
            max_iteration=max_iteration,
            executed=executed,
            baseline=lanes * max_iteration,
            chunks=chunks,
            device_chunks=device_chunks,
        )
    )
    return result_of(state)
