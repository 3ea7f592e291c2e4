"""Coordinate descent over GAME coordinates.

Reference spec: algorithm/CoordinateDescent.scala:37-212 — outer loop over
iterations x coordinates: subtract the coordinate's own score from the total
(partial score), update the coordinate's model on those residuals, re-score,
recompute objective = sum of losses + sum of per-coordinate regularization
terms, optionally evaluate on validation data after every update.

TPU-native: scores are dense (N,) device vectors in global row order, so the
reference's KeyValueScore join-arithmetic (KeyValueScore.scala:62-90) is
elementwise add/subtract; the persist/unpersist choreography disappears
(arrays are device-resident); each coordinate's update is one jitted call.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import TYPE_CHECKING, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from photon_ml_tpu.evaluation.evaluators import Evaluator
from photon_ml_tpu.resilience import faults as _faults
from photon_ml_tpu.resilience import preemption as _preemption
from photon_ml_tpu.types import real_dtype
from photon_ml_tpu.utils import profiling

if TYPE_CHECKING:  # pragma: no cover
    from photon_ml_tpu.checkpoint import CoordinateDescentCheckpointer
    from photon_ml_tpu.resilience.guards import DivergenceGuard, GuardEvent

Array = jax.Array


@dataclasses.dataclass
class CoordinateDescentResult:
    """Final per-coordinate parameters + tracking."""

    coefficients: Dict[str, Array]  # coordinate name -> params (D,) or (E, D_loc)
    total_scores: Array  # (N,) final summed training scores
    objective_history: List[float]  # after every coordinate update
    validation_history: List[Dict[str, float]]  # per update, per evaluator
    # which loop ran: "per-update" (one program per coordinate update),
    # "fused-cycle" (one per descent iteration) or "grid" (run_grid); a
    # trace's pml.cd.* spans give the times
    path: str
    # coordinate name -> the LAST update's OptResult (vmapped solves carry a
    # leading entity axis; bucketed coordinates a tuple per bucket) — the
    # raw material of the reference's OptimizationTracker summaries
    # (RandomEffectOptimizationTracker.scala:62-95). Empty in fused-cycle
    # mode (results stay inside the compiled cycle).
    trackers: Dict[str, object] = dataclasses.field(default_factory=dict)
    # divergence-guard incidents during this run (resilience.guards): every
    # rollback / skipped cycle, with the coordinate and step it hit
    guard_events: List["GuardEvent"] = dataclasses.field(default_factory=list)


def _is_pytree(coord) -> bool:
    """Whether a coordinate is a registered pytree, so that jitted programs
    can take it, and with it its arrays, as an argument."""
    return not jax.tree_util.treedef_is_leaf(jax.tree.structure(coord))


#: (kind, coordinate name, donation) -> the program every descent runs for
#: that site. It takes the coordinate as its first argument and closes over
#: nothing, so it holds no data and may outlive the descents that built it.
_SITE_PROGRAMS: Dict[Tuple[str, str, bool], Callable] = {}


def _call(kind: str, coord, *args):
    """``coord.score(w)``, or ``coord.update(off, w0)`` with the per-entity
    solve's evaluation counts reduced beside its tracker, in the same
    program, at the widths its one-shot solve ran
    (``optim.scheduler.result_evaluation_counts``; None where the tracker
    is no lane-stacked L-BFGS result)."""
    if kind == "score":
        return coord.score(*args)
    from photon_ml_tpu.optim.scheduler import result_evaluation_counts

    params, tracker = coord.update(*args)
    return params, tracker, result_evaluation_counts(
        tracker, getattr(coord, "rung_widths", None))


def _site_program(kind: str, name: str, donate: bool) -> Callable:
    key = (kind, name, donate)
    if key not in _SITE_PROGRAMS:
        from photon_ml_tpu.compile import instrumented_jit

        _SITE_PROGRAMS[key] = instrumented_jit(
            functools.partial(_call, kind),
            site=f"cd.{kind}[{name}]", donate_argnums=(2,) if donate else ())
    return _SITE_PROGRAMS[key]


def _bind(kind: str, name: str, coord, donate: bool) -> Callable:
    """``coord.update(off, w0)`` (with its evaluation counts, see
    :func:`_call`) or ``coord.score(w)`` as the descent calls it: raw for a
    ``cd_jit=False`` coordinate (no counts: reducing them there would be
    launches of their own), the shared site program with the coordinate as
    its argument for a pytree, else a jit that closes over the coordinate.
    ``donate`` donates ``w0`` into the update."""
    if not getattr(coord, "cd_jit", True):
        if kind == "score":
            return coord.score
        return lambda *args: (*coord.update(*args), None)
    if _is_pytree(coord):
        return functools.partial(_site_program(kind, name, donate), coord)
    from photon_ml_tpu.compile import instrumented_jit

    return instrumented_jit(functools.partial(_call, kind, coord),
                            site=f"cd.{kind}[{name}]",
                            donate_argnums=(1,) if donate else ())


class CoordinateDescent:
    """Orchestrates coordinates in an update sequence.

    ``coordinates`` is an ordered dict name -> coordinate object exposing:
      initial_coefficients(), update(residual_offsets, init) -> (params, res),
      score(params) -> (N,), regularization_term(params) -> scalar.
    """

    def __init__(
        self,
        coordinates: Dict[str, object],
        training_loss: Callable[[Array], Array],
        validation_scorer: Optional[Callable[[Dict[str, Array]], Array]] = None,
        validation_evaluators: Optional[Dict[str, Tuple[Evaluator, dict]]] = None,
        fused_cycle: bool = False,
        divergence_guard: Optional["DivergenceGuard"] = None,
    ):
        """``training_loss(total_scores) -> scalar`` is the loss-evaluator
        analogue used for the objective value (the training counterpart of
        cli/game/training/Driver.scala:185-202).

        ``validation_scorer(coefficients) -> (Nv,)`` maps current params to
        validation scores; each validation evaluator is (Evaluator, kwargs
        for evaluate, e.g. labels/weights arrays).

        The whole descent stays async: objective/validation values stay on
        device until the end of the run, so dispatch is never serialized on a
        host round-trip per update. Where the time goes is read from a
        profiler trace, not from the host clock: every step below opens a
        host span (``pml.cd.*``, utils/profiling.py) that a trace lays
        against the device's idle gaps.

        ``fused_cycle=True`` compiles ONE XLA program per full descent
        iteration — every coordinate's update + rescore + objective (+
        validation metrics) unrolled into a single jitted cycle. The host
        dispatches once per iteration instead of ~4x per coordinate, and
        XLA can overlap across coordinate boundaries. Trade-offs: checkpoints land at iteration
        (not per-update) granularity, and no tracker (nor evaluation
        record, ``optim.scheduler.solve_stats``) leaves the compiled cycle.

        ``divergence_guard`` (resilience.guards.DivergenceGuard) gates every
        update: a non-finite parameter/score state is rolled back to the
        coordinate's last good state instead of poisoning the shared score
        vectors. The check blocks on one small scalar per update, so leave
        it None on dispatch-latency-critical runs unless needed.
        """
        self.coordinates = coordinates
        self.training_loss = training_loss
        self.validation_scorer = validation_scorer
        self.validation_evaluators = validation_evaluators or {}
        self.fused_cycle = fused_cycle
        self.divergence_guard = divergence_guard
        self._cycle_fn = None
        self._grid_cycle_fn = None  # jitted vmap(_cycle_body), built once
        # each coordinate's update and score run as one jitted program per
        # site, with compile telemetry (photon_ml_tpu.compile.compile_stats).
        # A coordinate that is a pytree (the plain fixed and random effects)
        # is an ARGUMENT of its programs: its arrays are the programs'
        # inputs, never constants lowered into them, and the programs are
        # shared by every descent (_site_program), so data of the same
        # shapes reuses one executable. Any other coordinate is closed over.
        # A coordinate may opt OUT (class attr cd_jit=False) when its arrays
        # span non-addressable devices under multihost SPMD — closing over
        # them in an outer jit is illegal; such coordinates jit internally
        # with the global arrays as ARGUMENTS (shard_map calls).
        #
        # Donation: the incoming coefficient state w0 is DONATED into each
        # update — the solver's output state aliases it in place, halving
        # peak HBM for the largest (E, D) stacks — EXCEPT under a
        # divergence guard, whose rollback must keep the pre-update state
        # alive (donating it would hand the guard a deleted buffer).
        from photon_ml_tpu.compile import donation_enabled

        self._donate = donation_enabled() and divergence_guard is None
        # the coordinates the fused cycle and the grid take as an argument
        self._arguments = {
            name: coord for name, coord in coordinates.items()
            if _is_pytree(coord)
        }
        self._update_fns = {
            name: _bind("update", name, coord, self._donate)
            for name, coord in coordinates.items()
        }
        self._score_fns = {
            name: _bind("score", name, coord, False)
            for name, coord in coordinates.items()
        }

    # ------------------------------------------------------------------
    def _cycle_body(self, arguments, params, scores, total, lam=None):
        """THE descent cycle: one full iteration over all coordinates
        (unrolled at trace time; ``arguments`` are the pytree coordinates,
        whose arrays flow through as traced inputs, the others are closed
        over as static structure). ``lam``
        (coordinate name -> traced total reg weight) is the lambda-grid
        override; None uses each coordinate's static regularization —
        fused mode and the traced-lambda grid share this single body."""
        coordinates = {**self.coordinates, **arguments}
        names = list(coordinates)
        objs = []
        vals = []
        for name in names:
            coord = coordinates[name]
            partial = total - scores[name]
            if lam is None:
                new_params, _ = coord.update(partial, params[name])
            else:
                new_params, _ = coord.update(
                    partial, params[name], reg_weight=lam[name]
                )
            params = {**params, name: new_params}
            new_score = coord.score(new_params)
            total = partial + new_score
            scores = {**scores, name: new_score}
            obj = self.training_loss(total) + sum(
                coordinates[n].regularization_term(params[n])
                if lam is None
                else coordinates[n].regularization_term(params[n], lam[n])
                for n in names
            )
            objs.append(obj)
            if self.validation_scorer is not None:
                v_scores = self.validation_scorer(params)
                vals.append(
                    {
                        key: ev.evaluate(v_scores, **kw)
                        for key, (ev, kw) in self.validation_evaluators.items()
                    }
                )
        return params, scores, total, objs, vals

    def _record_evaluations(self, name: str, tracker, counts) -> None:
        """Enter a one-shot per-entity solve's evaluation counts (device
        scalars of the update's own program: no launch, no host wait) in the
        solve ledger under the coordinate's ``solve_label``."""
        from photon_ml_tpu.optim.scheduler import solve_stats

        coord = self.coordinates[name]
        solve_stats.record_evaluations(
            getattr(coord, "solve_label", name), "one-shot",
            tracker.evaluations.shape[0], counts,
            widths=getattr(coord, "rung_widths", None),
        )

    def _require_jittable_coordinates(self, mode: str) -> None:
        """fused_cycle / run_grid wrap EVERY coordinate in one outer jit; a
        cd_jit=False coordinate (multihost-sharded arrays) would be traced
        with non-addressable constants — fail with a clear message instead
        of JAX's opaque trace error."""
        bad = [n for n, c in self.coordinates.items()
               if not getattr(c, "cd_jit", True)]
        if bad:
            raise ValueError(
                f"{mode} compiles all coordinates into one jitted program, "
                f"but {bad} hold multihost-sharded arrays that cannot be "
                "closed over (cd_jit=False) — use the per-update run() path"
            )

    def _build_cycle(self):
        from photon_ml_tpu.compile import instrumented_jit

        self._require_jittable_coordinates("fused_cycle")
        # donate the carried (params, scores, total) pytrees: each fused
        # iteration's outputs alias the previous iteration's buffers — the
        # whole descent carries ONE copy of the model state instead of two
        return instrumented_jit(
            self._cycle_body,
            site="cd.fused_cycle",
            donate_argnums=(1, 2, 3) if self._donate else (),
        )

    def run_grid(
        self,
        reg_weights: Dict[str, "jnp.ndarray"],
        num_iterations: int,
        num_rows: int,
        init_params: Optional[Dict[str, Array]] = None,
        checkpointers: Optional[List[Optional[object]]] = None,
    ) -> List[CoordinateDescentResult]:
        """Train a lambda grid through ONE compiled descent cycle: the
        traced-``reg_weight`` cycle compiles once and every combo reuses the
        executable (the reference re-runs its whole driver per combo,
        re-tracing everything, cli/game/training/Driver.scala:330-337 —
        compile amortization is this API's win).

        Combos run SEQUENTIALLY, each at its own lambda. A batched variant
        that trained all G combos as one ``vmap`` lane axis shipped in
        rounds 2–4 and lost the measured race every round on every platform
        (0.8–0.86x: each lane pays the slowest lane's while_loop iterations,
        which costs more than the batched-arithmetic win) — it was removed
        per VERDICT r4 #9; the sequential strategy below is exactly what
        its auto-selector always picked.

        ``reg_weights`` maps every coordinate name to a (G,) vector of total
        regularization weights (combo g trains coordinate n at
        ``reg_weights[n][g]``). All coordinates must accept a traced
        ``reg_weight`` in update()/regularization_term() — the plain fixed /
        random-effect coordinates do; factored, bucketed, and distributed
        coordinates do not (their lambda lives in nested static configs).

        ``init_params`` (coordinate name -> unbatched params) warm-starts
        every combo's solver from the same point (e.g. a cheap pre-solve at
        one lambda), cutting each solve's while_loop iteration count.

        ``checkpointers`` (one per combo, or None) enables PER-CYCLE
        checkpoints on the grid: the compiled cycle returns at iteration
        granularity, so each crossed ``save_every`` boundary (and the final
        iteration) lands a checkpoint of the combo's (params, scores,
        total) lane pytree, and a restart resumes the combo from its last
        complete iteration — finished combos replay from their final
        checkpoint without re-solving. Per-UPDATE granularity is the one
        thing the grid cannot offer (updates live inside the compiled
        cycle); the iteration boundaries are also cooperative-preemption
        drain points, exactly like the fused cycle.

        Returns one CoordinateDescentResult per combo, in input order.
        """
        import inspect

        self._require_jittable_coordinates("run_grid")
        names = list(self.coordinates)
        for name in names:
            coord = self.coordinates[name]
            for method in (coord.update, coord.regularization_term):
                if "reg_weight" not in inspect.signature(method).parameters:
                    raise ValueError(
                        f"coordinate {name!r} ({type(coord).__name__})."
                        f"{method.__name__} does not accept a traced "
                        "reg_weight — the traced-lambda grid API needs "
                        "plain fixed/random-effect coordinates"
                    )
        if set(reg_weights) != set(names):
            raise ValueError(
                f"reg_weights keys {sorted(reg_weights)} != coordinates {sorted(names)}"
            )
        lam = {n: jnp.asarray(reg_weights[n], real_dtype()) for n in names}
        sizes = {n: lam[n].shape for n in names}
        g = sizes[names[0]][0] if sizes[names[0]] else 0
        if any(s != (g,) for s in sizes.values()):
            raise ValueError(f"all reg-weight vectors must be shape (G,), got {sizes}")

        if self._grid_cycle_fn is None:
            # one-lane vmap keeps the lane axis in the traced shapes, so
            # every combo (and every run_grid call on this instance) reuses
            # the SAME executable — the compile-amortization win
            from photon_ml_tpu.compile import instrumented_jit

            self._grid_cycle_fn = instrumented_jit(
                jax.vmap(self._cycle_body, in_axes=(None, 0, 0, 0, 0)),
                site="cd.grid_cycle",
                donate_argnums=(1, 2, 3) if self._donate else (),
            )
        cycle_v = self._grid_cycle_fn

        dt = real_dtype()
        # every combo starts from the SAME seeded state — build it once, not
        # once per combo (a G-combo grid would otherwise pay G-1 redundant
        # full-data score passes per coordinate)
        params0 = {
            n: jnp.broadcast_to(
                (w0 := (
                    init_params[n]
                    if init_params is not None and n in init_params
                    else self.coordinates[n].initial_coefficients()
                )), (1,) + w0.shape
            )
            for n in names
        }
        scores0 = {n: jnp.zeros((1, num_rows), dt) for n in names}
        total0 = jnp.zeros((1, num_rows), dt)
        if init_params is not None:
            # mirror run(initial_params=...): a warm-started coordinate
            # contributes its CURRENT scores from step zero, broadcast
            # to the lane axis — otherwise the first grid cycle trains
            # every combo against zero offsets, defeating the warm start.
            # Names MISSING from init_params (e.g. a coordinate new since
            # the prior model) start cold, exactly like run().
            for n in names:
                if n not in init_params:
                    continue
                s0 = self.coordinates[n].score(jnp.asarray(init_params[n], dt))
                scores0[n] = jnp.broadcast_to(s0, (1, num_rows)).astype(dt)
                total0 = total0 + scores0[n]
        if checkpointers is not None and len(checkpointers) != g:
            raise ValueError(
                f"checkpointers must match the grid ({g} combos), "
                f"got {len(checkpointers)}"
            )
        n_coords = len(names)
        out = []
        for i in range(g):
            lam_i = {n: lam[n][i : i + 1] for n in names}
            ck = checkpointers[i] if checkpointers is not None else None
            if self._donate:
                # the donating cycle consumes its (params, scores, total)
                # inputs — hand every combo a fresh copy of the shared
                # seeds, or combo 2 would read combo 1's deleted buffers
                params = jax.tree.map(jnp.copy, dict(params0))
                scores = jax.tree.map(jnp.copy, dict(scores0))
                total = jnp.copy(total0)
            else:
                params = dict(params0)
                scores = dict(scores0)
                total = total0
            objective_history: List[float] = []
            validation_history: List[Dict[str, float]] = []
            start_iter = 0
            if ck is not None:
                restored = ck.restore(params0, scores0, total0)
                if restored is not None:
                    # grid checkpoints land only at iteration boundaries,
                    # so a restored step is always iteration-aligned
                    start_iter = restored.step // n_coords
                    params = restored.params
                    scores = restored.scores
                    total = restored.total_scores
                    objective_history = restored.objective_history
                    validation_history = restored.validation_history

            objective_dev: List[Array] = []
            validation_dev: List[Dict[str, Array]] = []

            def _drain():
                # one batched transfer each, like run()'s _drain — never
                # one device-to-host sync per scalar
                with profiling.span("pml.cd.drain"):
                    if objective_dev:
                        objective_history.extend(
                            float(o[0]) for o in jax.device_get(objective_dev)
                        )
                        objective_dev.clear()
                    if validation_dev:
                        validation_history.extend(
                            {k: float(v[0]) for k, v in m.items()}
                            for m in jax.device_get(validation_dev)
                        )
                        validation_dev.clear()

            def _save(step):
                with profiling.span("pml.cd.checkpoint", step=step):
                    from photon_ml_tpu.checkpoint import CheckpointState

                    _drain()
                    ck.save(
                        CheckpointState(
                            step=step,
                            params=params,
                            scores=scores,
                            total_scores=total,
                            objective_history=objective_history,
                            validation_history=validation_history,
                        )
                    )

            for it in range(start_iter, num_iterations):
                with profiling.span("pml.cd.iteration", iteration=it, combo=i):
                    step = (it + 1) * n_coords
                    with profiling.span("pml.cd.cycle", iteration=it):
                        params, scores, total, objs, vals = cycle_v(
                            self._arguments, params, scores, total, lam_i
                        )
                    objective_dev.extend(objs)
                    validation_dev.extend(vals)
                    is_last = it == num_iterations - 1
                    saved_here = ck is not None and (
                        step % ck.save_every < n_coords or is_last
                    )
                    if saved_here:
                        _save(step)
                    if not is_last and _preemption.check(
                        "cycle", step=step, combo=i
                    ):
                        if ck is not None:
                            if not saved_here:
                                _save(step)
                            if hasattr(ck, "wait"):
                                ck.wait()
                        raise _preemption.Preempted(
                            f"preempted at grid iteration boundary (combo {i}, "
                            f"step {step}): {_preemption.reason()}",
                            site="cycle",
                        )
            jax.block_until_ready(total)

            _drain()
            out.append(
                CoordinateDescentResult(
                    coefficients={n: params[n][0] for n in names},
                    total_scores=total[0],
                    objective_history=objective_history,
                    validation_history=validation_history,
                    path="grid",
                )
            )
        return out

    def run(
        self,
        num_iterations: int,
        num_rows: int,
        checkpointer: Optional["CoordinateDescentCheckpointer"] = None,
        initial_params: Optional[Dict[str, object]] = None,
        frozen: Optional[set] = None,
    ) -> CoordinateDescentResult:
        """Run the descent; with a ``checkpointer``, state is saved after
        every coordinate update and a restart resumes from the last complete
        step (photon_ml_tpu.checkpoint — a designed upgrade, SURVEY.md §5.4:
        the reference has no mid-run checkpointing).

        ``initial_params`` warm-starts named coordinates from a previous
        run's coefficients (the grid-sweep warm start,
        ModelTraining.scala:158-191 semantics); missing names fall back to
        the coordinate's own initialization. A restored checkpoint takes
        precedence over both.

        ``frozen`` (the delta-retrain skip, photon_ml_tpu.retrain) names
        coordinates whose data AND configuration are unchanged since the
        prior run: they carry their ``initial_params`` coefficients and the
        step-zero scores forward BITWISE without ever solving — the
        objective still counts their loss/regularization contribution and
        histories/checkpoints stay step-aligned, so a frozen coordinate is
        indistinguishable from a converged one to everything downstream.
        Every frozen name must be warm-started (freezing an uninitialized
        coordinate would freeze zeros)."""
        with profiling.span("pml.cd.run", iterations=num_iterations):
            return self._run(
                num_iterations, num_rows, checkpointer, initial_params, frozen
            )

    def _run(self, num_iterations, num_rows, checkpointer, initial_params,
             frozen) -> CoordinateDescentResult:
        names = list(self.coordinates)
        frozen = frozenset(frozen or ())
        if frozen:
            unknown = frozen - set(names)
            if unknown:
                raise ValueError(f"frozen coordinates {sorted(unknown)} are "
                                 "not in the updating sequence")
            unseeded = [n for n in frozen
                        if initial_params is None or n not in initial_params]
            if unseeded:
                raise ValueError(
                    f"frozen coordinates {sorted(unseeded)} have no "
                    "initial_params — freezing needs the prior coefficients"
                )
            if self.fused_cycle:
                raise ValueError(
                    "frozen coordinates cannot compose with fused_cycle "
                    "(per-coordinate skip lives outside the compiled "
                    "iteration); use the per-update path"
                )
        params = {
            n: (
                initial_params[n]
                if initial_params is not None and n in initial_params
                else self.coordinates[n].initial_coefficients()
            )
            for n in names
        }
        if initial_params is not None and self._donate:
            # donating updates consume their w0 — warm-start params belong
            # to the CALLER (e.g. a previous combo's result); hand the
            # donation a private copy so the caller's arrays survive
            for n in names:
                if n in initial_params and getattr(
                    self.coordinates[n], "cd_jit", True
                ):
                    params[n] = jax.tree.map(jnp.copy, params[n])
        scores = {n: jnp.zeros((num_rows,), real_dtype()) for n in names}
        if initial_params is not None:
            # warm-started coordinates contribute their CURRENT scores from
            # step zero, so the first update already trains on residuals of
            # the warm model (the point of the warm start) rather than on
            # zero offsets
            for n in names:
                if n in initial_params:
                    scores[n] = self.coordinates[n].score(params[n])
        # device scalars until the end of the run — converting per update
        # would serialize every dispatch on a host round-trip; the reference
        # pays the same sync as a Spark reduce per update, we don't have to
        objective_dev: List[Array] = []
        validation_dev: List[Dict[str, Array]] = []
        objective_history: List[float] = []
        validation_history: List[Dict[str, float]] = []
        trackers: Dict[str, object] = {}
        total = jnp.zeros((num_rows,), real_dtype())
        for n in names:
            total = total + scores[n]  # zeros unless warm-started above

        start_step = 0
        midstep = None  # mid-coordinate resume payload from an emergency ckpt
        if checkpointer is not None:
            restored = checkpointer.restore(params, scores, total)
            if restored is not None:
                start_step = restored.step
                params = restored.params
                scores = restored.scores
                total = restored.total_scores
                objective_history = restored.objective_history
                validation_history = restored.validation_history
                midstep = restored.partial

        def _drain():
            """Pull accumulated device scalars to host (one batched transfer)."""
            with profiling.span("pml.cd.drain"):
                if objective_dev:
                    objective_history.extend(float(v) for v in jax.device_get(objective_dev))
                    objective_dev.clear()
                if validation_dev:
                    host = jax.device_get(validation_dev)
                    validation_history.extend(
                        {k: float(v) for k, v in m.items()} for m in host
                    )
                    validation_dev.clear()

        def _emergency_save(at_step: int, partial=None, already_saved=False):
            """Drain-to-boundary checkpoint for a preemption exit: make the
            completed work durable NOW (and fence an async commit) so the
            relaunched process resumes instead of recomputing. Returns the
            checkpoint path, or None without a checkpointer (the process
            still exits with the distinct preemption code — the supervisor
            just restarts from scratch)."""
            if checkpointer is None:
                return None
            from photon_ml_tpu.checkpoint import STEP_PREFIX, CheckpointState

            with profiling.span("pml.cd.checkpoint", step=at_step):
                _drain()
                # the boundary save a moment ago already covers this step
                path = os.path.join(
                    checkpointer.directory, f"{STEP_PREFIX}{at_step}"
                )
                if not already_saved or partial is not None:
                    path = checkpointer.save(
                        CheckpointState(
                            step=at_step,
                            params=params,
                            scores=scores,
                            total_scores=total,
                            objective_history=objective_history,
                            validation_history=validation_history,
                            partial=partial,
                        )
                    )
                # the fence: an async commit must be durable before the process
                # exits on the preemption path
                if hasattr(checkpointer, "wait"):
                    checkpointer.wait()
            return path

        guard = self.divergence_guard
        guard_events_start = len(guard.events) if guard is not None else 0
        if self.fused_cycle:
            n_coords = len(names)
            if start_step % n_coords != 0:
                raise ValueError(
                    f"fused_cycle resume requires an iteration-aligned "
                    f"checkpoint; restored step {start_step} is mid-iteration "
                    f"(coordinates={n_coords}). Re-run unfused to finish the "
                    "partial iteration first."
                )
            if self._cycle_fn is None:
                self._cycle_fn = self._build_cycle()
            for it in range(num_iterations):
                step = (it + 1) * n_coords
                if step <= start_step:
                    continue
                with profiling.span("pml.cd.iteration", iteration=it):
                    with profiling.span("pml.cd.cycle", iteration=it):
                        new_params, new_scores, new_total, objs, vals = self._cycle_fn(
                            self._arguments, params, scores, total
                        )
                    if guard is not None:
                        with profiling.span("pml.cd.guard", coordinate="(fused-cycle)"):
                            # iteration granularity: the per-update states live
                            # inside the compiled cycle, so a non-finite outcome
                            # rolls the WHOLE iteration back to its entry state
                            new_params, new_total, ok = guard.filter_update(
                                "(fused-cycle)", step, new_params, new_total, params, total
                            )
                            if not ok:
                                new_scores = scores
                                # re-evaluate the rolled-back state once and repeat
                                # it per update so histories (and the step-aligned
                                # checkpoint contract) keep one entry per update
                                obj = self.training_loss(total) + sum(
                                    self.coordinates[n].regularization_term(params[n])
                                    for n in names
                                )
                                objs = [obj] * n_coords
                                if self.validation_scorer is not None:
                                    v_scores = self.validation_scorer(params)
                                    vals = [
                                        {
                                            key: ev.evaluate(v_scores, **kw)
                                            for key, (ev, kw) in self.validation_evaluators.items()
                                        }
                                    ] * n_coords
                                else:
                                    vals = []
                    params, scores, total = new_params, new_scores, new_total
                    objective_dev.extend(objs)
                    validation_dev.extend(vals)
                    is_last = it == num_iterations - 1
                    # steps advance n_coords at a time here: fire whenever a
                    # save_every boundary was CROSSED this iteration, not only
                    # when step lands exactly on a multiple
                    saved_here = checkpointer is not None and (
                        step % checkpointer.save_every < n_coords or is_last
                    )
                    if saved_here:
                        with profiling.span("pml.cd.checkpoint", step=step):
                            from photon_ml_tpu.checkpoint import CheckpointState

                            _drain()
                            checkpointer.save(
                                CheckpointState(
                                    step=step,
                                    params=params,
                                    scores=scores,
                                    total_scores=total,
                                    objective_history=objective_history,
                                    validation_history=validation_history,
                                )
                            )
                    # cooperative preemption: iteration boundaries are the fused
                    # cycle's only safe points (per-update state lives inside
                    # the compiled program) — and they are iteration-ALIGNED, so
                    # an emergency checkpoint here always satisfies the fused
                    # resume contract above
                    if not is_last and _preemption.check("cycle", step=step):
                        path = _emergency_save(step, already_saved=saved_here)
                        raise _preemption.Preempted(
                            f"preempted at iteration boundary (step {step}): "
                            f"{_preemption.reason()}",
                            site="cycle",
                            checkpoint_path=path,
                        )
            _drain()
            return CoordinateDescentResult(
                coefficients=params,
                total_scores=total,
                objective_history=objective_history,
                validation_history=validation_history,
                path="fused-cycle",
                guard_events=(
                    list(guard.events[guard_events_start:])
                    if guard is not None
                    else []
                ),
            )

        step = 0
        for it in range(num_iterations):
            with profiling.span("pml.cd.iteration", iteration=it):
                skip_rest_of_cycle = False
                for name in names:
                    step += 1
                    if step <= start_step:
                        continue  # already completed before the restart
                    if not skip_rest_of_cycle and name not in frozen:
                        partial = total - scores[name]  # sum of the OTHER coordinates
                        with profiling.span("pml.cd.update", coordinate=name):
                            try:
                                if midstep is not None and step == int(
                                    midstep["meta"].get("resume_step", -1)
                                ):
                                    # the emergency checkpoint interrupted THIS step:
                                    # hand the in-flight coordinate its paused state
                                    # (scheduler carries / per-block progress) so it
                                    # finishes instead of restarting — bitwise the
                                    # same coefficients either way
                                    mid_name = midstep["meta"].get("coordinate")
                                    if mid_name != name:
                                        raise ValueError(
                                            f"checkpoint partial targets coordinate "
                                            f"{mid_name!r} at step {step} but the "
                                            f"sequence reaches {name!r} — updating "
                                            "sequence changed; refusing to resume"
                                        )
                                    new_params, trackers[name] = self.coordinates[
                                        name
                                    ].update(partial, params[name], resume=midstep)
                                    midstep = None
                                else:
                                    new_params, trackers[name], counts = (
                                        self._update_fns[name](partial, params[name])
                                    )
                                    if counts is not None:
                                        self._record_evaluations(
                                            name, trackers[name], counts
                                        )
                            except _preemption.Preempted as e:
                                # an inner loop drained at a block/chunk boundary:
                                # checkpoint the completed steps PLUS the in-flight
                                # coordinate's progress, then unwind to the driver
                                payload = dict(e.partial) if e.partial else None
                                if payload is not None:
                                    payload["meta"] = dict(
                                        payload.get("meta") or {},
                                        coordinate=name,
                                        resume_step=step,
                                    )
                                e.checkpoint_path = _emergency_save(
                                    step - 1, partial=payload
                                )
                                raise
                        # chaos-test hook: a kind="nan" fault at this site
                        # corrupts the update exactly like a diverged solve
                        new_params = _faults.corrupt(
                            "optim.step", new_params, coordinate=name, step=step
                        )
                        with profiling.span("pml.cd.score", coordinate=name):
                            new_score = self._score_fns[name](new_params)
                        if guard is not None:
                            with profiling.span("pml.cd.guard", coordinate=name):
                                new_params, new_score, ok = guard.filter_update(
                                    name, step, new_params, new_score,
                                    params[name], scores[name],
                                )
                                if not ok and guard.mode == "skip_cycle":
                                    skip_rest_of_cycle = True
                        params[name] = new_params
                        total = partial + new_score
                        scores[name] = new_score
                    # else: guard abandoned this cycle OR the coordinate is
                    # frozen (delta retrain) — state is unchanged, but
                    # histories and checkpoints below stay step-aligned

                    # objective = loss(total scores) + sum of reg terms
                    # (CoordinateDescent.scala:172-178) — stays on device
                    with profiling.span("pml.cd.objective", coordinate=name):
                        obj = self.training_loss(total) + sum(
                            self.coordinates[n].regularization_term(params[n]) for n in names
                        )
                        objective_dev.append(obj)

                    if self.validation_scorer is not None:
                        with profiling.span("pml.cd.validate", coordinate=name):
                            v_scores = self.validation_scorer(params)
                            validation_dev.append(
                                {
                                    key: ev.evaluate(v_scores, **kw)
                                    for key, (ev, kw) in self.validation_evaluators.items()
                                }
                            )

                    is_last = it == num_iterations - 1 and name == names[-1]
                    saved_here = checkpointer is not None and (
                        step % checkpointer.save_every == 0 or is_last
                    )
                    if saved_here:
                        with profiling.span("pml.cd.checkpoint", step=step):
                            from photon_ml_tpu.checkpoint import CheckpointState

                            _drain()
                            checkpointer.save(
                                CheckpointState(
                                    step=step,
                                    params=params,
                                    scores=scores,
                                    total_scores=total,
                                    objective_history=objective_history,
                                    validation_history=validation_history,
                                )
                            )
                    # cooperative preemption: every update boundary is a safe
                    # drain point — make the finished step durable and unwind
                    # with the distinct exit path (the final update just
                    # finishes; there is nothing left to preempt)
                    if not is_last and _preemption.check("cycle", step=step):
                        path = _emergency_save(step, already_saved=saved_here)
                        raise _preemption.Preempted(
                            f"preempted at update boundary (step {step}): "
                            f"{_preemption.reason()}",
                            site="cycle",
                            checkpoint_path=path,
                        )

        _drain()
        return CoordinateDescentResult(
            coefficients=params,
            total_scores=total,
            objective_history=objective_history,
            validation_history=validation_history,
            path="per-update",
            trackers=trackers,
            guard_events=(
                list(guard.events[guard_events_start:]) if guard is not None else []
            ),
        )
