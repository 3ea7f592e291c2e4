"""Convergence-compacted solve scheduler: chunk → compact → resume.

SURVEY §7.3 names the residual TPU-mapping hazard of GLMix random effects:
vmapping a while_loop means every lane steps until the slowest lane
converges. Size-bucketing (PR-3 ladder, bucketed/streaming coordinates)
fixed the *padding* waste; this module attacks the *iteration* waste — the
Snap ML observation (1803.06333) that hierarchical GLM training wins come
from scheduling work to match convergence heterogeneity, and the straggler
accounting of the Spark-ML study (1612.01437) applied to per-entity lanes.

Mechanism (host-side loop over device chunk kernels):

  1. **chunk** — run the resumable vmapped kernel (optim/lbfgs.py /
     optim/tron.py ``*_advance_``) for K more iterations; converged lanes
     freeze (the while_loop batching rule masks them), active lanes pause
     at the chunk boundary with their full carried state.
  2. **compact** — pull the per-lane ``reason`` flags (one tiny D2H), gather
     the unconverged lanes' problem data + carried state into a smaller
     batch padded up the :class:`~photon_ml_tpu.compile.ShapeBucketer`
     ladder, so compacted batches land on ~log(E) canonical lane counts and
     REUSE compiled chunk executables instead of recompiling per active
     count. Ladder-pad lanes repeat a real lane with ``reason`` forced
     nonzero, so they freeze at zero marginal iterations.
  3. **resume** — advance the compacted batch another K iterations and
     scatter its lanes' state back into the full entity-order state (pad
     lanes scatter nowhere).

Per-lane trajectories are branch-free and lane-independent, so chunking
and re-batching change WHICH lanes burn device iterations but not any
lane's arithmetic: final results are bitwise-equal to the one-shot kernel
(tests/test_scheduler.py pins this for LBFGS, OWL-QN, and TRON).

Telemetry: every compacted solve records per-chunk active-lane counts and
the lane-iteration ledger in :data:`solve_stats` (the CompileStats
pattern); drivers log ``solve_stats.summary()`` next to the compile stats.

Env control: ``PHOTON_SOLVE_CHUNK`` = ``off`` (default) | ``on`` | K
(chunk size) | ``device[:K]`` (the fused on-device loop,
optim/fused_schedule.py), read via the one env gate
(``compile/overrides.py``), the same resolve pattern as
``PHOTON_SHAPE_LADDER``.

Composition (photon_ml_tpu.compile.plan resolves it once per run): the
chunk kernels take their data as pytree ARGUMENTS, so the same host loop
drives unsharded solves, GSPMD entity-sharded solves (the mesh path:
sharded operands partition the vmapped lanes across devices; this loop
never enters the mesh program), and the per-host streaming block solves
(owner-computes: each host compacts its owned blocks independently —
the billion-coefficient path). Contexts with no host boundary to pause
at (``--fused-cycle``, the compiled traced-lambda grid cycle) run the
DEVICE loop instead: optim/fused_schedule.py fuses the whole
chunk→compact→resume cycle into one XLA program per ladder rung, so the
plan promotes the schedule rather than fencing it (only the
``--vmapped-grid true`` fence remains).
"""

from __future__ import annotations

import dataclasses
import logging
import threading
from typing import List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from photon_ml_tpu.compile import ShapeBucketer, instrumented_jit
from photon_ml_tpu.optim.common import OptResult
from photon_ml_tpu.resilience import preemption
from photon_ml_tpu.utils import profiling

Array = jax.Array

logger = logging.getLogger(__name__)

DEFAULT_CHUNK = 8

# reason code stamped on ladder-pad lanes so the chunk while_loop freezes
# them; never scattered back (pad lanes map out of bounds -> dropped)
_PAD_REASON = np.int32(1)


@dataclasses.dataclass(frozen=True)
class SolveSchedule:
    """Static compaction policy for one coordinate's solves.

    ``chunk_size`` — iterations per chunk between compaction pauses. Small
    K compacts sooner (less straggler burn) but pays more host syncs; K >=
    max_iterations degenerates to the one-shot kernel plus one sync.

    ``bucketer`` — the ladder compacted lane counts round up to, so every
    chunk/gather/scatter executable is shared across compaction steps (and
    across blocks/buckets that land on the same rung).

    ``loop`` — ``"host"`` (this module's chunk loop, the default) or
    ``"device"`` (optim/fused_schedule.py: the whole chunk→compact→resume
    cycle fused into one XLA program per ladder rung; host dispatches
    drop from O(max_iter/chunk) to O(#rungs), results stay bitwise).
    """

    chunk_size: int = DEFAULT_CHUNK
    bucketer: ShapeBucketer = ShapeBucketer()
    loop: str = "host"

    def __post_init__(self):
        if self.chunk_size < 1:
            raise ValueError(
                f"solve-compaction chunk size must be >= 1, got {self.chunk_size}"
            )
        if self.loop not in ("host", "device"):
            raise ValueError(
                f"solve-compaction loop must be 'host' or 'device', "
                f"got {self.loop!r}"
            )

    def describe(self) -> str:
        loop = f"loop={self.loop}, " if self.loop != "host" else ""
        return (
            f"compaction(chunk={self.chunk_size}, {loop}"
            f"{self.bucketer.describe()})"
        )


def resolve_schedule(
    spec: "Optional[SolveSchedule | str | bool | int]" = None,
) -> Optional[SolveSchedule]:
    """Effective schedule: an explicit value wins; ``None`` falls back to
    ``PHOTON_SOLVE_CHUNK``. Returns None when compaction is off.

    Accepted spellings (driver flag and env var share them):
    ``off``/``false``/``0`` -> None; ``on``/``true`` -> default chunk; a
    positive integer -> that chunk size; ``device`` or ``device:CHUNK``
    -> the fused on-device loop (optim/fused_schedule.py).
    """
    if isinstance(spec, SolveSchedule):
        return spec
    if spec is None:
        from photon_ml_tpu.compile.overrides import solve_chunk_spec

        raw = solve_chunk_spec()
        if raw is None:
            return None
        return resolve_schedule(raw)
    if isinstance(spec, bool):
        return SolveSchedule() if spec else None
    if isinstance(spec, int):
        return SolveSchedule(chunk_size=spec) if spec > 0 else None
    text = str(spec).strip().lower()
    if text in ("", "off", "false", "0", "none"):
        return None
    if text in ("on", "true", "default"):
        return SolveSchedule()
    if text == "device":
        return SolveSchedule(loop="device")
    if text.startswith("device:"):
        inner = resolve_schedule(text.split(":", 1)[1])
        if inner is None:
            raise ValueError(
                f"bad solve-compaction spec {spec!r}: 'device:' needs a "
                "chunk size (the device loop has no 'off' half)"
            )
        return dataclasses.replace(inner, loop="device")
    try:
        chunk = int(text)
    except ValueError as e:
        raise ValueError(
            f"bad solve-compaction spec {spec!r} (want off | on | CHUNK | "
            f"device[:CHUNK], e.g. 8 or device:8): {e}"
        ) from e
    if chunk < 1:
        raise ValueError(
            f"solve-compaction chunk size must be >= 1, got {chunk}"
        )
    return SolveSchedule(chunk_size=chunk)


# ---------------------------------------------------------------------------
# telemetry (the CompileStats pattern: process-wide, thread-safe)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ChunkRecord:
    """One chunk dispatch of one compacted solve."""

    chunk: int  # chunk index within the solve
    batch_lanes: int  # lanes in the dispatched batch (full E or ladder rung)
    active_lanes: int  # genuinely unconverged lanes in the batch
    limit: int  # absolute iteration bound the chunk ran to
    advanced: int  # iterations the loop actually stepped (max over lanes)


@dataclasses.dataclass
class SolveRecord:
    """Lane-iteration ledger of one compacted solve.

    ``chunks`` records one entry per HOST DISPATCH — every chunk on the
    host loop, every rung hop on the device loop (optim/fused_schedule
    .py), where the in-program chunk iterations additionally land on
    ``device_chunks`` (0 on the host loop)."""

    label: str
    lanes: int  # entity lanes in the full problem
    max_iteration: int  # slowest lane's final iteration count
    executed: int  # sum over chunks of batch_lanes * advanced
    baseline: int  # lanes * max_iteration: the one-shot vmapped burn
    chunks: List[ChunkRecord]
    device_chunks: int = 0  # chunk iterations run INSIDE fused rung programs

    @property
    def saved(self) -> int:
        return self.baseline - self.executed

    @property
    def dispatches(self) -> int:
        """Host dispatches this solve paid (the pause-tariff unit in
        compile/cost.py): chunk dispatches on the host loop, rung hops on
        the device loop."""
        return len(self.chunks)


class SolveStats:
    """Registry of compacted-solve ledgers (thread-safe: the streaming
    prefetch pipeline can overlap block solves with host work).

    BOUNDED, like the CompileStats counter pattern: totals aggregate into
    plain counters, and only the worst (largest-baseline) record plus a
    short ring of the most recent ones are retained — a B-blocks x
    I-iterations x C-combos run records B*I*C solves without growing
    process memory with the run length. The per-block convergence ledger
    added for adaptive scheduling (optim/convergence.py) is keyed by block
    label and updated in place, so it is bounded by the BLOCK COUNT, not
    the run length."""

    RECENT_KEEP = 32
    HOTTEST_KEEP = 5

    def __init__(self):
        self._lock = threading.Lock()
        self._counters = dict.fromkeys(
            ("solves", "lanes", "executed", "baseline", "chunks",
             "device_chunks", "blocks_visited", "blocks_skipped"), 0
        )
        self._worst: Optional[SolveRecord] = None
        self._recent: List[SolveRecord] = []
        self._blocks: dict = {}

    def record(self, rec: SolveRecord) -> None:
        with self._lock:
            self._counters["solves"] += 1
            self._counters["lanes"] += rec.lanes
            self._counters["executed"] += rec.executed
            self._counters["baseline"] += rec.baseline
            self._counters["chunks"] += len(rec.chunks)
            self._counters["device_chunks"] += rec.device_chunks
            if self._worst is None or rec.baseline > self._worst.baseline:
                self._worst = rec
            self._recent.append(rec)
            del self._recent[: -self.RECENT_KEEP]

    def record_block(self, label: str, *, score: Optional[float] = None,
                     executed: int = 0, skipped: bool = False) -> None:
        """One block-level visitation event for the adaptive-schedule
        ledger (optim/convergence.py): a solved visit carries the block's
        fresh convergence score and lane-iteration cost; an adaptive skip
        carries neither (the score is unchanged by definition)."""
        with self._lock:
            e = self._blocks.setdefault(
                label, {"visits": 0, "skips": 0, "score": None, "executed": 0}
            )
            if skipped:
                e["skips"] += 1
                self._counters["blocks_skipped"] += 1
            else:
                e["visits"] += 1
                e["executed"] += int(executed)
                if score is not None:
                    e["score"] = float(score)
                self._counters["blocks_visited"] += 1

    def snapshot(self) -> List[SolveRecord]:
        """The most recent solve records (bounded ring, newest last)."""
        with self._lock:
            return list(self._recent)

    def block_totals(self) -> dict:
        """Per-block visitation ledger snapshot: label -> visits/skips/
        last score/cumulative lane-iterations."""
        with self._lock:
            return {k: dict(v) for k, v in self._blocks.items()}

    def reset(self) -> None:
        with self._lock:
            self._counters = dict.fromkeys(self._counters, 0)
            self._worst = None
            self._recent.clear()
            self._blocks.clear()

    def totals(self) -> dict:
        with self._lock:
            return {
                "solves": self._counters["solves"],
                "lanes": self._counters["lanes"],
                "executed_lane_iterations": self._counters["executed"],
                "baseline_lane_iterations": self._counters["baseline"],
                "saved_lane_iterations": (
                    self._counters["baseline"] - self._counters["executed"]
                ),
                "chunk_dispatches": self._counters["chunks"],
                "device_chunk_iterations": self._counters["device_chunks"],
            }

    def realized_plan_cost(self) -> Optional[float]:
        """This run's solve ledger in planner cost units (compile/cost.py):
        executed lane-iterations plus the host-pause tariff per HOST
        dispatch — every chunk on the host loop, every rung hop on the
        device loop (in-program chunk iterations pause nothing and pay no
        tariff: the policy-dependent pricing the device prior predicts).
        The realized cost :meth:`ExecutionPlan.record_realized` feeds back
        into the cost model's schedule predictions. None when no solves
        ran (nothing to learn from)."""
        from photon_ml_tpu.compile.cost import CHUNK_PAUSE_COST

        with self._lock:
            if not self._counters["solves"]:
                return None
            return float(
                self._counters["executed"]
                + CHUNK_PAUSE_COST * self._counters["chunks"]
            )

    def summary(self) -> str:
        """Driver-log summary: the ledger plus per-chunk active-lane decay
        of the worst (largest-baseline) solve."""
        with self._lock:  # one acquisition: totals + worst must be coherent
            t = {
                "solves": self._counters["solves"],
                "lanes": self._counters["lanes"],
                "executed_lane_iterations": self._counters["executed"],
                "baseline_lane_iterations": self._counters["baseline"],
                "saved_lane_iterations": (
                    self._counters["baseline"] - self._counters["executed"]
                ),
                "blocks_visited": self._counters["blocks_visited"],
                "blocks_skipped": self._counters["blocks_skipped"],
            }
            worst = self._worst
            blocks = {k: dict(v) for k, v in self._blocks.items()}
        lines = []
        if not t["solves"]:
            lines.append("solve compaction: no compacted solves recorded")
        else:
            pct = (
                100.0 * t["saved_lane_iterations"]
                / t["baseline_lane_iterations"]
                if t["baseline_lane_iterations"]
                else 0.0
            )
            lines.append(
                f"solve compaction: {t['solves']} solves / {t['lanes']} lanes; "
                f"{t['executed_lane_iterations']} lane-iterations executed vs "
                f"{t['baseline_lane_iterations']} one-shot "
                f"(saved {t['saved_lane_iterations']}, {pct:.1f}%)"
            )
        if worst is not None:
            decay = " -> ".join(
                f"{c.active_lanes}/{c.batch_lanes}@{c.limit}" for c in worst.chunks
            )
            lines.append(
                f"  [{worst.label}] active-lane decay (active/batch@limit): {decay}"
            )
        if blocks:
            hottest = sorted(
                ((k, v) for k, v in blocks.items() if v["score"] is not None),
                key=lambda kv: -kv[1]["score"],
            )[: self.HOTTEST_KEEP]
            lines.append(
                f"adaptive blocks: {t['blocks_visited']} visits / "
                f"{t['blocks_skipped']} skips across {len(blocks)} blocks"
                + (
                    "; hottest: " + ", ".join(
                        f"{k}(score={v['score']:.3g}, "
                        f"iters={v['executed']})" for k, v in hottest
                    )
                    if hottest else ""
                )
            )
        return "\n".join(lines)


#: THE process-wide registry every compacted solve reports into.
solve_stats = SolveStats()


# ---------------------------------------------------------------------------
# shared chunk kernels (one per process, like streaming_re's block kernels:
# problem data rides as a pytree argument, solver configuration as hashable
# statics, so jit caches key on (shapes, config) — ladder-sized compacted
# batches and same-ladder streaming blocks collapse onto few executables)
# ---------------------------------------------------------------------------

_STATICS = ("task", "optimizer", "optimizer_config", "regularization")
_INIT_JIT = None
_CHUNK_JIT = None
_GATHER_JIT = None
_SCATTER_JIT = None


def _lane_fns(task, optimizer, optimizer_config, regularization):
    from photon_ml_tpu.algorithm.random_effect import entity_lane_fns

    return entity_lane_fns(task, optimizer, optimizer_config, regularization)


def _init_batch(data, w0, **cfg):
    """Vmapped fresh solve state for every lane (one objective eval)."""
    global _INIT_JIT
    if _INIT_JIT is None:

        def impl(data, w0, task, optimizer, optimizer_config, regularization):
            _, init_one, _, _ = _lane_fns(
                task, optimizer, optimizer_config, regularization
            )
            return jax.vmap(init_one)(*data, w0)

        _INIT_JIT = instrumented_jit(
            impl, site="scheduler.init", static_argnames=_STATICS
        )
    return _INIT_JIT(data, w0, **cfg)


def _chunk_batch(data, state, limit, **cfg):
    """Advance every lane to the absolute iteration bound ``limit`` (a
    TRACED scalar, so every chunk of every compaction step reuses the same
    executable per batch shape)."""
    global _CHUNK_JIT
    if _CHUNK_JIT is None:
        from photon_ml_tpu.compile import donation_enabled

        def impl(data, state, limit, task, optimizer, optimizer_config,
                 regularization):
            _, _, advance_one, _ = _lane_fns(
                task, optimizer, optimizer_config, regularization
            )
            return jax.vmap(
                advance_one, in_axes=(0, 0, 0, 0, 0, None)
            )(*data, state, limit)

        _CHUNK_JIT = instrumented_jit(
            impl,
            site="scheduler.chunk",
            static_argnames=_STATICS,
            # the paused state is dead once advanced — update it in place
            donate_argnums=(1,) if donation_enabled() else (),
        )
    return _CHUNK_JIT(data, state, limit, **cfg)


def _gather_batch(data, state, idx, n_active):
    """Compact the ``idx`` lanes of (data, state) into a smaller batch.
    ``idx`` is ladder-rung sized; entries past ``n_active`` repeat a real
    lane and get their ``reason`` forced nonzero so they freeze instead of
    burning chunk iterations."""
    global _GATHER_JIT
    if _GATHER_JIT is None:

        def impl(data, state, idx, n_active):
            take = lambda a: jnp.take(a, idx, axis=0)
            data_c = jax.tree.map(take, data)
            state_c = jax.tree.map(take, state)
            pad = jnp.arange(idx.shape[0]) >= n_active
            state_c = state_c._replace(
                reason=jnp.where(pad, _PAD_REASON, state_c.reason)
            )
            return data_c, state_c

        _GATHER_JIT = instrumented_jit(
            impl,
            site="scheduler.compact",
            # full state/data must stay alive (scatter target / next gather
            # source) — nothing to donate
            static_argnames=(),
        )
    return _GATHER_JIT(data, state, idx, n_active)


def _scatter_batch(full_state, part_state, idx, n_active):
    """Scatter a compacted batch's lanes back into entity order. Pad lanes
    (positions >= n_active) map out of bounds and are DROPPED by the jitted
    scatter — only real lanes land."""
    global _SCATTER_JIT
    if _SCATTER_JIT is None:
        from photon_ml_tpu.compile import donation_enabled

        def impl(full_state, part_state, idx, n_active):
            lanes = full_state.reason.shape[0]
            pos = jnp.where(jnp.arange(idx.shape[0]) < n_active, idx, lanes)
            return jax.tree.map(
                lambda f, p: f.at[pos].set(p, mode="drop"), full_state, part_state
            )

        _SCATTER_JIT = instrumented_jit(
            impl,
            site="scheduler.scatter",
            static_argnames=(),
            # the stale full state is consumed — scatter in place
            donate_argnums=(0,) if donation_enabled() else (),
        )
    return _SCATTER_JIT(full_state, part_state, idx, n_active)


# ---------------------------------------------------------------------------
# the scheduler loop
# ---------------------------------------------------------------------------


def _snapshot_state(state, label: str, limit: int, executed: int,
                    chunks: List[ChunkRecord]) -> dict:
    """Host snapshot of a paused solve: the full per-lane carried state
    (flattened to numbered numpy leaves — bitwise round-trip) plus the
    scheduler bookkeeping, in the ``partial`` payload shape checkpoint.py
    persists. Resume rebuilds the exact state and continues; PR 4 pinned
    chunked resume bitwise-equal at any boundary, so the interrupted solve
    finishes identical to an uninterrupted one."""
    leaves, treedef = jax.tree_util.tree_flatten(state)
    return {
        "meta": {
            "kind": "scheduler",
            "label": label,
            "limit": int(limit),
            "executed": int(executed),
            "treedef": str(treedef),
            "num_leaves": len(leaves),
            "chunks": [dataclasses.asdict(c) for c in chunks],
        },
        "arrays": {f"state.{i}": np.asarray(l) for i, l in enumerate(leaves)},
    }


def _restore_state(template_state, partial: dict):
    """Rebuild the paused state from a snapshot, using a freshly-initialized
    state purely as the structure template."""
    leaves, treedef = jax.tree_util.tree_flatten(template_state)
    meta = partial["meta"]
    if meta.get("treedef") != str(treedef) or meta.get("num_leaves") != len(leaves):
        raise ValueError(
            "scheduler resume snapshot does not match this solver's state "
            f"structure ({meta.get('treedef')} vs {treedef}) — optimizer or "
            "config changed since the emergency checkpoint; refusing to resume"
        )
    new_leaves = [
        jnp.asarray(partial["arrays"][f"state.{i}"]) for i in range(len(leaves))
    ]
    return jax.tree_util.tree_unflatten(treedef, new_leaves)


def compacted_solve(
    data,
    w0: Array,
    *,
    task,
    optimizer,
    optimizer_config,
    regularization,
    schedule: SolveSchedule,
    label: str = "re_solve",
    resume: Optional[dict] = None,
) -> OptResult:
    """Solve every lane of ``data = (x, labels, offsets, weights)`` (each
    with leading entity axis E) with chunked, convergence-compacted vmapped
    kernels. Returns the stacked :class:`OptResult` — bitwise-equal to
    ``vmap(solve_one)`` over the same data.

    The loop: init -> chunk on the FULL batch -> pull per-lane reason flags
    -> while any lane is unconverged: gather active lanes onto the ladder
    (only when the rung is strictly smaller than the current batch), chunk
    again, scatter back. Telemetry lands in :data:`solve_stats`.

    Chunk pauses are PREEMPTION drain points: when
    :func:`photon_ml_tpu.resilience.preemption.check` reports a request at
    the ``"chunk"`` site, the loop raises
    :class:`~photon_ml_tpu.resilience.preemption.Preempted` carrying a host
    snapshot of the paused carries; passing that snapshot back as
    ``resume`` continues the solve bitwise-identically (resumed batches
    restart uncompacted and re-compact at the next pause — lane arithmetic
    is batch-independent, so results are unchanged).

    ``schedule.loop == "device"`` routes the solve through the fused
    on-device loop (optim/fused_schedule.py) instead — same bitwise
    results, O(#rungs) host dispatches. The ``optim.device_drain`` fault
    site guards that dispatch: an INJECTED fault degrades THIS solve to
    the host chunk loop below, which recomputes from scratch — lane
    arithmetic is batch-independent, so the degraded results are still
    bitwise. A real compile or runtime error of the fused program raises:
    a run asked for the device loop must not report the host loop's
    behaviour under its name. A device-loop
    :class:`~photon_ml_tpu.resilience.preemption.Preempted` propagates
    with its rung-boundary snapshot intact.
    """
    cfg = dict(
        task=task,
        optimizer=optimizer,
        optimizer_config=optimizer_config,
        regularization=regularization,
    )
    with profiling.span("pml.sched.solve", label=label,
                        lanes=int(w0.shape[0]), loop=schedule.loop):
        if schedule.loop == "device":
            from photon_ml_tpu.optim import fused_schedule
            from photon_ml_tpu.resilience import faults

            try:
                faults.inject(
                    "optim.device_drain", label=label, lanes=int(w0.shape[0])
                )
            except (faults.InjectedIOError, faults.InjectedFatalError) as e:
                logger.warning(
                    "fused device solve (%s): injected fault (%s: %s); "
                    "degrading to the host chunk loop",
                    label, type(e).__name__, e,
                )
            else:
                return fused_schedule.device_solve(
                    data, w0, schedule=schedule, label=label, resume=resume,
                    **cfg,
                )
        return _host_solve(data, w0, cfg, schedule, label, resume)


def _host_solve(data, w0, cfg, schedule, label, resume) -> OptResult:
    """The host chunk loop of :func:`compacted_solve`."""
    lanes = int(w0.shape[0])
    max_iter = cfg["optimizer_config"].max_iterations
    chunk = schedule.chunk_size
    bucketer = schedule.bucketer

    _, _, _, result_of = _lane_fns(**cfg)

    state = _init_batch(data, w0, **cfg)
    chunks: List[ChunkRecord] = []
    executed = 0
    limit = 0
    if resume is not None:
        # the freshly-initialized state is only the structure template;
        # every carried value comes from the snapshot (bitwise round-trip)
        state = _restore_state(state, resume)
        limit = int(resume["meta"]["limit"])
        executed = int(resume["meta"]["executed"])
        chunks = [ChunkRecord(**c) for c in resume["meta"]["chunks"]]

    # current batch bookkeeping: lane_ids maps batch position -> entity
    # lane; the full state is authoritative (compacted chunks scatter back
    # into it at every pause)
    cur_data = data
    cur_state = state
    cur_ids = np.arange(lanes)
    cur_active = (
        int(np.count_nonzero(np.asarray(state.reason) == 0))
        if resume is not None
        else lanes
    )
    compacted = False

    while True:
        prev_limit = limit
        limit = min(limit + chunk, max_iter)
        with profiling.span("pml.sched.chunk", limit=limit,
                            lanes=len(cur_ids), active=cur_active):
            cur_state = _chunk_batch(cur_data, cur_state, jnp.int32(limit), **cfg)
        if compacted:
            with profiling.span("pml.sched.scatter", lanes=len(cur_ids),
                                active=cur_active):
                state = _scatter_batch(
                    state, cur_state, jnp.asarray(cur_ids, jnp.int32),
                    jnp.int32(cur_active),
                )
        else:
            state = cur_state
        # one tiny D2H per chunk: the lane flags + iteration counters that
        # drive compaction and the iteration ledger
        with profiling.span("pml.sched.sync"):
            reasons = np.asarray(state.reason)
            iters = np.asarray(state.iteration)
        advanced = (
            int(min(int(iters.max(initial=0)), limit) - prev_limit)
            if lanes
            else 0
        )
        advanced = max(advanced, 0)
        batch_lanes = len(cur_ids)
        active_idx = np.nonzero(reasons == 0)[0]
        chunks.append(
            ChunkRecord(
                chunk=len(chunks),
                batch_lanes=batch_lanes,
                active_lanes=cur_active,
                limit=limit,
                advanced=advanced,
            )
        )
        executed += batch_lanes * advanced
        if active_idx.size == 0 or limit >= max_iter:
            break
        if preemption.check("chunk", label=label, limit=limit):
            # drain to the chunk boundary: the full state was just
            # scattered back, so a host snapshot of it IS the solve —
            # coordinate descent folds it into the emergency checkpoint
            raise preemption.Preempted(
                f"preempted at chunk boundary ({label}, iteration limit "
                f"{limit}/{max_iter}): {preemption.reason()}",
                site="chunk",
                partial=_snapshot_state(state, label, limit, executed, chunks),
            )
        # compact when the ladder rung genuinely shrinks the batch; once
        # compacted, also re-gather whenever the active SET changed (so
        # newly-frozen lanes stop riding along) — but skip the dispatch
        # entirely when nothing converged this chunk, the common case deep
        # in a straggler tail
        rung = min(bucketer.canon(int(active_idx.size)), lanes)
        if (rung < batch_lanes or compacted) and not np.array_equal(
            active_idx, cur_ids[:cur_active]
        ):
            idx = np.concatenate(
                [active_idx, np.full(rung - active_idx.size, active_idx[0])]
            ).astype(np.int32)
            with profiling.span("pml.sched.gather", lanes=rung,
                                active=int(active_idx.size)):
                cur_data, cur_state = _gather_batch(
                    data, state, jnp.asarray(idx), jnp.int32(active_idx.size)
                )
            cur_ids = idx
            compacted = True
        cur_active = int(active_idx.size)

    max_iteration = int(np.asarray(state.iteration).max(initial=0))
    solve_stats.record(
        SolveRecord(
            label=label,
            lanes=lanes,
            max_iteration=max_iteration,
            executed=executed,
            baseline=lanes * max_iteration,
            chunks=chunks,
        )
    )
    return result_of(state)
