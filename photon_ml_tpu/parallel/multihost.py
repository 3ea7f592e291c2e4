"""Multi-host execution: jax.distributed bring-up, per-host ingest, global
array assembly, and coordinator-gated side effects.

Reference analogue — the driver/executor split (SURVEY.md §3.5,
cli/game/training/Driver.scala:537): Spark's driver JVM partitions input
paths across executors, broadcasts small state, and reduces over the
cluster. TPU-native multi-host is SPMD instead: every host runs the SAME
program under ``jax.distributed``, reads ONLY its slice of the input
(:func:`host_shard_paths` / :func:`host_row_slice`), and assembles globally
sharded arrays with ``jax.make_array_from_process_local_data``. Cross-host
reductions are the same ``psum``s the single-host path uses — XLA routes
them over ICI within a host and DCN across hosts, so no solver code changes
between 1 and N hosts.

Bring-up matrix (initialize()):
  * TPU pods: zero-config — the TPU runtime publishes coordinator/topology
    env vars and ``jax.distributed.initialize()`` discovers them.
  * CPU/GPU clusters (and the 2-process CPU test harness): pass
    coordinator_address/num_processes/process_id explicitly; collectives go
    through the PJRT CPU Gloo backend.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import threading
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from photon_ml_tpu.parallel.mesh import DATA_AXIS, MeshContext, data_mesh

Array = jax.Array

logger = logging.getLogger(__name__)

#: Env override for the barrier deadline (seconds; 0/unset = no deadline).
BARRIER_TIMEOUT_ENV = "PHOTON_BARRIER_TIMEOUT"

HEARTBEAT_PREFIX = "heartbeat-"


class BarrierTimeoutError(OSError):
    """A barrier did not complete within its deadline: converts an infinite
    hang behind a wedged host into a diagnosable failure (check the
    per-host heartbeat ages). Deliberately NOT retried by barrier() itself:
    re-entering ``sync_global_devices`` while the abandoned wait is still
    parked in the collective would desynchronize barrier sequencing across
    hosts — the recovery path is the restart supervisor, not a retry."""


def resolve_barrier_timeout(timeout: Optional[float]) -> Optional[float]:
    """Effective barrier deadline: explicit value wins; ``None`` falls back
    to ``PHOTON_BARRIER_TIMEOUT``; 0/absent means no deadline."""
    if timeout is not None:
        return timeout if timeout > 0 else None
    raw = os.environ.get(BARRIER_TIMEOUT_ENV)
    if not raw:
        return None
    try:
        val = float(raw)
    except ValueError:
        raise ValueError(
            f"{BARRIER_TIMEOUT_ENV} must be a number of seconds, got {raw!r}"
        )
    return val if val > 0 else None


def _call_with_deadline(fn, timeout: float, describe: str) -> None:
    """Run ``fn`` on a worker thread, raising :class:`BarrierTimeoutError`
    if it does not return within ``timeout`` seconds. The hung worker is a
    daemon and is left behind — a blocked collective cannot be cancelled,
    only diagnosed; retrying after its eventual completion is the caller's
    (retry policy's) judgement call."""
    done = threading.Event()
    box: List[BaseException] = []

    def run():
        try:
            fn()
        except BaseException as e:  # noqa: BLE001 — crossing the thread
            # boundary; re-raised below in the caller
            box.append(e)
        finally:
            done.set()

    t = threading.Thread(target=run, name=f"barrier-{describe}", daemon=True)
    t.start()
    if not done.wait(timeout):
        raise BarrierTimeoutError(
            f"{describe} did not complete within {timeout:g}s — a peer host "
            "is likely wedged, preempted, or dead; check the per-host "
            "heartbeat ages in the coordinator log"
        )
    if box:
        raise box[0]


def write_host_heartbeat(
    directory: str, host_id: int, step: Optional[int] = None
) -> str:
    """Atomic heartbeat write for one (logical or physical) host id —
    tmp+rename through the retry machinery, fault site
    ``multihost.heartbeat``. The file format is shared by the per-process
    beats (:meth:`MultihostContext.write_heartbeat`) and the per-logical-
    owner beats of elastic re-sharding (parallel/elastic.py), so one
    ``describe_heartbeats``-style reader diagnoses both."""
    from photon_ml_tpu import resilience
    from photon_ml_tpu.resilience import faults

    path = os.path.join(directory, f"{HEARTBEAT_PREFIX}{int(host_id)}.json")

    def write_once() -> None:
        faults.inject("multihost.heartbeat", process=int(host_id), path=path)
        os.makedirs(directory, exist_ok=True)
        payload = {
            "process": int(host_id),
            "time": time.time(),
            "step": step,
        }
        with open(path + ".tmp", "w") as f:
            json.dump(payload, f)
        os.replace(path + ".tmp", path)

    resilience.call_with_retry(
        write_once,
        resilience.current_config().io_policy,
        describe=f"heartbeat host {host_id}",
    )
    return path


def read_heartbeat_ages(directory: str) -> Dict[int, float]:
    """host id -> seconds since its last heartbeat (missing hosts absent
    from the map). Read-only, best-effort: unreadable beats are logged and
    skipped."""
    ages: Dict[int, float] = {}
    if not os.path.isdir(directory):
        return ages
    now = time.time()
    for name in sorted(os.listdir(directory)):
        if not name.startswith(HEARTBEAT_PREFIX) or not name.endswith(".json"):
            continue
        try:
            with open(os.path.join(directory, name)) as f:
                payload = json.load(f)
            ages[int(payload["process"])] = now - float(payload["time"])
        except (OSError, ValueError, KeyError) as e:
            logger.warning("unreadable heartbeat %s: %s", name, e)
    return ages


def lost_hosts(
    ages: Dict[int, float],
    expected: Sequence[int],
    deadline: float,
    missing_grace_elapsed: Optional[float] = None,
) -> List[int]:
    """Heartbeat-driven loss detection with a deadline: the expected hosts
    whose last beat is older than ``deadline`` seconds. A host MISSING from
    ``ages`` entirely (never beat) only counts as lost once
    ``missing_grace_elapsed`` (the observer's own uptime) exceeds the
    deadline — otherwise a slow-starting peer would be declared dead at
    the first poll. Pure function of its inputs so detection is unit-
    testable without wall-clock sleeps (parallel/elastic.py drives it)."""
    lost: List[int] = []
    for h in sorted(int(x) for x in expected):
        age = ages.get(h)
        if age is None:
            if (missing_grace_elapsed is not None
                    and missing_grace_elapsed > deadline):
                lost.append(h)
        elif age > deadline:
            lost.append(h)
    return lost


def initialize(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    local_device_count: Optional[int] = None,
) -> "MultihostContext":
    """Bring up jax.distributed (idempotent) and return the process context.

    With no arguments, relies on the TPU pod runtime's automatic discovery;
    on CPU/GPU test clusters pass all three of coordinator/num/process-id.
    """
    if (num_processes is not None and num_processes > 1) or coordinator_address:
        if not jax.distributed.is_initialized():
            kwargs = {}
            if local_device_count is not None:
                # spelled local_device_ids in this jax version
                kwargs["local_device_ids"] = list(range(local_device_count))
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id,
                **kwargs,
            )
    return MultihostContext(
        process_id=jax.process_index(), num_processes=jax.process_count()
    )


@dataclasses.dataclass(frozen=True)
class MultihostContext:
    """This process's coordinates in the job + global-array assembly."""

    process_id: int
    num_processes: int

    @property
    def is_coordinator(self) -> bool:
        return self.process_id == 0

    # -- topology ------------------------------------------------------
    def mesh_context(self, axis: str = DATA_AXIS) -> MeshContext:
        """MeshContext over ALL global devices (local + remote): the mesh's
        device order is process-major, so a P(axis) sharding assigns each
        host a contiguous row block — exactly the block host_row_slice
        ingests."""
        return MeshContext(data_mesh(axis=axis))

    # -- per-host ingest -----------------------------------------------
    def host_shard_paths(self, paths: Sequence[str]) -> List[str]:
        """Deterministic round-robin assignment of input files to hosts
        (the analogue of Spark assigning HDFS splits to executors)."""
        return [p for i, p in enumerate(sorted(paths)) if i % self.num_processes == self.process_id]

    def rows_per_host(self, n_global: int, ctx: Optional[MeshContext] = None) -> int:
        """Uniform per-host row-block size: ceil over hosts, then rounded up
        to a multiple of this host's local device count (so the global
        sharding divides evenly). The tail host's shortfall is covered by
        weight-0 padding in :meth:`global_row_sharded`."""
        per = -(-n_global // self.num_processes)
        if ctx is not None:
            local = max(ctx.num_devices // self.num_processes, 1)
            per = -(-per // local) * local
        return per

    def host_row_slice(self, n_global: int, ctx: Optional[MeshContext] = None) -> slice:
        """This host's contiguous row block of a conceptually global
        (n_global, ...) dataset. Blocks are uniform-size (rows_per_host);
        the tail host's slice may be SHORT — global_row_sharded pads it
        back to uniform with zero rows (mark them weight 0)."""
        per = self.rows_per_host(n_global, ctx)
        lo = min(self.process_id * per, n_global)
        hi = min(lo + per, n_global)
        return slice(lo, hi)

    # -- global array assembly -----------------------------------------
    def global_row_sharded(
        self,
        host_local: np.ndarray,
        ctx: MeshContext,
        n_global: Optional[int] = None,
    ) -> Array:
        """Assemble a globally row-sharded jax.Array from this host's local
        rows. Every host contributes its block; no host ever materializes
        the global array. Local row counts must be uniform across hosts —
        pass ``n_global`` to zero-pad a short tail block (from
        host_row_slice on a non-divisible n) up to rows_per_host; padding
        rows must carry weight 0 so they contribute nothing."""
        if n_global is not None:
            per = self.rows_per_host(n_global, ctx)
            short = per - host_local.shape[0]
            if short > 0:
                pad = np.zeros((short,) + host_local.shape[1:], host_local.dtype)
                host_local = np.concatenate([host_local, pad])
        sharding = NamedSharding(ctx.mesh, P(ctx.axis))
        return jax.make_array_from_process_local_data(sharding, host_local)

    def global_replicated(self, host_local: np.ndarray, ctx: MeshContext) -> Array:
        """Replicate identical per-host data globally (Spark broadcast)."""
        sharding = NamedSharding(ctx.mesh, P())
        return jax.make_array_from_process_local_data(sharding, host_local)

    # -- coordination ----------------------------------------------------
    def barrier(
        self, name: str = "photon-ml-tpu-barrier", timeout: Optional[float] = None
    ) -> None:
        """Block until every process reaches this point (checkpoint fences,
        output-dir creation). No-op single-process.

        Barrier *entry* is a fault-injection site (``multihost.barrier``)
        retried under the active I/O policy — the injected failure fires
        before the collective, so a retry is safe (the sync itself is never
        re-entered after succeeding). Chaos tests use this to prove the
        checkpoint fences survive transient coordination failures.

        ``timeout`` (default: ``PHOTON_BARRIER_TIMEOUT``) is the health
        fence: a ``sync_global_devices`` that outlives the deadline raises
        :class:`BarrierTimeoutError` instead of hanging the job forever
        behind one wedged host. The timeout is NOT retried (only the
        pre-collective entry faults are): the abandoned wait is still
        parked inside the collective, so re-entering it would desync
        barrier sequencing across hosts — a timed-out barrier is
        diagnose-and-fail (heartbeats name the wedged host), and recovery
        is the restart supervisor's job.
        """
        from photon_ml_tpu import resilience
        from photon_ml_tpu.resilience import faults

        deadline = resolve_barrier_timeout(timeout)

        def enter() -> None:
            # single-process still exercises the fault site, so chaos
            # tests run without a multi-host harness; the injected failure
            # fires BEFORE the collective, so retrying it is safe
            faults.inject("multihost.barrier", name=name, process=self.process_id)

        resilience.call_with_retry(
            enter,
            resilience.current_config().io_policy,
            describe=f"barrier {name}",
        )
        if self.num_processes > 1:
            from jax.experimental import multihost_utils

            sync = lambda: multihost_utils.sync_global_devices(name)
            if deadline is None:
                sync()
            else:
                _call_with_deadline(
                    sync, deadline,
                    f"barrier {name!r} (process {self.process_id})",
                )

    # -- health fencing --------------------------------------------------
    def agree_restore_step(self, local_step: Optional[int]) -> Optional[int]:
        """Collective MIN over every host's latest complete checkpoint step:
        the job resumes from the newest step EVERY host can restore, so no
        host resumes a step another host failed to commit (per-host
        checkpoint dirs, torn shared-FS writes). ``None`` (no checkpoint on
        this host) participates as -1; a -1 minimum means fresh start."""
        if self.num_processes <= 1:
            return local_step
        from jax.experimental import multihost_utils

        local = np.asarray([local_step if local_step is not None else -1], np.int64)
        gathered = np.asarray(
            multihost_utils.process_allgather(local, tiled=True)
        ).reshape(-1)
        agreed = int(gathered.min())
        if agreed != (local_step if local_step is not None else -1):
            logger.warning(
                "host %d: restoring step %s instead of local latest %s "
                "(collective-min agreement; per-host steps %s)",
                self.process_id, agreed if agreed >= 0 else None, local_step,
                gathered.tolist(),
            )
        return agreed if agreed >= 0 else None

    def write_heartbeat(
        self, directory: str, step: Optional[int] = None,
        host_id: Optional[int] = None,
    ) -> str:
        """Write this host's heartbeat file (atomic tmp+rename, retried;
        fault site ``multihost.heartbeat``). Every host calls this at its
        safe boundaries; the coordinator reads the ages back with
        :meth:`heartbeat_ages` so a wedged host is diagnosable by name.
        ``host_id`` overrides the beat's identity — a process hosting
        several LOGICAL owners (elastic re-sharding, parallel/elastic.py)
        beats once per owner it carries."""
        return write_host_heartbeat(
            directory,
            self.process_id if host_id is None else host_id,
            step=step,
        )

    def heartbeat_ages(self, directory: str) -> Dict[int, float]:
        """process id -> seconds since its last heartbeat (missing hosts
        absent from the map — a host that NEVER beat is the loudest
        diagnosis of all). Read-only; any host may call it, the coordinator
        logs it."""
        return read_heartbeat_ages(directory)

    def describe_heartbeats(self, directory: str) -> str:
        """Coordinator-log line: per-host heartbeat age (and who is MISSING
        entirely) — the first thing to read when a barrier times out."""
        ages = self.heartbeat_ages(directory)
        parts = []
        for pid in range(self.num_processes):
            if pid in ages:
                parts.append(f"host {pid}: {ages[pid]:.1f}s ago")
            else:
                parts.append(f"host {pid}: NO HEARTBEAT")
        return "heartbeats: " + ", ".join(parts)

    def coordinator_only_io(self) -> bool:
        """True when this process should perform global side effects (model
        save, log upload) — the PhotonLogger-on-driver analogue."""
        return self.is_coordinator


# Multi-host RANDOM-EFFECT ingest lives in photon_ml_tpu.parallel
# .perhost_ingest: each host decodes only its input partitions and the
# collective shuffle (parallel.shuffle) regroups rows by entity owner —
# no host ever builds the global dataset. (The earlier multihost_re_dataset
# helper, which sliced per-host slabs out of a replicated host-side build,
# was deleted when the true per-host path landed.)
