"""Per-host streaming coordinate descent: the billion-coefficient path.

The single-host streaming coordinate (algorithm/streaming_random_effect.py)
scales past device memory but was fenced off from the mesh; this module
lifts the fence with **owner-computes random-effect solves over a globally
agreed entity blocking**:

  1. every host derives the IDENTICAL entity blocking from collectively
     merged per-entity counts (:func:`plan_entity_blocks` — the exact
     single-host blocking, so block composition is host-count invariant);
  2. whole blocks are assigned to hosts by deterministic balanced
     bin-packing (``balanced_bucket_owners`` over block costs);
  3. each host's ingested rows are routed ONCE to their entity's block
     owner with one ``all_to_all`` (``shuffle.route_rows_to_hosts``) —
     never again per iteration (Spark's shuffle-per-pass is the
     anti-pattern, arXiv:1612.01437);
  4. the owner builds ONLY its blocks through the single-host Avro-decode →
     tensor-cache → prefetch → shape-ladder block-solve pipeline
     (:func:`build_block_payload` — byte-identical block files), and
     streams them per coordinate update;
  5. scores stay host-local (each host holds its own rows) and merge with
     one exact reduction (:func:`merge_disjoint`: every row is written by
     exactly one host, so the psum adds each value to zeros — the IEEE
     identity), which is also how the fixed-effect coordinate's chunk
     partials merge (optim/streaming.make_perhost_value_and_grad).

Because block composition, block tensor bytes, per-block solves, and every
cross-host reduction are exact, an N-process run is **bitwise-equal to the
single-host streaming run on the same data** — pinned by the 2-process
harness (tests/test_perhost_streaming.py). The same invariance is what
makes the fleet ELASTIC (parallel/elastic.py): the blocking never depends
on membership, so a membership change re-runs only the deterministic
balanced owner assignment (:meth:`EntityShardPlan.replan`), moves ONLY the
delta blocks as file copies, and resumes bitwise-equal to a fresh run on
the new topology. DrJAX (arXiv:2403.07128) showed
the MapReduce framing maps onto JAX collectives; Snap ML (arXiv:1803.06333)
showed hierarchical local-solve + reduce wins for exactly this workload —
per-entity solves are embarrassingly parallel once each entity's rows live
on one host.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import logging
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from photon_ml_tpu.algorithm.streaming_random_effect import (
    SpilledREState,
    StreamingREManifest,
    StreamingRandomEffectCoordinate,
    build_block_payload,
    plan_entity_blocks,
    write_block_file,
)
from photon_ml_tpu.data.game import GameData, HostFeatures, RandomEffectDataConfig
from photon_ml_tpu.parallel.mesh import MeshContext
from photon_ml_tpu.parallel.perhost_ingest import HostRows, _pad_to
from photon_ml_tpu.parallel.shuffle import (
    balanced_owners_over_hosts,
    collective_max,
    collective_sum,
    route_rows_to_hosts,
)
from photon_ml_tpu.types import real_dtype

Array = jax.Array

logger = logging.getLogger(__name__)

# fixed-width UTF-8 raw entity ids for the vocabulary agreement collective
# (same format/limit as the ingest exchange, perhost_ingest.RAW_ID_BYTES)
RAW_ID_BYTES = 48


# ---------------------------------------------------------------------------
# exact cross-host merges
# ---------------------------------------------------------------------------


def merge_disjoint(arr: np.ndarray, ctx: Optional[MeshContext],
                   num_processes: int) -> np.ndarray:
    """Exact cross-host sum of an array whose every element is written by at
    most ONE host (zeros elsewhere): ``x + 0`` is the IEEE identity, so the
    reduction is bitwise-exact regardless of host count or reduction order.
    float32 rides one psum over the mesh (``collective_sum``); other dtypes
    (the float64 regularization terms — a device psum would silently
    truncate them without x64) allgather and fold host-side in process
    order, which is equally exact for disjoint writes.

    Fault site ``multihost.streaming_reduce`` fires before the collective —
    also single-process, so chaos plans cover the reduction boundary
    without a multi-host harness; the injected (pre-collective) failure is
    retried under the active I/O policy, the collective itself never is.
    """
    from photon_ml_tpu import resilience
    from photon_ml_tpu.resilience import faults

    a = np.asarray(arr)

    def enter() -> None:
        faults.inject(
            "multihost.streaming_reduce",
            shape=tuple(a.shape), processes=num_processes,
        )

    resilience.call_with_retry(
        enter, resilience.current_config().io_policy,
        describe="streaming reduce",
    )
    if num_processes <= 1:
        return a.copy()
    if a.dtype == np.float32:
        flat = collective_sum(a.reshape(-1), ctx, num_processes)
        return np.asarray(flat, np.float32).reshape(a.shape)
    from jax.experimental import multihost_utils

    flat = a.reshape(-1)
    # x64 for the transport: process_allgather device_puts the host array,
    # and WITHOUT x64 that canonicalizes float64 -> float32 — exactly the
    # truncation this branch exists to avoid (same rule as the int64
    # reduces in shuffle._collective_reduce)
    with jax.enable_x64():
        gathered = np.asarray(
            multihost_utils.process_allgather(flat, tiled=True)
        ).reshape(num_processes, -1)
    if gathered.dtype != flat.dtype:
        raise TypeError(
            f"exact merge transport changed dtype {flat.dtype} -> "
            f"{gathered.dtype}; the disjoint-sum exactness argument "
            "requires value-preserving transport"
        )
    out = np.zeros_like(flat)
    for p in range(num_processes):
        out = out + gathered[p]
    return out.reshape(a.shape)


def merge_disjoint_devices(shards, ctx: MeshContext) -> np.ndarray:
    """The multi-device-single-host form of :func:`merge_disjoint`: exact
    merge of per-DEVICE disjoint partials over a local device mesh with
    ONE in-program ``shard_map`` + ``lax.psum`` — no file barrier, no Gloo
    process group, no host-side fold at all (the DrJAX mapped-reduce
    framing, arXiv:2403.07128). ``shards`` is ``(n_dev, ...)`` with every
    element written by at most one device (zeros elsewhere), so the psum
    adds each value to zeros — the IEEE identity — and the result is
    bitwise-equal to merge_disjoint's host-side fold of the same
    partials, on any device count and in any reduction order.

    The mesh is typically the FORCED CPU mesh
    (``compat.force_cpu_devices`` /
    ``--xla_force_host_platform_device_count``) standing in for a real
    accelerator mesh on a dev box; the same fault site as the host merge
    (``multihost.streaming_reduce``) fires before the collective, so one
    chaos plan covers both merge paths.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from photon_ml_tpu import resilience
    from photon_ml_tpu.resilience import faults

    a = np.asarray(shards)
    n = ctx.num_devices
    if a.ndim < 1 or a.shape[0] != n:
        raise ValueError(
            f"merge_disjoint_devices wants one leading shard per mesh "
            f"device: got shape {a.shape} on a {n}-device mesh"
        )

    def enter() -> None:
        faults.inject(
            "multihost.streaming_reduce",
            shape=tuple(a.shape), processes=n, path="device",
        )

    resilience.call_with_retry(
        enter, resilience.current_config().io_policy,
        describe="device streaming reduce",
    )
    if n == 1:
        return a[0].copy()
    g = jax.device_put(a, NamedSharding(ctx.mesh, P(ctx.axis)))
    merged = jax.jit(  # jit-ok: one-shot exact-merge collective, inputs are live partials (nothing to donate)
        jax.shard_map(
            lambda s: jax.lax.psum(s[0], ctx.axis),
            mesh=ctx.mesh, in_specs=P(ctx.axis), out_specs=P(),
        )
    )(g)
    return np.asarray(jax.device_get(merged))


def agree_entity_counts(
    raw_ids: Sequence[str],
    ctx: Optional[MeshContext],
    num_processes: int = 1,
) -> Tuple[List[str], np.ndarray]:
    """Globally agreed ``(vocab, counts)``: the sorted union of every
    host's raw entity ids (exactly the ``sorted(set(...))`` vocabulary a
    single-host decode of the full data produces — io/avro_data.py) and the
    merged (V,) int64 per-entity row counts, identical on every host.
    Metadata-scale collective: one allgather of (unique ids x 48B + counts)
    per coordinate, once per run — never per iteration."""
    uniq, counts = np.unique(np.asarray(list(raw_ids), dtype=object),
                             return_counts=True)
    if num_processes <= 1:
        return [str(u) for u in uniq], counts.astype(np.int64)
    from jax.experimental import multihost_utils

    n_local = len(uniq)
    rows_max = int(collective_max(
        np.asarray([n_local], np.int64), ctx, num_processes
    )[0])
    rows_max = max(rows_max, 1)
    raw_bytes = np.zeros((rows_max, RAW_ID_BYTES), np.uint8)
    cnt_pad = np.zeros((rows_max,), np.int32)
    for i, rid in enumerate(uniq):
        b = str(rid).encode("utf-8")
        if len(b) > RAW_ID_BYTES:
            raise ValueError(
                f"entity id {rid!r} exceeds {RAW_ID_BYTES} UTF-8 bytes"
            )
        raw_bytes[i, : len(b)] = np.frombuffer(b, np.uint8)
    cnt_pad[:n_local] = counts.astype(np.int32)
    g_raw = np.asarray(multihost_utils.process_allgather(
        raw_bytes.view(np.int32), tiled=True
    )).reshape(num_processes * rows_max, -1)
    g_cnt = np.asarray(multihost_utils.process_allgather(
        cnt_pad, tiled=True
    )).reshape(-1)
    keep = g_cnt > 0
    all_ids = [
        bytes(row).rstrip(b"\x00").decode("utf-8")
        for row in g_raw[keep].view(np.uint8)
    ]
    merged, inv = np.unique(np.asarray(all_ids, dtype=object),
                            return_inverse=True)
    g_counts = np.bincount(
        inv, weights=g_cnt[keep].astype(np.float64), minlength=len(merged)
    ).astype(np.int64)
    return [str(u) for u in merged], g_counts


# ---------------------------------------------------------------------------
# the global plan (blocking + block -> owner host)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class EntityShardPlan:
    """The globally agreed entity blocking and block->owner assignment —
    deterministic from (counts, config, owner-host set) alone, so every
    host derives the identical plan with no extra collective.

    VERSIONED and RE-PLANNABLE (elastic re-sharding, parallel/elastic.py):
    the blocking itself is a pure function of the per-entity counts — it
    never changes with membership — so :meth:`replan` keeps the blocks and
    re-runs only the deterministic balanced owner assignment over the new
    host set. ``owners`` holds LOGICAL owner ids (the unit of elasticity);
    a :class:`~photon_ml_tpu.parallel.elastic.FleetMembership` binds them
    to physical processes. The default (``hosts=None``) is the identity
    over ``range(num_processes)`` — byte-identical to the pre-versioned
    plans."""

    blocks: List[np.ndarray]  # per block: sorted dense entity ids
    owners: np.ndarray  # (n_blocks,) int32 owner HOST (logical) per block
    block_of_vocab: np.ndarray  # (V,) int32 owning block per entity, -1 absent
    num_entities: int  # present entities across all blocks
    num_processes: int
    version: int = 1
    hosts: Optional[List[int]] = None  # logical owner ids; None = identity
    block_costs: Optional[np.ndarray] = None  # (n_blocks,) int64 solve cost
    # fixed-effect CHUNK ownership, versioned WITH the plan: one LOGICAL
    # owner per global FE chunk (chunk c is input file c), so FE work
    # re-bases across a re-plan exactly the way RE blocks do instead of
    # being pinned to the physical process that first decoded the file.
    # None on plans that never attached chunks (pre-FE-ownership sidecars
    # fall back to the physical host_file_share split).
    fe_chunk_owners: Optional[np.ndarray] = None  # (n_chunks,) int32 logical
    fe_chunk_costs: Optional[np.ndarray] = None  # (n_chunks,) int64 row cost

    @classmethod
    def build(
        cls,
        counts: np.ndarray,
        num_processes: int,
        *,
        global_dim: int,
        active_upper_bound: Optional[int] = None,
        block_entities: Optional[int] = None,
        memory_budget_bytes: Optional[int] = None,
        hosts: Optional[Sequence[int]] = None,
        version: int = 1,
    ) -> "EntityShardPlan":
        counts = np.asarray(counts)
        blocks = plan_entity_blocks(
            counts,
            global_dim=global_dim,
            active_upper_bound=active_upper_bound,
            block_entities=block_entities,
            memory_budget_bytes=memory_budget_bytes,
        )
        cap = active_upper_bound or (int(counts.max()) if counts.sum() else 1)
        # block cost ~ active rows it will solve; the greedy min-heap
        # bin-packing is the RandomEffectIdPartitioner analogue at block
        # granularity (deterministic on every host). Persisted in the plan
        # sidecar so a RE-plan re-balances without re-deriving counts.
        costs = np.asarray(
            [int(np.minimum(counts[b], cap).sum()) for b in blocks], np.int64
        )
        host_list = (
            sorted(int(h) for h in hosts) if hosts is not None
            else list(range(max(num_processes, 1)))
        )
        owners = balanced_owners_over_hosts(costs, host_list)
        block_of = np.full(len(counts), -1, np.int32)
        for gi, ents in enumerate(blocks):
            block_of[ents] = gi
        return cls(
            blocks=blocks,
            owners=owners.astype(np.int32),
            block_of_vocab=block_of,
            num_entities=int((counts > 0).sum()),
            num_processes=max(num_processes, 1),
            version=int(version),
            hosts=host_list,
            block_costs=costs,
        )

    def host_list(self) -> List[int]:
        return (list(self.hosts) if self.hosts is not None
                else list(range(self.num_processes)))

    def with_fe_chunks(self, chunk_costs: Sequence[int],
                       owners: Optional[Sequence[int]] = None
                       ) -> "EntityShardPlan":
        """Attach fixed-effect chunk ownership: by default the same
        deterministic balanced assignment the RE blocks use, over per-chunk
        row counts. A fresh run instead passes the EXPLICIT ``owners`` its
        decode actually used (the physical ``host_file_share`` split), so
        the recorded v1 ownership matches the chunks each host already
        holds — the balanced re-assignment only kicks in at
        :meth:`replan`, when ownership must move anyway. Chunk composition
        (chunk c = input file c) is membership-invariant just like block
        composition, so replan re-bases it."""
        costs = np.asarray([int(c) for c in chunk_costs], np.int64)
        if owners is None:
            fe_owners = balanced_owners_over_hosts(costs, self.host_list())
        else:
            fe_owners = np.asarray([int(o) for o in owners], np.int32)
            if len(fe_owners) != len(costs):
                raise ValueError(
                    f"FE chunk owners ({len(fe_owners)}) and costs "
                    f"({len(costs)}) disagree on the chunk count"
                )
        return dataclasses.replace(
            self,
            fe_chunk_owners=fe_owners.astype(np.int32),
            fe_chunk_costs=costs,
        )

    def owned_fe_chunks(self, process_id: int,
                        membership=None) -> List[int]:
        """Global FE chunk ids this PHYSICAL process hosts under the plan
        (logical owners resolved through ``membership``; identity when
        None). Raises if the plan never attached chunk ownership — the
        caller must fall back to the physical file share."""
        if self.fe_chunk_owners is None:
            raise ValueError(
                "plan carries no FE chunk ownership (pre-FE-ownership "
                "sidecar) — fall back to the physical host_file_share"
            )
        if membership is None:
            return [c for c in range(len(self.fe_chunk_owners))
                    if int(self.fe_chunk_owners[c]) == process_id]
        phys = membership.physical_owners(self.fe_chunk_owners)
        return [c for c in range(len(self.fe_chunk_owners))
                if int(phys[c]) == process_id]

    def replan(self, hosts: Sequence[int],
               version: Optional[int] = None,
               observed_costs: Optional[Dict[int, float]] = None
               ) -> "EntityShardPlan":
        """The same blocking re-assigned over a NEW owner-host set: blocks
        are untouched (block composition is membership-invariant — the
        bitwise foundation), only the deterministic balanced owner map
        re-runs. Every survivor derives the identical v+1 plan.

        ``observed_costs`` (gid -> realized lane-iterations per visit,
        from the convergence ledger, optim/convergence.py) replaces the
        static row-count proxy for the blocks it covers, so hot blocks
        spread across owners instead of balancing by count — skew-aware
        rebalancing. The effective costs are persisted as the new plan's
        ``block_costs`` (the sidecars record what was actually balanced).
        Owner assignment never touches block arithmetic, so a re-plan with
        observed costs stays bitwise-pinned vs a fresh run on the same
        assignment. None (the default) is byte-identical to the static
        re-plan."""
        if self.block_costs is None:
            raise ValueError(
                "plan carries no block costs (pre-versioned sidecar) — "
                "cannot re-plan; rebuild the manifest instead"
            )
        host_list = sorted(int(h) for h in hosts)
        block_costs = self.block_costs
        if observed_costs:
            eff = np.asarray(block_costs, np.int64).copy()
            for g, c in observed_costs.items():
                g = int(g)
                if 0 <= g < len(eff) and c > 0:
                    # ceil so a tiny-but-hot block never rounds to 0 cost
                    eff[g] = max(int(np.ceil(float(c))), 1)
            block_costs = eff
        owners = balanced_owners_over_hosts(block_costs, host_list)
        fe_owners = self.fe_chunk_owners
        if self.fe_chunk_costs is not None:
            # FE chunks re-base the same way: costs are membership-
            # invariant, only the balanced owner map re-runs
            fe_owners = balanced_owners_over_hosts(
                self.fe_chunk_costs, host_list
            ).astype(np.int32)
        return dataclasses.replace(
            self,
            owners=owners.astype(np.int32),
            hosts=host_list,
            version=self.version + 1 if version is None else int(version),
            block_costs=block_costs,
            fe_chunk_owners=fe_owners,
        )

    def moved_blocks(self, new_plan: "EntityShardPlan",
                     old_membership, new_membership
                     ) -> List[Tuple[int, int, int]]:
        """The DELTA between two plan versions at physical granularity:
        ``(block gid, old physical owner, new physical owner)`` for every
        block whose hosting process changes — exactly the file copies an
        elastic re-shard performs (everything else stays put)."""
        old_phys = old_membership.physical_owners(self.owners)
        new_phys = new_membership.physical_owners(new_plan.owners)
        return [
            (gi, int(old_phys[gi]), int(new_phys[gi]))
            for gi in range(len(self.owners))
            if old_phys[gi] != new_phys[gi]
        ]

    @classmethod
    def from_sidecars(cls, dir_path: str) -> Optional["EntityShardPlan"]:
        """Reconstruct the FULL plan from a manifest dir's sidecars (the
        block entity lists fall out of ``block_of_vocab`` — blocks store
        sorted dense ids, which is exactly what the inverse map yields).
        None for pre-versioned layouts (no plan.json). This is what the
        elastic session re-plans FROM, so the replan()/moved_blocks()
        methods the unit tests pin are the methods production executes."""
        meta, owners, block_of = load_plan_sidecars(dir_path)
        if meta is None:
            return None
        n_blocks = len(owners)
        present = np.nonzero(block_of >= 0)[0]
        order = present[np.argsort(block_of[present], kind="stable")]
        bounds = np.searchsorted(block_of[order], np.arange(n_blocks + 1))
        blocks = [
            np.sort(order[bounds[g]:bounds[g + 1]]).astype(np.int64)
            for g in range(n_blocks)
        ]
        fe_owners = meta.get("fe_chunk_owners")
        fe_costs = meta.get("fe_chunk_costs")
        return cls(
            blocks=blocks,
            owners=owners.astype(np.int32),
            block_of_vocab=block_of.astype(np.int32),
            num_entities=int(meta["num_entities"]),
            num_processes=int(meta.get("num_processes", 1)),
            version=int(meta["version"]),
            hosts=[int(h) for h in meta["hosts"]],
            block_costs=np.asarray(meta["block_costs"], np.int64),
            fe_chunk_owners=(None if fe_owners is None
                             else np.asarray(fe_owners, np.int32)),
            fe_chunk_costs=(None if fe_costs is None
                            else np.asarray(fe_costs, np.int64)),
        )

    def owned_block_ids(self, process_id: int,
                        membership=None) -> List[int]:
        if membership is None:
            return [gi for gi in range(len(self.blocks))
                    if int(self.owners[gi]) == process_id]
        phys = membership.physical_owners(self.owners)
        return [gi for gi in range(len(self.blocks))
                if int(phys[gi]) == process_id]


# ---------------------------------------------------------------------------
# per-host manifest (owned blocks of a global blocking)
# ---------------------------------------------------------------------------


_PLAN_BLOCK_OF = "plan-block-of.npy"
_PLAN_OWNERS = "plan-owners.npy"
_PLAN_META = "plan.json"


def _plan_array_sha(arr: np.ndarray) -> str:
    return hashlib.sha256(
        np.ascontiguousarray(np.asarray(arr, np.int32)).tobytes()
    ).hexdigest()


def write_plan_sidecars(
    dir_path: str,
    owners: np.ndarray,
    block_of: np.ndarray,
    *,
    version: int,
    hosts: Sequence[int],
    binding: Dict[int, int],
    block_costs: np.ndarray,
    num_entities: int,
    num_processes: int = 1,
    fe_chunk_owners: Optional[np.ndarray] = None,
    fe_chunk_costs: Optional[np.ndarray] = None,
) -> None:
    """Persist the plan next to the blocks: the two routing arrays plus
    ``plan.json`` — version, logical host set, logical->physical binding,
    the per-block costs a re-plan re-balances over, and (when attached)
    the fixed-effect chunk ownership that re-bases alongside the blocks.
    Everything an elastic session (or a relaunched cohort restoring a v1
    checkpoint under v2) needs is durable and addressable here."""
    # tmp+rename like every other commit on this path: an elastic re-base
    # OVERWRITES live sidecars, and a crash mid-np.save must never leave a
    # torn owners array next to the previous version's plan.json. The
    # arrays land FIRST and plan.json is the COMMIT POINT: it records the
    # arrays' digests, so a crash between the three renames (new arrays,
    # old plan.json) is detected as a tear by load/from_sidecars instead
    # of silently mixing plan versions.
    block_of = np.asarray(block_of, np.int32)
    owners = np.asarray(owners, np.int32)
    for name, arr in ((_PLAN_BLOCK_OF, block_of), (_PLAN_OWNERS, owners)):
        tmp_npy = os.path.join(dir_path, name + ".tmp.npy")
        np.save(tmp_npy, arr)
        os.replace(tmp_npy, os.path.join(dir_path, name))
    meta = {
        "version": int(version),
        "hosts": [int(h) for h in hosts],
        "binding": {str(h): int(p) for h, p in binding.items()},
        "block_costs": [int(c) for c in np.asarray(block_costs)],
        "num_entities": int(num_entities),
        "num_processes": int(num_processes),
        "owners_sha": _plan_array_sha(owners),
        "block_of_sha": _plan_array_sha(block_of),
    }
    if fe_chunk_owners is not None:
        meta["fe_chunk_owners"] = [int(o) for o in np.asarray(fe_chunk_owners)]
        meta["fe_chunk_costs"] = [
            int(c) for c in np.asarray(
                fe_chunk_costs if fe_chunk_costs is not None
                else np.zeros(len(meta["fe_chunk_owners"]), np.int64)
            )
        ]
    tmp = os.path.join(dir_path, _PLAN_META + ".tmp")
    with open(tmp, "w") as f:
        json.dump(meta, f)
    os.replace(tmp, os.path.join(dir_path, _PLAN_META))


def load_plan_sidecars(
    dir_path: str,
) -> Tuple[Optional[dict], np.ndarray, np.ndarray]:
    """(plan meta or None for pre-versioned layouts, owners, block_of)."""
    owners = np.load(os.path.join(dir_path, _PLAN_OWNERS))
    block_of = np.load(os.path.join(dir_path, _PLAN_BLOCK_OF))
    meta_path = os.path.join(dir_path, _PLAN_META)
    meta = None
    if os.path.exists(meta_path):
        with open(meta_path) as f:
            meta = json.load(f)
        want = meta.get("owners_sha")
        if want is not None and (
            want != _plan_array_sha(owners)
            or meta.get("block_of_sha") != _plan_array_sha(block_of)
        ):
            # a crash between the three sidecar renames: the arrays and
            # plan.json belong to DIFFERENT plan versions — loudly refuse
            # rather than compute an empty delta from mixed state
            raise ValueError(
                f"plan sidecars in {dir_path} are torn (array digests do "
                "not match plan.json) — a re-base crashed mid-commit; "
                "rebuild this host's manifest (supervised relaunch "
                "re-ingests)"
            )
    return meta, owners, block_of


def attach_fe_chunks_to_sidecars(
    dir_path: str,
    fe_chunk_owners: Sequence[int],
    fe_chunk_costs: Sequence[int],
) -> None:
    """Record fixed-effect chunk ownership into ALREADY-COMMITTED plan
    sidecars (idempotent re-commit through :func:`write_plan_sidecars`, so
    the digest/commit-point discipline holds). The fresh-run driver calls
    this after decode: the manifest build committed the plan before the
    global row layout (and thus the per-chunk costs) existed, and the
    ownership recorded must be the split decode ACTUALLY used — not a
    recomputed one — so a later relaunch re-bases from ground truth."""
    meta, owners, block_of = load_plan_sidecars(dir_path)
    if meta is None:
        raise ValueError(
            f"{dir_path} has pre-versioned plan sidecars (no plan.json) — "
            "FE chunk ownership needs a versioned plan to ride in"
        )
    write_plan_sidecars(
        dir_path, owners, block_of,
        version=int(meta["version"]),
        hosts=[int(h) for h in meta["hosts"]],
        binding={int(h): int(p) for h, p in meta["binding"].items()},
        block_costs=np.asarray(meta["block_costs"], np.int64),
        num_entities=int(meta["num_entities"]),
        num_processes=int(meta.get("num_processes", 1)),
        fe_chunk_owners=np.asarray(
            [int(o) for o in fe_chunk_owners], np.int32
        ),
        fe_chunk_costs=np.asarray(
            [int(c) for c in fe_chunk_costs], np.int64
        ),
    )


@dataclasses.dataclass
class PerHostStreamingManifest(StreamingREManifest):
    """A host's slice of the global streaming layout: ``blocks`` lists ONLY
    the blocks this host owns (files named by GLOBAL block index), while
    ``num_rows`` / ``vocab`` / the plan sidecars describe the global run.
    Loaded with the base machinery — the streaming coordinate's update loop
    runs unchanged over the owned blocks. ``plan_version`` tracks elastic
    re-plans (parallel/elastic.py re-bases the manifest in place)."""

    global_block_ids: List[int] = dataclasses.field(default_factory=list)
    num_blocks_total: int = 0
    num_entities_global: int = 0
    process_index: int = 0
    num_processes: int = 1
    plan_version: int = 1

    def plan_arrays(self) -> Tuple[np.ndarray, np.ndarray]:
        """(block_of_vocab, owners) sidecars — owners are LOGICAL host ids
        (identical to physical under the default identity binding)."""
        return (
            np.load(os.path.join(self.dir, _PLAN_BLOCK_OF)),
            np.load(os.path.join(self.dir, _PLAN_OWNERS)),
        )

    def physical_plan_arrays(self) -> Tuple[np.ndarray, np.ndarray]:
        """(block_of_vocab, PHYSICAL owner process per block) — what
        validation-time row routing needs. Resolves the logical owners
        through the plan sidecar's binding; pre-versioned sidecars (no
        plan.json) are identity-bound already."""
        meta, owners, block_of = load_plan_sidecars(self.dir)
        if meta is None:
            return block_of, owners
        binding = {int(h): int(p) for h, p in meta["binding"].items()}
        table = np.full(max(binding) + 1, -1, np.int32)
        for h, p in binding.items():
            table[h] = p
        return block_of, table[owners.astype(np.int64)]


def commit_perhost_manifest(
    dir_path: str,
    metas: List[dict],
    base,
    *,
    owned_gids: Sequence[int],
    owners: np.ndarray,
    block_of: np.ndarray,
    plan_version: int,
    membership,
    block_costs: np.ndarray,
    fe_chunk_owners: Optional[np.ndarray] = None,
    fe_chunk_costs: Optional[np.ndarray] = None,
) -> None:
    """Atomically (re)write a per-host ``manifest.json`` + plan sidecars.
    ONE definition shared by the initial build (:func:`_write_owned_blocks`)
    and the elastic re-base (parallel/elastic.ElasticSession.replan_finish)
    so the two layouts cannot drift. ``base`` supplies the global,
    membership-invariant fields (num_rows/vocab/...)."""
    write_plan_sidecars(
        dir_path, owners, block_of,
        version=plan_version,
        hosts=membership.hosts,
        binding=membership.binding,
        block_costs=block_costs,
        num_entities=int(base.num_entities_global),
        num_processes=int(base.num_processes),
        fe_chunk_owners=fe_chunk_owners,
        fe_chunk_costs=fe_chunk_costs,
    )
    manifest = dict(
        blocks=list(metas),
        num_rows=int(base.num_rows),
        global_dim=int(base.global_dim),
        vocab=list(base.vocab),
        random_effect_id=base.random_effect_id,
        feature_shard_id=base.feature_shard_id,
        ladder=base.ladder,
        global_block_ids=[int(g) for g in owned_gids],
        num_blocks_total=int(len(owners)),
        num_entities_global=int(base.num_entities_global),
        process_index=int(base.process_index),
        num_processes=int(base.num_processes),
        plan_version=int(plan_version),
    )
    with open(os.path.join(dir_path, "manifest.json.tmp"), "w") as f:
        json.dump(manifest, f)
    os.replace(
        os.path.join(dir_path, "manifest.json.tmp"),
        os.path.join(dir_path, "manifest.json"),
    )


def build_perhost_streaming_manifest(
    rows: HostRows,
    config: RandomEffectDataConfig,
    out_dir: str,
    ctx: Optional[MeshContext] = None,
    num_processes: int = 1,
    process_id: int = 0,
    block_entities: Optional[int] = None,
    memory_budget_bytes: Optional[int] = None,
    bucketer=None,
    shared_vocab: Optional[List[str]] = None,
    tensor_cache=None,
    cache_key: Optional[str] = None,
    membership=None,
    block_cache=None,
    block_key_base: Optional[str] = None,
) -> PerHostStreamingManifest:
    """The per-host streaming ingest: agree on the vocabulary + counts,
    derive the global plan, route this host's rows to their entity's block
    owner, and build ONLY the owned blocks on local disk (atomic per-block
    writes through the retry machinery; fault site ``io.perhost_block_write``).

    ``rows.row_index`` must be dense global [0, N) ids (the residual gather
    and score scatter index them). ``shared_vocab`` skips the raw-id
    agreement collective when the dense entity space is already global (the
    2-process harness and bench workers; per-host Avro decodes use
    :func:`agree_entity_counts`).

    With a ``tensor_cache`` + ``cache_key`` (which MUST carry the host's
    shard scope — ``TensorCache(shard_scope=...)`` folds process index and
    topology into every key so per-host entries on a shared filesystem
    never collide or cross-read), the owned-block directory is reused on a
    hit. Hit/miss is agreed COLLECTIVELY: the row-routing exchange below is
    a collective, so one host skipping it while another rebuilds would
    deadlock the mesh — everyone rebuilds unless every host hits.

    ``membership`` (parallel/elastic.FleetMembership) makes the plan's
    owners LOGICAL host ids bound to physical processes — the versioned,
    re-plannable owner model; None is the identity over processes (the
    pre-elastic behavior, byte-identical plans).

    ``block_cache`` + ``block_key_base`` enable PER-BLOCK tensor-cache
    entries keyed on owned-block IDENTITY (global inputs + block id), with
    NO process scope: a block's tensors are a pure function of the global
    data and the plan — identical no matter which host builds them — so a
    membership change keeps every unmoved block's entry warm (the old
    dir-level shard-scoped key rebuilt everything on any topology change),
    and the elastic transfer path can serve a moved block from the cache
    when its file copy fails.
    """
    from photon_ml_tpu.compile import resolve_bucketer

    bucketer = resolve_bucketer(bucketer)
    if config.projector == "RANDOM":
        raise ValueError(
            "streaming random effects support INDEX_MAP/IDENTITY projectors "
            "(a shared RANDOM projection matrix would have to be replicated "
            "into every block; use the in-memory coordinate)"
        )
    if tensor_cache is not None and cache_key is not None:
        hit = tensor_cache.get_dir(cache_key)
        miss_flags = collective_sum(
            np.asarray([0 if hit is not None else 1], np.int64),
            ctx, num_processes,
        )
        if int(miss_flags[0]) == 0:
            return PerHostStreamingManifest.load(hit)
        if hit is not None:
            # a PEER missed, so everyone rebuilds (the routing below is a
            # collective) — but this host's key is unchanged, and block
            # content depends on rows routed FROM the peers: keeping the
            # old entry would let build_dir's lost-race path serve STALE
            # blocks built from the peers' previous inputs. Evict first so
            # the rebuild genuinely commits. (Callers should also fold the
            # GLOBAL input identity into the key — the drivers key on the
            # whole file list — making this the defense in depth, not the
            # primary freshness mechanism.)
            import shutil

            shutil.rmtree(hit, ignore_errors=True)

    # ---- agree vocabulary + counts ---------------------------------------
    if shared_vocab is not None:
        vocab = list(shared_vocab)
        varr = np.asarray(vocab, dtype=object)
        dense = np.searchsorted(varr, np.asarray(rows.entity_raw_ids, dtype=object))
        dense_c = np.clip(dense, 0, max(len(vocab) - 1, 0))
        if rows.num_rows and not (varr[dense_c] == np.asarray(
            rows.entity_raw_ids, dtype=object
        )).all():
            raise ValueError(
                "shared_vocab does not cover this host's entity ids (the "
                "vocabulary must be the sorted global id set)"
            )
        dense = dense_c.astype(np.int64)
        local_counts = np.bincount(dense, minlength=len(vocab)).astype(np.int64)
        counts = collective_sum(local_counts, ctx, num_processes)
    else:
        vocab, counts = agree_entity_counts(
            rows.entity_raw_ids, ctx, num_processes
        )
        varr = np.asarray(vocab, dtype=object)
        dense = np.searchsorted(
            varr, np.asarray(rows.entity_raw_ids, dtype=object)
        ).astype(np.int64)

    # ---- global row space sanity (the scatter/gather contract) -----------
    local_meta = np.asarray(
        [int(rows.row_index.max()) if rows.num_rows else -1], np.int64
    )
    g_max_row = int(collective_max(local_meta, ctx, num_processes)[0])
    n_global = int(collective_sum(
        np.asarray([rows.num_rows], np.int64), ctx, num_processes
    )[0])
    if g_max_row != n_global - 1:
        raise ValueError(
            f"row ids are not dense [0, N): max id {g_max_row} vs {n_global} "
            "global rows — use global_row_layout / densify_row_ids first"
        )
    i32_max = np.iinfo(np.int32).max
    if n_global > i32_max or len(vocab) > i32_max:
        # the routing exchange narrows row/entity ids to int32 (the packed
        # record format) — wrapped ids would read as padding and be DROPPED
        # silently; fail loudly at the scale boundary instead
        raise ValueError(
            f"{n_global} rows / {len(vocab)} entities exceed the int32 id "
            "space of the routing exchange; shard the input into multiple "
            "coordinates or widen the exchange record format"
        )

    # ---- the agreed plan ---------------------------------------------------
    plan = EntityShardPlan.build(
        counts, num_processes,
        global_dim=rows.global_dim,
        active_upper_bound=config.active_upper_bound,
        block_entities=block_entities,
        memory_budget_bytes=memory_budget_bytes,
        hosts=(membership.hosts if membership is not None else None),
        version=(membership.version if membership is not None else 1),
    )
    phys_owners = (
        membership.physical_owners(plan.owners)
        if membership is not None else plan.owners
    )

    # ---- route rows to their block's owner host ---------------------------
    host_data, row_to_global = _route_and_assemble(
        rows, dense, vocab, plan, phys_owners, config, ctx, num_processes,
        process_id,
    )

    # ---- build the owned blocks -------------------------------------------
    def build(dir_path: str) -> None:
        _write_owned_blocks(
            dir_path, host_data, row_to_global, config, plan, vocab,
            bucketer, memory_budget_bytes, n_global, process_id,
            membership=membership, block_cache=block_cache,
            block_key_base=block_key_base,
        )

    if tensor_cache is not None and cache_key is not None:
        from photon_ml_tpu.resilience import RetryError

        try:
            entry = tensor_cache.build_dir(cache_key, build)
            return PerHostStreamingManifest.load(entry)
        except RetryError:
            pass  # cache unusable: fall through to the plain build
    os.makedirs(out_dir, exist_ok=True)
    build(out_dir)
    return PerHostStreamingManifest.load(out_dir)


def _agree_padded_features(
    rows: HostRows,
    ctx: Optional[MeshContext],
    num_processes: int,
) -> Tuple[np.ndarray, np.ndarray]:
    """All hosts must pack the SAME record width before a routing exchange
    (per-host max nnz differs on real data, and a width mismatch would
    hand the collective inconsistent shard shapes). One definition shared
    by the training-ingest and validation-scoring routes. Returns this
    host's (feat_idx, feat_val) padded to the collectively agreed width."""
    k = int(collective_max(
        np.asarray([rows.feat_idx.shape[1] if rows.num_rows else 1], np.int64),
        ctx, num_processes,
    )[0])
    k = max(k, 1)
    fi = (_pad_to(rows.feat_idx.astype(np.int32).T, k, -1).T
          if rows.feat_idx.shape[1] != k else rows.feat_idx.astype(np.int32))
    fv = (_pad_to(rows.feat_val.astype(np.float32).T, k, 0.0).T
          if rows.feat_val.shape[1] != k else rows.feat_val.astype(np.float32))
    return fi, fv


def _route_and_assemble(
    rows: HostRows,
    dense: np.ndarray,
    vocab: List[str],
    plan: EntityShardPlan,
    phys_owners: np.ndarray,
    config: RandomEffectDataConfig,
    ctx: Optional[MeshContext],
    num_processes: int,
    process_id: int,
) -> Tuple[GameData, np.ndarray]:
    """Route this host's rows to their entity's block owner and reassemble
    the received rows — sorted by GLOBAL row id, so the owner's local data
    is exactly the single-host dataset restricted to its entities (the
    bitwise foundation: identical filtered rows -> identical block tensors).
    ``phys_owners`` is the per-block PHYSICAL destination (the plan's
    logical owners resolved through the membership binding). Returns
    (host-local GameData in the GLOBAL dense entity space, local row
    position -> global row id)."""
    dest_host = np.asarray(phys_owners)[plan.block_of_vocab[dense]].astype(np.int64)
    fi, fv = _agree_padded_features(rows, ctx, num_processes)
    int_payload = np.concatenate(
        [rows.row_index.astype(np.int32)[:, None],
         dense.astype(np.int32)[:, None], fi], axis=1
    )
    flt_payload = np.concatenate(
        [rows.labels.astype(np.float32)[:, None],
         rows.weights.astype(np.float32)[:, None],
         rows.offsets.astype(np.float32)[:, None], fv], axis=1
    )
    bi, bf = route_rows_to_hosts(
        dest_host, int_payload, flt_payload, ctx, num_processes, process_id
    )
    order = np.argsort(bi[:, 0], kind="stable")
    bi, bf = bi[order], bf[order]
    row_to_global = bi[:, 0].astype(np.int64)
    ofi, ofv = bi[:, 2:], bf[:, 3:]
    valid = ofi >= 0
    lens = valid.sum(axis=1).astype(np.int64)
    indptr = np.zeros(len(bi) + 1, np.int64)
    np.cumsum(lens, out=indptr[1:])
    feats = HostFeatures(
        indptr=indptr,
        indices=ofi[valid].astype(np.int32),
        values=ofv[valid].astype(np.float32),
        dim=rows.global_dim,
    )
    host_data = GameData(
        response=bf[:, 0].astype(np.float32),
        offset=bf[:, 2].astype(np.float32),
        weight=bf[:, 1].astype(np.float32),
        ids={config.random_effect_id: bi[:, 1].astype(np.int32)},
        id_vocabs={config.random_effect_id: list(vocab)},
        shards={config.feature_shard_id: feats},
    )
    return host_data, row_to_global


def _write_owned_blocks(
    dir_path: str,
    host_data: GameData,
    row_to_global: np.ndarray,
    config: RandomEffectDataConfig,
    plan: EntityShardPlan,
    vocab: List[str],
    bucketer,
    memory_budget_bytes: Optional[int],
    n_global: int,
    process_id: int,
    membership=None,
    block_cache=None,
    block_key_base: Optional[str] = None,
) -> None:
    import types

    from photon_ml_tpu import resilience
    from photon_ml_tpu.resilience import RetryError, faults

    owned = plan.owned_block_ids(process_id, membership)
    metas = []
    cache_hits = 0
    for gi in owned:
        payload = None
        block_key = (
            f"{block_key_base}-g{gi:05d}"
            if block_cache is not None and block_key_base is not None
            else None
        )
        built_fresh = False
        if block_key is not None:
            hit = block_cache.get(block_key)
            if hit is not None:
                # per-block entries are UNSCOPED: block gi's tensors are a
                # pure function of the global data + plan, identical no
                # matter which host built them — so a survivor (or a new
                # owner) reuses them across any membership change
                payload = {k: np.asarray(v) for k, v in hit.arrays.items()}
                cache_hits += 1
        if payload is None:
            payload = build_block_payload(
                host_data, config, plan.blocks[gi], bucketer=bucketer,
                memory_budget_bytes=memory_budget_bytes, label=f"block {gi}",
                row_to_global=row_to_global,
            )
            built_fresh = True

        def write_once(gi=gi, payload=payload):
            faults.inject(
                "io.perhost_block_write", block=gi, process=process_id
            )
            return write_block_file(dir_path, f"block-{gi:05d}.npz", payload)

        metas.append(resilience.call_with_retry(
            write_once, resilience.current_config().io_policy,
            describe=f"per-host block {gi} write",
        ))
        if block_key is not None and built_fresh:
            try:
                block_cache.put(block_key, payload)
            except RetryError as e:
                logger.warning(
                    "per-block cache write for block %d failed after "
                    "retries (%s); continuing uncached", gi, e,
                )
        del payload
    if cache_hits:
        logger.info(
            "per-host streaming build: %d/%d owned blocks served from the "
            "per-block tensor cache (owned-block-identity keys)",
            cache_hits, len(owned),
        )
    mem = membership
    if mem is None:
        from photon_ml_tpu.parallel.elastic import FleetMembership

        mem = FleetMembership.initial(plan.num_processes)
    base = types.SimpleNamespace(
        num_rows=int(n_global),
        global_dim=int(host_data.shards[config.feature_shard_id].dim),
        vocab=list(vocab),
        random_effect_id=config.random_effect_id,
        feature_shard_id=config.feature_shard_id,
        ladder=(f"{bucketer.base}:{bucketer.growth:g}" if bucketer else None),
        num_entities_global=int(plan.num_entities),
        process_index=int(process_id),
        num_processes=int(plan.num_processes),
    )
    commit_perhost_manifest(
        dir_path, metas, base,
        owned_gids=owned,
        owners=plan.owners,
        block_of=plan.block_of_vocab,
        plan_version=plan.version,
        membership=mem,
        block_costs=(
            plan.block_costs if plan.block_costs is not None
            else np.zeros(len(plan.blocks), np.int64)
        ),
    )


# ---------------------------------------------------------------------------
# per-host spilled state: files keyed by GLOBAL block id
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class PerHostSpilledREState(SpilledREState):
    """Per-host spilled coordinate state whose files are named by GLOBAL
    block id (``coefs-g<gid>.npy``), not local position: an elastic
    re-plan moves a block's coefficients between hosts as ONE file copy
    that keeps its name, and the checkpoint reference carries per-global-id
    shapes — so a checkpoint written under plan v1 restores under plan v2
    (the rebuild validates every still-owned block's shape and the
    presence of every coefficient file the save recorded, instead of the
    base class's positional shapes-list equality)."""

    global_ids: List[int] = dataclasses.field(default_factory=list)
    plan_version: int = 1

    def _path(self, i: int) -> str:
        return os.path.join(
            self.dir, f"coefs-g{int(self.global_ids[i]):05d}.npy"
        )

    def __checkpoint_ref__(self) -> dict:
        return {
            "kind": "perhost_spilled_re_state",
            "dir": self.dir,
            "plan_version": int(self.plan_version),
            "shapes_by_gid": {
                str(int(g)): [int(x) for x in s]
                for g, s in zip(self.global_ids, self.shapes)
            },
            "written_gids": [
                int(g) for i, g in enumerate(self.global_ids)
                if os.path.exists(self._path(i))
            ],
            "written": os.path.isdir(self.dir),
        }

    def __checkpoint_from_ref__(self, ref: dict) -> "PerHostSpilledREState":
        from photon_ml_tpu.checkpoint import CheckpointRefError

        if ref.get("kind") == "spilled_re_state":
            raise CheckpointRefError(
                "checkpoint holds a pre-elastic positional per-host spill "
                "ref; per-host states are now keyed by global block id "
                "(see MIGRATION.md) — falling back to an older step or a "
                "fresh epoch"
            )
        if ref.get("kind") != "perhost_spilled_re_state":
            raise CheckpointRefError(
                f"checkpoint ref kind {ref.get('kind')!r} is not a per-host "
                "spilled streaming state — coordinate types changed since "
                "the save"
            )
        if int(ref.get("plan_version", 1)) != int(self.plan_version):
            logger.info(
                "restoring per-host spilled state across a plan change "
                "(saved v%s, restoring under v%s) — shapes re-validated "
                "per global block id",
                ref.get("plan_version", 1), self.plan_version,
            )
        shapes_by_gid = {
            int(g): tuple(int(x) for x in s)
            for g, s in ref.get("shapes_by_gid", {}).items()
        }
        for g, s in zip(self.global_ids, self.shapes):
            want = shapes_by_gid.get(int(g))
            if want is not None and want != tuple(int(x) for x in s):
                raise CheckpointRefError(
                    f"block {g}: checkpoint shape {want} does not match "
                    f"this manifest's {tuple(s)} — the streaming blocks "
                    "were rebuilt differently; refusing to resume"
                )
        if ref.get("written") and not os.path.isdir(ref["dir"]):
            raise CheckpointRefError(
                f"spilled coefficient dir {ref['dir']} referenced by this "
                "checkpoint no longer exists — restoring would silently "
                "zero trained coefficients; falling back to an older step"
            )
        out = PerHostSpilledREState(
            dir=ref["dir"], shapes=list(self.shapes),
            global_ids=list(self.global_ids),
            plan_version=int(self.plan_version),
        )
        # blocks the SAVE recorded as written and this plan still owns
        # must be present after the re-base transfer — a missing file
        # would serve zeros for trained coefficients
        written = {int(g) for g in ref.get("written_gids", [])}
        missing = [
            int(g) for i, g in enumerate(self.global_ids)
            if int(g) in written and not os.path.exists(out._path(i))
        ]
        if missing:
            raise CheckpointRefError(
                f"blocks {missing} had coefficients at save time but their "
                f"files are missing from {ref['dir']} after the re-base — "
                "refusing to resume onto zeros"
            )
        return out


# ---------------------------------------------------------------------------
# the coordinate (drop-in for CoordinateDescent, like its single-host base)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class PerHostStreamingRandomEffectCoordinate(StreamingRandomEffectCoordinate):
    """Entity-sharded streaming random-effect coordinate: the inherited
    block loop (Avro-decoded tensors -> PR-2 prefetch pipeline -> PR-3
    shape-ladder block solves, preemption drain points at block boundaries)
    runs over ONLY the blocks this host owns; ``score`` merges the
    host-local scatters with one exact reduction over the mesh and
    ``regularization_term`` folds exactly merged per-block terms in global
    block order — so every host returns the replicated, bitwise
    single-host value. Updates need NO collective at all (owner-computes:
    each entity's rows live with its coefficients)."""

    # Composable policies (photon_ml_tpu.compile.plan threads them via the
    # inherited ``plan`` field): a solve schedule compacts each owned
    # block's lanes through the scheduler's process-shared chunk kernels,
    # and the sparse-kernel race selects per owned block — both run with
    # NO collective (updates are owner-computes), so the compacted/sparse
    # run stays bitwise-equal to the one-shot perhost run and to the
    # single-host streaming run (2-process harness-pinned).

    ctx: Optional[MeshContext] = None
    num_processes: int = 1

    def __post_init__(self):
        super().__post_init__()
        if self.num_processes > 1 and self.ctx is None:
            raise ValueError(
                "PerHostStreamingRandomEffectCoordinate needs a MeshContext "
                "to merge scores across processes"
            )
        m = self.manifest
        self._global_ids = list(
            getattr(m, "global_block_ids", None)
            or range(len(m.blocks))
        )
        self._blocks_total = int(
            getattr(m, "num_blocks_total", 0) or len(m.blocks)
        )

    @property
    def num_entities(self) -> int:
        return int(
            getattr(self.manifest, "num_entities_global", 0)
            or self.manifest.num_entities
        )

    def _ledger_gid(self, i: int) -> int:
        """Convergence-ledger key = GLOBAL block id: entries stay valid
        when an elastic re-plan moves the block to a different owner (the
        re-base merges every host's entries and re-writes each survivor's
        sidecar for its NEW owned set, parallel/elastic.py)."""
        return int(self._global_ids[i])

    # -- elastic re-sharding hooks (parallel/elastic.py) --------------------
    def _make_state(self, dir_path: str) -> PerHostSpilledREState:
        return PerHostSpilledREState(
            dir=dir_path, shapes=list(self._shapes),
            global_ids=list(int(g) for g in self._global_ids),
            plan_version=int(getattr(self.manifest, "plan_version", 1)),
        )

    def _partial_payload(self, new_state, done_blocks,
                         inner: Optional[dict] = None) -> dict:
        payload = super()._partial_payload(new_state, done_blocks, inner)
        # progress keyed by GLOBAL block id + plan version: after a
        # re-plan, still-owned done blocks map back to (new) local indices
        # and moved-away ones drop out (their new owner re-solves them —
        # deterministic, so bitwise either way)
        payload["meta"]["done_global_ids"] = [
            int(self._global_ids[i]) for i in sorted(done_blocks)
        ]
        payload["meta"]["plan_version"] = int(
            getattr(self.manifest, "plan_version", 1)
        )
        return payload

    def _resume_done_locals(self, m: dict, active) -> set:
        if m.get("done_global_ids") is not None:
            local_of = {int(g): i for i, g in enumerate(self._global_ids)}
            done = {
                local_of[int(g)] for g in m["done_global_ids"]
                if int(g) in local_of
            }
            return done & set(active)
        return super()._resume_done_locals(m, active)

    def _resume_inner_ok(self, m: dict) -> bool:
        cur = int(getattr(self.manifest, "plan_version", 1))
        saved = m.get("plan_version")
        if saved is not None and int(saved) != cur:
            logger.info(
                "dropping mid-chunk scheduler snapshot across plan change "
                "(saved v%s -> v%s): the block re-solves whole, which is "
                "bitwise-equal to the chunked resume", saved, cur,
            )
            return False
        return True

    def score(self, state) -> Array:
        local = np.asarray(super().score(state))
        return jnp.asarray(merge_disjoint(local, self.ctx, self.num_processes))

    def regularization_term(self, state) -> Array:
        l1 = self.regularization.l1_weight
        l2 = self.regularization.l2_weight
        terms = np.zeros(self._blocks_total, np.float64)
        for i in range(len(self.manifest.blocks)):
            w = state.block(i)
            terms[self._global_ids[i]] = l1 * float(
                np.sum(np.abs(w))
            ) + 0.5 * l2 * float(np.sum(np.square(w)))
        merged = merge_disjoint(terms, self.ctx, self.num_processes)
        # fold in global block order — the single-host coordinate's exact
        # accumulation sequence, replayed identically on every host
        acc = 0.0
        for gi in range(self._blocks_total):
            acc += float(merged[gi])
        return jnp.asarray(acc, real_dtype())


# ---------------------------------------------------------------------------
# validation / inference row routing against per-host streaming models
# ---------------------------------------------------------------------------


def score_routed_rows_streaming(
    manifest: PerHostStreamingManifest,
    means_by_raw_id: Dict[str, np.ndarray],
    rows: HostRows,
    num_rows_out: int,
    ctx: Optional[MeshContext],
    num_processes: int = 1,
    process_id: int = 0,
) -> np.ndarray:
    """Score rows THIS host ingested against entity models owned by any
    host: each row routes to its entity's block owner (the plan sidecars
    name it), the owner dots the row against its back-projected entity
    means, and the per-host partials merge exactly (each output row is
    written by exactly one host). Cold entities/features contribute 0
    (RandomEffectModel.scala:129-158 semantics). Returns the replicated
    (num_rows_out,) float32 score vector."""
    if num_rows_out > np.iinfo(np.int32).max:
        # same scale boundary as the training route: wrapped int32 row ids
        # would read as exchange padding and silently drop rows
        raise ValueError(
            f"{num_rows_out} scoring rows exceed the int32 id space of the "
            "routing exchange; shard the scoring pass"
        )
    # PHYSICAL owners: the plan sidecar's logical owners resolved through
    # the membership binding (identity for pre-elastic layouts) — and
    # re-based in place by any elastic re-plan, so routed scoring always
    # targets the CURRENT owner of a block
    block_of, owners = manifest.physical_plan_arrays()
    varr = np.asarray(manifest.vocab, dtype=object)
    raw = np.asarray(rows.entity_raw_ids, dtype=object)
    pos = np.searchsorted(varr, raw) if len(varr) else np.zeros(len(raw), np.int64)
    pos_c = np.clip(pos, 0, max(len(varr) - 1, 0))
    known = (varr[pos_c] == raw) if len(varr) else np.zeros(len(raw), bool)
    sel = np.nonzero(known)[0]
    dest = owners[block_of[pos_c[sel]]].astype(np.int64)
    fi_p, fv_p = _agree_padded_features(rows, ctx, num_processes)
    int_payload = np.concatenate(
        [rows.row_index[sel].astype(np.int32)[:, None],
         pos_c[sel].astype(np.int32)[:, None],
         fi_p[sel]], axis=1
    )
    bi, bf = route_rows_to_hosts(
        dest, int_payload, fv_p[sel], ctx, num_processes, process_id,
    )
    local = np.zeros(num_rows_out, np.float32)
    if len(bi):
        # vectorized owner-side scoring: one means row per distinct routed
        # entity, then a batched (R, K) gather-dot (cold entities on this
        # owner contribute 0 — RandomEffectModel.scala:129-158)
        uniq, inv = np.unique(bi[:, 1], return_inverse=True)
        w_rows = np.zeros((len(uniq), int(manifest.global_dim)), np.float32)
        have = np.zeros(len(uniq), bool)
        for j, de in enumerate(uniq):
            w = means_by_raw_id.get(str(varr[de]))
            if w is not None:
                w_rows[j] = np.asarray(w, np.float32)
                have[j] = True
        fi_r = bi[:, 2:]
        vals = w_rows[inv[:, None], np.maximum(fi_r, 0)]  # (R, K)
        contrib = np.sum(
            np.where(fi_r >= 0, vals * bf, 0.0), axis=1
        ) * have[inv]
        np.add.at(local, bi[:, 0], contrib.astype(np.float32))
    return np.asarray(
        merge_disjoint(local, ctx, num_processes), np.float32
    )
