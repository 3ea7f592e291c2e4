"""Device-collective shuffle: per-host ingest without a replicated build.

The reference's multi-host ingest is Spark's: each executor decodes only its
own Avro partitions with per-partition index maps
(avro/data/DataProcessingUtils.scala:57-80), then ``partitionBy`` /
``groupByKey`` SHUFFLES rows so each entity's samples land on the partition
that owns the entity (RandomEffectDataSet.scala:219-307, balanced by
RandomEffectIdPartitioner.scala:29-97). TPU-native, the same three steps are

  1. **count exchange** — each host bucket-hashes only ITS entity ids and
     one device-collective sum merges the (B,) bucket-count vectors;
  2. **balanced assignment** — every host runs the same greedy min-heap
     bin-packing over the identical global counts, so the entity->device
     owner map is agreed WITHOUT any host seeing another host's rows;
  3. **row exchange** — rows are packed into fixed-width records and moved
     with one ``lax.all_to_all`` over the mesh axis (ICI/DCN does the
     transport — the collective IS the shuffle).

No host ever materializes the global dataset: per-host memory is
O(rows_ingested_here + rows_owned_here), which shrinks ~1/n_hosts as hosts
are added — the property that makes multi-host ingest worth having.
"""

from __future__ import annotations

import dataclasses
import hashlib
import heapq
from typing import List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from photon_ml_tpu.parallel.mesh import MeshContext

Array = jax.Array

# sentinel row_index marking padding records in exchange buffers
_PAD = -1


# ---------------------------------------------------------------------------
# stable hashing (must agree across processes — python's hash() does not)
# ---------------------------------------------------------------------------


def stable_entity_key(raw_id: str) -> int:
    """64-bit stable key for a raw entity id string, process-stable across
    hosts (unlike ``hash()``) and genuinely 64-bit: blake2b truncated to 8
    bytes. A keyed/salted CRC pair is NOT enough here — CRC32 is linear, so
    any same-length crc32 collision collides in the salted stream too,
    making the pair effectively 32-bit (birthday at ~65k same-length ids).
    With a real 64-bit hash the expected-collision odds at 1e8 entities are
    ~ (1e8)^2 / 2^65 ~ 2.7e-4. Colliding entities would be silently merged
    by the shuffle grouping, so 32 bits was a correctness hazard, not a
    performance nit."""
    return int.from_bytes(
        hashlib.blake2b(raw_id.encode("utf-8"), digest_size=8).digest(), "big"
    )


def stable_entity_keys(raw_ids: Sequence[str]) -> np.ndarray:
    """(n,) uint64 stable keys."""
    return np.fromiter(
        (stable_entity_key(r) for r in raw_ids), np.uint64, count=len(raw_ids)
    )


def stable_row_priority(keys: np.ndarray, row_index: np.ndarray) -> np.ndarray:
    """Partitioning-invariant pseudo-random priority per row, for the
    active-set reservoir cap (RandomEffectDataSet.scala:246-307): the kept
    set depends only on (entity, global row), never on which host ingested
    the row or in what order — the determinism Spark's zipWithUniqueId-based
    reservoir explicitly lacks (RandomEffectDataSet.scala:281-285)."""
    mix = (keys.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15)) ^ (
        row_index.astype(np.uint64) * np.uint64(0xC2B2AE3D27D4EB4F)
    )
    mix ^= mix >> np.uint64(33)
    mix *= np.uint64(0xFF51AFD7ED558CCD)
    mix ^= mix >> np.uint64(33)
    return mix


def bucket_of(keys: np.ndarray, num_buckets: int) -> np.ndarray:
    """(n,) int64 bucket per key (num_buckets should be a power of two)."""
    return (keys & np.uint64(num_buckets - 1)).astype(np.int64)


# ---------------------------------------------------------------------------
# small collective reductions of host-side vectors
# ---------------------------------------------------------------------------


def _host_block(vec: np.ndarray, local_devices: int, fill) -> np.ndarray:
    """(L, B) block with this host's vector in row 0 and ``fill`` rows
    after — summing/maxing the device axis then yields the cross-host
    reduction with each host counted exactly once."""
    block = np.full((local_devices, vec.shape[0]), fill, vec.dtype)
    block[0] = vec
    return block


def _collective_reduce(
    vec: np.ndarray, ctx: MeshContext, num_processes: int, op: str
) -> np.ndarray:
    """Sum/max a per-host vector across hosts via one device reduction.

    Works identically single-process (the reduction is a no-op with L =
    num_devices) and multi-process (jax.make_array_from_process_local_data
    assembles the (n_dev, B) global, the jitted reduce runs SPMD).

    Single-process, the local vector IS the global reduction — returned
    host-side with no device dispatch at all, so an unavailable backend or
    wedged device client (the ``UNAVAILABLE`` tracebacks the r5 bench
    self-capture hit inside ``per_host_re_dataset``) can no longer fail
    the ingest metadata exchange; a backend failure on a mesh claiming
    multiple processes ALSO degrades to the local value — with a logged
    warning — when every mesh device is process-local (the backend lied /
    died but no other host can be waiting on us); a genuinely multi-host
    failure re-raises, since a silently-local value would desynchronize
    the hosts."""
    import contextlib
    import logging

    vec = np.asarray(vec)
    if num_processes <= 1:
        return vec.copy()

    local = max(ctx.num_devices // num_processes, 1)
    fill = 0 if op == "sum" else np.iinfo(vec.dtype).min if np.issubdtype(vec.dtype, np.integer) else -np.inf
    block = _host_block(np.asarray(vec), local, fill)
    sharding = NamedSharding(ctx.mesh, P(ctx.axis))
    fn = jnp.sum if op == "sum" else jnp.max
    # int64 must reduce EXACTLY: without x64 JAX silently wraps to int32,
    # which (a) overflows row-id sums past N ~ 65k (sum N(N-1)/2 > 2^31)
    # and (b) wraps the int64 min fill to 0, poisoning negative maxes
    is_i64 = np.issubdtype(block.dtype, np.integer) and block.dtype.itemsize == 8
    try:
        with jax.enable_x64() if is_i64 else contextlib.nullcontext():
            g = jax.make_array_from_process_local_data(sharding, block)
            out = jax.jit(
                lambda a: fn(a, axis=0), out_shardings=NamedSharding(ctx.mesh, P())
            )(g)
            return np.asarray(jax.device_get(out))
    except Exception as e:  # noqa: BLE001 — any backend fault, incl. JaxRuntimeError
        try:
            genuinely_multihost = jax.process_count() > 1
        except Exception:  # noqa: BLE001 — a dead runtime cannot be multihost
            genuinely_multihost = False
        if genuinely_multihost:
            raise RuntimeError(
                f"collective {op} over {num_processes} processes failed "
                f"mid-reduce; a local fallback would desynchronize hosts"
            ) from e
        logging.getLogger(__name__).warning(
            "collective %s degraded to the process-local value: backend "
            "unavailable in a single-process runtime (%s: %s)",
            op, type(e).__name__, e,
        )
        return vec.copy()


def collective_sum(vec, ctx, num_processes: int) -> np.ndarray:
    return _collective_reduce(np.asarray(vec), ctx, num_processes, "sum")


def collective_max(vec, ctx, num_processes: int) -> np.ndarray:
    return _collective_reduce(np.asarray(vec), ctx, num_processes, "max")


# ---------------------------------------------------------------------------
# balanced bucket -> device assignment (RandomEffectIdPartitioner analogue)
# ---------------------------------------------------------------------------


def balanced_bucket_owners(global_counts: np.ndarray, num_devices: int) -> np.ndarray:
    """(B,) int32 owner device per bucket: greedy min-heap bin-packing of
    buckets (heaviest first) onto the least-loaded device — the reference's
    balanced partitioner (RandomEffectIdPartitioner.scala:64-97) at bucket
    granularity. Deterministic: every host computes the identical map from
    the identical psum'd counts."""
    owners = np.zeros(len(global_counts), np.int32)
    heap = [(0, d) for d in range(num_devices)]
    heapq.heapify(heap)
    order = np.argsort(-global_counts, kind="stable")
    for b in order:
        load, d = heapq.heappop(heap)
        owners[b] = d
        heapq.heappush(heap, (load + int(global_counts[b]), d))
    return owners


def balanced_owners_over_hosts(
    costs: np.ndarray, hosts: Sequence[int]
) -> np.ndarray:
    """(B,) int32 owner HOST ID per block for an arbitrary live-host set:
    the same deterministic min-heap packing as :func:`balanced_bucket_owners`
    but assigning onto an explicit (sorted) host-id list instead of
    ``range(n)`` — the re-plan primitive of elastic entity re-sharding
    (parallel/elastic.py). Every survivor derives the IDENTICAL map from
    the identical (costs, survivor set), so a membership change needs no
    extra agreement collective beyond the membership itself."""
    host_ids = np.asarray(sorted(int(h) for h in hosts), np.int32)
    if len(host_ids) == 0:
        raise ValueError("cannot assign block owners over an empty host set")
    slots = balanced_bucket_owners(np.asarray(costs), len(host_ids))
    return host_ids[slots]


# ---------------------------------------------------------------------------
# the row exchange (all_to_all over the mesh axis)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ExchangeResult:
    """Rows received by THIS host's devices after the shuffle."""

    # per local device: (r_d, Wi) int32 and (r_d, Wf) float32 record blocks
    int_rows: List[np.ndarray]
    float_rows: List[np.ndarray]


def exchange_rows(
    dest_device: np.ndarray,
    int_payload: np.ndarray,
    float_payload: np.ndarray,
    ctx: MeshContext,
    num_processes: int,
    process_id: int,
) -> ExchangeResult:
    """Move each packed row to its destination device with one all_to_all.

    ``int_payload[:, 0]`` must be a non-negative record id (it doubles as
    the padding sentinel). Rows this host ingested are spread round-robin
    over its local devices as senders; send blocks are padded to the global
    max per (sender, dest) so the all_to_all block shape is uniform.
    """
    n = dest_device.shape[0]
    n_dev = ctx.num_devices
    local = max(n_dev // num_processes, 1)
    wi = int_payload.shape[1]
    wf = float_payload.shape[1]
    assert int_payload.shape[0] == n and float_payload.shape[0] == n

    # sender = round-robin over local devices WITHIN each destination's rows,
    # so every (sender, dest) cell gets an even share and M stays minimal
    order = np.argsort(dest_device, kind="stable")
    rank_in_dest = np.empty(n, np.int64)
    sorted_dest = dest_device[order]
    starts = np.searchsorted(sorted_dest, np.arange(n_dev), side="left")
    rank_in_dest[order] = np.arange(n) - starts[sorted_dest]
    sender_local = (rank_in_dest % local).astype(np.int64)

    counts = np.zeros((local, n_dev), np.int64)
    np.add.at(counts, (sender_local, dest_device.astype(np.int64)), 1)
    m = int(collective_max(counts.reshape(-1), ctx, num_processes).max())
    m = max(m, 1)

    ints = np.full((local, n_dev, m, wi), _PAD, np.int32)
    flts = np.zeros((local, n_dev, m, wf), np.float32)
    slot = rank_in_dest // local  # rank within the (sender, dest) cell
    ints[sender_local, dest_device, slot] = int_payload.astype(np.int32)
    flts[sender_local, dest_device, slot] = float_payload.astype(np.float32)

    sharding = NamedSharding(ctx.mesh, P(ctx.axis))
    g_int = jax.make_array_from_process_local_data(sharding, ints)
    g_flt = jax.make_array_from_process_local_data(sharding, flts)

    axis = ctx.axis

    def body(bi, bf):
        # local block (1, n_dev, m, W): split the dest axis, concat senders
        return (
            lax.all_to_all(bi, axis, split_axis=1, concat_axis=0),
            lax.all_to_all(bf, axis, split_axis=1, concat_axis=0),
        )

    mapped = jax.jit(
        shard_map(
            body,
            mesh=ctx.mesh,
            in_specs=(P(axis), P(axis)),
            out_specs=(P(None, axis), P(None, axis)),
        )
    )
    r_int, r_flt = mapped(g_int, g_flt)

    int_rows: List[np.ndarray] = []
    float_rows: List[np.ndarray] = []
    # this host's devices are process-major: [process_id*local, ...+local)
    for ld in range(local):
        d = process_id * local + ld
        # an unpartitioned dim (1-device mesh) reports index slice(None)
        bi = np.asarray(
            [s.data for s in r_int.addressable_shards
             if (s.index[1].start or 0) == d]
        ).reshape(n_dev, m, wi)
        bf = np.asarray(
            [s.data for s in r_flt.addressable_shards
             if (s.index[1].start or 0) == d]
        ).reshape(n_dev, m, wf)
        keep = bi[:, :, 0] != _PAD
        int_rows.append(bi[keep])
        float_rows.append(bf[keep])
    return ExchangeResult(int_rows=int_rows, float_rows=float_rows)


# ---------------------------------------------------------------------------
# host-granular entity routing (the streaming owner-computes shuffle)
# ---------------------------------------------------------------------------


def route_rows_to_hosts(
    dest_host: np.ndarray,
    int_payload: np.ndarray,
    float_payload: np.ndarray,
    ctx: MeshContext,
    num_processes: int,
    process_id: int,
) -> Tuple[np.ndarray, np.ndarray]:
    """Move packed rows to their OWNER HOST (not device) with the same
    one-``all_to_all`` exchange as :func:`exchange_rows`: each destination
    host's rows are spread round-robin over its local devices for the
    collective, then re-concatenated host-side on arrival. This is the
    entity-routing step of per-host streaming coordinate descent
    (parallel/perhost_streaming.py): rows move ONCE at ingest, to the host
    that owns their entity's block — never again per iteration (the Spark
    shuffle-per-pass anti-pattern this layout exists to beat).

    ``int_payload[:, 0]`` must be a non-negative record id (the padding
    sentinel, same contract as exchange_rows). Returns this host's received
    ``(int_rows, float_rows)`` blocks (row order unspecified — callers sort
    by their record id). Fault site ``multihost.entity_route`` fires before
    the collective — also single-process, so chaos plans can target the
    routing boundary without a multi-host harness.
    """
    from photon_ml_tpu.resilience import faults

    faults.inject(
        "multihost.entity_route",
        process=process_id,
        rows=int(len(dest_host)),
    )
    if num_processes <= 1:
        return int_payload.astype(np.int32), float_payload.astype(np.float32)
    local = max(ctx.num_devices // num_processes, 1)
    # round-robin within each destination host's rows, so the per-device
    # exchange cells stay balanced
    order = np.argsort(dest_host, kind="stable")
    rank_in_dest = np.empty(len(dest_host), np.int64)
    sorted_dest = dest_host[order]
    starts = np.searchsorted(sorted_dest, np.arange(num_processes), side="left")
    rank_in_dest[order] = np.arange(len(dest_host)) - starts[sorted_dest]
    dest_device = dest_host.astype(np.int64) * local + (rank_in_dest % local)
    ex = exchange_rows(
        dest_device, int_payload, float_payload, ctx, num_processes, process_id
    )
    return (
        np.concatenate(ex.int_rows, axis=0) if ex.int_rows else int_payload[:0].astype(np.int32),
        np.concatenate(ex.float_rows, axis=0) if ex.float_rows else float_payload[:0].astype(np.float32),
    )
