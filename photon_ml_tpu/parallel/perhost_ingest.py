"""True per-host GAME ingest: each host decodes only its input partitions,
the collective shuffle routes rows to entity owners, and each host builds
ONLY its devices' entity slabs (VERDICT r3 next-round #4).

Reference pipeline being re-expressed (SURVEY.md §3.2): per-executor Avro
decode with per-partition index maps (DataProcessingUtils.scala:57-80) ->
``partitionBy``/``groupByKey`` entity regroup with reservoir caps
(RandomEffectDataSet.scala:171-357) -> per-entity local datasets. Here the
regroup is :mod:`photon_ml_tpu.parallel.shuffle` (one all_to_all over the
mesh) and the per-entity grouping + INDEX_MAP projection + active/passive
split run on the OWNER host over only the rows it received. The active-set
reservoir uses a partitioning-invariant per-row priority, so the trained
model is bit-identical however the input files are assigned to hosts.

Memory: a host materializes its ingested row block and its owned slab —
never the global dataset. Peak host memory scales ~1/n_hosts (asserted by
tests/test_multihost.py via tracemalloc).

Skew: ``size_buckets > 1`` composes the size-bucketed treatment
(algorithm/bucketed_random_effect.py rationale) with the collective
shuffle — entities are partitioned into geometric active-count buckets
with collectively-agreed widths, and each bucket's slab pads only to ITS
width, so an uncapped skewed distribution (one 10^4-row entity among
singletons) no longer pads every entity to the global max. With
``size_buckets=1`` (default) the classic single-slab layout is built;
``active_upper_bound`` remains the hard-cap alternative the reference
always uses in production (RandomEffectDataSet.scala:171-200).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from photon_ml_tpu.parallel.mesh import MeshContext
from photon_ml_tpu.parallel.shuffle import (
    balanced_bucket_owners,
    bucket_of,
    collective_max,
    collective_sum,
    exchange_rows,
    stable_entity_keys,
    stable_row_priority,
)
from photon_ml_tpu.types import real_dtype

Array = jax.Array

# raw entity-id strings ride the exchange as fixed-width UTF-8 so the OWNER
# of an entity (who may never have ingested any of its rows) can write the
# model with real ids; 48 bytes covers every photon id format in the wild.
# Known tradeoff: the id words ship on EVERY row (they widen the all_to_all
# payload by 12 int32 columns); a narrower secondary exchange of one id per
# (source host, entity) would cut shuffle bytes for very sparse rows at the
# cost of a second collective — revisit if the exchange shows up in profiles.
RAW_ID_BYTES = 48


@dataclasses.dataclass
class HostRows:
    """This host's decoded rows (global feature space). ``row_index`` must
    be globally unique and < 2^31 (derive it from the ingest manifest:
    file ordinal x stride + row-in-file)."""

    entity_raw_ids: Sequence[str]  # (n,) raw entity id per row
    row_index: np.ndarray  # (n,) int64 global row id
    labels: np.ndarray  # (n,) float32
    weights: np.ndarray  # (n,) float32
    offsets: np.ndarray  # (n,) float32
    feat_idx: np.ndarray  # (n, K) int32, -1 padded, global feature indices
    feat_val: np.ndarray  # (n, K) float32
    global_dim: int

    @property
    def num_rows(self) -> int:
        return len(self.row_index)


@dataclasses.dataclass
class ShardedREData:
    """Entity-sharded random-effect tensors where each host only ever held
    its own slab. Training tensors are entity-major and device-sharded;
    scoring tensors are row-major over OWNED rows (active + passive) and
    device-sharded — nothing row-global is replicated except the (N,) score
    vector itself."""

    # training (active) tensors, sharded P(axis) on the entity axis
    row_index: Array  # (E_tot, S) int32, -1 pad
    x: Array  # (E_tot, S, D_loc) locally-projected dense
    labels: Array  # (E_tot, S)
    base_offsets: Array  # (E_tot, S)
    weights: Array  # (E_tot, S), 0 = pad
    local_to_global: Array  # (E_tot, D_loc) int32, -1 pad
    entity_keys: Array  # (E_tot, 2) int32 packed u64 key, padding rows 0
    entity_mask: Array  # (E_tot,) bool, False = padding lane
    # scoring tensors over owned rows, sharded P(axis) on the row axis
    score_row_index: Array  # (R_tot,) int32, -1 pad
    score_slot: Array  # (R_tot,) int32 entity slot WITHIN the device slab
    score_feat_idx: Array  # (R_tot, K) int32 local feature indices, -1 pad
    score_feat_val: Array  # (R_tot, K)
    # static metadata (identical on every host)
    num_entities: int  # real entities across all devices
    entities_per_device: int  # padded slab height E_tot / n_dev
    rows_per_device: int  # padded scoring rows R_tot / n_dev
    num_rows: int  # global N
    global_dim: int
    # True when the ingested row ids passed the dense-[0, num_rows) sanity
    # checks (collective max + sum match a permutation of [0, N) — necessary,
    # not sufficient). Sparse (e.g. strided) ids may only be used
    # slab-build-only; PerHostRandomEffectSolver.score refuses them.
    row_ids_dense: bool = True
    # HOST-LOCAL: raw id per entity key for the entities owned by THIS
    # host's devices (decoded from the exchanged fixed-width id bytes) —
    # what model save needs, never a device array
    raw_ids_by_key: Dict[int, str] = dataclasses.field(default_factory=dict)
    # the agreed bucket->device owner map (identical on every host): what
    # SCORING-time row routing needs so validation/inference rows reach the
    # device that holds their entity's model
    bucket_owners: Optional[np.ndarray] = None
    num_buckets: int = 0
    # local-space projector the slabs were built with (ProjectorType.scala
    # semantics): INDEX_MAP | IDENTITY | RANDOM; RANDOM carries the shared
    # host-side Gaussian matrix for routed scoring + model back-projection
    projector: str = "INDEX_MAP"
    projection_matrix: Optional[np.ndarray] = None

    @property
    def local_dim(self) -> int:
        return self.x.shape[-1]


@dataclasses.dataclass
class REBucketSlabs:
    """One size bucket's entity-sharded training slabs: the same training
    tensors as :class:`ShardedREData`, padded only to THIS bucket's
    collectively-agreed (sample, feature) widths."""

    row_index: Array  # (E_tot, S_b) int32, -1 pad
    x: Array  # (E_tot, S_b, D_b)
    labels: Array  # (E_tot, S_b)
    base_offsets: Array  # (E_tot, S_b)
    weights: Array  # (E_tot, S_b), 0 = pad
    local_to_global: Array  # (E_tot, D_b) int32, -1 pad
    entity_keys: Array  # (E_tot, 2) int32 packed u64
    entity_mask: Array  # (E_tot,) bool
    entities_per_device: int  # E_tot / n_dev
    samples_cap: int  # S_b — the bucket's active-count width
    num_entities: int  # real entities in this bucket (global)

    @property
    def local_dim(self) -> int:
        return self.x.shape[-1]


@dataclasses.dataclass
class BucketedShardedREData:
    """Entity-sharded random-effect tensors in size-bucketed form: training
    slabs are a LIST of per-bucket stacks (each padded to its own width),
    scoring tensors are shared row-major arrays whose entity slots index the
    per-device CONCATENATION of the bucket slabs (bucket base + rank)."""

    buckets: List[REBucketSlabs]
    # scoring tensors over owned rows, sharded P(axis) on the row axis
    score_row_index: Array  # (R_tot,) int32, -1 pad
    score_slot: Array  # (R_tot,) int32 slot in the concat of bucket slabs
    score_feat_idx: Array  # (R_tot, K) int32 local feature indices, -1 pad
    score_feat_val: Array  # (R_tot, K)
    num_entities: int
    entities_per_device: int  # sum over buckets of per-bucket heights
    rows_per_device: int
    num_rows: int
    global_dim: int
    local_dim: int  # max over buckets of D_b (scoring matrix width)
    row_ids_dense: bool = True
    raw_ids_by_key: Dict[int, str] = dataclasses.field(default_factory=dict)
    bucket_owners: Optional[np.ndarray] = None
    num_buckets: int = 0
    projector: str = "INDEX_MAP"
    projection_matrix: Optional[np.ndarray] = None

    @property
    def padded_elements(self) -> int:
        """Total x-slab element count across buckets (the skew-blowup
        diagnostic: compare against a single global-width slab)."""
        return sum(int(np.prod(b.x.shape)) for b in self.buckets)


def local_shards(arr: Array, axis: int = 0) -> List[np.ndarray]:
    """This host's shards of an array sharded along ``axis``, ordered by
    their position along that axis. ``addressable_shards`` iteration order
    is NOT documented to match local-device order, and this host's devices
    own a contiguous process-major block of the sharded axis — so sorting
    by the shard's start offset yields exactly local-device order, and two
    same-sharded arrays listed this way align lane-for-lane."""
    shards = sorted(
        arr.addressable_shards, key=lambda s: s.index[axis].start or 0
    )
    return [np.asarray(s.data) for s in shards]


def _pack_u64(keys: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    hi = (keys >> np.uint64(32)).astype(np.uint32).view(np.int32)
    lo = (keys & np.uint64(0xFFFFFFFF)).astype(np.uint32).view(np.int32)
    return hi, lo


def _unpack_u64(hi: np.ndarray, lo: np.ndarray) -> np.ndarray:
    return (hi.view(np.uint32).astype(np.uint64) << np.uint64(32)) | lo.view(
        np.uint32
    ).astype(np.uint64)


def csr_to_padded(feats, n: int) -> Tuple[np.ndarray, np.ndarray]:
    """CSR shard -> row-major padded (feat_idx (n, K) int32 with -1 mask,
    feat_val (n, K) f32) — the HostRows feature encoding."""
    nnz = np.diff(feats.indptr)
    k = max(int(nnz.max()) if n else 1, 1)
    fi = np.full((n, k), -1, np.int32)
    fv = np.zeros((n, k), np.float32)
    rows_rep = np.repeat(np.arange(n), nnz)
    slots = np.arange(len(feats.indices)) - np.repeat(feats.indptr[:-1], nnz)
    fi[rows_rep, slots] = feats.indices
    fv[rows_rep, slots] = feats.values
    return fi, fv


def _pad_to(a: np.ndarray, rows: int, fill) -> np.ndarray:
    if a.shape[0] == rows:
        return a
    pad = np.full((rows - a.shape[0],) + a.shape[1:], fill, a.dtype)
    return np.concatenate([a, pad])


def concat_host_rows(parts: Sequence[HostRows], global_dim: int) -> HostRows:
    """Concatenate per-file HostRows into one block, padding the feature
    width to the widest part (the per-file decode's K varies)."""
    if not parts:
        return HostRows(
            entity_raw_ids=[], row_index=np.zeros(0, np.int64),
            labels=np.zeros(0, np.float32), weights=np.zeros(0, np.float32),
            offsets=np.zeros(0, np.float32),
            feat_idx=np.full((0, 1), -1, np.int32),
            feat_val=np.zeros((0, 1), np.float32),
            global_dim=global_dim,
        )
    k_max = max(p.feat_idx.shape[1] for p in parts)

    def padk(a, fill):
        if a.shape[1] == k_max:
            return a
        ext = np.full((a.shape[0], k_max - a.shape[1]), fill, a.dtype)
        return np.concatenate([a, ext], axis=1)

    return HostRows(
        entity_raw_ids=[r for p in parts for r in p.entity_raw_ids],
        row_index=np.concatenate([p.row_index for p in parts]),
        labels=np.concatenate([p.labels for p in parts]),
        weights=np.concatenate([p.weights for p in parts]),
        offsets=np.concatenate([p.offsets for p in parts]),
        feat_idx=np.concatenate([padk(p.feat_idx, -1) for p in parts]),
        feat_val=np.concatenate([padk(p.feat_val, 0.0) for p in parts]),
        global_dim=global_dim,
    )


def per_host_re_dataset(
    rows: HostRows,
    ctx: MeshContext,
    num_processes: int = 1,
    process_id: int = 0,
    active_upper_bound: Optional[int] = None,
    num_buckets: int = 4096,
    slab_build_only: bool = False,
    size_buckets: int = 1,
    projector: str = "INDEX_MAP",
    projection_matrix: Optional[np.ndarray] = None,
    projection_dim: Optional[int] = None,
    projection_seed: int = 1234567890,
    projection_keep_intercept: bool = True,
) -> "ShardedREData | BucketedShardedREData":
    """Shuffle this host's rows to their entity owners and build the owned
    slabs. Every host calls this collectively (SPMD); the returned dataset's
    arrays are globally sharded with per-host-local backing.

    Resilience: the metadata collectives below (``collective_max`` /
    ``collective_sum``) never dispatch to the device single-process — the
    local value IS the reduction — and degrade to the local value with a
    logged warning when the backend dies under a single-process runtime,
    so a wedged device client cannot throw ``JaxRuntimeError`` out of this
    builder's bookkeeping (shuffle._collective_reduce; genuinely multihost
    failures still raise — a local fallback would desynchronize hosts).

    Row ids must be dense [0, N) across hosts (``global_row_layout`` or
    ``densify_row_ids`` produce that layout): the scoring path scatters into
    a (N,)-sized vector, and under jit an out-of-bounds scatter is DROPPED
    silently, so sparse (e.g. strided ``host_rows_from_avro``) ids would
    produce wrong scores with no error. Non-dense ids therefore raise here
    unless ``slab_build_only=True``, which marks the result so scoring
    refuses it loudly instead.

    ``size_buckets=1`` returns :class:`ShardedREData` (one slab padded to
    the global max active count); ``size_buckets>1`` returns
    :class:`BucketedShardedREData` with up to that many geometric
    active-count buckets, each padded only to its own collectively-agreed
    width — the skew-proof layout for uncapped entity distributions.

    ``projector`` selects the per-entity local feature space
    (projector/ProjectorType.scala:22-30 semantics):

    - ``"INDEX_MAP"`` (default): each entity's local space is the features
      it actually saw in training (IndexMapProjectorRDD.scala:30-119);
    - ``"IDENTITY"``: the local space IS the global shard space (what the
      factored coordinate requires — its latent matrix projects globally);
    - ``"RANDOM"``: every row is projected through a shared Gaussian matrix
      (ProjectionMatrix.scala:31-119) at slab-build time, so all entities
      share one dense ``projection_dim``(+intercept)-wide space. The matrix
      is derived deterministically from ``projection_seed`` (identical on
      every host with no collective) unless ``projection_matrix`` is given.
    """
    if projector not in ("INDEX_MAP", "IDENTITY", "RANDOM"):
        raise ValueError(f"unknown projector {projector!r}")
    if projector == "RANDOM":
        if projection_matrix is None:
            if projection_dim is None:
                raise ValueError(
                    "RANDOM projector needs projection_dim (or a prebuilt "
                    "projection_matrix)"
                )
            from photon_ml_tpu.projectors import (
                gaussian_random_projection_matrix,
            )

            projection_matrix = gaussian_random_projection_matrix(
                projection_dim, rows.global_dim,
                keep_intercept=projection_keep_intercept,
                seed=projection_seed,
            )
        projection_matrix = np.asarray(projection_matrix, real_dtype())
        if projection_matrix.shape[1] != rows.global_dim:
            raise ValueError(
                f"projection matrix is {projection_matrix.shape}, dataset "
                f"global_dim is {rows.global_dim}"
            )
        k_proj = projection_matrix.shape[0]
    else:
        projection_matrix = None
        k_proj = 0
    n_dev = ctx.num_devices
    local = max(n_dev // num_processes, 1)
    keys = stable_entity_keys(rows.entity_raw_ids)

    # ---- agree on record width (global max nnz) + row-id bounds ----------
    local_max_row = int(rows.row_index.max()) if rows.num_rows else -1
    km = collective_max(
        np.asarray([rows.feat_idx.shape[1], local_max_row]), ctx, num_processes
    )
    k, g_max_row = int(km[0]), int(km[1])
    sums = collective_sum(
        np.asarray(
            [rows.num_rows, int(rows.row_index.sum())], np.int64
        ),
        ctx,
        num_processes,
    )
    n_global, g_id_sum = int(sums[0]), int(sums[1])
    # necessary (not sufficient) sanity check for ids == permutation of
    # [0, N): right max AND right sum — catches the common off-by-stride /
    # duplicated-base bugs without an O(N log N) collective sort
    row_ids_dense = (
        g_max_row == n_global - 1
        and g_id_sum == n_global * (n_global - 1) // 2
    )
    if not row_ids_dense and not slab_build_only:
        raise ValueError(
            f"row ids are not dense [0, N): max id {g_max_row} vs "
            f"{n_global} global rows. Use global_row_layout / "
            "densify_row_ids to assign dense ids (host_rows_from_avro's "
            "strided ids are slab-build-only), or pass slab_build_only=True "
            "if this dataset will never be scored."
        )
    fi = _pad_to(rows.feat_idx.astype(np.int32).T, k, -1).T if rows.feat_idx.shape[1] != k else rows.feat_idx.astype(np.int32)
    fv = _pad_to(rows.feat_val.astype(np.float32).T, k, 0.0).T if rows.feat_val.shape[1] != k else rows.feat_val.astype(np.float32)

    # ---- balanced owner map from collectively-summed bucket counts --------
    buckets = bucket_of(keys, num_buckets)
    counts = np.bincount(buckets, minlength=num_buckets).astype(np.int64)
    g_counts = collective_sum(counts, ctx, num_processes)
    owners = balanced_bucket_owners(g_counts, n_dev)
    dest = owners[buckets]

    # ---- pack + exchange --------------------------------------------------
    hi, lo = _pack_u64(keys)
    raw_words = RAW_ID_BYTES // 4
    raw_bytes = np.zeros((rows.num_rows, RAW_ID_BYTES), np.uint8)
    for i, rid in enumerate(rows.entity_raw_ids):
        b = rid.encode("utf-8")
        if len(b) > RAW_ID_BYTES:
            raise ValueError(
                f"entity id {rid!r} exceeds {RAW_ID_BYTES} UTF-8 bytes"
            )
        raw_bytes[i, : len(b)] = np.frombuffer(b, np.uint8)
    raw_i32 = raw_bytes.view(np.int32)  # (n, raw_words)
    int_payload = np.concatenate(
        [rows.row_index.astype(np.int32)[:, None], hi[:, None], lo[:, None],
         raw_i32, fi], axis=1
    )
    flt_payload = np.concatenate(
        [
            rows.labels.astype(np.float32)[:, None],
            rows.weights.astype(np.float32)[:, None],
            rows.offsets.astype(np.float32)[:, None],
            fv,
        ],
        axis=1,
    )
    ex = exchange_rows(dest, int_payload, flt_payload, ctx, num_processes, process_id)

    # ---- per owned device: group, cap, project, measure -------------------
    per_dev = []
    for ld in range(local):
        bi, bf = ex.int_rows[ld], ex.float_rows[ld]
        okeys = _unpack_u64(bi[:, 1], bi[:, 2])
        orow = bi[:, 0].astype(np.int64)
        prio = stable_row_priority(okeys, orow)
        # group by entity, priority-ordered within (ties broken by row id,
        # then row id as final key for full determinism)
        order = np.lexsort((orow, prio, okeys))
        okeys, orow, prio = okeys[order], orow[order], prio[order]
        oraw = bi[order, 3 : 3 + raw_words]
        ofi, ofv = bi[order, 3 + raw_words :], bf[order, 3:]
        olab, owgt, ooff = bf[order, 0], bf[order, 1], bf[order, 2]
        uniq, ent_start, inv = np.unique(okeys, return_index=True, return_inverse=True)
        e_d = len(uniq)
        cnt = np.bincount(inv, minlength=e_d)
        rank = np.arange(len(okeys)) - ent_start[inv]
        cap = active_upper_bound or (int(cnt.max()) if e_d else 1)
        active = rank < cap
        # kept weights rescaled so the active set represents the entity
        # (RandomEffectDataSet.scala:298-301)
        scale = np.where(cnt > cap, cnt / cap, 1.0)
        wgt_eff = owgt * np.where(active, scale[inv], 1.0)
        # per-entity local feature space, by projector
        xproj = None
        if projector == "INDEX_MAP":
            # active feature set -> per-entity compacted index map
            a_rows = np.nonzero(active)[0]
            pe = np.repeat(inv[a_rows], ofi.shape[1])
            pf = ofi[a_rows].reshape(-1)
            keep = pf >= 0
            pair = np.unique(pe[keep].astype(np.int64) * rows.global_dim + pf[keep])
            pair_e = (pair // rows.global_dim).astype(np.int64)
            pair_f = (pair % rows.global_dim).astype(np.int64)
            dims = np.bincount(pair_e, minlength=e_d)
        elif projector == "IDENTITY":
            # local index == global index; no per-entity compaction
            pair_e = pair_f = np.zeros(0, np.int64)
            dims = np.full(e_d, rows.global_dim, np.int64)
        else:  # RANDOM: project every owned row through the shared matrix
            pair_e = pair_f = np.zeros(0, np.int64)
            dims = np.full(e_d, k_proj, np.int64)
            nr_d = len(orow)
            xproj = np.zeros((nr_d, k_proj), real_dtype())
            pm_t = projection_matrix.T  # (D_global, k_proj)
            for lo_r in range(0, nr_d, 8192):
                sl = slice(lo_r, min(lo_r + 8192, nr_d))
                fi_b = ofi[sl]
                fv_b = ofv[sl]
                cols = pm_t[np.maximum(fi_b, 0)]  # (B, K, k_proj)
                vals = np.where(fi_b >= 0, fv_b, 0.0)
                xproj[sl] = np.einsum("bk,bkp->bp", vals, cols)
        raw_ids = {}
        for e, first in enumerate(ent_start):
            b = np.ascontiguousarray(oraw[first]).view(np.uint8).tobytes()
            raw_ids[int(uniq[e])] = b.rstrip(b"\x00").decode("utf-8")
        per_dev.append(
            dict(
                keys=uniq, row=orow, inv=inv, rank=rank, active=active,
                fi=ofi, fv=ofv, lab=olab, wgt=wgt_eff, off=ooff, cnt=cnt,
                pair_e=pair_e, pair_f=pair_f, dims=dims, cap=cap,
                raw_ids=raw_ids, xproj=xproj,
            )
        )

    # ---- agree on uniform tensor dims (one collective max) ----------------
    # int64 reduces are exact (shuffle._collective_reduce runs them under
    # jax.enable_x64), so the int64 min is a safe "no entities" sentinel
    NEG_SENTINEL = np.iinfo(np.int64).min
    local_meta = np.zeros(5, np.int64)
    local_meta[4] = NEG_SENTINEL
    for d in per_dev:
        e_d = len(d["keys"])
        local_meta[0] = max(local_meta[0], e_d)  # entities per device
        if e_d:
            a_e = np.minimum(d["cnt"], d["cap"])
            local_meta[1] = max(local_meta[1], int(a_e.max()))
            local_meta[2] = max(local_meta[2], int(d["dims"].max()) if len(d["dims"]) else 1)
            # negated min: one collective_max also agrees the global MIN
            # active count (the geometric bucket base)
            local_meta[4] = max(local_meta[4], -int(a_e.min()))
        local_meta[3] = max(local_meta[3], len(d["row"]))  # owned rows
    e_max, s_max, d_loc, r_max, neg_min = (
        int(v) for v in collective_max(local_meta, ctx, num_processes)
    )
    e_max, s_max, d_loc, r_max = max(e_max, 1), max(s_max, 1), max(d_loc, 1), max(r_max, 1)
    g_min_act = max(-neg_min, 1) if neg_min > NEG_SENTINEL else 1
    real_entities = int(
        collective_sum(
            np.asarray([sum(len(d["keys"]) for d in per_dev)], np.int64),
            ctx,
            num_processes,
        )[0]
    )

    # ---- agree on bucket widths + per-bucket dims -------------------------
    # geometric widths doubling from the global min active count; the last
    # bucket absorbs everything up to the global max. Deterministic from
    # (g_min_act, s_max, size_buckets) alone — every host derives the same
    # partition with no extra collective.
    nb = max(int(size_buckets), 1)
    if nb > 1:
        widths = sorted(
            {min(g_min_act << b, s_max) for b in range(nb - 1)} | {s_max}
        )
    else:
        widths = [s_max]
    warr = np.asarray(widths, np.int64)
    nb_eff = len(widths)

    if nb_eff == 1:
        # single-slab default: the bucket dims ARE the already-collected
        # local_meta maxima — skip the two extra cross-host reductions
        for d in per_dev:
            e_d = len(d["keys"])
            d["bidx"] = np.zeros(e_d, np.int64)
            d["bslot"] = np.arange(e_d, dtype=np.int64)
        kept = [0]
        bdims = [(e_max, s_max, d_loc)]
        bucket_counts = np.asarray([real_entities], np.int64)
    else:
        bmeta = np.zeros(3 * nb_eff, np.int64)
        bucket_counts_local = np.zeros(nb_eff, np.int64)
        for d in per_dev:
            e_d = len(d["keys"])
            if not e_d:
                d["bidx"] = np.zeros(0, np.int64)
                d["bslot"] = np.zeros(0, np.int64)
                continue
            a_e = np.minimum(d["cnt"], d["cap"])
            bidx = np.searchsorted(warr, a_e, side="left")  # first width >= a_e
            bslot = np.zeros(e_d, np.int64)
            for b in range(nb_eff):
                sel = bidx == b
                n_sel = int(sel.sum())
                # slot = rank within the bucket on this device (key-sorted
                # order is preserved, so slots are deterministic)
                bslot[sel] = np.arange(n_sel)
                bucket_counts_local[b] += n_sel
                bmeta[3 * b] = max(bmeta[3 * b], n_sel)
                if n_sel:
                    bmeta[3 * b + 1] = max(bmeta[3 * b + 1], int(a_e[sel].max()))
                    dm = d["dims"][sel]
                    bmeta[3 * b + 2] = max(
                        bmeta[3 * b + 2], int(dm.max()) if len(dm) else 1
                    )
            d["bidx"], d["bslot"] = bidx, bslot
        g_bmeta = collective_max(bmeta, ctx, num_processes)
        bucket_counts = collective_sum(bucket_counts_local, ctx, num_processes)
        # drop globally-empty buckets (agreed: g_bmeta is collective)
        kept = [b for b in range(nb_eff) if int(g_bmeta[3 * b]) > 0]
        if not kept:
            kept = [0]
        # (entities/device, sample width, local feature width) per kept bucket
        bdims = [
            (
                max(int(g_bmeta[3 * b]), 1),
                max(int(g_bmeta[3 * b + 1]), 1),
                max(int(g_bmeta[3 * b + 2]), 1),
            )
            for b in kept
        ]
    pos_of_bucket = np.full(nb_eff, -1, np.int64)
    pos_of_bucket[kept] = np.arange(len(kept))
    bucket_base = np.concatenate(
        [[0], np.cumsum([bd[0] for bd in bdims])[:-1]]
    ).astype(np.int64)
    d_loc_max = max(bd[2] for bd in bdims)

    # ---- build the slabs --------------------------------------------------
    dt = real_dtype()
    train_names = (
        "row_index", "x", "labels", "base_offsets", "weights",
        "local_to_global", "entity_keys", "entity_mask",
    )
    score_names = (
        "score_row_index", "score_slot", "score_feat_idx", "score_feat_val",
    )
    tblocks: List[Dict[str, List[np.ndarray]]] = [
        {f: [] for f in train_names} for _ in kept
    ]
    sblocks: Dict[str, List[np.ndarray]] = {f: [] for f in score_names}
    k_sc = k_proj if projector == "RANDOM" else k  # scoring feature width
    for d in per_dev:
        e_d = len(d["keys"])
        nr = len(d["row"])
        # per-row local projection (shared by scoring + every bucket's
        # training block)
        li = lv = loc_idx = None
        if e_d:
            if projector == "INDEX_MAP":
                # the sorted (entity, feature) composite lookup
                ent_start_pairs = np.searchsorted(d["pair_e"], np.arange(e_d), side="left")
                loc_idx = np.arange(len(d["pair_e"])) - ent_start_pairs[d["pair_e"]]
                comp_keys = d["pair_e"] * rows.global_dim + d["pair_f"]
                rr = np.repeat(np.arange(nr), d["fi"].shape[1])
                cc = d["fi"].reshape(-1).astype(np.int64)
                valid = cc >= 0
                comp = d["inv"][rr].astype(np.int64) * rows.global_dim + cc
                pos = np.searchsorted(comp_keys, comp)
                pos_c = np.clip(pos, 0, max(len(comp_keys) - 1, 0))
                hit = valid & (len(comp_keys) > 0) & (comp_keys[pos_c] == comp)
                li = np.where(hit, loc_idx[pos_c], -1).reshape(nr, -1).astype(np.int32)
                lv = np.where(hit.reshape(nr, -1), d["fv"], 0.0)
            elif projector == "IDENTITY":
                li = d["fi"].astype(np.int32)  # local index IS global index
                lv = d["fv"]
            else:  # RANDOM: rows are dense k_proj-vectors in the shared space
                li = np.tile(np.arange(k_proj, dtype=np.int32), (nr, 1))
                lv = d["xproj"]
        # scoring tensors: every owned row; entity slot = bucket base + rank
        # within the bucket (indexes the per-device CONCAT of bucket slabs)
        sri = np.full((r_max,), -1, np.int32)
        ssl = np.zeros((r_max,), np.int32)
        sfi = np.full((r_max, k_sc), -1, np.int32)
        sfv = np.zeros((r_max, k_sc), dt)
        if e_d:
            gslot = bucket_base[pos_of_bucket[d["bidx"]]] + d["bslot"]
            sri[:nr] = d["row"].astype(np.int32)
            ssl[:nr] = gslot[d["inv"]].astype(np.int32)
            sfi[:nr] = li
            sfv[:nr] = lv
        sblocks["score_row_index"].append(sri)
        sblocks["score_slot"].append(ssl)
        sblocks["score_feat_idx"].append(sfi)
        sblocks["score_feat_val"].append(sfv)
        # per-bucket training tensors, padded to the bucket's own widths
        for bpos, b in enumerate(kept):
            e_max_b, s_b, dl_b = bdims[bpos]
            tri = np.full((e_max_b, s_b), -1, np.int32)
            tx = np.zeros((e_max_b, s_b, dl_b), dt)
            tlab = np.zeros((e_max_b, s_b), dt)
            toff = np.zeros((e_max_b, s_b), dt)
            twgt = np.zeros((e_max_b, s_b), dt)
            l2g = np.full((e_max_b, dl_b), -1, np.int32)
            ekeys = np.zeros((e_max_b, 2), np.int32)
            emask = np.zeros((e_max_b,), bool)
            if e_d:
                in_b = d["bidx"] == b  # (e_d,) entity membership
                sel_e = np.nonzero(in_b)[0]  # key-sorted; bslot == arange
                n_b = len(sel_e)
                if n_b:
                    emask[:n_b] = True
                    hi_d, lo_d = _pack_u64(d["keys"][sel_e])
                    ekeys[:n_b, 0], ekeys[:n_b, 1] = hi_d, lo_d
                    if projector == "INDEX_MAP":
                        pe_in = in_b[d["pair_e"]]
                        l2g[
                            d["bslot"][d["pair_e"][pe_in]], loc_idx[pe_in]
                        ] = d["pair_f"][pe_in].astype(np.int32)
                    elif projector == "IDENTITY":
                        # local space == global space for every entity lane
                        l2g[:n_b] = np.arange(dl_b, dtype=np.int32)
                    # RANDOM: l2g stays -1 — back-projection goes through
                    # the shared matrix, not a per-entity index map
                    # training rows: active rows of this bucket's entities
                    act = d["active"] & in_b[d["inv"]]
                    er = d["bslot"][d["inv"][act]]
                    rk = d["rank"][act]
                    tri[er, rk] = d["row"][act].astype(np.int32)
                    tlab[er, rk] = d["lab"][act]
                    toff[er, rk] = d["off"][act]
                    twgt[er, rk] = d["wgt"][act]
                    arow = np.nonzero(act)[0]
                    dense = np.zeros((len(arow), dl_b), dt)
                    rows2 = np.repeat(np.arange(len(arow)), li.shape[1])
                    lia = li[arow].reshape(-1)
                    lva = lv[arow].reshape(-1)
                    ok = lia >= 0
                    dense[rows2[ok], lia[ok]] = lva[ok]
                    tx[er, rk] = dense
            tb = tblocks[bpos]
            tb["row_index"].append(tri)
            tb["x"].append(tx)
            tb["labels"].append(tlab)
            tb["base_offsets"].append(toff)
            tb["weights"].append(twgt)
            tb["local_to_global"].append(l2g)
            tb["entity_keys"].append(ekeys)
            tb["entity_mask"].append(emask)

    sharding = NamedSharding(ctx.mesh, P(ctx.axis))

    def shard(blocks, name):
        return jax.make_array_from_process_local_data(
            sharding, np.concatenate(blocks[name], axis=0)
        )

    raw_ids = {k: v for d in per_dev for k, v in d["raw_ids"].items()}
    if nb == 1:
        # classic single-slab layout (bucket 0 IS the global-width slab)
        tb = tblocks[0]
        return ShardedREData(
            row_index=shard(tb, "row_index"),
            x=shard(tb, "x"),
            labels=shard(tb, "labels"),
            base_offsets=shard(tb, "base_offsets"),
            weights=shard(tb, "weights"),
            local_to_global=shard(tb, "local_to_global"),
            entity_keys=shard(tb, "entity_keys"),
            entity_mask=shard(tb, "entity_mask"),
            score_row_index=shard(sblocks, "score_row_index"),
            score_slot=shard(sblocks, "score_slot"),
            score_feat_idx=shard(sblocks, "score_feat_idx"),
            score_feat_val=shard(sblocks, "score_feat_val"),
            num_entities=real_entities,
            entities_per_device=bdims[0][0],
            rows_per_device=r_max,
            num_rows=n_global,
            global_dim=rows.global_dim,
            row_ids_dense=row_ids_dense,
            raw_ids_by_key=raw_ids,
            bucket_owners=owners,
            num_buckets=num_buckets,
            projector=projector,
            projection_matrix=projection_matrix,
        )

    bucket_slabs = [
        REBucketSlabs(
            row_index=shard(tb, "row_index"),
            x=shard(tb, "x"),
            labels=shard(tb, "labels"),
            base_offsets=shard(tb, "base_offsets"),
            weights=shard(tb, "weights"),
            local_to_global=shard(tb, "local_to_global"),
            entity_keys=shard(tb, "entity_keys"),
            entity_mask=shard(tb, "entity_mask"),
            entities_per_device=bdims[bpos][0],
            samples_cap=bdims[bpos][1],
            num_entities=int(bucket_counts[kept[bpos]]),
        )
        for bpos, tb in enumerate(tblocks)
    ]
    return BucketedShardedREData(
        buckets=bucket_slabs,
        score_row_index=shard(sblocks, "score_row_index"),
        score_slot=shard(sblocks, "score_slot"),
        score_feat_idx=shard(sblocks, "score_feat_idx"),
        score_feat_val=shard(sblocks, "score_feat_val"),
        num_entities=real_entities,
        entities_per_device=int(sum(bd[0] for bd in bdims)),
        rows_per_device=r_max,
        num_rows=n_global,
        global_dim=rows.global_dim,
        local_dim=d_loc_max,
        row_ids_dense=row_ids_dense,
        raw_ids_by_key=raw_ids,
        bucket_owners=owners,
        num_buckets=num_buckets,
        projector=projector,
        projection_matrix=projection_matrix,
    )


# ---------------------------------------------------------------------------
# the solver over per-host-built slabs (drop-in CoordinateDescent coordinate)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class PerHostRandomEffectSolver:
    """Entity-sharded random-effect coordinate over :class:`ShardedREData`.

    Same contract as algorithm.random_effect.RandomEffectCoordinate (update /
    score / initial_coefficients / regularization_term), but every tensor it
    touches was built per host: update is the vmapped local-solve kernel
    under shard_map (zero collectives — entities are independent), scoring is
    owner-computes: each device scores its OWN rows from its OWN slab and one
    psum merges the (N,) partials (coefficients never move; scores do —
    the transpose of RandomEffectCoordinate.scala:139-146's model collect)."""

    data: ShardedREData
    task: "TaskType"
    optimizer: "OptimizerType"
    optimizer_config: "OptimizerConfig"
    regularization: "RegularizationContext"
    ctx: MeshContext

    def __post_init__(self):
        self._update_fn = None
        self._score_fn = None
        # under multihost SPMD the sharded arrays are non-addressable and
        # CANNOT be closed over by an outer jit — CoordinateDescent must
        # call update/score raw (they jit internally with the global arrays
        # as ARGS). Single-process, everything is addressable and the
        # coordinate composes with fused_cycle / run_grid like any other.
        self.cd_jit = jax.process_count() == 1

    @property
    def local_dim(self) -> int:
        return self.data.local_dim

    def initial_coefficients(self) -> Array:
        w0 = jnp.zeros(
            (self.data.entity_mask.shape[0], self.data.local_dim), real_dtype()
        )
        return jax.device_put(w0, NamedSharding(self.ctx.mesh, P(self.ctx.axis)))

    def _coordinate_for(self, ds):
        from photon_ml_tpu.algorithm.random_effect import RandomEffectCoordinate

        # sparse_kernel="off": constructed inside jit(shard_map) — must not
        # re-resolve PHOTON_SPARSE_KERNEL under the trace (no per-host slab
        # selection on the mesh path)
        return RandomEffectCoordinate(
            ds, self.task, self.optimizer, self.optimizer_config,
            self.regularization, sparse_kernel="off",
        )

    def update(self, residual_offsets: Array, init_coefficients: Array):
        from photon_ml_tpu.data.game import RandomEffectDataset

        if self._update_fn is None:
            axis = self.ctx.axis
            d = self.data

            def solve_shard(x, labels, offs, wgts, row_index, w0, residuals):
                dummy = jnp.zeros((1,), jnp.int32)
                ds = RandomEffectDataset(
                    row_index=row_index, x=x, labels=labels, base_offsets=offs,
                    weights=wgts, entity_pos=dummy, feat_idx=dummy[None],
                    feat_val=dummy[None].astype(x.dtype),
                    local_to_global=dummy[None],
                    num_entities=x.shape[0], global_dim=d.global_dim,
                )
                return self._coordinate_for(ds).update(residuals, w0)

            self._update_fn = jax.jit(
                shard_map(
                    solve_shard,
                    mesh=self.ctx.mesh,
                    in_specs=(
                        P(axis), P(axis), P(axis), P(axis), P(axis), P(axis), P(),
                    ),
                    out_specs=(P(axis), P(axis)),
                    # same rationale as DistributedRandomEffectSolver: the
                    # replicated zero-init loop carries inside the vmapped
                    # while_loop kernel trip the varying-axes check although
                    # the body has zero collectives; the mandated
                    # compensating control is the sharded-vs-single-process
                    # equivalence assert in tests/test_perhost_ingest.py
                    check_vma=False,
                )
            )
        d = self.data
        residuals = jax.device_put(
            residual_offsets, NamedSharding(self.ctx.mesh, P())
        )
        return self._update_fn(
            d.x, d.labels, d.base_offsets, d.weights, d.row_index,
            self._sharded_init(init_coefficients), residuals,
        )

    def _sharded_init(self, w0) -> Array:
        """Accept either an already entity-sharded array or a HOST-side
        global array (e.g. a restored checkpoint): multihost jit cannot
        commit host data to a cross-process sharding implicitly, so slice
        this host's slab and contribute it explicitly."""
        if isinstance(w0, jax.core.Tracer):
            return w0  # inside an outer jit (fused_cycle) — already placed
        if isinstance(w0, jax.Array):
            # already device-resident: device_put is a no-op when the
            # sharding matches (never round-trip the slab through the host)
            if not w0.is_fully_addressable:
                return w0
            return jax.device_put(
                w0, NamedSharding(self.ctx.mesh, P(self.ctx.axis))
            )
        host = np.asarray(w0)
        n_proc = jax.process_count()
        if n_proc == 1:
            return jax.device_put(
                host, NamedSharding(self.ctx.mesh, P(self.ctx.axis))
            )
        per = host.shape[0] // n_proc
        sl = slice(jax.process_index() * per, (jax.process_index() + 1) * per)
        return jax.make_array_from_process_local_data(
            NamedSharding(self.ctx.mesh, P(self.ctx.axis)), host[sl]
        )

    def score(self, coefficients: Array) -> Array:
        if not self.data.row_ids_dense:
            raise ValueError(
                "dataset was built slab_build_only from non-dense row ids; "
                "scoring would silently drop out-of-bounds scatters — "
                "rebuild with dense [0, N) ids (densify_row_ids)"
            )
        if self._score_fn is None:
            axis = self.ctx.axis
            n = self.data.num_rows

            def score_shard(w_loc, srow, sslot, sfi, sfv):
                # w_loc (E_loc, D); rows reference entity slots in THIS slab
                wsel = w_loc[jnp.maximum(sslot, 0)]  # (R, D)
                vals = jnp.take_along_axis(wsel, jnp.maximum(sfi, 0), axis=-1)
                vals = jnp.where(sfi >= 0, vals * sfv, 0.0)
                s = jnp.where(srow >= 0, jnp.sum(vals, axis=-1), 0.0)
                out = jnp.zeros((n,), s.dtype).at[jnp.maximum(srow, 0)].add(
                    jnp.where(srow >= 0, s, 0.0)
                )
                return jax.lax.psum(out, axis)

            self._score_fn = jax.jit(
                shard_map(
                    score_shard,
                    mesh=self.ctx.mesh,
                    in_specs=(P(axis), P(axis), P(axis), P(axis), P(axis)),
                    out_specs=P(),
                )
            )
        d = self.data
        return self._score_fn(
            coefficients, d.score_row_index, d.score_slot,
            d.score_feat_idx, d.score_feat_val,
        )

    def regularization_term(self, coefficients: Array) -> Array:
        l1 = self.regularization.l1_weight
        l2 = self.regularization.l2_weight
        return l1 * jnp.sum(jnp.abs(coefficients)) + 0.5 * l2 * jnp.sum(
            jnp.square(coefficients)
        )


@dataclasses.dataclass
class PerHostBucketedRandomEffectSolver(PerHostRandomEffectSolver):
    """Size-bucketed variant of :class:`PerHostRandomEffectSolver` over
    :class:`BucketedShardedREData`: coefficients are a TUPLE of per-bucket
    entity-sharded (E_b, D_b) arrays (same pytree contract as
    algorithm.bucketed_random_effect), the vmapped solve runs once per
    bucket (each padded only to its own width), and scoring concatenates
    the per-device bucket slabs so one gather serves all buckets."""

    data: "BucketedShardedREData"  # type: ignore[assignment]

    def initial_coefficients(self) -> Tuple[Array, ...]:
        shardng = NamedSharding(self.ctx.mesh, P(self.ctx.axis))
        return tuple(
            jax.device_put(
                jnp.zeros((b.entity_mask.shape[0], b.local_dim), real_dtype()),
                shardng,
            )
            for b in self.data.buckets
        )

    def update(self, residual_offsets: Array, init_coefficients):
        from photon_ml_tpu.data.game import RandomEffectDataset

        if self._update_fn is None:
            axis = self.ctx.axis
            gdim = self.data.global_dim

            def solve_shard(x, labels, offs, wgts, row_index, w0, residuals):
                dummy = jnp.zeros((1,), jnp.int32)
                ds = RandomEffectDataset(
                    row_index=row_index, x=x, labels=labels, base_offsets=offs,
                    weights=wgts, entity_pos=dummy, feat_idx=dummy[None],
                    feat_val=dummy[None].astype(x.dtype),
                    local_to_global=dummy[None],
                    num_entities=x.shape[0], global_dim=gdim,
                )
                return self._coordinate_for(ds).update(residuals, w0)

            # one jitted shard_map serves every bucket: jit re-specializes
            # per (E_b, S_b, D_b) shape, so each bucket compiles once
            self._update_fn = jax.jit(
                shard_map(
                    solve_shard,
                    mesh=self.ctx.mesh,
                    in_specs=(
                        P(axis), P(axis), P(axis), P(axis), P(axis), P(axis), P(),
                    ),
                    out_specs=(P(axis), P(axis)),
                    # same rationale + compensating equivalence test as the
                    # monolithic solver (tests/test_perhost_ingest.py)
                    check_vma=False,
                )
            )
        residuals = jax.device_put(
            residual_offsets, NamedSharding(self.ctx.mesh, P())
        )
        new_state, results = [], []
        for b, w0 in zip(self.data.buckets, init_coefficients):
            w, res = self._update_fn(
                b.x, b.labels, b.base_offsets, b.weights, b.row_index,
                self._sharded_init(w0), residuals,
            )
            new_state.append(w)
            results.append(res)
        return tuple(new_state), tuple(results)

    def score(self, state) -> Array:
        if not self.data.row_ids_dense:
            raise ValueError(
                "dataset was built slab_build_only from non-dense row ids; "
                "scoring would silently drop out-of-bounds scatters — "
                "rebuild with dense [0, N) ids (densify_row_ids)"
            )
        if self._score_fn is None:
            axis = self.ctx.axis
            n = self.data.num_rows
            d_max = self.data.local_dim

            def score_shard(ws, srow, sslot, sfi, sfv):
                # per-device concat of the bucket slabs, feature axis padded
                # to the shared scoring width — slots were assigned against
                # exactly this layout at build time
                w_cat = jnp.concatenate(
                    [
                        jnp.pad(w, ((0, 0), (0, d_max - w.shape[-1])))
                        for w in ws
                    ],
                    axis=0,
                )
                wsel = w_cat[jnp.maximum(sslot, 0)]  # (R, D_max)
                vals = jnp.take_along_axis(wsel, jnp.maximum(sfi, 0), axis=-1)
                vals = jnp.where(sfi >= 0, vals * sfv, 0.0)
                s = jnp.where(srow >= 0, jnp.sum(vals, axis=-1), 0.0)
                out = jnp.zeros((n,), s.dtype).at[jnp.maximum(srow, 0)].add(
                    jnp.where(srow >= 0, s, 0.0)
                )
                return jax.lax.psum(out, axis)

            self._score_fn = jax.jit(
                shard_map(
                    score_shard,
                    mesh=self.ctx.mesh,
                    in_specs=(
                        tuple(P(axis) for _ in self.data.buckets),
                        P(axis), P(axis), P(axis), P(axis),
                    ),
                    out_specs=P(),
                )
            )
        d = self.data
        return self._score_fn(
            tuple(state), d.score_row_index, d.score_slot,
            d.score_feat_idx, d.score_feat_val,
        )

    def regularization_term(self, state) -> Array:
        l1 = self.regularization.l1_weight
        l2 = self.regularization.l2_weight
        return sum(
            (
                l1 * jnp.sum(jnp.abs(w)) + 0.5 * l2 * jnp.sum(jnp.square(w))
                for w in state
            ),
            jnp.asarray(0.0, real_dtype()),
        )


# ---------------------------------------------------------------------------
# per-host Avro decode (the DataProcessingUtils per-partition analogue)
# ---------------------------------------------------------------------------


def host_rows_from_avro(
    host_files: Sequence[str],
    file_ordinals: Sequence[int],
    index_map,
    random_effect_id: str,
    shard_id: str,
    shard_sections: Sequence[str],
    intercept: bool = True,
    row_stride: int = 1 << 22,
    prefetch_depth: Optional[int] = None,
) -> HostRows:
    """Decode ONLY this host's Avro part files into :class:`HostRows`.

    The real-driver entry to per-host ingest (DataProcessingUtils.scala:
    57-80 semantics): ``host_files`` is this host's slice of the input
    (``MultihostContext.host_shard_paths``), ``file_ordinals`` their
    positions in the GLOBAL sorted file list — global row ids are
    ``ordinal * row_stride + row_in_file``, unique without any cross-host
    coordination as long as every file holds < row_stride rows. These
    strided ids are SPARSE: pass the result through :func:`densify_row_ids`
    (one collective) before :func:`per_host_re_dataset` if the dataset will
    be scored — the build rejects sparse ids otherwise. The feature
    index map is consulted per decoded record; with the off-heap store
    (io/offheap.py) the backing is mmap'd, so each host faults in only the
    index pages its own partitions touch — per-partition index-map
    instantiation without explicit partition files.

    The per-file decode is the per-host block iteration of the async data
    pipeline (io/pipeline.py): up to ``prefetch_depth`` files decode on a
    background thread while the consumer pads/assembles earlier files'
    rows, so disk read + Avro decode overlap the tensor assembly. File
    order (and therefore every produced tensor) is identical pipelined or
    not.
    """
    from photon_ml_tpu.io.avro_data import read_game_data
    from photon_ml_tpu.io.pipeline import Prefetcher

    file_ordinals = list(file_ordinals)
    if len(host_files) != len(file_ordinals):
        raise ValueError(
            f"{len(host_files)} files but {len(file_ordinals)} ordinals — "
            "a mismatch would silently drop input files"
        )
    max_ord = max(file_ordinals) if file_ordinals else 0
    if (max_ord + 1) * row_stride >= 2**31:
        raise ValueError(
            f"file ordinal {max_ord} x stride {row_stride} overflows the "
            "int32 row-id space; lower row_stride or merge input files"
        )

    def decode_all():
        for path, ordinal in zip(host_files, file_ordinals):
            gd = read_game_data(
                [path],
                {shard_id: index_map},
                {shard_id: list(shard_sections)},
                [random_effect_id],
                shard_intercepts={shard_id: intercept},
            )
            yield path, ordinal, gd

    parts: List[HostRows] = []
    for path, ordinal, gd in Prefetcher(
        decode_all, depth=prefetch_depth, name="avro-decode-prefetch"
    ):
        feats = gd.shards[shard_id]
        n = gd.num_rows
        fi, fv = csr_to_padded(feats, n)
        vocab = gd.id_vocabs[random_effect_id]
        if n >= row_stride:
            raise ValueError(f"{path}: {n} rows exceeds row_stride {row_stride}")
        parts.append(
            HostRows(
                entity_raw_ids=[vocab[i] for i in gd.ids[random_effect_id]],
                row_index=ordinal * row_stride + np.arange(n, dtype=np.int64),
                labels=gd.response.astype(np.float32),
                weights=gd.weight.astype(np.float32),
                offsets=gd.offset.astype(np.float32),
                feat_idx=fi,
                feat_val=fv,
                global_dim=feats.dim,
            )
        )
    return concat_host_rows(parts, len(index_map))


def densify_row_ids(
    rows: HostRows,
    row_stride: int,
    ctx: MeshContext,
    num_processes: int = 1,
) -> HostRows:
    """Rewrite :func:`host_rows_from_avro`'s strided global row ids
    (``ordinal * row_stride + row_in_file``) into the dense [0, N) layout
    the scoring path requires, with one collective per-file row-count
    exchange (the same exclusive-prefix construction as
    :func:`global_row_layout`, recovered from the ids themselves).

    Requires the strided invariants host_rows_from_avro guarantees: each
    file decoded wholly by exactly one host, rows within a file numbered
    contiguously from 0. Both are validated and violations raise."""
    ords = rows.row_index // row_stride
    j = rows.row_index % row_stride
    local_max = int(ords.max()) if rows.num_rows else -1
    num_files = (
        int(collective_max(np.asarray([local_max]), ctx, num_processes)[0]) + 1
    )
    counts = np.bincount(ords, minlength=max(num_files, 1)).astype(np.int64)
    g_counts = collective_sum(counts, ctx, num_processes)
    # single-pass validation: sorting by strided id groups rows by
    # (ordinal, row-in-file), so within each file's contiguous segment the
    # j values must be exactly 0..count-1
    order = np.argsort(rows.row_index, kind="stable")
    ords_s, j_s = ords[order], j[order]
    uniq_o, seg_counts = np.unique(ords_s, return_counts=True)
    starts = np.concatenate([[0], np.cumsum(seg_counts)[:-1]])
    expected = np.arange(len(j_s)) - np.repeat(starts, seg_counts)
    bad = j_s != expected
    if bad.any():
        o = int(ords_s[np.argmax(bad)])
        raise ValueError(
            f"file ordinal {o}: row-in-file ids are not contiguous "
            f"[0, {int(counts[o])})"
        )
    split = g_counts[uniq_o] != seg_counts
    if split.any():
        o = int(uniq_o[np.argmax(split)])
        raise ValueError(
            f"file ordinal {o}: decoded on more than one host "
            f"({int(counts[o])} rows here, {int(g_counts[o])} globally)"
        )
    file_base = np.concatenate([[0], np.cumsum(g_counts)[:-1]])
    return dataclasses.replace(rows, row_index=file_base[ords] + j)


# ---------------------------------------------------------------------------
# scoring-time row routing (validation / inference over per-host models)
# ---------------------------------------------------------------------------


def score_routed_rows(
    sd: "ShardedREData | BucketedShardedREData",
    coefficients,
    rows: HostRows,
    num_rows_out: int,
    ctx: MeshContext,
    num_processes: int = 1,
    process_id: int = 0,
) -> np.ndarray:
    """Score rows THIS host ingested against entity models that may live on
    any device: route each row to its entity's owner with the same shuffle
    the training ingest used (``sd.bucket_owners``), have the owner project
    into the entity's local space and dot with its slab row, then merge the
    per-host (num_rows_out,) partials with one collective sum.

    ``coefficients`` is the matching solver state: the (E_tot, D_loc) array
    for a :class:`ShardedREData`, the per-bucket tuple for a
    :class:`BucketedShardedREData` (the buckets are flattened into the same
    per-device concat layout the scoring slots index).

    Cold-start semantics: a row whose entity has no model, or a feature the
    entity never saw in training, contributes 0
    (RandomEffectModel.scala:129-158). Returns the replicated host-side
    (num_rows_out,) score vector (identical on every host).
    """
    if sd.bucket_owners is None:
        raise ValueError("dataset was built without bucket_owners")
    if isinstance(sd, BucketedShardedREData):
        # flatten the size buckets into per-device concatenated views (the
        # same layout the scoring slots index); coefficients arrive as the
        # solver's per-bucket tuple state. Meta/coefficient arrays are tiny
        # next to the data slabs, so the host-side concat keeps the skew
        # memory profile intact.
        if not isinstance(coefficients, (tuple, list)) or len(
            coefficients
        ) != len(sd.buckets):
            raise ValueError(
                "bucketed dataset requires the per-bucket coefficient tuple "
                f"({len(sd.buckets)} buckets)"
            )
        d_max = sd.local_dim
        w_host, k_host, m_host, l_host = [], [], [], []
        n_local = max(ctx.num_devices // num_processes, 1)
        per_bucket = [
            (
                local_shards(w), local_shards(b.entity_keys),
                local_shards(b.entity_mask), local_shards(b.local_to_global),
            )
            for b, w in zip(sd.buckets, coefficients)
        ]
        for ld in range(n_local):
            w_host.append(np.concatenate([
                np.pad(np.asarray(pb[0][ld]),
                       ((0, 0), (0, d_max - pb[0][ld].shape[-1])))
                for pb in per_bucket
            ], axis=0))
            k_host.append(np.concatenate([pb[1][ld] for pb in per_bucket]))
            m_host.append(np.concatenate([pb[2][ld] for pb in per_bucket]))
            l_host.append(np.concatenate([
                np.pad(np.asarray(pb[3][ld]),
                       ((0, 0), (0, d_max - pb[3][ld].shape[-1])),
                       constant_values=-1)
                for pb in per_bucket
            ], axis=0))
        return _score_routed_rows_impl(
            sd, rows, num_rows_out, ctx, num_processes, process_id,
            w_host, k_host, m_host, l_host,
        )
    w_host = local_shards(coefficients)
    k_host = local_shards(sd.entity_keys)
    m_host = local_shards(sd.entity_mask)
    l_host = local_shards(sd.local_to_global)
    return _score_routed_rows_impl(
        sd, rows, num_rows_out, ctx, num_processes, process_id,
        w_host, k_host, m_host, l_host,
    )


def _score_routed_rows_impl(
    sd,
    rows: HostRows,
    num_rows_out: int,
    ctx: MeshContext,
    num_processes: int,
    process_id: int,
    w_host,
    k_host,
    m_host,
    l_host,
) -> np.ndarray:
    keys = stable_entity_keys(rows.entity_raw_ids)
    dest = sd.bucket_owners[bucket_of(keys, sd.num_buckets)]
    # all hosts must pack the SAME record width (the training path's rule)
    k = int(collective_max(
        np.asarray([rows.feat_idx.shape[1]]), ctx, num_processes
    )[0])
    fi_p = (_pad_to(rows.feat_idx.astype(np.int32).T, k, -1).T
            if rows.feat_idx.shape[1] != k else rows.feat_idx.astype(np.int32))
    fv_p = (_pad_to(rows.feat_val.astype(np.float32).T, k, 0.0).T
            if rows.feat_val.shape[1] != k else rows.feat_val.astype(np.float32))
    hi, lo = _pack_u64(keys)
    int_payload = np.concatenate(
        [rows.row_index.astype(np.int32)[:, None], hi[:, None], lo[:, None],
         fi_p], axis=1
    )
    ex = exchange_rows(dest, int_payload, fv_p, ctx, num_processes, process_id)

    local = max(ctx.num_devices // num_processes, 1)
    scores_local = np.zeros(num_rows_out, np.float64)
    # exchange blocks are keyed by explicit local-device index, so the
    # caller's slab shard lists MUST be in that same order (local_shards
    # sorts by axis offset; raw addressable_shards order is unspecified)
    for ld in range(local):
        bi, bf = ex.int_rows[ld], ex.float_rows[ld]
        if not len(bi):
            continue
        w_d, k_d, m_d, l_d = w_host[ld], k_host[ld], m_host[ld], l_host[ld]
        okeys = _unpack_u64(bi[:, 1], bi[:, 2])
        slab_keys = _unpack_u64(k_d[:, 0], k_d[:, 1])
        # key -> slot lookup over THIS device's (masked) lanes
        order = np.argsort(slab_keys, kind="stable")
        sk = slab_keys[order]
        pos = np.searchsorted(sk, okeys)
        pos_c = np.clip(pos, 0, max(len(sk) - 1, 0))
        hit = (sk[pos_c] == okeys) & m_d[order][pos_c]
        slot = np.where(hit, order[pos_c], -1)
        fi = bi[:, 3:]
        fv = bf
        # vectorized per-entity global->local projection: a slab row's
        # valid local_to_global prefix is sorted ascending (built from the
        # sorted (entity, feature) pairs), so local index = searchsorted
        keep = slot >= 0
        if not keep.any():
            continue
        rr = np.nonzero(keep)[0]
        if getattr(sd, "projection_matrix", None) is not None:
            # RANDOM projector: project the routed row through the shared
            # matrix and dot with the slab's k_proj-wide coefficients (the
            # l2g prefix lookup below is INDEX_MAP/IDENTITY machinery)
            pm_t = np.asarray(sd.projection_matrix).T  # (D_global, k_proj)
            fi_r, fv_r = fi[rr], fv[rr]
            cols = pm_t[np.maximum(fi_r, 0)]  # (R, K, k_proj)
            vals = np.where(fi_r >= 0, fv_r, 0.0)
            xp = np.einsum("bk,bkp->bp", vals, cols)
            contrib = np.sum(w_d[slot[rr]] * xp, axis=1)
            np.add.at(scores_local, bi[rr, 0], contrib)
            continue
        l2g_rows = l_d[slot[rr]]  # (R, D_loc), -1 pad AFTER the valid prefix
        big = np.int64(np.iinfo(np.int32).max)
        l2g_sorted = np.where(l2g_rows >= 0, l2g_rows, big).astype(np.int64)
        gidx = fi[rr].astype(np.int64)  # (R, K)
        safe_g = np.where(gidx >= 0, gidx, 0)
        # row-wise searchsorted via the flattened-offset trick (int64 so the
        # per-row stride never overflows)
        d_loc = l2g_sorted.shape[1]
        stride = big + 1
        flat = (l2g_sorted + np.arange(len(rr))[:, None] * stride).reshape(-1)
        targets = safe_g + np.arange(len(rr))[:, None] * stride
        j = np.searchsorted(flat, targets.reshape(-1)).reshape(len(rr), -1)
        j_local = j - np.arange(len(rr))[:, None] * d_loc
        j_c = np.clip(j_local, 0, d_loc - 1)
        found = (
            (gidx >= 0)
            & (j_local < d_loc)
            & (np.take_along_axis(l2g_rows, j_c, axis=1) == gidx)
        )
        wsel = w_d[slot[rr][:, None], j_c]  # (R, K)
        contrib = np.sum(np.where(found, wsel * fv[rr], 0.0), axis=1)
        np.add.at(scores_local, bi[rr, 0], contrib)
    merged = collective_sum(
        scores_local.astype(np.float32), ctx, num_processes
    )
    return np.asarray(merged, np.float32)


# ---------------------------------------------------------------------------
# per-host MODEL ingest (SPMD scoring: no host ever holds the full model)
# ---------------------------------------------------------------------------


def per_host_model_slabs(
    entity_ids: Sequence[str],
    coef_idx: np.ndarray,
    coef_val: np.ndarray,
    global_dim: int,
    ctx: MeshContext,
    num_processes: int = 1,
    process_id: int = 0,
    num_buckets: int = 4096,
) -> Tuple[ShardedREData, Array]:
    """Build entity-sharded MODEL slabs from the per-entity coefficient
    records THIS host loaded (its share of the random-effect model's
    part files, ModelProcessingUtils.scala:205-219 layout): each record is
    routed to its entity's owner device with the same stable-hash shuffle
    as training ingest, the owner builds (E_loc, D_loc) slabs + sparse
    local maps, and scoring routes rows to owners (score_routed_rows) — a
    model larger than any single host's memory scores without ever being
    gathered.

    ``coef_idx``/``coef_val``: (n_models, K) sparse global coefficients,
    -1-masked. Returns (a ShardedREData view carrying the slab/lookup/owner
    state score_routed_rows needs, the sharded (E_tot, D_loc) coefficient
    array)."""
    rows = HostRows(
        entity_raw_ids=list(entity_ids),
        # one "row" per model record; ids only need to be unique per host
        # (slab_build_only below — this dataset locates active slots and
        # routes scoring rows, it is never scored via the jit scatter)
        row_index=np.arange(len(entity_ids), dtype=np.int64),
        labels=np.zeros(len(entity_ids), np.float32),
        weights=np.ones(len(entity_ids), np.float32),
        offsets=np.zeros(len(entity_ids), np.float32),
        feat_idx=coef_idx.astype(np.int32),
        feat_val=coef_val.astype(np.float32),
        global_dim=global_dim,
    )
    # each entity has exactly ONE record-row, so the training-ingest build
    # produces slabs whose single active sample IS the coefficient vector
    # in the entity's local space — read it back out as the model
    sd = per_host_re_dataset(
        rows, ctx, num_processes, process_id, num_buckets=num_buckets,
        slab_build_only=True,
    )
    sharding = NamedSharding(ctx.mesh, P(ctx.axis))
    local_blocks = []
    # pair the two arrays' shards by slab position, not iteration order
    for x_d, r_d in zip(local_shards(sd.x), local_shards(sd.row_index)):
        # the record's coefficient vector sits at its (single) active slot
        has = (r_d >= 0).any(axis=1)
        first = np.argmax(r_d >= 0, axis=1)
        w_d = np.where(
            has[:, None],
            np.take_along_axis(x_d, first[:, None, None], axis=1)[:, 0, :],
            0.0,
        ).astype(np.float32)
        local_blocks.append(w_d)
    w = jax.make_array_from_process_local_data(
        sharding, np.concatenate(local_blocks, axis=0)
    )
    return sd, w


# ---------------------------------------------------------------------------
# per-host file-partition bookkeeping shared by the multihost drivers
# ---------------------------------------------------------------------------


def host_file_share(all_files: Sequence[str], num_processes: int,
                    process_id: int) -> List[Tuple[str, int]]:
    """Deterministic round-robin (file, global ordinal) share for this host."""
    return [(f, i) for i, f in enumerate(all_files)
            if i % num_processes == process_id]


def global_row_layout(num_files: int, decoded, ctx: MeshContext,
                      num_processes: int) -> Tuple[np.ndarray, int]:
    """(file_base, n_global): dense global row ids = exclusive prefix over
    per-file counts, agreed collectively (each host contributes only its
    files' counts). ``decoded`` is [(ordinal, obj-with-num_rows)]."""
    counts = np.zeros(num_files, np.int64)
    for ordinal, gd in decoded:
        counts[ordinal] = gd.num_rows
    g_counts = collective_sum(counts, ctx, num_processes)
    file_base = np.concatenate([[0], np.cumsum(g_counts)[:-1]])
    return file_base, int(g_counts.sum())


def merge_row_vectors(decoded, file_base: np.ndarray, n_global: int,
                      ctx: MeshContext, num_processes: int, vec_per_gd):
    """Replicated (n_global,) vector from per-host row values: each host
    scatters its rows into a zero vector, one collective sum merges (every
    global row is written by exactly one host, so the sum is exact)."""
    local = np.zeros(n_global, np.float32)
    for ordinal, gd in decoded:
        local[file_base[ordinal] + np.arange(gd.num_rows)] = vec_per_gd(gd)
    return collective_sum(local, ctx, num_processes)


def merge_group_ids(gds, file_base, n_rows, id_name, ctx,
                    num_processes: int):
    """Globally consistent dense group ids for grouped evaluators: each
    host hashes ITS rows' raw ids (64-bit stable keys), the (hi, lo) int32
    vectors merge exactly with one collective sum each, and every host
    ranks the identical reconstructed keys into dense int32 groups."""
    hi_l = np.zeros(n_rows, np.int32)
    lo_l = np.zeros(n_rows, np.int32)
    for ordinal, gd in gds:
        vocab = gd.id_vocabs[id_name]
        keys = stable_entity_keys([vocab[i] for i in gd.ids[id_name]])
        hi, lo = _pack_u64(keys)
        ids = file_base[ordinal] + np.arange(gd.num_rows)
        hi_l[ids] = hi
        lo_l[ids] = lo
    hi_g = collective_sum(hi_l, ctx, num_processes).astype(np.int32)
    lo_g = collective_sum(lo_l, ctx, num_processes).astype(np.int32)
    keys_g = _unpack_u64(hi_g, lo_g)
    _, dense = np.unique(keys_g, return_inverse=True)
    return dense.astype(np.int32)
