"""Worker for the 2-process multi-host harness (launched by
test_multihost.py; also runnable by hand:

    python tests/multihost_worker.py <proc_id> <nprocs> <port>

Each process gets 4 virtual CPU devices, ingests ONLY its row block of a
synthetic GLM dataset (per-host ingest), assembles the globally row-sharded
batch, runs the SAME DistributedFixedEffectSolver SPMD program, and prints
the trained coefficients. The test asserts both processes print coefficients
identical to a single-process fit — proving the psum-in-kernel solver is
host-count-invariant (SURVEY.md §3.5 driver/executor split, re-expressed)."""

import os
import sys

proc_id, nprocs, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from photon_ml_tpu.parallel import multihost

mh = multihost.initialize(
    coordinator_address=f"127.0.0.1:{port}", num_processes=nprocs, process_id=proc_id
)
assert mh.num_processes == nprocs and mh.process_id == proc_id
assert len(jax.devices()) == 4 * nprocs, jax.devices()

import jax.numpy as jnp

from photon_ml_tpu.ops.features import DenseFeatures
from photon_ml_tpu.ops.normalization import NormalizationContext
from photon_ml_tpu.ops.objective import GLMBatch
from photon_ml_tpu.ops.regularization import RegularizationContext
from photon_ml_tpu.optim.common import OptimizerConfig
from photon_ml_tpu.optim.problem import GLMOptimizationProblem
from photon_ml_tpu.parallel.distributed import DistributedFixedEffectSolver
from photon_ml_tpu.types import OptimizerType, TaskType

# -- the full dataset is DEFINED globally (seeded), INGESTED per host -------
# N deliberately NOT divisible by hosts*devices: the tail host's short block
# is zero-padded back to the uniform rows_per_host size (weight 0)
N, D = 500, 6
rng = np.random.default_rng(42)
x_all = rng.normal(size=(N, D)).astype(np.float32)
w_true = rng.normal(size=D).astype(np.float32)
y_all = (1.0 / (1.0 + np.exp(-x_all @ w_true)) > rng.random(N)).astype(np.float32)

ctx = mh.mesh_context()
sl = mh.host_row_slice(N, ctx)  # this host reads ONLY its block
x_loc, y_loc = x_all[sl], y_all[sl]

x_g = mh.global_row_sharded(x_loc, ctx, n_global=N)
y_g = mh.global_row_sharded(y_loc, ctx, n_global=N)
w_g = mh.global_row_sharded(np.ones(len(y_loc), np.float32), ctx, n_global=N)
batch = GLMBatch.create(DenseFeatures(x_g), y_g, weights=w_g)

problem = GLMOptimizationProblem(
    TaskType.LOGISTIC_REGRESSION,
    OptimizerType.LBFGS,
    OptimizerConfig(max_iterations=40, tolerance=1e-9),
    RegularizationContext.l2(0.5),
)
solver = DistributedFixedEffectSolver(problem, ctx)
model, result = solver.run(batch, NormalizationContext.identity())
coefs = np.asarray(jax.device_get(model.coefficients.means))

mh.barrier("after-solve")
# coordinator-gated side effect: only process 0 writes the model file
outdir = sys.argv[4] if len(sys.argv) > 4 else None
if outdir and mh.coordinator_only_io():
    np.save(os.path.join(outdir, "coefs.npy"), coefs)
mh.barrier("after-save")

# -- multihost-safe checkpoint: sharded leaves allgathered, coordinator
# writes, barriers fence (checkpoint.py multihost mode) ---------------------
if outdir:
    from photon_ml_tpu.checkpoint import CheckpointState, CoordinateDescentCheckpointer

    scores = jax.jit(lambda b, w: b.features.matvec(w))(
        batch, model.coefficients.means
    )  # (N,) row-sharded ACROSS HOSTS -> not fully addressable
    assert not scores.is_fully_addressable
    ck = CoordinateDescentCheckpointer(
        os.path.join(outdir, "ckpt"), run_fingerprint="mh-test", multihost=mh
    )
    ck.save(
        CheckpointState(
            step=1,
            params={"fe": model.coefficients.means},
            scores={"fe": scores},
            total_scores=scores,
            objective_history=[float(result.value)],
            validation_history=[],
        )
    )
    if mh.coordinator_only_io():
        n_pad = x_g.shape[0]  # global rows incl. the tail host's zero padding
        restored = ck.restore(
            {"fe": np.zeros(D, np.float32)},
            {"fe": np.zeros(n_pad, np.float32)},
            np.zeros(n_pad, np.float32),
            # coordinator-only read-back: the collective-min agreement
            # would deadlock (process 1 is not in this branch)
            agree=False,
        )
        full_scores = x_all @ coefs
        got = np.asarray(restored.total_scores)
        np.testing.assert_allclose(got[:N], full_scores, rtol=1e-4, atol=1e-4)
        np.testing.assert_array_equal(got[N:], 0.0)  # padding rows score 0
        print("MHCKPT-OK", flush=True)
    mh.barrier("after-ckpt-check")

# -- multihost health fencing: per-host heartbeats, barrier deadline (the
# completing path), and the collective-min restore-step agreement — host 1
# deliberately MISSES the latest checkpoint step, and both hosts must agree
# to restore the newest step EVERY host can serve ---------------------------
if outdir:
    hb_dir = os.path.join(outdir, "heartbeats")
    mh.write_heartbeat(hb_dir, step=1)
    mh.barrier("heartbeats-written", timeout=60)  # deadline path, completing
    ages = mh.heartbeat_ages(hb_dir)
    assert sorted(ages) == list(range(nprocs)), ages
    assert all(age < 60 for age in ages.values()), ages
    if mh.coordinator_only_io():
        desc = mh.describe_heartbeats(hb_dir)
        assert "NO HEARTBEAT" not in desc, desc
        print("MHHB-OK", flush=True)

    # per-host (NON-shared) checkpoint dirs: host 0 commits steps 1 and 2,
    # host 1 only step 1 (its "crash" lost the latest commit)
    per_host_dir = os.path.join(outdir, f"ckpt-host-{proc_id}")
    local_ck = CoordinateDescentCheckpointer(per_host_dir, run_fingerprint="agree")
    tiny = np.arange(4, dtype=np.float32)

    def tiny_state(step):
        return CheckpointState(
            step=step, params={"w": tiny + step}, scores={"w": tiny},
            total_scores=tiny, objective_history=[float(step)],
            validation_history=[],
        )

    local_ck.save(tiny_state(1))
    if proc_id == 0:
        local_ck.save(tiny_state(2))
    agreed = mh.agree_restore_step(local_ck.latest_step())
    assert agreed == 1, (proc_id, agreed)
    restored = local_ck.restore(
        {"w": tiny}, {"w": tiny}, tiny, max_step=agreed
    )
    assert restored is not None and restored.step == 1, proc_id
    np.testing.assert_array_equal(np.asarray(restored.params["w"]), tiny + 1)
    mh.barrier("agree-check")
    if mh.coordinator_only_io():
        print("MHAGREE-OK", flush=True)

print(f"MHOK proc={proc_id} coefs={','.join(f'{c:.6f}' for c in coefs)}", flush=True)

# -- entity parallelism ACROSS HOSTS: each host ingests only ITS entity
# block (per-host entity ingest, the RandomEffectIdPartitioner analogue at
# host granularity), solves its entities' local GLMs with the vmapped
# kernel under shard_map, and scores its own rows locally ---------------------
import jax.numpy as jnp2  # noqa: E402 (alias to keep the FE section intact)
from jax import shard_map  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from photon_ml_tpu.optim.lbfgs import lbfgs_minimize_  # noqa: E402
from photon_ml_tpu.ops.features import DenseFeatures as DF  # noqa: E402
from photon_ml_tpu.ops.normalization import NormalizationContext as NC  # noqa: E402
from photon_ml_tpu.ops.objective import GLMBatch as GB, GLMObjective  # noqa: E402
from photon_ml_tpu.ops import losses as losses_mod  # noqa: E402
from photon_ml_tpu.optim.common import OptimizerConfig as OC  # noqa: E402

E_GLOBAL, M, DR = 16, 6, 3  # entities x samples-per-entity x local dim
rng_re = np.random.default_rng(7)
x_re_all = rng_re.normal(size=(E_GLOBAL, M, DR)).astype(np.float32)
w_true_re = rng_re.normal(size=(E_GLOBAL, DR)).astype(np.float32)
z_all = np.einsum("emd,ed->em", x_re_all, w_true_re)
y_re_all = (1.0 / (1.0 + np.exp(-z_all)) > rng_re.random((E_GLOBAL, M))).astype(np.float32)

e_per = E_GLOBAL // nprocs
esl = slice(proc_id * e_per, (proc_id + 1) * e_per)  # this host's entity block
mesh = ctx.mesh
esh = NamedSharding(mesh, P(ctx.axis))
x_re = jax.make_array_from_process_local_data(esh, x_re_all[esl])
y_re = jax.make_array_from_process_local_data(esh, y_re_all[esl])

obj = GLMObjective(losses_mod.logistic)
cfg = OC(max_iterations=25, tolerance=1e-9)


def solve_shard(x_s, y_s):
    def solve_one(x_e, y_e):
        batch = GB.create(DF(x_e), y_e)
        vg = lambda wt: obj.value_and_grad(wt, batch, NC.identity(), 1.0)
        return lbfgs_minimize_(vg, jnp.zeros((DR,), jnp.float32), cfg).coefficients

    return jax.vmap(solve_one)(x_s, y_s)


re_solve = jax.jit(
    shard_map(
        solve_shard, mesh=mesh, in_specs=(P(ctx.axis), P(ctx.axis)),
        out_specs=P(ctx.axis), check_vma=False,
    )
)
w_re = re_solve(x_re, y_re)  # (E_GLOBAL, DR) entity-sharded across hosts
# owner-computes scoring of THIS HOST's rows (it ingested its entities' rows)
w_re_local = np.asarray(
    jax.device_get([s.data for s in w_re.addressable_shards])
).reshape(-1, DR)
scores_local = np.einsum("emd,ed->em", x_re_all[esl], w_re_local)
mh.barrier("re-done")
print(
    f"MHRE proc={proc_id} wsum={float(np.sum(w_re_local)):.6f} "
    f"ssum={float(np.sum(scores_local)):.6f}",
    flush=True,
)

# -- the PRODUCTION random-effect stack across hosts, with TRUE per-host
# ingest: each host converts only ITS row block to HostRows, the collective
# shuffle routes rows to entity owners, and each host builds only its slab
# (parallel.perhost_ingest — no replicated host-side build anywhere) --------
import tracemalloc  # noqa: E402

from game_test_utils import make_glmix_data  # noqa: E402
from photon_ml_tpu.parallel.perhost_ingest import (  # noqa: E402
    HostRows,
    PerHostRandomEffectSolver,
    per_host_re_dataset,
)

rng_g = np.random.default_rng(31)  # the DATASET is seeded; the DECODE is per host
gdata, _ = make_glmix_data(
    rng_g, num_users=1500, rows_per_user_range=(8, 20), d_fixed=4, d_random=6
)
n_rows_g = gdata.num_rows
# simulate this host's Avro partition decode: keep ONLY the host's row block
lo = proc_id * (n_rows_g // nprocs)
hi = n_rows_g if proc_id == nprocs - 1 else (proc_id + 1) * (n_rows_g // nprocs)
feats_g = gdata.shards["per_user"]
nnz = np.diff(feats_g.indptr)[lo:hi]
k_loc = max(int(nnz.max()) if len(nnz) else 1, 1)
fi_h = np.full((hi - lo, k_loc), -1, np.int32)
fv_h = np.zeros((hi - lo, k_loc), np.float32)
for r in range(hi - lo):
    s, e = feats_g.indptr[lo + r], feats_g.indptr[lo + r + 1]
    fi_h[r, : e - s] = feats_g.indices[s:e]
    fv_h[r, : e - s] = feats_g.values[s:e]
vocab_g = gdata.id_vocabs["userId"]
host_rows = HostRows(
    entity_raw_ids=[vocab_g[i] for i in gdata.ids["userId"][lo:hi]],
    row_index=np.arange(lo, hi, dtype=np.int64),
    labels=gdata.response[lo:hi].astype(np.float32),
    weights=gdata.weight[lo:hi].astype(np.float32),
    offsets=gdata.offset[lo:hi].astype(np.float32),
    feat_idx=fi_h,
    feat_val=fv_h,
    global_dim=feats_g.dim,
)
global_dim_g = feats_g.dim
del gdata, feats_g, fi_h, fv_h  # the full build must never exist on a host

tracemalloc.start()
sharded_ds = per_host_re_dataset(host_rows, ctx, nprocs, proc_id)
_, ingest_peak = tracemalloc.get_traced_memory()
tracemalloc.stop()

solver = PerHostRandomEffectSolver(
    sharded_ds,
    TaskType.LOGISTIC_REGRESSION,
    OptimizerType.LBFGS,
    OptimizerConfig(max_iterations=30, tolerance=1e-9),
    RegularizationContext.l2(0.3),
    ctx,
)
resid0 = mh.global_replicated(np.zeros(n_rows_g, np.float32), ctx)
coefs_re, tracker = solver.update(resid0, solver.initial_coefficients())
scores_dev = solver.score(coefs_re)  # psum-merged -> replicated, addressable
scores_re = np.asarray(jax.device_get(scores_dev))
from jax.experimental import multihost_utils  # noqa: E402

coefs_full = np.asarray(multihost_utils.process_allgather(coefs_re, tiled=True))
keys_full = np.asarray(
    multihost_utils.process_allgather(sharded_ds.entity_keys, tiled=True)
)
mask_full = np.asarray(
    multihost_utils.process_allgather(sharded_ds.entity_mask, tiled=True)
)
l2g_full = np.asarray(
    multihost_utils.process_allgather(sharded_ds.local_to_global, tiled=True)
)
mh.barrier("solver-re-done")
if outdir and mh.coordinator_only_io():
    np.savez(
        os.path.join(outdir, "re_perhost.npz"),
        coefs=coefs_full, keys=keys_full, mask=mask_full, l2g=l2g_full,
        global_dim=global_dim_g,
    )
    np.save(os.path.join(outdir, "re_scores.npy"), scores_re)
mh.barrier("solver-re-saved")
csum = float(np.sum(coefs_full[mask_full]))
# ingest_peak BEFORE csum: __graft_entry__ parses csum as the LAST token to
# assert cross-host agreement, and the peaks legitimately differ per host
print(
    f"MHRESOLVER proc={proc_id} ingest_peak={ingest_peak} csum={csum:.6f}",
    flush=True,
)

# -- UNCAPPED skewed distribution through SIZE-BUCKETED per-host slabs ------
# (VERDICT r4 next-round #2): one giant entity among thousands of
# singletons, rows interleaved across hosts. The global-max-padded slab for
# this shape would be ~singletons/devices x giant-width — never built here;
# the bucketed build pads each entity only to its bucket's width, so the
# per-host ingest peak must stay ~1/n_hosts of a single host's.
from photon_ml_tpu.parallel.perhost_ingest import (  # noqa: E402
    PerHostBucketedRandomEffectSolver,
)

rng_s = np.random.default_rng(53)
GIANT, SING, DS = 2048, 3000, 6
n_skew = GIANT + SING
ids_sk = np.array(["giant"] * GIANT + [f"s{i}" for i in range(SING)])
fi_sk = rng_s.integers(0, DS, size=(n_skew, 3)).astype(np.int32)
fv_sk = rng_s.normal(size=(n_skew, 3)).astype(np.float32)
y_sk = (rng_s.random(n_skew) < 0.5).astype(np.float32)
perm_sk = rng_s.permutation(n_skew)  # giant's rows land on BOTH hosts
ids_sk, fi_sk, fv_sk, y_sk = (
    ids_sk[perm_sk], fi_sk[perm_sk], fv_sk[perm_sk], y_sk[perm_sk]
)
lo_s = proc_id * (n_skew // nprocs)
hi_s = n_skew if proc_id == nprocs - 1 else (proc_id + 1) * (n_skew // nprocs)
skew_rows = HostRows(
    entity_raw_ids=list(ids_sk[lo_s:hi_s]),
    row_index=np.arange(lo_s, hi_s, dtype=np.int64),
    labels=y_sk[lo_s:hi_s],
    weights=np.ones(hi_s - lo_s, np.float32),
    offsets=np.zeros(hi_s - lo_s, np.float32),
    feat_idx=fi_sk[lo_s:hi_s],
    feat_val=fv_sk[lo_s:hi_s],
    global_dim=DS,
)
tracemalloc.start()
skew_ds = per_host_re_dataset(
    skew_rows, ctx, nprocs, proc_id, size_buckets=8
)
_, skew_peak = tracemalloc.get_traced_memory()
tracemalloc.stop()
bsolver = PerHostBucketedRandomEffectSolver(
    skew_ds,
    TaskType.LOGISTIC_REGRESSION,
    OptimizerType.LBFGS,
    OptimizerConfig(max_iterations=20, tolerance=1e-8),
    RegularizationContext.l2(0.3),
    ctx,
)
resid_sk = mh.global_replicated(np.zeros(n_skew, np.float32), ctx)
w_sk, _ = bsolver.update(resid_sk, bsolver.initial_coefficients())
ssum_sk = float(np.sum(np.asarray(jax.device_get(bsolver.score(w_sk)))))
print(
    f"MHSKEW proc={proc_id} ingest_peak={skew_peak} "
    f"padded={skew_ds.padded_elements} ssum={ssum_sk:.6f}",
    flush=True,
)
