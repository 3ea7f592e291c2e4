"""Runnable multi-host GAME demo: 2 SPMD processes on this machine train a
GLMix model with TRUE per-host ingest, then score it with the multihost
scoring driver — no process ever holds the full dataset or the full
random-effect model.

    python examples/multihost_game.py

What it shows (all on a 2-process x 4-virtual-CPU-device topology; on real
hardware the same commands span hosts and the mesh spans their chips):
  * FeatureIndexingJob -> shared mmap'd feature index,
  * per-host Avro decode + the collective shuffle (bucket-count psum,
    balanced owner map, one all_to_all) regrouping rows by entity owner,
  * coordinate descent over multihost-sharded coordinates with validation
    metrics (rows routed to their entity's owner for scoring),
  * per-host random-effect model part files,
  * SPMD scoring of that model (model parts loaded per host, records and
    rows routed to owners).
"""

import os
import socket
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))


def free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def launch(module, args):
    # a CPU harness: a chip belongs to one process at a time, so the two
    # worker processes are pinned to 4 virtual CPU devices each (each
    # driver logs its own ``platform: cpu`` line at start)
    port = free_port()
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        env["JAX_PLATFORMS"] = "cpu"
        procs.append(subprocess.Popen(
            [sys.executable, "-m", f"photon_ml_tpu.cli.{module}",
             "--multihost-coordinator", f"127.0.0.1:{port}",
             "--multihost-num-processes", "2",
             "--multihost-process-id", str(pid)] + args,
            cwd=REPO, env=env,
        ))
    for p in procs:
        if p.wait() != 0:
            raise SystemExit(f"{module} process failed")


def main():
    import numpy as np

    from game_test_utils import make_glmix_data
    from photon_ml_tpu.cli import feature_indexing
    from photon_ml_tpu.io import avro as avro_io
    from photon_ml_tpu.io import schemas

    work = tempfile.mkdtemp(prefix="mh-game-demo-")
    print(f"workdir: {work}")
    rng = np.random.default_rng(7)
    data, _ = make_glmix_data(
        rng, num_users=40, rows_per_user_range=(10, 30), d_fixed=6, d_random=4
    )
    schema = {
        "name": "DemoAvro", "type": "record", "namespace": "demo",
        "fields": [
            {"name": "label", "type": "double"},
            {"name": "fixedFeatures",
             "type": {"type": "array", "items": schemas.FEATURE}},
            {"name": "userFeatures",
             "type": {"type": "array",
                      "items": "com.linkedin.photon.avro.generated.FeatureAvro"}},
            {"name": "metadataMap",
             "type": ["null", {"type": "map", "values": "string"}],
             "default": None},
        ],
    }
    ff, uf = data.shards["global"], data.shards["per_user"]
    vocab = data.id_vocabs["userId"]

    def feats(f, r):
        s, e = f.indptr[r], f.indptr[r + 1]
        return [{"name": f"c{j}", "term": "", "value": float(v)}
                for j, v in zip(f.indices[s:e], f.values[s:e])]

    def write(sub, lo, hi, parts):
        d = os.path.join(work, sub)
        os.makedirs(d)
        bounds = np.linspace(lo, hi, parts + 1).astype(int)
        for pi in range(parts):
            avro_io.write_container(
                os.path.join(d, f"part-{pi}.avro"),
                ({"label": float(data.response[r]),
                  "fixedFeatures": feats(ff, r),
                  "userFeatures": feats(uf, r),
                  "metadataMap": {"userId": vocab[data.ids["userId"][r]]}}
                 for r in range(bounds[pi], bounds[pi + 1])),
                schema,
            )
        return d

    n = data.num_rows
    train = write("train", 0, int(n * 0.7), 4)
    val = write("validate", int(n * 0.7), int(n * 0.85), 2)
    score_in = write("score-in", int(n * 0.85), n, 2)

    idx = os.path.join(work, "index")
    feature_indexing.main([
        "--data-input-dirs", train, "--output-dir", idx,
        "--partition-num", "1",
        "--feature-shard-id-to-feature-section-keys-map",
        "global:fixedFeatures|per_user:userFeatures",
    ])

    print("== multihost training (2 SPMD processes, platform: cpu) ==")
    launch("game_multihost_driver", [
        "--output-dir", os.path.join(work, "model"),
        "--train-input-dirs", train,
        "--validate-input-dirs", val,
        "--evaluator-type", "AUC",
        "--task-type", "LOGISTIC_REGRESSION",
        "--updating-sequence", "fixed,per-user",
        "--feature-shard-id-to-feature-section-keys-map",
        "global:fixedFeatures|per_user:userFeatures",
        "--fixed-effect-optimization-configurations",
        "fixed:40,1e-9,0.1,1,LBFGS,L2",
        "--fixed-effect-data-configurations", "fixed:global,2",
        "--random-effect-optimization-configurations",
        "per-user:30,1e-9,0.5,1,LBFGS,L2",
        "--random-effect-data-configurations",
        "per-user:userId,per_user,2,-1,0,-1,index_map",
        "--num-iterations", "2",
        "--offheap-indexmap-dir", idx,
        "--delete-output-dir-if-exists", "true",
    ])
    re_parts = os.listdir(os.path.join(
        work, "model", "best", "random-effect", "per-user", "coefficients"
    ))
    print(f"model saved; random-effect parts (one per host): {sorted(re_parts)}")

    print("== multihost scoring (model stays sharded, platform: cpu) ==")
    launch("game_multihost_scoring_driver", [
        "--input-dirs", score_in,
        "--game-model-input-dir", os.path.join(work, "model", "best"),
        "--output-dir", os.path.join(work, "scores"),
        "--feature-shard-id-to-feature-section-keys-map",
        "global:fixedFeatures|per_user:userFeatures",
        "--offheap-indexmap-dir", idx,
        "--evaluator-type", "AUC",
        "--delete-output-dir-if-exists", "true",
    ])
    out = os.path.join(work, "scores", "scores")
    print(f"scores written: {sorted(os.listdir(out))}")
    print("demo OK")


if __name__ == "__main__":
    main()
