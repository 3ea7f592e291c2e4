"""BASELINE config 4 at MovieLens-1M scale, end-to-end through the GAME
driver (VERDICT r3 #7).

The environment has zero egress and no local MovieLens copy, so the run
uses a SYNTHETIC dataset with MovieLens-1M's exact shape and skew:
1,000,209 ratings, 6,040 users, 3,706 movies, power-law user activity and
movie popularity, 18 genre indicators + movie numerics as the fixed shard,
the same movie features as the per-user random-effect shard (the GLMix
tutorial configuration: fixed effect + per-user RE logistic regression on
rating >= 4). Labels come from a planted fixed+per-user model so AUC has
a real signal to recover.

Writes Avro (the real wire format), trains through
cli/game_training_driver (which scans the feature index itself) with AUC +
sec/iter recorded, and updates BASELINE.json.published with the platform
it ran on.

Run:  python tools/movielens_baseline.py [--rows N] [--out DIR]

Runs on jax's default device, like the drivers (set JAX_PLATFORMS=cpu for
a CPU record). ``chip_smoke.py`` at the repo root drives the same
generator and the same driver flags (:func:`synthesize`,
:func:`write_dataset`, :func:`game_args`) as the on-chip smoke.
"""

import argparse
import json
import os
import shutil
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np

SEED = 20260730
N_GENRES = 18
D_MOVIE = N_GENRES + 3  # genres + year + popularity + intercept-less numerics

# dataset shapes (rows, users, movies) — ml20m is the MovieLens-20M shape
# (VERDICT r4 #7: the size where bucketing/sharding actually gets exercised)
SCALES = {
    "ml1m": (1_000_209, 6_040, 3_706),
    "ml20m": (20_000_263, 138_493, 26_744),
}
N_RATINGS, N_USERS, N_MOVIES = SCALES["ml1m"]  # defaults: the ML-1M shape


def log(msg):
    print(f"[{time.strftime('%H:%M:%S')}] {msg}", flush=True)


def synthesize(rows, rng, n_users=N_USERS, n_movies=N_MOVIES):
    """(user, movie, features, label) with ML-1M-like skew."""
    # power-law activity/popularity (ML-1M: top user ~2300 ratings, median ~96)
    user_w = rng.pareto(1.3, n_users) + 1.0
    movie_w = rng.pareto(1.1, n_movies) + 1.0
    users = rng.choice(n_users, size=rows, p=user_w / user_w.sum())
    movies = rng.choice(n_movies, size=rows, p=movie_w / movie_w.sum())

    # movie features: 1-3 genres, year, log-popularity
    genres = np.zeros((n_movies, N_GENRES), np.float32)
    for m in range(n_movies):
        for g in rng.choice(N_GENRES, size=rng.integers(1, 4), replace=False):
            genres[m, g] = 1.0
    year = rng.uniform(-1, 1, n_movies).astype(np.float32)
    pop = np.log1p(movie_w / movie_w.mean()).astype(np.float32)
    movie_feats = np.concatenate(
        [genres, year[:, None], pop[:, None],
         rng.normal(size=(n_movies, 1)).astype(np.float32)], axis=1,
    )  # (M, D_MOVIE)

    # planted model: global weights + per-user weights (GLMix structure)
    w_fixed = rng.normal(size=D_MOVIE).astype(np.float32) * 0.8
    w_user = rng.normal(size=(n_users, D_MOVIE)).astype(np.float32) * 0.6
    x = movie_feats[movies]  # (rows, D_MOVIE)
    z = x @ w_fixed + np.einsum("rd,rd->r", x, w_user[users]) + rng.normal(
        scale=0.5, size=rows
    ).astype(np.float32)
    label = (1.0 / (1.0 + np.exp(-z)) > rng.random(rows)).astype(np.float32)
    return users, movies, x, label


def write_avro(dirpath, users, movies, x, label, rows_slice, parts=4):
    from photon_ml_tpu.io import avro as avro_io
    from photon_ml_tpu.io import schemas

    schema = {
        "name": "MovieLensExampleAvro",
        "namespace": "bench",
        "type": "record",
        "fields": [
            {"name": "label", "type": "double"},
            {"name": "movieFeatures", "type": {"type": "array", "items": schemas.FEATURE}},
            {"name": "userMovieFeatures",
             "type": {"type": "array",
                      "items": "com.linkedin.photon.avro.generated.FeatureAvro"}},
            {"name": "metadataMap",
             "type": ["null", {"type": "map", "values": "string"}], "default": None},
        ],
    }
    os.makedirs(dirpath, exist_ok=True)
    idx = np.arange(rows_slice.start, rows_slice.stop)
    per = -(-len(idx) // parts)
    for p in range(parts):
        sel = idx[p * per:(p + 1) * per]

        def records():
            for r in sel:
                feats = [
                    {"name": f"f{j}", "term": "", "value": float(v)}
                    for j, v in enumerate(x[r])
                    if v != 0.0
                ]
                yield {
                    "label": float(label[r]),
                    "movieFeatures": feats,
                    "userMovieFeatures": feats,
                    "metadataMap": {
                        "userId": f"u{users[r]}",
                        "movieId": f"m{movies[r]}",
                    },
                }

        avro_io.write_container(
            os.path.join(dirpath, f"part-{p:05d}.avro"), records(), schema
        )


def write_dataset(out, rows, n_users=N_USERS, n_movies=N_MOVIES):
    """Synthesize ``rows`` ratings from :data:`SEED` and write them under
    ``out``: the first 90 % as ``train/`` (4 parts), the held-out 10 % as
    ``validate/`` (1 part). Returns ``(train_rows, validate_rows)``."""
    users, movies, x, label = synthesize(
        rows, np.random.default_rng(SEED), n_users, n_movies
    )
    n_train = int(rows * 0.9)
    write_avro(os.path.join(out, "train"), users, movies, x, label,
               slice(0, n_train))
    write_avro(os.path.join(out, "validate"), users, movies, x, label,
               slice(n_train, rows), parts=1)
    return n_train, rows - n_train


def game_args(out, iterations=2, active_cap=512, full_game=False,
              bucketed=False, distributed=False):
    """The ``cli.game_training_driver`` flags of BASELINE config 4 (GLMix:
    fixed effect + per-user random effect, logistic) — or config 5 with
    ``full_game`` — over the dataset :func:`write_dataset` left in ``out``."""
    args = [
        "--train-input-dirs", os.path.join(out, "train"),
        "--validate-input-dirs", os.path.join(out, "validate"),
        "--task-type", "LOGISTIC_REGRESSION",
        "--output-dir", os.path.join(out, "model"),
        "--feature-shard-id-to-feature-section-keys-map",
        "global:movieFeatures|per_user:userMovieFeatures",
        "--fixed-effect-optimization-configurations",
        "global:60,1e-9,1.0,1,LBFGS,l2",
        "--fixed-effect-data-configurations", "global:global,4",
        "--num-iterations", str(iterations),
        "--evaluator-type", "AUC",
        "--delete-output-dir-if-exists", "true",
    ]
    if full_game:
        # config-5 shape: fixed + per-user RE + per-movie RE + factored MF
        # (per-movie latent over the shared feature space, latent dim 4)
        args += [
            "--updating-sequence", "global,per-user,per-movie,mf",
            "--random-effect-optimization-configurations",
            "per-user:40,1e-8,1.0,1,LBFGS,l2|"
            "per-movie:40,1e-8,1.0,1,LBFGS,l2",
            "--random-effect-data-configurations",
            f"per-user:userId,per_user,4,{active_cap},0,-1,index_map|"
            f"per-movie:movieId,per_user,4,{active_cap},0,-1,index_map|"
            f"mf:movieId,per_user,4,{active_cap},0,-1,IDENTITY",
            "--factored-random-effect-optimization-configurations",
            "mf:30,1e-8,1.0,1,LBFGS,l2:30,1e-8,1.0,1,LBFGS,l2:2,4",
        ]
    else:
        args += [
            "--updating-sequence", "global,per-user",
            "--random-effect-optimization-configurations",
            "per-user:40,1e-8,1.0,1,LBFGS,l2",
            "--random-effect-data-configurations",
            f"per-user:userId,per_user,4,{active_cap},0,-1,index_map",
        ]
    if bucketed:
        args += ["--bucketed-random-effects", "true"]
    if distributed:
        args += ["--distributed", "true"]
    return args


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", choices=sorted(SCALES), default="ml1m",
                    help="dataset shape: ml1m (default) or ml20m "
                         "(20,000,263 ratings / 138,493 users / 26,744 movies)")
    ap.add_argument("--rows", type=int, default=None,
                    help="override row count (default: the scale's)")
    ap.add_argument("--out", default="/tmp/ml1m_baseline")
    ap.add_argument("--iterations", type=int, default=2)
    ap.add_argument("--active-cap", type=int, default=512)
    ap.add_argument("--full-game", action="store_true",
                    help="BASELINE config-5 shape: + per-movie RE + factored "
                         "MF coordinate (latent 4)")
    ap.add_argument("--bucketed", action="store_true",
                    help="size-bucketed random-effect slabs (+ --distributed "
                         "entity sharding when devices > 1) — the skew-proof "
                         "path the 20M scale exercises")
    ap.add_argument("--distributed", action="store_true",
                    help="entity/row sharding over the visible device mesh")
    ap.add_argument("--reuse-data", action="store_true",
                    help="skip synthesis/writing when --out already holds "
                         "train/ and validate/ (the 20M write takes ~45 min; "
                         "a crashed training run should not pay it twice)")
    ns = ap.parse_args()
    n_ratings, n_users, n_movies = SCALES[ns.scale]
    if ns.rows is None:
        ns.rows = n_ratings

    import jax

    if os.environ.get("PHOTON_ML_TPU_SYNC_DISPATCH"):
        # single-physical-core boxes: async dispatch lets a second program's
        # device threads occupy the thread pool while an earlier program's
        # collective rendezvous starves -> livelock -> XLA's termination
        # timeout kills the run (observed 3x on the 20M run). Synchronous
        # dispatch serializes programs and removes the hazard.
        jax.config.update("jax_cpu_enable_async_dispatch", False)

    t0 = time.time()
    # a manifest written AFTER the last avro byte is the only acceptable
    # reuse evidence: train/ and validate/ existing proves nothing (the dirs
    # are created before the parts are written, so a crashed write leaves
    # both present but truncated), and the manifest must also match the
    # requested scale/rows or a stale dir would silently publish a baseline
    # entry describing data that was never used
    manifest_path = os.path.join(ns.out, "data-manifest.json")
    manifest = {"scale": ns.scale, "rows": ns.rows, "complete": True}
    reusable = False
    if ns.reuse_data and os.path.exists(manifest_path):
        with open(manifest_path) as f:
            on_disk = json.load(f)
        if on_disk == manifest:
            reusable = True
        else:
            log(f"--reuse-data refused: manifest {on_disk} != requested "
                f"{manifest}; regenerating")
    elif ns.reuse_data:
        log("--reuse-data refused: no data-manifest.json (a complete write "
            "stamps one); regenerating")
    if reusable:
        log(f"reusing data in {ns.out} (--reuse-data, manifest verified)")
    else:
        log(f"synthesizing + writing {ns.rows:,} ratings "
            f"({n_users:,} users x {n_movies:,} movies) as avro")
        if os.path.exists(ns.out):
            shutil.rmtree(ns.out)
        n_train, n_val = write_dataset(ns.out, ns.rows, n_users, n_movies)
        log(f"wrote {n_train:,} train / {n_val:,} validation rows")
        with open(manifest_path + ".tmp", "w") as f:
            json.dump(manifest, f)
        os.replace(manifest_path + ".tmp", manifest_path)
    t_data = time.time() - t0
    log(f"data ready in {t_data:.0f}s")

    from photon_ml_tpu.cli.game_training_driver import main as game_main

    t0 = time.time()
    driver = game_main(game_args(
        ns.out, ns.iterations, ns.active_cap, full_game=ns.full_game,
        bucketed=ns.bucketed, distributed=ns.distributed,
    ))
    wall = time.time() - t0
    _, result, metrics = driver.results[driver.best_index]
    auc = float(metrics["AUC"])
    # per-iteration cost: total train phase over coordinate-descent iterations
    sec_per_iter = driver.timer.totals.get("train", wall) / ns.iterations
    platform = jax.devices()[0].platform
    import resource

    peak_rss_gb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6
    log(f"done: AUC={auc:.4f}, {sec_per_iter:.1f}s/iter "
        f"(wall {wall:.0f}s, platform={platform}, peak RSS {peak_rss_gb:.1f} GB)")

    baseline_path = os.path.join(REPO, "BASELINE.json")
    with open(baseline_path) as f:
        baseline = json.load(f)
    scale_tag = "movielens1m" if ns.scale == "ml1m" else "movielens20m"
    entry_key = (
        f"config5_full_game_{scale_tag}_scale" if ns.full_game
        else f"config4_{scale_tag}_scale"
    )
    baseline.setdefault("published", {})[entry_key] = {
        "dataset": (
            f"synthetic MovieLens-{ns.scale[2:].upper()}-scale GLMix "
            f"(zero-egress environment: real data unavailable; same "
            f"shape/skew: {ns.rows:,} ratings, {n_users:,} users, "
            f"{n_movies:,} movies, planted fixed+per-user logistic model)"
        ),
        "model": (
            "fixed + per-user RE + per-movie RE + factored MF (latent 4)"
            if ns.full_game
            else "fixed effect (movie features) + per-user random effect"
        ),
        "auc": round(auc, 4),
        "sec_per_cd_iteration": round(sec_per_iter, 2),
        "cd_iterations": ns.iterations,
        "active_upper_bound": ns.active_cap,
        "bucketed": bool(ns.bucketed),
        "distributed": bool(ns.distributed),
        "peak_rss_gb": round(peak_rss_gb, 2),
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
        "captured": time.strftime("%Y-%m-%d"),
    }
    with open(baseline_path, "w") as f:
        json.dump(baseline, f, indent=2)
    log(f"BASELINE.json.published updated ({baseline_path})")


if __name__ == "__main__":
    main()
