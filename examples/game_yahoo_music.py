"""End-to-end GAME (GLMix) example on the reference's yahoo-music dataset:
fixed effect + per-user + per-song random effects trained by coordinate
descent, model saved in the reference's directory layout, then re-loaded
and scored by the scoring driver with evaluators.

Run:  python examples/game_yahoo_music.py  [--output-dir OUT] [--distributed]

Runs on jax's default device (the drivers log which). With JAX_PLATFORMS=cpu
it gets an 8-virtual-device CPU mesh (the XLA_FLAGS default below); pass
--distributed to entity-shard the random effects over the visible devices.
"""
import argparse
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

# import-clean shared helper (NOT the parity harness itself, which forces
# CPU + float64 at import time and would defeat this f32 example)
from yahoo_data import split_yahoo as _split_yahoo  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--output-dir", default="/tmp/photon-ml-tpu-example-game")
    ap.add_argument("--distributed", action="store_true")
    ns = ap.parse_args()

    data_dir = os.path.join(ns.output_dir, "data")
    os.makedirs(os.path.join(data_dir, "train"), exist_ok=True)
    os.makedirs(os.path.join(data_dir, "validation"), exist_ok=True)
    _split_yahoo(data_dir)

    from photon_ml_tpu.cli import game_scoring_driver, game_training_driver

    model_dir = os.path.join(ns.output_dir, "model")
    trainer = game_training_driver.main([
        "--train-input-dirs", os.path.join(data_dir, "train"),
        "--validate-input-dirs", os.path.join(data_dir, "validation"),
        "--output-dir", model_dir,
        "--task-type", "LINEAR_REGRESSION",
        "--feature-shard-id-to-feature-section-keys-map",
        "global:features|per_user:userFeatures|per_song:songFeatures",
        "--updating-sequence", "fixed,per-user,per-song",
        "--fixed-effect-data-configurations", "fixed:global,1",
        "--random-effect-data-configurations",
        "per-user:userId,per_user,1,-1,-1,-1,INDEX_MAP|"
        "per-song:songId,per_song,1,-1,-1,-1,INDEX_MAP",
        "--fixed-effect-optimization-configurations",
        "fixed:40,1e-7,1.0,1,LBFGS,L2",
        "--random-effect-optimization-configurations",
        "per-user:30,1e-6,5.0,1,LBFGS,L2|per-song:30,1e-6,5.0,1,LBFGS,L2",
        "--num-iterations", "2",
        "--evaluator-type", "RMSE",
        "--delete-output-dir-if-exists", "true",
        "--distributed", str(ns.distributed).lower(),
    ])
    _, _, metrics = trainer.results[trainer.best_index]
    print("\nvalidation metrics:", {k: round(v, 4) for k, v in metrics.items()})

    scores_dir = os.path.join(ns.output_dir, "scores")
    scorer = game_scoring_driver.main([
        "--input-dirs", os.path.join(data_dir, "validation"),
        "--game-model-input-dir", os.path.join(model_dir, "best"),
        "--output-dir", scores_dir,
        "--feature-shard-id-to-feature-section-keys-map",
        "global:features|per_user:userFeatures|per_song:songFeatures",
        "--random-effect-id-set", "userId,songId",
        "--evaluator-type", "RMSE",
        "--delete-output-dir-if-exists", "true",
    ])
    print("scoring-driver metrics:", {k: round(v, 4) for k, v in scorer.metrics.items()})
    print("\nmodel layout under", os.path.join(model_dir, "best"))
    for root, _, files in sorted(os.walk(os.path.join(model_dir, "best"))):
        for f in sorted(files):
            print("  ", os.path.relpath(os.path.join(root, f), model_dir))


if __name__ == "__main__":
    main()
