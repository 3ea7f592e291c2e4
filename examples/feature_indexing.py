"""Feature-indexing example: build a partitioned off-heap name->index map
from Avro training data (the reference's FeatureIndexingJob), then train
the GLM driver against it via --offheap-indexmap-dir.

Run:  python examples/feature_indexing.py  [--output-dir OUT]

Runs on jax's default device (the driver logs which); set JAX_PLATFORMS=cpu
for a CPU run.
"""
import argparse
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DATA = "/root/reference/photon-ml/src/integTest/resources/DriverIntegTest/input"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--output-dir", default="/tmp/photon-ml-tpu-example-indexing")
    ns = ap.parse_args()

    from photon_ml_tpu.cli import feature_indexing, glm_driver

    index_dir = os.path.join(ns.output_dir, "indexes")
    feature_indexing.main([
        "--data-input-dirs", os.path.join(DATA, "heart.avro"),
        "--partition-num", "2",
        "--output-dir", index_dir,
        "--format", "OFFHEAP",
    ])
    print("index partitions:", sorted(os.listdir(index_dir)))

    driver = glm_driver.main([
        "--training-data-directory", os.path.join(DATA, "heart.avro"),
        "--validating-data-directory", os.path.join(DATA, "heart_validation.avro"),
        "--output-directory", os.path.join(ns.output_dir, "model"),
        "--task", "LOGISTIC_REGRESSION",
        "--regularization-weights", "1",
        "--offheap-indexmap-dir", index_dir,
        "--offheap-indexmap-num-partitions", "2",
        "--delete-output-dirs-if-exist", "true",
    ])
    metrics = driver.validation_metrics[driver.best_reg_weight]
    print("AUROC with off-heap index:", round(metrics["Area under ROC"], 4))


if __name__ == "__main__":
    main()
