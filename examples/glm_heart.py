"""End-to-end GLM example: L2 logistic regression on the heart dataset
(the reference's own DriverIntegTest fixture) through the staged CLI driver
— preprocess, lambda-grid train with warm starts, validate, model-select,
diagnose (HTML report), save (text + Avro).

Run:  python examples/glm_heart.py  [--output-dir OUT]

Runs on jax's default device (the driver logs which); set JAX_PLATFORMS=cpu
for a CPU run, which gets 8 virtual devices from the XLA_FLAGS default below.
"""
import argparse
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DATA = "/root/reference/photon-ml/src/integTest/resources/DriverIntegTest/input"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--output-dir", default="/tmp/photon-ml-tpu-example-glm")
    ns = ap.parse_args()

    from photon_ml_tpu.cli import glm_driver

    driver = glm_driver.main([
        "--training-data-directory", os.path.join(DATA, "heart.avro"),
        "--validating-data-directory", os.path.join(DATA, "heart_validation.avro"),
        "--output-directory", ns.output_dir,
        "--task", "LOGISTIC_REGRESSION",
        "--regularization-weights", "0.1,1,10,100",
        "--regularization-type", "L2",
        "--normalization-type", "STANDARDIZATION",
        "--diagnostic-mode", "ALL",
        "--delete-output-dirs-if-exist", "true",
    ])

    stages = [s.name for s in driver.stage_history] + [driver.stage.name]
    print("\nstages:", " -> ".join(stages))
    for lam, metrics in sorted(driver.validation_metrics.items()):
        print(f"lambda={lam:<8g} AUROC={metrics['Area under ROC']:.4f}")
    print("best lambda:", driver.best_reg_weight)
    print("outputs in", ns.output_dir)
    for root, _, files in os.walk(ns.output_dir):
        for f in files:
            print("  ", os.path.relpath(os.path.join(root, f), ns.output_dir))


if __name__ == "__main__":
    main()
