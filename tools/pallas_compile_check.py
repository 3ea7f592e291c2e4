"""Which sparse kernel families does the chip's compiler accept?

Compiles every sparse family (``ops/fused_sparse.sparse_candidates``) on one
ladder-padded slab, ON THE DEVICE (never interpreted), runs each once and
checks it bitwise against the ``segment`` baseline through the repo's own
race (``race_sparse_kernels``). A candidate the compiler refuses is recorded
with the first line of its message. (What ``select_fused_block_rows`` hands
the dense kernel is compiled for the v5e, without a chip, by
``tests/test_dense_grid_reference.py``.)

Prints one table row per candidate as it goes and, as the last line of stdout, a JSON
object; the same object goes to ``chiprun_out/pallas_compile_check.json``.
Exits non-zero when jax has no TPU, or when a family other than the Pallas
ones (whose refusal is the record, ROADMAP R2) is refused or wrong. No timing is
reported: this is a compile-and-correctness check, not a race.

Run:  python3 tools/pallas_compile_check.py     (on the machine with the chip)
"""

import json
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# one slab on the canonical ladder: 64 lanes x 256 rows x D=2048 with the
# bench sparse_race section's skew (85 % of rows 1-4 nnz, 15 % 8-16)
SPARSE_LANES, SPARSE_ROWS, SPARSE_DIM = 64, 256, 2048


def _sparse_rows(e=SPARSE_LANES, m=SPARSE_ROWS, d=SPARSE_DIM):
    import jax.numpy as jnp

    from photon_ml_tpu.compile import ShapeBucketer
    from photon_ml_tpu.ops import fused_sparse
    from photon_ml_tpu.types import TaskType

    rng = np.random.default_rng(1)
    x = np.zeros((e, m, d), np.float32)
    nnz = np.where(rng.random((e, m)) < 0.85,
                   rng.integers(1, 5, (e, m)), rng.integers(8, 17, (e, m)))
    for i in range(e):
        for r in range(m):
            cols = rng.choice(d, size=nnz[i, r], replace=False)
            x[i, r, cols] = rng.normal(size=nnz[i, r])
    slab = fused_sparse.build_sparse_slab(x, bucketer=ShapeBucketer())
    labels = jnp.asarray((rng.random((e, m)) < 0.5).astype(np.float32))
    offsets = jnp.zeros((e, m), jnp.float32)
    weights = jnp.ones((e, m), jnp.float32)
    report = fused_sparse.race_sparse_kernels(
        TaskType.LOGISTIC_REGRESSION, slab, x, labels, offsets, weights,
        candidates=fused_sparse.sparse_candidates(m) + ("pallas:64",),
    )
    rows = {}
    for name, rec in report["candidates"].items():
        if "failed" in rec:  # a compile error, or not bitwise vs the baseline
            rows[name] = {"failed": rec["failed"].splitlines()[0]}
        else:
            rows[name] = {"compiled": True, "matches_reference": True}
        print(f"sparse {name:14s} {json.dumps(rows[name])}", flush=True)
    return rows, {"shape": report["shape"], "baseline": report["baseline"]}


def main() -> int:
    import jax

    from photon_ml_tpu import compat

    print(compat.device_summary(), flush=True)
    if jax.default_backend() != "tpu":
        print("pallas_compile_check: jax found no TPU; nothing is compiled "
              "by Mosaic here", file=sys.stderr)
        return 2
    compat.enable_persistent_cache()

    sparse, sparse_info = _sparse_rows()

    # the Pallas family's refusal is the record (ROADMAP R2), not a failure
    xla_ok = all(rec.get("matches_reference") for name, rec in sparse.items()
                 if not name.startswith("pallas"))
    devs = jax.devices()
    out = {
        "ok": xla_ok,
        "device": {"platform": devs[0].platform, "kind": devs[0].device_kind,
                   "count": len(devs)},
        "sparse_slab": sparse_info,
        "sparse": sparse,
    }
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out",
                           "pallas_compile_check.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out), flush=True)
    return 0 if xla_ok else 1


if __name__ == "__main__":
    sys.exit(main())
