"""Which Pallas kernels does the chip's compiler accept?

Compiles every dense fused-GLM candidate (``ops/fused_glm.AUTOTUNE_CANDIDATES``)
at the bench shape (262144 x 512, bfloat16 storage) and every sparse family
(``ops/fused_sparse.sparse_candidates``) on one ladder-padded slab, ON THE
DEVICE (never interpreted), runs each once and checks it: dense candidates
against ``reference_logistic_value_and_grad`` to the bf16 tolerances of
``tests/test_fused_glm.py``, sparse families bitwise against the
``segment`` baseline through the repo's own race
(``race_sparse_kernels``). A candidate the compiler refuses is recorded
with the first line of its message.

Prints one table row per candidate as it goes and, as the last line of stdout, a JSON
object; the same object goes to ``chiprun_out/pallas_compile_check.json``.
Exits non-zero when jax has no TPU, or when the default-on dense candidate
(``DEFAULT_BLOCK_ROWS``) is refused or wrong. No timing is reported: this
is a compile-and-correctness check, not a race.

Run:  python3 tools/pallas_compile_check.py     (on the machine with the chip)
"""

import json
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

N_DENSE, D_DENSE = 262144, 512  # bench.py's dense shape
# one slab on the canonical ladder: 64 lanes x 256 rows x D=2048 with the
# bench sparse_race section's skew (85 % of rows 1-4 nnz, 15 % 8-16)
SPARSE_LANES, SPARSE_ROWS, SPARSE_DIM = 64, 256, 2048


def _dense_rows(n=N_DENSE, d=D_DENSE, interpret=False):
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.ops import fused_glm, losses

    rng = np.random.default_rng(0)
    x32 = rng.normal(size=(n, d)).astype(np.float32)
    w = jnp.asarray((rng.normal(size=d) * 0.1).astype(np.float32))
    y = jnp.asarray((rng.random(n) < 0.5).astype(np.float32))
    wt = jnp.asarray(rng.uniform(0.5, 2.0, size=n).astype(np.float32))
    off = jnp.zeros((n,), jnp.float32)
    x_bf16 = jnp.asarray(x32, jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        v_ref, g_ref = jax.jit(  # jit-ok: one-shot reference, nothing to donate
            fused_glm.reference_logistic_value_and_grad
        )(jnp.asarray(x32), y, wt, w)
    v_ref, g_ref = float(v_ref), np.asarray(g_ref)
    g_norm = float(np.linalg.norm(g_ref))

    rows = {}
    for cand in fused_glm.AUTOTUNE_CANDIDATES:
        name = "{}:{}".format(*fused_glm._decode_block(cand))
        try:
            fn = jax.jit(  # jit-ok: one-shot compile check, inputs shared by every candidate
                lambda x, y, wt, off, w, b=cand: fused_glm.fused_value_grad_parts(
                    losses.logistic, x, y, wt, off, w, block_rows=b,
                    interpret=interpret,
                )[:2]
            )
            v, g = jax.block_until_ready(fn(x_bf16, y, wt, off, w))
        except Exception as e:  # noqa: BLE001 — the compiler's refusal IS the result being recorded
            rows[name] = {"refused": fused_glm._first_line(e)}
            print(f"dense  {name:14s} {json.dumps(rows[name])}", flush=True)
            continue
        rel_v = abs(float(v) - v_ref) / abs(v_ref)
        rel_g = float(np.linalg.norm(np.asarray(g) - g_ref)) / g_norm
        # tests/test_fused_glm.py::test_bf16_storage_close_to_f32
        ok = rel_v < 2e-2 and rel_g < 0.03
        rows[name] = {
            "compiled": True, "matches_reference": bool(ok),
            "value_rel_err": round(rel_v, 6), "grad_rel_err": round(rel_g, 6),
        }
        print(f"dense  {name:14s} {json.dumps(rows[name])}", flush=True)
    return rows


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
    from photon_ml_tpu.ops import fused_glm

    print(compat.device_summary(), flush=True)
    if jax.default_backend() != "tpu":
        print("pallas_compile_check: jax found no TPU; nothing is compiled "
              "by Mosaic here", file=sys.stderr)
        return 2
    compat.enable_persistent_cache()

    dense = _dense_rows()
    sparse, sparse_info = _sparse_rows()

    default = "grid:{}".format(fused_glm.DEFAULT_BLOCK_ROWS)
    default_ok = bool(dense[default].get("matches_reference"))
    devs = jax.devices()
    out = {
        "ok": default_ok,
        "device": {"platform": devs[0].platform, "kind": devs[0].device_kind,
                   "count": len(devs)},
        "dense_shape": [N_DENSE, D_DENSE, "bfloat16"],
        "dense": dense,
        "sparse_slab": sparse_info,
        "sparse": sparse,
    }
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out",
                           "pallas_compile_check.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out), flush=True)
    return 0 if default_ok else 1


if __name__ == "__main__":
    sys.exit(main())
