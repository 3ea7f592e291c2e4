"""Subprocess worker for the streaming-RE peak-RSS gate: train the same
random-effect dataset either in-memory or block-streamed under a memory
budget, and report ru_maxrss. Run: worker.py <streaming|inmemory> <outdir>."""

import os
import resource
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax

jax.config.update("jax_platforms", "cpu")  # a CPU harness: RSS is a host metric

import jax.numpy as jnp  # noqa: E402

from photon_ml_tpu.algorithm import (  # noqa: E402
    RandomEffectCoordinate,
    StreamingRandomEffectCoordinate,
    write_re_entity_blocks,
)
from photon_ml_tpu.data.game import (  # noqa: E402
    GameData,
    HostFeatures,
    RandomEffectDataConfig,
    build_random_effect_dataset,
)
from photon_ml_tpu.ops.regularization import RegularizationContext  # noqa: E402
from photon_ml_tpu.optim.common import OptimizerConfig  # noqa: E402
from photon_ml_tpu.types import OptimizerType, TaskType  # noqa: E402

mode, outdir = sys.argv[1], sys.argv[2]
E, LO, HI, D = 3000, 152, 160, 64
BUDGET = 16_000_000

rng = np.random.default_rng(5)
rows_per = rng.integers(LO, HI + 1, size=E)
n = int(rows_per.sum())
ids = np.repeat(np.arange(E, dtype=np.int32), rows_per)
ids = ids[rng.permutation(n)]
# dense features straight into CSR form (no (n, D) dense intermediate copy
# beyond the values themselves — the values ARE the dataset)
values = rng.normal(size=n * D).astype(np.float32)
feats = HostFeatures(
    np.arange(n + 1, dtype=np.int64) * D,
    np.tile(np.arange(D, dtype=np.int32), n),
    values,
    D,
)
y = (rng.random(n) < 0.5).astype(np.float32)
data = GameData(
    response=y,
    offset=np.zeros(n, np.float32),
    weight=np.ones(n, np.float32),
    ids={"userId": ids},
    id_vocabs={"userId": [f"u{i}" for i in range(E)]},
    shards={"per_user": feats},
)
slab_bytes = E * int(rows_per.max()) * D * 4  # the in-memory x-stack cost

cfg = OptimizerConfig(max_iterations=8, tolerance=1e-7)
reg = RegularizationContext.l2(0.3)
config = RandomEffectDataConfig("userId", "per_user")
resid = jnp.zeros((n,), jnp.float32)

if mode == "streaming":
    manifest = write_re_entity_blocks(
        data, config, outdir, memory_budget_bytes=BUDGET
    )
    assert manifest.max_block_bytes <= BUDGET, manifest.max_block_bytes
    coord = StreamingRandomEffectCoordinate(
        manifest, TaskType.LOGISTIC_REGRESSION,
        optimizer_config=cfg, regularization=reg,
    )
    w, _ = coord.update(resid, coord.initial_coefficients())
    total = float(jnp.sum(coord.score(w)))
else:
    ds = build_random_effect_dataset(data, config)
    coord = RandomEffectCoordinate(
        ds, TaskType.LOGISTIC_REGRESSION,
        optimizer_config=cfg, regularization=reg,
    )
    w, _ = coord.update(resid, coord.initial_coefficients())
    total = float(jnp.sum(coord.score(w)))

peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024  # kB on linux
print(f"checksum {total:.4f}", file=sys.stderr)
print(f"RSS mode={mode} peak_rss={peak} slab_bytes={slab_bytes} budget={BUDGET}")
