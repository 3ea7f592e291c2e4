"""Family ``glm_sparse``: L2 logistic regression over a sparse, wide
feature space, trained by ``photon_ml_tpu.training.train_glm_grid`` (the
call ``cli/glm_driver`` makes) on rows made on the device from the seed.
"""

from __future__ import annotations

import functools

import numpy as np

from benchmark.families import common

#: sizes of the CPU rehearsal (benchmark/check.py and the tests only)
TINY = {"features": 4096, "train_rows": 2048, "held_out_rows": 128,
        "reference_blocks": 16}

#: the features whose sums :meth:`Cell.gradient_readings` also reads alone
MOST_FREQUENT = 128


def _key(seed: int):
    import jax

    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def synthesize(sizes: dict, assumed: dict, seed: int):
    """Two (indices (n, K) int32, values (n, K) float32, labels (n,)) sets on
    the device, in one jitted call: the training rows and the held-out rows,
    which stay resident as the GLM driver's validation batch does and are
    never scored by this job. Feature ``floor(D u^p)`` for uniform ``u``, so
    a few features take most of the non-zeros; labels from a planted weight
    vector through the benchmark's own gather-and-sum. The planted vector is
    the configuration's (``planted_key``), the same for every seed: the few
    most popular features carry most of the signal, so a vector drawn from
    the seed would give every seed another problem and another number of
    line-search trials; the seed draws the rows and the labels."""
    import jax
    import jax.numpy as jnp

    from benchmark.references.glm_sparse import margins

    n, k = int(sizes["train_rows"]), int(sizes["nonzeros_per_row"])
    held, dim = int(sizes["held_out_rows"]), int(sizes["features"])

    @functools.partial(jax.jit, static_argnames=("n", "k", "dim"))
    def make(key, n, k, dim):
        k_idx, k_val, k_lab = jax.random.split(key, 3)
        k_w = jax.random.key(int(assumed["planted_key"]))
        u = jax.random.uniform(k_idx, (n, k), jnp.float32)
        idx = jnp.minimum(
            jnp.floor(dim * u ** assumed["popularity_power"]).astype(jnp.int32),
            dim - 1)
        val = jax.random.normal(k_val, (n, k), jnp.float32)
        planted = jax.random.normal(k_w, (dim,), jnp.float32) \
            * assumed["planted_scale"]
        p = jax.nn.sigmoid(margins(idx, val, planted))
        labels = (jax.random.uniform(k_lab, (n,)) < p).astype(jnp.float32)
        return idx, val, labels

    train_key, held_key = jax.random.split(_key(seed))
    return jax.block_until_ready((make(train_key, n, k, dim),
                                  make(held_key, held, k, dim)))


def program_inputs(indices, values, labels, dim: int):
    """The rows as ``io/libsvm.py`` ``to_batch(dense=False)`` hands them to
    the GLM driver: padded sparse features, zero offsets, unit weights."""
    import jax.numpy as jnp

    from photon_ml_tpu.ops.features import SparseFeatures, auto_transpose
    from photon_ml_tpu.ops.objective import GLMBatch

    n = labels.shape[0]
    return GLMBatch(
        auto_transpose(SparseFeatures(indices, values, dim)),
        labels, jnp.zeros((n,), jnp.float32), jnp.ones((n,), jnp.float32))


class Cell:
    def __init__(self, config: dict, job: dict, seed: int, tiny: bool):
        from photon_ml_tpu.ops.normalization import NormalizationContext
        from photon_ml_tpu.ops.regularization import RegularizationContext
        from photon_ml_tpu.optim.common import OptimizerConfig
        from photon_ml_tpu.optim.problem import GLMOptimizationProblem
        from photon_ml_tpu.types import OptimizerType, TaskType

        self.sizes = dict(config["sizes"])
        if tiny:
            self.sizes.update(TINY)
        self.limits = config["limits"]
        self._assumed = config["assumed"]
        solver = self.sizes["solver"]
        (self.indices, self.values, self.labels), self.held_out = synthesize(
            self.sizes, config["assumed"], seed)
        self._batch = program_inputs(self.indices, self.values, self.labels,
                                     int(self.sizes["features"]))
        self._problem = GLMOptimizationProblem(
            task=TaskType.LOGISTIC_REGRESSION,
            optimizer=OptimizerType[solver["optimizer"]],
            optimizer_config=OptimizerConfig(
                max_iterations=int(solver["max_iterations"]),
                tolerance=float(solver["tolerance"]),
                num_corrections=int(solver["corrections"])),
            regularization=RegularizationContext.l2(float(solver["l2"])),
        )
        self._norm = NormalizationContext.identity()
        self.shapes = {"indices": tuple(self.indices.shape)}

    def run_job(self):
        from photon_ml_tpu.training import train_glm_grid

        trained = train_glm_grid(
            self._problem, self._batch, self._norm,
            [float(self.sizes["solver"]["l2"])])
        trained.models[0].coefficients.means.block_until_ready()
        return trained

    def collect(self, trained) -> dict:
        res = trained.results[0]
        its = int(res.iterations)
        return {
            "coefficients": np.asarray(trained.models[0].coefficients.means),
            "values": np.asarray(res.value_history, np.float64)[:its + 1],
            "first_grad_norm": float(res.grad_norm_history[0]),
            "iterations": its,
        }

    def free(self):
        self._batch = None

    def permute_rows(self, seed: int):
        """A planted *sound* case (benchmark/control.py ``--permuted-rows``):
        hand the program the same rows in another order, a permutation made
        on the host from the seed; the reference keeps the rows as they were.
        Same data, same mathematics, another order of addition."""
        import jax.numpy as jnp

        self._batch = None
        order = jnp.asarray(np.random.default_rng(seed).permutation(
            self.labels.shape[0]).astype(np.int32))
        self._batch = program_inputs(
            self.indices[order], self.values[order], self.labels[order],
            int(self.sizes["features"]))

    def gradient_readings(self, seed: int) -> list:
        """How far the reference's gradient is from the exact sum of its own
        float32 products, at zero and at one random vector: relative L2
        against a float64 sum made on the host from the same rows
        (``numpy.bincount``), over all features and over the
        ``MOST_FREQUENT`` most frequent ones; beside it the accumulation the
        reference had until PR 35 (one float32 vector carried through all the
        rows, in 8 blocks)."""
        import jax
        import jax.numpy as jnp

        from benchmark.references import glm_sparse

        dim = int(self.sizes["features"])
        ones = jnp.ones(self.labels.shape, jnp.float32)
        cut = functools.partial(glm_sparse.in_blocks, self.indices,
                                self.values, self.labels, ones)
        rows = cut(int(self.sizes["reference_blocks"]))
        few = cut(min(8, int(self.sizes["reference_blocks"])))
        terms = jax.jit(glm_sparse.row_terms)
        new = jax.jit(lambda w, rows: glm_sparse.value_and_grad(
            w, rows, 0.0, dim)[1])

        @jax.jit
        def carried(w, rows):
            def block(grad, part):
                i = part[0]
                return grad.at[i.reshape(-1)].add(
                    glm_sparse.row_terms(w, *part)[1].reshape(-1)), None

            return jax.lax.scan(block, jnp.zeros((dim,), jnp.float32), rows)[0]

        counts = np.zeros(dim, np.int64)
        for i in few[0]:
            counts += np.bincount(np.asarray(i).reshape(-1), minlength=dim)
        hot = np.argsort(-counts, kind="stable")[:MOST_FREQUENT]
        scale = float(self._assumed["planted_scale"])
        lines = []
        for name, w in (("zero", jnp.zeros((dim,), jnp.float32)),
                        ("random", scale * jax.random.normal(
                            jax.random.fold_in(_key(seed), 1), (dim,)))):
            exact = np.zeros(dim, np.float64)
            for part in zip(*few):
                exact += np.bincount(
                    np.asarray(part[0]).reshape(-1),
                    np.asarray(terms(w, *part)[1], np.float64).reshape(-1),
                    minlength=dim)
            line = {"at": name, "norm": float(np.linalg.norm(exact)),
                    "most_frequent_share_of_norm2": float(
                        np.sum(exact[hot] ** 2) / np.sum(exact ** 2))}
            for how, grad in (("kept", new(w, rows)), ("carried", carried(w, few))):
                grad = np.asarray(grad, np.float64)
                line[how] = common.relative_difference(grad, exact)
                line[how + "_most_frequent"] = common.relative_difference(
                    grad[hot], exact[hot])
            lines.append(line)
        return lines

    def reference(self, storage: str = "float32", half_batch: bool = False) -> dict:
        import jax.numpy as jnp

        from benchmark.references import glm_sparse

        solver = self.sizes["solver"]
        n = self.labels.shape[0]
        weights = jnp.ones((n,), jnp.float32)
        if half_batch:
            weights = weights.at[1::2].set(0.0) * 2.0
        sol = glm_sparse.fit(
            self.indices, self.values, self.labels, weights,
            float(solver["l2"]), int(self.sizes["features"]),
            int(solver["max_iterations"]), float(solver["tolerance"]),
            int(self.sizes["reference_blocks"]), jnp.dtype(storage))
        its = int(sol.iterations)
        return {
            "coefficients": np.asarray(sol.w),
            "values": np.asarray(sol.values, np.float64)[:its + 1],
            "first_grad": np.asarray(sol.first_grad),
            "first_grad_norm": float(sol.grad_norms[0]),
            "iterations": its,
            "evaluations": int(sol.evaluations),
        }

    def compare(self, got: dict, ref: dict) -> dict:
        rel = common.relative_difference
        steps = min(len(got["values"]), len(ref["values"]))
        return {
            "values_gap": float(np.max(
                np.abs(got["values"][:steps] - ref["values"][:steps])
                / np.abs(ref["values"][:steps]))),
            # the solve's own float32 norm against the float64 norm of the
            # reference's vector: the reading is the program's rounding alone
            "first_grad_gap": rel(got["first_grad_norm"], np.linalg.norm(
                np.asarray(ref["first_grad"], np.float64))),
            "change_norm_gap": rel(np.linalg.norm(got["coefficients"]),
                                   np.linalg.norm(ref["coefficients"])),
            "coefficients_gap": rel(got["coefficients"], ref["coefficients"]),
            "iterations_gap": float(abs(got["iterations"] - ref["iterations"])),
        }

    def work(self, ref: dict) -> dict:
        """Required FLOPs and bytes of one job: (iterations + 1) passes, each
        4 FLOPs per stored value and one read of every value, its index and
        the row's label, offset and weight."""
        n, k = self.indices.shape
        passes = ref["iterations"] + 1.0
        one = {"flops": 4.0 * n * k * passes,
               "bytes": (8.0 * n * k + 12.0 * n) * passes}
        return {"fe_solve": one, "job": dict(one)}


def build(config: dict, job: dict, seed: int, tiny: bool = False) -> Cell:
    return Cell(config, job, seed, tiny)
