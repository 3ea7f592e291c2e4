"""Family ``glm_dense``: L2 logistic regression over a dense matrix of rows,
a warm-started grid of L2 weights trained by
``photon_ml_tpu.training.train_glm_grid`` (the call ``cli/glm_driver`` makes)
on the configuration's data set, made on the device and shuffled by the seed.
"""

from __future__ import annotations

import functools

import numpy as np

from benchmark.families import common

#: sizes of the CPU rehearsal (benchmark/check.py and the tests only); the
#: width is no multiple of 128 and there are some 200 rows a feature, as in
#: the cell
TINY = {"features": 40, "train_rows": 8192, "held_out_rows": 128,
        "reference_blocks": 2}


def mixing_matrix(dim: int, rho: float) -> np.ndarray:
    """``A`` (dim, dim) float32 with ``e @ A`` a row of unit-variance features
    whose correlation is ``rho ** |i - j|`` when ``e`` is standard normal:
    feature ``i`` is ``rho * feature[i - 1] + sqrt(1 - rho^2) * e[i]``, written
    out. The correlation's eigenvalues lie between ``(1 - rho) / (1 + rho)``
    and ``(1 + rho) / (1 - rho)``."""
    lag = np.arange(dim)[None, :] - np.arange(dim)[:, None]
    a = np.where(lag >= 0, float(rho) ** np.abs(lag).astype(np.float64), 0.0)
    a[1:] *= np.sqrt(1.0 - float(rho) ** 2)
    return a.astype(np.float32)


def _row_blocks(n: int, most: int = 16) -> int:
    return max(b for b in range(1, most + 1) if n % b == 0)


def synthesize(sizes: dict, assumed: dict, seed: int):
    """Two (matrix (n, D) float32, labels (n,)) sets on the device, each in
    one jitted call: the training rows and the held-out rows, which stay
    resident as the GLM driver's validation batch does and are never scored
    by this job.

    The data set is the configuration's, as epsilon is one file: row ``i``
    and its label are drawn from ``data_key`` and ``i`` alone. Rows are
    normal with unit-variance features of correlation
    ``feature_correlation ** |i - j|`` (``mixing_matrix``; made in blocks of
    rows so that the temporaries stay small), every row then scaled to unit
    length; labels Bernoulli(sigmoid(planted margin)) through the benchmark's
    own product, the planted vector normal(``planted_key``) times
    ``planted_scale``. The seed shuffles it as a loader would: the order of
    the rows, and the order and the signs of the features (the planted vector
    with them, so the labels are the row's own). L-BFGS's iterates do not
    depend on either but for float32 rounding, so every seed holds another
    matrix and runs the same evaluations."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from benchmark.references.glm_dense import margins

    n, held = int(sizes["train_rows"]), int(sizes["held_out_rows"])
    dim = int(sizes["features"])
    # the shuffles are drawn on the host: sorting 400,000 keys is a program
    # the chip's compiler takes most of a minute over
    shuffle = np.random.default_rng(seed)
    order = shuffle.permutation(dim)
    signs = shuffle.choice(np.array([-1.0, 1.0], np.float32), dim)
    mix = jnp.asarray(
        mixing_matrix(dim, assumed["feature_correlation"])[:, order] * signs)
    planted = np.asarray(jax.random.normal(
        jax.random.key(int(assumed["planted_key"])), (dim,), jnp.float32))
    planted = jnp.asarray(planted[order] * signs * assumed["planted_scale"])

    @functools.partial(jax.jit, static_argnames=("blocks",))
    def make(data_key, row_order, mix, planted, blocks):
        n = row_order.shape[0]
        row_keys = jax.random.split(data_key, n)[row_order]

        def block(keys):
            draws = jax.vmap(lambda k: jax.random.normal(k, (dim + 1,)))(keys)
            rows = jnp.dot(draws[:, :dim], mix, precision=lax.Precision.HIGHEST)
            rows = rows / jnp.linalg.norm(rows, axis=-1, keepdims=True)
            return rows, draws[:, dim]

        rows, coin = lax.map(block, row_keys.reshape(blocks, n // blocks))
        rows, coin = rows.reshape(n, dim), coin.reshape(n)
        # a standard normal draw under the probit of p is Bernoulli(p)
        p = jax.nn.sigmoid(margins(rows, planted))
        labels = (jax.scipy.stats.norm.cdf(coin) < p).astype(jnp.float32)
        return rows, labels

    data = jax.random.key(int(assumed["data_key"]))
    return jax.block_until_ready(tuple(
        make(jax.random.fold_in(data, which),
             jnp.asarray(shuffle.permutation(rows), jnp.int32), mix, planted,
             _row_blocks(rows))
        for which, rows in enumerate((n, held))))


def program_inputs(matrix, labels):
    """The rows as ``io/libsvm.py`` ``to_batch(dense=True)`` hands them to
    the GLM driver: a dense float32 matrix, zero offsets, unit weights."""
    import jax.numpy as jnp

    from photon_ml_tpu.ops.features import DenseFeatures
    from photon_ml_tpu.ops.objective import GLMBatch

    n = labels.shape[0]
    return GLMBatch(DenseFeatures(matrix), labels,
                    jnp.zeros((n,), jnp.float32), jnp.ones((n,), jnp.float32))


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
        solver = self.sizes["solver"]
        self._weights = [float(l2) for l2 in solver["l2_grid"]]  # as the driver gets them
        self.grid = sorted(self._weights, reverse=True)  # as the solves run
        (self.matrix, self.labels), self.held_out = synthesize(
            self.sizes, config["assumed"], seed)
        self._batch = program_inputs(self.matrix, self.labels)
        self._problem = GLMOptimizationProblem(
            task=TaskType.LOGISTIC_REGRESSION,
            optimizer=OptimizerType[solver["optimizer"]],
            optimizer_config=OptimizerConfig(
                max_iterations=int(solver["max_iterations"]),
                tolerance=float(solver["tolerance"]),
                num_corrections=int(solver["corrections"])),
            regularization=RegularizationContext.l2(self.grid[0]),
        )
        self._norm = NormalizationContext.identity()
        self.shapes = {"matrix": tuple(self.matrix.shape)}

    def run_job(self):
        from photon_ml_tpu.training import train_glm_grid

        trained = train_glm_grid(self._problem, self._batch, self._norm, self._weights)
        trained.models[-1].coefficients.means.block_until_ready()
        return trained

    def collect(self, trained) -> dict:
        """Per solve, in the order they ran (L2 weight high to low)."""
        assert [float(l2) for l2 in trained.weights] == self.grid
        solves = []
        for model, res in zip(trained.models, trained.results):
            its = int(res.iterations)
            solves.append({
                "coefficients": np.asarray(model.coefficients.means),
                "values": np.asarray(res.value_history, np.float64)[:its + 1],
                "first_grad_norm": float(res.grad_norm_history[0]),
                "iterations": its,
            })
        return {"solves": solves}

    def free(self):
        self._batch = None

    def reference(self, storage: str = "float32", half_batch: bool = False) -> dict:
        import jax.numpy as jnp

        from benchmark.references import glm_dense

        solver = self.sizes["solver"]
        n = self.labels.shape[0]
        weights = jnp.ones((n,), jnp.float32)
        if half_batch:
            weights = weights.at[1::2].set(0.0) * 2.0
        solves = []
        for sol in glm_dense.fit_grid(
                self.matrix, self.labels, weights, self.grid,
                int(solver["max_iterations"]), float(solver["tolerance"]),
                int(self.sizes["reference_blocks"]), int(solver["corrections"]),
                jnp.dtype(storage)):
            its = int(sol.iterations)
            solves.append({
                "coefficients": np.asarray(sol.w),
                "values": np.asarray(sol.values, np.float64)[:its + 1],
                "first_grad": np.asarray(sol.first_grad),
                "first_grad_norm": float(sol.grad_norms[0]),
                "iterations": its,
                "evaluations": int(sol.evaluations),
            })
        return {"solves": solves}

    def compare(self, got: dict, ref: dict) -> dict:
        """Each number the worst over the grid's solves. A solve's change is
        from the coefficients it started at: zero, then the solve before."""
        rel = common.relative_difference
        worst = {"values_gap": 0.0, "first_grad_gap": 0.0, "change_norm_gap": 0.0,
                 "coefficients_gap": 0.0, "iterations_gap": 0.0}
        got_from = ref_from = 0.0
        for g, r in zip(got["solves"], ref["solves"]):
            steps = min(len(g["values"]), len(r["values"]))
            one = {
                "values_gap": float(np.max(
                    np.abs(g["values"][:steps] - r["values"][:steps])
                    / np.abs(r["values"][:steps]))),
                # the solve's own float32 norm against the float64 norm of
                # the reference's vector; for the warm-started solves the
                # first gradient reads the hand-over of the coefficients
                "first_grad_gap": rel(g["first_grad_norm"], np.linalg.norm(
                    np.asarray(r["first_grad"], np.float64))),
                "change_norm_gap": rel(
                    np.linalg.norm(g["coefficients"] - got_from),
                    np.linalg.norm(r["coefficients"] - ref_from)),
                "coefficients_gap": rel(g["coefficients"], r["coefficients"]),
                "iterations_gap": float(abs(g["iterations"] - r["iterations"])),
            }
            worst = {name: max(worst[name], one[name]) for name in worst}
            got_from, ref_from = g["coefficients"], r["coefficients"]
        return worst

    def work(self, ref: dict) -> dict:
        """Required FLOPs and bytes of one job, whatever implements it: per
        solve (iterations + 1) evaluations, each one read of the training
        matrix and of the rows' label, offset and weight, and 4 FLOPs per
        stored value (two for the margins, two for the gradient). The line
        search's extra evaluations do not count."""
        n, d = self.matrix.shape
        passes = sum(s["iterations"] + 1.0 for s in ref["solves"])
        one = {"flops": 4.0 * n * d * passes,
               "bytes": (4.0 * n * d + 12.0 * n) * passes}
        return {"fe_solve": one, "job": dict(one)}


def build(config: dict, job: dict, seed: int, tiny: bool = False) -> Cell:
    return Cell(config, job, seed, tiny)
