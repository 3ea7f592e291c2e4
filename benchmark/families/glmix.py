"""Family ``glmix``: logistic GLMix (fixed effect + per-user random effect)
trained by the program's coordinate descent, as
``cli/game_training_driver.py`` builds it, from rows made here from the seed.

``build(config, job, seed, tiny)`` returns the cell: the timed job, what it
produced brought to the host under public names (coefficients by user id
and feature column), the plain reference, the comparison and the work.
"""

from __future__ import annotations

import numpy as np

from benchmark.families import common

N_GENRES = 18

#: sizes of the CPU rehearsal (benchmark/check.py and the tests only)
TINY = {"ratings": 6000, "train_rows": 5400, "users": 47, "movies": 90}


def synthesize(sizes: dict, assumed: dict, seed: int) -> dict:
    """Ratings with MovieLens' skew and a planted fixed + per-user logistic
    model; a vectorised copy of ``tools/movielens_baseline.py:synthesize``.
    Returns the training rows: dense ``x`` (movie features + a column of
    ones), ``users``, ``labels``. Every user has at least one training row,
    so the shapes the program builds do not depend on the seed."""
    rng = np.random.default_rng(seed)
    rows, n_train = int(sizes["ratings"]), int(sizes["train_rows"])
    n_users, n_movies = int(sizes["users"]), int(sizes["movies"])
    d_movie = int(sizes["movie_features"])
    user_w = rng.pareto(assumed["user_pareto"], n_users) + 1.0
    movie_w = rng.pareto(assumed["movie_pareto"], n_movies) + 1.0
    users = rng.choice(n_users, size=rows, p=user_w / user_w.sum())
    movies = rng.choice(n_movies, size=rows, p=movie_w / movie_w.sum())
    at = rng.choice(n_train, size=n_users, replace=False)
    users[at] = rng.permutation(n_users)

    how_many = rng.integers(1, 4, n_movies)
    order = rng.random((n_movies, N_GENRES)).argsort(axis=1).argsort(axis=1)
    genres = (order < how_many[:, None]).astype(np.float32)
    year = rng.uniform(-1, 1, n_movies).astype(np.float32)
    pop = np.log1p(movie_w / movie_w.mean()).astype(np.float32)
    extra = rng.normal(size=(n_movies, d_movie - N_GENRES - 2)).astype(np.float32)
    movie_feats = np.concatenate(
        [genres, year[:, None], pop[:, None], extra], axis=1)

    w_fixed = rng.normal(size=d_movie).astype(np.float32) * assumed["fixed_scale"]
    w_user = rng.normal(size=(n_users, d_movie)).astype(np.float32) \
        * assumed["user_scale"]
    users, movies = users[:n_train], movies[:n_train]
    x = movie_feats[movies]
    z = x @ w_fixed + np.einsum("rd,rd->r", x, w_user[users]) + rng.normal(
        scale=assumed["label_noise"], size=n_train).astype(np.float32)
    labels = (1.0 / (1.0 + np.exp(-z)) > rng.random(n_train)).astype(np.float32)
    x = np.concatenate([x, np.ones((n_train, 1), np.float32)], axis=1)
    return {"x": x, "users": users.astype(np.int32), "labels": labels}


def program_inputs(rows: dict):
    """The rows as the program's ingest would hand them on: a columnar
    ``GameData`` with both feature shards in CSR (zeros dropped)."""
    from photon_ml_tpu.data.game import GameData, HostFeatures

    x = rows["x"]
    n, dim = x.shape
    stored = x != 0.0
    indptr = np.concatenate([[0], np.cumsum(stored.sum(axis=1))]).astype(np.int64)
    feats = HostFeatures(indptr, np.nonzero(stored)[1].astype(np.int32),
                         x[stored], dim)
    return GameData(
        response=rows["labels"], offset=np.zeros(n, np.float32),
        weight=np.ones(n, np.float32), ids={"userId": rows["users"]},
        id_vocabs={"userId": []}, shards={"global": feats, "per_user": feats},
    )


class Cell:
    def __init__(self, config: dict, job: dict, seed: int, tiny: bool):
        import jax.numpy as jnp

        from photon_ml_tpu.algorithm.coordinate_descent import CoordinateDescent
        from photon_ml_tpu.algorithm.fixed_effect import FixedEffectCoordinate
        from photon_ml_tpu.algorithm.random_effect import RandomEffectCoordinate
        from photon_ml_tpu.data.game import (
            RandomEffectDataConfig, build_fixed_effect_batch,
            build_random_effect_dataset,
        )
        from photon_ml_tpu.ops import losses
        from photon_ml_tpu.ops.regularization import RegularizationContext
        from photon_ml_tpu.optim.common import OptimizerConfig
        from photon_ml_tpu.optim.problem import GLMOptimizationProblem
        from photon_ml_tpu.types import OptimizerType, TaskType

        self.sizes = dict(config["sizes"], passes=int(job["passes"]))
        if tiny:
            self.sizes.update(TINY)
        self.limits = config["limits"]
        fixed, per_user = self.sizes["fixed_effect"], self.sizes["per_user"]
        self.rows = synthesize(self.sizes, config["assumed"], seed)
        data = program_inputs(self.rows)
        task = TaskType.LOGISTIC_REGRESSION

        fe_batch = build_fixed_effect_batch(data, "global", dense=True)
        re_data = build_random_effect_dataset(data, RandomEffectDataConfig(
            random_effect_id="userId", feature_shard_id="per_user",
            num_shards=int(per_user["num_shards"]),
            active_upper_bound=int(per_user["active_upper_bound"]),
            passive_lower_bound=int(per_user["passive_lower_bound"]),
            projector=per_user["projector"],
            seed=int(per_user["reservoir_seed"]),
        ))
        coordinates = {
            "global": FixedEffectCoordinate(fe_batch, GLMOptimizationProblem(
                task=task, optimizer=OptimizerType[fixed["optimizer"]],
                optimizer_config=OptimizerConfig(
                    max_iterations=int(fixed["max_iterations"]),
                    tolerance=float(fixed["tolerance"])),
                regularization=RegularizationContext.l2(float(fixed["l2"])),
            )),
            "per-user": RandomEffectCoordinate(
                re_data, task, optimizer=OptimizerType[per_user["optimizer"]],
                optimizer_config=OptimizerConfig(
                    max_iterations=int(per_user["max_iterations"]),
                    tolerance=float(per_user["tolerance"])),
                regularization=RegularizationContext.l2(float(per_user["l2"])),
                solve_label="per-user",
            ),
        }
        loss = losses.for_task(task)
        labels = jnp.asarray(data.response)
        offsets = jnp.asarray(data.offset)
        weights = jnp.asarray(data.weight)

        def training_loss(total):
            return jnp.sum(weights * loss.loss(total + offsets, labels))

        self._descent = CoordinateDescent(coordinates, training_loss)
        # to read the per-user answer out by user id and feature column
        place = np.full(int(self.sizes["users"]), -1, np.int64)
        place[self.rows["users"]] = np.asarray(re_data.entity_pos)
        self._place = place
        self._columns = np.asarray(re_data.local_to_global)
        self.shapes = {
            "x": tuple(re_data.x.shape), "feat_idx": tuple(re_data.feat_idx.shape),
            "fixed": tuple(fe_batch.features.matrix.shape),
        }
        if not tiny:
            expect = {k: tuple(v) for k, v in config["shapes"].items()}
            if self.shapes != expect:
                raise RuntimeError(
                    f"seed {seed} gave shapes {self.shapes}, the configuration "
                    f"states {expect}: every seed must compile the same programs")

    def run_job(self):
        result = self._descent.run(self.sizes["passes"], len(self.rows["labels"]))
        result.total_scores.block_until_ready()
        return result

    def collect(self, result) -> dict:
        local = np.asarray(result.coefficients["per-user"])
        dim = self.rows["x"].shape[1]
        by_slot = np.zeros((local.shape[0], dim), np.float32)
        known = self._columns >= 0
        slot = np.broadcast_to(np.arange(local.shape[0])[:, None], local.shape)
        by_slot[slot[known], self._columns[known]] = local[known]
        fe, re = result.trackers["global"], result.trackers["per-user"]
        return {
            "objective": np.asarray(result.objective_history, np.float64),
            "fixed": np.asarray(result.coefficients["global"]),
            "per_user": by_slot[self._place],
            "scores": np.asarray(result.total_scores),
            "fixed_first_grad": float(fe.grad_norm_history[0]),
            "user_value_sum": float(np.sum(np.asarray(re.value, np.float64))),
            "program_iterations": {
                "fixed_last": int(fe.iterations),
                "per_user_last_max": int(np.max(np.asarray(re.iterations))),
            },
        }

    def free(self):
        self._descent = None

    def reference(self, storage: str = "float32", **fault) -> dict:
        import jax.numpy as jnp

        from benchmark.references import glmix

        return glmix.fit(self.rows, self.sizes, jnp.dtype(storage), **fault)

    def compare(self, got: dict, ref: dict) -> dict:
        rel = common.relative_difference
        ref_objective = ref["objective"][:len(got["objective"])]
        return {
            "objective_gap": float(np.max(
                np.abs(got["objective"] - ref_objective) / np.abs(ref_objective))),
            "fixed_gap": rel(got["fixed"], ref["fixed"]),
            "per_user_gap": rel(got["per_user"], ref["per_user"]),
            "scores_gap": rel(got["scores"], ref["scores"]),
            "fixed_first_grad_gap": rel(got["fixed_first_grad"],
                                        ref["fixed_first_grad"]),
            "user_value_sum_gap": rel(got["user_value_sum"],
                                      ref["user_value_sum"]),
        }

    def work(self, ref: dict) -> dict:
        """Required FLOPs and bytes of one job, from the shapes and the
        reference's own iteration counts: for each solve (iterations + 1)
        passes, 4 FLOPs and one read per stored feature value (padding not
        counted), the three row vectors read beside them."""
        dim = self.rows["x"].shape[1]
        out = {"fe_solve": {"flops": 0.0, "bytes": 0.0},
               "re_solve": {"flops": 0.0, "bytes": 0.0}}
        for kind, iterations, rows in ref["counts"]:
            visits = float(np.sum((np.asarray(iterations, np.float64) + 1.0)
                                  * np.asarray(rows, np.float64)))
            prog = out["fe_solve" if kind == "fixed" else "re_solve"]
            prog["flops"] += 4.0 * dim * visits
            prog["bytes"] += 4.0 * (dim + 3) * visits
        out["job"] = {k: out["fe_solve"][k] + out["re_solve"][k]
                      for k in ("flops", "bytes")}
        return out


def build(config: dict, job: dict, seed: int, tiny: bool = False) -> Cell:
    return Cell(config, job, seed, tiny)
