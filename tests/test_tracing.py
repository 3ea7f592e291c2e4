"""The names the program puts into a profiler trace (PERF.md, "Spans, scopes
and counters"): device scopes in the lowered text, XLA module names made from
the ``instrumented_jit`` sites, and host spans with their nesting and
metadata, read back from a CPU trace."""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu import training
from photon_ml_tpu.algorithm import (
    CoordinateDescent,
    FixedEffectCoordinate,
    RandomEffectCoordinate,
)
from photon_ml_tpu.compile.stats import module_name
from photon_ml_tpu.data.game import (
    RandomEffectDataConfig,
    build_fixed_effect_batch,
    build_random_effect_dataset,
)
from photon_ml_tpu.ops import losses
from photon_ml_tpu.ops.features import DenseFeatures, SparseFeatures
from photon_ml_tpu.ops.normalization import NormalizationContext
from photon_ml_tpu.ops.objective import GLMBatch
from photon_ml_tpu.ops.regularization import RegularizationContext
from photon_ml_tpu.optim.common import OptimizerConfig
from photon_ml_tpu.optim.problem import GLMOptimizationProblem
from photon_ml_tpu.optim.scheduler import SolveSchedule, compacted_solve
from photon_ml_tpu.types import OptimizerType, TaskType

from game_test_utils import make_glmix_data
from trace_utils import parents, trace_modules, trace_spans, traced

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOGISTIC = TaskType.LOGISTIC_REGRESSION


def lowered_text(fn, *args):
    return jax.jit(fn).lower(*args).as_text(debug_info=True)


def scopes_in(text):
    return set(re.findall(r"pml\.[a-z_.]+", text))


# -- device scopes ----------------------------------------------------------


def _glm_batch(rng, sparse, n=32):
    d, k = 16, 4
    labels = jnp.asarray(rng.integers(0, 2, n), jnp.float32)
    if sparse:
        feats = SparseFeatures(
            jnp.asarray(rng.integers(0, d, (n, k)), jnp.int32),
            jnp.asarray(rng.normal(size=(n, k)), jnp.float32), d)
    else:
        feats = DenseFeatures(jnp.asarray(rng.normal(size=(n, d)), jnp.float32))
    return GLMBatch.create(feats, labels)


GLM_PATHS = {
    # the benchmark cell's path: sparse features, L-BFGS
    "sparse-lbfgs": (True, OptimizerType.LBFGS, False, {
        "pml.features.matvec", "pml.features.rmatvec",
        "pml.objective.value_and_grad", "pml.lbfgs.direction",
        "pml.lbfgs.line_search", "pml.lbfgs.pair_update"}),
    "dense-tron-variance": (False, OptimizerType.TRON, True, {
        "pml.features.matvec", "pml.features.rmatvec",
        "pml.features.sq_rmatvec", "pml.objective.value_and_grad",
        "pml.objective.hvp", "pml.objective.hessian_diagonal", "pml.tron.cg"}),
    # the dense cell's path on the chip: the one-pass kernel in the place of
    # the two products, under a scope of its own inside the evaluation's
    "dense-lbfgs-one-pass": (False, OptimizerType.LBFGS, False, {
        "pml.features.value_grad",
        "pml.objective.value_and_grad", "pml.lbfgs.direction",
        "pml.lbfgs.line_search", "pml.lbfgs.pair_update"}),
}


def _lowered_solve(batch, optimizer=OptimizerType.LBFGS, variance=False,
                   fused_block_rows=None):
    problem = GLMOptimizationProblem(
        LOGISTIC, optimizer, OptimizerConfig(max_iterations=3, tolerance=1e-6),
        RegularizationContext.l2(1.0), compute_variance=variance,
        fused_block_rows=fused_block_rows)
    return training._solve.lower(
        problem, batch, NormalizationContext.identity(),
        jnp.zeros((batch.dim,), jnp.float32), jnp.float32(1.0))


@pytest.mark.parametrize("path", sorted(GLM_PATHS))
def test_glm_solve_lowers_with_its_scopes(rng, path):
    sparse, optimizer, variance, want = GLM_PATHS[path]
    one_pass = "pml.features.value_grad" in want
    # 256 rows of 16 features are held column-major: two 128-row blocks
    batch = _glm_batch(rng, sparse, n=256 if one_pass else 32)
    text = _lowered_solve(
        batch, optimizer, variance, 128 if one_pass else None
    ).as_text(debug_info=True)
    assert scopes_in(text) == want
    assert "module @jit__solve" in text  # the name fe_solve_roofline reads
    # the kernels sit inside the objective's scope, so a kernel swap keeps it
    kernel = "value_grad" if one_pass else "matvec"
    assert f"pml.objective.value_and_grad/pml.features.{kernel}" in text
    if one_pass:
        assert "pallas_call" in text
    elif not sparse:
        assert "pml.tron.cg/while/body/pml.objective.hvp/pml.features.rmatvec" in text


def test_blocked_solve_lowers_with_the_row_block_scope(rng, monkeypatch):
    """A sparse batch over the target walks its rows in blocks: the scan's
    step is ``pml.objective.row_block`` with both feature kernels under it,
    inside the evaluation's scope, and the program keeps its name."""
    from photon_ml_tpu.ops import objective

    monkeypatch.setattr(objective, "ROW_BLOCK_NNZ", 8 * 4)  # (32, 4): 4 blocks
    training._solve.clear_cache()
    lowered = _lowered_solve(_glm_batch(rng, sparse=True))
    training._solve.clear_cache()
    text = lowered.as_text(debug_info=True)
    assert scopes_in(text) == GLM_PATHS["sparse-lbfgs"][3] | {
        "pml.objective.row_block"}
    assert "module @jit__solve" in text
    # a scan's step is lowered as a function of its own, so the whole path
    # of an operation is in the compiled program's op_name, not in the text
    paths = set(re.findall(r'op_name="([^"]*)"', lowered.compile().as_text()))
    # (a reduction's or a scatter's combiner keeps a path of its own)
    kernels = {p for p in paths
               if p.startswith("jit(_solve)/") and "/pml.features." in p}
    block = (r"/pml\.objective\.value_and_grad/while/body/(closed_call/)?"
             r"pml\.objective\.row_block/pml\.features\.(matvec|rmatvec)/")
    assert kernels and all(re.search(block, p) for p in kernels), kernels
    assert {m for p in kernels for m in re.findall(r"features\.(\w+)", p)} == {
        "matvec", "rmatvec"}
    # the evaluation before the loop and the line search's both block
    assert any("pml.lbfgs.line_search" in p for p in kernels)
    assert any("pml.lbfgs.line_search" not in p for p in kernels)


def test_objective_value_has_its_scope(rng):
    problem = GLMOptimizationProblem(
        LOGISTIC, OptimizerType.LBFGS, OptimizerConfig.lbfgs_default(),
        RegularizationContext.l2(1.0))
    batch = _glm_batch(rng, sparse=True)
    text = lowered_text(
        lambda w: problem.objective.value(
            w, batch, NormalizationContext.identity(), 1.0),
        jnp.zeros((batch.dim,), jnp.float32))
    assert scopes_in(text) == {"pml.objective.value", "pml.features.matvec"}


@pytest.fixture(scope="module")
def glmix():
    data, _ = make_glmix_data(
        np.random.default_rng(5), num_users=12, rows_per_user_range=(4, 12),
        d_fixed=4, d_random=3)
    return data


def build_coordinates(data, schedule=None):
    fixed = FixedEffectCoordinate(
        build_fixed_effect_batch(data, "global", dense=True),
        GLMOptimizationProblem(
            LOGISTIC, OptimizerType.LBFGS,
            OptimizerConfig(max_iterations=5, tolerance=1e-6),
            RegularizationContext.l2(1e-2)))
    random = RandomEffectCoordinate(
        build_random_effect_dataset(
            data, RandomEffectDataConfig("userId", "per_user")),
        LOGISTIC, OptimizerType.LBFGS,
        OptimizerConfig(max_iterations=5, tolerance=1e-6),
        RegularizationContext.l2(1e-1), solve_schedule=schedule)
    return {"global": fixed, "per-user": random}


@pytest.mark.parametrize("coordinate,method,want", [
    ("global", "update", {"pml.fe.solve", "pml.objective.value_and_grad",
                          "pml.features.matvec", "pml.features.rmatvec",
                          "pml.lbfgs.direction", "pml.lbfgs.line_search",
                          "pml.lbfgs.pair_update"}),
    ("global", "score", {"pml.fe.score", "pml.features.matvec"}),
    ("per-user", "update", {"pml.re.lane_solve", "pml.re.value_and_grad",
                            "pml.re.offsets", "pml.objective.value_and_grad",
                            "pml.features.matvec", "pml.features.rmatvec",
                            "pml.lbfgs.direction", "pml.lbfgs.line_search",
                            "pml.lbfgs.pair_update"}),
    ("per-user", "score", {"pml.re.score"}),
])
def test_coordinate_programs_lower_with_their_scopes(glmix, coordinate, method, want):
    coord = build_coordinates(glmix)[coordinate]
    w = coord.initial_coefficients()
    if method == "update":
        text = lowered_text(
            lambda off, w0: coord.update(off, w0)[0],
            jnp.zeros((glmix.num_rows,), jnp.float32), w)
    else:
        text = lowered_text(coord.score, w)
    assert scopes_in(text) == want
    if (coordinate, method) == ("per-user", "update"):
        # a transform wraps the scope it maps over, and keeps the name
        assert "vmap(pml.re.lane_solve)/while/body/pml.lbfgs.line_search" in text


# -- program names and host spans, from CPU traces --------------------------


def _loss_fn(data):
    loss = losses.for_task(LOGISTIC)
    labels, weights = jnp.asarray(data.response), jnp.asarray(data.weight)
    return lambda total: jnp.sum(weights * loss.loss(total, labels))


def _descent(data, mode):
    coords = build_coordinates(
        data, SolveSchedule(chunk_size=2) if mode == "scheduled" else None)
    cd = CoordinateDescent(coords, _loss_fn(data), fused_cycle=mode == "fused")
    if mode == "grid":
        lam = {"global": jnp.asarray([1e-2]), "per-user": jnp.asarray([1e-1])}
        return cd.run_grid(lam, num_iterations=2, num_rows=data.num_rows)
    return cd.run(num_iterations=2, num_rows=data.num_rows)


def _lane_problem(lanes=12, rows=6, dim=3):
    rng = np.random.default_rng(3)
    x = rng.normal(size=(lanes, rows, dim)).astype(np.float32)
    x[:2] *= np.geomspace(1.0, 32.0, dim).astype(np.float32)  # two slow lanes
    y = (rng.random((lanes, rows)) > 0.5).astype(np.float32)
    data = tuple(jnp.asarray(a) for a in (
        x, y, np.zeros((lanes, rows), np.float32),
        np.ones((lanes, rows), np.float32)))
    return data, jnp.zeros((lanes, dim), jnp.float32)


def _compacted(loop):
    data, w0 = _lane_problem()
    return compacted_solve(
        data, w0, task=LOGISTIC, optimizer=OptimizerType.LBFGS,
        optimizer_config=OptimizerConfig(max_iterations=30, tolerance=1e-9),
        regularization=RegularizationContext.l2(0.5),
        schedule=SolveSchedule(chunk_size=2, loop=loop), label="tiny")


RUNS = {
    "per-update": lambda data: _descent(data, "per-update"),
    "scheduled": lambda data: _descent(data, "scheduled"),
    "fused": lambda data: _descent(data, "fused"),
    "grid": lambda data: _descent(data, "grid"),
    "sched-host": lambda data: _compacted("host"),
    "sched-device": lambda data: _compacted("device"),
}


@pytest.fixture(scope="module")
def traces(glmix, tmp_path_factory):
    """Each tiny run once, warmed up and then under the profiler: name of
    the run -> (its spans, the XLA modules that ran)."""
    out = {}
    for name, run in RUNS.items():
        run(glmix)
        for _ in range(3):
            trace_dir = tmp_path_factory.mktemp(name)
            with traced(trace_dir):
                run(glmix)
            out[name] = (trace_spans(trace_dir), trace_modules(trace_dir))
            if out[name][1]:
                break  # on a loaded machine a trace now and then holds the
                # spans and none of the CPU backend's operation events
    return out


SITES = [
    ("per-update", "cd.update[global]"), ("per-update", "cd.update[per-user]"),
    ("per-update", "cd.score[global]"), ("per-update", "cd.score[per-user]"),
    ("fused", "cd.fused_cycle"), ("grid", "cd.grid_cycle"),
    ("sched-host", "scheduler.init"), ("sched-host", "scheduler.chunk"),
    ("sched-host", "scheduler.compact"), ("sched-host", "scheduler.scatter"),
    ("sched-host", "scheduler.counts"), ("sched-device", "scheduler.rung"),
]


@pytest.mark.parametrize("run,site", SITES, ids=[s for _, s in SITES])
def test_instrumented_site_names_its_module(traces, run, site):
    assert "jit_" + module_name(site) in traces[run][1]


@pytest.mark.parametrize("run", ["per-update", "fused", "grid", "scheduled"])
def test_descent_launches_no_anonymous_program(traces, run):
    modules = traces[run][1]
    assert modules and not [m for m in modules if "lambda" in m or "impl" in m]


def test_site_names():
    assert module_name("cd.update[per-user]") == "cd_update_per_user"
    assert module_name("scheduler.rung") == "scheduler_rung"


def test_roofline_patterns_find_the_descent_programs_by_name():
    """The metric files PR 24 shipped try the sites' names first; they match
    now, so the ``nth`` fallback that guessed by order is never reached."""
    for metric, site in (("fe_solve_roofline", "cd.update[global]"),
                         ("re_solve_roofline", "cd.update[per-user]")):
        with open(os.path.join(ROOT, "benchmark", "metrics", metric + ".json")) as f:
            first = json.load(f)["patterns"][0]
        assert "nth" not in first
        assert re.search(first["match"], "jit_" + module_name(site))
        other = "cd.update[per-user]" if "global" in site else "cd.update[global]"
        assert not re.search(first["match"], "jit_" + module_name(other))


NESTING = {
    # run -> {span: the span that holds it}
    "per-update": {
        "pml.cd.run": None, "pml.cd.iteration": "pml.cd.run",
        "pml.cd.update": "pml.cd.iteration", "pml.cd.score": "pml.cd.iteration",
        "pml.cd.objective": "pml.cd.iteration", "pml.cd.drain": "pml.cd.run"},
    "scheduled": {
        "pml.sched.solve": "pml.cd.update", "pml.sched.chunk": "pml.sched.solve",
        "pml.sched.sync": "pml.sched.solve"},
    "fused": {"pml.cd.iteration": "pml.cd.run", "pml.cd.cycle": "pml.cd.iteration"},
    "grid": {"pml.cd.iteration": None, "pml.cd.cycle": "pml.cd.iteration"},
    "sched-host": {
        "pml.sched.solve": None, "pml.sched.chunk": "pml.sched.solve",
        "pml.sched.sync": "pml.sched.solve", "pml.sched.gather": "pml.sched.solve",
        "pml.sched.scatter": "pml.sched.solve"},
    "sched-device": {"pml.sched.solve": None, "pml.rung.step": "pml.sched.solve",
                     "pml.rung.sync": "pml.sched.solve"},
}


@pytest.mark.parametrize("run", sorted(NESTING))
def test_spans_nest_as_stated(traces, run):
    spans = traces[run][0]
    held_by = {}
    for span, parent in zip(spans, parents(spans)):
        held_by.setdefault(span.name, set()).add(parent)
    for name, parent in NESTING[run].items():
        assert held_by.get(name) == {parent}, (name, held_by.get(name))
    assert len({s.thread for s in spans}) == 1  # all on the dispatching thread


def test_span_metadata(traces):
    spans = traces["scheduled"][0]
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s.meta)
    assert [m["iteration"] for m in by_name["pml.cd.iteration"]] == ["0", "1"]
    assert [m["coordinate"] for m in by_name["pml.cd.update"]] == [
        "global", "per-user"] * 2
    assert by_name["pml.sched.solve"][0] == {
        "label": "re_solve", "lanes": "12", "loop": "host"}
    by_name = {}
    for s in traces["sched-host"][0]:
        by_name.setdefault(s.name, []).append(s.meta)
    chunks = by_name["pml.sched.chunk"]
    assert chunks[0] == {"limit": "2", "lanes": "12", "active": "12"}
    # compaction shrank the batch to a rung of the ladder
    assert by_name["pml.sched.gather"][0]["lanes"] == "8" == chunks[-1]["lanes"]
    assert all(set(m) == {"lanes", "active"} for m in by_name["pml.sched.scatter"])
    rung = [s.meta for s in traces["sched-device"][0] if s.name == "pml.rung.step"]
    assert rung[0] == {"rung": "12", "limit": "0"}


def test_validation_guard_and_checkpoint_spans(glmix, tmp_path):
    from photon_ml_tpu.checkpoint import CoordinateDescentCheckpointer
    from photon_ml_tpu.evaluation.evaluators import EvaluatorType, evaluator_for
    from photon_ml_tpu.resilience.guards import DivergenceGuard

    coords = build_coordinates(glmix)
    labels = jnp.asarray(glmix.response)
    cd = CoordinateDescent(
        coords, _loss_fn(glmix),
        validation_scorer=lambda params: sum(
            coords[n].score(params[n]) for n in coords),
        validation_evaluators={"auc": (evaluator_for(EvaluatorType.AUC), {
            "labels": labels, "weights": jnp.ones_like(labels)})},
        divergence_guard=DivergenceGuard())
    ck = CoordinateDescentCheckpointer(str(tmp_path / "ck"), save_every=1)
    with traced(tmp_path / "trace"):
        cd.run(num_iterations=1, num_rows=glmix.num_rows, checkpointer=ck)
    spans = trace_spans(tmp_path / "trace")
    held = dict(zip((s.name for s in spans), parents(spans)))
    assert held["pml.cd.validate"] == "pml.cd.iteration"
    assert held["pml.cd.guard"] == "pml.cd.iteration"
    assert held["pml.cd.checkpoint"] == "pml.cd.iteration"
    drains = {p for s, p in zip(spans, parents(spans)) if s.name == "pml.cd.drain"}
    assert drains == {"pml.cd.checkpoint", "pml.cd.run"}  # the last ends the run
    steps = [s.meta["step"] for s in spans if s.name == "pml.cd.checkpoint"]
    assert steps == ["1", "2"]


def test_glm_grid_spans(rng, tmp_path):
    problem = GLMOptimizationProblem(
        LOGISTIC, OptimizerType.LBFGS,
        OptimizerConfig(max_iterations=3, tolerance=1e-6),
        RegularizationContext.l2(1.0))
    batch = _glm_batch(rng, sparse=True)
    norm = NormalizationContext.identity()
    training.train_glm_grid(problem, batch, norm, [1.0, 0.1])
    with traced(tmp_path):
        training.train_glm_grid(problem, batch, norm, [1.0, 0.1])
    spans = trace_spans(tmp_path)
    assert [(s.name, p) for s, p in zip(spans, parents(spans))] == [
        ("pml.glm.grid", None), ("pml.glm.solve", "pml.glm.grid"),
        ("pml.glm.solve", "pml.glm.grid")]
    assert spans[0].meta == {"lambdas": "2"}
    assert [s.meta["reg_weight"] for s in spans[1:]] == ["1.0", "0.1"]


def test_collect_timings_is_gone(glmix):
    with pytest.raises(TypeError):
        CoordinateDescent(build_coordinates(glmix), _loss_fn(glmix),
                          collect_timings=True)
