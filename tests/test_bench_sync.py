"""Drift gate: bench.py SECTION_ORDER, the _run_sections dispatch, and
test_bench_cli's pinned expected list must stay in sync AUTOMATICALLY.
Every PR so far hand-edited these surfaces when adding a section; from now
on drift is a test failure, not a review catch.

Pure AST — imports neither bench.py nor jax, so it runs anywhere (same
contract as bench --list-sections)."""

import ast
import os

BENCH = os.path.join(os.path.dirname(__file__), os.pardir, "bench.py")
CLI_TEST = os.path.join(os.path.dirname(__file__), "test_bench_cli.py")


def _bench_tree():
    with open(BENCH) as f:
        return ast.parse(f.read())


def _top_level_assign(tree, name):
    for node in tree.body:
        if isinstance(node, ast.Assign) and any(
            isinstance(t, ast.Name) and t.id == name for t in node.targets
        ):
            return node.value
    raise AssertionError(f"bench.py no longer defines {name} at top level")


def _section_order(tree):
    value = _top_level_assign(tree, "SECTION_ORDER")
    assert isinstance(value, (ast.Tuple, ast.List)), (
        "SECTION_ORDER must stay a literal tuple (the --list-sections "
        "no-jax contract parses it, and so does this gate)"
    )
    return [ast.literal_eval(e) for e in value.elts]


def test_dispatch_covers_every_section():
    """Every SECTION_ORDER name must appear as a string constant inside
    _run_sections (the elif dispatch) — a section listed but not
    dispatchable silently no-ops."""
    tree = _bench_tree()
    order = _section_order(tree)
    run_sections = next(
        (n for n in tree.body
         if isinstance(n, ast.FunctionDef) and n.name == "_run_sections"),
        None,
    )
    assert run_sections is not None, "bench.py lost _run_sections"
    consts = {
        n.value for n in ast.walk(run_sections)
        if isinstance(n, ast.Constant) and isinstance(n.value, str)
    }
    missing = [s for s in order if s not in consts]
    assert not missing, (
        f"sections {missing} are in SECTION_ORDER but never dispatched in "
        "_run_sections"
    )


def test_host_only_sections_are_sections():
    tree = _bench_tree()
    order = _section_order(tree)
    host_only = ast.literal_eval(_top_level_assign(tree, "HOST_ONLY_SECTIONS"))
    stale = sorted(set(host_only) - set(order))
    assert not stale, f"HOST_ONLY_SECTIONS names unknown sections {stale}"


def test_cli_test_expected_list_matches_section_order():
    """The pinned list in test_bench_cli.test_list_sections_enumerates_all_
    sections must equal SECTION_ORDER — the historical three-surface
    hand-edit, now enforced."""
    order = _section_order(_bench_tree())
    with open(CLI_TEST) as f:
        cli_tree = ast.parse(f.read())
    fn = next(
        (n for n in cli_tree.body
         if isinstance(n, ast.FunctionDef)
         and n.name == "test_list_sections_enumerates_all_sections"),
        None,
    )
    assert fn is not None, (
        "test_bench_cli lost test_list_sections_enumerates_all_sections"
    )
    lists = [
        ast.literal_eval(n)
        for n in ast.walk(fn)
        if isinstance(n, ast.List)
        and all(isinstance(e, ast.Constant) for e in n.elts)
    ]
    expected = next((l for l in lists if len(l) > 3), None)
    assert expected is not None, (
        "could not find the expected-sections list literal in "
        "test_bench_cli — keep it a plain list literal so this gate can "
        "parse it"
    )
    assert expected == order, (
        "test_bench_cli's expected section list drifted from bench.py "
        f"SECTION_ORDER:\n  bench: {order}\n  test:  {expected}"
    )


# ---------------------------------------------------------------------------
# plan_auto lockstep: the cost-planner section, its banked capture, and
# compile/cost.py's constants must agree (same pure-AST/JSON contract —
# no bench or jax import)
# ---------------------------------------------------------------------------

import json

COST = os.path.join(
    os.path.dirname(__file__), os.pardir,
    "photon_ml_tpu", "compile", "cost.py",
)
CAPTURE = os.path.join(
    os.path.dirname(__file__), os.pardir, "docs", "PLAN_AUTO_r18.json"
)


def _plan_auto_fn(tree):
    fn = next(
        (n for n in tree.body
         if isinstance(n, ast.FunctionDef) and n.name == "_bench_plan_auto"),
        None,
    )
    assert fn is not None, "bench.py lost _bench_plan_auto"
    return fn


def _fn_const(fn, name):
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign) and any(
            isinstance(t, ast.Name) and t.id == name for t in node.targets
        ):
            return ast.literal_eval(node.value)
    raise AssertionError(f"_bench_plan_auto no longer declares {name}")


def test_plan_auto_is_a_section():
    order = _section_order(_bench_tree())
    assert "plan_auto" in order, (
        "plan_auto left SECTION_ORDER — the planner bench gate is gone"
    )


def test_plan_auto_capture_satisfies_declared_gates():
    """docs/PLAN_AUTO_r18.json is the banked evidence for the planner's
    acceptance gates; it must still satisfy the bound _bench_plan_auto
    declares TODAY (a loosened bound with a stale capture, or vice versa,
    is drift)."""
    bound = _fn_const(_plan_auto_fn(_bench_tree()), "PLAN_AUTO_BOUND")
    with open(CAPTURE) as f:
        capture = json.load(f)
    plan = capture["extra"]["plan_auto"]
    assert plan["bound"] == bound, (
        f"banked capture bound {plan['bound']} != bench.py's declared "
        f"PLAN_AUTO_BOUND {bound} — re-bank docs/PLAN_AUTO_r18.json"
    )
    shapes = set(plan["workloads"])
    assert {"skewed", "uniform"} <= shapes, (
        f"capture covers {sorted(shapes)}; the acceptance gate needs both "
        "skewed and uniform"
    )
    for shape, w in plan["workloads"].items():
        best = min(w["arms"].values())
        worst = max(w["arms"].values())
        assert w["warm_cost"] <= bound * best, (
            f"{shape}: banked warm cost {w['warm_cost']} outside "
            f"{bound}x of best arm {best}"
        )
        assert w["cold_cost"] < worst, (
            f"{shape}: banked cold cost {w['cold_cost']} does not beat "
            f"the worst arm {worst}"
        )
    assert plan["revised"], (
        "banked capture shows no warm-rerun decision revision — the "
        "feedback-loop acceptance gate has no evidence"
    )


def test_plan_auto_pause_tariff_matches_cost_model():
    """The capture's cost unit embeds CHUNK_PAUSE_COST; cost.py changing
    the tariff invalidates the banked numbers."""
    with open(COST) as f:
        cost_tree = ast.parse(f.read())
    tariff = ast.literal_eval(_top_level_assign(cost_tree, "CHUNK_PAUSE_COST"))
    with open(CAPTURE) as f:
        unit = json.load(f)["extra"]["plan_auto"]["cost_unit"]
    assert f"{tariff:.0f}/chunk-dispatch" in unit, (
        f"compile/cost.py CHUNK_PAUSE_COST={tariff} no longer matches the "
        f"banked capture's cost unit ({unit!r}) — re-bank "
        "docs/PLAN_AUTO_r18.json"
    )


# ---------------------------------------------------------------------------
# ops/fused_glm.py's names as bench.py and tools/ use them: tier-1 imports
# neither, so a name deleted from the module would rot there unseen
# ---------------------------------------------------------------------------

import glob

ROOT = os.path.join(os.path.dirname(__file__), os.pardir)
FUSED_GLM = os.path.join(ROOT, "photon_ml_tpu", "ops", "fused_glm.py")


def _fused_glm_names_used(tree):
    """Names a file takes from ``ops/fused_glm``: attributes of anything
    called ``fused_glm`` and the names of a ``from ... fused_glm import``."""
    used = set()
    for node in ast.walk(tree):
        if (isinstance(node, ast.Attribute) and isinstance(node.value, ast.Name)
                and node.value.id == "fused_glm"):
            used.add(node.attr)
        elif isinstance(node, ast.ImportFrom) and (node.module or "").endswith("ops.fused_glm"):
            used.update(alias.name for alias in node.names)
    return used


def test_fused_glm_names_in_bench_and_tools_exist():
    with open(FUSED_GLM) as f:
        module = ast.parse(f.read())
    defined = {n.name for n in module.body if isinstance(n, (ast.FunctionDef, ast.ClassDef))}
    defined |= {t.id for n in module.body if isinstance(n, ast.Assign)
                for t in n.targets if isinstance(t, ast.Name)}
    users = [BENCH] + sorted(glob.glob(os.path.join(ROOT, "tools", "**", "*.py"), recursive=True))
    used = {}
    for path in users:
        with open(path) as f:
            for name in _fused_glm_names_used(ast.parse(f.read())):
                used.setdefault(name, os.path.relpath(path, ROOT))
    assert "select_fused_block_rows" in used, "bench.py's dense section lost the selection"
    gone = {name: path for name, path in used.items() if name not in defined}
    assert not gone, f"names ops/fused_glm.py no longer defines: {gone}"
