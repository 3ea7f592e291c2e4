"""env-reads: no new ``os.environ`` reads outside the single resolver.

Motivating incident (PR 18): tuning knobs had scattered as ad-hoc env
reads across the tree (``PHOTON_ML_TPU_DTYPE`` in types.py,
``PHOTON_ML_TPU_SPARSE_TRANSPOSE`` in ops/features.py, ``PHOTON_DONATE``
in compile/__init__.py, ``PHOTON_SHAPE_LADDER`` in compile/canonical.py)
— invisible to the ExecutionPlan decision trail and to the cost-based
planner, which can only audit knobs it can SEE. PR 18 funnels every read
through ``compile/overrides.py`` (:func:`env_read`, the ONE gate) and
this rule holds that line: a new ``os.environ.get`` / ``os.environ[...]``
/ ``os.getenv`` inside ``photon_ml_tpu/`` is flagged unless the site is
the resolver itself or an allowlisted legacy resolver (whose stale
entries fail the lint, the jit-sites discipline).

Scope is the ``photon_ml_tpu`` package only: ``tools/`` and ``bench.py``
orchestrate subprocess environments by design. Env WRITES are never
flagged (benches and tests pin child environments legitimately).

Escape: ``# lint: env-reads — <why>`` or an ALLOWLIST entry.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterator, Optional, Set, Tuple

from tools.photon_lint.engine import RawFinding, Rule, ScanFile

# Legacy per-module resolvers that predate the single gate, keyed
# "relpath:qualname" with why the read stays local for now. A site
# migrated onto compile/overrides.py must be DELETED from here — stale
# entries fail the lint.
ALLOWLIST = {
    # THE gate itself
    "photon_ml_tpu/compile/overrides.py:env_read": "the single resolver",
    # policy resolvers consumed once by ExecutionPlan.resolve (the env
    # read is already plan-visible through the resolved policy object)
    "photon_ml_tpu/optim/convergence.py:resolve_adaptive": "plan-visible via resolve()",
    "photon_ml_tpu/ops/fused_sparse.py:resolve_sparse_kernel": "plan-visible via resolve()",
    "photon_ml_tpu/io/pipeline.py:resolve_depth": "plan-visible via resolve()",
    # the one-pass dense kernel's off/auto/force switch: a debug switch of
    # the kernel's selection, not a training-policy knob
    "photon_ml_tpu/ops/fused_glm.py:select_fused_block_rows": "kernel selection debug switch",
    # infrastructure knobs with no bearing on the training plan
    "photon_ml_tpu/parallel/multihost.py:resolve_barrier_timeout": "infra timeout, not a plan knob",
    "photon_ml_tpu/io/native_build.py:native_enabled": "build-time toggle",
    "photon_ml_tpu/io/native_build.py:build_cached": "XDG cache dir",
    # fault/preemption/retry injection plans: test harness controls that
    # must stay readable without importing the compile layer
    "photon_ml_tpu/resilience/faults.py:active_plan": "fault-injection harness",
    "photon_ml_tpu/resilience/preemption.py:_active_plan": "preemption-injection harness",
    "photon_ml_tpu/resilience/retry.py:_env_float": "retry tuning, harness-level",
    "photon_ml_tpu/utils/profiling.py:profile_dir": "profiling output dir",
}


def _env_read_target(node: ast.AST) -> Optional[str]:
    """The display name of an env READ at ``node``, or None.

    Matches ``os.environ.get(...)`` / ``<x>.environ.get(...)``,
    ``os.getenv(...)``, and ``os.environ[...]`` in Load context (writes,
    ``pop``, and ``del`` never match — pinning a child environment is
    legitimate everywhere)."""
    if isinstance(node, ast.Call):
        f = node.func
        if isinstance(f, ast.Attribute):
            if f.attr == "getenv":
                return "os.getenv"
            if (
                f.attr == "get"
                and isinstance(f.value, ast.Attribute)
                and f.value.attr == "environ"
            ):
                return "os.environ.get"
            if (
                f.attr == "get"
                and isinstance(f.value, ast.Name)
                and f.value.id == "environ"
            ):
                return "environ.get"
    if (
        isinstance(node, ast.Subscript)
        and isinstance(node.ctx, ast.Load)
    ):
        v = node.value
        if isinstance(v, ast.Attribute) and v.attr == "environ":
            return "os.environ[...]"
        if isinstance(v, ast.Name) and v.id == "environ":
            return "environ[...]"
    return None


class EnvReadsRule(Rule):
    name = "env-reads"
    description = (
        "os.environ reads outside the single resolver "
        "(PR 18: compile/overrides.py is the one env gate)"
    )

    def __init__(self, root=None, allowlist: Optional[Dict[str, str]] = None):
        super().__init__(root)
        self.allowlist = ALLOWLIST if allowlist is None else allowlist
        self._live_sites: Set[str] = set()
        self._scanned: Set[str] = set()

    def scope(self, relpath: str) -> bool:
        return relpath.startswith("photon_ml_tpu/")

    def check(self, scan: ScanFile) -> Iterator[RawFinding]:
        self._scanned.add(scan.relpath)
        if "environ" not in scan.source and "getenv" not in scan.source:
            return
        quals = scan.qualnames
        for node in ast.walk(scan.tree):
            ref = _env_read_target(node)
            if ref is None:
                continue
            site = f"{scan.relpath}:{quals.get(id(node), '<module>')}"
            self._live_sites.add(site)
            if site in self.allowlist:
                continue
            yield (
                node.lineno,
                f"{ref} read at {site} — tuning env is resolved ONCE "
                "through photon_ml_tpu.compile.overrides (env_read / "
                "resolve_overrides) so the planner can see every knob; "
                "route the read through the resolver or add "
                "'# lint: env-reads — <why>' for a genuine harness knob",
            )

    def finalize(self, full_scope: bool) -> Iterator[Tuple[str, int, str]]:
        for key in sorted(self.allowlist):
            rel = key.split(":", 1)[0]
            if rel in self._scanned and key not in self._live_sites:
                yield (
                    rel, 0,
                    f"stale ALLOWLIST entry (no env read there anymore): {key}",
                )
