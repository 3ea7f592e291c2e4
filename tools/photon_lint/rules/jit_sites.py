"""jit-sites: no bare hot-path jit sites without donation/static intent.

Motivating incident (PR 3): the compile-once layer gives every hot-path
jit site telemetry (``instrumented_jit``), buffer donation, and deliberate
static annotations; bare ``jax.jit(fn)`` sites silently reintroduce
un-donated, un-measured executables. PR 8 extends coverage to
``jax.pjit`` / ``pjit`` and ``jax.named_call``-wrapped sites.

A site is flagged when a ``jax.jit`` / ``jax.pjit`` / ``pjit`` call (or
``functools.partial(...)`` / decorator form) passes NONE of
donate_argnums/donate_argnames/static_argnums/static_argnames, and when a
``jax.named_call`` wrapper is not directly inside an annotated jit-like or
``instrumented_jit`` call. Escapes: ``# jit-ok: <why>`` (legacy),
``# lint: jit-sites — <why>``, or an ALLOWLIST entry — whose stale
entries fail the lint.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Optional, Set, Tuple

from tools.photon_lint.engine import RawFinding, Rule, ScanFile

ANNOTATION_KWARGS = {
    "donate_argnums", "donate_argnames", "static_argnums", "static_argnames",
}

# Pre-compile-layer sites, keyed "relpath:qualname" with why donation /
# statics genuinely do not apply. A site moved onto instrumented_jit (or
# annotated in place) should be DELETED from here -- stale entries fail
# the lint.
ALLOWLIST = {
    # the wrapper that ADDS the annotations (its inner jax.jit forwards
    # whatever donate/static kwargs the caller passed)
    "photon_ml_tpu/compile/stats.py:instrumented_jit": "instrumented_jit internals",
    # scoring: coefficient/feature tensors are read-only and reused across
    # every scored batch -- nothing to donate
    "photon_ml_tpu/cli/game_scoring_driver.py:_get_re_gather": "read-only scoring gathers",
    "photon_ml_tpu/cli/game_scoring_driver.py:_get_factored_contrib": "read-only scoring gathers",
    "photon_ml_tpu/cli/game_scoring_driver.py:GameScoringDriver._score_device": "read-only scoring matvec",
    # multihost coordinate helpers: inputs are multihost-sharded slabs a
    # donation would tear; scores fold out-of-place by design
    "photon_ml_tpu/cli/game_multihost_driver.py:MultihostFixedEffectCoordinate.__init__": "sharded slabs reused per update",
    "photon_ml_tpu/cli/game_multihost_driver.py:MultihostFixedEffectCoordinate.score": "sharded slabs reused per update",
    # streaming FE margin kernel: w and the chunk are both read-only (the
    # chunk is reused by the pipelined H2D double-buffer)
    "photon_ml_tpu/algorithm/streaming_fixed_effect.py:StreamingFixedEffectCoordinate.__post_init__": "w + chunk read-only",
    # one-shot summarization / diagnostics passes (run once per driver)
    "photon_ml_tpu/optim/streaming.py:streaming_summarize.partial": "one-shot colStats pass",
    "photon_ml_tpu/bootstrap.py:bootstrap_train": "one-shot diagnostic solve",
    "photon_ml_tpu/diagnostics/independence.py:analyze": "one-shot O(n^2) census",
    # in-memory GLM training entry points: w0 is the caller's warm-start
    # array, explicitly reused across the lambda grid
    "photon_ml_tpu/training.py:train_glm_grid": "warm-start w0 reused across grid",
    "photon_ml_tpu/training.py:train_glm_grid_vmapped": "lane-stacked w0 reused across lanes",
    # the one-pass dense kernel
    "photon_ml_tpu/ops/fused_glm.py:_fused_fn_vpu.call": "the solve's dataset, read every evaluation",
    # parallel/: shard_map wrappers over mesh-sharded slabs reused across
    # updates (the slabs ARE the dataset; donating them would tear it)
    "photon_ml_tpu/parallel/perhost_ingest.py:PerHostRandomEffectSolver.update": "dataset slabs reused per update",
    "photon_ml_tpu/parallel/perhost_ingest.py:PerHostRandomEffectSolver.score": "dataset slabs reused",
    "photon_ml_tpu/parallel/perhost_ingest.py:PerHostBucketedRandomEffectSolver.update": "dataset slabs reused per update",
    "photon_ml_tpu/parallel/perhost_ingest.py:PerHostBucketedRandomEffectSolver.score": "dataset slabs reused",
    "photon_ml_tpu/parallel/shuffle.py:_collective_reduce": "one-shot ingest collective",
    "photon_ml_tpu/parallel/shuffle.py:exchange_rows": "one-shot ingest collective",
    "photon_ml_tpu/parallel/distributed.py:DistributedFixedEffectSolver._build": "dataset slabs reused per update",
    "photon_ml_tpu/parallel/distributed.py:DistributedRandomEffectSolver._build": "dataset slabs reused per update",
    "photon_ml_tpu/parallel/distributed.py:DistributedRandomEffectSolver.score": "dataset slabs reused",
    "photon_ml_tpu/parallel/distributed.py:DistributedFactoredRandomEffectCoordinate._build": "dataset slabs reused per update",
    "photon_ml_tpu/parallel/distributed.py:DistributedFactoredRandomEffectCoordinate.score": "dataset slabs reused",
    "photon_ml_tpu/parallel/perhost_factored.py:PerHostFactoredRandomEffectCoordinate.update": "dataset slabs reused per update",
    "photon_ml_tpu/parallel/perhost_factored.py:PerHostFactoredRandomEffectCoordinate.score": "dataset slabs reused",
    "photon_ml_tpu/parallel/perhost_factored.py:PerHostFactoredRandomEffectCoordinate.regularization_term": "tiny v-term psum",
    "photon_ml_tpu/parallel/perhost_factored.py:PerHostFactoredRandomEffectCoordinate.random_effect_coefficients": "read-only export",
}


def _display(node: ast.AST) -> str:
    """Source-ish name for a jit-like reference ('jax.jit', 'pjit', ...)."""
    if isinstance(node, ast.Attribute):
        base = node.value.id if isinstance(node.value, ast.Name) else "?"
        return f"{base}.{node.attr}"
    if isinstance(node, ast.Name):
        return node.id
    return "jit"


def _is_jit_like(node: ast.AST) -> bool:
    """``jax.jit`` / ``jax.pjit`` / bare ``pjit`` / ``<mod>.pjit``."""
    if isinstance(node, ast.Attribute):
        if node.attr == "jit" and isinstance(node.value, ast.Name) and node.value.id == "jax":
            return True
        return node.attr == "pjit"
    return isinstance(node, ast.Name) and node.id == "pjit"


def _is_named_call(node: ast.AST) -> bool:
    if isinstance(node, ast.Attribute):
        return node.attr == "named_call"
    return isinstance(node, ast.Name) and node.id == "named_call"


def _is_instrumented(node: ast.AST) -> bool:
    name = node.attr if isinstance(node, ast.Attribute) else getattr(node, "id", "")
    return name == "instrumented_jit"


def _annotated(call: ast.Call) -> bool:
    return any(kw.arg in ANNOTATION_KWARGS for kw in call.keywords)


def _partial_of(call: ast.Call, pred) -> bool:
    """``functools.partial(<pred-matching>, ...)``."""
    return (
        isinstance(call.func, ast.Attribute)
        and call.func.attr == "partial"
        and bool(call.args)
        and pred(call.args[0])
    )


class JitSitesRule(Rule):
    name = "jit-sites"
    description = (
        "bare jax.jit/pjit/named_call sites missing donation/static intent "
        "(PR 3: compile-once layer; use instrumented_jit)"
    )
    legacy_tag = "jit-ok:"

    def __init__(self, root=None, allowlist: Optional[Dict[str, str]] = None):
        super().__init__(root)
        self.allowlist = ALLOWLIST if allowlist is None else allowlist
        # rel:qualname of every jit-like site seen (annotated or not), and
        # the set of relpaths scanned — allowlist entries for scanned files
        # with no remaining site there are STALE and fail in finalize().
        self._live_sites: Set[str] = set()
        self._scanned: Set[str] = set()

    def check(self, scan: ScanFile) -> Iterator[RawFinding]:
        self._scanned.add(scan.relpath)
        # identifier probe ("jit" also covers pjit; named_call explicit)
        if "jit" not in scan.source and "named_call" not in scan.source:
            return
        quals = scan.qualnames
        # named_call wrappers sitting DIRECTLY inside a jit-like or
        # instrumented_jit call are that site's plumbing, not a bare site
        wrapped: Set[int] = set()
        for node in ast.walk(scan.tree):
            if isinstance(node, ast.Call) and (
                _is_jit_like(node.func) or _is_instrumented(node.func)
                or _partial_of(node, _is_jit_like)
            ):
                for arg in list(node.args) + [kw.value for kw in node.keywords]:
                    wrapped.add(id(arg))

        def site_of(node: ast.AST) -> str:
            return f"{scan.relpath}:{quals.get(id(node), '<module>')}"

        def message(kind: str, site: str) -> str:
            return (
                f"bare {kind} at {site} — hot-path sites go through "
                "photon_ml_tpu.compile.instrumented_jit (telemetry + "
                "donate_argnums); for a genuinely read-only site add "
                "'# jit-ok: <reason>' or an ALLOWLIST entry"
            )

        for node in ast.walk(scan.tree):
            # bare @jax.jit / @pjit / @jax.named_call decorator (no call,
            # so never annotated)
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    if not (_is_jit_like(dec) or _is_named_call(dec)):
                        continue
                    site = site_of(node)
                    self._live_sites.add(site)
                    if site in self.allowlist:
                        continue
                    yield (dec.lineno, message(f"@{_display(dec)}", site))
            if not isinstance(node, ast.Call):
                continue
            if _is_jit_like(node.func) or _partial_of(node, _is_jit_like):
                ref = node.func if _is_jit_like(node.func) else node.args[0]
                site = site_of(node)
                self._live_sites.add(site)
                if _annotated(node) or site in self.allowlist:
                    continue
                yield (node.lineno, message(_display(ref), site))
            elif _is_named_call(node.func) or _partial_of(node, _is_named_call):
                ref = node.func if _is_named_call(node.func) else node.args[0]
                site = site_of(node)
                self._live_sites.add(site)
                if id(node) in wrapped or site in self.allowlist:
                    continue
                yield (
                    node.lineno,
                    message(_display(ref), site)
                    + " (a named_call wrapper outside an annotated jit "
                    "still stages out an un-donated executable)",
                )

    def finalize(self, full_scope: bool) -> Iterator[Tuple[str, int, str]]:
        # stale allowlist entries are errors too: a migrated site must
        # shrink the list, or it silently stops protecting anything
        for key in sorted(self.allowlist):
            rel = key.split(":", 1)[0]
            if rel in self._scanned and key not in self._live_sites:
                yield (
                    rel, 0,
                    f"stale ALLOWLIST entry (no jit-like site there anymore): {key}",
                )
