"""Compile telemetry: per-site trace counts + process-wide XLA cache stats.

The GLMix workload is thousands of repeated solves over near-identical
shapes; every avoidable retrace/recompile is host-side orchestration
overhead the steady-state loop should not pay (the Snap ML observation,
PAPERS.md). This module makes that overhead MEASURABLE:

  * :func:`instrumented_jit` — a ``jax.jit`` wrapper that counts, per named
    site, how many times the Python body was re-traced (a trace is the
    jit-cache-miss event: the wrapped body only runs under tracing), how
    many calls hit the already-compiled executable, and how many wall
    seconds the tracing calls took (trace + lower + compile, the full
    first-call penalty).
  * :class:`CompileStats` — the registry those counters live in, plus
    process-wide XLA persistent-cache hit/miss counts and backend-compile
    seconds harvested from ``jax.monitoring`` (version-gated: absent
    monitoring APIs degrade to trace-only telemetry, never an error).

Drivers log ``compile_stats.summary()`` at the end of a run; the
``bench.py compile_reuse`` section and the recompile-count tests assert on
``snapshot()``. A warm ``--persistent-cache`` run is "zero new XLA
compiles" exactly when ``xla_cache_misses`` stays 0.
"""

from __future__ import annotations

import dataclasses
import functools
import re
import threading
import time
from typing import Callable, Dict, Optional

import jax


@dataclasses.dataclass
class SiteStats:
    """Counters for one instrumented jit site."""

    calls: int = 0
    traces: int = 0
    compile_seconds: float = 0.0

    @property
    def cache_hits(self) -> int:
        return self.calls - self.traces


class CompileStats:
    """Process-wide compile-telemetry registry (thread-safe: prefetch
    threads and the main solve loop both dispatch jitted calls)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._sites: Dict[str, SiteStats] = {}
        # XLA persistent-cache counters (jax.monitoring, process-wide)
        self.xla_cache_hits = 0
        self.xla_cache_misses = 0
        self.backend_compile_seconds = 0.0
        self._listeners_installed = False

    # -- recording ----------------------------------------------------------
    def site(self, name: str) -> SiteStats:
        with self._lock:
            return self._sites.setdefault(name, SiteStats())

    def record_trace(self, name: str) -> None:
        with self._lock:
            self._sites.setdefault(name, SiteStats()).traces += 1

    def record_call(self, name: str, seconds: float, traced: bool) -> None:
        with self._lock:
            s = self._sites.setdefault(name, SiteStats())
            s.calls += 1
            if traced:
                s.compile_seconds += seconds

    # -- reading ------------------------------------------------------------
    def snapshot(self) -> Dict[str, dict]:
        """{site: {calls, traces, cache_hits, compile_seconds}} copy."""
        with self._lock:
            return {
                name: {
                    "calls": s.calls,
                    "traces": s.traces,
                    "cache_hits": s.cache_hits,
                    "compile_seconds": round(s.compile_seconds, 4),
                }
                for name, s in sorted(self._sites.items())
            }

    def traces_of(self, name: str) -> int:
        with self._lock:
            s = self._sites.get(name)
            return s.traces if s is not None else 0

    def total_traces(self) -> int:
        with self._lock:
            return sum(s.traces for s in self._sites.values())

    def reset(self) -> None:
        """Zero every counter (tests / bench arms). The monitoring
        listeners stay installed — they feed the fresh counters."""
        with self._lock:
            self._sites.clear()
            self.xla_cache_hits = 0
            self.xla_cache_misses = 0
            self.backend_compile_seconds = 0.0

    def summary(self) -> str:
        """One-line-per-site driver-log summary."""
        snap = self.snapshot()
        lines = [
            f"compile stats: {len(snap)} instrumented sites, "
            f"{sum(v['traces'] for v in snap.values())} traces / "
            f"{sum(v['calls'] for v in snap.values())} calls; "
            f"XLA cache {self.xla_cache_hits} hits / "
            f"{self.xla_cache_misses} misses (new compiles), "
            f"{self.backend_compile_seconds:.2f}s backend compile"
        ]
        for name, v in snap.items():
            lines.append(
                f"  {name}: {v['traces']} traces / {v['calls']} calls "
                f"({v['compile_seconds']:.2f}s in tracing calls)"
            )
        return "\n".join(lines)

    def watermark(self) -> "CompileWatermark":
        """Capture the current counters; the returned watermark reports how
        many NEW traces / XLA cache misses happened since. The zero-compile
        assertions (warm serving start, live model swap, warm resume) all
        phrase themselves as "no new compiles past this watermark"."""
        with self._lock:
            return CompileWatermark(
                self,
                sum(s.traces for s in self._sites.values()),
                self.xla_cache_misses,
            )

    # -- jax.monitoring bridge ----------------------------------------------
    def install_xla_listeners(self) -> bool:
        """Hook the XLA compilation-cache + compile-duration monitoring
        events (idempotent). Returns False when this jax has no monitoring
        API — telemetry then covers instrumented sites only."""
        if self._listeners_installed:
            return True
        try:
            from jax import monitoring
        except ImportError:
            return False

        def on_event(name: str, **kw) -> None:
            if name == "/jax/compilation_cache/cache_hits":
                with self._lock:
                    self.xla_cache_hits += 1
            elif name == "/jax/compilation_cache/cache_misses":
                with self._lock:
                    self.xla_cache_misses += 1

        def on_duration(name: str, secs: float, **kw) -> None:
            if name == "/jax/core/compile/backend_compile_duration":
                with self._lock:
                    self.backend_compile_seconds += secs

        try:
            monitoring.register_event_listener(on_event)
            monitoring.register_event_duration_secs_listener(on_duration)
        except (AttributeError, TypeError):
            return False  # older monitoring surface: trace-only telemetry
        self._listeners_installed = True
        return True


@dataclasses.dataclass(frozen=True)
class CompileWatermark:
    """A point-in-time snapshot of trace/XLA-miss counters (see
    :meth:`CompileStats.watermark`)."""

    stats: CompileStats
    traces0: int
    xla_misses0: int

    def new_traces(self) -> int:
        return self.stats.total_traces() - self.traces0

    def new_xla_misses(self) -> int:
        return self.stats.xla_cache_misses - self.xla_misses0

    def clean(self) -> bool:
        """True when nothing compiled since the watermark."""
        return self.new_traces() == 0 and self.new_xla_misses() == 0


#: THE process-wide registry every instrumented site reports into.
compile_stats = CompileStats()


def module_name(site: str) -> str:
    """The function name a site's program is jitted under (its XLA module is
    ``jit_<this>``)."""
    return re.sub(r"[^0-9A-Za-z]+", "_", site).strip("_")


def instrumented_jit(
    fn: Callable,
    site: Optional[str] = None,
    **jit_kwargs,
):
    """``jax.jit`` with per-site compile telemetry.

    The wrapped Python body only executes while jax is TRACING it, so a
    body execution == one jit-cache miss (a new shape/static signature at
    this site). Calls that skip the body hit the compiled executable.
    ``jit_kwargs`` pass through (``static_argnames``, ``donate_argnums``,
    ...), so instrumentation composes with donation.

    A ``site`` also names the XLA module: the jitted function's ``__name__``
    is the site with every run of other characters turned into ``_``
    (``cd.update[per-user]`` -> ``jit_cd_update_per_user``), so a profiler
    trace shows which program ran and not ``jit__lambda_`` or ``jit_impl``.
    """
    name = site or f"{fn.__module__}.{getattr(fn, '__qualname__', fn.__name__)}"

    def traced(*args, **kwargs):
        compile_stats.record_trace(name)
        return fn(*args, **kwargs)

    functools.update_wrapper(traced, fn)
    if site:
        traced.__name__ = traced.__qualname__ = module_name(site)
    jitted = jax.jit(traced, **jit_kwargs)

    @functools.wraps(fn)
    def call(*args, **kwargs):
        before = compile_stats.traces_of(name)
        t0 = time.perf_counter()
        out = jitted(*args, **kwargs)
        seconds = time.perf_counter() - t0
        compile_stats.record_call(
            name, seconds, traced=compile_stats.traces_of(name) != before
        )
        return out

    call._jitted = jitted  # the underlying PjitFunction (lower/inspect)
    call._site = name
    return call
