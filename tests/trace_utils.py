"""Helpers for tests that run a piece of the program under the profiler on
the CPU and read the trace back: the host spans (``pml.*``
``TraceAnnotation``s with their metadata) and the XLA module names."""

import collections
import contextlib
import glob
import os

import jax

Span = collections.namedtuple("Span", "name start end meta thread")


@contextlib.contextmanager
def traced(trace_dir):
    """A profiler trace into ``trace_dir`` with the Python tracer off (with it
    on, a CPU trace takes tens of seconds)."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _events(trace_dir):
    files = sorted(glob.glob(os.path.join(
        str(trace_dir), "plugins", "profile", "*", "*.xplane.pb")))
    assert files, f"no .xplane.pb under {trace_dir}"
    data = jax.profiler.ProfileData.from_file(files[-1])
    for plane in data.planes:
        for thread, line in enumerate(plane.lines):
            for ev in line.events:
                yield (plane.name, thread), ev


def trace_spans(trace_dir):
    """The program's spans in the newest trace under ``trace_dir``, in order
    of start."""
    out = [
        Span(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
             {k: str(v) for k, v in ev.stats}, thread)
        for thread, ev in _events(trace_dir) if ev.name.startswith("pml.")
    ]
    return sorted(out, key=lambda s: (s.start, -s.end))


def trace_modules(trace_dir):
    """Names of the XLA modules that ran (the CPU backend's operation events
    carry ``hlo_module``)."""
    names = set()
    for _, ev in _events(trace_dir):
        module = dict(ev.stats).get("hlo_module")
        if module:
            names.add(str(module))
    return names


def parents(spans):
    """For each span, the name of the innermost span of the same thread that
    holds it (None at the top), in the order of ``spans``."""
    out = []
    for s in spans:
        holders = [p for p in spans if p is not s and p.thread == s.thread
                   and p.start <= s.start and s.end <= p.end]
        out.append(min(holders, key=lambda p: p.end - p.start).name
                   if holders else None)
    return out
