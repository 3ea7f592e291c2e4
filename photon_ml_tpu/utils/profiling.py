"""Native profiler hooks (SURVEY.md §5.1 designed upgrade).

The reference's tracing is wall-clock Timers + per-iteration state
trackers (util/Timer.scala:32-235, OptimizationStatesTracker.scala:31-100)
— both reproduced here (utils/timer.py, optim/common.py histories). On TPU
the missing piece is a DEVICE-side trace: set

    PHOTON_ML_TPU_PROFILE=/path/to/tracedir

and every CLI driver wraps its train stage in a ``jax.profiler`` trace
(viewable in XProf/TensorBoard, or as a table of seconds per scope and idle
seconds per host span with ``python -m benchmark.trace_scopes <tracedir>``).

One vocabulary of names lands in that trace, on its one clock (PERF.md has
the table of them):

  * device scopes are ``jax.named_scope("pml.<layer>.<what>")`` where the
    work is traced — HLO metadata only, nothing at run time;
  * host spans are :func:`span`, a ``TraceAnnotation``: the name is a
    constant and whatever varies (coordinate, iteration, lanes, ...) is
    keyword metadata, which the profiler formats only while a trace is on.
"""

from __future__ import annotations

import contextlib
import os
from typing import Iterator, Optional

PROFILE_ENV = "PHOTON_ML_TPU_PROFILE"


def profile_dir() -> Optional[str]:
    return os.environ.get(PROFILE_ENV) or None


@contextlib.contextmanager
def maybe_trace(stage: str) -> Iterator[None]:
    """Device trace of ``stage`` into $PHOTON_ML_TPU_PROFILE/<stage>/ when
    the env var is set; otherwise a no-op."""
    base = profile_dir()
    if not base:
        yield
        return
    import jax

    out = os.path.join(base, stage)
    os.makedirs(out, exist_ok=True)
    # the spans say what the host does; the Python tracer would add every
    # call to the trace and seconds to the run
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def span(name: str, **metadata):
    """Host span ``name`` on the calling thread, with ``metadata`` beside it
    in the trace. With no trace running it is one enter/exit that builds no
    string: the metadata values are never formatted."""
    import jax

    return jax.profiler.TraceAnnotation(name, **metadata)
