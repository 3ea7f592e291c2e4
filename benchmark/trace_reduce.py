"""From a profiler trace to numbers: device busy time, idle share, time and
launches per program, the operations that took most time, the longest gaps.

Two layers. :func:`reduce_events` is arithmetic over plain tuples, checked in
``benchmark/check.py`` against a list worked by hand. :func:`read_xplane` is
the one way from a trace directory to what the metrics' readers get: it takes
the tuples from :func:`benchmark.trace_scopes.read_devices`, the one parser of
an ``.xplane.pb`` (a TPU plane's ``XLA Modules`` line gives the program
launches, ``XLA Ops`` the operations with their scope paths; on the CPU
backend, the rehearsal where no number is kept, operations are the host's
events that carry an ``hlo_module`` and have no scope), and adds that module's
three keys (``scopes``, ``scope_runs``, ``host_gaps``).
"""

from __future__ import annotations

import functools
import re
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]  # name, start_s, duration_s

WINDOW_SPAN = "benchmark_window"


def union_seconds(intervals: Sequence[Tuple[float, float]]) -> float:
    """Length of the union of (start, end) intervals."""
    total, reach = 0.0, None
    for start, end in sorted(intervals):
        if reach is None or start > reach:
            total += end - start
            reach = end
        elif end > reach:
            total += end - reach
            reach = end
    return total


def self_seconds(events: Sequence[Event]) -> Dict[str, float]:
    """Per name, the time its events spent outside the events nested in them
    (one line of a device plane nests a loop's body inside the loop)."""
    out: Dict[str, float] = {}
    stack: List[List] = []  # [name, end, self]

    def close(until: float):
        while stack and stack[-1][1] <= until:
            name, _, own = stack.pop()
            out[name] = out.get(name, 0.0) + own

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return out


def reduce_events(ops: Sequence[Event], launches: Sequence[Event],
                  window: Optional[Tuple[float, float]] = None,
                  spans: Sequence[Event] = ()) -> dict:
    """One device's events to the numbers the metrics read. ``ops`` are the
    operations (busy time is the union of their intervals, or of the
    launches' where a trace has no operations; operations of one name are
    summed in ``device_ops``); ``launches`` the programs. ``window`` clips
    both; without it the window runs from the first event's start to the last
    one's end. Only ``launches`` counts the programs unclipped: the window is
    a span on the host's clock, which the device's meets to about a
    millisecond, so a program of microseconds launched at a job's start lies
    inside it or not from run to run, while the trace holds it every time.
    ``spans`` are the dispatching thread's spans of the program: a gap most
    of which one of them covers is named `` | host <span>`` too."""
    timed = list(ops) or list(launches)
    if not timed:
        return {"busy_s": 0.0, "window_s": 0.0, "programs": {},
                "launches": 0, "device_ops": [], "idle_gaps": []}
    if window is None:
        window = (min(s for _, s, _ in timed), max(s + d for _, s, d in timed))
    lo, hi = window
    launched = len(launches)

    def clip(events):
        return [(n, max(s, lo), min(s + d, hi) - max(s, lo))
                for n, s, d in events if s + d > lo and s < hi]

    ops, launches = clip(ops), clip(launches)
    timed = ops or launches
    busy = union_seconds([(s, s + d) for _, s, d in timed])
    programs: Dict[str, dict] = {}
    first_seen: List[str] = []
    for name, start, dur in sorted(launches, key=lambda e: e[1]):
        if name not in programs:
            programs[name] = {"seconds": 0.0, "launches": 0,
                              "order": len(first_seen)}
            first_seen.append(name)
        programs[name]["seconds"] += dur
        programs[name]["launches"] += 1

    top = sorted(self_seconds(ops or launches).items(), key=lambda kv: -kv[1])
    # gaps: between the merged busy intervals, named by the programs around
    merged: List[List[float]] = []
    for start, end in sorted((s, s + d) for _, s, d in timed):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    marks = sorted((s, s + d, n) for n, s, d in launches)

    def around(t_from, t_to):
        # a program's launch starts before its first operation and ends
        # after its last: the program last started when the gap begins, and
        # the first one started later that still runs when it ends
        inside = [n for s, e, n in marks if s <= t_from and e >= t_to]
        if inside:
            return f"inside {inside[0]}"
        before = [n for s, e, n in marks if s <= t_from + 1e-9]
        after = [n for s, e, n in marks
                 if s > t_from + 1e-9 and e >= t_to - 1e-9]
        return f"after {before[-1] if before else 'window start'} " \
               f"before {after[0] if after else 'window end'}"

    edges = [lo] + [t for iv in merged for t in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] - edges[i] > 0]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    names = [around(a, b) for a, b in gaps]
    if spans:
        from benchmark import trace_scopes

        _, under = trace_scopes.gaps_by_span(gaps, spans)
        names = [n if span == trace_scopes.NO_SPAN else f"{n} | host {span}"
                 for n, span in zip(names, under)]
    return {
        "busy_s": busy, "window_s": hi - lo, "programs": programs,
        "launches": launched,
        "device_ops": [[n, s] for n, s in top[:10]],
        "idle_gaps": [[n[:160], b - a] for n, (a, b) in zip(names, gaps)],
    }


def find_program(programs: Dict[str, dict], patterns: Sequence[dict]):
    """The first pattern that matches any program wins. A pattern is
    ``{"match": regex}``, optionally with ``"nth"``: of the matching programs
    in order of first launch, take that one alone. Returns
    (seconds, launches) or None."""
    for pat in patterns:
        hits = sorted((p for p in programs.items()
                       if re.search(pat["match"], p[0])),
                      key=lambda p: p[1]["order"])
        if "nth" in pat:
            hits = hits[pat["nth"]:pat["nth"] + 1]
        if hits:
            return (sum(p[1]["seconds"] for p in hits),
                    sum(p[1]["launches"] for p in hits))
    return None


def _short(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def _op_label(name: str) -> str:
    """An operation's HLO text without layouts, operand names and the
    called computation: ``%fusion.21 = f32[268435456] fusion(f32[2097152],
    s32[268435456]), kind=kCustom``."""
    text = re.sub(r"\{[^{}]*\}", "", name)
    text = re.sub(r"/\*[^*]*\*/", "", text)
    text = re.sub(r" %[\w.\-]+", "", text)
    text = re.sub(r", calls=.*$", "", text)
    return re.sub(r"\s+", " ", text)


@functools.lru_cache(maxsize=None)
def op_key(op_name: str, instruction: str) -> str:
    """The name an operation's seconds are added up under in
    ``breakdown.device_ops``, at most 160 characters, made to outlive a
    renumbering of the program's instructions: the innermost ``pml.*`` scope
    of its ``op_name``, then the instruction's name without its number and
    its result shape (``pml.features.matvec | fusion f32[4194304]``). An
    operation under no scope keeps its whole label (:func:`_op_label`), less
    the number."""
    from benchmark import trace_scopes

    label = _op_label(instruction)
    head, eq, rest = label.partition(" = ")
    head = re.sub(r"\.\d+", "", head.lstrip("%"))
    path = trace_scopes.scope_path(op_name)
    if not path:
        return (head + eq + rest)[:160]
    if rest.startswith("("):  # a tuple: up to the bracket that closes it
        depth = 0
        for end, char in enumerate(rest):
            depth += (char == "(") - (char == ")")
            if depth == 0:
                break
        shape = rest[:end + 1]
    else:
        shape = rest.split(" ", 1)[0]
    return f"{path[-1]} | {head} {shape}".rstrip()[:160]


def read_xplane(trace_dir: str) -> List[dict]:
    """One reduced dict per device found in the newest trace under
    ``trace_dir``: the keys of :func:`reduce_events` and those of
    :func:`benchmark.trace_scopes.reduce_scopes`."""
    from benchmark import trace_scopes

    found = trace_scopes.read_devices(trace_dir)
    window, threads = found["window"], found["threads"]
    spans = threads.get(
        trace_scopes.dispatching_thread(threads, found["window_thread"]), ())
    out = []
    for _, device in sorted(found["devices"].items()):
        reduced = reduce_events(
            [(op_key(o, i), s, d) for o, i, s, d in device["ops"]],
            [(_short(n), s, d) for n, s, d in device["launches"]],
            window, spans)
        reduced.update(trace_scopes.reduce_scopes(
            device["ops"], threads, window, found["window_thread"]))
        out.append(reduced)
    return out
