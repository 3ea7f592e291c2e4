"""From a profiler trace to the program's own names: device seconds and
executions per ``jax.named_scope``, and the device's idle seconds laid against
the host spans (``photon_ml_tpu.utils.profiling.span``) on the same clock.

    python -m benchmark.trace_scopes <trace directory>

prints both tables for any trace directory, a ``PHOTON_ML_TPU_PROFILE`` run of
a driver included, so that such a run can be read without XProf.

Two layers, like :mod:`benchmark.trace_reduce`. The first is arithmetic over
plain tuples, checked in ``benchmark/tests/test_trace_scopes.py`` against lists
worked by hand. The second turns an ``.xplane.pb`` into those tuples. On a TPU
an operation's scope path is the ``tf_op`` stat of its *event metadata* (the
HLO ``op_name`` of the operation, for a fusion that of its root), which
``jax.profiler.ProfileData`` does not expose, so :func:`read_xspace` walks the
protobuf's wire format itself: the five messages of ``xplane.proto`` it needs
and nothing else. Host spans are the events of the host plane's thread lines
whose names start with :data:`PREFIX`. It is the one parser of a trace:
:func:`benchmark.trace_reduce.read_xplane` takes its tuples from
:func:`read_devices`, the CPU backend's (the rehearsal) too.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from benchmark.trace_reduce import WINDOW_SPAN, self_seconds

PREFIX = "pml."
NO_SCOPE = "(no scope)"
NO_SPAN = "(no span)"
_SCOPE = re.compile(re.escape(PREFIX) + r"[A-Za-z0-9_.]+")

# op_name ("" for an operation the compiler made: a copy-start, a slice, the
# while itself), the HLO instruction's text, start_s, duration_s
ScopedOp = Tuple[str, str, float, float]
Span = Tuple[str, float, float]  # name, start_s, duration_s


def scope_path(op_name: str) -> Tuple[str, ...]:
    """The program's scopes in an HLO ``op_name``, outermost first:
    ``jit(f)/pml.a/while/body/vmap(pml.b)/add`` -> ``("pml.a", "pml.b")``. A
    transform wraps the name of the scope it maps over and keeps it."""
    return tuple(_SCOPE.findall(op_name))


# -- device side: seconds and executions per scope ---------------------------


def scope_seconds(ops: Sequence[ScopedOp]) -> Dict[str, Dict[str, float]]:
    """Per scope, ``self_s``: the self time (the nesting rule of
    :func:`benchmark.trace_reduce.self_seconds`: a loop's body is not the
    loop's own time) of the operations whose *innermost* scope it is, and
    ``total_s``: that of all operations whose path holds it. Operations
    outside every scope are under :data:`NO_SCOPE`, so the ``self_s`` add up
    to the self time of all operations."""
    own = self_seconds([("/".join(scope_path(o)), s, d) for o, _, s, d in ops])
    out: Dict[str, Dict[str, float]] = {}
    for joined, seconds in own.items():
        path = joined.split("/") if joined else [NO_SCOPE]
        for scope in set(path):
            entry = out.setdefault(scope, {"self_s": 0.0, "total_s": 0.0})
            entry["total_s"] += seconds
        out[path[-1]]["self_s"] += seconds
    return out


def scope_runs(ops: Sequence[ScopedOp]) -> Dict[str, int]:
    """Executions per scope. An instruction that sits in a scope outside any
    loop of the scope's own runs once each time the scope is entered from one
    place in the program (one prefix of the ``op_name``); the compiler can
    only lower that count, by hoisting the instruction out of a loop around
    the scope. So: per place, the largest number of events of any one such
    instruction; per scope, the sum over its places (the evaluation before
    the solver's loop and the one in its line search are two places). The
    while's own copies of loop state carry the while's ``op_name`` and count
    for nothing; a scope with no instruction outside its own loops (a scope
    around one ``while_loop``) has no count.

    Not a count of runs of consecutive operations: the chip's scheduler puts
    independent operations of the line search between those of an evaluation
    and nothing between two evaluations, so such runs read 21 where the
    solver evaluated 4 times (PERF.md, Findings, PR 25)."""
    events: Dict[Tuple[str, str], Dict[str, int]] = {}
    for op_name, instruction, _, _ in ops:
        parts = op_name.rstrip(":").split("/")
        for i, part in enumerate(parts):
            found = _SCOPE.search(part)
            if found and not {"while", "cond"} & set(parts[i + 1:]):
                per = events.setdefault(
                    (found.group(), "/".join(parts[:i + 1])), {})
                per[instruction] = per.get(instruction, 0) + 1
    runs: Dict[str, int] = {}
    for (scope, _), per in events.items():
        runs[scope] = runs.get(scope, 0) + max(per.values())
    return runs


# -- host side: which span the device waited under ----------------------------


def idle_gaps(ops: Sequence[ScopedOp],
              window: Tuple[float, float]) -> List[Tuple[float, float]]:
    """The (start, end) stretches of ``window`` in which no operation runs."""
    lo, hi = window
    gaps, reach = [], lo
    for start, end in sorted((max(s, lo), min(s + d, hi)) for *_, s, d in ops
                             if s + d > lo and s < hi):
        if start > reach:
            gaps.append((reach, start))
        reach = max(reach, end)
    if hi > reach:
        gaps.append((reach, hi))
    return gaps


def innermost_segments(spans: Sequence[Span]) -> List[Tuple[float, float, str]]:
    """One thread's spans, which nest, cut into disjoint (start, end, name)
    pieces in order of time, each named by the innermost span open there."""
    out: List[Tuple[float, float, str]] = []
    stack: List[Tuple[str, float]] = []  # name, end
    at = float("-inf")

    def emit(until: float):
        nonlocal at
        if stack and until > at:
            out.append((at, until, stack[-1][0]))
        at = max(at, until)

    for name, start, dur in sorted(spans, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            emit(stack[-1][1])
            stack.pop()
        emit(start)
        stack.append((name, start + dur))
    while stack:
        emit(stack[-1][1])
        stack.pop()
    return out


def gaps_by_span(gaps: Sequence[Tuple[float, float]], spans: Sequence[Span]):
    """Lay idle gaps against the spans of the dispatching thread. Returns
    (seconds per span name, with :data:`NO_SPAN` for idle time no span covers;
    per gap, the name that holds most of it, a span winning a tie with none)."""
    segments = innermost_segments(spans)
    starts = [s for s, _, _ in segments]
    by_span: Dict[str, float] = {}
    winners: List[str] = []
    for lo, hi in gaps:
        parts: Dict[str, float] = {}
        covered = 0.0
        i = max(bisect.bisect_right(starts, lo) - 1, 0)
        while i < len(segments) and segments[i][0] < hi:
            s, e, name = segments[i]
            part = min(e, hi) - max(s, lo)
            if part > 0:
                parts[name] = parts.get(name, 0.0) + part
                covered += part
            i += 1
        if hi - lo - covered > 1e-12:
            parts[NO_SPAN] = hi - lo - covered
        for name, part in parts.items():
            by_span[name] = by_span.get(name, 0.0) + part
        winners.append(max(parts, key=lambda n: (parts[n], n != NO_SPAN)))
    return by_span, winners


def dispatching_thread(threads: Dict[object, List[Span]],
                       window_thread: Optional[object] = None):
    """The thread whose spans explain gaps: the one that opened the window,
    else the one whose spans cover the most seconds. Spans of other threads
    (prefetch) are kept in the trace and never used here."""
    if window_thread in threads:
        return window_thread
    if not threads:
        return None
    return max(threads, key=lambda t: sum(
        e - s for s, e, _ in innermost_segments(threads[t])))


def reduce_scopes(ops: Sequence[ScopedOp], threads: Dict[object, List[Span]],
                  window: Optional[Tuple[float, float]] = None,
                  window_thread: Optional[object] = None) -> dict:
    """One device's scoped operations and the host's spans to the three keys
    the readers in ``metrics/readers/scopes.py`` read."""
    if window is None and ops:
        window = (min(s for *_, s, _ in ops), max(s + d for *_, s, d in ops))
    if window is None:
        return {"scopes": {}, "scope_runs": {}, "host_gaps": None}
    lo, hi = window
    ops = [(o, i, max(s, lo), min(s + d, hi) - max(s, lo))
           for o, i, s, d in ops if s + d > lo and s < hi]
    gaps = idle_gaps(ops, window)
    spans = threads.get(dispatching_thread(threads, window_thread), [])
    by_span, winners = gaps_by_span(gaps, spans)
    idle = sum(b - a for a, b in gaps)
    longest = sorted(zip(gaps, winners), key=lambda g: g[0][0] - g[0][1])[:10]
    return {
        "scopes": scope_seconds(ops),
        "scope_runs": scope_runs(ops),
        "host_gaps": {
            "idle_s": idle,
            "unattributed_s": by_span.get(NO_SPAN, 0.0),
            "by_span": by_span,
            # [seconds after the window's start, seconds, span]
            "longest": [[a - lo, b - a, name] for (a, b), name in longest],
        },
    }


# -- the .xplane.pb's wire format ---------------------------------------------


def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf) -> Iterable[Tuple[int, object]]:
    """(field number, value) of one message: ints for varints, slices of the
    buffer for length-delimited and fixed-width fields."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {kind} in an .xplane.pb")
        yield key >> 3, value


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def _map_entry(buf):
    key = value = None
    for number, field in _fields(buf):
        if number == 1:
            key = field
        elif number == 2:
            value = field
    return key, value


def _stat(buf, stat_names: Dict[int, str]):
    """(stat's name, its value) of one XStat: text for a string or a
    reference to a name, else the integer."""
    stat = dict(_fields(buf))
    if 5 in stat:
        value = _text(stat[5])
    elif 7 in stat:
        value = stat_names.get(stat[7], "")
    else:
        value = stat.get(3, stat.get(4))
    return stat_names.get(stat.get(1), ""), value


def read_xspace(path: str) -> List[dict]:
    """The planes of an ``.xplane.pb``: ``{"name", "lines": [{"name",
    "events": [(event name, op_name or "", start_s, duration_s, hlo_module
    or "", run_id or None)]}]}``. The ``op_name`` is the ``tf_op`` stat of
    the event's metadata (a TPU's operations); ``hlo_module`` and ``run_id``
    are stats of the event itself (the CPU backend's operations, which carry
    nothing else to tell them by). Field numbers are those of
    ``tsl/profiler/protobuf/xplane.proto``: XSpace.planes 1; XPlane.name 2,
    lines 3, event_metadata 4, stat_metadata 5; XLine.name 2, timestamp_ns
    3, events 4; XEvent.metadata_id 1, offset_ps 2, duration_ps 3, stats 4;
    XEventMetadata.name 2, stats 5; XStat.metadata_id 1, uint64_value 3,
    int64_value 4, str_value 5, ref_value 7; XStatMetadata.name 2."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = []
    for number, plane in _fields(space):
        if number != 1:
            continue
        name, lines, events, stat_names = "", [], {}, {}
        for number, field in _fields(plane):
            if number == 2:
                name = _text(field)
            elif number == 3:
                lines.append(field)
            elif number == 4:
                key, value = _map_entry(field)
                events[key] = value
            elif number == 5:
                key, value = _map_entry(field)
                stat_names[key] = next(
                    (_text(v) for n, v in _fields(value) if n == 2), "")
        known = set(stat_names.values())
        named = {}
        for key, meta in events.items():
            event_name, op_name = "", ""
            for number, field in _fields(meta):
                if number == 2:
                    event_name = _text(field)
                elif number == 5 and "tf_op" in known:
                    stat, value = _stat(field, stat_names)
                    if stat == "tf_op":
                        op_name = value
            named[key] = (event_name, op_name)
        out_lines = []
        for line in lines:
            line_name, t0_ns, out_events = "", 0, []
            for number, field in _fields(line):
                if number == 2:
                    line_name = _text(field)
                elif number == 3:
                    t0_ns = field
                elif number == 4:
                    event, module, run_id = {}, "", None
                    for number, value in _fields(field):
                        if number != 4:
                            event[number] = value
                        elif "hlo_module" in known:
                            stat, value = _stat(value, stat_names)
                            if stat == "hlo_module":
                                module = value
                            elif stat == "run_id":
                                run_id = value
                    event_name, op_name = named.get(event.get(1), ("", ""))
                    out_events.append((
                        event_name, op_name,
                        t0_ns * 1e-9 + event.get(2, 0) * 1e-12,
                        event.get(3, 0) * 1e-12, module, run_id))
            out_lines.append({"name": line_name, "events": out_events})
        planes.append({"name": name, "lines": out_lines})
    return planes


def newest_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


HOST_DEVICE = "/host:CPU"


def read_devices(trace_dir: str) -> dict:
    """The newest trace under ``trace_dir`` as tuples, parsed once:
    ``devices`` maps each TPU plane's name to its ``ops``
    (:data:`ScopedOp`, the ``XLA Ops`` line) and ``launches`` (name, start_s,
    duration_s of the ``XLA Modules`` line), ``threads`` each host thread to
    its ``pml.*`` spans, ``window`` and ``window_thread`` say where the
    benchmark's window span lies. A trace of the CPU backend (the rehearsal)
    has no such plane: its one device is :data:`HOST_DEVICE`, the operations
    are the host's events that carry an ``hlo_module`` (they have no
    ``op_name``, so no scope), a launch is one run of a module from its first
    operation to its last. ``path`` is the file read, or nothing where there
    is none."""
    path = newest_xplane(trace_dir)
    found = {"path": path, "devices": {}, "threads": {}, "window": None,
             "window_thread": None}
    host_ops: List[ScopedOp] = []
    host_runs: Dict[tuple, List[float]] = {}
    for plane in read_xspace(path) if path else ():
        is_device = plane["name"].startswith("/device:") and "TPU" in plane["name"]
        for index, line in enumerate(plane["lines"]):
            thread = (plane["name"], index)
            for name, op_name, start, dur, module, run_id in line["events"]:
                if name == WINDOW_SPAN:
                    found["window"] = (start, start + dur)
                    found["window_thread"] = thread
                elif is_device and line["name"] in ("XLA Ops", "XLA Modules"):
                    device = found["devices"].setdefault(
                        plane["name"], {"ops": [], "launches": []})
                    if line["name"] == "XLA Ops":
                        device["ops"].append((op_name, name, start, dur))
                    else:
                        device["launches"].append((name, start, dur))
                elif not is_device and name.startswith(PREFIX):
                    found["threads"].setdefault(thread, []).append(
                        (name, start, dur))
                elif not is_device and module and dur > 0:
                    host_ops.append((op_name, name, start, dur))
                    run = host_runs.setdefault((module, run_id),
                                               [start, start + dur])
                    run[0] = min(run[0], start)
                    run[1] = max(run[1], start + dur)
    if host_ops and not found["devices"]:
        found["devices"][HOST_DEVICE] = {
            "ops": host_ops,
            "launches": [(module, lo, hi - lo)
                         for (module, _), (lo, hi) in host_runs.items()]}
    return found


def read_scopes(trace_dir: str) -> List[dict]:
    """One :func:`reduce_scopes` dict per device in the newest trace under
    ``trace_dir``, in the order :func:`benchmark.trace_reduce.read_xplane`
    gives its own (which holds these keys too: this is the command line's
    way in)."""
    found = read_devices(trace_dir)
    return [reduce_scopes(device["ops"], found["threads"], found["window"],
                          found["window_thread"])
            for _, device in sorted(found["devices"].items())]


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print(__doc__.split("\n\n")[1], file=sys.stderr)
        return 2
    devices = read_scopes(args[0])
    if not devices:
        print(f"no .xplane.pb with operations under {args[0]}",
              file=sys.stderr)
        return 1
    for index, reduced in enumerate(devices):
        print(f"device {index}")
        print(f"  {'scope':40s} {'self_s':>12s} {'total_s':>12s} {'executions':>10s}")
        for scope, t in sorted(reduced["scopes"].items(),
                               key=lambda kv: -kv[1]["total_s"]):
            print(f"  {scope:40s} {t['self_s']:12.6f} {t['total_s']:12.6f} "
                  f"{reduced['scope_runs'].get(scope, '-'):>10}")
        gaps = reduced["host_gaps"]
        print(f"  idle {gaps['idle_s']:.6f} s, of which under no span "
              f"{gaps['unattributed_s']:.6f} s")
        print(f"  {'host span':40s} {'idle_s':>12s}")
        for name, seconds in sorted(gaps["by_span"].items(), key=lambda kv: -kv[1]):
            print(f"  {name:40s} {seconds:12.6f}")
        print("  longest gaps (seconds into the window, seconds, span):")
        for at, seconds, name in gaps["longest"]:
            print(f"  {at:12.6f} {seconds:12.6f}  {name}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
