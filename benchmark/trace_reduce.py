"""From a profiler trace to numbers: device busy time, idle share, time and
launches per program, the operations that took most time, the longest gaps.

Two layers. :func:`reduce_events` is arithmetic over plain tuples, checked in
``benchmark/check.py`` against a list worked by hand. :func:`read_xplane`
turns an ``.xplane.pb`` into those tuples with ``jax.profiler.ProfileData``:
on a TPU the device planes' ``XLA Modules`` line gives the program launches
and ``XLA Ops`` the operations; on the CPU backend (the rehearsal, where no
number is kept) operations are the host events that carry an ``hlo_module``.
"""

from __future__ import annotations

import glob
import os
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
                  window: Optional[Tuple[float, float]] = None) -> dict:
    """One device's events to the numbers the metrics read. ``ops`` are the
    operations (busy time is the union of their intervals, or of the
    launches' where a trace has no operations); ``launches`` the programs.
    ``window`` clips both; without it the window runs from the first event's
    start to the last one's end."""
    timed = list(ops) or list(launches)
    if not timed:
        return {"busy_s": 0.0, "window_s": 0.0, "programs": {}, "launches": 0,
                "device_ops": [], "idle_gaps": []}
    if window is None:
        window = (min(s for _, s, _ in timed), max(s + d for _, s, d in timed))
    lo, hi = window

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
        inside = [n for s, e, n in marks if s <= t_from and e >= t_to]
        if inside:
            return f"inside {inside[0]}"
        before = [n for s, e, n in marks if e <= t_from + 1e-9]
        after = [n for s, e, n in marks if s >= t_to - 1e-9]
        return f"after {before[-1] if before else 'window start'} " \
               f"before {after[0] if after else 'window end'}"

    edges = [lo] + [t for iv in merged for t in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] - edges[i] > 0]
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "busy_s": busy, "window_s": hi - lo, "programs": programs,
        "launches": len(launches),
        "device_ops": [[n, s] for n, s in top[:10]],
        "idle_gaps": [[around(a, b), b - a] for a, b in gaps[:10]],
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
    s32[268435456]), kind=kCustom``, at most 160 characters."""
    text = re.sub(r"\{[^{}]*\}", "", name)
    text = re.sub(r"/\*[^*]*\*/", "", text)
    text = re.sub(r" %[\w.\-]+", "", text)
    text = re.sub(r", calls=.*$", "", text)
    return re.sub(r"\s+", " ", text)[:160]


def read_xplane(trace_dir: str) -> List[dict]:
    """One reduced dict per device found in the newest trace under
    ``trace_dir`` (see :func:`reduce_events`)."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        return []
    data = ProfileData.from_file(files[-1])
    window = None
    devices: Dict[str, dict] = {}
    host_ops: List[Event] = []
    host_runs: Dict[tuple, List[float]] = {}
    for plane in data.planes:
        is_device = plane.name.startswith("/device:") and "TPU" in plane.name
        for line in plane.lines:
            for ev in line.events:
                start, dur = ev.start_ns * 1e-9, ev.duration_ns * 1e-9
                if ev.name == WINDOW_SPAN:
                    window = (start, start + dur)
                elif is_device and line.name == "XLA Modules":
                    devices.setdefault(plane.name, {"ops": [], "launches": []})[
                        "launches"].append((_short(ev.name), start, dur))
                elif is_device and line.name == "XLA Ops":
                    devices.setdefault(plane.name, {"ops": [], "launches": []})[
                        "ops"].append((_op_label(ev.name), start, dur))
                elif not is_device and dur > 0:
                    stats = dict(ev.stats)
                    if "hlo_module" in stats:
                        host_ops.append((ev.name, start, dur))
                        run = host_runs.setdefault(
                            (stats["hlo_module"], stats.get("run_id")),
                            [start, start + dur])
                        run[0] = min(run[0], start)
                        run[1] = max(run[1], start + dur)
    if not devices and host_ops:
        devices["host"] = {
            "ops": host_ops,
            "launches": [(k[0], v[0], v[1] - v[0]) for k, v in host_runs.items()],
        }
    return [reduce_events(d["ops"], d["launches"], window)
            for _, d in sorted(devices.items())]
