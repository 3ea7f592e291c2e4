"""The benchmark checks itself, on the CPU, before a chip minute is spent.

    python -m benchmark.check            manifest, files, trace arithmetic,
                                         then every cell at tiny sizes
    python -m benchmark.check --static   the first three only

1. ``BENCHMARK.json`` and the data files keep to the contract's letter:
   names, units, printable-ASCII sources, ``moves``, files that exist.
2. :mod:`benchmark.trace_reduce` on a list of events worked by hand.
3. Each cell once with ``--trace 0`` and once with ``--trace 1`` at the
   family's ``TINY`` sizes with ``JAX_PLATFORMS=cpu``: the last line has the
   contract's keys. No number from these runs is kept anywhere.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import re
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "proj", "head",
               "expansion", "per_tok")


def line_ok(text, limit=200) -> bool:
    return (isinstance(text, str) and 1 <= len(text) <= limit
            and all(0x20 <= ord(c) <= 0x7E for c in text))


def check_manifest(problems: list) -> dict:
    def bad(msg):
        problems.append(msg)

    raw = open(os.path.join(ROOT, "BENCHMARK.json"), "rb").read()
    if len(raw) > 64 * 1024:
        bad("BENCHMARK.json is over 64 KiB")
    if any(b > 0x7E for b in raw):
        bad("BENCHMARK.json holds a non-ASCII byte")
    m = json.loads(raw)
    if set(m) != {"command", "paths", "run_seconds", "configs", "workloads",
                  "end_to_end", "per_layer"}:
        bad(f"top-level keys are {sorted(m)}")
    if not (isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51):
        bad("run_seconds must be a whole number from 1 to 51")
    if not 1 <= len(m["command"]) <= 32 or not all(map(line_ok, m["command"])):
        bad("command must be 1 to 32 one-line strings")
    for p in m["paths"]:
        if not PATH.match(p) or p.startswith("/") or ".." in p.split("/"):
            bad(f"path {p!r}")
    under = lambda f: any(f.startswith(p.rstrip("/") + "/") for p in m["paths"])

    conf_names, files = set(), set()
    for c in m["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            bad(f"config keys {sorted(c)}")
        if not NAME.match(c["name"]) or c["name"] in conf_names:
            bad(f"config name {c['name']!r}")
        conf_names.add(c["name"])
        if not line_ok(c["source"]):
            bad(f"config {c['name']}: source must be 1 to 200 printable "
                f"ASCII characters, has {len(c['source'])}")
        if not line_ok(c["why"]):
            bad(f"config {c['name']}: why")
        if not under(c["file"]) or c["file"] in files \
                or not os.path.isfile(os.path.join(ROOT, c["file"])):
            bad(f"config {c['name']}: file {c['file']!r}")
        files.add(c["file"])
        if len(c["reduced"]) > 16:
            bad(f"config {c['name']}: more than 16 reduced keys")
        for key in c["reduced"]:
            if not NAME.match(key) or key.endswith(("_dim", "_rank")) \
                    or any(w in key for w in WIDTH_WORDS):
                bad(f"config {c['name']}: reduced key {key!r} is a width")
        if os.path.isfile(os.path.join(ROOT, c["file"])):
            body = json.load(open(os.path.join(ROOT, c["file"])))
            for key in ("family", "source", "sizes", "reduced", "assumed",
                        "limits"):
                if key not in body:
                    bad(f"{c['file']}: no {key!r}")
            if body.get("source") != c["source"]:
                bad(f"{c['file']}: source differs from the manifest's")
            if body.get("reduced") != c["reduced"]:
                bad(f"{c['file']}: reduced differs from the manifest's")
            if not os.path.isfile(os.path.join(
                    HERE, "families", str(body.get("family")) + ".py")):
                bad(f"{c['file']}: no family module {body.get('family')!r}")

    cell_names, pairs, used = set(), set(), set()
    for w in m["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            bad(f"workload keys {sorted(w)}")
        for key in ("name", "config", "traffic"):
            if not NAME.match(w[key]):
                bad(f"workload {key} {w[key]!r}")
        if w["name"] in cell_names or (w["config"], w["traffic"]) in pairs:
            bad(f"workload {w['name']} appears twice")
        cell_names.add(w["name"])
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
        if w["config"] not in conf_names:
            bad(f"workload {w['name']}: no config {w['config']!r}")
        if w["chips"] not in (1, 4):
            bad(f"workload {w['name']}: chips {w['chips']!r}")
        if not line_ok(w["why"]):
            bad(f"workload {w['name']}: why")
        data = os.path.join(HERE, "workloads", w["name"] + ".json")
        if not os.path.isfile(data):
            bad(f"workload {w['name']}: no benchmark/workloads/{w['name']}.json")
        else:
            body = json.load(open(data))
            if (body.get("config"), body.get("traffic"), body.get("chips")) \
                    != (w["config"], w["traffic"], w["chips"]):
                bad(f"{data}: config, traffic or chips differ from the manifest")
    if conf_names - used:
        bad(f"configs no cell uses: {sorted(conf_names - used)}")
    four = sum(w["chips"] == 4 for w in m["workloads"])
    if four > max(1, len(m["workloads"]) // 4):
        bad(f"{four} cells ask for 4 chips")

    e2e = {}
    for e in m["end_to_end"]:
        if set(e) - {"workloads"} != {"name", "unit", "better", "bound", "source"}:
            bad(f"end_to_end keys {sorted(e)}")
        if not NAME.match(e["name"]) or e["name"] in e2e:
            bad(f"end_to_end name {e['name']!r}")
        if not UNIT.match(e["unit"]) or e["better"] not in ("lower", "higher"):
            bad(f"end_to_end {e['name']}: unit or better")
        if e["source"] not in ("host_clock", "device_trace"):
            bad(f"end_to_end {e['name']}: source {e['source']!r}")
        if not 0.01 <= e["bound"] <= 0.1:
            bad(f"end_to_end {e['name']}: bound {e['bound']!r}")
        e2e[e["name"]] = set(e.get("workloads", cell_names))
    if "setup_s" not in e2e or e2e["setup_s"] != cell_names:
        bad("every cell reports setup_s")
    if not 1 <= len(e2e) - 1 <= 4:
        bad("one to four end-to-end metrics besides setup_s")
    for cell in cell_names:
        if not any(cell in cells for n, cells in e2e.items() if n != "setup_s"):
            bad(f"cell {cell} reports no end-to-end metric besides setup_s")

    layers, seen = {}, set(e2e)
    for p in m["per_layer"]:
        if set(p) - {"workloads"} != {"name", "unit", "better", "source",
                                     "layer", "moves"}:
            bad(f"per_layer keys {sorted(p)}")
        if not NAME.match(p["name"]) or p["name"] in seen:
            bad(f"per_layer name {p['name']!r}")
        seen.add(p["name"])
        if not UNIT.match(p["unit"]) or p["better"] not in ("lower", "higher") \
                or p["source"] not in SOURCES or not line_ok(p["layer"]):
            bad(f"per_layer {p['name']}: unit, better, source or layer")
        cells = set(p.get("workloads", e2e.get(p["moves"], ())))
        if p["moves"] not in e2e:
            bad(f"per_layer {p['name']}: moves {p['moves']!r}")
        elif not cells or cells - e2e[p["moves"]]:
            bad(f"per_layer {p['name']}: a cell does not report {p['moves']}")
        for cell in cells:
            layers.setdefault(cell, []).append(p["name"])
        spec_path = os.path.join(HERE, "metrics", p["name"] + ".json")
        if not os.path.isfile(spec_path):
            bad(f"per_layer {p['name']}: no benchmark/metrics/{p['name']}.json")
            continue
        spec = json.load(open(spec_path))
        for key in ("unit", "layer", "moves", "better", "source"):
            if spec.get(key) != p[key]:
                bad(f"{spec_path}: {key} differs from the manifest's")
        module = spec.get("reader", ":").split(":")[0]
        if not os.path.isfile(os.path.join(
                HERE, "metrics", "readers", module + ".py")):
            bad(f"{spec_path}: no reader module {module!r}")
        if p["name"].endswith("_roofline") and p["unit"] != "%":
            bad(f"per_layer {p['name']}: a roofline share has the unit %")
    for cell in cell_names:
        if not layers.get(cell):
            bad(f"cell {cell} reports no per-layer metric")
    if not any("mfu" in re.split(r"[._\-]", p["name"]) for p in m["per_layer"]):
        bad("no whole-step share of the peak with mfu as a part of its name")

    listed = {"configs": {os.path.basename(c["file"]) for c in m["configs"]},
              "workloads": {w["name"] + ".json" for w in m["workloads"]},
              "metrics": {p["name"] + ".json" for p in m["per_layer"]}}
    for kind, names in listed.items():
        extra = sorted(set(n for n in os.listdir(os.path.join(HERE, kind))
                           if n.endswith(".json")) - names)
        if extra:
            print(f"check: benchmark/{kind} holds files the manifest does not "
                  f"list (kept for a later cell, PERF.md): {extra}")
    for dirpath, _, names in os.walk(HERE):
        if "__pycache__" in dirpath:
            continue
        for n in names:
            rel = os.path.relpath(os.path.join(dirpath, n), ROOT)
            if not re.match(r"^[A-Za-z0-9_.\-/]+$", rel):
                bad(f"file name {rel!r}")
    return m


def check_trace_arithmetic(problems: list) -> None:
    """Two programs; operations that overlap and nest; one gap. By hand:
    ops cover [0,4] and [6,9] -> busy 7 of a window of 10 (idle 30 %);
    program a launched twice for 4 + 1 s, b once for 2 s; the loop's own
    time is 4 - (1 + 2) = 1 s; the longest gap is 4..6 between a and b."""
    from benchmark import trace_reduce as tr

    ops = [("loop", 0.0, 4.0), ("gather", 0.5, 1.0), ("scatter", 1.5, 2.0),
           ("dot", 6.0, 2.0), ("add", 8.0, 1.0)]
    launches = [("a", 0.0, 4.0), ("b", 6.0, 2.0), ("a", 8.0, 1.0)]
    out = tr.reduce_events(ops, launches, (0.0, 10.0))
    own = tr.self_seconds(ops)
    want = [
        (out["busy_s"], 7.0), (out["window_s"], 10.0),
        (out["programs"]["a"]["seconds"], 5.0),
        (out["programs"]["a"]["launches"], 2),
        (out["programs"]["b"]["seconds"], 2.0), (out["launches"], 3),
        (own["loop"], 1.0), (own["gather"], 1.0), (own["scatter"], 2.0),
        (out["idle_gaps"][0][1], 2.0), (out["idle_gaps"][1][1], 1.0),
        (tr.union_seconds([(0, 2), (1, 3), (5, 6)]), 4.0),
        (tr.find_program(out["programs"], [{"match": "^z$"}, {"match": "a|b", "nth": 1}]), (2.0, 1)),
    ]
    for i, (got, expect) in enumerate(want):
        if got != expect:
            problems.append(f"trace arithmetic {i}: got {got!r}, by hand {expect!r}")
    if out["idle_gaps"][0][0] != "after a before b":
        problems.append(f"trace gap name: {out['idle_gaps'][0][0]!r}")
    if tr.reduce_events([], [], None)["busy_s"] != 0.0:
        problems.append("an empty trace must reduce to nothing")


RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def rehearse(manifest: dict, problems: list) -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    from benchmark import harness

    for cell in manifest["workloads"]:
        for trace in (0, 1):
            args = argparse.Namespace(
                workload=cell["name"], seed=2 ** 31 + 11, seconds=0.2, trace=trace)
            out = io.StringIO()
            with contextlib.redirect_stdout(out):
                code = harness.run(args, time.time(), allow_cpu=True, tiny=True)
            lines = out.getvalue().strip().splitlines()
            tag = f"{cell['name']} --trace {trace}"
            if code != 0:
                problems.append(f"{tag}: exit code {code}")
                continue
            last = json.loads(lines[-1])
            if not RESULT_KEYS <= set(last) or list(last)[-1] != "compared":
                problems.append(f"{tag}: last line has keys {list(last)}")
            group = "per_layer" if trace else "end_to_end"
            want = {m["name"] for m in harness.metrics_of(
                manifest, cell["name"], group)}
            if set(last["metrics"]) - want:
                problems.append(
                    f"{tag}: metrics {sorted(last['metrics'])}, the manifest "
                    f"lists {sorted(want)}")
            if want - set(last["metrics"]):
                print(f"check: {tag}: nothing to read on the CPU for "
                      f"{sorted(want - set(last['metrics']))}")
            if not last["correct"]:
                problems.append(f"{tag}: correct is false: {last['compared']}")
            print(f"check: {tag}: ok ({len(last['metrics'])} metrics, "
                  f"{last['attempted']} jobs)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--static", action="store_true")
    ns = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    problems: list = []
    manifest = check_manifest(problems)
    check_trace_arithmetic(problems)
    if not ns.static and not problems:
        rehearse(manifest, problems)
    for p in problems:
        print("check: FAIL:", p)
    print(f"check: {'ok' if not problems else f'{len(problems)} problem(s)'}")
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
