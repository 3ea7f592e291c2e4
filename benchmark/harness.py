"""One run of one cell: device check, set-up clock, warm-up, the measured
window under a compile watermark, the optional traced window, the
comparison with the plain reference, the result line.

Nothing here names a cell, a configuration or a metric: they are found
through ``BENCHMARK.json`` and the data files beside this module.
"""

from __future__ import annotations

import importlib
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NO_DEVICE, COMPILED_IN_WINDOW = 2, 3


def say(text: str) -> None:
    print(f"[benchmark {time.strftime('%H:%M:%S')}] {text}", flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find_cell(manifest: dict, name: str):
    """(workload entry, its data file, configuration entry, its file)."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                         f"there are {sorted(cells)}")
    cell = cells[name]
    conf = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    return (cell, load_json(HERE, "workloads", name + ".json"),
            conf, load_json(ROOT, conf["file"]))


def metrics_of(manifest: dict, cell: str, group: str):
    return [m for m in manifest[group]
            if "workloads" not in m or cell in m["workloads"]]


def device_report(jax) -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(jax) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") or 0
             for d in jax.devices()]
    return int(max(peaks))


def read_per_layer(manifest, cell_name, ctx) -> dict:
    out = {}
    for entry in metrics_of(manifest, cell_name, "per_layer"):
        spec = load_json(HERE, "metrics", entry["name"] + ".json")
        module, func = spec["reader"].split(":")
        reader = getattr(importlib.import_module(
            f"benchmark.metrics.readers.{module}"), func)
        value = reader(dict(ctx, metric=spec))
        if value is not None:
            out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out


def traced_window(jax, cell, jobs: int, trace_dir: str):
    """Run ``jobs`` jobs under the profiler; (last outputs, seconds, reduced
    trace averaged over the devices)."""
    from benchmark import trace_reduce

    shutil.rmtree(trace_dir, ignore_errors=True)
    kwargs = {}
    if hasattr(jax.profiler, "ProfileOptions"):  # keep the Python tracer off
        kwargs["profiler_options"] = jax.profiler.ProfileOptions()
        kwargs["profiler_options"].python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, **kwargs)
    try:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            for _ in range(jobs):
                outputs = cell.run_job()
        seconds = time.perf_counter() - t0
    finally:
        jax.profiler.stop_trace()
    per_device = trace_reduce.read_xplane(trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)
    if not per_device:
        return outputs, seconds, None
    n = len(per_device)
    fullest = max(per_device, key=lambda d: d["busy_s"])
    reduced = dict(fullest,
                   busy_s=sum(d["busy_s"] for d in per_device) / n,
                   window_s=sum(d["window_s"] for d in per_device) / n)
    return outputs, seconds, reduced


def run(args, t_start: float, allow_cpu: bool = False, tiny: bool = False) -> int:
    manifest = load_json(ROOT, "BENCHMARK.json")
    entry, cell_file, conf_entry, config = find_cell(manifest, args.workload)

    import jax

    device = device_report(jax)
    print(f"platform: {device['platform']}, device_kind: {device['kind']}, "
          f"count: {device['count']}", flush=True)
    if (device["platform"] != "tpu" and not allow_cpu) \
            or device["count"] < entry["chips"]:
        print(f"benchmark: the cell needs {entry['chips']} TPU chip(s); jax "
              "gave the devices above, and this never falls back",
              file=sys.stderr)
        return NO_DEVICE
    table = load_json(HERE, "peaks.json")
    peaks = table.get(device["kind"])
    if peaks is None:
        if not allow_cpu:
            print(f"benchmark: no peaks for device kind {device['kind']!r} in "
                  "benchmark/peaks.json", file=sys.stderr)
            return NO_DEVICE
        # the CPU rehearsal only sees that the readers run; check.py keeps
        # none of their numbers
        peaks = next(iter(table.values()))

    from photon_ml_tpu import compat
    from photon_ml_tpu.compile import compile_stats

    compat.start_up(say, os.path.join(ROOT, ".jax_compilation_cache"))
    compile_stats.install_xla_listeners()

    family = importlib.import_module(f"benchmark.families.{config['family']}")
    cell = family.build(config, cell_file["job"], args.seed, tiny=tiny)
    t_built = time.time()
    say(f"built {args.workload} seed {args.seed} in {t_built - t_start:.1f} s: "
        f"shapes {cell.shapes}")
    outputs = cell.run_job()  # warm-up: compiles, or loads from the cache
    setup_s = time.time() - t_start
    say(f"warm-up job {time.time() - t_built:.1f} s; XLA cache "
        f"{compile_stats.xla_cache_hits} hits / {compile_stats.xla_cache_misses}"
        f" misses, {compile_stats.backend_compile_seconds:.1f} s compiling; "
        f"setup_s {setup_s:.3f}")

    watermark = compile_stats.watermark()
    trace = None
    if args.trace:
        jobs = int(cell_file["job"].get("trace_jobs", 1))
        trace_dir = os.path.join(
            ROOT, ".benchmark_runs", f"{args.workload}-{args.seed}-trace")
        outputs, window_s, trace = traced_window(jax, cell, jobs, trace_dir)
    else:
        jobs = 0
        t0 = time.perf_counter()
        while True:
            outputs = cell.run_job()
            jobs += 1
            window_s = time.perf_counter() - t0
            if window_s >= args.seconds:
                break
    train_s = window_s / jobs
    say(f"window: {jobs} jobs in {window_s:.3f} s")
    if not watermark.clean():
        print(f"benchmark: {watermark.new_traces()} new traces and "
              f"{watermark.new_xla_misses()} new XLA compiles inside the "
              "measured window; the run is void", file=sys.stderr)
        return COMPILED_IN_WINDOW

    device["memory_peak_bytes"] = memory_peak(jax)
    got = cell.collect(outputs)
    del outputs
    cell.free()
    t_ref = time.time()
    ref = cell.reference()
    numbers = cell.compare(got, ref)
    say(f"reference and comparison {time.time() - t_ref:.1f} s; "
        f"all numbers {json.dumps(numbers)}")
    compared = {name: {"value": numbers[name], "limit": limit}
                for name, limit in cell.limits.items()}
    correct = bool(compared) and all(
        c["value"] <= c["limit"] for c in compared.values())

    if args.trace:
        device["busy_s"] = trace["busy_s"] if trace else 0.0
        device["window_s"] = trace["window_s"] if trace else window_s
        metrics = read_per_layer(manifest, args.workload, {
            "trace": trace, "work": cell.work(ref), "peaks": peaks,
            "jobs": jobs, "train_s": train_s, "chips": entry["chips"],
            "memory_peak_bytes": device["memory_peak_bytes"],
        })
    else:
        measured = {"train_s": train_s, "setup_s": setup_s}
        metrics = {m["name"]: {"value": measured[m["name"]], "unit": m["unit"]}
                   for m in metrics_of(manifest, args.workload, "end_to_end")}
    result = {"correct": correct, "attempted": jobs, "failed": 0,
              "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    result["compared"] = compared
    sys.stdout.flush()
    for name, c in compared.items():
        print(f"compared {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
