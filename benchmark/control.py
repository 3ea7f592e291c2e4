"""The readings a cell's limits are set from, at the cell's own size, many
seeds in one process (set-up is most of a run):

    python3 benchmark/control.py --workload NAME --seeds 11,12,13,... \
        --control-seeds 3

For every seed: build the cell, run its job once, compare with the plain
reference (the lower reading is the largest of these). For the first
``--control-seeds`` seeds also: the reference in bfloat16 storage put in the
program's place (the control: the upper reading is the smallest of these)
and the reference with half of the rows left out (a planted fault). One JSON
line per seed on standard output. The benchmark's own runs never call this.

``--permuted-rows`` adds a planted *sound* case to every seed, where the
family has one (``Cell.permute_rows``): the job on the same rows in another
order against the reference on the rows as they were; its readings count
among the sound ones. ``--gradient`` runs no job: for every seed it prints
the family's ``Cell.gradient_readings``, the reference's gradient against a
float64 sum made on the host.
"""

import argparse
import gc
import importlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    from benchmark import harness

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--permuted-rows", action="store_true")
    ap.add_argument("--gradient", action="store_true")
    ap.add_argument("--tiny", action="store_true",
                    help="the families' CPU rehearsal sizes")
    ns = ap.parse_args(argv)
    manifest = harness.load_json(harness.ROOT, "BENCHMARK.json")
    _, cell_file, _, config = harness.find_cell(manifest, ns.workload)

    import jax

    device = harness.device_report(jax)
    print(json.dumps({"device": device}), flush=True)
    if device["platform"] != "tpu" and not ns.tiny:
        print("control: no TPU; these readings are of the chip", file=sys.stderr)
        return harness.NO_DEVICE
    from photon_ml_tpu import compat

    compat.start_up(lambda text: print(text, file=sys.stderr),
                    os.path.join(harness.ROOT, ".jax_compilation_cache"))
    family = importlib.import_module(f"benchmark.families.{config['family']}")
    for i, seed in enumerate(int(s) for s in ns.seeds.split(",")):
        cell = family.build(config, cell_file["job"], seed, tiny=ns.tiny)
        if ns.gradient:
            cell.free()
            for reading in cell.gradient_readings(seed):
                print(json.dumps(dict(reading, seed=seed)), flush=True)
            del cell
            gc.collect()
            continue
        t0 = time.perf_counter()
        outputs = cell.run_job()
        job_s = time.perf_counter() - t0
        got = cell.collect(outputs)
        if ns.permuted_rows:
            cell.permute_rows(seed)
            outputs = cell.run_job()
            permuted = cell.collect(outputs)
        del outputs
        cell.free()
        t0 = time.perf_counter()
        ref = cell.reference()
        line = {"seed": seed, "job_s": job_s,
                "reference_s": time.perf_counter() - t0,
                "sound": cell.compare(got, ref)}
        if ns.permuted_rows:
            line["permuted_rows"] = cell.compare(permuted, ref)
        if i < ns.control_seeds:
            line["control"] = cell.compare(cell.reference("bfloat16"), ref)
            line["half_batch"] = cell.compare(
                cell.reference(half_batch=True), ref)
        print(json.dumps(line), flush=True)
        del cell, got, ref
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
