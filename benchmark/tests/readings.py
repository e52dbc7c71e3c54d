"""Readings for a cell's limits, many seeds in one process (set-up is long):

    python -m benchmark.tests.readings --workload <cell> --seeds 1,2,3 \
        [--control] [--fault half_batch] [--out file.jsonl] [--rehearse JSON]

For every seed it drives the program through its first steps exactly as a
run's warm-up does (`entries/fit.py` `Job.first_steps`), frees it, follows
the plain reference, and prints every number `compare.numbers` gives: the
program against the reference (the lower reading), with ``--control`` the
reference in the nearest precision below put in the program's place (the
upper reading), with ``--fault`` the program with that fault planted. No
window is measured. On a machine with no TPU it needs ``--rehearse``."""
import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import compare, reference, run as harness  # noqa: E402
from benchmark.tests import faults  # noqa: E402


def first_steps(run, entry, fault=None):
    if fault is None:
        job = entry.Job(run)
        return job.first_steps(), job.reference_side()
    with faults.plant(fault):
        job = entry.Job(run)
        return job.first_steps(), None


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--control", action="store_true")
    parser.add_argument("--fault", action="append", default=[])
    parser.add_argument("--out")
    parser.add_argument("--rehearse")
    args = parser.parse_args(argv)
    manifest = harness.load_json("BENCHMARK.json")
    lines = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        ns = argparse.Namespace(workload=args.workload, seed=seed, seconds=0,
                                trace=0, rehearse=args.rehearse)
        run = harness.Run(ns, manifest)
        if seed == int(args.seeds.split(",")[0]):
            harness.find_device(run)
            from mxnet_tpu.compiled import enable_compile_cache
            enable_compile_cache()
        entry = harness.load_module("entries", run.traffic["entry"])
        entry.quiet()
        program, side = first_steps(run, entry)
        gc.collect()
        ref, w0 = side()
        opt = run.config["optimizer"]
        doc = {"seed": seed, "workload": args.workload,
               "losses": {"program": program["losses"],
                          "reference": ref["losses"]},
               "program": compare.numbers(program, ref, w0, opt)}
        if args.control:
            below = reference.BELOW[reference.POLICY[run.config["dtype"]]]
            ctrl, _ = side(below)
            doc["control_" + below] = compare.numbers(ctrl, ref, w0, opt)
            doc["losses"]["control"] = ctrl["losses"]
            del ctrl
        for fault in args.fault:
            broken, _ = first_steps(run, entry, fault)
            gc.collect()
            doc["fault_" + fault] = compare.numbers(broken, ref, w0, opt)
            del broken
        doc["seconds"] = time.perf_counter() - t0
        del program, ref, w0, side
        gc.collect()
        text = json.dumps(doc)
        print(text, flush=True)
        lines.append(text)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "w") as f:
                f.write("\n".join(lines) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
